"""Transformer building blocks (pure functional JAX, dict params).

Conventions:
* params are nested dicts of jnp arrays; init fns take (key, cfg, ...).
* activations (B, S, D); attention heads laid out (B, S, H, hd) so the head
  axis is shardable over the ``model`` mesh axis.
* every block supports three execution modes: train/prefill over a full
  sequence (optionally returning a KV cache), and single-token decode against
  a cache (static shapes; position passed as a traced scalar).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from .config import ModelConfig

Axis = jax.sharding.PartitionSpec  # alias used by sharding rules


# ------------------------------------------------------------------- norms
def init_norm(cfg: ModelConfig, d: int) -> dict:
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if cfg.norm == "layer":
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


def norm_fwd(p: dict, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    if cfg.norm == "layer":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + 1e-5) * p["scale"] + p["bias"]
    else:
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(ms + 1e-6) * p["scale"]
    return out.astype(x.dtype)


# -------------------------------------------------------------------- rope
def yarn_mscale(scale: float, mscale: float) -> float:
    """YaRN's temperature factor, as DeepSeek-V2's ``yarn_get_mscale``."""
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def rope_freqs(cfg: ModelConfig, rot_dim: int) -> jnp.ndarray:
    """Inverse frequencies of the ``rot_dim / 2`` rotated pairs; under
    ``cfg.rope_scaling`` YaRN's: the slow dims (wavelengths past the
    original context) divided by ``factor``, the fast ones kept, a linear
    ramp between."""
    inv = 1.0 / (cfg.rope_theta ** (np.arange(0, rot_dim, 2) / rot_dim))
    ys = cfg.rope_scaling
    if ys is not None:
        def dim(rotations):   # the dim that turns ``rotations`` times
            return rot_dim * math.log(
                ys.original_max_position_embeddings
                / (rotations * 2 * math.pi)) / (2 * math.log(cfg.rope_theta))

        low = max(math.floor(dim(ys.beta_fast)), 0)
        high = min(math.ceil(dim(ys.beta_slow)), rot_dim - 1)
        if high == low:
            high += 0.001
        keep = 1 - np.clip((np.arange(rot_dim // 2) - low) / (high - low),
                           0, 1)
        inv = inv / ys.factor * (1 - keep) + inv * keep
    return jnp.asarray(inv, jnp.float32)


def rope_mscale(cfg: ModelConfig) -> float:
    """The factor on YaRN's cos and sin (1 without ``rope_scaling``)."""
    ys = cfg.rope_scaling
    if ys is None:
        return 1.0
    return yarn_mscale(ys.factor, ys.mscale) \
        / yarn_mscale(ys.factor, ys.mscale_all_dim)


def attn_mscale(cfg: ModelConfig) -> float:
    """The factor on the softmax scale: YaRN's ``mscale_all_dim`` squared
    (1 without ``rope_scaling``)."""
    ys = cfg.rope_scaling
    if ys is None or not ys.mscale_all_dim:
        return 1.0
    return yarn_mscale(ys.factor, ys.mscale_all_dim) ** 2


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, cfg: ModelConfig,
               rot_dim: Optional[int] = None) -> jnp.ndarray:
    """x: (B, S, H, hd); positions: (S,) or (B, S)."""
    hd = x.shape[-1]
    rot = rot_dim if rot_dim is not None else int(hd * cfg.rope_frac)
    if rot == 0:
        return x
    inv = rope_freqs(cfg, rot)
    if positions.ndim == 1:
        ang = positions[:, None].astype(jnp.float32) * inv[None, :]  # (S, rot/2)
        ang = ang[None, :, None, :]
    else:
        ang = positions[..., None].astype(jnp.float32) * inv
        ang = ang[:, :, None, :]
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    mscale = rope_mscale(cfg)
    if mscale != 1.0:
        sin, cos = sin * mscale, cos * mscale
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., ::2], xr[..., 1::2]
    r1 = x1 * cos - x2 * sin
    r2 = x2 * cos + x1 * sin
    xr = jnp.stack([r1, r2], axis=-1).reshape(xr.shape)
    return jnp.concatenate([xr, xp], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------- attention
def _dense(key, d_in, d_out, scale=None):
    s = scale if scale is not None else 1.0 / np.sqrt(d_in)
    return jax.random.normal(key, (d_in, d_out), jnp.float32) * s


def init_attention(key, cfg: ModelConfig) -> dict:
    hd = cfg.hd
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "wq": _dense(k1, cfg.d_model, cfg.n_heads * hd),
        "wk": _dense(k2, cfg.d_model, cfg.n_kv_heads * hd),
        "wv": _dense(k3, cfg.d_model, cfg.n_kv_heads * hd),
        "wo": _dense(k4, cfg.n_heads * hd, cfg.d_model),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads * hd,), jnp.float32)
        p["bk"] = jnp.zeros((cfg.n_kv_heads * hd,), jnp.float32)
        p["bv"] = jnp.zeros((cfg.n_kv_heads * hd,), jnp.float32)
    return p


def _sdpa_dense(q, k, v, bias):
    """q: (B,S,H,hd); k,v: (B,T,KV,hd); bias broadcastable to (B,KV,G,S,T)."""
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    group = H // KV
    qg = q.reshape(B, S, KV, group, hd)
    scores = jnp.einsum("bskgh,btkh->bkgst", qg, k) / np.sqrt(hd)
    scores = scores.astype(jnp.float32) + bias
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkh->bskgh", w, v)
    return out.reshape(B, S, H, hd)


def _causal_bias(S, T, causal, window, q_offset=0):
    qpos = jnp.arange(S)[:, None] + q_offset
    kpos = jnp.arange(T)[None, :]
    ok = kpos <= qpos if causal else jnp.ones((S, T), bool)
    if window is not None:
        ok = ok & (kpos > qpos - window)
    return jnp.where(ok, 0.0, jnp.finfo(jnp.float32).min)


# Above this query length the XLA path chunks queries to bound the softmax
# working set (on the TPU causal attention takes the kernel instead).
_CHUNK_THRESHOLD = 2048
_Q_BLOCK = 512


def takes_kernel(mask_given: bool, causal: bool, window: Optional[int],
                 S: int, T: int, platform: str) -> bool:
    """Whether ``sdpa`` runs through the block-skipping causal kernel
    (``kernels.ops.causal_attention``): causal self-attention over a whole
    sequence of a block multiple, with no mask or window given, on the TPU.
    Decode against a cache, cross-attention, encoders, sliding windows and
    the CPU keep the XLA paths."""
    if (platform != "tpu" or mask_given or not causal or window is not None
            or S != T):
        return False
    from ..kernels import ops as kops  # Pallas loads only where it runs
    return kops.causal_block(S) is not None


def sdpa(q, k, v, mask, use_flash: bool = False, window: Optional[int] = None,
         causal: bool = True):
    """q: (B,S,H,hd); k,v: (B,T,KV,hd); mask: (B,1,1|S,T) additive or None.

    GQA: query heads grouped over KV heads.  ``use_flash`` routes to the
    forward-only Pallas kernel when the mask is the standard
    causal(+window) one; on the TPU causal self-attention takes the
    splash kernel (``takes_kernel``); otherwise long sequences take a
    query-chunked XLA path so the score matrix working set stays bounded.
    Each call counts ``attention.kernel`` or ``attention.xla`` in
    ``repro.obs`` as it is traced.
    """
    B, S, H, hd = q.shape
    T = k.shape[1]
    if use_flash and mask is None:
        from ..kernels import ops as kops
        obs.count("attention.kernel")
        return kops.flash_attention(q, k, v, causal=causal, window=window)
    if takes_kernel(mask is not None, causal, window, S, T,
                    jax.default_backend()):
        from ..kernels import ops as kops
        obs.count("attention.kernel")
        return kops.causal_attention(q, k, v)
    obs.count("attention.xla")
    if mask is None:
        if S > _CHUNK_THRESHOLD and S == T:
            for blk in (_Q_BLOCK, 256, 128, 64):
                if S % blk == 0:
                    return _flash_xla(q, k, v, causal, window, qb=blk, kb=blk)
        bias = _causal_bias(S, T, causal, window)[None, None, None]
        return _sdpa_dense(q, k, v, bias)
    bias = mask[:, :, None] if mask.ndim == 4 else mask
    return _sdpa_dense(q, k, v, bias)


def _flash_xla(q, k, v, causal, window, qb: int = _Q_BLOCK, kb: int = _Q_BLOCK):
    """Online-softmax attention in plain XLA (double lax.scan over query and
    KV blocks).  Working set per step is (B,H,qb,kb) — the flash-attention
    recurrence, so 32k/500k contexts lower with bounded temps.  Causality is
    enforced by masking (blocks are not skipped — ``takes_kernel`` sends
    causal attention on the TPU to the block-skipping kernel)."""
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    nq, nk = S // qb, T // kb
    qs = q.reshape(B, nq, qb, KV, G, hd).transpose(1, 0, 3, 4, 2, 5)  # nq,B,KV,G,qb,hd
    ks = k.reshape(B, nk, kb, KV, hd).transpose(1, 0, 3, 2, 4)        # nk,B,KV,kb,hd
    vs = v.reshape(B, nk, kb, KV, hd).transpose(1, 0, 3, 2, 4)
    scale = 1.0 / np.sqrt(hd)
    neg = jnp.finfo(jnp.float32).min

    @jax.checkpoint
    def q_step(_, qin):
        qi, qblk = qin

        @jax.checkpoint
        def kv_step(carry, kin):
            m, l, acc = carry
            ki, kblk, vblk = kin
            s = jnp.einsum("bkgqh,bkth->bkgqt", qblk, kblk).astype(jnp.float32)
            s = s * scale
            qpos = qi * qb + jnp.arange(qb)[:, None]
            kpos = ki * kb + jnp.arange(kb)[None, :]
            ok = kpos <= qpos if causal else jnp.ones((qb, kb), bool)
            if window is not None:
                ok = ok & (kpos > qpos - window)
            s = jnp.where(ok[None, None, None], s, neg)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqt,bkth->bkgqh", p.astype(vblk.dtype), vblk)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KV, G, qb), neg, jnp.float32)
        l0 = jnp.zeros((B, KV, G, qb), jnp.float32)
        a0 = jnp.zeros((B, KV, G, qb, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                      (jnp.arange(nk), ks, vs))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return None, out.astype(q.dtype)

    _, outs = jax.lax.scan(q_step, None, (jnp.arange(nq), qs))
    # outs: (nq, B, KV, G, qb, hd) -> (B, S, H, hd)
    return outs.transpose(1, 0, 4, 2, 3, 5).reshape(B, S, H, hd)


@obs.scope("attention")
def attention_fwd(p: dict, cfg: ModelConfig, x, positions, *,
                  cache: Optional[dict] = None, pos: Optional[jnp.ndarray] = None,
                  window: Optional[int] = None, use_flash: bool = False,
                  return_cache: bool = False, cache_len: int = 0):
    """Self-attention.  Train/prefill when ``cache is None`` (optionally
    returning a fresh cache of length ``cache_len``); decode when ``cache``
    and ``pos`` are given (x is (B,1,D))."""
    B, S, D = x.shape
    hd = cfg.hd
    q = x @ p["wq"].astype(x.dtype)
    k = x @ p["wk"].astype(x.dtype)
    v = x @ p["wv"].astype(x.dtype)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    q = q.reshape(B, S, cfg.n_heads, hd)
    k = k.reshape(B, S, cfg.n_kv_heads, hd)
    v = v.reshape(B, S, cfg.n_kv_heads, hd)
    q = apply_rope(q, positions, cfg)
    k = apply_rope(k, positions, cfg)

    new_cache = None
    if cache is not None:
        # decode: write k,v at slot (pos % cache_size for ring buffers)
        ck, cv = cache["k"], cache["v"]
        csize = ck.shape[1]
        slot = pos % csize if window is not None else pos
        quant = "k_scale" in cache
        if quant:
            # int8 KV cache: symmetric per-(batch, slot, head) scales
            ks = jnp.max(jnp.abs(k.astype(jnp.float32)), axis=-1) / 127.0
            vs = jnp.max(jnp.abs(v.astype(jnp.float32)), axis=-1) / 127.0
            kq = jnp.round(k.astype(jnp.float32)
                           / jnp.maximum(ks[..., None], 1e-8)).astype(jnp.int8)
            vq = jnp.round(v.astype(jnp.float32)
                           / jnp.maximum(vs[..., None], 1e-8)).astype(jnp.int8)
            ck = jax.lax.dynamic_update_slice(ck, kq, (0, slot, 0, 0))
            cv = jax.lax.dynamic_update_slice(cv, vq, (0, slot, 0, 0))
            cks = jax.lax.dynamic_update_slice(
                cache["k_scale"], ks.astype(cache["k_scale"].dtype),
                (0, slot, 0))
            cvs = jax.lax.dynamic_update_slice(
                cache["v_scale"], vs.astype(cache["v_scale"].dtype),
                (0, slot, 0))
            new_cache = {"k": ck, "v": cv, "k_scale": cks, "v_scale": cvs}
        else:
            ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype),
                                              (0, slot, 0, 0))
            cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype),
                                              (0, slot, 0, 0))
            new_cache = {"k": ck, "v": cv}
        kpos = jnp.arange(csize)
        if window is not None:
            # ring buffer: entry i holds absolute position matching i when
            # within the last `csize` positions
            age = (slot - kpos) % csize
            ok = age <= jnp.minimum(pos, csize - 1)
        else:
            ok = kpos <= pos
        bias = jnp.where(ok, 0.0, jnp.finfo(jnp.float32).min)
        mask = jnp.broadcast_to(bias[None, None, None, :], (B, 1, 1, csize))
        if quant:
            kd = (ck.astype(q.dtype) * cks[..., None].astype(q.dtype))
            vd = (cv.astype(q.dtype) * cvs[..., None].astype(q.dtype))
            out = sdpa(q, kd, vd, mask)
        else:
            out = sdpa(q, ck.astype(q.dtype), cv.astype(q.dtype), mask)
    else:
        out = sdpa(q, k, v, None, use_flash=use_flash, window=window)
        if return_cache:
            size = cache_len or S
            ck = jnp.zeros((B, size, cfg.n_kv_heads, hd), x.dtype)
            cv = jnp.zeros((B, size, cfg.n_kv_heads, hd), x.dtype)
            take = min(S, size)
            ck = jax.lax.dynamic_update_slice(ck, k[:, -take:].astype(ck.dtype),
                                              (0, 0, 0, 0))
            cv = jax.lax.dynamic_update_slice(cv, v[:, -take:].astype(cv.dtype),
                                              (0, 0, 0, 0))
            new_cache = {"k": ck, "v": cv}
    out = out.reshape(B, S, cfg.n_heads * hd)
    out = out @ p["wo"].astype(x.dtype)
    return (out, new_cache) if (return_cache or cache is not None) else out


def init_cross_attention(key, cfg: ModelConfig) -> dict:
    return init_attention(key, cfg)


def cross_attention_fwd(p: dict, cfg: ModelConfig, x, memory):
    """Decoder cross-attention over encoder memory (B, T, D)."""
    B, S, D = x.shape
    hd = cfg.hd
    q = (x @ p["wq"].astype(x.dtype)).reshape(B, S, cfg.n_heads, hd)
    k = (memory @ p["wk"].astype(x.dtype)).reshape(
        B, memory.shape[1], cfg.n_kv_heads, hd)
    v = (memory @ p["wv"].astype(x.dtype)).reshape(
        B, memory.shape[1], cfg.n_kv_heads, hd)
    out = sdpa(q, k, v, None, causal=False)
    return out.reshape(B, S, -1) @ p["wo"].astype(x.dtype)


# --------------------------------------------------------------------- MLA
def init_mla(key, cfg: ModelConfig) -> dict:
    m = cfg.mla
    keys = jax.random.split(key, 7)
    qdim = cfg.n_heads * (m.qk_nope_head_dim + m.qk_rope_head_dim)
    p: dict = {}
    if m.q_lora_rank:
        p["w_dq"] = _dense(keys[0], cfg.d_model, m.q_lora_rank)
        p["w_uq"] = _dense(keys[1], m.q_lora_rank, qdim)
        p["q_norm"] = {"scale": jnp.ones((m.q_lora_rank,), jnp.float32)}
    else:
        p["wq"] = _dense(keys[0], cfg.d_model, qdim)
    p["w_dkv"] = _dense(keys[2], cfg.d_model, m.kv_lora_rank)
    p["w_kr"] = _dense(keys[3], cfg.d_model, m.qk_rope_head_dim)
    p["kv_norm"] = {"scale": jnp.ones((m.kv_lora_rank,), jnp.float32)}
    p["w_uk"] = _dense(keys[4], m.kv_lora_rank, cfg.n_heads * m.qk_nope_head_dim)
    p["w_uv"] = _dense(keys[5], m.kv_lora_rank, cfg.n_heads * m.v_head_dim)
    p["wo"] = _dense(keys[6], cfg.n_heads * m.v_head_dim, cfg.d_model)
    return p


def _rms(x, scale):
    ms = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(ms + 1e-6) * scale).astype(x.dtype)


@obs.scope("attention")
def mla_fwd(p: dict, cfg: ModelConfig, x, positions, *,
            cache: Optional[dict] = None, pos=None,
            return_cache: bool = False, cache_len: int = 0):
    """Multi-head Latent Attention (DeepSeek-V2).  The decode cache stores
    only the compressed latent (c_kv, k_rope) — MLA's memory saving."""
    m = cfg.mla
    B, S, D = x.shape
    H = cfg.n_heads
    if m.q_lora_rank:
        q = _rms(x @ p["w_dq"].astype(x.dtype), p["q_norm"]["scale"])
        q = q @ p["w_uq"].astype(x.dtype)
    else:
        q = x @ p["wq"].astype(x.dtype)
    q = q.reshape(B, S, H, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_rope = q[..., : m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    q_rope = apply_rope(q_rope, positions, cfg, rot_dim=m.qk_rope_head_dim)

    c_kv = _rms(x @ p["w_dkv"].astype(x.dtype), p["kv_norm"]["scale"])  # (B,S,r)
    k_rope = apply_rope((x @ p["w_kr"].astype(x.dtype))[:, :, None, :],
                        positions, cfg, rot_dim=m.qk_rope_head_dim)  # (B,S,1,rr)

    new_cache = None
    if cache is not None:
        cc, cr = cache["c_kv"], cache["k_rope"]
        cc = jax.lax.dynamic_update_slice(cc, c_kv.astype(cc.dtype), (0, pos, 0))
        cr = jax.lax.dynamic_update_slice(
            cr, k_rope[:, :, 0].astype(cr.dtype), (0, pos, 0))
        new_cache = {"c_kv": cc, "k_rope": cr}
        c_kv_all = cc.astype(x.dtype)
        k_rope_all = cr.astype(x.dtype)[:, :, None]
        T = cc.shape[1]
        ok = jnp.arange(T) <= pos
        bias = jnp.where(ok, 0.0, jnp.finfo(jnp.float32).min)[None, None, None]
    else:
        c_kv_all, k_rope_all = c_kv, k_rope
        T = S
        qpos = jnp.arange(S)[:, None]
        ok = jnp.arange(T)[None, :] <= qpos
        bias = jnp.where(ok, 0.0, jnp.finfo(jnp.float32).min)[None, None]
        if return_cache:
            size = cache_len or S
            cc = jnp.zeros((B, size, m.kv_lora_rank), x.dtype)
            cr = jnp.zeros((B, size, m.qk_rope_head_dim), x.dtype)
            cc = jax.lax.dynamic_update_slice(cc, c_kv.astype(cc.dtype), (0, 0, 0))
            cr = jax.lax.dynamic_update_slice(cr, k_rope[:, :, 0].astype(cr.dtype),
                                              (0, 0, 0))
            new_cache = {"c_kv": cc, "k_rope": cr}

    # absorb: k_nope = c_kv @ w_uk  (B,T,H,nope); v = c_kv @ w_uv
    k_nope = (c_kv_all @ p["w_uk"].astype(x.dtype)).reshape(
        B, T, H, m.qk_nope_head_dim)
    vv = (c_kv_all @ p["w_uv"].astype(x.dtype)).reshape(B, T, H, m.v_head_dim)
    if cache is None:
        # train/prefill: fold (nope ++ rope) into one effective head dim and
        # reuse the (flash-chunked) sdpa path — scores = qn.kn + qr.kr, and
        # long sequences must not materialise the (S, T) matrix densely.
        # (sdpa's 1/sqrt(hd_eff) scale == MLA's 1/sqrt(nope+rope); YaRN's
        # factor on it goes into q, in float32.)
        q_eff = jnp.concatenate([q_nope, q_rope], axis=-1)
        mscale = attn_mscale(cfg)
        if mscale != 1.0:
            q_eff = (q_eff.astype(jnp.float32) * mscale).astype(q_eff.dtype)
        k_eff = jnp.concatenate(
            [k_nope, jnp.broadcast_to(
                k_rope_all, (B, T, H, m.qk_rope_head_dim))], axis=-1)
        v_pad = jnp.pad(vv, ((0, 0), (0, 0), (0, 0),
                             (0, q_eff.shape[-1] - m.v_head_dim)))
        out = sdpa(q_eff, k_eff, v_pad, None, causal=True)[..., :m.v_head_dim]
        out = out.reshape(B, S, -1) @ p["wo"].astype(x.dtype)
        return (out, new_cache) if return_cache else out
    scale = attn_mscale(cfg) / np.sqrt(m.qk_nope_head_dim
                                       + m.qk_rope_head_dim)
    s_nope = jnp.einsum("bshd,bthd->bhst", q_nope, k_nope)
    s_rope = jnp.einsum("bshd,btxd->bhst", q_rope,
                        jnp.broadcast_to(k_rope_all, (B, T, 1, m.qk_rope_head_dim)))
    scores = (s_nope + s_rope).astype(jnp.float32) * scale + bias
    w = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = jnp.einsum("bhst,bthd->bshd", w, vv).reshape(B, S, -1)
    out = out @ p["wo"].astype(x.dtype)
    return (out, new_cache) if (return_cache or cache is not None) else out


# --------------------------------------------------------------------- FFN
def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> dict:
    d_ff = d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"w_down": _dense(k2, d_ff, cfg.d_model)}
    p["w_up"] = _dense(k1, cfg.d_model, d_ff)
    if cfg.glu:
        p["w_gate"] = _dense(k3, cfg.d_model, d_ff)
    return p


def _act(cfg: ModelConfig, x):
    if cfg.act == "silu":
        return jax.nn.silu(x)
    if cfg.act == "gelu":
        return jax.nn.gelu(x)
    return jax.nn.relu(x)


@obs.scope("mlp")
def mlp_fwd(p: dict, cfg: ModelConfig, x):
    up = x @ p["w_up"].astype(x.dtype)
    h = _act(cfg, x @ p["w_gate"].astype(x.dtype)) * up if cfg.glu else _act(cfg, up)
    return h @ p["w_down"].astype(x.dtype)


# --------------------------------------------------------------------- MoE
def _experts(key, shape, fan_in, first: int, held: int, n: int):
    """Held experts ``first .. first + held - 1`` of ``n``, each drawn from
    its own key: an expert's weights do not depend on which share holds
    it."""
    keys = jax.random.split(key, n)[first:first + held]
    return jax.vmap(lambda k: jax.random.normal(k, shape))(keys) \
        / np.sqrt(fan_in)


def init_moe(key, cfg: ModelConfig, first_expert: int = 0) -> dict:
    """Router over all ``n_routed`` experts, the ``held`` experts from
    ``first_expert`` on, and the shared experts."""
    e = cfg.moe
    keys = jax.random.split(key, 5)
    de, d = e.d_expert, cfg.d_model
    held = (first_expert, e.held, e.n_routed)
    p = {
        "router": _dense(keys[0], d, e.n_routed, scale=0.02),
        "w_up": _experts(keys[1], (d, de), d, *held),
        "w_down": _experts(keys[2], (de, d), de, *held),
    }
    if cfg.glu:
        p["w_gate"] = _experts(keys[3], (d, de), d, *held)
    if e.n_shared:
        p["shared"] = init_mlp(keys[4], cfg, d_ff=e.n_shared * e.d_expert)
    return p


def balance_loss(cfg: ModelConfig, probs, topi):
    """DeepSeek-V2's sequence-wise expert balance loss over all ``top_k``
    choices.  probs: (B, S, E) router softmax; topi: (B, S, k) chosen
    experts.  ``alpha * mean_b sum_e f[b,e] * P[b,e]`` with ``f`` the
    expert's share of the sequence's choices over ``k/E`` and ``P`` its
    mean probability."""
    e = cfg.moe
    B, S, k = topi.shape
    E = e.n_routed
    counts = jnp.sum(jax.nn.one_hot(topi.reshape(B, S * k), E,
                                    dtype=jnp.float32), axis=1)
    f = counts / (S * k / E)
    return e.aux_loss_coef * jnp.mean(
        jnp.sum(f * jnp.mean(probs, axis=1), axis=-1))


@obs.scope("moe")
def moe_fwd(p: dict, cfg: ModelConfig, x, first_expert: int = 0):
    """Top-k routed experts, dropless, over the experts held here.

    The router is whole: float32 logits over all ``n_routed`` experts,
    softmax, greedy top-k; the gate weights are the top-k probabilities,
    not renormalised (DeepSeek-V2's ``norm_topk_prob`` false), scaled by
    ``routed_scaling``; the balance loss is per sequence over all k
    choices (its ``seq_aux``).  The (token, choice) pairs are sorted by
    expert; those of the ``held`` experts from ``first_expert`` on lead,
    one group per expert, and the expert FFNs run as grouped matmuls
    (``jax.lax.ragged_dot``) over those rows.  A token
    picks ``top_k`` distinct experts, so at most ``T * min(top_k, held)``
    pairs are held: the static row count.  The grouped matmuls cover at
    least half of it, the last group taking the zero rows that make up the
    rest: a layer costs the same while its held experts get up to half the
    pairs they could get, so the step's cost does not follow the routing,
    which drifts as the router trains.  Pairs routed to experts not held
    here add nothing (their chips' part).  The gated results scatter-add
    back to token order in float32, and the shared experts are added
    once.  Returns (out, balance loss)."""
    e = cfg.moe
    B, S, D = x.shape
    T, k, H = B * S, e.top_k, e.held
    R = T * min(k, H)
    xt = x.reshape(T, D)
    with jax.named_scope("route"):
        logits = jnp.dot(xt.astype(jnp.float32),
                         p["router"].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        probs = jax.nn.softmax(logits, axis=-1)
        topv, topi = jax.lax.top_k(probs, k)              # (T, k)
        topv = topv * e.routed_scaling
        aux = balance_loss(cfg, probs.reshape(B, S, -1),
                           topi.reshape(B, S, k))
    obs.count("moe.grouped")
    with jax.named_scope("dispatch"):
        local = topi.reshape(-1) - first_expert           # (T*k,)
        held = (local >= 0) & (local < H)
        group = jnp.where(held, local, H).astype(jnp.int32)
        order = jnp.argsort(group, stable=True)[:R]
        sizes = jnp.bincount(group, length=H + 1)[:H].astype(jnp.int32)
        sizes = sizes.at[H - 1].add(jnp.maximum(R // 2 - jnp.sum(sizes), 0))
        tok = order // k
        hold = held[order][:, None]                       # (R, 1)
        w = jnp.where(hold[:, 0], topv.reshape(-1)[order], 0.0)
        # rows past the held groups are left unwritten by the TPU's grouped
        # matmul (NaN on the chip): every operand and result of one, and so
        # every cotangent, is selected to 0 there, never multiplied
        xs = jnp.where(hold, xt[tok], 0)                  # (R, D)
    with jax.named_scope("experts"):
        up = jax.lax.ragged_dot(xs, p["w_up"].astype(x.dtype), sizes)
        if cfg.glu:
            gate = jax.lax.ragged_dot(xs, p["w_gate"].astype(x.dtype), sizes)
            h = _act(cfg, gate) * up
        else:
            h = _act(cfg, up)
        h = jnp.where(hold, h, 0)
        ys = jnp.where(hold, jax.lax.ragged_dot(
            h, p["w_down"].astype(x.dtype), sizes), 0)
    with jax.named_scope("combine"):
        out = jnp.zeros((T, D), jnp.float32).at[tok].add(
            ys.astype(jnp.float32) * w[:, None]).astype(x.dtype)
    if e.n_shared:
        out = out + mlp_fwd(p["shared"], cfg, xt)
    return out.reshape(B, S, D), aux
