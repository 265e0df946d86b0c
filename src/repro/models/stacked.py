"""Scanned-layer (stacked-parameter) model path.

Production frameworks stack homogeneous layer parameters along a leading
``layer`` dim and apply them with ``lax.scan`` — compile time and HLO size
stay O(1) in depth (essential for the 60-62-layer assigned configs).

Layers are partitioned into homogeneous *groups* (same pytree structure):

* dense/GQA/MLA archs .... one group of n_layers
* DeepSeek MoE ........... [dense layer 0] + [MoE layers 1..n-1]
* RWKV-6 ................. one group
* RecurrentGemma ......... cycles of (rec, rec, attn) + a trailing remainder
* Seamless enc-dec ....... encoder group + decoder group

``params["groups"]`` is a list of stacked layer pytrees; caches are stacked
the same way so decode also scans.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from .config import ModelConfig
from . import layers as L
from . import model as M


# ------------------------------------------------------------------- groups
def layer_groups(cfg: ModelConfig) -> list[dict]:
    """Segments of homogeneous layers: [{"kind", "count", "start", "cycle"}]."""
    if cfg.recurrent is not None:
        cyc = len(cfg.recurrent.pattern)
        n_cycles = cfg.n_layers // cyc
        groups = []
        if n_cycles:
            groups.append({"kind": "cycle", "count": n_cycles, "start": 0,
                           "cycle": cyc})
        rem = cfg.n_layers - n_cycles * cyc
        if rem:
            groups.append({"kind": "tail", "count": 1, "start": n_cycles * cyc,
                           "cycle": rem})
        return groups
    if cfg.moe is not None and cfg.moe.first_dense_layers:
        fd = cfg.moe.first_dense_layers
        return [
            {"kind": "plain", "count": fd, "start": 0, "cycle": 1},
            {"kind": "plain", "count": cfg.n_layers - fd, "start": fd,
             "cycle": 1},
        ]
    return [{"kind": "plain", "count": cfg.n_layers, "start": 0, "cycle": 1}]


def _stack_init(init_one, count: int, keys):
    """Initialise ``count`` layers and stack leaves along axis 0."""
    if count == 1:
        return jax.tree.map(lambda a: a[None], init_one(keys[0]))
    trees = [init_one(k) for k in keys]
    return jax.tree.map(lambda *a: jnp.stack(a), *trees)


def init_params(key, cfg: ModelConfig) -> dict:
    dt = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, cfg.n_layers + 4)
    cast = lambda t: jax.tree.map(
        lambda a: a.astype(dt) if a.dtype == jnp.float32 else a, t)
    params: dict = {
        "embed": (jax.random.normal(keys[0], (cfg.vocab, cfg.d_model))
                  * 0.02).astype(dt),
        "final_norm": L.init_norm(cfg, cfg.d_model),
        "groups": [],
    }
    lk = jax.random.split(keys[1], cfg.n_layers)
    for g in layer_groups(cfg):
        cyc = g["cycle"]
        if g["kind"] in ("cycle", "tail"):
            def init_cycle(k, start=g["start"], cyc=cyc):
                ks = jax.random.split(k, cyc)
                return {f"b{j}": cast(M.init_layer(ks[j], cfg, start + j))
                        for j in range(cyc)}
            gkeys = lk[g["start"]:g["start"] + g["count"]]
            params["groups"].append(_stack_init(init_cycle, g["count"], gkeys))
        else:
            def init_plain(k, li=g["start"]):
                return cast(M.init_layer(k, cfg, li))
            gkeys = lk[g["start"]:g["start"] + g["count"]]
            params["groups"].append(_stack_init(init_plain, g["count"], gkeys))
    if not cfg.tie_embeddings:
        params["lm_head"] = (jax.random.normal(
            keys[-1], (cfg.d_model, cfg.vocab)) * 0.02).astype(dt)
    if cfg.encdec is not None:
        ek = jax.random.split(keys[-2], cfg.encdec.n_enc_layers + 1)
        params["encoder"] = {
            "in_proj": (jax.random.normal(ek[0], (cfg.encdec.frontend_dim,
                                                  cfg.d_model))
                        / np.sqrt(cfg.encdec.frontend_dim)).astype(dt),
            "layers": _stack_init(lambda k: cast(M.init_enc_layer(k, cfg)),
                                  cfg.encdec.n_enc_layers,
                                  jax.random.split(ek[0],
                                                   cfg.encdec.n_enc_layers)),
            "final_norm": L.init_norm(cfg, cfg.d_model),
        }
    if cfg.vlm_prefix_len:
        params["vision_proj"] = jnp.eye(cfg.d_model, dtype=dt)
    return params


# ------------------------------------------------------------------ forward
def _group_scan(x, gparams, cfg, g, positions, memory, use_kernels, remat,
                caches=None, pos=None, return_cache=False, cache_len=0):
    """Scan one group.  Returns (x, aux, new_caches or None)."""
    kind = g["kind"]
    want_cache = return_cache or caches is not None

    def body(carry, inp):
        x, aux = carry
        p, cache = inp
        if kind in ("cycle", "tail"):
            ncs = {}
            for j in range(g["cycle"]):
                li = g["start"] + j
                c_j = cache[f"b{j}"] if cache is not None else None
                x, a, nc = M._layer_fwd(
                    p[f"b{j}"], cfg, li, x, positions, memory=memory,
                    cache=c_j, pos=pos, return_cache=return_cache,
                    cache_len=cache_len, use_kernels=use_kernels)
                aux = aux + a
                ncs[f"b{j}"] = nc
            return (x, aux), (ncs if want_cache else 0)
        li = g["start"]
        x, a, nc = M._layer_fwd(p, cfg, li, x, positions, memory=memory,
                                cache=cache, pos=pos,
                                return_cache=return_cache,
                                cache_len=cache_len, use_kernels=use_kernels)
        return (x, aux + a), (nc if want_cache else 0)

    if remat:
        body = jax.checkpoint(body)
    if caches is None:
        # scan needs a pytree of xs with leading dim = count
        (x, aux), ys = jax.lax.scan(
            lambda c, p: body(c, (p, None)),
            (x, jnp.zeros((), jnp.float32)), gparams)
    else:
        (x, aux), ys = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                    (gparams, caches))
    return x, aux, (ys if want_cache else None)


def forward(params, cfg: ModelConfig, tokens, *, prefix_emb=None,
            enc_frames=None, use_kernels: bool = False, remat: bool = False):
    x = M._embed(params, cfg, tokens)
    offset = 0
    if cfg.vlm_prefix_len and prefix_emb is not None:
        pre = prefix_emb.astype(x.dtype) @ params["vision_proj"]
        x = jnp.concatenate([pre, x], axis=1)
        offset = prefix_emb.shape[1]
    S = x.shape[1]
    positions = jnp.arange(S)
    if cfg.rope_frac == 0.0 and cfg.block != "rwkv" and cfg.recurrent is None:
        x = x + M._sinusoid(S, cfg.d_model, x.dtype)[None]
    memory = encode(params, cfg, enc_frames) if enc_frames is not None else None
    total_aux = jnp.zeros((), jnp.float32)
    for g, gp in zip(layer_groups(cfg), params["groups"]):
        x, aux, _ = _group_scan(x, gp, cfg, g, positions, memory,
                                use_kernels, remat)
        total_aux = total_aux + aux
    x = L.norm_fwd(params["final_norm"], cfg, x)
    logits = M._unembed(params, cfg, x)
    if offset:
        logits = logits[:, offset:]
    return logits, total_aux


def encode(params, cfg: ModelConfig, frames):
    enc = params["encoder"]
    x = frames.astype(jnp.dtype(cfg.dtype)) @ enc["in_proj"]
    x = x + M._sinusoid(x.shape[1], cfg.d_model, x.dtype)[None]

    def body(x, lp):
        h = L.norm_fwd(lp["ln1"], cfg, x)
        B, T, D = h.shape
        hd = cfg.hd
        q = (h @ lp["attn"]["wq"].astype(h.dtype)).reshape(B, T, cfg.n_heads, hd)
        k = (h @ lp["attn"]["wk"].astype(h.dtype)).reshape(
            B, T, cfg.n_kv_heads, hd)
        v = (h @ lp["attn"]["wv"].astype(h.dtype)).reshape(
            B, T, cfg.n_kv_heads, hd)
        a = L.sdpa(q, k, v, None, causal=False).reshape(B, T, -1)
        x = x + a @ lp["attn"]["wo"].astype(h.dtype)
        h2 = L.norm_fwd(lp["ln2"], cfg, x)
        return x + L.mlp_fwd(lp["mlp"], cfg, h2), 0

    x, _ = jax.lax.scan(body, x, enc["layers"])
    return L.norm_fwd(enc["final_norm"], cfg, x)


_CE_CHUNK = 512


@obs.scope("embed")
def _embed_maybe_vp(params, cfg: ModelConfig, tokens, vp_mesh):
    from . import vocab_parallel as VP

    if vp_mesh is not None and VP.applicable(vp_mesh, cfg.vocab):
        x = VP.embed_lookup(params["embed"], tokens, vp_mesh)
        if cfg.tie_embeddings:
            x = x * jnp.asarray(np.sqrt(cfg.d_model), x.dtype)
        return x
    return M._embed(params, cfg, tokens)


def hidden_forward(params, cfg: ModelConfig, tokens, *, prefix_emb=None,
                   enc_frames=None, use_kernels=False, remat=False,
                   vp_mesh=None):
    """forward() up to (but excluding) the unembed; returns (hidden, aux,
    prefix_offset)."""
    x = _embed_maybe_vp(params, cfg, tokens, vp_mesh)
    offset = 0
    if cfg.vlm_prefix_len and prefix_emb is not None:
        pre = prefix_emb.astype(x.dtype) @ params["vision_proj"]
        x = jnp.concatenate([pre, x], axis=1)
        offset = prefix_emb.shape[1]
    S = x.shape[1]
    positions = jnp.arange(S)
    if cfg.rope_frac == 0.0 and cfg.block != "rwkv" and cfg.recurrent is None:
        x = x + M._sinusoid(S, cfg.d_model, x.dtype)[None]
    memory = encode(params, cfg, enc_frames) if enc_frames is not None else None
    total_aux = jnp.zeros((), jnp.float32)
    for g, gp in zip(layer_groups(cfg), params["groups"]):
        x, aux, _ = _group_scan(x, gp, cfg, g, positions, memory,
                                use_kernels, remat)
        total_aux = total_aux + aux
    x = L.norm_fwd(params["final_norm"], cfg, x)
    if offset:
        x = x[:, offset:]
    return x, total_aux, offset


def loss_fn(params, cfg: ModelConfig, batch, *, use_kernels: bool = False,
            remat: bool = False, vp_mesh=None, vp_ce: bool = True):
    """Next-token CE computed in sequence chunks — the full (B, S, V) logits
    tensor is never materialised.  With ``vp_mesh`` set the chunks run
    vocab-parallel (Megatron-style) over the ``model`` axis."""
    from . import vocab_parallel as VP

    tokens = batch["tokens"]
    x, aux, _ = hidden_forward(params, cfg, tokens,
                               prefix_emb=batch.get("prefix_emb"),
                               enc_frames=batch.get("enc_frames"),
                               use_kernels=use_kernels, remat=remat,
                               vp_mesh=vp_mesh)
    B, S, D = x.shape
    targets = jnp.concatenate(
        [tokens[:, 1:], jnp.zeros((B, 1), tokens.dtype)], axis=1)
    weights = jnp.concatenate(
        [jnp.ones((B, S - 1), jnp.float32), jnp.zeros((B, 1), jnp.float32)],
        axis=1)
    chunk = min(_CE_CHUNK, S)
    while S % chunk:
        chunk -= 1
    use_vp = vp_ce and vp_mesh is not None and VP.applicable(vp_mesh, cfg.vocab)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]

    def ce_chunk(carry, inp):
        xc, tc, wc = inp        # (B, c, D), (B, c), (B, c)
        if use_vp:
            # f32 in: the shard_map transpose inserts a psum over `model`
            # for the replicated xc cotangent — it must not be bf16
            # (XLA:CPU AllReducePromotion miscompiles 16-bit all-reduce).
            ce, cnt = VP.ce_chunk(xc.astype(jnp.float32), head, tc, wc,
                                  vp_mesh,
                                  transpose_head=cfg.tie_embeddings)
        else:
            logits = M._unembed(params, cfg, xc).astype(jnp.float32)
            m = jnp.max(logits, axis=-1)
            logz = m + jnp.log(
                jnp.sum(jnp.exp(logits - m[..., None]), axis=-1))
            gold = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
            ce = jnp.sum((logz - gold) * wc)
            cnt = jnp.sum(wc)
        return (carry[0] + ce, carry[1] + cnt), None

    nc = S // chunk
    xs = (x.reshape(B, nc, chunk, D).transpose(1, 0, 2, 3),
          targets.reshape(B, nc, chunk).transpose(1, 0, 2),
          weights.reshape(B, nc, chunk).transpose(1, 0, 2))
    body = jax.checkpoint(ce_chunk) if remat else ce_chunk
    with jax.named_scope("head"):
        (ce_sum, cnt), _ = jax.lax.scan(body, (jnp.zeros(()), jnp.zeros(())),
                                        xs)
    return ce_sum / jnp.maximum(cnt, 1.0) + aux


# ------------------------------------------------------------------- decode
def init_cache(cfg: ModelConfig, batch: int, cache_len: int, dtype=None):
    dt = dtype or jnp.dtype(cfg.dtype)
    flat = M.init_cache(cfg, batch, cache_len, dtype=dt)
    out = []
    for g in layer_groups(cfg):
        if g["kind"] in ("cycle", "tail"):
            per_cycle = []
            for c in range(g["count"]):
                start = g["start"] + c * g["cycle"]
                per_cycle.append({f"b{j}": flat[start + j]
                                  for j in range(g["cycle"])})
            out.append(jax.tree.map(lambda *a: jnp.stack(a), *per_cycle)
                       if g["count"] > 1 else
                       jax.tree.map(lambda a: a[None], per_cycle[0]))
        else:
            seg = flat[g["start"]:g["start"] + g["count"]]
            out.append(jax.tree.map(lambda *a: jnp.stack(a), *seg)
                       if len(seg) > 1 else
                       jax.tree.map(lambda a: a[None], seg[0]))
    return out


def decode_step(params, cfg: ModelConfig, caches, token, pos, *, memory=None,
                vp_mesh=None):
    x = _embed_maybe_vp(params, cfg, token[:, None], vp_mesh)
    if cfg.rope_frac == 0.0 and cfg.block != "rwkv" and cfg.recurrent is None:
        D = cfg.d_model
        dim = jnp.arange(0, D, 2) / D
        ang = pos.astype(jnp.float32) / jnp.power(10000.0, dim)
        pe = jnp.zeros((D,), x.dtype)
        pe = pe.at[0::2].set(jnp.sin(ang).astype(x.dtype))
        pe = pe.at[1::2].set(jnp.cos(ang).astype(x.dtype))
        x = x + pe[None, None]
    positions = pos[None]
    new_caches = []
    for g, gp, gc in zip(layer_groups(cfg), params["groups"], caches):
        x, _, nc = _group_scan(x, gp, cfg, g, positions, memory, False, False,
                               caches=gc, pos=pos)
        new_caches.append(nc)
    x = L.norm_fwd(params["final_norm"], cfg, x)
    return M._unembed(params, cfg, x)[:, 0], new_caches


def prefill(params, cfg: ModelConfig, tokens, cache_len: int, *,
            prefix_emb=None, enc_frames=None, use_kernels: bool = False,
            vp_mesh=None):
    x = _embed_maybe_vp(params, cfg, tokens, vp_mesh)
    if cfg.vlm_prefix_len and prefix_emb is not None:
        pre = prefix_emb.astype(x.dtype) @ params["vision_proj"]
        x = jnp.concatenate([pre, x], axis=1)
    S = x.shape[1]
    positions = jnp.arange(S)
    if cfg.rope_frac == 0.0 and cfg.block != "rwkv" and cfg.recurrent is None:
        x = x + M._sinusoid(S, cfg.d_model, x.dtype)[None]
    memory = encode(params, cfg, enc_frames) if enc_frames is not None else None
    caches = []
    for g, gp in zip(layer_groups(cfg), params["groups"]):
        x, _, nc = _group_scan(x, gp, cfg, g, positions, memory, use_kernels,
                               False, return_cache=True, cache_len=cache_len)
        caches.append(nc)
    x = L.norm_fwd(params["final_norm"], cfg, x)
    return M._unembed(params, cfg, x[:, -1:])[:, 0], caches
