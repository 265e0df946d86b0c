"""Reference family ``mla_moe``: DeepSeek-V2's decoder, latent attention
with YaRN positions and routed plus shared experts.

Token embedding; ``first_dense_layers`` layers of RMSNorm, multi-head
latent attention (MLA) and a SwiGLU MLP, then layers whose MLP is a mixture
of experts; a final RMSNorm and an untied head, with the next-token cross
entropy over the first ``S - 1`` positions plus the experts' balance loss.
Written from the published equations (arXiv:2405.04434 and the model's
``modeling_deepseek.py``), the weights drawn as the program's initialiser
draws them.  The shared step, optimizer and helpers are
``bench/reference.py``'s.

MLA without a query rank: ``q = h Wq`` split per head into a ``nope`` and a
``rope`` part; the latent ``c = RMSNorm(h Wdkv)``; ``k_nope = c Wuk`` and
``v = c Wuv`` per head; one rope key ``h Wkr`` shared by every head.
Scores ``(q_nope k_nope + q_rope k_rope) * mscale^2 / sqrt(nope + rope)``,
causal softmax, ``o Wo``.  YaRN: the rope frequencies of the dims that
turn fewer than ``beta_slow`` times over the original context are divided
by ``factor``, those that turn more than ``beta_fast`` times kept, a
linear ramp between; cos and sin are scaled by ``mscale(factor, mscale) /
mscale(factor, mscale_all_dim)`` and the softmax scale by ``mscale(factor,
mscale_all_dim)^2``, with ``mscale(s, m) = 0.1 m ln s + 1``.

The experts: a float32 router over all ``n_routed`` experts, softmax,
greedy top-k; each chosen expert's gate is its probability, not
renormalised over the top-k (``norm_topk_prob`` false), times
``routed_scaling``.
This chip's share is the ``held`` experts from expert 0 on (``n_held``, 0
meaning all): each is computed on every token and weighted by its gate,
zero where the token did not choose it.  What the experts not held would
add is left out, as in the program.  The shared experts, one SwiGLU of
``n_shared * d_expert``, see every token.  The balance loss (``seq_aux``
true): per sequence ``f[e] = (count of the sequence's top-k choices of e) / (S k /
E)`` and ``P[e]`` the mean probability of e over its positions; ``alpha *
mean over sequences of sum_e f[e] P[e]``, over all E experts, summed over
the expert layers and added to the loss.

Departures from the published model that this family restates, each shared
with the program and so restated on purpose (the helpers' own are listed in
``bench/reference.py``):

* the final norm's weights are kept in float32, every other weight in the
  configuration's dtype;
* an expert's three weights are drawn from keys of their own, so that a
  share holds the same experts as the whole layer;
* the router is drawn from a normal of standard deviation 0.02 (the
  published gate's is kaiming-uniform, bound ``1/sqrt(d_model)``); every
  other matrix from a normal of standard deviation ``1/sqrt(fan_in)``.

Not a departure: the published code de-interleaves the rope parts of q and
k (``view(d/2, 2).transpose``) and rotates halves; the program rotates the
same pairs ``(x[2i], x[2i+1])`` at the same frequencies in place.  q and k
are laid out alike either way, so every score is the same; this family
follows the published layout.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import (_attention, _dense, _mm, _next_token_ce, _norm,
                             _norm_init, _round_to)


def check_supported(m: dict) -> None:
    """The family covers DeepSeek-V2-style decoders: MLA without a query
    rank, softmax-routed experts after leading dense layers."""
    for key, ok in (("window", None), ("encdec", None), ("recurrent", None),
                    ("vlm_prefix_len", 0), ("tie_embeddings", False),
                    ("norm", "rms")):
        if m.get(key, ok) != ok:
            raise ValueError(f"reference does not cover {key}={m[key]!r}")
    if m["block"] != "mla" or not m.get("mla") or not m.get("moe"):
        raise ValueError("reference covers MLA with experts only")
    if m["mla"]["q_lora_rank"]:
        raise ValueError("reference covers full-rank queries only")
    if m["moe"].get("scoring_func", "softmax") != "softmax":
        raise ValueError("reference covers softmax routing only")
    if m["act"] != "silu" or not m["glu"]:
        raise ValueError("reference covers the SwiGLU MLP only")


def held(m: dict) -> int:
    return m["moe"]["n_held"] or m["moe"]["n_routed"]


# ----------------------------------------------------------------- weights
def _mlp_init(key, d: int, f: int) -> dict:
    m1, m2, m3 = jax.random.split(key, 3)
    return {"w_down": _dense(m2, f, d), "w_up": _dense(m1, d, f),
            "w_gate": _dense(m3, d, f)}


def _experts(key, shape, fan_in, first: int, n_held: int, n: int):
    keys = jax.random.split(key, n)[first:first + n_held]
    return jax.vmap(lambda k: jax.random.normal(k, shape))(keys) \
        / np.sqrt(fan_in)


def moe_init(key, m: dict, first_expert: int = 0) -> dict:
    """One expert layer's weights, the held experts from ``first_expert``
    on."""
    e, d = m["moe"], m["d_model"]
    de = e["d_expert"]
    k = jax.random.split(key, 5)
    share = (first_expert, held(m), e["n_routed"])
    p = {"router": jax.random.normal(k[0], (d, e["n_routed"])) * 0.02,
         "w_up": _experts(k[1], (d, de), d, *share),
         "w_down": _experts(k[2], (de, d), de, *share),
         "w_gate": _experts(k[3], (d, de), d, *share)}
    if e["n_shared"]:
        p["shared"] = _mlp_init(k[4], d, e["n_shared"] * de)
    return p


def _layer_init(key, m: dict, dense: bool) -> dict:
    a, d, h = m["mla"], m["d_model"], m["n_heads"]
    k_attn, k_mlp, _, _ = jax.random.split(key, 4)
    k = jax.random.split(k_attn, 7)
    attn = {"wq": _dense(k[0], d, h * (a["qk_nope_head_dim"]
                                       + a["qk_rope_head_dim"])),
            "w_dkv": _dense(k[2], d, a["kv_lora_rank"]),
            "w_kr": _dense(k[3], d, a["qk_rope_head_dim"]),
            "kv_norm": {"scale": jnp.ones((a["kv_lora_rank"],),
                                          jnp.float32)},
            "w_uk": _dense(k[4], a["kv_lora_rank"],
                           h * a["qk_nope_head_dim"]),
            "w_uv": _dense(k[5], a["kv_lora_rank"], h * a["v_head_dim"]),
            "wo": _dense(k[6], h * a["v_head_dim"], d)}
    p = {"ln1": _norm_init(m), "attn": attn, "ln2": _norm_init(m)}
    if dense:
        p["mlp"] = _mlp_init(k_mlp, d, m["d_ff"])
    else:
        p["moe"] = moe_init(k_mlp, m)
    return p


def init(key, m: dict) -> dict:
    """Float32 weights holding the values the program's initialiser stores
    for ``key``: the same draws, rounded to the storage dtype."""
    n, fd = m["n_layers"], m["moe"]["first_dense_layers"]
    keys = jax.random.split(key, n + 4)
    lk = jax.random.split(keys[1], n)
    groups = [jax.vmap(lambda k, dense=dense: _layer_init(k, m, dense))(ks)
              for ks, dense in ((lk[:fd], True), (lk[fd:], False))
              if len(ks)]
    params = {
        "embed": jax.random.normal(keys[0], (m["vocab"], m["d_model"]))
        * 0.02,
        "final_norm": _norm_init(m),
        "groups": groups,
        "lm_head": jax.random.normal(keys[-1], (m["d_model"], m["vocab"]))
        * 0.02,
    }
    dt = m["dtype"]
    out = jax.tree.map(lambda a: _round_to(a, dt), params)
    out["final_norm"] = params["final_norm"]
    return out


def storage_dtypes(m: dict) -> dict:
    """The dtype each weight is kept in, as a tree shaped like the
    parameters: the final norm in float32, the rest in ``m["dtype"]``."""
    dt = m["dtype"]
    tree = jax.eval_shape(lambda: init(jax.random.PRNGKey(0), m))
    out = jax.tree.map(lambda _: dt, tree)
    out["final_norm"] = jax.tree.map(lambda _: "float32", tree["final_norm"])
    return out


# ------------------------------------------------------------------ forward
def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(m: dict, dim: int) -> np.ndarray:
    """The ``dim / 2`` inverse frequencies of the rope part, YaRN's where
    ``m["rope_scaling"]`` is set."""
    base = m["rope_theta"]
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    ys = m.get("rope_scaling")
    if not ys:
        return extra
    inter = 1.0 / (ys["factor"] * base ** (np.arange(0, dim, 2) / dim))

    def correction_dim(rotations):
        return dim * math.log(ys["original_max_position_embeddings"]
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(ys["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(ys["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    extra_mask = 1.0 - ramp
    return inter * (1 - extra_mask) + extra * extra_mask


def _yarn_rope(x, m):
    """The published rotation: de-interleave the pairs into halves, then
    ``x cos + rotate_half(x) sin`` with cos and sin of the YaRN frequencies
    times their mscale.  x: (B, S, H, d)."""
    B, S, H, d = x.shape
    ys = m.get("rope_scaling")
    scale = yarn_mscale(ys["factor"], ys["mscale"]) \
        / yarn_mscale(ys["factor"], ys["mscale_all_dim"]) if ys else 1.0
    freqs = np.arange(S)[:, None] * yarn_inv_freq(m, d)[None, :]
    emb = np.concatenate([freqs, freqs], axis=-1)
    cos = jnp.asarray((np.cos(emb) * scale).astype(np.float32))[None, :, None]
    sin = jnp.asarray((np.sin(emb) * scale).astype(np.float32))[None, :, None]
    x = x.reshape(B, S, H, d // 2, 2).swapaxes(3, 4).reshape(B, S, H, d)
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos + half * sin


def softmax_mscale(m: dict) -> float:
    ys = m.get("rope_scaling")
    if not ys or not ys["mscale_all_dim"]:
        return 1.0
    return yarn_mscale(ys["factor"], ys["mscale_all_dim"]) ** 2


def _mla(p, m, h, numerics):
    B, S, _ = h.shape
    a, H = m["mla"], m["n_heads"]
    nope, rope, vd = (a["qk_nope_head_dim"], a["qk_rope_head_dim"],
                      a["v_head_dim"])
    q = _mm("bsd,de->bse", h, p["wq"], numerics).reshape(B, S, H,
                                                         nope + rope)
    c = _mm("bsd,dr->bsr", h, p["w_dkv"], numerics)
    c = c * jax.lax.rsqrt(jnp.mean(jnp.square(c), axis=-1, keepdims=True)
                          + 1e-6) * p["kv_norm"]["scale"]
    k_rope = _yarn_rope(_mm("bsd,dr->bsr", h, p["w_kr"],
                            numerics)[:, :, None], m)
    q = jnp.concatenate([q[..., :nope], _yarn_rope(q[..., nope:], m)],
                        axis=-1)
    k_nope = _mm("bsr,re->bse", c, p["w_uk"], numerics).reshape(B, S, H,
                                                                nope)
    v = _mm("bsr,re->bse", c, p["w_uv"], numerics).reshape(B, S, H, vd)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (B, S, H, rope))],
                        axis=-1)
    # _attention scales by 1/sqrt(nope + rope); YaRN's factor rides on q
    o = _attention(q * softmax_mscale(m), k, v, numerics)
    return _mm("bse,ed->bsd", o.reshape(B, S, H * vd), p["wo"], numerics)


def _swiglu(p, h, numerics):
    g = jax.nn.silu(_mm("bsd,df->bsf", h, p["w_gate"], numerics))
    u = _mm("bsd,df->bsf", h, p["w_up"], numerics)
    return _mm("bsf,fd->bsd", g * u, p["w_down"], numerics)


def moe(p, m, h, numerics="f32", first_expert: int = 0):
    """One expert layer on h (B, S, D): the held experts' part of the
    routed output plus the shared experts, and the balance loss summed
    over the rows (``alpha * sum_b sum_e f P``)."""
    e = m["moe"]
    S = h.shape[1]
    E, k = e["n_routed"], e["top_k"]
    probs = jax.nn.softmax(_mm("bsd,de->bse", h, p["router"], numerics),
                           axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)
    top_p = top_p * e["routed_scaling"]
    experts = first_expert + jnp.arange(held(m))
    gates = jnp.sum(jnp.where(top_i[..., None] == experts, top_p[..., None],
                              0.0), axis=2)                  # (B, S, held)

    @jax.checkpoint
    def expert(out, w):
        """One held expert on every token, weighted by its gate; one at a
        time, so that a block of 8192 tokens fits beside the weights."""
        w_gate, w_up, w_down, gate = w
        return out + _swiglu({"w_gate": w_gate, "w_up": w_up,
                              "w_down": w_down}, h, numerics) \
            * gate[..., None], None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                          (p["w_gate"], p["w_up"], p["w_down"],
                           jnp.moveaxis(gates, -1, 0)))
    if e["n_shared"]:
        out = out + _swiglu(p["shared"], h, numerics)
    chosen = jax.nn.one_hot(top_i, E, dtype=jnp.float32)     # (B, S, k, E)
    f = jnp.sum(chosen, axis=(1, 2)) / (S * k / E)            # (B, E)
    aux = jnp.sum(f * jnp.mean(probs, axis=1))
    return out, e["aux_loss_coef"] * aux


def _layer(x, p, m, numerics):
    x = x + _mla(p["attn"], m, _norm(p["ln1"], m, x), numerics)
    h = _norm(p["ln2"], m, x)
    if "mlp" in p:
        return x + _swiglu(p["mlp"], h, numerics), 0.0
    out, aux = moe(p["moe"], m, h, numerics)
    return x + out, aux


def _block_sums(params, m, tokens, numerics):
    """Sum of next-token cross entropies of ``tokens`` (B, S) over the
    first ``S - 1`` positions, and the balance loss summed over the rows
    and the expert layers."""
    x = params["embed"][tokens]
    aux = jnp.zeros((), jnp.float32)
    for g in params["groups"]:
        def body(carry, p):
            x, aux = carry
            x, a = jax.checkpoint(
                lambda x, p: _layer(x, p, m, numerics))(x, p)
            return (x, aux + a), None

        (x, aux), _ = jax.lax.scan(body, (x, aux), g)
    x = _norm(params["final_norm"], m, x)
    return _next_token_ce(x, params["lm_head"], tokens, numerics), aux


def mean_loss(params, m, blocks, numerics="f32"):
    """Mean next-token cross entropy of the rows of ``blocks`` (n, rows,
    S), summed one block of rows at a time, plus the balance loss: per
    sequence, the mean over all rows.  A block keeps each layer's input
    for the backward pass and recomputes the rest."""
    n, rows, S = blocks.shape

    def body(total, tb):
        ce, aux = _block_sums(params, m, tb, numerics)
        return (total[0] + ce, total[1] + aux), None

    zero = jnp.zeros((), jnp.float32)
    (ce, aux), _ = jax.lax.scan(body, (zero, zero), blocks)
    return ce / (n * rows * (S - 1)) + aux / (n * rows)


# ---------------------------------------------------------------- operations
def matmul_params(m: dict) -> float:
    """Weights a token meets in a matmul, forward: every layer's MLA
    projections, the dense layers' MLP, each expert layer's router and
    shared experts whole and its held experts at their expected share
    (``top_k * held / n_routed`` experts a token under uniform routing),
    and the head."""
    a, e = m["mla"], m["moe"]
    d, h, r = m["d_model"], m["n_heads"], a["kv_lora_rank"]
    mla = d * h * (a["qk_nope_head_dim"] + a["qk_rope_head_dim"]) \
        + d * (r + a["qk_rope_head_dim"]) \
        + r * h * (a["qk_nope_head_dim"] + a["v_head_dim"]) \
        + h * a["v_head_dim"] * d
    expert = 3 * d * e["d_expert"]
    routed = expert * e["top_k"] * held(m) / e["n_routed"]
    moe_layer = e["n_shared"] * expert + d * e["n_routed"] + routed
    fd = e["first_dense_layers"]
    return m["n_layers"] * mla + fd * 3 * d * m["d_ff"] \
        + (m["n_layers"] - fd) * moe_layer + m["vocab"] * d


def flops_per_token(m: dict, seq_len: int) -> float:
    """6 per active matmul weight (``matmul_params``: an expectation under
    uniform routing), plus causal attention at ``6 * S * H * (qk + v) / 2``
    a layer (scores over the ``nope + rope`` width, values over ``v``, half
    the positions on average, times three for the backward)."""
    a = m["mla"]
    width = a["qk_nope_head_dim"] + a["qk_rope_head_dim"] + a["v_head_dim"]
    attn = 6 * seq_len * m["n_heads"] * width / 2 * m["n_layers"]
    return 6.0 * matmul_params(m) + attn
