"""Reference family ``dense``: the dense attention decoder.

Token embedding, pre-norm blocks (RMSNorm or LayerNorm, attention with GQA
or full MHA, optional QKV bias, partial rotary, SwiGLU MLP), a final norm
and a tied or untied head, with the next-token cross entropy over the first
``S - 1`` positions; the weights drawn as the program's initialiser draws
them.  The shared step, optimizer and helpers are ``bench/reference.py``'s.

Departures from the published model descriptions that this family restates,
each shared with the program and so restated on purpose (the helpers'
own are listed in ``bench/reference.py``):

* a tied embedding is scaled by ``sqrt(d_model)`` on the way in (Qwen2
  publishes no such scale);
* the final norm's weights are kept in float32, every other weight in the
  configuration's dtype.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.reference import (_attention, _dense, _mm, _next_token_ce, _norm,
                             _norm_init, _rope, _round_to)


def head_dim(m: dict) -> int:
    return m["head_dim"] or m["d_model"] // m["n_heads"]


def check_supported(m: dict) -> None:
    """The family covers dense attention decoders only."""
    for key, ok in (("block", "attn"), ("window", None), ("mla", None),
                    ("moe", None), ("encdec", None), ("recurrent", None),
                    ("vlm_prefix_len", 0)):
        if m.get(key, ok) != ok:
            raise ValueError(f"reference does not cover {key}={m[key]!r}")
    if m["act"] != "silu" or not m["glu"]:
        raise ValueError("reference covers the SwiGLU MLP only")
    if m["rope_frac"] <= 0.0:
        raise ValueError("reference covers rotary positions only")


# ----------------------------------------------------------------- weights
def _layer_init(key, m: dict) -> dict:
    d, h, kv, hd, f = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                       head_dim(m), m["d_ff"])
    k_attn, k_mlp, _, _ = jax.random.split(key, 4)
    a1, a2, a3, a4 = jax.random.split(k_attn, 4)
    attn = {"wq": _dense(a1, d, h * hd), "wk": _dense(a2, d, kv * hd),
            "wv": _dense(a3, d, kv * hd), "wo": _dense(a4, h * hd, d)}
    if m["qkv_bias"]:
        attn["bq"] = jnp.zeros((h * hd,), jnp.float32)
        attn["bk"] = jnp.zeros((kv * hd,), jnp.float32)
        attn["bv"] = jnp.zeros((kv * hd,), jnp.float32)
    m1, m2, m3 = jax.random.split(k_mlp, 3)
    mlp = {"w_down": _dense(m2, f, d), "w_up": _dense(m1, d, f),
           "w_gate": _dense(m3, d, f)}
    return {"ln1": _norm_init(m), "attn": attn, "ln2": _norm_init(m),
            "mlp": mlp}


def init(key, m: dict) -> dict:
    """Float32 weights holding the values the program's initialiser stores
    for ``key``: the same draws, rounded to the storage dtype."""
    keys = jax.random.split(key, m["n_layers"] + 4)
    params = {
        "embed": jax.random.normal(keys[0], (m["vocab"], m["d_model"]))
        * 0.02,
        "final_norm": _norm_init(m),
        "groups": [jax.vmap(lambda k: _layer_init(k, m))(
            jax.random.split(keys[1], m["n_layers"]))],
    }
    if not m["tie_embeddings"]:
        params["lm_head"] = jax.random.normal(
            keys[-1], (m["d_model"], m["vocab"])) * 0.02
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
def _layer(x, p, m, numerics):
    B, S, D = x.shape
    hd = head_dim(m)
    a = p["attn"]
    h = _norm(p["ln1"], m, x)
    q = _mm("bsd,de->bse", h, a["wq"], numerics)
    k = _mm("bsd,de->bse", h, a["wk"], numerics)
    v = _mm("bsd,de->bse", h, a["wv"], numerics)
    if m["qkv_bias"]:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = _rope(q.reshape(B, S, m["n_heads"], hd), m)
    k = _rope(k.reshape(B, S, m["n_kv_heads"], hd), m)
    v = v.reshape(B, S, m["n_kv_heads"], hd)
    o = _attention(q, k, v, numerics).reshape(B, S, m["n_heads"] * hd)
    x = x + _mm("bse,ed->bsd", o, a["wo"], numerics)
    h = _norm(p["ln2"], m, x)
    mp = p["mlp"]
    g = jax.nn.silu(_mm("bsd,df->bsf", h, mp["w_gate"], numerics))
    u = _mm("bsd,df->bsf", h, mp["w_up"], numerics)
    return x + _mm("bsf,fd->bsd", g * u, mp["w_down"], numerics)


def _ce_sum(params, m, tokens, numerics):
    """Sum of next-token cross entropies of ``tokens`` (B, S) over the
    first ``S - 1`` positions."""
    x = params["embed"][tokens]
    if m["tie_embeddings"]:
        x = x * math.sqrt(m["d_model"])

    def body(x, p):
        return jax.checkpoint(lambda x, p: _layer(x, p, m, numerics))(x, p), \
            None

    x, _ = jax.lax.scan(body, x, params["groups"][0])
    x = _norm(params["final_norm"], m, x)
    head = params["embed"].T if m["tie_embeddings"] else params["lm_head"]
    return _next_token_ce(x, head, tokens, numerics)


def mean_loss(params, m, blocks, numerics="f32"):
    """Mean next-token cross entropy of the rows of ``blocks`` (n, rows,
    S), summed one block of rows at a time.  A block keeps each layer's
    input for the backward pass and recomputes the rest."""
    n, rows, S = blocks.shape

    def body(total, tb):
        return total + _ce_sum(params, m, tb, numerics), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), blocks)
    return total / (n * rows * (S - 1))


# ---------------------------------------------------------------- operations
def matmul_params(m: dict) -> int:
    """Weights that enter a matmul: attention and MLP projections of every
    layer, and the head (the embedding, where it is tied)."""
    d, f = m["d_model"], m["d_ff"]
    hd = head_dim(m)
    attn = d * hd * (m["n_heads"] + 2 * m["n_kv_heads"]) \
        + m["n_heads"] * hd * d
    mlp = (3 if m["glu"] else 2) * d * f
    return m["n_layers"] * (attn + mlp) + m["vocab"] * d


def flops_per_token(m: dict, seq_len: int) -> float:
    """6 per matmul weight, plus causal attention at ``6 * S * d_attn`` a
    layer (``2 * S * d_attn`` forward for scores and values over half the
    positions on average, times three for the backward)."""
    attn = 6 * seq_len * m["n_heads"] * head_dim(m) * m["n_layers"]
    return 6.0 * matmul_params(m) + attn
