"""Plain float32 reference of the dense decoder and its AdamW training step.

Imports nothing of the program under test.  It restates, in straightforward
``jax.numpy`` at ``Precision.HIGHEST``:

* the weight recipe (the same ``jax.random`` draws the program's initialiser
  makes from the seed), so that it never takes weights from the program;
* the forward pass: token embedding, pre-norm blocks (RMSNorm or LayerNorm,
  attention with GQA or full MHA, optional QKV bias, partial rotary, SwiGLU
  MLP), a final norm and a tied or untied head, with the next-token cross
  entropy over the first ``S - 1`` positions;
* gradients by ``jax.grad``, clipping by global norm, AdamW, and the
  rounding of each updated weight to the dtype the configuration stores.

Computed in blocks of rows, layer by layer and in query and token chunks,
so that a full-size step fits on one chip beside nothing else.

Departures from the published model descriptions, each shared with the
program and so restated here on purpose:

* a tied embedding is scaled by ``sqrt(d_model)`` on the way in (Qwen2
  publishes no such scale);
* rotary embedding rotates interleaved pairs ``(x[2i], x[2i+1])`` of the
  first ``rope_frac * head_dim`` channels; Hugging Face rotates the two
  halves.  The two differ by a fixed permutation of the query and key
  projection columns, which random weights do not see;
* the norm epsilons are 1e-6 (RMSNorm) and 1e-5 (LayerNorm), whatever the
  published configuration gives;
* the final norm's weights are kept in float32, every other weight in the
  configuration's dtype.

``numerics="fp8"`` rounds every matmul operand to an 8-bit float (e4m3,
subnormals flushed) under a per-tensor scale: the control that a
comparison must fail.
"""
from __future__ import annotations

import math
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512      # attention queries per chunk
CE_CHUNK = 512     # head positions per chunk
FP8_MAX = 240.0    # largest finite e4m3 that reduce_precision(4, 3) keeps


# ------------------------------------------------------------------ config
def head_dim(m: dict) -> int:
    return m["head_dim"] or m["d_model"] // m["n_heads"]


def check_supported(m: dict) -> None:
    """The reference covers dense attention decoders only."""
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
def _dense(key, d_in, d_out):
    return jax.random.normal(key, (d_in, d_out), jnp.float32) \
        * (1.0 / np.sqrt(d_in))


def _norm_init(m: dict) -> dict:
    p = {"scale": jnp.ones((m["d_model"],), jnp.float32)}
    if m["norm"] == "layer":
        p["bias"] = jnp.zeros((m["d_model"],), jnp.float32)
    return p


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


def storage_dtypes(m: dict) -> dict:
    """The dtype each weight is kept in, as a tree shaped like the
    parameters: the final norm in float32, the rest in ``m["dtype"]``."""
    dt = m["dtype"]
    tree = jax.eval_shape(lambda: init_params(0, m))
    out = jax.tree.map(lambda _: dt, tree)
    out["final_norm"] = jax.tree.map(lambda _: "float32", tree["final_norm"])
    return out


def _round_to(x, dtype: str):
    """Round float32 ``x`` to the values ``dtype`` holds.  A cast there and
    back would do on the CPU, but XLA may drop such a pair on the TPU (it
    allows excess precision); ``reduce_precision`` it keeps."""
    f = jnp.finfo(jnp.dtype(dtype))
    return jax.lax.reduce_precision(x, exponent_bits=f.nexp,
                                    mantissa_bits=f.nmant)


@partial(jax.jit, static_argnums=(1,))
def _init(key, m_items):
    m = dict(m_items)
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


def _hashable(m: dict):
    return tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, str, bool, type(None)))))


def init_params(seed: int, m: dict) -> dict:
    """Float32 weights holding the values the program's initialiser stores
    for ``seed``: the same draws, rounded to the storage dtype."""
    return _init(jax.random.PRNGKey(seed), _hashable(m))


# ----------------------------------------------------------------- numerics
def _fp8(x):
    """Round to an 8-bit float (4 exponent bits, 3 mantissa bits) under a
    per-tensor scale; identity gradient."""
    amax = jax.lax.stop_gradient(jnp.max(jnp.abs(x)))
    s = FP8_MAX / jnp.maximum(amax, 1e-30)
    q = jax.lax.reduce_precision(x * s, exponent_bits=4, mantissa_bits=3) / s
    return x + jax.lax.stop_gradient(q - x)


def _mm(spec, a, b, numerics):
    if numerics == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


# ------------------------------------------------------------------ forward
def _norm(p, m, x):
    if m["norm"] == "layer":
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + 1e-5) * p["scale"] + p["bias"]
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + 1e-6) * p["scale"]


def _rope(x, m):
    """Rotate interleaved pairs of the first ``rope_frac * hd`` channels.
    x: (B, S, H, hd)."""
    hd = x.shape[-1]
    rot = int(hd * m["rope_frac"])
    inv = 1.0 / (m["rope_theta"] ** (np.arange(0, rot, 2) / rot))
    ang = (np.arange(x.shape[1])[:, None] * inv[None, :]).astype(np.float32)
    sin = jnp.asarray(np.sin(ang))[None, :, None, :]
    cos = jnp.asarray(np.cos(ang))[None, :, None, :]
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    r = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([r.reshape(xr.shape), xp], axis=-1)


def _attention(q, k, v, numerics):
    """Causal softmax attention, exact, one chunk of queries at a time.
    q: (B, S, H, hd); k, v: (B, S, KV, hd)."""
    B, S, H, hd = q.shape
    kv = k.shape[2]
    qb = min(Q_BLOCK, S)
    nq = S // qb
    qg = q.reshape(B, nq, qb, kv, H // kv, hd)

    @jax.checkpoint
    def block(i):
        qi = jax.lax.dynamic_index_in_dim(qg, i, axis=1, keepdims=False)
        s = _mm("bqkgh,btkh->bkgqt", qi, k, numerics) / math.sqrt(hd)
        qpos = i * qb + jnp.arange(qb)[:, None]
        s = jnp.where(jnp.arange(S)[None, :] <= qpos, s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        return _mm("bkgqt,btkh->bqkgh", w, v, numerics)

    out = jax.lax.map(block, jnp.arange(nq))       # (nq, B, qb, KV, G, hd)
    return out.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, H, hd)


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
    B, S, D = x.shape
    c = min(CE_CHUNK, S)
    targets = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    valid = (jnp.arange(S) < S - 1).astype(jnp.float32)

    @jax.checkpoint
    def chunk(i):
        xs = jax.lax.dynamic_slice_in_dim(x, i * c, c, axis=1)
        ts = jax.lax.dynamic_slice_in_dim(targets, i * c, c, axis=1)
        ws = jax.lax.dynamic_slice_in_dim(valid, i * c, c)
        logits = _mm("bsd,dv->bsv", xs, head, numerics)
        lz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, ts[..., None], axis=-1)[..., 0]
        return jnp.sum((lz - gold) * ws[None, :])

    return jnp.sum(jax.lax.map(chunk, jnp.arange(S // c)))


def mean_loss(params, m, blocks, numerics="f32"):
    """Mean next-token cross entropy of the rows of ``blocks`` (n, rows,
    S), summed one block of rows at a time.  A block keeps each layer's
    input for the backward pass and recomputes the rest."""
    n, rows, S = blocks.shape

    def body(total, tb):
        return total + _ce_sum(params, m, tb, numerics), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), blocks)
    return total / (n * rows * (S - 1))


# --------------------------------------------------------------- optimizer
def learning_rate(job: dict, step):
    """Linear warm-up to ``lr`` over ``warmup`` steps, then a cosine decay
    to ``final_frac * lr`` at ``total_steps``; ``step`` counts from 0."""
    base, warm, total = job["lr"], job["warmup"], job["total_steps"]
    span = max(total - warm, 1)
    t = jnp.minimum(step - warm, span) / span
    cos = base * (job["final_frac"]
                  + (1 - job["final_frac"]) * 0.5 * (1 + jnp.cos(jnp.pi * t)))
    return jnp.where(step < warm, base * (step + 1) / max(warm, 1), cos)


def _leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x)))
                      for x in jax.tree.leaves(tree)])


def channel_norms(tree) -> list:
    """Per leaf, the norm of each index of its last axis (a channel) over
    all the other axes."""
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)),
                             axis=tuple(range(x.ndim - 1))))
            for x in jax.tree.leaves(tree)]


@partial(jax.jit, static_argnums=(2, 3, 4))
def _grads(params, blocks, m_items, clip_norm, numerics):
    """Loss, pre-clip global gradient norm, the clipping scale, and the
    unclipped gradients."""
    loss, grads = jax.value_and_grad(mean_loss)(
        params, dict(m_items), blocks, numerics)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                         for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, clip_norm / (gnorm + 1e-9))
    return loss, gnorm, scale, grads


@partial(jax.jit, static_argnums=(5, 6), donate_argnums=(0,))
def _update(p, g, past, count, scale, dtype, job_items):
    """AdamW on one weight.  The moments are folded from the clipped
    gradients of every step so far (``past``, oldest first, then this
    step's), in the order in which the optimizer would have updated them."""
    job = dict(job_items)
    b1, b2 = job["b1"], job["b2"]
    g = g * scale
    mu = nu = jnp.zeros_like(p)
    for h in list(past) + [g]:
        mu = b1 * mu + (1 - b1) * h
        nu = b2 * nu + (1 - b2) * jnp.square(h)
    c = (count + 1).astype(jnp.float32)
    step = mu * (1.0 / (1 - b1 ** c)) \
        / (jnp.sqrt(nu * (1.0 / (1 - b2 ** c))) + job["eps"])
    p = p - learning_rate(job, count) * (step + job["weight_decay"] * p)
    return _round_to(p, dtype), g


@jax.jit
def _change_norms(a, b):
    return channel_norms(jax.tree.map(jnp.subtract, a, b))


def train(m: dict, job: dict, seed: int, batches, rows_per_block: int,
          numerics: str = "f32", devices=None) -> dict:
    """Run ``len(batches)`` AdamW steps from the seed's weights.

    Returns per-step ``loss`` and pre-clip ``grad_norm``; the per-leaf
    norms of step 1's clipped gradient (``grad1``) and its per-channel norms
    (``grad1_channels``, see ``channel_norms``); the per-channel norms of
    the weights' change over all the steps as stored (``change``); leaves
    in ``jax.tree.leaves`` order of the parameter tree; and each step's
    ``seconds``.

    Between steps each weight's clipped gradients wait in host memory, from
    which its moments are folded again: beside the weights and one
    gradient, two moments would not fit on one chip at the larger cells.

    The rows of a block are spread over ``devices`` (default: the first
    device); every device holds all the weights, and XLA sums the partial
    gradients.  ``rows_per_block`` is a multiple of their number."""
    check_supported(m)
    mesh = jax.sharding.Mesh(np.asarray(devices or jax.devices()[:1]), ("d",))
    rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    spread = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(None, "d", None))
    mi, ji = _hashable(m), _hashable(job)
    params = jax.device_put(init_params(seed, m), rep)
    dtypes = jax.tree.leaves(storage_dtypes(m))
    past = [[] for _ in dtypes]
    losses, gnorms, grad1, grad1_ch, seconds = [], [], None, None, []
    with jax.default_matmul_precision("highest"):
        for i, tokens in enumerate(batches):
            t0 = time.perf_counter()
            blocks = np.asarray(tokens).reshape(-1, rows_per_block,
                                                tokens.shape[-1])
            loss, gnorm, scale, grads = _grads(
                params, jax.device_put(blocks, spread), mi, job["clip_norm"],
                numerics)
            losses.append(float(loss))
            gnorms.append(float(gnorm))
            if grad1 is None:
                grad1 = np.asarray(_leaf_norms(grads)) * float(scale)
                grad1_ch = [np.asarray(c) * float(scale)
                            for c in jax.jit(channel_norms)(grads)]
            leaves, tree = jax.tree.flatten(params)
            del params
            glv = jax.tree.leaves(grads)
            del grads
            last = i == len(batches) - 1
            for j, dt in enumerate(dtypes):
                leaves[j], g = _update(
                    leaves[j], glv[j],
                    [jax.device_put(h, rep) for h in past[j]],
                    jax.device_put(jnp.int32(i), rep), scale, dt, ji)
                glv[j] = None
                if not last:
                    past[j].append(np.asarray(g))
                del g
            params = jax.tree.unflatten(tree, leaves)
            del leaves
            seconds.append(time.perf_counter() - t0)
        # the start is made again rather than kept: a copy would cost the
        # memory of one more set of weights during the steps
        change = [np.asarray(c) for c in _change_norms(
            params, jax.device_put(init_params(seed, m), rep))]
    return {"loss": losses, "grad_norm": gnorms, "grad1": grad1,
            "grad1_channels": grad1_ch, "change": change,
            "seconds": seconds}


def leaf_names(m: dict) -> list:
    """Path names of the parameter leaves, in ``jax.tree.leaves`` order."""
    tree = jax.eval_shape(lambda: init_params(0, m))
    return [jax.tree_util.keystr(p) for p, _ in
            jax.tree_util.tree_flatten_with_path(tree)[0]]
