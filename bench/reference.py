"""Plain float32 reference of a training step: what every family of model
shares.

Imports nothing of the program under test.  A configuration file names its
family (``"reference": "<family>"``), and ``bench/references/<family>.py``
restates what depends on the architecture, in straightforward
``jax.numpy`` at ``Precision.HIGHEST``.  A family module defines:

    check_supported(m)            raise where the family does not cover ``m``
    init(key, m)                  float32 weights holding the values the
                                  program's initialiser stores from ``key``:
                                  the same ``jax.random`` draws, rounded to
                                  the storage dtypes, so that the reference
                                  never takes weights from the program
    storage_dtypes(m)             the dtype each weight is kept in, a tree
                                  shaped like the weights
    mean_loss(params, m, blocks, numerics)
                                  the training loss of the rows of ``blocks``
                                  (n, rows, S), one block of rows at a time,
                                  with whatever the program adds to it
    flops_per_token(m, seq_len)   model operations a token needs, forward and
                                  backward (``bench/flops.py`` says which)

``m`` is the configuration file's ``model`` object.  A jitted function gets
it as a static argument (``freeze``) and sees it again as a dict
(``thaw``): a nested block such as ``m["moe"]`` as a dict, a list as a
tuple.  ``spec.reference_family`` finds a family by its name.

This module holds the rest: gradients by ``jax.grad``, clipping by global
norm, AdamW folded from the past gradients, the rounding of each updated
weight to the dtype the family stores it in, and the per-leaf and
per-channel norms that the comparison reads.  It computes in blocks of rows
(a family's ``mean_loss``), so that a full-size step fits on one chip beside
nothing else.

The helpers a family imports rather than copies: ``_mm`` (a matmul at
HIGHEST, or on float8 operands), ``_round_to``, ``_dense`` and
``_norm_init`` (weight draws), ``_norm``, ``_rope``, ``_attention`` and
``_next_token_ce``.  Departures from the published model descriptions that
they restate, each shared with the program and so restated on purpose:

* rotary embedding rotates interleaved pairs ``(x[2i], x[2i+1])`` of the
  first ``rope_frac * head_dim`` channels; Hugging Face rotates the two
  halves.  The two differ by a fixed permutation of the query and key
  projection columns, which random weights do not see;
* the norm epsilons are 1e-6 (RMSNorm) and 1e-5 (LayerNorm), whatever the
  published configuration gives.

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
class _Block(tuple):
    """A nested block of settings, frozen as its sorted ``(key, value)``
    pairs."""


def freeze(m: dict):
    """``m`` as a hashable static argument: sorted ``(key, value)`` pairs, a
    nested dict as a ``_Block`` of its own, a list as a tuple."""
    if isinstance(m, dict):
        return _Block(sorted((k, freeze(v)) for k, v in m.items()))
    if isinstance(m, (list, tuple)):
        return tuple(freeze(v) for v in m)
    return m


def thaw(items):
    """The dict that ``freeze`` made ``items`` from, lists as tuples."""
    if isinstance(items, _Block):
        return {k: thaw(v) for k, v in items}
    if isinstance(items, tuple):
        return tuple(thaw(v) for v in items)
    return items


# ----------------------------------------------------------------- weights
def _dense(key, d_in, d_out):
    return jax.random.normal(key, (d_in, d_out), jnp.float32) \
        * (1.0 / np.sqrt(d_in))


def _norm_init(m: dict) -> dict:
    p = {"scale": jnp.ones((m["d_model"],), jnp.float32)}
    if m["norm"] == "layer":
        p["bias"] = jnp.zeros((m["d_model"],), jnp.float32)
    return p


def _round_to(x, dtype: str):
    """Round float32 ``x`` to the values ``dtype`` holds.  A cast there and
    back would do on the CPU, but XLA may drop such a pair on the TPU (it
    allows excess precision); ``reduce_precision`` it keeps."""
    f = jnp.finfo(jnp.dtype(dtype))
    return jax.lax.reduce_precision(x, exponent_bits=f.nexp,
                                    mantissa_bits=f.nmant)


@partial(jax.jit, static_argnums=(0, 2))
def _init(fam, key, m_items):
    return fam.init(key, thaw(m_items))


def init_params(fam, seed: int, m: dict) -> dict:
    """Family ``fam``'s float32 weights for ``seed``."""
    return _init(fam, jax.random.PRNGKey(seed), freeze(m))


def leaf_names(fam, m: dict) -> list:
    """Path names of the parameter leaves, in ``jax.tree.leaves`` order."""
    tree = jax.eval_shape(lambda: init_params(fam, 0, m))
    return [jax.tree_util.keystr(p) for p, _ in
            jax.tree_util.tree_flatten_with_path(tree)[0]]


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
    """Causal softmax attention, exact, one chunk of queries at a time,
    scores scaled by ``1/sqrt(hd)``.  q, k: (B, S, H or KV, hd); v: (B, S,
    KV, vd)."""
    B, S, H, hd = q.shape
    kv, vd = k.shape[2], v.shape[-1]
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

    out = jax.lax.map(block, jnp.arange(nq))       # (nq, B, qb, KV, G, vd)
    return out.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, H, vd)


def _next_token_ce(x, head, tokens, numerics):
    """Sum of next-token cross entropies of ``tokens`` (B, S) over the
    first ``S - 1`` positions, from the final hidden states ``x`` (B, S, D)
    through ``head`` (D, V), a chunk of positions at a time."""
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


@partial(jax.jit, static_argnums=(0, 3, 4, 5))
def _grads(fam, params, blocks, m_items, clip_norm, numerics):
    """Loss, pre-clip global gradient norm, the clipping scale, and the
    unclipped gradients."""
    loss, grads = jax.value_and_grad(fam.mean_loss)(
        params, thaw(m_items), blocks, numerics)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                         for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, clip_norm / (gnorm + 1e-9))
    return loss, gnorm, scale, grads


@partial(jax.jit, static_argnums=(5, 6), donate_argnums=(0,))
def _update(p, g, past, count, scale, dtype, job_items):
    """AdamW on one weight.  The moments are folded from the clipped
    gradients of every step so far (``past``, oldest first, then this
    step's), in the order in which the optimizer would have updated them."""
    job = thaw(job_items)
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


def train(fam, m: dict, job: dict, seed: int, batches, rows_per_block: int,
          numerics: str = "f32", devices=None) -> dict:
    """Run ``len(batches)`` AdamW steps of family ``fam`` from the seed's
    weights.

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
    fam.check_supported(m)
    mesh = jax.sharding.Mesh(np.asarray(devices or jax.devices()[:1]), ("d",))
    rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    spread = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(None, "d", None))
    mi, ji = freeze(m), freeze(job)
    params = jax.device_put(init_params(fam, seed, m), rep)
    dtypes = jax.tree.leaves(fam.storage_dtypes(m))
    past = [[] for _ in dtypes]
    losses, gnorms, grad1, grad1_ch, seconds = [], [], None, None, []
    with jax.default_matmul_precision("highest"):
        for i, tokens in enumerate(batches):
            t0 = time.perf_counter()
            blocks = np.asarray(tokens).reshape(-1, rows_per_block,
                                                tokens.shape[-1])
            loss, gnorm, scale, grads = _grads(
                fam, params, jax.device_put(blocks, spread), mi,
                job["clip_norm"], numerics)
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
            params, jax.device_put(init_params(fam, seed, m), rep))]
    return {"loss": losses, "grad_norm": gnorms, "grad1": grad1,
            "grad1_channels": grad1_ch, "change": change,
            "seconds": seconds}
