"""Megatron-style vocab-parallel embedding and cross-entropy.

The embedding table / LM head keep their vocab dim sharded over ``model``.
A plain GSPMD gather over a vocab-sharded table triggers involuntary full
rematerialisation (the partitioner replicates the table), so both the lookup
and the CE loss are written as explicit partial-manual ``shard_map`` over
the ``model`` axis:

* lookup: each rank gathers only its vocab slice (masked), then one small
  psum((B,S,D)) combines;
* CE: each rank computes logits against its vocab slice; max/sum/gold are
  combined with pmax/psum over ``model`` — the (B,S,V) logits tensor only
  ever exists vocab-sharded.

Both are differentiable (shard_map transposes psum/pmax correctly).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P


def _inside_manual() -> bool:
    return AxisType.Manual in jax.sharding.get_abstract_mesh().axis_types


def _smap(fn, mesh, in_specs, out_specs):
    # inside a manual region the nested shard_map takes the ambient mesh
    return jax.shard_map(fn, mesh=None if _inside_manual() else mesh,
                         in_specs=in_specs, out_specs=out_specs,
                         axis_names={"model"}, check_vma=False)


def applicable(mesh, vocab: int) -> bool:
    return (mesh is not None and "model" in mesh.shape
            and vocab % mesh.shape["model"] == 0)


def _vstarts(vocab: int, model_size: int):
    """(model_size,) array of per-rank vocab offsets; passed P("model") so
    each rank's local slice is its own offset (avoids axis_index, which
    Shardy cannot lower in nested manual contexts)."""
    vshard = vocab // model_size
    return jnp.arange(model_size, dtype=jnp.int32) * vshard


def embed_lookup(embed, tokens, mesh):
    """embed: (V, D) sharded P("model", None); tokens: (B, S) int32."""
    model_size = mesh.shape["model"] if mesh is not None else 1

    def local(emb_loc, toks, vstart):
        vshard = emb_loc.shape[0]
        loc = toks - vstart[0]
        ok = (loc >= 0) & (loc < vshard)
        x = emb_loc[jnp.clip(loc, 0, vshard - 1)]
        x = jnp.where(ok[..., None], x, jnp.zeros((), x.dtype))
        # psum in f32 (XLA:CPU bf16 all-reduce miscompile workaround)
        return jax.lax.psum(x.astype(jnp.float32), "model").astype(x.dtype)

    starts = _vstarts(embed.shape[0], model_size)
    return _smap(local, mesh, (P("model", None), P(), P("model")), P())(
        embed, tokens, starts)


def ce_chunk(x, head, targets, weights, mesh, *, transpose_head: bool):
    """Vocab-parallel CE over one sequence chunk.

    x: (B, c, D); head: (D, V) P(None,"model") or — tied — (V, D)
    P("model",None) with ``transpose_head=True``; targets/weights: (B, c).
    Returns (ce_sum, weight_sum) scalars (replicated).
    """
    head_spec = P("model", None) if transpose_head else P(None, "model")

    def local(xc, head_loc, tc, wc, vstart):
        w = head_loc.T if transpose_head else head_loc          # (D, V/m)
        logits = (xc @ w.astype(xc.dtype)).astype(jnp.float32)  # (B,c,V/m)
        vshard = logits.shape[-1]
        start = vstart[0]
        # the max is a constant shift for stability: stop_gradient *inside*
        # the pmax so its tangent is symbolically zero (pmax has no JVP rule)
        m = jax.lax.pmax(jax.lax.stop_gradient(jnp.max(logits, axis=-1)),
                         "model")
        z = jax.lax.psum(jnp.sum(jnp.exp(logits - m[..., None]), axis=-1),
                         "model")
        logz = m + jnp.log(z)
        loc = tc - start
        ok = (loc >= 0) & (loc < vshard)
        picked = jnp.take_along_axis(
            logits, jnp.clip(loc, 0, vshard - 1)[..., None], axis=-1)[..., 0]
        gold = jax.lax.psum(jnp.where(ok, picked, 0.0), "model")
        ce = jnp.sum((logz - gold) * wc)
        return ce, jnp.sum(wc)

    model_size = mesh.shape["model"] if mesh is not None else 1
    vocab = head.shape[0] if transpose_head else head.shape[-1]
    starts = _vstarts(vocab, model_size)
    return _smap(local, mesh, (P(), head_spec, P(), P(), P("model")),
                 (P(), P()))(x, head, targets, weights, starts)
