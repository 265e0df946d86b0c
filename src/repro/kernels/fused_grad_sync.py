"""In-kernel fused compute+comm gradient sync — the Pallas kernel halves.

The searched ``FusionGraph.bucket_fused`` dimension prices a bucket whose
collective overlaps the producing compute's tail (the event engine's
early-ready model, DESIGN.md Sec. 13).  Enacted, the overlap comes from
fusing the communication's *local* memory halves into the staging copies
that surround it (CoCoNet-style):

* **pack side** — the reduce-scatter's input staging is fused into the
  bucket-pack epilogue: leaves are cast+copied straight into the
  chunk-major, shard-tiled f32 layout ``psum_scatter(tiled=True)``
  consumes, so the scatter needs no separate pad/copy pass and each
  chunk's collective can begin as soon as its staging block lands (the
  per-chunk early start the pricing layer discounts).
* **unpack side** — the all-gather's output buffer is un-staged back into
  the parameter leaves with the f32 -> grad-dtype cast fused into the same
  tiled pass, so gather + unpack + cast cost one HBM round trip.

The wire collectives themselves stay ``jax.lax`` ops between the two
kernel halves — the kernels own every local byte moved around them.  No
scaling happens inside the pack (f32 summation is non-associative:
``sum(x / dp) != sum(x) / dp`` bitwise); the mean divide rides on the
scattered shard, exactly like the plain ``rs_ag`` lowering, keeping the
fused path loss-bit-identical to the ``psum`` path.
"""
from __future__ import annotations

import jax.numpy as jnp

from .bucket_pack import convert_copy_kernel


def chunk_cuts(total: int, chunks: int) -> list[int]:
    """Even byte-range chunk boundaries — the same split convention as
    ``chunk_phases`` (pricing) and ``sync_grads`` (enactment)."""
    k = max(int(chunks), 1)
    return [total * c // k for c in range(k + 1)]


def fused_pack_kernel(leaves, total: int, dp: int, chunks: int = 1,
                      block: int = 65536, *, interpret: bool):
    """Stage a bucket of gradient leaves into reduce-scatter-ready chunks.

    Returns a list of ``chunks`` f32 buffers: chunk ``c`` covers byte range
    ``[cuts[c], cuts[c+1])`` of the fused bucket (padded to ``total``
    first), each zero-padded to a multiple of ``dp`` so
    ``psum_scatter(tiled=True)`` tiles it directly.  The grad-dtype -> f32
    convert is fused into the staging copy.
    """
    parts = [convert_copy_kernel(l.reshape(-1), jnp.float32, block=block,
                                 interpret=interpret) for l in leaves]
    buf = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
    if buf.shape[0] < total:
        buf = jnp.pad(buf, (0, total - buf.shape[0]))
    cuts = chunk_cuts(total, chunks)
    out = []
    for c in range(len(cuts) - 1):
        part = buf[cuts[c]:cuts[c + 1]]
        pad = (-part.shape[0]) % max(int(dp), 1)
        if pad:
            part = jnp.pad(part, (0, pad))
        out.append(part)
    return out


def fused_unpack_kernel(buf, shapes, dtypes, block: int = 65536, *,
                        interpret: bool):
    """Un-stage the gathered f32 bucket back into leaves, the f32 ->
    grad-dtype cast fused into the same tiled pass (all-gather epilogue)."""
    out = []
    off = 0
    for shape, dt in zip(shapes, dtypes):
        n = 1
        for s in shape:
            n *= int(s)
        part = convert_copy_kernel(buf[off:off + n], dt, block=block,
                                   interpret=interpret)
        out.append(part.reshape(shape))
        off += n
    return out
