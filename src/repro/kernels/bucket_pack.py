"""Tensor-fusion bucket staging copy, as a Pallas-TPU kernel.

DisCo's tensor fusion stages many small gradient tensors into one fused
AllReduce buffer (and un-stages afterwards).  The copy is pure
HBM-bandwidth; the kernel tiles it through VMEM with an optional
bf16 -> f32 convert fused into the same pass (the dry-run reduces gradients
in f32), so staging + convert costs one HBM round-trip instead of two.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_TILE = 32 * 128  # elements in one TPU tile of the narrowest (8-bit) dtype

def _kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...].astype(o_ref.dtype)


def convert_copy_kernel(x, out_dtype=jnp.float32, block: int = 65536, *,
                        interpret: bool):
    """Tiled convert-copy of a flat array (the per-leaf staging primitive).

    x: (N,) any float dtype; returns (N,) ``out_dtype``.  N is padded up to
    a block multiple internally.
    """
    n = x.shape[0]
    # a block of whole (32, 128) tiles: Mosaic refuses a 1-D block that
    # ends inside a tile of the output (e.g. 896 elements of bf16)
    block = min(block, -(-max(n, 1) // _TILE) * _TILE)
    pad = (-n) % block
    if pad:
        x = jnp.pad(x, (0, pad))
    nb = x.shape[0] // block
    out = pl.pallas_call(
        _kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec((block,), lambda i: (i,))],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((x.shape[0],), out_dtype),
        interpret=interpret,
    )(x)
    return out[:n]


def bucket_pack_kernel(leaves, total: int, out_dtype=jnp.float32, *,
                       interpret: bool):
    """Stage a bucket of gradient leaves into one fused f32 buffer."""
    parts = [convert_copy_kernel(l.reshape(-1), out_dtype,
                                 interpret=interpret) for l in leaves]
    buf = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
    if buf.shape[0] < total:
        buf = jnp.pad(buf, (0, total - buf.shape[0]))
    return buf
