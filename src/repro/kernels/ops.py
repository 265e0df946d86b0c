"""Public jit'd wrappers for the Pallas kernels.

The platform picks how a kernel runs.  On an accelerator it compiles to a
Mosaic kernel (``tpu_custom_call`` in the HLO); on the CPU, where Mosaic
cannot lower, it runs in the Pallas interpreter (the kernel body in plain
JAX ops, numerically identical).  There is no switch: tests on the CPU
interpret, runs on the chip compile, and a kernel the chip's compiler
refuses raises.  ``tests/test_tpu_compile.py`` compiles the kernels for a
described TPU without one attached.
"""
from __future__ import annotations

import functools
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_kernel as _splash, splash_attention_mask as _smask)
from jax.sharding import PartitionSpec as P

from . import flash_attention as _fa
from . import rglru as _rg
from . import rwkv6 as _rk
from . import bucket_pack as _bp
from . import fused_grad_sync as _fg
from . import ref as _ref


def _interpret() -> bool:
    """Interpret on the CPU backend, compile everywhere else."""
    return jax.default_backend() == "cpu"


@partial(jax.jit, static_argnames=("causal", "window"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None):
    """GQA flash attention.  q: (B,S,H,hd); k,v: (B,T,KV,hd)."""
    return _fa.flash_attention_kernel(q, k, v, causal=causal, window=window,
                                      interpret=_interpret())


def causal_block(S: int, head_dim: int = 128
                 ) -> Optional[_splash.BlockSizes]:
    """The causal kernel's blocks for sequence length ``S``, or None where
    no block of 128 lanes or more divides it.  One square block for the
    forward and the fused dq/dkv backward: the largest of 1024, 512, 256
    and 128 that divides ``S`` and is at most half of it (128 alone up to
    256 tokens).  Measured on a v5e chip at heads of 64 (PERF.md): 1024 at
    4096 tokens, 512 at 1024; the fused backward beats the separate dq and
    dkv kernels at both.  Heads wider than 128 (MLA's 192) cap the
    backward's block at 512: at 1024 its VMEM stack (18.4 MiB at 192)
    passes the compiler's 16 MiB scoped limit."""
    for blk in (1024, 512, 256, 128):
        if S % blk == 0 and (2 * blk <= S or blk == 128):
            bwd = blk if head_dim <= 128 else min(blk, 512)
            return _splash.BlockSizes(
                block_q=blk, block_kv=blk, block_kv_compute=blk,
                block_q_dkv=bwd, block_kv_dkv=bwd, block_kv_dkv_compute=bwd,
                use_fused_bwd_kernel=True)
    return None


@functools.lru_cache(maxsize=None)
def _causal_kernel(S: int, H: int, sizes: _splash.BlockSizes,
                   interpret: bool):
    """The splash kernel (forward and backward Pallas kernels under one
    ``custom_vjp``) for one static shape; its block masks skip every block
    above the diagonal.  The masks are concrete arrays even when the first
    call comes while a step is traced, so the cache holds no tracer."""
    mask = _smask.MultiHeadMask([_smask.CausalMask((S, S))] * H)
    with jax.ensure_compile_time_eval():
        return _splash.make_splash_mha(mask, block_sizes=sizes,
                                       head_shards=1, q_seq_shards=1,
                                       interpret=interpret)


def causal_attention(q, k, v, sizes: Optional[_splash.BlockSizes] = None):
    """Causal GQA self-attention through the block-skipping splash kernel.
    q: (B,S,H,hd); k,v: (B,S,KV,hd) with H % KV == 0.  ``sizes`` defaults
    to ``causal_block(S, hd)``.  bf16 operands meet the MXU as they are; the
    softmax and the accumulators are f32.

    A Mosaic call cannot be partitioned, so where the ambient mesh leaves
    axes automatic (``model`` inside the trainer's ``shard_map`` over the
    data axes) the kernel runs in a nested ``shard_map`` over them, heads
    split over ``model`` where it divides both head counts."""
    _, S, H, hd = q.shape
    kernel = _causal_kernel(S, H, sizes or causal_block(S, hd), _interpret())
    scale = 1.0 / np.sqrt(hd)

    def run(q, k, v):
        qt = (q.astype(jnp.float32) * scale).astype(q.dtype)
        out = jax.vmap(kernel)(qt.transpose(0, 2, 1, 3),
                               k.transpose(0, 2, 1, 3),
                               v.transpose(0, 2, 1, 3))
        return out.transpose(0, 2, 1, 3)

    mesh = jax.sharding.get_abstract_mesh()
    free = [a for a in mesh.axis_names if a not in mesh.manual_axes]
    if not free:
        return run(q, k, v)
    heads = "model" if ("model" in free and H % mesh.shape["model"] == 0
                        and k.shape[2] % mesh.shape["model"] == 0) else None
    spec = P(None, None, heads, None)
    return jax.shard_map(run, in_specs=(spec,) * 3, out_specs=spec,
                         axis_names=set(free), check_vma=False)(q, k, v)


@jax.jit
def rglru_scan(x, r_gate, i_gate, lam, c: float = 8.0):
    """RG-LRU over (B,S,L): gate math in XLA (fuses), recurrence in the
    kernel."""
    log_a = -c * jax.nn.softplus(lam)[None, None, :] * r_gate
    a = jnp.exp(log_a)
    g = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) * (i_gate * x)
    return _rg.rglru_scan_kernel(a, g, interpret=_interpret())


@jax.jit
def rwkv6_wkv(r, k, v, w, u):
    """WKV-6.  r,k,v,w: (B,S,H,hd); u: (H,hd)."""
    return _rk.rwkv6_wkv_kernel(r, k, v, w, u, interpret=_interpret())


def bucket_pack(leaves, total: int, out_dtype=jnp.float32):
    return _bp.bucket_pack_kernel(leaves, total, out_dtype,
                                  interpret=_interpret())


def fused_pack(leaves, total: int, dp: int, chunks: int = 1):
    """Reduce-scatter-ready chunked staging of a fused bucket (the
    in-kernel compute+comm overlap path's pack half)."""
    return _fg.fused_pack_kernel(leaves, total, dp, chunks,
                                 interpret=_interpret())


def fused_unpack(buf, shapes, dtypes):
    """All-gather epilogue: un-stage the gathered f32 bucket back into
    leaves with the dtype cast fused (the overlap path's unpack half)."""
    return _fg.fused_unpack_kernel(buf, shapes, dtypes,
                                   interpret=_interpret())


# re-exported oracles (tests assert kernel == ref)
flash_attention_ref = _ref.flash_attention_ref
rglru_ref = _ref.rglru_ref
rwkv6_ref = _ref.rwkv6_ref
bucket_pack_ref = _ref.bucket_pack_ref
bucket_unpack_ref = _ref.bucket_unpack_ref
fused_pack_ref = _ref.fused_pack_ref
fused_unpack_ref = _ref.fused_unpack_ref
