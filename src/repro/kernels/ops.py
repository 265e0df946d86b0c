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

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

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
