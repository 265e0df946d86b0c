"""RG-LRU recurrence Pallas-TPU kernel.

The recurrence h_t = a_t * h_{t-1} + g_t is elementwise over the LRU width,
so the natural TPU mapping is: tile the width across the lane dimension
(blocks of 128 lanes x 8 sublanes) and keep the running state h in VMEM
scratch while marching over time chunks — one HBM read of (a, g) and one
write of h per element, with the sequential dependence handled by a
``fori_loop`` inside the kernel (VPU latency-bound, bandwidth-optimal).

Grid: (B, n_width_blocks, n_time_chunks), time innermost (state persists).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, g_ref, h_ref, state, *, tc: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        state[...] = jnp.zeros_like(state)

    a = a_ref[0].astype(jnp.float32)     # (tc, Lb)
    g = g_ref[0].astype(jnp.float32)

    def step(t, h):
        h = a[t] * h + g[t]
        h_ref[0, t, :] = h.astype(h_ref.dtype)
        return h

    state[...] = jax.lax.fori_loop(0, tc, step, state[...])


def rglru_scan_kernel(a, g, *, tc: int = 128, lb: int = 512,
                      interpret: bool):
    """a, g: (B, S, L) decay and gated input; returns h: (B, S, L).

    h_t = a_t * h_{t-1} + g_t  (the caller precomputes a = exp(log_a) and
    g = sqrt(1-a^2) * i * x; those are elementwise and fuse in XLA).
    """
    B, S, L = a.shape
    tc = min(tc, S)
    lb = min(lb, L)
    assert S % tc == 0 and L % lb == 0
    grid = (B, L // lb, S // tc)
    return pl.pallas_call(
        functools.partial(_kernel, tc=tc),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tc, lb), lambda b, l, t: (b, t, l)),
            pl.BlockSpec((1, tc, lb), lambda b, l, t: (b, t, l)),
        ],
        out_specs=pl.BlockSpec((1, tc, lb), lambda b, l, t: (b, t, l)),
        out_shape=jax.ShapeDtypeStruct((B, S, L), a.dtype),
        scratch_shapes=[pltpu.VMEM((lb,), jnp.float32)],
        interpret=interpret,
    )(a, g)
