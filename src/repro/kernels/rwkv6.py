"""RWKV-6 WKV recurrence Pallas-TPU kernel.

State S is (hd, hd) per (batch, head); the recurrence
    out_t = r_t . (S + u * k_t v_t^T);   S <- diag(w_t) S + k_t v_t^T
is a rank-1 update + vector-matrix product per step.  TPU mapping: keep S
resident in VMEM scratch (hd<=128 -> 64 KiB f32, trivially fits), march over
time chunks so r/k/v/w stream through VMEM once (bandwidth-optimal), with
the per-step rank-1 updates on the VPU (outer products are lane-parallel).

Grid: (B, H, n_time_chunks), time innermost (state persists across chunks).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, state, *, tc: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        state[...] = jnp.zeros_like(state)

    r = r_ref[0, 0].astype(jnp.float32)   # (tc, hd)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    w = w_ref[0, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)      # (hd,)

    def step(t, S):
        kv = k[t][:, None] * v[t][None, :]            # (hd, hd) rank-1
        out = jnp.sum(r[t][:, None] * (S + u[:, None] * kv), axis=0)
        o_ref[0, 0, t, :] = out.astype(o_ref.dtype)
        return w[t][:, None] * S + kv

    state[...] = jax.lax.fori_loop(0, tc, step, state[...])


def rwkv6_wkv_kernel(r, k, v, w, u, *, tc: int = 128, interpret: bool):
    """r,k,v,w: (B, S, H, hd); u: (H, hd) -> out (B, S, H, hd)."""
    B, S, H, hd = r.shape
    tc = min(tc, S)
    assert S % tc == 0
    grid = (B, H, S // tc)
    # (B, H, S, hd) layout: one program owns one (b, h) stream
    rr, kk, vv, ww = (x.transpose(0, 2, 1, 3) for x in (r, k, v, w))
    out = pl.pallas_call(
        functools.partial(_kernel, tc=tc),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, tc, hd), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, 1, tc, hd), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, 1, tc, hd), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, 1, tc, hd), lambda b, h, t: (b, h, t, 0)),
            pl.BlockSpec((1, hd), lambda b, h, t: (h, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, tc, hd), lambda b, h, t: (b, h, t, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), r.dtype),
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=interpret,
    )(rr, kk, vv, ww, u)
    return out.transpose(0, 2, 1, 3)
