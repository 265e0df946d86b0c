"""Flash attention Pallas-TPU kernel (GQA / causal / sliding-window).

Grid: (B, KV, n_q_blocks, n_kv_blocks), KV-block axis innermost so the
online-softmax state (m, l, acc) persists in VMEM scratch across KV steps
for one query block.  Each program instance covers all G = H/KV query heads
of one KV head — GQA reads each K/V block once per group, the kernel-level
arithmetic-intensity win over head-replicated attention.

Block sizes default to (128, 128): MXU-aligned (multiples of 128) and a
VMEM working set of G*qb*hd + 2*kb*hd + G*qb*kb floats — well under the
128 MiB v5e VMEM for hd <= 256, G <= 8.

Causal/window structure skips fully-masked KV blocks via ``pl.when`` (the
roofline-visible FLOP saving the XLA reference path does not get).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float(np.finfo(np.float32).min)


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: Optional[int],
            qb: int, kb: int, nk: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * qb
    k_start = ki * kb
    # block-level structural skip: block fully above the diagonal, or fully
    # outside the sliding window
    live = True
    if causal:
        live = k_start <= q_start + qb - 1
    if window is not None:
        live = jnp.logical_and(live, k_start + kb - 1 > q_start - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # (G, qb, hd)
        k = k_ref[0, 0].astype(jnp.float32)            # (kb, hd)
        v = v_ref[0, 0].astype(jnp.float32)            # (kb, hd)
        s = jax.lax.dot_general(q, k, (((2,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale                                   # (G, qb, kb)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (qb, kb), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (qb, kb), 1)
        ok = kpos <= qpos if causal else jnp.ones((qb, kb), bool)
        if window is not None:
            ok = jnp.logical_and(ok, kpos > qpos - window)
        s = jnp.where(ok[None], s, NEG_INF)
        m_prev = m_scr[...]                             # (G, qb)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1)
        pv = jax.lax.dot_general(p, v, (((2,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * corr[..., None] + pv
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[..., None]).astype(o_ref.dtype)


def flash_attention_kernel(q, k, v, *, causal: bool = True,
                           window: Optional[int] = None,
                           qb: int = 128, kb: int = 128,
                           interpret: bool):
    """q: (B, S, H, hd); k, v: (B, T, KV, hd) -> (B, S, H, hd)."""
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    qb = min(qb, S)
    kb = min(kb, T)
    assert S % qb == 0 and T % kb == 0
    nq, nk = S // qb, T // kb
    # (B, KV, G, S, hd) layout so one program sees one (b, kv) slice
    qr = q.reshape(B, S, KV, G, hd).transpose(0, 2, 3, 1, 4)
    kr = k.transpose(0, 2, 1, 3)                        # (B, KV, T, hd)
    vr = v.transpose(0, 2, 1, 3)
    grid = (B, KV, nq, nk)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=1.0 / np.sqrt(hd), causal=causal,
                          window=window, qb=qb, kb=kb, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, G, qb, hd), lambda b, h, i, j: (b, h, 0, i, 0)),
            pl.BlockSpec((1, 1, kb, hd), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, kb, hd), lambda b, h, i, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, qb, hd),
                               lambda b, h, i, j: (b, h, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, qb), jnp.float32),        # m
            pltpu.VMEM((G, qb), jnp.float32),        # l
            pltpu.VMEM((G, qb, hd), jnp.float32),    # acc
        ],
        interpret=interpret,
    )(qr, kr, vr)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, hd)
