"""Block-size sweep of the causal splash kernel on the chip.

For each benchmark cell's per-chip attention shape, ``--stage blocks``
times the forward of ``kernels.ops.causal_attention`` over query and KV
blocks, then forward plus backward over the dq and dkv blocks (separate
and fused backward) with the best forward blocks, beside the XLA path
``sdpa`` takes on the CPU (dense below 2048 tokens, the ``_flash_xla``
scan above).  ``--stage layouts`` keeps the blocks of
``ops.causal_block`` and varies the operands' layouts and the KV compute
sub-blocks.  Each line of the output file is one timing in milliseconds
per call.

    python3 benchmarks/attention_blocks.py [--stage blocks|layouts]
        [--out chiprun_out/attn_blocks.jsonl]

Needs a TPU; one process, one chip.
"""
from __future__ import annotations

import argparse
import dataclasses
import itertools
import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

from jax.experimental.pallas.ops.tpu.splash_attention import (  # noqa: E402
    BlockSizes, QKVLayout)

from repro.kernels import ops as K  # noqa: E402
from repro.models import layers as L  # noqa: E402

# (name, B, S, H, KV, hd): each cell's attention on one chip
SHAPES = [("stablelm-1.6b-l8.s4096", 2, 4096, 32, 32, 64),
          ("qwen2-0.5b.s1024", 8, 1024, 14, 2, 64)]
SIZES = (128, 256, 512, 1024)


def timed(fn, *args, reps: int = 10) -> float:
    """Mean milliseconds of one call, compiled and warmed first."""
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e3


def fwd_bwd(attn):
    def loss(q, k, v, ct):
        return jnp.sum(attn(q, k, v).astype(jnp.float32) * ct)
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))


def xla_path(q, k, v):
    S = q.shape[1]
    if S > L._CHUNK_THRESHOLD:
        return L._flash_xla(q, k, v, True, None)
    return L._sdpa_dense(q, k, v,
                         L._causal_bias(S, S, True, None)[None, None, None])


def operands(B, S, H, KV, hd):
    key = jax.random.split(jax.random.PRNGKey(0), 4)
    return (jax.random.normal(key[0], (B, S, H, hd), jnp.bfloat16),
            jax.random.normal(key[1], (B, S, KV, hd), jnp.bfloat16),
            jax.random.normal(key[2], (B, S, KV, hd), jnp.bfloat16),
            jax.random.normal(key[3], (B, S, H, hd), jnp.float32))


def sweep_layouts(name, B, S, H, KV, hd, emit):
    q, k, v, ct = operands(B, S, H, KV, hd)
    rule = K.causal_block(S)
    seq, head = QKVLayout.SEQ_MINOR, QKVLayout.HEAD_DIM_MINOR
    layouts = {"head_minor": (head, head, head),
               "k_seq_minor": (head, seq, head),
               "qk_seq_minor": (seq, seq, head),
               "kv_seq_minor": (head, seq, seq)}
    for (lname, (ql, kl, vl)), div in itertools.product(layouts.items(),
                                                        (1, 2, 4)):
        blk = rule.block_kv // div
        if blk < 128:
            continue
        bs = dataclasses.replace(rule, block_kv_compute=blk,
                                 block_kv_dkv_compute=blk, q_layout=ql,
                                 k_layout=kl, v_layout=vl)
        fn = fwd_bwd(lambda q, k, v, bs=bs: K.causal_attention(q, k, v, bs))
        blocks = [rule.block_q, rule.block_kv, blk, lname]
        try:
            emit(name, "splash", "fwd_bwd", blocks, timed(fn, q, k, v, ct))
        except Exception as e:
            emit(name, "splash", "fwd_bwd", blocks, None, repr(e)[:200])


def sweep_blocks(name, B, S, H, KV, hd, emit):
    q, k, v, ct = operands(B, S, H, KV, hd)
    sizes = [s for s in SIZES if s <= S]

    want = jax.jit(xla_path)(q, k, v)
    emit(name, "xla", "fwd", None, timed(jax.jit(xla_path), q, k, v))
    emit(name, "xla", "fwd_bwd", None, timed(fwd_bwd(xla_path), q, k, v, ct))

    best = None
    for bq, bkv in itertools.product(sizes, sizes):
        bs = BlockSizes(block_q=bq, block_kv=bkv, block_kv_compute=bkv)
        fn = jax.jit(lambda q, k, v, bs=bs: K.causal_attention(q, k, v, bs))
        try:
            ms = timed(fn, q, k, v)
            err = float(jnp.max(jnp.abs(fn(q, k, v).astype(jnp.float32)
                                        - want.astype(jnp.float32))))
        except Exception as e:  # a block the compiler refuses
            emit(name, "splash", "fwd", [bq, bkv], None, repr(e)[:200])
            continue
        emit(name, "splash", "fwd", [bq, bkv], ms, f"max_abs_err {err:.3g}")
        if best is None or ms < best[0]:
            best = (ms, bq, bkv)
    _, bq, bkv = best

    for fused, bq_b, bkv_b in itertools.product((False, True), sizes, sizes):
        dq = {} if fused else {"block_q_dq": bq_b, "block_kv_dq": bkv_b}
        bs = BlockSizes(block_q=bq, block_kv=bkv, block_kv_compute=bkv,
                        block_q_dkv=bq_b, block_kv_dkv=bkv_b,
                        block_kv_dkv_compute=bkv_b,
                        use_fused_bwd_kernel=fused, **dq)
        fn = fwd_bwd(lambda q, k, v, bs=bs: K.causal_attention(q, k, v, bs))
        blocks = [bq, bkv, bq_b, bkv_b, "fused" if fused else "dq+dkv"]
        try:
            emit(name, "splash", "fwd_bwd", blocks, timed(fn, q, k, v, ct))
        except Exception as e:
            emit(name, "splash", "fwd_bwd", blocks, None, repr(e)[:200])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--stage", choices=("blocks", "layouts"),
                    default="blocks")
    ap.add_argument("--out", default="chiprun_out/attn_blocks.jsonl")
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 1
    out = pathlib.Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    dev = jax.devices()[0]
    with out.open("w") as f:
        def emit(shape, path, what, blocks, ms, note=""):
            row = {"shape": shape, "path": path, "what": what,
                   "blocks": blocks, "ms": ms, "note": note,
                   "device": dev.device_kind}
            f.write(json.dumps(row) + "\n")
            f.flush()
            print(json.dumps(row), flush=True)

        for shape in SHAPES:
            (sweep_blocks if args.stage == "blocks" else sweep_layouts)(
                *shape, emit)
    return 0


if __name__ == "__main__":
    sys.exit(main())
