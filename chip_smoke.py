#!/usr/bin/env python3
"""Chip smoke test: train qwen2-0.5b at its published widths on a TPU.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # four chips: the gradient-sync phase

One chip: ``repro.launch.train.main`` -- the trainer's own entry point --
runs qwen2-0.5b unreduced (24 layers, d_model 896, vocab 151936) at batch
8 x seq 1024 under ``--strategy auto``: the DisCo search on the traced
step (``repro.plan.compile``), ``Plan.grad_sync``, ``build_train_step`` /
``jit_train_step``, then ten steps.  The script then checks

* every loss is finite;
* step 1's loss matches a plain jitted ``stacked.loss_fn`` on the same
  parameters (re-made from the seed) and batch, within ``LOSS_RTOL``;
* the fused-sync Pallas kernels (``ops.fused_pack`` / ``ops.fused_unpack``),
  compiled for the chip, reproduce ``kernels/ref.py`` exactly at the sizes
  of the model's two largest gradient leaves.  One chip has no data
  parallelism, so the train step never reaches them there.

Four chips (``--chips 4``) run only the multi-chip phase: a ``(4, 1)`` mesh
at global batch 32 x 1024, three gradient-sync strategies from the same
seed, parameters and batches -- the searched Plan, ``size_capped`` DDP
buckets, and the Plan's buckets with ``fused=1`` (the only path on which
the fused-sync kernels run inside the step).  It prints each strategy's
losses, step time and the collectives of its compiled HLO beside its bucket
count, and checks that the losses agree.

The script runs in one process, touches the chip only from it, and exits
non-zero without printing a result when JAX finds no TPU.  Its last line
is one JSON object: ``{"ok": true, "device": {...}}``.
"""
import argparse
import json
import math
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH, SEED = "qwen2-0.5b", 0
# Step 1's loss against the plain reference.  Both programs compute in
# bf16 with f32 accumulation; they differ in remat, fusion and CE chunk
# order, so bf16 intermediates round differently.  One bf16 epsilon
# (2**-7) of relative error on the mean loss is the bound.
LOSS_RTOL = 2.0 ** -7
# Losses of one run under different bucketings.  Step 1 is the same
# forward pass; later steps differ only by the f32 summation order of the
# gradient collectives, so they agree far inside a bf16 epsilon.
SYNC_RTOL = 1e-3


def fail(msg: str):
    print(f"chip_smoke FAILED: {msg}", file=sys.stderr)
    sys.exit(1)


def tpu_devices(count: int):
    """The TPU devices, or exit non-zero when there are none."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU: JAX found {devices[0].platform} devices")
    if len(devices) != count:
        fail(f"this phase needs {count} chip(s), JAX found {len(devices)}")
    return devices


def peak_bytes(device) -> int:
    return int((device.memory_stats() or {}).get("peak_bytes_in_use", -1))


def median_after_first(ms):
    return statistics.median(ms[1:]) if len(ms) > 1 else ms[0]


def reference_loss(cfg, batch, seq, seed=SEED):
    """Plain jitted ``stacked.loss_fn`` on the trainer's step-0 params and
    batch (both re-made from the seed)."""
    import jax

    from repro.data.pipeline import SyntheticLMDataset, materialize_batch
    from repro.launch import train as T
    from repro.models import stacked as ST

    params = T.init_params(cfg, seed)
    ds = SyntheticLMDataset(cfg.vocab, seq, batch, seed=seed)
    b0 = T.step_batch(materialize_batch(cfg, batch, seq, seed=seed), ds, 0,
                      cfg.vocab)
    return float(jax.jit(lambda p, b: ST.loss_fn(p, cfg, b))(params, b0))


def kernel_parity(cfg, seed=SEED, dp=4, chunks=2):
    """``ops.fused_pack`` / ``ops.fused_unpack``, jitted on the default
    backend, against ``ref.py`` on the two largest parameter leaves.
    Returns (max abs error of pack, of unpack and round trip, element
    count)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops as K
    from repro.launch import train as T

    leaves = sorted(jax.tree.leaves(T.init_params(cfg, seed)),
                    key=lambda l: l.size)[-2:]
    total = sum(l.size for l in leaves)
    shapes = [l.shape for l in leaves]
    dtypes = [l.dtype for l in leaves]
    pack = jax.jit(lambda ls: K.fused_pack(ls, total, dp, chunks))
    unpack = jax.jit(lambda b: K.fused_unpack(b, shapes, dtypes))
    got = pack(leaves)
    want = K.fused_pack_ref(leaves, total, dp, chunks)
    err_pack = max(float(jnp.max(jnp.abs(g - w))) for g, w in zip(got, want))
    cuts = [total * c // chunks for c in range(chunks + 1)]
    flat = jnp.concatenate([g[:cuts[c + 1] - cuts[c]]
                            for c, g in enumerate(got)])
    del got, want
    back = unpack(flat)
    want_back = K.fused_unpack_ref(flat, shapes, dtypes)
    err_unpack = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                           - b.astype(jnp.float32))))
                     for a, b in zip(back, want_back))
    roundtrip = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                          - l.astype(jnp.float32))))
                    for a, l in zip(back, leaves))
    return err_pack, max(err_unpack, roundtrip), total


def one_chip(reduced=False, batch=8, seq=1024, steps=10):
    """The one-chip phase; raises on a failed check.  ``reduced`` runs the
    config cut to test size, for a rehearsal on the CPU."""
    from repro.configs import get_config
    from repro.launch import train as T

    argv = ["--arch", ARCH, "--steps", str(steps), "--batch", str(batch),
            "--seq", str(seq), "--strategy", "auto", "--log-every", "1",
            "--seed", str(SEED)]
    rec = T.main(argv + (["--reduced"] if reduced else []))
    cfg = get_config(ARCH)
    cfg = cfg.reduced() if reduced else cfg
    losses = rec["losses"]
    print(f"step ms median {median_after_first(rec['step_ms']):.3f} "
          f"(steps 2..{steps}); all {[round(t, 3) for t in rec['step_ms']]}")
    print(f"losses {losses}")
    if len(losses) != steps or not all(math.isfinite(l) for l in losses):
        raise AssertionError(f"non-finite or missing losses: {losses}")
    ref = reference_loss(cfg, batch, seq)
    rel = abs(losses[0] - ref) / abs(ref)
    print(f"step-1 loss {losses[0]:.6f} vs plain loss_fn {ref:.6f}: "
          f"rel err {rel:.3e} (tol {LOSS_RTOL:.3e})")
    if not rel <= LOSS_RTOL:
        raise AssertionError("step-1 loss does not match the reference")
    err_pack, err_unpack, n = kernel_parity(cfg)
    print(f"kernels fused_pack/fused_unpack on {n} elements: max abs err "
          f"pack {err_pack:.3e} unpack {err_unpack:.3e}")
    if err_pack != 0.0 or err_unpack != 0.0:
        raise AssertionError("fused-sync kernels differ from ref.py")


def four_chip(reduced=False, batch=32, seq=1024, steps=6):
    """The multi-chip phase: three gradient-sync strategies on one data-
    parallel mesh over every device; raises on a failed check."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.data.pipeline import SyntheticLMDataset, materialize_batch
    from repro.distributed.train_step import GradSyncStrategy
    from repro.launch import train as T
    from repro.launch.dryrun import parse_collectives
    from repro.launch.mesh import make_device_mesh
    from repro.optim import adamw, linear_warmup_cosine

    cfg = get_config(ARCH)
    cfg = cfg.reduced() if reduced else cfg
    mesh = make_device_mesh()
    dp = mesh.shape["data"]
    print(f"arch={cfg.name} mesh={dict(mesh.shape)} global batch {batch} x "
          f"seq {seq}")
    ds = SyntheticLMDataset(cfg.vocab, seq, batch, seed=SEED)
    example = materialize_batch(cfg, batch, seq, seed=SEED)
    local = {k: v[:batch // dp] for k, v in example.items()}
    params = T.init_params(cfg, SEED)
    searched, _ = T.choose_strategy("auto", cfg, params, local, dp)
    strategies = {
        "searched": searched,
        "size_capped": GradSyncStrategy.size_capped(params),
        "fused": GradSyncStrategy.from_buckets(
            searched.buckets, searched.comms, searched.chunks,
            params=params, fused=[1] * len(searched.buckets)),
    }
    del params
    optimizer = adamw(linear_warmup_cosine(1e-3, warmup=20,
                                           total_steps=steps),
                      weight_decay=0.01)
    losses_of = {}
    for name, strat in strategies.items():
        params = T.init_params(cfg, SEED)
        opt = optimizer[0](jax.tree.map(lambda p: p.astype(jnp.float32),
                                        params))
        step, compile_s = T.compile_step(cfg, mesh, strat, params, opt,
                                         example, optimizer)
        coll = {op: d["count"] for op, d in
                parse_collectives(step.as_text())["per_op"].items()}
        losses, ms = [], []
        for i in range(steps):
            b = T.step_batch(example, ds, i, cfg.vocab)
            t0 = time.perf_counter()
            params, opt, m = jax.block_until_ready(step(params, opt, b))
            ms.append((time.perf_counter() - t0) * 1e3)
            losses.append(float(m["loss"]))
        del params, opt
        losses_of[name] = losses
        print(f"[{name}] buckets {len(strat.buckets)} (fused "
              f"{sum(strat.fused or [])}, chunks {strat.chunks}"
              f"); compiled collectives {coll}; compile seconds "
              f"{compile_s:.3f}; step ms median "
              f"{median_after_first(ms):.3f}; losses {losses}")
    base = np.asarray(losses_of["searched"])
    for name, losses in losses_of.items():
        got = np.asarray(losses)
        if not np.all(np.isfinite(got)):
            raise AssertionError(f"[{name}] non-finite losses {losses}")
        rel = float(np.max(np.abs(got - base) / np.abs(base)))
        print(f"[{name}] max rel loss diff vs searched {rel:.3e} "
              f"(tol {SYNC_RTOL:.0e})")
        if not rel <= SYNC_RTOL:
            raise AssertionError(f"[{name}] losses disagree with searched")
    print(f"peak bytes in use per chip "
          f"{[peak_bytes(d) for d in jax.devices()]}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the four-chip gradient-sync phase")
    args = ap.parse_args(argv)
    devices = tpu_devices(args.chips)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch import train as T

    print(f"compile cache {T.use_compile_cache()}")
    t0 = time.perf_counter()
    if args.chips == 1:
        one_chip()
    else:
        four_chip()
    print(f"peak bytes in use {peak_bytes(devices[0])}")
    print(f"phase seconds {time.perf_counter() - t0:.3f}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
