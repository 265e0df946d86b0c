"""One run of one cell: set up the trainer through ``launch/train.py``, drive
its compiled step over the checked steps and then a timed window, read the
device, and decide ``correct`` against the plain reference.

``run_cell`` takes the devices it runs on, so that a test can hand it the
CPU; ``accelerators`` is the look for a chip that the command makes first.
"""
from __future__ import annotations

import contextlib
import gc
import math
import shutil
import sys
import tempfile
import time

import numpy as np

from . import check, flops, reference, spec, trace
from .traffic import TokenPool

SPANS = ("bench.step_batch", "bench.dispatch", "bench.block", "bench.loss")


def log(*args):
    print(*args, file=sys.stderr, flush=True)


def accelerators(chips: int):
    """The first ``chips`` TPU devices; exits non-zero when JAX finds no
    TPU or fewer chips."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {devices[0].platform} devices")
    if len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX found "
                         f"{len(devices)}")
    return devices[:chips]


def _leaf_norms(tree):
    import jax
    import jax.numpy as jnp

    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


def _change_norms(a, b):
    import jax

    return reference.channel_norms(jax.tree.map(
        lambda x, y: x.astype("float32") - y.astype("float32"), a, b))


def _loop(T, step, params, opt, example, pool, vocab, first, seconds,
          max_steps, traced):
    """The window: the trainer's step loop (``train.main``'s), each step
    ending in ``block_until_ready`` and a read of the loss."""
    import jax

    span = jax.profiler.TraceAnnotation if traced else \
        (lambda _: contextlib.nullcontext())
    losses = []
    i = first
    t0 = time.perf_counter()
    while True:
        with span(SPANS[0]):
            batch = T.step_batch(example, pool, i, vocab)
        with span(SPANS[1]):
            out = step(params, opt, batch)
        with span(SPANS[2]):
            params, opt, metrics = jax.block_until_ready(out)
        with span(SPANS[3]):
            losses.append(float(metrics["loss"]))
        i += 1
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds or len(losses) == max_steps:
            return params, opt, losses, elapsed


def set_up(cell: dict, seed: int, devices) -> dict:
    """Set-up through the trainer's own functions, then the checked steps.

    Returns the state the window continues from (``step``, ``params``,
    ``opt``, ``example``, ``pool``, ``T``), the record ``rec`` of set-up
    times, and ``prog``: the checked steps' losses, the per-leaf norms of
    step 1's gradient as the optimizer received it (``grad1``, from its
    first moment) and the per-channel norms of the weights' change over the
    checked steps as the next step receives them (``change``)."""
    import jax
    import jax.numpy as jnp

    from repro.distributed import train_step as TS
    from repro.launch import train as T
    from repro.launch.dryrun import parse_collectives
    from repro.launch.mesh import make_mesh
    from repro.optim import adamw, linear_warmup_cosine

    job = cell["traffic"]
    oc = job["optimizer"]
    chips = cell["chips"]
    if oc["clip_norm"] != _default(TS.build_train_step, "clip_norm"):
        raise ValueError("the job's clip_norm is not the trainer's")
    rec = {"chips": chips}
    log(f"compile cache {T.use_compile_cache()}")
    cfg = spec.model_config(cell["config"]["model"])
    mesh = make_mesh((chips, 1), ("data", "model"), devices=devices)
    params = T.init_params(cfg, seed)
    names = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(params)[0]]
    optimizer = adamw(
        linear_warmup_cosine(oc["lr"], warmup=oc["warmup"],
                             total_steps=oc["total_steps"],
                             final_frac=oc["final_frac"]),
        b1=oc["b1"], b2=oc["b2"], eps=oc["eps"],
        weight_decay=oc["weight_decay"])
    opt = optimizer[0](jax.tree.map(lambda p: p.astype(jnp.float32), params))
    pool = TokenPool(job, cfg.vocab, job["batch_per_chip"] * chips, seed)
    example = {"tokens": jnp.asarray(pool.global_step_batch(0) % cfg.vocab)}
    local = {"tokens": example["tokens"][:job["batch_per_chip"]]}
    t0 = time.perf_counter()
    strat, plan = T.choose_strategy(job["strategy"], cfg, params, local,
                                    chips)
    rec["search_s"] = time.perf_counter() - t0
    if plan is not None:
        rec["plan_step_s"] = float(plan.provenance["best_cost"])
    t0 = time.perf_counter()
    step, _ = T.compile_step(cfg, mesh, strat, params, opt, example,
                             optimizer)
    rec["compile_s"] = time.perf_counter() - t0
    coll = parse_collectives(step.as_text())["per_op"]
    log(f"buckets {len(strat.buckets)}; compiled collectives "
        f"{ {k: v['count'] for k, v in coll.items()} }; bytes "
        f"{ {k: v['bytes'] for k, v in coll.items()} }")

    # the checked steps: the window's own call and feed, from the seed
    prog = {"loss": []}
    for i in range(job["checked_steps"]):
        b = T.step_batch(example, pool, i, cfg.vocab)
        params, opt, metrics = jax.block_until_ready(step(params, opt, b))
        prog["loss"].append(float(metrics["loss"]))
        if i == 0:
            prog["grad1"] = np.asarray(jax.jit(_leaf_norms)(opt.mu)) \
                / (1 - oc["b1"])
    prog["change"] = [np.asarray(c) for c in jax.jit(_change_norms)(
        params, T.init_params(cfg, seed))]
    return {"T": T, "cfg": cfg, "step": step, "params": params, "opt": opt,
            "example": example, "pool": pool, "rec": rec, "prog": prog,
            "names": names}


def drive(st: dict, cell: dict, seconds: float, traced: bool) -> list:
    """The window, on the state ``set_up`` left; returns its losses and
    records its length (and with ``traced`` the reduced trace)."""
    import jax

    job = cell["traffic"]
    args = (st["T"], st["step"], st["params"], st["opt"], st["example"],
            st["pool"], st["cfg"].vocab, job["checked_steps"], seconds)
    rec = st["rec"]
    if traced:
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        try:
            jax.profiler.start_trace(tdir)
            out = _loop(*args, job["trace_steps"], True)
            jax.profiler.stop_trace()
            loaded = trace.load(tdir)
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        log(f"trace planes {loaded['layout']}")
        rec["trace"] = trace.reduce(loaded["devices"], loaded["spans"]) \
            if loaded["devices"] else {}
    else:
        out = _loop(*args, None, False)
    st["params"], st["opt"], losses, elapsed = out
    rec["window"] = {"steps": len(losses), "seconds": elapsed,
                     "tokens": len(losses) * job["batch_per_chip"]
                     * cell["chips"] * job["seq_len"]}
    log(f"window {len(losses)} steps in {elapsed:.3f} s; losses "
        f"{losses[0]:.4f} .. {losses[-1]:.4f}")
    return losses


def reference_run(cell: dict, seed: int, pool, devices, rows=None,
                  numerics: str = "f32") -> dict:
    """The cell's reference family over the checked steps' batches (the
    first ``rows`` rows of each, where given), on the cell's devices."""
    job, m = cell["traffic"], cell["config"]["model"]
    batches = [pool.global_step_batch(i)[:rows] % m["vocab"]
               for i in range(job["checked_steps"])]
    t0 = time.perf_counter()
    ref = reference.train(cell["reference"], m, job["optimizer"], seed,
                          batches, job["reference_rows_per_block"], numerics,
                          devices)
    log(f"reference ({numerics}, rows {rows}) "
        f"{time.perf_counter() - t0:.3f} s, steps {ref['seconds']}; losses "
        f"{ref['loss']}; grad norms {ref['grad_norm']}")
    return ref


def compare(cell: dict, names: list, got: dict, ref: dict) -> dict:
    """``check.readings`` where the parameter trees agree leaf for leaf."""
    if names != reference.leaf_names(cell["reference"],
                                     cell["config"]["model"]):
        return {k: (math.inf, "leaves differ") for k in check.NUMBERS}
    keep = check.moving_channels(ref)
    pairs = {"grad1": (got["grad1"], ref["grad1"]),
             "change": (check.masked_norms(got["change"], keep),
                        check.masked_norms(ref["change"], keep))}
    for key, (p, r) in pairs.items():
        gap = check.leaf_gaps(p, r) if key == "grad1" \
            else check.own_gaps(p, r)
        worst = np.argsort(-gap)[:4]
        log(f"{key} worst leaves: " + "; ".join(
            f"{names[i]} {p[i]:.6g} vs {r[i]:.6g}" for i in worst))
    log("change: channels left out: " + "; ".join(
        f"{n} {int((~k).sum())} of {k.size}" for n, k in zip(names, keep)
        if not k.all()))
    return check.readings(got, ref, names)


def run_cell(cell: dict, seed: int, seconds: float, traced: bool, devices,
             t_start: float) -> dict:
    """Run the cell once on ``devices``; return the result line as a
    dict.  ``t_start`` is the process's start on the ``perf_counter``
    clock."""
    st = set_up(cell, seed, devices)
    rec, prog, pool, names = st["rec"], st["prog"], st["pool"], st["names"]
    rec["setup_s"] = time.perf_counter() - t_start
    log(f"setup_s {rec['setup_s']:.3f} search_s {rec['search_s']:.3f} "
        f"compile_s {rec['compile_s']:.3f} checked losses {prog['loss']}")
    losses = drive(st, cell, seconds, traced)
    stats = [d.memory_stats() or {} for d in devices]
    rec["peak_hbm_bytes"] = max(s.get("peak_bytes_in_use", 0) for s in stats)
    kind = devices[0].device_kind
    rec["flops_per_token"] = cell["reference"].flops_per_token(
        cell["config"]["model"], cell["traffic"]["seq_len"])
    if devices[0].platform == "tpu":
        rec["peak_flops_per_s"] = flops.peaks(kind)["bf16_flops_per_s"]

    st.clear()      # the program's state goes before the reference runs
    gc.collect()
    ref = reference_run(cell, seed, pool, devices)
    ok, lines = check.verdict(compare(cell, names, prog, ref), cell["limits"])
    failed = sum(1 for x in losses if not math.isfinite(x))

    entries = cell["per_layer"] if traced else cell["end_to_end"]
    metrics = {}
    for e in entries:
        value = spec.metric_reader(cell["bench_dir"], e["name"])(rec)
        if value is not None:
            metrics[e["name"]] = {"value": value, "unit": e["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": rec["peak_hbm_bytes"]}
    result = {"correct": ok and failed == 0, "attempted": len(losses),
              "failed": failed, "metrics": metrics, "device": device}
    if traced and rec["trace"]:
        device["busy_s"] = rec["trace"]["busy_s"]
        device["window_s"] = rec["trace"]["window_s"]
        result["breakdown"] = {"device_ops": rec["trace"]["device_ops"],
                               "idle_gaps": rec["trace"]["idle_gaps"]}
    result["checks"] = {x["name"]: {"value": x["value"], "limit": x["limit"]}
                        for x in lines}
    for x in lines:
        log(f"{x['name']} {x['value']!r} limit {x['limit']!r} at {x['at']} "
            f"{'ok' if x['ok'] else 'FAILED'}")
    return result


def _default(fn, name):
    import inspect

    return inspect.signature(fn).parameters[name].default
