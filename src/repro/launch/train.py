"""End-to-end training driver, on whatever devices this process sees.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \
        --steps 10 --batch 8 --seq 1024 --strategy auto

Pipeline: synthetic data -> (optional) DisCo strategy search on the traced
step -> DisCo-enacted distributed train step (bucketed gradient sync) ->
checkpoints.  The mesh is ``(data, model)`` over ``jax.devices()``: every
device on ``data`` unless ``--model`` asks for tensor parallelism.  On a
TPU the configs run at their published widths (``chip_smoke.py`` drives
qwen2-0.5b this way); on the CPU pass ``--reduced`` for a config cut to
test size, and set ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
from outside for ``N`` host devices.

Compiled programs are cached in ``$JAX_COMPILATION_CACHE_DIR`` when it is
set, and in ``<checkout>/.jax_cache`` otherwise (:func:`use_compile_cache`).
"""
import argparse
import os
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from .. import plan as RP
from ..checkpoint import restore_checkpoint, save_checkpoint
from ..configs import get_config
from ..core import profile_graph, trace_grad_graph
from ..data.pipeline import SyntheticLMDataset, materialize_batch
from ..distributed.train_step import (GradSyncStrategy, build_train_step,
                                      jit_train_step)
from ..models import stacked as ST
from ..optim import adamw, linear_warmup_cosine
from .mesh import make_device_mesh

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    ``$JAX_COMPILATION_CACHE_DIR``, where set, is read by JAX itself and
    nothing else is set.  Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache``: the directory is part of the cache key, so
    a path that moved between runs would never hit.  Entry points call
    this; library imports never do."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def init_params(cfg, seed: int):
    """The model's parameters from ``seed``, initialised on the device in
    one compiled program (the same values on every call)."""
    with obs.span("train.init_params"):
        return jax.jit(ST.init_params, static_argnums=1)(
            jax.random.PRNGKey(seed), cfg)


def search_strategy(cfg, params, batch, n_devices: int,
                    unchanged_limit: int = 80, seed: int = 0, cluster=None):
    """Trace the step on the per-device training batch and run the DisCo
    search through the ``repro.plan.compile`` facade.  ``cluster`` (a
    preset name or ClusterSpec) prices collectives on that topology;
    default is the legacy flat model.  Returns (strategy, Plan)."""
    def loss(p, bt):
        return ST.loss_fn(p, cfg, bt)

    with obs.span("plan.trace"):
        g = trace_grad_graph(loss, params, batch)
    with obs.span("plan.profile"):
        g = profile_graph(g)
    with obs.span("plan.search"):
        plan = RP.compile(graph=g, cluster=cluster, n_devices=n_devices,
                          unchanged_limit=unchanged_limit, seed=seed)
    return plan.grad_sync(params), plan


def choose_strategy(kind: str, cfg, params, local_batch, dp: int, *,
                    cluster=None, strategy_file=None):
    """The gradient-sync strategy a run enacts; returns (strategy, Plan or
    None).  ``kind`` is ``auto`` (DisCo search), ``ddp`` (25 MiB size-capped
    buckets), ``single-bucket`` or ``per-tensor``."""
    if strategy_file:
        # Plan.load reads both repro.plan artifacts and legacy
        # strategy.json files (migration shim)
        plan = RP.Plan.load(strategy_file)
        strat = plan.grad_sync(params)
        print(f"loaded strategy: {len(strat.buckets)} buckets")
        return strat, plan
    if kind == "auto":
        with obs.span("train.choose_strategy") as sp:
            strat, plan = search_strategy(cfg, params, local_batch,
                                          n_devices=dp, cluster=cluster)
        prov = plan.provenance
        print(f"DisCo search: {prov['initial_cost'] * 1e6:.1f} -> "
              f"{prov['best_cost'] * 1e6:.1f} us simulated; "
              f"plan buckets {len(strat.buckets)} over "
              f"{prov['grad_tensors']} gradient tensors; search "
              f"simulations {prov['simulations']}; search seconds "
              f"{sp.seconds:.3f}")
        return strat, plan
    if kind == "ddp":
        return GradSyncStrategy.size_capped(params), None
    if kind == "single-bucket":
        return GradSyncStrategy.single_bucket(params), None
    if kind == "per-tensor":
        return GradSyncStrategy.per_tensor(params), None
    raise ValueError(f"unknown strategy {kind!r}")


def compile_step(cfg, mesh, strat, params, opt, batch, optimizer):
    """Build, jit and AOT-compile the ``ddp_tp`` train step for ``batch``'s
    shapes.  Returns (compiled step, seconds of its lowering and
    compile)."""
    step_fn = build_train_step(cfg, mesh, mode="ddp_tp", strategy=strat,
                               optimizer=optimizer, remat=True)
    specs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
             for k, v in batch.items()}
    jf = jit_train_step(step_fn, cfg, mesh, params, opt, specs)
    with obs.span("train.lower") as lo:
        lowered = jf.lower(params, opt, specs)
    with obs.span("train.compile") as co:
        compiled = lowered.compile()
    return compiled, lo.seconds + co.seconds


def step_batch(example: dict, ds: SyntheticLMDataset, step: int,
               vocab: int) -> dict:
    """The global batch of ``step``: fresh synthetic tokens, the rest of
    ``example`` (stubbed modality inputs) unchanged, on the device."""
    with obs.span("train.step_batch"):
        batch = dict(example)
        batch["tokens"] = jnp.asarray(ds.global_step_batch(step) % vocab)
        return batch


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--reduced", action="store_true",
                    help="the config cut to test size (CPU runs)")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=16, help="global batch")
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--model", type=int, default=1,
                    help="tensor-parallel degree; the other devices are "
                         "data parallel")
    ap.add_argument("--strategy", default="auto",
                    choices=["auto", "per-tensor", "ddp", "single-bucket"],
                    help="auto = DisCo backtracking search")
    ap.add_argument("--strategy-file", default=None,
                    help="enact a saved repro.plan artifact (or a legacy "
                         "strategy.json) instead of searching")
    from ..cluster import list_presets

    ap.add_argument("--cluster", default=None, choices=list_presets(),
                    help="cluster preset the strategy search prices "
                         "collectives on")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--log-every", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def main(argv=None) -> dict:
    """Run the driver; returns its ``losses`` and per-step ``step_ms``
    (the ``train.dispatch`` and ``train.wait`` spans of the step, which
    ends in ``block_until_ready``).

    Each step runs under ``StepTraceAnnotation("train", step_num=i)`` and
    the spans ``train.step_batch``, ``train.dispatch``, ``train.wait`` and
    ``train.loss`` (``repro.obs``).  Every ``--log-every`` steps the log
    line gives, besides loss and step time, each span's mean milliseconds
    and calls and the ``obs`` counters (compilation cache hits and misses)
    since the last line, the first line since the start, set-up included:
    the operator's view of where the host's time went.  For a timeline,
    run under ``jax.profiler``: the spans land on its host plane, on the
    device ops' clock."""
    args = parse_args(argv)
    last = obs.snapshot()
    use_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    mesh = make_device_mesh(model=args.model)
    dp = mesh.shape["data"]
    if args.batch % dp:
        raise ValueError(f"--batch {args.batch} does not split over "
                         f"{dp} data-parallel devices")

    params = init_params(cfg, args.seed)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"arch={cfg.name} reduced={args.reduced} "
          f"params={n_params / 1e6:.2f}M d_model={cfg.d_model} "
          f"layers={cfg.n_layers} vocab={cfg.vocab} mesh={dict(mesh.shape)} "
          f"devices={jax.devices()[0].platform}x{len(jax.devices())}")

    sched = linear_warmup_cosine(args.lr, warmup=20, total_steps=args.steps)
    optimizer = adamw(sched, weight_decay=0.01)
    opt = optimizer[0](jax.tree.map(lambda p: p.astype(jnp.float32), params))

    ds = SyntheticLMDataset(cfg.vocab, args.seq, args.batch, seed=args.seed)
    example = materialize_batch(cfg, args.batch, args.seq, seed=args.seed)
    local = {k: v[:args.batch // dp] for k, v in example.items()}
    strat, _ = choose_strategy(args.strategy, cfg, params, local, dp,
                               cluster=args.cluster,
                               strategy_file=args.strategy_file)
    step, compile_s = compile_step(cfg, mesh, strat, params, opt, example,
                                   optimizer)
    print(f"compile seconds {compile_s:.3f}")

    start = 0
    if args.ckpt_dir:
        try:
            (params, opt), start = restore_checkpoint(
                args.ckpt_dir, (params, opt))
            print(f"resumed from step {start}")
        except FileNotFoundError:
            pass

    losses, step_ms = [], []
    for i in range(start, args.steps):
        with jax.profiler.StepTraceAnnotation("train", step_num=i):
            batch = step_batch(example, ds, i, cfg.vocab)
            with obs.span("train.dispatch") as sent:
                out = step(params, opt, batch)
            with obs.span("train.wait") as waited:
                params, opt, metrics = jax.block_until_ready(out)
            with obs.span("train.loss"):
                losses.append(float(metrics["loss"]))
        step_ms.append((sent.seconds + waited.seconds) * 1e3)
        if i % args.log_every == 0 or i == args.steps - 1:
            now = obs.snapshot()
            print(f"step {i:5d}  loss {losses[-1]:.4f}  "
                  f"gnorm {float(metrics['grad_norm']):.3f}  "
                  f"{step_ms[-1]:.1f} ms/step  | "
                  f"{obs.line(obs.delta(now, last))}")
            last = now
        if args.ckpt_dir and i > start and i % args.ckpt_every == 0:
            save_checkpoint(args.ckpt_dir, i, (params, opt))
    if args.ckpt_dir:
        save_checkpoint(args.ckpt_dir, args.steps, (params, opt))
    if losses:
        first = np.mean(losses[:10])
        last = np.mean(losses[-10:])
        print(f"loss: first10 {first:.4f} -> last10 {last:.4f} "
              f"({'improved' if last < first else 'NOT improved'})")
    return {"losses": losses, "step_ms": step_ms}


if __name__ == "__main__":
    main()
