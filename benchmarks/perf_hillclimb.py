"""Perf hillclimbing (deliverable g, Sec. Perf of EXPERIMENTS.md).

Three pairs, chosen from the baseline roofline table:
  H1 qwen2-0.5b x train_4k      — most collective-bound (ratio 2.5x)
  H2 deepseek-v2-lite x train_4k — most representative of the paper (DisCo
                                    bucket enactment on the MoE training step)
  H3 stablelm-1.6b x decode_32k  — most memory-bound (ratio 156x)

Each iteration is run in a subprocess (XLA:CPU crash isolation) and records
hypothesis / change / before / after into experiments/perf/<id>.json.

    PYTHONPATH=src python benchmarks/perf_hillclimb.py [--only H1]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

OUT = "experiments/perf"

_COMMON = r"""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=512'
import sys, json, dataclasses
sys.path.insert(0, 'src')
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.core.analytic import shape_cost
from repro.core.hw import TPU_V5E
from repro.distributed import sharding as SH
from repro.distributed.train_step import build_train_step, jit_train_step, GradSyncStrategy
from repro.launch.dryrun import parse_collectives, build_dryrun_decode
from repro.launch.mesh import make_production_mesh
from repro.launch.shapes import input_specs, GRAD_ACCUM
from repro.models import stacked as ST
from repro.optim import adamw

def measure_train(cfg, arch, layout='tp', zero1=False, strategy=None,
                  accum=None):
    mesh = make_production_mesh()
    params = jax.eval_shape(lambda: ST.init_params(jax.random.PRNGKey(0), cfg))
    init, _ = adamw(3e-4)
    opt = jax.eval_shape(lambda: init(jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), params)))
    specs = input_specs(cfg, 'train_4k')
    step = build_train_step(cfg, mesh, mode='ddp_tp', layout=layout,
                            strategy=strategy,
                            grad_accum=accum or GRAD_ACCUM.get(arch, 1))
    jf = jit_train_step(step, cfg, mesh, params, opt, specs, layout=layout,
                        zero1=zero1)
    compiled = jf.lower(params, opt, specs).compile()
    coll = parse_collectives(compiled.as_text())
    ma = compiled.memory_analysis()
    return {
        'collectives': {k: {'count': v['count'], 'bytes': v['bytes']}
                        for k, v in coll['per_op'].items()},
        'hlo_ici_static': coll['ici_traffic_bytes'],
        'mem_args_gib': ma.argument_size_in_bytes / 2**30,
        'mem_temp_gib': ma.temp_size_in_bytes / 2**30,
        'hlo_flops': compiled.cost_analysis().get('flops'),
    }

def terms(cb):
    hw = TPU_V5E
    return {
        'compute_ms': cb.flops / (hw.peak_flops * hw.efficiency) * 1e3,
        'memory_ms': cb.hbm_bytes / hw.hbm_bw * 1e3,
        'collective_ms': cb.ici_bytes / hw.ici_bw * 1e3,
    }
"""


def run_snippet(code: str, timeout=2400) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    proc = subprocess.run([sys.executable, "-c", _COMMON + code], env=env,
                          capture_output=True, text=True, timeout=timeout)
    if proc.returncode != 0:
        return {"error": proc.stderr[-1500:]}
    try:
        return json.loads(proc.stdout.strip().splitlines()[-1])
    except Exception:
        return {"error": "no json", "stdout": proc.stdout[-1500:]}


def h1():
    """qwen2-0.5b x train_4k: TP16 -> pure DP256 (+ bf16-reduce analytic)."""
    steps = []
    steps.append(dict(
        name="baseline ddp_tp (TP=16)",
        hypothesis=("TP=16 for a 0.5B model trades ~427 ms of per-layer "
                    "activation psums for weight memory it does not need; "
                    "collective term dominates compute 2.5x"),
        **run_snippet(r"""
cfg = get_config('qwen2-0.5b')
m = measure_train(cfg, 'qwen2-0.5b', layout='tp')
cb = shape_cost(cfg, 'train_4k', {'data': 16, 'model': 16})
m.update(terms(cb)); print(json.dumps(m))
""")))
    steps.append(dict(
        name="iter1: layout=dp (DP over all 256 devices)",
        hypothesis=("napkin: replicated 0.5B weights = 1 GiB bf16 + 4 GiB "
                    "f32 moments fit easily; collective becomes one f32 "
                    "gradient allreduce 2*(255/256)*4*N = 3.9 GiB -> "
                    "~79 ms at 50 GB/s, 5.4x less than TP's 427 ms; "
                    "compute term unchanged -> compute-bound"),
        **run_snippet(r"""
import numpy as np
cfg = get_config('qwen2-0.5b')
m = measure_train(cfg, 'qwen2-0.5b', layout='dp')
# analytic: pure DP -> no TP collectives, grads over 256
n = cfg.param_count()
cb = shape_cost(cfg, 'train_4k', {'data': 256, 'model': 1})
cb = dataclasses.replace(cb, ici_bytes=n * 4 * 2 * 255 / 256)
m.update(terms(cb)); print(json.dumps(m))
""")))
    steps.append(dict(
        name="iter2: bf16 gradient allreduce (analytic; TPU-only)",
        hypothesis=("reducing gradients in bf16 halves allreduce bytes -> "
                    "~40 ms; REFUTABLE only on real TPU (XLA:CPU miscompiles "
                    "16-bit all-reduce, the f32 upcast in sync_grads is the "
                    "documented workaround), so analytic-only"),
        analytic_only=True,
        collective_ms=39.7,
        note="2*(255/256)*2B*0.494e9 / 50 GB/s",
    ))
    steps.append(dict(
        name="iter3: DisCo bucket fusion on top of dp layout",
        hypothesis=("stacked gradient tree has ~30 leaves -> 30 allreduce "
                    "latencies = 0.3 ms, <1% of the 79 ms bandwidth term; "
                    "expect negligible wall-clock change (bucketing matters "
                    "in the many-small-tensor regime of the paper's per-op "
                    "graphs, not for layer-stacked tensors)"),
        **run_snippet(r"""
cfg = get_config('qwen2-0.5b')
params = jax.eval_shape(lambda: ST.init_params(jax.random.PRNGKey(0), cfg))
strat = GradSyncStrategy.size_capped(params, 64 * 2**20)
m = measure_train(cfg, 'qwen2-0.5b', layout='dp', strategy=strat)
n = cfg.param_count()
cb = shape_cost(cfg, 'train_4k', {'data': 256, 'model': 1})
cb = dataclasses.replace(cb, ici_bytes=n * 4 * 2 * 255 / 256)
m.update(terms(cb))
m['n_buckets'] = len(strat.buckets)
print(json.dumps(m))
""")))
    return steps


def h2():
    """deepseek-v2-lite x train_4k: DisCo bucket enactment + ZeRO-1."""
    steps = []
    steps.append(dict(
        name="baseline: per-tensor gradient AllReduce (JAX default analogue)",
        hypothesis=("one AllReduce per stacked gradient leaf; latency term = "
                    "count x 10 us; bandwidth term fixed by param bytes"),
        **run_snippet(r"""
cfg = get_config('deepseek-v2-lite-16b')
m = measure_train(cfg, 'deepseek-v2-lite-16b', layout='tp')
cb = shape_cost(cfg, 'train_4k', {'data': 16, 'model': 16})
m.update(terms(cb)); print(json.dumps(m))
""")))
    steps.append(dict(
        name="iter1: DisCo single-bucket tensor fusion (paper's method iii)",
        hypothesis=("merging compatible neighbouring buckets cuts AllReduce "
                    "count to ~2 (one per sharding signature); the compiled "
                    "HLO must show the collective count drop — the paper's "
                    "tensor fusion carried verbatim into the program"),
        **run_snippet(r"""
cfg = get_config('deepseek-v2-lite-16b')
params = jax.eval_shape(lambda: ST.init_params(jax.random.PRNGKey(0), cfg))
strat = GradSyncStrategy.size_capped(params, 512 * 2**20)
m = measure_train(cfg, 'deepseek-v2-lite-16b', layout='tp', strategy=strat)
cb = shape_cost(cfg, 'train_4k', {'data': 16, 'model': 16})
m.update(terms(cb))
m['n_buckets'] = len(strat.buckets)
print(json.dumps(m))
""")))
    steps.append(dict(
        name="iter2: + ZeRO-1 optimizer-state sharding",
        hypothesis=("adam moments sharded over data axes: argument bytes "
                    "drop by ~15/16 of the 8 B/param f32 moments "
                    "(~7.4 GiB/dev); XLA inserts slice+allgather around the "
                    "update (collective +~2 B/param)"),
        **run_snippet(r"""
cfg = get_config('deepseek-v2-lite-16b')
params = jax.eval_shape(lambda: ST.init_params(jax.random.PRNGKey(0), cfg))
strat = GradSyncStrategy.size_capped(params, 512 * 2**20)
m = measure_train(cfg, 'deepseek-v2-lite-16b', layout='tp', strategy=strat,
                  zero1=True)
cb = shape_cost(cfg, 'train_4k', {'data': 16, 'model': 16})
m.update(terms(cb))
print(json.dumps(m))
""")))
    return steps


def h3():
    """stablelm-1.6b x decode_32k: int8 KV cache."""
    steps = []
    steps.append(dict(
        name="baseline: bf16 KV cache",
        hypothesis=("decode is HBM-bound on the KV cache: 24L x 32k x 32kv x "
                    "64hd x 2 x 2B x 8 local seqs / 16 TP = ~3.2 GiB read "
                    "per step >> 0.2 GiB weights; memory term ~4.4 ms"),
        **run_snippet(r"""
cfg = get_config('stablelm-1.6b')
mesh = make_production_mesh()
jf, args = build_dryrun_decode(cfg, mesh, 'decode_32k')
compiled = jf.lower(*args).compile()
ma = compiled.memory_analysis()
cb = shape_cost(cfg, 'decode_32k', {'data': 16, 'model': 16})
m = terms(cb)
m['mem_args_gib'] = ma.argument_size_in_bytes / 2**30
m['mem_temp_gib'] = ma.temp_size_in_bytes / 2**30
print(json.dumps(m))
""")))
    steps.append(dict(
        name="iter1: int8 KV cache (+f32 per-head scales)",
        hypothesis=("quantising K/V to int8 halves cache bytes (scale "
                    "overhead 1/64): memory term 4.4 -> ~2.4 ms and cache "
                    "argument bytes halve in the compiled artifact; decode "
                    "logit error ~1.7e-2 (measured on the reduced model) is "
                    "acceptable for serving"),
        **run_snippet(r"""
cfg = dataclasses.replace(get_config('stablelm-1.6b'),
                          kv_cache_dtype='int8')
mesh = make_production_mesh()
jf, args = build_dryrun_decode(cfg, mesh, 'decode_32k')
compiled = jf.lower(*args).compile()
ma = compiled.memory_analysis()
cb = shape_cost(cfg, 'decode_32k', {'data': 16, 'model': 16})
# analytic: cache bytes halve + 1/64 scale overhead
cache_gib = 24 * 32768 * 32 * 64 * 2 * 8 / 16
new_hbm = cb.hbm_bytes - cache_gib * 1.05 + cache_gib * (0.5 + 1 / 64)
cb = dataclasses.replace(cb, hbm_bytes=new_hbm)
m = terms(cb)
m['mem_args_gib'] = ma.argument_size_in_bytes / 2**30
m['mem_temp_gib'] = ma.temp_size_in_bytes / 2**30
print(json.dumps(m))
""")))
    return steps


def h4():
    """Search-engine throughput trajectory: simulations/sec of the
    backtracking search over the course of a run, incremental fusion-graph
    engine vs the seed full-replay engine (in-process; see
    benchmarks/perf_search.py for the engine comparison itself)."""
    import time

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from common import arch_graph
    from perf_search import SeedPathSimulator
    from repro.core import Simulator, backtracking_search

    steps = []
    for arch in ("transformer-paper", "deepseek-v2-236b"):
        for mode in ("incremental", "seed"):
            sim = (Simulator(n_devices=256, incremental=True)
                   if mode == "incremental" else SeedPathSimulator())
            g = arch_graph(arch)
            traj = []
            t0 = time.perf_counter()
            state = {"sims": 0}

            def on_step(step, best, _t0=t0, _traj=traj, _sim=sim, _st=state):
                if step % 10:
                    return
                if isinstance(_sim, Simulator):
                    sims = sum(_sim.stats.values())
                else:
                    sims = len(_sim._memo)
                wall = time.perf_counter() - _t0
                _traj.append({"step": step, "wall_s": round(wall, 3),
                              "sims": sims,
                              "sims_per_sec": round(sims / wall, 1),
                              "best_cost": best})
                _st["sims"] = sims

            res = backtracking_search(g, sim, unchanged_limit=10**9,
                                      max_steps=150, seed=0, on_step=on_step)
            steps.append(dict(
                name=f"search throughput {arch} [{mode}]",
                hypothesis=("incremental engine sustains >=5x the seed "
                            "engine's simulations/sec as the search "
                            "progresses (ISSUE 1 tentpole)"),
                sims_per_sec=round(res.simulations / res.wall_time, 1),
                wall_s=round(res.wall_time, 3),
                simulations=res.simulations,
                best_cost=res.best_cost,
                trajectory=traj,
            ))
    return steps


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    os.makedirs(OUT, exist_ok=True)
    campaigns = {"H1": h1, "H2": h2, "H3": h3, "H4": h4}
    for hid, fn in campaigns.items():
        if args.only and hid != args.only:
            continue
        print(f"=== {hid} ===", flush=True)
        steps = fn()
        path = os.path.join(OUT, f"{hid}.json")
        json.dump(steps, open(path, "w"), indent=1, default=str)
        for s in steps:
            keys = {k: v for k, v in s.items()
                    if k in ("collective_ms", "memory_ms", "compute_ms",
                             "mem_args_gib", "mem_temp_gib", "n_buckets",
                             "sims_per_sec", "wall_s", "error")}
            coll = s.get("collectives", {})
            nar = coll.get("all-reduce", {}).get("count")
            print(f"  {s['name']}: {keys} all-reduce-count={nar}", flush=True)


if __name__ == "__main__":
    main()
