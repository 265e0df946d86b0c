"""Search -> save -> enact: the full DisCo workflow (paper Sec. 3.1),
through the ``repro.plan`` public API.

    PYTHONPATH=src python examples/search_and_enact.py
    PYTHONPATH=src python examples/search_and_enact.py \
        --cluster a100_nvlink_ib --streams 4

Search Phase: one call — ``repro.plan.compile()`` owns trace -> profile ->
backtracking search and returns a frozen, versioned :class:`repro.plan.
Plan` artifact (op-fusion groups, buckets, per-bucket algo/comm/chunks,
cluster fingerprint, predicted iteration time).  ``plan.save()`` writes the
schema-versioned JSON — the paper's "optimized HLO module" configuration
file, now a first-class value that ``dryrun --plan`` can re-price and any
trainer can load.

Enactment Phase: ``Plan.load()`` round-trips the artifact (asserted
bit-for-bit) and ``plan.grad_sync(params)`` lowers it to the
:class:`GradSyncStrategy` built into the distributed train step; we lower
the per-tensor baseline and the DisCo-bucketed step and compare their
AllReduce counts in the compiled HLO (XLA's all-reduce combiner may merge
buckets further; DESIGN.md Sec. 13).
"""
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp

import repro.plan as RP
from repro.configs import get_config
from repro.data.pipeline import make_batch_specs
from repro.distributed.train_step import (GradSyncStrategy, build_train_step,
                                          jit_train_step)
from repro.launch.dryrun import parse_collectives
from repro.launch.mesh import make_mesh
from repro.models import stacked as ST
from repro.optim import adamw


def allreduce_count(cfg, mesh, strategy, params, opt, specs):
    step = build_train_step(cfg, mesh, mode="ddp_tp", strategy=strategy)
    jf = jit_train_step(step, cfg, mesh, params, opt, specs)
    compiled = jf.lower(params, opt, specs).compile()
    coll = parse_collectives(compiled.as_text())
    return coll["per_op"].get("all-reduce", {"count": 0})["count"], coll


def main():
    import argparse

    from repro.cluster import list_presets

    ap = argparse.ArgumentParser()
    ap.add_argument("--cluster", default=None, choices=list_presets(),
                    help="cluster preset to search against; default: "
                         "legacy flat model")
    ap.add_argument("--streams", type=int, default=1,
                    help="search against the N-stream event engine; with "
                         "--cluster the comm kind (AllReduce vs ZeRO-3 "
                         "RS+AG) and chunk count become searched dimensions "
                         "too (the flat default spec is algorithm-blind and "
                         "drops them)")
    ap.add_argument("--steps", type=int, default=None,
                    help="bound the search step count (CI smoke lane)")
    args = ap.parse_args()

    # ---- Search Phase (ENABLE_SEARCH=1 in the paper) ----
    print("search phase ...")
    if args.cluster:
        from repro.cluster import get_preset

        spec = get_preset(args.cluster)
        print(f"  pricing collectives on {spec.name} "
              f"({spec.n_devices} devices, {len(spec.levels)} link levels, "
              f"{args.streams} stream(s))")
    plan = RP.compile("qwen2-0.5b", cluster=args.cluster,
                      streams=args.streams, n_devices=4,
                      unchanged_limit=120, max_steps=args.steps, seed=0)
    path = os.path.join(tempfile.gettempdir(), "disco_plan.json")
    plan.save(path)
    d = plan.describe()
    print(f"  {d['grad_tensors']} gradient tensors -> "
          f"{d['allreduce_buckets']} fused AllReduce buckets "
          f"(predicted {plan.predicted_iteration_time*1e3:.3f} ms, "
          f"{plan.provenance['simulations']} simulations); saved {path}")
    if args.cluster:
        print(f"  searched collective-algorithm mix: {d['bucket_algos']}")
        if args.streams > 1:
            print(f"  searched comm kinds: {d['bucket_comm']}  "
                  f"chunk counts: {d['bucket_chunks']}")

    # ---- Enactment Phase (ENABLE_SEARCH=0) ----
    print("enactment phase ...")
    loaded = RP.Plan.load(path)
    # the artifact is a value: the round trip is exact, identity included
    assert loaded == plan and loaded.fingerprint() == plan.fingerprint(), \
        "plan save/load round-trip drifted"
    print(f"  plan round-trips bit-for-bit [{loaded.fingerprint()}]")

    cfg = get_config("qwen2-0.5b").reduced()
    key = jax.random.PRNGKey(0)
    mesh = make_mesh((4, 2), ("data", "model"))
    params_s = jax.eval_shape(lambda: ST.init_params(key, cfg))
    strat = loaded.grad_sync(params_s)
    init, _ = adamw(1e-3)
    opt_s = jax.eval_shape(lambda: init(jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), params_s)))
    specs = make_batch_specs(cfg, 8, 64)

    n_pt, _ = allreduce_count(cfg, mesh, GradSyncStrategy.per_tensor(params_s),
                              params_s, opt_s, specs)
    n_disco, coll = allreduce_count(cfg, mesh, strat, params_s, opt_s, specs)
    print(f"  compiled HLO all-reduce count: per-tensor={n_pt}, "
          f"DisCo={n_disco}")
    print(f"  DisCo collective mix: "
          f"{ {k: v['count'] for k, v in coll['per_op'].items()} }")
    assert n_disco <= n_pt
    print("the DisCo step compiles to no more all-reduces than per-tensor")


if __name__ == "__main__":
    main()
