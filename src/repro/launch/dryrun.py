"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes, and extract the roofline terms from the compiled
artifact.

    PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all
    PYTHONPATH=src python -m repro.launch.dryrun --arch rwkv6-3b \
        --shape train_4k --multi-pod
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-0.5b \
        --shape train_4k --cluster a100_nvlink_ib
    PYTHONPATH=src python -m repro.launch.dryrun --plan plan.json

With ``--plan <file>`` a saved :class:`repro.plan.Plan` artifact is priced
directly (serialized channel + event engine on its recorded bucket volumes
and cluster fingerprint) — no model trace, no search, no compile.

Otherwise, outputs one JSON per combination under experiments/dryrun/,
including a
``cluster`` block that prices the compiled collectives on a
:class:`repro.cluster.ClusterSpec` (``--cluster <preset>`` to pick one of
the preset zoo; default derives the topology from the mesh).
"""
import os

if __name__ == "__main__":
    # run as a program: 512 placeholder host devices for the pod meshes
    # (before jax initialises; importing this module sets nothing)
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=512")

import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import ARCHS, get_config
from ..distributed import sharding as SH
from ..distributed.train_step import (GradSyncStrategy, build_train_step,
                                      jit_train_step)
from ..models import stacked as ST
from ..optim import adamw
from ..cluster import (COLLECTIVE_ALGOS, best_algo, bucket_time, comm_time,
                       get_preset, list_presets)
from ..core.pipeline import PipelineSchedule, SCHED_1F1B, SCHEDULES
from .mesh import cluster_from_mesh, make_production_mesh
from .shapes import (FSDP_ARCHS, GRAD_ACCUM, SHAPES, ZERO1_ARCHS,
                     applicability, cache_capacity, input_specs)

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1,
                "f8e5m2": 1, "s64": 8, "s32": 4, "s16": 2, "s8": 1,
                "u64": 8, "u32": 4, "u16": 2, "u8": 1, "pred": 1,
                "c64": 8, "c128": 16}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"=\s*(\(?[^=]*?)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_OLD_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
# long tuple shapes carry /*index=N*/ markers, whose '=' would cut _COLL_RE
_COMMENT_RE = re.compile(r"/\*.*?\*/")


def _shape_bytes(text: str) -> float:
    total = 0.0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> dict:
    """Sum collective tensor sizes and estimate per-device ICI traffic.

    Per-device traffic factors (ring algorithms over group size G):
      all-reduce 2(G-1)/G; all-gather/reduce-scatter/all-to-all (G-1)/G;
      collective-permute 1.

    Each per-op entry additionally carries a ``by_group`` breakdown keyed
    by replica-group size — the signal that classifies a collective as
    tensor-parallel (group == TP degree) vs data-parallel traffic for the
    event engine's traffic classes (DESIGN.md Sec. 9).
    """
    per_op: dict = {}
    traffic = 0.0
    for line in hlo_text.splitlines():
        line = _COMMENT_RE.sub("", line)
        m = _COLL_RE.search(line)
        if m is None or "-done(" in line:
            continue
        op = m.group(2)
        nbytes = _shape_bytes(m.group(1))
        g = 1
        gm = _GROUPS_RE.search(line)
        if gm:
            g = int(gm.group(2))
        else:
            gm2 = _GROUPS_OLD_RE.search(line)
            if gm2:
                g = len(gm2.group(1).split(","))
        if g <= 1:
            factor = 0.0
        elif op == "all-reduce":
            factor = 2.0 * (g - 1) / g
        elif op == "collective-permute":
            factor = 1.0
        else:
            factor = (g - 1) / g
        d = per_op.setdefault(op, {"count": 0, "bytes": 0.0, "traffic": 0.0,
                                   "by_group": {}})
        d["count"] += 1
        d["bytes"] += nbytes
        d["traffic"] += nbytes * factor
        bg = d["by_group"].setdefault(g, {"count": 0, "bytes": 0.0})
        bg["count"] += 1
        bg["bytes"] += nbytes
        traffic += nbytes * factor
    return {"per_op": per_op, "ici_traffic_bytes": traffic}


# ------------------------------------------------------------ step builders
def build_dryrun_train(cfg, mesh, arch: str):
    fsdp = arch in FSDP_ARCHS
    mode = "fsdp_tp" if fsdp else "ddp_tp"
    dp = int(np.prod([v for k, v in mesh.shape.items() if k != "model"]))
    local_batch = SHAPES["train_4k"]["batch"] // dp
    accum = min(GRAD_ACCUM.get(arch, 1), local_batch)
    params = jax.eval_shape(lambda: ST.init_params(jax.random.PRNGKey(0), cfg))
    opt_init, _ = adamw(3e-4)
    # optimizer moments in f32 (realistic memory accounting)
    opt = jax.eval_shape(lambda: opt_init(
        jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
                     params)))
    step = build_train_step(cfg, mesh, mode=mode, grad_accum=accum,
                            remat=True,
                            strategy=None)
    jf = jit_train_step(step, cfg, mesh, params, opt,
                        input_specs(cfg, "train_4k"), fsdp=fsdp,
                        zero1=arch in ZERO1_ARCHS)
    return jf, (params, opt, input_specs(cfg, "train_4k"))


def build_dryrun_prefill(cfg, mesh, shape: str, fsdp: bool = False):
    """Prefill runs partial-manual over the data axes (like training): the
    MoE sort-based dispatch must see *local* tokens — under pure GSPMD its
    data-dependent scatter replicates the full global token buffer."""
    specs = input_specs(cfg, shape)
    S = SHAPES[shape]["seq"]
    cap = cache_capacity(cfg, S)
    params = jax.eval_shape(lambda: ST.init_params(jax.random.PRNGKey(0), cfg))
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    lead = dp_axes if len(dp_axes) > 1 else dp_axes[0]

    def fn(params, batch):
        return ST.prefill(params, cfg, batch["tokens"], cap,
                          prefix_emb=batch.get("prefix_emb"),
                          enc_frames=batch.get("enc_frames"),
                          vp_mesh=mesh)

    # out specs: logits (B, V) + stacked caches (batch at axis 1)
    out_shape = jax.eval_shape(
        lambda p, b: fn(p, b), params,
        {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in specs.items()})
    logits_spec = P(lead, None)
    cache_specs = jax.tree.map(
        lambda l: P(None, lead, *([None] * (l.ndim - 2))), out_shape[1])
    bspec = {k: P(lead) for k in specs}
    smfn = jax.shard_map(fn, mesh=mesh, in_specs=(P(), bspec),
                         out_specs=(logits_spec, cache_specs),
                         axis_names=set(dp_axes), check_vma=False)
    # NOTE: under the data-manual region, params must not be data-sharded
    # (they enter with spec P()); big-arch serving shards experts over
    # `model` only — weights stream from the EP shards.
    pshard = SH.param_shardings(params, mesh, cfg=cfg)
    bshard = SH.batch_shardings(specs, mesh)
    jf = jax.jit(smfn, in_shardings=(pshard, bshard))
    return jf, (params, specs)


def build_dryrun_decode(cfg, mesh, shape: str, fsdp: bool = False):
    specs = input_specs(cfg, shape)
    params = jax.eval_shape(lambda: ST.init_params(jax.random.PRNGKey(0), cfg))

    if cfg.encdec is not None:
        def fn(params, caches, token, pos, memory):
            return ST.decode_step(params, cfg, caches, token, pos,
                                  memory=memory, vp_mesh=mesh)
    else:
        def fn(params, caches, token, pos):
            return ST.decode_step(params, cfg, caches, token, pos,
                                  vp_mesh=mesh)

    pshard = SH.param_shardings(params, mesh, cfg=cfg, fsdp=fsdp)
    cshard = SH.cache_shardings(specs["caches"], mesh)
    rep = NamedSharding(mesh, P())
    tshard = NamedSharding(mesh, SH.batch_pspec(specs["token"].shape[0], mesh, 1))
    B = specs["token"].shape[0]
    # NOTE: model-sharding the logits output forces a degenerate reshard
    # collective that crashes XLA:CPU's AllReducePromotion; batch-only.
    logits_sh = NamedSharding(mesh, P(SH.batch_pspec(B, mesh, 1)[0], None))
    in_sh = [pshard, cshard, tshard, rep]
    args = [params, specs["caches"], specs["token"], specs["pos"]]
    if cfg.encdec is not None:
        in_sh.append(NamedSharding(
            mesh, SH.batch_pspec(specs["memory"].shape[0], mesh, 3)))
        args.append(specs["memory"])
    jf = jax.jit(fn, in_shardings=tuple(in_sh),
                 out_shardings=(logits_sh, cshard),
                 donate_argnums=(1,))
    return jf, tuple(args)


def background_from_collectives(coll: dict, tp_degree: int) -> list:
    """Classify the compiled HLO's collectives into recurring background
    traffic for the event engine (DESIGN.md Sec. 9): collectives whose
    replica-group size equals the TP degree are tensor-parallel activation
    traffic; collective-permutes are pipeline-parallel stage-boundary
    transfers.  Returns ``(traffic_class, comm_kind, mean_bytes, count)``
    tuples.  Heuristic by construction — when the DP and TP degrees
    coincide the split is ambiguous and everything counts as TP."""
    kind_map = {"all-reduce": "ar", "all-gather": "ag",
                "reduce-scatter": "rs", "all-to-all": "ag"}
    out = []
    for op, d in coll.get("per_op", {}).items():
        if op == "collective-permute":
            if d.get("count"):
                out.append(("pp", "p2p", d["bytes"] / d["count"],
                            int(d["count"])))
            continue
        kind = kind_map.get(op)
        if kind is None or tp_degree <= 1:
            continue
        bg = d.get("by_group", {}).get(tp_degree)
        if bg and bg["count"]:
            out.append(("tp", kind, bg["bytes"] / bg["count"],
                        int(bg["count"])))
    return out


def collective_cost_model(coll: dict, spec, streams: int = 1,
                          tp_degree: int = 1,
                          keep_timeline: bool = False) -> dict:
    """Price the compiled HLO's collective traffic on a ClusterSpec: the
    all-reduce traffic under each algorithm, and the cheapest choice.
    Priced as ``count`` collectives of the mean size so the per-collective
    latency term is charged once per op, not once for the aggregate.
    A topology-blind consumer can still read ``ici_traffic_bytes``; this
    block says what the traffic *costs* on the actual interconnect.

    ZeRO-3 / ``fsdp_tp`` modules compile to reduce-scatter + all-gather
    instead of all-reduce; the ``rs_ag`` block prices those per level so
    FSDP strategies get topology-aware ranking too.  With ``--streams N``
    the ``streams`` block additionally reports the event-engine finish time
    of the AllReduce set under N concurrent streams (pipelined hierarchical
    phases) next to the serialized channel, and — when the module carries
    TP/PP collectives — a ``contention`` block pricing the gradient set
    against that background traffic as recurring ``tp``/``pp``-class jobs
    on the same link levels (DESIGN.md Sec. 9).  ``keep_timeline`` embeds
    the contended schedule's 8-tuple records."""
    ar = coll["per_op"].get("all-reduce", {})
    ar_bytes = ar.get("bytes", 0.0)
    count = max(int(ar.get("count", 0)), 1)
    mean_bytes = ar_bytes / count
    name, t = best_algo(mean_bytes, spec)
    out = {
        "spec": spec.describe(),
        "allreduce_bytes": ar_bytes,
        "allreduce_count": ar.get("count", 0),
        "allreduce_time_s": {
            algo: count * bucket_time(mean_bytes, spec, algo)
            for algo in COLLECTIVE_ALGOS
        },
        "best_algo": name,
        "best_time_s": count * t,
    }
    rs_ag = {}
    for op, kind in (("reduce-scatter", "rs"), ("all-gather", "ag")):
        d = coll["per_op"].get(op)
        if not d or not d.get("count"):
            continue
        mean = d["bytes"] / d["count"]
        times = {algo: d["count"] * comm_time(mean, spec, algo, kind)
                 for algo in COLLECTIVE_ALGOS}
        rs_ag[op] = {
            "bytes": d["bytes"],
            "count": d["count"],
            "time_s": times,
            "best_algo": min(times, key=times.get),
        }
    if rs_ag:
        out["rs_ag"] = rs_ag
    # the DP gradient set: all-reduces minus the TP-group ones (those are
    # activation traffic, re-injected below as tp-class background jobs —
    # counting them in both sets would price the TP bytes twice).  When the
    # DP and TP replica-group sizes coincide (e.g. a 16x16 mesh) the split
    # is ambiguous: every all-reduce lands in by_group[tp_degree] and the
    # subtraction would empty the gradient set, so treat the all-reduces as
    # the DP set and drop only the ar-kind TP background (gather/scatter/
    # permute classes are still unambiguous).
    ar_groups = set(ar.get("by_group", {}))
    dp_tp_ambiguous = tp_degree > 1 and ar_groups == {tp_degree}
    tp_ar = (ar.get("by_group", {}).get(tp_degree, {"count": 0, "bytes": 0.0})
             if tp_degree > 1 and not dp_tp_ambiguous
             else {"count": 0, "bytes": 0.0})
    dp_count = int(ar.get("count", 0)) - int(tp_ar["count"])
    dp_bytes = ar_bytes - tp_ar["bytes"]
    if streams > 1 and dp_count > 0:
        from repro.core.events import CommEngine, CommJob

        mean_bytes = dp_bytes / dp_count
        name, _ = best_algo(mean_bytes, spec)
        n_jobs = min(dp_count, 128)  # cap the event-loop size
        # readiness staggered (gradients are produced over the backward
        # pass) at a rate that backlogs the serialized channel: arrivals
        # every t_one/streams keep `streams` jobs in flight, so the block
        # reports the engine's steady-state pipeline against the serialized
        # FIFO.  Simultaneous identical jobs would progress in lockstep
        # under fair share and show no pipeline at all.
        t_one = comm_time(mean_bytes, spec, name)
        jobs = [CommJob(bucket=i, ready=i * t_one / streams,
                        nbytes=mean_bytes, algo=name) for i in range(n_jobs)]
        ser = CommEngine(spec, streams=1).run(list(jobs))[1]
        pip = CommEngine(spec, streams=streams).run(list(jobs))[1]
        out["streams"] = {
            "streams": streams,
            "jobs": n_jobs,
            "dp_allreduce_count": dp_count,
            "dp_allreduce_bytes": dp_bytes,
            "dp_tp_ambiguous": dp_tp_ambiguous,
            "algo": name,
            "serialized_finish_s": ser,
            "pipelined_finish_s": pip,
            "speedup": ser / pip if pip > 0 else 1.0,
        }
        # TP/PP traffic classes: recurring background jobs extracted from
        # the compiled HLO contend with the gradient set on the same levels
        from repro.core.events import BackgroundTraffic

        classified = background_from_collectives(coll, tp_degree)
        if dp_tp_ambiguous:
            classified = [t for t in classified
                          if not (t[0] == "tp" and t[1] == "ar")]
        bg_jobs = []
        base_id = n_jobs + 1
        for tclass, kind, mean, cnt in classified:
            n = min(cnt, 64)  # cap the event-loop size per class
            traffic = BackgroundTraffic(
                tclass, mean, period=pip / n if n else 0.0, kind=kind,
                count=n)
            made = traffic.materialize(pip, base_id)
            base_id += len(made)
            bg_jobs.extend(made)
        if bg_jobs:
            eng = CommEngine(spec, streams=streams)
            tl: list | None = [] if keep_timeline else None
            eng.run(list(jobs) + bg_jobs, tl)
            dp_fin = eng.class_finish.get("dp", 0.0)
            out["contention"] = {
                "classes": [
                    {"traffic_class": tclass, "kind": kind,
                     "mean_bytes": mean, "count": cnt}
                    for tclass, kind, mean, cnt in classified
                ],
                "background_jobs": len(bg_jobs),
                "grad_finish_alone_s": pip,
                "grad_finish_contended_s": dp_fin,
                "slowdown": dp_fin / pip if pip > 0 else 1.0,
                "class_busy_s": dict(eng.class_busy),
            }
            if tl is not None:
                out["contention"]["timeline"] = [list(e) for e in tl]
    return out


def pipeline_cost_model(coll: dict, spec, sched, flops: float,
                        streams: int = 1,
                        keep_timeline: bool = False) -> dict:
    """Price the compiled step under a 1F1B pipeline schedule on the
    unified engine (DESIGN.md Sec. 11): the step's flops on the reference
    chip are split uniformly over ``n_stages`` and ``n_microbatches`` into
    fwd/bwd compute units, lowered to the schedule's compute+p2p job
    graph, and run together with the DP gradient all-reduce set — so the
    block reports the PP bubble *and* the gradient slowdown from sharing
    link levels with stage-boundary transfers.  The stage-boundary p2p
    volume defaults to the compiled collective-permute mean.
    ``keep_timeline`` embeds the unified 8-tuple records (compute spans
    carry their interval at both the legacy (2,3) and unified (6,7)
    positions)."""
    from repro.core.events import CommJob, EventEngine, TC_PP
    from repro.core.hw import TPU_V5E
    from repro.core.pipeline import bubble_stats, lower_schedule

    S, M = sched.n_stages, sched.n_microbatches
    r = sched.fwd_bwd_ratio
    step_s = flops / (TPU_V5E.peak_flops * TPU_V5E.efficiency)
    stage_busy = [step_s / S] * S
    stage_fwd = [b / M * (r / (1.0 + r)) for b in stage_busy]
    stage_bwd = [b / M - f for b, f in zip(stage_busy, stage_fwd)]
    if sched.p2p_bytes is not None:
        p2p_bytes = sched.p2p_bytes
    else:
        perm = coll.get("per_op", {}).get("collective-permute", {})
        p2p_bytes = (perm["bytes"] / perm["count"]
                     if perm.get("count") else 0.0)
    # the DP gradient set, priced as `count` collectives of the mean size
    # (same model as the streams block); the HLO carries no per-tensor
    # stage provenance, so bucket i deps on stage i % S's last backward
    ar = coll["per_op"].get("all-reduce", {})
    count = int(ar.get("count", 0))
    n_grads, mean, algo = 0, 0.0, "ring"
    if count and ar.get("bytes", 0.0) > 0.0:
        mean = ar["bytes"] / count
        algo, _ = best_algo(mean, spec)
        n_grads = min(count, 128)
    cjobs, p2p, last_bwd, _ = lower_schedule(
        sched, stage_fwd, stage_bwd, p2p_bytes, next_id=n_grads)
    grads = [CommJob(bucket=i, ready=0.0, nbytes=mean, algo=algo,
                     deps=(last_bwd[i % S],))
             for i in range(n_grads)]
    eng = EventEngine(spec, streams=max(int(streams or 1), 1))
    tl: list | None = [] if keep_timeline else None
    u = eng.run_unified(cjobs, grads + p2p, tl)
    grad_fin = eng.class_finish.get("dp", 0.0)
    out = {
        "schedule": sched.schedule,
        "n_stages": S,
        "n_microbatches": M,
        "interleave": sched.chunks_per_stage,
        "ref_chip": TPU_V5E.name,
        "step_compute_s": step_s,
        "p2p_bytes": p2p_bytes,
        "p2p_jobs": len(p2p),
        "grad_jobs": n_grads,
        "compute_finish_s": u.compute_finish,
        "grad_finish_s": grad_fin,
        "iteration_s": u.finish,
        "p2p_busy_s": eng.class_busy.get(TC_PP, 0.0),
        "bubble": bubble_stats(sched, stage_busy, u.compute_finish),
    }
    if tl is not None:
        out["timeline"] = [list(e) for e in tl]
    return out


def tp_cost_model(coll: dict, spec, tp_degree: int, flops: float,
                  streams: int = 1, keep_timeline: bool = False) -> dict | None:
    """Price the compiled step's tensor-parallel activation traffic as
    **dep-coupled first-class jobs** (DESIGN.md Sec. 14) next to the
    ``background`` average the contention block uses: the step's flops on
    the reference chip become a chained per-layer compute schedule, each
    layer's TP collective deps on the compute that produced it (forward
    jobs gate the next layer's compute, backward jobs gate the gradient
    buckets), and the DP gradient set runs against that coupled schedule
    on the unified engine.  Reports the gradient finish alone, under the
    dep-coupled TP jobs, and under the legacy periodic-background model of
    the *same* volume — the spread between the last two is the
    quiet-window signal the tentpole search exploits.  Returns None when
    the module carries no TP-classified collectives."""
    from repro.core.events import CommJob, ComputeJob, EventEngine, TC_TP
    from repro.core.hw import TPU_V5E
    from repro.core.tp_traffic import TPTraffic, couple_tp

    classified = [t for t in background_from_collectives(coll, tp_degree)
                  if t[0] == "tp"]
    if not classified:
        return None
    total_tp = sum(mean * cnt for _, _, mean, cnt in classified)
    count = sum(cnt for _, _, _, cnt in classified)
    if total_tp <= 0.0:
        return None
    # dominant comm kind by volume; half the collectives are the backward
    # mirrors, so the layer count is count/2 (capped for the event loop) and
    # fwd/bwd each carry half the volume — total bytes conserve exactly
    kind = max(classified, key=lambda t: t[2] * t[3])[1]
    L = max(1, min(count // 2, 32))
    tp = TPTraffic(n_layers=L, fwd_bytes=total_tp / (2.0 * L), kind=kind)
    step_s = flops / (TPU_V5E.peak_flops * TPU_V5E.efficiency)
    # the DP gradient set minus the TP-group all-reduces (the same
    # ambiguity rule as collective_cost_model: when every replica group has
    # the TP size the split is meaningless — keep the ar set as DP)
    ar = coll["per_op"].get("all-reduce", {})
    ar_groups = set(ar.get("by_group", {}))
    ambiguous = tp_degree > 1 and ar_groups == {tp_degree}
    tp_ar = (ar.get("by_group", {}).get(tp_degree, {"count": 0, "bytes": 0.0})
             if tp_degree > 1 and not ambiguous
             else {"count": 0, "bytes": 0.0})
    dp_count = int(ar.get("count", 0)) - int(tp_ar["count"])
    dp_bytes = ar.get("bytes", 0.0) - tp_ar["bytes"]
    n_grads, mean, algo = 0, 0.0, "ring"
    if dp_count > 0 and dp_bytes > 0.0:
        mean = dp_bytes / dp_count
        algo, _ = best_algo(mean, spec)
        n_grads = min(dp_count, 128)
    # chained per-layer compute; span s ends at unit s (one unit per layer)
    compute = []
    prev = None
    for i in range(L):
        j = ComputeJob(ref=i, duration=step_s / L, job_id=-(i + 1),
                       key=(i,), deps=() if prev is None else (prev,))
        prev = j.job_id
        compute.append(j)
    coupled, fwd_jobs, bwd_jobs, next_id = couple_tp(
        compute, list(range(1, L + 1)), tp, n_grads)

    def grads(gate_of):
        # no per-tensor stage provenance in the HLO: bucket i is gated by
        # layer (i % L)'s backward (mirroring pipeline_cost_model's i % S)
        return [CommJob(bucket=i, ready=0.0, nbytes=mean, algo=algo,
                        deps=(gate_of(i),)) for i in range(n_grads)]

    last_compute = compute[-1].job_id
    eng = EventEngine(spec, streams=max(int(streams or 1), 1))
    u_alone = eng.run_unified(list(compute), grads(lambda i: last_compute))
    alone = eng.class_finish.get("dp", 0.0)
    # dep-coupled: TP jobs scheduled where the compute actually emits them
    gate = ((lambda i: bwd_jobs[i % L].job_id) if bwd_jobs
            else (lambda i: last_compute))
    eng_c = EventEngine(spec, streams=max(int(streams or 1), 1))
    tl: list | None = [] if keep_timeline else None
    u = eng_c.run_unified(list(coupled), grads(gate) + fwd_jobs + bwd_jobs,
                          tl)
    coupled_fin = eng_c.class_finish.get("dp", 0.0)
    # legacy model: the same volume as periodic background averages
    bg_jobs = []
    base_id = next_id
    for b in tp.to_background(u_alone.compute_finish):
        made = b.materialize(u_alone.compute_finish, base_id)
        base_id += len(made)
        bg_jobs.extend(made)
    eng_b = EventEngine(spec, streams=max(int(streams or 1), 1))
    eng_b.run_unified(list(compute), grads(lambda i: last_compute) + bg_jobs)
    background_fin = eng_b.class_finish.get("dp", 0.0)
    out = {
        "tp_degree": tp_degree,
        "n_layers": L,
        "fwd_bytes": tp.fwd_bytes,
        "bwd_bytes": tp.bwd,
        "kind": kind,
        "total_tp_bytes": tp.total_bytes,
        "ref_chip": TPU_V5E.name,
        "step_compute_s": step_s,
        "grad_jobs": n_grads,
        "tp_jobs": len(fwd_jobs) + len(bwd_jobs),
        "compute_finish_s": u.compute_finish,
        "iteration_s": u.finish,
        "tp_busy_s": eng_c.class_busy.get(TC_TP, 0.0),
        "grad_finish_alone_s": alone,
        "grad_finish_coupled_s": coupled_fin,
        "grad_finish_background_s": background_fin,
        "slowdown": coupled_fin / alone if alone > 0 else 1.0,
    }
    if tl is not None:
        out["timeline"] = [list(e) for e in tl]
    return out


# -------------------------------------------------------------- plan pricing
def price_plan(path: str, cluster: str | None = None,
               streams: int | None = None,
               out_dir: str | None = None, verbose: bool = True) -> dict:
    """Price a saved :class:`repro.plan.Plan` artifact without re-tracing
    or re-searching (``--plan <file>``): the serialized-channel sum and the
    event-engine finish of the plan's recorded bucket volumes, on the
    plan's own cluster fingerprint or an explicit ``--cluster`` override.
    An override that differs from what the plan was searched against is
    reported field-by-field (``cluster_fingerprint_diff``: which levels
    and which constants disagree) so the mismatch is diagnosable, and the
    CLI exits nonzero."""
    from repro.plan import (Plan, cluster_fingerprint,
                            cluster_fingerprint_diff)

    plan = Plan.load(path)
    spec = get_preset(cluster) if cluster else None
    result = {
        "plan": path,
        "fingerprint": plan.fingerprint(),
        "describe": plan.describe(),
        "provenance": plan.provenance,
        "pricing": plan.price(cluster=spec, streams=streams),
    }
    if (spec is not None and plan.cluster is not None
            and not result["pricing"]["cluster_fingerprint_match"]):
        result["pricing"]["cluster_fingerprint_diff"] = \
            cluster_fingerprint_diff(plan.cluster, cluster_fingerprint(spec))
    if verbose:
        p = result["pricing"]
        print(f"  plan {path} [{result['fingerprint']}]: "
              f"{p['buckets']} buckets, "
              f"{p['total_grad_bytes']:.3e} B on {p['cluster']['name']} "
              f"(fingerprint match: {p['cluster_fingerprint_match']})")
        for line in p.get("cluster_fingerprint_diff", ()):
            print(f"    fingerprint diff: {line}")
        print(f"    serialized comm {p['serialized_comm_s']*1e3:.3f} ms, "
              f"{p['streams']}-stream engine finish "
              f"{p['engine_finish_s']*1e3:.3f} ms, searched prediction "
              f"{(plan.predicted_iteration_time or 0.0)*1e3:.3f} ms")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        tag = os.path.splitext(os.path.basename(path))[0]
        out_path = os.path.join(out_dir, f"plan__{tag}.json")
        with open(out_path, "w") as f:
            json.dump(result, f, indent=1)
        if verbose:
            print(f"    wrote {out_path}")
    return result


def price_serving_plan(path: str, cluster: str | None = None,
                       out_dir: str | None = None,
                       verbose: bool = True) -> dict:
    """Price a saved :class:`repro.serving.plan.ServingPlan` artifact
    (``--serve-plan <file>``): re-run the decode-step lowering with the
    plan's searched knobs on the recorded cluster fingerprint or an
    explicit ``--cluster`` override.  A mismatched override is diagnosed
    field-by-field, same contract as ``--plan``."""
    from repro.plan import cluster_fingerprint, cluster_fingerprint_diff
    from repro.serving.plan import ServingPlan

    plan = ServingPlan.load(path)
    spec = get_preset(cluster) if cluster else None
    result = {
        "serve_plan": path,
        "fingerprint": plan.fingerprint(),
        "describe": plan.describe(),
        "provenance": plan.provenance,
        "pricing": plan.price(cluster=spec),
    }
    if spec is not None and not result["pricing"]["cluster_fingerprint_match"]:
        result["pricing"]["cluster_fingerprint_diff"] = \
            cluster_fingerprint_diff(plan.cluster, cluster_fingerprint(spec))
    if verbose:
        p = result["pricing"]
        d = result["describe"]
        print(f"  serve-plan {path} [{result['fingerprint']}]: "
              f"{d['arch']} slots={d['slots']} batch={d['decode_batch']} "
              f"kv={d['kv_layout']} algo={d['algo']} "
              f"streams={d['streams']} on {p['cluster']['name']} "
              f"(fingerprint match: {p['cluster_fingerprint_match']})")
        for line in p.get("cluster_fingerprint_diff", ()):
            print(f"    fingerprint diff: {line}")
        print(f"    {p['tokens_per_s']:.0f} tok/s "
              f"({p['seconds_per_token']*1e6:.2f} us/token), "
              f"ttft p99 {p['ttft_p99_s']*1e3:.3f} ms, "
              f"decode TP traffic {p['tp_bytes_decode']:.3e} B, "
              f"HBM {p['mem_bytes']/1e9:.2f}/{p['hbm_bytes']/1e9:.0f} GB; "
              f"searched prediction "
              f"{plan.predicted_tokens_per_s:.0f} tok/s")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        tag = os.path.splitext(os.path.basename(path))[0]
        out_path = os.path.join(out_dir, f"serve_plan__{tag}.json")
        with open(out_path, "w") as f:
            json.dump(result, f, indent=1, default=repr)
        if verbose:
            print(f"    wrote {out_path}")
    return result


# -------------------------------------------------------------------- main
def dryrun_one(arch: str, shape: str, multi_pod: bool,
               verbose: bool = True, cluster: str | None = None,
               streams: int = 1, keep_timeline: bool = False,
               pp=None) -> dict:
    cfg0 = get_config(arch)
    ok, reason, cfg = applicability(cfg0, shape)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    result = {
        "arch": arch, "shape": shape, "mesh": mesh_name,
        "applicable": ok, "reason": reason,
    }
    if not ok:
        return result
    mesh = make_production_mesh(multi_pod=multi_pod)
    kind = SHAPES[shape]["kind"]
    t0 = time.perf_counter()
    if kind == "train":
        jf, args = build_dryrun_train(cfg, mesh, arch)
        lowered = jf.lower(*args)
    elif kind == "prefill":
        jf, args = build_dryrun_prefill(cfg, mesh, shape)
        lowered = jf.lower(*args)
    else:
        # serving FSDP (= expert-parallel weight sharding over data axes)
        # only helps MoE archs; ZeRO-3 gathering hurts dense serving.
        jf, args = build_dryrun_decode(
            cfg, mesh, shape,
            fsdp=arch in FSDP_ARCHS and cfg.moe is not None)
        lowered = jf.lower(*args)
    t_lower = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0

    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    coll = parse_collectives(compiled.as_text())
    # price the collectives on the requested preset, or on the topology the
    # mesh itself implies (--cluster <preset> overrides the mesh bridge)
    spec = get_preset(cluster) if cluster else cluster_from_mesh(mesh)
    tp_degree = int(mesh.shape.get("model", 1))
    result["cluster"] = collective_cost_model(
        coll, spec, streams=streams, tp_degree=tp_degree,
        keep_timeline=keep_timeline)
    # first-class dep-coupled TP pricing next to the contention block's
    # background average (mirrors the cluster.pp block; DESIGN.md Sec. 14)
    tpb = tp_cost_model(coll, spec, tp_degree, float(ca.get("flops", 0.0)),
                        streams=streams, keep_timeline=keep_timeline)
    if tpb is not None:
        result["cluster"]["tp"] = tpb
    if pp is not None:
        result["cluster"]["pp"] = pipeline_cost_model(
            coll, spec, pp, float(ca.get("flops", 0.0)),
            streams=streams, keep_timeline=keep_timeline)
    result.update({
        "kind": kind,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        "transcendentals": float(ca.get("transcendentals", 0.0)),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
        },
        "collectives": coll,
        "grad_accum": GRAD_ACCUM.get(arch, 1) if kind == "train" else None,
        "mode": ("fsdp_tp" if arch in FSDP_ARCHS else "ddp_tp")
                if kind == "train" else "auto",
    })
    if verbose:
        peak = (ma.argument_size_in_bytes + ma.temp_size_in_bytes) / 2**30
        print(f"  {arch} x {shape} x {mesh_name}: compiled OK "
              f"(lower {t_lower:.1f}s compile {t_compile:.1f}s, "
              f"args+temp {peak:.2f} GiB/dev, "
              f"flops {result['flops']:.3e}, "
              f"ici {coll['ici_traffic_bytes']:.3e} B)")
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--cluster", default=None, choices=list_presets(),
                    help="cluster preset to price collectives on; "
                         "default: derived from the mesh via "
                         "cluster_from_mesh")
    ap.add_argument("--streams", type=int, default=None,
                    help="price the AllReduce set under N concurrent event-"
                         "engine streams next to the serialized channel "
                         "(with --plan: overrides the artifact's recorded "
                         "width, including an explicit 1 for serialized "
                         "pricing; default: the recorded width)")
    ap.add_argument("--timeline", action="store_true",
                    help="print (and embed) the contended comm schedule as "
                         "8-tuple records (kind, bucket, chunk, "
                         "traffic_class, algo, level, start, end): kind is "
                         "the phase ('allreduce' / 'reduce_scatter' / "
                         "'all_gather', hierarchical legs prefixed per "
                         "level; in-kernel fused buckets carry a 'fused_' "
                         "prefix), bucket/chunk index the job, "
                         "traffic_class is 'dp'|'tp'|'pp'|'bg', algo the "
                         "collective algorithm, level the link-level name, "
                         "start/end seconds from iteration start (needs "
                         "--streams > 1); when the module carries TP "
                         "collectives, also the cluster.tp block's "
                         "dep-coupled schedule — tp-class records are "
                         "per-layer activation collectives gated on the "
                         "compute that produces them, interleaved with "
                         "the compute spans and dp-class gradient "
                         "records; with --pp-stages also the unified "
                         "compute+p2p+grad records and the PP bubble")
    ap.add_argument("--pp-stages", type=int, default=None,
                    help="price the step under a 1F1B pipeline schedule "
                         "with this many stages (adds a cluster.pp block)")
    ap.add_argument("--pp-microbatches", type=int, default=8,
                    help="microbatches per iteration for --pp-stages "
                         "(default 8)")
    ap.add_argument("--pp-schedule", default=SCHED_1F1B,
                    choices=list(SCHEDULES),
                    help="pipeline schedule family (default 1f1b)")
    ap.add_argument("--pp-interleave", type=int, default=1,
                    help="virtual-stage chunks per device for "
                         "interleaved_1f1b (default 1)")
    ap.add_argument("--plan", default=None, metavar="FILE",
                    help="price a saved repro.plan artifact instead of "
                         "compiling archs (no re-trace, no re-search); "
                         "--cluster overrides the recorded topology, "
                         "--streams the engine width")
    ap.add_argument("--serve-plan", default=None, metavar="FILE",
                    help="price a saved repro.serving_plan artifact "
                         "(decode-step lowering under its recorded "
                         "workload) instead of compiling archs; --cluster "
                         "overrides the recorded topology")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    if args.serve_plan:
        result = price_serving_plan(args.serve_plan, cluster=args.cluster,
                                    out_dir=args.out)
        diff = result["pricing"].get("cluster_fingerprint_diff")
        if diff:
            print(f"CLUSTER MISMATCH: serve-plan {args.serve_plan} was "
                  f"searched against a different topology than --cluster "
                  f"{args.cluster} ({len(diff)} field(s) differ; "
                  f"first: {diff[0]})")
            raise SystemExit(1)
        return

    if args.plan:
        result = price_plan(args.plan, cluster=args.cluster,
                            streams=args.streams, out_dir=args.out)
        diff = result["pricing"].get("cluster_fingerprint_diff")
        if diff:
            print(f"CLUSTER MISMATCH: plan {args.plan} was searched "
                  f"against a different topology than --cluster "
                  f"{args.cluster} ({len(diff)} field(s) differ; "
                  f"first: {diff[0]})")
            raise SystemExit(1)
        return

    pp = None
    if args.pp_stages:
        pp = PipelineSchedule(n_stages=args.pp_stages,
                              n_microbatches=args.pp_microbatches,
                              schedule=args.pp_schedule,
                              interleave=args.pp_interleave)

    archs = ARCHS if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    os.makedirs(args.out, exist_ok=True)
    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}__{shape}__{'pod2x16x16' if mp else 'pod16x16'}"
                path = os.path.join(args.out, tag + ".json")
                try:
                    res = dryrun_one(arch, shape, mp, cluster=args.cluster,
                                     streams=args.streams or 1,
                                     keep_timeline=args.timeline, pp=pp)
                except Exception as e:  # a failure here is a bug in the system
                    traceback.print_exc()
                    failures.append(tag)
                    res = {"arch": arch, "shape": shape, "error": str(e)}
                if args.timeline:
                    rec = (res.get("cluster", {}).get("contention", {})
                           .get("timeline"))
                    if rec:
                        print(f"  {tag} comm timeline "
                              f"(kind, bucket, chunk, class, algo, level, "
                              f"start, end):")
                        for e in rec:
                            print(f"    {tuple(e)}")
                    tpb = res.get("cluster", {}).get("tp", {})
                    if tpb.get("timeline"):
                        print(f"  {tag} dep-coupled tp timeline "
                              f"(kind, ref/bucket, *, class, resource, "
                              f"start, end):")
                        for e in tpb["timeline"]:
                            print(f"    {tuple(e)}")
                    if tpb:
                        print(f"  {tag} tp coupling: "
                              f"{tpb['n_layers']} layers x "
                              f"{tpb['total_tp_bytes']:.3e} B total, grad "
                              f"finish alone "
                              f"{tpb['grad_finish_alone_s']*1e3:.3f} ms, "
                              f"coupled "
                              f"{tpb['grad_finish_coupled_s']*1e3:.3f} ms, "
                              f"background model "
                              f"{tpb['grad_finish_background_s']*1e3:.3f} ms")
                    ppb = res.get("cluster", {}).get("pp", {})
                    if ppb.get("timeline"):
                        print(f"  {tag} unified pp timeline "
                              f"(kind, ref, *, class, resource, "
                              f"start, end):")
                        for e in ppb["timeline"]:
                            print(f"    {tuple(e)}")
                    if ppb:
                        bub = ppb["bubble"]
                        print(f"  {tag} pp bubble: "
                              f"fraction {bub['fraction']:.3f} over "
                              f"{ppb['n_stages']} stages x "
                              f"{ppb['n_microbatches']} microbatches "
                              f"(compute finish "
                              f"{ppb['compute_finish_s']*1e3:.3f} ms, "
                              f"iteration {ppb['iteration_s']*1e3:.3f} ms)")
                with open(path, "w") as f:
                    json.dump(res, f, indent=1)
    if failures:
        print(f"FAILURES ({len(failures)}): {failures}")
        raise SystemExit(1)
    print("all dry-runs passed")


if __name__ == "__main__":
    main()
