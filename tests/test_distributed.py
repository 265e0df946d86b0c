"""Distributed-runtime integration tests.

These need >1 XLA host device, so they run in subprocesses that set
``--xla_force_host_platform_device_count`` before importing jax (the main
pytest process keeps the default single device for the smoke tests).
"""
import json
import os
import subprocess
import sys

import jax
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(code: str, devices: int = 8, timeout: int = 900) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return proc.stdout


@pytest.mark.slow
def test_ddp_tp_step_matches_single_device():
    """Bucketed-psum DisCo enactment on a 2x2 mesh computes the same loss
    trajectory as plain single-device training."""
    out = run_sub("""
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.configs import get_config
from repro.models import stacked as ST
from repro.distributed.train_step import (GradSyncStrategy, build_train_step,
                                          jit_train_step)
from repro.distributed import sharding as SH
from repro.optim import adamw, apply_updates, clip_by_global_norm
from repro.data.pipeline import materialize_batch

cfg = get_config("qwen2-0.5b").reduced()
key = jax.random.PRNGKey(0)
params = ST.init_params(key, cfg)
init, update = adamw(1e-3, weight_decay=0.01)
opt = init(jax.tree.map(lambda p: p.astype(jnp.float32), params))
batch = materialize_batch(cfg, 8, 32, seed=0)

# single-device reference (same clip + optimizer math)
def ref_step(params, opt, batch):
    loss, grads = jax.value_and_grad(
        lambda p: ST.loss_fn(p, cfg, batch, remat=True))(params)
    grads, _ = clip_by_global_norm(grads, 1.0)
    updates, opt = update(grads, opt, params)
    return apply_updates(params, updates), opt, loss

p_ref, o_ref = params, opt
ref_losses = []
for i in range(3):
    p_ref, o_ref, l = ref_step(p_ref, o_ref, batch)
    ref_losses.append(float(l))

mesh = make_mesh((2, 2), ("data", "model"))
strat = GradSyncStrategy.size_capped(params, 1 << 16)
step = build_train_step(cfg, mesh, mode="ddp_tp", strategy=strat,
                        grad_accum=1, remat=True, lr=1e-3)
jf = jit_train_step(step, cfg, mesh, params, opt,
                    {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                     for k, v in batch.items()})
p, o = params, opt
dist_losses = []
for i in range(3):
    p, o, m = jf(p, o, batch)
    dist_losses.append(float(m["loss"]))
print("REF", ref_losses)
print("DIST", dist_losses)
np.testing.assert_allclose(ref_losses, dist_losses, rtol=2e-4, atol=2e-4)
print("MATCH_OK")
""")
    assert "MATCH_OK" in out


@pytest.mark.slow
def test_bucketing_strategies_equivalent():
    """per-tensor / capped / single-bucket gradient sync produce identical
    gradients (tensor fusion must not change the math — paper Sec. 2.5)."""
    out = run_sub("""
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.configs import get_config
from repro.models import stacked as ST
from repro.distributed.train_step import GradSyncStrategy, build_train_step, jit_train_step
from repro.optim import adamw
from repro.data.pipeline import materialize_batch

cfg = get_config("tinyllama-1.1b").reduced()
key = jax.random.PRNGKey(0)
params = ST.init_params(key, cfg)
init, _ = adamw(1e-3)
opt = init(jax.tree.map(lambda p: p.astype(jnp.float32), params))
batch = materialize_batch(cfg, 8, 32, seed=0)
mesh = make_mesh((4, 2), ("data", "model"))
specs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in batch.items()}
results = []
for strat in (GradSyncStrategy.per_tensor(params),
              GradSyncStrategy.size_capped(params, 1 << 14),
              GradSyncStrategy.single_bucket(params)):
    step = build_train_step(cfg, mesh, mode="ddp_tp", strategy=strat, lr=1e-3)
    jf = jit_train_step(step, cfg, mesh, params, opt, specs)
    # donate_argnums consumes inputs: pass fresh copies each round
    p_in = jax.tree.map(jnp.array, params)
    o_in = jax.tree.map(jnp.array, opt)
    p2, _, m = jf(p_in, o_in, batch)
    results.append((float(m["loss"]), float(m["grad_norm"])))
print(results)
for a, b in zip(results, results[1:]):
    np.testing.assert_allclose(a, b, rtol=1e-4)
print("EQUIV_OK")
""")
    assert "EQUIV_OK" in out


@pytest.mark.slow
def test_rs_ag_bucket_lowering_matches_allreduce():
    """A ZeRO-3 ``rs_ag`` bucket enacts as reduce-scatter + all-gather in
    the compiled HLO (``layout="dp"``) and computes losses identical to the
    fused AllReduce path, in the TP layout too."""
    out = run_sub("""
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.configs import get_config
from repro.models import stacked as ST
from repro.distributed.train_step import (GradSyncStrategy, build_train_step,
                                          jit_train_step)
from repro.launch.dryrun import parse_collectives
from repro.optim import adamw
from repro.data.pipeline import materialize_batch

cfg = get_config("tinyllama-1.1b").reduced()
key = jax.random.PRNGKey(0)
params = ST.init_params(key, cfg)
init, _ = adamw(1e-3)
opt = init(jax.tree.map(lambda p: p.astype(jnp.float32), params))
batch = materialize_batch(cfg, 8, 32, seed=0)
mesh = make_mesh((4, 2), ("data", "model"))
specs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in batch.items()}
base = GradSyncStrategy.size_capped(params, 1 << 14)
results = {}
for kind in ("ar", "rs_ag"):
    strat = GradSyncStrategy(base.buckets, comms=[kind] * len(base.buckets))
    step = build_train_step(cfg, mesh, mode="ddp_tp", strategy=strat,
                            lr=1e-3, layout="dp")
    jf = jit_train_step(step, cfg, mesh, params, opt, specs, layout="dp")
    lowered = jf.lower(params, opt, specs)
    coll = parse_collectives(lowered.compile().as_text())
    p_in = jax.tree.map(jnp.array, params)
    o_in = jax.tree.map(jnp.array, opt)
    _, _, m = jf(p_in, o_in, batch)
    results[kind] = (float(m["loss"]), float(m["grad_norm"]), coll["per_op"])
print({k: v[:2] for k, v in results.items()})
np.testing.assert_allclose(results["ar"][:2], results["rs_ag"][:2], rtol=1e-4)
# the rs_ag lowering really emits RS+AG pairs where the ar path psums
assert results["rs_ag"][2].get("reduce-scatter", {}).get("count", 0) > 0
assert results["rs_ag"][2].get("all-gather", {}).get("count", 0) > 0
assert results["ar"][2].get("reduce-scatter", {}).get("count", 0) == 0

# TP layout: the pair lowers inside the nested model-manual region
strat = GradSyncStrategy(base.buckets, comms=["rs_ag"] * len(base.buckets))
step = build_train_step(cfg, mesh, mode="ddp_tp", strategy=strat, lr=1e-3)
jf = jit_train_step(step, cfg, mesh, params, opt, specs)
p_in = jax.tree.map(jnp.array, params)
o_in = jax.tree.map(jnp.array, opt)
_, _, m = jf(p_in, o_in, batch)
np.testing.assert_allclose(float(m["loss"]), results["ar"][0], rtol=2e-4)
print("RS_AG_OK")
""")
    assert "RS_AG_OK" in out


@pytest.mark.slow
def test_chunked_bucket_enactment():
    """A bucket with ``chunks=k`` enacts as k per-chunk collectives: the
    lowered program carries exactly ``k`` psums (or RS+AG pairs) per bucket
    while the loss and grad norm stay bit-identical (each element's
    reduction is unchanged, only the op it rides in shrinks).

    XLA's all-reduce combiner then merges psums: the compiled program
    carries fewer all-reduce ops than were issued (on the CPU, one tuple
    all-reduce for every bucket and chunk), moving the same bytes.  So the
    searched bucket schedule does not survive compilation for ``ar``
    buckets; reduce-scatter / all-gather pairs do survive here."""
    out = run_sub("""
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.configs import get_config
from repro.models import stacked as ST
from repro.distributed.train_step import (GradSyncStrategy, build_train_step,
                                          jit_train_step)
from repro.launch.dryrun import parse_collectives
from repro.optim import adamw
from repro.data.pipeline import materialize_batch

cfg = get_config("tinyllama-1.1b").reduced()
key = jax.random.PRNGKey(0)
params = ST.init_params(key, cfg)
init, _ = adamw(1e-3)
opt = init(jax.tree.map(lambda p: p.astype(jnp.float32), params))
batch = materialize_batch(cfg, 8, 32, seed=0)
mesh = make_mesh((4, 2), ("data", "model"))
specs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in batch.items()}
base = GradSyncStrategy.size_capped(params, 1 << 14)
B = len(base.buckets)
res = {}
for kind, k in (("ar", 1), ("ar", 4), ("rs_ag", 2)):
    strat = GradSyncStrategy(base.buckets, comms=[kind] * B,
                             chunks=[k] * B)
    step = build_train_step(cfg, mesh, mode="ddp_tp", strategy=strat,
                            lr=1e-3, layout="dp")
    jf = jit_train_step(step, cfg, mesh, params, opt, specs, layout="dp")
    lowered = jf.lower(params, opt, specs)
    issued = parse_collectives(lowered.as_text(dialect="hlo"))["per_op"]
    compiled = parse_collectives(lowered.compile().as_text())["per_op"]
    p_in = jax.tree.map(jnp.array, params)
    o_in = jax.tree.map(jnp.array, opt)
    _, _, m = jf(p_in, o_in, batch)
    res[(kind, k)] = (float(m["loss"]), float(m["grad_norm"]),
                      {op: (d["count"], d["bytes"]) for op, d in issued.items()},
                      {op: (d["count"], d["bytes"]) for op, d in compiled.items()})
print(res)
ar1, ar4, rs2 = res[("ar", 1)], res[("ar", 4)], res[("rs_ag", 2)]
# issued: one collective per chunk per bucket (+ the loss pmean) ...
assert ar1[2]["all-reduce"][0] == B + 1, (ar1[2], B)
assert ar4[2]["all-reduce"][0] == 4 * B + 1, (ar4[2], B)
assert rs2[2]["reduce-scatter"][0] == 2 * B, rs2[2]
assert rs2[2]["all-gather"][0] == 2 * B, rs2[2]
# ... chunking moves the same bytes ...
assert ar1[2]["all-reduce"][1] == ar4[2]["all-reduce"][1], (ar1, ar4)
# ... the combiner merges the psums but keeps their bytes, and leaves the
# RS+AG pairs alone
for r in (ar1, ar4):
    assert r[3]["all-reduce"][0] < r[2]["all-reduce"][0], r
    assert r[3]["all-reduce"][1] == r[2]["all-reduce"][1], r
assert rs2[3]["reduce-scatter"] == rs2[2]["reduce-scatter"], rs2
assert rs2[3]["all-gather"] == rs2[2]["all-gather"], rs2
# ... the psum path is bit-identical chunked vs whole, and the chunked
# RS+AG split matches to collective-reassociation tolerance
assert res[("ar", 4)][:2] == res[("ar", 1)][:2], res
np.testing.assert_allclose(res[("rs_ag", 2)][:2], res[("ar", 1)][:2],
                           rtol=1e-4)
print("CHUNKED_OK")
""")
    assert "CHUNKED_OK" in out


@pytest.mark.slow
def test_fused_bucket_enactment():
    """A ``fused``-flagged bucket enacts through the Pallas fused-sync
    kernel path (pack epilogue -> per-chunk reduce-scatter + all-gather ->
    unpack prologue) in the fully-manual ``layout="dp"`` region: the
    compiled HLO carries one RS/AG pair per chunk per bucket and the loss /
    grad norm match the plain AllReduce path to collective-reassociation
    tolerance.  In the TP layout the kernels run inside the nested
    model-manual region with the same numerics."""
    out = run_sub("""
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.configs import get_config
from repro.models import stacked as ST
from repro.distributed.train_step import (GradSyncStrategy, build_train_step,
                                          jit_train_step)
from repro.launch.dryrun import parse_collectives
from repro.optim import adamw
from repro.data.pipeline import materialize_batch

cfg = get_config("tinyllama-1.1b").reduced()
key = jax.random.PRNGKey(0)
params = ST.init_params(key, cfg)
init, _ = adamw(1e-3)
opt = init(jax.tree.map(lambda p: p.astype(jnp.float32), params))
batch = materialize_batch(cfg, 8, 32, seed=0)
mesh = make_mesh((4, 2), ("data", "model"))
specs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in batch.items()}
base = GradSyncStrategy.size_capped(params, 1 << 14)
B = len(base.buckets)
res = {}
for tag, kind, fused, k in (("ar", "ar", 0, 1),
                            ("rs_ag", "rs_ag", 0, 1),
                            ("fused", "ar", 1, 1),
                            ("fused_c2", "ar", 1, 2)):
    strat = GradSyncStrategy(base.buckets, comms=[kind] * B,
                             fused=[fused] * B, chunks=[k] * B)
    step = build_train_step(cfg, mesh, mode="ddp_tp", strategy=strat,
                            lr=1e-3, layout="dp")
    jf = jit_train_step(step, cfg, mesh, params, opt, specs, layout="dp")
    coll = parse_collectives(jf.lower(params, opt, specs).compile().as_text())
    p_in = jax.tree.map(jnp.array, params)
    o_in = jax.tree.map(jnp.array, opt)
    _, _, m = jf(p_in, o_in, batch)
    res[tag] = (float(m["loss"]), float(m["grad_norm"]),
                {op: d["count"] for op, d in coll["per_op"].items()})
print({t: v[:2] for t, v in res.items()})
# the fused kernel path really lowers to RS+AG pairs, one per chunk ...
for tag, k in (("fused", 1), ("fused_c2", 2)):
    per_op = res[tag][2]
    assert per_op.get("reduce-scatter", 0) == k * B, (tag, per_op, B)
    assert per_op.get("all-gather", 0) >= k * B, (tag, per_op, B)
assert res["ar"][2].get("reduce-scatter", 0) == 0, res["ar"][2]
# ... and the enacted numerics match the psum and ZeRO-3 paths
np.testing.assert_allclose(res["fused"][:2], res["ar"][:2], rtol=1e-4)
np.testing.assert_allclose(res["fused"][:2], res["rs_ag"][:2], rtol=1e-4)
np.testing.assert_allclose(res["fused_c2"][:2], res["ar"][:2], rtol=1e-4)

# TP layout: the kernel path keeps numerics identical to AllReduce
strat = GradSyncStrategy(base.buckets, comms=["ar"] * B, fused=[1] * B)
step = build_train_step(cfg, mesh, mode="ddp_tp", strategy=strat, lr=1e-3)
jf = jit_train_step(step, cfg, mesh, params, opt, specs)
p_in = jax.tree.map(jnp.array, params)
o_in = jax.tree.map(jnp.array, opt)
_, _, m = jf(p_in, o_in, batch)
np.testing.assert_allclose(float(m["loss"]), res["ar"][0], rtol=2e-4)
print("FUSED_OK")
""")
    assert "FUSED_OK" in out


@pytest.mark.slow
def test_vocab_parallel_matches_dense():
    out = run_sub("""
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.models import vocab_parallel as VP
mesh = make_mesh((2, 4), ("data", "model"))
key = jax.random.PRNGKey(0)
V, D, B, S = 64, 16, 2, 8
embed = jax.random.normal(key, (V, D))
toks = jax.random.randint(key, (B, S), 0, V)
x = jax.jit(lambda e, t: VP.embed_lookup(e, t, mesh))(embed, toks)
np.testing.assert_allclose(np.asarray(x), np.asarray(embed[toks]), rtol=1e-5)
# CE
head = jax.random.normal(key, (D, V))
h = jax.random.normal(key, (B, S, D))
w = jnp.ones((B, S))
ce, cnt = jax.jit(lambda *a: VP.ce_chunk(*a, mesh, transpose_head=False))(
    h, head, toks, w)
logits = (h @ head).astype(jnp.float32)
logz = jax.nn.logsumexp(logits, -1)
gold = jnp.take_along_axis(logits, toks[..., None], -1)[..., 0]
ref = float(jnp.sum(logz - gold))
np.testing.assert_allclose(float(ce), ref, rtol=1e-5)
assert float(cnt) == B * S
# grads flow (jit: the shard_map transpose needs the jit context to
# resolve auto-axis specs)
g = jax.jit(jax.grad(lambda hh: VP.ce_chunk(hh, head, toks, w, mesh,
                                            transpose_head=False)[0]))(h)
gref = jax.grad(lambda hh: jnp.sum(
    jax.nn.logsumexp((hh @ head).astype(jnp.float32), -1)
    - jnp.take_along_axis((hh @ head).astype(jnp.float32),
                          toks[..., None], -1)[..., 0]))(h)
np.testing.assert_allclose(np.asarray(g), np.asarray(gref), rtol=1e-4,
                           atol=1e-5)
print("VP_OK")
""")
    assert "VP_OK" in out


@pytest.mark.slow
def test_dryrun_reduced_mesh():
    """End-to-end dryrun machinery on a small mesh + reduced config."""
    out = run_sub("""
import jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.configs import get_config
from repro.models import stacked as ST
from repro.distributed.train_step import build_train_step, jit_train_step
from repro.optim import adamw
from repro.launch.dryrun import parse_collectives
from repro.data.pipeline import make_batch_specs

cfg = get_config("deepseek-v2-lite-16b").reduced()
mesh = make_mesh((4, 2), ("data", "model"))
params = jax.eval_shape(lambda: ST.init_params(jax.random.PRNGKey(0), cfg))
init, _ = adamw(1e-3)
opt = jax.eval_shape(lambda: init(jax.tree.map(
    lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), params)))
specs = make_batch_specs(cfg, 8, 64)
step = build_train_step(cfg, mesh, mode="ddp_tp")
jf = jit_train_step(step, cfg, mesh, params, opt, specs)
lowered = jf.lower(params, opt, specs)
compiled = lowered.compile()
assert compiled.cost_analysis().get("flops", 0) > 0
coll = parse_collectives(compiled.as_text())
assert coll["per_op"].get("all-reduce", {}).get("count", 0) > 0
print("DRYRUN_OK", coll["per_op"]["all-reduce"]["count"])
""")
    assert "DRYRUN_OK" in out


def test_strategy_save_load(tmp_path):
    from repro.distributed.train_step import GradSyncStrategy

    s = GradSyncStrategy([[0, 1], [2], [3, 4, 5]], barriers=True,
                         comms=["ar", "rs_ag", "ar"], chunks=[1, 2, 4])
    p = str(tmp_path / "s.json")
    s.save(p)
    s2 = GradSyncStrategy.load(p)
    assert s2.buckets == s.buckets and s2.barriers is True
    assert s2.comms == s.comms and s2.comm_kind(1) == "rs_ag"
    assert s2.chunks == s.chunks and s2.chunk_count(2) == 4
    # legacy strategy files (no comms/chunks) default to one fused AllReduce
    s3 = GradSyncStrategy([[0]])
    p3 = str(tmp_path / "legacy.json")
    s3.save(p3)
    loaded = GradSyncStrategy.load(p3)
    assert loaded.comm_kind(0) == "ar" and loaded.chunk_count(0) == 1


def test_compile_cache_placement(tmp_path):
    """The entry points' compile cache: ``$JAX_COMPILATION_CACHE_DIR`` when
    set (left to JAX), else the fixed ``<checkout>/.jax_cache``."""
    code = """
import os, jax
from repro.launch import train as T
path = T.use_compile_cache()
print("CACHE", path, jax.config.jax_compilation_cache_dir)
"""
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    outs = []
    for extra in ({}, {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)}):
        proc = subprocess.run([sys.executable, "-c", code],
                              env=dict(env, **extra), capture_output=True,
                              text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr[-3000:]
        outs.append(proc.stdout.split("CACHE ")[1].split())
    checkout = os.path.abspath(os.path.join(SRC, ".."))
    assert outs[0] == [os.path.join(checkout, ".jax_cache")] * 2, outs
    assert outs[1] == [str(tmp_path)] * 2, outs


def test_strategy_from_fusion_graph():
    import jax.numpy as jnp
    from repro.core import profile_graph, trace_grad_graph
    from repro.distributed.train_step import GradSyncStrategy

    params = {"a": jnp.ones((8, 8)), "b": jnp.ones((8,)),
              "c": jnp.ones((8, 8))}

    def loss(p, x):
        return jnp.sum(jnp.tanh(x @ p["a"] + p["b"]) @ p["c"])

    g = profile_graph(trace_grad_graph(loss, params, jnp.ones((4, 8))))
    while g.merge_buckets(0, 1):
        pass
    g.set_bucket_comm(0, "rs_ag")
    g.set_bucket_chunks(0, 4)
    strat = GradSyncStrategy.from_fusion_graph(g, params)
    flat = sorted(i for b in strat.buckets for i in b)
    assert flat == [0, 1, 2]
    assert len(strat.buckets) == 1
    # the searched comm kind and chunk count ride along into enactment
    assert strat.comms == ["rs_ag"]
    assert strat.chunks == [4]


@pytest.mark.slow
def test_dp_layout_and_zero1():
    """layout='dp' (all-axes data parallel) and ZeRO-1 moment sharding both
    compile and train one step equal to the tp layout's loss."""
    out = run_sub("""
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.configs import get_config
from repro.models import stacked as ST
from repro.distributed.train_step import build_train_step, jit_train_step
from repro.optim import adamw
from repro.data.pipeline import materialize_batch

cfg = get_config("tinyllama-1.1b").reduced()
key = jax.random.PRNGKey(0)
params = ST.init_params(key, cfg)
init, _ = adamw(1e-3)
opt = init(jax.tree.map(lambda p: p.astype(jnp.float32), params))
batch = materialize_batch(cfg, 8, 32, seed=0)
mesh = make_mesh((4, 2), ("data", "model"))
specs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in batch.items()}
losses = {}
for name, kw in (("tp", {}), ("dp", {"layout": "dp"}),
                 ("tp_zero1", {"zero1": True})):
    step = build_train_step(cfg, mesh, mode="ddp_tp", lr=1e-3,
                            layout=kw.get("layout", "tp"))
    jf = jit_train_step(step, cfg, mesh, params, opt, specs,
                        layout=kw.get("layout", "tp"),
                        zero1=kw.get("zero1", False))
    p_in = jax.tree.map(jnp.array, params)
    o_in = jax.tree.map(jnp.array, opt)
    _, _, m = jf(p_in, o_in, batch)
    losses[name] = float(m["loss"])
print(losses)
vals = list(losses.values())
np.testing.assert_allclose(vals, [vals[0]] * len(vals), rtol=1e-4)
print("LAYOUTS_OK")
""")
    assert "LAYOUTS_OK" in out


def test_int8_kv_cache_decode_accuracy():
    import dataclasses

    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.models import stacked as ST

    cfg = dataclasses.replace(get_config("stablelm-1.6b").reduced(),
                              kv_cache_dtype="int8", dtype="float32")
    params = ST.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab)
    logits_full, _ = ST.forward(params, cfg, toks)
    caches = ST.init_cache(cfg, 2, 16)
    for leaf in jax.tree.leaves(caches):
        assert leaf.dtype in (jnp.int8, jnp.bfloat16, jnp.float32)
    errs = []
    for t in range(12):
        lg, caches = ST.decode_step(params, cfg, caches, toks[:, t],
                                    jnp.int32(t))
        errs.append(float(jnp.max(jnp.abs(lg - logits_full[:, t]))))
    assert max(errs) < 0.05, f"int8 cache decode error too large: {max(errs)}"
