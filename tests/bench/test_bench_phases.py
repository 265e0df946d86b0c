"""The phase split of the step (``bench/phases.py``): by hand on made-up
ops; on the test-size step's compiled HLO and a CPU profiler trace of it
on one device; its gradient-sync buckets on four CPU devices; and the
faults ``calibrate_faults.py`` reads, on a test-size cell of four."""
import glob
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from bench import phases, spec
from bench_cells import REPO, TINY

NS = 1e-9
J = "jit(step)/shard_map/"
# instruction: (opcode, shape, op_name); XLA names an all-reduce after the
# primitive that made it (psum.2), so only the opcode says it is one
INSTRS = {
    "while.1": ("while", "()", J + "transpose(jvp())/while"),
    "fusion.1": ("fusion", "f32[8]", J + "transpose(jvp())/while/body/"
                 "checkpoint/attention/dot"),
    "fusion.2": ("fusion", "f32[8]", J + "transpose(jvp())/while/body/"
                 "checkpoint/rematted_computation/mlp/dot"),
    "fusion.3": ("fusion", "f32[8]", J + "jvp(embed)/gather"),
    "fusion.4": ("fusion", "f32[8]", J + "jvp()/while/body/attention/exp"),
    "all-reduce-start.1": ("all-reduce-start", "f32[8]",
                           J + "grad_sync/bucket_0/psum"),
    "all-reduce-done.1": ("all-reduce-done", "f32[8]",
                          J + "grad_sync/bucket_0/psum"),
    "psum.2": ("all-reduce", "f32[8]", J + "grad_sync/bucket_1/psum"),
    "fusion.5": ("fusion", "f32[8]", J + "grad_sync/bucket_1/concatenate"),
    "fusion.6": ("fusion", "f32[8]", J + "optimizer/mul"),
    "copy.1": ("copy", "f32[8]", "jit(step)"),
}
# One step of 100 ns: forward 0-20 (embed, then attention), a backward
# while 20-70 holding attention 25-35 and recomputed mlp 40-50; bucket 0's
# all-reduce runs alone 70-72 and 80-85 around the pack of bucket 1
# (72-80); bucket 1's all-reduce 85-95 is hidden by the optimizer from 88;
# a copy 95-100.
OPS = [("fusion.3", 0, 5), ("fusion.4", 5, 20), ("while.1", 20, 70),
       ("fusion.1", 25, 35), ("fusion.2", 40, 50),
       ("all-reduce-start.1", 70, 72), ("fusion.5", 72, 80),
       ("all-reduce-done.1", 80, 85), ("psum.2", 85, 95),
       ("fusion.6", 88, 95), ("copy.1", 95, 100)]


def test_phase_times_by_hand():
    r = phases.phase_times(OPS, INSTRS, 0, 100, 1)
    assert r["forward_s"] == pytest.approx(20 * NS)
    assert r["backward_s"] == pytest.approx(50 * NS)
    assert r["recompute_s"] == pytest.approx(10 * NS)
    assert r["attention_s"] == pytest.approx(25 * NS)
    # the all-reduce of bucket 1 holds the optimizer's op for 7 ns
    assert r["optimizer_s"] == pytest.approx(7 * NS)
    assert r["grad_sync_s"] == pytest.approx(18 * NS)
    assert r["unattributed_s"] == pytest.approx(5 * NS)
    # exposed: 70-72 and 80-88 (fusion.5 hides 72-80, fusion.6 88-95)
    assert r["sync_exposed_s"] == pytest.approx(10 * NS)
    assert r["busy_s"] == pytest.approx(100 * NS)
    assert r["buckets"][0] == pytest.approx(
        {"self": 7 * NS, "collective": 7 * NS, "exposed": 7 * NS})
    assert r["buckets"][1] == pytest.approx(
        {"self": 11 * NS, "collective": 10 * NS, "exposed": 3 * NS})


def test_phase_times_per_step_and_window():
    two = OPS + [(n, s + 100, e + 100) for n, s, e in OPS]
    r = phases.phase_times(two, INSTRS, 0, 200, 2)
    assert r["backward_s"] == pytest.approx(50 * NS)
    only = phases.phase_times(two, INSTRS, 100, 200, 1)
    assert only["forward_s"] == pytest.approx(20 * NS)


@pytest.mark.parametrize("op_name,phase", [
    (J + "jvp()/while/body/closed_call/mlp/dot_general", "forward"),
    (J + "transpose(jvp(head))/while/body", "backward"),
    (J + "attention/le", "forward"),
    (J + "grad_sync/bucket_3/psum", "grad_sync"),
    ("optimizer/reduce_sum", "optimizer"),
    ("jit(step)", "unattributed"),
    (J + "transpose(jvp())/while/body/attentions", "backward"),
])
def test_phase_of(op_name, phase):
    assert phases.phase_of(op_name) == phase


def test_name_gap_prefers_the_innermost_span():
    spans = [("bench.block", 0, 100), ("python.gc", 40, 90),
             ("train.step_batch", 95, 120)]
    assert phases.name_gap(spans, 50, 80) == "python.gc"
    assert phases.name_gap(spans, 85, 100) == "bench.block"
    assert phases.name_gap(spans, 96, 130) == "train.step_batch"
    assert phases.name_gap([], 0, 1) == "none"


HLO = textwrap.dedent("""\
    HloModule jit_step

    %fused_computation.3 (param_0: f32[4]) -> f32[4] {
      %param_0 = f32[4]{0} parameter(0)
      ROOT %exp.1 = f32[4]{0} exponential(%param_0), metadata={op_name="jit(step)/jvp()/attention/exp"}
    }

    ENTRY %main.9 (Arg_0.1: f32[4]) -> (f32[4], f32[2,8]) {
      %Arg_0.1 = f32[4]{0} parameter(0), metadata={op_name="params"}
      %fusion.3 = f32[4]{0} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.3
      %copy.2 = f32[4]{0} copy(%fusion.3)
      %all-reduce.7 = (f32[4]{0}, bf16[2,8]{1,0}) all-reduce(%copy.2, %copy.2), channel_id=1, metadata={op_name="jit(step)/grad_sync/bucket_2/psum"}
      %gte.1 = f32[4]{0} get-tuple-element(%all-reduce.7), index=0
      ROOT %tuple.4 = (f32[4]{0}, f32[2,8]{1,0}) tuple(%gte.1, %gte.1)
    }
    """)


def test_instructions_read_hlo_text():
    ins = phases.instructions(HLO)
    assert ins["fusion.3"] == ("fusion", "f32[4]{0}",
                               "jit(step)/jvp()/attention/exp")
    # no metadata: the operand's, else the first user's
    assert ins["copy.2"][2] == "jit(step)/jvp()/attention/exp"
    assert ins["Arg_0.1"][2] == "jit(step)/jvp()/attention/exp"
    assert ins["all-reduce.7"][:2] == ("all-reduce",
                                       "(f32[4]{0}, bf16[2,8]{1,0})")
    assert phases.op_scopes(HLO)["gte.1"] == ins["gte.1"][2]
    assert phases.bucket_bytes(ins) == {2: 4 * 4 + 2 * 16}
    assert phases.shape_bytes("(f32[2,3], pred[], s32[5]{0})") == 24 + 1 + 20


ALLOWED = {"parameter", "tuple", "get-tuple-element", "constant", "copy",
           "bitcast"}


def _tiny_step(n):
    from repro.distributed.train_step import GradSyncStrategy
    from repro.launch import train as T
    from repro.launch.mesh import make_mesh
    from repro.models.config import ModelConfig
    from repro.optim import adamw

    cfg = ModelConfig(**TINY["tiny-gqa"])
    mesh = make_mesh((n, 1), ("data", "model"), devices=jax.devices()[:n])
    params = T.init_params(cfg, 0)
    optimizer = adamw(1e-3)
    opt = optimizer[0](jax.tree.map(lambda p: p.astype(jnp.float32), params))
    batch = {"tokens": jnp.zeros((2 * n, 64), jnp.int32)}
    strat = GradSyncStrategy.size_capped(params, 2**17)
    step, _ = T.compile_step(cfg, mesh, strat, params, opt, batch, optimizer)
    return step, params, opt, batch


def test_phases_of_the_test_size_step(tmp_path):
    """One device: the traced step's ops fall into forward, backward,
    recompute, attention and optimizer; left unattributed are only ops
    that move or name data, and the loss's mean over the data axis."""
    from jax.profiler import ProfileData

    step, params, opt, batch = _tiny_step(1)
    ins = phases.instructions(step.as_text())
    params, opt, _ = jax.block_until_ready(step(params, opt, batch))
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready(step(params, opt, batch))
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    ops = []
    for plane in ProfileData.from_file(path).planes:
        for ln in plane.lines:
            for e in ln.events:
                st = dict(e.stats)
                if str(st.get("hlo_module", "")).startswith("jit_step") \
                        and "hlo_op" in st:
                    ops.append((st["hlo_op"], e.start_ns,
                                e.start_ns + e.duration_ns))
    assert ops and all(n in ins for n, _, _ in ops)
    lo, hi = min(o[1] for o in ops), max(o[2] for o in ops) + 1
    r = phases.phase_times(ops, ins, lo, hi, 1)
    for k in ("forward", "backward", "recompute", "attention", "optimizer"):
        assert r[f"{k}_s"] > 0, k
    left = {n for n, _, _ in ops if phases.phase_of(ins[n][2])
            == "unattributed"}
    odd = {n: ins[n] for n in left if ins[n][0] not in ALLOWED
           and not (ins[n][0] == "all-reduce"
                    and ins[n][2] in ("jit(step)/psum", "jit(step)/pmean"))}
    assert not odd


CHILD = textwrap.dedent("""
    import json, pathlib, sys
    sys.path[:0] = sys.argv[2:]
    import jax
    from bench import calibrate_faults, phases, spec
    from repro.launch import train as T
    import test_bench_phases as tp
    T.use_compile_cache = lambda: "off"
    step = tp._tiny_step(4)[0]
    ins = phases.instructions(step.as_text())
    coll = {op: k for k, (kind, _, op) in ins.items()
            if kind.startswith("all-reduce") and "grad_sync/bucket_" in op}
    root = pathlib.Path(sys.argv[1])
    cell = spec.load_cell(root, "tiny-gqa.dp4.b4", root / "bench")
    lines = calibrate_faults.readings(cell, 3, jax.devices()[:4])
    print(json.dumps({"collectives": sorted(coll),
                      "bytes": phases.bucket_bytes(ins),
                      "faults": {x["kind"]: x["correct"] for x in lines}}))
""")


@pytest.fixture(scope="module")
def four_devices(tmp_path_factory):
    """The test-size step compiled for four CPU devices, and the faults of
    a test-size cell of four chips whose chip 0 holds one reference block
    (four rows a chip), in one child process."""
    from bench_cells import OPTIMIZER, TINY_LIMITS, add_tiny_cells

    root = pathlib.Path(add_tiny_cells(str(tmp_path_factory.mktemp("f4"))))
    b = root / "bench"
    (b / "traffic" / "tiny.b4x64.c4.json").write_text(json.dumps(
        {"batch_per_chip": 4, "seq_len": 64, "vocab_slice": 4096,
         "branching": 8, "jump": 0.05, "pool": 4, "strategy": "auto",
         "optimizer": OPTIMIZER, "checked_steps": 3, "trace_steps": 3,
         "reference_rows_per_block": 4}))
    (b / "limits" / "tiny-gqa.dp4.b4.json").write_text(
        json.dumps(TINY_LIMITS))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "tiny-gqa.dp4.b4",
                               "config": "tiny-gqa",
                               "traffic": "tiny.b4x64.c4", "chips": 4,
                               "why": "test size"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    here = os.path.dirname(__file__)
    p = subprocess.run([sys.executable, "-c", CHILD, str(root), str(root),
                        os.path.join(REPO, "src"), here],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_bucket_collectives_on_four_devices(four_devices):
    assert four_devices["collectives"]
    assert all(phases._BUCKET.search(op) for op in four_devices["collectives"])
    # every gradient crosses the chips in f32: the tiny model's 361,600
    # parameters
    assert sum(four_devices["bytes"].values()) >= 4 * 361600


@pytest.mark.parametrize("kind", ["control_fp8", "half_batch",
                                  "no_exchange"])
def test_faults_are_not_correct(four_devices, kind):
    assert four_devices["faults"][kind] is False


def test_qwen2_cell_loads_from_its_files():
    from repro.configs import get_config

    cell = spec.load_cell(pathlib.Path(REPO), "qwen2-0.5b.dp4.s1024")
    assert cell["chips"] == 4 and cell["config_name"] == "qwen2-0.5b"
    m, prog = cell["config"]["model"], get_config("qwen2-0.5b")
    for k, v in m.items():
        if k != "source":
            assert getattr(prog, k) == v, k
    job = cell["traffic"]
    assert (job["batch_per_chip"], job["seq_len"]) == (8, 1024)
    assert job["batch_per_chip"] % job["reference_rows_per_block"] == 0
    assert set(cell["limits"]) == {"loss_gap", "grad_gap", "change_gap"}
    names = {x["name"] for x in cell["per_layer"]}
    assert {"input_ms", "cache_misses", "search_s", "plan_error_pct"} <= names
