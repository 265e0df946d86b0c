"""The trace reduction on a trace whose answers are worked out by hand, the
table of peaks, and the dense family's operation count."""
import pytest

from bench import flops, spec, trace

NS = 1e-9
# Device 0 runs two steps of 100 ns with a 20 ns gap.  Step 1: compute,
# an async all-reduce whose start and done halves run alone for 2 and
# 20 ns, compute.  Step 2: an all-reduce from 150 to 200 that compute
# hides until 160.  Device 1 is busy for 50 ns.
DEVICES = [
    {"steps": [("jit_step(1)", 0, 100), ("jit_step(1)", 120, 220)],
     "ops": [("fusion.1", 0, 40), ("all-reduce-start.1", 40, 42),
             ("fusion.2", 42, 70), ("all-reduce-done.1", 70, 90),
             ("fusion.3", 90, 100), ("fusion.1", 120, 160),
             ("all-reduce.2", 150, 200), ("fusion.3", 200, 220)]},
    {"steps": [("jit_step(1)", 0, 100), ("jit_step(1)", 120, 220)],
     "ops": [("fusion.1", 0, 50)]},
]
SPANS = [("bench.block", 90, 115), ("bench.step_batch", 100, 120),
         ("bench.dispatch", 118, 121)]


def test_reduce_by_hand():
    r = trace.reduce(DEVICES, SPANS)
    assert r["window_s"] == pytest.approx(220 * NS)
    assert r["busy0_s"] == pytest.approx(200 * NS)
    assert r["busy_s"] == pytest.approx((200 + 50) / 2 * NS)
    assert r["steps"] == 2
    assert r["step_device_s"] == pytest.approx(100 * NS)
    # compute: 40 + 28 + 10 + 40 + 20 over two steps
    assert r["compute_s_per_step"] == pytest.approx(69 * NS)
    # alone: 2 + 20 in step 1, 160..200 in step 2
    assert r["sync_exposed_s_per_step"] == pytest.approx(31 * NS)
    assert r["collective_s_per_step"] == pytest.approx(36 * NS)
    # self time: all-reduce.2 runs inside the second fusion.1 for 10 ns
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(70 * NS)]
    assert r["device_ops"][1] == ["all-reduce.2", pytest.approx(50 * NS)]
    assert r["idle_gaps"] == [["bench.step_batch", pytest.approx(20 * NS)]]


def test_reduce_without_steps_is_empty():
    assert trace.reduce([{"steps": [], "ops": []}], []) == {}


@pytest.mark.parametrize("a,b,want", [
    ([(0, 10)], [(2, 3), (5, 7)], [(0, 2), (3, 5), (7, 10)]),
    ([(0, 10), (20, 30)], [(5, 25)], [(0, 5), (25, 30)]),
    ([(0, 10)], [], [(0, 10)]),
    ([(0, 10)], [(0, 10)], []),
])
def test_subtract(a, b, want):
    assert trace.subtract(a, b) == want


def test_union_merges_touching_and_nested():
    assert trace.union([(5, 6), (0, 2), (2, 4), (1, 3)]) == [(0, 4), (5, 6)]


@pytest.mark.parametrize("name,coll", [
    ("all-reduce.3", True), ("all-reduce-start.12", True),
    ("all-gather-done", True), ("reduce-scatter.1", True),
    ("collective-permute-start.2", True), ("fusion.7", False),
    ("convolution.1", False), ("copy-start.4", False)])
def test_collective_names(name, coll):
    assert trace.is_collective(name) is coll


def test_peaks_table():
    p = flops.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        flops.peaks("TPU v4")


def test_flops_per_token_qwen2():
    dense = spec.reference_family(spec.BENCH, "dense")
    m = {"n_layers": 24, "d_model": 896, "n_heads": 14, "n_kv_heads": 2,
         "d_ff": 4864, "vocab": 151936, "head_dim": 0, "glu": True}
    assert dense.matmul_params(m) == 24 * (896 * 64 * 18 + 14 * 64 * 896
                                           + 3 * 896 * 4864) + 151936 * 896
    assert dense.flops_per_token(m, 1024) == 6 * 493961216 \
        + 6 * 1024 * 896 * 24


def test_self_time_of_nested_ops():
    ops = [("while.1", 0, 100), ("fusion.1", 10, 30), ("fusion.2", 40, 60),
           ("while.2", 200, 250), ("fusion.1", 210, 215)]
    assert trace.self_times(ops) == {"while.1": 60, "fusion.1": 25,
                                     "fusion.2": 20, "while.2": 45}


def test_short_name():
    assert trace.short_name("%while.310 = (s32[], bf16[2]) while(%t)") \
        == "while.310"
    assert trace.short_name("fusion.7") == "fusion.7"
