"""The harness: what BENCHMARK.json may hold, that an added cell,
configuration and metric are found from new files alone, that the command
refuses to run without a TPU, and the whole run at test size on the CPU."""
import hashlib
import json
import os
import pathlib
import subprocess
import sys
import time

import jax
import pytest

from bench import harness, spec
from bench_cells import REPO


def _bench():
    return spec.load_benchmark(pathlib.Path(REPO))


def test_benchmark_json_names_units_and_moves():
    bench = _bench()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert spec.validate(bench) == []
    e2e = {e["name"] for e in bench["end_to_end"]}
    assert {"tokens_per_s", "peak_hbm_gb", "setup_s"} <= e2e
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert os.path.exists(os.path.join(REPO, "bench", "metrics",
                                           m["name"] + ".py"))
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(REPO, c["file"]))
    for w in bench["workloads"]:
        for sub in (f"traffic/{w['traffic']}.json", f"limits/{w['name']}.json"):
            assert os.path.exists(os.path.join(REPO, "bench", sub))


@pytest.mark.parametrize("bad", [
    {"name": "tokens per s"}, {"name": "a/b"}, {"unit": "tokens per s"},
    {"unit": "µs"}, {"moves": "nowhere"}])
def test_validate_refuses(bad):
    bench = _bench()
    key = "unit" if "unit" in bad else "name"
    entry = bench["per_layer"][0]
    if "moves" in bad:
        entry["moves"] = bad["moves"]
    else:
        entry[key] = bad[key]
    assert spec.validate(bench)


def test_cell_metrics_follow_workloads_and_moves():
    bench = _bench()
    for w in bench["workloads"]:
        e2e, layer = spec.cell_metrics(bench, w["name"])
        names = {e["name"] for e in e2e}
        assert "setup_s" in names and len(names) >= 2 and layer
        assert all(m["moves"] in names for m in layer)


def _digests(root):
    out = {}
    for p in sorted(pathlib.Path(root, "bench").rglob("*")):
        if p.is_file() and "__pycache__" not in p.parts:
            out[str(p.relative_to(root))] = hashlib.sha256(
                p.read_bytes()).hexdigest()
    return out


def test_added_files_are_found_without_edits(tiny_root):
    before = _digests(REPO)
    root = pathlib.Path(tiny_root)
    metric = root / "bench" / "metrics" / "checked_steps.py"
    metric.write_text("def read(rec):\n    return rec.get('checked')\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "checked_steps", "unit": "steps", "better": "higher",
        "source": "host_clock", "layer": "driver set-up",
        "moves": "setup_s", "workloads": ["tiny-gqa.1chip"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    after = _digests(root)
    assert {k: v for k, v in after.items() if k in before} == before
    cell = spec.load_cell(root, "tiny-gqa.1chip", root / "bench")
    assert cell["config"]["model"]["name"] == "tiny-gqa"
    assert cell["traffic"]["seq_len"] == 64 and cell["chips"] == 1
    assert "checked_steps" in [m["name"] for m in cell["per_layer"]]
    read = spec.metric_reader(cell["bench_dir"], "checked_steps")
    assert read({"checked": 3}) == 3 and read({}) is None
    assert spec.validate(bench) == []
    other = spec.load_cell(root, "tiny-mha.1chip", root / "bench")
    assert "checked_steps" not in [m["name"] for m in other["per_layer"]]


def test_command_needs_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "stablelm-1.6b-l8.1chip.s4096", "--seed", "1", "--seconds", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert "{" not in p.stdout


def test_command_needs_its_files(tmp_path):
    """Without the program beside it, the command fails and prints no
    result."""
    import shutil

    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "bench"), tmp_path / "bench")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "stablelm-1.6b-l8.1chip.s4096", "--seed", "1", "--seconds", "1"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and "{" not in p.stdout


@pytest.mark.parametrize("traced", [False, True])
def test_whole_run_at_test_size(tiny_root, traced):
    """The command's path after the look for a chip, on the CPU."""
    root = pathlib.Path(tiny_root)
    cell = spec.load_cell(root, "tiny-mha.1chip", root / "bench")
    res = harness.run_cell(cell, 2**31 + 11, 0.5, traced, jax.devices()[:1],
                           time.perf_counter())
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {"loss_gap", "grad_gap", "change_gap"}
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == 1
    names = set(res["metrics"])
    if traced:
        # no device planes on the CPU: the trace readers find nothing;
        # the planner's metrics list the cells they are read in
        assert names == {"compile_s"}
        assert not names & {"compute_ms", "device_idle_share", "mfu"}
    else:
        assert {"tokens_per_s", "setup_s"} <= names
        assert res["metrics"]["tokens_per_s"]["unit"] == "tokens/s"
