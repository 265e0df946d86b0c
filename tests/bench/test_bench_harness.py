"""The harness: what BENCHMARK.json may hold, that an added cell,
configuration, reference family and metric are found from new files alone,
that a configuration file builds the program's settings with their nested
blocks, that the command refuses to run without a TPU, and the whole run at
test size on the CPU."""
import dataclasses
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
        with open(os.path.join(REPO, c["file"])) as f:
            family = json.load(f)["reference"]
        assert os.path.exists(os.path.join(REPO, "bench", "references",
                                           family + ".py"))
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


ALIAS = '''"""The dense family under another name, with an operation count of
its own."""
import pathlib

from bench import spec

_dense = spec.reference_family(pathlib.Path(__file__).parents[1], "dense")
check_supported = _dense.check_supported
init = _dense.init
storage_dtypes = _dense.storage_dtypes
mean_loss = _dense.mean_loss


def flops_per_token(m, seq_len):
    return 1234.5
'''


def _add_alias_family(root):
    """A reference family, a configuration that names it, its cell and a
    metric that reads the record's operation count, as new files and
    entries."""
    b = root / "bench"
    (b / "references" / "dense_alias.py").write_text(ALIAS)
    config = json.loads((b / "configs" / "tiny-gqa.json").read_text())
    (b / "configs" / "tiny-alias.json").write_text(
        json.dumps({**config, "reference": "dense_alias"}))
    (b / "limits" / "tiny-alias.1chip.json").write_text(
        (b / "limits" / "tiny-gqa.1chip.json").read_text())
    (b / "metrics" / "model_flops.py").write_text(
        "def read(rec):\n    return rec.get('flops_per_token')\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-alias", "source": "test size",
                             "file": "bench/configs/tiny-alias.json",
                             "reduced": [], "why": "test size"})
    bench["workloads"].append({"name": "tiny-alias.1chip",
                               "config": "tiny-alias",
                               "traffic": "tiny.b2x64.c1", "chips": 1,
                               "why": "test size"})
    bench["end_to_end"].append({
        "name": "model_flops", "unit": "FLOP", "better": "lower",
        "bound": 0.01, "source": "host_clock",
        "workloads": ["tiny-alias.1chip"]})
    return bench


def test_added_files_are_found_without_edits(tiny_root):
    before = _digests(REPO)
    root = pathlib.Path(tiny_root)
    metric = root / "bench" / "metrics" / "checked_steps.py"
    metric.write_text("def read(rec):\n    return rec.get('checked')\n")
    bench = _add_alias_family(root)
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
    assert spec.validate(bench, root / "bench") == []
    other = spec.load_cell(root, "tiny-mha.1chip", root / "bench")
    assert "checked_steps" not in [m["name"] for m in other["per_layer"]]
    # the new family is the alias cell's reference and operation count
    alias = spec.load_cell(root, "tiny-alias.1chip", root / "bench")
    assert alias["reference"].__file__ == str(
        (root / "bench" / "references" / "dense_alias.py").resolve())
    res = harness.run_cell(alias, 2**31 + 13, 0.2, False, jax.devices()[:1],
                           time.perf_counter())
    assert res["metrics"]["model_flops"]["value"] == 1234.5
    assert res["correct"] is True, res["checks"]


@pytest.mark.parametrize("family", [None, "nowhere"])
def test_config_names_a_known_family(tiny_root, family):
    """A configuration without a reference family, or with one that has no
    file, is refused by ``validate`` and by ``load_cell``."""
    root = pathlib.Path(tiny_root)
    path = root / "bench" / "configs" / "tiny-gqa.json"
    config = json.loads(path.read_text())
    config.pop("reference")
    if family:
        config["reference"] = family
    path.write_text(json.dumps(config))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    assert any("tiny-gqa" in b for b in spec.validate(bench, root / "bench"))
    with pytest.raises(KeyError):
        spec.load_cell(root, "tiny-gqa.1chip", root / "bench")


@pytest.mark.parametrize("arch", ["deepseek-v2-lite-16b", "recurrentgemma-9b",
                                  "seamless-m4t-medium", "qwen2-0.5b"])
def test_model_config_from_its_file(arch):
    """The program's settings, written as a configuration file's ``model``
    object, build the same ``ModelConfig`` again, nested blocks and
    all."""
    from repro.configs import get_config

    cfg = get_config(arch).reduced()
    d = json.loads(json.dumps(dataclasses.asdict(cfg)))
    assert spec.model_config(d) == cfg


def test_nested_blocks_reach_the_trainer(tmp_path):
    """A configuration file with ``mla`` and ``moe`` blocks builds their
    dataclasses, which the trainer's initialiser accepts; a key that a
    dataclass lacks is refused."""
    from repro.configs import get_config
    from repro.launch import train as T
    from repro.models.config import MLAConfig, MoEConfig

    path = tmp_path / "deepseek.json"
    path.write_text(json.dumps({"reference": "dense", "model":
                                dataclasses.asdict(get_config(
                                    "deepseek-v2-lite-16b").reduced())}))
    m = json.loads(path.read_text())["model"]
    cfg = spec.model_config(m)
    assert isinstance(cfg.mla, MLAConfig) and isinstance(cfg.moe, MoEConfig)
    assert cfg.mla.kv_lora_rank == m["mla"]["kv_lora_rank"]
    params = T.init_params(cfg, 0)
    assert len(jax.tree.leaves(params)) > 0
    hash(cfg)
    for bad in ({**m, "n_experts": 8},
                {**m, "moe": {**m["moe"], "n_experts": 8}}):
        with pytest.raises(ValueError):
            spec.model_config(bad)


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
