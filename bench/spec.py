"""What a cell is, read from data: ``BENCHMARK.json`` names the cells and
metrics, and each configuration, traffic mix, per-cell limit and metric
reader sits in a file of its own that is found by its name:

    bench/configs/<config>.json     the model as run (``model``, the
                                    program's ``ModelConfig`` with its
                                    nested blocks), its source and cuts,
                                    and its ``reference`` family
    bench/references/<family>.py    the plain reference of an architecture
                                    and its operation count (the contract
                                    is in ``bench/reference.py``)
    bench/traffic/<traffic>.json    the training job: batch, lengths, tokens,
                                    optimizer, reference blocking
    bench/limits/<cell>.json        the limits of the comparison that
                                    decides ``correct``, with their readings
    bench/metrics/<metric>.py       ``read(record) -> float | None``

Adding a cell, a configuration, an architecture's reference or a metric
adds files and entries; no file that is there needs an edit.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import pathlib
import re
import typing

BENCH = pathlib.Path(__file__).resolve().parent
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
FAMILY_API = ("check_supported", "init", "storage_dtypes", "mean_loss",
              "flops_per_token")


def _json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: pathlib.Path) -> dict:
    return _json(root / "BENCHMARK.json")


def cell_metrics(bench: dict, cell: str) -> tuple[list, list]:
    """The end-to-end and per-layer metric entries that ``cell`` reports.

    A metric with a ``workloads`` list is reported in those cells; one
    without it in every cell.  A per-layer metric without the list goes
    with its ``moves`` metric, so it is reported where that one is."""
    def reported(entry):
        return cell in entry.get("workloads", [cell])

    e2e = [e for e in bench["end_to_end"] if reported(e)]
    names = {e["name"] for e in e2e}
    layer = [e for e in bench["per_layer"]
             if reported(e) and e["moves"] in names]
    return e2e, layer


def load_cell(root: pathlib.Path, name: str, bench_dir: pathlib.Path = BENCH
              ) -> dict:
    """Everything a run of cell ``name`` needs, from its files."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    e2e, layer = cell_metrics(bench, name)
    config = _json(bench_dir / "configs" / f"{w['config']}.json")
    if "reference" not in config:
        raise KeyError(f"configuration {w['config']!r} names no reference "
                       f"family")
    return {
        "name": name,
        "chips": int(w["chips"]),
        "config_name": w["config"],
        "config": config,
        "reference": reference_family(bench_dir, config["reference"]),
        "traffic_name": w["traffic"],
        "traffic": _json(bench_dir / "traffic" / f"{w['traffic']}.json"),
        "limits": _json(bench_dir / "limits" / f"{name}.json"),
        "end_to_end": e2e,
        "per_layer": layer,
        "bench_dir": str(bench_dir),
    }


def metric_reader(bench_dir: str, name: str):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    path = pathlib.Path(bench_dir) / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def reference_family(bench_dir, family: str):
    """The module ``bench/references/<family>.py``; a family without a
    file, or whose file lacks a function of ``FAMILY_API``, raises."""
    path = _family_file(bench_dir, family)
    if path is None:
        raise KeyError(f"unknown reference family {family!r}: no "
                       f"{bench_dir}/references/{family}.py")
    mod = _family_module(str(path.resolve()))
    missing = [f for f in FAMILY_API if not hasattr(mod, f)]
    if missing:
        raise KeyError(f"reference family {family!r} lacks {missing}")
    return mod


def _family_file(bench_dir, family):
    path = pathlib.Path(bench_dir) / "references" / f"{family}.py"
    ok = isinstance(family, str) and NAME_RE.match(family) and path.is_file()
    return path if ok else None


@functools.lru_cache(maxsize=None)
def _family_module(path: str):
    """Loaded once per file: a family's functions are static arguments of
    the reference's jitted functions, and a module loaded again would
    compile them again."""
    spec = importlib.util.spec_from_file_location(
        f"bench_reference_{pathlib.Path(path).stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def model_config(d: dict):
    """The program's ``ModelConfig`` from a configuration file's ``model``
    object: a nested block (``mla``, ``moe``, ``encdec``, ``recurrent``) as
    its dataclass, a list as a tuple; a key the dataclass lacks raises."""
    from repro.models.config import ModelConfig

    return _dataclass(ModelConfig, d)


def _dataclass(cls, d: dict):
    hints = typing.get_type_hints(cls)
    unknown = set(d) - {f.name for f in dataclasses.fields(cls)}
    if unknown:
        raise ValueError(f"{cls.__name__} has no {sorted(unknown)}")
    return cls(**{k: _setting(hints[k], v) for k, v in d.items()})


def _setting(hint, v):
    if isinstance(v, dict):
        (cls,) = [t for t in typing.get_args(hint) or (hint,)
                  if dataclasses.is_dataclass(t)]
        return _dataclass(cls, v)
    if isinstance(v, list):
        return tuple(v)
    return v


def validate(bench: dict, bench_dir: pathlib.Path = BENCH) -> list[str]:
    """Problems with names, units and cross references in ``bench``, and
    configurations under ``bench_dir`` that name no reference family with a
    file."""
    bad = []
    names = [c["name"] for c in bench["configs"]]
    names += [w["name"] for w in bench["workloads"]]
    names += [w["config"] for w in bench["workloads"]]
    names += [w["traffic"] for w in bench["workloads"]]
    names += [k for c in bench["configs"] for k in c["reduced"]]
    metrics = bench["end_to_end"] + bench["per_layer"]
    names += [m["name"] for m in metrics]
    bad += [f"name {n!r}" for n in names if not NAME_RE.match(n)]
    bad += [f"unit {m['unit']!r}" for m in metrics
            if not UNIT_RE.match(m["unit"])]
    configs = set(c["name"] for c in bench["configs"])
    bad += [f"cell {w['name']} names unknown config {w['config']}"
            for w in bench["workloads"] if w["config"] not in configs]
    for c in sorted(configs):
        path = pathlib.Path(bench_dir) / "configs" / f"{c}.json"
        family = _json(path).get("reference") if path.is_file() else None
        if _family_file(bench_dir, family) is None:
            bad.append(f"config {c} names no reference family with a file: "
                       f"{family!r}")
    for m in bench["per_layer"]:
        e2e = {e["name"]: e for e in bench["end_to_end"]}
        if m["moves"] not in e2e:
            bad.append(f"{m['name']} moves unknown {m['moves']}")
            continue
        for cell in m.get("workloads", [w["name"]
                                        for w in bench["workloads"]]):
            if cell not in e2e[m["moves"]].get("workloads", [cell]):
                bad.append(f"{m['name']}: {cell} does not report "
                           f"{m['moves']}")
    return bad
