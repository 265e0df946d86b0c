#!/usr/bin/env python3
"""Readings of the faults a cell's limits must fail, without the program.

    python bench/calibrate_faults.py --workload <cell> --seeds 11,12,13 \
        [--kinds no_exchange] [--out readings.jsonl]

For each seed, against the f32 reference of the cell's checked steps:
the reference computed with float8 matmul operands (``control_fp8``), the
reference on half of each batch (``half_batch``), and, where the cell has
more than one chip, the reference on the rows of chip 0 alone
(``no_exchange``: what each chip computes when the gradient exchange is
left out); ``--kinds`` keeps some of them.  Lines as ``calibrate.py``
writes them, which ``calibrate.py --judge`` reads; the program's own
readings are the ``checks`` of the benchmark's runs.  Not run by the benchmark's own runs.
"""
import argparse
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def faults(cell: dict) -> list:
    """``(kind, rows, numerics)`` of each fault of ``cell``."""
    job = cell["traffic"]
    rows = job["batch_per_chip"] * cell["chips"]
    out = [("control_fp8", None, "fp8"), ("half_batch", rows // 2, "f32")]
    if cell["chips"] > 1:
        out.append(("no_exchange", job["batch_per_chip"], "f32"))
    return out


def readings(cell: dict, seed: int, devices, kinds=None) -> list:
    """One line per fault of ``cell`` (of ``kinds``, where given) on
    ``seed``."""
    from bench import check, harness, reference
    from bench.traffic import TokenPool

    job, m = cell["traffic"], cell["config"]["model"]
    pool = TokenPool(job, m["vocab"], job["batch_per_chip"] * cell["chips"],
                     seed)
    names = reference.leaf_names(cell["reference"], m)
    ref = harness.reference_run(cell, seed, pool, devices)
    out = []
    for kind, rows, numerics in faults(cell):
        if kinds and kind not in kinds:
            continue
        got = harness.reference_run(cell, seed, pool, devices, rows, numerics)
        found = harness.compare(cell, names, got, ref)
        out.append({"kind": kind, "seed": seed,
                    **{k: v for k, (v, _) in found.items()},
                    "at": {k: w for k, (_, w) in found.items()},
                    "correct": check.verdict(found, cell["limits"])[0]})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--kinds", default="",
                    help="comma-separated faults to read (default: all)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness, spec

    cell = spec.load_cell(ROOT, args.workload)
    devices = harness.accelerators(cell["chips"])
    with open(args.out or os.devnull, "a") as out:
        for seed in (int(s) for s in args.seeds.split(",") if s):
            for line in readings(cell, seed, devices,
                                 [k for k in args.kinds.split(",") if k]):
                print(json.dumps(line), flush=True)
                out.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
