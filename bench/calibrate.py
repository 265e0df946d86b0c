#!/usr/bin/env python3
"""Readings from which the limits of a cell's comparison are set.

    python bench/calibrate.py --workload <cell> --seeds 11,12,... \
        --control-seeds 11,12,13 [--fault-seeds 11,12,13] \
        [--out readings.jsonl]
    python bench/calibrate.py --workload <cell> --judge readings.jsonl

For each of ``--seeds``: the program's set-up and checked steps, exactly
as a benchmark run makes them, against the f32 reference (the lower
readings). For each of ``--control-seeds``, against the same f32
reference: the reference computed with float8 matmul operands (the
control); for each of ``--fault-seeds``, the reference on half of each
batch (half the batch left out). A step that leaves the state unchanged
reads 1 by construction and needs no run, and so does a leaf left
unmoved (see ``bench/check.py``). One JSON line per reading, with the
verdict of the cell's committed limits on it (``correct``); the last
line gives, per kind, the largest sound reading and the smallest of each
fault's per number, and how many readings came out correct. ``--judge``
reads such lines again under the limits as they are now, on any machine.
Not run by the benchmark's own runs.
"""
import argparse
import gc
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default=None,
                    help="seeds of the faults other than the control "
                         "(default: the control's)")
    ap.add_argument("--out", default=None)
    ap.add_argument("--judge", default=None)
    args = ap.parse_args(argv)
    if args.judge:
        return judge(args.workload, args.judge)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    # libtpu logs to /tmp/tpu_logs unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import check, harness, spec

    cell = spec.load_cell(ROOT, args.workload)
    devices = harness.accelerators(cell["chips"])
    job = cell["traffic"]
    rows = job["batch_per_chip"] * cell["chips"]
    out = open(args.out, "a") if args.out else None
    summary = {}

    def emit(kind, seed, found):
        line = {"kind": kind, "seed": seed,
                **{k: v for k, (v, _) in found.items()},
                "at": {k: w for k, (_, w) in found.items()},
                "correct": check.verdict(found, cell["limits"])[0]}
        print(json.dumps(line), flush=True)
        if out:
            out.write(json.dumps(line) + "\n")
            out.flush()
        _tally(summary, line)

    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = [int(s) for s in args.control_seeds.split(",") if s]
    faulty = controls if args.fault_seeds is None else \
        [int(s) for s in args.fault_seeds.split(",") if s]
    for seed in sorted(set(seeds) | set(controls) | set(faulty)):
        st = harness.set_up(cell, seed, devices)
        prog, pool, names = st["prog"], st["pool"], st["names"]
        st.clear()
        gc.collect()
        ref = harness.reference_run(cell, seed, pool, devices)
        if seed in seeds:
            emit("program", seed, harness.compare(cell, names, prog, ref))
        faults = [("control_fp8", None, "fp8")] if seed in controls else []
        if seed in faulty:
            faults.append(("half_batch", rows // 2, "f32"))
        for kind, r, numerics in faults:
            got = harness.reference_run(cell, seed, pool, devices, r,
                                        numerics)
            emit(kind, seed, harness.compare(cell, names, got, ref))
    print(json.dumps({"summary": summary}), flush=True)
    if out:
        out.close()
    return 0


def _tally(summary: dict, line: dict) -> None:
    from bench import check

    kind = line["kind"]
    agg = summary.setdefault(kind, {"runs": 0, "correct": 0})
    agg["runs"] += 1
    agg["correct"] += bool(line["correct"])
    pick = max if kind == "program" else min
    for k in check.NUMBERS:
        agg[k] = pick(agg.get(k, line[k]), line[k])


def judge(workload: str, path: str) -> int:
    """Recorded readings under the cell's limits as committed."""
    sys.path[:0] = [str(ROOT)]
    from bench import check, spec

    limits = spec.load_cell(ROOT, workload)["limits"]
    summary = {}
    with open(path) as f:
        for text in f:
            line = json.loads(text)
            if "kind" not in line:
                continue
            ok, lines = check.verdict(
                {k: (line[k], line["at"][k]) for k in check.NUMBERS}, limits)
            line["correct"] = ok
            print(json.dumps({"kind": line["kind"], "seed": line["seed"],
                              "correct": ok, "failed": [
                                  x["name"] for x in lines if not x["ok"]]}))
            _tally(summary, line)
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
