#!/usr/bin/env python3
"""Run one cell of the benchmark on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration, its training job and its limits are found by
name from ``BENCHMARK.json`` (see ``bench/spec.py``).  Exits non-zero,
printing no result, when JAX finds no TPU or fewer chips than the cell
needs.  The last line of standard output is the result as one JSON object;
the last lines of standard error give each number of the correctness
comparison beside its limit.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    # a fixed directory inside the checkout: the path is part of the cache
    # key, and every program of a cell is found there after its first run
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    # libtpu logs to /tmp/tpu_logs unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness, spec

    cell = spec.load_cell(ROOT, args.workload)
    devices = harness.accelerators(cell["chips"])
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), devices, T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
