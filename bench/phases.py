#!/usr/bin/env python3
"""The step's device time split into phases, and what the host did in
the longest idle gaps, from a profiler trace and the compiled step's HLO.

    python bench/phases.py --workload <cell> --seed <n> [--windows 3] \
        [--seconds 10]

Autodiff writes the phase into every op's ``op_name`` metadata:
``.../jvp(...)/...`` is the forward pass, ``.../transpose(jvp(...))/...``
the backward, ``.../checkpoint/rematted_computation/...`` the forward
recomputed inside the backward; the program's named scopes (``repro.obs``)
add ``optimizer``, ``grad_sync`` with ``bucket_<i>`` under it, and
``embed``, ``attention``, ``mlp``, ``head``.  ``instructions`` reads that
metadata and each instruction's opcode from ``compiled.as_text()``;
``phase_times`` joins them with each traced op's self time
(``trace.self_times``, so a ``while`` does not count its body twice).  A
collective is known by its opcode: XLA names an all-reduce after the
primitive that made it (``psum.55``), which ``trace.is_collective``, going
by the name alone, does not see.

The command sets up a cell as a benchmark run does (``harness.set_up``),
times one untraced window of ``--seconds``, then traces ``--windows``
windows of the cell's ``trace_steps`` steps, each in a profiler session of
its own, and prints one JSON line per traced window: the phases, the
gradient sync per bucket, the idle gaps named by the innermost host span
covering most of each, and the traced and untraced tokens per second.
For the three longest gaps it logs to standard error every host event,
of any thread, that overlaps the gap by half or more.  The reference is
not run: this reads time, not correctness.  Exits non-zero without a TPU.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import pathlib
import re
import shutil
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[1]
if __package__ in (None, ""):
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import trace  # noqa: E402

PHASES = ("forward", "backward", "recompute", "optimizer", "attention",
          "grad_sync", "unattributed")
# host spans that may name a gap: the program's and the benchmark's
SPAN_PREFIXES = ("train.", "plan.", "ckpt.", "python.", "bench.")
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([^\s=]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_COMP = re.compile(r"^(?:ENTRY )?%?([^\s(]+) \(.*\) -> .*\{$")
_REF = re.compile(r"%([^\s,()=]+)")
_ARRAY = re.compile(r"\b(pred|[subf]\d+|bf16|f8e\w+)\[([\d,]*)\]")
_BUCKET = re.compile(r"grad_sync/bucket_(\d+)")


def _seg(name: str) -> re.Pattern:
    """A scope as a whole segment of an ``op_name``, also where a
    transformation holds it (``jvp(head)``)."""
    return re.compile(rf"(?:^|[/(]){name}(?:[/)]|$)")


_SCOPES = {k: _seg(k) for k in ("grad_sync", "optimizer", "attention")}
_MODEL = re.compile(r"(?:^|[/(])(?:embed|attention|mlp|head)(?:[/)]|$)")


def instructions(hlo_text: str) -> dict:
    """``{instruction: (opcode, shape, op_name)}`` of every instruction in
    the HLO text.  An instruction whose metadata does not name a path from
    the program's root (``jit(...)/...``), such as a fusion or a wrapped
    call the compiler made, takes that of its first operand that has one,
    else of the root of the computation it calls, else of its first
    user."""
    rows, roots = {}, {}
    comp = None
    for line in hlo_text.splitlines():
        head = _COMP.match(line)
        if head:
            comp = head.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, rest = m.groups()
        if rest.startswith("("):        # a tuple shape: skip to its end
            depth = 0
            for i, ch in enumerate(rest):
                depth += {"(": 1, ")": -1}.get(ch, 0)
                if depth == 0:
                    break
            shape, rest = rest[:i + 1], rest[i + 1:].lstrip()
        else:
            shape, _, rest = rest.partition(" ")
        op = _OP_NAME.search(rest)
        rows[name] = [rest.split("(", 1)[0], shape, op.group(1) if op else "",
                      _REF.findall(rest)]
        if line.lstrip().startswith("ROOT"):
            roots[comp] = name

    def full(n):
        return n in rows and rows[n][2].startswith("jit(")

    for row in rows.values():          # producers come first in the text
        if not row[2].startswith("jit("):
            calls = [roots[r] for r in row[3] if r in roots]
            src = next((r for r in row[3] + calls if full(r)), None)
            row[2] = rows[src][2] if src else row[2]
    users: dict = {}
    for name, row in rows.items():
        for r in row[3]:
            users.setdefault(r, []).append(name)
    for name in reversed(list(rows)):
        if not full(name):
            src = next((u for u in users.get(name, []) if full(u)), None)
            rows[name][2] = rows[src][2] if src else rows[name][2]
    return {k: (v[0], v[1], v[2]) for k, v in rows.items()}


def op_scopes(hlo_text: str) -> dict:
    """``{instruction: op_name}`` of the compiled step."""
    return {k: v[2] for k, v in instructions(hlo_text).items()}


def shape_bytes(shape: str) -> int:
    size = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
            "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
            "u64": 8}
    total = 0
    for dt, dims in _ARRAY.findall(shape):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * size.get(dt, 1)
    return total


def phase_of(op_name: str) -> str:
    """The one phase an op's time goes to (``attention`` and ``recompute``
    are parts of the others and are counted apart)."""
    if _SCOPES["grad_sync"].search(op_name):
        return "grad_sync"
    if _SCOPES["optimizer"].search(op_name):
        return "optimizer"
    if "transpose(" in op_name:
        return "backward"
    if "jvp(" in op_name or _MODEL.search(op_name):
        return "forward"
    return "unattributed"


def phase_times(ops, instrs: dict, lo: float, hi: float, steps: int) -> dict:
    """Seconds per step of each phase over ``[lo, hi)`` on one chip.

    ``ops``: ``(instruction, start_ns, end_ns)``; ``instrs``:
    ``instructions`` of the step.  Each op's self time goes to ``phase_of``
    its ``op_name``; ``attention`` collects the ``attention`` scope's (forward
    and backward), ``recompute`` the backward's rematerialised forward.
    ``sync_exposed`` is the time inside collectives under ``grad_sync``
    while no non-collective op runs; ``buckets`` gives per bucket its self
    time, collective time and exposed collective time."""
    ops = [o for o in ops if lo <= o[1] < hi]
    scopes = {k: v[2] for k, v in instrs.items()}

    def is_coll(n):
        return trace.is_collective(instrs[n][0] if n in instrs else n)

    out = dict.fromkeys(PHASES, 0.0)
    buckets: dict = {}
    for name, t in trace.self_times(ops).items():
        op = scopes.get(name, "")
        out[phase_of(op)] += t
        if _SCOPES["attention"].search(op):
            out["attention"] += t
        if "rematted_computation" in op and "transpose(" in op:
            out["recompute"] += t
        b = _BUCKET.search(op)
        if b:
            agg = buckets.setdefault(int(b.group(1)), dict.fromkeys(
                ("self", "collective", "exposed"), 0.0))
            agg["self"] += t
    comp = trace.union((s, e) for n, s, e in ops if not is_coll(n))

    def exposed(keep):
        coll = trace.union((s, e) for n, s, e in ops
                           if is_coll(n) and keep(scopes.get(n)))
        return trace.length(coll), trace.length(trace.subtract(coll, comp))

    sync = _SCOPES["grad_sync"]
    out["sync_exposed"] = exposed(lambda op: op and sync.search(op))[1]
    for i, agg in buckets.items():
        def mine(op, i=i):
            b = _BUCKET.search(op or "")
            return b is not None and int(b.group(1)) == i
        agg["collective"], agg["exposed"] = exposed(mine)
    res = {f"{k}_s": v * 1e-9 / steps for k, v in out.items()}
    res["busy_s"] = sum(out[k] for k in ("forward", "backward", "optimizer",
                                         "grad_sync", "unattributed")) \
        * 1e-9 / steps
    res["buckets"] = {i: {k: v * 1e-9 / steps for k, v in agg.items()}
                      for i, agg in sorted(buckets.items())}
    return res


def bucket_bytes(instrs: dict) -> dict:
    """Per bucket, the bytes its collectives carry (their result shapes)."""
    out: dict = {}
    for name, (opcode, shape, op) in instrs.items():
        b = _BUCKET.search(op)
        if b and trace.is_collective(opcode) and not opcode.endswith("done"):
            out[int(b.group(1))] = out.get(int(b.group(1)), 0) \
                + shape_bytes(shape)
    return out


def name_gap(spans, lo: float, hi: float) -> str:
    """The innermost host span that covers at least half of ``[lo, hi)``,
    else the one that covers most of it, else ``"none"``."""
    over = [(min(e, hi) - max(s, lo), e - s, n) for n, s, e in spans
            if min(e, hi) > max(s, lo)]
    half = [o for o in over if o[0] >= (hi - lo) / 2]
    if half:
        return min(half, key=lambda o: o[1])[2]
    return max(over)[2] if over else "none"


def host_events(trace_dir: str) -> list:
    """Every event of the host planes: ``(thread, name, start, end)``."""
    from jax.profiler import ProfileData

    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                out += [(ln.name, e.name, e.start_ns,
                         e.start_ns + e.duration_ns) for e in ln.events]
    return out


def under_gap(events, lo: float, hi: float) -> list:
    """Host events that overlap ``[lo, hi)`` by half of it or more, the
    shortest first."""
    return sorted((ev for ev in events
                   if min(ev[3], hi) - max(ev[2], lo) >= (hi - lo) / 2),
                  key=lambda ev: ev[3] - ev[2])


def window(loaded: dict, events: list, instrs: dict, gaps: int = 3) -> dict:
    """The phases and named gaps of one traced window."""
    spans = [(n, s, e) for _, n, s, e in events
             if n.startswith(SPAN_PREFIXES)]
    red = trace.reduce(loaded["devices"], spans)
    d0 = loaded["devices"][0]
    lo = min(s for _, s, _ in d0["steps"])
    hi = max(e for _, _, e in d0["steps"])
    steps = len(d0["steps"])
    ph = phase_times(d0["ops"], instrs, lo, hi, steps)
    idle = trace.subtract([(lo, hi)], trace.union(trace.clip(
        [(s, e) for _, s, e in d0["ops"]], lo, hi)))
    idle = sorted(idle, key=lambda g: g[0] - g[1])
    named = [{"span": name_gap(spans, s, e), "ms": (e - s) * 1e-6,
              "at_ms": (s - lo) * 1e-6} for s, e in idle[:10]]
    for g, (s, e) in zip(named[:gaps], idle[:gaps]):
        g["host"] = [[t, n, (b - a) * 1e-6]
                     for t, n, a, b in under_gap(events, s, e)]
    size = bucket_bytes(instrs)
    for i, b in ph["buckets"].items():
        b["bytes"] = size.get(i, 0)
    return {"steps": steps, "window_s": (hi - lo) * 1e-9,
            "busy0_s_per_step": red["busy0_s"] / steps,
            "compute_s_per_step": red["compute_s_per_step"],
            "sync_exposed_s_per_step": red["sync_exposed_s_per_step"],
            "phases": ph, "gaps": named}


def profile_cell(cell: dict, seed: int, devices, windows: int,
                 seconds: float) -> list:
    """Set up ``cell``, time an untraced window, then trace ``windows``
    windows; one dict per traced window."""
    import jax

    from bench import harness

    st = harness.set_up(cell, seed, devices)
    instrs = instructions(st["step"].as_text())
    job = cell["traffic"]
    tokens = job["batch_per_chip"] * cell["chips"] * job["seq_len"]

    def loop(steps, traced, secs):
        out = harness._loop(st["T"], st["step"], st["params"], st["opt"],
                            st["example"], st["pool"], st["cfg"].vocab,
                            job["checked_steps"], secs, steps, traced)
        st["params"], st["opt"], losses, elapsed = out
        return len(losses) * tokens / elapsed

    untraced = loop(None, False, seconds)
    harness.log(f"untraced window {untraced:.1f} tokens/s")
    res = []
    for w in range(windows):
        tdir = tempfile.mkdtemp(prefix="bench-phases-")
        try:
            jax.profiler.start_trace(tdir)
            rate = loop(job["trace_steps"], True, 1e9)
            jax.profiler.stop_trace()
            loaded = trace.load(tdir)
            events = host_events(tdir)
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        r = window(loaded, events, instrs)
        r.update(window_index=w, traced_tokens_per_s=rate,
                 untraced_tokens_per_s=untraced)
        for g in r["gaps"][:3]:
            harness.log(f"window {w} gap {g['ms']:.3f} ms at "
                        f"{g['at_ms']:.1f} ms in {g['span']}: "
                        + "; ".join(f"{t} | {n} {ms:.3f} ms"
                                    for t, n, ms in g["host"]))
        res.append(r)
    from repro import obs

    harness.log(f"obs {json.dumps(obs.snapshot())}")
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--windows", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    from bench import harness, spec

    cell = spec.load_cell(ROOT, args.workload)
    devices = harness.accelerators(cell["chips"])
    for r in profile_cell(cell, args.seed, devices, args.windows,
                          args.seconds):
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
