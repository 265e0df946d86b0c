"""Reduction of a profiler trace to device busy time, idle share, exposed
collective time, the costliest operations and the idle gaps by host span.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes; ``reduce``
works on plain lists of ``(name, start_ns, end_ns)`` so that a test can
hand it a trace whose answers are worked out by hand.
"""
from __future__ import annotations

import glob
import re

COLLECTIVE_RE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast)")


def is_collective(name: str) -> bool:
    """Collective ops by their HLO name, async ``-start`` / ``-done``
    halves included."""
    return bool(COLLECTIVE_RE.match(name))


def union(intervals) -> list:
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def length(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def subtract(a, b) -> list:
    """``a`` minus ``b``; both sorted and disjoint."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def reduce(devices: list, spans: list, top: int = 10) -> dict:
    """Reduce a trace.

    ``devices``: one dict per chip, ``{"ops": [(name, start, end)],
    "steps": [(name, start, end)]}`` with the executions of the step program
    in ``steps``.  ``spans``: host spans ``(name, start, end)`` on the same
    clock.  Times in ns.

    The window runs from the start of device 0's first step to the end of
    its last.  Busy is the union of op intervals in it; ``compute`` the
    union of non-collective ops; ``sync_exposed`` the time inside collective
    ops while no other op runs.  Per-step numbers are over device 0."""
    d0 = devices[0]
    if not d0["steps"]:
        return {}
    lo = min(s for _, s, _ in d0["steps"])
    hi = max(e for _, _, e in d0["steps"])
    window = hi - lo
    busy = []
    for d in devices:
        busy.append(length(clip(union((s, e) for _, s, e in d["ops"]),
                                lo, hi)))
    ops = d0["ops"]
    coll = clip(union((s, e) for n, s, e in ops if is_collective(n)), lo, hi)
    comp = clip(union((s, e) for n, s, e in ops if not is_collective(n)),
                lo, hi)
    n_steps = len(d0["steps"])
    top_ops = sorted(self_times(ops).items(), key=lambda kv: -kv[1])[:top]
    busy0 = union(comp + coll)
    gaps = subtract([(lo, hi)], busy0)
    gaps = sorted(gaps, key=lambda g: -(g[1] - g[0]))[:top]
    return {
        "window_s": window * 1e-9,
        "busy_s": sum(busy) / len(busy) * 1e-9,
        "busy0_s": length(busy0) * 1e-9,
        "steps": n_steps,
        "step_device_s": sum(e - s for _, s, e in d0["steps"]) / n_steps
        * 1e-9,
        "compute_s_per_step": length(comp) / n_steps * 1e-9,
        "sync_exposed_s_per_step": length(subtract(coll, comp)) / n_steps
        * 1e-9,
        "collective_s_per_step": length(coll) / n_steps * 1e-9,
        "device_ops": [[n, t * 1e-9] for n, t in top_ops],
        "idle_gaps": [[host_activity(spans, s, e), (e - s) * 1e-9]
                      for s, e in gaps],
    }


def self_times(ops) -> dict:
    """Total time of each op name less the time of the ops nested in it
    (a ``while`` holds the ops of its body on the same line)."""
    out: dict = {}
    stack: list = []       # [name, end, self time] of the enclosing ops

    def close(item):
        out[item[0]] = out.get(item[0], 0.0) + item[2]

    for n, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([n, e, float(e - s)])
    for item in stack:
        close(item)
    return out


def short_name(name: str) -> str:
    """``%while.3 = (...) while(...)`` -> ``while.3``."""
    return name.split(" = ", 1)[0].lstrip("%")


def host_activity(spans, lo, hi) -> str:
    """The host span that covers most of ``[lo, hi)``, or ``"none"``."""
    best, best_len = "none", 0.0
    for n, s, e in spans:
        o = min(e, hi) - max(s, lo)
        if o > best_len:
            best, best_len = n, o
    return best


# ------------------------------------------------------------ xplane files
def load(trace_dir: str, span_prefix: str = "bench.",
         step_prefix: str = "jit_step") -> dict:
    """Device ops, step executions and host spans from the ``.xplane.pb``
    under ``trace_dir``.  Device planes are ``/device:TPU:<n>``; their
    ``XLA Ops`` line holds the HLO ops and ``XLA Modules`` the program
    executions, of which those named ``step_prefix...`` are steps."""
    from jax.profiler import ProfileData

    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane file, found {paths}")
    pd = ProfileData.from_file(paths[0])
    devices, spans, layout = {}, [], {}
    for plane in pd.planes:
        lines = {ln.name: ln for ln in plane.lines}
        layout[plane.name] = sorted(lines)
        m = re.match(r"^/device:TPU:(\d+)$", plane.name)
        if m:
            ops = [(short_name(e.name), e.start_ns,
                    e.start_ns + e.duration_ns)
                   for e in lines["XLA Ops"].events] \
                if "XLA Ops" in lines else []
            steps = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                     for e in lines["XLA Modules"].events
                     if e.name.startswith(step_prefix)] \
                if "XLA Modules" in lines else []
            devices[int(m.group(1))] = {"ops": ops, "steps": steps}
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in ln.events
                          if e.name.startswith(span_prefix)]
    return {"devices": [devices[k] for k in sorted(devices)],
            "spans": spans, "layout": layout}
