"""Mean host milliseconds per step of the program's ``train.step_batch``
span (make the step's batch and put it on the device) in the window: the
last ``rec["window"]["steps"]`` calls, since nothing after the window makes
a batch.  Read from ``repro.obs`` in this process; nothing where the
program has no such span."""


def read(rec):
    try:
        from repro import obs
    except ImportError:
        return None
    n = rec.get("window", {}).get("steps")
    calls = obs.recent("train.step_batch")[-n:] if n else []
    return sum(s for _, s in calls) / len(calls) * 1e3 if calls else None
