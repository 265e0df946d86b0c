"""The planner's predicted step time (``Plan.provenance["best_cost"]``)
against the step program's mean device time in the trace, as a share of
the measured time."""


def read(rec):
    t = rec.get("trace") or {}
    if "plan_step_s" not in rec or not t.get("step_device_s"):
        return None
    return 100.0 * abs(rec["plan_step_s"] - t["step_device_s"]) \
        / t["step_device_s"]
