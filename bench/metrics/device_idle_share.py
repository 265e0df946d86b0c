"""Share of the traced window in which no op runs on a chip (mean over the
chips), from the trace."""


def read(rec):
    t = rec.get("trace") or {}
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"]) \
        if t.get("window_s") else None
