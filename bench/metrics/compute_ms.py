"""Device 0's time per step in which a non-collective op runs, from the
trace."""


def read(rec):
    t = rec.get("trace") or {}
    return t["compute_s_per_step"] * 1e3 if t else None
