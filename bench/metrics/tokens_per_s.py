"""Global tokens of every step completed in the window over its wall time."""


def read(rec):
    w = rec.get("window")
    return w["tokens"] / w["seconds"] if w and w["steps"] else None
