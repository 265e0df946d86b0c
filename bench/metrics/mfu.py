"""Model FLOP/s utilization of the whole step: model operations per token
(the reference family's ``flops_per_token``) times the traced window's
tokens per second, over the chips' peak from ``peaks.json``."""


def read(rec):
    w, peak = rec.get("window"), rec.get("peak_flops_per_s")
    if not w or not w["steps"] or not peak:
        return None
    return 100.0 * rec["flops_per_token"] * w["tokens"] / w["seconds"] \
        / (rec["chips"] * peak)
