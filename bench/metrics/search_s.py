"""Host seconds of the strategy search (``train.choose_strategy``)."""


def read(rec):
    return rec.get("search_s") if "plan_step_s" in rec else None
