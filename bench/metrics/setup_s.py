"""Process start to the first timed step: start-up, weights, optimizer
state, search, compile, and the checked steps that warm the step up."""


def read(rec):
    return rec.get("setup_s")
