"""Host seconds of ``train.compile_step`` (from the persistent cache after
a cell's first run)."""


def read(rec):
    return rec.get("compile_s")
