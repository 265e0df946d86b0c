"""Peak bytes in use on the fullest chip after the window, in GB
(``memory_stats()["peak_bytes_in_use"]``)."""


def read(rec):
    b = rec.get("peak_hbm_bytes")
    return b / 1e9 if b else None
