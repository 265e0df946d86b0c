"""Programs set-up compiled rather than loaded from JAX's persistent
compilation cache: backend compiles (``repro.obs`` span ``jax.compile``,
each a compile or a cache load) less cache hits, up to the end of the
window's last ``train.step_batch`` span.  The window compiles nothing, so
that is set-up's count; the reference, which runs after the window, is
left out.  Nothing where the program has no such counters, or where more
programs followed than ``obs.recent`` keeps."""


def read(rec):
    try:
        from repro import obs
    except ImportError:
        return None
    batches = obs.recent("train.step_batch")
    if not rec.get("window") or not batches:
        return None
    end = batches[-1][0]
    snap = obs.snapshot()

    def before(name, total, weight):
        after = [weight(v) for t, v in obs.recent(name) if t > end]
        return None if len(after) == obs.RECENT else total - sum(after)

    done = before("jax.compile", snap["spans"].get(
        "jax.compile", {"count": 0})["count"], lambda s: 1)
    hits = before("compile_cache.hits",
                  snap["counts"].get("compile_cache.hits", 0), lambda n: n)
    return None if done is None or hits is None else float(done - hits)
