"""Every metric reader on a hand-made record: the readers that were there
read the same numbers from the same record, and the new ones read the
program's spans and counters, or nothing where the program has none."""
import sys

import numpy as np
import pytest

from bench import spec
from bench_cells import REPO

BENCH_DIR = f"{REPO}/bench"
REC = {
    "chips": 4, "search_s": 0.97, "plan_step_s": 0.45, "compile_s": 3.0,
    "setup_s": 20.5, "peak_hbm_bytes": 7.0e9,
    "window": {"steps": 6, "seconds": 3.0, "tokens": 6 * 32 * 1024},
    "flops_per_token": 3.1e9, "peak_flops_per_s": 197e12,
    "trace": {"window_s": 3.0, "busy_s": 2.94, "busy0_s": 2.95, "steps": 6,
              "step_device_s": 0.46, "compute_s_per_step": 0.395,
              "sync_exposed_s_per_step": 0.06,
              "collective_s_per_step": 0.08, "device_ops": [],
              "idle_gaps": []},
}
OLD = {
    "tokens_per_s": 6 * 32 * 1024 / 3.0,
    "peak_hbm_gb": 7.0,
    "setup_s": 20.5,
    "search_s": 0.97,
    "compile_s": 3.0,
    "compute_ms": 395.0,
    "device_idle_share": 2.0,
    "plan_error_pct": 100 * 0.01 / 0.46,
    "mfu": 100 * 3.1e9 * 65536 / (4 * 197e12),
}


@pytest.mark.parametrize("name", sorted(OLD))
def test_reader_on_a_hand_made_record(name):
    read = spec.metric_reader(BENCH_DIR, name)
    assert read(REC) == pytest.approx(OLD[name])


@pytest.mark.parametrize("name", sorted(OLD))
def test_reader_on_an_empty_record(name):
    assert spec.metric_reader(BENCH_DIR, name)({"chips": 1}) is None


class Tokens:
    def global_step_batch(self, step):
        return np.arange(8) + step


def test_program_span_and_counter_readers():
    """Set-up makes two batches and two programs, one loaded from the
    cache; the window makes three batches; the reference then compiles two
    more.  ``input_ms`` reads the window's batches, ``cache_misses`` set-up's
    programs."""
    from repro import obs
    from repro.launch import train as T

    obs.reset()
    try:
        for i in range(2):
            T.step_batch({}, Tokens(), i, 5)
        obs._add("jax.compile", 1.0)
        obs._add("jax.compile", 1.0)
        obs.count("compile_cache.hits")
        for i in range(2, 5):
            T.step_batch({}, Tokens(), i, 5)
        window = [s for _, s in obs.recent("train.step_batch")[-3:]]
        obs._add("jax.compile", 1.0)
        obs._add("jax.compile", 1.0)
        rec = {"window": {"steps": 3, "seconds": 1.0, "tokens": 24}}
        assert spec.metric_reader(BENCH_DIR, "input_ms")(rec) == \
            pytest.approx(sum(window) / 3 * 1e3)
        assert spec.metric_reader(BENCH_DIR, "cache_misses")(rec) == 1.0
        for name in ("input_ms", "cache_misses"):
            assert spec.metric_reader(BENCH_DIR, name)({}) is None
        for _ in range(obs.RECENT):     # set-up's count is then unknown
            obs._add("jax.compile", 1.0)
        assert spec.metric_reader(BENCH_DIR, "cache_misses")(rec) is None
    finally:
        obs.reset()


@pytest.mark.parametrize("name", ["input_ms", "cache_misses"])
def test_program_readers_without_the_program(monkeypatch, name):
    import repro

    monkeypatch.delattr(repro, "obs", raising=False)
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert spec.metric_reader(BENCH_DIR, name)({}) is None
