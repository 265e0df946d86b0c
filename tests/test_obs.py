"""The program's spans, scopes and counters (``repro.obs``): aggregates of
nested spans, counters fed by a real compile, the persistent compilation
cache and a forced collection, and program spans found in a CPU profiler
trace of the trainer by the benchmark's trace reader."""
import gc
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from repro import obs

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture
def fresh():
    obs.reset()
    yield
    obs.reset()


def test_nested_spans_aggregate(fresh):
    with obs.span("outer") as out:
        for _ in range(3):
            with obs.span("inner") as inn:
                time.sleep(0.002)
    snap = obs.snapshot()["spans"]
    assert snap["outer"]["count"] == 1 and snap["inner"]["count"] == 3
    assert inn.seconds >= 0.002 and out.seconds >= 3 * 0.002
    assert snap["outer"]["total_s"] == pytest.approx(out.seconds)
    assert snap["inner"]["total_s"] <= snap["outer"]["total_s"]
    assert snap["inner"]["max_s"] <= snap["inner"]["total_s"]


def test_delta_and_log_line(fresh):
    with obs.span("a"):
        pass
    obs.count("c", 2)
    old = obs.snapshot()
    with obs.span("a"):
        pass
    with obs.span("b"):
        pass
    obs.count("c")
    d = obs.delta(obs.snapshot(), old)
    assert {k: v["count"] for k, v in d["spans"].items()} == {"a": 1, "b": 1}
    assert d["counts"] == {"c": 1}
    line = obs.line(d)
    assert line.startswith("a ") and " ms x1; b " in line
    assert line.endswith("; c 1")
    assert obs.line(obs.delta(old, old)) == "no spans"


def test_recent_keeps_the_last_calls_in_order(fresh):
    for i in range(obs.RECENT + 3):
        obs._add("s", float(i))
    obs.count("c", 2)
    got = obs.recent("s")
    assert [v for _, v in got] == [float(i) for i in range(3, obs.RECENT + 3)]
    assert all(a[0] <= b[0] for a, b in zip(got, got[1:]))
    assert [v for _, v in obs.recent("c")] == [2]
    assert obs.recent("none") == []


def test_span_closes_on_error(fresh):
    with pytest.raises(ValueError):
        with obs.span("fails"):
            raise ValueError("x")
    assert obs.snapshot()["spans"]["fails"]["count"] == 1


def test_compile_and_collection_are_counted(fresh):
    def f(x):
        return x * 3.0 + 1.0

    jax.block_until_ready(jax.jit(f)(jnp.arange(7.0)))
    gc.collect()
    snap = obs.snapshot()["spans"]
    assert snap["jax.compile"]["count"] >= 1
    assert snap["jax.compile"]["total_s"] > 0
    assert snap["python.gc"]["count"] >= 1


def test_scope_names_ops_and_keeps_numbers():
    @obs.scope("attention")
    def g(x):
        return jnp.sin(x) * 2.0

    x = jnp.linspace(0.0, 1.0, 8)
    text = jax.jit(g).lower(x).as_text(debug_info=True)
    assert "attention/sin" in text
    assert (jax.jit(g)(x) == jax.jit(g.__wrapped__)(x)).all()


CACHE_CHILD = """
import json, sys
import jax, jax.numpy as jnp
from repro import obs
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
def f(x):
    return jnp.cos(x) @ x.T
x = jnp.ones((16, 16))
jax.block_until_ready(jax.jit(f)(x))
first = obs.snapshot()["counts"]
jax.clear_caches()
jax.block_until_ready(jax.jit(f)(x))
print(json.dumps([first, obs.snapshot()["counts"]]))
"""


def test_persistent_cache_hits_and_misses(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               PYTHONPATH=os.path.join(REPO, "src"))
    p = subprocess.run([sys.executable, "-c", CACHE_CHILD], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    first, then = json.loads(p.stdout.strip().splitlines()[-1])
    assert first.get("compile_cache.misses", 0) >= 1
    assert first.get("compile_cache.hits", 0) == 0
    assert then["compile_cache.hits"] >= 1
    assert then["compile_cache.misses"] == first["compile_cache.misses"]


def test_trainer_spans_in_a_profiler_trace(tmp_path, monkeypatch, capsys):
    """One tiny step of ``train.main`` under the CPU profiler: the step's
    spans are on the trace's host plane, where ``bench.trace.load`` finds
    them, and the log line gives their means and the counters."""
    sys.path.insert(0, REPO)
    from bench import trace
    from repro.launch import train as T

    monkeypatch.setattr(T, "use_compile_cache", lambda: "off")
    jax.profiler.start_trace(str(tmp_path))
    try:
        rec = T.main(["--reduced", "--steps", "2", "--batch", "2",
                      "--seq", "16", "--log-every", "1"])
    finally:
        jax.profiler.stop_trace()
    spans = trace.load(str(tmp_path), span_prefix="train.")["spans"]
    names = [n for n, _, _ in spans]
    for n in ("train.step_batch", "train.dispatch", "train.wait",
              "train.loss", "train.init_params", "train.compile"):
        assert n in names, n
    waits = [e - s for n, s, e in spans if n == "train.wait"]
    sent = [e - s for n, s, e in spans if n == "train.dispatch"]
    assert len(rec["step_ms"]) == 2 == len(waits)
    for ms, w, d in zip(rec["step_ms"], waits, sent):
        assert ms == pytest.approx((w + d) * 1e-6, rel=0.2, abs=0.5)
    out = capsys.readouterr().out
    first = [ln for ln in out.splitlines() if ln.startswith("step     0")]
    assert "train.wait " in first[0] and "train.compile " in first[0]
    assert "jax.compile " in first[0]
