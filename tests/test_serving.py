"""Serving-engine tests: continuous-batching correctness vs offline
decode, plus the plan-enactment surface added in DESIGN.md Sec. 15 —
chunked (gathered) decode dispatch, injected virtual clock, per-request
metrics, and ``ServeEngine(plan=...)``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import stacked as ST
from repro.serving import Request, ServeEngine, VirtualClock, Workload, replay


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("tinyllama-1.1b").reduced()
    params = ST.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def offline_greedy(params, cfg, prompt, n, cache_len=64):
    lg, caches = ST.prefill(params, cfg, jnp.asarray(prompt)[None], cache_len)
    toks = [int(jnp.argmax(lg[0]))]
    pos = len(prompt)
    for _ in range(n - 1):
        lg, caches = ST.decode_step(
            params, cfg, caches, jnp.asarray([toks[-1]], jnp.int32),
            jnp.int32(pos))
        toks.append(int(jnp.argmax(lg[0])))
        pos += 1
    return toks


def test_engine_matches_offline(setup):
    cfg, params = setup
    eng = ServeEngine(params, cfg, max_slots=3, cache_len=64)
    rng = np.random.default_rng(1)
    reqs = []
    for i in range(7):
        p = rng.integers(0, cfg.vocab, size=int(rng.integers(3, 16)))
        r = Request(rid=i, prompt=p.astype(np.int32),
                    max_new_tokens=int(rng.integers(4, 12)))
        reqs.append(r)
        eng.submit(r)
    done = eng.run_to_completion()
    assert len(done) == 7
    for r in sorted(done, key=lambda r: r.rid):
        ref = offline_greedy(params, cfg, r.prompt, len(r.output))
        assert r.output == ref, f"request {r.rid} diverged"


def test_engine_slot_reuse(setup):
    """More requests than slots: slots must be recycled correctly."""
    cfg, params = setup
    eng = ServeEngine(params, cfg, max_slots=2, cache_len=48)
    rng = np.random.default_rng(2)
    for i in range(5):
        eng.submit(Request(rid=i,
                           prompt=rng.integers(0, cfg.vocab, size=5).astype(
                               np.int32),
                           max_new_tokens=4))
    done = eng.run_to_completion()
    s = eng.metrics()
    assert s["completed"] == 5
    assert s["tokens"] == 5 * 4
    # with 2 slots and 5 requests of 4 tokens, decode steps must exceed 4
    assert s["decode_steps"] >= 8
    assert 0 < s["mean_ttft_s"] <= s["mean_latency_s"]


def test_engine_eos_stops(setup):
    cfg, params = setup
    # find the first greedily generated token, use it as eos -> length 1
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab, size=6).astype(np.int32)
    first = offline_greedy(params, cfg, prompt, 2)
    eng = ServeEngine(params, cfg, max_slots=1, cache_len=48)
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=16,
                       eos_id=first[1]))
    done = eng.run_to_completion()
    assert done[0].output[-1] == first[1]
    assert len(done[0].output) == 2


def test_request_timing_none_until_finished(setup):
    cfg, params = setup
    r = Request(rid=0, prompt=np.zeros(4, np.int32), max_new_tokens=4)
    # unfinished requests report None, never a nonsense 0/negative
    assert r.ttft is None and r.latency is None
    eng = ServeEngine(params, cfg, max_slots=1, cache_len=48,
                      clock=VirtualClock())
    eng.submit(r)
    assert r.submitted_at == 0.0 and r.ttft is None
    eng.run_to_completion()
    assert r.ttft is not None and r.latency is not None
    assert r.latency >= r.ttft >= 0.0


def test_chunked_dispatch_matches_offline(setup):
    """decode_batch < max_slots takes the gathered-chunk decode path; the
    generated tokens must be bit-identical to the full-width engine."""
    cfg, params = setup
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab, size=int(rng.integers(3, 12)))
               for _ in range(5)]
    eng = ServeEngine(params, cfg, max_slots=4, cache_len=64,
                      decode_batch=2)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p.astype(np.int32),
                           max_new_tokens=6))
    done = eng.run_to_completion()
    assert len(done) == 5
    for r in sorted(done, key=lambda r: r.rid):
        ref = offline_greedy(params, cfg, r.prompt, len(r.output))
        assert r.output == ref, f"request {r.rid} diverged (chunked path)"


def test_plan_enactment_and_metrics(setup):
    cfg, params = setup
    from repro.serving.plan import compile_serving

    plan = compile_serving("tinyllama-1.1b", cluster="tpu_v5e_pod_16",
                           workload=Workload(n_requests=16, seed=0),
                           unchanged_limit=8, max_steps=15, seed=0)
    clk = VirtualClock()
    eng = ServeEngine(params, cfg, plan=plan, max_slots=3, cache_len=48,
                      decode_batch=2, clock=clk)
    # explicit kwargs clamp the pod-sized plan onto this host
    assert eng.max_slots == 3 and eng.decode_batch == 2
    assert eng.plan is plan and eng.kv_layout == plan.kv_layout
    wl = Workload(n_requests=5, rate=64.0, concurrency=3,
                  prompt_lens=(3, 6), new_tokens=(2, 4), seed=2)
    m = replay(eng, wl, step_time=1e-3)
    assert m["completed"] == 5
    assert m["tokens"] == sum(r.new_tokens for r in wl.requests()) \
        or m["tokens"] >= m["completed"]  # eos can shorten outputs
    for k in ("tokens_per_s", "ttft_p50_s", "ttft_p99_s", "tpot_p50_s",
              "latency_p50_s", "latency_p99_s", "mean_ttft_s"):
        assert k in m
    assert m["tokens_per_s"] > 0.0
    assert m["latency_p99_s"] >= m["ttft_p50_s"] >= 0.0


def test_replay_is_deterministic(setup):
    cfg, params = setup
    wl = Workload(n_requests=4, rate=64.0, concurrency=2,
                  prompt_lens=(3, 6), new_tokens=(2, 4), seed=5)

    def one():
        eng = ServeEngine(params, cfg, max_slots=2, cache_len=48,
                          clock=VirtualClock())
        return replay(eng, wl, step_time=1e-3)

    assert one() == one()  # virtual time: bit-identical metrics


def test_replay_rejects_wall_clock(setup):
    cfg, params = setup
    eng = ServeEngine(params, cfg, max_slots=1, cache_len=48)
    with pytest.raises(TypeError):
        replay(eng, Workload(n_requests=2, seed=0))
