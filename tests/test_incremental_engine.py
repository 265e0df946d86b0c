"""Golden equivalence: the incremental fusion-graph engine (maintained
quotient + delta simulation + rolling signature + worker pool) must be
bit-identical in cost to the seed full-replay path on fixed seeds."""
import random

import pytest

from repro.configs import get_config
from repro.core import (OracleEstimator, Simulator, backtracking_search,
                        profile_graph, trace_grad_graph)
from repro.core.graph import EW, FusionGraph, PrimOp
from repro.core.search import ALL_METHODS, random_apply


def traced_graph(arch: str):
    import jax

    from repro.data.pipeline import materialize_batch
    from repro.models import model as M

    cfg = get_config(arch).reduced()
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    data = materialize_batch(cfg, 2, 16, seed=0)
    return profile_graph(trace_grad_graph(
        lambda p, bt: M.loss_fn(p, cfg, bt), params, data))


@pytest.fixture(scope="module")
def transformer_graph():
    return traced_graph("transformer-paper")


@pytest.fixture(scope="module")
def qwen_graph():
    return traced_graph("qwen2-0.5b")


def _mutation_walk_equivalence(g0, seed, steps=60):
    """After every accepted mutation: maintained quotient == from-scratch
    quotient, and delta-path SimResult == full-replay SimResult (bit-equal)."""
    rng = random.Random(seed)
    sim_inc = Simulator(n_devices=64, incremental=True)
    sim_full = Simulator(n_devices=64, incremental=False)
    parent = g0
    for step in range(steps):
        child = parent.clone()
        for _ in range(rng.randint(1, 3)):
            random_apply(child, rng.choice(ALL_METHODS), 1, rng)
        succs, preds = child.quotient()
        succs2, preds2 = child._quotient_from_scratch()
        assert succs == succs2 and preds == preds2, step
        ri = sim_inc.run(child)
        rf = sim_full.run(child)
        assert ri.iteration_time == rf.iteration_time, step
        assert ri.compute_time == rf.compute_time, step
        assert ri.comm_time == rf.comm_time, step
        assert ri.compute_finish == rf.compute_finish, step
        assert ri.comm_finish == rf.comm_finish, step
        if rng.random() < 0.6:
            parent = child
    assert sim_inc.stats["delta"] > 0, "delta path never exercised"


def test_mutation_walk_equivalence_transformer(transformer_graph):
    _mutation_walk_equivalence(transformer_graph, seed=0)


def test_mutation_walk_equivalence_qwen(qwen_graph):
    _mutation_walk_equivalence(qwen_graph, seed=1, steps=40)


@pytest.mark.parametrize("seed", [0, 7])
def test_search_golden_equivalence_transformer(transformer_graph, seed):
    kw = dict(unchanged_limit=30, max_steps=40, seed=seed)
    r_inc = backtracking_search(
        transformer_graph, Simulator(n_devices=64, incremental=True), **kw)
    r_full = backtracking_search(
        transformer_graph, Simulator(n_devices=64, incremental=False), **kw)
    assert r_inc.best_cost == r_full.best_cost
    assert r_inc.initial_cost == r_full.initial_cost
    assert r_inc.steps == r_full.steps
    assert r_inc.simulations == r_full.simulations
    assert r_inc.best.signature() == r_full.best.signature()


def test_search_golden_equivalence_qwen(qwen_graph):
    kw = dict(unchanged_limit=25, max_steps=30, seed=3)
    r_inc = backtracking_search(
        qwen_graph, Simulator(n_devices=64, incremental=True), **kw)
    r_full = backtracking_search(
        qwen_graph, Simulator(n_devices=64, incremental=False), **kw)
    assert r_inc.best_cost == r_full.best_cost
    assert r_inc.simulations == r_full.simulations
    assert r_inc.best.signature() == r_full.best.signature()


# --------------------------------------------------------------- unit tests
def chain_graph(n=12, grads=(3, 6, 9)):
    prims = []
    for i in range(n):
        prims.append(PrimOp(
            pid=i, op_type="mul", category=EW, flops=100.0, in_bytes=64.0,
            out_bytes=64.0, time=1e-6,
            grad_param=list(grads).index(i) if i in grads else -1,
            grad_bytes=256.0 if i in grads else 0.0,
            grad_sig="f32" if i in grads else ""))
    return FusionGraph(prims, [(i, i + 1) for i in range(n - 1)])


def test_fast_signature_tracks_full_signature():
    """Graphs with equal strategies have equal rolling hashes regardless of
    the mutation path that produced them."""
    a = chain_graph()
    b = chain_graph()
    # same end state via different operand orders
    assert a.fuse_nondup(2, 1) and a.fuse_nondup(a.provider[1], 0)
    assert b.fuse_nondup(1, 0) and b.fuse_nondup(2, b.provider[0])
    assert a.signature() == b.signature()
    assert a.fast_signature() == b.fast_signature()
    # diverge: hashes must split too
    assert a.merge_buckets(0, 1)
    assert a.fast_signature() != b.fast_signature()


class _ConstSim:
    """Stub simulator: constant cost — no candidate ever improves."""

    def cost(self, g):
        return 1.0


def test_unchanged_counted_once_per_step():
    """Paper Alg. 1: patience is per dequeued step, independent of how many
    method draws a step makes (the seed counted up to 3x per step)."""
    res = backtracking_search(chain_graph(), _ConstSim(), unchanged_limit=9,
                              alpha=2.0, seed=0)
    assert res.steps == 9


def test_estimator_cache_not_stale_across_graphs():
    """One estimator shared across graphs whose prims differ (same pids,
    different flops/bytes) must not return cached times from the other."""
    prims_a = [PrimOp(i, "mul", EW, 1e4, 64.0, 64.0, 0.0) for i in range(3)]
    prims_b = [PrimOp(i, "mul", EW, 1e9, 1e6, 1e6, 0.0) for i in range(3)]
    edges = [(0, 1), (1, 2)]
    ga = profile_graph(FusionGraph(prims_a, edges))
    gb = profile_graph(FusionGraph(prims_b, edges))
    est = OracleEstimator()
    gid_a = next(iter(ga.groups))
    gid_b = next(iter(gb.groups))
    ta = est.group_time(ga, gid_a)
    tb = est.group_time(gb, gid_b)
    assert ta != tb
    # and repeated queries still hit the (now correctly keyed) cache
    assert est.group_time(ga, gid_a) == ta
    assert est.group_time(gb, gid_b) == tb


def test_worker_pool_matches_serial():
    g = chain_graph(n=10, grads=(4, 8))
    kw = dict(unchanged_limit=20, max_steps=25, seed=5)
    r_ser = backtracking_search(g, Simulator(n_devices=64), **kw)
    r_par = backtracking_search(g, Simulator(n_devices=64), workers=2, **kw)
    assert r_par.best_cost == r_ser.best_cost
    assert r_par.simulations == r_ser.simulations
    assert r_par.best.signature() == r_ser.best.signature()


def test_worker_pool_imports_no_jax():
    """The spawn pool's workers price candidates without importing jax, so
    a search with ``workers > 1`` starts no child that could reach for the
    process's accelerator.  The parent is a bare ``-c`` interpreter: spawn
    re-imports only the parent's ``__main__`` besides the pool's own code."""
    import os
    import subprocess
    import sys

    code = (
        "import sys\n"
        "from repro.core import Simulator\n"
        "from repro.core.search import _CandidatePool\n"
        "sys.path.insert(0, 'tests')\n"
        "from test_incremental_engine import chain_graph\n"
        "g = chain_graph(n=6, grads=(2, 4))\n"
        "pool = _CandidatePool(Simulator(n_devices=8), g, workers=2)\n"
        "assert pool.evaluate([g])[0] > 0\n"
        "print('JAX_IN_WORKER', pool._ex.submit(\n"
        "    eval, \"'jax' in __import__('sys').modules\").result())\n"
        "pool.close()\n")
    root = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"))
    proc = subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "JAX_IN_WORKER False" in proc.stdout, proc.stdout
