"""The comparison that decides ``correct`` fails what it must, at test
size on the CPU: the control (the reference with float8 matmul operands,
the precision below the configuration's bfloat16) and a timed path broken
underneath a run: a step that leaves its state unchanged, biases and norm
weights left unmoved, half of each batch left out, an answer altered where
it is produced, and the exchange between chips left out.

The test cells' limits (``bench_cells.TINY_LIMITS``) were set from CPU
readings at these sizes, largest of the program over seeds 1-6 and
smallest of each fault over seeds 1-3, the two one-chip cells:

    number       program   fp8 control   half batch
    loss_gap     6.6e-5    1.7e-4        3.1e-3
    grad_gap     1.9e-3    6.7e-3        3.6e-2
    change_gap   0.080     0.013         0.060

A state left unchanged reads 1 on ``change_gap`` and ``grad_gap``, and a
leaf left unmoved reads 1 on ``change_gap``.
"""
import json
import os
import pathlib
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import pytest

from bench import check, harness, spec
from bench_cells import REPO, TINY_LIMITS


@pytest.mark.parametrize("cell", ["tiny-mha.1chip", "tiny-gqa.1chip"])
def test_control_fails(tiny_root, cell):
    root = pathlib.Path(tiny_root)
    c = spec.load_cell(root, cell, root / "bench")
    pool = harness.TokenPool(c["traffic"], c["config"]["model"]["vocab"],
                             2, 1)
    dev = jax.devices()[:1]
    ref = harness.reference_run(c, 1, pool, dev)
    ctl = harness.reference_run(c, 1, pool, dev, numerics="fp8")
    names = harness.reference.leaf_names(c["reference"],
                                         c["config"]["model"])
    ok, lines = check.verdict(harness.compare(c, names, ctl, ref),
                              TINY_LIMITS)
    assert not ok, lines


def _frozen(monkeypatch, T, TS, ST):
    real = T.compile_step

    def compile_step(*a, **k):
        step, s = real(*a, **k)

        def frozen(params, opt, batch):
            keep = jax.tree.map(jnp.copy, (params, opt))
            return (*keep, step(params, opt, batch)[2])

        frozen.as_text = step.as_text
        return frozen, s

    monkeypatch.setattr(T, "compile_step", compile_step)


def _small_leaves_frozen(monkeypatch, T, TS, ST):
    """Biases and norm weights keep their first values; every other leaf,
    and the optimizer's state, steps as it should."""
    real = T.compile_step

    def compile_step(*a, **k):
        step, s = real(*a, **k)

        def small(path):
            return jax.tree_util.keystr(path).endswith(
                ("['bq']", "['bk']", "['bv']", "['bias']", "['scale']"))

        def frozen(params, opt, batch):
            # copied first: the step donates its arguments
            old = jax.tree_util.tree_map_with_path(
                lambda path, x: jnp.copy(x) if small(path) else None, params)
            p, o, met = step(params, opt, batch)
            p = jax.tree_util.tree_map_with_path(
                lambda path, new, x: x if small(path) else new, p, old)
            return p, o, met

        frozen.as_text = step.as_text
        return frozen, s

    monkeypatch.setattr(T, "compile_step", compile_step)


def _half_batch(monkeypatch, T, TS, ST):
    real = ST.loss_fn

    def loss_fn(params, cfg, batch, **kw):
        half = batch["tokens"].shape[0] // 2
        return real(params, cfg, {"tokens": batch["tokens"][:half]}, **kw)

    monkeypatch.setattr(ST, "loss_fn", loss_fn)


def _loss_altered(monkeypatch, T, TS, ST):
    real = T.compile_step

    def compile_step(*a, **k):
        step, s = real(*a, **k)

        def altered(params, opt, batch):
            p, o, met = step(params, opt, batch)
            return p, o, {**met, "loss": met["loss"] * 1.01}

        altered.as_text = step.as_text
        return altered, s

    monkeypatch.setattr(T, "compile_step", compile_step)


@pytest.mark.parametrize("fault", [_frozen, _small_leaves_frozen,
                                   _half_batch, _loss_altered])
def test_broken_step_is_not_correct(tiny_root, monkeypatch, fault):
    from repro.distributed import train_step as TS
    from repro.launch import train as T
    from repro.models import stacked as ST

    fault(monkeypatch, T, TS, ST)
    root = pathlib.Path(tiny_root)
    cell = spec.load_cell(root, "tiny-gqa.1chip", root / "bench")
    res = harness.run_cell(cell, 5, 0.2, False, jax.devices()[:1],
                           time.perf_counter())
    assert res["correct"] is False, res["checks"]


CHILD = textwrap.dedent("""
    import json, pathlib, sys, time
    root = pathlib.Path(sys.argv[1])
    sys.path[:0] = [str(root), sys.argv[2]]
    import jax
    import numpy as np
    from bench import harness, spec
    from repro.distributed import train_step as TS
    from repro.launch import train as T
    T.use_compile_cache = lambda: "off"
    cell = spec.load_cell(root, "tiny-gqa.dp4", root / "bench")
    pool = harness.TokenPool(cell["traffic"], 512, 8, 3)
    batches = [pool.global_step_batch(i) for i in range(3)]
    m, oc = cell["config"]["model"], cell["traffic"]["optimizer"]
    fam = cell["reference"]
    one = harness.reference.train(fam, m, oc, 3, batches, 4)
    four = harness.reference.train(fam, m, oc, 3, batches, 4,
                                   devices=jax.devices()[:4])
    for r in (one, four):
        r["change"] = [float(np.sqrt(np.sum(np.square(c))))
                       for c in r["change"]]
    out = {"spread_reference": max(
        float(abs(np.asarray(four[k]) - np.asarray(one[k])).max()
              / abs(np.asarray(one[k])).max())
        for k in ("loss", "grad1", "change"))}
    for kind in ("sound", "no_exchange"):
        if kind == "no_exchange":
            TS.sync_grads = lambda grads, *a, **k: grads
        res = harness.run_cell(cell, 7, 0.2, False, jax.devices()[:4],
                               time.perf_counter())
        out[kind] = res["correct"]
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def four_device_runs(tmp_path_factory):
    """A sound run of the data-parallel test cell on four CPU devices, then
    one with the bucketed gradient sync left out, in one child process
    (the host device count is fixed when JAX starts)."""
    from bench_cells import add_tiny_cells

    root = add_tiny_cells(str(tmp_path_factory.mktemp("dp4")))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", CHILD, root,
                        os.path.join(REPO, "src")],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("kind,correct", [("sound", True),
                                          ("no_exchange", False)])
def test_data_parallel_runs(four_device_runs, kind, correct):
    assert four_device_runs[kind] is correct


def test_reference_spread_over_four_devices(four_device_runs):
    """The reference with each block's rows on four devices gives what it
    gives on one, to float32 summation order."""
    assert four_device_runs["spread_reference"] < 1e-5
