"""The plain reference against the program at test size on the CPU: the
same weights from a seed, the same loss and gradients in float32, and the
same training steps through the trainer's own compiled step; and the form
in which a configuration reaches the reference's jitted functions."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, reference as R, spec
from bench_cells import OPTIMIZER, TINY

DENSE = spec.reference_family(spec.BENCH, "dense")


def _cfg(name, **kw):
    from repro.models.config import ModelConfig

    return ModelConfig(**{**TINY[name], **kw})


@pytest.mark.parametrize("name", sorted(TINY))
def test_weights_from_the_seed(name):
    from repro.models import stacked as ST

    cfg = _cfg(name)
    prog = jax.jit(ST.init_params, static_argnums=1)(
        jax.random.PRNGKey(2**31 + 7), cfg)
    ref = R.init_params(DENSE, 2**31 + 7, dataclasses.asdict(cfg))
    pl = jax.tree_util.tree_flatten_with_path(prog)[0]
    rl = jax.tree_util.tree_flatten_with_path(ref)[0]
    assert [jax.tree_util.keystr(k) for k, _ in pl] == \
        [jax.tree_util.keystr(k) for k, _ in rl]
    assert R.leaf_names(DENSE, dataclasses.asdict(cfg)) == \
        [jax.tree_util.keystr(k) for k, _ in pl]
    for (_, a), (_, b) in zip(pl, rl):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b))


@pytest.mark.parametrize("name", sorted(TINY))
def test_loss_and_gradients_in_float32(name):
    from repro.models import stacked as ST

    cfg = _cfg(name, dtype="float32")
    m = dataclasses.asdict(cfg)
    toks = np.random.default_rng(3).integers(0, cfg.vocab, (4, 64),
                                             dtype=np.int32)
    params = R.init_params(DENSE, 5, m)
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.jit(jax.value_and_grad(
            lambda p, t: ST.loss_fn(p, cfg, {"tokens": t})))(params, toks)
        lr, gr = jax.jit(jax.value_and_grad(
            lambda p, t: DENSE.mean_loss(p, m, t.reshape(2, 2, 64))))(
                params, toks)
    np.testing.assert_allclose(float(lp), float(lr), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-7)


def _program_steps(cfg, seed, batches):
    """Three steps of the trainer's compiled step on one device, read as
    the benchmark reads them."""
    from bench import harness
    from repro.distributed.train_step import GradSyncStrategy
    from repro.launch import train as T
    from repro.launch.mesh import make_mesh
    from repro.optim import adamw, linear_warmup_cosine

    oc = OPTIMIZER
    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    params = T.init_params(cfg, seed)
    optimizer = adamw(linear_warmup_cosine(
        oc["lr"], warmup=oc["warmup"], total_steps=oc["total_steps"],
        final_frac=oc["final_frac"]), weight_decay=oc["weight_decay"])
    opt = optimizer[0](jax.tree.map(lambda p: p.astype(jnp.float32), params))
    ex = {"tokens": jnp.asarray(batches[0])}
    step, _ = T.compile_step(cfg, mesh, GradSyncStrategy.size_capped(params),
                             params, opt, ex, optimizer)
    out = {"loss": []}
    for i, b in enumerate(batches):
        params, opt, met = step(params, opt, {"tokens": jnp.asarray(b)})
        out["loss"].append(float(met["loss"]))
        if i == 0:
            out["grad1"] = np.asarray(harness._leaf_norms(opt.mu)) \
                / (1 - oc["b1"])
    out["change"] = [np.asarray(c) for c in harness._change_norms(
        params, T.init_params(cfg, seed))]
    return out


@pytest.mark.parametrize("name", sorted(TINY))
def test_training_steps_in_float32(name):
    """In float32 the trainer's steps and the reference's agree to
    rounding: every compared number reads near 0."""
    cfg = _cfg(name, dtype="float32")
    m = dataclasses.asdict(cfg)
    rng = np.random.default_rng(11)
    batches = [rng.integers(0, cfg.vocab, (2, 64), dtype=np.int32)
               for _ in range(3)]
    with jax.default_matmul_precision("highest"):
        got = _program_steps(cfg, 9, batches)
    ref = R.train(DENSE, m, OPTIMIZER, 9, batches, 1)
    found = check.readings(got, ref, R.leaf_names(DENSE, m))
    assert found["loss_gap"][0] < 1e-6, found
    assert found["grad_gap"][0] < 1e-4, found
    assert found["change_gap"][0] < 1e-3, found


def test_frozen_settings_keep_nested_blocks():
    """A nested block reaches a jitted function as a dict, a list as a
    tuple; the frozen form is hashable and thaws to what it was made
    from."""
    from repro.configs import get_config

    m = dataclasses.asdict(get_config("deepseek-v2-lite-16b").reduced())
    frozen = R.freeze(m)
    hash(frozen)
    assert R.thaw(frozen) == m
    assert R.freeze({"pattern": ["rec", "attn"]}) == (
        ("pattern", ("rec", "attn")),)

    top_k = jax.jit(lambda x, mi: x * R.thaw(mi)["moe"]["top_k"],
                    static_argnums=1)
    assert float(top_k(jnp.float32(1.0), frozen)) == m["moe"]["top_k"]
