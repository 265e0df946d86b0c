"""Per-architecture smoke tests (deliverable f): reduced variant of each
assigned family runs one forward/train step on CPU with shape + finiteness
asserts; decode agrees with the parallel forward."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config
from repro.data.pipeline import materialize_batch
from repro.models import model as M
from repro.models import stacked as ST


@pytest.fixture(scope="module")
def key():
    return jax.random.PRNGKey(0)


def _batch(cfg, B=2, S=16):
    return materialize_batch(cfg, B, S, seed=0)


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_and_finite(arch, key):
    cfg = get_config(arch).reduced()
    params = ST.init_params(key, cfg)
    B, S = 2, 16
    batch = _batch(cfg, B, S)
    logits, aux = ST.forward(params, cfg, batch["tokens"],
                             prefix_emb=batch.get("prefix_emb"),
                             enc_frames=batch.get("enc_frames"))
    assert logits.shape == (B, S, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits)))
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_no_nans(arch, key):
    from repro.optim import adamw, apply_updates

    cfg = get_config(arch).reduced()
    params = ST.init_params(key, cfg)
    batch = _batch(cfg)
    init, update = adamw(1e-3)
    opt = init(params)
    loss, grads = jax.value_and_grad(
        lambda p: ST.loss_fn(p, cfg, batch, remat=True))(params)
    assert bool(jnp.isfinite(loss))
    for leaf in jax.tree.leaves(grads):
        assert bool(jnp.all(jnp.isfinite(leaf)))
    updates, opt = update(grads, opt, params)
    params2 = apply_updates(params, updates)
    loss2 = ST.loss_fn(params2, cfg, batch)
    assert bool(jnp.isfinite(loss2))


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "deepseek-v2-lite-16b",
                                  "recurrentgemma-9b", "rwkv6-3b",
                                  "seamless-m4t-medium"])
def test_decode_matches_forward(arch, key):
    cfg = get_config(arch).reduced()
    params = ST.init_params(key, cfg)
    B, S = 2, 12
    batch = _batch(cfg, B, S)
    toks = batch["tokens"]
    memory = (ST.encode(params, cfg, batch["enc_frames"])
              if cfg.encdec else None)
    logits_full, _ = ST.forward(params, cfg, toks,
                                enc_frames=batch.get("enc_frames"))
    caches = ST.init_cache(cfg, B, 32)
    for t in range(S):
        lg, caches = ST.decode_step(params, cfg, caches, toks[:, t],
                                    jnp.int32(t), memory=memory)
        np.testing.assert_allclose(np.asarray(lg),
                                   np.asarray(logits_full[:, t]),
                                   rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "qwen2-0.5b"])
def test_prefill_then_decode(arch, key):
    cfg = get_config(arch).reduced()
    params = ST.init_params(key, cfg)
    B, S = 2, 12
    toks = _batch(cfg, B, S)["tokens"]
    logits_full, _ = ST.forward(params, cfg, toks)
    lg_pf, caches = ST.prefill(params, cfg, toks[:, :-1], 32)
    np.testing.assert_allclose(np.asarray(lg_pf),
                               np.asarray(logits_full[:, S - 2]),
                               rtol=2e-3, atol=2e-3)
    lg, _ = ST.decode_step(params, cfg, caches, toks[:, -1],
                           jnp.int32(S - 1))
    np.testing.assert_allclose(np.asarray(lg),
                               np.asarray(logits_full[:, S - 1]),
                               rtol=2e-3, atol=2e-3)


def test_stacked_matches_unstacked(key):
    """The scanned-layer path is numerically identical to the per-layer
    loop (same per-layer RNG keys)."""
    cfg = get_config("tinyllama-1.1b").reduced()
    sp = ST.init_params(key, cfg)
    # rebuild the unstacked layout from the stacked leaves
    up = {k: v for k, v in sp.items() if k not in ("groups",)}
    layers = []
    g = sp["groups"][0]
    n = jax.tree.leaves(g)[0].shape[0]
    for i in range(n):
        layers.append(jax.tree.map(lambda a: a[i], g))
    up["layers"] = layers
    toks = _batch(cfg)["tokens"]
    l1, _ = ST.forward(sp, cfg, toks)
    l2, _ = M.forward(up, cfg, toks)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               rtol=1e-5, atol=1e-5)


def test_sliding_window_variant_matches_ref():
    """long_500k dense variant: windowed attention == full attention
    restricted to the window."""
    from repro.kernels.ref import flash_attention_ref

    cfg = dataclasses.replace(get_config("tinyllama-1.1b").reduced(),
                              window=8)
    key = jax.random.PRNGKey(1)
    params = ST.init_params(key, cfg)
    toks = _batch(cfg, 2, 24)["tokens"]
    logits, _ = ST.forward(params, cfg, toks)
    assert bool(jnp.all(jnp.isfinite(logits)))
    # direct attention check
    q = jax.random.normal(key, (1, 16, 4, 8))
    k = jax.random.normal(jax.random.PRNGKey(2), (1, 16, 2, 8))
    v = jax.random.normal(jax.random.PRNGKey(3), (1, 16, 2, 8))
    from repro.models.layers import sdpa
    out = sdpa(q, k, v, None, window=4)
    ref = flash_attention_ref(q, k, v, causal=True, window=4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_param_count_accounting():
    """active_param_count < param_count for MoE; both positive."""
    for arch in ARCHS:
        cfg = get_config(arch)
        n = cfg.param_count()
        na = cfg.active_param_count()
        assert n > 0 and na > 0
        if cfg.moe is not None:
            assert na < n
        else:
            assert na == n


def test_moe_dispatch_balanced_load_exact():
    """The sort-based dispatch drops nothing and does not renormalise the
    top-k gates (DeepSeek-V2's ``norm_topk_prob`` false): the MoE output
    equals the dense per-token expert mixture."""
    from repro.models import layers as L

    cfg = get_config("deepseek-v2-lite-16b").reduced()
    key = jax.random.PRNGKey(0)
    p = L.init_moe(key, cfg)
    x = jax.random.normal(key, (2, 8, cfg.d_model))
    out, aux = L.moe_fwd(p, cfg, x)
    # dense reference: run every expert on every token
    e = cfg.moe
    xt = x.reshape(-1, cfg.d_model)
    logits = jnp.dot(xt, p["router"], precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits.astype(jnp.float32), -1)
    topv, topi = jax.lax.top_k(probs, e.top_k)
    topv = topv * e.routed_scaling
    ref = jnp.zeros_like(xt)
    for ei in range(e.n_routed):
        up = xt @ p["w_up"][ei]
        gate = jax.nn.silu(xt @ p["w_gate"][ei])
        h = (gate * up) @ p["w_down"][ei]
        w = jnp.sum(jnp.where(topi == ei, topv, 0.0), axis=-1)
        ref = ref + h * w[:, None]
    ref = ref + L.mlp_fwd(p["shared"], cfg, xt)
    np.testing.assert_allclose(np.asarray(out.reshape(-1, cfg.d_model)),
                               np.asarray(ref), rtol=2e-4, atol=2e-4)
