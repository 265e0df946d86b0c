"""The ``mla_moe`` reference family against the program at test size on the
CPU: DeepSeek-V2-Lite's structure (MLA with YaRN positions, a dense layer,
then expert layers of which this chip holds a share) at reduced widths,
with seeded random weights."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, reference as R, spec
from bench_cells import OPTIMIZER

FAM = spec.reference_family(spec.BENCH, "mla_moe")
HIGHEST = jax.lax.Precision.HIGHEST


def _cfg(dtype="float32", **moe):
    """The reduced DeepSeek-V2-Lite: 1 dense and 2 expert layers, 2 of 8
    experts held, YaRN, the balance loss per sequence."""
    from repro.configs import get_config

    cfg = get_config("deepseek-v2-lite-16b").reduced()
    assert cfg.rope_scaling is not None
    return dataclasses.replace(
        cfg, n_layers=3, dtype=dtype,
        moe=dataclasses.replace(cfg.moe, n_routed=8, n_held=2, **moe))


def _tokens(seed, rows, S=64, vocab=512):
    return np.random.default_rng(seed).integers(0, vocab, (rows, S),
                                                dtype=np.int32)


def test_weights_from_the_seed():
    from repro.models import stacked as ST

    cfg = _cfg("bfloat16")
    m = dataclasses.asdict(cfg)
    prog = jax.jit(ST.init_params, static_argnums=1)(
        jax.random.PRNGKey(2**31 + 5), cfg)
    ref = R.init_params(FAM, 2**31 + 5, m)
    pl = jax.tree_util.tree_flatten_with_path(prog)[0]
    assert R.leaf_names(FAM, m) == [jax.tree_util.keystr(k) for k, _ in pl]
    assert prog["groups"][1]["moe"]["w_up"].shape == (2, 2, 256, 128)
    for (_, a), b in zip(pl, jax.tree.leaves(ref)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b))


def test_loss_and_gradients_in_float32():
    """The program's loss (cross entropy plus the balance loss) and its
    gradients are the reference's to float32 rounding."""
    from repro.models import stacked as ST

    cfg = _cfg()
    m = dataclasses.asdict(cfg)
    toks = _tokens(3, 4)
    params = R.init_params(FAM, 5, m)

    def both(p, t):
        lp, gp = jax.value_and_grad(
            lambda p: ST.loss_fn(p, cfg, {"tokens": t}))(p)
        lr, gr = jax.value_and_grad(
            lambda p: FAM.mean_loss(p, m, t.reshape(2, 2, 64)))(p)
        return lp, gp, lr, gr, ST.hidden_forward(p, cfg, t)[1]

    with jax.default_matmul_precision("highest"):
        lp, gp, lr, gr, aux = jax.jit(both)(params, toks)
    assert float(aux) > 1e-4       # the balance loss is in the loss
    np.testing.assert_allclose(float(lp), float(lr), rtol=2e-6)
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                                   atol=1e-4 * scale + 1e-9)


@pytest.fixture(scope="module")
def steps():
    """Three trainer steps of the program in float32, and the reference's
    in float32 and with float8 operands, on the same batches."""
    from test_bench_reference import _program_steps

    cfg = _cfg()
    m = dataclasses.asdict(cfg)
    batches = [_tokens(11 + i, 2) for i in range(3)]
    with jax.default_matmul_precision("highest"):
        got = _program_steps(cfg, 9, batches)
    ref = R.train(FAM, m, OPTIMIZER, 9, batches, 1)
    fp8 = R.train(FAM, m, OPTIMIZER, 9, batches, 1, numerics="fp8")
    return got, ref, fp8, R.leaf_names(FAM, m)


def test_training_steps_in_float32(steps):
    """In float32 the trainer's steps and the reference's agree to
    rounding: every compared number reads near 0."""
    got, ref, _, names = steps
    found = check.readings(got, ref, names)
    assert found["loss_gap"][0] < 1e-6, found
    assert found["grad_gap"][0] < 1e-4, found
    assert found["change_gap"][0] < 1e-3, found


def test_float8_steps_are_told_apart(steps):
    """The reference with float8 matmul operands reads far from its own
    float32 steps: the comparison sees a lower precision."""
    _, ref, fp8, names = steps
    found = check.readings(fp8, ref, names)
    assert found["loss_gap"][0] > 1e-3, found
    assert found["grad_gap"][0] > 1e-2, found


def _layer_input(cfg, seed=4, B=2, S=16):
    return jax.random.normal(jax.random.PRNGKey(seed), (B, S, cfg.d_model))


def test_shares_add_up_to_the_uncut_layer():
    """Each share of 2 of the 8 experts, from every ``first_expert``, routes
    over all 8 and computes its own experts' part; the parts, with the
    shared experts and the balance loss counted once, add up to the uncut
    reference layer."""
    from repro.models import layers as L

    cfg = _cfg()
    uncut = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe,
                                                             n_held=0))
    key = jax.random.PRNGKey(6)
    x = _layer_input(cfg)
    with jax.default_matmul_precision("highest"):
        whole = FAM.moe_init(key, dataclasses.asdict(uncut))
        want, want_aux = FAM.moe(whole, dataclasses.asdict(uncut), x)
        shared = L.mlp_fwd(whole["shared"], cfg, x)
        total = jnp.zeros_like(x)
        for first in range(0, 8, 2):
            p = L.init_moe(key, cfg, first_expert=first)
            for name in ("w_up", "w_gate", "w_down"):
                np.testing.assert_array_equal(
                    p[name], whole[name][first:first + 2])
            out, aux = L.moe_fwd(p, cfg, x, first_expert=first)
            total = total + out - shared
            # the reference sums the per-sequence loss over the rows
            np.testing.assert_allclose(float(aux), float(want_aux) / 2,
                                       rtol=1e-5)
        total = total + shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_skewed_router_drops_nothing():
    """Every token sends its top-k choices to the same held experts: all
    ``T * k`` pairs land on this chip, and none is dropped."""
    from repro.models import layers as L

    cfg = _cfg()
    m = dataclasses.asdict(cfg)
    p = L.init_moe(jax.random.PRNGKey(7), cfg)
    router = np.full((cfg.d_model, 8), -1.0, np.float32)
    router[:, 0], router[:, 1] = 2.0, 1.0
    p["router"] = jnp.asarray(router)
    x = jnp.abs(_layer_input(cfg)) + 0.1
    with jax.default_matmul_precision("highest"):
        got, _ = L.moe_fwd(p, cfg, x)
        want, _ = FAM.moe(p, m, x)
    probs = jax.nn.softmax(x.reshape(-1, cfg.d_model) @ p["router"])
    assert bool(jnp.all(jnp.argsort(-probs, axis=-1)[:, :2]
                        == jnp.array([0, 1])))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("skewed", [False, True])
def test_grouped_rows_cover_half_the_bound(monkeypatch, skewed):
    """The grouped matmuls cover the held pairs and never fewer than half
    the static bound ``T * min(top_k, held)``: an even routing, about a
    quarter of the bound held here, costs what half of it costs, and a
    router that sends every pair here covers all of it.  The output is the
    reference's either way."""
    from repro.models import layers as L

    cfg = _cfg()
    m = dataclasses.asdict(cfg)
    p = L.init_moe(jax.random.PRNGKey(12), cfg)
    x = _layer_input(cfg)
    if skewed:
        router = np.full((cfg.d_model, 8), -1.0, np.float32)
        router[:, 0], router[:, 1] = 2.0, 1.0
        p["router"] = jnp.asarray(router)
        x = jnp.abs(x) + 0.1
    real = jax.lax.ragged_dot
    covered = []

    def counting(lhs, rhs, sizes):
        covered.append(int(jnp.sum(sizes)))
        return real(lhs, rhs, sizes)

    monkeypatch.setattr(jax.lax, "ragged_dot", counting)
    with jax.default_matmul_precision("highest"):
        got, _ = L.moe_fwd(p, cfg, x)
        want, _ = FAM.moe(p, m, x)
        probs = jax.nn.softmax(x.reshape(-1, cfg.d_model) @ p["router"])
    T, k, H = x.shape[0] * x.shape[1], cfg.moe.top_k, cfg.moe.held
    bound = T * min(k, H)
    held = int(jnp.sum(jax.lax.top_k(probs, k)[1] < H))
    assert held == bound if skewed else 0 < held < bound // 2
    assert covered == [max(held, bound // 2)] * 3
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("scaling", [1.0, 16.0])
def test_gates_not_renormalised(scaling):
    """The gates are the router's top-k probabilities as they are, whose
    sum is below 1, times ``routed_scaling`` (1 in V2-Lite, 16 in
    DeepSeek-V2): the routed part scales with it, and the program follows
    the reference."""
    from repro.models import layers as L

    cfg = _cfg(routed_scaling=scaling)
    one = _cfg(routed_scaling=1.0)
    m = dataclasses.asdict(cfg)
    p = L.init_moe(jax.random.PRNGKey(8), cfg)
    x = _layer_input(cfg)
    with jax.default_matmul_precision("highest"):
        got, _ = L.moe_fwd(p, cfg, x)
        want, _ = FAM.moe(p, m, x)
        unit, _ = L.moe_fwd(p, one, x)
        shared = L.mlp_fwd(p["shared"], cfg, x)
        probs = jax.nn.softmax(x @ p["router"], axis=-1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got - shared),
                               scaling * np.asarray(unit - shared),
                               rtol=1e-4, atol=1e-5)
    top = jax.lax.top_k(probs, cfg.moe.top_k)[0].sum(-1)
    assert float(jnp.max(top)) < 0.99
    assert float(jnp.max(jnp.abs(unit - shared))) > 1e-3


@pytest.mark.parametrize("rows", [1, 3])
def test_balance_loss_over_all_choices(rows):
    """The balance loss counts all k choices of every token, per sequence
    (``seq_aux``), as the reference does; a router that sends every token
    to the same experts costs more than a spread one."""
    from repro.models import layers as L

    cfg = _cfg()
    m = dataclasses.asdict(cfg)
    p = L.init_moe(jax.random.PRNGKey(10), cfg)
    x = _layer_input(cfg, B=rows)
    with jax.default_matmul_precision("highest"):
        _, got = L.moe_fwd(p, cfg, x)
        _, want = FAM.moe(p, m, x)
    # the reference sums over the rows; the program takes their mean
    np.testing.assert_allclose(float(got), float(want) / rows, rtol=1e-5)
    E, k = cfg.moe.n_routed, cfg.moe.top_k
    onehot = jax.nn.one_hot(jnp.zeros((rows, 16, k), jnp.int32)
                            + jnp.arange(k), E)
    skewed = L.balance_loss(
        cfg, onehot.sum(2) / k,
        jnp.zeros((rows, 16, k), jnp.int32) + jnp.arange(k))
    assert float(skewed) == pytest.approx(cfg.moe.aux_loss_coef * E / k)
    assert float(skewed) > float(got)


def test_rope_freqs_yarn():
    """YaRN's frequencies match a direct transcription of the published
    formulas (for 64 rope dims the ramp runs from dim 10 to 23); without
    ``rope_scaling`` the plain frequencies are kept bit for bit."""
    from repro.configs import get_config
    from repro.models import layers as L

    cfg = get_config("deepseek-v2-lite-16b")
    d, base, ys = 64, 10000.0, cfg.rope_scaling
    i = np.arange(d // 2)
    extra = base ** (-2.0 * i / d)
    low = math.floor(d * math.log(4096 / (32 * 2 * math.pi))
                     / (2 * math.log(base)))
    high = math.ceil(d * math.log(4096 / (1 * 2 * math.pi))
                     / (2 * math.log(base)))
    assert (low, high) == (10, 23)
    mask = 1 - np.clip((i - low) / (high - low), 0, 1)
    want = extra / ys.factor * (1 - mask) + extra * mask
    np.testing.assert_allclose(np.asarray(L.rope_freqs(cfg, d)), want,
                               rtol=1e-6)
    np.testing.assert_allclose(FAM.yarn_inv_freq(dataclasses.asdict(cfg), d),
                               want, rtol=1e-12)
    plain = dataclasses.replace(cfg, rope_scaling=None)
    assert np.array_equal(
        np.asarray(L.rope_freqs(plain, d)),
        np.asarray(1.0 / (base ** (np.arange(0, d, 2) / d)), np.float32))
    assert L.rope_mscale(cfg) == 1.0 and L.rope_mscale(plain) == 1.0


def test_mscale_reaches_the_scores():
    """YaRN's ``mscale(40, 0.707)^2 = 1.5896`` multiplies the softmax scale:
    the program's MLA matches the reference's, and not the reference's with
    the factor left out."""
    from repro.models import layers as L

    cfg = _cfg()
    m = dataclasses.asdict(cfg)
    assert abs(L.attn_mscale(cfg) - 1.5896) < 1e-4
    assert FAM.softmax_mscale(m) == L.attn_mscale(cfg)
    p = L.init_mla(jax.random.PRNGKey(9), cfg)
    x = _layer_input(cfg, B=1, S=32)
    with jax.default_matmul_precision("highest"):
        got = L.mla_fwd(p, cfg, x, jnp.arange(32))
        want = FAM._mla(p, m, x, "f32")
        unscaled = FAM._mla(p, {**m, "rope_scaling": {
            **m["rope_scaling"], "mscale_all_dim": 0.0,
            "mscale": 0.0}}, x, "f32")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    assert float(jnp.max(jnp.abs(got - unscaled))) > 1e-2


def test_flops_per_token_counts_the_held_share():
    """6 per active weight, the held experts at ``top_k * held / n_routed``
    a token, and MLA's causal attention; the published numbers of the
    benchmark's cut: 2.53 GFLOP a token at 8192."""
    import json

    with open(spec.BENCH / "configs" / "deepseek-v2-lite-l6e8.json") as f:
        m = json.load(f)["model"]
    assert FAM.held(m) == 8
    active = FAM.matmul_params(m)
    assert abs(active / 1e6 - 295.6) < 0.5
    attn = 6 * 8192 * 16 * (192 + 128) / 2 * 6
    assert FAM.flops_per_token(m, 8192) == 6.0 * active + attn
    assert abs(FAM.flops_per_token(m, 8192) / 1e9 - 2.53) < 0.01
    FAM.check_supported(m)
    for bad in ({"window": 128}, {"mla": {**m["mla"], "q_lora_rank": 64}},
                {"moe": {**m["moe"], "scoring_func": "sigmoid"}}):
        with pytest.raises(ValueError):
            FAM.check_supported({**m, **bad})


def test_rows_past_the_groups_never_reach_the_result(monkeypatch):
    """On the TPU a grouped matmul leaves the rows past its groups
    unwritten, forward and backward.  With NaN there, in results and in
    the cotangents of operands, the layer's output and gradients are
    those of the CPU's zeros."""
    from repro.models import layers as L

    real = jax.lax.ragged_dot

    @jax.custom_vjp
    def poison(x, past):
        return x

    def poison_fwd(x, past):
        return x, past

    def poison_bwd(past, g):
        return jnp.where(past[:, None], jnp.nan, g), None

    poison.defvjp(poison_fwd, poison_bwd)

    def unwritten(lhs, rhs, sizes):
        past = jnp.arange(lhs.shape[0]) >= jnp.sum(sizes)
        out = real(poison(lhs, past), rhs, sizes)
        return jnp.where(past[:, None], jnp.nan, out)

    cfg = _cfg()
    p = L.init_moe(jax.random.PRNGKey(11), cfg)
    x = _layer_input(cfg)

    def loss(p, x):
        out, aux = L.moe_fwd(p, cfg, x)
        return jnp.sum(out * out) + aux

    want = jax.value_and_grad(loss, argnums=(0, 1))(p, x)
    monkeypatch.setattr(jax.lax, "ragged_dot", unwritten)
    got = jax.value_and_grad(loss, argnums=(0, 1))(p, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-6)
