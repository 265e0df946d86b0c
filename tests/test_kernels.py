"""Per-kernel correctness: shape/dtype sweeps asserted against the pure-jnp
oracles in ref.py (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops as K

RNG = np.random.default_rng(0)


def arr(shape, dtype=jnp.float32):
    return jnp.asarray(RNG.standard_normal(shape), dtype)


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,KV,hd", [
    (1, 128, 4, 4, 64),     # MHA
    (2, 256, 4, 2, 64),     # GQA
    (1, 256, 8, 1, 32),     # MQA
    (1, 128, 2, 2, 128),    # large head dim
])
def test_flash_attention_causal(B, S, H, KV, hd, dtype):
    q, k, v = arr((B, S, H, hd), dtype), arr((B, S, KV, hd), dtype), \
        arr((B, S, KV, hd), dtype)
    out = K.flash_attention(q, k, v, causal=True)
    ref = K.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol(dtype))


@pytest.mark.parametrize("window", [32, 128, 500])
def test_flash_attention_window(window):
    q, k, v = arr((1, 256, 4, 64)), arr((1, 256, 2, 64)), arr((1, 256, 2, 64))
    out = K.flash_attention(q, k, v, causal=True, window=window)
    ref = K.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_noncausal():
    q, k, v = arr((1, 128, 4, 64)), arr((1, 128, 4, 64)), arr((1, 128, 4, 64))
    out = K.flash_attention(q, k, v, causal=False)
    ref = K.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,L", [(1, 128, 256), (2, 256, 512),
                                   (1, 512, 128)])
def test_rglru(B, S, L, dtype):
    x = arr((B, S, L), dtype)
    r = jax.nn.sigmoid(arr((B, S, L), dtype))
    i = jax.nn.sigmoid(arr((B, S, L), dtype))
    lam = jnp.linspace(2.0, 6.0, L)
    out = K.rglru_scan(x, r, i, lam)
    ref = K.rglru_ref(x, r, i, lam)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,hd", [(1, 128, 2, 64), (2, 256, 4, 32),
                                      (1, 256, 1, 128)])
def test_rwkv6(B, S, H, hd, dtype):
    r, k, v = (arr((B, S, H, hd), dtype) for _ in range(3))
    w = (jax.nn.sigmoid(arr((B, S, H, hd))) * 0.5 + 0.45).astype(dtype)
    u = (arr((H, hd)) * 0.1).astype(jnp.float32)
    out = K.rwkv6_wkv(r, k, v, w, u)
    ref = K.rwkv6_ref(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=5e-2 if dtype == jnp.bfloat16 else 5e-4,
                               atol=5e-2 if dtype == jnp.bfloat16 else 5e-4)


@pytest.mark.parametrize("sizes", [[17], [31, 64], [5, 1000, 3]])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bucket_pack(sizes, dtype):
    leaves = [arr((s,), dtype) for s in sizes]
    total = sum(sizes) + 13
    out = K.bucket_pack(leaves, total)
    ref = K.bucket_pack_ref(leaves, sizes, total)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("sizes", [[17], [31, 64], [5, 1000, 3]])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("dp,chunks", [(1, 1), (4, 1), (4, 3), (8, 8)])
def test_fused_pack(sizes, dtype, dp, chunks):
    """Chunked fused-sync pack == jnp oracle exactly: same chunk cuts, same
    per-chunk shard padding, f32 upcast, zero tails."""
    leaves = [arr((s,), dtype) for s in sizes]
    total = sum(sizes)
    parts = K.fused_pack(leaves, total, dp, chunks)
    refs = K.fused_pack_ref(leaves, total, dp, chunks)
    assert len(parts) == len(refs)
    for part, ref in zip(parts, refs):
        assert part.dtype == jnp.float32
        assert part.shape == ref.shape
        assert part.shape[0] % dp == 0
        np.testing.assert_array_equal(np.asarray(part), np.asarray(ref))


@pytest.mark.parametrize("sizes", [[17], [31, 64], [5, 1000, 3]])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_unpack_roundtrip(sizes, dtype):
    """unpack(concat(pack(...)) trimmed to total) returns every leaf
    bit-identically (f32) / value-identically after the bf16 round-trip."""
    leaves = [arr((s,), dtype) for s in sizes]
    total = sum(sizes)
    parts = K.fused_pack(leaves, total, 1, 2)
    flat = jnp.concatenate(parts)[:total]
    out = K.fused_unpack(flat, [l.shape for l in leaves],
                         [l.dtype for l in leaves])
    ref = K.fused_unpack_ref(flat, [l.shape for l in leaves],
                             [l.dtype for l in leaves])
    for o, r, l in zip(out, ref, leaves):
        assert o.shape == l.shape and o.dtype == l.dtype
        np.testing.assert_array_equal(np.asarray(o), np.asarray(r))
        np.testing.assert_array_equal(
            np.asarray(o, np.float32),
            np.asarray(l.astype(jnp.float32).astype(dtype), np.float32))


def test_flash_kernel_inside_model():
    """use_kernels=True path produces the same logits as the XLA path."""
    from repro.configs import get_config
    from repro.models import stacked as ST

    cfg = get_config("tinyllama-1.1b").reduced()
    key = jax.random.PRNGKey(0)
    params = ST.init_params(key, cfg)
    toks = jax.random.randint(key, (2, 128), 0, cfg.vocab)
    l_ref, _ = ST.forward(params, cfg, toks, use_kernels=False)
    l_ker, _ = ST.forward(params, cfg, toks, use_kernels=True)
    np.testing.assert_allclose(np.asarray(l_ker), np.asarray(l_ref),
                               rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("S", [256, 512])
@pytest.mark.parametrize("H,KV", [(14, 2), (4, 4)])
def test_causal_attention_kernel(H, KV, S):
    """The splash wrapper (interpreted here) at the rule's blocks of 128
    with the fused backward, so whole blocks above the diagonal are
    skipped: output and the gradients of q, k and v against the dense XLA
    path in bf16 and the oracle in f32."""
    from repro.models import layers as L

    hd = 64
    q, k, v = arr((2, S, H, hd)), arr((2, S, KV, hd)), arr((2, S, KV, hd))
    ct = arr((2, S, H, hd))
    blocks = K.causal_block(256)
    assert blocks.block_q == blocks.block_kv_dkv == 128
    kern = lambda q, k, v: K.causal_attention(q, k, v, blocks)
    dense = lambda q, k, v: L._sdpa_dense(
        q, k, v, L._causal_bias(S, S, True, None)[None, None, None])
    ref = lambda q, k, v: K.flash_attention_ref(q, k, v, causal=True)

    def out_and_grads(fn, dt):
        args = [x.astype(dt) for x in (q, k, v)]
        loss = lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * ct)
        return [np.asarray(x, np.float32) for x in
                (jax.jit(fn)(*args),
                 *jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(*args))]

    for got, want, dt in ((kern, dense, jnp.bfloat16),
                          (kern, ref, jnp.float32)):
        for g, w in zip(out_and_grads(got, dt), out_and_grads(want, dt)):
            scale = max(1.0, float(np.abs(w).max()))
            np.testing.assert_allclose(g / scale, w / scale, **tol(dt))


@pytest.mark.parametrize("mask_given,causal,window,S,T,platform,takes", [
    (False, True, None, 1024, 1024, "tpu", True),    # training, prefill
    (False, True, None, 4096, 4096, "tpu", True),
    (False, True, None, 1024, 1024, "cpu", False),   # the CPU
    (False, True, None, 1024, 1024, "gpu", False),
    (True, True, None, 1, 1024, "tpu", False),       # decode, cache mask
    (True, True, None, 1024, 1024, "tpu", False),    # a given mask
    (False, False, None, 1024, 1024, "tpu", False),  # encoder
    (False, False, None, 64, 1024, "tpu", False),    # cross-attention
    (False, True, 512, 1024, 1024, "tpu", False),    # sliding window
    (False, True, None, 1000, 1000, "tpu", False),   # no block divides
    (False, True, None, 64, 64, "tpu", False),
])
def test_attention_kernel_route(mask_given, causal, window, S, T, platform,
                                takes):
    from repro.models import layers as L

    assert L.takes_kernel(mask_given, causal, window, S, T,
                          platform) is takes


@pytest.mark.parametrize("arch,in_mesh", [("qwen2-0.5b", False),
                                          ("qwen2-0.5b", True),
                                          ("deepseek-v2-lite-16b", False)])
def test_causal_kernel_inside_model(arch, in_mesh, monkeypatch):
    """A reduced model's loss and gradients with ``sdpa`` routed as on the
    TPU (the kernel interpreted here) match the XLA path: GQA, and MLA's
    call with v padded to the query width; in the trainer's ``shard_map``
    over ``data`` with ``model`` left automatic, the kernel's own nested
    ``shard_map`` places it."""
    from jax.sharding import AxisType, Mesh, PartitionSpec as P
    from repro import obs
    from repro.configs import get_config
    from repro.models import layers as L
    from repro.models import stacked as ST

    cfg = get_config(arch).reduced()
    params = ST.init_params(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 128),
                                          0, cfg.vocab)}
    def step():  # a fresh function, traced anew under each route
        grad = jax.value_and_grad(
            lambda p, b: ST.loss_fn(p, cfg, b, remat=True))
        if not in_mesh:
            return jax.jit(grad)
        mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                    ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        return jax.jit(jax.shard_map(grad, mesh=mesh,
                                     in_specs=(P(), P("data")),
                                     out_specs=P(), axis_names={"data"},
                                     check_vma=False))

    want = step()(params, batch)
    before = obs.snapshot()["counts"].get("attention.kernel", 0)
    route = L.takes_kernel
    monkeypatch.setattr(L, "takes_kernel",
                        lambda *a: route(*a[:-1], "tpu"))
    got = step()(params, batch)
    assert obs.snapshot()["counts"]["attention.kernel"] > before
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-5)
