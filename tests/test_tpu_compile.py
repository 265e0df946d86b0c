"""Compile-only checks for the TPU, at qwen2-0.5b's published widths.

Nothing here runs on a chip: each test compiles for a *described* v5e:2x2
topology with the TPU compiler installed beside JAX, which refuses what
the chip would refuse -- unaligned tiling, too much VMEM, a Mosaic kernel
it cannot partition, a program that does not fit the device's memory.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may hold the TPU library, and the
test workers must all collect the same tests.  Keep every such test in
this one file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.data.pipeline import make_batch_specs
from repro.distributed.train_step import (GradSyncStrategy, build_train_step,
                                          jit_train_step)
from repro.kernels import bucket_pack as BP
from repro.kernels import flash_attention as FA
from repro.kernels import fused_grad_sync as FG
from repro.kernels import ops as K
from repro.models import layers as L
from repro.models import stacked as ST
from repro.optim import adamw

HBM_BYTES = 16 * 10**9  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.experimental import topologies

    saved = (os.environ.get("TPU_LOG_DIR"),
             jax.config.jax_enable_compilation_cache)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a TPU entry written here could never be read back without a chip
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", saved[1])
    if saved[0] is None:
        os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def qwen():
    cfg = get_config("qwen2-0.5b")
    params = jax.eval_shape(
        lambda: ST.init_params(jax.random.PRNGKey(0), cfg))
    return cfg, params


def _leaf_shapes(params):
    """The largest and the smallest gradient leaf of the model."""
    leaves = sorted(jax.tree.leaves(params), key=lambda l: l.size)
    return [leaves[-1].shape, leaves[0].shape]


def _custom_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("kernel", ["convert_copy", "fused_pack",
                                    "fused_unpack"])
def test_grad_sync_kernels_compile(kernel, dtype, one_chip, qwen):
    """The fused-sync staging kernels lower to Mosaic at the model's own
    largest and smallest leaf sizes."""
    shapes = _leaf_shapes(qwen[1])
    sizes = [int(np.prod(s)) for s in shapes]
    total = sum(sizes)

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    if kernel == "convert_copy":
        out = jnp.float32 if dtype == jnp.bfloat16 else jnp.bfloat16
        fn = lambda x: BP.convert_copy_kernel(x, out, interpret=False)
        args = (arg((sizes[0],), dtype),)
    elif kernel == "fused_pack":
        fn = lambda ls: FG.fused_pack_kernel(ls, total, 4, 2,
                                             interpret=False)
        args = ([arg(s, dtype) for s in shapes],)
    else:
        fn = lambda b: FG.fused_unpack_kernel(b, shapes, [dtype] * 2,
                                              interpret=False)
        args = (arg((total,), jnp.float32),)
    compiled = jax.jit(fn).lower(*args).compile()
    assert _custom_calls(compiled) >= (1 if kernel == "convert_copy" else 2)


def test_flash_attention_compiles(one_chip):
    """The flash-attention kernel at qwen2-0.5b's head shapes: 14 query
    heads over 2 KV heads of width 64, sequence 2048."""
    q = jax.ShapeDtypeStruct((1, 2048, 14, 64), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 2048, 2, 64), jnp.bfloat16,
                              sharding=one_chip)
    compiled = jax.jit(lambda q, k, v: FA.flash_attention_kernel(
        q, k, v, causal=True, interpret=False)).lower(q, kv, kv).compile()
    assert _custom_calls(compiled) >= 1


def test_full_width_train_step_fits_one_chip(topo, qwen):
    """The whole qwen2-0.5b ``ddp_tp`` train step (24 layers, bf16 params,
    f32 Adam moments, batch 8 x seq 1024) compiles for one chip and needs
    less than its 16 GB."""
    cfg, params = qwen
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    optimizer = adamw(1e-3, weight_decay=0.01)
    opt = jax.eval_shape(lambda: optimizer[0](jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), params)))
    specs = make_batch_specs(cfg, 8, 1024)
    step = build_train_step(cfg, mesh, mode="ddp_tp", optimizer=optimizer,
                            strategy=GradSyncStrategy.size_capped(params))
    jf = jit_train_step(step, cfg, mesh, params, opt, specs)
    ma = jf.lower(params, opt, specs).compile().memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert ma.argument_size_in_bytes > 4 * 10**9  # params + Adam moments
    assert total < HBM_BYTES, total


def _attention_grads(q, k, v):
    """Forward and backward of the causal kernel wrapper."""
    return jax.grad(lambda q, k, v: jnp.sum(
        K.causal_attention(q, k, v).astype(jnp.float32)),
        argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("B,S,H,KV", [(2, 4096, 32, 32),   # stablelm, 1 chip
                                      (8, 1024, 14, 2)])   # qwen2, per chip
def test_causal_attention_compiles(B, S, H, KV, one_chip, monkeypatch):
    """The splash wrapper's forward and fused dq/dkv backward kernels lower
    to Mosaic at both benchmark cells' per-chip attention shapes (heads of
    64)."""
    monkeypatch.setattr(K, "_interpret", lambda: False)
    q = jax.ShapeDtypeStruct((B, S, H, 64), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((B, S, KV, 64), jnp.bfloat16,
                              sharding=one_chip)
    compiled = jax.jit(_attention_grads).lower(q, kv, kv).compile()
    assert _custom_calls(compiled) >= 2


def test_mla_attention_compiles(one_chip, monkeypatch):
    """DeepSeek-V2-Lite's MLA at 2 x 8192 on one chip: 16 heads of 192
    (nope 128 + rope 64; v padded to 192) through the splash kernel's
    forward and fused backward, whose blocks ``causal_block`` caps at 512
    for heads this wide."""
    monkeypatch.setattr(K, "_interpret", lambda: False)
    q = jax.ShapeDtypeStruct((2, 8192, 16, 192), jnp.bfloat16,
                             sharding=one_chip)
    assert K.causal_block(8192, 192).block_q_dkv == 512
    assert K.causal_block(8192).block_q_dkv == 1024
    compiled = jax.jit(_attention_grads).lower(q, q, q).compile()
    assert _custom_calls(compiled) >= 2


def test_grouped_expert_matmuls_compile(one_chip):
    """The expert layer's grouped matmuls at DeepSeek-V2-Lite's widths
    (d_model 2048, experts of 1408, 8 held) over the 2 x 8192 x 6 row
    bound, forward and backward, as the chip's compiler takes them."""
    rows, d, de, held = 2 * 8192 * 6, 2048, 1408, 8

    def layer(x, wg, wu, wd, sizes):
        h = jax.nn.silu(jax.lax.ragged_dot(x, wg, sizes)) \
            * jax.lax.ragged_dot(x, wu, sizes)
        return jnp.sum(jax.lax.ragged_dot(h, wd, sizes).astype(jnp.float32))

    def arg(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    compiled = jax.jit(jax.grad(layer, argnums=(0, 1, 2, 3))).lower(
        arg((rows, d)), arg((held, d, de)), arg((held, d, de)),
        arg((held, de, d)), arg((held,), jnp.int32)).compile()
    assert "ragged" in compiled.as_text()


@pytest.mark.parametrize("dp,tp", [(4, 1), (2, 2)])
def test_causal_attention_partitions_in_data_shard_map(dp, tp, topo,
                                                       monkeypatch):
    """Inside a ``shard_map`` over ``data`` with ``model`` left automatic,
    as ``build_train_step`` runs the model, the wrapper's nested
    ``shard_map`` places the Mosaic calls: qwen2-0.5b's attention at 8 x
    1024 a chip, data-parallel over the four chips, or over two with the
    heads split over two more."""
    monkeypatch.setattr(K, "_interpret", lambda: False)
    mesh = Mesh(np.array(topo.devices).reshape(dp, tp), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    heads = NamedSharding(mesh, P("data", None, "model"))
    q = jax.ShapeDtypeStruct((8 * dp, 1024, 14, 64), jnp.bfloat16,
                             sharding=heads)
    kv = jax.ShapeDtypeStruct((8 * dp, 1024, 2, 64), jnp.bfloat16,
                              sharding=heads)
    step = jax.shard_map(_attention_grads, mesh=mesh,
                         in_specs=(P("data"),) * 3, out_specs=(P("data"),) * 3,
                         axis_names={"data"}, check_vma=False)
    compiled = jax.jit(step).lower(q, kv, kv).compile()
    assert _custom_calls(compiled) >= 2


def test_full_width_train_step_with_kernel_fits_one_chip(topo, qwen,
                                                         monkeypatch):
    """The qwen2-0.5b step of ``test_full_width_train_step_fits_one_chip``
    with attention routed as on the TPU: its 24 layers' kernels partition
    and the step still needs less than one chip's 16 GB."""
    cfg, params = qwen
    monkeypatch.setattr(K, "_interpret", lambda: False)
    route = L.takes_kernel
    monkeypatch.setattr(L, "takes_kernel", lambda *a: route(*a[:-1], "tpu"))
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    optimizer = adamw(1e-3, weight_decay=0.01)
    opt = jax.eval_shape(lambda: optimizer[0](jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), params)))
    specs = make_batch_specs(cfg, 8, 1024)
    step = build_train_step(cfg, mesh, mode="ddp_tp", optimizer=optimizer,
                            strategy=GradSyncStrategy.size_capped(params))
    jf = jit_train_step(step, cfg, mesh, params, opt, specs)
    compiled = jf.lower(params, opt, specs).compile()
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert _custom_calls(compiled) >= 3
    assert total < HBM_BYTES, total
