"""Distributed training steps.

Two modes:

* ``ddp_tp`` — **the DisCo enactment path**: manual ``shard_map`` over the
  data axes; tensor parallelism stays GSPMD-auto over ``model``.  Gradient
  synchronisation is *explicit*: one ``psum`` per AllReduce bucket of the
  searched :class:`GradSyncStrategy`, with optional
  ``optimization_barrier`` fences pinning the bucket schedule.  The lowered
  HLO therefore carries exactly the collective schedule the search chose;
  XLA's all-reduce combiner may still merge buckets (DESIGN.md Sec. 13).

* ``fsdp_tp`` — GSPMD-auto ZeRO-3 for architectures whose replicated
  weights+optimizer do not fit one TP shard (DeepSeek-V2-236B,
  DeepSeek-Coder-33B).  Gradient reduce-scatters are inserted by XLA per
  tensor; DisCo bucket enactment is N/A here (DESIGN.md Sec. 4).
"""
from __future__ import annotations

import dataclasses
import json
from functools import partial
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import obs
from ..models import model as M
from ..models.config import ModelConfig
from ..optim import adamw, apply_updates, clip_by_global_norm
from . import sharding as SH


# ----------------------------------------------------------------- strategy
@dataclasses.dataclass
class GradSyncStrategy:
    """Tensor-fusion strategy: a partition of parameter leaves into ordered
    buckets (leaf indices in ``jax.tree.leaves`` order), each synchronised
    by one fused collective.  ``comms[i]`` picks the collective kind of
    bucket ``i``: ``"ar"`` (one fused AllReduce, the paper's DDP path) or
    ``"rs_ag"`` (ZeRO-3-style reduce-scatter + all-gather — the searched
    ``FusionGraph.bucket_comm`` dimension, enacted for real).
    ``chunks[i] > 1`` splits bucket ``i``'s fused tensor into that many
    even byte ranges, each synchronised by its own collective — the
    searched ``FusionGraph.bucket_chunks`` store-and-forward dimension,
    enacted for real (identical numerics: a psum of disjoint slices is the
    sliced psum).  ``fused[i]`` truthy marks bucket ``i`` for the in-kernel
    compute+comm overlap path (the searched ``FusionGraph.bucket_fused``
    dimension): Pallas staging kernels pack straight into reduce-scatter
    layout and unpack straight out of the all-gather, with the same RS+AG
    wire arithmetic — loss-bit-identical to the psum path."""
    buckets: list[list[int]]
    barriers: bool = False      # fence buckets with optimization_barrier
    comms: Optional[list[str]] = None   # per-bucket "ar" | "rs_ag"
    chunks: Optional[list[int]] = None  # per-bucket collective count (>= 1)
    fused: Optional[list[int]] = None   # per-bucket in-kernel overlap flag

    def comm_kind(self, i: int) -> str:
        return self.comms[i] if self.comms else "ar"

    def chunk_count(self, i: int) -> int:
        return max(int(self.chunks[i]), 1) if self.chunks else 1

    def is_fused(self, i: int) -> bool:
        return bool(self.fused[i]) if self.fused else False

    @staticmethod
    def per_tensor(params) -> "GradSyncStrategy":
        n = len(jax.tree.leaves(params))
        return GradSyncStrategy([[i] for i in range(n)])

    @staticmethod
    def single_bucket(params) -> "GradSyncStrategy":
        n = len(jax.tree.leaves(params))
        return GradSyncStrategy([list(range(n))])

    @staticmethod
    def size_capped(params, cap_bytes: int = 25 * 2**20) -> "GradSyncStrategy":
        """DDP-style: consecutive leaves bucketed up to a byte cap."""
        leaves = jax.tree.leaves(params)
        buckets, cur, cur_b = [], [], 0
        for i, l in enumerate(leaves):
            b = l.size * l.dtype.itemsize
            if cur and cur_b + b > cap_bytes:
                buckets.append(cur)
                cur, cur_b = [], 0
            cur.append(i)
            cur_b += b
        if cur:
            buckets.append(cur)
        return GradSyncStrategy(buckets)

    @staticmethod
    def from_buckets(buckets, comms=None, chunks=None, params=None,
                     barriers: bool = False, fused=None) -> "GradSyncStrategy":
        """Build a strategy from explicit per-bucket state (the single
        implementation of the clip-to-leaves contract, shared by
        ``from_fusion_graph`` and ``repro.plan.Plan.grad_sync``).  With
        ``params``, bucket entries are clipped to the real leaf count and
        uncovered leaves get singleton unfused AllReduce buckets."""
        buckets = [list(b) for b in buckets]
        comms = (list(comms) if comms is not None
                 else ["ar"] * len(buckets))
        chunks = ([int(k) for k in chunks] if chunks is not None
                  else [1] * len(buckets))
        fused = ([int(bool(f)) for f in fused] if fused is not None
                 else [0] * len(buckets))
        if params is not None:
            n = len(jax.tree.leaves(params))
            seen: set = set()
            kept, kcomms, kchunks, kfused = [], [], [], []
            for b, kind, k, fz in zip(buckets, comms, chunks, fused):
                bk = [i for i in b if i < n]
                seen.update(bk)
                if bk:
                    kept.append(bk)
                    kcomms.append(kind)
                    kchunks.append(k)
                    kfused.append(fz)
            rest = [i for i in range(n) if i not in seen]
            kept.extend([[i] for i in rest])
            kcomms.extend(["ar"] * len(rest))
            kchunks.extend([1] * len(rest))
            kfused.extend([0] * len(rest))
            buckets, comms, chunks, fused = kept, kcomms, kchunks, kfused
        return GradSyncStrategy(buckets, barriers=barriers, comms=comms,
                                chunks=chunks, fused=fused)

    @staticmethod
    def from_fusion_graph(g, params) -> "GradSyncStrategy":
        """Lift the searched FusionGraph's bucket partition onto the real
        parameter leaves (grad_param indices == leaf indices), carrying the
        searched per-bucket comm kind and chunk count along so ``rs_ag``
        buckets lower to reduce-scatter + all-gather and chunked buckets
        to per-chunk collectives when enacted."""
        kinds = getattr(g, "bucket_comm", None) or ["ar"] * len(g.buckets)
        counts = getattr(g, "bucket_chunks", None) or [1] * len(g.buckets)
        flags = getattr(g, "bucket_fused", None)
        return GradSyncStrategy.from_buckets(g.buckets, kinds, counts,
                                             params=params, fused=flags)

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump({"buckets": self.buckets, "barriers": self.barriers,
                       "comms": self.comms, "chunks": self.chunks,
                       "fused": self.fused}, f)

    @staticmethod
    def load(path: str) -> "GradSyncStrategy":
        with open(path) as f:
            d = json.load(f)
        return GradSyncStrategy(d["buckets"], d.get("barriers", False),
                                comms=d.get("comms"),
                                chunks=d.get("chunks"),
                                fused=d.get("fused"))


def _fused_bucket_sync(leaves, dp: int, chunks: int, dp_axes,
                       barrier_with=None):
    """In-kernel fused bucket sync: Pallas pack (grad leaves -> chunked,
    shard-tiled f32 staging, cast fused) -> per-chunk real reduce-scatter +
    mean + all-gather -> Pallas unpack (f32 -> grad dtype cast fused into
    the un-staging pass).  The wire arithmetic is exactly the ``rs_ag``
    lowering's, so numerics match the fused ``psum`` bit-for-bit."""
    from ..kernels import ops as K
    total = sum(l.size for l in leaves)
    k = min(max(int(chunks), 1), max(total, 1))
    parts = K.fused_pack(leaves, total, dp, k)
    if barrier_with is not None:
        fenced = jax.lax.optimization_barrier(tuple(parts) + (barrier_with,))
        parts = list(fenced[:-1])
    cuts = [total * c // k for c in range(k + 1)]
    outs = []
    for c, part in enumerate(parts):
        shard = jax.lax.psum_scatter(part, tuple(dp_axes),
                                     scatter_dimension=0, tiled=True) / dp
        part = jax.lax.all_gather(shard, tuple(dp_axes), tiled=True)
        outs.append(part[:cuts[c + 1] - cuts[c]])
    f32 = jnp.concatenate(outs) if k > 1 else outs[0]
    return K.fused_unpack(f32, [l.shape for l in leaves],
                          [l.dtype for l in leaves]), f32


@obs.scope("grad_sync")
def sync_grads(grads, strategy: GradSyncStrategy, dp_axes: Sequence[str],
               mesh=None, pspecs=None):
    """Explicit bucketed gradient synchronisation (mean) — DisCo tensor
    fusion with the searched per-bucket comm kind enacted.

    Each bucket is flattened+concatenated into one fused tensor, reduced as
    a *single* collective over the data axes, and split back — exactly the
    paper's tensor fusion.  An ``"ar"`` bucket is one fused ``psum``; an
    ``"rs_ag"`` bucket lowers to ``psum_scatter`` + ``all_gather`` (the
    ZeRO-3-style split the event engine prices per link level), padded to a
    multiple of the data-parallel degree so the shards tile evenly — the
    compiled HLO carries reduce-scatter/all-gather ops instead of
    all-reduce, with identical numerics.

    A bucket with ``chunks > 1`` splits its fused tensor into that many
    even byte ranges and issues one collective per chunk (the same lowering
    path as above, applied per range) — the searched store-and-forward
    chunking, enacted so the compiled HLO carries exactly the collective
    count the event engine priced.  Numerics are bit-identical to the
    whole-bucket collective: each element's reduction is unchanged, only
    the op it rides in shrinks.

    A *fused* bucket (``strategy.fused[i]`` — the searched in-kernel
    compute+comm overlap dimension) routes through
    :func:`_fused_bucket_sync`: Pallas staging kernels pack the leaves
    straight into the reduce-scatter's chunked shard-tiled layout and
    unpack straight out of the all-gather with the dtype cast fused, with
    the identical RS+AG wire arithmetic in between.  A kernel that cannot
    lower raises; there is no silent fallback.

    Fusing must not destroy tensor-parallel sharding, so when ``mesh`` and
    ``pspecs`` are given the bucketing runs inside a nested ``shard_map``
    over the ``model`` axis: the fused buffer concatenates the *local TP
    shards* (Megatron-DDP style), keeping the collective 1/TP-sized and the
    HLO free of gather/reshard traffic.  It does so at TP degree 1 too: the
    nesting leaves no mesh axis automatic, and a Mosaic kernel can only
    lower where none is.
    """
    leaves, treedef = jax.tree_util.tree_flatten(grads)

    def fuse_and_reduce(leaves_local: list):
        dp = 1
        for a in dp_axes:
            dp *= jax.lax.axis_size(a)
        out: list = [None] * len(leaves_local)
        prev_fused = None
        for bi, bucket in enumerate(strategy.buckets):
            with jax.named_scope(f"bucket_{bi}"):
                if strategy.is_fused(bi) and dp > 1:
                    # searched in-kernel fused path: Pallas staging kernels
                    # around a real RS+AG pair
                    outs, packed = _fused_bucket_sync(
                        [leaves_local[i] for i in bucket], dp,
                        strategy.chunk_count(bi), dp_axes,
                        barrier_with=(prev_fused if strategy.barriers
                                      else None))
                    for i, o in zip(bucket, outs):
                        out[i] = o
                    prev_fused = packed
                    continue
                flats = [leaves_local[i].reshape(-1) for i in bucket]
                fused = jnp.concatenate(flats) if len(flats) > 1 \
                    else flats[0]
                if strategy.barriers and prev_fused is not None:
                    fused, _ = jax.lax.optimization_barrier(
                        (fused, prev_fused))
                # reduce in f32: gradient-accuracy standard practice, and
                # works around an XLA:CPU bf16 all-reduce miscompile in the
                # dry-run.
                dt = fused.dtype
                f32 = fused.astype(jnp.float32)
                rs_ag = strategy.comm_kind(bi) == "rs_ag" and dp > 1

                def reduce_one(part):
                    if rs_ag:
                        n0 = part.shape[0]
                        pad = (-n0) % dp
                        if pad:
                            part = jnp.concatenate(
                                [part, jnp.zeros((pad,), jnp.float32)])
                        shard = jax.lax.psum_scatter(part, tuple(dp_axes),
                                                     scatter_dimension=0,
                                                     tiled=True) / dp
                        part = jax.lax.all_gather(shard, tuple(dp_axes),
                                                  tiled=True)
                        if pad:
                            part = part[:n0]
                    else:
                        part = jax.lax.psum(part, tuple(dp_axes)) / dp
                    return part

                k = min(strategy.chunk_count(bi), max(f32.shape[0], 1))
                if k > 1:
                    # even byte split; each chunk is its own collective
                    cuts = [f32.shape[0] * c // k for c in range(k + 1)]
                    f32 = jnp.concatenate([reduce_one(f32[cuts[c]:cuts[c + 1]])
                                           for c in range(k)])
                else:
                    f32 = reduce_one(f32)
                fused = f32.astype(dt)
                prev_fused = fused
                off = 0
                for i in bucket:
                    n = leaves_local[i].size
                    out[i] = fused[off:off + n].reshape(leaves_local[i].shape)
                    off += n
        return tuple(out)

    if mesh is None or "model" not in mesh.shape:
        # every mesh axis is a manual data axis already
        return jax.tree_util.tree_unflatten(treedef, fuse_and_reduce(leaves))

    specs = tuple(jax.tree_util.tree_leaves(
        pspecs, is_leaf=lambda x: isinstance(x, P)))
    assert len(specs) == len(leaves)
    # the nested shard_map picks up the ambient (partial-manual) mesh
    synced = jax.shard_map(
        lambda *ls: fuse_and_reduce(list(ls)),
        in_specs=specs, out_specs=specs, axis_names={"model"},
        check_vma=False,
    )(*leaves)
    return jax.tree_util.tree_unflatten(treedef, list(synced))


# --------------------------------------------------------------- step build
def _split_batch(batch: dict, n_micro: int) -> dict:
    return {k: v.reshape(n_micro, v.shape[0] // n_micro, *v.shape[1:])
            for k, v in batch.items()}


def build_train_step(
    cfg: ModelConfig,
    mesh,
    *,
    mode: str = "ddp_tp",
    strategy: Optional[GradSyncStrategy] = None,
    optimizer=None,
    grad_accum: int = 1,
    remat: bool = True,
    clip_norm: float = 1.0,
    lr: float = 3e-4,
    loss_fn: Optional[Callable] = None,
    layout: str = "tp",
) -> Callable:
    """Returns step(params, opt_state, batch) -> (params, opt_state, metrics),
    jit-compiled with the mesh's shardings.  ``loss_fn(params, cfg, batch,
    remat=...)`` defaults to the scanned-layer implementation."""
    opt_init, opt_update = optimizer or adamw(lr, weight_decay=0.01)
    if layout == "dp":
        # pure data parallelism: the `model` axis carries batch too (small
        # models waste ICI on TP activation psums — see EXPERIMENTS.md Perf)
        dp_axes = tuple(a for a in ("pod", "data", "model")
                        if a in mesh.shape)
    else:
        dp_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    if loss_fn is None:
        from ..models import stacked as ST
        loss_fn = ST.loss_fn

    # vocab-parallel CE crashes XLA:CPU's AllReducePromotion when the
    # shard_map is not nested inside a manual region (fsdp/auto mode);
    # the non-VP chunked CE is used there instead (see DESIGN.md).
    # In pure-DP layout everything is replicated: no vocab parallelism.
    vp_ce = mode == "ddp_tp" and layout != "dp"
    vp = None if layout == "dp" else mesh

    def local_loss(params, batch):
        return loss_fn(params, cfg, batch, remat=remat, vp_mesh=vp,
                       vp_ce=vp_ce)

    def grads_of(params, batch):
        if grad_accum > 1:
            micro = _split_batch(batch, grad_accum)

            def body(carry, mb):
                l, g = jax.value_and_grad(local_loss)(params, mb)
                return (carry[0] + l,
                        jax.tree.map(jnp.add, carry[1], g)), None

            zero = (jnp.zeros(()),
                    jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                 params))
            (loss, grads), _ = jax.lax.scan(body, zero, micro)
            scale = 1.0 / grad_accum
            return loss * scale, jax.tree.map(lambda g: g * scale, grads)
        return jax.value_and_grad(local_loss)(params, batch)

    @obs.scope("optimizer")
    def update(params, opt_state, loss, grads):
        grads, gnorm = clip_by_global_norm(grads, clip_norm)
        updates, opt_state = opt_update(grads, opt_state, params)
        params = apply_updates(params, updates)
        return params, opt_state, {"loss": loss, "grad_norm": gnorm}

    if mode == "ddp_tp":
        strat = strategy  # captured; None -> per-tensor at first call site

        def local_step(params, opt_state, batch):
            loss, grads = grads_of(params, batch)
            if layout == "dp":
                # every mesh axis is a (manual) data axis here
                grads = sync_grads(
                    grads, strat or GradSyncStrategy.per_tensor(params),
                    dp_axes)
            else:
                align = SH.head_alignment(cfg, mesh)
                pspecs = jax.tree_util.tree_map_with_path(
                    lambda pth, l: SH.param_spec(
                        pth, l, model_size=mesh.shape.get("model", 1),
                        dp_axes=(), fsdp=False, **align),
                    grads)
                grads = sync_grads(
                    grads, strat or GradSyncStrategy.per_tensor(params),
                    dp_axes, mesh=mesh, pspecs=pspecs)
            loss = jax.lax.pmean(loss, tuple(dp_axes))
            return update(params, opt_state, loss, grads)

        def make(batch_keys):
            bspec = {}
            for k in batch_keys:
                lead = dp_axes if len(dp_axes) > 1 else dp_axes[0]
                bspec[k] = P(lead)
            return jax.shard_map(local_step, mesh=mesh,
                                 in_specs=(P(), P(), bspec),
                                 out_specs=(P(), P(), P()),
                                 axis_names=set(dp_axes), check_vma=False)

        def step(params, opt_state, batch):
            return make(tuple(sorted(batch)))(params, opt_state, batch)

        return step

    if mode == "fsdp_tp":
        def full_step(params, opt_state, batch):
            loss, grads = grads_of(params, batch)
            return update(params, opt_state, loss, grads)

        return full_step

    raise ValueError(f"unknown mode {mode!r}")


def jit_train_step(step_fn, cfg: ModelConfig, mesh, params_like, opt_like,
                   batch_specs: dict, *, fsdp: bool = False,
                   layout: str = "tp", zero1: bool = False):
    """jit with explicit in/out shardings.

    layout="dp": params replicated, batch over ALL mesh axes.
    zero1=True: optimizer moments additionally sharded over the data axes
    (largest divisible free dim) — ZeRO-1; XLA slices the update and
    all-gathers the applied deltas.
    """
    from ..optim import OptState

    rep = NamedSharding(mesh, P())
    if layout == "dp":
        pshard = jax.tree.map(lambda _: rep, params_like)
        bshard = {k: NamedSharding(mesh, P(tuple(mesh.axis_names),
                                           *([None] * (len(v.shape) - 1))))
                  for k, v in batch_specs.items()}
    else:
        pshard = SH.param_shardings(params_like, mesh, fsdp=fsdp, cfg=cfg)
        bshard = SH.batch_shardings(batch_specs, mesh)
    moment_shard = pshard
    if zero1:
        moment_shard = SH.zero1_shardings(params_like, mesh, pshard)
    oshard = OptState(mu=moment_shard, nu=moment_shard,
                      count=NamedSharding(mesh, P()))
    jf = jax.jit(
        step_fn,
        in_shardings=(pshard, oshard, bshard),
        out_shardings=(pshard, oshard, {"loss": rep, "grad_norm": rep}),
        donate_argnums=(0, 1),
    )
    return jf
