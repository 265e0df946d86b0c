"""Batched serving engine (continuous-batching-lite).

Static-shape slot model, the standard TPU serving pattern: a fixed number
of decode slots with a shared static-capacity cache; requests are admitted
into free slots via single-sequence prefill (right-aligned write into the
slot's cache region), every decode step advances ALL active slots with one
jit'd call, finished slots are retired and refilled — prefill and decode
interleave without recompilation (all shapes static).

This is the substrate the ``decode_32k`` / ``long_500k`` dry-run shapes
lower; on the production mesh the same engine runs with the sharded
params/cache shardings from :mod:`repro.distributed.sharding`.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models import stacked as ST
from ..models.config import ModelConfig


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray               # (P,) int32
    max_new_tokens: int = 32
    eos_id: Optional[int] = None
    # filled by the engine (None until the lifecycle event happened, so an
    # unfinished request reports None instead of a nonsense 0/negative)
    output: list = dataclasses.field(default_factory=list)
    submitted_at: Optional[float] = None
    first_token_at: Optional[float] = None
    done_at: Optional[float] = None

    @property
    def ttft(self) -> Optional[float]:
        if self.first_token_at is None or self.submitted_at is None:
            return None
        return self.first_token_at - self.submitted_at

    @property
    def latency(self) -> Optional[float]:
        if self.done_at is None or self.submitted_at is None:
            return None
        return self.done_at - self.submitted_at


class ServeEngine:
    """max_slots concurrent sequences, cache capacity ``cache_len`` each."""

    def __init__(self, params, cfg: ModelConfig, *,
                 max_slots: Optional[int] = None,
                 cache_len: Optional[int] = None,
                 sampler: Optional[Callable] = None,
                 clock: Callable[[], float] = time.monotonic,
                 plan=None, decode_batch: Optional[int] = None):
        # a serving plan (repro.serving.plan.ServingPlan, duck-typed)
        # enacts the searched slot/batch/shard choices; explicit kwargs
        # still win over the plan's fields (e.g. to clamp a pod-sized
        # plan onto a small host)
        if max_slots is None:
            max_slots = int(plan.slots) if plan is not None else 8
        if cache_len is None:
            cache_len = int(plan.cache_len) if plan is not None else 256
        if decode_batch is None and plan is not None:
            decode_batch = int(plan.decode_batch)
        self.plan = plan
        self.kv_layout = getattr(plan, "kv_layout", "replicated")
        self.params = params
        self.cfg = cfg
        self.max_slots = max_slots
        self.cache_len = cache_len
        self.clock = clock
        # decode dispatch width: < max_slots decodes the active slots in
        # gathered chunks of this many lanes (the searched batch knob)
        self.decode_batch = (max_slots if decode_batch is None
                             else max(1, min(int(decode_batch), max_slots)))
        self.sampler = sampler or (lambda logits, rng: jnp.argmax(
            logits, axis=-1).astype(jnp.int32))
        # slot state
        self.caches = ST.init_cache(cfg, max_slots, cache_len)
        self.slot_req: list[Optional[Request]] = [None] * max_slots
        self.slot_pos = np.zeros(max_slots, np.int32)      # next write pos
        self.slot_last = np.zeros(max_slots, np.int32)     # last sampled tok
        self.slot_budget = np.zeros(max_slots, np.int32)
        self.queue: deque[Request] = deque()
        self.completed: list[Request] = []
        self._steps = 0

        # jit'd engine kernels (static shapes)
        self._decode = jax.jit(self._decode_impl)
        self._decode_chunk = jax.jit(self._decode_chunk_impl,
                                     static_argnames=("n_valid",))
        self._prefill_one = jax.jit(self._prefill_impl,
                                    static_argnames=("plen",))

    # ------------------------------------------------------------- kernels
    def _decode_impl(self, params, caches, tokens, positions):
        """Advance all slots one token.  tokens: (S,), positions: (S,)."""
        # per-slot positions: run decode with per-slot rope positions by
        # vmapping over the slot dim? decode_step uses a single scalar pos;
        # we batch with the max-consistent trick: positions differ per slot,
        # so rope/cache writes must be per-slot — use vmap over slots.
        def one(p, cache, tok, pos):
            # vmap strips the slot axis (axis 1 of stacked caches); decode
            # expects a batch dim there — reinsert a singleton
            c = jax.tree.map(lambda a: jnp.expand_dims(a, 1), cache)
            logits, nc = ST.decode_step(p, self.cfg, c, tok[None], pos)
            nc = jax.tree.map(lambda a: jnp.squeeze(a, 1), nc)
            return logits[0], nc

        logits, new_caches = jax.vmap(
            one, in_axes=(None, _slot_axes(caches), 0, 0),
            out_axes=(0, _slot_axes(caches)))(
                params, caches, tokens, positions)
        return logits, new_caches

    def _decode_chunk_impl(self, params, caches, tokens, positions, idx,
                           *, n_valid):
        """Advance a gathered chunk of slots one token: gather the chunk's
        cache columns (slot axis 1), decode at the chunk width, scatter
        only the ``n_valid`` real lanes back (padding lanes duplicate a
        real slot for the gather and are discarded — the scatter indices
        stay distinct, so the update is deterministic)."""
        sub = jax.tree.map(lambda a: jnp.take(a, idx, axis=1), caches)
        logits, new_sub = self._decode_impl(params, sub, tokens, positions)
        idx_v = idx[:n_valid]
        new_caches = jax.tree.map(
            lambda full, new: full.at[:, idx_v].set(
                jax.lax.slice_in_dim(new, 0, n_valid, axis=1).astype(
                    full.dtype)),
            caches, new_sub)
        return logits, new_caches

    def _prefill_impl(self, params, tokens, *, plen):
        """Single-sequence prefill into a fresh cache region."""
        logits, cache = ST.prefill(params, self.cfg, tokens[None],
                                   self.cache_len)
        return logits[0], cache

    # ------------------------------------------------------------- control
    def submit(self, req: Request) -> None:
        req.submitted_at = self.clock()
        self.queue.append(req)

    def _admit(self) -> None:
        for slot in range(self.max_slots):
            if self.slot_req[slot] is not None or not self.queue:
                continue
            req = self.queue.popleft()
            plen = len(req.prompt)
            assert plen < self.cache_len
            logits, cache = self._prefill_impl(
                self.params, jnp.asarray(req.prompt, jnp.int32), plen=plen)
            # install the prefilled single-sequence cache into this slot
            self.caches = jax.tree.map(
                lambda full, new: _install_slot(full, new, slot),
                self.caches, cache)
            tok = int(np.argmax(np.asarray(logits)))
            req.first_token_at = self.clock()
            req.output.append(tok)
            self.slot_req[slot] = req
            self.slot_pos[slot] = plen
            self.slot_last[slot] = tok
            self.slot_budget[slot] = req.max_new_tokens - 1

    def step(self) -> int:
        """One engine iteration: admit waiting requests, decode all active
        slots (in gathered dispatches of ``decode_batch`` lanes when the
        batch knob is below the slot count).  Returns the number of active
        slots."""
        self._admit()
        active = [i for i, r in enumerate(self.slot_req) if r is not None]
        if not active:
            return 0
        if self.decode_batch >= self.max_slots:
            # full-width dispatch: the original (default) path, unchanged
            tokens = jnp.asarray(self.slot_last, jnp.int32)
            positions = jnp.asarray(self.slot_pos, jnp.int32)
            logits, self.caches = self._decode(self.params, self.caches,
                                               tokens, positions)
            full = np.asarray(self.sampler(logits, None))
            nxt = {slot: int(full[slot]) for slot in active}
        else:
            nxt = {}
            width = self.decode_batch
            for c0 in range(0, len(active), width):
                chunk = active[c0:c0 + width]
                # pad the gather with a duplicate of a real lane; only the
                # first len(chunk) (distinct) lanes are scattered back
                idx = chunk + [chunk[-1]] * (width - len(chunk))
                idx_arr = jnp.asarray(idx, jnp.int32)
                tokens = jnp.asarray(self.slot_last[idx], jnp.int32)
                positions = jnp.asarray(self.slot_pos[idx], jnp.int32)
                logits, self.caches = self._decode_chunk(
                    self.params, self.caches, tokens, positions, idx_arr,
                    n_valid=len(chunk))
                got = np.asarray(self.sampler(logits, None))
                for j, slot in enumerate(chunk):
                    nxt[slot] = int(got[j])
        self._steps += 1
        for slot in active:
            req = self.slot_req[slot]
            tok = nxt[slot]
            req.output.append(tok)
            self.slot_pos[slot] += 1
            self.slot_last[slot] = tok
            self.slot_budget[slot] -= 1
            done = (self.slot_budget[slot] <= 0
                    or (req.eos_id is not None and tok == req.eos_id)
                    or self.slot_pos[slot] >= self.cache_len - 1)
            if done:
                req.done_at = self.clock()
                self.completed.append(req)
                self.slot_req[slot] = None
        return len(active)

    def run_to_completion(self, max_steps: int = 10_000) -> list[Request]:
        while (self.queue or any(r is not None for r in self.slot_req)):
            if self.step() == 0 and not self.queue:
                break
            max_steps -= 1
            if max_steps <= 0:
                raise RuntimeError("serve loop did not converge")
        return self.completed

    def metrics(self) -> dict:
        """Per-request latency summary over the completed set: TTFT /
        TPOT / end-to-end latency percentiles plus the aggregate decode
        throughput over the serving span (first submit to last finish).
        Consumed by ``benchmarks/fig_serving_sweep.py`` and printed by
        ``examples/serve_with_plan.py``."""
        done = self.completed
        ttfts = [r.ttft for r in done if r.ttft is not None]
        lats = [r.latency for r in done if r.latency is not None]
        tpots = [(r.latency - r.ttft) / (len(r.output) - 1)
                 for r in done
                 if r.latency is not None and r.ttft is not None
                 and len(r.output) > 1]
        toks = sum(len(r.output) for r in done)
        starts = [r.submitted_at for r in done if r.submitted_at is not None]
        ends = [r.done_at for r in done if r.done_at is not None]
        span = (max(ends) - min(starts)) if starts and ends else 0.0

        def pct(vals, q):
            return float(np.percentile(vals, q)) if vals else None

        return {
            "completed": len(done),
            "tokens": toks,
            "decode_steps": self._steps,
            "span_s": span,
            "tokens_per_s": toks / span if span > 0.0 else 0.0,
            "ttft_p50_s": pct(ttfts, 50), "ttft_p99_s": pct(ttfts, 99),
            "tpot_p50_s": pct(tpots, 50), "tpot_p99_s": pct(tpots, 99),
            "latency_p50_s": pct(lats, 50), "latency_p99_s": pct(lats, 99),
            "mean_ttft_s": float(np.mean(ttfts)) if ttfts else None,
            "mean_latency_s": float(np.mean(lats)) if lats else None,
        }


# ------------------------------------------------------------------ helpers
def _slot_axes(caches):
    """vmap in_axes tree: slot/batch axis is 1 for stacked cache leaves."""
    return jax.tree.map(lambda a: 1, caches)


def _install_slot(full, new, slot):
    """Write a single-sequence cache (batch==1 at axis 1) into slot
    ``slot`` of the engine cache (batch==max_slots at axis 1)."""
    return jax.lax.dynamic_update_slice_in_dim(full, new.astype(full.dtype),
                                               slot, axis=1)
