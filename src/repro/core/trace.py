"""jaxpr -> FusionGraph tracer.

Extracts the primitive-level DAG of a real JAX computation (the per-device
forward+backward of a training step), estimates per-primitive FLOPs/bytes
from avals, and attaches one AllReduce instruction per parameter-gradient
output — the input representation DisCo searches over.

``pjit`` / ``custom_vjp`` / ``remat`` sub-jaxprs are inlined so the graph is
flat (JAX groups the whole step into a single HLO module — paper Sec. 5).
``scan``/``while`` stay as single OPAQUE nodes with body-cost x trip-count.
"""
from __future__ import annotations

from typing import Callable, Sequence

import jax
import numpy as np
from jax.extend import core as jcore

from .graph import DOT, EW, FusionGraph, LAYOUT, OPAQUE, PrimOp, REDUCE

_EW_PRIMS = {
    "add", "sub", "mul", "div", "rem", "pow", "integer_pow", "neg", "sign",
    "abs", "exp", "exp2", "expm1", "log", "log1p", "tanh", "logistic", "erf",
    "erf_inv", "erfc", "rsqrt", "sqrt", "cbrt", "sin", "cos", "floor", "ceil",
    "round", "clamp", "max", "min", "and", "or", "xor", "not", "select_n",
    "eq", "ne", "lt", "le", "gt", "ge", "is_finite", "nextafter", "square",
    "shift_left", "shift_right_logical", "shift_right_arithmetic", "stop_gradient",
    "copy", "real", "imag", "complex", "conj", "add_any", "atan2", "tan",
    "asin", "acos", "atan", "sinh", "cosh", "asinh", "acosh", "atanh",
    "population_count", "clz", "igamma", "igammac", "lgamma", "digamma",
}
_REDUCE_PRIMS = {
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "reduce_and",
    "reduce_or", "reduce_xor", "argmax", "argmin", "cumsum", "cumprod",
    "cummax", "cummin", "cumlogsumexp", "reduce_precision",
}
_LAYOUT_PRIMS = {
    "reshape", "transpose", "broadcast_in_dim", "squeeze", "expand_dims",
    "rev", "slice", "concatenate", "pad", "convert_element_type",
    "bitcast_convert_type", "gather", "scatter", "scatter_add", "scatter_max",
    "scatter_min", "scatter_mul", "dynamic_slice", "dynamic_update_slice",
    "iota", "split",
}
_SUBJAXPR_INLINE = {
    "pjit", "closed_call", "custom_jvp_call", "custom_vjp_call",
    "custom_vjp_call_jaxpr", "remat", "remat2", "checkpoint", "custom_lin",
}


def _nbytes(v) -> float:
    aval = v.aval if hasattr(v, "aval") else v
    if not hasattr(aval, "shape"):
        return 0.0
    try:
        return float(np.prod(aval.shape, dtype=np.float64) * aval.dtype.itemsize)
    except Exception:
        return 0.0


def _classify(prim_name: str) -> str:
    if prim_name in ("dot_general", "conv_general_dilated",
                     "ragged_dot_general"):
        return DOT
    if prim_name in _EW_PRIMS:
        return EW
    if prim_name in _REDUCE_PRIMS:
        return REDUCE
    if prim_name in _LAYOUT_PRIMS:
        return LAYOUT
    return OPAQUE


def _ragged_dot_flops(eqn) -> float:
    """A grouped matmul priced by the rows its groups cover: each element
    of the lhs meets one group's matrix, so ``2 * |lhs| * N`` with ``N``
    the rhs's free size (its dims that are not contracted, batched or
    groups).  The group sizes are known only at run time: every static
    row counts as covered."""
    rdn = eqn.params["ragged_dot_dimension_numbers"]
    (_, rhs_c), (_, rhs_b) = rdn.dot_dimension_numbers
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    bound = set(rhs_c) | set(rhs_b) | set(rdn.rhs_group_dimensions)
    free = [n for i, n in enumerate(rhs.shape) if i not in bound]
    return 2.0 * float(np.prod(lhs.shape, dtype=np.float64)) \
        * float(np.prod(free, dtype=np.float64))


def _dot_flops(eqn) -> float:
    if eqn.primitive.name == "ragged_dot_general":
        return _ragged_dot_flops(eqn)
    out = eqn.outvars[0].aval
    dnums = eqn.params["dimension_numbers"]
    (lhs_c, _), _ = dnums
    lhs = eqn.invars[0].aval
    k = float(np.prod([lhs.shape[i] for i in lhs_c], dtype=np.float64)) if lhs_c else 1.0
    return 2.0 * float(np.prod(out.shape, dtype=np.float64)) * k


def _conv_flops(eqn) -> float:
    out = eqn.outvars[0].aval
    rhs = eqn.invars[1].aval  # kernel
    groups = eqn.params.get("feature_group_count", 1)
    k_elems = float(np.prod(rhs.shape, dtype=np.float64)) / max(rhs.shape[-1], 1)
    return 2.0 * float(np.prod(out.shape, dtype=np.float64)) * k_elems / max(groups, 1)


def _eqn_cost(eqn, scale: float = 1.0) -> tuple[str, float, float, float]:
    """(category, flops, in_bytes, out_bytes) for a flat eqn."""
    name = eqn.primitive.name
    cat = _classify(name)
    in_b = sum(_nbytes(v) for v in eqn.invars if hasattr(v, "aval")) * scale
    out_b = sum(_nbytes(v) for v in eqn.outvars) * scale
    out_elems = sum(
        float(np.prod(v.aval.shape, dtype=np.float64))
        for v in eqn.outvars
        if hasattr(v.aval, "shape")
    )
    if cat == DOT:
        flops = (_conv_flops(eqn) if name == "conv_general_dilated" else _dot_flops(eqn)) * scale
    elif cat == EW:
        flops = out_elems * scale
    elif cat == REDUCE:
        flops = sum(_nbytes(v) for v in eqn.invars if hasattr(v, "aval")) / 4.0 * scale
    elif cat == LAYOUT:
        flops = 0.0
        in_b = min(in_b, out_b * 2 + 64)  # slices/gathers read ~what they emit
    else:
        flops = out_elems * scale
    return cat, flops, in_b, out_b


class _Builder:
    def __init__(self):
        self.prims: list[PrimOp] = []
        self.edges: set[tuple[int, int]] = set()

    def add(self, op_type, category, flops, in_b, out_b, dep_pids) -> int:
        pid = len(self.prims)
        self.prims.append(
            PrimOp(pid=pid, op_type=op_type, category=category, flops=flops,
                   in_bytes=in_b, out_bytes=out_b, time=0.0)
        )
        for d in dep_pids:
            if d is not None and d != pid:
                self.edges.add((d, pid))
        return pid


def _subjaxpr_totals(jaxpr) -> tuple[float, float, float]:
    """Total (flops, in_bytes, out_bytes) of a sub-jaxpr body (for OPAQUE
    scan/while nodes)."""
    fl = ib = ob = 0.0
    for eqn in jaxpr.eqns:
        sub = _find_subjaxpr(eqn)
        if sub is not None:
            n = float(eqn.params.get("length", eqn.params.get("num_carry", 1)) or 1)
            f2, i2, o2 = _subjaxpr_totals(sub)
            fl += f2 * n
            ib += i2 * n
            ob += o2 * n
        else:
            _, f, i, o = _eqn_cost(eqn)
            fl += f
            ib += i
            ob += o
    return fl, ib, ob


def _find_subjaxpr(eqn):
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        if key in eqn.params:
            j = eqn.params[key]
            return j.jaxpr if hasattr(j, "jaxpr") else j
    return None


def _walk(jaxpr, env: dict, b: _Builder) -> None:
    """env maps jaxpr Var -> producing pid (or None for graph inputs)."""
    def rd(v):
        if isinstance(v, jcore.Literal):
            return None
        return env.get(v)

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        sub = _find_subjaxpr(eqn)
        if name in _SUBJAXPR_INLINE and sub is not None:
            inner = sub.jaxpr if hasattr(sub, "jaxpr") else sub
            ienv = dict(zip(inner.invars, [rd(v) for v in eqn.invars]))
            # constvars: treat as inputs
            for cv in inner.constvars:
                ienv[cv] = None
            saved = dict(env)
            env.update(ienv)
            _walk(inner, env, b)
            for ov, iv in zip(eqn.outvars, inner.outvars):
                env[ov] = rd(iv) if not isinstance(iv, jcore.Literal) else None
            # restore outer bindings that inner shadowed is unnecessary:
            # jaxpr vars are unique objects
            continue
        if sub is not None:  # scan / while / cond -> one OPAQUE node
            trips = float(eqn.params.get("length", 1) or 1)
            inner = sub.jaxpr if hasattr(sub, "jaxpr") else sub
            f, i_, o_ = _subjaxpr_totals(inner)
            in_b = sum(_nbytes(v) for v in eqn.invars if hasattr(v, "aval"))
            out_b = sum(_nbytes(v) for v in eqn.outvars)
            pid = b.add(name, OPAQUE, f * trips, max(in_b, i_), max(out_b, o_),
                        {rd(v) for v in eqn.invars if not isinstance(v, jcore.Literal)})
            for ov in eqn.outvars:
                env[ov] = pid
            continue
        cat, flops, in_b, out_b = _eqn_cost(eqn)
        deps = {rd(v) for v in eqn.invars if not isinstance(v, jcore.Literal)}
        pid = b.add(name, cat, flops, in_b, out_b, deps)
        for ov in eqn.outvars:
            env[ov] = pid


def graph_from_jaxpr(
    closed_jaxpr,
    grad_out_indices: Sequence[int],
    grad_bytes: Sequence[float],
    grad_sigs: Sequence[str] | None = None,
) -> FusionGraph:
    """Build a FusionGraph from a closed jaxpr whose outputs at
    ``grad_out_indices`` are the parameter gradients."""
    jaxpr = closed_jaxpr.jaxpr
    b = _Builder()
    env: dict = {v: None for v in list(jaxpr.invars) + list(jaxpr.constvars)}
    _walk(jaxpr, env, b)
    # attach gradient markers; insert identity prims on collision
    sigs = list(grad_sigs) if grad_sigs is not None else ["" for _ in grad_out_indices]
    marked: set[int] = set()
    for gi, (oi, gb) in enumerate(zip(grad_out_indices, grad_bytes)):
        ov = jaxpr.outvars[oi]
        pid = env.get(ov) if not isinstance(ov, jcore.Literal) else None
        if pid is None or pid in marked:
            pid = b.add("grad_identity", EW, 0.0, gb, gb,
                        {pid} if pid is not None else set())
        marked.add(pid)
        p = b.prims[pid]
        b.prims[pid] = PrimOp(
            pid=p.pid, op_type=p.op_type, category=p.category, flops=p.flops,
            in_bytes=p.in_bytes, out_bytes=p.out_bytes, time=p.time,
            grad_param=gi, grad_bytes=float(gb), grad_sig=sigs[gi],
        )
    return FusionGraph(b.prims, b.edges)


def trace_grad_graph(
    loss_fn: Callable,
    params,
    batch,
    grad_sig_fn: Callable[[int, object], str] | None = None,
) -> FusionGraph:
    """Trace ``jax.grad(loss_fn)`` (w.r.t. params) into a FusionGraph with one
    AllReduce per parameter-gradient leaf — the per-device data-parallel
    training graph DisCo optimises."""
    grad_fn = jax.grad(lambda p, bt: loss_fn(p, bt))
    closed = jax.make_jaxpr(grad_fn)(params, batch)
    leaves = jax.tree_util.tree_leaves(params)
    n = len(leaves)
    gbytes = [float(np.prod(l.shape, dtype=np.float64) * l.dtype.itemsize)
              if hasattr(l, "shape") else 8.0 for l in leaves]
    sigs = None
    if grad_sig_fn is not None:
        sigs = [grad_sig_fn(i, l) for i, l in enumerate(leaves)]
    else:
        sigs = [str(getattr(l, "dtype", "f32")) for l in leaves]
    return graph_from_jaxpr(closed, list(range(n)), gbytes, sigs)
