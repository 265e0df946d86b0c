"""Mesh builders.

``make_device_mesh`` lays the devices this process sees out as
``(data, model)`` — every device on ``data`` by default; the trainer and
``chip_smoke.py`` use it on one chip, four chips or forced host devices
alike.  ``make_production_mesh`` builds the pod meshes the dry-run prices:

Single pod: 16x16 = 256 chips, axes (data, model).
Multi pod:  2x16x16 = 512 chips, axes (pod, data, model).

Every builder is a FUNCTION (importing this module never touches jax
device state).  The dry-run program sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before jax
initialises so 512 placeholder host devices exist.

``make_mesh`` is ``jax.make_mesh`` with every axis ``AxisType.Auto``: the
train step goes manual over the data axes with ``shard_map`` and leaves
tensor parallelism to GSPMD over ``model``.

``cluster_from_mesh`` bridges a mesh to the topology model of
:mod:`repro.cluster` (intra-pod axes -> one ICI level, a ``pod`` axis -> an
outer DCN level) so dry-runs and searches can price collectives on the
interconnect the mesh actually spans.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes),
                         devices=devices)


def make_device_mesh(model: int = 1):
    """``(data, model)`` mesh over every device this process sees:
    ``model``-way tensor parallelism, the rest data parallel."""
    devices = jax.devices()
    if len(devices) % model:
        raise ValueError(f"{len(devices)} devices do not split into "
                         f"model={model}")
    return make_mesh((len(devices) // model, model), ("data", "model"),
                     devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 512 if multi_pod else 256
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices (set XLA_FLAGS="
            f"--xla_force_host_platform_device_count=512 before importing "
            f"jax); have {len(devices)}")
    return make_mesh(shape, axes, devices=devices[:n])


def cluster_from_mesh(mesh, hw=None):
    """``from_mesh`` bridge: lift a jax Mesh onto a
    :class:`repro.cluster.ClusterSpec` (DESIGN.md Sec. 7).

    Intra-pod axes collapse into the v5e-style ICI torus levels
    (``tpu_pod_levels``, at ``hw.ici_bw``); a ``pod`` axis (the multi-pod
    production mesh) becomes an outer DCN level (``dcn_level`` — same
    constants as the ``cross_dc_2pod`` preset, single source).  Only
    ``mesh.shape`` (an axis-name -> size mapping) is consulted, so any
    mesh-shaped object works — no jax device state is touched.
    """
    from repro.cluster import ClusterSpec, dcn_level, tpu_pod_levels
    from repro.core.hw import TPU_V5E

    hw = hw or TPU_V5E
    shape = dict(mesh.shape)
    pods = int(shape.pop("pod", 1))
    ici = 1
    for v in shape.values():
        ici *= int(v)
    levels = tpu_pod_levels(ici, bw=hw.ici_bw)
    if pods > 1:
        levels = levels + (dcn_level(pods),)
    name = "mesh_" + "x".join(str(s) for s in
                              ([pods] if pods > 1 else []) + list(shape.values()))
    return ClusterSpec(name, levels)
