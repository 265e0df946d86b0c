"""Pytree checkpointing on npz (the container has no orbax/tensorstore).

Layout: ``<dir>/step_<n>/arrays.npz`` + ``treedef.json``.  Arrays are
flattened with stable keypath names so checkpoints survive refactors that
preserve the tree structure; bfloat16 leaves are stored via a uint16 view
(npz has no native bf16).  Writes are atomic (tmp dir + rename) — a killed
run never leaves a half-written "latest" checkpoint.
"""
from __future__ import annotations

import json
import os
import shutil
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs


def _keystr(path) -> str:
    return jax.tree_util.keystr(path).replace("/", "_")


def save_checkpoint(ckpt_dir: str, step: int, tree) -> str:
    with obs.span("ckpt.save"):
        return _save(ckpt_dir, step, tree)


def _save(ckpt_dir: str, step: int, tree) -> str:
    os.makedirs(ckpt_dir, exist_ok=True)
    leaves_with_paths = jax.tree_util.tree_flatten_with_path(tree)[0]
    arrays = {}
    meta = {}
    for i, (path, leaf) in enumerate(leaves_with_paths):
        key = f"leaf_{i}"
        arr = np.asarray(leaf)
        if arr.dtype == jnp.bfloat16:
            arrays[key] = arr.view(np.uint16)
            meta[key] = {"path": _keystr(path), "dtype": "bfloat16"}
        else:
            arrays[key] = arr
            meta[key] = {"path": _keystr(path), "dtype": str(arr.dtype)}
    final = os.path.join(ckpt_dir, f"step_{step:08d}")
    tmp = tempfile.mkdtemp(dir=ckpt_dir, prefix=".tmp_")
    try:
        np.savez(os.path.join(tmp, "arrays.npz"), **arrays)
        with open(os.path.join(tmp, "meta.json"), "w") as f:
            json.dump({"step": step, "leaves": meta}, f)
        if os.path.exists(final):
            shutil.rmtree(final)
        os.rename(tmp, final)
    finally:
        if os.path.exists(tmp):
            shutil.rmtree(tmp, ignore_errors=True)
    return final


def latest_step(ckpt_dir: str) -> int | None:
    if not os.path.isdir(ckpt_dir):
        return None
    steps = [int(d.split("_")[1]) for d in os.listdir(ckpt_dir)
             if d.startswith("step_")]
    return max(steps) if steps else None


def restore_checkpoint(ckpt_dir: str, tree, step: int | None = None):
    """Restore into the structure of ``tree`` (a template pytree)."""
    if step is None:
        step = latest_step(ckpt_dir)
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {ckpt_dir}")
    d = os.path.join(ckpt_dir, f"step_{step:08d}")
    with open(os.path.join(d, "meta.json")) as f:
        meta = json.load(f)
    data = np.load(os.path.join(d, "arrays.npz"))
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if len(leaves) != len(meta["leaves"]):
        raise ValueError(
            f"checkpoint has {len(meta['leaves'])} leaves, template has "
            f"{len(leaves)}")
    out = []
    for i, template in enumerate(leaves):
        key = f"leaf_{i}"
        arr = data[key]
        if meta["leaves"][key]["dtype"] == "bfloat16":
            arr = jnp.asarray(arr.view(np.uint16)).view(jnp.bfloat16)
        out.append(jnp.asarray(arr))
    return jax.tree_util.tree_unflatten(treedef, out), step
