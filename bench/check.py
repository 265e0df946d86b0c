"""The comparison that decides ``correct``: the program's first training
steps against the plain reference's, number by number, each against the
limit in ``bench/limits/<cell>.json``.

The numbers, each the largest over its items:

* ``loss_gap``: relative gap of each checked step's loss;
* ``grad_gap``: step 1's clipped gradient as the optimizer received it,
  the gap between the program's and the reference's norm of a leaf, over
  the larger of the reference's norm of that leaf and of the median leaf;
* ``change_gap``: the change of the weights over the checked steps, as
  stored after the last one: the gap between the two norms of a leaf over
  the reference's norm of that leaf, so that a leaf left unmoved, or moved
  double, reads 1 however small it is.

Parts of the weights whose reference gradient is nought to rounding move
under Adam by round-off alone, and are left out of ``change_gap`` by a
rule on the reference's step-1 gradient: a leaf under a thousandth of the
median leaf's, and within a leaf a channel (an index of its last axis)
under a thousandth of the leaf's root-mean-square channel.  A key bias's
channels that rotary embedding never rotates are such channels: softmax
cancels a bias that is the same for every key.  Leaves that the
reference's steps leave as they were (norm scales of 1, whose updates
round away in bfloat16) are left out too.
"""
from __future__ import annotations

import math

import numpy as np

NUMBERS = ("loss_gap", "grad_gap", "change_gap")
QUIET_GRAD = 1e-3


def leaf_gaps(prog, ref) -> np.ndarray:
    """Per leaf, the gap between two norms over the larger of the
    reference's norm of that leaf and of the median leaf."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    floor = max(float(np.median(ref)), 1e-30)
    gap = np.abs(prog - ref) / np.maximum(ref, floor)
    return np.where(np.isfinite(gap), gap, np.inf)


def own_gaps(prog, ref) -> np.ndarray:
    """Per leaf, the gap between two norms over the reference's norm of
    that leaf; 0 where the reference's is 0 (a leaf left out)."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    with np.errstate(divide="ignore", invalid="ignore"):
        gap = np.abs(prog - ref) / ref
    gap = np.where(ref > 0, gap, 0.0)
    return np.where(np.isfinite(gap), gap, np.inf)


def moving(ref: dict) -> np.ndarray:
    """Leaves whose reference gradient is not nought to rounding."""
    g = np.asarray(ref["grad1"], np.float64)
    return g >= QUIET_GRAD * np.median(g)


def moving_channels(ref: dict) -> list:
    """Per leaf, a mask of the channels whose reference gradient is not
    nought to rounding, in leaves that are not."""
    out = []
    for keep, ch in zip(moving(ref), ref["grad1_channels"]):
        ch = np.asarray(ch, np.float64)
        rms = math.sqrt(float(np.mean(np.square(ch))))
        out.append(keep & (ch >= QUIET_GRAD * rms))
    return out


def masked_norms(channels: list, masks: list) -> np.ndarray:
    """Per leaf, the norm over the channels that ``masks`` keeps."""
    return np.asarray([math.sqrt(float(np.sum(np.square(
        np.asarray(c, np.float64)[k])))) for c, k in zip(channels, masks)])


def _worst(gap, names):
    if not len(gap):
        return math.inf, "no leaves"
    i = int(np.argmax(gap))
    return float(gap[i]), names[i]


def readings(prog: dict, ref: dict, names: list) -> dict:
    """``{number: (value, where)}`` for the program's and the reference's
    ``loss`` (per step), ``grad1`` (per leaf) and ``change`` (per leaf and
    channel)."""
    lp = np.asarray(prog["loss"], np.float64)
    lr = np.asarray(ref["loss"], np.float64)
    rel = np.abs(lp - lr) / np.abs(lr)
    rel = np.where(np.isfinite(rel), rel, np.inf)
    i = int(np.argmax(rel))
    out = {"loss_gap": (float(rel[i]), f"step {i + 1}")}
    if len(prog["grad1"]) != len(ref["grad1"]) or \
            len(prog["change"]) != len(ref["change"]):
        out["grad_gap"] = out["change_gap"] = (math.inf, "leaf count differs")
        return out
    out["grad_gap"] = _worst(leaf_gaps(prog["grad1"], ref["grad1"]), names)
    masks = moving_channels(ref)
    out["change_gap"] = _worst(own_gaps(masked_norms(prog["change"], masks),
                                        masked_norms(ref["change"], masks)),
                               names)
    return out


def verdict(found: dict, limits: dict) -> tuple[bool, list]:
    """Whether every number is within its limit, and one line per number."""
    lines, ok = [], True
    for key in NUMBERS:
        value, where = found[key]
        limit = limits[key]["limit"]
        good = value <= limit
        ok = ok and good
        lines.append({"name": key, "value": value, "limit": limit,
                      "at": where, "ok": good})
    return ok, lines
