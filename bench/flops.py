"""Model operations per token and the chip's peaks.

Counts the operations the forward and backward passes require, not those a
program executes: recomputation under remat and the masked half of causal
attention are left out.
"""
from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def matmul_params(m: dict) -> int:
    """Weights that enter a matmul: attention and MLP projections of every
    layer, and the head (the embedding, where it is tied)."""
    d, f = m["d_model"], m["d_ff"]
    hd = m["head_dim"] or d // m["n_heads"]
    attn = d * hd * (m["n_heads"] + 2 * m["n_kv_heads"]) \
        + m["n_heads"] * hd * d
    mlp = (3 if m["glu"] else 2) * d * f
    return m["n_layers"] * (attn + mlp) + m["vocab"] * d


def flops_per_token(m: dict, seq_len: int) -> float:
    """6 per matmul weight, plus causal attention at ``6 * S * d_attn`` a
    layer (``2 * S * d_attn`` forward for scores and values over half the
    positions on average, times three for the backward)."""
    hd = m["head_dim"] or m["d_model"] // m["n_heads"]
    attn = 6 * seq_len * m["n_heads"] * hd * m["n_layers"]
    return 6.0 * matmul_params(m) + attn


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip; a kind not in the table raises."""
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]
