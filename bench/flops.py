"""The chip's peaks, and what a family's operation count counts.

Each reference family counts its model's operations per token
(``flops_per_token(m, seq_len)`` in ``bench/references/<family>.py``):
those the forward and backward passes require, not those a program
executes, so recomputation under remat and the masked half of causal
attention are left out, and an expert a token is not routed to counts
nothing.
"""
from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip; a kind not in the table raises."""
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]
