"""Token batches of a training job, made from the seed during set-up.

The generator is a copy of the Markov process of the program's
``SyntheticLMDataset`` (each token prefers ``branching`` successors from a
fixed table over a slice of the vocabulary, with a jump to a uniform token
at rate ``jump``), written over all rows of all batches at once so that
set-up pays one pass over the sequence length and not one per step.

Every seed gives the same amount of work: ``pool`` distinct global batches
of the same shape, served in order and then again from the first.
"""
from __future__ import annotations

import numpy as np


class TokenPool:
    """Serves ``global_step_batch(step)`` as the program's data pipeline
    does, from batches made in advance."""

    def __init__(self, job: dict, vocab: int, global_batch: int, seed: int):
        rng = np.random.default_rng([seed, 0x5eed])
        v = min(vocab, job["vocab_slice"])
        succ = rng.integers(0, v, size=(v, job["branching"]))
        rows = job["pool"] * global_batch
        toks = np.empty((rows, job["seq_len"]), np.int32)
        cur = rng.integers(0, v, size=rows)
        for t in range(job["seq_len"]):
            toks[:, t] = cur
            pick = rng.integers(0, job["branching"], size=rows)
            jump = rng.random(rows) < job["jump"]
            cur = np.where(jump, rng.integers(0, v, size=rows),
                           succ[cur, pick])
        self.batches = toks.reshape(job["pool"], global_batch,
                                    job["seq_len"])

    def global_step_batch(self, step: int) -> np.ndarray:
        return self.batches[step % len(self.batches)]
