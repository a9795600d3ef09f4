"""The training tokens of a run, made from its seed.

A copy of the recipe of the program's ``SyntheticPipeline`` (a noisy
periodic walk over the vocabulary, seeded per step), kept here so that the
reference reads its own tokens: a pipeline that hands the step other tokens
or misaligned targets is caught by the comparison.
"""
from __future__ import annotations

import numpy as np

N_STATES = 64


def batch(seed: int, step: int, rows: int, seq: int, vocab: int) -> tuple[np.ndarray, np.ndarray]:
    """(tokens, targets), each (rows, seq) int32; targets are the tokens
    shifted left by one."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, step, 0]))
    base = rng.integers(0, N_STATES, size=(rows, 1))
    drift = np.cumsum(rng.integers(0, 3, size=(rows, seq + 1)), axis=1)
    noise = rng.integers(0, 2, size=(rows, seq + 1))
    toks = ((base + drift + noise) % vocab).astype(np.int32)
    return toks[:, :seq], toks[:, 1:]
