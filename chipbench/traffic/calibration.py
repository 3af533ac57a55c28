"""Calibration batches for the search: ``n_seqs`` x ``seq_len`` token ids
drawn uniformly from the vocabulary with the run's seed (no dataset is in
the repository, so the text is random; every seed gives the same shape)."""
from __future__ import annotations

import numpy as np


def make(traffic: dict, model: dict, seed: int) -> np.ndarray:
    rng = np.random.default_rng([int(seed), 0xCA11B])
    return rng.integers(0, model["vocab_size"],
                        size=(traffic["n_seqs"], traffic["seq_len"]),
                        dtype=np.int32)
