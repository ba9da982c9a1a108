"""The synthetic token stream of the training traffic, as the data
pipeline documents it (a copy, so that a change to the program cannot
move what the reference trains on).

Each (step, part) draws from its own counter-seeded generator: Zipf(1.3)
unigrams folded into [1, vocab), then a periodic copy pattern (position
t repeats t - 8 with probability 1/2); labels are the next token.
"""
from __future__ import annotations

import numpy as np


def _fold(seed: int, *xs: int) -> np.random.Generator:
    return np.random.default_rng(np.uint64(seed)
                                 + np.uint64(hash(xs) & 0x7FFFFFFF))


def part_rows(vocab: int, seq_len: int, rows: int, seed: int, step: int,
              part: int):
    rng = _fold(seed, step, part)
    z = rng.zipf(1.3, size=(rows, seq_len + 1))
    toks = (z % (vocab - 1)).astype(np.int32) + 1
    mask = rng.random((rows, seq_len + 1)) < 0.5
    toks[:, 8:][mask[:, 8:]] = toks[:, :-8][mask[:, 8:]]
    return toks[:, :-1], toks[:, 1:]


def unique_rows(vocab: int, seq_len: int, unique: int, groups: int,
                seed: int, step: int):
    """The step's unique rows: ``groups`` parts of ``unique / groups``
    rows each, in part order."""
    parts = [part_rows(vocab, seq_len, unique // groups, seed, step, j)
             for j in range(groups)]
    return (np.concatenate([p[0] for p in parts]),
            np.concatenate([p[1] for p in parts]))
