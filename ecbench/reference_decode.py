"""The plain reference of a degraded read: lost shards of an RS k+m
encoding recomputed from any k of the shards that are left.

From `reference.py`'s field and its coding matrix, and like it from
nothing of the program: the rows of the coding matrix that belong to
the k source shards form a square matrix that takes the data shards to
those sources; its inverse takes the sources back to the data shards,
and a row of the coding matrix times that inverse takes them to any
shard at all. Table look-ups on the host, no kernels.
"""

from __future__ import annotations

import numpy as np

from ecbench import reference as R


def decode_rows(k: int, m: int, sources: list[int], want: list[int]) -> list[list[int]]:
    """(len(want), k) coefficients taking the shards `sources` (k of
    them, in that order) to the shards `want`."""
    if len(sources) != k or len(set(sources)) != k:
        raise ValueError(f"a decode takes {k} distinct source shards, got {sources}")
    coding = R.coding_matrix(k, m)
    to_data = R._mat_inv([coding[s] for s in sources])
    return R._mat_mul([coding[w] for w in want], to_data)


def decode(
    shards: dict[int, np.ndarray], want: list[int], k: int, m: int
) -> dict[int, np.ndarray]:
    """Shards `want`, each from the first k shards of `shards` by shard
    id (uint8 arrays of one length)."""
    sources = sorted(shards)[:k]
    rows = decode_rows(k, m, sources, want)
    out = {}
    for w, row in zip(want, rows):
        acc = np.zeros(len(shards[sources[0]]), dtype=np.uint8)
        for c, s in zip(row, sources):
            if c:
                acc ^= R.mul_table(c)[shards[s]]
        out[w] = acc
    return out
