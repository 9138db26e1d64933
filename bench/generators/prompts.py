"""The generator of prompt mixes: a closed loop's requests from a data file
``bench/traffic/<mix>.json`` whose ``generator`` is ``prompts``.

Every seed gets the same set of sizes, only in another order, and the order
is stratified: each run of ``stratum`` consecutive requests holds one size
from each of ``stratum`` equal bands of the size distribution.  So a window
that ends anywhere has the same mix of sizes whatever the seed, and the seed
changes which tokens are sent and in what order, not how much work there is.
"""

from __future__ import annotations

import statistics

import numpy as np


def _sizes(spec: dict, n: int) -> np.ndarray:
    """``n`` sizes at evenly spaced quantiles of the spec's distribution."""
    q = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(float(p)) for p in q])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        x = spec["min"] + q * (spec["max"] - spec["min"])
    else:
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    return np.clip(np.round(x), spec["min"], spec["max"]).astype(np.int64)


def stratified_order(sizes: np.ndarray, stratum: int,
                     rng: np.random.Generator) -> np.ndarray:
    """``sizes`` (sorted, a multiple of ``stratum`` long) reordered so every
    block of ``stratum`` holds one element of each band."""
    n = len(sizes)
    if n % stratum:
        raise ValueError(f"pool {n} is not a multiple of stratum {stratum}")
    blocks = n // stratum
    bands = np.sort(sizes).reshape(stratum, blocks)
    grid = np.stack([rng.permutation(b) for b in bands], 1)   # [blocks, stratum]
    grid = np.stack([rng.permutation(row) for row in grid])
    return grid[rng.permutation(blocks)].reshape(-1)


def bucket_of(n: int, buckets: list[int]) -> int:
    for b in sorted(buckets):
        if n <= b:
            return b
    raise ValueError(f"prompt of {n} tokens exceeds the largest bucket")


def generate(spec: dict, seed: int, *, vocab: int) -> list[tuple[np.ndarray, int]]:
    """A closed loop's request list: ``(tokens [1, bucket] int32, real
    length)``, right-padded with id 0 to the smallest bucket that holds it."""
    rng = np.random.default_rng([int(seed), 0x70726F6D])
    order = stratified_order(_sizes(spec["lengths"], spec["pool"]),
                             spec["stratum"], rng)
    out = []
    for n in order:
        b = bucket_of(int(n), spec["buckets"])
        row = np.zeros((1, b), np.int32)
        row[0, :n] = rng.integers(0, vocab, int(n), dtype=np.int32)
        out.append((row, int(n)))
    return out
