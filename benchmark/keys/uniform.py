"""``"keys": "uniform"``: chip_smoke.py's keys, splitmix64 of (index +
seed salt) modulo the key space: uniform, a pure function of the event
index, with duplicates in every batch."""

from __future__ import annotations

import numpy as np

from benchmark.reference import seed_salt, splitmix64


def keys(idx: np.ndarray, seed: int, n_keys: int) -> np.ndarray:
    z = np.asarray(idx).astype(np.uint64) + np.uint64(
        seed_salt(seed, 0x9E3779B97F4A7C15))
    return (splitmix64(z) % np.uint64(n_keys)).astype(np.int64)
