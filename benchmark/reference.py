"""What every plain reference shares: the seeded hash that makes keys
and values, as ``chip_smoke.py`` makes them, copied here so that later
PRs cannot change the yardstick. The reference of each job kind is
``references/<kind>.py``. Imports nothing of the program.

Values are integers uniform in [low, high] stored as float32, not all
ones as in ``chip_smoke.py``: a per-(key, window) sum then stays far
below 2**24 and is exact in float32, whereas a sum accumulated in
bfloat16 is not.
"""

from __future__ import annotations

import numpy as np

_M64 = 2**64


def splitmix64(z: np.ndarray) -> np.ndarray:
    z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return z ^ (z >> np.uint64(31))


def seed_salt(seed: int, mult: int, add: int = 0) -> int:
    """A 64-bit salt from a seed of any size."""
    return (seed * mult + add) % _M64


def gen_values(idx: np.ndarray, seed: int, low: int, high: int) -> np.ndarray:
    """Integers uniform in [low, high] from a second salt, as float32."""
    salt = seed_salt(seed, 0xD1B54A32D192ED03, 0x8CB92BA72F3D8DD7)
    z = splitmix64(np.asarray(idx).astype(np.uint64) + np.uint64(salt))
    return (np.uint64(low) + z % np.uint64(high - low + 1)).astype(np.float32)
