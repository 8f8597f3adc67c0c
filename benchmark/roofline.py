"""Peaks by device kind, and the operations and bytes that the update and
the fire need, computed from shapes and counts.

The bytes are what the algorithm needs, not what the program moves:

- update: each event's input columns are read once (key 8 bytes as two
  uint32 halves, event tick 4, value 4, valid 1), and each distinct
  (key, pane) accumulator the step touches is read and written once
  (4 + 4 bytes for a float32 sum). One add per event.
- fire: each fired (key, window) accumulator is read (4) and its result
  written (key slot 4, value 4). Fired pairs, not the capacity swept.
"""

from __future__ import annotations

import json
import math
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")
EVENT_IN_BYTES = 8 + 4 + 4 + 1
ACC_BYTES = 4
FIRE_PAIR_BYTES = 4 + 4 + 4


def peaks(device_kind: str) -> dict:
    with open(PEAKS_FILE) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def expected_distinct(n_draws: float, n_keys: int) -> float:
    """Expected distinct keys among ``n_draws`` uniform draws from
    ``n_keys``."""
    return -n_keys * math.expm1(n_draws * math.log1p(-1.0 / n_keys))


def update_cost(n_events: int, n_steps: int, n_keys: int) -> tuple:
    """(ops, bytes) of ``n_steps`` update steps that apply ``n_events``
    uniform-key events in all, each step within one pane."""
    per_step = n_events / max(1, n_steps)
    touched = n_steps * expected_distinct(per_step, n_keys)
    return (float(n_events),
            n_events * EVENT_IN_BYTES + touched * 2 * ACC_BYTES)


def fire_cost(n_fired: int) -> tuple:
    return float(n_fired), n_fired * FIRE_PAIR_BYTES


def roofline_pct(ops: float, nbytes: float, device_s: float,
                 peak: dict) -> float:
    """Least time the chip could take over the time it took, in %."""
    least = max(nbytes / peak["hbm_bytes_per_s"], ops / peak["bf16_flops"])
    return 100.0 * least / device_s
