"""``{"arrival": "saturate", "events_per_ms": E}``: the source always has
the next batch ready; event *i* has event time ``i // E`` ms."""

from __future__ import annotations

import numpy as np


class Schedule:
    open_loop = False

    def __init__(self, traffic: dict, batch: int, window_ms: int):
        self.per_ms = int(traffic["events_per_ms"])
        # one window of event time, plus one batch
        self.warmup = window_ms * self.per_ms + batch

    def event_ms(self, idx) -> np.ndarray:
        return np.asarray(idx, np.int64) // self.per_ms

    def start(self, t_open: float, n_fed: int) -> None:
        pass

    def last_event_before(self, end_ms) -> np.ndarray:
        """Index of the last event whose event time is below ``end_ms``
        (exclusive), whatever its key."""
        return np.asarray(end_ms, np.int64) * self.per_ms - 1
