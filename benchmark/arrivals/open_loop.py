"""``{"arrival": "open_loop", "rate_per_s": R}``: a fixed schedule. Event
*i* is due at ``t0 + i / R`` and its event time is its due time in ms,
``i * 1000 // R``; the job never slows it. The schedule starts when the
window opens, with the warm-up's events treated as due before it."""

from __future__ import annotations

import numpy as np


class Schedule:
    open_loop = True

    def __init__(self, traffic: dict, batch: int, window_ms: int):
        self.rate = int(traffic["rate_per_s"])
        # one window of event time, plus one batch
        self.warmup = -(-window_ms * self.rate // 1000) + batch
        self.t0 = None

    def event_ms(self, idx) -> np.ndarray:
        return np.asarray(idx, np.int64) * 1000 // self.rate

    def start(self, t_open: float, n_fed: int) -> None:
        self.t0 = t_open - n_fed / self.rate

    def due_s(self, idx):
        """Host-clock time at which event ``idx`` is due."""
        return self.t0 + np.asarray(idx, np.float64) / self.rate

    def due_by(self, now: float) -> int:
        """How many events are due by ``now``: indices 0..n-1."""
        # the epsilon keeps an event due at exactly ``now`` from rounding out
        return int(np.floor((now - self.t0) * self.rate + 1e-6)) + 1

    def last_event_before(self, end_ms) -> np.ndarray:
        """Index of the last event whose event time is below ``end_ms``
        (exclusive), whatever its key."""
        end_ms = np.asarray(end_ms, np.int64)
        # i * 1000 // R < E  <=>  i * 1000 < E * R
        return -(-end_ms * self.rate // 1000) - 1
