"""The one general generator of a cell's events. A traffic file
(``benchmark/traffic/<name>.json``) holds parameters only, and names the
two parts that read them, each a file found by name (``registry.py``):

- ``"arrival"``: ``arrivals/<arrival>.py``, when each event is due, its
  event time, and how much a run feeds before its window opens;
- ``"keys"``: ``keys/<keys>.py``, each event's key.

Values are the configuration's: integers uniform in [low, high]
(``reference.gen_values``). Every part is a pure function of the event
index and the seed, so the reference remakes the same events.
"""

from __future__ import annotations

import numpy as np

from benchmark import registry
from benchmark.reference import gen_values


class Traffic:
    def __init__(self, traffic: dict, job: dict, root: str = registry.ROOT):
        arrival = registry.load("arrivals", traffic["arrival"], root)
        self.sched = arrival.Schedule(traffic, job["batch"],
                                      job["window"]["size_ms"])
        self._keys = registry.load("keys", traffic["keys"], root).keys
        self.n_keys = job["keys"]
        self.low, self.high = job["values"]["low"], job["values"]["high"]

    def keys(self, idx: np.ndarray, seed: int) -> np.ndarray:
        return self._keys(np.asarray(idx, np.int64), seed, self.n_keys)

    def values(self, idx: np.ndarray, seed: int) -> np.ndarray:
        return gen_values(idx, seed, self.low, self.high)

    def events(self, idx: np.ndarray, seed: int):
        """(columns, event times in ms) of the events ``idx``."""
        return ({"key": self.keys(idx, seed),
                 "value": self.values(idx, seed)},
                self.sched.event_ms(idx))


def nearest_rank(values: np.ndarray, weights: np.ndarray, q: float) -> float:
    """The q-th percentile (0..100) by nearest rank over weighted values:
    the smallest value whose cumulative weight reaches q% of the total."""
    order = np.argsort(values, kind="stable")
    v, w = np.asarray(values, np.float64)[order], np.asarray(weights)[order]
    cum = np.cumsum(w)
    rank = max(1, int(np.ceil(q / 100.0 * cum[-1])))
    return float(v[np.searchsorted(cum, rank)])
