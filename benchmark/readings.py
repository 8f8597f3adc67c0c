"""What a metric reader is given: one run's record, and the arithmetic
that several readers share. Each metric is a file under ``end_to_end/``
or ``per_layer/``, found by its name in ``BENCHMARK.json``
(``registry.load_reader``), with ``read(ctx)`` returning a number, or
None where it finds nothing to read."""

from __future__ import annotations

from typing import Optional

import numpy as np

from benchmark import trace as trace_mod
from benchmark.traffic import nearest_rank

class Ctx:
    def __init__(self, cfg: dict, rec: dict, setup_s: float,
                 reduced: Optional[dict] = None, peak: Optional[dict] = None):
        self.cfg, self.rec, self.setup_s = cfg, rec, setup_s
        self.win = rec["window"]
        self.sched = rec["sched"]
        self.trace = reduced      # trace.reduce(...) of the traced window
        self.peak = peak

    @property
    def window_s(self) -> float:
        return self.win.t_close - self.win.t_open

    def span_share(self, names) -> Optional[float]:
        """Share of the window covered by the union of the step loop's
        spans of these names."""
        iv = np.asarray([[s[2], s[2] + s[3]] for s in self.rec["spans"]
                         if s[0] in names], np.float64).reshape(-1, 2)
        if not len(iv):
            return None
        iv = np.clip(iv, self.win.t_open, self.win.t_close)
        return trace_mod.length(trace_mod.union(iv)) / self.window_s

    def window_calls(self):
        return [c for c in self.rec["sink_calls"]
                if self.win.t_open <= c[0] <= self.win.t_close]

    def result_latencies(self):
        """Per (key, window) result emitted in the window: sink-call time
        minus the due time of the last event of its window, whatever its
        key. Open loop only; (seconds, weights) or None."""
        if not self.sched.open_loop:
            return None
        lat, w = [], []
        for t, _, ends, _ in self.window_calls():
            e, n = np.unique(np.asarray(ends, np.int64), return_counts=True)
            last = self.sched.last_event_before(e)
            lat.append(t - self.sched.due_s(last))
            w.append(n)
        if not lat:
            return None
        return np.concatenate(lat), np.concatenate(w)

    def latency_pct(self, q: float) -> Optional[float]:
        lw = self.result_latencies()
        return None if lw is None else 1e3 * nearest_rank(lw[0], lw[1], q)

    def fired_in_window(self) -> int:
        return int(sum(len(c[2]) for c in self.window_calls()))

    def events_in_window(self) -> int:
        return self.win.records_close - self.win.records_open

    def kernel_share(self, kernel: str, cost) -> Optional[float]:
        """Roofline share, in %, of one kernel's device time summed over
        devices, for ``cost = (ops, bytes)`` of the work it did."""
        if self.trace is None:
            return None
        dev_ns = sum(d["kernel_ns"].get(kernel, 0.0)
                     for d in self.trace["devices"])
        if dev_ns <= 0:
            return None
        from benchmark.roofline import roofline_pct

        return roofline_pct(cost[0], cost[1], dev_ns / 1e9, self.peak)

    def kernel_calls(self, kernel: str) -> int:
        return sum(d["kernel_calls"].get(kernel, 0)
                   for d in self.trace["devices"])

    def idle_share(self) -> Optional[float]:
        if self.trace is None or not self.trace["devices"]:
            return None
        busy = np.mean([d["busy_ns"] for d in self.trace["devices"]]) / 1e9
        return 1.0 - busy / self.window_s
