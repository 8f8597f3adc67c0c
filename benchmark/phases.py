"""The program's own spans on the profiler trace's clock.

The step loop's spans (``flink_tpu.metrics.tracing``) are recorded on
``time.perf_counter``; the device ops are on the profiler's clock. The
program anchors the two: at most once a second it records a ``clock`` span
inside a ``flink_tpu.clock`` host annotation. ``offset_ns`` pairs the
annotations with the spans, and everything else here works on spans mapped
through it:

- ``name_gaps``: each idle gap of device 0, named by the ``gc`` or
  ``compile`` span that overlaps it, else by the innermost span of the
  executor thread that overlaps it most, else by the harness's marks, else
  "other host";
- ``unattributed_share``: the share of the window in which device 0 is idle
  and no executor-thread span covers it;
- ``clock_check``: how far the mapping misplaces the spans against the
  device modules they caused.

The per-layer readers that read spans alone (no trace) use ``named`` and
``batch_ids``. A program that records no ``clock`` span records none of
the spans these read, and the readers then return None.

Run as a script it makes one traced run of a cell exactly as ``run.py``
does, keeps the trace, and prints one more JSON line, ``phases``::

    python3 benchmark/phases.py --workload <cell> --seed <n> --seconds <s>
"""

from __future__ import annotations

import os
import sys
from typing import Dict, List, Optional, Tuple

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import trace as trace_mod  # noqa: E402

CLOCK_ANNOTATION = "flink_tpu.clock"
STALLS = ("gc", "compile")
# two anchors are at least a second apart; an annotation matches its span
# well inside that
MATCH_NS = 1e6


# ----------------------------------------------------- spans alone

def named(spans, *names) -> list:
    return [s for s in spans if s[0] in names]


def has_anchor(spans) -> bool:
    """Whether the program anchors its spans (and so records the spans
    the readers of this module read)."""
    return any(s[0] == "clock" for s in spans)


def batch_ids(span) -> list:
    """The poll sequence numbers a span carries: one, a group's, or
    none."""
    b = (span[4] or {}).get("batch") if len(span) > 4 else None
    if b is None:
        return []
    return list(b) if isinstance(b, (list, tuple)) else [b]


def executor_thread(spans) -> Optional[str]:
    """The thread that runs the step loop: the one that records the clock
    anchors."""
    for s in spans:
        if s[0] == "clock" and len(s) > 5:
            return s[5]
    return None


# ----------------------------------------------------- onto the trace

def clock_annotations(tr: dict) -> np.ndarray:
    """Start times (ns, trace clock) of the host's flink_tpu.clock
    annotations, sorted."""
    out = [ev[1] for pl in tr["planes"] if pl["name"].startswith("/host:")
           for ln in pl["lines"] for ev in ln["events"]
           if ev[0] == CLOCK_ANNOTATION]
    return np.sort(np.asarray(out, np.float64))


def _nearest(sorted_x: np.ndarray, y: np.ndarray) -> np.ndarray:
    """Index into ``sorted_x`` of the element nearest each of ``y``."""
    j = np.clip(np.searchsorted(sorted_x, y), 1, max(1, len(sorted_x) - 1))
    j = np.minimum(j, len(sorted_x) - 1)
    left = np.maximum(j - 1, 0)
    return np.where(np.abs(sorted_x[left] - y) <= np.abs(sorted_x[j] - y),
                    left, j)


def anchor_pairs(spans, anns: np.ndarray) -> List[Tuple[float, float]]:
    """(span start s, annotation start ns) for each annotation matched
    to its clock span. The first annotation is paired with the clock span
    under which most of the others find a span within MATCH_NS."""
    starts = np.sort(np.asarray([s[2] for s in named(spans, "clock")],
                                np.float64))
    if not len(anns) or not len(starts):
        return []
    best, best_n = None, 0
    for s0 in starts:
        mapped = starts * 1e9 + (anns[0] - s0 * 1e9)
        n = int(np.sum(np.abs(mapped[_nearest(mapped, anns)] - anns)
                       < MATCH_NS))
        if n > best_n:
            best, best_n = anns[0] - s0 * 1e9, n
    mapped = starts * 1e9 + best
    i = _nearest(mapped, anns)
    return [(float(starts[k]), float(a)) for k, a in zip(i, anns)
            if abs(mapped[k] - a) < MATCH_NS]


def offset_ns(pairs, t_mid: float) -> Optional[float]:
    """Trace ns minus perf_counter ns, from the anchor nearest t_mid."""
    if not pairs:
        return None
    s, a = min(pairs, key=lambda p: abs(p[0] - t_mid))
    return a - s * 1e9


def on_trace_clock(spans, off: float) -> list:
    """Spans as (name, thread, start ns, end ns, attrs) on the trace
    clock."""
    return [(s[0], s[5] if len(s) > 5 else None, s[2] * 1e9 + off,
             (s[2] + s[3]) * 1e9 + off, s[4]) for s in spans]


def _overlap(s, e, a, b) -> float:
    return max(0.0, min(e, b) - max(s, a))


def innermost(cands: list, s: float, e: float):
    """Of spans overlapping [s, e), the one that overlaps it most among
    those that hold no other of them."""
    leaves = [c for c in cands
              if not any(o is not c and c[2] <= o[2] and o[3] <= c[3]
                         and (o[3] - o[2]) < (c[3] - c[2]) for o in cands)]
    return max(leaves, key=lambda c: _overlap(s, e, c[2], c[3]))


def name_gap(s: float, e: float, mapped: list, thread: Optional[str],
             marks: Dict[str, np.ndarray]) -> str:
    hit = [m for m in mapped if m[2] < e and m[3] > s]
    stalls = [m for m in hit if m[0] in STALLS]
    if stalls:
        return max(stalls, key=lambda m: _overlap(s, e, m[2], m[3]))[0]
    ex = [m for m in hit if m[1] == thread and m[0] != "clock"]
    if ex:
        return innermost(ex, s, e)[0]
    best, name = 0.0, "other host"
    for m, iv in marks.items():
        ov = trace_mod._overlap(iv, s, e)
        if ov > best:
            best, name = ov, m
    return name


def idle_intervals(busy: np.ndarray, t0: float, t1: float) -> np.ndarray:
    """The parts of [t0, t1) that no busy interval covers."""
    out, cur = [], t0
    for s, e in trace_mod.union(busy):
        if e <= cur:
            continue
        if s >= t1:
            break
        if s > cur:
            out.append([cur, s])
        cur = max(cur, e)
    if cur < t1:
        out.append([cur, t1])
    return np.asarray(out, np.float64).reshape(-1, 2)


def name_gaps(busy: np.ndarray, mapped: list, thread: Optional[str],
              marks: Dict[str, np.ndarray], top: int = 10,
              min_ns: float = 0.0) -> List[list]:
    """The ``top`` longest gaps between busy intervals, and every other
    one of at least ``min_ns``, each ``[name, seconds, start ns]``."""
    if len(busy) < 2:
        return []
    starts, ends = busy[1:, 0], busy[:-1, 1]
    length = starts - ends
    order = list(np.argsort(-length)[:top])
    order += [i for i in np.nonzero(length >= min_ns)[0] if i not in order]
    return [[name_gap(ends[i], starts[i], mapped, thread, marks),
             float(length[i]) / 1e9, float(ends[i])] for i in order]


def unattributed_share(busy: np.ndarray, mapped: list,
                       thread: Optional[str], t0: float,
                       t1: float) -> float:
    idle = idle_intervals(busy, t0, t1)
    ex = np.asarray([[m[2], m[3]] for m in mapped if m[1] == thread],
                    np.float64).reshape(-1, 2)
    return trace_mod.minus(idle, ex) / (t1 - t0)


# ----------------------------------------------------- clock check

def _modules(tr: dict, kernels: dict, kernel: str) -> np.ndarray:
    planes = trace_mod.device_planes(tr)
    if not planes:
        return np.zeros((0, 2))
    evs = [e for e in trace_mod.line_events(planes[0], trace_mod.MODULES_LINE)
           if trace_mod.kernel_of(e[0], kernels) == kernel]
    return trace_mod.intervals(evs)


def clock_check(tr: dict, kernels: dict, mapped: list,
                thread: Optional[str]) -> dict:
    """How far the mapped spans contradict causality, in ms (positive is
    a contradiction):

    - ``fire_end_after_fetch_ms``: each fire module ends before the
      barrier_fetch span that waited for it ends;
    - ``update_start_before_dispatch_ms``: each update module starts after
      its dispatch span starts.

    A fire module is paired with the nearest fire span; between two fires,
    the update modules on device 0 and the dispatch spans on the host are
    paired in order (the device runs them in dispatch order), in each
    stretch whose counts agree."""
    ex = sorted((m for m in mapped if m[1] == thread), key=lambda m: m[2])
    fires = [m for m in ex if m[0] == "fire"]
    fetches = [m for m in ex if m[0] == "barrier_fetch"]
    disp = np.asarray([m[2] for m in ex if m[0] == "dispatch"], np.float64)
    fmods = _modules(tr, kernels, "fire")
    umods = _modules(tr, kernels, "update")
    out = {"fires": 0, "fire_end_after_fetch_ms": None, "updates": 0,
           "update_start_before_dispatch_ms": None, "stretches_skipped": 0}
    if not len(fmods) or not fires:
        return out
    f_starts = np.asarray([f[2] for f in fires])
    b_starts = np.asarray([b[2] for b in fetches])
    worst_f, paired = -np.inf, []
    for s, e in fmods:
        i = int(np.argmin(np.abs(f_starts - s)))
        j = int(np.searchsorted(b_starts, fires[i][3] - MATCH_NS))
        if j < len(fetches):
            worst_f = max(worst_f, (e - fetches[j][3]) / 1e6)
            paired.append((s, fires[i][2]))
    out["fires"] = len(paired)
    out["fire_end_after_fetch_ms"] = float(worst_f) if paired else None
    worst_u, n_u = -np.inf, 0
    bounds = [(-np.inf, -np.inf)] + paired + [(np.inf, np.inf)]
    for (m0, h0), (m1, h1) in zip(bounds[:-1], bounds[1:]):
        mods = umods[(umods[:, 0] > m0) & (umods[:, 0] < m1)]
        ds = disp[(disp > h0) & (disp < h1)]
        if not len(mods):
            continue
        if np.isinf(m0):
            ds = ds[-len(mods):]          # the trace opened mid-stretch
        elif np.isinf(m1):
            ds = ds[:len(mods)]           # and closed mid-stretch
        if len(ds) != len(mods):
            out["stretches_skipped"] += 1
            continue
        worst_u = max(worst_u, float(np.max(ds - mods[:, 0])) / 1e6)
        n_u += len(mods)
    out["updates"] = n_u
    out["update_start_before_dispatch_ms"] = float(worst_u) if n_u else None
    return out


# ----------------------------------------------------- one run

def analyse(tr: dict, spans, t_open: float, t_close: float,
            kernels: dict, min_gap_s: float = 0.02) -> Optional[dict]:
    """Everything above for one traced run; None when the program left no
    anchor in the trace."""
    pairs = anchor_pairs(spans, clock_annotations(tr))
    off = offset_ns(pairs, (t_open + t_close) / 2)
    if off is None:
        return None
    drift = [abs(a - s * 1e9 - off) / 1e6 for s, a in pairs]
    thread = executor_thread(spans)
    mapped = on_trace_clock(spans, off)
    planes = trace_mod.device_planes(tr)
    busy = trace_mod.union(trace_mod.intervals(
        trace_mod.line_events(planes[0], trace_mod.OPS_LINE))) \
        if planes else np.zeros((0, 2))
    w0, w1 = t_open * 1e9 + off, t_close * 1e9 + off
    busy_w = busy[(busy[:, 1] > w0) & (busy[:, 0] < w1)]
    idle = trace_mod.length(idle_intervals(busy, w0, w1)) / (w1 - w0)
    gaps = name_gaps(busy_w, mapped, thread, trace_mod.host_marks(tr),
                     min_ns=min_gap_s * 1e9)
    return {
        "anchors": len(pairs),
        "anchor_spread_ms": max(drift),
        "idle_share": idle,
        "unattributed_share": unattributed_share(busy, mapped, thread,
                                                 w0, w1),
        "idle_gaps": [g[:2] + [(g[2] - w0) / 1e9] for g in gaps],
        "clock": clock_check(tr, kernels, mapped, thread),
        "span_ms": span_summary(spans, t_open, t_close),
    }


def span_summary(spans, t0: float, t1: float) -> dict:
    """Per span name, of the spans that start in [t0, t1): [count, mean
    ms, max ms]."""
    by: Dict[str, list] = {}
    for s in spans:
        if t0 <= s[2] < t1:
            by.setdefault(s[0], []).append(1e3 * s[3])
    return {k: [len(v), float(np.mean(v)), max(v)]
            for k, v in sorted(by.items())}


def run_kept(workload: str, seed: int, seconds: float,
             root: str = ROOT) -> tuple:
    """``run.run_cell`` traced, as ``run.py --trace 1`` runs it, keeping
    what it drops: (its result, the run's record, the loaded trace, the
    kernel map)."""
    from benchmark import job as job_mod
    from benchmark import run as run_mod

    kept = {}
    job_run, reduce = job_mod.run, trace_mod.reduce

    def keep_run(*a, **kw):
        kept["rec"] = job_run(*a, **kw)
        return kept["rec"]

    def keep_trace(tr, kernels, *a, **kw):
        kept["tr"], kept["kernels"] = tr, kernels
        return reduce(tr, kernels, *a, **kw)

    job_mod.run, trace_mod.reduce = keep_run, keep_trace
    try:
        out = run_mod.run_cell(workload, seed, seconds, True, root)
    finally:
        job_mod.run, trace_mod.reduce = job_run, reduce
    return out, kept["rec"], kept["tr"], kept["kernels"]


def main(argv=None) -> int:
    import argparse
    import json

    from benchmark import run as run_mod

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = run_mod.CACHE_DIR
    import jax

    jax.config.update("jax_compilation_cache_dir", run_mod.CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        out, rec, tr, kernels = run_kept(args.workload, args.seed,
                                         args.seconds)
    except run_mod.NoChip as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    win = rec["window"]
    print(json.dumps({"phases": analyse(tr, rec["spans"], win.t_open,
                                        win.t_close, kernels)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
