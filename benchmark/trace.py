"""Reduction of a profiler trace to the numbers the per-layer metrics and
the ``breakdown`` read.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` wrote into plain
data (planes -> lines -> ``[name, start_ns, dur_ns]``), and everything
else works on that data, so tests check it on a small recorded excerpt.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# collectives whose time counts as exchange
ALL_TO_ALL = re.compile(r"all-to-all|all_to_all|alltoall", re.I)
HOST_MARKS = ("source poll", "sink")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def short_name(name: str) -> str:
    """An XLA op's name without its HLO text: ``%fusion.28 = s32[..]
    fusion(..)`` -> ``fusion.28``."""
    return name.split(" = ", 1)[0].lstrip("%")


def load(path: str) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    return {"planes": [
        {"name": pl.name, "lines": [
            {"name": ln.name,
             "events": [[short_name(ev.name), float(ev.start_ns),
                         float(ev.duration_ns)] for ev in ln.events]}
            for ln in pl.lines]}
        for pl in pd.planes]}


def device_planes(tr: dict) -> List[dict]:
    out = [pl for pl in tr["planes"] if DEVICE_PLANE.match(pl["name"])]
    return sorted(out, key=lambda pl: int(DEVICE_PLANE.match(pl["name"])[1]))


def line_events(plane: dict, line: str) -> List[list]:
    return [ev for ln in plane["lines"] if ln["name"] == line
            for ev in ln["events"]]


def intervals(events: List[list]) -> np.ndarray:
    """``[n, 2]`` start/end in ns."""
    if not events:
        return np.zeros((0, 2))
    a = np.asarray([[e[1], e[1] + e[2]] for e in events], np.float64)
    return a[np.argsort(a[:, 0], kind="stable")]


def union(iv: np.ndarray) -> np.ndarray:
    """Merge overlapping intervals; sorted, disjoint ``[m, 2]``."""
    if len(iv) == 0:
        return iv
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out)


def length(iv: np.ndarray) -> float:
    return float(np.sum(iv[:, 1] - iv[:, 0])) if len(iv) else 0.0


def minus(a: np.ndarray, b: np.ndarray) -> float:
    """Length of union(a) not covered by union(b), in ns."""
    a, b = union(a), union(b)
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def kernel_of(module: str, kernels: Dict[str, List[str]]) -> Optional[str]:
    for name, patterns in kernels.items():
        if any(re.search(p, module) for p in patterns):
            return name
    return None


def reduce(tr: dict, kernels: Dict[str, List[str]], top: int = 10) -> dict:
    """Per device: busy ns (union of op intervals), per-kernel module ns,
    module counts, all-to-all ns not overlapped by other ops; over all:
    the device ops that took most time and the longest idle gaps on the
    first device, each named by what the host was doing in it."""
    devs = []
    op_time: Dict[str, float] = {}
    for pl in device_planes(tr):
        ops = line_events(pl, OPS_LINE)
        busy = union(intervals(ops))
        a2a = [e for e in ops if ALL_TO_ALL.search(e[0])]
        rest = [e for e in ops if not ALL_TO_ALL.search(e[0])]
        mod_ns: Dict[str, float] = {}
        mod_n: Dict[str, int] = {}
        mods = sorted(line_events(pl, MODULES_LINE), key=lambda e: e[1])
        for name, _, dur in mods:
            k = kernel_of(name, kernels) or "other"
            mod_ns[k] = mod_ns.get(k, 0.0) + dur
            mod_n[k] = mod_n.get(k, 0) + 1
        # each op is named within the kernel whose module it runs in
        starts = np.asarray([e[1] for e in mods])
        for name, t, dur in ops:
            i = int(np.searchsorted(starts, t, side="right")) - 1
            if i >= 0 and t < mods[i][1] + mods[i][2]:
                name = f"{kernel_of(mods[i][0], kernels) or 'other'}/{name}"
            op_time[name] = op_time.get(name, 0.0) + dur
        devs.append({
            "name": pl["name"], "busy_ns": length(busy),
            "kernel_ns": mod_ns, "kernel_calls": mod_n,
            "a2a_ns": length(union(intervals(a2a))),
            "a2a_exposed_ns": minus(intervals(a2a), intervals(rest)),
            "_busy": busy,
        })
    n_dev = max(1, len(devs))
    gaps = idle_gaps(devs[0]["_busy"], host_marks(tr), top) if devs else []
    for d in devs:
        del d["_busy"]
    return {
        "devices": devs,
        "device_ops": [[n, t / n_dev / 1e9] for n, t in
                       sorted(op_time.items(), key=lambda x: -x[1])[:top]],
        "idle_gaps": gaps,
    }


def host_marks(tr: dict) -> Dict[str, np.ndarray]:
    """The harness's own host annotations, by name, as intervals."""
    out: Dict[str, list] = {m: [] for m in HOST_MARKS}
    for pl in tr["planes"]:
        if not pl["name"].startswith("/host:"):
            continue
        for ln in pl["lines"]:
            for ev in ln["events"]:
                if ev[0] in out:
                    out[ev[0]].append(ev)
    return {m: union(intervals(evs)) for m, evs in out.items()}


def _overlap(iv: np.ndarray, s: float, e: float) -> float:
    if not len(iv):
        return 0.0
    return float(np.sum(np.clip(np.minimum(iv[:, 1], e)
                                - np.maximum(iv[:, 0], s), 0, None)))


def idle_gaps(busy: np.ndarray, marks: Dict[str, np.ndarray],
              top: int) -> List[list]:
    """The ``top`` longest gaps between busy intervals, each named by the
    host mark that overlaps it most, else "other host"."""
    if len(busy) < 2:
        return []
    starts, ends = busy[1:, 0], busy[:-1, 1]
    order = np.argsort(-(starts - ends))[:top]
    out = []
    for i in order:
        s, e = ends[i], starts[i]
        best, name = 0.0, "other host"
        for m, iv in marks.items():
            ov = _overlap(iv, s, e)
            if ov > best:
                best, name = ov, m
        out.append([name, float(e - s) / 1e9])
    return out
