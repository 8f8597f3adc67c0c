"""Benchmark entry, run from the root of a checkout:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell, its configuration, its traffic mix and every part they
name through ``BENCHMARK.json`` (``registry.py``), runs the job once on
the chip (``benchmark/job.py``),
and prints one JSON line last: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer ones), ``device``, with ``--trace 1`` ``breakdown``, and last
``checks``: each number compared beside its limit. With no TPU, or fewer
chips than the cell asks for, it exits 2 and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import numpy as np  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import registry  # noqa: E402
from benchmark.roofline import peaks  # noqa: E402
from benchmark.traffic import Traffic  # noqa: E402
# fixed and inside the checkout, so only a cell's first run compiles
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
KERNELS_FILE = os.path.join(ROOT, "benchmark", "kernels.json")


class NoChip(RuntimeError):
    pass


class Cell:
    """A cell and all that it is made of, found by name: its entry in
    ``BENCHMARK.json``, its configuration file, its traffic file with the
    parts that file names, and its job kind's reference."""

    def __init__(self, workload: str, root: str = ROOT):
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.bench = json.load(f)
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if workload not in cells:
            raise SystemExit(f"unknown workload {workload!r}; known: "
                             f"{sorted(cells)}")
        self.name, self.entry, self.root = workload, cells[workload], root
        conf = {c["name"]: c
                for c in self.bench["configs"]}[self.entry["config"]]
        with open(os.path.join(root, conf["file"])) as f:
            self.cfg = json.load(f)
        self.job = self.cfg["job"]
        with open(os.path.join(root, "benchmark", "traffic",
                               self.entry["traffic"] + ".json")) as f:
            self.traffic_params = json.load(f)
        self.ref = registry.load("references", registry.job_kind(self.job),
                                 root)

    def traffic(self, **override) -> Traffic:
        return Traffic(dict(self.traffic_params, **override), self.job,
                       self.root)


def cell_metrics(bench: dict, cell: str, trace: bool) -> list:
    """The cell's end-to-end metrics, or with ``trace`` its per-layer
    ones, as BENCHMARK.json entries."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


def require_chips(chips: int) -> list:
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise NoChip("no accelerator: JAX initialized on the CPU")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX sees "
                     f"{len(devices)}")
    return devices


def checks_of(rec: dict, ref, cell: Cell) -> tuple:
    """Each number compared, with its limit; and the results that the
    comparison of rows found wrong."""
    calls = rec["sink_calls"]
    cols = {k: np.concatenate([c[i] for c in calls]) if calls
            else np.zeros(0, np.int64)
            for i, k in ((1, "key_id"), (2, "window_end_ms"), (3, "value"))}
    out = cell.ref.compare(cols, ref, cell.job)
    failed = int(sum(out.values()))
    m = rec["metrics"]
    out.update({"dropped_late": int(m.dropped_late),
                "dropped_capacity": int(m.dropped_capacity),
                "restarts": int(m.restarts),
                "state_devices_missing":
                    cell.entry["chips"] - rec["state_devices"]})
    return {k: {"value": v, "limit": 0} for k, v in out.items()}, failed


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             root: str = ROOT) -> dict:
    """One run of a cell; the result object as printed."""
    from benchmark import job as job_mod
    from benchmark import trace as trace_mod
    from benchmark.readings import Ctx

    cell = Cell(workload, root)
    chips = cell.entry["chips"]
    devices = require_chips(chips)
    kind = devices[0].device_kind
    peak = peaks(kind)
    traffic = cell.traffic()
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        rec = job_mod.run(cell.cfg, traffic, seed, seconds, trace_dir, root)
        setup_s = rec["window"].t_open - T_START
        used = devices[:chips]
        mem = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used]
        reduced = None
        if trace:
            with open(KERNELS_FILE) as f:
                kernels = json.load(f)["kernels"]
            reduced = trace_mod.reduce(
                trace_mod.load(trace_mod.find_xplane(trace_dir)), kernels)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = Ctx(cell.cfg, rec, setup_s, reduced, peak)
    metrics = {}
    for m in cell_metrics(cell.bench, workload, trace):
        kind_dir = "per_layer" if trace else "end_to_end"
        v = registry.load_reader(kind_dir, m["name"], root)(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    win = rec["window"]
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": max(mem)}
    n_comp, comp_s = win.compiles_in_window
    # per boundary in the window, open loop: the result latency and the
    # program's fire latency, to tell a slow boundary's cause
    lw = ctx.result_latencies()
    print(json.dumps({"window": {
        "result_latency_ms": [] if lw is None else (1e3 * lw[0]).tolist(),
        "fire_latency_ms": [float(s[1]) for s in rec["fire_samples"]],
        "seconds": ctx.window_s, "events": ctx.events_in_window(),
        "results": ctx.fired_in_window(), "compiles": n_comp,
        "compile_s": comp_s, "events_total": rec["n_events"],
        "steps": rec["metrics"].steps, "layout": rec["metrics"].state_layout,
        "exchange_mode": rec["metrics"].exchange_mode,
        "steps_exchanged": rec["metrics"].steps_exchanged}}), flush=True)
    out = {"correct": None, "attempted": 0, "failed": 0,
           "metrics": metrics, "device": device}
    if trace:
        busy = [d["busy_ns"] / 1e9 for d in reduced["devices"]]
        device["busy_s"] = sum(busy) / len(busy) if busy else 0.0
        device["window_s"] = ctx.window_s
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    # the check runs once the window has closed and the peak is read
    ref = cell.ref.reference(seed, cell.job, rec["n_events"], traffic)
    checks, out["failed"] = checks_of(rec, ref, cell)
    out["correct"] = all(c["value"] <= c["limit"] for c in checks.values())
    out["attempted"] = cell.ref.attempted(ref)
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except NoChip as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
