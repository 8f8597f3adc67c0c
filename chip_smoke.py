"""Chip smoke run: the north-star keyed-window job on the TPU, through the
public API, checked exactly against a plain numpy reference.

The job is the one ``bench.py`` measures: a columnar ``GeneratorSource`` ->
``key_by`` -> 5 s event-time tumbling window -> ``sum`` -> a collecting
sink, at a real deployment's state and rate: 1,048,576 keys with state
capacity equal to the key space (so the direct layout), batches of 262,144
events at 2,000 events/ms, over three full windows (30M events). Keys are a
seeded hash of the event index, so every batch carries duplicate keys.

    python chip_smoke.py             # one chip: default, resident, while
    python chip_smoke.py --chips 4   # four chips: sharded drain, all_to_all

Each phase runs the job once with no restart strategy and checks every
(key, window) sum exactly against the reference (all values are ones, so
float32 sums are exact), with no late or capacity-dropped events. The
lines before the last are smoke output, not benchmark numbers. The last
line is ``{"ok": true, "device": {...}}``, printed only when every phase
passed on a TPU; anywhere else the script exits non-zero without it.
"""

import argparse
import json
import sys
import time

import numpy as np

N_KEYS = 1 << 20
WINDOW_MS = 5_000
EVENTS_PER_MS = 2_000
BATCH = 262_144
N_EVENTS = 3 * WINDOW_MS * EVENTS_PER_MS

# phase -> configuration over the defaults. On one chip the default phase
# takes the TPU's `auto` choices (pre-combine, packed planes, single-step
# dispatch); the others force the resident scan and the while drain. On
# four chips: the data-parallel sharded drain, and the all_to_all exchange
# route with data parallelism off.
PHASES = {
    1: {
        "default": {},
        "resident": {"pipeline.steps-per-dispatch": 8,
                     "pipeline.resident-loop": "on"},
        "while": {"pipeline.steps-per-dispatch": 8,
                  "pipeline.resident-loop": "while"},
    },
    4: {
        "sharded": {"pipeline.steps-per-dispatch": 8,
                    "pipeline.resident-loop": "on",
                    "pipeline.data-parallel": "on"},
        "exchange": {"pipeline.data-parallel": "off",
                     "exchange.mode": "all_to_all"},
    },
}


# phase -> counters that must be non-zero: proof the phase took its path
PATH_COUNTERS = {
    "resident": ("resident_drains",),
    "while": ("resident_drains",),
    "sharded": ("resident_drains", "steps_sharded"),
    "exchange": ("steps_exchanged",),
}


def gen_keys(idx: np.ndarray, seed: int, n_keys: int) -> np.ndarray:
    """splitmix64 of (index + seed) modulo the key space: a pure function
    of the event index, so replays and the reference see the same keys."""
    z = idx.astype(np.uint64) + np.uint64(seed * 0x9E3779B97F4A7C15 % 2**64)
    z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    z = z ^ (z >> np.uint64(31))
    return (z % np.uint64(n_keys)).astype(np.int64)


def reference(seed: int, n_keys: int, n_events: int,
              chunk: int = 1 << 22) -> np.ndarray:
    """Per-(window, key) event counts, [n_windows * n_keys], in plain
    numpy: the sum of all-ones values is the count."""
    n_windows = -(-n_events // (WINDOW_MS * EVENTS_PER_MS))
    out = np.zeros(n_windows * n_keys, np.int64)
    for off in range(0, n_events, chunk):
        idx = np.arange(off, min(off + chunk, n_events), dtype=np.int64)
        w = idx // EVENTS_PER_MS // WINDOW_MS
        out += np.bincount(w * n_keys + gen_keys(idx, seed, n_keys),
                           minlength=len(out))
    return out


def run_phase(name: str, overrides: dict, seed: int, parallelism: int,
              n_keys: int = N_KEYS, batch: int = BATCH,
              n_events: int = N_EVENTS) -> dict:
    """Run the job once under ``overrides``; return its output columns and
    what the smoke lines print."""
    from flink_tpu import StreamExecutionEnvironment
    from flink_tpu.core.config import Configuration
    from flink_tpu.core.time import TimeCharacteristic
    from flink_tpu.metrics.tracing import CompileEvents
    from flink_tpu.runtime.sinks import Sink
    from flink_tpu.runtime.sources import GeneratorSource

    class ColumnSink(Sink):
        columnar = True

        def __init__(self):
            self.cols = []

        def invoke_columnar(self, cols):
            self.cols.append({k: np.asarray(v) for k, v in cols.items()})

    def gen(offset, n):
        idx = np.arange(offset, offset + n, dtype=np.int64)
        return ({"key": gen_keys(idx, seed, n_keys),
                 "value": np.ones(n, np.float32)},
                idx // EVENTS_PER_MS)

    cfg = Configuration({
        "keys.reverse-map": False,
        "window.fires-per-step": 2,
        "restart-strategy": "none",
        **overrides,
    })
    env = StreamExecutionEnvironment(cfg)
    env.set_parallelism(parallelism)
    env.set_max_parallelism(128)
    env.set_stream_time_characteristic(TimeCharacteristic.EventTime)
    env.set_state_capacity(n_keys)
    env.batch_size = batch
    sink = ColumnSink()
    (
        env.add_source(GeneratorSource(gen, total=n_events))
        .key_by(lambda c: c["key"])
        .time_window(WINDOW_MS)
        .sum(lambda c: c["value"])
        .add_sink(sink)
    )
    CompileEvents.install()
    mark = CompileEvents.mark()
    t0 = time.perf_counter()
    job = env.execute(f"chip-smoke-{name}")
    wall_s = time.perf_counter() - t0
    n_compiles, compile_s = CompileEvents.since(mark)
    m = job.metrics
    cols = {k: np.concatenate([c[k] for c in sink.cols])
            if sink.cols else np.zeros(0)
            for k in ("key_id", "window_end_ms", "value")}
    return {
        "cols": cols,
        "state_devices": len(job.state.acc.sharding.device_set),
        "line": {
            "phase": name, "wall_s": wall_s, "compile_s": compile_s,
            "compiles": n_compiles, "steps": m.steps,
            "resident_drains": m.resident_drains,
            "fused_dispatches": m.fused_dispatches,
            "steps_sharded": m.steps_sharded,
            "steps_exchanged": m.steps_exchanged,
            "layout": m.state_layout, "packed_planes": m.state_packed_planes,
            "exchange_mode": m.exchange_mode, "fires": m.fires,
            "dropped_late": m.dropped_late,
            "dropped_capacity": m.dropped_capacity, "restarts": m.restarts,
        },
    }


def check(res: dict, ref: np.ndarray, chips: int,
          n_keys: int = N_KEYS) -> list:
    """Every way the phase's output differs from the reference, or its
    run from the path and placement it was meant to take."""
    line, cols = res["line"], res["cols"]
    errors = [f"{k}={line[k]}" for k in
              ("dropped_late", "dropped_capacity", "restarts") if line[k]]
    errors += [f"{k}=0: the phase did not take its path"
               for k in PATH_COUNTERS.get(line["phase"], ()) if not line[k]]
    if res["state_devices"] != chips:
        errors.append(f"state spans {res['state_devices']} devices, "
                      f"not {chips}")
    key = cols["key_id"].astype(np.int64)
    win = cols["window_end_ms"].astype(np.int64) // WINDOW_MS - 1
    flat = win * n_keys + key
    if (np.any(key < 0) or np.any(key >= n_keys) or np.any(win < 0)
            or np.any(flat >= len(ref))):
        return errors + ["a fired (key, window) lies outside the input"]
    got = np.zeros(len(ref), np.int64)
    np.add.at(got, flat, 1)
    if np.any(got > 1):
        errors.append(f"{int(np.sum(got > 1))} (key, window) pairs fired "
                      "more than once")
    if np.any((got == 0) & (ref > 0)):
        errors.append(f"{int(np.sum((got == 0) & (ref > 0)))} "
                      "(key, window) pairs never fired")
    sums = np.zeros(len(ref), np.float64)
    np.add.at(sums, flat, cols["value"].astype(np.float64))
    bad = int(np.sum(sums != ref))
    if bad:
        errors.append(f"{bad} (key, window) sums differ from the reference")
    return errors


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from flink_tpu.core.jaxenv import (
        device_stamp,
        require_accelerator,
        use_compile_cache,
    )

    use_compile_cache()
    devices = require_accelerator()
    if len(devices) < args.chips:
        raise RuntimeError(f"--chips {args.chips}: JAX sees "
                           f"{len(devices)} device(s)")
    t0 = time.perf_counter()
    ref = reference(args.seed, N_KEYS, N_EVENTS)
    print(json.dumps({"smoke": "reference", "events": N_EVENTS,
                      "keys": N_KEYS, "batch": BATCH,
                      "pairs": int(np.count_nonzero(ref)),
                      "s": time.perf_counter() - t0}), flush=True)
    failed = []
    for name, overrides in PHASES[args.chips].items():
        res = run_phase(name, overrides, args.seed, args.chips)
        errors = check(res, ref, args.chips)
        line = res["line"]
        line["state_devices"] = res["state_devices"]
        line["peak_bytes_in_use"] = [
            d.memory_stats()["peak_bytes_in_use"] for d in devices
        ]
        line["errors"] = errors
        print(json.dumps({"smoke": line}), flush=True)
        if errors:
            failed.append(name)
        del res
    if failed:
        print(f"chip smoke failed in: {failed}", file=sys.stderr)
        return 1
    device = device_stamp()
    if device["platform"] != "tpu":
        print(f"ran on {device}, not a TPU", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
