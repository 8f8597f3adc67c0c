"""BASELINE.md configs #1/#3/#4/#5: subject vs scalar-reference baseline.

Five measured rows (the north-star config #2 lives in bench.py):
  socket_wc    SocketWindowWordCount: socket text -> split -> keyBy word ->
               5s tumbling count (ref flink-examples SocketWindowWordCount
               .java:76-79)
  count_min    sliding-window Count-Min sketch aggregation (8s/4s)
  sessions     event-time session windows, mergeable sum, 500ms gap
  cep          CEP pattern a -> followed_by b over a keyed stream
               (ref flink-cep NFA.java:132)
  cep_event_time  the same pattern on an out-of-order EVENT-TIME stream
               (round 5: host reorder buffer fronting the device NFA,
               baseline = per-key ts-sorted host NFA)

Each baseline re-implements the reference's scalar hot path in-process
(per-record dict/NFA work — the HeapKeyedStateBackend / NFA analog, see
BASELINE.md). Prints ONE JSON line per config:
  {"config": ..., "subject_eps": ..., "baseline_eps": ..., "ratio": ...}

Usage: python bench_configs.py [--cpu] [--only NAME] [--events N]
"""

import argparse
import json
import socket
import sys
import threading
import time

import numpy as np

WORDS = [f"w{i:04d}" for i in range(500)]


# ------------------------------------------------------------ socket WC
def run_socket_wc(total_events: int, cpu: bool):
    """Lines of "<ts_ms> <word> <word> ..." over a real TCP socket."""
    words_per_line = 8
    n_lines = total_events // words_per_line
    rng = np.random.default_rng(0)
    widx = rng.integers(0, len(WORDS), total_events)
    lines = []
    for i in range(n_lines):
        ws = widx[i * words_per_line:(i + 1) * words_per_line]
        lines.append(
            (f"{i * 2} " + " ".join(WORDS[j] for j in ws) + "\n").encode()
        )
    payload = b"".join(lines)

    # baseline: scalar split -> dict[(word, window)] += 1 with drains
    t0 = time.perf_counter()
    state, fired, wm_pane = {}, 0, -1
    for i in range(n_lines):
        parts = lines[i].decode().split()
        ts = int(parts[0])
        pane = ts // 5000
        for w in parts[1:]:
            k = (w, pane)
            state[k] = state.get(k, 0) + 1
        if pane - 1 > wm_pane:
            wm_pane = pane - 1
            for k in [k for k in state if k[1] <= wm_pane]:
                fired += 1
                state.pop(k)
    fired += len(state)
    base_dt = time.perf_counter() - t0
    baseline_eps = total_events / base_dt

    # subject: real socket ingestion through the framework's columnar
    # word source — the native one-pass tokenizer
    # (native/src/textparse.cpp) plays the reference flatMap's
    # split/parse role (SocketWindowWordCount.java:76-79), keys are
    # 64-bit token identities, and the window count runs on device;
    # word strings materialize lazily via source.word_of()
    from flink_tpu import StreamExecutionEnvironment
    from flink_tpu.core.config import Configuration
    from flink_tpu.core.time import TimeCharacteristic
    from flink_tpu.runtime.sinks import CountingSink
    from flink_tpu.runtime.sources import SocketWordsSource
    from flink_tpu.runtime.watermarks import WatermarkStrategy

    srv = socket.create_server(("127.0.0.1", 0))
    port = srv.getsockname()[1]

    def feed():
        conn, _ = srv.accept()
        with conn:
            conn.sendall(payload)

    t = threading.Thread(target=feed, daemon=True)
    t.start()

    env = StreamExecutionEnvironment(Configuration())
    env.set_parallelism(1)
    env.set_max_parallelism(32)
    env.set_stream_time_characteristic(TimeCharacteristic.EventTime)
    env.set_state_capacity(4096)
    env.batch_size = 32768
    sink = CountingSink()
    t0 = time.perf_counter()
    (
        env.add_source(SocketWordsSource("127.0.0.1", port))
        .assign_timestamps_and_watermarks(
            lambda c: c["ts"],
            WatermarkStrategy.for_monotonous_timestamps(),
        )
        .key_by(lambda c: c["key"])
        .time_window(5000)
        .count()
        .add_sink(sink)
    )
    env.execute("socket-wc")
    dt = time.perf_counter() - t0
    srv.close()
    assert sink.count > 0
    return total_events / dt, baseline_eps


# ------------------------------------------------------------ count-min
def run_count_min(total_events: int, cpu: bool):
    depth, width = 4, 1024
    rng = np.random.default_rng(1)
    items = rng.zipf(1.3, total_events).astype(np.int64) % 100_000
    ts = (np.arange(total_events, dtype=np.int64) // 500)

    # baseline: scalar CM update (depth hashes + row increments per item)
    from flink_tpu.ops.hashing import splitmix64

    seeds = splitmix64(np.arange(1, depth + 1, dtype=np.uint64))
    t0 = time.perf_counter()
    sketch = np.zeros((depth, width), np.int64)
    wm_pane = -1
    n_done = 0
    CH = 65536
    for off in range(0, total_events, CH):
        it = items[off:off + CH].tolist()
        tss = ts[off:off + CH]
        seed_i = [int(s) for s in seeds]
        for i in range(len(it)):
            x = it[i]
            for d in range(depth):
                h = (((x * seed_i[d]) & 0xFFFFFFFFFFFFFFFF)
                     >> (64 - 10)) % width
                sketch[d, h] += 1
            n_done += 1
        pane = int(tss[-1]) // 4000 - 1
        if pane > wm_pane:
            wm_pane = pane
            sketch[:] = 0          # pane rotation stand-in
    base_dt = time.perf_counter() - t0
    baseline_eps = total_events / base_dt

    from flink_tpu import StreamExecutionEnvironment
    from flink_tpu.core.config import Configuration
    from flink_tpu.core.time import TimeCharacteristic
    from flink_tpu.runtime.sinks import CountingSink
    from flink_tpu.runtime.sources import GeneratorSource

    env = StreamExecutionEnvironment(Configuration())
    env.set_parallelism(1)
    env.set_max_parallelism(32)
    env.set_stream_time_characteristic(TimeCharacteristic.EventTime)
    env.set_state_capacity(64)
    env.batch_size = 131_072
    sink = CountingSink()

    def gen(offset, n):
        s = slice(offset, offset + n)
        m = len(items[s])
        return {"key": np.zeros(m, np.int32), "item": items[s]}, ts[s]

    t0 = time.perf_counter()
    (
        env.add_source(GeneratorSource(gen, total=total_events))
        .key_by(lambda cols: cols["key"])
        .time_window(8000, 4000)
        .count_min(lambda cols: cols["item"], depth=depth, width=width,
                   query=[1, 2, 3])
        .add_sink(sink)
    )
    env.execute("count-min")
    dt = time.perf_counter() - t0
    assert sink.count > 0
    return total_events / dt, baseline_eps


# ------------------------------------------------------------- sessions
def run_sessions(total_events: int, cpu: bool):
    n_keys = 50_000
    gap = 500
    rng = np.random.default_rng(2)
    keys = rng.integers(0, n_keys, total_events).astype(np.int64)
    ts = (np.arange(total_events, dtype=np.int64) // 200)
    vals = np.ones(total_events, np.float32)

    # baseline: scalar session tracking (key -> [start, last, acc]),
    # close-on-gap at watermark advances (per-key timer analog)
    t0 = time.perf_counter()
    live = {}
    closed = 0
    CH = 65536
    kl, tl = keys.tolist(), ts.tolist()
    last_scan_wm = -1
    for off in range(0, total_events, CH):
        hi_i = min(off + CH, total_events)
        for i in range(off, hi_i):
            k = kl[i]
            t = tl[i]
            s = live.get(k)
            if s is None:
                live[k] = [t, t, 1.0]
            elif t - s[1] > gap:
                closed += 1
                live[k] = [t, t, 1.0]
            else:
                s[1] = t
                s[2] += 1.0
        wm = tl[hi_i - 1]
        if wm - last_scan_wm >= gap:       # timer sweep
            last_scan_wm = wm
            for k in [k for k, s in live.items() if wm - s[1] > gap]:
                closed += 1
                live.pop(k)
    closed += len(live)
    base_dt = time.perf_counter() - t0
    baseline_eps = total_events / base_dt

    from flink_tpu import StreamExecutionEnvironment
    from flink_tpu.core.config import Configuration
    from flink_tpu.core.time import TimeCharacteristic
    from flink_tpu.datastream.window.assigners import EventTimeSessionWindows
    from flink_tpu.runtime.sinks import CountingSink
    from flink_tpu.runtime.sources import GeneratorSource

    env = StreamExecutionEnvironment(Configuration())
    env.set_parallelism(1)
    env.set_max_parallelism(32)
    env.set_stream_time_characteristic(TimeCharacteristic.EventTime)
    env.set_state_capacity(1 << 18)   # load ~0.2 at 50k live sessions
    env.batch_size = 131_072
    sink = CountingSink()

    def gen(offset, n):
        s = slice(offset, offset + n)
        return {"key": keys[s], "value": vals[s]}, ts[s]

    t0 = time.perf_counter()
    (
        env.add_source(GeneratorSource(gen, total=total_events))
        .key_by(lambda c: c["key"])
        .window(EventTimeSessionWindows.with_gap(gap))
        .sum(lambda c: c["value"])
        .add_sink(sink)
    )
    env.execute("sessions-bench")
    dt = time.perf_counter() - t0
    assert sink.count > 0
    return total_events / dt, baseline_eps


# ------------------------------------------------------------------ CEP
def run_cep(total_events: int, cpu: bool):
    from flink_tpu.cep import CEP

    events = _cep_events(total_events, seed=3)
    baseline_eps, n_matches = _cep_host_baseline(events, total_events)

    from flink_tpu import StreamExecutionEnvironment
    from flink_tpu.core.config import Configuration
    from flink_tpu.runtime.sinks import CountingSink

    env = StreamExecutionEnvironment(Configuration())
    env.set_parallelism(1)
    env.batch_size = 16_384
    sink = CountingSink()
    stream = env.from_collection(events).key_by(lambda e: e.key)
    t0 = time.perf_counter()
    CEP.pattern(stream, _cep_pattern()).select(lambda m: 1.0).add_sink(
        sink)
    job = env.execute("cep-bench")
    dt = time.perf_counter() - t0
    assert job.metrics.cep_device_steps > 0, "device CEP path not taken"
    assert sink.count == n_matches, (sink.count, n_matches)
    return total_events / dt, baseline_eps


def _cep_events(total_events, seed, ooo=0):
    """Shared CEP bench stream: names/keys from `seed`; ooo>0 shuffles
    arrival order within +-ooo of timestamp order."""
    rng = np.random.default_rng(seed)
    names = rng.choice(["a", "b", "x", "y"], total_events,
                       p=[0.05, 0.05, 0.45, 0.45])
    keyarr = rng.integers(0, 1000, total_events)

    class Ev:
        __slots__ = ("name", "key", "ts")

        def __init__(self, name, key, ts):
            self.name = name
            self.key = key
            self.ts = ts

    order = (np.argsort(np.arange(total_events)
                        + rng.uniform(0, ooo, total_events))
             if ooo else range(total_events))
    return [Ev(str(names[i]), int(keyarr[i]), int(i)) for i in order]


def _cep_pattern():
    """Scalar per-record predicates — the baseline host NFA's form (the
    reference's SimpleCondition is per-record by construction)."""
    from flink_tpu.cep import Pattern

    return (
        Pattern.begin("a").where(lambda e: e.name == "a")
        .followed_by("b").where(lambda e: e.name == "b")
    )


def _cep_host_baseline(events, total_events, ordered=False):
    """Per-record host NFA (the reference's NFA.process path); with
    `ordered`, per-key ts-sorted feed (the event-time operator's work)."""
    from flink_tpu.cep import NFA

    nfa = NFA(_cep_pattern())
    t0 = time.perf_counter()
    # the ts-sort IS part of the event-time operator's work: time it
    feed = sorted(events, key=lambda e: e.ts) if ordered else events
    partials = {}
    n_matches = 0
    for e in feed:
        p = partials.get(e.key, [])
        p, ms = nfa.process(p, e, e.ts)
        partials[e.key] = p
        n_matches += len(ms)
    return total_events / (time.perf_counter() - t0), n_matches


def run_cep_event_time(total_events: int, cpu: bool):
    """Event-time device CEP (round 5): the host reorder buffer fronting
    the count-NFA kernel, measured against the per-record host NFA fed
    the same timestamp-ordered stream."""
    from flink_tpu.cep import CEP
    from flink_tpu.core.time import TimeCharacteristic

    events = _cep_events(total_events, seed=5, ooo=16)
    baseline_eps, n_matches = _cep_host_baseline(
        events, total_events, ordered=True)

    from flink_tpu import StreamExecutionEnvironment
    from flink_tpu.core.config import Configuration
    from flink_tpu.runtime.sinks import CountingSink
    from flink_tpu.runtime.watermarks import WatermarkStrategy

    env = StreamExecutionEnvironment(Configuration())
    env.set_parallelism(1)
    env.set_stream_time_characteristic(TimeCharacteristic.EventTime)
    env.batch_size = 16_384
    sink = CountingSink()
    stream = (
        env.from_collection(events)
        .assign_timestamps_and_watermarks(
            lambda e: e.ts,
            WatermarkStrategy.for_bounded_out_of_orderness(16))
        .key_by(lambda e: e.key)
    )
    t0 = time.perf_counter()
    CEP.pattern(stream, _cep_pattern()).select(lambda m: 1.0).add_sink(
        sink)
    job = env.execute("cep-et-bench")
    dt = time.perf_counter() - t0
    assert job.metrics.cep_engine == "device", job.metrics.cep_engine
    assert sink.count == n_matches, (sink.count, n_matches)
    return total_events / dt, baseline_eps


# ------------------------------------------------- checkpoint overhead
def run_checkpoint_overhead(total_events: int, cpu: bool):
    """Checkpoint-overhead config (flink_tpu/checkpointing): the same
    keyed windowed sum run with checkpointing off / sync-full /
    async-incremental at a fixed step interval. Reports steady-state
    throughput and the step-loop stall a checkpoint causes (the
    sync-phase ms of every checkpoint; async mode only stalls for the
    staging fetch, sync mode for the whole serialize+write).

    subject = async-incremental eps, baseline = sync-full eps; a detail
    JSON line carries per-mode eps + max/mean stall for BENCH_*.json.
    """
    import shutil
    import tempfile

    from flink_tpu import StreamExecutionEnvironment
    from flink_tpu.core.config import Configuration
    from flink_tpu.core.time import TimeCharacteristic
    from flink_tpu.runtime.sinks import CountingSink
    from flink_tpu.runtime.sources import GeneratorSource

    n_keys = 10_000

    def gen(offset, n):
        idx = np.arange(offset, offset + n)
        cols = {
            "key": (idx * 48271) % n_keys,
            "value": np.ones(n, np.float32),
        }
        return cols, (idx // 4096) * 1000

    def run(mode):
        cfg = Configuration()
        ckpt_dir = None
        if mode != "off":
            ckpt_dir = tempfile.mkdtemp(prefix=f"ckbench-{mode}-")
            cfg.set("checkpoint.mode",
                    "incremental" if mode == "async_incremental" else "full")
            cfg.set("checkpoint.async", mode == "async_incremental")
        env = StreamExecutionEnvironment(cfg)
        env.set_parallelism(1)
        env.set_max_parallelism(128)
        env.set_stream_time_characteristic(TimeCharacteristic.EventTime)
        env.set_state_capacity(1 << 15)
        env.batch_size = 32768
        if ckpt_dir:
            env.enable_checkpointing(8, ckpt_dir)
        sink = CountingSink()
        t0 = time.perf_counter()
        (
            env.add_source(GeneratorSource(gen, total=total_events))
            .key_by(lambda c: c["key"])
            .time_window(10_000)
            .sum(lambda c: c["value"])
            .add_sink(sink)
        )
        env.execute(f"ckpt-bench-{mode}")
        dt = time.perf_counter() - t0
        stats = env.last_job.metrics.checkpoint_stats or []
        stalls = [s["sync_ms"] for s in stats]
        if ckpt_dir:
            shutil.rmtree(ckpt_dir, ignore_errors=True)
        assert sink.count > 0
        return {
            "eps": round(total_events / dt),
            "checkpoints": len(stats),
            "max_stall_ms": round(max(stalls), 2) if stalls else 0.0,
            "mean_stall_ms": round(
                sum(stalls) / len(stalls), 2) if stalls else 0.0,
            "bytes_written": sum(s["bytes"] for s in stats),
        }

    detail = {m: run(m) for m in ("off", "sync_full", "async_incremental")}
    print(json.dumps(
        {"config": "checkpoint_overhead", "detail": detail}), flush=True)
    return (detail["async_incremental"]["eps"],
            detail["sync_full"]["eps"])


# -------------------------------------------------- pipelined ingest
def run_ingest_pipeline(total_events: int, cpu: bool):
    """Pipelined-ingest config (ISSUE 3, runtime/ingest.py): the 1M-key
    tumbling-window sum run with prefetch off / on / on+checkpointing
    (incremental+async, the production configuration). Prefetch overlaps
    source poll + encode + device staging with the device step;
    epoch-tagged applied-offset cuts make the overlap legal while
    checkpoints are being written.

    subject = prefetch-on **with** checkpointing eps, baseline =
    prefetch-on without — the acceptance criterion is ratio >= 0.90
    (checkpointing must not give the overlap back). The detail line
    additionally carries the prefetch-off row (the escape hatch /
    pre-pipelining throughput) and per-mode checkpoint stalls.
    """
    import shutil
    import tempfile

    from flink_tpu import StreamExecutionEnvironment
    from flink_tpu.core.config import Configuration
    from flink_tpu.core.time import TimeCharacteristic
    from flink_tpu.runtime.sinks import CountingSink
    from flink_tpu.runtime.sources import GeneratorSource

    n_keys = 1 << 20

    def gen(offset, n):
        idx = np.arange(offset, offset + n)
        cols = {
            "key": (idx * 2654435761) % n_keys,
            "value": np.ones(n, np.float32),
        }
        return cols, (idx // 32768) * 1000

    def run(mode):
        cfg = Configuration()
        cfg.set("pipeline.prefetch",
                "off" if mode == "prefetch_off" else "on")
        cfg.set("keys.reverse-map", False)   # 1M-key columnar fast path
        ckpt_dir = None
        if mode == "prefetch_on_ckpt":
            ckpt_dir = tempfile.mkdtemp(prefix="ingestbench-")
            cfg.set("checkpoint.mode", "incremental")
            cfg.set("checkpoint.async", True)
        env = StreamExecutionEnvironment(cfg)
        env.set_parallelism(1)
        env.set_max_parallelism(128)
        env.set_stream_time_characteristic(TimeCharacteristic.EventTime)
        env.set_state_capacity(1 << 21)
        env.batch_size = 131072
        if ckpt_dir:
            env.enable_checkpointing(8, ckpt_dir)
        sink = CountingSink()
        t0 = time.perf_counter()
        (
            env.add_source(GeneratorSource(gen, total=total_events))
            .key_by(lambda c: c["key"])
            .time_window(10_000)
            .sum(lambda c: c["value"])
            .add_sink(sink)
        )
        env.execute(f"ingest-bench-{mode}")
        dt = time.perf_counter() - t0
        stats = env.last_job.metrics.checkpoint_stats or []
        if ckpt_dir:
            shutil.rmtree(ckpt_dir, ignore_errors=True)
        assert sink.count > 0
        return {
            "eps": round(total_events / dt),
            "checkpoints": len(stats),
            "max_stall_ms": round(
                max((s["sync_ms"] for s in stats), default=0.0), 2),
        }

    detail = {
        m: run(m)
        for m in ("prefetch_off", "prefetch_on", "prefetch_on_ckpt")
    }
    print(json.dumps(
        {"config": "ingest_pipeline", "detail": detail}), flush=True)
    return (detail["prefetch_on_ckpt"]["eps"],
            detail["prefetch_on"]["eps"])


# ---------------------------------------------- observability overhead
def run_observability_overhead(total_events: int, cpu: bool):
    """Observability-overhead config (ISSUE 2): the same keyed windowed
    sum run with span tracing off / sampled (every 64th cycle) / every
    step, so the "negligible overhead" claim is measured, not asserted.
    The always-on telemetry (kg_fill scatter + sampled monitoring fetch)
    is present in every mode — the off row IS the shipping default.

    subject = sampled-tracing eps, baseline = tracing-off eps (the ratio
    is the sampled overhead; the every-step row rides the detail line).
    """
    from flink_tpu import StreamExecutionEnvironment
    from flink_tpu.core.config import Configuration
    from flink_tpu.core.time import TimeCharacteristic
    from flink_tpu.runtime.sinks import CountingSink
    from flink_tpu.runtime.sources import GeneratorSource

    n_keys = 10_000

    def gen(offset, n):
        idx = np.arange(offset, offset + n)
        cols = {
            "key": (idx * 48271) % n_keys,
            "value": np.ones(n, np.float32),
        }
        return cols, (idx // 4096) * 1000

    def run(mode):
        cfg = Configuration()
        if mode != "off":
            cfg.set("observability.tracing", True)
            cfg.set("observability.trace-sample-every",
                    64 if mode == "sampled" else 1)
        env = StreamExecutionEnvironment(cfg)
        env.set_parallelism(1)
        env.set_max_parallelism(128)
        env.set_stream_time_characteristic(TimeCharacteristic.EventTime)
        env.set_state_capacity(1 << 15)
        env.batch_size = 32768
        sink = CountingSink()
        t0 = time.perf_counter()
        (
            env.add_source(GeneratorSource(gen, total=total_events))
            .key_by(lambda c: c["key"])
            .time_window(10_000)
            .sum(lambda c: c["value"])
            .add_sink(sink)
        )
        env.execute(f"obs-bench-{mode}")
        dt = time.perf_counter() - t0
        assert sink.count > 0
        tracer = env._span_tracer
        return {
            "eps": round(total_events / dt),
            "spans": len(tracer) if tracer is not None else 0,
            "spans_dropped": tracer.dropped if tracer is not None else 0,
        }

    detail = {m: run(m) for m in ("off", "sampled", "every_step")}
    detail["resident_drain_stats"] = _resident_drain_stats_rows()
    detail["chained_drain_stats"] = _chained_drain_stats_rows()
    print(json.dumps(
        {"config": "observability_overhead", "detail": detail}),
        flush=True)
    return detail["sampled"]["eps"], detail["off"]["eps"]


def _resident_drain_stats_rows():
    """Round-14 rows: the drain-interior flight recorder measured at the
    PR 12 matched dims (B/C/ring/slide/D of ``run_resident_loop``, full
    ring drains, lagged fire consumption). Three modes:

    * ``off`` — ``drain_stats=False``: the kernel compiles WITHOUT the
      telemetry payload (the trace-tier ledger pins this byte-identical
      to pre-PR), so this row is the shipping default;
    * ``sampled`` — payload compiled in, host fetches every 8th drain
      (the ``observability.drain-stats-every`` default);
    * ``every_drain`` — payload fetched with every fire batch.

    The sampled-vs-off ratio is the acceptance criterion (<= 2%
    events/s): the payload is element ops and tiny reductions over
    fields the fused body already materialized, and the fetch rides the
    existing lagged device_get, so the steady-state cost must stay in
    the noise."""
    from collections import deque as _dq

    import jax
    import jax.numpy as jnp

    from flink_tpu.ops import window_kernels as wk
    from flink_tpu.parallel.mesh import MeshContext
    from flink_tpu.runtime.step import (
        WindowStageSpec,
        build_window_resident_drain,
        init_sharded_state,
    )

    n_dev = len(jax.devices())
    ctx = MeshContext.create(n_dev, 128)
    B, C, RING, SLIDE = DEVICE_CEILING_BATCH, 4096, 9, 1000
    BPP, D = 4, 32
    n_groups = 6
    n_batches = n_groups * D
    spec = WindowStageSpec(
        win=wk.WindowSpec(SLIDE, SLIDE, ring=RING, fires_per_step=4),
        red=wk.ReduceSpec("sum", jnp.float32),
        capacity_per_shard=C, layout="direct", precombine=False,
    )

    rng = np.random.default_rng(11)
    batches, wms = [], []
    for j in range(n_batches):
        p = j // BPP
        n_hot = B // 2
        lo = np.concatenate([
            rng.integers(0, C - 1, B - n_hot),
            rng.integers(0, 64, n_hot),
        ]).astype(np.uint32)
        rng.shuffle(lo)
        ts = np.full(B, p * SLIDE + SLIDE // 2, np.int32)
        batches.append(tuple(jax.device_put(a) for a in (
            np.zeros(B, np.uint32), lo, ts,
            np.ones(B, np.float32), np.ones(B, bool),
        )))
        wms.append(np.int32(p * SLIDE - 1))

    def measure(drain_stats, fetch_every):
        step = build_window_resident_drain(
            ctx, spec, D, reduced=True, drain_stats=drain_stats
        )

        def run_once():
            state = init_sharded_state(ctx, spec)
            t0 = time.perf_counter()
            handles = _dq()
            mon = None
            for g in range(n_groups):
                sel = range(g * D, (g + 1) * D)
                flat = [a for i in sel for a in batches[i]]
                wmv = np.tile(
                    np.asarray([wms[i] for i in sel], np.int32),
                    (n_dev, 1),
                )
                res = step(state, *flat, wmv, np.int32(D))
                state, mon, fires = res[:3]
                ds = (res[3] if drain_stats
                      and (g + 1) % fetch_every == 0 else None)
                handles.append((fires, ds))
                if len(handles) > 1:
                    cf, ds_h = handles.popleft()
                    payload = (cf.counts, cf.lane_valid,
                               cf.window_end_ticks, cf.value_sums)
                    jax.device_get(
                        payload + (ds_h,) if ds_h is not None
                        else payload
                    )
            while handles:
                cf, ds_h = handles.popleft()
                payload = (cf.counts, cf.lane_valid,
                           cf.window_end_ticks, cf.value_sums)
                jax.device_get(
                    payload + (ds_h,) if ds_h is not None else payload
                )
            jax.block_until_ready(mon[1])
            return time.perf_counter() - t0

        run_once()                               # compile + settle
        dt = min(run_once() for _ in range(3))
        return round(B * n_batches / dt)

    rows = {
        "off": measure(False, 0),
        "sampled": measure(True, 8),
        "every_drain": measure(True, 1),
        "B": B, "C": C, "ring_depth": D, "n_batches": n_batches,
        "fetch_every_sampled": 8,
    }
    rows["sampled_over_off"] = round(
        rows["sampled"] / max(rows["off"], 1), 4
    )
    rows["criterion"] = "sampled >= 0.98x off (<= 2% overhead)"
    return rows


def _chained_drain_stats_rows():
    """Round-17 rows: the STAGE-AWARE flight recorder measured inside
    the 2-stage chained drain at the round-16 matched dims (B=512 /
    C=4096 / ring depth D=32, firing rollup stream). Three modes,
    mirroring ``_resident_drain_stats_rows``:

    * ``off`` — ``drain_stats=False``: the chained kernel compiles
      WITHOUT the telemetry payload (op_budget_pre_stage_stats.json
      pins this byte-identical to pre-PR);
    * ``sampled`` — stage-0 per-slot payload + per-downstream-stage
      records compiled in, host fetches every 8th drain;
    * ``every_drain`` — both payload planes fetched with every drain.

    The sampled-vs-off ratio is the acceptance criterion (<= 2%
    events/s): the stage tail's record is six scalar reductions over
    planes the edge pack already materialized, riding the same lagged
    fetch as the stage-0 block."""
    from collections import deque as _dq

    import jax
    import jax.numpy as jnp

    from flink_tpu.ops import window_kernels as wk
    from flink_tpu.parallel.mesh import MeshContext
    from flink_tpu.runtime.step import (
        WindowStageSpec,
        build_window_chained_drain,
        init_sharded_state,
    )

    n_dev = len(jax.devices())
    ctx = MeshContext.create(n_dev, 128)
    B, C, RING, SLIDE = DEVICE_CEILING_BATCH, 4096, 9, 1000
    BPP, D = 4, 32
    ROLLUP, KEYSPACE, EX_LANES = 4, 256, 2048
    n_groups = 6
    n_batches = n_groups * D
    spec1 = WindowStageSpec(
        win=wk.WindowSpec(SLIDE, SLIDE, ring=RING, fires_per_step=4),
        red=wk.ReduceSpec("sum", jnp.float32),
        capacity_per_shard=C, layout="direct", precombine=False,
    )
    s2 = ROLLUP * SLIDE
    slack = (D * spec1.win.fires_per_step * SLIDE) // s2 + 2
    spec2 = WindowStageSpec(
        win=wk.WindowSpec(s2, s2, ring=max(8, 2 + slack, 4),
                          fires_per_step=4),
        red=wk.ReduceSpec("sum", jnp.float32),
        capacity_per_shard=C, layout="direct", precombine=False,
    )

    rng = np.random.default_rng(11)
    batches, wms = [], []
    for j in range(n_batches):
        p = j // BPP
        n_hot = B // 2
        lo = np.concatenate([
            rng.integers(0, KEYSPACE, B - n_hot),
            rng.integers(0, 64, n_hot),
        ]).astype(np.uint32)
        rng.shuffle(lo)
        ts = np.full(B, p * SLIDE + SLIDE // 2, np.int32)
        batches.append(tuple(jax.device_put(a) for a in (
            np.zeros(B, np.uint32), lo, ts,
            np.ones(B, np.float32), np.ones(B, bool),
        )))
        wms.append(np.int32(p * SLIDE - 1))

    def measure(drain_stats, fetch_every):
        step = build_window_chained_drain(
            ctx, (spec1, spec2), D, exchange_lanes=EX_LANES,
            drain_stats=drain_stats,
        )

        def run_once():
            state = (init_sharded_state(ctx, spec1),
                     init_sharded_state(ctx, spec2))
            t0 = time.perf_counter()
            handles = _dq()
            mon = None
            for g in range(n_groups):
                sel = range(g * D, (g + 1) * D)
                flat = [a for i in sel for a in batches[i]]
                wmv = np.tile(
                    np.asarray([wms[i] for i in sel], np.int32),
                    (n_dev, 1),
                )
                res = step(state, *flat, wmv, np.int32(D))
                state, mon, fires = res[:3]
                ds = (res[3] if drain_stats
                      and (g + 1) % fetch_every == 0 else None)
                handles.append((fires, ds))
                if len(handles) > 1:
                    cf, ds_h = handles.popleft()
                    payload = (cf.counts, cf.lane_valid,
                               cf.window_end_ticks, cf.value_sums)
                    jax.device_get(
                        payload + (ds_h,) if ds_h is not None
                        else payload
                    )
            while handles:
                cf, ds_h = handles.popleft()
                payload = (cf.counts, cf.lane_valid,
                           cf.window_end_ticks, cf.value_sums)
                jax.device_get(
                    payload + (ds_h,) if ds_h is not None else payload
                )
            jax.block_until_ready(mon[1])
            return time.perf_counter() - t0

        run_once()                               # compile + settle
        dt = min(run_once() for _ in range(3))
        return round(B * n_batches / dt)

    rows = {
        "off": measure(False, 0),
        "sampled": measure(True, 8),
        "every_drain": measure(True, 1),
        "B": B, "C": C, "ring_depth": D, "n_batches": n_batches,
        "n_stages": 2, "exchange_lanes": EX_LANES,
        "fetch_every_sampled": 8,
    }
    rows["sampled_over_off"] = round(
        rows["sampled"] / max(rows["off"], 1), 4
    )
    rows["criterion"] = "sampled >= 0.98x off (<= 2% overhead)"
    return rows


# ------------------------------------------------- containment overhead
def run_fault_overhead(total_events: int, cpu: bool):
    """Failure-containment overhead config (ISSUE 4): the PR 3
    production path (prefetch + async-incremental checkpointing) run
    with the watchdog OFF vs ON — fault injection disabled in both, the
    failure budget active in both (its bookkeeping is always-on). The
    delta is the per-cycle phase arming plus the monitor thread, which
    is the entire cost a healthy job pays for hang attribution.

    subject = watchdog-on eps, baseline = watchdog-off eps; the
    acceptance criterion is ratio >= 0.98 (<2% containment tax on the
    PR 3 throughput path).
    """
    import shutil
    import tempfile

    from flink_tpu import StreamExecutionEnvironment
    from flink_tpu.core.config import Configuration
    from flink_tpu.core.time import TimeCharacteristic
    from flink_tpu.runtime.sinks import CountingSink
    from flink_tpu.runtime.sources import GeneratorSource

    n_keys = 1 << 20

    def gen(offset, n):
        idx = np.arange(offset, offset + n)
        cols = {
            "key": (idx * 2654435761) % n_keys,
            "value": np.ones(n, np.float32),
        }
        return cols, (idx // 32768) * 1000

    def run(mode):
        cfg = Configuration()
        cfg.set("pipeline.prefetch", "on")
        cfg.set("keys.reverse-map", False)
        cfg.set("checkpoint.mode", "incremental")
        cfg.set("checkpoint.async", True)
        cfg.set("checkpoint.tolerable-failures", 3)
        cfg.set("watchdog.enabled", mode == "watchdog_on")
        ckpt_dir = tempfile.mkdtemp(prefix="faultbench-")
        env = StreamExecutionEnvironment(cfg)
        env.set_parallelism(1)
        env.set_max_parallelism(128)
        env.set_stream_time_characteristic(TimeCharacteristic.EventTime)
        env.set_state_capacity(1 << 21)
        env.batch_size = 131072
        env.enable_checkpointing(8, ckpt_dir)
        sink = CountingSink()
        t0 = time.perf_counter()
        (
            env.add_source(GeneratorSource(gen, total=total_events))
            .key_by(lambda c: c["key"])
            .time_window(10_000)
            .sum(lambda c: c["value"])
            .add_sink(sink)
        )
        env.execute(f"fault-bench-{mode}")
        dt = time.perf_counter() - t0
        m = env.last_job.metrics
        shutil.rmtree(ckpt_dir, ignore_errors=True)
        assert sink.count > 0
        assert m.checkpoints_aborted == 0    # no faults were injected
        return {
            "eps": round(total_events / dt),
            "checkpoints": len(m.checkpoint_stats or []),
            "watchdog_trips": m.watchdog_trips,
        }

    detail = {m: run(m) for m in ("watchdog_off", "watchdog_on")}
    print(json.dumps(
        {"config": "fault_overhead", "detail": detail}), flush=True)
    return (detail["watchdog_on"]["eps"], detail["watchdog_off"]["eps"])


# ---------------------------------------------------- MTTR drill
def run_mttr_recovery(total_events: int, cpu: bool):
    """MTTR drill (ISSUE 6): detect-to-first-fire of the three recovery
    paths, measured through the recovery tracker's per-attempt phase
    spans (metrics/recovery.py).

      cold_remote  a FRESH process-equivalent start (new executor, full
                   XLA recompile) restoring from primary storage with an
                   injected per-directory fetch latency (the
                   ckpt.read.primary fault point models remote object-
                   store RTT; local cache off)
      cold_local   the same fresh start, but the task-local snapshot
                   cache is primed — every chain member reads from
                   verified local disk and the injected remote latency
                   is never paid
      warm         a mid-stream TRANSIENT failure (injected ingest-
                   thread kill): in-process restart reusing the live
                   jitted kernels, local fetch, dirty-only re-stage

    subject = cold_remote detect-to-first-fire ms, baseline = warm ms;
    acceptance is ratio >= 2 (the local+warm path beats cold-remote by
    2x or more). The detail JSON carries the per-phase breakdowns.
    """
    import shutil
    import tempfile

    from flink_tpu import StreamExecutionEnvironment
    from flink_tpu.core.config import Configuration
    from flink_tpu.core.time import TimeCharacteristic
    from flink_tpu.runtime.sinks import CountingSink
    from flink_tpu.runtime.sources import GeneratorSource
    from flink_tpu.testing import faults
    from flink_tpu.testing.faults import FaultInjector, FaultRule

    n_keys = 1 << 14
    events = min(total_events, 2_000_000)
    READ_DELAY_S = 0.25      # injected per-chain-member remote fetch RTT

    def gen(offset, n):
        idx = np.arange(offset, offset + n)
        cols = {
            "key": (idx * 48271) % n_keys,
            "value": np.ones(n, np.float32),
        }
        return cols, (idx // 8192) * 1000

    def build(ckpt_dir, local_on, extra_cfg=None):
        cfg = Configuration({
            "checkpoint.mode": "incremental",
            "checkpoint.async": True,
            "checkpoint.local.enabled": local_on,
            "pipeline.prefetch": "on",
            "keys.reverse-map": False,
            **(extra_cfg or {}),
        })
        env = StreamExecutionEnvironment(cfg)
        env.set_parallelism(1)
        env.set_max_parallelism(128)
        env.set_stream_time_characteristic(TimeCharacteristic.EventTime)
        env.set_state_capacity(1 << 16)
        env.batch_size = 32768
        env.enable_checkpointing(4, ckpt_dir)
        return env

    def wire(env, total):
        sink = CountingSink()
        (
            env.add_source(GeneratorSource(gen, total=total))
            .key_by(lambda c: c["key"])
            .time_window(10_000)
            .sum(lambda c: c["value"])
            .add_sink(sink)
        )
        return sink

    def attempt_row(env, mode_filter=None):
        rep = env._recovery_report()
        rows = [a for a in rep["attempts"] if a["first_fire_ms"]]
        if mode_filter:
            rows = [a for a in rows
                    if (a["mode"] or "").startswith(mode_filter)]
        a = rows[-1]
        return {
            "detect_to_first_fire_ms": a["first_fire_ms"],
            "mode": a["mode"],
            "phases_ms": a["phases_ms"],
            "local_cache": rep["local-cache"],
        }

    # ---- prime: one complete run leaves a restorable chain behind -----
    ckpt_dir = tempfile.mkdtemp(prefix="mttr-")
    env = build(ckpt_dir, local_on=True)
    sink = wire(env, events)
    env.execute("mttr-prime")
    assert sink.count > 0
    local_dir = ckpt_dir.rstrip("/\\") + "-local"

    remote_rules = [FaultRule("ckpt.read.primary", action="sleep",
                              delay_s=READ_DELAY_S, every=1, times=10**9)]

    detail = {"events": events, "read_delay_ms": READ_DELAY_S * 1e3}

    # ---- cold_remote: fresh start, no cache, remote fetch latency -----
    shutil.rmtree(local_dir, ignore_errors=True)   # cache absent
    env = build(ckpt_dir, local_on=False)
    sink = wire(env, events * 2)
    with faults.active(FaultInjector(remote_rules)):
        env.execute("mttr-cold-remote", restore_from=ckpt_dir)
    assert sink.count > 0
    detail["cold_remote"] = attempt_row(env)

    # ---- cold_local: fresh start, cache re-primed by the run above ----
    # (the cold_remote run wrote checkpoints with local off; re-prime by
    # restoring once more WITH the cache on — its own checkpoints mirror)
    env = build(ckpt_dir, local_on=True)
    sink = wire(env, events * 3)
    env.execute("mttr-prime-cache", restore_from=ckpt_dir)
    env = build(ckpt_dir, local_on=True)
    sink = wire(env, events * 4)
    with faults.active(FaultInjector(list(remote_rules))):
        env.execute("mttr-cold-local", restore_from=ckpt_dir)
    assert sink.count > 0
    detail["cold_local"] = attempt_row(env)

    # ---- warm: mid-stream transient failure, in-process restart -------
    env = build(ckpt_dir, local_on=True, extra_cfg={
        "restart-strategy": "exponential-backoff",
        "restart-strategy.exponential-backoff.initial-delay": 0.01,
        "restart-strategy.exponential-backoff.max-delay": 0.05,
    })
    sink = wire(env, events * 5)
    rules = [FaultRule("ingest.producer", action="kill", at=30)] + \
        list(remote_rules)
    with faults.active(FaultInjector(rules)):
        env.execute("mttr-warm", restore_from=ckpt_dir)
    assert sink.count > 0
    detail["warm"] = attempt_row(env, mode_filter="warm")

    print(json.dumps(
        {"config": "mttr_recovery", "detail": detail}), flush=True)
    # subject/baseline slots carry the two MTTR numbers; "ratio" is the
    # acceptance number (cold_remote / warm >= 2)
    return (detail["cold_remote"]["detect_to_first_fire_ms"],
            detail["warm"]["detect_to_first_fire_ms"])


# ------------------------------------------------------ elasticity drill
def run_elastic_recovery(total_events: int, cpu: bool):
    """Elasticity drill (ISSUE 8, ``bench.py --elastic``): kill one
    shard of an 8-device mesh mid-stream and measure the lose-one ->
    degraded run -> scale-back cycle end to end.

    Phases (one job, one stream):

      pre       8-shard steady state (throughput sampled)
      kill      the ``device_loss`` fault class fires at a step
                dispatch — shard 5's device is declared dead
      degraded  elastic recovery re-sliced the key-group ranges over
                the 7 survivors, rebuilt the compiled step family, and
                rescaled-restored the last durable cut (preferring the
                PR 6 task-local cache); the job keeps serving
      scale-back once degraded throughput is established, the drill
                requests scale-up and the job performs a savepoint-cut
                live rescale back to 8 shards

    Stamps: degraded-throughput fraction (criterion >= 0.6 x 7/8 =
    0.525 of pre-fault), the rescaled-recovery detect-to-first-fire
    alongside PR 6's MTTR tiers, and the exactly-once oracle — the
    emission set across the whole cycle equals the unfaulted analytic
    oracle. Returns (degraded_fraction, rescale_first_fire_ms,
    p99_fire_ms) — the p99 is the job's weighted fire-latency
    percentile across the whole cycle (ISSUE 17: the latency half of
    the north-star metric stamped in the headline)."""
    import tempfile

    import jax

    from flink_tpu import StreamExecutionEnvironment
    from flink_tpu.core.config import Configuration
    from flink_tpu.core.time import TimeCharacteristic
    from flink_tpu.runtime.sinks import CollectSink
    from flink_tpu.runtime.sources import GeneratorSource
    from flink_tpu.testing import faults
    from flink_tpu.testing.faults import FaultInjector, FaultRule

    N_DEV = 8
    if len(jax.devices()) < N_DEV:
        raise RuntimeError(
            f"elastic_recovery needs an {N_DEV}-device mesh; found "
            f"{len(jax.devices())} (bench.py --elastic forces the "
            f"virtual CPU mesh via XLA_FLAGS before JAX initializes)"
        )
    n_keys = 1 << 14
    B = 16384
    WINDOW = 10_000
    events = min(total_events, 2_000_000)
    KILL_SHARD, KILL_AT = 5, 30

    def gen(offset, n):
        idx = np.arange(offset, offset + n)
        cols = {
            "key": (idx * 48271) % n_keys,
            "value": np.ones(n, np.float32),
        }
        return cols, (idx // 8192) * 1000

    def expected(total):
        idx = np.arange(total)
        keys = (idx * 48271) % n_keys
        we = ((idx // 8192) * 1000 // WINDOW + 1) * WINDOW
        pair = keys.astype(np.int64) * (1 << 34) + we
        uniq, counts = np.unique(pair, return_counts=True)
        return {
            (int(p >> 34), int(p & ((1 << 34) - 1))): float(c)
            for p, c in zip(uniq.tolist(), counts.tolist())
        }

    ckpt_dir = tempfile.mkdtemp(prefix="elastic-")
    cfg = Configuration({
        "checkpoint.mode": "incremental",
        "checkpoint.async": True,
        "checkpoint.local.enabled": True,
        "pipeline.prefetch": "on",
        "keys.reverse-map": False,
        "restart-strategy": "exponential-backoff",
        "restart-strategy.exponential-backoff.initial-delay": 0.01,
        "restart-strategy.exponential-backoff.max-delay": 0.05,
    })
    env = StreamExecutionEnvironment(cfg)
    env.set_parallelism(N_DEV)
    env.set_max_parallelism(128)
    env.set_stream_time_characteristic(TimeCharacteristic.EventTime)
    # capacity == keyspace: the direct-index layout (key == slot), the
    # bench.py configuration — no insert phase, no adaptive tier flip
    # to pollute the phase throughput windows
    env.set_state_capacity(n_keys)
    env.batch_size = B
    env.enable_checkpointing(2, ckpt_dir)

    sink = CollectSink()
    (
        env.add_source(GeneratorSource(gen, total=events))
        .key_by(lambda c: c["key"])
        .time_window(WINDOW)
        .sum(lambda c: c["value"])
        .add_sink(sink)
    )

    marks = {"t_kill": None, "t_deg0": None, "t_scale_req": None}
    samples = []                  # (t_perf, records_in)
    stop = threading.Event()

    def sampler():
        while not stop.is_set():
            m = getattr(env, "_live_metrics", None)
            if m is not None:
                samples.append((time.perf_counter(), m.records_in))
            time.sleep(0.025)

    def scale_up_trigger():
        """Request scale-back once degraded throughput is established:
        the measurement window opens only after real post-replan
        progress (past the re-plan's compile burst + replay catch-up),
        so the degraded slope measures steady degraded serving."""
        deadline = time.monotonic() + 600
        while time.monotonic() < deadline and not stop.is_set():
            ctl = getattr(env, "_elastic_controller", None)
            m = getattr(env, "_live_metrics", None)
            if ctl is not None and ctl.degraded and m is not None:
                r0 = m.records_in
                while time.monotonic() < deadline and not stop.is_set():
                    if marks["t_deg0"] is None and \
                            m.records_in >= r0 + 4 * B:
                        marks["t_deg0"] = time.perf_counter()
                        r0 = m.records_in
                    if marks["t_deg0"] is not None and \
                            m.records_in >= r0 + 16 * B and \
                            time.perf_counter() - marks["t_deg0"] >= 1.0:
                        marks["t_scale_req"] = time.perf_counter()
                        ctl.request_scale_up()
                        return
                    time.sleep(0.025)
                return
            time.sleep(0.025)

    rules = [
        FaultRule("step.dispatch", action="call",
                  fn=lambda _ctx: marks.__setitem__(
                      "t_kill", time.perf_counter()),
                  at=KILL_AT),
        faults.device_loss_rule(shard=KILL_SHARD, at=KILL_AT),
    ]
    threads = [threading.Thread(target=sampler, daemon=True),
               threading.Thread(target=scale_up_trigger, daemon=True)]
    for t in threads:
        t.start()
    try:
        with faults.active(FaultInjector(rules)):
            env.execute("elastic-drill")
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=5)

    got = {(r.key, r.window_end_ms): r.value for r in sink.results}
    exp = expected(events)
    missing = sum(1 for k, v in exp.items() if got.get(k) != v)
    extra = sum(1 for k in got if k not in exp)
    oracle_ok = not missing and not extra

    def slope_eps(t_start, t_end):
        """records/s over the sample window [t_start, t_end)."""
        if t_start is None or t_end is None:
            return None
        win = [(t, r) for t, r in samples if t_start <= t < t_end]
        if len(win) < 4 or win[-1][0] - win[0][0] < 0.2:
            return None
        return (win[-1][1] - win[0][1]) / (win[-1][0] - win[0][0])

    # pre window: the last 3s of the 8-shard steady state, clamped to
    # after the first real progress (the initial compile burst is flat)
    t_first = next((t for t, r in samples if r >= 2 * B), None)
    pre_eps = (
        slope_eps(max(t_first, marks["t_kill"] - 3.0), marks["t_kill"])
        if t_first is not None and marks["t_kill"] is not None else None
    )
    degraded_eps = slope_eps(marks["t_deg0"], marks["t_scale_req"])
    frac = (
        degraded_eps / pre_eps if pre_eps and degraded_eps else 0.0
    )

    rep = env._recovery_report()
    rescaled = [a for a in rep["attempts"]
                if (a["mode"] or "").startswith("rescale")]
    first_fire_ms = (
        rescaled[-1]["first_fire_ms"] if rescaled
        and rescaled[-1]["first_fire_ms"] else 0.0
    )
    el = env._elasticity_report()
    live_m = getattr(env, "_live_metrics", None)
    p99 = live_m.fire_latency_pct(99) if live_m is not None else None
    p99 = round(p99, 2) if p99 is not None else None
    detail = {
        "events": events,
        "devices": N_DEV,
        "killed_shard": KILL_SHARD,
        "p99_fire_ms": p99,
        "pre_fault_eps": round(pre_eps) if pre_eps else None,
        "degraded_eps": round(degraded_eps) if degraded_eps else None,
        "degraded_fraction": round(frac, 3),
        "criterion": ">= 0.6 * (7/8) = 0.525",
        "rescale_detect_to_first_fire_ms": first_fire_ms,
        "rescale_phases_ms": (
            rescaled[-1]["phases_ms"] if rescaled else None
        ),
        "exactly_once": bool(oracle_ok),
        # diagnosable on failure: which side diverged and by how much
        "oracle_missing_or_wrong": int(missing),
        "oracle_extra": int(extra),
        "finished_at_shards": el["current-shards"],
        "rescales": el["rescales"],
        "local_cache": rep["local-cache"],
    }
    print(json.dumps(
        {"config": "elastic_recovery", "detail": detail}), flush=True)
    assert oracle_ok, (
        "exactly-once oracle FAILED across kill -> degraded -> "
        "scale-back"
    )
    return frac, first_fire_ms, p99


# ------------------------------------------------ device update ceiling
DEVICE_CEILING_BATCH = 512   # bench.py --device-ceiling reports this


def run_device_update_ceiling(total_events: int, cpu: bool):
    """Device update + fire ceiling (ISSUE 5, extended by ISSUE 7): a
    pre-staged synthetic batch stream feeds the compiled steps directly
    — no source, no prefetch, no emit path — so the compute ceiling is measured per-round as a
    first-class number.

    Three blocks:

    * ``fusion`` / ``precombine`` — the PR-5 QUIET grid, unchanged for
      trajectory continuity: K in {1,4,8} megasteps x duplicate-key
      fraction, sentinel watermark (no fires mid-loop), plus the
      precombine on/off pair per duplicate fraction.
    * ``fire_grid`` — the ISSUE-7 acceptance grid: a FIRING workload
      (event time advances ~1 pane per ``BPP`` batches, watermark
      trailing, so windows really close mid-stream) run through BOTH
      dispatch disciplines on the same K/dup grid:
        - ``split``: the PR-5 runtime's pattern — the fused group breaks
          at every pane-boundary crossing (partial groups dispatch as
          sequential single steps), then a separate fire dispatch plus
          the blocking small-field fetch the split drain pays;
        - ``fused``: resident-pipeline megasteps (fire folded into the
          scan, build_window_megastep_fired), fire payload handles
          consumed LAGGED like the executor's consume_fires.
      ``acceptance`` stamps best(fused) / best(split) — the "PR 5 best
      cell" is the best the split discipline achieves on this container,
      same K/dup grid, best-of-3 — criterion >= 1.15.
    * ``state_planes`` — the kernel-variant sweep at the base firing
      cell (K=8, dup=0.5, direct, f32-sum, pane-major, split planes),
      varying one axis at a time: packed planes, i32-count accumulators
      (plain + packed), the hash table layout, and slot-major
      accumulator order — so the platform-gated auto defaults (packed /
      precombine off on CPU, on for accelerators) stay grounded in this
      artifact instead of asserted.
    """
    import jax
    import jax.numpy as jnp

    from flink_tpu.ops import window_kernels as wk
    from flink_tpu.parallel.mesh import MeshContext
    from flink_tpu.runtime.step import (
        WindowStageSpec,
        build_window_fire_reduced_step,
        build_window_fire_step,
        build_window_megastep,
        build_window_megastep_fired,
        build_window_update_step,
        init_sharded_state,
    )

    n_dev = len(jax.devices())
    ctx = MeshContext.create(n_dev, 128)
    # dispatch-overhead regime: small enough that the fixed per-dispatch
    # cost is a measurable share of the step; ring 9
    # holds the 8 cycling panes without evicting unfired data
    B, C, RING, SLIDE = DEVICE_CEILING_BATCH, 4096, 9, 1000
    N_SLOTS = 8
    BPP = 4            # firing stream: batches per pane (crossing cadence)
    iters = max(128, min(8192, total_events // B))
    # firing cells pre-stage every batch (panes advance monotonically,
    # so batches cannot be reused across iterations like the quiet ring)
    iters_f = max(96, iters // 8)

    def _spec(K=1, dup=0.0, precombine=False, layout="direct",
              red=None, packed=False, acc_layout="pane"):
        return WindowStageSpec(
            win=wk.WindowSpec(SLIDE, SLIDE, ring=RING, fires_per_step=4,
                              acc_layout=acc_layout),
            red=red or wk.ReduceSpec("sum", jnp.float32),
            capacity_per_shard=C, layout=layout, precombine=precombine,
            packed=packed,
        )

    def _keys(dup, rng, layout):
        n_hot = int(B * dup)
        lo = np.concatenate([
            rng.integers(0, C - 1, B - n_hot),
            rng.integers(0, 64, n_hot),
        ]).astype(np.uint32)
        rng.shuffle(lo)
        if layout == "direct":
            return np.zeros(B, np.uint32), lo
        from flink_tpu.ops.hashing import hash64_host

        h = hash64_host(lo.astype(np.int64))
        return ((h >> np.uint64(32)).astype(np.uint32),
                (h & np.uint64(0xFFFFFFFF)).astype(np.uint32))

    # ---------------------------------------------------- quiet grid (PR 5)
    def make_ring(dup, rng):
        """N_SLOTS pre-staged batches; slot i's records land in pane i,
        so the slot cycle exercises the pane-ring rotation without ever
        evicting unfired data."""
        slots = []
        for i in range(N_SLOTS):
            hi, lo = _keys(dup, rng, "direct")
            ts = np.full(B, i * SLIDE + SLIDE // 2, np.int32)
            slots.append(tuple(jax.device_put(a) for a in (
                hi, lo, ts, np.ones(B, np.float32), np.ones(B, bool),
            )))
        return slots

    WM_MIN = np.int32(-(2**31) + 1)   # sentinel: no fires mid-loop

    def measure_quiet(K, dup, precombine):
        spec = _spec(K, dup, precombine)
        step = (
            build_window_update_step(ctx, spec) if K == 1
            else build_window_megastep(ctx, spec, K)
        )
        fire = build_window_fire_step(ctx, spec)
        state = init_sharded_state(ctx, spec)
        slots = make_ring(dup, np.random.default_rng(7))
        wm = np.full(n_dev, WM_MIN)
        wmv = np.tile(WM_MIN, (n_dev, K))

        def disp(state, it):
            if K == 1:
                return step(state, *slots[it % N_SLOTS], wm)
            flat = [a for j in range(K)
                    for a in slots[(it * K + j) % N_SLOTS]]
            return step(state, *flat, wmv)

        for w in range(3):                      # compile + settle
            state, mon = disp(state, w)
        # compile the fire step too (the sentinel watermark fires
        # nothing, so the live state is untouched)
        state, fr = fire(state, wm)
        jax.block_until_ready(fr.counts)
        # best-of-3: each cell recompiles its own step variant, and a
        # single short pass is at the mercy of host scheduling noise —
        # the ceiling claimed is the best the device actually did
        n_disp = max(1, iters // K)
        upd_dt = float("inf")
        for _rep in range(3):
            t0 = time.perf_counter()
            for it in range(n_disp):
                state, mon = disp(state, it)
            jax.block_until_ready(mon[1])
            upd_dt = min(upd_dt, time.perf_counter() - t0)
        # fire probe: one fire dispatch over the full key population
        t1 = time.perf_counter()
        state, fr = fire(state, np.full(n_dev, np.int32(2**31 - 5)))
        jax.block_until_ready(fr.counts)
        fire_ms = (time.perf_counter() - t1) * 1e3
        return B * n_disp * K / upd_dt, fire_ms

    # ------------------------------------------------- firing-stream cells
    def make_stream(dup, rng, n_batches, layout):
        """Pre-staged batches whose panes ADVANCE (pane j//BPP) with the
        watermark trailing one pane, so windows fire mid-stream — the
        workload the resident pipeline exists for."""
        batches, wms = [], []
        for j in range(n_batches):
            p = j // BPP
            hi, lo = _keys(dup, rng, layout)
            ts = np.full(B, p * SLIDE + SLIDE // 2, np.int32)
            batches.append(tuple(jax.device_put(a) for a in (
                hi, lo, ts, np.ones(B, np.float32), np.ones(B, bool),
            )))
            wms.append(np.int32(p * SLIDE - 1))
        return batches, wms

    def measure_split_fire(K, dup, layout="direct", red=None,
                           packed=False, acc_layout="pane",
                           reduced=False):
        """The PR-5 dispatch discipline on the firing stream: groups
        break at every crossing (partials dispatch as singles), each
        crossing pays a separate fire dispatch + the blocking
        small-field fetch of the split drain. ``reduced`` uses the
        on-chip-reduced fire variant (device_reduce sink topology) —
        the split path's best case, so the acceptance comparison never
        flatters the resident pipeline."""
        spec = _spec(K, dup, layout=layout, red=red, packed=packed,
                     acc_layout=acc_layout)
        step1 = build_window_update_step(ctx, spec)
        mega = build_window_megastep(ctx, spec, K) if K > 1 else None
        fire = (
            build_window_fire_reduced_step(ctx, spec) if reduced
            else build_window_fire_step(ctx, spec)
        )
        n_batches = iters_f * max(1, K)
        batches, wms = make_stream(dup, np.random.default_rng(11),
                                   n_batches, layout)

        def run_once():
            state = init_sharded_state(ctx, spec)
            t0 = time.perf_counter()
            pend = []
            last_wm = WM_MIN
            mon = None
            for j in range(n_batches):
                pend.append(j)
                crossing = wms[j] > last_wm
                if crossing or len(pend) == K:
                    if len(pend) == K and mega is not None:
                        flat = [a for i in pend for a in batches[i]]
                        wmv = np.tile(
                            np.asarray([wms[i] for i in pend], np.int32),
                            (n_dev, 1),
                        )
                        state, mon = mega(state, *flat, wmv)
                    else:
                        for i in pend:
                            state, mon = step1(
                                state, *batches[i],
                                np.full(n_dev, wms[i]),
                            )
                    pend = []
                    if crossing:
                        state, cf = fire(state, np.full(n_dev, wms[j]))
                        # the split drain's blocking small-field fetch
                        jax.device_get((cf.counts, cf.lane_valid,
                                        cf.window_end_ticks,
                                        cf.value_sums))
                        last_wm = wms[j]
            jax.block_until_ready(mon[1])
            return time.perf_counter() - t0

        run_once()                               # compile + settle
        dt = min(run_once() for _ in range(3))
        return B * n_batches / dt

    def measure_fused_fire(K, dup, layout="direct", red=None,
                           packed=False, acc_layout="pane",
                           reduced=False):
        """The resident pipeline on the same firing stream: full fired
        megasteps throughout (crossings fire IN the scan), payload
        handles consumed lagged like executor.consume_fires.
        ``reduced`` surfaces ReducedFires — no payload stacking, the
        device_reduce topology's path."""
        from collections import deque as _dq

        spec = _spec(K, dup, layout=layout, red=red, packed=packed,
                     acc_layout=acc_layout)
        mega = build_window_megastep_fired(ctx, spec, K, reduced=reduced)
        n_disp = iters_f
        n_batches = n_disp * K
        batches, wms = make_stream(dup, np.random.default_rng(11),
                                   n_batches, layout)

        def consume(cf):
            jax.device_get((cf.counts, cf.lane_valid,
                            cf.window_end_ticks, cf.value_sums))

        def run_once():
            state = init_sharded_state(ctx, spec)
            t0 = time.perf_counter()
            handles = _dq()
            mon = None
            for g in range(n_disp):
                sel = range(g * K, (g + 1) * K)
                flat = [a for i in sel for a in batches[i]]
                wmv = np.tile(
                    np.asarray([wms[i] for i in sel], np.int32),
                    (n_dev, 1),
                )
                state, mon, fires = mega(state, *flat, wmv)
                handles.append(fires)
                if len(handles) > 1:
                    consume(handles.popleft())
            while handles:
                consume(handles.popleft())
            jax.block_until_ready(mon[1])
            return time.perf_counter() - t0

        run_once()                               # compile + settle
        dt = min(run_once() for _ in range(3))
        return B * n_batches / dt

    platform = jax.default_backend()
    pre_default = platform != "cpu"    # the executor's auto resolutions
    packed_default = platform != "cpu"
    detail = {"platform": platform, "B": B, "C": C,
              "iters": iters, "iters_firing": iters_f, "bpp": BPP,
              "n_devices": n_dev,
              "precombine_auto": pre_default,
              "packed_planes_auto": packed_default,
              "fusion": {}, "precombine": {},
              "fire_grid": {"split": {}, "fused": {}},
              "state_planes": {}}
    for dup in (0.0, 0.5, 0.9):
        row = {}
        for K in (1, 4, 8):
            eps, fire_ms = measure_quiet(K, dup, pre_default)
            row[f"K{K}"] = round(eps)
            if K == 1:
                row["fire_ms"] = round(fire_ms, 2)
        row["K4_vs_K1"] = round(row["K4"] / row["K1"], 2)
        row["K8_vs_K1"] = round(row["K8"] / row["K1"], 2)
        detail["fusion"][f"dup_{dup}"] = row
    for dup in (0.0, 0.5, 0.9):
        on, _ = measure_quiet(1, dup, True)
        off, _ = measure_quiet(1, dup, False)
        detail["precombine"][f"dup_{dup}"] = {
            "on": round(on), "off": round(off),
            "ratio": round(on / off, 2),
        }

    # the ISSUE-7 acceptance grid: both dispatch disciplines, both fire
    # payload modes, same K/dup cells. The headline acceptance compares
    # the device_reduce (on-chip-reduced) topology — the reference
    # northstar bench's path and BOTH disciplines' best case; the
    # compact-payload pair is stamped alongside for the general
    # (key-emitting) topology.
    detail["fire_grid"]["split_reduced"] = {}
    detail["fire_grid"]["fused_reduced"] = {}
    bests = {k: (None, 0.0) for k in
             ("split", "fused", "split_reduced", "fused_reduced")}
    for dup in (0.0, 0.5, 0.9):
        for K in (4, 8):
            cell = f"K{K}_dup_{dup}"
            for mode, eps in (
                ("split", measure_split_fire(K, dup)),
                ("fused", measure_fused_fire(K, dup)),
                ("split_reduced", measure_split_fire(K, dup,
                                                     reduced=True)),
                ("fused_reduced", measure_fused_fire(K, dup,
                                                     reduced=True)),
            ):
                detail["fire_grid"][mode][cell] = round(eps)
                if eps > bests[mode][1]:
                    bests[mode] = (cell, eps)
    best_split = bests["split_reduced"]
    best_fused = bests["fused_reduced"]
    detail["acceptance"] = {
        "topology": "device_reduce (on-chip-reduced fires)",
        "pr5_best_cell": {"cell": best_split[0],
                          "eps": round(best_split[1])},
        "fused_fire_best_cell": {"cell": best_fused[0],
                                 "eps": round(best_fused[1])},
        "ratio": round(best_fused[1] / max(best_split[1], 1.0), 2),
        "criterion": ">= 1.15",
    }
    detail["acceptance_compact"] = {
        "topology": "compact payloads (key-emitting sinks)",
        "pr5_best_cell": {"cell": bests["split"][0],
                          "eps": round(bests["split"][1])},
        "fused_fire_best_cell": {"cell": bests["fused"][0],
                                 "eps": round(bests["fused"][1])},
        "ratio": round(
            bests["fused"][1] / max(bests["split"][1], 1.0), 2
        ),
    }

    # state-plane sweep: one axis at a time off the base firing cell
    KB, DB = 8, 0.5
    i32 = wk.ReduceSpec("count", jnp.int32)
    plane_cells = {
        "base_f32_split": dict(),
        "packed": dict(packed=True),
        "i32_count": dict(red=i32),
        "packed_i32": dict(red=i32, packed=True),
        "hash_table": dict(layout="hash"),
        "slot_major": dict(acc_layout="slot"),
    }
    for name, kw in plane_cells.items():
        detail["state_planes"][name] = {
            "split_fire": round(measure_split_fire(KB, DB, **kw)),
            "fused_fire": round(measure_fused_fire(KB, DB, **kw)),
        }

    # structural stamp (ISSUE 11): grouped op counts, signature digest
    # and compiled memory_analysis bytes for three representative
    # ceiling kernels AT THE BENCH DIMS — so the perf artifact carries
    # a structural trajectory (did a sort appear? did the temp
    # footprint move?) next to events/s. Telemetry only: a stamp
    # failure never changes the bench verdict.
    try:
        from tools.lint.kernel_audit import kernel_structural_stamp

        sds = jax.ShapeDtypeStruct
        batch_sig = (sds((B,), jnp.uint32), sds((B,), jnp.uint32),
                     sds((B,), jnp.int32), sds((B,), jnp.float32),
                     sds((B,), jnp.bool_))
        wm_sig = sds((n_dev,), jnp.int32)
        wmv_sig = sds((n_dev, KB), jnp.int32)
        spec_a = _spec(KB, DB, pre_default)
        st = init_sharded_state(ctx, spec_a)
        detail["audit"] = {
            "update_K1": kernel_structural_stamp(
                build_window_update_step(ctx, spec_a),
                (st,) + batch_sig + (wm_sig,)),
            f"megastep_fired_K{KB}_reduced": kernel_structural_stamp(
                build_window_megastep_fired(ctx, spec_a, KB,
                                            reduced=True),
                (st,) + batch_sig * KB + (wmv_sig,)),
            "fire_reduced": kernel_structural_stamp(
                build_window_fire_reduced_step(ctx, spec_a),
                (st, wm_sig)),
        }
    except Exception as ex:  # noqa: BLE001 — never the bench verdict
        detail["audit"] = {"error": f"{type(ex).__name__}: {ex}"}

    print(json.dumps(
        {"config": "device_update_ceiling", "detail": detail}), flush=True)
    return (best_fused[1], best_split[1])


def run_resident_loop(total_events: int, cpu: bool):
    """Resident ring-drain discipline vs K-megastep dispatch (ISSUE 12):
    the same pre-staged FIRING stream as ``device_update_ceiling``'s
    fire grid, run through

    * ``fused_k8`` — the PR 7 best discipline: K=8
      ``build_window_megastep_fired`` megasteps, fire handles consumed
      lagged (one host dispatch per 8 batches), and
    * ``resident`` — the round-12 drain: ``build_window_resident_drain``
      at ring depth D=32, ONE count-gated dispatch retiring 32 staged
      slots (the steady-state full-ring drain the executor issues when
      the prefetch thread keeps the HBM ring ahead of the device).

    Matched dims throughout (same B/C/ring/slide/BPP, same stream
    generator, same lagged fire consumption), so the delta is purely the
    dispatch discipline. The headline compares the device_reduce
    (on-chip-reduced fires) topology — both disciplines' best case — and
    stamps the compact-payload pair alongside. ``dispatch`` carries host
    dispatches per 1k events for both paths: structural counts (the loop
    issues exactly n_batches/K and n_batches/D dispatches), so the >= 4x
    drop criterion is auditable from the artifact alone."""
    from collections import deque as _dq

    import jax
    import jax.numpy as jnp

    from flink_tpu.ops import window_kernels as wk
    from flink_tpu.parallel.mesh import MeshContext
    from flink_tpu.runtime.step import (
        WindowStageSpec,
        build_window_megastep_fired,
        build_window_resident_drain,
        init_sharded_state,
    )

    n_dev = len(jax.devices())
    ctx = MeshContext.create(n_dev, 128)
    B, C, RING, SLIDE = DEVICE_CEILING_BATCH, 4096, 9, 1000
    BPP = 4
    K, D = 8, 32            # PR 7 best megastep depth vs drain ring depth
    iters = max(128, min(8192, total_events // B))
    # full groups only for BOTH disciplines (steady state: the prefetch
    # ring stays ahead), so n_batches is a multiple of lcm(K, D) = D and
    # the dispatch-count ratio is structurally D/K
    n_groups = max(3, max(96, iters // 8) // D)
    n_batches = n_groups * D

    def _spec():
        return WindowStageSpec(
            win=wk.WindowSpec(SLIDE, SLIDE, ring=RING, fires_per_step=4),
            red=wk.ReduceSpec("sum", jnp.float32),
            capacity_per_shard=C, layout="direct", precombine=False,
        )

    def _keys(dup, rng):
        n_hot = int(B * dup)
        lo = np.concatenate([
            rng.integers(0, C - 1, B - n_hot),
            rng.integers(0, 64, n_hot),
        ]).astype(np.uint32)
        rng.shuffle(lo)
        return np.zeros(B, np.uint32), lo

    def make_stream(dup, rng):
        batches, wms = [], []
        for j in range(n_batches):
            p = j // BPP
            hi, lo = _keys(dup, rng)
            ts = np.full(B, p * SLIDE + SLIDE // 2, np.int32)
            batches.append(tuple(jax.device_put(a) for a in (
                hi, lo, ts, np.ones(B, np.float32), np.ones(B, bool),
            )))
            wms.append(np.int32(p * SLIDE - 1))
        return batches, wms

    def consume(cf):
        got = jax.device_get((cf.counts, cf.lane_valid,
                              cf.window_end_ticks, cf.value_sums))
        return max(int(np.asarray(got[1]).sum()), 1)

    def measure(group, build, dup, reduced):
        """One discipline at group size ``group``: n_batches/group
        dispatches over the shared stream, lagged fire consumption,
        best-of-3. Also samples fire-VISIBILITY latency — dispatch of
        the producing group to its fires host-fetched, the lag the
        discipline actually imposes on the emit path — weighted by
        live fire lanes, so p99 stamps beside events/s (ISSUE 16
        satellite: latency as a first-class acceptance axis)."""
        spec = _spec()
        step = build(spec, reduced)
        batches, wms = make_stream(dup, np.random.default_rng(11))
        n_disp = n_batches // group
        lat = []

        def run_once():
            state = init_sharded_state(ctx, spec)
            t0 = time.perf_counter()
            handles = _dq()
            mon = None
            for g in range(n_disp):
                sel = range(g * group, (g + 1) * group)
                flat = [a for i in sel for a in batches[i]]
                wmv = np.tile(
                    np.asarray([wms[i] for i in sel], np.int32),
                    (n_dev, 1),
                )
                if group == D:
                    # count-gated drain: full ring, all slots live
                    state, mon, fires = step(
                        state, *flat, wmv, np.int32(group)
                    )
                else:
                    state, mon, fires = step(state, *flat, wmv)
                handles.append((time.perf_counter(), fires))
                if len(handles) > 1:
                    t_d, cf = handles.popleft()
                    lat.append((consume(cf),
                                (time.perf_counter() - t_d) * 1e3))
            while handles:
                t_d, cf = handles.popleft()
                lat.append((consume(cf),
                            (time.perf_counter() - t_d) * 1e3))
            jax.block_until_ready(mon[1])
            return time.perf_counter() - t0

        run_once()                               # compile + settle
        lat.clear()                              # drop compile-run samples
        dt = min(run_once() for _ in range(3))
        return B * n_batches / dt, lat

    def m_fused(dup, reduced=True):
        return measure(
            K, lambda s, r: build_window_megastep_fired(ctx, s, K,
                                                        reduced=r),
            dup, reduced,
        )

    def m_resident(dup, reduced=True):
        return measure(
            D, lambda s, r: build_window_resident_drain(ctx, s, D,
                                                        reduced=r),
            dup, reduced,
        )

    detail = {
        "platform": jax.default_backend(), "B": B, "C": C,
        "k_megastep": K, "ring_depth": D, "n_batches": n_batches,
        "bpp": BPP, "n_devices": n_dev,
        "fused_k8": {}, "resident_d32": {},
        # structural dispatch accounting: the measurement loops above
        # issue EXACTLY these counts (full groups only), so the per-1k
        # numbers are exact, not sampled
        "dispatch": {
            "fused_k8_per_1k_events": round(1000.0 / (B * K), 4),
            "resident_per_1k_events": round(1000.0 / (B * D), 4),
            "drop": round(D / K, 2),
            "criterion": ">= 4x",
        },
    }
    from flink_tpu.metrics.latency import weighted_percentile

    def _p99(lat):
        p = weighted_percentile(lat, 99)
        return round(p, 2) if p is not None else None

    bests = {"fused": (None, 0.0, []), "resident": (None, 0.0, [])}
    for dup in (0.0, 0.5, 0.9):
        cell = f"dup_{dup}"
        ef, lf = m_fused(dup)
        er, lr = m_resident(dup)
        detail["fused_k8"][cell] = {"eps": round(ef),
                                    "p99_fire_ms": _p99(lf)}
        detail["resident_d32"][cell] = {"eps": round(er),
                                        "p99_fire_ms": _p99(lr)}
        if ef > bests["fused"][1]:
            bests["fused"] = (cell, ef, lf)
        if er > bests["resident"][1]:
            bests["resident"] = (cell, er, lr)
    # compact-payload (key-emitting sink) pair at the base cell, stamped
    # for the general topology next to the reduced headline
    detail["compact_dup_0.5"] = {
        "fused_k8": round(m_fused(0.5, reduced=False)[0]),
        "resident_d32": round(m_resident(0.5, reduced=False)[0]),
    }
    res_p99 = _p99(bests["resident"][2])
    fused_p99 = _p99(bests["fused"][2])
    detail["acceptance"] = {
        "topology": "device_reduce (on-chip-reduced fires)",
        "pr7_fused_best_cell": {"cell": bests["fused"][0],
                                "eps": round(bests["fused"][1]),
                                "p99_fire_ms": fused_p99},
        "resident_best_cell": {"cell": bests["resident"][0],
                               "eps": round(bests["resident"][1]),
                               "p99_fire_ms": res_p99},
        "ratio": round(
            bests["resident"][1] / max(bests["fused"][1], 1.0), 2
        ),
        "criterion": ">= 1.15",
        "dispatch_drop": round(D / K, 2),
        "dispatch_criterion": ">= 4x",
    }
    print(json.dumps(
        {"config": "resident_loop", "detail": detail}), flush=True)
    return (bests["resident"][1], bests["fused"][1], res_p99, fused_p99)


def run_while_drain(total_events: int, cpu: bool):
    """Early-exit while drain vs the count-gated scan drain (ISSUE 20):
    matched dims (B=512 / C=4096 / scan ring depth D=32, the
    ``run_resident_loop`` firing stream), two dispatch disciplines:

    * ``scan_d32`` — ``build_window_resident_drain`` at D=32, one
      count-gated dispatch per 32 staged slots (the round-12 steady
      state), and
    * ``while_ms64`` — ``build_window_while_drain`` at
      max_slots=2xD=64 (the executor's default
      pipeline.while-drain.max-slots resolution): the publish cursor
      runs ahead of the drain base, so one dispatch retires the whole
      64-slot burst the accumulator groups under sustained ingest.

    The dispatch accounting is structural (full groups only):
    1000/(B*D) vs 1000/(B*MS) host dispatches per 1k events, a 2x cut
    against the >= 1.5x criterion. The throughput criterion is parity
    or better (>= 1.0x) — the while lowering must not tax the per-slot
    body — and fire-VISIBILITY p99 stamps beside events/s for both
    disciplines (the while drain holds fires until the loop exits, so
    its emit lag is the number the criterion guards)."""
    from collections import deque as _dq

    import jax
    import jax.numpy as jnp

    from flink_tpu.metrics.latency import weighted_percentile
    from flink_tpu.ops import window_kernels as wk
    from flink_tpu.parallel.mesh import MeshContext
    from flink_tpu.runtime.step import (
        WindowStageSpec,
        build_window_resident_drain,
        build_window_while_drain,
        init_sharded_state,
    )

    n_dev = len(jax.devices())
    ctx = MeshContext.create(n_dev, 128)
    B, C, RING, SLIDE = DEVICE_CEILING_BATCH, 4096, 9, 1000
    BPP = 4
    D = 32                  # scan ring depth (matched with PR 12)
    MS = 2 * D              # while-drain bound: the executor default
    iters = max(128, min(8192, total_events // B))
    n_groups = max(2, max(96, iters // 8) // MS)
    n_batches = n_groups * MS

    def _spec():
        return WindowStageSpec(
            win=wk.WindowSpec(SLIDE, SLIDE, ring=RING, fires_per_step=4),
            red=wk.ReduceSpec("sum", jnp.float32),
            capacity_per_shard=C, layout="direct", precombine=False,
        )

    def _keys(rng, dup=0.5):
        n_hot = int(B * dup)
        lo = np.concatenate([
            rng.integers(0, C - 1, B - n_hot),
            rng.integers(0, 64, n_hot),
        ]).astype(np.uint32)
        rng.shuffle(lo)
        return np.zeros(B, np.uint32), lo

    def make_stream(rng):
        batches, wms = [], []
        for j in range(n_batches):
            p = j // BPP
            hi, lo = _keys(rng)
            ts = np.full(B, p * SLIDE + SLIDE // 2, np.int32)
            batches.append(tuple(jax.device_put(a) for a in (
                hi, lo, ts, np.ones(B, np.float32), np.ones(B, bool),
            )))
            wms.append(np.int32(p * SLIDE - 1))
        return batches, wms

    def consume(cf):
        got = jax.device_get((cf.counts, cf.lane_valid,
                              cf.window_end_ticks, cf.value_sums))
        return max(int(np.asarray(got[1]).sum()), 1)

    def measure(group, step, is_while):
        batches, wms = make_stream(np.random.default_rng(11))
        n_disp = n_batches // group
        lat = []

        def run_once():
            state = init_sharded_state(ctx, spec)
            t0 = time.perf_counter()
            handles = _dq()
            mon = None
            for g in range(n_disp):
                sel = range(g * group, (g + 1) * group)
                flat = [a for i in sel for a in batches[i]]
                wmv = np.tile(
                    np.asarray([wms[i] for i in sel], np.int32),
                    (n_dev, 1),
                )
                if is_while:
                    # steady state: the publish cursor committed the
                    # whole staged burst (absolute seqs; base = the
                    # group's first ring seq)
                    base = g * group
                    state, mon, fires, _consumed = step(
                        state, *flat, wmv,
                        np.full(1, base + group, np.int32),
                        np.int32(base), np.int32(group),
                    )
                else:
                    state, mon, fires = step(
                        state, *flat, wmv, np.int32(group)
                    )
                handles.append((time.perf_counter(), fires))
                if len(handles) > 1:
                    t_d, cf = handles.popleft()
                    lat.append((consume(cf),
                                (time.perf_counter() - t_d) * 1e3))
            while handles:
                t_d, cf = handles.popleft()
                lat.append((consume(cf),
                            (time.perf_counter() - t_d) * 1e3))
            jax.block_until_ready(mon[1])
            return time.perf_counter() - t0

        run_once()                               # compile + settle
        lat.clear()
        dt = min(run_once() for _ in range(3))
        return B * n_batches / dt, lat

    def _p99(lat):
        p = weighted_percentile(lat, 99)
        return round(p, 2) if p is not None else None

    spec = _spec()
    scan_eps, scan_lat = measure(
        D, build_window_resident_drain(ctx, spec, D, reduced=True),
        False,
    )
    while_eps, while_lat = measure(
        MS, build_window_while_drain(ctx, spec, MS, reduced=True),
        True,
    )
    scan_p99, while_p99 = _p99(scan_lat), _p99(while_lat)
    detail = {
        "platform": jax.default_backend(), "B": B, "C": C,
        "scan_ring_depth": D, "while_max_slots": MS,
        "n_batches": n_batches, "bpp": BPP, "n_devices": n_dev,
        "scan_d32": {"eps": round(scan_eps), "p99_fire_ms": scan_p99},
        "while_ms64": {"eps": round(while_eps),
                       "p99_fire_ms": while_p99},
        # structural dispatch accounting (full groups only — exact)
        "dispatch": {
            "scan_per_1k_events": round(1000.0 / (B * D), 4),
            "while_per_1k_events": round(1000.0 / (B * MS), 4),
            "cut": round(MS / D, 2),
            "criterion": ">= 1.5x fewer",
        },
        "throughput_ratio": round(while_eps / max(scan_eps, 1.0), 2),
        "throughput_criterion": ">= 1.0",
    }
    print(json.dumps(
        {"config": "while_drain", "detail": detail}), flush=True)
    return while_eps, scan_eps, while_p99, scan_p99


def run_dcn_resident(total_events: int, cpu: bool):
    """Per-host DCN-resident mode vs the single-step lockstep fallback
    (ISSUE 20b). The honest form is a two-process ensemble (each host
    stacks up to ring-depth locally-polled chunks into one drain per
    lockstep round; >= 1.3x wall-clock criterion vs lockstep); on
    backends without cross-process collectives (this container's CPU
    runtime) the row degrades to a SINGLE-process measurement of the
    same two runners — the same drain kernel, real collectives across
    the local shards — and stamps ``mode`` so the artifact says which
    topology produced the numbers. Cycle counts are exact either way:
    the resident runner's cycles are drain dispatches, the lockstep
    runner's are single-chunk rounds, so the dispatch cut is auditable
    from the artifact alone."""
    import os

    import jax

    from flink_tpu.runtime.dcn import (
        DCNJobSpec,
        GeneratorPartitionSource,
        runner_for_spec,
    )

    n_keys, ts_div, win_ms = 977, 16, 1000
    total = max(8192, min(total_events, 40_000))

    def source_factory(pid, nproc, _total=total):
        per_host = n_keys // nproc

        def gen(offset, n):
            idx = np.arange(offset, offset + n, dtype=np.int64)
            return (pid + nproc * (idx % per_host), idx // ts_div,
                    np.ones(n, np.float32))

        return GeneratorPartitionSource(gen, _total)

    def _spec(resident):
        return DCNJobSpec(
            source_factory=source_factory,
            size_ms=win_ms,
            capacity_per_shard=2048,
            max_parallelism=64,
            batch_per_host=2048,
            fires_per_step=4,
            resident=resident,
            resident_ring_depth=4,
        )

    def run_single(resident):
        r = runner_for_spec(_spec(resident), 0, 1)
        t0 = time.perf_counter()
        out = r.run()
        dt = time.perf_counter() - t0
        return total / dt, int(out["cycles"])

    def _two_proc_supported():
        import sys as _sys

        tests_dir = os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "tests")
        if tests_dir not in _sys.path:
            _sys.path.insert(0, tests_dir)
        try:
            from dcn_probe import multiprocess_collectives_supported
            return multiprocess_collectives_supported()
        except Exception:  # noqa: BLE001 — probe absent: assume not
            return False

    def run_two_proc(builder):
        """One 2-process ensemble (tests/dcn_jobs.py builders — the
        same specs the gated ensemble tests run); wall-clock covers the
        whole run, cycles come from the workers' stats line."""
        import sys as _sys

        repo = os.path.dirname(os.path.abspath(__file__))
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            coord = f"127.0.0.1:{s.getsockname()[1]}"
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        outs = [os.path.join(tempfile.mkdtemp(), f"out-{p}.npz")
                for p in range(2)]
        t0 = time.perf_counter()
        procs = [subprocess.Popen(
            [_sys.executable, "-m", "flink_tpu.runtime.dcn",
             "--coordinator", coord, "--num-processes", "2",
             "--process-id", str(p), "--builder",
             os.path.join(repo, "tests", "dcn_jobs.py") + ":" + builder,
             "--out", outs[p]],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        ) for p in range(2)]
        cycles = None
        for p in procs:
            out, _ = p.communicate(timeout=600)
            if p.returncode != 0:
                raise RuntimeError(out.decode(errors="replace")[-2000:])
            for line in out.decode(errors="replace").splitlines():
                if line.startswith("{"):
                    cycles = json.loads(line)["cycles"]
        dt = time.perf_counter() - t0
        # two hosts x TOTAL_PER_HOST records (tests/dcn_jobs.py)
        return 80_000 / dt, int(cycles)

    if _two_proc_supported():
        import socket
        import subprocess
        import tempfile

        res_eps, res_cycles = run_two_proc("two_host_window_resident")
        lock_eps, lock_cycles = run_two_proc("two_host_window")
        mode, note = "two_process", "real cross-process ensemble"
    else:
        # compile-and-settle once per discipline, then measure
        run_single(True)
        res_eps, res_cycles = run_single(True)
        run_single(False)
        lock_eps, lock_cycles = run_single(False)
        mode = "single_process_fallback"
        note = ("cross-process collectives unavailable on this "
                "backend; same kernels, one host over the local mesh")
    detail = {
        "platform": jax.default_backend(),
        "mode": mode,
        "note": note,
        "total_events": total,
        "resident": {"eps": round(res_eps), "cycles": res_cycles},
        "lockstep": {"eps": round(lock_eps), "cycles": lock_cycles},
        "cycle_cut": round(lock_cycles / max(res_cycles, 1), 2),
        "throughput_ratio": round(res_eps / max(lock_eps, 1.0), 2),
        "criterion": ">= 1.3x vs lockstep (two-process); cycle cut "
                     "~ring-depth structurally",
    }
    print(json.dumps(
        {"config": "dcn_resident", "detail": detail}), flush=True)
    return res_eps, lock_eps, res_cycles, lock_cycles


def run_chained_stages(total_events: int, cpu: bool):
    """Chained 2-stage drain vs the single-stage resident drain at
    matched dims (ISSUE 16): B=512 / C=4096 / ring depth D=32, the same
    firing stream, compact fire payload on BOTH sides (the chained
    drain's final stage emits compact fires, so the single-stage
    comparator runs ``reduced=False`` for a like-for-like topology).

    The chained discipline is ``build_window_chained_drain`` over
    (1s tumbling sum) -> device edge -> (4s tumbling rollup): the
    drain's stacked stage-1 fires pack once per drain into the edge
    lanes and feed one stage-2 update + advance (the per-drain stage
    tail). The stream is the multi-level-rollup shape the chain
    exists for: a bounded key population at the aggregation level
    (256 distinct keys + a 64-key hot set, dup ~0.5) — both
    disciplines consume the SAME stream, so the ratio isolates the
    cost of carrying the second stage. The acceptance criterion is
    <15% throughput cost, and fire-VISIBILITY p50/p99 (dispatch of
    the producing drain to fires host-fetched, lagged one dispatch —
    the emit-path lag the discipline imposes) stamps beside
    events/s."""
    from collections import deque as _dq

    import jax
    import jax.numpy as jnp

    from flink_tpu.metrics.latency import weighted_percentile
    from flink_tpu.ops import window_kernels as wk
    from flink_tpu.parallel.mesh import MeshContext
    from flink_tpu.runtime.step import (
        WindowStageSpec,
        build_window_chained_drain,
        build_window_resident_drain,
        init_sharded_state,
    )

    n_dev = len(jax.devices())
    ctx = MeshContext.create(n_dev, 128)
    B, C, RING, SLIDE = DEVICE_CEILING_BATCH, 4096, 9, 1000
    BPP, D = 4, 32
    ROLLUP = 4               # stage-2 tumbling size, in stage-1 panes
    KEYSPACE = 256           # distinct keys at the rollup level
    # per-DRAIN edge budget: one drain closes D/BPP = 8 stage-1 panes,
    # each firing <= KEYSPACE distinct keys -> <= 2048 edge records per
    # drain (verified drop-free: edge overflow counts into the stage-2
    # dropped_capacity counter, which stays 0 on this stream)
    EX_LANES = 2048
    iters = max(128, min(8192, total_events // B))
    n_groups = max(3, max(96, iters // 8) // D)
    n_batches = n_groups * D

    spec1 = WindowStageSpec(
        win=wk.WindowSpec(SLIDE, SLIDE, ring=RING, fires_per_step=4),
        red=wk.ReduceSpec("sum", jnp.float32),
        capacity_per_shard=C, layout="direct", precombine=False,
    )
    # stage-2 ring sized by the StageGraph.plan_specs rule: the stage
    # tail advances once per drain, so the ring absorbs a whole drain's
    # worth of upstream fires (D slots x F pane-ends, the catch-up
    # worst case) on top of the live window span
    s2 = ROLLUP * SLIDE
    ppw = 1
    slack = (D * spec1.win.fires_per_step * SLIDE) // s2 + 2
    spec2 = WindowStageSpec(
        win=wk.WindowSpec(s2, s2, ring=max(8, 2 * ppw + slack, ppw + 3),
                          fires_per_step=4),
        red=wk.ReduceSpec("sum", jnp.float32),
        capacity_per_shard=C, layout="direct", precombine=False,
    )

    def _keys(rng):
        n_hot = B // 2
        lo = np.concatenate([
            rng.integers(0, KEYSPACE, B - n_hot),
            rng.integers(0, 64, n_hot),
        ]).astype(np.uint32)
        rng.shuffle(lo)
        return np.zeros(B, np.uint32), lo

    def make_stream(rng):
        batches, wms = [], []
        for j in range(n_batches):
            p = j // BPP
            hi, lo = _keys(rng)
            ts = np.full(B, p * SLIDE + SLIDE // 2, np.int32)
            batches.append(tuple(jax.device_put(a) for a in (
                hi, lo, ts, np.ones(B, np.float32), np.ones(B, bool),
            )))
            wms.append(np.int32(p * SLIDE - 1))
        return batches, wms

    def consume(cf):
        got = jax.device_get((cf.counts, cf.lane_valid,
                              cf.window_end_ticks, cf.value_sums))
        return max(int(np.asarray(got[1]).sum()), 1)

    def prep(step, init_state):
        """Compile + settle one discipline; returns (run_once, lat) so
        the timed reps of BOTH disciplines can interleave — host load
        drift then hits single and chained alike instead of biasing
        whichever ran second."""
        batches, wms = make_stream(np.random.default_rng(11))
        n_disp = n_batches // D
        lat = []

        def run_once():
            state = init_state()
            t0 = time.perf_counter()
            handles = _dq()
            mon = None
            for g in range(n_disp):
                sel = range(g * D, (g + 1) * D)
                flat = [a for i in sel for a in batches[i]]
                wmv = np.tile(
                    np.asarray([wms[i] for i in sel], np.int32),
                    (n_dev, 1),
                )
                state, mon, fires = step(state, *flat, wmv, np.int32(D))
                handles.append((time.perf_counter(), fires))
                if len(handles) > 1:
                    t_d, cf = handles.popleft()
                    lat.append((consume(cf),
                                (time.perf_counter() - t_d) * 1e3))
            while handles:
                t_d, cf = handles.popleft()
                lat.append((consume(cf),
                            (time.perf_counter() - t_d) * 1e3))
            jax.block_until_ready(mon[1])
            return time.perf_counter() - t0

        run_once()                               # compile + settle
        lat.clear()                              # drop compile-run samples
        return run_once, lat

    def _pct(lat, q):
        p = weighted_percentile(lat, q)
        return round(p, 2) if p is not None else None

    single_step = build_window_resident_drain(ctx, spec1, D,
                                              reduced=False)
    run_s, s_lat = prep(
        single_step, lambda: init_sharded_state(ctx, spec1)
    )
    chained_step = build_window_chained_drain(
        ctx, (spec1, spec2), D, exchange_lanes=EX_LANES
    )
    run_c, c_lat = prep(
        chained_step,
        lambda: (init_sharded_state(ctx, spec1),
                 init_sharded_state(ctx, spec2)),
    )
    t_s, t_c = [], []
    for _ in range(4):
        t_s.append(run_s())
        t_c.append(run_c())
    s_eps = B * n_batches / min(t_s)
    c_eps = B * n_batches / min(t_c)

    detail = {
        "platform": jax.default_backend(), "B": B, "C": C,
        "ring_depth": D, "n_batches": n_batches, "bpp": BPP,
        "n_devices": n_dev, "rollup_panes": ROLLUP,
        "keyspace": KEYSPACE, "exchange_lanes": EX_LANES,
        "single_stage": {"events_per_s": round(s_eps),
                         "p50_fire_ms": _pct(s_lat, 50),
                         "p99_fire_ms": _pct(s_lat, 99)},
        "chained_2stage": {"events_per_s": round(c_eps),
                           "p50_fire_ms": _pct(c_lat, 50),
                           "p99_fire_ms": _pct(c_lat, 99)},
        "acceptance": {
            "ratio": round(c_eps / max(s_eps, 1.0), 3),
            "criterion": ">= 0.85 (<15% throughput cost for the "
                         "second chained stage)",
        },
    }
    print(json.dumps(
        {"config": "chained_stages", "detail": detail}), flush=True)
    return (s_eps, c_eps, _pct(s_lat, 99), _pct(c_lat, 99))


def run_scaling_cell(total_events: int, n_devices=None):
    """ONE cell of the chips-vs-events/s curve (ISSUE 13): the sharded
    resident drain (``build_window_sharded_drain``) at THIS process's
    device count, matched dims with ``run_resident_loop`` (same B per
    shard / C / ring / slide, ring depth D=32), pre-routed per-shard
    batches so every staged row lands on its owning shard — weak
    scaling, each chip drains its own full ring slice. The caller
    (``bench.py --scaling``) forces the device count per child process;
    this function just measures where it lands and returns
    (n_devices, events/s, p99_fire_ms) — the p99 is the weighted
    dispatch-to-consume fire latency over emitted lanes (ISSUE 17:
    both halves of the north-star metric stamped in the headline)."""
    from collections import deque as _dq

    import jax
    import jax.numpy as jnp

    from flink_tpu.core.keygroups import assign_to_key_group
    from flink_tpu.ops import window_kernels as wk
    from flink_tpu.ops.hashing import route_hash
    from flink_tpu.parallel.mesh import MeshContext
    from flink_tpu.runtime.step import (
        WindowStageSpec,
        build_window_sharded_drain,
        init_sharded_state,
    )

    # virtual-CPU path: the caller forces the process device count and
    # n_devices stays None. Real-device path (ISSUE 20 satellite): the
    # caller passes n_devices to slice the FIRST n chips of the real
    # mesh — distinct physical cores, so the curve measures genuine
    # chip-count speedup, not shard_map partitioning overhead
    n = int(n_devices) if n_devices else len(jax.devices())
    MAXP = 128
    ctx = MeshContext.create(n, MAXP)
    B, C, RING, SLIDE = DEVICE_CEILING_BATCH, 4096, 9, 1000
    D = 32
    spec = WindowStageSpec(
        win=wk.WindowSpec(SLIDE, SLIDE, ring=RING, fires_per_step=4),
        red=wk.ReduceSpec("sum", jnp.float32),
        capacity_per_shard=C, layout="direct", precombine=False,
    )
    drain = build_window_sharded_drain(ctx, spec, D, reduced=True)

    # per-shard key pools: draw a lo pool, route it with the SAME
    # host-side key-group math the ingest planner uses, and bucket by
    # owning shard — staged rows are then sampled per shard from its own
    # bucket, so the drain's ownership mask never drops a row and the
    # events/s denominator is exact
    rng = np.random.default_rng(11)
    pool = rng.integers(0, C, 1 << 16).astype(np.uint32)
    kg = assign_to_key_group(
        route_hash(np.zeros_like(pool), pool, np), MAXP, np)
    shard_of = ctx.shard_of_key_groups(kg)
    buckets = [pool[shard_of == s] for s in range(n)]
    assert all(len(b) for b in buckets), "key pool missed a shard"

    iters = max(2 * D, min(4096, total_events // (B * n)))
    n_batches = (iters // D) * D
    batches, wmvs = [], []
    for j in range(n_batches):
        p = j // 4                      # BPP=4 batches per pane
        lo = np.stack([
            rng.choice(buckets[s], B) for s in range(n)
        ])
        batches.append(tuple(jax.device_put(a) for a in (
            np.zeros((n, B), np.uint32), lo,
            np.full((n, B), p * SLIDE + SLIDE // 2, np.int32),
            np.ones((n, B), np.float32), np.ones((n, B), bool),
        )))
        wmvs.append(np.int32(p * SLIDE - 1))

    def consume(cf):
        got = jax.device_get((cf.counts, cf.lane_valid,
                              cf.window_end_ticks, cf.value_sums))
        return int(np.asarray(got[1]).sum())

    counts = np.full(n, D, np.int32)    # full ring, every shard live

    def run_once(lat=None):
        state = init_sharded_state(ctx, spec)
        t0 = time.perf_counter()
        handles = _dq()
        mon = None
        for g in range(n_batches // D):
            sel = range(g * D, (g + 1) * D)
            flat = [a for i in sel for a in batches[i]]
            wmv = np.tile(
                np.asarray([wmvs[i] for i in sel], np.int32), (n, 1))
            state, mon, fires = drain(state, *flat, wmv, counts)
            handles.append((fires, time.perf_counter()))
            if len(handles) > 1:
                cf, t_pub = handles.popleft()
                lanes = consume(cf)
                if lat is not None and lanes:
                    lat.append(
                        (lanes, (time.perf_counter() - t_pub) * 1e3))
        while handles:
            cf, t_pub = handles.popleft()
            lanes = consume(cf)
            if lat is not None and lanes:
                lat.append((lanes, (time.perf_counter() - t_pub) * 1e3))
        jax.block_until_ready(mon[1])
        return time.perf_counter() - t0

    from flink_tpu.metrics.latency import weighted_percentile

    run_once()                           # compile + settle
    lat = []
    dt = min(run_once(lat) for _ in range(3))
    p99 = weighted_percentile(lat, 99)
    return n, n * B * n_batches / dt, (
        round(p99, 2) if p99 is not None else None)


def run_tiered(total_events: int, cpu: bool):
    """Tiered key-group state under a cold-tail working set (ISSUE 18):
    the same Zipf-skewed keyed windowed sum run twice through the full
    executor — once all-resident (the baseline every earlier PR ships)
    and once with ``state.tiers.resident-key-groups`` capping the HBM
    hot set at BUDGET of MAXP key-groups (~13x more groups than the
    budget, inside the >= 10x acceptance floor). The stream is the shape
    the
    tier exists for: a handful of Zipf-hot keys carry ~90%% of the
    traffic and hash into few enough groups to fit the budget, while
    the cold tail sprays the whole group space — so the manager must
    keep the hot set pinned, demote the tail to the host pane stores,
    and promote ahead of each pane close off the watermark.

    subject = tiered eps, baseline = all-resident eps; the acceptance
    fraction (>= 0.6x all-resident) stamps in the detail JSON next to
    p99 fire latency and the prefetch hit/miss counters pulled from the
    job's tiers report."""
    from flink_tpu import StreamExecutionEnvironment
    from flink_tpu.core.config import Configuration
    from flink_tpu.core.time import TimeCharacteristic
    from flink_tpu.runtime.sinks import CountingSink
    from flink_tpu.runtime.sources import GeneratorSource

    MAXP, BUDGET = 64, 5
    N_KEYS = 4096
    WINDOW_MS = 1000
    BATCH = 32768
    ZIPF_A = 2.5
    total = int(min(total_events, 2_000_000))

    # Zipf(2.5) key pool, drawn once: top-4 keys ~95% of traffic; the
    # rest spreads over N_KEYS keys -> all MAXP key-groups get touched
    rng = np.random.default_rng(7)
    pool = (np.minimum(rng.zipf(ZIPF_A, size=total), N_KEYS) - 1).astype(
        np.int64)

    def gen(offset, n):
        idx = np.arange(offset, offset + n)
        cols = {
            "key": pool[offset:offset + n],
            "value": np.ones(n, np.float32),
        }
        # one pane per batch: steady watermark advance -> ~60 pane
        # closes over the run, each a promote-ahead opportunity
        return cols, (idx // (BATCH // 8)) * (WINDOW_MS // 8)

    def run(budget):
        # best-of-2 per config: the first rep pays the XLA compiles
        # (the tiered build is a distinct kernel family, so compile
        # cost would otherwise masquerade as tier overhead); the claim
        # is SUSTAINED throughput, which is the second rep
        opts = {}
        if budget:
            opts = {"state.tiers.resident-key-groups": budget}
        best = None
        for _ in range(2):
            env = StreamExecutionEnvironment(Configuration(opts))
            env.set_parallelism(1)
            env.set_max_parallelism(MAXP)
            env.set_stream_time_characteristic(
                TimeCharacteristic.EventTime)
            env.set_state_capacity(1 << 14)
            env.batch_size = BATCH
            sink = CountingSink()
            t0 = time.perf_counter()
            (
                env.add_source(GeneratorSource(gen, total=total))
                .key_by(lambda c: c["key"])
                .time_window(WINDOW_MS)
                .sum(lambda c: c["value"])
                .add_sink(sink)
            )
            job = env.execute(f"tiered-bench-budget{budget}")
            dt = time.perf_counter() - t0
            assert sink.value_sum == total, (sink.value_sum, total)
            if best is not None and total / dt <= best["events_per_s"]:
                continue
            p99 = job.metrics.fire_latency_pct(99)
            rep = env._pipeline_report()
            best = {
                "events_per_s": total / dt,
                "p99_fire_ms": (round(p99, 2) if p99 is not None
                                else None),
                "tiers": (rep.get("tiers") if isinstance(rep, dict)
                          else None),
            }
        best["events_per_s"] = round(best["events_per_s"])
        return best

    base = run(0)
    tiered = run(BUDGET)
    ratio = tiered["events_per_s"] / max(base["events_per_s"], 1)
    detail = {
        "events": total, "batch": BATCH, "n_keys": N_KEYS,
        "max_parallelism": MAXP, "resident_budget": BUDGET,
        "group_to_budget_ratio": round(MAXP / BUDGET, 1),
        "zipf_a": ZIPF_A,
        "all_resident": base,
        "tiered": tiered,
        "acceptance": {
            "ratio": round(ratio, 3),
            "criterion": ">= 0.6 of all-resident throughput at >= 10x "
                         "more key-groups than the resident budget",
        },
    }
    print(json.dumps({"config": "tiered_state", "detail": detail}),
          flush=True)
    t = tiered["tiers"] or {}
    return (tiered["events_per_s"], base["events_per_s"],
            tiered["p99_fire_ms"],
            {"prefetch_hits": int(t.get("prefetch_hits", 0)),
             "prefetch_misses": int(t.get("prefetch_misses", 0)),
             "demotes": int(t.get("demotes", 0)),
             "promotes": int(t.get("promotes", 0)),
             "tier_faults": int(t.get("faults", 0))})


# ---------------------------------------------------- self-tuning drill
def run_selftune(total_events: int, cpu: bool):
    """Self-healing runtime drill (ISSUE 19, ``bench.py --selftune``):
    a skew-shifting keyed windowed stream on a 4-shard TIERED mesh
    (``state.tiers.resident-key-groups`` caps each shard's HBM hot set
    at BUDGET key-groups). Each phase concentrates ALL traffic on 16
    hot groups packed inside HALF the mesh's default ranges — phase A
    in shards 0-1, then (mid-run) migrating into shards 2-3. Eight hot
    groups per shard against a budget of six means a quarter of every
    batch dives to the overflow ring and the host pane stores while
    the tier planner churns the remainder — host-bound degradation
    that bites even on the shared-core virtual CPU mesh.

    Three runs, same config modulo keys/controller:

      balanced       uniform keys over the 4*BUDGET default-resident
                     groups (the throughput the slicing should buy
                     back: zero tier faults, sharded route),
                     controller off
      skewed, off    the degradation floor: the hot set fights two
                     shards' residency budgets end to end
      skewed, on     the controller's rebalance arm re-slices the
                     shard ranges LIVE (heat-balanced contiguous
                     partition through the savepoint-cut rescale) —
                     once per hot phase — spreading the hot groups 4
                     per shard, back under every budget, WITHOUT a
                     restart. The healed slicing reproduces the
                     balanced run's residency profile exactly, so the
                     recovered tail rate is directly comparable.

    Measured: steady tail throughput (the last 15%% of each run's
    record progress, sampler slope; the controller-on window
    additionally starts after the last rebalance settles, so the cut
    and its recompile burst are not billed against the recovered
    rate). Acceptance: controller-on tail >= 0.8x balanced while
    controller-off stays under the same bar. Returns
    (ratio_on, ratio_off, p99_fire_ms, controller counters)."""
    import jax

    from flink_tpu import StreamExecutionEnvironment
    from flink_tpu.core.config import Configuration
    from flink_tpu.core.keygroups import assign_to_key_group
    from flink_tpu.core.time import TimeCharacteristic
    from flink_tpu.runtime.sinks import CountingSink
    from flink_tpu.runtime.sources import GeneratorSource

    N_DEV = 4
    if len(jax.devices()) < N_DEV:
        raise RuntimeError(
            f"selftune needs a {N_DEV}-device mesh; found "
            f"{len(jax.devices())} (bench.py --selftune forces the "
            f"virtual CPU mesh via XLA_FLAGS before JAX initializes)"
        )
    MAXP = 64
    BUDGET = 6                 # resident key-groups per shard
    B = 4096
    WINDOW = 10_000
    TAIL = 0.15
    SETTLE_S = 2.5             # post-rebalance settle before the tail
    total = int(min(total_events, 2_000_000))

    # key-group census: the identity key encode (hi=0, lo=k) means
    # group(k) = murmur3(k) % MAXP — the SAME math the ingest planner
    # uses, so hot keys can be picked per TARGET GROUP
    cand = np.arange(4096, dtype=np.int64)
    kg = assign_to_key_group(cand.astype(np.uint32), MAXP, np)

    def keys_in(groups, per_group):
        out = []
        for g in groups:
            ks = cand[kg == g]
            if len(ks) < per_group:
                raise RuntimeError(
                    f"key-group {g} has only {len(ks)} candidate keys")
            out.append(ks[:per_group])
        return np.concatenate(out)

    # default equal slicing of 64 groups over 4 shards: shard 0 owns
    # [0..15], shard 3 owns [48..63], and each shard's initial
    # resident set is the FIRST `BUDGET` groups of its range. Each
    # phase's 16 hot groups interleave across HALF the mesh (the
    # greedy prefix partition needs cut points between them), 8 per
    # shard against a budget of 6: phase A lives in shards 0-1's
    # ranges, phase B in shards 2-3's — the mid-run migration the
    # controller must chase. The healed 4-per-shard spread fits every
    # budget with slack, so an imperfect first re-slice (stale EWMA
    # heat from the previous phase skews the prefix boundaries) still
    # lands every hot group resident.
    HOT_A = tuple(range(1, 32, 2))
    HOT_B = tuple(range(33, 64, 2))
    # the balanced pool covers exactly the default-resident groups, so
    # the baseline runs fault-free without any planner help
    RESIDENT0 = tuple(
        s * (MAXP // N_DEV) + i for s in range(N_DEV) for i in range(BUDGET)
    )
    hot_a = keys_in(HOT_A, 2)
    hot_b = keys_in(HOT_B, 2)
    balanced_keys = keys_in(RESIDENT0, 4)

    rng = np.random.default_rng(11)
    cold_pool = balanced_keys[rng.integers(0, len(balanced_keys), total)]
    hot_pick = rng.integers(0, len(hot_a), total)
    # the migration lands at one THIRD: detecting + re-slicing phase A
    # is cheap (no stale heat yet), while phase B pays the full chase —
    # stale decay, re-slice, recompile, tier re-promotion — and the
    # recovered tail must still have runway to measure
    skew_pool = np.where(np.arange(total) < total // 3,
                         hot_a[hot_pick], hot_b[hot_pick])

    def gen_of(pool):
        def gen(offset, n):
            idx = np.arange(offset, offset + n)
            cols = {
                "key": pool[offset:offset + n],
                "value": np.ones(n, np.float32),
            }
            # steady watermark advance: one pane per batch
            return cols, (idx // (B // 8)) * (WINDOW // 8)
        return gen

    BASE_CFG = {
        "pipeline.prefetch": "on",
        "pipeline.device-staging": "on",
        "pipeline.resident-loop": "on",
        "pipeline.ring-depth": 4,
        "pipeline.data-parallel": "on",
        # the tier is the degradation mechanism: a phase's 2*BUDGET hot
        # groups crammed into one shard's range can never all be
        # resident, so half the traffic rides the overflow ring into
        # the host pane stores until the controller re-slices
        "state.tiers.resident-key-groups": BUDGET,
        "state.tiers.min-dwell-cycles": 1,
        "state.tiers.max-swaps-per-cycle": 4,
        "observability.drain-stats": True,
        "observability.kg-stats": True,
        # fast heat: the drill's phases are seconds apart, not
        # minutes, and stale heat from the finished phase must decay
        # before it can distort the next re-slice's prefix boundaries
        # (one sample at alpha 0.8 leaves 20% stale weight — small
        # enough that the greedy prefix still fits every budget)
        "observability.kg-heat-alpha": 0.8,
        "keys.reverse-map": False,
    }
    CTL_CFG = {
        "controller.enabled": True,
        "controller.interval-cycles": 8,
        "controller.probation-cycles": 8,
        "controller.cooldown-cycles": 32,
        # a phase's onset reads as skew 2.0 (two shards carry all the
        # heat) or worse, while the healed spread plus residual stale
        # heat stays near 1.3 — the threshold sits between the two
        "controller.rebalance-threshold": 1.6,
        # one re-slice per hot phase: a live rescale recompiles the
        # step family, so marginal touch-ups cost more than they buy
        "controller.min-rebalance-interval": 4.0,
        "controller.min-gain": 1.25,
    }

    def run(pool, controller):
        opts = dict(BASE_CFG)
        if controller:
            opts.update(CTL_CFG)
        env = StreamExecutionEnvironment(Configuration(opts))
        env.set_parallelism(N_DEV)
        env.set_max_parallelism(MAXP)
        env.set_stream_time_characteristic(TimeCharacteristic.EventTime)
        env.set_state_capacity(1 << 13)
        env.batch_size = B
        sink = CountingSink()
        (
            env.add_source(GeneratorSource(gen_of(pool), total=total))
            .key_by(lambda c: c["key"])
            .time_window(WINDOW)
            .sum(lambda c: c["value"])
            .add_sink(sink)
        )
        # wall-clock samples: the controller ledger stamps decisions
        # with time.time(), and the on-run tail window is keyed off
        # the LAST rebalance stamp, so both must share a clock
        samples = []                 # (t_wall, records_in)
        stop = threading.Event()

        def sampler():
            while not stop.is_set():
                m = getattr(env, "_live_metrics", None)
                if m is not None:
                    samples.append((time.time(), m.records_in))
                time.sleep(0.01)

        th = threading.Thread(target=sampler, daemon=True)
        th.start()
        t0 = time.perf_counter()
        job = env.execute(f"selftune-{'on' if controller else 'off'}")
        dt = time.perf_counter() - t0
        stop.set()
        th.join(timeout=2)
        # every event lands in exactly one window of the analytic sum
        assert sink.value_sum == total, (sink.value_sum, total)
        rep_fn = getattr(env, "_controller_report", None)
        rep = rep_fn() if rep_fn is not None else {}
        p99 = job.metrics.fire_latency_pct(99)
        eps_total = total / dt
        # steady tail slope over the sampler's last TAIL fraction of
        # record progress (records_in may exceed `total` when a rescale
        # cut replays prefetched batches, so the window keys off the
        # final sample, not the event count). The controller-on window
        # additionally starts SETTLE_S after the last rebalance stamp:
        # the claim is the recovered steady rate, not the cost of the
        # cut + recompile burst that bought it.
        def slope(xs, win_s=3.0):
            """Best sustained rate: max slope over >=win_s/2 sliding
            windows — the steady measure is robust to one GC pause or
            checkpoint hiccup landing inside the region (every run is
            scored the same way)."""
            best = None
            j = 0
            for i in range(len(xs)):
                while xs[i][0] - xs[j][0] > win_s:
                    j += 1
                dt_w = xs[i][0] - xs[j][0]
                if dt_w >= win_s / 2 and xs[i][1] > xs[j][1]:
                    sl = (xs[i][1] - xs[j][1]) / dt_w
                    if best is None or sl > best:
                        best = sl
            return best

        tail = None
        if samples:
            r_final = samples[-1][1]
            xs = [p for p in samples if p[1] >= (1 - TAIL) * r_final]
            t_rb = [e.get("t_wall") for e in rep.get("ledger", [])
                    if e.get("kind") == "rebalance" and e.get("t_wall")]
            if t_rb:
                clipped = [p for p in samples
                           if p[0] >= max(t_rb) + SETTLE_S]
                tail = slope(clipped)
            tail = tail if tail is not None else slope(xs)
        return {
            "events_per_s": round(eps_total),
            "tail_events_per_s": round(tail if tail else eps_total),
            "p99_fire_ms": (round(p99, 2) if p99 is not None
                            else None),
            "controller": ({
                "rebalances": int(rep.get("rebalances", 0)),
                "actions": int(rep.get("actions", 0)),
                "reverts": int(rep.get("reverts", 0)),
                "rebalance_skips": int(rep.get("rebalance_skips", 0)),
                "ledger_tail": [
                    {k: e.get(k) for k in ("kind", "cycle", "evidence")}
                    for e in rep.get("ledger", [])[-6:]
                ],
            } if rep.get("available") else None),
        }

    balanced = run(cold_pool, controller=False)
    off = run(skew_pool, controller=False)
    on = run(skew_pool, controller=True)
    base_t = max(balanced["tail_events_per_s"], 1)
    ratio_on = on["tail_events_per_s"] / base_t
    ratio_off = off["tail_events_per_s"] / base_t
    detail = {
        "events": total, "batch": B, "max_parallelism": MAXP,
        "n_shards": N_DEV, "resident_groups_per_shard": BUDGET,
        "hot_groups_phase_a": list(HOT_A),
        "hot_groups_phase_b": list(HOT_B),
        "tail_fraction": TAIL, "settle_s": SETTLE_S,
        "balanced": balanced,
        "skewed_controller_off": off,
        "skewed_controller_on": on,
        "acceptance": {
            "ratio_on": round(ratio_on, 3),
            "ratio_off": round(ratio_off, 3),
            "criterion": "controller-on tail >= 0.8 of balanced; "
                         "controller-off stays degraded",
        },
    }
    print(json.dumps({"config": "selftune", "detail": detail}),
          flush=True)
    ctl = on["controller"] or {}
    return (round(ratio_on, 3), round(ratio_off, 3), on["p99_fire_ms"],
            {"rebalances": int(ctl.get("rebalances", 0)),
             "actions": int(ctl.get("actions", 0)),
             "reverts": int(ctl.get("reverts", 0))})


CONFIGS = {
    "socket_wc": (run_socket_wc, 2_000_000),
    "count_min": (run_count_min, 4_000_000),
    "sessions": (run_sessions, 4_000_000),
    "cep": (run_cep, 400_000),
    "cep_event_time": (run_cep_event_time, 400_000),
    "checkpoint_overhead": (run_checkpoint_overhead, 2_000_000),
    "observability_overhead": (run_observability_overhead, 2_000_000),
    "ingest_pipeline": (run_ingest_pipeline, 4_000_000),
    "fault_overhead": (run_fault_overhead, 4_000_000),
    "device_update_ceiling": (run_device_update_ceiling, 2_000_000),
    "resident_loop": (run_resident_loop, 2_000_000),
    "mttr_recovery": (run_mttr_recovery, 2_000_000),
    "elastic_recovery": (run_elastic_recovery, 2_000_000),
    "selftune": (run_selftune, 2_000_000),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--only", default=None, choices=list(CONFIGS))
    ap.add_argument("--events", type=int, default=None)
    args = ap.parse_args()
    if args.cpu:
        import os

        # before JAX initializes: JAX reads the variable at start-up
        os.environ["JAX_PLATFORMS"] = "cpu"
    from flink_tpu.core.jaxenv import require_accelerator, use_compile_cache

    use_compile_cache()
    if not args.cpu:
        require_accelerator()

    failed = []
    for name, (fn, default_events) in CONFIGS.items():
        if args.only and name != args.only:
            continue
        n = args.events or default_events
        try:
            subj, base = fn(n, args.cpu)
            print(json.dumps({
                "config": name,
                "events": n,
                "subject_eps": round(subj),
                "baseline_eps": round(base),
                "ratio": round(subj / base, 2),
            }), flush=True)
        except Exception as e:  # noqa: BLE001 — a row per config, always
            import traceback

            traceback.print_exc()
            print(json.dumps({"config": name, "error": f"{type(e).__name__}: {e}"}),
                  flush=True)
            failed.append(name)
    if failed:
        sys.exit(f"configs failed: {failed}")


if __name__ == "__main__":
    main()
