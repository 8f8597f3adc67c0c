"""Step-loop span tracing + XLA compile visibility.

The reference samples per-record visibility out of a running job
(LatencyMarker sentinels, BackPressureStatsTracker stack sampling). The
micro-batch design makes that structurally impossible — and unnecessary:
every cycle of the executor's step loop decomposes EXACTLY into named
phases (source drain, key routing, device step dispatch, barrier/scalar
fetch, fire extraction, emit, checkpoint sync). The tracer records those
phases as spans into a bounded ring buffer and exports them as
Chrome-trace JSON (chrome://tracing / Perfetto `traceEvents` array), so a
tail-latency stall is attributable to a phase instead of a mystery
(Hazelcast Jet's 99.99%-ile work, PAPERS.md: tails come from rare
coordination stalls — here barrier fetches, transfers, recompiles).

Design constraints:
  * OFF by default. When off, the executor holds no tracer and the hot
    path pays nothing. When on, the per-span cost is two perf_counter()
    reads (usually reusing timestamps the cycle attribution already
    takes) and one deque.append of a tuple.
  * SAMPLED. `observability.trace-sample-every: N` records every N-th
    cycle only; the skipped cycles pay one integer compare.
  * BOUNDED. The ring holds `observability.trace-buffer-spans` records;
    old spans fall off — a perpetual job cannot grow host memory.

Shared clock: the spans are on `time.perf_counter`, the profiler's trace
(host annotations and device ops) on its own clock. While a tracer
exists the executor calls `clock_anchor()` at sampled cycle boundaries,
at most once a second: a `jax.profiler.TraceAnnotation("flink_tpu.clock")`
around a `clock` span of the same ring. Under a running profiler session
the annotation lands on the host plane, and the offset between its trace
timestamp and the span's start maps every span onto the trace's clock;
with no session it is one no-op TraceMe. Each span also records the name
of the thread it ran on, and `watch_process()` adds a `gc` span per
garbage collection and a `compile` span per XLA compile, the two usual
causes of a whole-process stall.

Compile visibility (`CompileEvents`): jax.monitoring emits an event per
XLA backend compile (`/jax/core/compile/backend_compile_duration`). One
process-wide listener counts them and records wall time, attributed to
the stage label the executor sets around its step builds/warmups — a
recompile storm shows up as a named counter moving, not a mystery stall.
"""

from __future__ import annotations

import gc
import json
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

# span record layout: (name, stage, t_start_s, dur_s, attrs_or_None,
# thread_name); fields are only ever appended, never reordered
_Span = Tuple[str, str, float, float, Optional[dict], str]

# the profiler annotation wrapped around each `clock` span
CLOCK_ANNOTATION = "flink_tpu.clock"
# the most often a clock anchor is emitted, in perf_counter seconds
CLOCK_ANCHOR_EVERY_S = 1.0

# the step-loop phases the executor instruments; exported for tests and
# the docs so the catalog cannot silently drift from the wiring
STEP_PHASES = (
    "source",           # source poll / prefetch wait + host chain/encode
    "route",            # per-batch exchange-route feasibility (key routing;
                        #   recorded from the ingest thread when planned
                        #   at prep time, runtime/ingest.py)
    "stage",            # ingest-thread pad into the staging ring
    "transfer",         # ingest-thread H2D device_put + completion wait
    "dispatch",         # device step dispatch: the host enqueue alone
    "inflight_wait",    # block on the oldest inflight step once more than
                        #   pipeline.max-inflight-steps are queued
    "drain",            # resident ring-drain dispatch (pipeline.
                        #   resident-loop); attrs carry the slot count
    "fire",             # fire-step dispatch at a pane boundary
    "barrier_fetch",    # step-boundary scalar/lane fetch (the d2h barrier)
    "emit",             # fire extraction + sink invocation
    "emit_fetch",       # inside emit: the [:n] slices + batched device_get
    "emit_sink",        # inside emit: concatenation, key ids, result
                        #   projection and the sink calls
    "checkpoint_sync",  # checkpoint sync phase (the only ckpt loop stall)
    "poll",             # ingest side: source poll + encode of one batch
    "handoff",          # ingest thread blocked on the full prefetch queue
    "clock",            # clock anchor inside a flink_tpu.clock annotation
    "gc",               # one garbage collection, any thread
    "compile",          # one XLA compile, ending when its event arrived
)


class SpanTracer:
    """Bounded ring buffer of step-loop phase spans.

    One tracer per job run, owned by the executor thread; `snapshot()`
    and the exporters may be called from web/reporter threads (the deque
    append/iterate pair is guarded by a lock — spans are tiny, the
    critical sections are nanoseconds).
    """

    def __init__(self, stage: str = "job", sample_every: int = 1,
                 max_spans: int = 65536):
        self.stage = stage
        self.sample_every = max(1, int(sample_every))
        self._spans: deque = deque(maxlen=max(16, int(max_spans)))
        # counter-track samples ride their own ring so a chatty counter
        # cannot evict spans: (track, t_sample_s, {series: value})
        self._counters: deque = deque(maxlen=max(16, int(max_spans)))
        # reentrant: a gc callback may record while its thread is inside
        # a record of its own
        self._lock = threading.RLock()
        # perf_counter origin for relative span timestamps + the wall
        # clock at that origin so exported ts can be absolute-ish
        self.t0 = time.perf_counter()
        self.epoch_ms = time.time() * 1000.0
        self._cycle = -1
        self.active = False       # does the CURRENT cycle record spans?
        self.dropped = 0          # spans recorded while ring was full
        self._last_anchor = float("-inf")
        # wall clock (ns since the epoch, the profiler's absolute clock)
        # at the tracer origin, as of the newest clock anchor
        self.origin_trace_ns: Optional[int] = None
        self._gc_t0: Optional[float] = None

    # -- recording (executor thread) ------------------------------------
    def begin_cycle(self) -> bool:
        """Advance the cycle counter; returns whether this cycle records."""
        self._cycle += 1
        self.active = (self._cycle % self.sample_every) == 0
        return self.active

    def rec(self, name: str, t_start: float, t_end: Optional[float] = None,
            stage: Optional[str] = None, **attrs):
        """Record one span from perf_counter() timestamps. Callers guard
        with `if tr is not None and tr.active:` so the off path costs one
        attribute read."""
        if t_end is None:
            t_end = time.perf_counter()
        span = (name, stage or self.stage, t_start, t_end - t_start,
                attrs or None, threading.current_thread().name)
        with self._lock:
            if len(self._spans) == self._spans.maxlen:
                self.dropped += 1
            self._spans.append(span)

    def clock_anchor(self):
        """At a sampled cycle boundary, at most once per
        CLOCK_ANCHOR_EVERY_S: a `clock` span inside a profiler annotation
        named CLOCK_ANNOTATION (see the module docstring)."""
        t_start = time.perf_counter()
        if t_start - self._last_anchor < CLOCK_ANCHOR_EVERY_S:
            return
        self._last_anchor = t_start
        self.origin_trace_ns = time.time_ns() - round(
            (t_start - self.t0) * 1e9)
        from jax.profiler import TraceAnnotation

        with TraceAnnotation(CLOCK_ANNOTATION):
            pass
        self.rec("clock", t_start)

    def watch_process(self):
        """Record a `gc` span per garbage collection and a `compile` span
        per XLA compile, until `unwatch_process()`."""
        if self._on_gc not in gc.callbacks:
            gc.callbacks.append(self._on_gc)
        CompileEvents.install()
        CompileEvents.add_sink(self._on_compile)

    def unwatch_process(self):
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
        CompileEvents.remove_sink(self._on_compile)

    def _on_gc(self, phase: str, info: dict):
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            if self.active:
                self.rec("gc", self._gc_t0, generation=info["generation"])
            self._gc_t0 = None

    def _on_compile(self, duration_s: float):
        if self.active:
            t_end = time.perf_counter()
            self.rec("compile", t_end - duration_s, t_end)

    def rec_counter(self, track: str, t_sample: Optional[float] = None,
                    **values):
        """Record one sample on a Perfetto counter track ("ph": "C"):
        the drain flight recorder emits ring fill / duty cycle / events
        retired this way so they render as stacked counter lanes above
        the phase spans. Same guard discipline as `rec`."""
        if not values:
            return
        if t_sample is None:
            t_sample = time.perf_counter()
        with self._lock:
            self._counters.append((
                track, t_sample,
                {k: float(v) for k, v in values.items()},
            ))

    def span(self, name: str, **attrs):
        """Context-manager form for code paths without an existing
        timestamp pair (the executor's occupancy refresh uses it). The
        sampling decision is captured at ENTRY so a cycle boundary
        inside the block cannot split the decision."""
        return _SpanCtx(self, name, attrs)

    # -- export (any thread) --------------------------------------------
    def snapshot(self) -> List[_Span]:
        with self._lock:
            return list(self._spans)

    def counter_snapshot(self) -> List[Tuple[str, float, Dict[str, float]]]:
        with self._lock:
            return list(self._counters)

    def __len__(self):
        with self._lock:
            return len(self._spans)

    def to_chrome_trace(self) -> Dict[str, Any]:
        """Chrome-trace / Perfetto JSON object: complete ("ph": "X")
        events with microsecond timestamps relative to the tracer origin,
        one `tid` per thread (``otherData.threads`` names them). Loadable
        directly in chrome://tracing and ui.perfetto.dev. Once a clock
        anchor was seen, ``otherData.origin_trace_ns`` is the origin on
        the profiler's absolute clock, so ``origin_trace_ns + ts * 1e3``
        overlays a span on a `jax.profiler` trace."""
        events = []
        tids: Dict[str, int] = {}
        for name, stage, t_start, dur, attrs, thread in self.snapshot():
            ev = {
                "name": name,
                "cat": stage,
                "ph": "X",
                "ts": round((t_start - self.t0) * 1e6, 3),
                "dur": round(dur * 1e6, 3),
                "pid": 1,
                "tid": tids.setdefault(thread, len(tids) + 1),
            }
            if attrs:
                ev["args"] = attrs
            events.append(ev)
        for track, t_sample, values in self.counter_snapshot():
            # Perfetto draws one stacked counter lane per (pid, name)
            # with the series keys of "args" as the stack components
            events.append({
                "name": track,
                "cat": "counter",
                "ph": "C",
                "ts": round((t_sample - self.t0) * 1e6, 3),
                "pid": 1,
                "args": values,
            })
        other = {
            "stage": self.stage,
            "sample_every": self.sample_every,
            "origin_epoch_ms": round(self.epoch_ms, 1),
            "spans_dropped": self.dropped,
            "threads": {str(t): n for n, t in tids.items()},
        }
        if self.origin_trace_ns is not None:
            other["origin_trace_ns"] = self.origin_trace_ns
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": other,
        }

    def dump(self, path: str) -> str:
        """Write the Chrome-trace JSON to a file; returns the path."""
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)
        return path


class _SpanCtx:
    __slots__ = ("tracer", "name", "attrs", "t0", "active")

    def __init__(self, tracer: SpanTracer, name: str, attrs: dict):
        self.tracer = tracer
        self.name = name
        self.attrs = attrs

    def __enter__(self):
        self.active = self.tracer.active
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self.active:
            self.tracer.rec(self.name, self.t0, **self.attrs)
        return False


def tracer_from_config(config, stage: str = "job") -> Optional[SpanTracer]:
    """Build a SpanTracer from the `observability.*` config keys, or None
    when tracing is off (the default — the hot path then carries no
    tracer reference at all)."""
    if config is None or not config.get_bool("observability.tracing", False):
        return None
    return SpanTracer(
        stage=stage,
        sample_every=config.get_int("observability.trace-sample-every", 1),
        max_spans=config.get_int("observability.trace-buffer-spans", 65536),
    )


# ---------------------------------------------------------------- compiles

class CompileEvents:
    """Process-wide XLA compile accounting via jax.monitoring.

    jax has exactly one global listener list, so this is a singleton:
    `install()` registers once and is idempotent. Each job snapshots the
    counters at start (`mark()`) and exposes deltas as gauges — per-job
    attribution over a process-global event stream, the same shape the
    reference uses for JVM-global GC counters on per-job dashboards.

    The executor labels compile bursts with `set_stage(...)` around its
    step builds/warmups; an event arriving outside any labelled section
    attributes to "steady". Small eager ops (device_put, tiny zeros)
    also compile once per shape and land there, so the recompile-storm
    alarm is a steady count that keeps GROWING while the job is in
    steady state — the loop dispatches only pre-compiled steps, so
    sustained growth means per-batch recompilation (a shape leak).
    """

    _lock = threading.Lock()
    _installed = False
    _stage = "steady"
    # stage -> {"count": int, "time_s": float}
    _by_stage: Dict[str, Dict[str, float]] = {}
    total_count = 0
    total_time_s = 0.0
    # per-event sinks (e.g. a job's compile-time histogram); jobs MUST
    # remove_sink on teardown or the process-global list leaks closures
    _sinks: List[Any] = []
    # trace-phase durations worth exporting alongside backend compiles
    _EVENT = "/jax/core/compile/backend_compile_duration"
    # with the persistent compilation cache on, a cache HIT skips
    # backend_compile entirely and emits this retrieval event instead —
    # count it as a compile (an executable still materialized for a new
    # signature; the jit cache absorbs true repeats, so storm semantics
    # are unchanged) or compile-count gauges would read 0 on cached runs
    _EVENT_CACHED = "/jax/compilation_cache/cache_retrieval_time_sec"

    @classmethod
    def install(cls):
        with cls._lock:
            if cls._installed:
                return
            try:
                from jax import monitoring
                monitoring.register_event_duration_secs_listener(
                    cls._on_duration
                )
            except Exception:
                # observability must never kill the job; without the
                # monitoring API the counters just stay at zero
                return
            cls._installed = True

    @classmethod
    def _on_duration(cls, event: str, duration_s: float, **kw):
        if event != cls._EVENT and event != cls._EVENT_CACHED:
            return
        with cls._lock:
            cls.total_count += 1
            cls.total_time_s += duration_s
            row = cls._by_stage.setdefault(
                cls._stage, {"count": 0, "time_s": 0.0}
            )
            row["count"] += 1
            row["time_s"] += duration_s
            sinks = list(cls._sinks)
        for s in sinks:      # outside the lock: sinks may take their own
            try:
                s(duration_s)
            except Exception:
                pass         # observability must never kill a compile

    @classmethod
    def add_sink(cls, fn):
        with cls._lock:
            cls._sinks.append(fn)
        return fn

    @classmethod
    def remove_sink(cls, fn):
        with cls._lock:
            if fn in cls._sinks:
                cls._sinks.remove(fn)

    @classmethod
    def set_stage(cls, stage: str):
        with cls._lock:
            cls._stage = stage

    @classmethod
    def stage(cls, name: str):
        """Context manager labelling compiles triggered inside the block."""
        return _StageCtx(cls, name)

    @classmethod
    def mark(cls) -> Tuple[int, float]:
        """(count, time_s) baseline for per-job delta gauges."""
        with cls._lock:
            return cls.total_count, cls.total_time_s

    @classmethod
    def since(cls, mark: Tuple[int, float]) -> Tuple[int, float]:
        with cls._lock:
            return (cls.total_count - mark[0],
                    cls.total_time_s - mark[1])

    @classmethod
    def report(cls) -> Dict[str, Any]:
        with cls._lock:
            return {
                "compiles": cls.total_count,
                "compile_time_ms": round(cls.total_time_s * 1e3, 2),
                "by_stage": {
                    k: {"count": v["count"],
                        "time_ms": round(v["time_s"] * 1e3, 2)}
                    for k, v in sorted(cls._by_stage.items())
                },
            }


class _StageCtx:
    __slots__ = ("cls", "name", "prev")

    def __init__(self, cls, name):
        self.cls = cls
        self.name = name

    def __enter__(self):
        with self.cls._lock:
            self.prev = self.cls._stage
            self.cls._stage = self.name
        return self

    def __exit__(self, *exc):
        with self.cls._lock:
            self.cls._stage = self.prev
        return False


def cost_analysis_of(jitted, *args) -> Optional[Dict[str, float]]:
    """FLOPs / bytes-accessed of one compiled step via the AOT
    `lower().compile().cost_analysis()` path, where the backend provides
    it (CPU and TPU do; some runtimes return None). This triggers a
    second trace+compile of the function, so callers gate it behind
    `observability.compile-cost` — it is a diagnosis tool, not an
    always-on probe."""
    try:
        ca = jitted.lower(*args).compile().cost_analysis()
    except Exception:
        return None
    if ca is None:
        return None
    # jax returns either a dict or a 1-element list of dicts by version
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    out = {}
    for k in ("flops", "bytes accessed"):
        v = ca.get(k)
        if isinstance(v, (int, float)):
            out[k.replace(" ", "_")] = float(v)
    return out or None
