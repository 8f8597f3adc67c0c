"""Drives one run of a keyed-window job through the public entry:
``StreamExecutionEnvironment`` -> ``add_source(<harness source>)`` -> the
job kind's ``build`` (``jobs/<kind>.py``: ``key_by`` -> ``time_window``
-> ``sum``, for a tumbling sum) -> ``add_sink(<harness sink>)`` ->
``execute()``.

The harness source runs the phases of a run from inside the job:

1. warm-up: one window of event time plus one batch, fed as fast as the
   job takes it, which compiles and runs the update, the fire and the
   emit; the source then waits until the sink has seen that window fire;
2. the timed window of ``seconds``: saturated, or on the open-loop
   schedule, which starts as the window opens;
3. end of stream at the window's close; the job's end-of-stream flush
   then fires every window left, and all of it is checked.
"""

from __future__ import annotations

import threading
import time
from typing import Optional

import numpy as np

from benchmark import registry
from benchmark.traffic import Traffic
from flink_tpu import StreamExecutionEnvironment
from flink_tpu.core.config import Configuration
from flink_tpu.metrics.tracing import CompileEvents
from flink_tpu.runtime.sinks import Sink
from flink_tpu.runtime.sources import ColumnarSource

WARM, WAIT, OPEN = "warm", "wait", "open"
# the longest the source waits for the warm-up's fire once it has fed the
# warm-up; a job that never fires then still opens its window, and fails
# its check, instead of hanging
FIRE_WAIT_S = 60.0


def _annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


class Window:
    """What the run's window saw, filled in by the source and the monitor
    thread."""

    def __init__(self, seconds: float, trace_dir: Optional[str]):
        self.seconds = seconds
        self.trace_dir = trace_dir
        self.t_open = self.t_close = None
        self.records_open = self.records_close = None
        self.fire_samples_open = self.fire_samples_close = 0
        self.compile_mark = None
        self.compiles_in_window = None
        self.closed = threading.Event()
        self.monitor: Optional[threading.Thread] = None


def make_source(traffic: Traffic, seed: int, win: Window, env, sink):
    """The harness source: a columnar source the program polls like any
    other."""
    sched = traffic.sched

    class BenchSource(ColumnarSource):
        def __init__(self):
            self.offset = 0
            self.state = WARM
            self.fed_at = None      # when the warm-up was all handed over
            self.lag_s = []         # open loop: poll time - oldest due time

        def _take(self, n: int):
            idx = np.arange(self.offset, self.offset + n, dtype=np.int64)
            self.offset += n
            return traffic.events(idx, seed)

        def _open(self):
            if win.trace_dir is not None:
                import jax

                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(win.trace_dir,
                                         profiler_options=opts)
            live = env._live_metrics
            win.compile_mark = CompileEvents.mark()
            win.records_open = live.records_in
            win.fire_samples_open = len(live.fire_latency or ())
            win.t_open = time.perf_counter()
            sched.start(win.t_open, self.offset)
            win.monitor = threading.Thread(target=_monitor, name="bench-window",
                                           args=(win, env), daemon=True)
            win.monitor.start()

        def poll(self, max_records: int):
            with _annotate("source poll"):
                return self._poll(max_records)

        def _poll(self, B: int):
            if self.state == WARM:
                n = min(B, sched.warmup - self.offset)
                out = self._take(n)
                if self.offset >= sched.warmup:
                    self.state = WAIT
                    self.fed_at = time.perf_counter()
                return out, False
            if self.state == WAIT:
                # an empty poll lets the job surface a lagged fire
                if not sink.fired.wait(0.005) and \
                        time.perf_counter() - self.fed_at < FIRE_WAIT_S:
                    return ({}, None), False
                self._open()
                self.state = OPEN
            if win.closed.is_set():
                return ({}, None), True
            if not sched.open_loop:
                return self._take(B), False
            now = time.perf_counter()
            due = sched.due_by(now) - self.offset
            if due <= 0:
                time.sleep(max(0.0, float(sched.due_s(self.offset)) - now))
                now = time.perf_counter()
                due = max(1, sched.due_by(now) - self.offset)
            self.lag_s.append(now - float(sched.due_s(self.offset)))
            return self._take(min(B, due)), False

        def snapshot_offsets(self):
            return self.offset

    return BenchSource()


def _monitor(win: Window, env) -> None:
    """Close the window ``seconds`` after it opened: read the counters,
    end the stream, then stop the profiler."""
    time.sleep(max(0.0, win.t_open + win.seconds - time.perf_counter()))
    live = env._live_metrics
    win.records_close = live.records_in
    win.fire_samples_close = len(live.fire_latency or ())
    win.t_close = time.perf_counter()
    win.compiles_in_window = CompileEvents.since(win.compile_mark)
    win.closed.set()
    if win.trace_dir is not None:
        import jax

        jax.profiler.stop_trace()


class BenchSink(Sink):
    columnar = True

    def __init__(self):
        self.calls = []        # (host time, key_id, window_end_ms, value)
        self.fired = threading.Event()

    def invoke_columnar(self, cols):
        with _annotate("sink"):
            self.calls.append((time.perf_counter(), cols["key_id"],
                               cols["window_end_ms"], cols["value"]))
            self.fired.set()


def run(cfg: dict, traffic: Traffic, seed: int, seconds: float,
        trace_dir: Optional[str] = None, root: str = registry.ROOT) -> dict:
    """Run the job once; return what the metrics and the check read."""
    job = cfg["job"]
    build = registry.load("jobs", registry.job_kind(job), root).build
    options = dict(cfg["options"])
    if trace_dir is not None:
        # the traced run records the step loop's spans; the two telemetry
        # paths that tracing would also switch on stay off, so the traced
        # run does the untraced run's work
        options.update({"observability.tracing": True,
                        "observability.trace-buffer-spans": 1 << 20,
                        "observability.kg-stats": False,
                        "observability.drain-stats": False})
    win = Window(seconds, trace_dir)
    env = StreamExecutionEnvironment(Configuration(options))
    env.set_parallelism(job["parallelism"])
    env.set_max_parallelism(job["max_parallelism"])
    env.set_state_capacity(job["state_capacity"])
    env.batch_size = job["batch"]
    sink = BenchSink()
    src = make_source(traffic, seed, win, env, sink)
    build(env, src, sink, job)
    CompileEvents.install()
    handle = env.execute("bench")
    if win.monitor is not None:
        win.monitor.join()
    if win.t_close is None:
        raise RuntimeError("the job ended before the window opened")
    m = handle.metrics
    fire_samples = list(m.fire_latency._samples) if m.fire_latency else []
    return {
        "sched": traffic.sched, "window": win, "sink_calls": sink.calls,
        "n_events": src.offset, "lag_s": src.lag_s, "metrics": m,
        "fire_samples": fire_samples[win.fire_samples_open:
                                     win.fire_samples_close],
        "spans": env._span_tracer.snapshot() if env._span_tracer else [],
        "state_devices": len(handle.state.acc.sharding.device_set),
    }
