"""Step-loop span tracing + device-resident telemetry (ISSUE 2).

The classic per-record observability of the reference (LatencyMarker
sampling, stack-trace back-pressure probes) is structurally impossible
over whole-key-group XLA kernels — visibility comes from the step loop
(span tracer, metrics/tracing.py) and from device-side scalars (key-group
skew, watermark lag). These tests pin: the tracer mechanics (bounding,
sampling, Chrome-trace validity), the executor wiring (every step-loop
phase appears as a span), the web surface (/traces, /keygroups,
/metrics), and the JSON-404 guards on job-scoped endpoints.
"""

import gc
import glob
import json
import os
import urllib.error
import urllib.request

import numpy as np
import pytest

from flink_tpu import StreamExecutionEnvironment
from flink_tpu.core.config import Configuration
from flink_tpu.core.time import TimeCharacteristic
from flink_tpu.metrics.tracing import (
    CLOCK_ANNOTATION,
    STEP_PHASES,
    CompileEvents,
    SpanTracer,
)
from flink_tpu.runtime.sinks import CountingSink, DiscardingSink
from flink_tpu.runtime.sources import GeneratorSource


# ---------------------------------------------------------- tracer unit

def test_span_tracer_ring_and_sampling():
    tr = SpanTracer(stage="s", sample_every=3, max_spans=16)
    # sampling: cycle 0 records, 1-2 don't, 3 records again
    assert tr.begin_cycle() is True
    assert tr.begin_cycle() is False
    assert tr.begin_cycle() is False
    assert tr.begin_cycle() is True
    # ring bound: 40 spans into a 16-slot ring keeps the NEWEST 16
    for i in range(40):
        tr.rec(f"span{i}", 0.0, 1.0)
    assert len(tr) == 16
    names = [s[0] for s in tr.snapshot()]
    assert names[0] == "span24" and names[-1] == "span39"
    assert tr.dropped == 40 - 16


def test_span_tracer_chrome_trace_shape(tmp_path):
    tr = SpanTracer(stage="job-x")
    tr.begin_cycle()
    tr.rec("source", 10.0, 10.5, records=7)
    tr.rec("dispatch", 10.5, 10.6)
    ct = tr.to_chrome_trace()
    # the export must round-trip through json (the endpoint contract)
    parsed = json.loads(json.dumps(ct))
    evs = parsed["traceEvents"]
    assert len(evs) == 2
    for ev in evs:
        assert ev["ph"] == "X"
        assert isinstance(ev["ts"], (int, float))
        assert isinstance(ev["dur"], (int, float))
        assert ev["dur"] >= 0
    assert evs[0]["name"] == "source"
    assert evs[0]["args"] == {"records": 7}
    assert evs[1]["ts"] >= evs[0]["ts"]
    # file dump is the same JSON
    p = tr.dump(str(tmp_path / "trace.json"))
    on_disk = json.load(open(p))
    assert on_disk["traceEvents"] == parsed["traceEvents"]


def test_span_tracer_counter_tracks():
    """Round 14: `rec_counter` samples export as Perfetto counter events
    ("ph": "C") alongside the phase spans — one stacked lane per track,
    the kwargs as the stack components. Counters live in their own ring
    so a chatty fill series can never evict spans."""
    tr = SpanTracer(stage="job-c", max_spans=16)
    tr.begin_cycle()
    tr.rec("dispatch", 10.0, 10.5)
    tr.rec_counter("drain/shard0", 10.1, fill=3, duty_pct=75.0)
    tr.rec_counter("drain/shard1", 10.2, fill=0, duty_pct=12.5)
    tr.rec_counter("empty_is_dropped", 10.3)   # no values: no event
    ct = json.loads(json.dumps(tr.to_chrome_trace()))
    cs = [ev for ev in ct["traceEvents"] if ev["ph"] == "C"]
    assert len(cs) == 2
    assert cs[0]["name"] == "drain/shard0"
    assert cs[0]["cat"] == "counter"
    assert cs[0]["args"] == {"fill": 3.0, "duty_pct": 75.0}
    assert cs[1]["args"]["fill"] == 0.0
    # counters ride the same pid so they stack above the span lanes
    assert all(ev["pid"] == 1 for ev in ct["traceEvents"])
    # spans survive a counter flood (independent rings)
    for i in range(100):
        tr.rec_counter("noisy", 11.0 + i, fill=i)
    assert [s[0] for s in tr.snapshot()] == ["dispatch"]


def test_span_context_manager_respects_active():
    tr = SpanTracer(sample_every=2)
    tr.begin_cycle()            # active
    with tr.span("a"):
        pass
    tr.begin_cycle()            # inactive
    with tr.span("b"):
        pass
    assert [s[0] for s in tr.snapshot()] == ["a"]


# ------------------------------------------------- executor wiring (e2e)

def _windowed_env(extra_cfg=None, total=20_000, sink=None, on_poll=None):
    """A traced keyed tumbling-sum job; `on_poll(offset)` runs inside
    every source poll, on the thread that polls."""
    env = StreamExecutionEnvironment(Configuration({
        "observability.tracing": True,
        "observability.kg-stats-interval-ms": 0,
        **(extra_cfg or {}),
    }))
    env.set_parallelism(1)
    env.set_max_parallelism(8)
    env.set_stream_time_characteristic(TimeCharacteristic.EventTime)
    env.set_state_capacity(1 << 12)
    env.batch_size = 1024

    def gen(offset, n):
        if on_poll is not None:
            on_poll(offset)
        idx = np.arange(offset, offset + n, dtype=np.int64)
        return {"key": idx % 100, "value": np.ones(n, np.float32)}, idx // 10

    sink = CountingSink() if sink is None else sink
    (
        env.add_source(GeneratorSource(gen, total=total))
        .key_by(lambda c: c["key"])
        .time_window(500)
        .sum(lambda c: c["value"])
        .add_sink(sink)
    )
    return env, sink


def test_windowed_job_records_step_phase_spans():
    env, sink = _windowed_env()
    env.execute("traced-job")
    assert sink.value_sum == 20_000
    tr = env._span_tracer
    assert tr is not None and len(tr) > 0
    names = {s[0] for s in tr.snapshot()}
    # every hot phase of the loop must appear (checkpoint_sync needs a
    # checkpointing job — covered below)
    assert {"source", "route", "dispatch", "fire", "barrier_fetch",
            "emit"} <= names
    ct = tr.to_chrome_trace()
    assert ct["traceEvents"], "trace export must be non-empty"
    # skew + lag telemetry landed in the registry
    snap = env.metric_registry.snapshot("jobs.traced-job.")
    assert snap["jobs.traced-job.kg_occupied_groups"] > 0
    assert snap["jobs.traced-job.kg_occupancy_max"] >= 1
    assert snap["jobs.traced-job.kg_skew_ratio"] >= 1.0
    assert snap["jobs.traced-job.kg_fill_max"] > 0
    assert snap["jobs.traced-job.watermark_ms"] > 0
    assert snap["jobs.traced-job.event_time_lag_ms"] >= 0
    assert snap["jobs.traced-job.watermark_lag_ms"] is not None
    # compile visibility: the warmup compiles were counted + attributed
    assert snap["jobs.traced-job.xla_compile_count"] > 0
    rep = env._compile_report()
    assert any(k.startswith("window-update") for k in rep["by_stage"])
    # hot-group report serves top-k
    top = env._kg_report(3)
    assert 1 <= len(top["occupancy_top"]) <= 3
    assert top["occupancy_top"][0]["count"] >= 1


def test_tracing_off_by_default_and_sampling():
    callbacks = list(gc.callbacks)
    env, _ = _windowed_env({"observability.tracing": False})
    env.execute("untraced")
    assert env._span_tracer is None
    # off: no gc hook was ever registered, nor one left behind
    assert gc.callbacks == callbacks

    env2, _ = _windowed_env({"observability.trace-sample-every": 1000})
    env2.execute("sampled")
    # the traced job's gc hook went with the job
    assert gc.callbacks == callbacks
    # cycle 0 is sampled, later cycles are not: far fewer step spans than
    # steps (cycle 0 is also the job's set-up, whose compiles and
    # collections are process-wide records, not step spans)
    spans = sum(1 for s in env2._span_tracer.snapshot()
                if s[0] not in ("compile", "gc"))
    steps = env2.last_job.metrics.steps
    assert 0 < spans < steps + 10


def _by_name(tr):
    out = {}
    for s in tr.snapshot():
        out.setdefault(s[0], []).append(s)
    return out


def test_dispatch_and_inflight_wait_share_the_poll_batch_id():
    """`dispatch` is the host enqueue alone; the block on the oldest
    inflight step is its own `inflight_wait` span right after it, and
    both carry the `batch` id of that batch's `poll`, which ran on the
    ingest thread."""
    env, sink = _windowed_env({"pipeline.max-inflight-steps": 1})
    env.execute("inflight")
    assert sink.value_sum == 20_000
    spans = _by_name(env._span_tracer)
    assert set(spans) <= set(STEP_PHASES) | {"kg_occupancy"}
    polls = {s[4]["batch"]: s for s in spans["poll"]}
    assert sorted(polls) == list(range(len(polls)))
    waits = {s[4]["batch"]: s for s in spans["inflight_wait"]}
    paired = 0
    for d in spans["dispatch"]:
        b = d[4]["batch"]
        if b is None or b not in waits:
            continue
        w = waits[b]
        # the wait starts where the enqueue ended
        assert w[2] == pytest.approx(d[2] + d[3], abs=1e-9)
        assert d[5] == w[5] != polls[b][5]
        assert polls[b][5] == "flink-tpu-ingest"
        assert d[2] >= polls[b][2] + polls[b][3]
        paired += 1
    assert paired >= 5


@pytest.mark.parametrize("path", ["drain_fires", "consume_fires"])
def test_emit_fetch_and_sink_lie_inside_emit(path):
    extra = {} if path == "drain_fires" else {
        "pipeline.prefetch": "on",
        "pipeline.device-staging": "on",
        "pipeline.resident-loop": "on",
    }
    env, _ = _windowed_env(extra, sink=DiscardingSink())
    env.execute(f"emit-{path}")
    spans = _by_name(env._span_tracer)
    emits = [(s[2], s[2] + s[3]) for s in spans["emit"]]
    for child in ("emit_fetch", "emit_sink"):
        assert spans[child]
        for s in spans[child]:
            assert any(a <= s[2] and s[2] + s[3] <= b for a, b in emits)
    assert sum(s[4]["fired"] for s in spans["emit_sink"]) > 0


def _collect(offset):
    gc.collect()


def _compile_new_shape(offset):
    import jax
    import jax.numpy as jnp

    # a fresh shape per poll: one XLA compile each
    jax.jit(lambda x: x + 1)(jnp.zeros(offset // 1024 + 1)).block_until_ready()


@pytest.mark.parametrize("name,on_poll", [("gc", _collect),
                                          ("compile", _compile_new_shape)])
def test_process_wide_stall_spans(name, on_poll):
    """A collection or a compile during a traced job is a span of its own,
    on the thread that paid for it (here the ingest thread's poll)."""
    env, _ = _windowed_env(on_poll=on_poll, total=8192)
    env.execute(f"stall-{name}")
    spans = _by_name(env._span_tracer)
    mine = [s for s in spans[name] if s[5] == "flink-tpu-ingest"]
    assert mine and all(s[3] > 0 for s in mine)
    if name == "gc":
        assert any(s[4]["generation"] == 2 for s in mine)
    polls = [(s[2], s[2] + s[3]) for s in spans["poll"]]
    assert any(a <= s[2] and s[2] + s[3] <= b + 1e-6
               for s in mine for a, b in polls)


def test_clock_anchor_maps_onto_the_profiler_trace(tmp_path):
    """Each `clock` span sits inside a flink_tpu.clock annotation of the
    profiler trace; `origin_trace_ns` puts it on the trace's absolute
    clock within 1 ms, and anchors come at most once a second."""
    import jax
    from jax.profiler import ProfileData

    tr = SpanTracer()
    tr.begin_cycle()
    with jax.profiler.trace(str(tmp_path)):
        tr.clock_anchor()
        tr.clock_anchor()             # within the second: no anchor
        tr._last_anchor = float("-inf")
        tr.clock_anchor()
    clocks = [s for s in tr.snapshot() if s[0] == "clock"]
    assert len(clocks) == 2
    path, = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    pd = ProfileData.from_file(path)
    start = [v for pl in pd.planes for k, v in pl.stats
             if k == "profile_start_time"][0]
    anns = sorted(ev.start_ns for pl in pd.planes for ln in pl.lines
                  for ev in ln.events if ev.name == CLOCK_ANNOTATION)
    assert len(anns) == 2
    origin = tr.to_chrome_trace()["otherData"]["origin_trace_ns"]
    for s, ann in zip(clocks, anns):
        mapped = origin + (s[2] - tr.t0) * 1e9
        assert abs(start + ann - mapped) < 1e6
    # the offset between span and annotation holds from anchor to anchor
    offs = [ann - s[2] * 1e9 for s, ann in zip(clocks, anns)]
    assert abs(offs[0] - offs[1]) < 1e6


def test_chrome_trace_gives_each_thread_its_track():
    import threading

    tr = SpanTracer()
    tr.begin_cycle()
    tr.rec("dispatch", 1.0, 1.1)
    t = threading.Thread(target=tr.rec, args=("poll", 1.0, 1.2),
                         name="ingest-x")
    t.start()
    t.join()
    ct = tr.to_chrome_trace()
    tids = {ev["name"]: ev["tid"] for ev in ct["traceEvents"]}
    assert tids["dispatch"] != tids["poll"]
    assert ct["otherData"]["threads"][str(tids["poll"])] == "ingest-x"
    # no anchor yet: nothing to overlay on a profiler trace
    assert "origin_trace_ns" not in ct["otherData"]


def test_kg_stats_gating():
    """The occupancy kernel is gated by observability.kg-stats, which
    defaults to the tracing flag: the shipping default pays nothing; the
    explicit flag lights up skew telemetry without span tracing."""
    env, _ = _windowed_env({
        "observability.tracing": False,
        "observability.kg-stats": True,
    }, total=8192)
    env.execute("kg-only")
    assert env._span_tracer is None
    snap = env.metric_registry.snapshot("jobs.kg-only.")
    assert snap["jobs.kg-only.kg_occupied_groups"] > 0

    env2, _ = _windowed_env({"observability.tracing": False}, total=8192)
    env2.execute("default-job")
    # default: no occupancy kernel ran (cache stays empty)
    snap2 = env2.metric_registry.snapshot("jobs.default-job.")
    assert snap2["jobs.default-job.kg_occupied_groups"] == 0


def test_drain_stats_gating():
    """The drain flight recorder is gated by observability.drain-stats
    (defaulting to the tracing flag, same discipline as kg-stats): off
    means the drain kernels compile WITHOUT the telemetry payload (the
    trace-tier ledger test pins byte-identity) and the /pipeline report
    stays unavailable; on lights up the per-shard aggregation without
    span tracing."""
    resident = {
        "observability.tracing": False,
        "pipeline.prefetch": "on",
        "pipeline.device-staging": "on",
        "pipeline.resident-loop": "on",
        "pipeline.ring-depth": 4,
    }
    env, _ = _windowed_env({
        **resident,
        "observability.drain-stats": True,
        # fetch the payload on every drain: short jobs drain only a
        # handful of times, far fewer than the default sampling stride
        "observability.drain-stats-every": 1,
    }, total=8192)
    env.execute("drain-only")
    assert env._span_tracer is None
    rep = env._pipeline_report()
    assert rep["available"] is True
    assert rep["n_shards"] == 1 and rep["ring_depth"] == 4
    assert rep["drains"] > 0 and rep["payload_fetches"] > 0
    assert rep["shards"][0]["totals"]["events"] > 0
    assert rep["shards"][0]["occupancy"]

    # default (tracing off): the recorder never instantiates
    env2, _ = _windowed_env(resident, total=4096)
    env2.execute("drain-default")
    rep2 = env2._pipeline_report()
    assert rep2["available"] is False and "reason" in rep2


def test_checkpoint_sync_span_and_trace_dump(tmp_path):
    dump = tmp_path / "trace.json"
    env, _ = _windowed_env({
        "observability.trace-dump": str(dump),
    })
    env.enable_checkpointing(4, str(tmp_path / "ck"))
    env.execute("ck-traced")
    names = {s[0] for s in env._span_tracer.snapshot()}
    assert "checkpoint_sync" in names
    # the end-of-job dump wrote loadable Chrome-trace JSON
    on_disk = json.load(open(dump))
    assert on_disk["traceEvents"]


# --------------------------------------------------------- web endpoints

def _get_json(port, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=10
    ) as r:
        return json.loads(r.read())


def test_web_traces_keygroups_and_prometheus():
    from flink_tpu.runtime.cluster import MiniCluster
    from flink_tpu.runtime.web import WebMonitor

    env, _ = _windowed_env()
    cluster = MiniCluster()
    web = WebMonitor(cluster)
    port = web.start()
    jid = cluster.submit(env, "obs-web-job")
    try:
        assert cluster.wait(jid, 120) == "FINISHED"
        # acceptance: /traces returns valid Chrome-trace JSON with the
        # step-phase spans
        tr = _get_json(port, f"/jobs/{jid}/traces")
        assert tr["enabled"] is True
        assert tr["traceEvents"], "non-empty traceEvents required"
        names = {ev["name"] for ev in tr["traceEvents"]}
        assert {"source", "dispatch", "barrier_fetch", "emit"} <= names
        # skew telemetry over the web API
        kg = _get_json(port, f"/jobs/{jid}/keygroups?k=5")
        assert kg["available"] is True
        assert kg["occupancy_top"] and kg["fill_top"]
        assert len(kg["occupancy_top"]) <= 5
        # gauges visible via the job metric snapshot...
        snap = _get_json(port, f"/jobs/{jid}/metrics")
        assert snap["jobs.obs-web-job.kg_skew_ratio"] >= 1.0
        assert "jobs.obs-web-job.watermark_lag_ms" in snap
        # ...and via the Prometheus endpoint (text exposition, one port)
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10
        ) as r:
            assert r.headers["Content-Type"].startswith("text/plain")
            text = r.read().decode()
        assert '# TYPE flink_tpu_kg_skew_ratio gauge' in text
        assert 'flink_tpu_kg_skew_ratio{job="obs-web-job"}' in text
        assert 'flink_tpu_watermark_lag_ms{job="obs-web-job"}' in text
        assert 'flink_tpu_records_in{job="obs-web-job"} 20000' in text
    finally:
        web.stop()


def test_web_job_scoped_endpoints_404_unknown_job():
    """Unknown/finished job ids on job-scoped endpoints return a JSON 404
    body, never a raised 500 (satellite: guard the web surface)."""
    from flink_tpu.runtime.cluster import MiniCluster
    from flink_tpu.runtime.web import WebMonitor

    cluster = MiniCluster()
    web = WebMonitor(cluster)
    port = web.start()
    try:
        for path in (
            "/jobs/nope", "/jobs/nope/traces", "/jobs/nope/keygroups",
            "/jobs/nope/backpressure", "/jobs/nope/checkpoints",
            "/jobs/nope/metrics", "/jobs/nope/checkpoints/config",
            "/jobs/nope/plan", "/jobs/nope/exceptions",
            "/jobs/nope/recovery", "/jobs/nope/elasticity",
            "/jobs/nope/pipeline", "/jobs/nope/doctor",
            "/jobs/nope/controller",
        ):
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get_json(port, path)
            assert ei.value.code == 404, path
            body = json.loads(ei.value.read())
            assert "error" in body, path
    finally:
        web.stop()


def test_web_traces_job_without_tracing():
    """A known job that never enabled tracing gets a 200 with an explicit
    enabled:false payload — distinguishable from an unknown job's 404."""
    from flink_tpu.runtime.cluster import MiniCluster
    from flink_tpu.runtime.web import WebMonitor

    env, _ = _windowed_env({"observability.tracing": False}, total=2048)
    cluster = MiniCluster()
    web = WebMonitor(cluster)
    port = web.start()
    jid = cluster.submit(env, "untraced-web")
    try:
        assert cluster.wait(jid, 120) == "FINISHED"
        tr = _get_json(port, f"/jobs/{jid}/traces")
        assert tr["enabled"] is False and tr["traceEvents"] == []
    finally:
        web.stop()


# ------------------------------------------------------ compile tracking

def test_compile_events_counts_and_stage_attribution():
    import jax
    import jax.numpy as jnp

    CompileEvents.install()
    mark = CompileEvents.mark()

    @jax.jit
    def f(x):
        return x * 3 + 1

    with CompileEvents.stage("test-stage"):
        f(jnp.arange(7)).block_until_ready()
    count, secs = CompileEvents.since(mark)
    assert count >= 1 and secs > 0
    rep = CompileEvents.report()
    assert rep["by_stage"]["test-stage"]["count"] >= 1
