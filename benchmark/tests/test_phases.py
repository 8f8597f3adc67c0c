"""The program's spans on the trace's clock (``benchmark/phases.py``) and
the per-layer readers of the spans, on synthetic traces and spans, and on
a tiny traced run on the CPU."""

import pytest

from benchmark import phases, registry
from benchmark.readings import Ctx

EX, ING = "MainThread", "flink-tpu-ingest"
# the trace's clock runs 1,000 s ahead of perf_counter, in ns
OFF = 1000e9


def span(name, t0, t1, thread=EX, **attrs):
    return (name, "job", t0, t1 - t0, attrs or None, thread)


def synthetic():
    """Device 0 busy [0, 10) [20, 30) [40, 50) [60, 70) ms of trace time
    (perf_counter 0 is trace 1,000 s); program spans around the gaps."""
    ms = 1e6
    ops = [["fusion", OFF + a * ms, 10 * ms] for a in (0, 20, 40, 60)]
    host = [[phases.CLOCK_ANNOTATION, OFF + 1 * ms + 2e3, 5e3],
            [phases.CLOCK_ANNOTATION, OFF + 1001 * ms + 2e3, 5e3],
            ["source poll", OFF + 50 * ms, 10 * ms],
            ["sink", OFF + 30 * ms, 10 * ms]]
    tr = {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops",
                                             "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "python",
                                         "events": host}]}]}
    s = 1e-3
    spans = [
        span("clock", 1 * s, 1.01 * s), span("clock", 1001 * s, 1001.01 * s),
        # gap [10, 20): emit, its fetch child, and a collection inside
        span("emit", 8 * s, 22 * s), span("emit_fetch", 9 * s, 19 * s),
        span("gc", 12 * s, 14 * s, generation=2),
        # gap [30, 40): the ingest thread's poll covers all of it, the
        # executor's emit_sink half, and inside emit; the sink mark too
        span("poll", 28 * s, 42 * s, thread=ING, batch=7),
        span("emit", 29 * s, 41 * s), span("emit_sink", 35 * s, 41 * s),
        # gap [50, 60): no program span, the harness's poll mark
    ]
    return tr, spans


def test_gaps_are_named_by_the_program_where_it_has_a_span():
    tr, spans = synthetic()
    pairs = phases.anchor_pairs(spans, phases.clock_annotations(tr))
    assert len(pairs) == 2
    off = phases.offset_ns(pairs, 0.05)
    assert off == pytest.approx(OFF + 2e3)
    mapped = phases.on_trace_clock(spans, off)
    busy = phases.trace_mod.union(phases.trace_mod.intervals(
        tr["planes"][0]["lines"][0]["events"]))
    gaps = phases.name_gaps(busy, mapped, EX,
                            phases.trace_mod.host_marks(tr))
    assert [g[0] for g in sorted(gaps, key=lambda g: g[2])] == [
        "gc", "emit_sink", "source poll"]


def test_unattributed_share_counts_idle_time_no_executor_span_covers():
    tr, spans = synthetic()
    mapped = phases.on_trace_clock(spans, OFF)
    busy = phases.trace_mod.union(phases.trace_mod.intervals(
        tr["planes"][0]["lines"][0]["events"]))
    t0, t1 = OFF, OFF + 70e6
    # idle: [10, 20) covered by emit; [30, 40) by emit; [50, 60) by
    # nothing of the executor (the ingest poll does not count)
    share = phases.unattributed_share(busy, mapped, EX, t0, t1)
    assert share == pytest.approx(10 / 70)


def test_analyse_needs_an_anchor():
    tr, spans = synthetic()
    no_clock = [s for s in spans if s[0] != "clock"]
    assert phases.analyse(tr, no_clock, 0.0, 0.07, {}) is None
    out = phases.analyse(tr, spans, 0.0, 0.07, {})
    assert out["anchors"] == 2 and out["anchor_spread_ms"] < 1e-6
    assert [g[0] for g in out["idle_gaps"]] == ["gc", "emit_sink",
                                                "source poll"]
    assert out["idle_share"] == pytest.approx(30 / 70, abs=1e-3)


def test_clock_check_pairs_modules_with_their_spans():
    ms = 1e6
    mods = [["jit_update_step(1)", 5 * ms, 3 * ms],
            ["jit_fire_step(2)", 10 * ms, 4 * ms],
            ["jit_update_step(1)", 20 * ms, 3 * ms],
            ["jit_update_step(1)", 24 * ms, 3 * ms]]
    tr = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": mods}]}]}
    kernels = {"update": ["^jit_update_step"], "fire": ["^jit_fire_step"]}
    mapped = [(n, EX, a * ms, b * ms, None) for n, a, b in (
        ("dispatch", 4.5, 4.6), ("fire", 9.5, 9.6),
        ("barrier_fetch", 9.6, 14.5), ("dispatch", 19.0, 19.1),
        ("dispatch", 19.2, 19.3))]
    out = phases.clock_check(tr, kernels, mapped, EX)
    assert out["fires"] == 1 and out["updates"] == 3
    assert out["fire_end_after_fetch_ms"] == pytest.approx(-0.5)
    assert out["update_start_before_dispatch_ms"] == pytest.approx(-0.5)
    # host spans mapped 1 ms late: an update now starts before its
    # dispatch
    late = [(n, t, a + ms, b + ms, x) for n, t, a, b, x in mapped]
    assert phases.clock_check(tr, kernels, late, EX)[
        "update_start_before_dispatch_ms"] > 0


class Win:
    t_open, t_close = 0.0, 1.0


def ctx_of(spans):
    return Ctx({}, {"window": Win(), "sched": None, "spans": spans}, 0.0)


def read(name, spans):
    return registry.load_reader("per_layer", name)(ctx_of(spans))


NEW = ("executor.inflight_wait_share.sat", "ingest.blocked_share.sat",
       "ingest.queue_wait_p99_ms.rate", "job.gc_pause_max_ms.rate")


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_spans_reads_nothing(name):
    # the spans of a program before the clock anchor: five fields, none
    # of the new names, no batch ids
    old = [s[:5] for s in (span("dispatch", 0.1, 0.2, step=3),
                           span("source", 0.0, 0.1, records=9))]
    assert read(name, old) is None


def test_the_span_readers():
    spans = [span("clock", 0.0, 0.001),
             span("poll", -0.2, -0.1, ING, batch=0),
             span("poll", 0.1, 0.2, ING, batch=1),
             span("handoff", 0.2, 0.6, ING, batch=1),
             span("dispatch", 0.05, 0.06, batch=0),
             span("inflight_wait", 0.06, 0.3, batch=0),
             span("dispatch", 0.7, 0.71, batch=1),
             span("drain", 0.8, 0.9, batch=[1, 2]),
             span("gc", 0.5, 0.53, ING, generation=2),
             span("gc", 1.5, 1.9, generation=2)]
    assert read("executor.inflight_wait_share.sat", spans) == \
        pytest.approx(0.24)
    assert read("ingest.blocked_share.sat", spans) == pytest.approx(0.4)
    # batch 0 waited 0.15 s, batch 1 0.5 s (its first dispatch)
    assert read("ingest.queue_wait_p99_ms.rate", spans) == \
        pytest.approx(500)
    assert read("job.gc_pause_max_ms.rate", spans) == pytest.approx(30)
    # anchored and quiet: zero, not nothing
    quiet = spans[:1]
    assert read("ingest.blocked_share.sat", quiet) == 0.0
    assert read("job.gc_pause_max_ms.rate", quiet) == 0.0


def test_a_tiny_traced_run_anchors_its_spans(tiny_root, on_cpu):
    """On the CPU the trace has no device plane, but the program's
    anchors are found in it and pair with the clock spans."""
    out, rec, tr, kernels = phases.run_kept("tiny.sat", 2**31 + 11, 2.5,
                                            root=str(tiny_root))
    assert out["correct"] is True
    pairs = phases.anchor_pairs(rec["spans"], phases.clock_annotations(tr))
    assert len(pairs) >= 2
    offs = [a - s * 1e9 for s, a in pairs]
    assert max(offs) - min(offs) < 1e6
    win = rec["window"]
    res = phases.analyse(tr, rec["spans"], win.t_open, win.t_close, kernels)
    assert res["anchors"] == len(pairs) and res["idle_gaps"] == []
