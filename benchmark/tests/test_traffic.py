import numpy as np
import pytest

from benchmark import registry
from benchmark.job import Window
from benchmark.readings import Ctx
from benchmark.registry import load_reader
from benchmark.traffic import nearest_rank

R = 1000   # events per second: event i is due at t0 + i ms


def Schedule(traffic, batch, window_ms):
    return registry.load("arrivals", traffic["arrival"]).Schedule(
        traffic, batch, window_ms)


def open_loop(batch=64, window_ms=5000):
    return Schedule({"arrival": "open_loop", "rate_per_s": R}, batch,
                    window_ms)


def test_warmup_is_one_window_plus_one_batch():
    assert open_loop().warmup == 5 * R + 64
    sat = Schedule({"arrival": "saturate", "events_per_ms": 3}, 10, 5000)
    assert sat.warmup == 15_000 + 10
    assert list(sat.event_ms([0, 2, 3, 14_999, 15_000])) == [0, 0, 1, 4999,
                                                             5000]


def test_unknown_arrival_is_refused():
    with pytest.raises(ValueError, match="no arrivals named"):
        Schedule({"arrival": "closed_loop"}, 1, 1)


def test_open_loop_schedule_starts_at_the_window():
    s = open_loop()
    s.start(t_open=100.0, n_fed=s.warmup)
    # the first event after the warm-up is due as the window opens
    assert s.due_s(s.warmup) == pytest.approx(100.0)
    assert s.due_s(s.warmup + 250) == pytest.approx(100.25)
    # event time is the due time in ms from the stream's start
    assert s.event_ms(s.warmup + 250) == (s.warmup + 250)
    # due_by counts indices 0..n-1 due by a time
    assert s.due_by(100.0) == s.warmup + 1
    assert s.due_by(100.0105) == s.warmup + 11


def test_last_event_of_a_window_whatever_its_key():
    s = open_loop()
    assert list(s.last_event_before([5000, 10000])) == [4999, 9999]
    r3 = Schedule({"arrival": "open_loop", "rate_per_s": 3}, 1, 5000)
    # event i at 3/s has event time i * 1000 // 3; 14 -> 4666 ms, 15 -> 5000
    assert int(r3.last_event_before(5000)) == 14
    sat = Schedule({"arrival": "saturate", "events_per_ms": 2}, 1, 5000)
    assert int(sat.last_event_before(5000)) == 9999


def test_nearest_rank():
    v = np.arange(1, 101, dtype=float)
    assert nearest_rank(v, np.ones(100), 50) == 50
    assert nearest_rank(v, np.ones(100), 99) == 99
    assert nearest_rank(np.array([5.0, 1.0]), np.array([1, 99]), 99) == 1.0
    assert nearest_rank(np.array([5.0, 1.0]), np.array([1, 99]), 99.5) == 5.0


def fake_ctx(sched, calls, lag=()):
    win = Window(10.0, None)
    win.t_open, win.t_close = 100.0, 110.0
    rec = {"window": win, "sched": sched, "sink_calls": calls,
           "lag_s": list(lag), "spans": [], "fire_samples": []}
    return Ctx({"job": {"keys": 16}}, rec, 1.0)


def test_result_latency_is_from_the_windows_last_event():
    s = open_loop()
    s.start(t_open=100.0, n_fed=s.warmup)
    end = 10_000   # window [5000, 10000): its last event is 9999
    due_last = s.due_s(9999)
    # two keys of the window, one call; the keys' own last events differ,
    # and neither moves the latency
    calls = [(due_last + 0.2, np.array([1, 2]), np.array([end, end]),
              np.array([1.0, 2.0])),
             # a result emitted after the window closed is not counted
             (111.0, np.array([3]), np.array([15_000]), np.array([1.0]))]
    ctx = fake_ctx(s, calls)
    lat, w = ctx.result_latencies()
    assert list(w) == [2]
    assert lat[0] == pytest.approx(0.2)
    assert ctx.latency_pct(50) == pytest.approx(200.0)
    assert load_reader("per_layer", "job.result_latency_p99_ms.rate")(ctx) == \
        pytest.approx(200.0)


def test_saturated_cells_have_no_result_latency():
    sat = Schedule({"arrival": "saturate", "events_per_ms": 2}, 1, 5000)
    assert fake_ctx(sat, []).latency_pct(99) is None


def test_source_lag_is_the_p99_over_polls():
    lag = [0.001] * 99 + [0.5]
    ctx = fake_ctx(open_loop(), [], lag)
    read = load_reader("per_layer", "ingest.source_lag_ms.rate")
    assert read(ctx) == pytest.approx(1.0)
    ctx = fake_ctx(open_loop(), [], [0.001] * 98 + [0.5, 0.5])
    assert read(ctx) == pytest.approx(500.0)
    assert read(fake_ctx(open_loop(), [])) is None


def test_span_share_clips_to_the_window():
    ctx = fake_ctx(open_loop(), [])
    ctx.rec["spans"] = [("emit", "job", 99.0, 2.0, None),      # 1 s inside
                        ("emit", "job", 105.0, 1.0, None),
                        ("emit", "job", 105.5, 1.0, None),     # overlaps
                        ("route", "job", 101.0, 1.0, None)]
    assert load_reader("per_layer", "executor.emit_share.sat")(ctx) == \
        pytest.approx(0.25)
    assert load_reader("per_layer", "ingest.busy_share.sat")(ctx) == \
        pytest.approx(0.1)
