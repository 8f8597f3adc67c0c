import json
import os

import numpy as np
import pytest

from benchmark import trace

HERE = os.path.dirname(os.path.abspath(__file__))


def iv(*pairs):
    return np.asarray(pairs, np.float64).reshape(-1, 2)


def test_union_and_length():
    u = trace.union(iv((5, 7), (0, 2), (1, 3), (7, 8)))
    assert u.tolist() == [[0, 3], [5, 8]]
    assert trace.length(u) == 6


def test_minus_is_the_uncovered_part():
    assert trace.minus(iv((0, 10)), iv((2, 3), (5, 7))) == 7
    assert trace.minus(iv((0, 10)), iv()) == 10
    assert trace.minus(iv((0, 4), (6, 10)), iv((3, 7))) == 6
    assert trace.minus(iv((2, 3)), iv((0, 10))) == 0


def test_short_name_drops_the_hlo_text():
    assert trace.short_name("%fusion.28 = s32[4]{0} fusion(s32[4] %a)") \
        == "fusion.28"
    assert trace.short_name("jit_update_step(123)") == "jit_update_step(123)"


def test_kernel_of():
    k = {"update": ["update_step"], "fire": ["fire_step"]}
    assert trace.kernel_of("jit_update_step(12)", k) == "update"
    assert trace.kernel_of("jit_fire_step", k) == "fire"
    assert trace.kernel_of("jit_add", k) is None


def synthetic():
    ops = [["fusion.1", 0, 100], ["all-to-all.2", 100, 50],
           ["fusion.3", 120, 80], ["fusion.4", 500, 100]]
    mods = [["jit_update_step(1)", 0, 200], ["jit_fire_step(2)", 500, 100]]
    dev = lambda n: {"name": f"/device:TPU:{n}", "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": mods}]}
    host = {"name": "/host:CPU", "lines": [{"name": "python", "events": [
        ["source poll", 210, 90], ["sink", 350, 100]]}]}
    return {"planes": [dev(1), host, dev(0)]}


def test_reduce_on_a_synthetic_trace():
    out = trace.reduce(synthetic(), {"update": ["update_step"],
                                     "fire": ["fire_step"]})
    d0, d1 = out["devices"]
    assert d0["name"] == "/device:TPU:0" and d1["name"] == "/device:TPU:1"
    assert d0["busy_ns"] == 300            # [0, 200) and [500, 600)
    assert d0["kernel_ns"] == {"update": 200, "fire": 100}
    assert d0["kernel_calls"] == {"update": 1, "fire": 1}
    assert d0["a2a_ns"] == 50
    assert d0["a2a_exposed_ns"] == 20      # [100, 120) has no other op
    # per-op time averaged over the devices, longest first
    # named within the kernel whose module they run in
    assert out["device_ops"][0] == ["update/fusion.1", 100 / 1e9]
    assert out["device_ops"][1] == ["fire/fusion.4", 100 / 1e9]
    assert out["device_ops"][2] == ["update/fusion.3", 80 / 1e9]
    # the one gap [200, 500): "sink" overlaps it 100, "source poll" 90
    assert out["idle_gaps"] == [["sink", 300 / 1e9]]


def test_idle_gap_with_no_host_mark_is_other_host():
    gaps = trace.idle_gaps(iv((0, 1), (5, 6), (7, 8)), {"sink": iv()}, 10)
    assert gaps == [["other host", 4e-9], ["other host", 1e-9]]


def test_reduce_on_a_recorded_excerpt():
    """A slice of a v5e trace of tumble_sum_1m.rate80 (PR 22) around a
    fire: the reduction agrees with a brute-force count at 1 us."""
    with open(os.path.join(HERE, "data", "trace_excerpt.json")) as f:
        tr = json.load(f)
    with open(os.path.join(HERE, "..", "kernels.json")) as f:
        kernels = json.load(f)["kernels"]
    out = trace.reduce(tr, kernels)
    (dev,) = out["devices"]
    ops = trace.line_events(trace.device_planes(tr)[0], trace.OPS_LINE)
    t0 = min(e[1] for e in ops)
    t1 = max(e[1] + e[2] for e in ops)
    grid = np.zeros(int((t1 - t0) / 1e3) + 2, bool)
    for _, s, d in ops:
        grid[int((s - t0) / 1e3):int((s + d - t0) / 1e3)] = True
    assert dev["busy_ns"] == pytest.approx(grid.sum() * 1e3, rel=0.01)
    mods = trace.line_events(trace.device_planes(tr)[0], trace.MODULES_LINE)
    fire_ns = sum(d for n, _, d in mods if n.startswith("jit_fire_step"))
    assert fire_ns > 0 and dev["kernel_ns"]["fire"] == fire_ns
    assert dev["kernel_calls"]["fire"] == sum(
        1 for n, _, _ in mods if n.startswith("jit_fire_step"))
    assert any(name.startswith("fire/") for name, _ in out["device_ops"])
    assert dev["a2a_ns"] == 0
