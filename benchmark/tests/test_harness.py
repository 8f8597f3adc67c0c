import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import registry, run

from .conftest import ROOT, write


def run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "tumble_sum_1m.saturate", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_the_cpu_and_prints_no_result(tmp_path):
    # a copy, so the refused run leaves no compile cache in the repo
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    os.symlink(os.path.join(ROOT, "flink_tpu"), tmp_path / "flink_tpu")
    p = run_cli(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no accelerator" in p.stderr


def test_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = run_cli(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_cell_metrics_follow_the_entries():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    names = lambda c, t: {m["name"] for m in run.cell_metrics(bench, c, t)}
    assert names("tumble_sum_1m.rate80", False) == {
        "result_latency_p50_ms", "setup_s"}
    assert names("tumble_sum_1m.saturate", False) == {"events_per_s",
                                                      "setup_s"}
    assert names("tumble_sum_1m.rate80", True) == {
        "job.result_latency_p99_ms.rate", "ingest.source_lag_ms.rate",
        "executor.fire_latency_p99_ms.rate", "device.idle_share.rate",
        "ingest.queue_wait_p99_ms.rate", "job.gc_pause_max_ms.rate"}
    assert "update_roofline" in names("tumble_sum_1m.saturate", True)


def test_every_entry_has_its_files():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for w in bench["workloads"]:
        cell = run.Cell(w["name"])
        traffic = cell.traffic()
        assert traffic.sched.warmup > 0
        registry.load("jobs", registry.job_kind(cell.job))
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            assert callable(registry.load_reader(kind, m["name"]))


def test_a_missing_part_is_refused():
    with pytest.raises(ValueError, match="no arrivals named"):
        registry.load("arrivals", "closed_loop")


@pytest.mark.parametrize("cell", ["tiny.sat", "tiny.rate", "tiny4.sat"])
def test_a_tiny_cell_runs_correct_on_the_cpu(tiny_root, on_cpu, cell):
    out = on_cpu(cell, 2**31 + 11, 1.5, False, root=str(tiny_root))
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert "setup_s" in out["metrics"]
    assert list(out)[-1] == "checks"


MAX_JOB = """
from flink_tpu.core.time import TimeCharacteristic


def build(env, source, sink, job):
    env.set_stream_time_characteristic(TimeCharacteristic.EventTime)
    (env.add_source(source).key_by(lambda c: c["key"])
     .time_window(job["window"]["size_ms"]).max(lambda c: c["value"])
     .add_sink(sink))
"""

MAX_REFERENCE = """
import numpy as np


def reference(seed, job, n_events, traffic):
    K, W = job["keys"], job["window"]["size_ms"]
    idx = np.arange(n_events)
    flat = traffic.sched.event_ms(idx) // W * K + traffic.keys(idx, seed)
    out = np.zeros(int(flat.max()) + 1)
    np.maximum.at(out, flat, traffic.values(idx, seed))
    return out


def attempted(ref):
    return int(np.count_nonzero(ref))


def compare(cols, ref, job):
    K, W = job["keys"], job["window"]["size_ms"]
    flat = (np.asarray(cols["window_end_ms"]) // W - 1) * K + np.asarray(
        cols["key_id"]).astype(np.int64)
    got = np.zeros(len(ref))
    got[flat] = cols["value"]
    return {"max_differing": int((got != ref).sum()),
            "rows": int(len(flat) - np.count_nonzero(ref))}
"""

HOT_KEYS = """
import numpy as np


def keys(idx, seed, n_keys):
    return (np.asarray(idx) * 7 + seed) % 16
"""

SLOW_SATURATE = """
import numpy as np


class Schedule:
    open_loop = False

    def __init__(self, traffic, batch, window_ms):
        self.per = int(traffic["events_per_2ms"])
        self.warmup = window_ms * self.per // 2 + batch

    def event_ms(self, idx):
        return np.asarray(idx, np.int64) * 2 // self.per

    def start(self, t_open, n_fed):
        pass
"""


def test_adding_a_config_traffic_and_metric_is_files_only(tiny_root, on_cpu):
    """A later PR adds a deployment with a new aggregate, a mix with a new
    arrival kind and key distribution, and a per-layer metric, as new
    files and new BENCHMARK.json entries; the harness finds them."""
    b = tiny_root / "benchmark"
    cfg = json.load(open(b / "configs" / "tiny.json"))
    cfg["job"]["aggregate"] = "max"
    write(b / "configs" / "tiny_max.json", cfg)
    write(b / "traffic" / "hot_slow.json",
          {"arrival": "slow_saturate", "events_per_2ms": 5, "keys": "hot"})
    for kind, name, text in (("jobs", "tumbling_max", MAX_JOB),
                             ("references", "tumbling_max", MAX_REFERENCE),
                             ("keys", "hot", HOT_KEYS),
                             ("arrivals", "slow_saturate", SLOW_SATURATE)):
        (b / kind / f"{name}.py").write_text(text)
    (b / "per_layer" / "source.events_total.py").write_text(
        "def read(ctx):\n    return ctx.rec['n_events']\n")
    bench = json.load(open(tiny_root / "BENCHMARK.json"))
    bench["configs"].append({"name": "tiny_max", "source": "test",
                             "file": "benchmark/configs/tiny_max.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny_max.hot", "config": "tiny_max",
                               "traffic": "hot_slow", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "source.events_total",
                               "unit": "events", "better": "higher",
                               "source": "host_clock", "layer": "ingest",
                               "moves": "setup_s",
                               "workloads": ["tiny_max.hot"]})
    write(tiny_root / "BENCHMARK.json", bench)
    cell = run.Cell("tiny_max.hot", str(tiny_root))
    assert [m["name"] for m in run.cell_metrics(cell.bench, cell.name,
                                                True)] \
        == ["source.events_total"]
    out = on_cpu("tiny_max.hot", 3, 1.0, True, root=str(tiny_root))
    assert out["correct"] is True, out["checks"]
    assert set(out["checks"]) >= {"max_differing", "rows"}
    assert out["metrics"]["source.events_total"]["value"] > 0
    assert out["attempted"] > 0


def test_a_split_quantity_shares_its_reader():
    assert registry.load_reader("per_layer", "device.idle_share.sat") is \
        registry.load_reader("per_layer", "device.idle_share.rate")
    with pytest.raises(ValueError):
        registry.load_reader("per_layer", "device.no_such_share.sat")
