"""The benchmark's own tests: on the CPU, at tiny sizes, with four virtual
devices for the mesh path and JAX's persistent compile cache off.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import json
import os
import shutil
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)

TINY_JOB = {"keys": 4096, "state_capacity": 4096,
            "window": {"kind": "tumbling", "size_ms": 5000},
            "aggregate": "sum",
            "values": {"dtype": "float32", "low": 1, "high": 1000},
            "parallelism": 1, "max_parallelism": 128, "batch": 2048}
TINY_TRAFFIC = {"tiny_sat": {"arrival": "saturate", "events_per_ms": 4,
                             "keys": "uniform"},
                "tiny_rate": {"arrival": "open_loop", "rate_per_s": 40000,
                              "keys": "uniform"}}


def write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


@pytest.fixture
def on_cpu(monkeypatch):
    """Runs of the harness with its look for a chip skipped: the CPU's
    devices stand in, with no peaks."""
    from benchmark import run

    monkeypatch.setattr(run, "require_chips", lambda chips: jax.devices())
    monkeypatch.setattr(run, "peaks", lambda kind: None)
    return run.run_cell


@pytest.fixture
def tiny_root(tmp_path):
    """A checkout root, a copy of this one's benchmark, to which tiny
    cells are added as a later PR would add them: new files and new
    entries, with no existing file edited."""
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for name, par, batch in (("tiny", 1, 2048), ("tiny4", 4, 8192)):
        job = dict(TINY_JOB, parallelism=par, batch=batch)
        write(tmp_path / "benchmark" / "configs" / f"{name}.json",
              {"source": "test", "job": job,
               "options": {"restart-strategy": "none"}})
        bench["configs"].append({"name": name, "source": "test",
                                 "file": f"benchmark/configs/{name}.json",
                                 "reduced": [], "why": "test"})
    for name, t in TINY_TRAFFIC.items():
        write(tmp_path / "benchmark" / "traffic" / f"{name}.json", t)
    cells = {"tiny.sat": ("tiny", "tiny_sat", 1),
             "tiny.rate": ("tiny", "tiny_rate", 1),
             "tiny4.sat": ("tiny4", "tiny_sat", 4)}
    for cell, (conf, traffic, chips) in cells.items():
        bench["workloads"].append({"name": cell, "config": conf,
                                   "traffic": traffic, "chips": chips,
                                   "why": "test"})
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    e2e["events_per_s"]["workloads"] += ["tiny.sat", "tiny4.sat"]
    e2e["result_latency_p50_ms"]["workloads"].append("tiny.rate")
    write(tmp_path / "BENCHMARK.json", bench)
    return tmp_path
