"""Finds the benchmark's parts by name. Everything that belongs to one
kind of job, arrival, key distribution or metric is a file of its own
under ``<root>/benchmark/<kind>/<name>.py``, so a later PR adds one as a
new file:

- ``jobs/<window kind>_<aggregate>.py``: ``build`` wires the job through
  the public entry;
- ``references/<window kind>_<aggregate>.py``: the plain reference of
  the same semantics, the comparison, and its lower-precision control;
- ``arrivals/<arrival>.py``: ``Schedule``, when each event is due and
  its event time;
- ``keys/<keys>.py``: ``keys``, each event's key from its index and seed;
- ``end_to_end/<metric>.py``, ``per_layer/<metric>.py``: ``read``.
"""

from __future__ import annotations

import importlib.util
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_loaded: dict = {}


def path_of(kind: str, name: str, root: str = ROOT) -> str:
    return os.path.join(root, "benchmark", kind, name + ".py")


def load(kind: str, name: str, root: str = ROOT):
    """The module ``<root>/benchmark/<kind>/<name>.py``."""
    path = path_of(kind, name, root)
    if path not in _loaded:
        if not os.path.exists(path):
            raise ValueError(f"no {kind} named {name!r}: {path} is missing")
        spec = importlib.util.spec_from_file_location(
            f"benchmark_{kind}_{name.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _loaded[path] = mod
    return _loaded[path]


def job_kind(job: dict) -> str:
    """``tumbling_sum`` for a tumbling window summed: the name of the
    job's builder and of its reference."""
    return f"{job['window']['kind']}_{job['aggregate']}"


def load_reader(kind: str, name: str, root: str = ROOT):
    """``read`` of the metric's file. A quantity split by the end-to-end
    metric it moves, ``<quantity>.<split>`` (``device.idle_share.sat``,
    ``device.idle_share.rate``), is read by ``<quantity>.py`` where it has
    no file of its own."""
    stem = name.rsplit(".", 1)[0]
    if not os.path.exists(path_of(kind, name, root)) and \
            os.path.exists(path_of(kind, stem, root)):
        name = stem
    return load(kind, name, root).read
