"""Rule ``hot-path-sync``: no host synchronization in hot-path modules.

The step loop's whole performance story is that update steps dispatch
asynchronously and nothing reads device memory between barriers — a
single stray ``block_until_ready()``, ``.item()``, or
``np.asarray(<device array>)`` in a kernel or step builder serializes
the pipeline and costs a device-to-host round trip per call
(tools/microbench_d2h.py measures it on the chip). This rule
fails the build when one of those host-sync constructs appears in the
hot-path modules:

    flink_tpu/ops/**.py          (device kernels)
    flink_tpu/runtime/step.py    (compiled step builders)
    flink_tpu/runtime/ingest.py  (pipelined ingest / device staging)
    flink_tpu/runtime/elastic.py (elastic re-plan helpers)

outside an allowlisted barrier section.

Round 12 (resident drain loop) extends the detected constructs: the
drain's host sections — ring publish/release in ingest.py and the
drain-group assembly feeding ``build_window_resident_drain`` — must
stay sync-free for the one-dispatch-per-ring-drain story to hold, so
``jax.device_get(...)`` (the D2H fetch a stray eager fire consumption
would spell) and ``np.array(<device array>)`` (materializes like
``np.asarray``) now flag alongside the original three. The staging
ring's transfer-completion wait keeps its inline marker: it blocks on
the INGEST thread by design, never the step loop.

Allowlisting, in order of preference:

  1. Naming convention — functions whose name contains ``host`` or ends
     with ``_np`` are host-side by contract (hash64_host, estimate_np,
     ...); the hot path never calls them per step.
  2. The explicit ALLOWLIST below — (relative path, function qualname)
     pairs for documented host-facing APIs that don't fit the naming
     convention (e.g. segment.grouped_reduce, the batch DataSet seam).
  3. An inline ``# host-sync-ok: <reason>`` comment on the flagged line
     for true one-offs (the pre-framework spelling, kept so existing
     markers stay honored) — or the framework-wide
     ``# lint: allow(hot-path-sync): <reason>``.

Detection is AST-based (not grep) so strings/comments can't false-
positive and aliasing ``numpy as np`` is resolved per call site.

Migrated from tools/check_hot_path_sync.py (ISSUE 2) into the shared
framework (ISSUE 9) without weakening: same constructs, same paths,
same allowlists. tools/check_hot_path_sync.py remains as a thin shim.
"""

from __future__ import annotations

import os
from typing import List, NamedTuple, Tuple

import ast

from tools.lint.core import Finding, QualnameVisitor, RepoTree, Rule

# hot-path locations, relative to the repo root
HOT_PATHS = (
    "flink_tpu/ops",
    "flink_tpu/runtime/step.py",
    "flink_tpu/runtime/ingest.py",
    # elastic re-plan helpers (ISSUE 8): imported by the executor's
    # recovery path; the one legitimate wait — the recovery-path device
    # health probe — carries the inline marker
    "flink_tpu/runtime/elastic.py",
    # drain flight-recorder host half (ISSUE 14): the consume-path
    # unpack target. Its whole contract is pure host arithmetic over
    # ALREADY-FETCHED numpy payloads — the lagged telemetry channel must
    # never introduce a fresh device sync, so the module is held to the
    # same standard as the kernels it observes. (The publish-time stamps
    # stay inside ingest.py's two allowlisted ingest-thread blocks.)
    "flink_tpu/metrics/drain_stats.py",
    # pipeline doctor rule engine (ISSUE 17): pure dict arithmetic over
    # already-assembled snapshots — a rule that synced the device would
    # turn a diagnostics scrape into a pipeline stall, so the module is
    # held to hot-path discipline alongside drain_stats.py
    "flink_tpu/metrics/doctor.py",
    # stage-graph planner (ISSUE 16): setup-time only, but its plan
    # products (specs, codecs, snapshot/restore payloads) feed the
    # chained drain directly — hold it to hot-path discipline so no
    # per-drain device sync sneaks in through a planner helper
    "flink_tpu/runtime/stages.py",
    # self-tuning runtime controller (ISSUE 19): serviced at the poll-
    # cycle boundary on the step-loop thread — its whole contract is
    # host-side arithmetic over ALREADY-FETCHED telemetry (regime/heat
    # EWMAs, doctor findings), so a device sync in a decision would
    # stall the very pipeline it tunes
    "flink_tpu/runtime/controller.py",
)

# hot SECTIONS (ISSUE 20b): function-scoped coverage for modules that
# are legitimately host-heavy overall but contain drain-boundary code
# held to hot-path discipline. dcn.py is a host control loop — its
# lockstep poll/pad path syncs freely by design — but the per-host
# RESIDENT drain boundary multiplies every stray sync by the drain
# depth, so the new boundary functions are scanned with the same rule;
# their few legitimate barriers (the stop/drained fetch, the fire-
# payload unpack, the source-poll timestamp math) carry inline
# ``# host-sync-ok:`` markers documenting WHY each one is a boundary.
HOT_SECTIONS = {
    "flink_tpu/runtime/dcn.py": (
        "_RebalanceRing._frame_deadline_s",
        "_DCNRunnerBase._poll_chunk",
        "_DCNRunnerBase._run_resident",
        "_DCNRunnerBase._gslots",
        "DCNWindowRunner._emit_local_slots",
    ),
}

# documented host-facing seams that live in hot-path modules but are
# never called from inside the step loop
ALLOWLIST: set = {
    # host-side key encode: runs in prep_batch on numpy inputs
    ("flink_tpu/ops/hashing.py", "splitmix64"),
    ("flink_tpu/ops/hashing.py", "key_identity64"),
    # batch DataSet/Table aggregation API: documented to return numpy
    ("flink_tpu/ops/segment.py", "grouped_reduce"),
    # sketch host mirrors: query-path estimates over fetched registers
    ("flink_tpu/ops/sketches.py", "CountMinSketch.__init__"),
    ("flink_tpu/ops/sketches.py", "_numeric"),
}

SYNC_ATTRS = ("block_until_ready", "item")
INLINE_MARKER = "host-sync-ok"


class Violation(NamedTuple):
    path: str
    line: int
    func: str
    what: str

    def __str__(self):
        return (f"{self.path}:{self.line}: {self.what} in {self.func!r} "
                f"— host sync on the hot path (allowlist it only if this "
                f"is a documented barrier section; see "
                f"tools/lint/rules/hot_path_sync.py)")


def _is_np_asarray(call: ast.Call) -> bool:
    f = call.func
    return (
        isinstance(f, ast.Attribute)
        and f.attr in ("asarray", "array")
        and isinstance(f.value, ast.Name)
        and f.value.id in ("np", "numpy")
    )


def _is_device_get(call: ast.Call) -> bool:
    f = call.func
    return (
        isinstance(f, ast.Attribute)
        and f.attr == "device_get"
        and isinstance(f.value, ast.Name)
        and f.value.id == "jax"
    )


def _is_sync_attr(call: ast.Call) -> bool:
    f = call.func
    return isinstance(f, ast.Attribute) and f.attr in SYNC_ATTRS


class _Scanner(QualnameVisitor):
    def __init__(self, relpath: str, lines: List[str], sections=None):
        super().__init__()
        self.relpath = relpath
        self.lines = lines
        self.sections = sections   # qualname prefixes, or None = whole file
        self.out: List[Violation] = []

    def _in_section(self) -> bool:
        if self.sections is None:
            return True
        qn = self.qualname()
        return any(qn == s or qn.startswith(s + ".")
                   for s in self.sections)

    def _allowed(self, node: ast.Call) -> bool:
        qn = self.qualname()
        # naming convention: host-side helpers
        for part in self.stack:
            if "host" in part or part.endswith("_np"):
                return True
        if (self.relpath, qn) in ALLOWLIST:
            return True
        line = (
            self.lines[node.lineno - 1]
            if 0 < node.lineno <= len(self.lines) else ""
        )
        return INLINE_MARKER in line

    def visit_Call(self, node: ast.Call):
        what = None
        if _is_sync_attr(node):
            what = f".{node.func.attr}()"
        elif _is_np_asarray(node):
            what = f"np.{node.func.attr}(...)"
        elif _is_device_get(node):
            what = "jax.device_get(...)"
        if what is not None and self._in_section() \
                and not self._allowed(node):
            self.out.append(Violation(
                self.relpath, node.lineno, self.qualname(), what
            ))
        self.generic_visit(node)


def check_source(src: str, relpath: str) -> List[Violation]:
    tree = ast.parse(src, filename=relpath)
    sc = _Scanner(relpath, src.splitlines())
    sc.visit(tree)
    return sc.out


def hot_path_files(root: str) -> List[Tuple[str, str]]:
    """[(abs_path, rel_path)] of every hot-path module under `root`."""
    out = []
    for hp in HOT_PATHS:
        full = os.path.join(root, hp)
        if os.path.isfile(full):
            out.append((full, hp))
        elif os.path.isdir(full):
            for dirpath, _dirs, files in os.walk(full):
                for f in sorted(files):
                    if not f.endswith(".py"):
                        continue
                    p = os.path.join(dirpath, f)
                    out.append((p, os.path.relpath(p, root)))
    return out


def check_tree(root: str) -> List[Violation]:
    violations: List[Violation] = []
    for path, rel in hot_path_files(root):
        with open(path) as f:
            violations.extend(
                check_source(f.read(), rel.replace(os.sep, "/"))
            )
    for rel, sections in HOT_SECTIONS.items():
        full = os.path.join(root, rel)
        if not os.path.isfile(full):
            continue
        with open(full) as f:
            src = f.read()
        sc = _Scanner(rel, src.splitlines(), sections=sections)
        sc.visit(ast.parse(src, filename=rel))
        violations.extend(sc.out)
    return violations


class HotPathSyncRule(Rule):
    name = "hot-path-sync"
    title = ("no block_until_ready/.item()/np.asarray/np.array/"
             "jax.device_get host sync in hot-path modules outside "
             "allowlisted barrier sections")
    established = "PR 2"

    def check(self, tree: RepoTree) -> List[Finding]:
        out: List[Finding] = []
        for pm in tree.walk(*HOT_PATHS):
            sc = _Scanner(pm.relpath, pm.lines)
            sc.visit(pm.tree)
            out.extend(
                Finding(self.name, v.path, v.line, str(v), v.func)
                for v in sc.out
            )
        for rel, sections in HOT_SECTIONS.items():
            for pm in tree.walk(rel):
                sc = _Scanner(pm.relpath, pm.lines, sections=sections)
                sc.visit(pm.tree)
                out.extend(
                    Finding(self.name, v.path, v.line, str(v), v.func)
                    for v in sc.out
                )
        return out


def main(argv=None) -> int:
    """Back-compat CLI (tools/check_hot_path_sync.py)."""
    import argparse
    import sys

    ap = argparse.ArgumentParser(
        description="Static check: no host synchronization in hot-path "
                    "modules.")
    ap.add_argument(
        "--root",
        default=os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))),
    )
    args = ap.parse_args(argv)
    violations = check_tree(args.root)
    for v in violations:
        print(v, file=sys.stderr)
    if violations:
        print(f"{len(violations)} hot-path host-sync violation(s)",
              file=sys.stderr)
        return 1
    return 0
