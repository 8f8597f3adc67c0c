"""Rule ``sort-seam``: device sorts in ops/ live ONLY in segment.py.

The update kernel's pre-combine design is "pay ONE sort per micro-batch
and feed every consumer from it": the accumulator scatter, the fire-
eligibility (touched) plane, the kg_dirty changelog bits, and the
kg_fill skew telemetry all ride the single ``segment.segment_sort``
permutation (window_kernels.update; ISSUE 7). A sort is the most
expensive reordering primitive the kernels use — XLA's CPU sort costs
~4.5ms per 16k lanes, and on TPU it is the whole pre-combine budget —
so a second sort quietly added to a kernel doubles exactly the cost the
shared-sort seam exists to pay once.

This rule fails the build when a sort primitive (``jnp.sort`` /
``jnp.argsort`` / ``jnp.lexsort`` / ``jax.lax.sort`` /
``jax.lax.sort_key_val``, under any of the conventional module aliases)
appears in ``flink_tpu/ops`` outside ``segment.py``. Kernels order
lanes through the segment.py wrappers instead (``segment_sort``,
``sort_values``, ``argsort_ids``, ``invert_permutation``,
``stable_partition``), which keeps
every sort call site greppable in one file and the one-sort-per-batch
contract reviewable at the seam.

There is deliberately NO escape hatch — not the inline marker, and not
the framework's ``# lint: allow`` either (``suppressible = False``): a
new sort in a kernel is a design decision that belongs in segment.py,
not an annotation.

Migrated from tools/check_segment_sort_seam.py (ISSUE 7) into the
shared framework (ISSUE 9) without weakening. The old path remains as
a thin shim.
"""

from __future__ import annotations

import os
from typing import List, NamedTuple, Optional, Tuple

import ast

from tools.lint.core import Finding, QualnameVisitor, RepoTree, Rule

# the scanned tree and the one file sorts may live in
OPS_PATH = "flink_tpu/ops"
SORT_HOME = "flink_tpu/ops/segment.py"

# sort primitives by attribute name; the owning module alias is checked
# against the conventional jax/jnp/lax spellings so dict.sort() false
# positives (list.sort is a bare Name call anyway) cannot fire
SORT_ATTRS = ("sort", "argsort", "lexsort", "sort_key_val", "top_k")
SORT_MODULES = ("jnp", "jax", "lax", "numpy", "np")


class Violation(NamedTuple):
    path: str
    line: int
    func: str
    what: str

    def __str__(self):
        return (
            f"{self.path}:{self.line}: {self.what} in {self.func!r} — "
            f"device sorts in ops/ belong in segment.py (the one-sort "
            f"pre-combine seam; see tools/lint/rules/sort_seam.py)"
        )


def _sort_call(call: ast.Call) -> Optional[str]:
    """Return 'mod.attr' when this call is a sort primitive, else None."""
    f = call.func
    if not isinstance(f, ast.Attribute) or f.attr not in SORT_ATTRS:
        return None
    v = f.value
    # jnp.sort / np.argsort
    if isinstance(v, ast.Name) and v.id in SORT_MODULES:
        return f"{v.id}.{f.attr}"
    # jax.lax.sort / jax.numpy.argsort
    if (
        isinstance(v, ast.Attribute)
        and isinstance(v.value, ast.Name)
        and v.value.id in SORT_MODULES
    ):
        return f"{v.value.id}.{v.attr}.{f.attr}"
    return None


class _Scanner(QualnameVisitor):
    def __init__(self, relpath: str):
        super().__init__()
        self.relpath = relpath
        self.out: List[Violation] = []

    def visit_Call(self, node: ast.Call):
        what = _sort_call(node)
        if what is not None:
            self.out.append(
                Violation(self.relpath, node.lineno, self.qualname(), what)
            )
        self.generic_visit(node)


def check_source(src: str, relpath: str) -> List[Violation]:
    if relpath.replace(os.sep, "/") == SORT_HOME:
        return []
    tree = ast.parse(src, filename=relpath)
    sc = _Scanner(relpath.replace(os.sep, "/"))
    sc.visit(tree)
    return sc.out


def ops_files(root: str) -> List[Tuple[str, str]]:
    """[(abs_path, rel_path)] of every module under flink_tpu/ops."""
    out = []
    full = os.path.join(root, OPS_PATH)
    for dirpath, _dirs, files in os.walk(full):
        for f in sorted(files):
            if f.endswith(".py"):
                p = os.path.join(dirpath, f)
                out.append((p, os.path.relpath(p, root)))
    return out


def check_tree(root: str) -> List[Violation]:
    violations: List[Violation] = []
    for path, rel in ops_files(root):
        with open(path) as f:
            violations.extend(check_source(f.read(), rel))
    return violations


class SortSeamRule(Rule):
    name = "sort-seam"
    title = ("jnp/lax sort primitives in flink_tpu/ops appear only in "
             "segment.py — the one-sort pre-combine seam")
    established = "PR 5"
    suppressible = False   # a new sort is a design decision, not an allow

    def check(self, tree: RepoTree) -> List[Finding]:
        out: List[Finding] = []
        for pm in tree.walk(OPS_PATH):
            if pm.relpath == SORT_HOME:
                continue
            sc = _Scanner(pm.relpath)
            sc.visit(pm.tree)
            out.extend(
                Finding(self.name, v.path, v.line, str(v), v.func)
                for v in sc.out
            )
        return out


def main(argv=None) -> int:
    """Back-compat CLI (tools/check_segment_sort_seam.py)."""
    import argparse
    import sys

    ap = argparse.ArgumentParser(
        description="Static check: device sorts in ops/ live ONLY in "
                    "segment.py.")
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
        print(f"{len(violations)} ops/ sort-seam violation(s)",
              file=sys.stderr)
        return 1
    return 0
