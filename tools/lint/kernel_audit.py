"""Trace-time evidence cache for the compiled-graph auditor (ISSUE 11).

The AST tier reads source; every property it checks is a property of
what the author WROTE. But the contracts the ROADMAP's perf story
actually rests on — donated state buffers really aliasing, PR 7's "one
sort feeds four" staying one sort, no host callbacks inside the
megastep scan, no silent f64 widening — are properties of the COMPILED
program, visible only after tracing. This module builds that evidence
exactly once per process and shares it across every trace-tier rule,
the same parse-once economics RepoTree gives the AST tier:

  * The CANONICAL grid: runtime/step.py ``kernel_family_grid()`` (the
    real step builders over routes x layouts x planes x fused depths)
    plus ops/window_kernels.py ``kernel_family_grid()`` (the raw kernel
    bodies). Each family is traced (``jax.make_jaxpr``) for primitive
    evidence; donated step families are additionally LOWERED for the
    StableHLO input/output alias table; the ``deep`` representatives are
    fully COMPILED for the executable's alias table + memory stats.
    Everything runs on the CPU backend under abstract-or-tiny inputs —
    no accelerator needed, tier-1 friendly.
  * FIXTURE kernels: a virtual tree (the red-team fixture path) yields
    families only from files carrying the ``# lint-kernel-fixture``
    marker, each defining ``lint_kernel_families()``. The canonical grid
    is NEVER built for virtual trees, so AST fixtures impersonating
    runtime/step.py stay cheap and trace fixtures are explicit.

Evidence per family (:class:`FamilyTrace`): grouped primitive counts
(sort/scatter/gather/while_scan/cond — the op-budget ledger currency),
host-crossing primitives with their scan/cond nesting path, wide-dtype
(64-bit) values, and the abstract input signature (the compile-signature
ledger currency: two call sites disagreeing on this string means two
compiles of the "same" step — a recompile storm). Donation evidence
(:meth:`KernelAudit.donation_report`) is computed lazily because only
the donation-effective rule pays for lowering/compiling.
"""

from __future__ import annotations

import contextlib
import hashlib
import os
import re
import time
import warnings
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from tools.lint.core import LintInternalError, RepoTree

# the module that owns the step-builder grid; a disk tree without it is
# not this repo (e.g. a CLI test tmp dir) and gets an empty audit
STEP_HOME = "flink_tpu/runtime/step.py"
WK_HOME = "flink_tpu/ops/window_kernels.py"

# virtual-tree files carrying this marker are exec'd for fixture
# families; everything else in a virtual tree is AST-tier material
FIXTURE_MARKER = "# lint-kernel-fixture"

# ledger currency: jaxpr primitive name -> budget group
OP_GROUPS = ("sort", "scatter", "gather", "while_scan", "cond")

# primitives that cross the device/host boundary from inside a traced
# program — any of these inside a kernel serializes the step pipeline
HOST_CROSSING_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "callback",
    "infeed", "outfeed", "host_local_array_to_global_array",
    "device_put",
})

# 64-bit dtypes a kernel jaxpr must never materialize (dtype-discipline)
WIDE_DTYPES = ("float64", "int64", "uint64", "complex128")

_ALIAS_ARG_SPLIT = re.compile(r"%arg\d+\s*:")


def _op_group(prim_name: str) -> Optional[str]:
    if prim_name == "sort":
        return "sort"
    if prim_name.startswith("scatter"):
        return "scatter"
    if prim_name.startswith("gather"):
        return "gather"
    if prim_name in ("scan", "while"):
        return "while_scan"
    if prim_name == "cond":
        return "cond"
    return None


def _subjaxprs(value):
    """Every ClosedJaxpr/Jaxpr reachable from one eqn.params value."""
    from jax.extend import core as jex_core

    vals = value if isinstance(value, (list, tuple)) else (value,)
    for v in vals:
        if isinstance(v, jex_core.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, jex_core.Jaxpr):
            yield v


def iter_eqns(jaxpr, path: Tuple[str, ...] = ()):
    """Depth-first (path, eqn) over a jaxpr and everything it closes
    over — scan/cond/while/jit bodies included. ``path`` is the chain
    of enclosing control primitives, so a rule can say "debug_callback
    inside scan" instead of just "somewhere"."""
    for eqn in jaxpr.eqns:
        yield path, eqn
        name = eqn.primitive.name
        sub_path = path if name == "jit" else path + (name,)
        for v in eqn.params.values():
            for sub in _subjaxprs(v):
                yield from iter_eqns(sub, sub_path)


def _aval_str(x) -> str:
    import jax

    aval = getattr(x, "aval", None)
    if aval is None:
        aval = jax.typeof(x)
    return aval.str_short()


def abstract_signature(args) -> str:
    """The family's abstract input signature: one comma-joined
    ``aval.str_short()`` per flattened leaf, in tree order. Two calls
    that disagree on this string compile separately — the signature
    ledger pins it so an accidental split (a recompile storm) fails lint
    before it fails in production."""
    import jax

    leaves = jax.tree_util.tree_leaves(args)
    return ",".join(_aval_str(x) for x in leaves)


def signature_digest(signature: str) -> str:
    return hashlib.sha256(signature.encode()).hexdigest()[:12]


@dataclass
class FamilyTrace:
    """Jaxpr-level evidence for one kernel family (compile evidence is
    lazy; see KernelAudit.donation_report)."""

    name: str
    path: str                  # repo-relative anchor for findings
    line: int
    donated: bool
    deep: bool
    builder: str               # source builder/function name ("" = n/a)
    op_counts: Dict[str, int]  # group -> count (OP_GROUPS keys, always)
    signature: str
    digest: str
    host_crossings: List[Tuple[str, str]]   # (primitive, nesting path)
    wide_dtypes: List[Tuple[str, str]]      # (primitive, aval string)
    n_eqns: int = 0


@dataclass
class _Entry:
    name: str
    fn: Any
    args: Tuple
    donate: Tuple[int, ...]
    path: str
    line: int
    builder: str = ""
    deep: bool = False
    x64: bool = False


def _trace_entry(e: _Entry) -> FamilyTrace:
    import jax

    ctx = (jax.enable_x64(True) if e.x64
           else contextlib.nullcontext())
    with ctx, warnings.catch_warnings():
        warnings.simplefilter("ignore")
        closed = jax.make_jaxpr(getattr(e.fn, "jit", e.fn))(*e.args)
    counts = {g: 0 for g in OP_GROUPS}
    crossings: List[Tuple[str, str]] = []
    wide: Dict[Tuple[str, str], None] = {}
    n_eqns = 0
    for path, eqn in iter_eqns(closed.jaxpr):
        n_eqns += 1
        prim = eqn.primitive.name
        g = _op_group(prim)
        if g is not None:
            counts[g] += 1
        if prim in HOST_CROSSING_PRIMS:
            crossings.append((prim, "/".join(path) or "<top>"))
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            dt = str(getattr(aval, "dtype", ""))
            if dt in WIDE_DTYPES:
                wide[(prim, aval.str_short())] = None
    sig = abstract_signature(e.args)
    return FamilyTrace(
        name=e.name, path=e.path, line=e.line, donated=bool(e.donate),
        deep=e.deep, builder=e.builder, op_counts=counts,
        signature=sig, digest=signature_digest(sig),
        host_crossings=crossings, wide_dtypes=sorted(wide),
        n_eqns=n_eqns,
    )


def _lowered_alias_params(mlir_text: str) -> Tuple[set, int]:
    """Parameter indices of ``@main`` carrying ``tf.aliasing_output``
    in the lowered StableHLO, plus the total parameter count. A donated
    leaf the lowering could not alias (shape/dtype mismatch, runtime
    refusal) simply drops out of this table."""
    m = re.search(r"func\.func public @main\((.*?)\)(?:\s*->|\s*\{)",
                  mlir_text, re.S)
    if m is None:
        return set(), 0
    chunks = _ALIAS_ARG_SPLIT.split(m.group(1))[1:]
    return (
        {i for i, c in enumerate(chunks) if "tf.aliasing_output" in c},
        len(chunks),
    )


def _executable_alias_params(hlo_text: str) -> set:
    """Parameter indices in the compiled executable's
    ``input_output_alias={...}`` table (what XLA actually kept)."""
    m = re.search(r"input_output_alias=\{(.*?)\}\s*\n", hlo_text)
    if m is None:
        m = re.search(r"input_output_alias=\{(.*)", hlo_text)
    if m is None:
        return set()
    return {int(x) for x in re.findall(r"\(\s*(\d+)\s*,", m.group(1))}


def _donated_leaves(args, donate: Tuple[int, ...]):
    """[(flat_index, leaf_path_str, leaf_size)] for every leaf of every
    donated argument, in flattened-argument order (closure consts lower
    to module constants, not params, so flat indices are the module's
    pre-DCE parameter space)."""
    import jax
    import numpy as np

    out = []
    offset = 0
    for i, a in enumerate(args):
        flat, _ = jax.tree_util.tree_flatten_with_path(a)
        if i in donate:
            for j, (kp, leaf) in enumerate(flat):
                out.append((
                    offset + j,
                    f"arg{i}{jax.tree_util.keystr(kp)}",
                    int(np.prod(getattr(leaf, "shape", ()) or (1,))),
                ))
        offset += len(flat)
    return out


def _kept_param_map(lowered, n_flat: int) -> Dict[int, int]:
    """{flat invar index: lowered param position}. jit lowers with
    keep_unused=False, so unused invars are DROPPED from the module's
    parameter list (``kept_var_idx``) and every later param shifts —
    the packed families' zero-size touched plane taught us this the
    hard way. Falls back to the identity map when the private lowering
    attribute moves."""
    try:
        kept = sorted(lowered._lowering.compile_args["kept_var_idx"])
        return {flat: pos for pos, flat in enumerate(kept)}
    except (AttributeError, KeyError, TypeError):
        return {i: i for i in range(n_flat)}


class KernelAudit:
    """Shared trace-time evidence for one set of kernel families.

    ``traces`` (eager, built at construction) carries the jaxpr
    evidence every rule reads; :meth:`donation_report` lowers — and for
    ``deep`` families compiles — on first use and caches, so a CLI run
    filtered to a jaxpr-only rule never pays for XLA."""

    def __init__(self, entries: List[_Entry]):
        t0 = time.monotonic()
        self._entries = {e.name: e for e in entries}
        self.traces: Dict[str, FamilyTrace] = {}
        for e in entries:
            try:
                self.traces[e.name] = _trace_entry(e)
            except Exception as ex:   # an untraceable family is a broken
                raise LintInternalError(      # build, not a finding
                    f"kernel family {e.name!r} failed to trace: "
                    f"{type(ex).__name__}: {ex}"
                ) from ex
        self.build_seconds = time.monotonic() - t0
        self.donation_seconds = 0.0
        self._donation: Dict[str, dict] = {}

    def donation_report(self, name: str) -> dict:
        """Alias evidence for one donated family:

        ``leaves``: donated (param, leaf-path) pairs;
        ``missing_lowered``: leaf paths absent from the lowered alias
        table (the donation is ineffective — XLA will copy);
        ``dropped_by_executable``: lowered-aliased leaves the compiled
        executable's table dropped (deep families only);
        ``executable_checked``: whether the compile-level check ran.
        """
        if name in self._donation:
            return self._donation[name]
        e = self._entries[name]
        if not e.donate:
            rep = {"leaves": [], "missing_lowered": [],
                   "dropped_by_executable": [], "executable_checked": False}
            self._donation[name] = rep
            return rep
        t0 = time.monotonic()
        jitfn = getattr(e.fn, "jit", e.fn)
        try:
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                lowered = jitfn.lower(*e.args)
                aliased, _nparams = _lowered_alias_params(
                    lowered.as_text())
                exec_aliased = None
                if e.deep:
                    compiled = lowered.compile()
                    exec_aliased = _executable_alias_params(
                        compiled.as_text())
        except Exception as ex:
            raise LintInternalError(
                f"kernel family {name!r} failed to lower/compile: "
                f"{type(ex).__name__}: {ex}"
            ) from ex
        import jax

        leaves = _donated_leaves(e.args, e.donate)
        param_of = _kept_param_map(
            lowered, len(jax.tree_util.tree_leaves(e.args)))
        missing = []
        for flat, lp, size in leaves:
            p = param_of.get(flat)
            if p is None:
                # dropped as unused: a zero-size leaf costs nothing; a
                # real leaf the kernel never reads means its output is
                # written fresh — the donation buys nothing
                if size > 0:
                    missing.append(f"{lp} (unused by the kernel body)")
            elif p not in aliased:
                missing.append(lp)
        dropped = []
        if exec_aliased is not None:
            dropped = [lp for flat, lp, _size in leaves
                       if param_of.get(flat) is not None
                       and param_of[flat] in aliased
                       and param_of[flat] not in exec_aliased]
        rep = {
            "leaves": leaves,
            "missing_lowered": missing,
            "dropped_by_executable": dropped,
            "executable_checked": exec_aliased is not None,
        }
        self.donation_seconds += time.monotonic() - t0
        self._donation[name] = rep
        return rep


# ---------------------------------------------------------- entry points

_canonical_audit: Optional[KernelAudit] = None
_fixture_audits: Dict[tuple, KernelAudit] = {}


def _canonical_entries() -> List[_Entry]:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from flink_tpu.ops import window_kernels as wk
    from flink_tpu.parallel.mesh import MeshContext
    from flink_tpu.runtime import step as rstep

    ctx = MeshContext.create(n_shards=1, max_parallelism=8)
    entries: List[_Entry] = []
    for fam in rstep.kernel_family_grid():
        fn, args, donate = rstep.build_family(fam, ctx)
        entries.append(_Entry(
            name=fam.name, fn=fn, args=args, donate=donate,
            path=STEP_HOME,
            line=fam.builder.__code__.co_firstlineno,
            builder=fam.builder.__name__, deep=fam.deep,
        ))
    for name, fn, args in wk.kernel_family_grid():
        entries.append(_Entry(
            name=name, fn=fn, args=tuple(args), donate=(),
            path=WK_HOME, line=fn.__code__.co_firstlineno,
            builder=fn.__name__,
        ))
    return entries


def _fixture_entries(tree: RepoTree) -> List[_Entry]:
    import jax

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    entries: List[_Entry] = []
    for relpath in sorted(tree._virtual):
        src = tree._virtual[relpath]
        if not relpath.endswith(".py") or FIXTURE_MARKER not in src:
            continue
        ns: dict = {}
        try:
            exec(compile(src, relpath, "exec"), ns)
            fams = ns["lint_kernel_families"]()
        except Exception as ex:
            raise LintInternalError(
                f"kernel fixture {relpath} failed to load: "
                f"{type(ex).__name__}: {ex}"
            ) from ex
        for d in fams:
            fn = d["fn"]
            donate = tuple(d.get("donate", ()))
            if donate:
                fn = jax.jit(fn, donate_argnums=donate)
            entries.append(_Entry(
                name=d["name"], fn=fn, args=tuple(d["args"]),
                donate=donate, path=relpath, line=int(d.get("line", 1)),
                builder=d.get("builder", ""), deep=True,
                x64=bool(d.get("x64", False)),
            ))
    return entries


def get_audit(tree: RepoTree) -> Optional[KernelAudit]:
    """The KernelAudit for ``tree``, or None when the tree has no kernel
    families to audit (a disk tree that isn't this repo, or a virtual
    tree without fixture-marked files).

    Disk trees share ONE process-wide audit: the canonical grid is built
    from the installed flink_tpu modules, independent of the tree root,
    so every rule — and every parametrized test — pays the trace cost
    once. Virtual (fixture) audits are cached by file content."""
    global _canonical_audit
    if tree._virtual is not None:
        key = tuple(sorted(
            (rp, hashlib.sha256(src.encode()).hexdigest())
            for rp, src in tree._virtual.items()
            if rp.endswith(".py") and FIXTURE_MARKER in src
        ))
        if not key:
            return None
        if key not in _fixture_audits:
            _fixture_audits[key] = KernelAudit(_fixture_entries(tree))
        return _fixture_audits[key]
    if not tree.exists(STEP_HOME):
        return None
    if _canonical_audit is None:
        _canonical_audit = KernelAudit(_canonical_entries())
    return _canonical_audit


# ---------------------------------------------------------------- ledgers

def load_ledger(tree: RepoTree, relpath: str) -> Optional[dict]:
    """Parse one checked-in ledger; None when absent, LintInternalError
    when present but not valid JSON (a corrupt ledger is a broken
    build, not a finding)."""
    import json

    text = tree.read_text(relpath)
    if text is None:
        return None
    try:
        return json.loads(text)
    except ValueError as ex:
        raise LintInternalError(
            f"ledger {relpath} is not valid JSON: {ex}"
        ) from ex


def write_ledger(root: str, relpath: str, data: dict) -> None:
    """Rewrite one ledger deterministically (sorted keys, 2-space
    indent, trailing newline) so --update-ledger diffs are minimal."""
    import json

    path = os.path.join(root, relpath)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(data, f, indent=2, sort_keys=True)
        f.write("\n")


# ------------------------------------------------------------- bench hook

def kernel_structural_stamp(fn, args) -> dict:
    """Structural evidence for ONE kernel, for the bench detail JSON
    (ISSUE 11 satellite): grouped op counts, abstract-signature digest,
    and the compiled executable's memory_analysis byte totals — so
    BENCH_*.json carries a structural trajectory (did the sort count or
    the temp footprint move?) next to events/s."""
    import jax

    jitfn = getattr(fn, "jit", fn)
    closed = jax.make_jaxpr(jitfn)(*args)
    counts = {g: 0 for g in OP_GROUPS}
    for _path, eqn in iter_eqns(closed.jaxpr):
        g = _op_group(eqn.primitive.name)
        if g is not None:
            counts[g] += 1
    sig = abstract_signature(args)
    out = {"ops": counts, "signature_digest": signature_digest(sig)}
    try:
        mem = jitfn.lower(*args).compile().memory_analysis()
        if mem is not None:
            out["memory_bytes"] = {
                "argument": int(getattr(mem, "argument_size_in_bytes", 0)),
                "output": int(getattr(mem, "output_size_in_bytes", 0)),
                "temp": int(getattr(mem, "temp_size_in_bytes", 0)),
                "generated_code": int(
                    getattr(mem, "generated_code_size_in_bytes", 0)),
                "alias": int(getattr(mem, "alias_size_in_bytes", 0)),
            }
    except Exception as ex:    # memory stats are best-effort telemetry
        out["memory_bytes"] = {"error": f"{type(ex).__name__}: {ex}"}
    return out
