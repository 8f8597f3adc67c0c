"""Rule ``dtype-discipline``: no 64-bit values anywhere in a kernel
jaxpr.

The state planes are f32/int32/uint32 by design — the TPU has no f64
ALU (it emulates at >10x cost) and every widened plane doubles HBM
traffic on the bandwidth-bound sweep. The classic leak: a Python float
literal or an ``np.float64`` scalar folding into a traced op under
``jax.enable_x64``, silently promoting a whole accumulator
plane. With x64 DISABLED the leak self-heals (JAX demotes), so unit
tests never see it; this rule traces the canonical families and flags
any equation whose output materializes float64/int64/uint64/complex128
— the evidence tier where the leak is visible regardless of the test
environment's x64 setting.
"""

from __future__ import annotations

from typing import List

from tools.lint.core import Finding, RepoTree, Rule
from tools.lint.kernel_audit import get_audit


class DtypeDisciplineRule(Rule):
    name = "dtype-discipline"
    title = ("no f64/i64 widening in any traced kernel family (the TPU "
             "emulates 64-bit at >10x cost)")
    established = "PR 10"
    tier = "trace"

    def check(self, tree: RepoTree) -> List[Finding]:
        audit = get_audit(tree)
        if audit is None:
            return []
        out: List[Finding] = []
        for name in sorted(audit.traces):
            tr = audit.traces[name]
            for prim, aval in tr.wide_dtypes:
                out.append(Finding(
                    self.name, tr.path, tr.line,
                    f"kernel family {name!r}: primitive {prim!r} "
                    f"materializes a 64-bit value ({aval}) — a Python "
                    f"scalar or np.float64 leaked into the trace; cast "
                    f"at the boundary (jnp.float32/int32) so the plane "
                    f"never widens",
                    tr.builder or "<family>",
                ))
        return out
