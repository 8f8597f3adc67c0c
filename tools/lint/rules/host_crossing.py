"""Rule ``no-host-crossing``: no host-callback / transfer primitives
inside a traced kernel.

The AST hot-path-sync rule catches the constructs an author WRITES
(``block_until_ready``, ``.item()``, ``np.asarray``); this is its
compiled-program complement: ``jax.debug.print`` left over from a
debugging session lowers to a ``debug_print`` primitive INSIDE the
megastep scan body, ``pure_callback``/``io_callback`` smuggle arbitrary
host round trips into the graph, and a traced ``device_put`` is an
implicit transfer — all invisible to source-level scanning once they
hide behind a helper, all serializing the dispatch pipeline at every
scan iteration. The finding names the nesting path (e.g. ``scan/cond``)
so "a print inside the K-fused scan body fires K times per dispatch" is
legible from the lint output.
"""

from __future__ import annotations

from typing import List

from tools.lint.core import Finding, RepoTree, Rule
from tools.lint.kernel_audit import get_audit


class HostCrossingRule(Rule):
    name = "no-host-crossing"
    title = ("no callback/transfer primitives in any traced kernel "
             "family (the compiled complement of hot-path-sync)")
    established = "PR 10"
    tier = "trace"

    def check(self, tree: RepoTree) -> List[Finding]:
        audit = get_audit(tree)
        if audit is None:
            return []
        out: List[Finding] = []
        for name in sorted(audit.traces):
            tr = audit.traces[name]
            for prim, path in tr.host_crossings:
                where = ("at the kernel top level" if path == "<top>"
                         else f"inside the {path} body")
                out.append(Finding(
                    self.name, tr.path, tr.line,
                    f"kernel family {name!r}: host-crossing primitive "
                    f"{prim!r} {where} — every execution pays a device->"
                    f"host round trip (a leftover jax.debug.print lowers "
                    f"to debug_print; remove it or move the readback "
                    f"to the lagged monitoring channel)",
                    tr.builder or "<family>",
                ))
        return out
