"""pallas-guard: every Pallas kernel entry point must degrade to CPU.

The repo's contract (docs/FUSED_COLLECTIVES.md) is that tier-1 runs
EVERY code path on CPU: TPU kernels execute in Pallas interpret mode
instead of being skipped.  That only holds if each ``pl.pallas_call``
site threads a runtime interpret decision (``interpret=_interpret()``).

Rules:

``missing-interpret``
    a ``pallas_call`` invocation without an ``interpret=`` keyword —
    the kernel would try to lower for a TPU backend on CPU CI.
``static-interpret``
    ``interpret=`` passed as a literal constant — a compile-time pin
    that either never interprets (broken on CPU) or always interprets
    (broken on TPU); the decision must be a runtime call like
    ``pallas_kernels._interpret()``.
"""

from __future__ import annotations

import ast
from typing import List

from .core import Analyzer, Finding, Project


class PallasGuard(Analyzer):
    name = "pallas-guard"
    description = "pallas_call sites carry a runtime interpret= fallback"

    def run(self, project: Project) -> List[Finding]:
        out: List[Finding] = []
        for sf in project.package_files():
            tree = sf.tree
            if tree is None:
                continue
            for node in ast.walk(tree):
                if isinstance(node, ast.Call):
                    self._check_call(sf, node, out)
        return out

    def _check_call(self, sf, node: ast.Call, out: List[Finding]) -> None:
        name = self.dotted(node.func)
        if name is None or not name.endswith("pallas_call"):
            return
        interp = next((kw for kw in node.keywords
                       if kw.arg == "interpret"), None)
        if interp is None:
            if not sf.allowed("missing-interpret", node.lineno):
                out.append(Finding(
                    self.name, "missing-interpret", sf.rel, node.lineno,
                    f"{name}(...) has no interpret= keyword; pass a "
                    f"runtime guard (e.g. interpret=_interpret()) so "
                    f"the kernel runs on CPU tier-1"))
            return
        if isinstance(interp.value, ast.Constant):
            if not sf.allowed("static-interpret", node.lineno):
                out.append(Finding(
                    self.name, "static-interpret", sf.rel, node.lineno,
                    f"{name}(...) pins interpret={interp.value.value!r} "
                    f"at compile time; the fallback must be a runtime "
                    f"decision (interpret=_interpret())"))
