"""timeline-catalog: timeline event names in code vs docs/TIMELINE.md.

Every instant-event name the runtime can emit (`Timeline.instant(...)`
call sites in `horovod_tpu/`) must appear in the instant-catalog table
of docs/TIMELINE.md — the table the fleet tracer's docs/TRACE.md span
schema is defined against — and every documented name must still be
emitted somewhere.  The same contract holds for COMPLETE spans
(`Timeline.complete(...)` and `timeline.span(...)` call sites, e.g. the
serve lifecycle spans `queue_wait`/`prefill`/`decode`) against the
span-catalog table.
Drift in either direction is a finding.

Name matching: a literal call site (`tl.instant("PROFILER_TRACE_START"`,
or a module-level UPPER_CASE string constant passed by name) must match
a doc row exactly; an f-string site (`tl.instant(f"wire_bucket_{k}"`)
is a runtime-built family and matches any doc row sharing its literal
prefix (`wire_bucket_k`, `CYCLE_n`, ...) — the same dynamic-name stance
the fault-points analyzer takes.
"""

from __future__ import annotations

import re
from typing import Dict, List, Tuple

from .core import Analyzer, Finding, Project

#: Literal and f-string instant call sites.  Group 1: "f" when an
#: f-string; group 2: the (possibly placeholder-bearing) name.
_CALL_RE = re.compile(
    r"""\.instant\(\s*(f?)["']([A-Za-z0-9_{}\[\].]+)["']""")

#: Same, for complete-span call sites: `tl.complete("queue_wait", ...)`
#: and the span primitive, `span("prefill", "serve", ...)`, which writes
#: the same event on leaving (`utils/timeline.span`).
_SPAN_CALL_RE = re.compile(
    r"""(?:\.complete|\bspan)\(\s*(f?)["']([A-Za-z0-9_{}\[\].]+)["']""")

#: Instant passed as a module-level constant: `tl.instant(TRACE_MARKER`.
_CONST_CALL_RE = re.compile(r"\.instant\(\s*([A-Z][A-Z0-9_]*)\s*[,)]")

#: Module-level string constant definitions.
_CONST_DEF_RE = re.compile(
    r"""^([A-Z][A-Z0-9_]*)(?::\s*[A-Za-z\[\]. ]+)?\s*=\s*["']([^"']+)["']""",
    re.MULTILINE)

#: Rows of the instant-catalog table in docs/TIMELINE.md, between the
#: start/end markers.
_DOC_SECTION_RE = re.compile(
    r"<!--\s*instant-catalog:start\s*-->(.*?)<!--\s*instant-catalog:end"
    r"\s*-->", re.DOTALL)
_SPAN_SECTION_RE = re.compile(
    r"<!--\s*span-catalog:start\s*-->(.*?)<!--\s*span-catalog:end"
    r"\s*-->", re.DOTALL)
_DOC_ROW_RE = re.compile(r"^\|\s*`([A-Za-z0-9_]+)`", re.MULTILINE)

_DOC_PATH = "docs/TIMELINE.md"


def _code_calls(project: Project, call_re: re.Pattern,
                const_re: re.Pattern = None
                ) -> Dict[str, Tuple[str, int, bool]]:
    """{name-or-prefix: (rel_path, line, is_prefix)} for every matching
    Timeline call site in the runtime package."""
    out: Dict[str, Tuple[str, int, bool]] = {}
    for sf in project.package_files():
        consts = dict(_CONST_DEF_RE.findall(sf.text))
        for i, ln in enumerate(sf.lines, 1):
            for m in call_re.finditer(ln):
                is_f, name = bool(m.group(1)), m.group(2)
                if is_f and "{" in name:
                    prefix = name.split("{", 1)[0]
                    out.setdefault(prefix, (sf.rel, i, True))
                else:
                    out.setdefault(name, (sf.rel, i, False))
            if const_re is None:
                continue
            for m in const_re.finditer(ln):
                val = consts.get(m.group(1))
                if val is not None:
                    out.setdefault(val, (sf.rel, i, False))
    return out


def _doc_rows(text: str, section_re: re.Pattern = _DOC_SECTION_RE) -> List[str]:
    m = section_re.search(text)
    if m is None:
        return []
    return _DOC_ROW_RE.findall(m.group(1))


class TimelineCatalog(Analyzer):
    name = "timeline-catalog"
    description = ("timeline instant + span names in code vs the "
                   "docs/TIMELINE.md catalog tables (drift in both "
                   "directions)")

    def _check(self, doc_text: str, rows: List[str],
               code: Dict[str, Tuple[str, int, bool]],
               kind: str) -> List[Finding]:
        findings: List[Finding] = []

        def matches(doc_name: str, code_name: str, is_prefix: bool) -> bool:
            return (doc_name.startswith(code_name) if is_prefix
                    else doc_name == code_name)

        for code_name, (rel, line, is_prefix) in sorted(code.items()):
            if not any(matches(d, code_name, is_prefix) for d in rows):
                shown = f"{code_name}{{...}}" if is_prefix else code_name
                findings.append(Finding(
                    self.name, f"undocumented-{kind}", rel, line,
                    f"{kind} `{shown}` is emitted here but has no row "
                    f"in the {_DOC_PATH} {kind}-catalog table"))
        for d in rows:
            if not any(matches(d, c, p)
                       for c, (_, _, p) in code.items()):
                line = 1
                for i, ln in enumerate(doc_text.splitlines(), 1):
                    if f"`{d}`" in ln:
                        line = i
                        break
                findings.append(Finding(
                    self.name, "stale-doc-entry", _DOC_PATH, line,
                    f"documented {kind} `{d}` is emitted nowhere in "
                    "horovod_tpu/"))
        return findings

    def run(self, project: Project) -> List[Finding]:
        doc_path = project.root / _DOC_PATH
        if not doc_path.is_file():
            return [Finding(self.name, "error", _DOC_PATH, 1,
                            f"{_DOC_PATH} not found")]
        doc_text = doc_path.read_text()
        findings: List[Finding] = []
        for section_re, call_re, const_re, kind in (
                (_DOC_SECTION_RE, _CALL_RE, _CONST_CALL_RE, "instant"),
                (_SPAN_SECTION_RE, _SPAN_CALL_RE, None, "span")):
            code = _code_calls(project, call_re, const_re)
            if section_re.search(doc_text) is None:
                # A package that emits no spans needs no span table; a
                # missing INSTANT table is always an error (the runtime
                # always emits instants — and if it truly emitted none,
                # the stale-regex guard below would have to fire first).
                if code or kind == "instant":
                    findings.append(Finding(
                        self.name, "error", _DOC_PATH, 1,
                        f"no <!-- {kind}-catalog:start/end --> section "
                        f"in {_DOC_PATH}"))
                continue
            if not code and kind == "instant":
                findings.append(Finding(
                    self.name, "error", "horovod_tpu", 1,
                    "no Timeline.instant call sites found — the call "
                    "regex is stale"))
                continue
            rows = _doc_rows(doc_text, section_re)
            findings.extend(self._check(doc_text, rows, code, kind))
        return findings
