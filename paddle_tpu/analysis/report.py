"""Findings, reports, and the allowlist for the jaxpr-level TPU lint.

A :class:`Finding` is one typed diagnostic (rule, severity, message, eqn
provenance).  A :class:`Report` is the result of one ``analyze()`` run:
findings partitioned into active vs allowlisted, renderable for the CLI and
queryable from tests/CI (``tools/lint_gate.py`` exits nonzero on any active
finding at or above ``warning``).

The allowlist (``analysis/allowlist.toml``) records *accepted* findings with a
one-line justification — the linter's equivalent of a lint-ignore pragma, but
centralized so every suppression is visible and reviewed in one file.  Python
3.10 has no ``tomllib``, so a minimal TOML-subset reader lives here (array of
``[[allow]]`` tables with string values — exactly what the allowlist uses).
"""

from __future__ import annotations

import dataclasses
import os
import re

__all__ = ["Severity", "Finding", "Report", "AllowRule", "load_allowlist",
           "DEFAULT_ALLOWLIST"]

# severity order for gating: info findings are advisory and never fail the
# lint gate; warning/error do unless allowlisted
_SEV_ORDER = {"info": 0, "warning": 1, "error": 2}

DEFAULT_ALLOWLIST = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 "allowlist.toml")


class Severity:
    INFO = "info"
    WARNING = "warning"
    ERROR = "error"


@dataclasses.dataclass
class Finding:
    """One typed lint finding.

    ``rule``: dtype_upcast | donation | recompile | host_sync | resharding |
    engine_audit | program_card | budget | kernel_bounds | kernel_race |
    kernel_lost_write | kernel_alias | kernel_registry | kernel_name (the
    last six: kernel_contracts.py).  ``where`` is eqn provenance
    (``file.py:line (fn)``) when the jaxpr carries source info, else a
    structural path (``params/layers/wq``).
    """

    rule: str
    severity: str
    message: str
    where: str = ""
    target: str = ""

    def key(self) -> str:
        return f"{self.rule}:{self.target}:{self.where}:{self.message}"

    def render(self) -> str:
        loc = f" [{self.where}]" if self.where else ""
        return f"{self.severity.upper():7s} {self.rule}: {self.message}{loc}"


@dataclasses.dataclass
class AllowRule:
    """One ``[[allow]]`` entry: rule + optional target + substring match."""

    rule: str = "*"
    target: str = "*"
    match: str = ""
    reason: str = ""

    def covers(self, f: Finding) -> bool:
        if self.rule not in ("*", f.rule):
            return False
        if self.target not in ("*", "", f.target):
            return False
        return (not self.match or self.match in f.where
                or self.match in f.message)


def _parse_mini_toml(text: str, header: str = "allow") -> list[dict]:
    """Parse the allowlist/budgets TOML subset: ``[[<header>]]``
    array-of-tables with ``key = "string"`` or ``key = <int>`` pairs and
    ``#`` comments.  Anything else is a loud error — a silently ignored
    allowlist line would un-suppress findings (and a silently ignored
    budget line would un-gate a ceiling)."""
    entries: list[dict] = []
    current: dict | None = None
    for ln, raw in enumerate(text.splitlines(), 1):
        line = raw.strip()
        if not line or line.startswith("#"):
            continue
        if line == f"[[{header}]]":
            current = {}
            entries.append(current)
            continue
        m = re.match(r'^([A-Za-z_][A-Za-z0-9_]*)\s*=\s*'
                     r'(?:"((?:[^"\\]|\\.)*)"|(-?\d+))'
                     r'\s*(?:#.*)?$', line)
        if m is None or current is None:
            raise ValueError(
                f"{header} table parse error at line {ln}: {raw!r} "
                f'(expected [[{header}]], key = "value", or key = <int>)')
        current[m.group(1)] = (int(m.group(3)) if m.group(3) is not None
                               else re.sub(r'\\(["\\])', r"\1", m.group(2)))
    return entries


def load_allowlist(path: str | None = None) -> list[AllowRule]:
    """Load allow rules; a missing default file is an empty allowlist, a
    missing *explicit* path is an error (a typoed --allowlist must not
    silently allow nothing)."""
    explicit = path is not None
    path = path or DEFAULT_ALLOWLIST
    if not os.path.exists(path):
        if explicit:
            raise FileNotFoundError(f"allowlist file not found: {path}")
        return []
    with open(path) as f:
        entries = _parse_mini_toml(f.read())
    rules = []
    for i, e in enumerate(entries):
        unknown = set(e) - {"rule", "target", "match", "reason"}
        if unknown:
            raise ValueError(f"allowlist entry {i}: unknown keys {unknown}")
        bad = {k for k, v in e.items() if not isinstance(v, str)}
        if bad:
            raise ValueError(f"allowlist entry {i}: non-string value(s) for "
                             f"{sorted(bad)} (budgets live in budgets.toml)")
        if not e.get("reason"):
            raise ValueError(
                f"allowlist entry {i} ({e}): every suppression needs a "
                f"one-line reason")
        rules.append(AllowRule(**e))
    return rules


class Report:
    """Result of one ``analyze()`` run over one target."""

    def __init__(self, target: str, findings: list[Finding],
                 allowlist: list[AllowRule] | None = None,
                 n_traces: int | None = None):
        self.target = target
        self.n_traces = n_traces  # distinct trace signatures seen (churn rule)
        self.card = None          # ProgramCard when analyze(card=True)
        #: wall seconds of the analyze() pass; the number of rule/card
        #: consumers that REUSED its one baseline trace; and the number
        #: of jaxpr traces ACTUALLY performed (a live counter on the
        #: trace closure — expected 2: the baseline plus the recompile
        #: rule's deliberate determinism re-trace; any growth means a
        #: rule started re-tracing).  Surfaced by
        #: ``python -m paddle_tpu.analysis --json`` so CI logs show the
        #: gate stayed single-trace/single-compile per target.
        self.seconds: float | None = None
        self.trace_reuse: int | None = None
        self.traces_performed: int | None = None
        self.findings: list[Finding] = []       # active (not allowlisted)
        self.allowlisted: list[tuple[Finding, AllowRule]] = []
        for f in findings:
            rule = next((a for a in (allowlist or []) if a.covers(f)), None)
            if rule is None:
                self.findings.append(f)
            else:
                self.allowlisted.append((f, rule))

    @property
    def ok(self) -> bool:
        """True when no active finding gates (info is advisory)."""
        return not self.gating()

    def gating(self) -> list[Finding]:
        return [f for f in self.findings
                if _SEV_ORDER[f.severity] >= _SEV_ORDER["warning"]]

    def by_rule(self, rule: str) -> list[Finding]:
        return [f for f in self.findings if f.rule == rule]

    def render(self, verbose: bool = False) -> str:
        lines = [f"== {self.target}: {len(self.findings)} finding(s), "
                 f"{len(self.allowlisted)} allowlisted =="]
        for f in self.findings:
            lines.append("  " + f.render())
        if verbose:
            for f, a in self.allowlisted:
                lines.append(f"  ALLOWED {f.render().strip()}  "
                             f"(reason: {a.reason})")
        return "\n".join(lines)
