"""Unified finding/report model for the trace doctor.

Every pass — jaxpr lint, HLO lint, recompile guard — reports through
one :class:`Finding` shape (rule id, severity, entry-point label, op
path, byte estimate, message), collected per linted program into a
:class:`TraceReport`. The CI gate (``scripts/lint_traces.py``) and the
in-suite tests fail on any ``error``-severity finding that is not
waived by an allowlist entry.

Rule catalogue (see README "Static analysis / trace doctor"):

========  ========  =====================================================
rule      pass      what it catches
========  ========  =====================================================
TD001     jaxpr     dense closure constant above the size threshold (the
                    fused-step ~300 MB embedded-dataset incident class)
TD002     jaxpr     host callback primitives staged into a hot path
                    (``debug_callback`` / ``pure_callback`` / ...)
TD003     jaxpr     dtype widening to f64 inside traced code
TD004     jaxpr/hlo buffer donation compiled on the CPU backend, where
                    zero-copy ``np.asarray`` views alias the donated
                    buffers (the PR-3 corrupted-metrics incident class)
TD005     jaxpr     class-unrolled build: more ``build``-phase grow
                    loops staged per program than the caller's budget
                    (a multiclass iteration tracing K sequential tree
                    builds instead of one class-batched build)
TD008     jaxpr     per-row table read in the ``build`` stage: a
                    ``gather`` of >= R index tuples from fewer than R
                    table entries, or a ``scatter-add`` of >= R updates
                    into fewer than R segments (9.7 ns an element on a
                    v5e; the round's W records are selected by compares)
TD101     hlo       oversized dense ``constant`` op in the compiled
                    program
TD102     hlo       host transfer (infeed/outfeed/send/recv, callback
                    custom-calls) in the compiled program
TD103     hlo       sizeable collective whose op name carries none of
                    the program's allowed profiler phases
TD201     guard     XLA compilation count exceeding the documented bound
                    (steady-state training, serving bucket ladder)
========  ========  =====================================================

Waivers: an allowlist entry is ``(rule, pattern)`` — ``fnmatch``
patterns matched against ``"label:op_path"``. A waived finding is kept
(severity ``info``, ``waived=True``) so reports stay auditable, but it
no longer fails the gate.
"""

from __future__ import annotations

import dataclasses
from fnmatch import fnmatch
from typing import Iterable, List, Sequence, Tuple

__all__ = ["Finding", "TraceReport", "SEVERITIES", "merge_errors"]

SEVERITIES = ("error", "warn", "info")


@dataclasses.dataclass
class Finding:
    """One rule violation in one linted program."""
    rule: str                 # TDnnn
    severity: str             # error | warn | info
    label: str                # entry-point label (e.g. fused_step/plain)
    op_path: str              # op name / jaxpr var / const index
    message: str
    nbytes: int = 0           # byte estimate where meaningful
    waived: bool = False

    def __post_init__(self):
        if self.severity not in SEVERITIES:
            raise ValueError(f"unknown severity {self.severity!r}")

    def key(self) -> str:
        return f"{self.label}:{self.op_path}"

    def render(self) -> str:
        size = f" [{self.nbytes / 2**20:.1f} MiB]" if self.nbytes else ""
        waived = " (waived)" if self.waived else ""
        return (f"{self.rule} {self.severity:<5} {self.label}: "
                f"{self.message}{size} @ {self.op_path}{waived}")


@dataclasses.dataclass
class TraceReport:
    """Findings of one linted program (or one guard scope)."""
    label: str
    findings: List[Finding] = dataclasses.field(default_factory=list)

    def add(self, rule: str, severity: str, op_path: str, message: str,
            nbytes: int = 0) -> Finding:
        f = Finding(rule=rule, severity=severity, label=self.label,
                    op_path=op_path, message=message, nbytes=nbytes)
        self.findings.append(f)
        return f

    def apply_allowlist(
            self, allow: Sequence[Tuple[str, str]]) -> "TraceReport":
        """Downgrade findings matching ``(rule, pattern)`` entries to
        waived info-severity. Patterns fnmatch against
        ``"label:op_path"`` (so ``("TD101", "fused_step/*")`` waives a
        whole entry point and ``("TD103", "*iota*")`` one op)."""
        for f in self.findings:
            for rule, pat in allow:
                if f.rule == rule and (fnmatch(f.key(), pat)
                                       or fnmatch(f.op_path, pat)
                                       or fnmatch(f.label, pat)):
                    f.waived = True
                    f.severity = "info"
                    break
        return self

    @property
    def errors(self) -> List[Finding]:
        return [f for f in self.findings
                if f.severity == "error" and not f.waived]

    @property
    def ok(self) -> bool:
        return not self.errors

    def render(self, verbose: bool = False) -> str:
        shown = self.findings if verbose else [
            f for f in self.findings if f.severity != "info" or f.waived]
        lines = [f"{self.label}: "
                 f"{len(self.errors)} error(s), "
                 f"{len(self.findings)} finding(s)"]
        lines += ["  " + f.render() for f in shown]
        return "\n".join(lines)


def merge_errors(reports: Iterable[TraceReport]) -> List[Finding]:
    """Every unwaived error across a report batch (gate helper)."""
    out: List[Finding] = []
    for r in reports:
        out.extend(r.errors)
    return out
