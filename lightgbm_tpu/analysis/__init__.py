"""Trace doctor: static analysis over jaxprs and compiled HLO.

Three passes, one report model:

- :mod:`.jaxpr_lint` — walks ``ClosedJaxpr``s of the hot-path entry
  points (TD001 closure constants, TD002 host callbacks, TD003 f64
  widening, TD004 CPU donation).
- :mod:`.hlo_lint` — walks compiled-HLO text via the shared
  :mod:`.hlo_walk` parser (TD101 oversized constants, TD102 host
  transfers, TD103 out-of-phase collectives, TD004 at the module
  level).
- :mod:`.recompile_guard` — counts XLA compilations per jitted
  function and fails when steady state exceeds the documented bounds
  (TD201).

:mod:`.doctor` wires the passes over the repo's canonical entry points
(fused step, tree builder, predict ensemble, serving batcher);
``scripts/lint_traces.py`` runs it as the CI gate and
``python -m lightgbm_tpu trace-doctor`` exposes it to users.
"""

from .report import Finding, TraceReport, merge_errors  # noqa: F401
from .jaxpr_lint import lint_deferred_guard, lint_jaxpr  # noqa: F401
from .hlo_lint import lint_hlo  # noqa: F401
from .hlo_walk import (HloOp, COLLECTIVE_KINDS, parse_ops,  # noqa: F401
                       parse_collective_ops, input_output_aliases,
                       lower_hlo)
from .recompile_guard import (RecompileGuard,  # noqa: F401
                              RecompileError, cache_size)
from .doctor import (run_doctor, doctor_main,  # noqa: F401
                     CANONICAL_CONFIGS)

__all__ = [
    "Finding", "TraceReport", "merge_errors",
    "lint_jaxpr", "lint_deferred_guard", "lint_hlo",
    "HloOp", "COLLECTIVE_KINDS", "parse_ops", "parse_collective_ops",
    "input_output_aliases", "lower_hlo",
    "RecompileGuard", "RecompileError", "cache_size",
    "run_doctor", "doctor_main", "CANONICAL_CONFIGS",
]
