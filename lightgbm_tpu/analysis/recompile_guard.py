"""Compile-cache discipline: count XLA compilations, enforce bounds.

Steady-state training must not recompile: the fused step compiles ONE
program per booster config (``gbdt._fused_dispatch``), and the serving
batcher pads every burst onto its power-of-two bucket ladder so at most
``log2(max_batch_rows) + 1`` signatures ever exist
(``serving/batcher.bucket_rows``). A shape leak — a Python int that
becomes a weak type, a batch that misses the ladder, a donated buffer
changing avals — silently turns the 1-compile contract into
compile-per-call, and on real TPUs each compile is seconds, not
microseconds. This guard makes the contract testable:

    with RecompileGuard(max_compiles=1, label="fused_step") as g:
        train(...)
    # raises RecompileError (TD201) when XLA compiled > 1 program

Counting uses ``jax.monitoring``'s event-duration stream: XLA fires
``/jax/core/compile/backend_compile_duration`` once per actual backend
compile (cache hits don't fire), so the count is exact and includes
compiles triggered anywhere in the scope, not just through one handle.
``cache_size(jitted)`` complements it with the per-function signature
count for ladder-bound assertions.
"""

from __future__ import annotations

from typing import Optional

from .report import TraceReport

__all__ = ["RecompileGuard", "RecompileError", "cache_size",
           "COMPILE_EVENT"]

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class RecompileError(AssertionError):
    """Raised when a guarded scope exceeds its compile bound; carries
    the TD201 :class:`~.report.TraceReport` as ``.report``."""

    def __init__(self, report: TraceReport):
        self.report = report
        super().__init__(report.render())


def cache_size(jitted) -> int:
    """Number of compiled signatures held by one jitted function (the
    per-function view; the guard counts globally)."""
    try:
        return int(jitted._cache_size())
    except AttributeError:
        raise TypeError(
            f"{jitted!r} is not a jitted function (no _cache_size)")


class RecompileGuard:
    """Context manager counting XLA backend compiles in its scope.

    ``max_compiles`` is the documented bound for the scope (1 per
    booster for the fused step; ``log2(max_batch_rows) + 1`` for the
    serving ladder; 0 for a warmed steady state). On exit the guard
    raises :class:`RecompileError` when the count exceeds the bound —
    unless ``strict=False``, in which case the report is just kept on
    ``.report`` for the caller to assert on.
    """

    def __init__(self, max_compiles: int, *, label: str = "scope",
                 strict: bool = True):
        self.max_compiles = int(max_compiles)
        self.label = label
        self.strict = strict
        self.compiles = 0
        self.events: list = []          # (event key observed, duration)
        self.report: Optional[TraceReport] = None
        self._cb = None

    def _on_event(self, event, duration, **kw) -> None:
        if event == COMPILE_EVENT:
            self.compiles += 1
            self.events.append((event, float(duration)))

    def __enter__(self) -> "RecompileGuard":
        import jax
        self._cb = self._on_event
        jax.monitoring.register_event_duration_secs_listener(self._cb)
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._cb is not None:
            import jax
            jax.monitoring.unregister_event_duration_listener(self._cb)
            self._cb = None
        rep = TraceReport(label=self.label)
        if self.compiles > self.max_compiles:
            rep.add("TD201", "error", "xla_compile",
                    f"{self.compiles} XLA compilation(s) in a scope "
                    f"bounded to {self.max_compiles}; a shape or dtype "
                    "is leaking new signatures into steady state")
        self.report = rep
        if exc_type is not None:        # don't mask the real failure
            return False
        if self.strict and not rep.ok:
            raise RecompileError(rep)
        return False
