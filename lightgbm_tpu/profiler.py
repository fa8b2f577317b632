"""Profiling / tracing hooks, and the program's one span record.

Analog of the reference timing instrumentation (``Common::Timer`` /
``FunctionTimer``, common.h:973,1037, compiled under TIMETAG) — on TPU
the native tool is the XLA profiler: ``jax.profiler`` traces viewable in
TensorBoard/Perfetto, with per-iteration step markers emitted by
engine.train (StepTraceAnnotation).

Workflow::

    with lightgbm_tpu.profiler.trace("/tmp/tb"):
        lgb.train(params, ds, 100)
    # then: python -m lightgbm_tpu monitor --perf /tmp/tb

Three kinds of marker, one name set (``phases.py``):

- :func:`stage` — inside traced code (the fused step, the tree builder,
  the histogram wrapper): a ``jax.named_scope`` and nothing else. It
  costs nothing at run time and changes no fusion; it puts ``<name>/``
  on the ``op_name`` path of every instruction staged under it. A device
  event carries no scope on a TPU (its name is the instruction's text),
  so the road from an event to its stage is the instruction map built
  from the compiled module (``telemetry/costmodel.instruction_phase_map``
  and ``telemetry/xprof.py``).
- :func:`phase` — around the eager dispatches of the legacy driver,
  engine eval, ingest and prefetch: the named scope AND a host
  :func:`span` of the same name, so a per-phase dispatch is timed on
  the host and its device ops can be attributed by overlap.
- :func:`span` — a host boundary that happens once a tree or more
  rarely (``gbdt.dispatch``, ``gbdt.sync.wait``, ``engine.eval``, ...;
  the list is in PERF.md section 3). Never called from traced code and
  never per round or per row block.

Every :func:`span` lands in :data:`recorder`, a bounded in-memory ring
of ``(name, start_ns, end_ns, parent, iteration)`` that is always on,
and is also a ``TraceAnnotation`` named ``lgbtpu:<name>``, so it lies
in the profiler's host plane beside the device's events.
``start_ns``/``end_ns`` are ``time.time_ns()``: the clock the xplane's
events are on once ``profile_start_time`` is added to them.

:class:`PhaseTotals` (per-name seconds and counts of a stretch of the
ring) is what ``collect_phase_totals``, the telemetry session's
``iteration`` event and ``train_phase_seconds_total`` read. Span COUNTS
are driver- and knob-dependent — the legacy multiclass loop fires
``build`` K times per iteration where the class-batched build fires it
once — so comparisons use :meth:`PhaseTotals.per_iteration`. Under the
fused driver the ring holds ``gbdt.dispatch`` / ``gbdt.sync.*`` and no
per-phase span: the phases of the one program are device time, read
from a trace.
"""

from __future__ import annotations

import collections
import contextlib
import threading
import time
from typing import Any, Dict, Iterator, List, NamedTuple, Optional, Tuple

from .phases import HOST_SPANS, KNOWN_PHASES

__all__ = ["trace", "step_annotation", "stage",
           "stage_sequence", "phase", "span", "Span",
           "SpanRecorder", "recorder", "PhaseTotals",
           "collect_phase_totals", "ANNOTATION_PREFIX"]

ANNOTATION_PREFIX = "lgbtpu:"
RING_SPANS = 8192


@contextlib.contextmanager
def trace(log_dir: str, create_perfetto_link: bool = False) -> Iterator[None]:
    """Capture an XLA profiler trace of the enclosed block."""
    import jax
    jax.profiler.start_trace(log_dir,
                             create_perfetto_link=create_perfetto_link)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def step_annotation(name: str, step_num: Optional[int] = None):
    """Step marker context (the per-iteration wall-clock log of
    gbdt.cpp:246-249, as trace events)."""
    import jax
    kwargs = {} if step_num is None else {"step_num": step_num}
    return jax.profiler.StepTraceAnnotation(name, **kwargs)


# ----------------------------------------------------------------------
# The span record

class Span(NamedTuple):
    name: str
    start_ns: int        # time.time_ns()
    end_ns: int
    parent: str          # name of the enclosing span on this thread, or ""
    iteration: int       # recorder.iteration when the span closed
    seq: int             # position in the recorder's whole history
    fields: Dict[str, Any]

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


class SpanRecorder:
    """Bounded ring of closed spans. ``seq`` counts every span ever
    recorded, so a reader that remembers the last ``seq`` it saw gets
    exactly the new ones from :meth:`since` (or knows, from a gap, that
    the ring wrapped under it)."""

    def __init__(self, capacity: int = RING_SPANS):
        self._ring: collections.deque = collections.deque(maxlen=capacity)
        self._lock = threading.Lock()
        self._local = threading.local()
        self.seq = 0
        self.iteration = 0       # set by the driver at each dispatch

    def _stack(self) -> List[str]:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def record(self, name: str, start_ns: int, end_ns: int,
               parent: str = "", fields: Optional[dict] = None) -> Span:
        with self._lock:
            sp = Span(name, int(start_ns), int(end_ns), parent,
                      self.iteration, self.seq, fields or {})
            self.seq += 1
            self._ring.append(sp)
        return sp

    def since(self, seq: int = 0) -> List[Span]:
        """Spans with ``span.seq >= seq`` that the ring still holds."""
        with self._lock:
            return [s for s in self._ring if s.seq >= seq]

    def spans(self, name: Optional[str] = None) -> List[Span]:
        with self._lock:
            return [s for s in self._ring if name is None or s.name == name]

    def __len__(self) -> int:
        return len(self._ring)


recorder = SpanRecorder()


@contextlib.contextmanager
def span(name: str, *, parts: int = 1, **fields) -> Iterator[Dict[str, Any]]:
    """Host span: one record in :data:`recorder` and one
    ``TraceAnnotation`` named ``lgbtpu:<name>``. Yields the span's
    ``fields`` dict, which the body may add to. ``name`` is one of
    ``phases.HOST_SPANS`` or a canonical phase: the span sites are a
    fixed list, so a reader of the ring knows every name it can meet.

    ``parts`` > 1: the span is that many units of its site's work at once
    (a fused dispatch that grows the K trees of a multiclass iteration),
    and the ring keeps it as ``parts`` consecutive records, each a
    ``parts``-th of its time with ``part`` and ``parts`` among its
    fields, so that one record is one unit wherever the site's unit is
    (one ``gbdt.dispatch`` record a tree). The annotation stays one.
    TEMPORARY: the split times are made up, and ``parts`` exists only
    because the benchmark's ``driver.dispatch_ms_per_tree`` reads one
    record a tree. Once that reader divides a record by a field that
    counts its trees, the dispatch site keeps one record with that field
    and ``parts`` goes."""
    if name not in HOST_SPANS:
        _check_phase(name)
    import jax
    stack = recorder._stack()
    parent = stack[-1] if stack else ""
    stack.append(name)
    t0 = time.time_ns()
    try:
        with jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + name):
            yield fields
    finally:
        t1 = time.time_ns()
        stack.pop()
        if parts == 1:
            recorder.record(name, t0, t1, parent, fields)
        else:
            cut = [t0 + (t1 - t0) * k // parts for k in range(parts + 1)]
            for k in range(parts):
                recorder.record(name, cut[k], cut[k + 1], parent,
                                dict(fields, part=k, parts=parts))


def _check_phase(name: str) -> None:
    if name not in KNOWN_PHASES:
        raise ValueError(
            f"unknown profiler phase {name!r}; canonical phases are "
            f"{sorted(KNOWN_PHASES)} (lightgbm_tpu/phases.py — add new "
            "phases there so the HLO auditors keep attributing them)")


def stage(name: str):
    """Stage marker for TRACED code: a ``jax.named_scope`` and nothing
    else. ``name`` must be canonical (``phases.py``): the stage map and
    the collective auditors attribute instructions by these strings."""
    _check_phase(name)
    import jax
    return jax.named_scope(name)


class stage_sequence:
    """Consecutive stages of one traced function, for code whose steps
    follow each other in one long body: ``stg(name)`` leaves the stage
    that is open and enters ``name``; leaving the ``with`` closes the
    last one (on an exception too, so the thread's name stack is never
    left extended)::

        with profiler.stage_sequence() as stg:
            stg(phases.POP); ...
            stg(phases.APPLY); ...
    """

    def __init__(self):
        self._open = None

    def __enter__(self) -> "stage_sequence":
        return self

    def __call__(self, name: str) -> None:
        self._close()
        cm = stage(name)
        cm.__enter__()
        self._open = cm

    def _close(self) -> None:
        cm, self._open = self._open, None
        if cm is not None:
            cm.__exit__(None, None, None)

    def __exit__(self, *exc) -> None:
        self._close()


@contextlib.contextmanager
def phase(name: str) -> Iterator[None]:
    """Phase marker around EAGER dispatches (the legacy loop, engine
    eval, ingest, prefetch): a host :func:`span` named ``name`` plus the
    ``jax.named_scope`` of :func:`stage`, so whatever is traced inside
    carries the phase too. Inside traced code use :func:`stage`: a host
    clock there would time the tracing, not the phase."""
    with stage(name), span(name):
        yield


# ----------------------------------------------------------------------
# Per-phase totals of a stretch of the ring.
#
# The raw spans are NOT comparable across drivers or across the
# class_batch knob: the legacy loop fires ``build``/``update`` once per
# class per iteration (K spans), the class-batched build exactly once.
# Aggregating to per-name TOTALS keeps before/after timings comparable —
# the sum over K unrolled spans lines up against the one batched span.

class PhaseTotals:
    """Seconds and span counts by name, over the spans the recorder saw
    from this object's creation on (until :meth:`close`). It reads the
    ring when asked and remembers the last ``seq`` it folded in, so it
    stays exact as long as it is asked at least once per ring length of
    spans (the telemetry session asks at every sync)."""

    def __init__(self, rec: Optional[SpanRecorder] = None):
        self._rec = recorder if rec is None else rec
        self._next = self._rec.seq
        self._stop: Optional[int] = None
        self._acc: Dict[str, List[float]] = {}
        self._lock = threading.Lock()

    def _fold(self) -> None:
        with self._lock:
            for sp in self._rec.since(self._next):
                if self._stop is not None and sp.seq >= self._stop:
                    break
                ent = self._acc.setdefault(sp.name, [0.0, 0])
                ent[0] += sp.seconds
                ent[1] += 1
                self._next = sp.seq + 1

    def close(self) -> None:
        """Fold what is there and take no later span."""
        self._fold()
        self._stop = self._rec.seq

    def total_s(self, name: str) -> float:
        self._fold()
        return self._acc.get(name, [0.0, 0])[0]

    def count(self, name: str) -> int:
        self._fold()
        return int(self._acc.get(name, [0.0, 0])[1])

    def items(self) -> List[Tuple[str, float, int]]:
        self._fold()
        return [(k, v[0], int(v[1])) for k, v in sorted(self._acc.items())]

    def per_iteration(self, iterations: int) -> Dict[str, dict]:
        """{name: {total_s, count, s_per_iter, spans_per_iter}} —
        ``s_per_iter`` is the comparable number: the K unrolled
        ``build`` spans of one legacy multiclass iteration and the one
        class-batched span both aggregate to that iteration's build
        seconds."""
        it = max(int(iterations), 1)
        return {k: {"total_s": tot, "count": cnt, "s_per_iter": tot / it,
                    "spans_per_iter": cnt / it}
                for k, tot, cnt in self.items()}

    def render(self, iterations: Optional[int] = None) -> str:
        rows = []
        for name, tot, cnt in self.items():
            line = f"{name:<16} {tot * 1e3:9.2f} ms  x{cnt}"
            if iterations:
                line += (f"  ({tot * 1e3 / max(iterations, 1):.2f} "
                         f"ms/iter over {iterations} iter)")
            rows.append(line)
        return "\n".join(rows) or "(no spans recorded)"


@contextlib.contextmanager
def collect_phase_totals() -> Iterator[PhaseTotals]:
    """Totals of every span recorded inside the block (any thread).
    Host-side wall clock: around eager dispatches (legacy driver) a
    phase span covers dispatch + device wait; the fused driver records
    ``gbdt.dispatch`` / ``gbdt.sync.*`` and no per-phase span."""
    col = PhaseTotals()
    try:
        yield col
    finally:
        col.close()
