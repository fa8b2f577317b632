"""From the profiler's xplane to numbers: device busy and idle time, time
by class of operation, the longest idle gaps and what the host was doing
in them. The reduction works on plain event lists, so it can be checked on
hand-made events as well as on a recorded trace.

On a TPU the "XLA Ops" line of a device plane holds one event per executed
HLO instruction, named by the instruction's text (result shape, opcode and
operand shapes). A ``while`` is one long event with its body's operations
nested inside it, so time is taken as *self* time: an event's duration less
that of the events nested directly in it. Operations are told apart by the
shapes in that text and never by fusion numbers, which change with every
compile.
"""

from __future__ import annotations

import glob
import gzip
import os
import re
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench:"
WINDOW_SPAN = "bench:window"
CONTAINERS = ("while", "conditional", "call")
TOP = 10            # entries of a breakdown list
TEXT_CHARS = 160    # of an op's text kept as its key there

_SHAPE_RE = re.compile(r"\b(?:pred|bf16|[sufc]\d+)\[([0-9,]*)\]")
_OPCODE_RE = re.compile(r" ([a-z][a-z0-9\-]*)\(")


class Event(NamedTuple):
    name: str
    start: float     # seconds on the trace's clock
    dur: float


class Op(NamedTuple):
    """What an instruction's text says about it."""
    name: str          # "%fusion.376"
    opcode: str        # "fusion", "custom-call", "while", ...
    result_elems: int  # elements of the largest result array
    max_elems: int     # elements of the largest result or operand array


def _elems(dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n


def parse_op(text: str) -> Op:
    head, sep, rest = text.partition(" = ")
    if not sep:
        return Op(text, "", 0, 0)
    m = _OPCODE_RE.search(rest)
    if m is None:
        return Op(head, "", 0, 0)
    res = [_elems(d) for d in _SHAPE_RE.findall(rest[:m.start()])]
    args = [_elems(d) for d in _SHAPE_RE.findall(rest[m.end():])]
    r = max(res, default=0)
    return Op(head, m.group(1), r, max([r] + args))


def op_class(op: Op, in_loop: bool, rows: int, cols: int,
             kernel_pattern: str) -> str:
    """One of ``kernel`` (the program's own custom call), ``relayout`` (an
    XLA operation whose result has at least rows x cols elements: the
    gathered, cast or transposed bin matrix), ``rowwise`` (an XLA operation
    inside a loop whose largest array has at least ``rows`` elements:
    per-row lookups, compaction, relabelling) or ``other``."""
    if op.opcode in CONTAINERS:
        return "other"
    if op.opcode == "custom-call" and kernel_pattern in op.name:
        return "kernel"
    if op.result_elems >= rows * cols:
        return "relayout"
    if in_loop and op.max_elems >= rows:
        return "rowwise"
    return "other"


class Report(NamedTuple):
    window_s: float
    busy_s: float
    class_s: Dict[str, float]           # self seconds by op_class
    root_kernel_s: List[float]          # kernel events outside any loop
    device_ops: List[Tuple[str, float]]  # top self seconds by op text
    idle_gaps: List[Tuple[str, float]]   # longest gaps by covering span

    @property
    def idle_share(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def class_share(self, cls: str) -> float:
        return 100.0 * self.class_s.get(cls, 0.0) / self.busy_s


def _covering_span(t: float, spans: Sequence[Event]) -> str:
    """The innermost benchmark span that holds time ``t``."""
    best = None
    for s in spans:
        if s.start <= t <= s.start + s.dur and s.name != WINDOW_SPAN:
            if best is None or s.dur < best.dur:
                best = s
    return best.name[len(SPAN_PREFIX):] if best else "outside-spans"


def reduce_plane(events: Sequence[Event], spans: Sequence[Event],
                 rows: int, cols: int, kernel_pattern: str = "pallas"
                 ) -> Report:
    """Reduce one device's operation events. ``spans`` are the benchmark's
    host spans on the same clock; the one named ``bench:window`` is the
    traced window (without it, the window is first event to last)."""
    evs = sorted(events, key=lambda e: (e.start, -e.dur))
    win = next((s for s in spans if s.name == WINDOW_SPAN), None)
    if win is not None:
        w0, w1 = win.start, win.start + win.dur
        evs = [e for e in evs if e.start + e.dur > w0 and e.start < w1]
    elif evs:
        w0, w1 = evs[0].start, max(e.start + e.dur for e in evs)
    else:
        w0 = w1 = 0.0
    ops = [parse_op(e.name) for e in evs]
    self_s = [e.dur for e in evs]
    in_loop = [False] * len(evs)
    stack: List[int] = []            # indices of the open, nested events
    busy: List[List[float]] = []     # merged top-level intervals
    eps = 1e-9
    for i, e in enumerate(evs):
        while stack and evs[stack[-1]].start + evs[stack[-1]].dur <= e.start + eps:
            stack.pop()
        if stack:
            self_s[stack[-1]] -= e.dur
            in_loop[i] = any(ops[j].opcode == "while" for j in stack)
        else:
            a, b = max(e.start, w0), min(e.start + e.dur, w1)
            if busy and a <= busy[-1][1] + eps:
                busy[-1][1] = max(busy[-1][1], b)
            else:
                busy.append([a, b])
        stack.append(i)
    class_s: Dict[str, float] = {}
    by_text: Dict[str, float] = {}
    root_kernel: List[float] = []
    for e, op, s, loop in zip(evs, ops, self_s, in_loop):
        s = max(s, 0.0)
        cls = op_class(op, loop, rows, cols, kernel_pattern)
        class_s[cls] = class_s.get(cls, 0.0) + s
        key = e.name[:TEXT_CHARS]
        by_text[key] = by_text.get(key, 0.0) + s
        if cls == "kernel" and not loop:
            root_kernel.append(e.dur)
    gaps: List[Tuple[float, float]] = []
    edge = w0
    for a, b in busy:
        if a > edge:
            gaps.append((edge, a))
        edge = max(edge, b)
    if w1 > edge:
        gaps.append((edge, w1))
    gaps.sort(key=lambda g: g[0] - g[1])
    return Report(
        window_s=w1 - w0,
        busy_s=sum(b - a for a, b in busy),
        class_s=class_s,
        root_kernel_s=root_kernel,
        device_ops=sorted(by_text.items(), key=lambda kv: -kv[1])[:TOP],
        idle_gaps=[(_covering_span((a + b) / 2, spans), b - a)
                   for a, b in gaps[:TOP]])


def merge_reports(reports: Sequence[Report]) -> Report:
    """Several chips: seconds are averaged over the chips, and the lists
    are those of the first."""
    n = len(reports)
    first = reports[0]
    if n == 1:
        return first
    classes = sorted({c for r in reports for c in r.class_s})
    return first._replace(
        window_s=sum(r.window_s for r in reports) / n,
        busy_s=sum(r.busy_s for r in reports) / n,
        class_s={c: sum(r.class_s.get(c, 0.0) for r in reports) / n
                 for c in classes})


# -- reading the profiler's file ---------------------------------------------

def load_xplane(path: str) -> Tuple[Dict[str, List[Event]], List[Event]]:
    """(operation events by device plane, the benchmark's host spans) from
    an ``.xplane.pb`` file, a gzipped one, or a profiler log directory."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(
            path, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise FileNotFoundError(f"no xplane.pb under {path}")
        path = found[-1]
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(
                    Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                    for e in line.events if e.name.startswith(SPAN_PREFIX))
    return devices, spans


def reduce_xplane(path: str, rows: int, cols: int,
                  kernel_pattern: str = "pallas") -> Optional[Report]:
    """The whole reduction; None where the trace holds no device plane."""
    devices, spans = load_xplane(path)
    reports = [reduce_plane(evs, spans, rows, cols, kernel_pattern)
               for _, evs in sorted(devices.items()) if evs]
    return merge_reports(reports) if reports else None
