"""Device time by stage from ``jax.profiler`` captures.

The span record (profiler.py) is host wall-clock; the roadmap's kernel
work is judged on *device* time. This module reduces what a capture
leaves behind (``profiler.trace``, the ``/trace`` endpoint,
``jax.profiler.start_trace``) to seconds by canonical stage
(``phases.py``), the longest instructions with their stage and source
scope, and the longest idle gaps with the program span that covers each.

There is ONE reduction (:func:`reduce_capture`), over plain event
lists, with two loaders in front of it:

- :func:`load_xplane` — the ``.xplane.pb`` the profiler writes (read
  with ``jax.profiler.ProfileData``). On a TPU the "XLA Ops" line of a
  device plane holds one event per executed HLO instruction, named by
  the instruction's TEXT (``%fusion.1 = pred[...] fusion(...)``) and
  carrying no scope and no ``op_name``; its module is the event of the
  "XLA Modules" line that encloses it. On a CPU the executor threads of
  the host plane stand in for a device and name ``hlo_op`` /
  ``hlo_module`` in their stats.
- :func:`load_trace_json` — the trace-event JSON of the same capture
  (``*.trace.json[.gz]``), kept for captures that have no xplane.

Attribution, per device event:

1. **Stage map** — ``{module: StageMap}`` built from the compiled
   module's ``op_name`` metadata (``costmodel.instruction_phase_map``)
   and looked up by (module, instruction name). It is the only road
   from a device event to a source scope. Captures taken through the
   telemetry server save it as ``phase_map.json`` next to the trace so
   offline ``monitor --perf`` has it; the same sidecar holds, under
   ``step_work``, the step's shape and the count of each stage's work
   over the captured trees (:func:`step_work_of`), which puts a count,
   its unit and the cost of one unit beside a stage's seconds.
2. **Host-span overlap** — the legacy driver dispatches one program per
   phase under a host phase span, so what the map misses is attributed
   to the host phase span(s) it overlaps.

Anything both miss lands in the explicit ``unknown`` bucket —
attribution never silently drops device time.

Time is *self* time by nesting: an event's duration less that of the
events nested directly in it, per track, so a ``while`` container keeps
only its own overhead and its body's instructions are counted where
they ran. Pure runtime wrappers of the CPU executor
(``ThunkExecutor::Execute``) are never a stage of their own: a wrapper
mostly covered by its own thread's ops contributes its remainder
(inter-thunk scheduling) through path 2; one mostly empty is a
dispatcher blocking on worker threads and is dropped.
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

from ..phases import KNOWN_PHASES
from ..profiler import ANNOTATION_PREFIX

__all__ = ["PhaseProfile", "OpEvent", "HostSpan", "Capture", "parse_trace",
           "reduce_capture", "load_xplane", "load_trace_json",
           "find_trace_files", "save_phase_map", "load_phase_map",
           "step_work_of",
           "find_phase_map", "stage_of_path", "instruction_of",
           "PHASE_MAP_NAME", "UNKNOWN"]

PHASE_MAP_NAME = "phase_map.json"
STEP_WORK_KEY = "step_work"     # the sidecar's one entry that is no module
UNKNOWN = "unknown"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10            # entries of a breakdown list
TEXT_CHARS = 160    # of an instruction's text kept in such a list
EPS = 1e-9          # seconds; nesting tolerance, as benchmarks/harness/trace

_STEP_NAME = "boost_iter"
_DISPATCH_SPAN = "gbdt.dispatch"

Interval = Tuple[float, float]


class OpEvent(NamedTuple):
    """One executed instruction (or runtime container) on a device track."""
    name: str         # instruction text, or its bare name
    start: float      # seconds on the capture's clock
    dur: float
    module: str = ""  # HLO module it belongs to, '' when unknown


class HostSpan(NamedTuple):
    name: str         # 'gbdt.dispatch', 'build', 'boost_iter', ...
    start: float
    dur: float


class Capture(NamedTuple):
    tracks: Dict[str, List[OpEvent]]   # device label -> events
    host_spans: List[HostSpan]         # lgbtpu: spans, phases, step markers
    n_events: int
    sources: List[str]
    epoch_ns: Optional[int] = None     # time.time_ns() of the clock's zero


# ----------------------------------------------------------------------
# Names

def stage_of_path(name: str) -> Optional[str]:
    """Deepest canonical stage along a scope path:
    ``jit(f)/build/while/body/compact/hist_gather/gather`` →
    ``hist_gather``. Only exact components count (``named_scope`` emits
    the raw string)."""
    found = None
    for part in str(name).replace(":", "/").split("/"):
        if part in KNOWN_PHASES:
            found = part
    return found


def instruction_of(name: str) -> str:
    """``%fusion.1 = pred[...] fusion(...)`` → ``fusion.1``; a bare
    instruction name is returned as it is."""
    head = name.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def _module_of(name: str) -> str:
    """``jit__fused_step_entry(9155629205207315214)`` → the module name."""
    return name.split("(", 1)[0]


# ----------------------------------------------------------------------
# Files

def find_trace_files(source: str) -> List[str]:
    """Capture files under ``source``: a file itself, a profiler log
    dir (``<dir>/plugins/profile/<ts>/<host>.xplane.pb``) or a run dir
    holding several. Xplane files are preferred; the trace-event JSON of
    a capture is used only where no xplane lies beside it."""
    if os.path.isfile(source):
        return [source]
    if not os.path.isdir(source):
        return []

    def hits(*pats):
        out: List[str] = []
        for pat in pats:
            out.extend(glob.glob(os.path.join(source, pat), recursive=True))
        return sorted(set(out))

    planes = hits("**/*.xplane.pb", "**/*.xplane.pb.gz")
    dirs = {os.path.dirname(p) for p in planes}
    return planes + [p for p in hits("**/*.trace.json.gz", "**/*.trace.json")
                     if os.path.dirname(p) not in dirs]


def save_phase_map(log_dir: str, maps: Dict[str, Any],
                   step_work: Optional[Dict[str, Any]] = None) -> str:
    """Write the stage maps next to a capture so offline parsers can
    attribute its events; with ``step_work`` (:func:`step_work_of`) also
    the step's shape and the work of the captured trees, so that they can
    put a count and a cost a unit beside a stage's seconds."""
    doc = {m: {"stages": sm.stages, "scopes": sm.scopes,
               "mixed_fusions": sm.mixed_fusions}
           if hasattr(sm, "stages") else dict(sm)
           for m, sm in maps.items()}
    if step_work:
        doc[STEP_WORK_KEY] = step_work
    path = os.path.join(log_dir, PHASE_MAP_NAME)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(doc, f, sort_keys=True)
    return path


def step_work_of(trainer, trees: int) -> Optional[Dict[str, Any]]:
    """The sidecar's ``step_work`` entry for a capture of ``trees`` trees:
    the trainer's step shape (``phases.STEP_SHAPE``) and the work of the
    newest ``trees`` trees its round log holds (``GBDT.stage_work``).
    None where the trainer has neither yet."""
    shape = getattr(trainer, "step_shape", None)
    held = min(int(trees), len(getattr(trainer, "round_log", ())))
    if not shape or held <= 0:
        return None
    return {"step_shape": dict(shape), "trees": held,
            "stage_work": {k: [c, u] for k, (c, u) in
                           trainer.stage_work(held).items()}}


def _load_sidecar(path: str) -> Dict[str, Any]:
    with open(path, "r", encoding="utf-8") as f:
        return dict(json.load(f) or {})


def load_phase_map(path: str) -> Dict[str, Any]:
    """The stage maps of a sidecar file, by module."""
    doc = _load_sidecar(path)
    doc.pop(STEP_WORK_KEY, None)
    return doc


def _find_sidecar(trace_file: str, max_up: int = 4) -> Dict[str, Any]:
    """Walk up from a trace file looking for ``phase_map.json`` (the
    capture root is a few levels above ``plugins/profile/<ts>/``)."""
    d = os.path.dirname(os.path.abspath(trace_file))
    for _ in range(max_up):
        cand = os.path.join(d, PHASE_MAP_NAME)
        if os.path.isfile(cand):
            try:
                return _load_sidecar(cand)
            except (OSError, ValueError):
                return {}
        parent = os.path.dirname(d)
        if parent == d:
            break
        d = parent
    return {}


def find_phase_map(trace_file: str, max_up: int = 4) -> Dict[str, Any]:
    """The stage maps of the sidecar found above a trace file."""
    doc = _find_sidecar(trace_file, max_up)
    doc.pop(STEP_WORK_KEY, None)
    return doc


class _Lookup:
    """(module, instruction) -> (stage, scope) over maps given as
    ``costmodel.StageMap``s, as ``phase_map.json`` entries, or as plain
    ``{instruction: stage}`` tables."""

    def __init__(self, maps: Optional[Dict[str, Any]]):
        self.tables: Dict[str, Tuple[Dict[str, str], Dict[str, str]]] = {}
        self.mixed = 0
        for mod, sm in (maps or {}).items():
            if hasattr(sm, "stages"):
                st, sc, mx = sm.stages, sm.scopes, sm.mixed_fusions
            elif isinstance(sm.get("stages"), dict):
                st, sc = sm["stages"], sm.get("scopes") or {}
                mx = int(sm.get("mixed_fusions", 0))
            else:
                st, sc, mx = sm, {}, 0
            self.tables[str(mod)] = (st, sc)
            self.mixed += mx

    def find(self, module: str, instr: str) -> Tuple[Optional[str], str]:
        table = self.tables.get(module)
        if table is None and len(self.tables) == 1:
            table = next(iter(self.tables.values()))
        if table is None:
            return None, ""
        stage = table[0].get(instr)
        return (stage if stage in KNOWN_PHASES else None,
                table[1].get(instr, ""))


# ----------------------------------------------------------------------
# Loaders

def _host_span(name: str, start: float, dur: float) -> Optional[HostSpan]:
    """Host events the reduction reads: the program's ``lgbtpu:`` spans
    (prefix dropped), bare canonical phases, and step markers."""
    if name.startswith(ANNOTATION_PREFIX):
        return HostSpan(name[len(ANNOTATION_PREFIX):], start, dur)
    if name in KNOWN_PHASES or name.startswith(_STEP_NAME):
        return HostSpan(name, start, dur)
    return None


def load_xplane(path: str) -> Capture:
    """A capture from an ``.xplane.pb`` file (or a gzipped one)."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    tracks: Dict[str, List[OpEvent]] = {}
    spans: List[HostSpan] = []
    epoch = None
    n = 0
    for plane in data.planes:
        if plane.name == "Task Environment":
            epoch = dict(plane.stats).get("profile_start_time")
        elif plane.name.startswith("/device:"):
            ops: List[Tuple[str, float, float]] = []
            mods: List[Tuple[float, float, str]] = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                           for e in line.events]
                elif line.name == MODULES_LINE:
                    mods = sorted((e.start_ns * 1e-9,
                                   (e.start_ns + e.duration_ns) * 1e-9,
                                   _module_of(e.name)) for e in line.events)
            n += len(ops)
            if ops:
                tracks[plane.name.split("/device:", 1)[1]] = \
                    _with_modules(ops, mods)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                executor = line.name.startswith("tf_XLA")
                for e in line.events:
                    n += 1
                    if executor:
                        st = dict(e.stats)
                        if "hlo_op" in st:
                            tracks.setdefault("cpu:0", []).append(OpEvent(
                                str(st["hlo_op"]), e.start_ns * 1e-9,
                                e.duration_ns * 1e-9,
                                str(st.get("hlo_module", ""))))
                        continue
                    sp = _host_span(e.name, e.start_ns * 1e-9,
                                    e.duration_ns * 1e-9)
                    if sp is not None:
                        spans.append(sp)
    return Capture(tracks, spans, n, [path],
                   int(epoch) if epoch is not None else None)


def _with_modules(ops: Sequence[Tuple[str, float, float]],
                  mods: Sequence[Tuple[float, float, str]]) -> List[OpEvent]:
    """Each op event with the module whose "XLA Modules" event encloses
    its start (modules run one after another on a device)."""
    out: List[OpEvent] = []
    j = 0
    for name, start, dur in sorted(ops, key=lambda o: o[1]):
        while j + 1 < len(mods) and mods[j][1] <= start + EPS:
            j += 1
        mod = ""
        if mods and mods[j][0] <= start + EPS and start < mods[j][1] + EPS:
            mod = mods[j][2]
        out.append(OpEvent(name, start, dur, mod))
    return out


def load_trace_events(path: str) -> List[dict]:
    """The ``traceEvents`` list of one trace-event JSON file
    (gzipped or plain)."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt", encoding="utf-8") as f:
        obj = json.load(f)
    if isinstance(obj, list):
        return obj
    return list(obj.get("traceEvents") or [])


def _device_label(pname: str, tname: str) -> Optional[str]:
    """Device label for a (process, thread) track of a trace-event JSON,
    or None for host tracks. TPU/GPU device processes are
    ``/device:TPU:0``-style; their step/module summary lines are
    excluded (op lines carry the time). On CPU the XLA executor threads
    (``tf_XLATfrtCpuClient...``) merge into one ``cpu:0`` label, each
    thread a track of its own."""
    low_t = tname.lower()
    if "/device:" in pname:
        if "step" in low_t or "module" in low_t:
            return None
        return pname.split("/device:", 1)[1] or pname
    if tname.startswith("tf_XLA") and "codegen" not in low_t \
            and "llvm" not in low_t:
        return "cpu:0"
    return None


def load_trace_json(path: str) -> Capture:
    """A capture from trace-event JSON (timestamps in microseconds)."""
    events = load_trace_events(path)
    procs: Dict[Any, str] = {}
    threads: Dict[Tuple[Any, Any], str] = {}
    for ev in events:
        if ev.get("ph") == "M":
            args = ev.get("args") or {}
            if ev.get("name") == "process_name":
                procs[ev.get("pid")] = str(args.get("name", ""))
            elif ev.get("name") == "thread_name":
                threads[(ev.get("pid"), ev.get("tid"))] = \
                    str(args.get("name", ""))
    tracks: Dict[str, List[OpEvent]] = {}
    spans: List[HostSpan] = []
    n = 0
    for ev in events:
        if ev.get("ph") != "X":
            continue
        n += 1
        try:
            start = float(ev["ts"]) * 1e-6
            dur = float(ev.get("dur", 0.0)) * 1e-6
        except (KeyError, TypeError, ValueError):
            continue
        key = (ev.get("pid"), ev.get("tid"))
        name = str(ev.get("name", ""))
        dev = _device_label(procs.get(key[0], ""), threads.get(key, ""))
        if dev is None:
            sp = _host_span(name, start, dur)
            if sp is not None:
                spans.append(sp)
            continue
        args = ev.get("args") or {}
        # a thread is a track: nesting is per thread, labels merge later
        tracks.setdefault(f"{dev}\t{key[0]}/{key[1]}", []).append(OpEvent(
            str(args.get("hlo_op") or name), start, dur,
            str(args.get("hlo_module", ""))))
    return Capture(tracks, spans, n, [path])


def load_capture(path: str) -> Capture:
    if ".xplane.pb" in os.path.basename(path):
        return load_xplane(path)
    return load_trace_json(path)


# ----------------------------------------------------------------------
# Interval helpers (all in seconds)

def _union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1] + EPS:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _total(intervals: List[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def _intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    i = j = 0
    while i < len(a) and j < len(b):
        s = max(a[i][0], b[j][0])
        e = min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _covering_span(t: float, spans: Sequence[HostSpan]) -> str:
    """The innermost program span that holds time ``t``, else the one
    that ended last before it (``after <name>``)."""
    best = last = None
    for s in spans:
        if s.start <= t <= s.start + s.dur:
            if best is None or s.dur < best.dur:
                best = s
        elif s.start + s.dur < t and (
                last is None or s.start + s.dur > last.start + last.dur):
            last = s
    if best is not None:
        return best.name
    # no span holds it: the host had already returned from the one before
    return f"after {last.name}" if last is not None else "outside-spans"


# ----------------------------------------------------------------------
# The profile

@dataclasses.dataclass
class PhaseProfile:
    """Per-stage device/host time of one capture (or of several merged
    capture files)."""
    device_phase_s: Dict[str, float]           # merged across devices
    per_device: Dict[str, Dict[str, float]]    # device → stage → s
    host_phase_s: Dict[str, float]             # host spans by name
    device_busy_s: float      # union of device-busy time, summed/device
    host_phase_busy_s: float  # union of host phase spans
    overlap_s: float          # device busy ∩ host phase spans
    dispatch_gap_s: float     # device idle inside boost_iter windows
    steps: int                # boost_iter step markers in the capture
    step_span_s: float        # union of the step windows
    n_events: int
    sources: List[str]
    # (instruction text, stage, source scope, self seconds), longest first
    top_ops: List[Tuple[str, str, str, float]] = \
        dataclasses.field(default_factory=list)
    # (device, covering program span, seconds), longest first
    idle_gaps: List[Tuple[str, str, float]] = \
        dataclasses.field(default_factory=list)
    dispatches: int = 0       # lgbtpu:gbdt.dispatch spans in the capture
    mixed_fusions: int = 0    # of the stage maps used
    epoch_ns: Optional[int] = None
    # the sidecar's ``step_work`` entry (:func:`step_work_of`): the step's
    # shape, and {stage: [count, unit]} over ``trees`` trees, one device's
    step_work: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def iterations(self) -> int:
        """Trees of the capture: its ``boost_iter`` markers, else its
        ``gbdt.dispatch`` spans (one fused dispatch is one iteration)."""
        return self.steps or self.dispatches

    def device_s_per_iter(self,
                          iterations: Optional[int] = None
                          ) -> Dict[str, float]:
        """Per-stage device seconds per boost iteration (the number
        comparable to ``ms_per_tree``)."""
        it = int(iterations if iterations is not None
                 else self.iterations())
        if it <= 0:
            return {}
        return {k: v / it for k, v in self.device_phase_s.items()}

    def unknown_share(self) -> float:
        tot = sum(self.device_phase_s.values())
        return self.device_phase_s.get(UNKNOWN, 0.0) / tot if tot else 0.0

    def stage_costs(self) -> Dict[str, Tuple[float, str, float]]:
        """``{stage: (count, unit, seconds a unit)}`` for the stages the
        sidecar counted work for and the capture has device seconds of:
        a device's seconds (the devices' mean) over a device's count.
        Empty without the sidecar's ``step_work``."""
        devices = max(len(self.per_device), 1)
        out = {}
        for stage, (count, unit) in sorted(
                (self.step_work.get("stage_work") or {}).items()):
            dv = self.device_phase_s.get(stage, 0.0) / devices
            if count > 0 and dv > 0:
                out[stage] = (count, unit, dv / count)
        return out

    def summary_dict(self) -> Dict[str, Any]:
        """JSON-ready summary (the ``/trace`` response body)."""
        d = {
            "device_phase_s": {k: round(v, 6) for k, v in
                               sorted(self.device_phase_s.items())},
            "host_phase_s": {k: round(v, 6) for k, v in
                             sorted(self.host_phase_s.items())},
            "devices": sorted(self.per_device),
            "device_busy_s": round(self.device_busy_s, 6),
            "overlap_s": round(self.overlap_s, 6),
            "dispatch_gap_s": round(self.dispatch_gap_s, 6),
            "steps": self.steps,
            "n_events": self.n_events,
            "top_ops": [[n, st, sc, round(s, 6)]
                        for n, st, sc, s in self.top_ops],
            "idle_gaps": [[dv, sp, round(s, 6)]
                          for dv, sp, s in self.idle_gaps],
        }
        per_iter = self.device_s_per_iter()
        if per_iter:
            d["device_s_per_iter"] = {k: round(v, 6)
                                      for k, v in sorted(per_iter.items())}
            d["dispatch_gap_s_per_iter"] = round(
                self.dispatch_gap_s / max(self.iterations(), 1), 6)
        costs = self.stage_costs()
        if costs:
            d["step_shape"] = dict(self.step_work.get("step_shape") or {})
            d["work_trees"] = self.step_work.get("trees")
            d["stage_work"] = {
                k: {"count": c, "unit": u, "ns_per_unit": s * 1e9}
                for k, (c, u, s) in costs.items()}
        return d

    def render(self) -> str:
        """Stage table, longest instructions and idle gaps for
        ``monitor --perf``."""
        its = self.iterations()
        rows = [f"devices: {', '.join(sorted(self.per_device)) or '-'}"
                f"  steps: {self.steps}  dispatches: {self.dispatches}"
                f"  events: {self.n_events}"]
        names = sorted(set(self.device_phase_s) | set(self.host_phase_s),
                       key=lambda k: -self.device_phase_s.get(k, 0.0))
        tot = sum(self.device_phase_s.values())
        costs = self.stage_costs()
        if names:
            rows.append(f"  {'stage':<16} {'device ms':>12} {'share':>7} "
                        f"{'host ms':>12}"
                        + (f" {'device ms/iter':>16}" if its else "")
                        + (f" {'count':>16} {'unit':<17} {'a unit':>10}"
                           if costs else ""))
            for name in names:
                dv = self.device_phase_s.get(name, 0.0)
                hv = self.host_phase_s.get(name, 0.0) * 1e3
                line = (f"  {name:<16} {dv * 1e3:12.3f} "
                        f"{100.0 * dv / tot if tot else 0.0:6.2f}% "
                        f"{hv:12.3f}")
                if its:
                    line += f" {dv * 1e3 / its:16.4f}"
                if name in costs:
                    count, unit, s = costs[name]
                    line += (f" {count:16.6g} {unit:<17} "
                             f"{_unit_cost(s):>10}")
                rows.append(line)
            if costs:
                rows.append(
                    f"  counts: one device's work over "
                    f"{self.step_work.get('trees')} tree(s), from the "
                    "round log and the step's shape; a unit = the "
                    "devices' mean seconds / count")
        rows.append(f"  device busy {self.device_busy_s * 1e3:.3f} ms, "
                    f"self time by stage {tot * 1e3:.3f} ms "
                    f"({UNKNOWN} {100.0 * self.unknown_share():.2f}%), "
                    f"host∩device overlap {self.overlap_s * 1e3:.3f} ms, "
                    f"dispatch gap {self.dispatch_gap_s * 1e3:.3f} ms"
                    + (f" ({self.dispatch_gap_s / self.steps * 1e3:.3f}"
                       " ms/iter)" if self.steps else ""))
        if self.mixed_fusions:
            rows.append(f"  {self.mixed_fusions} fusion(s) of the stage map "
                        "mix stages (counted under the stage holding most "
                        "of their instructions)")
        if self.top_ops:
            rows.append("  longest instructions (self time):")
            for text, stage, scope, s in self.top_ops:
                rows.append(f"    {s * 1e3:12.3f} ms  [{stage}]  {text}")
                if scope:
                    rows.append(f"    {'':>15}  at {scope}")
        if self.idle_gaps:
            rows.append("  longest idle gaps (device, covering span):")
            for dev, sp, s in self.idle_gaps:
                rows.append(f"    {s * 1e3:12.3f} ms  {dev}  {sp}")
        return "\n".join(rows)


def _unit_cost(seconds: float) -> str:
    """Seconds a unit at the scale they read at: ``1.63 ps``."""
    for scale, name in ((1e-6, "us"), (1e-9, "ns")):
        if seconds >= scale:
            return f"{seconds / scale:.3f} {name}"
    return f"{seconds / 1e-12:.3f} ps"


def _is_wrapper(name: str) -> bool:
    """Pure runtime wrapper events: they cover whole dispatches on the
    same thread as the op events, carry no stage of their own, and
    would double-count everything beneath them."""
    return "ThunkExecutor" in name


def reduce_capture(capture: Capture,
                   maps: Optional[Dict[str, Any]] = None) -> PhaseProfile:
    """The one reduction: self time by nesting on every track, stage by
    (module, instruction) lookup, host-span overlap for what the map
    misses, ``unknown`` for the rest."""
    lookup = _Lookup(maps)
    phase_spans = sorted((s.start, s.start + s.dur, s.name)
                         for s in capture.host_spans
                         if s.name in KNOWN_PHASES)
    program_spans = [s for s in capture.host_spans
                     if not s.name.startswith(_STEP_NAME)]
    per_device: Dict[str, Dict[str, float]] = {}
    dev_busy: Dict[str, List[Interval]] = {}
    by_text: Dict[Tuple[str, str], List[Any]] = {}

    for track, events in capture.tracks.items():
        dev = track.split("\t", 1)[0]
        bucket = per_device.setdefault(dev, {})
        evs = sorted(events, key=lambda e: (e.start, -e.dur))
        self_s = [e.dur for e in evs]
        stack: List[int] = []
        for i, e in enumerate(evs):
            while stack and (evs[stack[-1]].start + evs[stack[-1]].dur
                             <= e.start + EPS):
                stack.pop()
            if stack:
                self_s[stack[-1]] -= e.dur
            else:
                dev_busy.setdefault(dev, []).append(
                    (e.start, e.start + e.dur))
            stack.append(i)
        for e, s in zip(evs, self_s):
            s = max(s, 0.0)
            if _is_wrapper(e.name):
                # see the module docstring: mostly covered -> its
                # remainder is scheduling time; mostly empty -> dropped
                if e.dur <= 0 or (e.dur - s) / e.dur < 0.5:
                    continue
                stage, scope = None, ""
            else:
                stage, scope = lookup.find(e.module, instruction_of(e.name))
            if stage is None and s > 0:
                # path 2: the host phase span(s) the event overlaps
                end = e.start + e.dur
                left = s
                for a, b, ph in phase_spans:
                    if b <= e.start:
                        continue
                    if a >= end or left <= 0:
                        break
                    ov = min(min(b, end) - max(a, e.start), left)
                    if ov > 0:
                        bucket[ph] = bucket.get(ph, 0.0) + ov
                        left -= ov
                if left > 1e-12:
                    bucket[UNKNOWN] = bucket.get(UNKNOWN, 0.0) + left
            elif stage is not None:
                bucket[stage] = bucket.get(stage, 0.0) + s
            if not _is_wrapper(e.name):
                ent = by_text.setdefault((e.module, e.name[:TEXT_CHARS]),
                                         [stage or UNKNOWN, scope, 0.0])
                ent[2] += s

    merged: Dict[str, float] = {}
    for p in per_device.values():
        for k, v in p.items():
            merged[k] = merged.get(k, 0.0) + v
    busy_unions = {d: _union(iv) for d, iv in dev_busy.items()}
    gaps: List[Tuple[float, str, float]] = []
    for dev, u in busy_unions.items():
        for (_, a), (b, _) in zip(u, u[1:]):
            gaps.append((b - a, dev, (a + b) / 2))
    gaps.sort(key=lambda g: -g[0])
    host_phase: Dict[str, float] = {}
    steps: List[Interval] = []
    dispatches = 0
    for s in capture.host_spans:
        if s.name.startswith(_STEP_NAME):
            steps.append((s.start, s.start + s.dur))
            continue
        host_phase[s.name] = host_phase.get(s.name, 0.0) + s.dur
        dispatches += s.name == _DISPATCH_SPAN
    host_union = _union([(a, b) for a, b, _ in phase_spans])
    all_busy = _union([iv for u in busy_unions.values() for iv in u])
    steps_union = _union(steps)
    return PhaseProfile(
        device_phase_s=dict(sorted(merged.items())),
        per_device={d: dict(sorted(p.items()))
                    for d, p in sorted(per_device.items())},
        host_phase_s=dict(sorted(host_phase.items())),
        device_busy_s=sum(_total(u) for u in busy_unions.values()),
        host_phase_busy_s=_total(host_union),
        overlap_s=_total(_intersect(all_busy, host_union)),
        dispatch_gap_s=max(_total(steps_union) - _total(
            _intersect(all_busy, steps_union)), 0.0),
        steps=len(steps),
        step_span_s=_total(steps_union),
        n_events=capture.n_events,
        sources=list(capture.sources),
        top_ops=[(text, st, sc, s) for (_, text), (st, sc, s) in sorted(
            by_text.items(), key=lambda kv: -kv[1][2])[:TOP]],
        idle_gaps=[(dev, _covering_span(mid, program_spans), g)
                   for g, dev, mid in gaps[:TOP]],
        dispatches=dispatches,
        mixed_fusions=lookup.mixed,
        epoch_ns=capture.epoch_ns)


def merge_captures(captures: Sequence[Capture]) -> Capture:
    """Several files of one capture (one per host) as one. Track labels
    of later files are kept apart by a file index."""
    if len(captures) == 1:
        return captures[0]
    tracks: Dict[str, List[OpEvent]] = {}
    for i, c in enumerate(captures):
        for k, v in c.tracks.items():
            dev, _, rest = k.partition("\t")
            tracks[f"{dev}\t{i}:{rest}"] = v
    return Capture(tracks, [s for c in captures for s in c.host_spans],
                   sum(c.n_events for c in captures),
                   [p for c in captures for p in c.sources],
                   captures[0].epoch_ns)


def parse_trace(source: str,
                phase_maps: Optional[Dict[str, Any]] = None
                ) -> PhaseProfile:
    """Parse one capture (file, log dir, or run dir — every capture file
    found under ``source`` merges into one profile). ``phase_maps``
    overrides the stage maps of the per-capture ``phase_map.json``
    discovery; the sidecar's ``step_work`` entry is read either way."""
    files = find_trace_files(source)
    if not files:
        raise FileNotFoundError(f"no profiler capture under {source!r}")
    found: Dict[str, Any] = {}
    for path in files:
        found.update(_find_sidecar(path))
    step_work = found.pop(STEP_WORK_KEY, None) or {}
    prof = reduce_capture(merge_captures([load_capture(p) for p in files]),
                          found if phase_maps is None else phase_maps)
    prof.step_work = step_work
    return prof
