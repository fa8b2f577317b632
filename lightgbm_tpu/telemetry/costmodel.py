"""What a compiled program is made of, read from the program itself.

The instruction→stage map (`instruction_phase_map`): every instruction
of a compiled module under the canonical stage of ``phases.py`` whose
``jax.named_scope`` is deepest on its ``op_name`` metadata (parsed by
``analysis/hlo_walk.py``). A device event carries only
``{hlo_module, hlo_op}``, so this map is the road from an event to a
source line; the trace parser (``xprof.py``) and the benchmark's stage
readers attribute device time through it.

Also the analytical FLOP/byte count of one histogram build
(`analytical_hist_counts`) with XLA's own price of the same work to
hold it to (`hist_xla_cost`, within 2x), and the chip peak table
(``TPU_PEAKS``) a roofline share is stated against.
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

from ..analysis.hlo_walk import parse_all_ops
from .xprof import stage_of_path

__all__ = ["TPU_PEAKS", "ChipPeaks", "HIST_CH",
           "instruction_phase_map", "StageMap", "module_name",
           "fused_compiled", "booster_phase_maps", "analytical_hist_counts",
           "kernel_roofline_fields", "roofline_utilization",
           "hist_xla_cost", "chip_peaks"]


class ChipPeaks(NamedTuple):
    kind: str            # jax.devices()[0].device_kind, verbatim
    bf16_tflops: float
    int8_tops: float
    hbm_gbps: float


# Published per-chip peaks keyed by the EXACT ``device_kind`` the
# installed runtime reports (jax 0.9.0 / libtpu 0.0.34 name a v5e chip
# "TPU v5 lite"). Source: Google Cloud documentation, "TPU v5e" system
# architecture — 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at
# 819 GB/s. A TPU that is not in the table is an error, not a default.
TPU_PEAKS = {p.kind: p for p in (
    ChipPeaks("TPU v5 lite", 197.0, 393.0, 819.0),
)}

# histogram channels: (grad, hess, count)
HIST_CH = 3

_MODULE_RE = re.compile(r"^HloModule\s+([^\s,]+)", re.MULTILINE)
_COMP_RE = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.-]+)\s*\(.*\)\s*->"
                      r"\s*.+\{\s*$")
_CALLS_RE = re.compile(r"\b(?:calls|to_apply|body|condition)="
                       r"%?([\w.-]+)")

# opcodes that never execute (metadata / plumbing) — excluded from the
# per-phase op/byte attribution so it reflects real work
_NOOP_OPCODES = frozenset({"parameter", "constant", "tuple",
                           "get-tuple-element", "bitcast"})


def chip_peaks() -> Optional[ChipPeaks]:
    """Peaks of device 0: None off-TPU (a CPU host has no roofline to
    state), the table row for a known ``device_kind``, and LookupError
    for a TPU the table does not know — every roofline field is a
    ratio against these numbers, so there is no honest default."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return None
    try:
        return TPU_PEAKS[dev.device_kind]
    except KeyError:
        raise LookupError(
            f"no published peaks for TPU device_kind "
            f"{dev.device_kind!r}; add it to costmodel.TPU_PEAKS with "
            f"its source (known: {sorted(TPU_PEAKS)})") from None


# ----------------------------------------------------------------------
# Analytical histogram-kernel counts

def analytical_hist_counts(R: int, F: int, B: int,
                           L: int) -> Tuple[float, float]:
    """(flops, bytes) of one histogram build as hand-derived: FLOPs
    count the one-hot matmul as executed on the MXU
    (2·R·(F·B)·(L·CH)); bytes count the irreducible streams (bins
    uint8 + gh f32 in, hist f32 out)."""
    flops = 2.0 * R * (F * B) * (L * HIST_CH)
    bytes_ = R * F + R * HIST_CH * 4 + F * B * L * HIST_CH * 4
    return flops, bytes_


def roofline_utilization(tflops: float, gbps: float) -> Dict[str, Any]:
    """MFU / HBM utilization vs the chip peak, when on a known TPU."""
    peaks = chip_peaks()
    if peaks is None:
        return {}
    return {"hist_mfu": round(tflops / peaks.bf16_tflops, 4),
            "hist_hbm_util": round(gbps / peaks.hbm_gbps, 4),
            "chip": peaks.kind}


def kernel_roofline_fields(platform: str, t_hist_s: float,
                           R: int, F: int, B: int, L: int) -> dict:
    """Derived FLOP/s + HBM bandwidth for one histogram build vs chip
    peak. Off-TPU the achieved-rate fields are still emitted, labelled
    by `platform`, with no peak comparison."""
    flops, bytes_ = analytical_hist_counts(R, F, B, L)
    out = {"hist_tflops": round(flops / t_hist_s / 1e12, 3),
           "hist_hbm_gbps": round(bytes_ / t_hist_s / 1e9, 2)}
    if platform == "tpu":
        out.update(roofline_utilization(out["hist_tflops"],
                                        out["hist_hbm_gbps"]))
    return out


def _cost_dict(compiled) -> Dict[str, float]:
    try:
        ca = compiled.cost_analysis()
    except Exception:  # noqa: BLE001 — backend may not implement it
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return dict(ca) if isinstance(ca, dict) else {}


def hist_xla_cost(R: int, F: int, B: int, L: int, *,
                  impl: str = "matmul",
                  hist_dtype: str = "bfloat16") -> Dict[str, float]:
    """XLA's own price of one histogram build: compile
    ``ops.histogram.build_histograms`` at the given lattice and read
    ``cost_analysis``. ``impl='matmul'`` is the formulation the
    analytical count models (one-hot MXU matmul), so these two must
    agree within 2x (``tests/test_perf_observability.py`` asserts it).

    Compiled with ``block_rows=R`` (one block): ``cost_analysis``
    prices a while-loop body ONCE regardless of trip count, so the
    production row-chunked program under-reports total flops by the
    number of blocks. The unchunked program does the same logical work
    in straight-line HLO, which is what both the analytical count and
    a measured wall-clock divide against."""
    import jax
    import jax.numpy as jnp

    from ..ops.histogram import build_histograms
    bins = jnp.zeros((R, F), jnp.uint8)
    gh = jnp.zeros((R, HIST_CH), jnp.float32)
    rl = jnp.zeros((R,), jnp.int32)
    lids = jnp.arange(L, dtype=jnp.int32)

    def fn(b, g, r, li):
        return build_histograms(b, g, r, li, num_bins=B,
                                hist_dtype=hist_dtype, impl=impl,
                                block_rows=R)
    compiled = jax.jit(fn).lower(bins, gh, rl, lids).compile()
    ca = _cost_dict(compiled)
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0))}


# ----------------------------------------------------------------------
# Instruction → stage maps (the road from a device event to its source)

def module_name(hlo_text: str) -> str:
    m = _MODULE_RE.search(hlo_text or "")
    return m.group(1) if m else ""


class StageMap(NamedTuple):
    """What ``instruction_phase_map`` knows about one compiled module."""
    module: str
    stages: Dict[str, str]    # instruction name -> canonical stage
    scopes: Dict[str, str]    # instruction name -> its op_name path
    mixed_fusions: int        # fusions whose fused instructions disagree


class _Instr(NamedTuple):
    op: Any                   # hlo_walk.HloOp
    comp: str                 # enclosing computation
    callee: Optional[str]     # computation a fusion/call/while runs
    operands: Tuple[str, ...]


_OPERAND_RE = re.compile(r"%([\w.-]+)")


def _instructions(hlo_text: str) -> List[_Instr]:
    comp = ""
    rows: List[_Instr] = []
    for line in (hlo_text or "").splitlines():
        mc = _COMP_RE.match(line)
        if mc and "= " not in line.split("{")[0]:
            comp = mc.group(1)
            continue
        parsed = parse_all_ops(line)
        if not parsed:
            continue
        op = parsed[0]
        body = line.split(", metadata=", 1)[0]
        calls = _CALLS_RE.findall(body)
        rest = body.partition(" " + op.opcode + "(")[2]
        rows.append(_Instr(op, comp, calls[-1] if calls else None,
                           tuple(_OPERAND_RE.findall(rest))))
    return rows


def _resolved_phases(hlo_text: str, rows: Optional[List[_Instr]] = None):
    """[(HloOp, computation, stage-or-None)] and the number of mixed
    fusions. An instruction's stage is the deepest canonical name on its
    own ``op_name`` path. A fusion is judged by what it fuses: where its
    fused instructions agree that is the stage (it is also its root's),
    where they disagree the stage holding most of them, and such fusions
    are counted. What the compiler added without metadata (copies for a
    layout, loop-carry plumbing) takes the stage of the instruction that
    produces its operand, else of the one that uses its result (seen
    through tuples and bitcasts), else of
    the instruction that runs its computation (the ``while``, the
    fusion): so a body op is never worse off than its loop."""
    if rows is None:
        rows = _instructions(hlo_text)
    own: Dict[str, Optional[str]] = {}
    votes: Dict[str, Dict[str, int]] = {}
    for r in rows:
        ph = stage_of_path(r.op.op_name)
        own[r.op.name] = ph
        if ph is not None and r.op.opcode not in _NOOP_OPCODES:
            v = votes.setdefault(r.comp, {})
            v[ph] = v.get(ph, 0) + 1
    mixed = 0
    caller_stage: Dict[str, Optional[str]] = {}
    for r in rows:
        if r.op.opcode == "fusion" and r.callee in votes:
            v = votes[r.callee]
            if len(v) > 1:
                mixed += 1
            top = max(v.values())
            best = [k for k, n in v.items() if n == top]
            own[r.op.name] = (own[r.op.name] if own[r.op.name] in best
                              else sorted(best)[0])
    # producers, then users, then the caller: two sweeps settle chains
    # like copy-start -> copy-done -> user
    users: Dict[str, List[str]] = {}
    noop = {r.op.name for r in rows if r.op.opcode in _NOOP_OPCODES}
    for r in rows:
        for o in r.operands:
            users.setdefault(o, []).append(r.op.name)

    def real_users(name, depth=3):
        """Users, seen through tuples and bitcasts: what the compiler
        prefetches for a nested loop reaches it through an operand
        tuple."""
        for u in users.get(name, ()):
            if u in noop and depth:
                yield from real_users(u, depth - 1)
            else:
                yield u

    for sweep in (rows, rows[::-1]):
        for r in sweep:
            if own[r.op.name] is not None or r.op.opcode in _NOOP_OPCODES:
                continue
            near = ([own.get(o) for o in r.operands]
                    + [own.get(u) for u in real_users(r.op.name)])
            own[r.op.name] = next((p for p in near if p is not None), None)
    for r in rows:
        if r.callee is not None:
            caller_stage.setdefault(r.callee, own[r.op.name])
    changed = True
    while changed:      # nested callees inherit through their callers
        changed = False
        for r in rows:
            if own[r.op.name] is None:
                ph = caller_stage.get(r.comp)
                if ph is not None:
                    own[r.op.name] = ph
                    changed = True
            if r.callee is not None and caller_stage.get(r.callee) is None \
                    and own[r.op.name] is not None:
                caller_stage[r.callee] = own[r.op.name]
                changed = True
    return [(r.op, r.comp, own[r.op.name]) for r in rows], mixed


class StagedOp(NamedTuple):
    """One instruction of a compiled module as :func:`staged_ops` reads it."""
    op: Any                   # hlo_walk.HloOp
    computation: str
    stage: Optional[str]      # canonical stage, resolved as the stage map's
    in_loop: bool             # its computation runs inside a ``while``


def staged_ops(hlo_text: str) -> List[StagedOp]:
    """Every instruction of the module with its computation, its stage (as
    :func:`instruction_phase_map` resolves it) and whether it runs inside
    a ``while`` (directly, or in a computation a loop body calls): one
    parse of the text, for readers that count instructions by stage and
    by how often they run (``parallel/comms.plan_counters``)."""
    rows = _instructions(hlo_text)
    loops = {r.callee for r in rows if r.op.opcode == "while"}
    called_from: Dict[str, str] = {}
    for r in rows:
        if r.callee is not None:
            called_from.setdefault(r.callee, r.comp)

    def in_loop(comp):
        seen = set()
        while comp is not None and comp not in seen:
            if comp in loops:
                return True
            seen.add(comp)
            comp = called_from.get(comp)
        return False

    inside = {c: in_loop(c) for c in {r.comp for r in rows}}
    return [StagedOp(op, comp, stage, inside[comp])
            for op, comp, stage in _resolved_phases(hlo_text, rows)[0]]


def instruction_phase_map(hlo_text: str) -> StageMap:
    """The lookup table from an instruction of the compiled module —
    entry computation, ``while`` bodies and fusions alike — to the
    deepest canonical stage on its ``op_name`` path (see
    :func:`_resolved_phases` for fusions and unannotated plumbing).
    A device event names its instruction and nothing else, so this map
    is the only road from the event to a source scope."""
    stages: Dict[str, str] = {}
    scopes: Dict[str, str] = {}
    resolved, mixed = _resolved_phases(hlo_text)
    for op, _comp, ph in resolved:
        if not op.name:
            continue
        if ph is not None:
            stages[op.name] = ph
        if op.op_name:
            scopes[op.name] = op.op_name
    return StageMap(module_name(hlo_text), stages, scopes, mixed)


# ----------------------------------------------------------------------
# The trainer's own compiled step, and its map

def fused_compiled(bst, *, force: bool = True):
    """The trainer's own compiled fused step (donation flags and all),
    or None when the fused gate pins the legacy driver. ``force=False``
    refuses to trigger a fresh trace/compile — the mode for calls off
    the training thread, where ``_fused_step_entry``'s trace-time
    attribute rebinding must not race a concurrent dispatch."""
    from ..analysis.doctor import _fused_trace_args, _pin_fused
    gb = getattr(bst, "_gbdt", None) or bst
    with _pin_fused(True):
        reason = gb._fused_gate_reason()
    if reason:
        return None
    if gb._fused_jit is None:
        if not force:
            return None
        gb._fused_dispatch()
        gb.sync()
    args = _fused_trace_args(gb)
    return gb._fused_jit.lower(*args).compile()


def booster_phase_maps(bst, compiled=None, *,
                       force: bool = True) -> Dict[str, "StageMap"]:
    """Phase maps for a trained booster's staged programs (today: the
    fused step — the one whose CPU executor events need the lookup)."""
    if compiled is None:
        try:
            compiled = fused_compiled(bst, force=force)
        except Exception:  # noqa: BLE001 — maps are best-effort
            compiled = None
    if compiled is None:
        return {}
    sm = instruction_phase_map(compiled.as_text())
    return {sm.module: sm} if sm.stages else {}
