"""What a compiled program is made of, read from the program itself.

The instruction→stage map (`instruction_phase_map`): every instruction
of a compiled module under the canonical stage of ``phases.py`` whose
``jax.named_scope`` is deepest on its ``op_name`` metadata (parsed by
``analysis/hlo_walk.py``). A device event carries only
``{hlo_module, hlo_op}``, so this map is the road from an event to a
source line; the trace parser (``xprof.py``) and the benchmark's stage
readers attribute device time through it.

Beside it a stage's work (`stage_work`): what each stage worked through
over some trees, counted from the round log and the step's shape in the
units of ``phases.STAGE_WORK``, so that a stage's device seconds have a
count to be divided by.
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from .. import phases as PHS
from ..analysis.hlo_walk import parse_all_ops
from .xprof import stage_of_path

__all__ = ["instruction_phase_map", "StageMap", "module_name",
           "fused_compiled", "booster_phase_maps", "staged_ops",
           "stage_work"]


_MODULE_RE = re.compile(r"^HloModule\s+([^\s,]+)", re.MULTILINE)
_COMP_RE = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.-]+)\s*\(.*\)\s*->"
                      r"\s*.+\{\s*$")
_CALLS_RE = re.compile(r"\b(?:calls|to_apply|body|condition)="
                       r"%?([\w.-]+)")
# a conditional's branches (lax.cond: the grow loop's route of a round)
_BRANCHES_RE = re.compile(r"\b(?:branch_computations=\{([^}]*)\}|"
                          r"(?:true|false)_computation=%?([\w.-]+))")

# opcodes that never execute (metadata / plumbing) — excluded from the
# per-phase op/byte attribution so it reflects real work
_NOOP_OPCODES = frozenset({"parameter", "constant", "tuple",
                           "get-tuple-element", "bitcast"})


def _ceil_to(x, m: int):
    return -(-x // m) * m


def stage_work(shape: Dict[str, int], log: Sequence[Any], *,
               plan_bytes: Optional[Dict[str, Any]] = None,
               pair_slots: int = 0,
               fullest: bool = False) -> Dict[str, Tuple[float, str]]:
    """``{stage: (count, unit)}``: what each stage of the fused step worked
    through over the trees of ``log`` (``GBDT.round_log`` records), from
    the round log and the step's shape (``phases.STEP_SHAPE`` fields,
    ``shape``) alone, in the units ``phases.STAGE_WORK`` fixes. Counted
    at the boundaries of the stage scopes, so that a stage's device
    seconds over its count is what one unit costs:

    - ``hist_gather``: stream positions gathered, ``sum(stream_rows)``
      over the rounds that streamed (not the dense ones, which gather
      nothing: ``RoundLog.dense``);
    - ``hist_relayout``: elements re-laid, positions x stored columns,
      plus the root's rows x stored columns a tree, plus a dense round's
      one leaf row over the resident operands' lanes (the rows up to a
      multiple of both row blocks);
    - ``hist_kernel``: one-hot elements sent through the MXU: the rows
      the kernel's grid steps cover (a round's live rows rounded up to
      the plan's row block, all rows so rounded in a dense round, the
      root's padded rows a tree) x chunks x feature chunk x padded bins.
      Padding of bins, features and the row block is counted (the pass
      pays for it); positions of a chunk past the last live row block
      are not (the kernel skips them);
    - ``compact``: elements sorted, rows x the rounds that streamed,
      where the step compacts; ``apply``, ``count``: row passes, rounds
      x rows each;
    - ``find``, ``subtract``: lattice positions scanned, rounds x slots x
      positions a slot; ``unbundle`` the same plus the root's (its scope
      is open at the root too); ``root_pass``: the root's scan, trees x
      slots x positions a slot;
    - ``update``, ``grads``: rows x trees; ``rank_pairs``: a ranking
      objective's ``pair_slots`` x trees;
    - ``hist_merge``, ``winner_sync``: bytes one chip puts on the wire,
      rounds x the plan's bytes a round plus trees x its bytes a tree
      (``plan_bytes``: the ``phases.PLAN_COUNTERS`` of the step);
    - linear leaves, from the records' ``linear`` entries
      (``GBDT._linear_work``): ``linear_gram`` the Gram's multiply-adds,
      ``sum rows_l ((k_l + 1)(k_l + 2) / 2 + k_l + 1)``; ``linear_solve``
      the path features ``sum k_l``; ``linear_update`` rows x linear
      trees.

    A round is one that built for at least one leaf. Under the
    class-batched multiclass build a record (a tree) carries its
    iteration's ``batched_rounds``: the row-wide stages (``apply``,
    ``count``, ``compact``, ``find``, ``subtract``, ``unbundle``) run for
    every class in every round of the batched loop, so a tree counts that
    many rounds there, while the stream's stages count a class's own
    rounds (its stream runs alone and stops at its own live rows). One
    root pass histograms ``shape_classes`` trees (the class-root kernel's
    K; 1 where each tree has its own): a tree counts that share of one
    root pass in ``hist_kernel`` and ``hist_relayout``; the shape is read
    with 1 where it has no ``shape_classes``. All counts are ONE
    device's. Under a row-sharded plan the log is ``[n_shards, rounds]``:
    the counts are the mean over the shards (what the chips' mean seconds
    go with), or the fullest shard's with ``fullest`` (the shard a round
    waits for)."""
    rows = shape[PHS.SHAPE_ROWS]
    cols = shape[PHS.SHAPE_STORED_COLUMNS]
    blk = shape[PHS.SHAPE_KERNEL_ROW_BLOCK]
    a_row = (shape[PHS.SHAPE_KERNEL_CHUNKS]
             * shape[PHS.SHAPE_KERNEL_FEATURE_CHUNK]
             * shape[PHS.SHAPE_KERNEL_PADDED_BINS])
    scan = shape[PHS.SHAPE_SLOTS] * shape[PHS.SHAPE_SEARCH_POSITIONS]
    trees = len(log)
    roots = trees / shape.get(PHS.SHAPE_CLASSES, 1)
    kernel_rows = _ceil_to(rows, blk)
    lanes = int(np.lcm(blk, shape[PHS.SHAPE_KERNEL_ROOT_ROW_BLOCK]))
    rounds, positions, covered = 0, 0.0, 0.0
    dense_rounds, streamed_rounds = 0.0, 0.0
    linear = [lin for lin in (getattr(rec, "linear", None) for rec in log)
              if lin is not None]
    for rec in log:
        built = np.asarray(rec.leaves) > 0
        batched = getattr(rec, "batched_rounds", None)
        rounds += int(built.sum()) if batched is None else int(batched)
        # [shards, rounds] (one shard where the log keeps no shard axis)
        live = np.asarray(rec.rows).reshape(-1, built.shape[-1])[:, built]
        stream = np.asarray(rec.stream_rows).reshape(
            -1, built.shape[-1])[:, built]
        # a log from before the dense route has no ``dense``
        dense = getattr(rec, "dense", None)
        dense = (np.zeros(live.shape, bool) if dense is None else
                 np.asarray(dense).reshape(-1, built.shape[-1])[:, built]
                 > 0)
        over = np.max if fullest else np.mean
        positions += float(over(np.where(dense, 0, stream).sum(
            axis=1, dtype=np.int64)))
        covered += float(over(np.where(
            dense, kernel_rows, _ceil_to(live.astype(np.int64), blk))
            .sum(axis=1)))
        dense_rounds += float(over(dense.sum(axis=1)))
        streamed_rounds += (float(over((~dense).sum(axis=1)))
                            if batched is None else int(batched))
    root_rows = _ceil_to(rows, shape[PHS.SHAPE_KERNEL_ROOT_ROW_BLOCK])
    lanes_rows = _ceil_to(rows, lanes)
    counts = {
        PHS.HIST_GATHER: positions,
        PHS.HIST_RELAYOUT: ((positions + roots * rows) * cols
                            + dense_rounds * lanes_rows),
        PHS.HIST_KERNEL: (covered + roots * root_rows) * a_row,
        PHS.COMPACT: (streamed_rounds * rows
                      * shape[PHS.SHAPE_STREAM_COMPACTED]),
        PHS.APPLY: rounds * rows,
        PHS.COUNT: rounds * rows,
        PHS.FIND: rounds * scan,
        PHS.SUBTRACT: rounds * scan,
        PHS.UNBUNDLE: (rounds + trees) * scan,
        PHS.ROOT_PASS: trees * scan,
        PHS.UPDATE: trees * rows,
        PHS.GRADS: trees * rows,
        PHS.RANK_PAIRS: trees * int(pair_slots),
        PHS.LINEAR_GRAM: sum(lin[0] for lin in linear),
        PHS.LINEAR_SOLVE: sum(lin[1] for lin in linear),
        PHS.LINEAR_UPDATE: len(linear) * rows,
    }
    a_round = (plan_bytes or {}).get(PHS.PLAN_ROUND_BYTES_BY_STAGE) or {}
    a_tree = (plan_bytes or {}).get(PHS.PLAN_TREE_BYTES_BY_STAGE) or {}
    for stage in PHS.COLLECTIVE_PHASES:
        counts[stage] = (rounds * a_round.get(stage, 0)
                         + trees * a_tree.get(stage, 0))
    return {stage: (count, PHS.STAGE_WORK[stage])
            for stage, count in counts.items() if count}


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
    branches: Tuple[str, ...] = ()   # a conditional's computations

    @property
    def called(self) -> Tuple[str, ...]:
        """Every computation this instruction runs."""
        return ((self.callee,) if self.callee else ()) + self.branches


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
        branches = tuple(
            b.strip().lstrip("%") for group, one in _BRANCHES_RE.findall(body)
            for b in (group.split(",") if group else [one]) if b.strip())
        rows.append(_Instr(op, comp, calls[-1] if calls else None,
                           tuple(_OPERAND_RE.findall(rest)), branches))
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
        for c in r.called:
            caller_stage.setdefault(c, own[r.op.name])
    changed = True
    while changed:      # nested callees inherit through their callers
        changed = False
        for r in rows:
            if own[r.op.name] is None:
                ph = caller_stage.get(r.comp)
                if ph is not None:
                    own[r.op.name] = ph
                    changed = True
            for c in r.called:
                if caller_stage.get(c) is None \
                        and own[r.op.name] is not None:
                    caller_stage[c] = own[r.op.name]
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
        for c in r.called:
            called_from.setdefault(c, r.comp)

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
