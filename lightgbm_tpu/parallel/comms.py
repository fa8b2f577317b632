"""Collective-traffic accounting for the parallel tree programs.

The reference's distributed learners budget communication explicitly
(PV-Tree, arxiv 1611.01276, exists because the O(F*B) histogram merge
dominates DCN time; the GPU-scaled XGBoost study arxiv 1806.11248 makes
the same point for AllReduce). Under XLA the collectives are implicit in
the compiled program, so this module makes them auditable again: it
walks the compiled HLO of a tree-build (or fused-step) program, extracts
every collective op with its payload bytes, and attributes histogram
traffic via the ``hist_merge`` / ``winner_sync`` op-name phases the
builders emit (ops/histogram.merge_histograms,
tree_builder._sync_best).

Used by ``scripts/audit_collectives.py`` (CI gate: the reduce-scatter
program must emit no full-histogram all-reduce and move <= (1/n + eps) x
the allreduce baseline's histogram bytes) and by
``tests/test_comm_audit.py`` (the fast in-suite form).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..analysis.hlo_walk import (COLLECTIVE_KINDS, base_opcode,
                                 lower_hlo as _walk_lower_hlo,
                                 parse_collective_ops)
from .. import phases
from ..phases import HIST_MERGE, WINNER_SYNC

__all__ = ["CollectiveOp", "CommReport", "parse_collectives",
           "plan_counters",
           "lower_hlo", "audit_fn", "audit_tree_program", "audit_plans",
           "hist_bytes_per_tree", "render_table", "COLLECTIVE_KINDS"]


@dataclasses.dataclass(frozen=True)
class CollectiveOp:
    """One collective in the compiled program."""
    kind: str                       # all-reduce | reduce-scatter | ...
    shapes: Tuple[Tuple[str, Tuple[int, ...]], ...]
    out_bytes: int                  # bytes of the op's RESULT per chip
    op_name: str                    # HLO metadata (named_scope prefixes)

    @property
    def is_hist(self) -> bool:
        """Histogram-merge traffic (tagged by merge_histograms)."""
        return HIST_MERGE in self.op_name

    @property
    def is_winner_sync(self) -> bool:
        """SplitInfo-sized winner merge (_sync_best)."""
        return WINNER_SYNC in self.op_name

    def wire_bytes(self, n: int) -> int:
        """Per-chip wire-traffic estimate under ring algorithms:
        all-reduce moves 2(n-1)/n x payload, reduce-scatter and
        all-gather (n-1)/n x payload (payload = the full logical
        buffer; a reduce-scatter's RESULT is payload/n)."""
        if n <= 1:
            return 0
        if self.kind == "all-reduce":
            return int(2 * (n - 1) / n * self.out_bytes)
        if self.kind == "reduce-scatter":
            return int((n - 1) * self.out_bytes)       # out = payload/n
        if self.kind == "all-gather":
            return int((n - 1) / n * self.out_bytes)
        return self.out_bytes


def parse_collectives(hlo_text: str) -> List[CollectiveOp]:
    """Extract every collective op from compiled-HLO text (the shared
    walker, ``analysis/hlo_walk.py``, owns the parsing; this wraps its
    generic ops into the comms accounting type)."""
    return [CollectiveOp(kind=o.opcode, shapes=o.shapes,
                         out_bytes=o.out_bytes, op_name=o.op_name)
            for o in parse_collective_ops(hlo_text)]


# The TPU compiler turns a small reduce-scatter into a fusion named
# ``all-reduce-scatter`` that holds an all-reduce of the whole buffer and
# the slice of this chip's part: on the wire it is a reduce-scatter
_FUSED_SCATTER = "all-reduce-scatter"


def plan_counters(compiled, num_shards: int,
                  rows_per_shard: int) -> Dict[str, object]:
    """The counters of a parallel plan (``phases.PLAN_COUNTERS``) from
    its compiled fused step, or the step's HLO text: every collective
    instruction of the module (the ops :func:`parse_collectives` finds),
    told apart by whether it runs inside a ``while`` (the grow loop is
    the one loop of the step that holds collectives: once a round) or
    outside any (once a tree), and by its stage as the stage map
    resolves it (``costmodel.staged_ops``: the deepest canonical name on
    its op_name path, or for an instruction the compiler left without
    one, its neighbours'; a device event is attributed the same way);
    '' where neither gives one."""
    from ..telemetry.costmodel import staged_ops
    text = compiled if isinstance(compiled, str) else compiled.as_text()
    kinds: Dict[str, int] = {}
    per_round: Dict[str, int] = {}
    per_tree: Dict[str, int] = {}
    for op, comp, stage, in_round in staged_ops(text):
        kind = base_opcode(op.opcode)
        if kind not in COLLECTIVE_KINDS:
            continue
        out_bytes = op.out_bytes
        if kind == "all-reduce" and comp.startswith(_FUSED_SCATTER):
            kind, out_bytes = "reduce-scatter", out_bytes // num_shards
        wire = CollectiveOp(kind, op.shapes, out_bytes,
                            op.op_name).wire_bytes(num_shards)
        by_stage = per_tree
        if in_round:
            by_stage = per_round
            kinds[kind] = kinds.get(kind, 0) + 1
        by_stage[stage or ""] = by_stage.get(stage or "", 0) + wire
    return {
        phases.PLAN_SHARDS: int(num_shards),
        phases.PLAN_ROWS_PER_SHARD: int(rows_per_shard),
        phases.PLAN_COLLECTIVES_PER_ROUND: dict(sorted(kinds.items())),
        phases.PLAN_ROUND_BYTES_BY_STAGE: dict(sorted(per_round.items())),
        phases.PLAN_TREE_BYTES_BY_STAGE: dict(sorted(per_tree.items())),
    }


@dataclasses.dataclass
class CommReport:
    """Collectives of one compiled program, with per-kind accounting."""
    label: str
    n_devices: int
    ops: List[CollectiveOp]

    def count(self, kind: Optional[str] = None) -> int:
        return sum(1 for o in self.ops
                   if kind is None or o.kind == kind)

    def bytes_by_kind(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for o in self.ops:
            out[o.kind] = out.get(o.kind, 0) + o.out_bytes
        return out

    @property
    def hist_ops(self) -> List[CollectiveOp]:
        return [o for o in self.ops if o.is_hist]

    @property
    def hist_result_bytes(self) -> int:
        """Per-chip bytes of merged histogram MATERIALIZED per round
        set (root + loop body): the 1/n economics of reduce-scatter
        show up here directly."""
        return sum(o.out_bytes for o in self.hist_ops)

    @property
    def hist_wire_bytes(self) -> int:
        return sum(o.wire_bytes(self.n_devices) for o in self.hist_ops)

    def full_hist_allreduces(self, min_bytes: int) -> List[CollectiveOp]:
        """All-reduce ops carrying a full-histogram-sized payload
        (>= min_bytes — pass one slot's F*B*CH*itemsize)."""
        return [o for o in self.ops
                if o.kind == "all-reduce" and o.out_bytes >= min_bytes]


def lower_hlo(fn, *args) -> str:
    """Compiled (post-SPMD) HLO text of ``jit(fn)(*args)``. Nested jits
    (the plans' inner pjits) inline into the one lowered module, so the
    collectives of the whole tree build are visible."""
    return _walk_lower_hlo(fn, *args)


def audit_fn(fn, *args, label: str = "program",
             n_devices: Optional[int] = None) -> CommReport:
    import jax
    n = n_devices if n_devices is not None else len(jax.devices())
    return CommReport(label=label, n_devices=n,
                      ops=parse_collectives(lower_hlo(fn, *args)))


def _synthetic_inputs(R: int, F: int, B: int, seed: int = 0):
    import jax.numpy as jnp
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, B, size=(R, F)).astype(np.uint8)
    g = rng.normal(size=R).astype(np.float32)
    h = rng.uniform(0.5, 1.5, size=R).astype(np.float32)
    gh = np.stack([g, h, np.ones(R, np.float32)], axis=1)
    meta = (jnp.full((F,), B, jnp.int32), jnp.full((F,), -1, jnp.int32),
            jnp.zeros((F,), bool), jnp.ones((F,), bool))
    return bins, gh, np.zeros(R, np.int32), meta


def audit_tree_program(plan, *, R: int = 512, F: int = 16, B: int = 16,
                       num_leaves: int = 15, leaf_batch: int = 4,
                       label: Optional[str] = None,
                       hist_dtype: str = "float32",
                       **build_kw) -> CommReport:
    """Compile one tree build under ``plan`` on synthetic inputs and
    account its collectives."""
    from ..ops.split import SplitParams
    bins, gh, rl0, meta = _synthetic_inputs(R, F, B)
    rows_sharded = getattr(plan, "rows_sharded", True)
    block = R // plan.num_shards if rows_sharded else R
    kw = dict(num_leaves=num_leaves, leaf_batch=leaf_batch, max_depth=-1,
              num_bins=B, hist_dtype=hist_dtype, block_rows=block,
              split_params=SplitParams(min_data_in_leaf=2,
                                       min_sum_hessian_in_leaf=1e-3),
              **build_kw)
    args = (plan.shard_bins(bins), plan.shard_rows(gh),
            plan.shard_rows(rl0))

    def fn(b, g, rl):
        return plan.build_tree(b, g, rl, *meta, **kw)[0]
    if label is None:
        label = plan.parallel_mode
        if getattr(plan, "hist_merge", None):
            label += f"/{plan.hist_merge}"
    return audit_fn(fn, *args, label=label, n_devices=plan.num_shards)


def audit_plans(devices: Optional[Sequence] = None, *, R: int = 512,
                F: int = 16, B: int = 16,
                top_k: int = 4) -> Dict[str, CommReport]:
    """The standard per-plan audit set: data/voting under both merge
    modes, plus feature-parallel (which must emit ZERO histogram
    collectives — its slot histograms are feature-disjoint)."""
    from .data_parallel import (DataParallelPlan, FeatureParallelPlan,
                                VotingParallelPlan)
    reports = {}
    for hm in ("allreduce", "reduce_scatter"):
        reports[f"data/{hm}"] = audit_tree_program(
            DataParallelPlan(devices, hist_merge=hm), R=R, F=F, B=B)
        reports[f"voting/{hm}"] = audit_tree_program(
            VotingParallelPlan(devices, top_k=top_k, hist_merge=hm),
            R=R, F=F, B=B)
    reports["feature"] = audit_tree_program(
        FeatureParallelPlan(devices), R=R, F=F, B=B)
    return reports


def hist_bytes_per_tree(report: CommReport, num_leaves: int,
                        leaf_batch: int) -> int:
    """Per-chip histogram-merge bytes for one FULL tree: the compiled
    program carries each loop collective once; scale the loop-body ops
    by the round bound (max_rounds_for) and count the root merge once.
    The root merge is the op outside the while body — approximated as
    the largest hist op (the root histograms 2W slots; loop rounds
    merge the W smaller children)."""
    from ..boosting.tree_builder import max_rounds_for
    rounds = max_rounds_for(num_leaves,
                            max(1, min(leaf_batch, num_leaves - 1)))
    ops = sorted(report.hist_ops, key=lambda o: -o.out_bytes)
    if not ops:
        return 0
    root, loop = ops[0], ops[1:]
    return root.out_bytes + rounds * sum(o.out_bytes for o in loop)


def render_table(reports: Dict[str, CommReport]) -> str:
    """Per-plan collective table (README / CI output)."""
    rows = [f"{'plan':<22} {'collectives':>11} {'hist ops':>8} "
            f"{'hist kinds':<24} {'hist KiB/chip':>13} "
            f"{'wire KiB/chip':>13}"]
    for name, r in reports.items():
        kinds = ",".join(sorted({o.kind for o in r.hist_ops})) or "-"
        rows.append(
            f"{name:<22} {r.count():>11} {len(r.hist_ops):>8} "
            f"{kinds:<24} {r.hist_result_bytes / 1024:>13.1f} "
            f"{r.hist_wire_bytes / 1024:>13.1f}")
    return "\n".join(rows)
