"""The data-parallel cell's program side on a four-device CPU mesh: one
compile of the fused step under a parallel plan, the plan's counters, the
sharded tree against the serial tree and the plain reference; and, run
again here so that tier-1 holds them, the reference's own cases from
``benchmarks/tests/test_dp_job.py`` (rounding boundaries, the float8 and
coarser-program controls, the four readers, the job's refusal)."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(ROOT, "benchmarks", "tests"),
          os.path.join(ROOT, "benchmarks")):
    if p not in sys.path:
        sys.path.insert(0, p)

import test_dp_job as dp  # noqa: E402
from test_dp_job import (  # noqa: E402,F401  (collected here too)
    four_chips, notes, tiny_root,
    test_a_hessian_near_a_rounding_boundary_passes_on_either_side,
    test_the_other_neighbour_is_admitted_inside_the_margin_only,
    test_a_leaf_of_a_few_rows_may_carry_its_parents_float32_roundings,
    test_chain_counts_are_a_columns_rows_down_the_subtraction_chain,
    test_float8_addends_and_a_coarser_program_read_incorrect,
    test_a_reader_with_nothing_to_read_returns_nothing,
    test_a_program_without_the_counters_is_refused_before_any_data,
    test_base_rate_lies_far_from_every_rounding_boundary,
    test_dp_cell_runs_as_a_rehearsal)

import jax  # noqa: E402
import jax.monitoring  # noqa: E402

import lightgbm_tpu as lgb  # noqa: E402
from lightgbm_tpu import phases, profiler  # noqa: E402
from lightgbm_tpu.parallel import comms  # noqa: E402
from lightgbm_tpu.telemetry import costmodel  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@pytest.fixture()
def compiles():
    seen = []

    def on(event, duration, **_):
        if event == COMPILE_EVENT:
            seen.append(duration)
    jax.monitoring.register_event_duration_secs_listener(on)
    yield seen
    jax.monitoring.unregister_event_duration_listener(on)


def _booster(learner, x, y, **more):
    params = dict(dp.PARAMS, tree_learner=learner, **more)
    return lgb.Booster(params, lgb.Dataset(
        np.ascontiguousarray(x.T), label=y, params=params))


@pytest.mark.parametrize("learner", ["data", "voting", "feature"])
def test_the_step_tree_0_compiles_is_the_step_every_tree_runs(
        learner, four_chips, compiles):
    x, y = dp.built_case(6100, 400)
    bst = _booster(learner, x, y)
    bst.update(defer=True)
    gb = bst._gbdt
    first = len(compiles)
    assert first >= 1 and gb.fused_reason == ""
    # the scores are handed back as the plan places them when they go in
    put = gb.plan.shard_scores(np.zeros(gb.scores.shape, np.float32))
    placed = gb.scores.sharding
    assert put.committed and put.sharding == placed
    for _ in range(2):
        bst.update(defer=True)
    assert len(compiles) == first
    assert gb.scores.sharding == placed
    # ... and the text read for the counters came from that one compile
    ready = profiler.recorder.spans("gbdt.step_ready")[-1]
    assert ready.fields[phases.PLAN_SHARDS] == 4
    assert ready.fields["backend_compile_s"] > 0
    costmodel.fused_compiled(bst, force=False)
    assert len(compiles) == first


def test_plan_counters_are_the_compiled_steps_own_collectives(four_chips):
    x, y = dp.built_case(6100, 400)
    bst = _booster("data", x, y)
    bst.update(defer=True)
    gb = bst._gbdt
    text = costmodel.fused_compiled(bst, force=False).as_text()
    ops = comms.parse_collectives(text)
    rows = gb.train_dd.r_pad // 4
    got = comms.plan_counters(text, 4, rows)
    assert set(got) == set(phases.PLAN_COUNTERS)
    in_round = [o for o in ops if "/while/body/" in o.op_name]
    assert got[phases.PLAN_SHARDS] == 4
    assert got[phases.PLAN_ROWS_PER_SHARD] == rows
    kinds = got[phases.PLAN_COLLECTIVES_PER_ROUND]
    assert sum(kinds.values()) == len(in_round)
    assert kinds == {"all-reduce": 6, "reduce-scatter": 1}
    by_round = got[phases.PLAN_ROUND_BYTES_BY_STAGE]
    by_tree = got[phases.PLAN_TREE_BYTES_BY_STAGE]
    assert by_round[phases.HIST_MERGE] == sum(
        o.wire_bytes(4) for o in in_round if o.is_hist) > 0
    assert by_round[phases.WINNER_SYNC] == sum(
        o.wire_bytes(4) for o in in_round if o.is_winner_sync) > 0
    assert sum(by_round.values()) + sum(by_tree.values()) == sum(
        o.wire_bytes(4) for o in ops)
    # every collective has a stage: the round's count rides under ``count``,
    # the NaN guard's under ``update``
    assert "" not in by_round and "" not in by_tree
    assert set(by_round) == {phases.HIST_MERGE, phases.WINNER_SYNC,
                             phases.COUNT}
    assert set(by_tree) >= {phases.HIST_MERGE, phases.WINNER_SYNC}
    # the span carries the same numbers, under the names the readers use
    fields = profiler.recorder.spans("gbdt.step_ready")[-1].fields
    assert {k: fields[k] for k in phases.PLAN_COUNTERS} == got
    assert set(dp.PLAN) <= set(phases.PLAN_COUNTERS)
    # a serial trainer has no plan and no such fields
    serial = _booster("serial", x, y)
    serial.update(defer=True)
    assert not set(phases.PLAN_COUNTERS) & set(
        profiler.recorder.spans("gbdt.step_ready")[-1].fields)


def test_the_sharded_tree_is_the_serial_tree_and_the_references(four_chips):
    x, y = dp.built_case(40900, 1407, seed=11)
    text, ubs, bins_cm, shard_rows, gb = dp.program_tree(x, y, four_chips)
    assert gb.plan.hist_merge == "reduce_scatter"
    serial = _booster("serial", x, y)
    serial.update()
    one = dp.ref.parse_tree(serial.model_to_string(), 0)
    four = dp.ref.parse_tree(text, 0)
    for key in ("split_feature", "threshold", "left_child", "right_child",
                "leaf_count"):
        assert np.array_equal(one[key], four[key]), key
    np.testing.assert_allclose(four["leaf_value"], one["leaf_value"],
                               rtol=1e-5, atol=1e-7)
    # the plain reference, rows on four shards and on one, agrees with both
    for rows_a_shard in (shard_rows, len(y)):
        rep = dp.sref.check_first_tree(text, ubs, bins_cm, y, dp.PARAMS,
                                       rows_a_shard)
        assert rep["ok"] and rep["roundings_tried"] == 1, rep
    rep = dp.sref.check_first_tree(serial.model_to_string(), ubs, bins_cm, y,
                                   dp.PARAMS, shard_rows)
    assert rep["ok"] and rep["shards"] == 4


def test_the_counters_read_a_v5e_hosts_text():
    """The collectives of the tree build as the TPU's compiler leaves them
    (``tests/golden/dp_step_v5e_2x2_collectives.hlo.txt``: the lines of an
    ahead-of-time compile for a described v5e 2x2, 67 columns, 255 bins,
    that hold or neighbour a collective): tiled layouts in every shape,
    the round's reduce-scatter as an ``all-reduce-scatter`` fusion around
    an all-reduce that carries no op_name, the root's merge a plain
    all-reduce. PR 35's first chip run read zeros here."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "golden",
                        "dp_step_v5e_2x2_collectives.hlo.txt")
    with open(path) as f:
        text = f.read()
    ops = comms.parse_collectives(text)
    assert len(ops) == 13 and {o.kind for o in ops} == {"all-reduce"}
    assert max(o.out_bytes for o in ops) == 32 * 68 * 255 * 3 * 4
    got = comms.plan_counters(text, 4, 13281280)
    assert got[phases.PLAN_COLLECTIVES_PER_ROUND] == {
        "all-reduce": 6, "reduce-scatter": 1}
    # 4,352 lattice rows a chip x 16 slots x 3 sums x 4 B, sent to 3 chips
    assert got[phases.PLAN_ROUND_BYTES_BY_STAGE] == {
        "count": 192, "hist_merge": 3 * 4352 * 16 * 3 * 4, "winner_sync": 4224}
    assert got[phases.PLAN_TREE_BYTES_BY_STAGE] == {
        "hist_merge": 9987840, "winner_sync": 4242}


CPU_TEXT = """\
HloModule jit_step, entry_computation_layout={(f32[8,4]{1,0})->f32[8,4]{1,0}}

ENTRY %main (p: f32[8,4]) -> f32[8,4] {
  %p = f32[8,4]{1,0} parameter(0)
  %ar = f32[8,4]{1,0} all-reduce(f32[8,4]{1,0} %p), replica_groups={{0,1,2,3}}, to_apply=%add, metadata={op_name="jit(step)/hist_merge/psum"}
  %rs-start = ((f32[8,4]{1,0}), f32[2,4]{1,0}) reduce-scatter-start(f32[8,4]{1,0} %ar), dimensions={0}, metadata={op_name="jit(step)/hist_merge/psum_scatter"}
  %rs-done = f32[2,4]{1,0} reduce-scatter-done(((f32[8,4]{1,0}), f32[2,4]{1,0}) %rs-start)
  ROOT %c = f32[8,4]{1,0} copy(f32[8,4]{1,0} %ar)
}
"""
# the same module as a TPU's compiler prints it: tiled layouts, memory spaces
TPU_TEXT = CPU_TEXT.replace("]{1,0}", "]{1,0:T(8,128)S(1)}")


@pytest.mark.parametrize("text", [CPU_TEXT, TPU_TEXT], ids=["cpu", "tpu"])
def test_parse_ops_reads_a_cpus_text_and_a_tpus_alike(text):
    from lightgbm_tpu.analysis import hlo_walk
    ops = hlo_walk.parse_ops(text, ("all-reduce", "reduce-scatter"))
    assert [(o.opcode, o.out_bytes) for o in ops] == [
        ("all-reduce", 8 * 4 * 4), ("reduce-scatter", (8 + 2) * 4 * 4)]
    assert [o.op_name for o in ops] == ["jit(step)/hist_merge/psum",
                                        "jit(step)/hist_merge/psum_scatter"]
    assert ops[0].shapes == hlo_walk.parse_ops(
        CPU_TEXT, ("all-reduce",))[0].shapes
    # the halves that only wait are skipped unless asked for
    assert len(hlo_walk.parse_ops(text, ("reduce-scatter",),
                                  skip_done=False)) == 1
    staged = costmodel.staged_ops(text)
    assert [s.stage for s in staged if s.op.opcode == "all-reduce"] == [
        phases.HIST_MERGE]
    assert not any(s.in_loop for s in staged)
