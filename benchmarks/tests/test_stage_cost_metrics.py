"""Cases for the seven metrics that read what a unit of a stage's work
costs (a stage's device seconds over the program's own count of its work),
all on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_stage_cost_metrics.py -q
"""

import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness.manifest import Manifest  # noqa: E402

from lightgbm_tpu import phases  # noqa: E402
from lightgbm_tpu.telemetry import costmodel  # noqa: E402

THREE = ["msltr-rank-train", "criteo-dp-train", "allstate-efb-train"]
FIVE = ["higgs-train", "epsilon-train"] + THREE
# name: (unit, layer, cells)
METRICS = {
    "histwrap.gather_ns_per_position": ("ns", "hist wrapper", THREE),
    "kernels.hist_ps_per_onehot_element": ("ps", "kernels", FIVE),
    "builder.compact_ns_per_row_round": ("ns", "builder", THREE),
    "builder.apply_ns_per_row_round": ("ns", "builder", THREE),
    "split.search_ns_per_position": ("ns", "builder", THREE),
    "driver.update_ns_per_row": ("ns", "driver", THREE),
    "objective.grads_ns_per_row": ("ns", "objectives", THREE),
}

# a step of 1,000 rows a device, 10 stored columns of 16 bins, 12 features
# of 8 bins searched in 2 x 4 slots, a kernel plan of two chunks of
# 5 columns x 16 padded bins over row blocks of 128 (root: 256)
SHAPE = {
    phases.SHAPE_ROWS: 1000, phases.SHAPE_STORED_COLUMNS: 10,
    phases.SHAPE_STORED_BINS: 16, phases.SHAPE_SEARCH_POSITIONS: 96,
    phases.SHAPE_SLOTS: 8, phases.SHAPE_STREAM_CHUNK_ROWS: 256,
    phases.SHAPE_STREAM_COMPACTED: 1, phases.SHAPE_KERNEL_ROW_BLOCK: 128,
    phases.SHAPE_KERNEL_ROOT_ROW_BLOCK: 256,
    phases.SHAPE_KERNEL_FEATURE_CHUNK: 5, phases.SHAPE_KERNEL_CHUNKS: 2,
    phases.SHAPE_KERNEL_PADDED_BINS: 16, phases.SHAPE_KERNEL_LANES: 128,
    phases.SHAPE_ROUNDS_BOUND: 3}
# seconds of the traced window by stage, and the kernel's by its events
STAGE_S = {"hist_gather": 4.0e-3, "hist_kernel": 9.0e-3, "compact": 2.0e-3,
           "apply": 1.0e-3, "count": 0.5e-3, "find": 3.0e-3,
           "subtract": 0.25e-3, "unbundle": 0.75e-3, "update": 0.6e-3,
           "grads": 0.1e-3, "rank_scatter": 0.7e-3, "root_pass": 5.0e-3}
KERNEL_S = 8.0e-3


def _rounds(rows, leaves, stream, it=0):
    return SimpleNamespace(
        iteration=it, class_index=0, rows=np.asarray(rows, np.int32),
        leaves=np.asarray(leaves, np.int32),
        stream_rows=np.asarray(stream, np.int32))


# tree 0 is set-up; the window's two trees ran 2 + 1 = 3 rounds that
# built for a leaf, the second on two shards
LOG = [_rounds([1000, 1000, 0], [1, 1, 0], [1024, 1024, 0]),
       _rounds([400, 100, 0], [1, 2, 0], [512, 256, 0], 1),
       _rounds([[130, 0, 0], [300, 0, 0]], [1, 0, 0],
               [[256, 0, 0], [512, 0, 0]], 2)]


def _program(shape=SHAPE, log=LOG, **more):
    def stage_work(n=None, *, fullest=False):
        return costmodel.stage_work(shape, log[-n:] if n else log,
                                    fullest=fullest, **more)
    return SimpleNamespace(recorder=None, round_log=list(log),
                           step_shape=dict(shape), stage_work=stage_work)


def _run(program, stage_s=STAGE_S, kernel_s=KERNEL_S, trees=2):
    trace = None if kernel_s is None else SimpleNamespace(
        class_s={"kernel": kernel_s, "rowwise": 1.0}, root_kernel_s=[])
    # a shape that no reader may take anything from
    return SimpleNamespace(
        spans={}, counters={"trees": trees, "stage_s": stage_s},
        trace=trace, memory={}, shape={"rows": -1, "cols": -1, "bins": -1},
        device={"kind": "TPU v5 lite"}, notes={}, program=program)


def _read(name, run):
    return Manifest(ROOT).metric_reader(name).read(run)


# rounds 3; positions 512 + 256 + mean(256, 512) = 1152; rows the kernel's
# steps cover 512 + 128 + mean(256, 384) = 960, and the root's 1024 a tree
ROUNDS, POSITIONS, COVERED = 3, 1152.0, 960.0
HAND = {
    "histwrap.gather_ns_per_position": 1e9 * 4.0e-3 / POSITIONS,
    "kernels.hist_ps_per_onehot_element":
        1e12 * KERNEL_S / ((COVERED + 2 * 1024) * 2 * 5 * 16),
    "builder.compact_ns_per_row_round": 1e9 * 2.0e-3 / (ROUNDS * 1000),
    "builder.apply_ns_per_row_round": 1e9 * 1.5e-3 / (ROUNDS * 1000),
    "split.search_ns_per_position": 1e9 * 4.0e-3 / (ROUNDS * 8 * 96),
    "driver.update_ns_per_row": 1e9 * 0.6e-3 / (2 * 1000),
    "objective.grads_ns_per_row": 1e9 * 0.8e-3 / (2 * 1000),
}


def test_manifest_has_the_seven_entries_appended_for_their_cells():
    man = Manifest(ROOT)
    assert man.problems() == []
    tail = man.doc["per_layer"][-len(METRICS):]
    assert [m["name"] for m in tail] == list(METRICS)
    for m in tail:
        unit, layer, cells = METRICS[m["name"]]
        assert m == {"name": m["name"], "unit": unit, "better": "lower",
                     "source": "device_trace", "layer": layer,
                     "moves": "train_row_trees_per_s", "workloads": m[
                         "workloads"]}
        assert sorted(m["workloads"]) == sorted(cells)
    # none is a share: nothing here can read over 100% of anything
    assert not any("roofline" in n or "mfu" in n or n.endswith("_share")
                   for n in METRICS)


@pytest.mark.parametrize("name", list(METRICS))
def test_a_reader_gives_the_hand_arithmetic(name):
    run = _run(_program())
    assert _read(name, run) == pytest.approx(HAND[name], rel=1e-12)
    note = run.notes[name]
    assert note["trees"] == 2 and note["seconds"] > 0 and note["count"] > 0
    assert note["unit"] in set(phases.STAGE_WORK.values())
    assert HAND[name] == pytest.approx(
        (1e12 if name.startswith("kernels.") else 1e9)
        * note["seconds"] / note["count"])


@pytest.mark.parametrize("name", list(METRICS))
def test_a_reader_takes_no_shape_from_the_configuration(name):
    """Rows, columns, bins, slots and the kernel's plan are the program's:
    another step shape moves the reading, ``run.shape`` (all -1 here, and
    absent there) does not."""
    run = _run(_program())
    del run.shape
    assert _read(name, run) == pytest.approx(HAND[name], rel=1e-12)
    wider = dict(SHAPE, **{phases.SHAPE_ROWS: 2000,
                           phases.SHAPE_SEARCH_POSITIONS: 192,
                           phases.SHAPE_KERNEL_PADDED_BINS: 32})
    got = _read(name, _run(_program(shape=wider)))
    if name == "histwrap.gather_ns_per_position":
        assert got == pytest.approx(HAND[name])   # positions are the log's
    else:
        assert got < HAND[name] * 0.76


@pytest.mark.parametrize("name", list(METRICS))
def test_a_reader_with_nothing_to_read_returns_nothing(name):
    kernel = name.startswith("kernels.")
    # without the stage seconds (job kind ``train`` keeps none)
    run = _run(_program(), stage_s=None)
    assert (_read(name, run) is None) != kernel
    # without a device trace
    run = _run(_program(), kernel_s=None)
    assert (_read(name, run) is None) == kernel
    run = _run(_program(), stage_s=None, kernel_s=None)
    assert _read(name, run) is None and run.notes == {}
    # a parent commit: a round log and no work function
    parent = SimpleNamespace(recorder=None, round_log=list(LOG))
    run = _run(parent)
    assert _read(name, run) is None and run.notes == {}
    # a program that has made no fused step counts nothing
    idle = _program()
    idle.stage_work = lambda n=None, **kw: {}
    assert _read(name, _run(idle)) is None
    # a round log that does not hold all the window's trees
    assert _read(name, _run(_program(), trees=5)) is None
    # no trees
    assert _read(name, _run(_program(), trees=0)) is None


def test_the_stage_without_seconds_or_without_a_count_reads_nothing():
    # an elementwise objective on a step that sorts nothing
    shape = dict(SHAPE, **{phases.SHAPE_STREAM_COMPACTED: 0})
    run = _run(_program(shape=shape))
    assert _read("builder.compact_ns_per_row_round", run) is None
    quiet = {k: v for k, v in STAGE_S.items() if k != "update"}
    assert _read("driver.update_ns_per_row",
                 _run(_program(), stage_s=quiet)) is None


def test_on_several_chips_seconds_and_counts_are_both_a_chips():
    """The mean shard's count goes with the chips' mean seconds (what job
    kind ``train-dp`` keeps); the fullest shard's is the program's to give
    beside it."""
    work = _program().stage_work(2)
    full = _program().stage_work(2, fullest=True)
    assert work["hist_gather"] == (1152.0, phases.UNIT_POSITIONS)
    assert full["hist_gather"] == (1280.0, phases.UNIT_POSITIONS)
    assert work["hist_kernel"][0] == (960 + 2048) * 160
    assert full["hist_kernel"][0] == (1024 + 2048) * 160
    plan = {phases.PLAN_ROUND_BYTES_BY_STAGE: {"hist_merge": 1000,
                                               "winner_sync": 10},
            phases.PLAN_TREE_BYTES_BY_STAGE: {"hist_merge": 4000}}
    wired = _program(plan_bytes=plan).stage_work(2)
    assert wired["hist_merge"] == (3 * 1000 + 2 * 4000, phases.UNIT_BYTES)
    assert wired["winner_sync"] == (30, phases.UNIT_BYTES)
