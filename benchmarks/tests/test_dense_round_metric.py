"""Cases for ``builder.dense_round_share``, the metric that reads the
round log's ``dense``, all on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_dense_round_metric.py -q
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

NAME = "builder.dense_round_share"


def _rounds(leaves, dense=None, it=0):
    rec = SimpleNamespace(iteration=it, class_index=0,
                          rows=np.ones(np.shape(dense) if dense is not None
                                       else len(leaves), np.int32),
                          leaves=np.asarray(leaves, np.int32),
                          stream_rows=np.ones(len(leaves), np.int32))
    if dense is not None:
        rec.dense = np.asarray(dense, np.int32)
    return rec


def _run(round_log, trees=2):
    return SimpleNamespace(
        spans={}, counters={"trees": trees}, trace=None, memory={},
        shape={"rows": 1000, "cols": 28, "bins": 63},
        device={"kind": "TPU v5 lite"}, notes={},
        program=SimpleNamespace(recorder=None, round_log=list(round_log)))


def _read(run):
    return Manifest(ROOT).metric_reader(NAME).read(run)


def test_dense_round_metric_is_in_the_manifest_for_every_cell():
    man = Manifest(ROOT)
    assert man.problems() == []
    entries = [m for m in man.doc["per_layer"] if m["name"] == NAME]
    assert entries == [{"name": NAME, "unit": "%", "better": "higher",
                        "source": "program_counter", "layer": "builder",
                        "moves": "train_row_trees_per_s"}]
    assert man.doc["per_layer"][-1] == entries[0]
    for w in man.doc["workloads"]:
        assert NAME in {m["name"]
                        for m in man.metrics_for(w["name"], "per_layer")}


def test_dense_rounds_over_built_rounds_of_the_windows_trees():
    log = [_rounds([1, 1], [1, 1]),                       # tree 0: set-up
           _rounds([1, 2, 4, 0], [1, 1, 0, 0], 1),
           _rounds([1, 2, 0], [[1, 0, 0], [0, 0, 0]], 2)]  # two shards
    run = _run(log)
    # tree 1: 2 of 3 built rounds; tree 2: 1 of 2 rounds x 2 shards
    assert _read(run) == pytest.approx(100.0 * 3 / 7)
    note = run.notes[NAME]
    assert note == {"dense_rounds": 3, "built_rounds": 7, "trees": 2}


def test_a_step_without_the_route_reads_zero():
    log = [_rounds([1, 2, 3], [0, 0, 0]), _rounds([1, 2], [0, 0], 1)]
    assert _read(_run(log)) == 0.0


@pytest.mark.parametrize("log,trees", [
    ([_rounds([1, 2])], 1),                      # a parent: no dense
    ([_rounds([1], [1]), _rounds([1], it=1)], 2),
    ([], 2),                                     # nothing fetched
    ([_rounds([0, 0], [0, 0])], 1),              # no round ran
    ([_rounds([1], [1])], 0),                    # no tree in the window
])
def test_dense_round_metric_is_none_without_the_counter(log, trees):
    assert _read(_run(log, trees=trees)) is None


def test_dense_round_metric_without_a_program_is_none():
    run = _run([])
    run.program = None
    run.counters = {}
    assert _read(run) is None
