"""Cases for ``builder.stream_row_share``, the metric that reads the
round log's ``stream_rows``, all on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_stream_metric.py -q
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

NAME = "builder.stream_row_share"


def _rounds(rows, leaves, stream=None, it=0):
    rec = SimpleNamespace(iteration=it, class_index=0,
                          rows=np.asarray(rows, np.int32),
                          leaves=np.asarray(leaves, np.int32))
    if stream is not None:
        rec.stream_rows = np.asarray(stream, np.int32)
    return rec


def _run(round_log, trees=2):
    return SimpleNamespace(
        spans={}, counters={"trees": trees}, trace=None, memory={},
        shape={"rows": 1000, "cols": 10, "bins": 63},
        device={"kind": "TPU v5 lite"}, notes={},
        program=SimpleNamespace(recorder=None, round_log=list(round_log)))


def _read(run):
    return Manifest(ROOT).metric_reader(NAME).read(run)


def test_manifest_has_the_entry_and_in_every_cell():
    man = Manifest(ROOT)
    assert man.problems() == []
    entries = [m for m in man.doc["per_layer"] if m["name"] == NAME]
    assert entries == [{"name": NAME, "unit": "%", "better": "higher",
                        "source": "program_counter", "layer": "builder",
                        "moves": "train_row_trees_per_s"}]
    for w in man.doc["workloads"]:
        assert NAME in {m["name"]
                        for m in man.metrics_for(w["name"], "per_layer")}


def test_share_is_live_over_touched_positions_of_the_windows_trees():
    log = [_rounds([1000, 1000], [1, 1], [1000, 1000]),     # tree 0: set-up
           _rounds([400, 100, 0], [1, 2, 0], [512, 128, 0], 1),
           _rounds([[100, 50, 0], [300, 0, 0]], [1, 1, 0],  # two shards
                   [[128, 128, 0], [384, 0, 0]], 2)]
    run = _run(log)
    got = _read(run)
    assert got == pytest.approx(100.0 * 950 / 1280)
    note = run.notes[NAME]
    assert note["live_rows"] == 950 and note["stream_rows"] == 1280
    assert note["rounds"] == 4 and note["trees"] == 2
    assert note["touched_share_of_rounds_x_rows_pct"] == pytest.approx(32.0)


def test_an_unbounded_stream_reads_what_live_row_share_reads():
    log = [_rounds([400, 100], [1, 1], [1000, 1000])]
    assert _read(_run(log, trees=1)) == pytest.approx(25.0)


@pytest.mark.parametrize("log,trees", [
    ([_rounds([400, 100], [1, 1])], 1),          # a parent: no stream_rows
    ([_rounds([400], [1], [512]), _rounds([300], [1], it=1)], 2),
    ([], 2),                                     # nothing fetched
    ([_rounds([0, 0], [0, 0], [0, 0])], 1),      # no round ran
    ([_rounds([400], [1], [512])], 0),           # no tree in the window
])
def test_returns_none_and_never_raises_without_the_counter(log, trees):
    assert _read(_run(log, trees=trees)) is None


def test_no_program_at_all_is_none():
    run = _run([])
    run.program = None
    run.counters = {}
    assert _read(run) is None
