"""Cases for the four metrics that read the program's span record and
round log, all on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""

import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import peaks, trace  # noqa: E402
from harness.manifest import Manifest  # noqa: E402
from harness.runner import run_cell  # noqa: E402
from test_harness import copy_with_added_cell, notes  # noqa: E402,F401


def test_manifest_takes_the_four_entries():
    man = Manifest(ROOT)
    assert man.problems() == []
    four = {"kernels.hist_inloop_roofline", "builder.live_row_share",
            "entry.step_ready_s", "driver.dispatch_ms_per_tree"}
    assert four <= {m["name"] for m in man.doc["per_layer"]}
    for w in man.doc["workloads"]:
        mine = {m["name"] for m in man.metrics_for(w["name"], "per_layer")}
        assert four <= mine


# -- the metrics that read the program's span record and round log -------------------

def _span(name, seconds, **fields):
    from types import SimpleNamespace
    return SimpleNamespace(name=name, seconds=seconds, fields=fields)


class _Recorder:
    def __init__(self, spans):
        self._spans = spans

    def spans(self, name=None):
        return [s for s in self._spans if name is None or s.name == name]


def _hand_made_run(spans=(), round_log=(), trees=2, kernel_s=None):
    """A ``run`` as the runner builds it, with the program's recorder and
    round log filled in by hand through the readers' ``run.program``."""
    from types import SimpleNamespace
    rep = None
    if kernel_s is not None:
        rep = trace.Report(window_s=10.0, busy_s=9.0,
                           class_s={"kernel": kernel_s, "rowwise": 5.0},
                           root_kernel_s=[0.5, 0.5], device_ops=[],
                           idle_gaps=[])
    return SimpleNamespace(
        spans={}, counters={"trees": trees, "host_syncs": 1}, trace=rep,
        memory={}, shape={"rows": 1000, "cols": 10, "bins": 63},
        device={"kind": "TPU v5 lite"}, notes={},
        program=SimpleNamespace(recorder=_Recorder(list(spans)),
                                round_log=list(round_log)))


def _rounds(rows, leaves, it=0):
    from types import SimpleNamespace
    return SimpleNamespace(iteration=it, class_index=0,
                           rows=np.asarray(rows, np.int32),
                           leaves=np.asarray(leaves, np.int32))


def _reader(name):
    return Manifest(ROOT).metric_reader(name)


def test_inloop_roofline_prices_live_rows_and_valid_leaves():
    log = [_rounds([9, 9, 9], [1, 1, 1]),            # tree 0: before the window
           _rounds([400, 300, 0], [1, 2, 0], 1),
           _rounds([[100, 50, 0], [350, 20, 0]], [1, 1, 0], 2)]  # two shards
    run = _hand_made_run(round_log=log, kernel_s=3.0)
    v5e = peaks.peaks_for("TPU v5 lite")
    want = sum(peaks.roofline_seconds(*peaks.hist_counts(r, 10, 63, l), v5e)[0]
               for r, l in ((400, 1), (300, 2), (350, 1), (50, 1)))
    got = _reader("kernels.hist_inloop_roofline").read(run)
    assert got == pytest.approx(100.0 * want / (3.0 - 1.0))
    note = run.notes["kernels.hist_inloop_roofline"]
    assert note["rounds"] == 4 and note["trees"] == 2
    assert note["measured_s"] == pytest.approx(2.0)
    assert sum(note["rounds_by_bound"].values()) == 4
    # no trace, or a program without the counters: nothing, and no raise
    assert _reader("kernels.hist_inloop_roofline").read(
        _hand_made_run(round_log=log)) is None
    bare = _hand_made_run(kernel_s=3.0)
    bare.program.round_log = []
    assert _reader("kernels.hist_inloop_roofline").read(bare) is None


def test_live_row_share_is_useful_over_attempted_rows():
    log = [_rounds([1000, 1000], [1, 1]),
           _rounds([400, 100, 0], [1, 2, 0], 1),
           _rounds([300, 0, 0], [1, 0, 0], 2)]
    run = _hand_made_run(round_log=log)
    got = _reader("builder.live_row_share").read(run)
    assert got == pytest.approx(100.0 * 800 / (3 * 1000))
    assert run.notes["builder.live_row_share"]["rounds_per_tree"] == 1.5
    assert _reader("builder.live_row_share").read(_hand_made_run()) is None


def test_step_ready_is_to_device_plus_first_call():
    spans = [_span("gbdt.to_device", 1.0), _span("gbdt.to_device", 0.25),
             _span("gbdt.step_ready", 2.0, trace_s=0.5, lowering_s=0.25,
                   backend_compile_s=1.0, cache_hits=3),
             _span("gbdt.dispatch", 2.5)]
    run = _hand_made_run(spans=spans)
    assert _reader("entry.step_ready_s").read(run) == pytest.approx(3.25)
    note = run.notes["entry.step_ready_s"]
    assert note["cache"] == "hit" and note["backend_compile_s"] == 1.0
    assert note["to_device_s"] == 1.25 and note["step_ready_s"] == 2.0
    assert _reader("entry.step_ready_s").read(_hand_made_run()) is None


def test_dispatch_ms_per_tree_reads_the_windows_dispatches():
    spans = [_span("gbdt.dispatch", 30.0),            # tree 0: compiles
             _span("gbdt.sync.wait", 9.0),
             _span("gbdt.dispatch", 0.004), _span("gbdt.dispatch", 0.002)]
    run = _hand_made_run(spans=spans)
    assert _reader("driver.dispatch_ms_per_tree").read(run) == pytest.approx(3.0)
    assert run.notes["driver.dispatch_ms_per_tree"]["max_ms"] == pytest.approx(4.0)
    short = _hand_made_run(spans=spans[:1])
    assert _reader("driver.dispatch_ms_per_tree").read(short) is None


def test_new_readers_find_the_program_in_a_rehearsal(copy_with_added_cell, notes):
    """No ``run.program``: the readers import the program and read its
    recorder and the live trainer's round log themselves. On a CPU there
    is no device trace, so the roofline is left out of the line."""
    root = copy_with_added_cell
    seen, note = notes
    res = run_cell(root, "tiny-train", 2 ** 31 + 11, 0.2, True,
                   require_tpu=False, note=note)
    m = res["metrics"]
    assert 0 < m["builder.live_row_share"]["value"] <= 100
    assert m["entry.step_ready_s"]["value"] > 0
    assert m["driver.dispatch_ms_per_tree"]["value"] > 0
    assert "kernels.hist_inloop_roofline" not in m
    assert m["driver.host_syncs_per_tree"]["value"] == pytest.approx(
        1.0 / seen["counters"]["trees"])
    pl = seen["per_layer_notes"]
    assert pl["builder.live_row_share"]["trees"] == seen["counters"]["trees"]
    assert pl["entry.step_ready_s"]["step_ready_s"] < seen["spans_s"][
        "setup.first_dispatch"]
