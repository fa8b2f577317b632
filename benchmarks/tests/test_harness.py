"""The harness's own tests, all on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import peaks, trace  # noqa: E402
from harness.manifest import NAME_RE, UNIT_RE, Manifest  # noqa: E402
from harness.runner import run_cell  # noqa: E402


# -- the manifest ---------------------------------------------------------------

def test_manifest_loads_and_keeps_to_the_contract():
    man = Manifest(ROOT)
    assert man.problems() == []
    doc = man.doc
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in doc[group]:
            assert NAME_RE.match(e["name"]), e["name"]
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert UNIT_RE.match(m["unit"]), m["unit"]
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "layer", "moves", "workloads"}
    assert len(json.dumps(doc)) < 64 * 1024


def test_every_cell_finds_its_files():
    man = Manifest(ROOT)
    for w in man.doc["workloads"]:
        cfg = man.config(w["config"])
        mix = man.traffic(w["traffic"])
        assert hasattr(man.job(mix["job"]), "run")
        assert hasattr(man.generator(cfg["generator"]["name"]), "generate")
        assert man.metrics_for(w["name"], "per_layer")
        for m in man.metrics_for(w["name"], "per_layer"):
            assert hasattr(man.metric_reader(m["name"]), "read")


def test_run_py_branches_on_no_name():
    src = open(os.path.join(BENCH, "run.py")).read()
    man = Manifest(ROOT)
    names = [e["name"] for g in ("configs", "workloads", "per_layer")
             for e in man.doc[g]]
    assert not [n for n in names if n in src]


# -- a cell, a mix and a metric added as files only -----------------------------

TINY = {"rows": 20000, "cols": 12}


@pytest.fixture()
def copy_with_added_cell(tmp_path):
    """A copy of the benchmark with a configuration, a mix, a per-layer
    metric and a cell added: new files and new manifest entries, and no
    edit to a file that was there."""
    root = str(tmp_path)
    shutil.copytree(BENCH, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {}
    for d, _, files in os.walk(os.path.join(root, "benchmarks")):
        for f in files:
            p = os.path.join(d, f)
            before[p] = open(p, "rb").read()
    doc = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cfg = json.load(open(os.path.join(BENCH, "configs", "higgs.json")))
    cfg.update(name="tiny", shape=TINY, bin_sample_rows=5000)
    cfg["params"]["num_leaves"] = 15
    bench = os.path.join(root, "benchmarks")
    json.dump(cfg, open(os.path.join(bench, "configs", "tiny.json"), "w"))
    mix = json.load(open(os.path.join(BENCH, "traffic", "train-steady.json")))
    json.dump(mix, open(os.path.join(bench, "traffic", "train-again.json"), "w"))
    with open(os.path.join(bench, "metrics", "entry.data_s.py"), "w") as f:
        f.write("def read(run):\n    return run.spans.get('setup.data')\n")
    doc["configs"].append({"name": "tiny", "source": "test", "reduced": [],
                           "file": "benchmarks/configs/tiny.json", "why": "t"})
    doc["workloads"].append({"name": "tiny-train", "config": "tiny", "chips": 1,
                             "traffic": "train-again", "why": "test"})
    doc["per_layer"].append({"name": "entry.data_s", "unit": "s",
                             "better": "lower", "source": "host_clock",
                             "layer": "entry", "moves": "setup_s",
                             "workloads": ["tiny-train"]})
    json.dump(doc, open(os.path.join(root, "BENCHMARK.json"), "w"))
    yield root
    for p, content in before.items():
        assert open(p, "rb").read() == content, f"{p} was edited"


@pytest.fixture()
def notes():
    seen = {}
    return seen, lambda label, obj: seen.__setitem__(label, obj)


def test_added_cell_runs_as_a_rehearsal(copy_with_added_cell, notes):
    """The rehearsal path: the job kind's set-up, window and check from
    Python, another root, a tiny shape, no TPU; and the plain reference
    agreeing with the program's first tree on that data."""
    root = copy_with_added_cell
    seen, note = notes
    assert Manifest(root).problems() == []
    res = run_cell(root, "tiny-train", 2 ** 31 + 5, 0.2, False,
                   require_tpu=False, note=note)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) == {"train_row_trees_per_s", "setup_s"}
    assert res["metrics"]["train_row_trees_per_s"]["unit"] == "row-trees/s"
    assert res["device"]["platform"] == "cpu"
    assert seen["replay"]["ok"]
    assert all(s["tree"] == s["reference"] for s in seen["replay"]["splits"])
    assert len(seen["replay"]["splits"]) == 5
    assert seen["counters"]["compiles_in_window"] == 0
    assert seen["loss"][1] < seen["loss"][0]

    res = run_cell(root, "tiny-train", 2 ** 31 + 5, 0.2, True,
                   require_tpu=False, note=note)
    # the added metric is read; on a CPU there is no device plane, so the
    # trace's readers return nothing and are left out of the line
    assert "entry.data_s" in res["metrics"]
    assert "entry.first_dispatch_s" in res["metrics"]
    assert "builder.rowwise_share" not in res["metrics"]
    assert "busy_s" not in res["device"]
    # a metric listed for one cell only is not reported by the others
    assert "entry.data_s" not in [
        m["name"] for m in Manifest(root).metrics_for("higgs-train", "per_layer")]


def test_reference_catches_a_wrong_tree():
    from reference import gbdt_reference as ref
    rng = np.random.default_rng(3)
    rows, cols, nb = 4000, 5, 8
    bins_cm = rng.integers(0, nb, size=(cols, rows), dtype=np.uint8)
    y = (bins_cm[2] > 3).astype(np.float64)
    y[rng.random(rows) < 0.1] = 1.0
    ubs = [np.append(np.arange(nb - 1) + 0.5, np.inf)] * cols
    init = ref.binary_init_score(y)
    g, h = ref.binary_gradients(y, np.full(rows, init))
    counts = ref.class_counts(bins_cm, nb, y.astype(np.uint8), 2)
    assert counts[1, 4, 1] == np.sum((bins_cm[1] == 4) & (y == 1))
    hist = counts @ np.array([[g[y == 0][0], h[0], 1], [g[y == 1][0], h[0], 1]])
    assert hist[3, 2, 0] == pytest.approx(g[bins_cm[3] == 2].sum())
    gains = ref.split_gains(hist, 0.0, 1, 1.0)
    assert np.unravel_index(np.argmax(gains), gains.shape) == (2, 3)
    assert list(ref.to_bfloat16([1.0, 1.00390625, 1.01171875, -0.3])) == [
        1.0, 1.0, 1.015625, -0.30078125]
    # a stump split at the reference's best: passes; moved one bin: fails
    def stump(feature, tbin):
        left = bins_cm[feature] <= tbin
        vals = [float(init - 0.1 * g[m].sum() / h[m].sum())
                for m in (left, ~left)]
        return ("Tree=0\nnum_leaves=2\nnum_cat=0\n"
                f"split_feature={feature}\nsplit_gain=1\nthreshold={tbin + 0.5}\n"
                "decision_type=0\nleft_child=-1\nright_child=-2\n"
                f"leaf_value={vals[0]!r} {vals[1]!r}\nleaf_weight=1 1\n"
                f"leaf_count={left.sum()} {(~left).sum()}\n"
                "internal_value=0\ninternal_weight=0\n"
                f"internal_count={rows}\nis_linear=0\nshrinkage=0.1\n\n")
    params = {"learning_rate": 0.1, "min_data_in_leaf": 1,
              "min_sum_hessian_in_leaf": 1.0}
    def ok(text):
        return ref.check_first_tree(text, ubs, bins_cm, y, params, "float32")["ok"]
    assert ok(stump(2, 3))
    assert not ok(stump(2, 5))
    assert not ok(stump(1, 3))
    # leaf values from unrounded addends are not what bfloat16 addends give
    assert not ref.check_first_tree(stump(2, 3), ubs, bins_cm, y, params)["ok"]


# -- the trace reduction ----------------------------------------------------------

R, F = 1000, 10


def _hand_made_events():
    E = trace.Event
    big = f"u8[{R},{F}]"
    row = f"s32[{R}]"
    return [
        E(f"%fusion.1 = {row} fusion({row} %a)", 1.0, 1.0),       # other: no loop
        E(f"%while.1 = ({row}, {big}) while(({row}, {big}) %t)", 2.0, 6.0),
        E(f"%fusion.2 = {row} fusion(s32[256] %t, {row} %l)", 2.0, 2.0),   # rowwise
        E(f"%fusion.3 = {big} fusion({big} %b, {row} %g)", 4.0, 1.0),      # relayout
        E(f"%k_pallas.1 = f32[640,128] custom-call(s32[1,{F},{R}] %c)", 5.0, 2.5),
        E(f"%k_pallas.2 = f32[640,128] custom-call(s32[1,{F},{R}] %c)", 9.0, 0.5),
        E("%copy.1 = f32[8] copy(f32[8] %x)", 9.75, 0.25),
    ], [E("bench:window", 0.0, 10.0), E("bench:window.update", 0.0, 1.5),
        E("bench:window.block", 1.5, 8.5)]


def test_reduction_on_hand_made_events():
    events, spans = _hand_made_events()
    rep = trace.reduce_plane(events, spans, R, F, "pallas")
    assert rep.window_s == pytest.approx(10.0)
    assert rep.busy_s == pytest.approx(7.75)      # [1,2] [2,8] [9,9.5] [9.75,10]
    assert rep.idle_share == pytest.approx(22.5)
    assert rep.class_s["rowwise"] == pytest.approx(2.0)
    assert rep.class_s["relayout"] == pytest.approx(1.0)
    assert rep.class_s["kernel"] == pytest.approx(3.0)
    # the while's own 0.5 s, the fusion outside the loop, the small copy
    assert rep.class_s["other"] == pytest.approx(0.5 + 1.0 + 0.25)
    assert sum(rep.class_s.values()) == pytest.approx(rep.busy_s)
    assert rep.class_share("kernel") == pytest.approx(100 * 3.0 / 7.75)
    assert rep.root_kernel_s == [pytest.approx(0.5)]   # the one outside the loop
    assert rep.idle_gaps[:2] == [("window.update", pytest.approx(1.0)),
                                 ("window.block", pytest.approx(1.0))]
    assert rep.idle_gaps[2] == ("window.block", pytest.approx(0.25))
    assert rep.device_ops[0][0].startswith("%k_pallas.1")


def test_op_text_is_parsed_by_shape():
    op = trace.parse_op(
        "%fusion.361 = pred[10502144]{0:T(1024)(128)(4,1)} fusion(pred[256]"
        "{0:T(512)(128)(4,1)S(1)} %fusion.360, s32[10502144]{0:T(1024)S(1)} %c)")
    assert (op.name, op.opcode) == ("%fusion.361", "fusion")
    assert op.result_elems == op.max_elems == 10502144
    op = trace.parse_op("%while.26 = (u32[256,2]{1,0:T(8,128)}, /*index=5*/"
                        "u8[2097152,28]{0,1}) while((u32[256,2]) %tuple.1)")
    assert op.opcode == "while" and op.result_elems == 2097152 * 28
    assert trace.op_class(op, False, 2097152, 28, "pallas") == "other"


def test_reduction_on_a_recorded_xplane():
    """A trace of two fused iterations at 65,536 x 28 on one TPU v5 lite
    (recorded by PR 23's probe run, before the benchmark's spans existed,
    so the window is first operation to last)."""
    rep = trace.reduce_xplane(
        os.path.join(HERE, "fixtures", "trace_tiny.xplane.pb.gz"), 65536, 28)
    assert rep.window_s == pytest.approx(0.0107772, rel=1e-4)
    assert rep.busy_s == pytest.approx(0.0107744, rel=1e-4)
    assert rep.idle_share == pytest.approx(0.0257, abs=1e-3)
    assert sum(rep.class_s.values()) == pytest.approx(rep.busy_s, rel=1e-4)
    assert rep.class_share("kernel") == pytest.approx(7.21, abs=0.01)
    assert rep.class_share("relayout") == pytest.approx(5.46, abs=0.01)
    assert rep.class_share("rowwise") == pytest.approx(80.70, abs=0.01)
    assert rep.root_kernel_s == [pytest.approx(322.741e-6)]
    assert any("custom-call" in n for n, _ in rep.device_ops)
    assert len(rep.device_ops) == 10 and len(rep.idle_gaps) <= 10


# -- peaks and the roofline ---------------------------------------------------------

def test_roofline_reproduces_a_hand_worked_case():
    # Higgs root pass: 10.5M rows x 28 columns x 63 bins, one leaf
    ops, byts = peaks.hist_counts(10_500_000, 28, 63, 1)
    assert ops == 2 * 10_500_000 * 28 * 63 * 3 == 111_132_000_000
    assert byts == 10_500_000 * 28 + 10_500_000 * 12 + 28 * 63 * 3 * 4
    v5e = peaks.peaks_for("TPU v5 lite")
    least, bound = peaks.roofline_seconds(ops, byts, v5e)
    assert bound == "compute"
    assert least == pytest.approx(111.132e9 / 197e12)        # 0.564 ms
    assert byts / 819e9 == pytest.approx(0.5128e-3, rel=1e-3)
    # a narrow, long stream is bound by memory
    assert peaks.roofline_seconds(*peaks.hist_counts(10 ** 6, 1, 4, 1), v5e)[1] == "memory"
    with pytest.raises(LookupError):
        peaks.peaks_for("TPU v99")


# -- the command refuses to measure without a TPU -------------------------------------

def test_run_py_prints_no_result_on_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "higgs-train", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode == 3
    assert '"metrics"' not in p.stdout and '"correct"' not in p.stdout
