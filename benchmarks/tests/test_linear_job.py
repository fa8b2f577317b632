"""Job kind ``train-linear``, its configuration, reference and two
readers, all on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_linear_job.py -q
"""

import json
import os
import re
import shutil
import sys
import time
from types import SimpleNamespace

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import datagen  # noqa: E402
from harness.manifest import Manifest  # noqa: E402
from harness.runner import run_cell  # noqa: E402
from reference import gbdt_linear_reference as lref  # noqa: E402
from reference import gbdt_reference as ref  # noqa: E402

CELL = "higgs-linear-train"
NEW = ("linear.leaf_ns_per_row", "kernels.linear_gram_roofline")
TINY = {"rows": 40000, "cols": 28}


# -- the manifest and the configuration -------------------------------------------

def test_manifest_has_the_cell_its_configuration_and_two_metrics():
    man = Manifest(ROOT)
    assert man.problems() == []
    cell = man.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "higgs-linear", "train-linear-steady", 1)
    assert len(cell["why"]) <= 200
    entry = [c for c in man.doc["configs"] if c["name"] == "higgs-linear"]
    assert len(entry) == 1 and entry[0]["reduced"] == ["num_iterations"]
    assert len(entry[0]["source"]) <= 200
    assert man.doc["configs"][-1] is entry[0]
    assert man.doc["workloads"][-1] is cell
    tail = man.doc["per_layer"][-2:]
    assert [m["name"] for m in tail] == list(NEW)
    for m in tail:
        assert m["workloads"] == [CELL]
        assert m["moves"] == "train_row_trees_per_s"
    assert man.traffic(cell["traffic"])["job"] == "train-linear"
    # every metric without a list reads in the new cell too
    names = {m["name"] for m in man.metrics_for(CELL, "per_layer")}
    assert set(NEW) <= names and names >= {
        m["name"] for m in man.doc["per_layer"] if "workloads" not in m}
    for old in ("higgs-train", "epsilon-train"):
        assert not set(NEW) & {m["name"]
                               for m in man.metrics_for(old, "per_layer")}


def test_configuration_is_higgs_with_linear_leaves_and_nothing_else():
    man = Manifest(ROOT)
    higgs, lin = man.config("higgs"), man.config("higgs-linear")
    assert lin["params"] == dict(higgs["params"], linear_tree=True,
                                 linear_lambda=0.0)
    for key in ("shape", "generator", "bin_sample_rows", "expect",
                "reduced"):
        assert lin[key] == higgs[key], key
    assert "first-tree rule" in lin["assumed"] and lin["guarantees"]


# -- the reference ------------------------------------------------------------------

def _trained(rows=20000, leaves=15, trees=3, seed=2 ** 31 + 5):
    import lightgbm_tpu as lgb
    man = Manifest(ROOT)
    x_cm, y = man.generator("higgs_like").generate(rows, 28, seed, {})
    params = dict(man.config("higgs-linear")["params"], num_leaves=leaves,
                  min_sum_hessian_in_leaf=1.0, verbosity=-1)
    ds = lgb.Dataset(np.ascontiguousarray(x_cm.T), label=y, params=params,
                     free_raw_data=False).construct()
    bst = lgb.Booster(params, ds)
    for _ in range(trees):
        bst.update()
    ubs = [np.asarray(ds.bin_mappers[f].bin_upper_bound, np.float64)
           for f in ds.used_features]
    return (bst, bst.model_to_string(), ubs, datagen.bin_columns(x_cm, ubs),
            x_cm, y, params)


def _with_tree_model(text, index, model):
    """The model text with tree ``index``'s linear block replaced."""
    start = text.index(f"Tree={index}\n")
    end = text.index("\nTree=", start + 1)
    block = text[start:end]
    feats = " ".join(str(f) for fs in model["features"] for f in fs)
    coefs = " ".join(repr(float(c)) for cs in model["coeff"] for c in cs)
    for key, val in (
            ("leaf_const", " ".join(repr(float(c)) for c in model["const"])),
            ("num_features", " ".join(str(len(f))
                                      for f in model["features"])),
            ("leaf_features", feats), ("leaf_coeff", coefs)):
        block = re.sub(rf"^{key}=.*$", f"{key}={val}", block,
                       flags=re.MULTILINE)
    return text[:start] + block + text[end:]


def test_reference_holds_the_program_and_refuses_the_bfloat16_control():
    """(b) passes on the program's own tree 1, and fails on the same tree
    whose leaves were fitted from bfloat16 raw values."""
    bst, text, ubs, bins_cm, x_cm, y, params = _trained()
    rep = lref.check_first_linear_tree(text, ubs, bins_cm, x_cm, y, params)
    assert rep["ok"], rep
    assert rep["output_error_over_limit"] < 0.25
    assert rep["control_error_over_limit"] > 4 and not rep["control_ok"]
    # the control's leaves written into the text: (b) refuses them
    tree = lref.parse_linear(text, 1)
    _, leaf_rows = ref.replay(tree, ref.threshold_bins(tree, ubs), bins_cm)
    score = lref.replay_scores_f32(text, 1, ubs, bins_cm, x_cm,
                                   ref.binary_init_score(y),
                                   params["learning_rate"])
    g, h = ref.binary_gradients(y, score.astype(np.float64))
    control = lref.fit_leaves(tree, leaf_rows, x_cm, g, h, 0.0,
                              params["learning_rate"], first=False,
                              gram="bfloat16")
    bad = lref.check_first_linear_tree(_with_tree_model(text, 1, control),
                                       ubs, bins_cm, x_cm, y, params)
    assert not bad["ok"] and bad["output_error_over_limit"] > 4
    # the first-tree rule and the walk of the text
    assert lref.check_constant_first_tree(text)["ok"]
    scores = bst._gbdt.eval_scores(-1)[:, 0]
    rows = lref.sample_rows(len(y), 7, 5000)
    walk = lref.check_scores(text, 3, ubs, bins_cm, x_cm, scores, rows)
    assert walk["ok"] and walk["score_error_over_limit"] < 0.25
    off = scores.copy()
    off[rows[::50]] += 1e-3
    assert not lref.check_scores(text, 3, ubs, bins_cm, x_cm, off,
                                 rows)["ok"]


def test_a_linear_first_tree_breaks_the_first_tree_rule():
    _, text, *_ = _trained(trees=2)
    bad = text.replace("Tree=0", "Tree=X", 1).replace("Tree=1", "Tree=0",
                                                       1)
    assert not lref.check_constant_first_tree(bad)["ok"]


# -- the two readers on hand-made inputs --------------------------------------------

def _program(stage_work, trees=2, columns=29):
    return SimpleNamespace(
        round_log=[SimpleNamespace(leaves=np.array([1]))] * trees,
        stage_work=lambda n: stage_work, step_shape={
            "shape_linear_columns": columns})


def _run(counters, program):
    return SimpleNamespace(
        spans={}, counters=counters, trace=None, memory={},
        shape={"rows": 1000, "cols": 28, "bins": 63},
        device={"kind": "TPU v5 lite"}, notes={}, program=program)


def _read(name, run):
    return Manifest(ROOT).metric_reader(name).read(run)


WORK = {"linear_gram": (4.0e9, "gram_macs"),
        "linear_solve": (3000, "path_features"),
        "linear_update": (2.0e7, "rows")}
STAGE_S = {"linear_gram": 0.02, "linear_solve": 0.001,
           "linear_update": 0.004, "update": 1.0}


def test_leaf_ns_per_row_is_the_three_stages_over_rows_and_trees():
    run = _run({"trees": 2, "stage_s": STAGE_S}, _program(WORK))
    assert _read(NEW[0], run) == pytest.approx(1e9 * 0.025 / 2.0e7)
    assert run.notes[NEW[0]]["count"] == 2.0e7


def test_gram_roofline_is_the_least_time_over_the_stage():
    run = _run({"trees": 2, "stage_s": STAGE_S}, _program(WORK))
    ops, byts = 2 * 4.0e9, 2.0e7 * (4 * 28 + 12)
    least = max(ops / 197e12, byts / 819e9)
    assert _read(NEW[1], run) == pytest.approx(100 * least / 0.02)
    assert run.notes[NEW[1]]["bound"] == "memory"


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("counters,work", [
    ({"trees": 2}, WORK), ({"trees": 2, "stage_s": None}, WORK),
    ({"trees": 2, "stage_s": {"update": 1.0}}, WORK),
    ({"trees": 2, "stage_s": STAGE_S}, {}),
    ({"trees": 2, "stage_s": STAGE_S}, {"update": (5, "rows")})])
def test_readers_read_nothing_where_the_program_has_no_linear_stages(
        name, counters, work):
    assert _read(name, _run(counters, _program(work))) is None


# -- the job: a rehearsal, and its refusal -----------------------------------------

@pytest.fixture()
def tiny_root(tmp_path):
    """A copy of the benchmark with the configuration cut to a tiny shape
    under the cell's own name; no file that was there is edited."""
    root = str(tmp_path)
    shutil.copytree(BENCH, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    cfg = json.load(open(os.path.join(BENCH, "configs", "higgs-linear.json")))
    cfg["shape"] = TINY
    cfg["bin_sample_rows"] = 20000
    cfg["params"].update(num_leaves=31, min_sum_hessian_in_leaf=1.0)
    json.dump(cfg, open(os.path.join(
        root, "benchmarks", "configs", "higgs-linear.json"), "w"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    return root


@pytest.fixture()
def notes():
    seen = {}
    return seen, lambda label, obj: seen.__setitem__(label, obj)


def test_linear_cell_runs_as_a_rehearsal(tiny_root, notes):
    seen, note = notes
    res = run_cell(tiny_root, CELL, 2 ** 31 + 13, 0.3, False,
                   require_tpu=False, note=note)
    assert res["correct"], seen["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) == {"train_row_trees_per_s", "setup_s"}
    assert res["device"]["platform"] == "cpu"
    checks = seen["checks"]
    assert checks["first_tree_constant"] and checks["first_linear_tree"]
    assert checks["scores_follow_the_model_text"] and checks["fused_step"]
    cmp = seen["compared"]
    # what the limit is there to refuse
    assert cmp["control_error_over_limit"] > 4 and not cmp["control_ok"]
    assert cmp["output_error_over_limit"] < 0.25
    assert seen["resolved"]["linear_impl"] == "xla"


def test_a_program_without_linear_stages_is_refused_at_once(
        tiny_root, notes, monkeypatch):
    from lightgbm_tpu import phases
    monkeypatch.setattr(phases, "KNOWN_PHASES",
                        phases.KNOWN_PHASES - phases.LINEAR_STAGES)
    t0 = time.time()
    with pytest.raises(RuntimeError, match="linear_gram") as refused:
        run_cell(tiny_root, CELL, 2 ** 31 + 13, 0.3, False,
                 require_tpu=False, note=notes[1])
    assert type(refused.value).__name__ == "CannotRunCell"
    assert time.time() - t0 < 10 and "device" not in notes[0]
