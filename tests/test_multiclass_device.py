"""The class-batched multiclass build on the chip's kernels (the Pallas
histogram kernel and the class-root kernel, through the interpreter),
held to the plain multiclass reference
(``benchmarks/reference/gbdt_multiclass_reference.py``, loaded by path)
at a small size: 6,000 rows, 10 columns, 7 classes of which two hold 5
and 2 rows (their roots cannot split), 31 leaves, 15 bins, bfloat16
addends."""

import importlib.util
import os
import sys

import numpy as np
import pytest

import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu import phases, profiler
from lightgbm_tpu.boosting import tree_builder
from lightgbm_tpu.boosting.gbdt import ROUND_LOG_TREES
from lightgbm_tpu.ops import pallas_histogram as PH

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BENCH = os.path.join(_ROOT, "benchmarks")
if _BENCH not in sys.path:
    sys.path.insert(0, _BENCH)


def _reference():
    spec = importlib.util.spec_from_file_location(
        "gbdt_multiclass_reference",
        os.path.join(_BENCH, "reference", "gbdt_multiclass_reference.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


mref = _reference()

K = 7
COUNTS = (2400, 1500, 1000, 600, 493, 5, 2)
PARAMS = {"objective": "multiclass", "num_class": K, "num_leaves": 31,
          "max_bin": 15, "learning_rate": 0.1, "min_data_in_leaf": 1,
          "min_sum_hessian_in_leaf": 10.0, "tree_learner": "serial",
          "hist_impl": "pallas", "verbosity": -1}


def _data(seed=11, rows=6000, cols=10):
    assert sum(COUNTS) == rows
    rng = np.random.default_rng(seed)
    y = rng.permutation(np.repeat(np.arange(K), COUNTS))
    centres = np.random.default_rng(0).normal(size=(K, cols))
    X = centres[y] + rng.normal(size=(rows, cols))
    return X.astype(np.float32), y.astype(np.float32)


@pytest.fixture()
def trained(interp, monkeypatch):
    """Three iterations through ``Booster.update(defer=True)``: the fused
    step and the class-batched build, ``hist_impl=pallas``."""
    for k in [k for k in os.environ if k.startswith("LIGHTGBM_TPU_")]:
        monkeypatch.delenv(k)
    X, y = _data()
    ds = lgb.Dataset(X, label=y, params=PARAMS).construct()
    bst = lgb.Booster(PARAMS, ds)
    seq0 = profiler.recorder.seq
    for _ in range(3):
        bst.update(defer=True)
    bst._sync_trees()
    gb = bst._gbdt
    assert gb.fused_ok and gb.class_batch_ok, (gb.fused_reason,
                                              gb.class_batch_reason)
    assert gb.config.hist_impl == "pallas"
    ubs = [np.asarray(ds.bin_mappers[f].bin_upper_bound, np.float64)
           for f in ds.used_features]
    bins_cm = np.ascontiguousarray(np.asarray(ds.bins)[:len(y)].T)
    return bst, gb, ubs, bins_cm, y, seq0


def test_first_iteration_equals_the_reference_for_every_class(trained):
    bst, gb, ubs, bins_cm, y, _ = trained
    text = bst.model_to_string()
    num_bins = max(len(u) for u in ubs)
    roots = mref.root_counts(bins_cm, num_bins, y, K)
    reps = [mref.check_class_tree(text, k, K, ubs, bins_cm, y, PARAMS,
                                  roots, str(gb.config.hist_dtype))
            for k in range(K)]
    assert all(r["ok"] for r in reps), [r for r in reps if not r["ok"]]
    # float8 addends through the same comparison read not ok
    assert not all(r["control"]["ok"] for r in reps[:5])
    # the two classes of 5 and 2 rows cannot split: one leaf, the init
    # score; every other class's tree splits
    assert [r["can_split"] for r in reps] == [True] * 5 + [False] * 2
    assert [r["num_leaves"] for r in reps][5:] == [1, 1]
    assert all(r["num_leaves"] > 2 for r in reps[:5])
    # the benchmark's selection names both and the largest
    picked = mref.check_first_iteration(text, K, ubs, bins_cm, y, PARAMS,
                                        3)
    assert picked["ok"] and picked["picked"]["cannot_split"] == [5, 6]


def test_second_iteration_equals_the_reference(trained):
    """Iteration 1's trees, grown on softmax addends that differ row by
    row (the scores iteration 0's trees left), held to the reference for
    every class that splits, and the two that cannot split keep one leaf
    of output 0."""
    bst, gb, ubs, bins_cm, y, _ = trained
    text = bst.model_to_string()
    picked = {"largest": [0], "drawn": list(range(1, K))}
    rep = mref.check_second_iteration(text, K, ubs, bins_cm, y, PARAMS,
                                      picked, str(gb.config.hist_dtype))
    assert rep["ok"], [t for t in rep["trees"] if not t["ok"]]
    assert [t["can_split"] for t in rep["trees"]] == [True] * 5 + [False] * 2
    assert rep["compared"]["leaf_error_over_limit"] < 0.5, rep["compared"]
    # float8 addends through the same leaves read not ok
    assert not rep["compared"]["control_ok"], rep["compared"]
    # the addends differ row by row: more than the two a class has at
    # iteration 0
    scores = mref.walk_scores(text, K, K, ubs, bins_cm, np.arange(len(y)))
    g, _ = mref.softmax_addends(scores, y, 0)
    assert len(np.unique(mref.round_rows(g, "bfloat16")[0])) > 10


def test_device_scores_follow_the_model_text(trained):
    bst, gb, ubs, bins_cm, y, _ = trained
    text = bst.model_to_string()
    assert text.count("\nTree=") == 3 * K
    scores = gb.eval_scores(-1)
    rows = np.arange(len(y))
    rep = mref.check_scores(text, 3 * K, K, ubs, bins_cm, scores, rows)
    assert rep["ok"] and rep["score_error_over_limit"] < 0.25, rep
    # the classes that never split keep their init scores on the device
    init = mref.init_scores(y, K)
    np.testing.assert_allclose(scores[:, 5:], np.broadcast_to(
        init[5:], (len(y), 2)), rtol=1e-6)
    assert mref.multi_logloss(y, scores) < mref.multi_logloss(
        y, np.broadcast_to(init, scores.shape))


def test_round_log_holds_k_records_an_iteration(trained):
    bst, gb, ubs, bins_cm, y, seq0 = trained
    assert gb.round_log.maxlen == ROUND_LOG_TREES * K
    log = list(gb.round_log)
    assert len(log) == 3 * K
    for it in range(3):
        recs = [r for r in log if r.iteration == it]
        assert [r.class_index for r in recs] == list(range(K))
        built = [int((r.leaves > 0).sum()) for r in recs]
        # the batched loop ran as many rounds as the class that needed most
        assert {r.batched_rounds for r in recs} == {max(built)}
        assert built[5:] == [0, 0]
    # one root pass for all K classes: the step's shape says so, and the
    # row block of the root is the class-root call's
    assert gb.step_shape[phases.SHAPE_CLASSES] == K
    fields = profiler.recorder.spans("gbdt.step_ready")[-1].fields
    assert fields[phases.SHAPE_CLASSES] == K
    # one dispatch an iteration, kept as K records of a K-th each
    disp = [s for s in profiler.recorder.since(seq0)
            if s.name == "gbdt.dispatch"]
    assert len(disp) == 3 * K
    assert [s.fields["part"] for s in disp[:K]] == list(range(K))
    assert all(a.end_ns == b.start_ns for a, b in zip(disp[:K - 1],
                                                      disp[1:K]))


@pytest.mark.parametrize("mode,bundle_bins,classes", [
    (None, 0, 23), ("data", 0, 1), (None, 256, 1)])
def test_the_step_shape_at_the_cells_width(mode, bundle_bins, classes):
    """``tree_builder.step_shape`` at ``kdd99``'s shape (4,898,816 padded
    rows, 41 columns, 63 bins) under the class-batched build: no dense
    route (its ``cond`` would run both branches under ``vmap``), and one
    root pass for all 23 classes, at the class-root call's row block,
    where that call is made: one device, no bundles. Binary, the same
    shape would take the route (2,688 one-hot elements a row)."""
    kw = dict(rows=4_898_816, stored_columns=41, features=41, num_bins=63,
              bundle_bins=bundle_bins, num_leaves=255, leaf_batch=16,
              hist_impl="pallas", gh_dtype=jnp.float32,
              hist_dtype="bfloat16", block_rows=0, hist_sub=True,
              parallel_mode=mode, n_shards=4 if mode else 1)
    sh = tree_builder.step_shape(class_batched=True, classes=23, **kw)
    assert sh.dense_round_rows == 0
    assert sh.fields()[phases.SHAPE_CLASSES] == sh.classes == classes
    if classes > 1:
        assert sh.kernel_root_row_block == PH._plan(
            41, 63, 23 * 3, 2)[0]
    binary = tree_builder.step_shape(**kw)
    assert binary.classes == 1
    if mode is None and not bundle_bins:
        assert binary.dense_round_rows > 0
