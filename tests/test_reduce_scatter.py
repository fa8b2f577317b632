"""Reduce-scatter histogram merge (ISSUE 4): bit-parity of the
feature-slot-scattered data-parallel build against the allreduce
formulation and the serial oracle, on the 8-virtual-device mesh.

The scattered layout must change WHERE work happens (each chip holds
one F/n block of the merged histogram, searches it, winners sync
SplitInfo-sized) without changing a single decision: same splits, same
thresholds, same leaf values, same co-partitioned row_leaf — across
plain numerics, categoricals/NaN, EFB bundles (bundle-space scatter),
and quantized gradients (exact int32 scattered cache).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.boosting.tree_builder import build_tree
from lightgbm_tpu.ops.split import SplitParams
from lightgbm_tpu.parallel.data_parallel import (DataParallelPlan,
                                                 VotingParallelPlan,
                                                 resolve_hist_merge)

SP = SplitParams(min_data_in_leaf=5, min_sum_hessian_in_leaf=1e-3)
KW = dict(num_leaves=15, leaf_batch=4, max_depth=-1, num_bins=32,
          split_params=SP, hist_dtype="float32")


def _data(rng, R=1024, F=13, B=32):
    # odd F: the feature-slot scatter must pad the axis
    bins = rng.randint(0, B, size=(R, F)).astype(np.uint8)
    g = rng.normal(size=R).astype(np.float32)
    h = rng.uniform(0.5, 1.5, size=R).astype(np.float32)
    gh = np.stack([g, h, np.ones(R, np.float32)], axis=1)
    meta = (jnp.full((F,), B, jnp.int32), jnp.full((F,), -1, jnp.int32),
            jnp.zeros((F,), bool), jnp.ones((F,), bool))
    return bins, gh, meta


def _dp_tree(plan, bins, gh, meta, **kw):
    R = bins.shape[0]
    rl0 = np.zeros(R, np.int32)
    args = dict(KW)
    args.update(kw)
    return plan.build_tree(
        plan.shard_rows(bins), plan.shard_rows(gh), plan.shard_rows(rl0),
        *meta, block_rows=R // plan.num_shards, **args)


def test_resolve_hist_merge():
    assert resolve_hist_merge("auto", 8) == "reduce_scatter"
    assert resolve_hist_merge("auto", 1) == "allreduce"
    assert resolve_hist_merge("allreduce", 8) == "allreduce"
    with pytest.raises(ValueError):
        resolve_hist_merge("ring", 8)
    os.environ["LIGHTGBM_TPU_DP_HIST_MERGE"] = "allreduce"
    try:
        assert resolve_hist_merge("auto", 8) == "allreduce"
        assert DataParallelPlan().hist_merge == "allreduce"
    finally:
        del os.environ["LIGHTGBM_TPU_DP_HIST_MERGE"]
    assert DataParallelPlan().hist_merge == "reduce_scatter"


def test_rs_bit_parity_with_allreduce_and_serial(rng):
    bins, gh, meta = _data(rng)
    R = bins.shape[0]
    ref_tree, ref_rl, _, _rounds = build_tree(
        jnp.asarray(bins), jnp.asarray(gh),
        jnp.asarray(np.zeros(R, np.int32)), *meta, block_rows=R, **KW)
    out = {}
    for hm in ("allreduce", "reduce_scatter"):
        plan = DataParallelPlan(hist_merge=hm)
        assert plan.num_shards == 8
        t, rl, _, _rounds = _dp_tree(plan, bins, gh, meta)
        out[hm] = (jax.device_get(t), np.asarray(rl))
    ta, rla = out["allreduce"]
    ts, rls = out["reduce_scatter"]
    # reduce-scatter vs allreduce: EVERY tree field bit-identical
    for fld in ta._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(ta, fld)), np.asarray(getattr(ts, fld)),
            err_msg=f"field {fld} diverged between merge modes")
    np.testing.assert_array_equal(rla, rls)
    # and vs serial: identical structure/partition, leaf values to
    # reduction-order tolerance (the pre-existing dp-vs-serial contract)
    assert int(ts.num_leaves) == int(ref_tree.num_leaves)
    np.testing.assert_array_equal(np.asarray(ts.split_feature),
                                  np.asarray(ref_tree.split_feature))
    np.testing.assert_array_equal(np.asarray(ts.threshold_bin),
                                  np.asarray(ref_tree.threshold_bin))
    np.testing.assert_allclose(np.asarray(ts.leaf_values),
                               np.asarray(ref_tree.leaf_values),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(rls, np.asarray(ref_rl))


def test_rs_hist_cache_slot_sharded(rng):
    """Histogram-subtraction cache correctness in the slot-sharded
    space: the cached-parent-minus-child derivation must reproduce the
    direct (hist_sub=False) build under reduce_scatter."""
    bins, gh, meta = _data(rng, R=2048)
    plan = DataParallelPlan(hist_merge="reduce_scatter")
    t_sub, rl_sub, _, _rounds = _dp_tree(plan, bins, gh, meta, hist_sub=True)
    t_dir, rl_dir, _, _rounds = _dp_tree(plan, bins, gh, meta, hist_sub=False)
    np.testing.assert_array_equal(np.asarray(t_sub.split_feature),
                                  np.asarray(t_dir.split_feature))
    np.testing.assert_array_equal(np.asarray(t_sub.threshold_bin),
                                  np.asarray(t_dir.threshold_bin))
    np.testing.assert_allclose(np.asarray(t_sub.leaf_values),
                               np.asarray(t_dir.leaf_values),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(rl_sub),
                                  np.asarray(rl_dir))


def test_voting_rs_matches_voting_allreduce(rng):
    """Voting-parallel's elected-column merge in the scattered layout:
    same elections (votes are replicated), scattered sub-histogram
    search + winner sync must reproduce the replicated search."""
    bins, gh, meta = _data(rng, F=12)
    out = {}
    for hm in ("allreduce", "reduce_scatter"):
        plan = VotingParallelPlan(top_k=3, hist_merge=hm)
        t, rl, _, _rounds = _dp_tree(plan, bins, gh, meta)
        out[hm] = (jax.device_get(t), np.asarray(rl))
    ta, rla = out["allreduce"]
    ts, rls = out["reduce_scatter"]
    for fld in ta._fields:
        if fld == "gain":
            # recorded gains may differ in the last f32 ulp: the
            # [S, k2_loc]-shaped scattered search gives XLA a different
            # fusion (FMA) context than the replicated [S, k2] one —
            # the same benign divergence the fused driver documents for
            # split_gain. DECISIONS (features/thresholds/leaf values/
            # partition) are compared exactly below.
            np.testing.assert_allclose(
                np.asarray(ta.gain), np.asarray(ts.gain),
                rtol=1e-5, atol=1e-6)
            continue
        np.testing.assert_array_equal(
            np.asarray(getattr(ta, fld)), np.asarray(getattr(ts, fld)),
            err_msg=f"voting field {fld} diverged between merge modes")
    np.testing.assert_array_equal(rla, rls)


def _exclusive_data(rng, n=2048, F=12):
    X = np.zeros((n, F))
    perm = rng.permutation(n)
    for f in range(F):   # strictly exclusive features -> bundles form
        rows = perm[f * (n // F):(f + 1) * (n // F)]
        X[rows, f] = rng.normal(size=len(rows)) + 1.0
    y = (X[:, 0] - X[:, 1] + 0.3 * X[:, 2] > 0.2).astype(float)
    return X, y


def test_rs_end_to_end_cats_nan(rng):
    """Full training: categoricals + NaN under the default
    (reduce_scatter) merge — bit-equal predictions vs allreduce,
    tolerance-equal vs serial."""
    n, f = 2048, 9
    X = rng.normal(size=(n, f))
    X[rng.random(size=(n, f)) < 0.05] = np.nan
    X[:, 3] = rng.randint(0, 12, size=n)
    y = ((np.nan_to_num(X[:, 0]) + 0.5 * np.nan_to_num(X[:, 1])
          + (X[:, 3] % 3 == 0)) > 0.7).astype(float)
    base = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
            "min_data_in_leaf": 5}
    mk = lambda: lgb.Dataset(X, label=y, categorical_feature=[3],  # noqa
                             free_raw_data=False)
    serial = lgb.train(dict(base, tree_learner="serial"), mk(), 5)
    rs = lgb.train(dict(base, tree_learner="data"), mk(), 5)
    ar = lgb.train(dict(base, tree_learner="data",
                        dp_hist_merge="allreduce"), mk(), 5)
    assert rs._gbdt.plan.hist_merge == "reduce_scatter"
    assert ar._gbdt.plan.hist_merge == "allreduce"
    np.testing.assert_array_equal(rs.predict(X), ar.predict(X))
    np.testing.assert_allclose(serial.predict(X), rs.predict(X),
                               rtol=1e-5, atol=1e-6)


def test_rs_efb_bundle_space_scatter(rng):
    """EFB rides reduce-scatter by scattering along the BUNDLE axis
    (whole features stay chip-local; the mfb reconstruction reads
    broadcast totals) — trees must be bit-equal to allreduce."""
    X, y = _exclusive_data(rng)
    base = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
            "min_data_in_leaf": 5, "enable_bundle": True}
    rs = lgb.train(dict(base, tree_learner="data"),
                   lgb.Dataset(X, label=y, free_raw_data=False), 6)
    ar = lgb.train(dict(base, tree_learner="data",
                        dp_hist_merge="allreduce"),
                   lgb.Dataset(X, label=y, free_raw_data=False), 6)
    sr = lgb.train(dict(base, tree_learner="serial"),
                   lgb.Dataset(X, label=y, free_raw_data=False), 6)
    assert rs._gbdt._bundle_meta is not None, "bundles must form"
    np.testing.assert_array_equal(rs.predict(X), ar.predict(X))
    np.testing.assert_allclose(sr.predict(X), rs.predict(X),
                               rtol=1e-5, atol=1e-6)


def test_rs_quantized_renew(rng):
    """Quantized training (+renew): the scattered raw cache stays
    int32-exact, so rs must be bit-equal to allreduce."""
    n, f = 2048, 9
    X = rng.normal(size=(n, f))
    y = (X[:, 0] + 0.5 * X[:, 1] > 0.3).astype(float)
    base = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
            "min_data_in_leaf": 5, "use_quantized_grad": True,
            "quant_train_renew_leaf": True}
    mk = lambda: lgb.Dataset(X, label=y, free_raw_data=False)  # noqa
    rs = lgb.train(dict(base, tree_learner="data"), mk(), 5)
    ar = lgb.train(dict(base, tree_learner="data",
                        dp_hist_merge="allreduce"), mk(), 5)
    sr = lgb.train(dict(base, tree_learner="serial"), mk(), 5)
    np.testing.assert_array_equal(rs.predict(X), ar.predict(X))
    np.testing.assert_allclose(sr.predict(X), rs.predict(X),
                               rtol=1e-5, atol=1e-6)


def test_fused_over_mesh_reduce_scatter(rng):
    """The scattered build nests inside the fused single-dispatch trace
    (the test_fused_over_device_mesh analog for hist_merge=
    reduce_scatter): fused and legacy drivers must agree bit-for-bit."""
    if len(jax.devices()) < 2:
        pytest.skip("needs the virtual device mesh")
    n = 512
    X = rng.normal(size=(n, 6)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] > 0).astype(np.float32)
    params = {"objective": "binary", "metric": "auc", "num_leaves": 5,
              "learning_rate": 0.2, "min_data_in_leaf": 5,
              "verbosity": -1, "tree_learner": "data"}
    prev = os.environ.get("LIGHTGBM_TPU_FUSED_TRAIN")
    try:
        os.environ["LIGHTGBM_TPU_FUSED_TRAIN"] = "0"
        bl = lgb.train(dict(params),
                       lgb.Dataset(X, label=y, free_raw_data=False), 3)
        os.environ["LIGHTGBM_TPU_FUSED_TRAIN"] = "1"
        bf = lgb.train(dict(params),
                       lgb.Dataset(X, label=y, free_raw_data=False), 3)
    finally:
        if prev is None:
            os.environ.pop("LIGHTGBM_TPU_FUSED_TRAIN", None)
        else:
            os.environ["LIGHTGBM_TPU_FUSED_TRAIN"] = prev
    assert bf._gbdt.fused_ok and bf._gbdt.plan is not None
    assert bf._gbdt.plan.hist_merge == "reduce_scatter"
    np.testing.assert_array_equal(np.asarray(bl._gbdt.eval_scores(-1)),
                                  np.asarray(bf._gbdt.eval_scores(-1)))
    np.testing.assert_array_equal(bl.predict(X), bf.predict(X))


def test_forced_splits_pin_allreduce(rng, tmp_path):
    """Forced splits read full-feature histogram rows from the cache:
    the plan must pin allreduce (with a warning), and train correctly."""
    import json
    n = 1024
    X = rng.normal(size=(n, 4))
    y = (X[:, 0] > 0.2).astype(float)
    fs = tmp_path / "forced.json"
    fs.write_text(json.dumps({"feature": 0, "threshold": 0.2}))
    bst = lgb.train({"objective": "binary", "num_leaves": 7,
                     "verbosity": -1, "min_data_in_leaf": 5,
                     "tree_learner": "data",
                     "forcedsplits_filename": str(fs)},
                    lgb.Dataset(X, label=y, free_raw_data=False), 2)
    assert bst._gbdt.plan.hist_merge == "allreduce"
    assert bst.num_trees() == 2
