"""Data-parallel (shard_map) tree build vs single-device oracle.

Mirrors the reference distributed test strategy
(tests/distributed/_test_distributed.py asserts data-parallel training
matches expectations on synthetic data) — here the 8 virtual CPU devices
from conftest stand in for TPU chips.
"""


import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.boosting.tree_builder import build_tree
from lightgbm_tpu.ops.split import SplitParams
from lightgbm_tpu.parallel.data_parallel import DataParallelPlan

from conftest import sharded_isolated as _sharded_isolated


def _data(rng, R=1024, F=6, B=32):
    bins = rng.randint(0, B, size=(R, F)).astype(np.uint8)
    g = rng.normal(size=R).astype(np.float32)
    h = rng.uniform(0.5, 1.5, size=R).astype(np.float32)
    gh = np.stack([g, h, np.ones(R, np.float32)], axis=1)
    meta = dict(
        num_bins_pf=jnp.full((F,), B, jnp.int32),
        nan_bin_pf=jnp.full((F,), -1, jnp.int32),
        is_cat_pf=jnp.zeros((F,), bool),
        feature_mask=jnp.ones((F,), bool),
    )
    return bins, gh, meta


SP = SplitParams(min_data_in_leaf=5, min_sum_hessian_in_leaf=1e-3)
KW = dict(num_leaves=15, leaf_batch=4, max_depth=-1, num_bins=32,
          split_params=SP, hist_dtype="float32")


def test_dp_tree_matches_single_device(rng):
    bins, gh, meta = _data(rng)
    R = bins.shape[0]
    rl0 = np.zeros(R, np.int32)

    ref_tree, ref_rl, _, _rounds = build_tree(
        jnp.asarray(bins), jnp.asarray(gh), jnp.asarray(rl0),
        meta["num_bins_pf"], meta["nan_bin_pf"], meta["is_cat_pf"],
        meta["feature_mask"], block_rows=R, **KW)

    plan = DataParallelPlan()
    nsh = plan.num_shards
    assert nsh == 8
    got_tree, got_rl, _, _rounds = plan.build_tree(
        plan.shard_rows(bins), plan.shard_rows(gh), plan.shard_rows(rl0),
        meta["num_bins_pf"], meta["nan_bin_pf"], meta["is_cat_pf"],
        meta["feature_mask"], block_rows=R // nsh, **KW)

    assert int(got_tree.num_leaves) == int(ref_tree.num_leaves)
    np.testing.assert_array_equal(np.asarray(got_tree.split_feature),
                                  np.asarray(ref_tree.split_feature))
    np.testing.assert_array_equal(np.asarray(got_tree.threshold_bin),
                                  np.asarray(ref_tree.threshold_bin))
    np.testing.assert_allclose(np.asarray(got_tree.leaf_values),
                               np.asarray(ref_tree.leaf_values),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(got_rl), np.asarray(ref_rl))


def test_dp_valid_copartition(rng):
    bins, gh, meta = _data(rng)
    vbins, _, _ = _data(rng, R=512)
    R, VR = bins.shape[0], vbins.shape[0]
    rl0 = np.zeros(R, np.int32)
    vrl0 = np.zeros(VR, np.int32)

    _, _, ref_v, _rounds = build_tree(
        jnp.asarray(bins), jnp.asarray(gh), jnp.asarray(rl0),
        meta["num_bins_pf"], meta["nan_bin_pf"], meta["is_cat_pf"],
        meta["feature_mask"], block_rows=R,
        valid_bins=(jnp.asarray(vbins),),
        valid_row_leaf0=(jnp.asarray(vrl0),), **KW)

    plan = DataParallelPlan()
    nsh = plan.num_shards
    _, _, got_v, _rounds = plan.build_tree(
        plan.shard_rows(bins), plan.shard_rows(gh), plan.shard_rows(rl0),
        meta["num_bins_pf"], meta["nan_bin_pf"], meta["is_cat_pf"],
        meta["feature_mask"], block_rows=R // nsh,
        valid_bins=(plan.shard_rows(vbins),),
        valid_row_leaf0=(plan.shard_rows(vrl0),), **KW)

    np.testing.assert_array_equal(np.asarray(got_v[0]), np.asarray(ref_v[0]))


def test_feature_parallel_matches_single_device(rng):
    """tree_learner=feature: rows replicated, split work feature-sharded,
    winner merged by gain argmax (SyncUpGlobalBestSplit analog) — the
    tree must be IDENTICAL to the single-device build."""
    from lightgbm_tpu.parallel.data_parallel import FeatureParallelPlan
    bins, gh, meta = _data(rng, F=10)
    R = bins.shape[0]
    rl0 = np.zeros(R, np.int32)

    ref_tree, ref_rl, _, _rounds = build_tree(
        jnp.asarray(bins), jnp.asarray(gh), jnp.asarray(rl0),
        meta["num_bins_pf"], meta["nan_bin_pf"], meta["is_cat_pf"],
        meta["feature_mask"], block_rows=R, **KW)

    plan = FeatureParallelPlan()
    got_tree, got_rl, _, _rounds = plan.build_tree(
        plan.shard_rows(bins), plan.shard_rows(gh), plan.shard_rows(rl0),
        meta["num_bins_pf"], meta["nan_bin_pf"], meta["is_cat_pf"],
        meta["feature_mask"], block_rows=R, **KW)

    assert int(got_tree.num_leaves) == int(ref_tree.num_leaves)
    np.testing.assert_array_equal(np.asarray(got_tree.split_feature),
                                  np.asarray(ref_tree.split_feature))
    np.testing.assert_array_equal(np.asarray(got_tree.threshold_bin),
                                  np.asarray(ref_tree.threshold_bin))
    np.testing.assert_allclose(np.asarray(got_tree.leaf_values),
                               np.asarray(ref_tree.leaf_values),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(got_rl), np.asarray(ref_rl))


def test_voting_parallel_full_topk_matches_data_parallel(rng):
    """With top_k >= F every feature is elected, so PV-Tree must produce
    exactly the data-parallel tree (global sub-hist == global hist)."""
    from lightgbm_tpu.parallel.data_parallel import VotingParallelPlan
    bins, gh, meta = _data(rng, F=6)
    R = bins.shape[0]
    rl0 = np.zeros(R, np.int32)

    ref_tree, ref_rl, _, _rounds = build_tree(
        jnp.asarray(bins), jnp.asarray(gh), jnp.asarray(rl0),
        meta["num_bins_pf"], meta["nan_bin_pf"], meta["is_cat_pf"],
        meta["feature_mask"], block_rows=R, **KW)

    plan = VotingParallelPlan(top_k=6)
    nsh = plan.num_shards
    got_tree, got_rl, _, _rounds = plan.build_tree(
        plan.shard_rows(bins), plan.shard_rows(gh), plan.shard_rows(rl0),
        meta["num_bins_pf"], meta["nan_bin_pf"], meta["is_cat_pf"],
        meta["feature_mask"], block_rows=R // nsh, **KW)

    assert int(got_tree.num_leaves) == int(ref_tree.num_leaves)
    np.testing.assert_array_equal(np.asarray(got_tree.split_feature),
                                  np.asarray(ref_tree.split_feature))
    np.testing.assert_array_equal(np.asarray(got_rl), np.asarray(ref_rl))


def test_voting_parallel_small_topk_grows_sane_tree(rng):
    """top_k < F: communication-restricted election still grows a full
    tree whose splits all carry positive gain."""
    from lightgbm_tpu.parallel.data_parallel import VotingParallelPlan
    bins, gh, meta = _data(rng, F=12)
    R = bins.shape[0]
    rl0 = np.zeros(R, np.int32)
    plan = VotingParallelPlan(top_k=2)
    nsh = plan.num_shards
    tree, rl, _, _rounds = plan.build_tree(
        plan.shard_rows(bins), plan.shard_rows(gh), plan.shard_rows(rl0),
        meta["num_bins_pf"], meta["nan_bin_pf"], meta["is_cat_pf"],
        meta["feature_mask"], block_rows=R // nsh, **KW)
    nl = int(tree.num_leaves)
    assert nl > 1
    # slots beyond num_nodes (incl. the dummy scatter sink) excluded
    sf = np.asarray(tree.split_feature)[:int(tree.num_nodes)]
    internal = sf[sf >= 0]
    assert len(internal) == nl - 1
    # every row parks in a live leaf slot
    assert np.asarray(rl).max() < nl


@pytest.mark.slow
def test_end_to_end_voting_booster(rng):
    """Full training loop with tree_learner=voting on the 8-device mesh."""
    import lightgbm_tpu as lgb
    X = rng.normal(size=(2048, 10))
    y = (X[:, 0] + X[:, 1] ** 2 > 0.5).astype(float)
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    bst = lgb.train({"objective": "binary", "num_leaves": 15,
                     "tree_learner": "voting", "top_k": 3,
                     "verbosity": -1}, ds, 8)
    from sklearn.metrics import roc_auc_score
    assert roc_auc_score(y, bst.predict(X)) > 0.9


def test_end_to_end_feature_booster(rng):
    import lightgbm_tpu as lgb
    X = rng.normal(size=(2048, 10))
    y = (X[:, 0] + X[:, 1] ** 2 > 0.5).astype(float)
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    bst = lgb.train({"objective": "binary", "num_leaves": 15,
                     "tree_learner": "feature", "verbosity": -1}, ds, 8)
    from sklearn.metrics import roc_auc_score
    assert roc_auc_score(y, bst.predict(X)) > 0.9


def test_feature_parallel_composes_with_constraints(rng):
    """tree_learner=feature now composes with interaction constraints,
    per-node sampling, and extra_trees (the reference composes them via
    the templated learners, tree_learner.cpp:15-57): the sharded search
    must match the serial learner exactly — the constraint state and
    PRNG are replicated, so the sliced global mask is identical."""
    import lightgbm_tpu as lgb
    X = rng.normal(size=(1536, 8))
    y = X[:, 0] * X[:, 1] + X[:, 2] ** 2 + 0.1 * rng.normal(size=1536)
    base = {"objective": "regression", "num_leaves": 15, "verbosity": -1,
            "min_data_in_leaf": 5, "deterministic": True,
            "interaction_constraints": [[0, 1, 4, 5], [2, 3, 6, 7]],
            "extra_trees": True, "feature_fraction_bynode": 0.6}
    serial = lgb.train(dict(base, tree_learner="serial"),
                       lgb.Dataset(X, label=y, free_raw_data=False), 6)
    fp = lgb.train(dict(base, tree_learner="feature"),
                   lgb.Dataset(X, label=y, free_raw_data=False), 6)
    np.testing.assert_allclose(serial.predict(X), fp.predict(X),
                               rtol=1e-5, atol=1e-6)


def test_feature_parallel_sorted_cat(rng):
    """Sorted-subset categorical splits under tree_learner=feature match
    the serial learner (local window slice of cat_sorted_mask)."""
    import lightgbm_tpu as lgb
    n = 1536
    ncat = 24
    cat = rng.randint(0, ncat, size=n)
    means = rng.normal(size=ncat) * 2
    X = np.column_stack([cat.astype(float), rng.normal(size=(n, 5))])
    y = means[cat] + 0.4 * X[:, 1] + 0.1 * rng.normal(size=n)
    base = {"objective": "regression", "num_leaves": 15, "verbosity": -1,
            "min_data_in_leaf": 5, "min_data_per_group": 5}
    serial = lgb.train(dict(base, tree_learner="serial"),
                       lgb.Dataset(X, label=y, categorical_feature=[0],
                                   free_raw_data=False), 6)
    fp = lgb.train(dict(base, tree_learner="feature"),
                   lgb.Dataset(X, label=y, categorical_feature=[0],
                               free_raw_data=False), 6)
    np.testing.assert_allclose(serial.predict(X), fp.predict(X),
                               rtol=1e-5, atol=1e-6)


def test_efb_composes_with_voting(rng):
    """EFB-bundled datasets now run under tree_learner=voting: local
    unbundling commutes with the elected-column psum, so the result must
    equal the EFB run under tree_learner=data (which is itself
    oracle-tested against serial in test_efb.py)."""
    import lightgbm_tpu as lgb
    n, F = 2048, 12
    X = np.zeros((n, F))
    perm = rng.permutation(n)
    for f in range(F):  # strictly exclusive features -> bundles form
        rows = perm[f * (n // F):(f + 1) * (n // F)]
        X[rows, f] = rng.normal(size=len(rows)) + 1.0
    y = (X[:, 0] - X[:, 1] + 0.3 * X[:, 2] > 0.2).astype(float)
    base = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
            "min_data_in_leaf": 5, "enable_bundle": True}
    data = lgb.train(dict(base, tree_learner="data"),
                     lgb.Dataset(X, label=y, free_raw_data=False), 6)
    voting = lgb.train(dict(base, tree_learner="voting",
                            top_k=F),   # full top-k == data-parallel
                       lgb.Dataset(X, label=y, free_raw_data=False), 6)
    np.testing.assert_allclose(data.predict(X), voting.predict(X),
                               rtol=1e-5, atol=1e-6)
    # the bundles must actually have formed, or this test is vacuous
    ds = lgb.Dataset(X, label=y).construct()
    assert ds.bundle_plan is not None


def test_advanced_monotone_data_parallel_parity(rng):
    """monotone_constraints_method=advanced under tree_learner=data:
    the fresh per-candidate bounds derive only from replicated state
    (tree outputs + boxes), so the sharded run must equal serial."""
    import lightgbm_tpu as lgb
    X = rng.uniform(-1, 1, size=(1536, 3))
    y = 3 * X[:, 0] + np.sin(4 * X[:, 1]) + 0.1 * rng.normal(size=1536)
    base = {"objective": "regression", "num_leaves": 15, "verbosity": -1,
            "monotone_constraints": [1, 0, 0],
            "monotone_constraints_method": "advanced",
            "min_data_in_leaf": 5, "deterministic": True}
    serial = lgb.train(dict(base, tree_learner="serial"),
                       lgb.Dataset(X, label=y, free_raw_data=False), 6)
    dist = lgb.train(dict(base, tree_learner="data"),
                     lgb.Dataset(X, label=y, free_raw_data=False), 6)
    np.testing.assert_allclose(serial.predict(X), dist.predict(X),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.slow
def test_sorted_cat_composes_with_voting(rng):
    """Sorted-subset categorical splits now run under
    tree_learner=voting: the elected-column metadata is gathered
    per-slot ([S, k2]) and both finders broadcast 2-D metadata. With
    full top_k every feature is elected, so the result must equal the
    same run under tree_learner=data."""
    import lightgbm_tpu as lgb
    n = 3000
    # high-cardinality categorical (> max_cat_to_onehot=4 forces the
    # sorted path) + numerical noise columns
    cat = rng.randint(0, 12, size=n).astype(np.float64)
    X = np.column_stack([cat, rng.normal(size=(n, 3))])
    effect = rng.normal(size=12)
    y = effect[cat.astype(int)] + 0.3 * X[:, 1] \
        + 0.1 * rng.normal(size=n)
    base = {"objective": "regression", "num_leaves": 15,
            "verbosity": -1, "min_data_in_leaf": 5,
            "max_cat_to_onehot": 4, "categorical_feature": [0]}
    data = lgb.train(dict(base, tree_learner="data"),
                     lgb.Dataset(X, label=y, free_raw_data=False,
                                 categorical_feature=[0]), 5)
    voting = lgb.train(dict(base, tree_learner="voting", top_k=4),
                       lgb.Dataset(X, label=y, free_raw_data=False,
                                   categorical_feature=[0]), 5)
    np.testing.assert_allclose(data.predict(X), voting.predict(X),
                               rtol=1e-5, atol=1e-6)
    # the sorted path must actually engage, or this test is vacuous
    t = data._all_trees()[0]
    cat_nodes = [i for i in range(t.num_leaves - 1)
                 if t.split_feature[i] == 0 and (t.decision_type[i] & 1)]
    assert cat_nodes, "expected a categorical split on feature 0"
    assert any(len(t.cat_threshold) and bin(int(w)).count("1") > 1
               for w in t.cat_threshold), "sorted subset expected"


def test_efb_composes_with_feature_parallel(rng):
    """tree_learner=feature on an EFB-bundled dataset: GBDT decodes the
    bundled storage back to per-feature columns (rows are replicated in
    this mode anyway), so the result must equal the EFB run under
    tree_learner=data."""
    import lightgbm_tpu as lgb
    n, F = 2048, 12
    X = np.zeros((n, F))
    perm = rng.permutation(n)
    for f in range(F):  # strictly exclusive features -> bundles form
        rows = perm[f * (n // F):(f + 1) * (n // F)]
        X[rows, f] = rng.normal(size=len(rows)) + 1.0
    y = (X[:, 0] - X[:, 1] + 0.3 * X[:, 2] > 0.2).astype(float)
    base = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
            "min_data_in_leaf": 5, "enable_bundle": True}
    data = lgb.train(dict(base, tree_learner="data"),
                     lgb.Dataset(X, label=y, free_raw_data=False), 6)
    feat = lgb.train(dict(base, tree_learner="feature"),
                     lgb.Dataset(X, label=y, free_raw_data=False), 6)
    np.testing.assert_allclose(data.predict(X), feat.predict(X),
                               rtol=1e-5, atol=1e-6)
    # the data run must actually have used bundles, or this is vacuous
    assert data._gbdt.train_set.bundle_plan is not None
    assert data._gbdt._bundle_meta is not None
    # and the feature run decoded them away
    assert feat._gbdt._unbundle_feature


def test_efb_feature_parallel_rollback_replays_correctly(rng):
    """RollbackOneIter under tree_learner=feature + EFB: the host
    replay must use the same (already unbundled) matrix the device
    trained on — decoding twice corrupts the score state."""
    import lightgbm_tpu as lgb
    n, F = 1024, 8
    X = np.zeros((n, F))
    perm = rng.permutation(n)
    for f in range(F):
        rows = perm[f * (n // F):(f + 1) * (n // F)]
        X[rows, f] = rng.normal(size=len(rows)) + 1.0
    y = (X[:, 0] - X[:, 1] > 0.1).astype(float)
    params = {"objective": "binary", "num_leaves": 7, "verbosity": -1,
              "min_data_in_leaf": 5, "enable_bundle": True,
              "tree_learner": "feature"}
    bst = lgb.train(params, lgb.Dataset(X, label=y,
                                        free_raw_data=False), 3)
    assert bst._gbdt._unbundle_feature
    # train 2 then snapshot, train a 3rd, roll it back: scores must
    # return exactly to the 2-tree state
    b2 = lgb.train(params, lgb.Dataset(X, label=y,
                                       free_raw_data=False), 2)
    scores_after_2 = np.asarray(b2._gbdt.scores)
    bst.rollback_one_iter()
    # compare REAL rows only (padded tail rows carry arbitrary values:
    # training and replay update them differently, by design)
    np.testing.assert_allclose(np.asarray(bst._gbdt.scores)[:, :n],
                               scores_after_2[:, :n],
                               rtol=1e-5, atol=1e-6)


@pytest.mark.slow
@_sharded_isolated
def test_feature_shard_storage_matches_serial(rng):
    """feature_shard_storage=true column-shards the device bin matrix
    ([R, F_pad/n] per chip) and resolves the partition step's bin values
    with a one-hot psum over the feature axis — the training result must
    equal serial exactly (numeric + categorical + NaN, odd F so the
    feature axis needs padding)."""
    import lightgbm_tpu as lgb
    n, f = 4096, 21
    X = rng.normal(size=(n, f))
    X[rng.random(size=(n, f)) < 0.05] = np.nan
    X[:, 5] = rng.randint(0, 12, size=n)
    y = ((np.nan_to_num(X[:, 0]) + 0.5 * np.nan_to_num(X[:, 1])
          + (X[:, 5] % 3 == 0)) > 0.7).astype(float)
    common = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
              "min_data_in_leaf": 5}
    mk = lambda: lgb.Dataset(X, label=y, categorical_feature=[5],  # noqa
                             free_raw_data=False)
    serial = lgb.train(dict(common, tree_learner="serial"), mk(), 5)
    shard = lgb.train(dict(common, tree_learner="feature",
                           feature_shard_storage=True), mk(), 5)
    np.testing.assert_allclose(serial.predict(X), shard.predict(X),
                               rtol=1e-6, atol=1e-7)
    # the matrix must actually be column-sharded on the mesh: each
    # device holds F_pad / n columns, not a replica
    dd = shard._gbdt.train_dd
    n_dev = shard._gbdt.plan.num_shards
    F_pad = -(-f // n_dev) * n_dev
    shapes = {s.data.shape for s in dd.bins.addressable_shards}
    assert shapes == {(dd.bins.shape[0], F_pad // n_dev)}, shapes


@_sharded_isolated
def test_feature_shard_storage_valid_early_stopping(rng):
    """Validation matrices are column-sharded too; their co-partitioned
    row_leaf (psum relabel) must yield the same eval metrics as serial,
    including the early-stopping decision."""
    import lightgbm_tpu as lgb
    n, f = 3000, 10
    X = rng.normal(size=(n, f))
    y = (X[:, 0] - 0.5 * X[:, 1] > 0).astype(float)
    Xv = rng.normal(size=(1000, f))
    yv = (Xv[:, 0] - 0.5 * Xv[:, 1] > 0).astype(float)
    out = {}
    for name, extra in [("serial", {"tree_learner": "serial"}),
                        ("shard", {"tree_learner": "feature",
                                   "feature_shard_storage": True})]:
        ds = lgb.Dataset(X, label=y, free_raw_data=False)
        dv = lgb.Dataset(Xv, label=yv, reference=ds, free_raw_data=False)
        ev = {}
        bst = lgb.train(dict({"objective": "binary", "num_leaves": 15,
                              "metric": "auc", "verbosity": -1}, **extra),
                        ds, 8, valid_sets=[dv], valid_names=["v"],
                        callbacks=[lgb.record_evaluation(ev)])
        out[name] = ev["v"]["auc"]
    np.testing.assert_allclose(out["serial"], out["shard"],
                               rtol=1e-6, atol=1e-7)


@pytest.mark.slow
@_sharded_isolated
def test_feature_shard_storage_with_efb(rng):
    """EFB + feature_shard_storage: bundled storage decodes back to
    per-feature columns, THEN column-shards. Result equals the
    data-parallel EFB run."""
    import lightgbm_tpu as lgb
    n, F = 2048, 12
    X = np.zeros((n, F))
    perm = rng.permutation(n)
    for f in range(F):
        rows = perm[f * (n // F):(f + 1) * (n // F)]
        X[rows, f] = rng.normal(size=len(rows)) + 1.0
    y = (X[:, 0] - X[:, 1] + 0.3 * X[:, 2] > 0.2).astype(float)
    base = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
            "min_data_in_leaf": 5, "enable_bundle": True}
    data = lgb.train(dict(base, tree_learner="data"),
                     lgb.Dataset(X, label=y, free_raw_data=False), 6)
    shard = lgb.train(dict(base, tree_learner="feature",
                           feature_shard_storage=True),
                      lgb.Dataset(X, label=y, free_raw_data=False), 6)
    np.testing.assert_allclose(data.predict(X), shard.predict(X),
                               rtol=1e-5, atol=1e-6)
    assert shard._gbdt._unbundle_feature
    assert shard._gbdt.plan.shard_storage


@_sharded_isolated
def test_feature_shard_storage_capacity_width(rng, monkeypatch):
    """The capacity gate divides the stored width by the shard count:
    a matrix too wide for one device must pass once column-sharded
    (VERDICT r4 #5 — the sharded-feature answer to wide data)."""
    import lightgbm_tpu as lgb
    n, f = 512, 64
    X = rng.normal(size=(n, f))
    y = (X[:, 0] > 0).astype(float)
    # budget sized so the REPLICATED working set (bins 32 KB + 4x[R]
    # f32 per-row state 8 KB + the split search's lattice, 6 slots x 64
    # features x 16 bins x 12 B x 8 copies = 590 KB: 630 KB) fails but
    # the column-sharded one (bins 4 KB + 8 KB + the search over its own
    # 8 features 74 KB = 86 KB) fits under 0.85 * 200 KB = 170 KB
    monkeypatch.setenv("LIGHTGBM_TPU_DEVICE_MEM_GB",
                       str(200e3 / (1 << 30)))  # ~200 KB
    common = {"objective": "binary", "num_leaves": 4, "verbosity": -1,
              "max_bin": 16, "hist_subtraction": False}
    with pytest.raises(MemoryError):
        lgb.train(dict(common, tree_learner="feature"),
                  lgb.Dataset(X, label=y, free_raw_data=False), 1)
    bst = lgb.train(dict(common, tree_learner="feature",
                         feature_shard_storage=True),
                    lgb.Dataset(X, label=y, free_raw_data=False), 1)
    assert bst.num_trees() == 1


def test_feature_shard_storage_rejects_dart():
    """DART's drop/restore replay gathers whole matrix rows per stored
    tree — on column-sharded storage that would re-materialize the full
    [R, F] per device (the OOM the mode exists to avoid), so the combo
    must fail fast at setup."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(0)
    X = rng.normal(size=(512, 8))
    y = (X[:, 0] > 0).astype(float)
    with pytest.raises(NotImplementedError,
                       match="feature_shard_storage"):
        lgb.train({"objective": "binary", "boosting": "dart",
                   "tree_learner": "feature",
                   "feature_shard_storage": True, "verbosity": -1},
                  lgb.Dataset(X, label=y, free_raw_data=False), 2)
