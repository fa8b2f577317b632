"""Linear leaves fitted on the device, in the fused step: each tree's
per-row outputs against a plain float64 ridge fit of the same leaves
(LinearTreeLearner::CalculateLinear), the first-tree rule, validation
scores, multiclass, the model text, and the Pallas kernels against their
XLA form."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu as lgb
from lightgbm_tpu import phases
from lightgbm_tpu.ops import linear_leaves as LL
from lightgbm_tpu.tree import Tree


@pytest.fixture(autouse=True)
def _fused_by_default(monkeypatch):
    """The fused step unless a case pins ``fused_train`` off."""
    monkeypatch.setenv("LIGHTGBM_TPU_FUSED_TRAIN", "1")


def _data(seed=0, n=1500, nan_share=0.05):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 5))
    X[rng.random(n) < nan_share, 3] = np.nan
    y = np.where(X[:, 0] > 0, 2.0 * X[:, 1] + 1.0, -1.5 * X[:, 1] - 0.5)
    y += 0.5 * X[:, 2] + rng.normal(scale=0.05, size=n)
    return X, y


def _params(objective, lam, **kw):
    return {**dict(objective=objective, num_leaves=8, learning_rate=0.3,
                   min_data_in_leaf=20, linear_tree=True, linear_lambda=lam,
                   verbosity=-1), **kw}


def _tree_text(bst):
    return bst.model_to_string().split("end of trees")[0]


def _train(X, y, params, rounds, **kw):
    return lgb.train(params, lgb.Dataset(X, label=y, free_raw_data=False),
                     rounds, **kw)


def _trees(bst):
    text = bst.model_to_string()
    return [Tree.from_text("Tree=" + b) for b in
            text.split("end of trees")[0].split("Tree=")[1:]]


def _branch_features(tree):
    """{leaf: sorted numerical split features on its branch}."""
    out, stack = {}, [(0, frozenset())]
    while stack:
        node, path = stack.pop()
        if node < 0:
            out[~node] = sorted(path)
            continue
        path = path | {int(tree.split_feature[node])}
        stack += [(int(tree.left_child[node]), path),
                  (int(tree.right_child[node]), path)]
    return out


def _reference_outputs(tree, X, g, h, lam, shrink, first):
    """Shrunk per-row outputs of ``tree``'s leaves refitted in float64 by
    the reference's rules, over the program's leaf partition."""
    leaf = tree.predict_leaf_index(X)
    out = tree.leaf_value[leaf].astype(np.float64)
    if first or tree.num_leaves == 1:
        return out
    for s, feats in _branch_features(tree).items():
        rows = np.flatnonzero(leaf == s)
        vals = X[np.ix_(rows, feats)]
        ok = ~np.isnan(vals).any(axis=1)
        if ok.sum() < len(feats) + 1:
            continue
        A = np.concatenate([vals[ok], np.ones((ok.sum(), 1))], axis=1)
        M = (A * h[rows][ok][:, None]).T @ A
        M[np.arange(len(feats)), np.arange(len(feats))] += lam
        beta = -np.linalg.solve(M, A.T @ g[rows][ok])
        lin = (vals @ beta[:-1] + beta[-1]) * shrink
        out[rows] = np.where(np.isnan(vals).any(axis=1), out[rows], lin)
    return out


def _grads(objective, y, score):
    if objective == "binary":
        p = 1.0 / (1.0 + np.exp(-score))
        return p - y, p * (1.0 - p)
    return score - y, np.ones_like(score)


@pytest.mark.parametrize("objective", ["regression", "binary"])
@pytest.mark.parametrize("lam", [0.0, 0.01])
def test_fused_linear_leaves_match_a_float64_fit(objective, lam):
    X, y = _data()
    if objective == "binary":
        y = (y > 0.3).astype(np.float64)
    bst = _train(X, y, _params(objective, lam), 4)
    gb = bst._gbdt
    assert gb.fused_ok and gb.fused_reason == ""
    trees = _trees(bst)
    assert len(trees) == 4
    score = np.full(len(y), float(gb._init_scores[0]))
    for t, tree in enumerate(trees):
        g, h = _grads(objective, y, score)
        got = tree.predict(X)
        if t == 0:
            got = got - gb._init_scores[0]     # AddBias
            tree.leaf_value = tree.leaf_value - gb._init_scores[0]
        want = _reference_outputs(tree, X, g, h, lam, 0.3, first=t == 0)
        err = np.abs(got - want).max() / np.abs(want).max()
        assert err < 1e-4, (t, err)
        score = score + got
    # the device scores follow the model, tree by tree
    np.testing.assert_allclose(gb.eval_scores(-1)[:, 0], score, atol=2e-5)
    assert any(len(f) for f in trees[1].leaf_features)


def test_a_table_wider_than_the_gram_kernel_trains_in_xla():
    """Past ``MAX_FEATURES`` the kernels cannot hold the Gram; the fit
    takes the XLA form there (on a TPU too) and still matches the float64
    ridge fit of every leaf."""
    F = LL.MAX_FEATURES + 6
    rng = np.random.default_rng(12)
    X = rng.normal(size=(1500, F))
    X[rng.random(1500) < 0.05, 40] = np.nan
    y = np.where(X[:, 0] > 0, 2.0 * X[:, 1], -1.5 * X[:, 66]) + X[:, 40]
    y = np.nan_to_num(y) + rng.normal(scale=0.05, size=1500)
    bst = _train(X, y, _params("regression", 0.01), 3)
    gb = bst._gbdt
    assert gb.fused_ok and gb._linear_impl == "xla"
    score = np.full(len(y), float(gb._init_scores[0]))
    for t, tree in enumerate(_trees(bst)):
        g, h = _grads("regression", y, score)
        got = tree.predict(X)
        if t == 0:
            got = got - gb._init_scores[0]
            tree.leaf_value = tree.leaf_value - gb._init_scores[0]
        want = _reference_outputs(tree, X, g, h, 0.01, 0.3, first=t == 0)
        err = np.abs(got - want).max() / np.abs(want).max()
        assert err < 1e-4, (t, err)
        score = score + got
    np.testing.assert_allclose(gb.eval_scores(-1)[:, 0], score, atol=2e-5)
    assert any(max(f, default=0) >= LL.MAX_FEATURES
               for t in gb.models[1:] for f in t.leaf_features)


def test_the_kernels_give_way_to_xla_where_they_cannot_take_the_shape():
    rows = 10_502_144
    assert LL.choose_impl(True, 28, 256, [rows]) == ("pallas", "")
    impl, why = LL.choose_impl(True, LL.MAX_FEATURES + 1, 256, [rows])
    assert impl == "xla" and f"{LL.MAX_FEATURES + 1} features" in why
    # a validation set whose rows no block divides sends every set to XLA
    impl, why = LL.choose_impl(True, 28, 256, [rows, 1000])
    assert impl == "xla" and "1000 rows" in why
    assert LL.choose_impl(False, 28, 256, [rows]) == ("xla", "not a TPU")


def test_first_tree_keeps_constant_leaves():
    """is_first_tree: the model's first tree keeps every leaf constant;
    a continued model's next tree is not a first tree."""
    X, y = _data(seed=1)
    bst = _train(X, y, _params("regression", 0.0), 2)
    t0, t1 = bst._gbdt.models[:2]
    assert t0.is_linear and t1.is_linear
    assert all(not f for f in t0.leaf_features)
    np.testing.assert_array_equal(t0.leaf_const, t0.leaf_value)
    assert any(t1.leaf_features)
    more = _train(X, y, _params("regression", 0.0), 1, init_model=bst)
    assert any(more._gbdt.models[0].leaf_features)


def test_fused_gate_clears_under_linear_tree():
    X, y = _data(seed=2)
    bst = _train(X, y, _params("regression", 0.0), 1)
    gb = bst._gbdt
    assert gb._fused_gate_reason() == "" and gb.fused_ok
    assert not hasattr(gb, "_fit_linear_leaves")
    assert not hasattr(gb, "_linear_score_delta")
    assert gb.step_shape[phases.SHAPE_LINEAR_COLUMNS] == X.shape[1] + 1


def test_validation_scores_follow_the_linear_model():
    X, y = _data(seed=3, n=2000)
    tr = lgb.Dataset(X[:1500], label=y[:1500], free_raw_data=False)
    va = lgb.Dataset(X[1500:], label=y[1500:], reference=tr,
                     free_raw_data=False)
    bst = lgb.train(_params("regression", 0.01), tr, 4, valid_sets=[va],
                    valid_names=["v"])
    np.testing.assert_allclose(bst._gbdt.eval_scores(0)[:, 0],
                               bst.predict(X[1500:], raw_score=True),
                               atol=2e-5)


@pytest.mark.parametrize("fused", [True, False])
def test_multiclass_linear_leaves(fused):
    """K = 3: the class-batched build, each class's trees fitted on the
    device; the legacy loop runs the same device functions."""
    rng = np.random.default_rng(4)
    X = rng.normal(size=(900, 4))
    y = (np.digitize(X[:, 0] + 0.5 * X[:, 1], [-0.5, 0.5])).astype(float)
    params = dict(_params("multiclass", 0.0), num_class=3,
                  fused_train=fused)
    bst = _train(X, y, params, 3)
    gb = bst._gbdt
    assert gb.class_batch_ok and gb.fused_ok == fused
    assert len(gb.models) == 9
    np.testing.assert_allclose(gb.eval_scores(-1),
                               bst.predict(X, raw_score=True), atol=2e-5)
    assert any(any(t.leaf_features) for t in gb.models[3:])


def test_legacy_loop_grows_the_fused_model():
    """The same trees; the fit's float32 sums may round in another order
    where XLA fuses the one program otherwise (a last place)."""
    X, y = _data(seed=5)
    fused, legacy = (_train(X, y, _params("regression", 0.01,
                                          fused_train=f), 3)
                     for f in (True, False))
    assert not legacy._gbdt.fused_ok
    for a, b in zip(fused._gbdt.models, legacy._gbdt.models):
        np.testing.assert_array_equal(a.split_feature, b.split_feature)
        np.testing.assert_array_equal(a.threshold, b.threshold)
        np.testing.assert_array_equal(a.leaf_count, b.leaf_count)
        assert a.leaf_features == b.leaf_features
        np.testing.assert_allclose(a.leaf_const, b.leaf_const, rtol=1e-5,
                                   atol=1e-6)
    np.testing.assert_allclose(fused.predict(X), legacy.predict(X),
                               atol=1e-5)


def test_linear_model_text_round_trip():
    X, y = _data(seed=6)
    bst = _train(X, y, _params("regression", 0.0), 3)
    text = bst.model_to_string()
    again = lgb.Booster(model_str=text)
    assert _tree_text(again) == _tree_text(bst)
    np.testing.assert_array_equal(again.predict(X), bst.predict(X))
    block = text.split("Tree=1\n")[1].split("\n\n")[0]
    keys = [ln.split("=")[0] for ln in block.splitlines()]
    assert keys[-6:] == ["is_linear", "leaf_const", "num_features",
                         "leaf_features", "leaf_coeff", "shrinkage"]


def test_stage_work_counts_linear_leaves():
    X, y = _data(seed=7)
    bst = _train(X, y, _params("regression", 0.0), 3)
    gb = bst._gbdt
    work = gb.stage_work()
    rows = gb.step_shape[phases.SHAPE_ROWS]
    assert work[phases.LINEAR_UPDATE] == (3 * rows, phases.UNIT_ROWS)
    macs, feats = 0, 0
    for tree in gb.models:
        for s, f in _branch_features(tree).items():
            k = len(f)
            macs += int(tree.leaf_count[s]) * ((k + 1) * (k + 2) // 2 + k + 1)
            feats += k
    assert work[phases.LINEAR_GRAM] == (macs, phases.UNIT_GRAM_MACS)
    assert work[phases.LINEAR_SOLVE] == (feats, phases.UNIT_PATH_FEATURES)


def test_pallas_kernels_match_their_xla_form():
    """The three kernels in the Pallas interpreter against the segment
    sums of the XLA form, on a tree with NaN rows and a dropped leaf."""
    X, y = _data(seed=8, n=3000)
    bst = _train(X, y, _params("regression", 0.0, num_leaves=16), 1)
    gb = bst._gbdt
    g, h = gb._grads(0)
    mask = gb._full_row_mask()
    tree, row_leaf, _, _ = gb._build_one_tree(
        jnp.stack([g[0], h[0], mask], axis=1), gb._feature_mask(), 0)
    kw = dict(numerical=gb._numerical, lam=0.0, first=False)
    x = gb.train_dd.raw
    a = LL.fit_leaves(tree, x, row_leaf, g[0], h[0], mask, impl="xla", **kw)
    b = LL.fit_leaves(tree, x, row_leaf, g[0], h[0], mask, impl="pallas",
                      interpret=True, **kw)
    scale = float(jnp.abs(a.leaf_coeff).max())
    assert scale > 0
    np.testing.assert_allclose(b.leaf_coeff, a.leaf_coeff, atol=1e-4 * scale)
    np.testing.assert_allclose(b.leaf_const, a.leaf_const,
                               atol=1e-4 * float(jnp.abs(a.leaf_const).max()))
    ox = LL.leaf_outputs(a, x, row_leaf, impl="xla")
    op = LL.leaf_outputs(a, x, row_leaf, impl="pallas", interpret=True)
    np.testing.assert_allclose(op, ox, atol=1e-6 * float(jnp.abs(ox).max()))


def test_path_masks_follow_each_branch():
    X, y = _data(seed=9)
    bst = _train(X, y, _params("regression", 0.0, num_leaves=12), 2)
    gb = bst._gbdt
    g, h = gb._grads(1)
    tree, *_ = gb._build_one_tree(
        jnp.stack([g[0], h[0], gb._full_row_mask()], axis=1),
        gb._feature_mask(), 0)
    host = Tree.from_device(jax.tree.map(np.asarray, tree),
                            gb.train_set.bin_mappers,
                            gb.train_set.used_features, 1.0)
    masks = np.asarray(LL.path_masks(tree, gb._numerical, 128))
    for s, feats in _branch_features(host).items():
        assert np.flatnonzero(masks[s]).tolist() == feats
    assert not masks[host.num_leaves:].any()


def test_raw_values_lie_feature_major_beside_the_bins():
    X, y = _data(seed=10, n=700)
    bst = _train(X, y, _params("regression", 0.0), 1)
    dd = bst._gbdt.train_dd
    raw = np.asarray(dd.raw)
    assert raw.shape == (LL.padded_features(5), dd.r_pad)
    np.testing.assert_array_equal(raw[:5, :700], X.T.astype(np.float32))
    assert not raw[5:].any() and not raw[:, 700:].any()


def test_categorical_splits_add_no_path_feature():
    """A leaf's model takes the numerical features on its branch only
    (CalculateLinear keeps NumericalBin features)."""
    rng = np.random.default_rng(11)
    X = rng.normal(size=(2000, 4))
    X[:, 3] = rng.integers(0, 5, size=2000)
    y = np.where(X[:, 3] >= 2, 2.0 * X[:, 0], -X[:, 1]) + X[:, 3]
    ds = lgb.Dataset(X, label=y, categorical_feature=[3],
                     free_raw_data=False)
    bst = lgb.train(_params("regression", 0.0), ds, 3)
    gb = bst._gbdt
    assert any((t.decision_type & 1).any() for t in gb.models)
    assert all(3 not in f for t in gb.models for f in t.leaf_features)
    assert any(any(t.leaf_features) for t in gb.models[1:])
    np.testing.assert_allclose(gb.eval_scores(-1)[:, 0],
                               bst.predict(X, raw_score=True), atol=2e-5)
