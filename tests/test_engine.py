"""End-to-end training tests.

Models the reference's integration-test strategy
(tests/python_package_test/test_engine.py): train on small real datasets,
assert metric levels, round-trip models.
"""

import numpy as np
import pytest
from sklearn.datasets import load_breast_cancer, load_diabetes, load_iris
from sklearn.metrics import (accuracy_score, mean_squared_error,
                             roc_auc_score)
from sklearn.model_selection import train_test_split

import lightgbm_tpu as lgb


def _split(X, y, seed=42):
    return train_test_split(X, y, test_size=0.2, random_state=seed)


@pytest.fixture(scope="module")
def breast_cancer():
    X, y = load_breast_cancer(return_X_y=True)
    return _split(X, y)


@pytest.mark.slow
def test_binary_auc(breast_cancer):
    X_tr, X_te, y_tr, y_te = breast_cancer
    train = lgb.Dataset(X_tr, label=y_tr, free_raw_data=False)
    bst = lgb.train({"objective": "binary", "num_leaves": 15,
                     "learning_rate": 0.1, "verbosity": -1},
                    train, num_boost_round=50)
    pred = bst.predict(X_te)
    assert pred.min() >= 0 and pred.max() <= 1
    auc = roc_auc_score(y_te, pred)
    assert auc > 0.98, f"AUC too low: {auc}"
    # training accuracy should be very high
    pred_tr = bst.predict(X_tr)
    assert accuracy_score(y_tr, pred_tr > 0.5) > 0.98


@pytest.mark.slow
def test_regression_l2(rng):
    X, y = load_diabetes(return_X_y=True)
    X_tr, X_te, y_tr, y_te = _split(X, y)
    train = lgb.Dataset(X_tr, label=y_tr)
    bst = lgb.train({"objective": "regression", "num_leaves": 31,
                     "min_data_in_leaf": 5, "verbosity": -1},
                    train, num_boost_round=100)
    pred = bst.predict(X_te)
    base = mean_squared_error(y_te, np.full_like(y_te, y_tr.mean()))
    mse = mean_squared_error(y_te, pred)
    assert mse < 0.65 * base, f"MSE {mse} vs baseline {base}"


def test_multiclass(rng):
    X, y = load_iris(return_X_y=True)
    X_tr, X_te, y_tr, y_te = _split(X, y)
    train = lgb.Dataset(X_tr, label=y_tr)
    bst = lgb.train({"objective": "multiclass", "num_class": 3,
                     "num_leaves": 7, "min_data_in_leaf": 3,
                     "verbosity": -1}, train, num_boost_round=30)
    pred = bst.predict(X_te)
    assert pred.shape == (len(y_te), 3)
    np.testing.assert_allclose(pred.sum(axis=1), 1.0, atol=1e-5)
    acc = accuracy_score(y_te, pred.argmax(axis=1))
    assert acc > 0.9


@pytest.mark.slow
def test_early_stopping_and_valid(breast_cancer):
    X_tr, X_te, y_tr, y_te = breast_cancer
    train = lgb.Dataset(X_tr, label=y_tr)
    valid = lgb.Dataset(X_te, label=y_te, reference=train)
    record = {}
    bst = lgb.train({"objective": "binary", "num_leaves": 31,
                     "metric": ["binary_logloss", "auc"],
                     "verbosity": -1},
                    train, num_boost_round=500, valid_sets=[valid],
                    valid_names=["val"],
                    callbacks=[lgb.early_stopping(10, verbose=False),
                               lgb.record_evaluation(record)])
    assert bst.best_iteration > 0
    assert bst.best_iteration < 500
    assert "val" in record
    assert len(record["val"]["binary_logloss"]) >= bst.best_iteration


@pytest.mark.slow
def test_model_save_load_roundtrip(tmp_path, breast_cancer):
    X_tr, X_te, y_tr, y_te = breast_cancer
    train = lgb.Dataset(X_tr, label=y_tr)
    bst = lgb.train({"objective": "binary", "num_leaves": 15,
                     "verbosity": -1}, train, num_boost_round=20)
    pred = bst.predict(X_te)
    path = str(tmp_path / "model.txt")
    bst.save_model(path)
    bst2 = lgb.Booster(model_file=path)
    pred2 = bst2.predict(X_te)
    np.testing.assert_allclose(pred, pred2, rtol=1e-6)


def test_weights_change_model(breast_cancer):
    X_tr, X_te, y_tr, y_te = breast_cancer
    w = np.where(y_tr > 0, 10.0, 1.0)
    t1 = lgb.Dataset(X_tr, label=y_tr)
    t2 = lgb.Dataset(X_tr, label=y_tr, weight=w)
    p = {"objective": "binary", "num_leaves": 7, "verbosity": -1}
    b1 = lgb.train(p, t1, num_boost_round=10)
    b2 = lgb.train(p, t2, num_boost_round=10)
    p1, p2 = b1.predict(X_te), b2.predict(X_te)
    assert not np.allclose(p1, p2)
    assert p2.mean() > p1.mean()  # upweighted positives push probs up


@pytest.mark.slow
def test_custom_objective(breast_cancer):
    X_tr, X_te, y_tr, y_te = breast_cancer

    def logloss_obj(preds, dataset):
        y = dataset.get_label()
        p = 1.0 / (1.0 + np.exp(-preds))
        return p - y, p * (1 - p)

    train = lgb.Dataset(X_tr, label=y_tr)
    bst = lgb.train({"objective": "custom", "num_leaves": 15,
                     "verbosity": -1}, train, num_boost_round=30,
                    fobj=logloss_obj)
    raw = bst.predict(X_te, raw_score=True)
    auc = roc_auc_score(y_te, raw)
    assert auc > 0.97


@pytest.mark.slow
def test_bagging_and_feature_fraction(breast_cancer):
    X_tr, X_te, y_tr, y_te = breast_cancer
    train = lgb.Dataset(X_tr, label=y_tr)
    bst = lgb.train({"objective": "binary", "num_leaves": 15,
                     "bagging_fraction": 0.7, "bagging_freq": 1,
                     "feature_fraction": 0.7, "verbosity": -1},
                    train, num_boost_round=30)
    auc = roc_auc_score(y_te, bst.predict(X_te))
    assert auc > 0.97


@pytest.mark.slow
def test_goss(breast_cancer):
    X_tr, X_te, y_tr, y_te = breast_cancer
    train = lgb.Dataset(X_tr, label=y_tr)
    bst = lgb.train({"objective": "binary", "boosting": "goss",
                     "num_leaves": 15, "verbosity": -1},
                    train, num_boost_round=40)
    auc = roc_auc_score(y_te, bst.predict(X_te))
    assert auc > 0.97


@pytest.mark.slow
def test_exact_leafwise_matches_batched_reasonably(breast_cancer):
    """leaf_batch=1 (exact best-first) vs default batching: similar quality."""
    X_tr, X_te, y_tr, y_te = breast_cancer
    p = {"objective": "binary", "num_leaves": 15, "verbosity": -1}
    train1 = lgb.Dataset(X_tr, label=y_tr)
    b1 = lgb.train({**p, "leaf_batch": 1}, train1, num_boost_round=15)
    train2 = lgb.Dataset(X_tr, label=y_tr)
    b2 = lgb.train({**p, "leaf_batch": 8}, train2, num_boost_round=15)
    a1 = roc_auc_score(y_te, b1.predict(X_te))
    a2 = roc_auc_score(y_te, b2.predict(X_te))
    assert abs(a1 - a2) < 0.02


@pytest.mark.slow
def test_add_features_from(breast_cancer):
    """Dataset.add_features_from (Dataset::AddFeaturesFrom,
    dataset.cpp:1586): horizontal concat of two constructed datasets."""
    import numpy as np
    X, _, y, _ = breast_cancer
    half = X.shape[1] // 2
    dA = lgb.Dataset(X[:, :half], label=y).construct()
    dB = lgb.Dataset(X[:, half:],
                     params={"_allow_no_label": True}).construct()
    dA.add_features_from(dB)
    assert dA.num_features == X.shape[1]
    # colliding auto-names are deduplicated
    assert len(set(dA.feature_name)) == len(dA.feature_name)
    merged = lgb.train({"objective": "binary", "verbosity": -1,
                        "num_leaves": 15}, dA, 10)
    full = lgb.train({"objective": "binary", "verbosity": -1,
                      "num_leaves": 15}, lgb.Dataset(X, label=y), 10)
    from sklearn.metrics import roc_auc_score
    a_m = roc_auc_score(y, merged.predict(X))
    a_f = roc_auc_score(y, full.predict(X))
    assert a_m > a_f - 0.01, (a_m, a_f)
    # row-count mismatch is rejected
    import pytest as _pytest
    dC = lgb.Dataset(X[:100, half:],
                     params={"_allow_no_label": True}).construct()
    with _pytest.raises(ValueError, match="num_data"):
        dA.add_features_from(dC)


def _cache_rule(monkeypatch, backend, env_dir):
    """Run engine.enable_compilation_cache under a fake backend and
    return (returned dir, every jax.config.update call it made)."""
    import jax
    calls = []
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    return lgb.enable_compilation_cache(), calls


def test_compile_cache_env_dir_wins_and_nothing_is_set_in_code(
        monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: jax reads it itself; the program
    reports it and touches no config (on any backend)."""
    for backend in ("tpu", "cpu"):
        d, calls = _cache_rule(monkeypatch, backend, str(tmp_path))
        assert d == str(tmp_path) and calls == []


def test_compile_cache_defaults_to_checkout_xla_cache(monkeypatch):
    """Unset: <checkout>/.xla_cache on an accelerator — a fixed path
    beside the code, never ~ / a temp name / a pid — and off on CPU."""
    import os
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    d, calls = _cache_rule(monkeypatch, "tpu", None)
    assert d == os.path.join(repo, ".xla_cache")
    assert ("jax_compilation_cache_dir", d) in calls
    d, calls = _cache_rule(monkeypatch, "cpu", None)
    assert d is None and calls == []
