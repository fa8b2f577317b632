"""Aux subsystems: logging, profiling hooks, plotting."""

import matplotlib
matplotlib.use("Agg")

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import log


@pytest.fixture
def booster(rng):
    X = rng.normal(size=(500, 6))
    y = X[:, 0] + (X[:, 1] > 0) + rng.normal(scale=0.1, size=500)
    evals = {}
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    vs = lgb.Dataset(X[:100], label=y[:100], reference=ds,
                     free_raw_data=False)
    bst = lgb.train({"objective": "regression", "num_leaves": 7,
                     "metric": ["l2", "l1"], "verbosity": -1},
                    ds, 8, valid_sets=[vs], valid_names=["v0"],
                    callbacks=[lgb.record_evaluation(evals)])
    bst._evals = evals
    return bst


class _Catcher:
    def __init__(self):
        self.lines = []

    def info(self, msg):
        self.lines.append(("info", msg))

    def warning(self, msg):
        self.lines.append(("warning", msg))


def test_register_logger_redirects():
    catcher = _Catcher()
    lgb.register_logger(catcher)
    try:
        log.set_verbosity(1)
        log.info("hello")
        log.warning("watch out")
        log.set_verbosity(-1)
        log.info("muted")
        with pytest.raises(RuntimeError, match="Fatal"):
            log.fatal("boom")
    finally:
        log._State.logger = None
        log.set_verbosity(1)
    assert ("info", "[LightGBM-TPU] [Info] hello") in catcher.lines
    assert any(lvl == "warning" for lvl, _ in catcher.lines)
    assert not any("muted" in m for _, m in catcher.lines)


def test_log_evaluation_respects_logger(rng):
    catcher = _Catcher()
    lgb.register_logger(catcher)
    try:
        X = rng.normal(size=(200, 3))
        y = (X[:, 0] > 0).astype(float)
        ds = lgb.Dataset(X, label=y, free_raw_data=False)
        vs = lgb.Dataset(X[:50], label=y[:50], reference=ds)
        lgb.train({"objective": "binary", "verbosity": -1,
                   "num_leaves": 4}, ds, 2, valid_sets=[vs],
                  callbacks=[lgb.log_evaluation(1)])
    finally:
        log._State.logger = None
        log.set_verbosity(1)
    assert any("binary_logloss" in m for _, m in catcher.lines)


def test_plot_importance(booster):
    ax = lgb.plot_importance(booster)
    assert len(ax.patches) > 0
    ax2 = lgb.plot_importance(booster, importance_type="gain",
                              max_num_features=3)
    assert len(ax2.patches) <= 3


def test_plot_metric(booster):
    ax = lgb.plot_metric(booster._evals)
    assert ax.get_ylabel() == "l2"
    ax2 = lgb.plot_metric(booster._evals, metric="l1")
    assert ax2.get_ylabel() == "l1"
    with pytest.raises(TypeError):
        lgb.plot_metric(booster)  # Booster keeps no history (reference)


def test_plot_split_value_histogram(booster):
    ax = lgb.plot_split_value_histogram(booster, 0)
    assert len(ax.patches) > 0
    with pytest.raises(ValueError):
        lgb.plot_split_value_histogram(booster, 5)  # likely unused feat


def test_tree_digraph_dot_source(booster):
    from lightgbm_tpu.plotting import _tree_to_dot
    dot = _tree_to_dot(booster._gbdt.models[0], booster.feature_name(),
                       show_info=("leaf_count", "split_gain"))
    assert dot.startswith("digraph Tree {")
    assert "split0" in dot and "leaf0" in dot
    # graphviz package is absent in this image: the public API must fail
    # with the reference's error message, not an AttributeError
    try:
        import graphviz  # noqa: F401
        has_gv = True
    except ImportError:
        has_gv = False
    if not has_gv:
        with pytest.raises(ImportError, match="graphviz"):
            lgb.create_tree_digraph(booster)


def test_profiler_annotations_smoke(booster, rng, tmp_path):
    import lightgbm_tpu.profiler as prof
    with prof.step_annotation("step", step_num=3):
        pass


def test_trees_to_dataframe_and_bounds(rng):
    """Booster.trees_to_dataframe / lower_bound / upper_bound /
    num_model_per_iteration (basic.py Booster surface)."""
    pd = pytest.importorskip("pandas")
    import lightgbm_tpu as lgb
    X = rng.normal(size=(800, 4))
    y = (X[:, 0] > 0).astype(float)
    bst = lgb.train({"objective": "binary", "num_leaves": 7,
                     "verbosity": -1},
                    lgb.Dataset(X, label=y, free_raw_data=False), 3)
    df = bst.trees_to_dataframe()
    assert set(df.columns) == {
        "tree_index", "node_depth", "node_index", "left_child",
        "right_child", "parent_index", "split_feature", "split_gain",
        "threshold", "decision_type", "missing_direction",
        "missing_type", "value", "weight", "count"}
    t0 = df[df.tree_index == 0]
    n_leaves = bst._all_trees()[0].num_leaves
    assert len(t0) == 2 * n_leaves - 1
    root = t0[t0.node_index == "0-S0"].iloc[0]
    assert pd.isna(root.parent_index) and root.node_depth == 1
    # every child named by an internal node exists
    names = set(t0.node_index)
    for _, r in t0.iterrows():
        if pd.notna(r.left_child):
            assert r.left_child in names and r.right_child in names
    # leaf counts per tree sum to the dataset size
    assert t0[t0.node_index.str.contains("-L")]["count"].sum() == 800
    # bounds bracket every prediction
    raw = bst.predict(X, raw_score=True)
    assert bst.lower_bound() <= raw.min() + 1e-9
    assert bst.upper_bound() >= raw.max() - 1e-9
    assert bst.num_model_per_iteration() == 1


def test_trees_to_dataframe_categorical_threshold(rng):
    """Categorical splits must show the category set ("0||2||..."), not
    the internal cat-storage index (same decoding as dump_model)."""
    pd = pytest.importorskip("pandas")
    import lightgbm_tpu as lgb
    c = rng.randint(0, 12, size=1200)
    means = rng.normal(size=12) * 2
    X = np.column_stack([c.astype(float), rng.normal(size=1200)])
    y = means[c] + 0.1 * rng.normal(size=1200)
    bst = lgb.train({"objective": "regression", "num_leaves": 7,
                     "verbosity": -1, "min_data_per_group": 5,
                     "min_data_in_leaf": 5},
                    lgb.Dataset(X, label=y, categorical_feature=[0],
                                free_raw_data=False), 3)
    df = bst.trees_to_dataframe()
    cat_rows = df[df.decision_type == "=="]
    assert len(cat_rows) > 0
    assert all("||" in str(t) or str(t).isdigit()
               for t in cat_rows.threshold)
