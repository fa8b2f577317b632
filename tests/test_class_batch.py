"""Class-batched tree construction (ISSUE 8): one build for all K
classes per iteration.

``class_batch=auto|on`` vmaps the whole tree build over the class axis
(boosting/tree_builder._build_tree_class_batched): per-class gradients
[K, R, 3] become batched loop-carried state and every histogram /
split-finding / partition kernel runs ONCE per round for all K classes.
``class_batch=off`` pins the sequential per-class loop — the reference
semantics (gbdt.cpp per-class tree loop) and the bit-parity oracle.

Required parity: scores, metrics and tree structure bit-identical
between the batched and sequential paths, on BOTH drivers (fused and
legacy), across multiclass x {plain, GOSS, bagging, quantized(+renew),
EFB}, serial and the 8-virtual-device data-parallel mesh under both
dp_hist_merge modes. Same 1-ulp split_gain caveat as fused-vs-legacy
(tests/test_fused_train.py): only recorded gains may move by float
noise, never a decision.

Trace discipline: the batched fused step stays ONE program per booster
(recompile guard), stages exactly ONE build-phase grow loop (the TD005
counter), and its equation count is independent of num_class.
"""

import contextlib
import os

import numpy as np
import pytest

import lightgbm_tpu as lgb


@contextlib.contextmanager
def _pin_fused(on: bool):
    prev = os.environ.get("LIGHTGBM_TPU_FUSED_TRAIN")
    os.environ["LIGHTGBM_TPU_FUSED_TRAIN"] = "1" if on else "0"
    try:
        yield
    finally:
        if prev is None:
            os.environ.pop("LIGHTGBM_TPU_FUSED_TRAIN", None)
        else:
            os.environ["LIGHTGBM_TPU_FUSED_TRAIN"] = prev


def _mc_data(seed=3, n=240, f=8, k=3):
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(n, f)).astype(np.float32)
    y = (X[:, :k] + 0.5 * rng.normal(size=(n, k))).argmax(1) \
        .astype(np.float32)
    return X, y


BASE = dict(objective="multiclass", num_class=3, metric="multi_logloss",
            num_leaves=5, learning_rate=0.2, min_data_in_leaf=5,
            verbosity=-1)

# satellite parity matrix: every sampling/binning mode that reorders or
# reweights the per-class gradient streams
CONFIGS = {
    "plain": {},
    "goss": dict(data_sample_strategy="goss", top_rate=0.3,
                 other_rate=0.3),
    "bagging": dict(bagging_fraction=0.6, bagging_freq=1,
                    bagging_seed=7),
    "quantized": dict(use_quantized_grad=True,
                      quant_train_renew_leaf=True),
    "efb": dict(enable_bundle=True),
}


def _train(params, rounds, fused, X, y):
    with _pin_fused(fused):
        ds = lgb.Dataset(X, label=y)
        rec = {}
        bst = lgb.train(dict(params), ds, num_boost_round=rounds,
                        valid_sets=[ds], valid_names=["v"],
                        callbacks=[lgb.record_evaluation(rec)])
        return bst, rec


def _model_lines(bst):
    # the knob itself is echoed into the serialized params block;
    # split_gain/tree_sizes carry the documented 1-ulp fused-context
    # caveat and are compared separately
    return [l for l in bst.model_to_string().splitlines()
            if not l.startswith(("split_gain", "tree_sizes",
                                 "[class_batch"))]


def _gains(bst):
    return [
        np.asarray([float(v) for v in l.split("=", 1)[1].split()])
        for l in bst.model_to_string().splitlines()
        if l.startswith("split_gain=")]


def _assert_pair(params, rounds=4, fused=True, data=None):
    X, y = data if data is not None else _mc_data()
    b_on, r_on = _train(dict(params, class_batch="on"), rounds, fused,
                        X, y)
    b_off, r_off = _train(dict(params, class_batch="off"), rounds,
                          fused, X, y)
    assert b_on._gbdt.class_batch_ok, b_on._gbdt.class_batch_reason
    assert not b_off._gbdt.class_batch_ok
    assert _model_lines(b_on) == _model_lines(b_off)
    for ga, gb in zip(_gains(b_on), _gains(b_off)):
        np.testing.assert_allclose(ga, gb, rtol=1e-4)
    assert np.array_equal(b_on._gbdt.eval_scores(-1),
                          b_off._gbdt.eval_scores(-1))
    assert r_on == r_off                 # eval-metric sequences, exact
    return b_on, b_off


@pytest.mark.slow
@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_batched_matches_sequential_fused(config):
    # tier-1 keeps the legacy-driver parity matrix plus the fused
    # cross-driver check below; each fused cell compiles two boosters
    # (>=15 s on the 1-core host) so the full fused matrix is slow-only
    _assert_pair(dict(BASE, **CONFIGS[config]), fused=True)


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_batched_matches_sequential_legacy(config):
    _assert_pair(dict(BASE, **CONFIGS[config]), fused=False)


def test_batched_fused_matches_sequential_legacy_cross_driver():
    """The strongest cross: fused + class-batched against the fully
    sequential legacy per-class loop."""
    X, y = _mc_data()
    bf, rf = _train(dict(BASE, class_batch="on"), 4, True, X, y)
    bl, rl = _train(dict(BASE, class_batch="off"), 4, False, X, y)
    assert bf._gbdt.fused_ok and bf._gbdt.class_batch_ok
    assert _model_lines(bf) == _model_lines(bl)
    assert rf == rl


@pytest.mark.slow
@pytest.mark.parametrize("merge", ["allreduce", "reduce_scatter"])
@pytest.mark.parametrize("learner", ["data", "voting"])
def test_batched_matches_sequential_on_mesh(learner, merge):
    """8-virtual-device mesh: the class axis rides through the
    shard_map build — histogram merge collectives batch over K in one
    collective — without perturbing a single decision."""
    import jax
    if len(jax.devices()) < 2:
        pytest.skip("single-device host")
    params = dict(BASE, tree_learner=learner, dp_hist_merge=merge)
    _assert_pair(params, rounds=3)


@pytest.mark.parametrize("learner", ["data"])
def test_batched_matches_sequential_on_mesh_legacy_driver(learner):
    import jax
    if len(jax.devices()) < 2:
        pytest.skip("single-device host")
    _assert_pair(dict(BASE, tree_learner=learner), rounds=3,
                 fused=False)


def test_gate_fallbacks():
    """Configs the batched build cannot express pin the sequential
    path (and say why) instead of failing."""
    X, y = _mc_data()
    for extra, frag in ((dict(cegb_penalty_split=0.1), "CEGB"),
                        (dict(class_batch="off"), "class_batch=off")):
        bst, _ = _train(dict(BASE, **extra), 2, False, X, y)
        gb = bst._gbdt
        assert not gb.class_batch_ok
        assert frag in gb.class_batch_reason
    # linear leaves fit on the device after the batched build
    bst, _ = _train(dict(BASE, linear_tree=True), 2, False, X, y)
    assert bst._gbdt.class_batch_ok
    # binary objective: one model per iteration, nothing to batch
    rng = np.random.RandomState(0)
    Xb = rng.normal(size=(120, 4)).astype(np.float32)
    yb = (Xb[:, 0] > 0).astype(np.float32)
    with _pin_fused(False):
        bst = lgb.train(dict(objective="binary", verbosity=-1,
                             num_leaves=4),
                        lgb.Dataset(Xb, label=yb), num_boost_round=2)
    assert not bst._gbdt.class_batch_ok


def test_env_pin_overrides_config():
    X, y = _mc_data()
    prev = os.environ.get("LIGHTGBM_TPU_CLASS_BATCH")
    try:
        os.environ["LIGHTGBM_TPU_CLASS_BATCH"] = "0"
        bst, _ = _train(dict(BASE, class_batch="on"), 2, False, X, y)
        assert not bst._gbdt.class_batch_ok
        assert "LIGHTGBM_TPU_CLASS_BATCH" in bst._gbdt.class_batch_reason
        os.environ["LIGHTGBM_TPU_CLASS_BATCH"] = "1"
        bst, _ = _train(dict(BASE, class_batch="off"), 2, False, X, y)
        assert bst._gbdt.class_batch_ok
    finally:
        if prev is None:
            os.environ.pop("LIGHTGBM_TPU_CLASS_BATCH", None)
        else:
            os.environ["LIGHTGBM_TPU_CLASS_BATCH"] = prev


def test_batched_fused_step_compiles_once_per_booster():
    """Class batching keeps the fused discipline: ONE compiled
    signature per booster, zero recompiles in steady state. Serial
    learner pinned: on a multi-device host the auto-selected mesh plan
    adds one extra first-dispatch signature (input shardings settle
    after the first call) for EVERY objective, batched or not — that
    pre-existing behavior is covered by the mesh steady-state test
    below."""
    from lightgbm_tpu.analysis import RecompileGuard
    from lightgbm_tpu.analysis.recompile_guard import cache_size
    X, y = _mc_data()
    bst, _ = _train(dict(BASE, class_batch="on",
                         tree_learner="serial"), 2, True, X, y)
    gb = bst._gbdt
    assert gb.fused_ok and gb.class_batch_ok
    assert gb._fused_jit is not None
    with _pin_fused(True):
        bst.update()
        gb.sync()
        with RecompileGuard(max_compiles=0, label="class_batch_steady"):
            for _ in range(8):
                bst.update()
            gb.sync()
    assert cache_size(gb._fused_jit) == 1


def test_batched_mesh_steady_state_no_recompiles():
    """On the data-parallel mesh the batched fused step still never
    recompiles once warm."""
    import jax
    from lightgbm_tpu.analysis import RecompileGuard
    if len(jax.devices()) < 2:
        pytest.skip("single-device host")
    X, y = _mc_data()
    bst, _ = _train(dict(BASE, class_batch="on", tree_learner="data"),
                    2, True, X, y)
    gb = bst._gbdt
    assert gb.fused_ok and gb.class_batch_ok
    with _pin_fused(True):
        bst.update()
        gb.sync()
        with RecompileGuard(max_compiles=0, label="cb_mesh_steady"):
            for _ in range(6):
                bst.update()
            gb.sync()


@pytest.mark.slow
def test_one_build_loop_and_k_independent_trace():
    """TD005's counting pass on the real fused program: the batched
    step stages exactly ONE build-phase grow loop, and its equation
    count does not scale with num_class (the unrolled shape is both
    K loops and ~K x the equations). Trace sizes being within a few
    percent across K is the compile-time bound in static form — a
    wall-clock ratio is a chip measurement, not a unit test on a
    shared host."""
    import jax
    from lightgbm_tpu.analysis.doctor import _fused_trace_args
    from lightgbm_tpu.analysis.jaxpr_lint import (count_build_loops,
                                                  iter_eqns)

    def trace_of(k, cb):
        X, y = _mc_data(k=max(k, 2), f=12)
        params = dict(BASE, num_class=k, class_batch=cb)
        if k == 1:
            params = dict(BASE, class_batch=cb)
            params.pop("num_class")
            params.update(objective="binary", metric="auc")
            y = (X[:, 0] > 0).astype(np.float32)
        bst, _ = _train(params, 1, True, X, y)
        gb = bst._gbdt
        closed = jax.make_jaxpr(gb._fused_step_entry)(
            *_fused_trace_args(gb))
        return (count_build_loops(closed.jaxpr),
                sum(1 for _ in iter_eqns(closed.jaxpr)))

    loops1, eqns1 = trace_of(1, "on")
    loops3, eqns3 = trace_of(3, "on")
    loops3_off, eqns3_off = trace_of(3, "off")
    assert loops1 == 1 and loops3 == 1
    assert loops3_off == 3
    # batched trace size is K-independent (tiny slack for the K-shaped
    # stack/unstack glue); unrolled grows ~K x
    assert eqns3 <= eqns1 * 1.1
    assert eqns3_off > 2 * eqns3
