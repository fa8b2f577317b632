"""Fused Pallas build+split kernel (ISSUE 14): interpret-mode bit
parity against the two-pass path, the class-batched vmap, the chunked
subtraction cache, and the GBDT-level gate."""

import functools as ft

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.ops import pallas_histogram as PH
from lightgbm_tpu.ops.split import (SplitParams, find_best_splits,
                                    monotone_penalty_factor)

R, F, B, L = 512, 8, 16, 6


@pytest.fixture
def interp(monkeypatch):
    """Route the Pallas kernels through the interpreter."""
    for name in ("fused_build_best_splits", "build_histograms_pallas",
                 "build_root_histograms_classes"):
        monkeypatch.setattr(PH, name,
                            ft.partial(getattr(PH, name),
                                       interpret=True))


def _stream(rng, quant=False, R=R, F=F, B=B, L=L):
    bins = rng.randint(0, B - 1, size=(R, F)).astype(np.uint8)
    bins[rng.rand(R) < 0.1, 2] = B - 1            # NaN bin rows (feat 2)
    rl = rng.randint(-1, L, size=R).astype(np.int32)
    if quant:
        gh = np.stack([rng.randint(-3, 4, size=R),
                       rng.randint(0, 5, size=R),
                       np.ones(R)], axis=1).astype(np.int8)
    else:
        g = rng.normal(size=R).astype(np.float32)
        gh = np.stack([g, np.abs(g) + 0.5, np.ones(R, np.float32)],
                      axis=1)
        gh[rl < 0] = 0.0
    lids = np.arange(L, dtype=np.int32)
    return (jnp.asarray(bins), jnp.asarray(gh), jnp.asarray(rl),
            jnp.asarray(lids))


_META = dict(
    num_bins_pf=jnp.full((F,), B, jnp.int32),
    nan_bin_pf=jnp.asarray(
        np.where(np.arange(F) == 2, B - 1, -1).astype(np.int32)),
    is_cat_pf=jnp.asarray(np.arange(F) == 5),      # one-hot categorical
)


def _assert_parity(best, oracle, extra=""):
    """Winner fields (integer / bool) must be bit-equal; float fields
    carry the documented 1-ulp XLA contraction variance between the
    in-kernel epilogue and the separately-jitted standalone scan (same
    drift class as eager-vs-jitted find_best_splits)."""
    for k in oracle:
        a, b = np.asarray(best[k]), np.asarray(oracle[k])
        if a.dtype.kind in "f":
            np.testing.assert_allclose(
                a, b, rtol=3e-6, atol=3e-6,
                err_msg=f"field {k!r} diverges {extra}")
        else:
            np.testing.assert_array_equal(
                a, b, err_msg=f"field {k!r} diverges {extra}")


@pytest.mark.parametrize("config",
                         ["plain", "mono_smooth", "quant"])
def test_fused_kernel_bit_parity(rng, interp, config):
    """Winners AND sums of the fused epilogue are bit-equal to the
    jitted find_best_splits scan over the same accumulator (plain /
    NaN / one-hot categorical always in the lattice; monotone +
    path-smooth and int8-quantized as parametrized gates)."""
    quant = config == "quant"
    bins, gh, rl, lids = _stream(rng, quant=quant)
    sp = SplitParams(min_data_in_leaf=5, min_sum_hessian_in_leaf=1e-3,
                     cat_smooth=10.0, cat_l2=10.0,
                     **({"path_smooth": 2.0, "monotone_penalty": 0.5}
                        if config == "mono_smooth" else {}))
    kw = dict(_META, feature_mask=jnp.ones((F,), bool))
    okw = dict(feature_mask=kw["feature_mask"])
    if config == "mono_smooth":
        mono = np.zeros(F, np.int32)
        mono[0], mono[3] = 1, -1
        depth = jnp.asarray(rng.randint(1, 4, size=L), jnp.int32)
        kw.update(mono_type=jnp.asarray(mono),
                  leaf_lo=jnp.full((L,), -2.0, jnp.float32),
                  leaf_hi=jnp.full((L,), 2.0, jnp.float32),
                  parent_output=jnp.asarray(
                      rng.normal(size=L).astype(np.float32)),
                  mono_pen=monotone_penalty_factor(
                      depth, sp.monotone_penalty))
        okw.update({k: kw[k] for k in ("mono_type", "leaf_lo",
                                       "leaf_hi", "parent_output")},
                   slot_depth=depth)
    if quant:
        # global (g_scale, h_scale) pair — the trainer's per-iteration
        # grid scales; the kernel broadcasts them across leaf slots
        qs = jnp.asarray([0.25, 0.5], jnp.float32)
        kw["quant_scales"] = okw["quant_scales"] = qs
    hist = PH.build_histograms_pallas(
        bins, gh, rl, lids, num_bins=B, hist_dtype="float32")
    oracle = jax.jit(lambda h: find_best_splits(
        h, _META["num_bins_pf"], _META["nan_bin_pf"],
        _META["is_cat_pf"], sp, **okw))(hist)
    best, hout = PH.fused_build_best_splits(
        bins, gh, rl, lids, num_bins=B, params=sp,
        hist_dtype="float32", emit_hist=True, **kw)
    _assert_parity(best, oracle, f"({config})")
    # emit mode: the histogram leaving the kernel is the two-pass one
    np.testing.assert_array_equal(np.asarray(hout), np.asarray(hist))
    # pure-mode slot totals == lattice totals of any single feature
    # (the kernel reports de-quantized totals: grid units x scale)
    want = np.asarray(hist[:, 0].sum(axis=1))
    if quant:
        want = want * np.asarray([0.25, 0.5, 1.0], np.float32)
    np.testing.assert_allclose(
        np.asarray(best["slot_totals"]), want, rtol=1e-5, atol=1e-5)


def test_fused_kernel_vmapped_classes(rng, interp):
    """vmap over the class axis (the class-batched multiclass build)
    == per-class serial launches, bit-for-bit."""
    K = 3
    bins, _, rl, lids = _stream(rng)
    gh_k = jnp.asarray(np.stack([
        np.asarray(_stream(rng)[1]) for _ in range(K)]))
    sp = SplitParams(min_data_in_leaf=5, min_sum_hessian_in_leaf=1e-3)

    def one(g):
        return PH.fused_build_best_splits(
            bins, g, rl, lids, num_bins=B, params=sp,
            hist_dtype="float32", **_META)[0]
    batched = jax.vmap(one)(gh_k)
    for k in range(K):
        single = one(gh_k[k])
        for key in single:
            np.testing.assert_array_equal(
                np.asarray(batched[key][k]), np.asarray(single[key]),
                err_msg=f"class {k} field {key!r}")


@pytest.mark.parametrize("hist_sub", [True, False])
def test_builder_fused_matches_two_pass(rng, interp, hist_sub):
    """Full-tree parity: build_tree with fused_split=True vs the
    two-pass pallas path, with the subtraction cache on and off.
    Structure (winners, row routing, leaf values) is bit-equal; gain
    carries the documented 1-ulp epilogue-vs-lattice contraction drift
    when the sibling accumulator comes from the subtraction cache."""
    from lightgbm_tpu.boosting.tree_builder import build_tree
    bins, gh, _, _ = _stream(rng, R=1024)
    sp = SplitParams(min_data_in_leaf=5, min_sum_hessian_in_leaf=1e-3,
                     cat_smooth=10.0, cat_l2=10.0)
    out = {}
    for fused in (True, False):
        t, rl_out, _, _rounds = build_tree(
            bins, gh, jnp.zeros((1024,), jnp.int32),
            _META["num_bins_pf"], _META["nan_bin_pf"],
            _META["is_cat_pf"], jnp.ones((F,), bool),
            num_leaves=15, leaf_batch=2, max_depth=-1, num_bins=B,
            split_params=sp, hist_dtype="float32", hist_impl="pallas",
            block_rows=256, hist_sub=hist_sub, fused_split=fused)
        out[fused] = (np.asarray(t.split_feature),
                      np.asarray(t.threshold_bin),
                      np.asarray(t.default_left),
                      np.asarray(t.leaf_values),
                      np.asarray(rl_out), np.asarray(t.gain))
    for a, b in zip(out[True][:-1], out[False][:-1]):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_allclose(out[True][-1], out[False][-1],
                               rtol=3e-6, atol=3e-6)


def test_builder_class_batched_fused(rng, interp):
    """Class-batched fused build (root histograms deduped over the
    shared bins operand, vmapped fused sweep) == per-class fused."""
    from lightgbm_tpu.boosting.tree_builder import build_tree
    K = 3
    bins, _, _, _ = _stream(rng)
    gh_k = jnp.asarray(np.stack([
        np.asarray(_stream(rng)[1]) for _ in range(K)]))
    sp = SplitParams(min_data_in_leaf=5, min_sum_hessian_in_leaf=1e-3)
    kw = dict(num_leaves=7, leaf_batch=2, max_depth=-1, num_bins=B,
              split_params=sp, hist_dtype="float32",
              hist_impl="pallas", block_rows=256, fused_split=True)
    meta = (_META["num_bins_pf"], _META["nan_bin_pf"],
            _META["is_cat_pf"], jnp.ones((F,), bool))
    tb, rlb, _, _rounds = build_tree(bins, gh_k, jnp.zeros((R,), jnp.int32),
                            *meta, class_batched=True, **kw)
    for k in range(K):
        t, rl_out, _, _rounds = build_tree(bins, gh_k[k],
                                  jnp.zeros((R,), jnp.int32),
                                  *meta, **kw)
        np.testing.assert_array_equal(np.asarray(tb.split_feature[k]),
                                      np.asarray(t.split_feature))
        np.testing.assert_array_equal(np.asarray(tb.threshold_bin[k]),
                                      np.asarray(t.threshold_bin))
        # structure is exact; leaf values carry the vmapped-vs-serial
        # 1-ulp contraction drift (same class as the epilogue drift)
        np.testing.assert_allclose(np.asarray(tb.leaf_values[k]),
                                   np.asarray(t.leaf_values),
                                   rtol=3e-6, atol=3e-6)
        np.testing.assert_array_equal(np.asarray(rlb[k]),
                                      np.asarray(rl_out))


def _tiny(rng, n=200, f=6):
    X = rng.normal(size=(n, f)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
    return X, y


def _train(rng, **overrides):
    X, y = _tiny(rng)
    # serial learner: the conftest 8-virtual-device mesh otherwise
    # auto-selects a parallel plan, which (correctly) closes the fused
    # gate — these tests exercise the single-chip builder path
    p = dict(objective="binary", num_leaves=7, min_data_in_leaf=5,
             verbosity=-1, tree_learner="serial")
    p.update(overrides)
    return lgb.train(p, lgb.Dataset(X, label=y), num_boost_round=2)


def test_gbdt_gate_reasons(rng, interp):
    """The eager fused-split gate names its binding reason: every
    epilogue-inexpressible config trips it, and auto stays closed with
    the TPU compiler's own reason (the epilogue has no Mosaic
    lowering; only fused_split=on, below, opens it)."""
    gb = _train(rng, fused_split="off",
                hist_impl="pallas")._gbdt
    assert not gb.fused_split_ok and "off" in gb.fused_split_reason
    gb = _train(rng, fused_split="on", hist_impl="scatter")._gbdt
    assert (not gb.fused_split_ok
            and "pallas" in gb.fused_split_reason.lower())
    gb = _train(rng, fused_split="on", hist_impl="pallas",
                extra_trees=True)._gbdt
    assert not gb.fused_split_ok
    # parallel plans merge full histograms -> gate closes
    gb = _train(rng, fused_split="on", hist_impl="pallas",
                tree_learner="data")._gbdt
    assert not gb.fused_split_ok and "parallel" in gb.fused_split_reason
    gb = _train(rng, fused_split="auto", hist_impl="pallas")._gbdt
    assert (not gb.fused_split_ok
            and gb.fused_split_reason == PH.FUSED_SPLIT_TPU_REASON)


def test_gbdt_gate_trust_mode(rng, interp):
    """fused_split="on" forces the kernel: with the interpreter patch
    the gate opens end to end (on a TPU the compile error surfaces)."""
    gb = _train(rng, fused_split="on", hist_impl="pallas")._gbdt
    assert gb.fused_split_ok and gb.fused_split_reason == ""


def test_gbdt_fused_end_to_end_parity(rng, interp):
    """Trained models match with the fused kernel pinned on vs off
    (float mode: bit-identical trees; split_gain stays out of the
    comparison — documented 1-ulp XLA contraction variance)."""
    X, y = _tiny(rng)        # ONE dataset — _train would redraw per call
    p = dict(objective="binary", num_leaves=7, min_data_in_leaf=5,
             verbosity=-1, tree_learner="serial", hist_impl="pallas",
             deterministic=True)
    outs = {}
    for fs in ("on", "off"):
        outs[fs] = lgb.train(dict(p, fused_split=fs),
                             lgb.Dataset(X, label=y),
                             num_boost_round=2)
    skip = ("split_gain", "tree_sizes", "[fused_split")
    lines = {fs: [ln for ln in b.model_to_string().splitlines()
                  if not ln.startswith(skip)]
             for fs, b in outs.items()}
    assert lines["on"] == lines["off"]
    X, _ = _tiny(rng)
    np.testing.assert_array_equal(outs["on"].predict(X),
                                  outs["off"].predict(X))


@pytest.mark.parametrize("quant", [False, True])
def test_chunked_subtraction_cache_parity(rng, quant):
    """Chunked out-of-core rounds with the parent-minus-child
    subtraction cache == full per-child rebuilds: exact in int32
    quantized mode and for the f32 serial accumulator."""
    X, y = _tiny(rng, n=900, f=6)
    p = dict(objective="binary", num_leaves=15, min_data_in_leaf=5,
             verbosity=-1, hist_impl="scatter", deterministic=True,
             tree_learner="serial",  # chunked driver needs a host plan
             out_of_core="on", chunk_budget_mb=0.05)
    if quant:
        p["use_quantized_grad"] = True
    preds = {}
    for sub in (True, False):
        ds = lgb.Dataset(X, label=y, params=dict(p))
        bst = lgb.train(dict(p, hist_subtraction=sub), ds,
                        num_boost_round=3)
        preds[sub] = bst.predict(X)
    np.testing.assert_array_equal(preds[True], preds[False])


@pytest.mark.slow
def test_trace_doctor_fused_split_clean():
    """The TD007 VMEM-residency lint: fused program stages no
    [.., F, B, 3] lattice; the two-pass negative control still does."""
    from lightgbm_tpu.analysis import doctor_fused_split
    reports = doctor_fused_split()
    assert all(r.ok for r in reports), [
        f.render() for r in reports for f in r.findings]
