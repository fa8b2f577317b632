"""Histogram kernel vs NumPy oracle (dense_bin.hpp ConstructHistogram
semantics)."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.ops.histogram import (build_histograms,
                                        build_histograms_reference)


def _case(rng, R=512, F=5, B=16, L=3, pad=128):
    bins = rng.randint(0, B, size=(R, F)).astype(np.uint8)
    gh = np.stack([rng.normal(size=R), rng.uniform(0.1, 1, size=R),
                   np.ones(R)], axis=1).astype(np.float32)
    row_leaf = rng.randint(0, L + 1, size=R).astype(np.int32)  # leaf L unused
    # padding rows
    bins = np.concatenate([bins, np.zeros((pad, F), np.uint8)])
    gh = np.concatenate([gh, np.zeros((pad, 3), np.float32)])
    row_leaf = np.concatenate([row_leaf, np.full(pad, -1, np.int32)])
    leaf_ids = np.arange(L, dtype=np.int32)
    return bins, gh, row_leaf, leaf_ids


def test_matches_oracle(rng):
    bins, gh, row_leaf, leaf_ids = _case(rng)
    got = np.asarray(build_histograms(
        jnp.asarray(bins), jnp.asarray(gh), jnp.asarray(row_leaf),
        jnp.asarray(leaf_ids), num_bins=16, block_rows=128,
        hist_dtype="float32"))
    want = build_histograms_reference(bins, gh, row_leaf, leaf_ids, 16)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


def test_bfloat16_accumulation_close(rng):
    bins, gh, row_leaf, leaf_ids = _case(rng, R=4096, pad=0)
    got = np.asarray(build_histograms(
        jnp.asarray(bins), jnp.asarray(gh), jnp.asarray(row_leaf),
        jnp.asarray(leaf_ids), num_bins=16, block_rows=512,
        hist_dtype="bfloat16"))
    want = build_histograms_reference(bins, gh, row_leaf, leaf_ids, 16)
    # bf16 inputs, f32 accumulate: ~0.4% relative error budget
    np.testing.assert_allclose(got[..., 2], want[..., 2], atol=0.5)
    denom = np.abs(want[..., 0]) + 1.0
    assert (np.abs(got[..., 0] - want[..., 0]) / denom).max() < 0.02


def test_dummy_leaf_ids_match_nothing(rng):
    bins, gh, row_leaf, _ = _case(rng)
    leaf_ids = np.array([-2, 0, -2], np.int32)
    got = np.asarray(build_histograms(
        jnp.asarray(bins), jnp.asarray(gh), jnp.asarray(row_leaf),
        jnp.asarray(leaf_ids), num_bins=16, block_rows=128,
        hist_dtype="float32"))
    assert (got[0] == 0).all()
    assert (got[2] == 0).all()
    assert got[1].sum() > 0


def test_psum_merge_across_shards(rng):
    """Data-parallel histogram merge == single-device histogram
    (ReduceScatter semantics, data_parallel_tree_learner.cpp:284)."""
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map

    n_dev = len(jax.devices())
    assert n_dev == 8, "conftest should force 8 cpu devices"
    bins, gh, row_leaf, leaf_ids = _case(rng, R=1024, pad=0)
    mesh = Mesh(np.array(jax.devices()), ("data",))

    def local(b, g, rl):
        return build_histograms(b, g, rl, jnp.asarray(leaf_ids),
                                num_bins=16, block_rows=128,
                                axis_name="data", hist_dtype="float32")

    sharded = shard_map(
        local, mesh=mesh,
        in_specs=(P("data"), P("data"), P("data")),
        out_specs=P())  # replicated result
    got = np.asarray(sharded(jnp.asarray(bins), jnp.asarray(gh),
                             jnp.asarray(row_leaf)))
    want = build_histograms_reference(bins, gh, row_leaf, leaf_ids, 16)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


def test_scatter_matches_matmul(rng):
    """The CPU scatter-add path and the MXU matmul path are two
    lowerings of the same histogram; bf16 addend rounding included."""
    bins, gh, row_leaf, leaf_ids = _case(rng, R=700, F=7, B=13, L=4)
    kw = dict(num_bins=13, block_rows=0)
    for dt in ("float32", "bfloat16"):
        a = np.asarray(build_histograms(
            jnp.asarray(bins), jnp.asarray(gh), jnp.asarray(row_leaf),
            jnp.asarray(leaf_ids), hist_dtype=dt, impl="scatter", **kw))
        b = np.asarray(build_histograms(
            jnp.asarray(bins), jnp.asarray(gh), jnp.asarray(row_leaf),
            jnp.asarray(leaf_ids), hist_dtype=dt, impl="matmul", **kw))
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_pallas_interpret_matches_oracle(rng):
    """The Pallas TPU kernel (run through the interpreter on CPU) must
    reproduce the oracle exactly — the same kernel lowers to the MXU on
    real chips."""
    from lightgbm_tpu.ops.pallas_histogram import build_histograms_pallas
    bins, gh, row_leaf, leaf_ids = _case(rng, R=640, F=6, B=16, L=5)
    ref = build_histograms_reference(bins, gh, row_leaf, leaf_ids, 16)
    got = np.asarray(build_histograms_pallas(
        jnp.asarray(bins), jnp.asarray(gh), jnp.asarray(row_leaf),
        jnp.asarray(leaf_ids), num_bins=16, hist_dtype="float32",
        interpret=True))
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)
    # bf16 addend rounding agrees with the XLA matmul formulation
    xla = np.asarray(build_histograms(
        jnp.asarray(bins), jnp.asarray(gh), jnp.asarray(row_leaf),
        jnp.asarray(leaf_ids), num_bins=16, hist_dtype="bfloat16",
        impl="matmul"))
    pls = np.asarray(build_histograms_pallas(
        jnp.asarray(bins), jnp.asarray(gh), jnp.asarray(row_leaf),
        jnp.asarray(leaf_ids), num_bins=16, hist_dtype="bfloat16",
        interpret=True))
    np.testing.assert_allclose(pls, xla, rtol=1e-5, atol=1e-5)


class _Ref:
    """An array behind the slice of a kernel ref's surface the
    histogram kernel's helpers use."""

    def __init__(self, a):
        self.a = a
        self.shape = a.shape

    def __getitem__(self, idx):
        return self.a[idx]


def _prims(jp):
    """(primitive name, equation) of a jaxpr and every jaxpr under it
    (a ``pallas_call``'s body, a ``pl.when``'s branches)."""
    for e in jp.eqns:
        yield e.primitive.name, e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _prims(sub)


def test_pallas_kernel_body_uses_only_mosaic_safe_ops():
    """What real Mosaic on a v5e rejected in this kernel, in order of
    discovery: a lax.gather from a mixed newaxis+slice index, a 2-D ->
    3-D reshape of the addends, int8 vector multiplies, cumsum, and
    (PR 36) a stride-0 sublane load of a bin row ("the last dim size is
    not 128 in original base memref"). The body is now 2-D
    selects/compares/iotas/casts, ONE matmul, and one join: the one-hot's
    ``fc`` pieces of ``[Bp, blk]`` are concatenated along sublanes at
    offsets ``f * Bp`` in 32 bits, which Mosaic takes (``Bp`` is a
    multiple of 8, the 32-bit sublane tile; the same pieces narrowed
    first compile too but every register is repacked). This keeps it so
    on every CPU run (tests/test_mosaic_aot.py, slow, runs the real
    compiler)."""
    from lightgbm_tpu.ops import pallas_histogram as PH

    F, B, L = 28, 255, 21
    blk, fc, n_fb, Bp, lanes = PH._plan(F, B, 3 * L, 1)
    assert fc < F, "exercise a chunked plan"

    def body(bins, gh, leaf, cols):
        onehot = PH._onehot_t(_Ref(bins), Bp=Bp, cdt=jnp.int8)
        add = PH._slot_addends(_Ref(gh), _Ref(leaf), _Ref(cols))
        return onehot, add.astype(jnp.int8)

    jaxpr = jax.make_jaxpr(body)(
        jnp.zeros((fc, blk), jnp.int32), jnp.zeros((3, blk), jnp.int32),
        jnp.zeros((1, blk), jnp.int32), jnp.zeros((lanes, 2), jnp.int32))

    seen = list(_prims(jaxpr.jaxpr))
    names = {n for n, _ in seen}
    banned = {"gather", "reshape", "cumsum", "scatter", "scatter-add",
              "dynamic_slice", "dot_general"}
    assert not names & banned, sorted(names & banned)
    joins = [e for n, e in seen if n == "concatenate"]
    assert len(joins) == 1
    assert joins[0].params["dimension"] == 0
    for v in joins[0].invars:
        assert v.aval.shape == (Bp, blk) and v.aval.dtype.itemsize == 4, \
            "a join that is not sublane-aligned in 32 bits"
    for n, e in seen:
        if n == "mul":
            assert all(v.aval.dtype != jnp.int8 for v in e.invars), \
                "int8 vector multiply: v5e has no int8 VPU multiply"

    # the whole grid step holds one matmul: the 0/1 expansion that made
    # the one-hot on the MXU until PR 36 cannot come back unnoticed
    for quant in (False, True):
        blk_q, fc_q, n_fb_q, _, _ = PH._plan(F, B, 3 * L, 1 if quant else 2)
        r_pad = 2 * blk_q
        call = jax.make_jaxpr(
            lambda b, g, r, l, n: PH.build_histograms_pallas_lanes(
                b, g, r, l, n, num_features=F, num_bins=B))(
            jnp.zeros((n_fb_q, fc_q, r_pad), jnp.int32),
            jnp.zeros((3, r_pad), jnp.int32 if quant else jnp.float32),
            jnp.zeros((1, r_pad), jnp.int32), jnp.zeros((L,), jnp.int32),
            jnp.zeros((1,), jnp.int32))
        kernels = [e for n, e in _prims(call.jaxpr) if n == "pallas_call"]
        assert len(kernels) == 1
        inside = [n for n, _ in _prims(kernels[0].params["jaxpr"])]
        assert inside.count("dot_general") == 1, inside.count("dot_general")


# (F, B, slots, live slots, quantized): the four benchmark cells' plans
# (Epsilon's 2,000 columns cut to two of its 63 feature chunks), the
# root's lattice (2W slots, one live), int8 addends, and the least Bp
_PLAN_CASES = {
    "higgs": (28, 63, 16, 16, False),
    "epsilon": (64, 63, 16, 16, False),
    "msltr": (137, 63, 16, 16, False),
    "criteo": (67, 255, 16, 16, False),
    "root": (28, 63, 32, 1, False),
    "int8": (28, 63, 16, 16, True),
    "bp8": (3, 5, 2, 2, False),
}
# what _plan gave these shapes at the parent of PR 36 (bfloat16)
_CELL_PLANS = {
    "higgs": (28, (1152, 28, 1, 64, 128)),
    "epsilon": (2000, (1024, 32, 63, 64, 128)),
    "msltr": (137, (1152, 28, 5, 64, 128)),
    "criteo": (67, (1024, 8, 9, 256, 128)),
}


@pytest.mark.parametrize("case", list(_PLAN_CASES))
def test_pallas_onehot_and_histogram_are_exact_counts(case):
    """The one-hot is made on the VPU (PR 36). (1) The helper alone, at
    the case's plan: row ``f * Bp + b`` equals ``bins[f, r] == b`` at
    every (f, b, r), the padded bins ``B..Bp-1`` included (all zero).
    (2) The kernel in the interpreter with whole-number addends equals
    exact integer counts from numpy bit for bit: nothing is rounded, so
    any element of the one-hot that is not exactly 0 or 1, or sits in
    another row, shows."""
    from lightgbm_tpu.ops import pallas_histogram as PH
    F, B, L, n_live, quant = _PLAN_CASES[case]
    cdt = jnp.int8 if quant else jnp.bfloat16
    blk, fc, n_fb, Bp, lanes = PH._plan(F, B, 3 * L, 1 if quant else 2)
    if case in _CELL_PLANS:
        F_cell, plan = _CELL_PLANS[case]
        assert PH._plan(F_cell, B, 3 * L, 2) == plan
        assert (blk, fc, Bp, lanes) == plan[:2] + plan[3:]
    if case == "epsilon":
        assert n_fb == 2
    if case == "bp8":
        assert Bp == 8
    rng = np.random.RandomState(36)
    R = 2 * blk + 77
    bins = rng.randint(0, B, size=(R, F)).astype(np.uint8)
    bins[0, :] = B - 1
    bins[1, :] = 0

    block = np.zeros((fc, 256), np.int32)
    block[:min(fc, F)] = bins[:256, :fc].T
    onehot = np.asarray(PH._onehot_t(_Ref(jnp.asarray(block)), Bp=Bp,
                                     cdt=cdt))
    assert onehot.dtype == cdt and onehot.shape == (fc * Bp, 256)
    want = block[:, None, :] == np.arange(Bp)[None, :, None]
    assert np.array_equal(onehot.astype(np.int32).reshape(fc, Bp, 256),
                          want.astype(np.int32))
    assert not onehot.reshape(fc, Bp, 256)[:, B:].any()

    g = rng.randint(-100, 101, size=R)
    h = rng.randint(0, 101, size=R)
    gh = np.stack([g, h, np.ones(R, np.int64)], axis=1)
    leaf_ids = np.full(L, -2, np.int32)
    leaf_ids[:n_live] = np.arange(n_live)
    row_leaf = rng.randint(-1, n_live, size=R).astype(np.int32)
    got = np.asarray(PH.build_histograms_pallas(
        jnp.asarray(bins), jnp.asarray(gh.astype(np.int8 if quant
                                                 else np.float32)),
        jnp.asarray(row_leaf), jnp.asarray(leaf_ids), num_bins=B,
        interpret=True))
    want = build_histograms_reference(bins, gh, row_leaf, leaf_ids, B)
    want = want.astype(got.dtype)       # whole numbers under 2^24: exact
    assert got.dtype == (np.int32 if quant else np.float32)
    assert got.tobytes() == want.tobytes()
    assert not got[n_live:].any()


def test_pallas_dynamic_row_bound_skips_blocks(rng):
    """VERDICT r4 #3: with ``num_rows`` the kernel must never touch row
    blocks past ``ceil(num_rows / blk)``. Rows past the bound are
    POISONED — live leaf ids with huge gradients — so if any skipped
    block were processed the histogram would be visibly corrupt. (The
    trailing partial block is covered separately: inside it, rows past
    num_rows carry row_leaf == -1 per the caller contract.)"""
    from lightgbm_tpu.ops import pallas_histogram as PH
    F, B, L = 4, 16, 3
    blk = PH._plan(F, B, 3 * L, 4)[0]
    R = 3 * blk                       # three full blocks
    n_live = blk + 7                  # block 0 full + 7 rows of block 1
    bins = rng.randint(0, B, size=(R, F)).astype(np.uint8)
    gh = np.stack([rng.normal(size=R), rng.uniform(0.1, 1, size=R),
                   np.ones(R)], 1).astype(np.float32)
    row_leaf = rng.randint(0, L, size=R).astype(np.int32)
    # caller contract: within the trailing partial block, rows past
    # num_rows are dead
    row_leaf_in = row_leaf.copy()
    row_leaf_in[n_live:2 * blk] = -1
    # poison: block 2 is ENTIRELY past the bound and stays live+huge —
    # only the grid bound (not the leaf mask) protects against it
    gh_in = gh.copy()
    gh_in[2 * blk:] = 1e9
    leaf_ids = np.arange(L, dtype=np.int32)
    got = np.asarray(PH.build_histograms_pallas(
        jnp.asarray(bins), jnp.asarray(gh_in), jnp.asarray(row_leaf_in),
        jnp.asarray(leaf_ids), num_bins=B, hist_dtype="float32",
        interpret=True, num_rows=jnp.asarray(n_live, jnp.int32)))
    want = build_histograms_reference(
        bins[:n_live], gh[:n_live], row_leaf[:n_live], leaf_ids, B)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    # num_rows=0: empty histogram, accumulator still initialized
    got0 = np.asarray(PH.build_histograms_pallas(
        jnp.asarray(bins), jnp.asarray(gh_in),
        jnp.asarray(np.full(R, -1, np.int32)),
        jnp.asarray(leaf_ids), num_bins=B, hist_dtype="float32",
        interpret=True, num_rows=jnp.asarray(0, jnp.int32)))
    assert (got0 == 0).all()


def test_pallas_tree_with_subtraction_matches_scatter(rng, interp):
    """The full training path hist_impl=pallas + hist_subtraction runs
    the kernel over the COMPACTED dynamic row stream (row_gather +
    num_rows — VERDICT r4 #3's reachability: the same call
    tree_builder makes on TPU, here through the interpreter). Must grow
    the scatter tree. The lattice is wide enough (20 x 255) that no round
    takes the dense route (tests/test_dense_round.py has that route)."""
    from lightgbm_tpu.boosting.tree_builder import build_tree
    from lightgbm_tpu.ops.split import SplitParams
    R, F, B = 1024, 20, 255
    bins = rng.randint(0, B, size=(R, F)).astype(np.uint8)
    y = rng.normal(size=R)
    g = (y - y.mean()).astype(np.float32)
    gh = np.stack([g, np.ones(R, np.float32),
                   np.ones(R, np.float32)], axis=1)
    meta = dict(
        num_bins_pf=jnp.full((F,), B, jnp.int32),
        nan_bin_pf=jnp.full((F,), -1, jnp.int32),
        is_cat_pf=jnp.zeros((F,), bool),
        feature_mask=jnp.ones((F,), bool))
    sp = SplitParams(min_data_in_leaf=5, min_sum_hessian_in_leaf=1e-3)
    out = {}
    for impl in ("pallas", "scatter"):
        t, rl, _, _rounds = build_tree(
            jnp.asarray(bins), jnp.asarray(gh),
            jnp.zeros((R,), jnp.int32), meta["num_bins_pf"],
            meta["nan_bin_pf"], meta["is_cat_pf"], meta["feature_mask"],
            num_leaves=15, leaf_batch=2, max_depth=-1, num_bins=B,
            split_params=sp, hist_dtype="float32", hist_impl=impl,
            block_rows=256, hist_sub=True)
        assert not np.asarray(_rounds.dense).any()
        out[impl] = (np.asarray(t.split_feature),
                     np.asarray(t.threshold_bin), np.asarray(rl))
    np.testing.assert_array_equal(out["pallas"][0], out["scatter"][0])
    np.testing.assert_array_equal(out["pallas"][1], out["scatter"][1])
    np.testing.assert_array_equal(out["pallas"][2], out["scatter"][2])


def _interp_data(rng, case, n=400, f=6):
    """(X, y, Dataset kwargs) of one training case: a nonlinear binary
    task on dense columns; the cases that need another kind of column
    or label add it."""
    X = rng.normal(size=(n, f)).astype(np.float32)
    logit = X[:, 0] + 0.5 * X[:, 1] - 0.4 * X[:, 2] ** 2
    ds_kw = {}
    if case == "efb_bundled":
        # two one-hot groups: columns exclusive by construction
        blocks = []
        for _ in range(2):
            blk = np.zeros((n, 5), np.float32)
            blk[np.arange(n), rng.randint(0, 5, size=n)] = \
                rng.uniform(0.5, 2.0, size=n)
            blocks.append(blk)
        X = np.concatenate([X[:, :3]] + blocks, axis=1)
        logit = logit + 2 * blocks[0][:, 0] - blocks[0][:, 1]
    if case == "cat_sorted_subset":
        cat = rng.randint(0, 12, size=n)
        X[:, 3] = cat
        logit = logit + np.where(cat % 3 == 0, 1.0, -0.5)
        ds_kw["categorical_feature"] = [3]
    if case in ("multiclass_class_batch", "quant_multiclass"):
        y = np.digitize(logit, [-0.5, 0.5]).astype(np.float32)
    elif case == "lambdarank":
        y = np.digitize(logit, [-1.0, 0.0, 1.0]).astype(np.float32)
        ds_kw["group"] = [25] * (n // 25)
    else:
        y = (logit + 0.3 * rng.normal(size=n) > 0).astype(np.float32)
    return X, y, ds_kw


# case -> (train params, attribute of the GBDT that shows the case took
# hold). The seven from efb_bundled on are the single-device
# configurations whose split search needs the whole histogram in HBM.
_INTERP_CASES = {
    "plain": ({}, None),
    "mono_smooth": ({"monotone_constraints": [1, -1, 0, 0, 0, 0],
                     "path_smooth": 2.0, "monotone_penalty": 0.5},
                    "mono_type_pf"),
    "quant": ({"use_quantized_grad": True}, None),
    "multiclass_class_batch": ({"objective": "multiclass", "num_class": 3},
                               "class_batch_ok"),
    "hist_sub_off": ({"hist_subtraction": False}, None),
    "leaf_batch_1": ({"leaf_batch": 1}, None),
    "efb_bundled": ({"enable_bundle": True}, "_bundle_meta"),
    "extra_trees": ({"extra_trees": True}, None),
    "forced_splits": ({}, "_forced_splits"),
    "cegb": ({"cegb_penalty_split": 0.01}, "_cegb"),
    "feature_contri": ({"feature_contri": [1.0, 0.5, 1.0, 0.2, 1.0, 1.0]},
                       "_gain_scale"),
    "cat_sorted_subset": ({"max_cat_to_onehot": 4, "min_data_per_group": 5,
                           "cat_smooth": 1.0}, "_cat_sorted_mask"),
    "mono_advanced": ({"monotone_constraints": [1, -1, 0, 0, 0, 0],
                       "monotone_constraints_method": "advanced"},
                      "mono_type_pf"),
    # int8 addends through the class-batched root kernel, per-row g and
    # h of a ranking objective, and an in-bag count channel that is not
    # all ones
    "quant_multiclass": ({"objective": "multiclass", "num_class": 3,
                          "use_quantized_grad": True}, "class_batch_ok"),
    "lambdarank": ({"objective": "lambdarank"}, None),
    "bagging": ({"bagging_fraction": 0.6, "bagging_freq": 1}, None),
}


@pytest.mark.parametrize("case", list(_INTERP_CASES))
def test_pallas_interpret_training_matches_scatter(rng, interp, monkeypatch,
                                                   tmp_path, case):
    """A whole ``lgb.train`` with ``hist_impl=pallas`` (the chip's
    kernels, here through the interpreter: the compacted stream, the
    class-batched root kernel, int8 addends) grows the trees
    ``hist_impl=scatter`` grows, in every configuration of the split
    search: same split features, threshold bins and leaf counts,
    predictions to 1e-6 and equal where the addends are integers."""
    import lightgbm_tpu as lgb
    over, took_hold = _INTERP_CASES[case]
    X, y, ds_kw = _interp_data(rng, case)
    params = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
              "verbosity": -1, "tree_learner": "serial", "max_bin": 15,
              "hist_dtype": "float32", "deterministic": True, **over}
    if case == "forced_splits":
        forced = tmp_path / "forced.json"
        forced.write_text(json.dumps({
            "feature": 2, "threshold": 0.0,
            "left": {"feature": 0, "threshold": -0.5}}))
        params["forcedsplits_filename"] = str(forced)
    root_calls = []
    if "multiclass" in case:
        kernel = interp.build_root_histograms_classes

        def counted(*a, **kw):
            root_calls.append(1)
            return kernel(*a, **kw)
        monkeypatch.setattr(interp, "build_root_histograms_classes", counted)
    out = {}
    for impl in ("pallas", "scatter"):
        bst = lgb.train(dict(params, hist_impl=impl),
                        lgb.Dataset(X, label=y, **ds_kw),
                        num_boost_round=3)
        gb = bst._gbdt
        assert gb.config.hist_impl == impl
        if took_hold is not None:
            held = getattr(gb, took_hold)
            assert held is not None and held is not False, took_hold
        out[impl] = (bst._all_trees(), bst.predict(X))
    if "multiclass" in case:
        assert root_calls, "the class-batched root kernel never ran"
    trees_p, pred_p = out["pallas"]
    trees_s, pred_s = out["scatter"]
    assert len(trees_p) == len(trees_s) == 3 * params.get("num_class", 1)
    assert any(t.num_leaves > 2 for t in trees_p)
    for tp, ts in zip(trees_p, trees_s):
        for field in ("split_feature", "threshold_bin", "leaf_count"):
            np.testing.assert_array_equal(
                getattr(tp, field), getattr(ts, field), err_msg=field)
    if "quant" in case:
        np.testing.assert_array_equal(pred_p, pred_s)
    else:
        np.testing.assert_allclose(pred_p, pred_s, rtol=0, atol=1e-6)


def test_pallas_interpret_training_takes_the_dense_route(rng, interp):
    """The sibling of the test above on a lattice where the dense route
    engages (8 x 255: ``tree_builder.dense_round_rows``): a whole
    ``lgb.train`` with ``hist_impl=pallas`` sends some rounds to one
    kernel pass over all rows and the others through the compacted
    stream (``round_log[i].dense``), and grows the trees
    ``hist_impl=scatter`` grows."""
    import lightgbm_tpu as lgb
    # enough rows that the rows the Dataset pads to (16,384) leave the
    # first rounds' smaller children over the threshold
    X, y, _ = _interp_data(rng, "plain", n=12000, f=8)
    params = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
              "verbosity": -1, "tree_learner": "serial", "max_bin": 255,
              "hist_dtype": "float32", "deterministic": True}
    out = {}
    for impl in ("pallas", "scatter"):
        bst = lgb.train(dict(params, hist_impl=impl),
                        lgb.Dataset(X, label=y), num_boost_round=3)
        out[impl] = (bst._all_trees(), bst.predict(X), bst._gbdt)
    gb = out["pallas"][2]
    assert gb._step_shape().dense_round_rows > 0
    assert out["scatter"][2]._step_shape().dense_round_rows == 0
    routes = np.concatenate([rec.dense[rec.leaves > 0]
                             for rec in gb.round_log])
    assert routes.any() and not routes.all()
    for tp, ts in zip(out["pallas"][0], out["scatter"][0]):
        for field in ("split_feature", "threshold_bin", "leaf_count"):
            np.testing.assert_array_equal(
                getattr(tp, field), getattr(ts, field), err_msg=field)
    np.testing.assert_allclose(out["pallas"][1], out["scatter"][1],
                               rtol=0, atol=1e-6)


@pytest.mark.parametrize("backend,impl,num_bins,want", [
    ("tpu", "auto", 63, "pallas"),
    ("tpu", "auto", 256, "pallas"),
    ("tpu", "auto", 257, "matmul"),     # by rule, with a named reason
    ("tpu", "matmul", 63, "matmul"),    # explicit choices pass through
    ("tpu", "pallas", 1024, "pallas"),  # ... and raise at the kernel
    ("gpu", "auto", 63, "matmul"),
    ("cpu", "pallas", 63, "pallas"),
])
def test_resolve_impl_rule_table(monkeypatch, backend, impl, num_bins,
                                 want):
    """hist_impl=auto resolves from backend and lattice width alone —
    no probe compile, nothing cached, nothing caught."""
    from lightgbm_tpu.ops import histogram as H
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert H.resolve_impl(impl, num_bins) == want
    assert bool(H.pallas_shape_reason(num_bins)) == (num_bins > 256)


@pytest.mark.parametrize("knob", ["fused_split", "LIGHTGBM_TPU_FUSED_SPLIT"])
def test_removed_selection_knob(rng, interp, monkeypatch, knob):
    """There is one split search, so nothing selects one: the key is
    not a registered parameter (it raises as any unknown key does), a
    GBDT carries no reason for it, and the variable is read nowhere: a
    training with it set is the training without it."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.config import PARAMS
    X, y, _ = _interp_data(rng, "plain", n=200)
    params = dict(objective="binary", num_leaves=7, min_data_in_leaf=5,
                  verbosity=-1, tree_learner="serial", max_bin=15,
                  hist_impl="pallas", hist_dtype="float32")

    def train(**over):
        return lgb.train(dict(params, **over), lgb.Dataset(X, label=y),
                         num_boost_round=2)
    base = train()
    assert not hasattr(base._gbdt, "fused_split_reason")
    if knob == "fused_split":
        assert knob not in PARAMS
        with pytest.raises(ValueError, match="Unknown parameter: fused_split"):
            train(fused_split="on")
    else:
        monkeypatch.setenv(knob, "1")
        assert train().model_to_string() == base.model_to_string()


def test_pallas_unsupported_shape_raises(rng):
    """An explicit hist_impl=pallas past the kernel's lattice width
    raises the rule's reason instead of degrading."""
    bins, gh, row_leaf, leaf_ids = _case(rng, R=256, F=3, B=16, L=2)
    with pytest.raises(ValueError, match="num_bins=300 > 256"):
        build_histograms(
            jnp.asarray(bins), jnp.asarray(gh), jnp.asarray(row_leaf),
            jnp.asarray(leaf_ids), num_bins=300, impl="pallas")


def test_auto_impl_cpu_prefers_native(monkeypatch):
    """auto on CPU: the runtime-compiled C kernel when a toolchain
    exists, XLA scatter otherwise."""
    from lightgbm_tpu import native as N
    from lightgbm_tpu.ops import histogram as H
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    want = "native" if N.hist_lib() is not None else "scatter"
    assert H.resolve_impl("auto", 63) == want
    monkeypatch.setattr(N, "hist_lib", lambda: None)
    assert H.resolve_impl("auto", 63) == "scatter"


def test_native_matches_scatter(rng):
    """The C histogram kernel (native/hist.c) is bit-identical to the
    XLA scatter path: same skip rules, same bf16 addend rounding, exact
    int32 accumulation when quantized, and the compacted dynamic row
    stream (row_gather + num_rows over UNCOMPACTED gh / row_leaf: the
    wrapper compacts them ahead of the FFI call) honored."""
    pytest.importorskip("ctypes")
    from lightgbm_tpu import native as N
    if N.hist_lib() is None:
        pytest.skip("native toolchain unavailable")
    bins, gh, row_leaf, leaf_ids = _case(rng, R=700, F=7, B=13, L=4)
    kw = dict(num_bins=13, block_rows=0)
    for dt in ("float32", "bfloat16"):
        a = np.asarray(build_histograms(
            jnp.asarray(bins), jnp.asarray(gh), jnp.asarray(row_leaf),
            jnp.asarray(leaf_ids), hist_dtype=dt, impl="native", **kw))
        b = np.asarray(build_histograms(
            jnp.asarray(bins), jnp.asarray(gh), jnp.asarray(row_leaf),
            jnp.asarray(leaf_ids), hist_dtype=dt, impl="scatter", **kw))
        np.testing.assert_array_equal(a, b)
    # quantized: int8 addends accumulate exactly into int32
    gh8 = np.random.RandomState(5).randint(
        -100, 100, size=gh.shape).astype(np.int8)
    gh8[row_leaf < 0] = 0
    a = np.asarray(build_histograms(
        jnp.asarray(bins), jnp.asarray(gh8), jnp.asarray(row_leaf),
        jnp.asarray(leaf_ids), impl="native", **kw))
    b = np.asarray(build_histograms(
        jnp.asarray(bins), jnp.asarray(gh8), jnp.asarray(row_leaf),
        jnp.asarray(leaf_ids), impl="scatter", **kw))
    assert a.dtype == np.int32
    np.testing.assert_array_equal(a, b)
    # compacted dynamic row stream: only leaf 1's rows are streamed
    R = len(row_leaf)
    m = row_leaf == 1
    n_small = int(m.sum())
    pos = np.cumsum(m) - 1
    c_idx = np.zeros(R, np.int32)
    c_idx[pos[m]] = np.arange(R, dtype=np.int32)[m]
    got = np.asarray(build_histograms(
        jnp.asarray(bins), jnp.asarray(gh), jnp.asarray(row_leaf),
        jnp.asarray(leaf_ids), hist_dtype="float32", impl="native",
        row_gather=jnp.asarray(c_idx),
        num_rows=jnp.asarray(n_small, jnp.int32), **kw))
    want = build_histograms_reference(bins, gh, row_leaf, leaf_ids, 13)
    np.testing.assert_allclose(got[1], want[1], rtol=1e-5, atol=1e-4)
    assert (got[0] == 0).all() and (got[2] == 0).all()


def test_native_tree_matches_scatter_tree(rng):
    """Growing a whole tree with hist_impl=native (the FFI partition +
    perm-histogram path, incl. the column-major bins copy) reproduces
    the scatter tree bit-for-bit in routing: same splits, same row
    partition, matching leaf values. Covers NaN-bin routing, a
    categorical bitset split, padded rows and zeroed-gh (bagged) rows."""
    from lightgbm_tpu import native as N
    if N.hist_lib() is None:
        pytest.skip("native toolchain unavailable")
    from lightgbm_tpu.boosting.tree_builder import build_tree
    from lightgbm_tpu.ops.split import SplitParams
    R, F, B, pad = 2048, 8, 32, 64
    bins = rng.randint(0, B - 1, size=(R, F)).astype(np.uint8)
    # feature 2 carries a NaN bin (last); ~10% of its rows are missing
    bins[rng.rand(R) < 0.1, 2] = B - 1
    # feature 5 is categorical
    y = rng.normal(size=R) + (bins[:, 5] % 3 == 0) * 2.0 \
        + (bins[:, 2] == B - 1) * 1.5
    g = (y - y.mean()).astype(np.float32)
    gh = np.stack([g, np.ones(R, np.float32),
                   np.ones(R, np.float32)], axis=1)
    gh[rng.rand(R) < 0.2] = 0.0          # "bagged-out" rows
    bins = np.concatenate([bins, np.zeros((pad, F), np.uint8)])
    gh = np.concatenate([gh, np.zeros((pad, 3), np.float32)])
    rl0 = np.concatenate([np.zeros(R, np.int32),
                          np.full(pad, -1, np.int32)])
    nan_bin = np.full((F,), -1, np.int32)
    nan_bin[2] = B - 1
    is_cat = np.zeros((F,), bool)
    is_cat[5] = True
    meta = dict(
        num_bins_pf=jnp.full((F,), B, jnp.int32),
        nan_bin_pf=jnp.asarray(nan_bin),
        is_cat_pf=jnp.asarray(is_cat),
        feature_mask=jnp.ones((F,), bool))
    sp = SplitParams(min_data_in_leaf=5, min_sum_hessian_in_leaf=1e-3,
                     cat_smooth=10.0, cat_l2=10.0)
    out = {}
    for impl in ("native", "scatter"):
        kw = {}
        if impl == "native":
            kw["bins_cm"] = jnp.asarray(bins.T)
        t, rl, _, _rounds = build_tree(
            jnp.asarray(bins), jnp.asarray(gh),
            jnp.asarray(rl0), meta["num_bins_pf"],
            meta["nan_bin_pf"], meta["is_cat_pf"], meta["feature_mask"],
            num_leaves=31, leaf_batch=4, max_depth=-1, num_bins=B,
            split_params=sp, hist_dtype="float32", hist_impl=impl,
            block_rows=256, hist_sub=True, **kw)
        out[impl] = (np.asarray(t.split_feature),
                     np.asarray(t.threshold_bin),
                     np.asarray(t.leaf_values), np.asarray(rl),
                     np.asarray(t.is_cat).sum())
    np.testing.assert_array_equal(out["native"][0], out["scatter"][0])
    np.testing.assert_array_equal(out["native"][1], out["scatter"][1])
    np.testing.assert_allclose(out["native"][2], out["scatter"][2],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(out["native"][3], out["scatter"][3])
    assert out["native"][4] > 0, "test should exercise a categorical split"


def test_subtraction_tree_matches_direct(rng):
    """hist_sub=True (smaller-child + parent-minus-child subtraction
    over a compacted dynamic row stream) must grow the same tree as the
    both-children-direct path (float32 hist: subtraction differs only
    by f32 associativity)."""
    import jax.numpy as jnp
    from lightgbm_tpu.boosting.tree_builder import build_tree
    from lightgbm_tpu.ops.split import SplitParams

    R, F, B = 2048, 8, 32
    bins = rng.randint(0, B, size=(R, F)).astype(np.uint8)
    y = rng.normal(size=R)
    g = (y - y.mean()).astype(np.float32)
    gh = np.stack([g, np.ones(R, np.float32),
                   np.ones(R, np.float32)], axis=1)
    meta = dict(
        num_bins_pf=jnp.full((F,), B, jnp.int32),
        nan_bin_pf=jnp.full((F,), -1, jnp.int32),
        is_cat_pf=jnp.zeros((F,), bool),
        feature_mask=jnp.ones((F,), bool))
    sp = SplitParams(min_data_in_leaf=5, min_sum_hessian_in_leaf=1e-3)
    trees = {}
    for sub in (True, False):
        t, rl, _, _rounds = build_tree(
            jnp.asarray(bins), jnp.asarray(gh),
            jnp.zeros((R,), jnp.int32), meta["num_bins_pf"],
            meta["nan_bin_pf"], meta["is_cat_pf"], meta["feature_mask"],
            num_leaves=31, leaf_batch=4, max_depth=-1, num_bins=B,
            split_params=sp, hist_dtype="float32", hist_impl="scatter",
            block_rows=256, hist_sub=sub)
        trees[sub] = (np.asarray(t.split_feature), np.asarray(t.threshold_bin),
                      np.asarray(t.leaf_values), np.asarray(rl))
    np.testing.assert_array_equal(trees[True][0], trees[False][0])
    np.testing.assert_array_equal(trees[True][1], trees[False][1])
    np.testing.assert_allclose(trees[True][2], trees[False][2],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(trees[True][3], trees[False][3])


def test_native_perm_kernel_threaded_matches_serial(rng, monkeypatch):
    """The partition-ordered histogram kernel parallelizes over
    (slot, row-range) chunks with per-thread scratches. Quantized int8
    accumulation is EXACT (order-free), so any thread count must be
    bit-identical; f32 differs only by addend association, so serial vs
    8 threads must agree to float tolerance."""
    from lightgbm_tpu import native as N
    if N.hist_lib() is None:
        pytest.skip("native toolchain unavailable")
    # R above the kernel's 2^18-row serial cutoff so the 8-thread run
    # actually takes the parallel path
    R, F, B, S = 600_000, 6, 16, 3
    bins = rng.randint(0, B, size=(R, F)).astype(np.uint8)
    # segment layout: a permutation split into S contiguous leaf runs
    perm = rng.permutation(R).astype(np.int32)
    begin = np.asarray([0, R // 2, 3 * R // 4], np.int32)
    cnt = np.asarray([R // 2, R // 4, R - 3 * R // 4], np.int32)
    lids = np.arange(S, dtype=np.int32)

    def run(gh):
        out_dt = jnp.int32 if gh.dtype == np.int8 else jnp.float32
        target = ("lgbtpu_hist_perm_i8" if gh.dtype == np.int8
                  else "lgbtpu_hist_perm_f32")
        return np.asarray(jax.ffi.ffi_call(
            target, jax.ShapeDtypeStruct((S, F, B, 3), out_dt))(
            jnp.asarray(bins), jnp.asarray(gh), jnp.asarray(perm),
            jnp.asarray(begin), jnp.asarray(cnt), jnp.asarray(lids),
            bf16_round=False))

    ghf = np.stack([rng.normal(size=R), rng.uniform(0.1, 1, size=R),
                    np.ones(R)], 1).astype(np.float32)
    ghq = rng.randint(-100, 100, size=(R, 3)).astype(np.int8)

    monkeypatch.setenv("LIGHTGBM_TPU_NUM_THREADS", "1")
    f_serial, q_serial = run(ghf), run(ghq)
    monkeypatch.setenv("LIGHTGBM_TPU_NUM_THREADS", "8")
    f_par, q_par = run(ghf), run(ghq)

    np.testing.assert_array_equal(q_serial, q_par)   # int32: exact
    np.testing.assert_allclose(f_serial, f_par, rtol=1e-5, atol=1e-3)
    # and the serial result is itself correct vs the numpy oracle
    row_leaf = np.full(R, -1, np.int32)
    for s in range(S):
        row_leaf[perm[begin[s]:begin[s] + cnt[s]]] = s
    want = build_histograms_reference(bins, ghf, row_leaf, lids, B)
    np.testing.assert_allclose(f_serial, want, rtol=1e-4, atol=1e-2)


# ---------------------------------------------------------------------------
# The compacted stream's contract (row_gather + num_rows): bins, gh and
# row_leaf arrive UNCOMPACTED, position p reads row row_gather[p], positions
# at or past num_rows are dead, and nothing past the live prefix is consumed.
# ---------------------------------------------------------------------------

_STREAM_F, _STREAM_B, _STREAM_L = 8, 256, 3


def _stream_blk(impl):
    """Row block of ``impl``'s bounded loop at the contract test's
    shape (the kernel's for pallas; native has none, any will do)."""
    from lightgbm_tpu.ops import pallas_histogram as PH
    if impl == "pallas":
        return PH._plan(_STREAM_F, _STREAM_B, 3 * _STREAM_L, 4)[0]
    return 256


def _stream_call(impl, bins, gh, row_leaf, leaf_ids, row_gather, num_rows):
    from lightgbm_tpu.ops import pallas_histogram as PH
    args = (jnp.asarray(bins), jnp.asarray(gh), jnp.asarray(row_leaf),
            jnp.asarray(leaf_ids))
    kw = dict(num_bins=_STREAM_B, hist_dtype="float32",
              row_gather=jnp.asarray(row_gather),
              num_rows=jnp.asarray(num_rows, jnp.int32))
    if impl == "pallas":
        return np.asarray(PH.build_histograms_pallas(
            *args, interpret=True, **kw))
    return np.asarray(build_histograms(*args, impl=impl, block_rows=256,
                                       **kw))


@pytest.mark.parametrize("num_rows", ["0", "1", "blk-1", "blk", "chunk",
                                      "chunk+1", "R"])
@pytest.mark.parametrize("impl", ["matmul", "scatter", "pallas", "native"])
def test_compacted_stream_contract(impl, num_rows):
    """Uncompacted gh / row_leaf + row_gather equals the oracle on the
    live prefix, for every impl and around every loop boundary. The
    tail is POISONED: positions past num_rows point at rows whose leaf
    IS in leaf_ids and whose gh is huge, so a loop that consumes one
    position too many (or forgets the dead-leaf mask inside the last
    block) is visibly corrupt."""
    if impl == "native":
        from lightgbm_tpu import native as N
        if N.hist_lib() is None:
            pytest.skip("native toolchain unavailable")
    rng = np.random.RandomState(7)
    F, B, L = _STREAM_F, _STREAM_B, _STREAM_L
    from lightgbm_tpu.ops import pallas_histogram as PH
    blk = _stream_blk(impl)
    R = 5 * blk if impl == "pallas" else 2048      # a multiple of 256
    # the XLA formulations bound by the row block; pallas lays out in
    # chunks of whole kernel row blocks
    chunk = PH.stream_chunk(R, blk) if impl == "pallas" else blk
    assert chunk + 1 < R
    n = {"0": 0, "1": 1, "blk-1": blk - 1, "blk": blk, "chunk": chunk,
         "chunk+1": chunk + 1, "R": R}[num_rows]
    bins = rng.randint(0, B, size=(R, F)).astype(np.uint8)
    gh = np.stack([rng.normal(size=R), rng.uniform(0.1, 1, size=R),
                   np.ones(R)], 1).astype(np.float32)
    row_leaf = rng.randint(0, L, size=R).astype(np.int32)
    # the second half of the table is poison: live leaves, huge addends
    good = np.arange(R // 2, dtype=np.int32)
    poison = np.arange(R // 2, R, dtype=np.int32)
    gh[poison] = 1e9
    row_gather = np.concatenate([rng.choice(good, size=n),
                                 rng.choice(poison, size=R - n)]
                                ).astype(np.int32)
    leaf_ids = np.arange(L, dtype=np.int32)
    got = _stream_call(impl, bins, gh, row_leaf, leaf_ids, row_gather, n)
    live = row_gather[:n]
    want = build_histograms_reference(bins[live], gh[live], row_leaf[live],
                                      leaf_ids, B)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)
    assert got[..., 2].sum() == n * F


def _walk_outside_loops(jaxpr, tainted, whiles, eqns):
    """Every equation of ``jaxpr`` (through nested jits, not into loop
    bodies or kernels) into ``eqns``; ``while`` equations, each with
    whether an operand depends on the ``tainted`` inputs, into
    ``whiles``. Returns the tainted outvars."""
    from jax.extend import core as jex_core
    tainted = set(tainted)
    for e in jaxpr.eqns:
        ins = [v for v in e.invars if not isinstance(v, jex_core.Literal)]
        hit = any(v in tainted for v in ins)
        sub = e.params.get("jaxpr") if e.primitive.name in (
            "pjit", "jit", "closed_call", "core_call") else None
        if sub is not None:
            inner = getattr(sub, "jaxpr", sub)
            t_in = {iv for iv, ov in zip(inner.invars, e.invars)
                    if not isinstance(ov, jex_core.Literal)
                    and ov in tainted}
            t_out = _walk_outside_loops(inner, t_in, whiles, eqns)
            tainted |= {ov for iv, ov in zip(inner.outvars, e.outvars)
                        if iv in t_out}
            continue
        eqns.append(e)
        if e.primitive.name == "while":
            whiles.append((e, [v in tainted for v in e.invars
                               if not isinstance(v, jex_core.Literal)]))
        if hit:
            tainted |= set(e.outvars)
    return tainted


def test_pallas_stream_has_no_row_sized_gather_outside_the_chunk_loop():
    """The pallas branch with row_gather: no gather, convert_element_type
    or transpose equation outside the chunk loop touches an R-sized
    array, there is exactly one loop, its trip count derives from
    num_rows, and the kernel is still called once. The one R-sized
    thing made outside the loop is the per-row table (gh's words and
    the leaf, one concatenate and one bitcast), and a trip gathers
    twice: the bin rows and the table's rows."""
    from lightgbm_tpu.ops import pallas_histogram as PH
    R, F, B, L = 1 << 17, 28, 63, 16
    blk = PH._plan(F, B, 3 * L, 2)[0]
    chunk = PH.stream_chunk(R, blk)
    assert chunk % blk == 0 and chunk < R // 16

    def fn(bins, gh, rl, ids, rg, n):
        return build_histograms(bins, gh, rl, ids, num_bins=B, impl="pallas",
                                row_gather=rg, num_rows=n)

    sds = jax.ShapeDtypeStruct
    closed = jax.make_jaxpr(fn)(
        sds((R, F), jnp.uint8), sds((R, 3), jnp.float32),
        sds((R,), jnp.int32), sds((L,), jnp.int32), sds((R,), jnp.int32),
        sds((), jnp.int32))
    whiles, eqns = [], []
    _walk_outside_loops(closed.jaxpr, {closed.jaxpr.invars[5]}, whiles, eqns)
    names = [e.primitive.name for e in eqns]
    assert names.count("pallas_call") == 1
    assert len(whiles) == 1
    loop, operand_tainted = whiles[0]
    # fori_loop's carry is (i, upper, *bufs): the bound is an operand
    # and it is computed from num_rows
    assert any(operand_tainted)
    cond = loop.params["cond_jaxpr"].jaxpr
    assert [e.primitive.name for e in cond.eqns] == ["lt"]
    for e in eqns:
        if e.primitive.name in ("gather", "convert_element_type",
                                "transpose"):
            sizes = [int(np.prod(v.aval.shape))
                     for v in list(e.invars) + list(e.outvars)]
            assert max(sizes) < R, (e.primitive.name, sizes)
    # the table's assembly: gh's float32 words as int32, the leaf beside
    # them, once
    cats = [e for e in eqns if e.primitive.name == "concatenate"
            and e.outvars[0].aval.shape[0] == R]
    assert [(c.outvars[0].aval.shape, c.outvars[0].aval.dtype)
            for c in cats] == [((R, 4), jnp.int32)]
    casts = [e for e in eqns if e.primitive.name == "bitcast_convert_type"]
    assert [c.invars[0].aval.shape for c in casts] == [(R, 3)]
    # and the loop's body does hold them, chunk-sized
    def flat(jaxpr):
        for e in jaxpr.eqns:
            sub = e.params.get("jaxpr")
            if sub is not None:
                yield from flat(getattr(sub, "jaxpr", sub))
            else:
                yield e

    inner = [e for e in flat(loop.params["body_jaxpr"].jaxpr)
             if e.primitive.name == "gather"]
    assert len(inner) == 2
    assert {e.outvars[0].aval.shape for e in inner} == {(chunk, F),
                                                        (chunk, 4)}
    assert not any(v.aval.shape == (R,) for e in inner for v in e.invars)


@pytest.mark.parametrize("start", ["0", "chunk-1", "chunk", "chunk+1"])
@pytest.mark.parametrize("quant", [False, True], ids=["f32", "int8"])
def test_row_table_gather_equals_the_two_takes_it_replaces(quant, start):
    """``_gather_rows`` over ``_row_table`` against a ``take`` of ``gh``
    and one of ``row_leaf``: float32 and int8 addends, every 32-bit
    pattern of the addends (NaN, inf, -0.0, a denormal) and the leaf ids
    -1, -2, 0 and 131,071 bit for bit, a poisoned dead tail masked to
    -1, positions counted from ``start`` around a chunk boundary."""
    from lightgbm_tpu.ops.histogram import _gather_rows, _row_table
    rng = np.random.RandomState(3)
    R, chunk = 4096, 256
    s = {"0": 0, "chunk-1": chunk - 1, "chunk": chunk,
         "chunk+1": chunk + 1}[start]
    if quant:
        gh = rng.randint(-128, 128, size=(R, 3)).astype(np.int8)
        acc_dt = jnp.int32
    else:
        gh = rng.normal(size=(R, 3)).astype(np.float32)
        gh[:6, 0] = [np.nan, np.inf, -np.inf, -0.0, 1e-45, 3e38]
        acc_dt = jnp.float32
    row_leaf = rng.randint(0, 255, size=R).astype(np.int32)
    row_leaf[:4] = [-1, -2, 0, 131071]
    idx = np.concatenate([np.arange(8), rng.randint(0, R, size=chunk - 8)]
                         ).astype(np.int32)
    num_rows = s + chunk - 5          # the chunk's last five are dead
    table = jax.jit(_row_table, static_argnums=2)(
        jnp.asarray(gh), jnp.asarray(row_leaf), acc_dt)
    assert table.shape == (R, 4) and table.dtype == jnp.int32
    ghb, lb = jax.jit(_gather_rows, static_argnums=4)(
        table, jnp.asarray(idx), jnp.int32(s), jnp.int32(num_rows), acc_dt)
    want_gh = gh[idx].astype(np.dtype(acc_dt))
    assert ghb.dtype == acc_dt and lb.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(ghb).view(np.int32),
                                  want_gh.view(np.int32))
    want_leaf = np.where(s + np.arange(chunk) < num_rows, row_leaf[idx], -1)
    assert list(want_leaf[:4]) == [-1, -2, 0, 131071]
    assert list(want_leaf[-5:]) == [-1] * 5
    np.testing.assert_array_equal(np.asarray(lb), want_leaf)


def _exact_tree_case(R=4096, F=8, B=32):
    """Integer gradients and unit hessians: every histogram sum is exact
    in float32, so parent-minus-child subtraction is too and two correct
    builds agree to the bit."""
    rng = np.random.RandomState(11)
    bins = rng.randint(0, B, size=(R, F)).astype(np.uint8)
    g = (bins[:, 0].astype(np.float32) // 4 - 4
         + (bins[:, 1] > 11) * 3 - (bins[:, 2] > 20) * 2
         + rng.randint(-2, 3, size=R)).astype(np.float32)
    gh = np.stack([g, np.ones(R, np.float32), np.ones(R, np.float32)], 1)
    meta = (jnp.full((F,), B, jnp.int32), jnp.full((F,), -1, jnp.int32),
            jnp.zeros((F,), bool), jnp.ones((F,), bool))
    return bins, gh, meta


@pytest.mark.parametrize("plan_kind", ["serial", "data8"])
def test_compacted_tree_equals_uncompacted_tree_bit_for_bit(plan_kind):
    """A 63-leaf tree grown over the compacted stream (hist_sub) equals,
    node for node and bit for bit in leaf values, the tree grown with
    compaction off, serial and on the 8-virtual-device data-parallel
    mesh; RoundLog.stream_rows covers rows in whole chunks."""
    from lightgbm_tpu.boosting.tree_builder import build_tree
    from lightgbm_tpu.ops.histogram import stream_chunk_rows
    from lightgbm_tpu.ops.split import SplitParams
    bins, gh, meta = _exact_tree_case()
    R, F = bins.shape
    kw = dict(num_leaves=63, leaf_batch=4, max_depth=-1, num_bins=32,
              split_params=SplitParams(min_data_in_leaf=5,
                                       min_sum_hessian_in_leaf=1e-3),
              hist_dtype="float32", hist_impl="scatter")
    rl0 = np.zeros(R, np.int32)
    if plan_kind == "serial":
        r_shard = R
        block = 256

        def grow(sub):
            return build_tree(jnp.asarray(bins), jnp.asarray(gh),
                              jnp.asarray(rl0), *meta, block_rows=block,
                              hist_sub=sub, **kw)
    else:
        from lightgbm_tpu.parallel.data_parallel import DataParallelPlan
        plan = DataParallelPlan()
        assert plan.num_shards == 8
        r_shard = R // 8
        block = 128

        def grow(sub):
            return plan.build_tree(
                plan.shard_rows(bins), plan.shard_rows(gh),
                plan.shard_rows(rl0), *meta, block_rows=block,
                hist_sub=sub, **kw)

    t1, rl1, _, log1 = grow(True)
    t0, rl0_, _, log0 = grow(False)
    assert int(t1.num_leaves) == 63
    for name, a, b in zip(t1._fields, t1, t0):
        if name == "gain":
            # the two programs fuse the gain formula differently (a few
            # ulp on the mesh); the sums it is computed from are exact
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4)
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=name)
    np.testing.assert_array_equal(np.asarray(rl1), np.asarray(rl0_))

    chunk = stream_chunk_rows("scatter", r_shard, F, 32, 4, jnp.float32,
                              "float32", block)
    assert chunk == block
    rows, leaves, stream, dense = (np.asarray(a) for a in log1)
    assert not dense.any()      # scatter: no dense route
    ran = leaves > 0
    assert ran.sum() >= 16
    assert (stream >= rows).all() and (stream % chunk == 0).all()
    assert (stream - rows < chunk).all()
    assert (stream[..., ~ran] == 0).all() and (rows[..., ~ran] == 0).all()
    # the stream is bounded: well under every round x every row
    assert stream.sum() < 0.7 * ran.sum() * R
    # compaction off: every round streams all of the shard's rows
    s0 = np.asarray(log0.stream_rows)
    assert (s0[..., np.asarray(log0.leaves) > 0] == r_shard).all()
