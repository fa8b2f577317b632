"""The grow round's per-row reads, held to the table formulation (PR 29).

``tree_builder.relabel_rows`` / ``select_by_slot`` / ``slot_counts``
find a row's pending split by comparing ``row_leaf`` with the round's W
slots. The oracle below does what the builder did before: scatter the W
records into ``[L+1]`` tables, gather them by ``row_leaf``, count rows
with a ``segment_sum`` into ``L+1`` segments. Integers are selected and
rows are counted, so the two must agree bit for bit on every input.
The native CPU custom calls still read such tables (and are held to the
XLA formulation by tests/test_histogram.py's native parity tests).
"""

import hashlib
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu.boosting.tree_builder import (relabel_rows,
                                                select_by_slot,
                                                slot_counts)
from lightgbm_tpu.efb import decode_feature_bins
from lightgbm_tpu.ops.predict import row_feature_gather

L = 31                      # num_leaves of the round under test
DUMMY = L


def _round(seed, W, *, B=64, F=6, R=1500, n_valid=None, cat=False,
           nan=False, dead_rows=True, G=None):
    """Seeded inputs of one round: a bin matrix, ``row_leaf`` with some
    rows out of the bag (< 0), and W split records of which the first
    ``n_valid`` lanes are used (the rest hold the dummy leaf, as the
    builder leaves them). ``G`` bundles the F features into G columns."""
    rng = np.random.RandomState(seed)
    # a round splits at most as many leaves as there are and as fit
    n_valid = min(W, L // 2) if n_valid is None else n_valid
    BW = (B + 31) // 32
    cur = rng.randint(n_valid, L - n_valid + 1) if n_valid else 1
    rl = rng.randint(0, cur, size=R).astype(np.int32)
    if dead_rows:
        rl[rng.rand(R) < 0.1] = -1
    valid = np.arange(W) < n_valid
    sel = np.full(W, DUMMY, np.int32)
    sel[:n_valid] = rng.choice(cur, size=n_valid, replace=False)
    right = np.where(valid, cur + np.cumsum(valid) - 1, DUMMY) \
        .astype(np.int32)
    # unused lanes carry whatever the best-split cache held at the dummy
    # leaf: arbitrary in-range records that must never reach a row
    feat = rng.randint(0, F, size=W).astype(np.int32)
    thr = rng.randint(0, B - 1, size=W).astype(np.int32)
    dl = rng.rand(W) < 0.5
    is_cat = (rng.rand(W) < 0.5) if cat else np.zeros(W, bool)
    bits = rng.randint(0, 2 ** 32, size=(W, BW), dtype=np.uint64) \
        .astype(np.uint32)
    nan_bin_pf = np.where(rng.rand(F) < (0.7 if nan else 0.0), B - 1, -1) \
        .astype(np.int32)
    out = dict(rl=rl, sel=sel, valid=valid, right=right, feat=feat, thr=thr,
               dl=dl, cat=is_cat, bits=bits, nan_bin_pf=nan_bin_pf,
               BW=BW, F=F, B=B)
    if G is None:
        out["bins"] = rng.randint(0, B, size=(R, F)).astype(np.uint8)
        out["vbins"] = rng.randint(0, B, size=(R // 3, F)).astype(np.uint8)
        out["vrl"] = rng.randint(-1, cur, size=R // 3).astype(np.int32)
    else:
        # bundle layout: feature f lives in column f % G at an offset;
        # a row outside the feature's range decodes to its mfb
        nb = rng.randint(3, 12, size=F).astype(np.int32)
        gof = (np.arange(F) % G).astype(np.int32)
        off = np.zeros(F, np.int32)
        width = np.ones(G, np.int32)
        for f in range(F):
            off[f] = width[gof[f]]
            width[gof[f]] += nb[f]
        out.update(gof=gof, off=off, nbpf=nb,
                   mfb=rng.randint(0, 3, size=F).astype(np.int32),
                   thr=(rng.randint(0, 3, size=W)).astype(np.int32),
                   nan_bin_pf=np.where(rng.rand(F) < 0.5, nb - 1, -1)
                   .astype(np.int32),
                   bins=rng.randint(0, width.max(), size=(R, G))
                   .astype(np.uint8))
    return {k: (jnp.asarray(v) if isinstance(v, np.ndarray) else v)
            for k, v in out.items()}


# ------------------------------------------------- the table formulation

def _tables(d):
    """The W records as ``[L+1]`` tables (the builder's ``pend_*``)."""
    sel = d["sel"]
    z = lambda dt, *s: jnp.zeros((L + 1,) + s, dt)     # noqa: E731
    return dict(
        active=z(bool).at[sel].set(d["valid"]).at[DUMMY].set(False),
        feat=z(jnp.int32).at[sel].set(d["feat"]),
        thr=z(jnp.int32).at[sel].set(d["thr"]),
        dl=z(bool).at[sel].set(d["dl"]),
        cat=z(bool).at[sel].set(d["cat"]),
        right=z(jnp.int32).at[sel].set(d["right"]),
        bits=z(jnp.uint32, d["BW"]).at[sel].set(d["bits"]))


def _oracle_relabel(d, bmat, rl, bundle=False):
    t = _tables(d)
    rlc = jnp.where(rl < 0, DUMMY, rl)
    active = jnp.take(t["active"], rlc)
    feat = jnp.take(t["feat"], rlc)
    if bundle:
        raw = row_feature_gather(bmat, jnp.take(d["gof"], feat))
        binv = decode_feature_bins(
            raw, jnp.take(d["off"], feat), jnp.take(d["nbpf"], feat),
            jnp.take(d["mfb"], feat), xp=jnp)
    else:
        binv = row_feature_gather(bmat, feat)
    thr = jnp.take(t["thr"], rlc)
    nb = jnp.take(d["nan_bin_pf"], feat)
    isnan = (binv == nb) & (nb >= 0)
    cat_row = jnp.take(t["cat"], rlc)
    word = binv >> 5
    rbits = jnp.take(t["bits"], rlc, axis=0)
    wsel = jnp.arange(d["BW"], dtype=jnp.int32)[None, :] == word[:, None]
    wval = jnp.sum(jnp.where(wsel, rbits, jnp.uint32(0)), axis=1)
    in_set = ((wval >> (binv & 31).astype(jnp.uint32))
              & jnp.uint32(1)) == 1
    go_left = jnp.where(cat_row, in_set, binv <= thr)
    go_left = jnp.where(isnan & ~cat_row, jnp.take(t["dl"], rlc), go_left)
    return jnp.where(active & ~go_left, jnp.take(t["right"], rlc), rl)


def _oracle_counts(rl, slots):
    rlc = jnp.where(rl < 0, DUMMY, rl)
    raw = jax.ops.segment_sum(jnp.ones(rl.shape, jnp.int32), rlc,
                              num_segments=L + 1)
    return jnp.take(raw, jnp.clip(slots, 0, L))


def _oracle_member(rl, small_slots):
    lut = jnp.zeros((L + 2,), bool).at[
        jnp.clip(small_slots, -1, L) + 1].set(True).at[0].set(False)
    return jnp.take(lut, jnp.clip(rl, -1, L) + 1)


# ------------------------------------------------------ the new passes

def _new_relabel(d, bmat, rl, bundle=False):
    recs, bin_of = (), None
    if bundle:
        recs = [jnp.take(d[k], d["feat"])
                for k in ("gof", "off", "nbpf", "mfb")]

        def bin_of(bm, active, feat, gof, off, nbf, mfb):
            return decode_feature_bins(row_feature_gather(bm, gof),
                                       off, nbf, mfb, xp=jnp)
    return relabel_rows(
        bmat, rl, d["sel"], d["valid"], d["feat"], d["thr"], d["dl"],
        d["cat"], d["right"], jnp.take(d["nan_bin_pf"], d["feat"]),
        d["bits"], recs, bin_of)


def _check_round(d, bundle=False):
    for bmat, rl in ((d["bins"], d["rl"]),) + (
            ((d["vbins"], d["vrl"]),) if "vbins" in d else ()):
        want = _oracle_relabel(d, bmat, rl, bundle)
        got = _new_relabel(d, bmat, rl, bundle)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # the relabel moved rows: count the children on what it left
    new_rl = _new_relabel(d, d["bins"], d["rl"], bundle)
    assert (np.asarray(new_rl) != np.asarray(d["rl"])).any() \
        or not bool(d["valid"].any())
    slots = jnp.concatenate([d["sel"], d["right"]])
    want = np.asarray(_oracle_counts(new_rl, slots))
    got = np.asarray(slot_counts(new_rl, slots))
    v2 = np.concatenate([d["valid"], d["valid"]])
    np.testing.assert_array_equal(got[v2], want[v2])
    W = d["sel"].shape[0]
    # unused lanes tie (which is all the round reads of them)
    np.testing.assert_array_equal(got[:W] <= got[W:], want[:W] <= want[W:])
    small = jnp.where(d["valid"],
                      jnp.where(got[:W] <= got[W:], d["sel"], d["right"]),
                      -2)
    m, none = select_by_slot(new_rl, small, small >= 0)
    assert none == []
    np.testing.assert_array_equal(
        np.asarray(m), np.asarray(_oracle_member(new_rl, small)))


@pytest.mark.parametrize("W", [1, 16, L - 1])
@pytest.mark.parametrize("kind", ["numerical", "categorical", "nan"])
def test_relabel_counts_membership_match_the_tables(W, kind):
    """Numerical, categorical with two bitset words, and NaN rows with
    ``default_left`` both ways (the records draw it per lane); train
    and valid matrix; rows out of the bag."""
    d = _round(7 + W, W, B=64, cat=kind == "categorical",
               nan=kind == "nan")
    assert d["BW"] == 2
    if kind == "nan":
        dl = np.asarray(d["dl"])[np.asarray(d["valid"])]
        assert W == 1 or (dl.any() and not dl.all())
    _check_round(d)


@pytest.mark.parametrize("n_valid", [0, 1, 5])
def test_tail_round_with_unused_lanes(n_valid):
    """Fewer valid lanes than W: the unused lanes all hold the dummy
    leaf and arbitrary records, which ``lane_ok`` keeps from every row
    whatever leaf a row carries, the dummy's own number included."""
    d = _round(100 + n_valid, 16, n_valid=n_valid, cat=True, nan=True)
    _check_round(d)
    rl = d["rl"].at[:7].set(DUMMY)
    hit, (thr,) = select_by_slot(rl, d["sel"], d["valid"], [d["thr"] + 1])
    assert not bool(hit[:7].any()) and not bool(thr[:7].any())
    assert int(hit.sum()) == int(jnp.isin(rl, d["sel"][:n_valid]).sum())


def test_rows_out_of_the_bag_never_hit():
    d = _round(3, 16)
    rl = jnp.where(jnp.arange(d["rl"].shape[0]) % 2 == 0, d["rl"], -1)
    hit, (feat,) = select_by_slot(rl, d["sel"], d["valid"], [d["feat"]])
    assert not bool(hit[rl < 0].any()) and not bool(feat[rl < 0].any())
    got = _new_relabel(d, d["bins"], rl)
    np.testing.assert_array_equal(np.asarray(got)[np.asarray(rl) < 0], -1)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(_oracle_relabel(d, d["bins"], rl)))


@pytest.mark.parametrize("W", [1, 16])
def test_efb_bundles(W):
    """Bundled matrix: the decode's per-feature metadata is taken at
    the W split features and selected per row."""
    d = _round(40 + W, W, F=9, G=4, nan=True)
    _check_round(d, bundle=True)


def test_round_under_vmap():
    """``class_batch``: K rounds at once, ``sel_s`` is [K, W]."""
    K, W = 3, 16
    ds = [_round(200 + k, W, cat=True, nan=True, n_valid=15 - 3 * k)
          for k in range(K)]
    keys = ("rl", "sel", "valid", "feat", "thr", "dl", "cat", "right", "bits")
    st = {k: jnp.stack([d[k] for d in ds]) for k in keys}
    bins, nanpf = ds[0]["bins"], ds[0]["nan_bin_pf"]

    def one(rl, sel, valid, feat, thr, dl, cat, right, bits):
        new = relabel_rows(bins, rl, sel, valid, feat, thr, dl, cat, right,
                           jnp.take(nanpf, feat), bits)
        return new, slot_counts(new, jnp.concatenate([sel, right]))
    new, cnt = jax.vmap(one)(*(st[k] for k in keys))
    for k, d in enumerate(ds):
        dk = dict(d, bins=bins, nan_bin_pf=nanpf)
        want = _oracle_relabel(dk, bins, d["rl"])
        np.testing.assert_array_equal(np.asarray(new[k]), np.asarray(want))
        slots = jnp.concatenate([d["sel"], d["right"]])
        v2 = np.concatenate([d["valid"], d["valid"]])
        np.testing.assert_array_equal(
            np.asarray(cnt[k])[v2],
            np.asarray(_oracle_counts(want, slots))[v2])


def test_counts_after_psum_on_a_row_mesh():
    """``tree_learner=data``: each shard counts its own rows of the 2W
    children and the psum is over [2W], where it was over [L+1]."""
    from jax.experimental.shard_map import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    if len(jax.devices()) < 2:
        pytest.skip("needs two virtual devices")
    d = _round(11, 16, R=2048)
    new_rl = _new_relabel(d, d["bins"], d["rl"])
    slots = jnp.concatenate([d["sel"], d["right"]])
    mesh = Mesh(np.array(jax.devices()[:2]), ("d",))

    def new(rl, s):
        return jax.lax.psum(slot_counts(rl, s), "d")

    def old(rl, s):
        rlc = jnp.where(rl < 0, DUMMY, rl)
        raw = jax.ops.segment_sum(jnp.ones(rl.shape, jnp.int32), rlc,
                                  num_segments=L + 1)
        return jnp.take(jax.lax.psum(raw, "d"), jnp.clip(s, 0, L))
    run = lambda f: np.asarray(shard_map(             # noqa: E731
        f, mesh=mesh, in_specs=(P("d"), P()), out_specs=P())(new_rl, slots))
    v2 = np.concatenate([d["valid"], d["valid"]])
    np.testing.assert_array_equal(run(new)[v2], run(old)[v2])
    np.testing.assert_array_equal(
        run(new), np.asarray(slot_counts(new_rl, slots)))


# ------------------------------------- whole trainings: same model text

_SHA = os.path.join(os.path.dirname(__file__), "golden",
                    "round_select_model_sha256.json")


def _family_run(family):
    """One small training run of an objective family of tests/golden,
    through the XLA round body (``hist_impl=scatter``: what a TPU runs
    around its kernel; ``auto`` on a CPU takes the native custom calls)
    with a categorical column, NaNs and a valid set."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(5)
    n, f = 600, 7
    X = rng.normal(size=(n, f)).astype(np.float32)
    X[:, 0] = rng.randint(0, 40, size=n)
    X[rng.rand(n) < 0.1, 2] = np.nan
    s = X[:, 1] + np.where(X[:, 0] % 3 == 0, 1.0, -0.5) \
        + 0.5 * np.nan_to_num(X[:, 2]) * X[:, 3]
    params = dict(num_leaves=15, learning_rate=0.2, min_data_in_leaf=5,
                  verbosity=-1, hist_impl="scatter", tree_learner="serial",
                  max_bin=63)
    kw = {}
    if family == "binary_classification":
        y = (s > 0).astype(np.float32)
        params.update(objective="binary")
    elif family == "regression":
        y = s.astype(np.float32)
        params.update(objective="regression")
    elif family == "multiclass_classification":
        y = np.digitize(s, [-0.5, 0.7]).astype(np.float32)
        params.update(objective="multiclass", num_class=3)
    else:
        y = np.clip(np.round(s + 1.5), 0, 4).astype(np.float32)
        params.update(objective="lambdarank" if family == "lambdarank"
                      else "rank_xendcg", min_data_in_leaf=2)
        kw["group"] = [30] * 10
    nt = 300 if kw else 450
    train = lgb.Dataset(X[:nt], label=y[:nt], categorical_feature=[0], **kw)
    vkw = {"group": [30] * 10} if kw else {}
    valid = lgb.Dataset(X[nt:], label=y[nt:], reference=train, **vkw)
    bst = lgb.train(params, train, num_boost_round=4, valid_sets=[valid])
    return bst.model_to_string()


FAMILIES = ["binary_classification", "regression",
            "multiclass_classification", "lambdarank", "xendcg"]


@pytest.mark.parametrize("family", FAMILIES)
def test_model_text_unchanged(family):
    """The model text of a small run per objective family equals the
    one the table formulation gave (SHA-256 recorded at the parent
    commit of PR 29 by this same function)."""
    with open(_SHA) as fh:
        want = json.load(fh)["sha256"][family]
    got = hashlib.sha256(_family_run(family).encode()).hexdigest()
    assert got == want
