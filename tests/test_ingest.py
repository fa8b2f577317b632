"""Out-of-core ingest (ISSUE 13): sketch merge laws + accuracy bound,
shard format round-trip/corruption, chunked-vs-resident training
parity, capacity fallback, prefetch budget."""

import glob
import os

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.binning import BinMapper
from lightgbm_tpu.config import Config
from lightgbm_tpu.data.chunked import ArraySource
from lightgbm_tpu.data.ingest import ingest
from lightgbm_tpu.data.prefetch import ChunkPrefetcher, chunk_rows_for
from lightgbm_tpu.data.shardfile import (ShardFormatError,
                                         open_shard_dir, verify_shard)
from lightgbm_tpu.data.sketch import (FeatureSketch, SketchSet,
                                      truncate_mantissa)


def _sketch_state(s):
    return (s.level, s.n_nan, s.values.tobytes(), s.counts.tobytes())


def _mapper_state(m):
    ub = m.bin_upper_bound
    cats = getattr(m, "categories", None)
    return (m.bin_type, m.num_bin, m.missing_type, m.most_freq_bin,
            None if ub is None else ub.tobytes(),
            None if cats is None else np.asarray(cats).tobytes())


# ---------------------------------------------------------------------
# quantile sketch: merge laws + accuracy contract


def test_sketch_merge_associative_commutative(rng):
    cols = [rng.normal(size=400) for _ in range(3)]
    cols[1][::7] = np.nan
    cap = 64  # force coarsening so the law is tested PAST overflow

    def sk(col):
        return FeatureSketch(capacity=cap).update(col)

    ab_c = sk(cols[0]).merge(sk(cols[1])).merge(sk(cols[2]))
    a_bc = sk(cols[0]).merge(sk(cols[1]).merge(sk(cols[2])))
    cba = sk(cols[2]).merge(sk(cols[1])).merge(sk(cols[0]))
    one_pass = sk(np.concatenate(cols))
    want = _sketch_state(ab_c)
    assert _sketch_state(a_bc) == want        # associative
    assert _sketch_state(cba) == want         # commutative
    assert _sketch_state(one_pass) == want    # grouping-free


def test_sketch_exact_matches_in_memory(rng):
    # no overflow -> the sketch holds the exact multiset and the fitted
    # mappers are bit-identical to the in-memory fit, NaN and
    # categorical columns included
    R, F = 1000, 4
    X = rng.normal(size=(R, F))
    X[::9, 1] = np.nan
    X[:, 2] = rng.randint(0, 12, size=R)  # categorical
    cfg = Config({"max_bin": 63})
    ss = SketchSet(F, capacity=1 << 16, cat_idx={2})
    for lo in range(0, R, 137):            # odd-sized blocks
        ss.update(X[lo:lo + 137])
    fitted = ss.fit_mappers(cfg)
    for f in range(F):
        ref = BinMapper.from_values(
            X[:, f], max_bin=cfg.max_bin,
            min_data_in_bin=cfg.min_data_in_bin,
            bin_type="categorical" if f == 2 else "numerical",
            use_missing=cfg.use_missing,
            zero_as_missing=cfg.zero_as_missing)
        assert _mapper_state(fitted[f]) == _mapper_state(ref), f


def test_sketch_overflow_bound(rng):
    # the documented accuracy contract: an overflowed sketch at level L
    # is the EXACT multiset summary of truncate_mantissa(values, L), so
    # its mapper is bit-identical to the in-memory fit on those
    # truncated values — and truncation perturbs every value by less
    # than 2**(L-52) relative. Counts never coarsen.
    vals = rng.normal(size=5000)
    s = FeatureSketch(capacity=128).update(vals)
    L = s.level
    assert L > 0
    assert int(s.counts.sum()) == len(vals)  # counts exact
    tv = truncate_mantissa(vals, L)
    ref = BinMapper.from_values(tv, max_bin=63)
    got = s.to_mapper(max_bin=63)
    assert _mapper_state(got) == _mapper_state(ref)
    assert np.all(np.abs(tv - vals) <= 2.0 ** (L - 52) * np.abs(vals))


# ---------------------------------------------------------------------
# shard format + crash-idempotent ingest


def _make_shards(rng, tmp_path, R=2000, F=5, rows_per_shard=600):
    X = rng.normal(size=(R, F))
    y = (X[:, 0] > 0).astype(np.float64)
    xp, yp = str(tmp_path / "X.npy"), str(tmp_path / "y.npy")
    np.save(xp, X)
    np.save(yp, y)
    out = str(tmp_path / "shards")
    summary = ingest(xp, out, params={"max_bin": 63,
                                      "ingest_rows_per_shard":
                                      rows_per_shard},
                     label=yp, verbose=False)
    return X, y, xp, yp, out, summary


def test_shard_roundtrip_and_corruption(rng, tmp_path):
    X, y, xp, yp, out, summary = _make_shards(rng, tmp_path)
    assert summary["num_shards"] == 4
    readers, h0 = open_shard_dir(out)
    assert h0["total_rows"] == len(X)
    got_label = np.concatenate([r.label for r in readers])
    np.testing.assert_array_equal(got_label, y)
    # binned content == mappers applied to the raw rows
    mappers = readers[0].mappers()
    used = h0["used_features"]
    want = np.stack([mappers[f].values_to_bins(X[:600, f])
                     for f in used], axis=1)
    np.testing.assert_array_equal(
        np.asarray(readers[0].read_rows(0, 600)), want)
    for r in readers:
        r.close()
    # corruption must be detected
    shards = sorted(glob.glob(os.path.join(out, "*.lgbtpu")))
    with open(shards[2], "r+b") as f:
        f.seek(200)
        f.write(b"\x00\xff\x00\xff")
    assert not verify_shard(shards[2])
    with pytest.raises(ShardFormatError):
        open_shard_dir(out)


def test_ingest_retry_rewrites_only_missing(rng, tmp_path):
    X, y, xp, yp, out, summary = _make_shards(rng, tmp_path)
    shards = sorted(glob.glob(os.path.join(out, "*.lgbtpu")))
    os.unlink(shards[1])
    keep = {p: os.path.getmtime(p) for p in shards if p != shards[1]}
    again = ingest(xp, out, params={"max_bin": 63,
                                    "ingest_rows_per_shard": 600},
                   label=yp, verbose=False)
    assert again["shards_written"] == 1
    assert again["shards_reused"] == len(shards) - 1
    assert all(os.path.getmtime(p) == t for p, t in keep.items())
    assert verify_shard(shards[1])


# ---------------------------------------------------------------------
# chunked training: bit parity with the resident path

_PARITY = dict(objective="binary", num_leaves=15, learning_rate=0.1,
               min_data_in_leaf=5, verbosity=-1, tree_learner="serial",
               hist_subtraction=False, hist_impl="scatter",
               deterministic=True)


def _parity_data(rng, R=1200, F=8):
    X = rng.normal(size=(R, F))
    X[:, 2] = rng.randint(0, 6, size=R)      # categorical
    X[rng.rand(R) < 0.05, 4] = np.nan  # missing
    y = (X[:, 0] + 0.5 * X[:, 1] + 0.2 * X[:, 2] > 0).astype(
        np.float64)
    return X, y


def _train(params, X, y, rounds=5):
    ds = lgb.Dataset(X, label=y, params=dict(params))
    return lgb.train(dict(params), ds, num_boost_round=rounds)


def test_chunked_bitwise_parity(rng):
    # same bin boundaries (same in-memory Dataset fit): chunked
    # streaming must reproduce the resident build bit-for-bit,
    # categoricals and NaN bins included
    X, y = _parity_data(rng)
    p_res = _train(dict(_PARITY), X, y).predict(X)
    chunked = dict(_PARITY, out_of_core="on", chunk_budget_mb=0.05)
    p_chk = _train(chunked, X, y).predict(X)
    np.testing.assert_array_equal(p_res, p_chk)


def test_chunked_quantized_bagging_parity(rng):
    X, y = _parity_data(rng)
    # min_gain_to_split screens degenerate near-tie splits (gain ~1e-5):
    # resident and chunked split-scans are separately-jitted programs, so
    # XLA may contract the gain arithmetic differently (1-ulp, same class
    # of variance as the documented fused-vs-legacy split_gain caveat)
    # and flip the argmax on an exact tie. Away from ties the quantized
    # chunked build is bit-identical.
    q = dict(_PARITY, use_quantized_grad=True, bagging_fraction=0.7,
             bagging_freq=1, bagging_seed=7, min_gain_to_split=1e-3)
    p_res = _train(dict(q), X, y, rounds=4).predict(X)
    p_chk = _train(dict(q, out_of_core="on", chunk_budget_mb=0.05),
                   X, y, rounds=4).predict(X)
    np.testing.assert_array_equal(p_res, p_chk)


@pytest.mark.parametrize("quant", [False, True])
def test_chunked_subtraction_cache_parity(rng, quant):
    """Chunked out-of-core rounds with the parent-minus-child
    subtraction cache == full per-child rebuilds: exact in int32
    quantized mode and for the f32 serial accumulator."""
    X = rng.normal(size=(900, 6)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
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


def test_chunked_gate_raises_reasoned(rng):
    X, y = _parity_data(rng, R=400)
    bad = dict(_PARITY, out_of_core="on", linear_tree=True)
    with pytest.raises(ValueError, match="out_of_core=on"):
        _train(bad, X, y, rounds=1)


def test_shard_dataset_trains_with_eval_parity(rng, tmp_path):
    # sketch-fitted boundaries (the shard path) vs the in-memory
    # sample fit: eval-metric parity within 5e-3 (ISSUE acceptance)
    X, y, xp, yp, out, _ = _make_shards(rng, tmp_path)
    tp = dict(_PARITY, chunk_budget_mb=0.05, max_bin=63)
    bst_s = lgb.train(dict(tp), lgb.Dataset(out, params=dict(tp)),
                      num_boost_round=5)
    assert bst_s._gbdt.chunked  # shard-backed + auto => streamed
    bst_m = _train(dict(tp, max_bin=63), X, y)

    def logloss(p):
        p = np.clip(p, 1e-12, 1 - 1e-12)
        return float(-np.mean(y * np.log(p)
                              + (1 - y) * np.log(1 - p)))

    assert abs(logloss(bst_s.predict(X))
               - logloss(bst_m.predict(X))) <= 5e-3


def test_capacity_overflow_falls_back_to_chunked(rng, monkeypatch):
    # a dataset over the device budget transparently takes the chunked
    # path under out_of_core=auto — and still trains bit-identically —
    # while out_of_core=off keeps the hard MemoryError
    X, y = _parity_data(rng, R=800)
    p_ref = _train(dict(_PARITY), X, y, rounds=3).predict(X)
    monkeypatch.setenv("LIGHTGBM_TPU_DEVICE_MEM_GB", "0.000001")
    bst = _train(dict(_PARITY), X, y, rounds=3)
    assert bst._gbdt.chunked
    np.testing.assert_array_equal(bst.predict(X), p_ref)
    with pytest.raises(MemoryError):
        _train(dict(_PARITY, out_of_core="off"), X, y, rounds=1)


# ---------------------------------------------------------------------
# sequence reader (non-contiguous batches) + prefetch budget


class _OddSeq(lgb.Sequence):
    """Non-C-contiguous rows (transposed backing) + a batch size that
    never aligns with block or chunk boundaries."""

    batch_size = 37

    def __init__(self, arr):
        self._t = np.ascontiguousarray(np.asarray(arr).T)

    def __getitem__(self, idx):
        return self._t.T[idx]

    def __len__(self):
        return self._t.shape[1]


def test_sequence_non_contiguous_batches(rng):
    X = rng.normal(size=(1100, 6))
    y = (X[:, 0] + X[:, 1] ** 2 > 0.5).astype(float)
    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1}
    b1 = lgb.train(dict(params), lgb.Dataset(X, label=y), 5)
    # three unequal sequences, none a multiple of batch_size
    seqs = [_OddSeq(X[:401]), _OddSeq(X[401:402]), _OddSeq(X[402:])]
    b2 = lgb.train(dict(params), lgb.Dataset(seqs, label=y), 5)
    np.testing.assert_allclose(b1.predict(X), b2.predict(X), rtol=1e-6)


def test_chunk_rows_for_respects_budget():
    for budget_mb in (0.05, 0.5, 4.0):
        for block in (64, 256):
            c = chunk_rows_for(100_000, 28, 1, budget_mb, block)
            assert c % block == 0
            # two staged [C, F] buffers fit the budget, unless the
            # block floor itself is bigger than the budget allows
            if c > block:
                assert 2 * c * 28 * 1 <= budget_mb * (1 << 20)
    # never chunks finer than the padded dataset
    assert chunk_rows_for(100, 4, 1, 1e9, 64) == 128


def test_prefetcher_sweeps_every_row(rng):
    bins = rng.randint(0, 16, size=(777, 3)).astype(np.uint8)
    pref = ChunkPrefetcher(ArraySource(bins), chunk_rows=256)
    try:
        got = []
        for off, dev in pref.chunks():
            got.append((off, np.asarray(dev)))
        assert [o for o, _ in got] == [0, 256, 512, 768]
        stitched = np.concatenate([c for _, c in got])[:777]
        np.testing.assert_array_equal(stitched, bins)
        # tail chunk is zero-padded to the static shape
        assert got[-1][1].shape == (256, 3)
        assert pref.stats.chunks == 4
        assert pref.stats.bytes == 4 * 256 * 3
    finally:
        pref.close()
