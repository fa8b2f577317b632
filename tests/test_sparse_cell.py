"""The sparse cell's program side: the ingest that costs the stored
values (against the dense path on the same data, its cost at the cell's
width, the conflict rule and its counter), bundled training against
unbundled, the ``unbundle`` stage in the compiled step; and, run again here
so that tier-1 holds them, the cases of
``benchmarks/tests/test_sparse_job.py`` (the generator's constant table, the
reference against a literal dense loop, the float8 control, a wrong member
order, the four readers, the job's rehearsal and its refusal)."""

import os
import sys
import time
import tracemalloc

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(ROOT, "benchmarks", "tests"),
          os.path.join(ROOT, "benchmarks")):
    if p not in sys.path:
        sys.path.insert(0, p)

import test_sparse_job as sj  # noqa: E402
from test_sparse_job import (  # noqa: E402,F401  (collected here too)
    notes, tiny_root,
    test_manifest_has_the_cell_its_configuration_and_four_metrics,
    test_the_field_table_is_a_constant_and_a_row_stores_32_values,
    test_positives_are_exact_whatever_the_blocks,
    test_the_plan_has_the_stated_stored_columns_at_every_seed,
    test_the_tiny_table_plans_alike_at_every_seed,
    test_the_reference_agrees_with_a_literal_dense_loop,
    test_float8_addends_read_incorrect,
    test_a_wrong_member_order_and_a_wrong_count_are_refused,
    test_the_four_readers_read_what_the_job_keeps,
    test_a_reader_with_nothing_to_read_returns_nothing,
    test_sparse_cell_runs_as_a_rehearsal,
    test_a_program_without_the_sparse_ingest_is_refused_before_any_data)

import lightgbm_tpu as lgb  # noqa: E402
from lightgbm_tpu import phases, profiler  # noqa: E402

sp = pytest.importorskip("scipy.sparse")


# -- (i) the sparse ingest against the dense path on the same data ---------------

def _one_hot(rng, n, n_vars, card):
    cats = rng.randint(0, card, size=(n, n_vars))
    out = np.zeros((n, n_vars * card))
    out[np.arange(n)[:, None], cats + np.arange(n_vars)[None, :] * card] = 1.0
    return out


def _case(name):
    rng = np.random.RandomState(5)
    n = 3000
    hot = _one_hot(rng, n, 8, 6)
    if name == "one_hot":
        return hot, {}
    num = rng.normal(size=(n, 3)) + 2.0      # zero is not the common bin
    num[rng.rand(n, 3) < 0.1] = 0.0
    if name == "one_hot_and_numeric":
        return np.hstack([num, hot]), {}
    if name == "empty_in_the_sample":
        last = np.zeros((n, 1))
        last[n - 1] = 1.0                    # one stored value, outside
        return np.hstack([num, hot, last]), {"bin_construct_sample_cnt": 1000}
    if name == "mostly_stored_members":
        # two columns that store 1.0 in all rows but a few of their own:
        # members of one bundle whose implied zeros are the rare bin
        a, b = np.ones((n, 1)), np.ones((n, 1))
        a[:100], b[100:200] = 0.0, 0.0
        return np.hstack([a, b, hot]), {}
    dense = rng.normal(size=(n, 60)) * (rng.rand(n, 60) < 0.03)
    dense[5, 7] = np.nan                     # a stored NaN
    return dense, {"max_conflict_rate": 0.02 if name == "conflicts" else 0.0}


def _state(ds):
    bp = ds.bundle_plan
    return ([m.state_arrays() for m in ds.bin_mappers], ds.used_features,
            None if bp is None else bp.state_arrays(), ds.bins)


def _equal(a, b):
    if a is None or b is None:
        return a is None and b is None
    if isinstance(a, (tuple, list)):
        return len(a) == len(b) and all(_equal(x, y) for x, y in zip(a, b))
    a, b = np.asarray(a), np.asarray(b)
    return a.dtype == b.dtype and np.array_equal(a, b, equal_nan=True)


@pytest.mark.parametrize("fmt", ["csr", "csc"])
@pytest.mark.parametrize("name", [
    "one_hot", "one_hot_and_numeric", "empty_in_the_sample",
    "mostly_stored_members", "no_conflicts_allowed", "conflicts"])
def test_sparse_ingest_equals_the_dense_path(name, fmt, monkeypatch):
    """Equal mappers, plan and ``bins`` (the dense path is the parent
    commit's formulation, column by column; against the parent's own
    sparse path the same cases were held equal before it was deleted)."""
    dense, extra = _case(name)
    params = dict(extra, verbosity=-1)
    y = np.arange(len(dense)) % 2
    want = lgb.Dataset(dense, label=y, params=params).construct()
    # no dense view of the sparse input may be made along the way
    for cls in (sp.csr_matrix, sp.csc_matrix):
        for m in ("todense", "toarray"):
            monkeypatch.setattr(cls, m, lambda *a, **k: pytest.fail(
                "the sparse ingest densified"))
    x = sp.csr_matrix(dense) if fmt == "csr" else sp.csc_matrix(dense)
    got = lgb.Dataset(x, label=y, params=params).construct()
    for w, g, what in zip(_state(want), _state(got),
                          ("mappers", "used", "plan", "bins")):
        assert _equal(w, g), what
    assert got.efb_conflict_rows == want.efb_conflict_rows
    if name == "conflicts":
        assert got.bundle_plan.sample_conflicts > 0
        assert got.efb_conflict_rows > 0
    if name == "mostly_stored_members":
        bp = got.bundle_plan
        assert bp.feat_bundle[0] == bp.feat_bundle[1] and bp.feat_offset[0]
        assert got.bin_mappers[0].most_freq_bin != \
            got.bin_mappers[0].default_bin
    spans = {s.name: s.fields for s in profiler.recorder.spans()[-6:]}
    assert {"dataset.fit_bins", "dataset.apply_bins"} <= set(spans)
    if got.bundle_plan is not None:
        assert spans["dataset.plan_bundles"]["stored_columns"] == \
            got.bundle_plan.num_bundles
        assert spans["dataset.encode_bundles"]["conflict_rows"] == \
            got.efb_conflict_rows


def test_a_valid_set_is_encoded_as_its_train_set():
    dense, _ = _case("one_hot_and_numeric")
    y = np.arange(len(dense)) % 2
    train = lgb.Dataset(sp.csr_matrix(dense), label=y,
                        params={"verbosity": -1}).construct()
    valid = lgb.Dataset(sp.csr_matrix(dense[:500]), label=y[:500],
                        reference=train).construct()
    assert np.array_equal(valid.bins, train.bins[:500])


# -- (ii) what the ingest costs at the cell's width -------------------------------

def test_ingest_at_the_cells_width_costs_the_stored_values():
    """50,000 x 4,228: no array of rows x cols (or sample x cols: the
    sample is all 50,000 rows here) elements is ever made, 211 MB even as
    bytes, and the construct is a matter of seconds (the parent: 14.7 s at
    20,000 rows, not done after 170 s at 100,000)."""
    rows, cols = 50_000, 4228
    x, y = sj._generator().generate_csr(rows, cols, 17,
                                        {"positive_rate": 0.01})
    t0 = time.perf_counter()
    tracemalloc.start()
    ds = lgb.Dataset(x, label=y, params={"verbosity": -1}).construct()
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    seconds = time.perf_counter() - t0
    assert ds.bins.shape == (rows, 79) and ds.bins.dtype == np.uint8
    # all that was ever held at once is less than ONE such array of bytes
    assert peak < rows * cols, f"peak {peak / 1e6:.0f} MB"
    assert seconds < 90, seconds        # ~2 s untraced on the sandbox's host
    c = ds.ingest_counters
    assert c["stored_columns"] == 79 and c["bundle_bins_offered"] == 79 * 256
    assert c["bundle_bins_used"] == ds.bundle_plan.bundle_num_bins.sum()


# -- (iii) bundled training, and the conflict rule --------------------------------

def test_bundled_training_from_csr_equals_unbundled_dense_training():
    x, y = sj._generator().generate_csr(sj.TINY_ROWS, sj.TINY_COLS, 9, sj.TINY)
    params = dict(sj.PARAMS)
    a = lgb.Booster(params, lgb.Dataset(x, label=y, params=params))
    dense = dict(params, enable_bundle=False)
    b = lgb.Booster(dense, lgb.Dataset(np.asarray(x.todense()), label=y,
                                       params=dense))
    for _ in range(3):
        a.update()
        b.update()
    assert a._gbdt.train_set.bundle_plan is not None
    assert a._gbdt.train_set.efb_conflict_rows == 0
    assert b._gbdt.train_set.bundle_plan is None
    ta, tb = (sj.ref.parse_tree(m.model_to_string(), 2) for m in (a, b))
    for key in ("split_feature", "threshold", "left_child", "right_child",
                "leaf_count"):
        assert np.array_equal(ta[key], tb[key]), key
    np.testing.assert_allclose(ta["leaf_value"], tb["leaf_value"],
                               rtol=1e-5, atol=1e-7)
    assert a._gbdt.ingest_counters["stored_columns"] == sj.TINY_STORED_COLUMNS


def test_where_rows_conflict_the_later_member_wins_and_is_counted():
    x, y, ds, params = sj._conflicting(seed=3)
    ubs = [np.asarray(m.bin_upper_bound, np.float64) for m in ds.bin_mappers]
    csc = sj.spref.block_csc(x.indptr, x.indices, x.data, 0, x.shape[0],
                             x.shape[1])
    bins, zero = sj.spref.stored_bins(csc, ubs)
    mine, lost = sj.spref.encode_block(csc, bins, zero, sj.plan_of(ds))
    assert np.array_equal(ds.bins, mine)
    assert ds.efb_conflict_rows == lost > 0
    assert ds.ingest_counters["efb.conflict_rows"] == lost
    # and it trains: the bundled histograms of rows that lost a value
    bst = lgb.Booster(params, ds)
    bst.update()
    assert sj.ref.parse_tree(bst.model_to_string(), 0)["num_leaves"] > 1


def test_capacity_counts_the_feature_space_lattice(monkeypatch):
    from lightgbm_tpu.dataset import (SEARCH_LATTICE_COPIES,
                                      check_device_capacity,
                                      estimate_device_bytes)
    lattice = 32 * 4228 * 255 * 3 * 4
    base = estimate_device_bytes(13_184_290, 79, 1, 255, 256, True)
    full = estimate_device_bytes(13_184_290, 79, 1, 255, 256, True,
                                 search_lattice=(32, 4228, 255))
    assert full - base == SEARCH_LATTICE_COPIES * lattice
    monkeypatch.setenv("LIGHTGBM_TPU_DEVICE_MEM_GB", "4")
    check_device_capacity(13_184_290, 79, 1, 255, 256, True)
    with pytest.raises(MemoryError, match="4,228 features x 255 bins"):
        check_device_capacity(13_184_290, 79, 1, 255, 256, True,
                              search_lattice=(32, 4228, 255))


# -- (v) the stage ---------------------------------------------------------------------

def test_the_unbundling_is_a_stage_of_the_compiled_step():
    """Trace Doctor's EFB cell: the bundle-space histogram's gather to
    feature space lies under ``unbundle`` in the fused step's stage map,
    in the root pass and in the round; a plain booster has no such stage."""
    from lightgbm_tpu.analysis.doctor import ROUND_BODY_CELLS, make_booster
    from lightgbm_tpu.telemetry import costmodel, xprof
    assert ("efb", "serial") in ROUND_BODY_CELLS
    assert phases.UNBUNDLE in phases.BUILD_STAGES
    assert xprof.stage_of_path(
        "jit(step)/build/while/body/subtract/unbundle/gather") == "unbundle"
    bst = make_booster("efb", "serial")
    assert bst._gbdt.train_set.bundle_plan is not None
    text = costmodel.fused_compiled(bst, force=False).as_text()
    sm = costmodel.instruction_phase_map(text)
    staged = costmodel.staged_ops(text)
    mine = [s for s in staged if s.stage == phases.UNBUNDLE]
    assert mine and {s.in_loop for s in mine} == {True, False}
    assert any("gather" in s.op.opcode or "gather" in s.op.op_name
               for s in mine)
    assert set(sm.stages.values()) <= phases.KNOWN_PHASES
    plain = make_booster("plain", "serial")
    text = costmodel.fused_compiled(plain, force=False).as_text()
    assert phases.UNBUNDLE not in set(
        costmodel.instruction_phase_map(text).stages.values())
