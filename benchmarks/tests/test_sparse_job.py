"""Job kind ``train-sparse``, its generator, its reference and its four
readers, all on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_sparse_job.py -q

``tests/test_sparse_cell.py`` runs these cases again in tier-1.
"""

import json
import os
import shutil
import sys
from types import SimpleNamespace

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness.manifest import Manifest  # noqa: E402
from harness.runner import run_cell  # noqa: E402
from reference import gbdt_reference as ref  # noqa: E402
from reference import gbdt_sparse_reference as spref  # noqa: E402

CELL = "allstate-efb-train"
NEW = ("split.unbundle_find_share", "split.lattice_valid_share",
       "efb.bundle_fill", "ingest.sparse_construct_s")
# the field table cut to a size a test can train on: 4 numeric columns,
# 5 / 20 / 45 nested levels, three small fields; 83 columns, 10 stored values
TINY = {"numeric": 4, "vehicle": [5, 20, 45], "small": [4, 3, 2],
        "sample_rows": 20000, "positive_rate": 0.08}
TINY_COLS, TINY_ROWS, TINY_STORED_COLUMNS = 83, 30000, 11
PARAMS = {"objective": "binary", "num_leaves": 15, "learning_rate": 0.1,
          "max_bin": 255, "min_data_in_leaf": 1,
          "min_sum_hessian_in_leaf": 5.0, "enable_bundle": True,
          "bin_construct_sample_cnt": 20000, "verbosity": -1}


def _generator():
    return Manifest(ROOT).generator("allstate_like")


# -- the manifest -----------------------------------------------------------------

def test_manifest_has_the_cell_its_configuration_and_four_metrics():
    man = Manifest(ROOT)
    assert man.problems() == []
    cell = man.cell(CELL)
    assert cell["config"] == "allstate"
    assert cell["traffic"] == "train-sparse-steady"
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    entry = [c for c in man.doc["configs"] if c["name"] == "allstate"]
    assert len(entry) == 1 and entry[0]["reduced"] == ["num_iterations"]
    assert len(entry[0]["source"]) <= 200
    assert "Experiments.rst" in entry[0]["source"]
    mine = {m["name"]: m for m in man.doc["per_layer"] if m["name"] in NEW}
    assert set(mine) == set(NEW)
    assert all(m["workloads"] == [CELL] for m in mine.values())
    assert {m["name"] for m in man.metrics_for(CELL, "per_layer")} >= set(NEW)
    assert not set(NEW) & {m["name"] for m in man.metrics_for(
        "higgs-train", "per_layer")}
    cfg = man.config("allstate")
    assert cfg["shape"]["rows"] == 13184290 and cfg["shape"]["cols"] == 4228
    assert cfg["source"] == entry[0]["source"]
    assert cfg["params"]["max_conflict_rate"] == 0.0
    assert man.traffic("train-sparse-steady")["job"] == "train-sparse"


# -- the generator ------------------------------------------------------------------

def test_the_field_table_is_a_constant_and_a_row_stores_32_values():
    gen = _generator()
    t = gen.field_table()
    assert gen.COLS == 4228 == gen.columns({})
    assert t["fields"] == (75, 1303, 2765, 10, 3, 6, 3, 3, 5, 4, 3, 2, 3, 6,
                           6, 15)
    pop = t["popularity"]
    # every level is expected 50 times in the program's bin sample, no
    # one-hot column comes near one half but the field of two's 56 / 44
    assert pop.min() * 200000 >= 50
    assert np.sort(pop)[-2:].tolist() == [0.44, 0.56] and np.sort(pop)[-3] < 0.46
    # nested: a model's popularity is its submodels', a make's its models'
    sub = pop[75 + 1303:75 + 1303 + 2765]
    assert np.allclose(np.bincount(t["model_of"], weights=sub),
                       pop[75:75 + 1303])
    assert np.bincount(t["model_of"]).min() >= 2
    a, ya = gen.generate_csr(3000, 4228, 2 ** 31 + 5, {"positive_rate": 0.01})
    b, yb = gen.generate_csr(3000, 4228, 2 ** 31 + 5, {"positive_rate": 0.01})
    c, _ = gen.generate_csr(3000, 4228, 2 ** 31 + 6, {"positive_rate": 0.01})
    assert a.dtype == np.float32 and a.indices.dtype == np.int32
    assert np.array_equal(np.diff(a.indptr), np.full(3000, 32))
    assert (np.diff(a.indices.reshape(3000, 32), axis=1) > 0).all()
    assert np.array_equal(a.indices, b.indices) and np.array_equal(ya, yb)
    assert not np.array_equal(a.indices, c.indices)
    assert ya.sum() == 30                    # the count of positives is exact
    # one level a field a row, and the nesting holds in every row
    lev = a.indices.reshape(3000, 32)[:, 16:] - t["offsets"][None, :]
    assert (lev >= 0).all() and (lev < np.array(t["fields"])[None, :]).all()
    assert np.array_equal(t["model_of"][lev[:, 2]], lev[:, 1])
    assert np.array_equal(t["make_of"][lev[:, 1]], lev[:, 0])


def test_positives_are_exact_whatever_the_blocks(monkeypatch):
    gen = _generator()
    monkeypatch.setattr(gen, "BLOCK_ROWS", 1000)
    _, y = gen.generate_csr(7300, TINY_COLS, 11, TINY)
    assert y.sum() == round(0.08 * 7300)
    cfg = Manifest(ROOT).config("allstate")
    assert gen.positives(cfg["shape"]["rows"],
                         cfg["generator"]["params"]["positive_rate"]) \
        == cfg["base_rate"]["positives"] == 102191


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 7])
def test_the_plan_has_the_stated_stored_columns_at_every_seed(seed):
    """The bundle plan is greedy over a sample; with the configuration's
    field table its count of stored columns does not hang on the seed."""
    import lightgbm_tpu as lgb
    gen = _generator()
    cfg = Manifest(ROOT).config("allstate")
    x, y = gen.generate_csr(200000, 4228, seed, {"positive_rate": 0.01})
    ds = lgb.Dataset(x, label=y, params=dict(cfg["params"], verbosity=-1)
                     ).construct()
    assert ds.bundle_plan.num_bundles == cfg["expect"]["stored_columns"] == 79
    assert ds.bundle_plan.max_bundle_bins == cfg["expect"]["max_bundle_bins"]
    c = ds.ingest_counters
    assert c["features_used"] == 4228 and c["sample_conflicts"] == 0
    assert c["valid_feature_bins"] == 4212 * 3 + 16 * 255
    assert c["scanned_positions"] == 4228 * 255
    assert c["efb.conflict_rows"] == 0      # the nesting: no two members meet
    # 16 numeric, both levels of the field of two, 12 small fields, the
    # makes, and ceil(4068 / 85) of models and submodels
    sizes = np.bincount(ds.bundle_plan.feat_bundle)
    assert (sizes == 1).sum() == 18 and (sizes == 85).sum() == 47
    assert sorted(sizes[(sizes > 1) & (sizes < 85)]) == sorted(
        [10, 3, 6, 3, 3, 5, 4, 3, 3, 6, 6, 15, 75, 4068 - 47 * 85])


@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_the_tiny_table_plans_alike_at_every_seed(seed):
    import lightgbm_tpu as lgb
    x, y = _generator().generate_csr(TINY_ROWS, TINY_COLS, seed, TINY)
    ds = lgb.Dataset(x, label=y, params=PARAMS).construct()
    assert ds.bundle_plan.num_bundles == TINY_STORED_COLUMNS


# -- the reference --------------------------------------------------------------------

def _trained(rows=6000, seed=5, trees=1, **more):
    """(model text, bounds, CSR rows, labels, the plan as data, the
    program's matrix, its lost rows, params) of a bundled booster on the
    tiny table."""
    import lightgbm_tpu as lgb
    params = dict(PARAMS, **more)
    x, y = _generator().generate_csr(rows, TINY_COLS, seed, TINY)
    ds = lgb.Dataset(x, label=y, params=params).construct()
    bst = lgb.Booster(params, ds)
    for _ in range(trees):
        bst.update()
    ubs = [np.asarray(m.bin_upper_bound, np.float64) for m in ds.bin_mappers]
    return (bst.model_to_string(), ubs, x, y, plan_of(ds), ds.bins,
            ds.efb_conflict_rows, params)


def plan_of(ds):
    bp = ds.bundle_plan
    return {"columns": int(bp.num_bundles),
            "order": np.arange(len(bp.feat_bundle)),
            "column": np.asarray(bp.feat_bundle, np.int64),
            "offset": np.asarray(bp.feat_offset, np.int64),
            "most_frequent": np.asarray(bp.feat_mfb, np.int64)}


def _dense_bins(x, ubs):
    dense = np.asarray(x.todense(), np.float64)
    return np.stack([np.searchsorted(ubs[f], dense[:, f], side="left")
                     for f in range(dense.shape[1])]).astype(np.uint8)


def test_the_reference_agrees_with_a_literal_dense_loop():
    # 63 bins: the dense reference counts (bin, label) pairs in a byte
    text, ubs, x, y, plan, bins, lost, params = _trained(max_bin=63)
    rep = spref.check(text, ubs, x, y, params, plan, bins, lost)
    assert rep["ok"] and rep["encoding"]["ok"] and rep["tree_ok"], rep
    assert rep["roundings_tried"] == 1 and len(rep["splits"]) == 5
    assert rep["features_searched"] == TINY_COLS
    # the dense reference of the other cells, on the densified rows
    dense = ref.check_first_tree(text, ubs, _dense_bins(x, ubs), y, params)
    assert dense["ok"]
    for a, b in zip(rep["splits"], dense["splits"]):
        assert a["reference"] == b["reference"] and a["tree"] == b["tree"]
        assert a["reference_gain"] == pytest.approx(b["reference_gain"],
                                                    rel=1e-12)
    assert rep["leaves"]["worst_error_over_scale"] == pytest.approx(
        dense["leaves"]["worst_error_over_scale"], rel=1e-6, abs=1e-12)
    # the encoding, row by row and member by member, the slowest way
    csc = spref.block_csc(x.indptr, x.indices, x.data, 0, 300, TINY_COLS)
    b, zero = spref.stored_bins(csc, ubs)
    mine, n_lost = spref.encode_block(csc, b, zero, plan)
    cm = _dense_bins(x[:300], ubs)
    want = np.zeros((300, plan["columns"]), np.uint8)
    for r in range(300):
        for f in range(TINY_COLS):
            c, off = plan["column"][f], plan["offset"][f]
            if off == 0:
                want[r, c] = cm[f, r]
            elif cm[f, r] != plan["most_frequent"][f]:
                want[r, c] = off + cm[f, r]
    assert np.array_equal(mine, want) and np.array_equal(mine, bins[:300])
    assert n_lost == 0


def test_float8_addends_read_incorrect():
    text, ubs, x, y, plan, bins, lost, params = _trained()
    rep = spref.check(text, ubs, x, y, params, plan, bins, lost)
    assert rep["ok"] and rep["control"]["ok"] is False
    assert rep["control"]["worst_error_over_limit"] > 8
    assert rep["leaves"]["worst_error_over_limit"] < 0.5
    coarse = spref.check(text, ubs, x, y, params, plan, bins, lost,
                         addend_dtype="float8_e4m3", control_dtype="")
    assert coarse["ok"] is False and coarse["encoding"]["ok"]


def _conflicting(seed=0, rate=0.05):
    """Sparse columns that do meet: a plan that admits conflicts."""
    import scipy.sparse as sp
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(seed)
    n, f = 4000, 40
    vals = rng.normal(size=(n, f)) * (rng.rand(n, f) < 0.04)
    x = sp.csr_matrix(vals.astype(np.float32))
    y = (vals[:, 0] + vals[:, 1] > 0.05).astype(np.float32)
    params = dict(PARAMS, max_conflict_rate=rate, min_sum_hessian_in_leaf=1.0)
    ds = lgb.Dataset(x, label=y, params=params).construct()
    return x, y, ds, params


def test_a_wrong_member_order_and_a_wrong_count_are_refused():
    import lightgbm_tpu as lgb
    x, y, ds, params = _conflicting()
    assert ds.bundle_plan is not None and ds.efb_conflict_rows > 0
    bst = lgb.Booster(params, ds)
    bst.update()
    ubs = [np.asarray(m.bin_upper_bound, np.float64) for m in ds.bin_mappers]
    plan = plan_of(ds)
    rep = spref.check(bst.model_to_string(), ubs, x, y, params, plan,
                      ds.bins, ds.efb_conflict_rows)
    assert rep["encoding"]["ok"], rep["encoding"]
    assert rep["encoding"]["rows_lost"] == ds.efb_conflict_rows > 0
    back = dict(plan, order=plan["order"][::-1])
    rep = spref.check(bst.model_to_string(), ubs, x, y, params, back,
                      ds.bins, ds.efb_conflict_rows)
    assert rep["encoding"]["ok"] is False and rep["ok"] is False
    assert rep["encoding"]["n_unequal_blocks"] == 1
    rep = spref.check(bst.model_to_string(), ubs, x, y, params, plan,
                      ds.bins, ds.efb_conflict_rows + 1)
    assert rep["encoding"]["ok"] is False


# -- the readers ----------------------------------------------------------------------

STAGE_S = {"unbundle": 2.0, "find": 3.0, "subtract": 1.0, "hist_kernel": 3.0,
           "compact": 1.0}
INGEST = {"features_used": 4228, "valid_feature_bins": 16716,
          "scanned_positions": 1078140, "stored_columns": 79,
          "bundle_bins_used": 16777, "bundle_bins_offered": 20224,
          "sample_conflicts": 0, "efb.conflict_rows": 0}
SPANS = {"dataset.fit_bins": {"seconds": 1.5},
         "dataset.plan_bundles": {"seconds": 2.0, "stored_columns": 79},
         "dataset.apply_bins": {"seconds": 30.0},
         "dataset.encode_bundles": {"seconds": 29.0, "conflict_rows": 0}}


def _run(counters):
    return SimpleNamespace(counters=counters, notes={}, spans={}, trace=None,
                           memory={}, shape={"rows": 1, "cols": 79,
                                             "bins": 256},
                           device={"kind": "TPU v5 lite"})


def _read(name, run):
    return Manifest(ROOT).metric_reader(name).read(run)


def test_the_four_readers_read_what_the_job_keeps():
    run = _run({"trees": 2, "stage_s": STAGE_S, "ingest": INGEST,
                "ingest_spans": SPANS})
    assert _read(NEW[0], run) == pytest.approx(60.0)
    assert run.notes[NEW[0]]["search_s_per_tree"] == {
        "unbundle": 1.0, "find": 1.5, "subtract": 0.5}
    assert _read(NEW[1], run) == pytest.approx(100 * 16716 / 1078140)
    assert _read(NEW[2], run) == pytest.approx(100 * 16777 / 20224)
    assert run.notes[NEW[2]]["efb.conflict_rows"] == 0
    assert _read(NEW[3], run) == pytest.approx(33.5)     # encode lies inside


@pytest.mark.parametrize("name,counters", [
    (NEW[0], {"trees": 2}),
    (NEW[0], {"trees": 2, "stage_s": {"find": 1.0, "apply": 1.0}}),
    (NEW[1], {"trees": 2}),
    (NEW[1], {"ingest": {"features_used": 28}}),
    (NEW[2], {"ingest": {"features_used": 28, "scanned_positions": 1764,
                         "valid_feature_bins": 1764}}),
    (NEW[3], {"ingest_spans": {"dataset.fit_bins": {"seconds": 1.0},
                               "dataset.apply_bins": {"seconds": 1.0}}}),
    (NEW[3], {})])
def test_a_reader_with_nothing_to_read_returns_nothing(name, counters):
    """As on a parent commit (no ``unbundle`` stage, no plan span) or in
    another job kind: no value, no exception."""
    assert _read(name, _run(counters)) is None


# -- the job: a rehearsal, and its refusal ----------------------------------------------

@pytest.fixture()
def tiny_root(tmp_path):
    """A copy of the benchmark with the configuration cut to a tiny table
    under the cell's own name; no file that was there is edited."""
    root = str(tmp_path)
    shutil.copytree(BENCH, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    cfg = json.load(open(os.path.join(BENCH, "configs", "allstate.json")))
    cfg["shape"].update(rows=TINY_ROWS, cols=TINY_COLS,
                        stored_values_per_row=10)
    cfg["generator"]["params"] = dict(TINY)
    cfg["params"].update({k: PARAMS[k] for k in (
        "num_leaves", "min_sum_hessian_in_leaf", "bin_construct_sample_cnt")})
    cfg["expect"].update(stored_columns=TINY_STORED_COLUMNS,
                         max_bundle_bins=254)
    json.dump(cfg, open(os.path.join(
        root, "benchmarks", "configs", "allstate.json"), "w"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    return root


@pytest.fixture()
def notes(monkeypatch):
    for k in [k for k in os.environ if k.startswith("LIGHTGBM_TPU_")]:
        monkeypatch.delenv(k)
    seen = {}
    return seen, lambda label, obj: seen.__setitem__(label, obj)


def test_sparse_cell_runs_as_a_rehearsal(tiny_root, notes):
    seen, note = notes
    res = run_cell(tiny_root, CELL, 2147483659, 0.3, False,
                   require_tpu=False, note=note)
    assert res["correct"], seen["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) == {"train_row_trees_per_s", "setup_s"}
    checks = seen["checks"]
    assert checks["kernel_as_stated"] is None       # off a TPU: not judged
    assert all(v for k, v in checks.items() if k != "kernel_as_stated")
    assert seen["ingest"]["stored_columns"] == TINY_STORED_COLUMNS
    assert seen["ingest"]["stored_values"] == TINY_ROWS * 10
    assert seen["spans_s"]["setup.refusal"] < 1.0
    # the job's last note: every compared number beside its limit
    assert list(seen)[-4] == "compared"     # then the runner's three
    cmp = seen["compared"]
    assert cmp["encoding_unequal_blocks"] == 0
    assert cmp["rows_lost"] == cmp["program_rows_lost"] == 0
    assert all(s["gain_short_by"] <= s["limit"] for s in cmp["splits"])
    assert cmp["leaf_error_over_limit"] <= 0.5
    assert cmp["control_ok"] is False and cmp["control_error_over_limit"] > 2
    assert cmp["features_searched"] == TINY_COLS
    assert set(cmp["addends"]) == {"g_negative", "g_positive", "h"}
    # the shape the kernel's readers are handed: the stored lattice
    res = run_cell(tiny_root, CELL, 2 ** 31 + 12, 0.3, True,
                   require_tpu=False, note=note)
    assert res["correct"], seen["checks"]
    m = res["metrics"]
    assert 0 < m["split.unbundle_find_share"]["value"] <= 100
    assert m["split.lattice_valid_share"]["value"] == pytest.approx(
        100 * seen["ingest"]["valid_feature_bins"]
        / seen["ingest"]["scanned_positions"])
    assert 0 < m["efb.bundle_fill"]["value"] <= 100
    assert m["ingest.sparse_construct_s"]["value"] > 0
    assert "unbundle" in seen["counters"]["stage_s"]
    assert seen["counters"]["compiles_in_window"] == 0


def test_a_program_without_the_sparse_ingest_is_refused_before_any_data(
        tiny_root, notes, monkeypatch):
    """The parent's sparse path densifies every column on the host: the
    job says so at once instead of starting on 13.2M rows."""
    from lightgbm_tpu import phases
    seen, note = notes
    monkeypatch.delattr(phases, "UNBUNDLE")
    job = Manifest(tiny_root).job("train-sparse")
    with pytest.raises(job.CannotRunCell, match="unbundle"):
        job.refuse_unless_supported()
    with pytest.raises(Exception, match="unbundle"):
        run_cell(tiny_root, CELL, 1, 0.3, False, require_tpu=False,
                 note=note)
    assert "device" not in seen and "ingest" not in seen
