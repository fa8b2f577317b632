"""Job kind ``train-rank``, its generator and its three readers, all on
the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_rank_job.py -q
"""

import json
import os
import shutil
import sys
from types import SimpleNamespace

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness.manifest import Manifest  # noqa: E402
from harness.runner import run_cell  # noqa: E402

CELL = "msltr-rank-train"
NEW = ("objective.grads_share", "objective.pair_slot_share",
       "objective.init_s")
TINY = {"rows": 6000, "cols": 30, "queries": 90, "min_query": 1,
        "max_query": 400}


# -- the manifest ---------------------------------------------------------------

def test_manifest_has_the_cell_its_configuration_and_three_metrics():
    man = Manifest(ROOT)
    assert man.problems() == []
    cell = man.cell(CELL)
    assert cell["config"] == "msltr" and cell["traffic"] == "rank-steady"
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    entry = [c for c in man.doc["configs"] if c["name"] == "msltr"]
    assert len(entry) == 1 and entry[0]["reduced"] == ["num_iterations"]
    mine = [m for m in man.doc["per_layer"] if m["name"] in NEW]
    assert sorted(m["name"] for m in mine) == sorted(NEW)
    for m in mine:
        assert m["workloads"] == [CELL] and m["layer"] == "objectives"
    cfg = man.config("msltr")
    assert cfg["shape"] == {"rows": 2270296, "cols": 137, "queries": 18919,
                            "min_query": 1, "max_query": 1251}
    assert cfg["params"]["objective"] == "lambdarank"
    assert cfg["reduced"] == ["num_iterations"]
    assert man.traffic(cell["traffic"])["job"] == "train-rank"
    # every metric without a list reads in the new cell too
    names = {m["name"] for m in man.metrics_for(CELL, "per_layer")}
    assert set(NEW) <= names and names >= {
        m["name"] for m in man.doc["per_layer"] if "workloads" not in m}
    for old in ("higgs-train", "epsilon-train"):
        assert not set(NEW) & {m["name"]
                               for m in man.metrics_for(old, "per_layer")}


# -- the generator's size table ----------------------------------------------------

def test_size_table_is_the_configurations_and_no_seeds():
    gen = Manifest(ROOT).generator("msltr_like")
    sizes = gen.query_sizes(2270296, 18919, 1, 1251)
    assert len(sizes) == 18919 and int(sizes.sum()) == 2270296
    assert sizes.min() == 1 and sizes.max() == 1251
    assert np.all(np.diff(sizes) >= 0)
    assert 100 <= np.median(sizes) <= 120 < sizes.mean() + 1
    assert np.array_equal(sizes, gen.query_sizes(2270296, 18919, 1, 1251))
    with pytest.raises(ValueError):
        gen.query_sizes(10, 20, 1, 5)


def test_generator_orders_the_table_by_seed_and_grades_five_levels():
    gen = Manifest(ROOT).generator("msltr_like")
    p = {"queries": TINY["queries"], "min_query": 1, "max_query": 400}
    x1, y1, s1 = gen.generate(TINY["rows"], 30, 2 ** 31 + 3, p)
    x2, y2, s2 = gen.generate(TINY["rows"], 30, 2 ** 31 + 4, p)
    xa, ya, sa = gen.generate(TINY["rows"], 30, 2 ** 31 + 3, p)
    assert x1.shape == (30, TINY["rows"]) and x1.dtype == np.float32
    assert np.array_equal(np.sort(s1), np.sort(s2))       # one multiset
    assert not np.array_equal(s1, s2)                     # another order
    assert np.array_equal(x1, xa) and np.array_equal(y1, ya)
    assert np.array_equal(s1, sa) and not np.array_equal(x1, x2)
    assert s1.sum() == TINY["rows"] and s1.min() == 1 and s1.max() == 400
    shares = np.bincount(y1.astype(int), minlength=5) / len(y1)
    np.testing.assert_allclose(shares, gen.GRADE_SHARES, atol=0.005)


# -- the three readers on hand-made inputs -----------------------------------------

def _run(counters, program=None, rows=1000):
    return SimpleNamespace(
        spans={}, counters=counters, trace=None, memory={},
        shape={"rows": rows, "cols": 10, "bins": 63},
        device={"kind": "TPU v5 lite"}, notes={}, program=program)


def _read(name, run):
    return Manifest(ROOT).metric_reader(name).read(run)


def test_grads_share_is_the_ranking_stages_over_all_stages():
    stage_s = {"grads": 0.5, "rank_gather": 1.0, "rank_sort": 2.0,
               "rank_pairs": 0.25, "rank_scatter": 0.25, "apply": 10.0,
               "hist_kernel": 5.0, "unknown": 1.0}
    run = _run({"trees": 2, "stage_s": stage_s})
    assert _read(NEW[0], run) == pytest.approx(100.0 * 4.0 / 20.0)
    note = run.notes[NEW[0]]
    assert note["grads_s_per_tree"] == pytest.approx(2.0)
    assert note["unknown_share_pct"] == pytest.approx(5.0)
    assert note["stage_s_per_tree"]["apply"] == pytest.approx(5.0)


@pytest.mark.parametrize("counters", [
    {}, {"trees": 2}, {"trees": 2, "stage_s": None},
    {"trees": 2, "stage_s": {}}, {"stage_s": {"apply": 0.0}}])
def test_grads_share_is_none_without_stage_seconds(counters):
    assert _read(NEW[0], _run(counters)) is None


def test_pair_slot_share_is_pairs_over_pair_slots():
    c = {"pair_slots": 4000, "pairs": 1000, "slots": 1300, "queries": 7,
         "max_query": 40}
    run = _run({"objective": c})
    assert _read(NEW[1], run) == pytest.approx(25.0)
    assert run.notes[NEW[1]]["slots_per_row"] == pytest.approx(1.3)
    # a window that bounds one side of a pair reads over 100%
    assert _read(NEW[1], _run({"objective": dict(c, pair_slots=500)})) \
        == pytest.approx(200.0)


@pytest.mark.parametrize("counters", [
    {}, {"objective": None}, {"objective": {"pairs": 5, "pair_slots": 0}}])
def test_pair_slot_share_is_none_without_the_counters(counters):
    assert _read(NEW[1], _run(counters)) is None


def _span(name, seconds, **fields):
    return SimpleNamespace(name=name, seconds=seconds, fields=fields)


def test_init_s_is_the_newest_objective_init_span():
    spans = [_span("objective.init", 9.0), _span("gbdt.to_device", 1.0),
             _span("objective.init", 0.25, pairs=7)]
    prog = SimpleNamespace(recorder=SimpleNamespace(spans=lambda: spans))
    run = _run({}, program=prog)
    assert _read(NEW[2], run) == pytest.approx(0.25)
    assert run.notes[NEW[2]] == {"pairs": 7}
    none = SimpleNamespace(recorder=SimpleNamespace(
        spans=lambda: [_span("gbdt.to_device", 1.0)]))
    assert _read(NEW[2], _run({}, program=none)) is None


# -- the job: a rehearsal, and its refusal -------------------------------------------

@pytest.fixture()
def tiny_root(tmp_path):
    """A copy of the benchmark with the configuration cut to a tiny
    shape under the cell's own name; no file that was there is edited."""
    root = str(tmp_path)
    shutil.copytree(BENCH, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    cfg = json.load(open(os.path.join(BENCH, "configs", "msltr.json")))
    cfg["shape"] = TINY
    cfg["generator"]["params"] = {k: TINY[k] for k in
                                  ("queries", "min_query", "max_query")}
    cfg["bin_sample_rows"] = 3000
    cfg["params"].update(num_leaves=15, min_sum_hessian_in_leaf=0.5)
    json.dump(cfg, open(os.path.join(
        root, "benchmarks", "configs", "msltr.json"), "w"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    return root


@pytest.fixture()
def notes():
    seen = {}
    return seen, lambda label, obj: seen.__setitem__(label, obj)


def test_rank_cell_runs_as_a_rehearsal(tiny_root, notes):
    seen, note = notes
    res = run_cell(tiny_root, CELL, 2 ** 31 + 11, 0.2, False,
                   require_tpu=False, note=note)
    assert res["correct"], seen["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) == {"train_row_trees_per_s", "setup_s"}
    assert res["device"]["platform"] == "cpu"
    first, last = seen["replay"]
    assert first["tree"] == 0 and last["tree"] == res["attempted"]
    assert first["ok"] and last["ok"] and len(first["splits"]) == 5
    grads = seen["gradient_check"]
    assert grads["ok"] and grads["queries"] >= 3
    # what the limit is there to refuse
    assert grads["bfloat16_scores_error_over_scale"] > 4 * grads["limit"]
    assert grads["worst_error_over_scale"] < grads["limit"] / 4
    assert seen["ndcg_at_10"][1] > seen["ndcg_at_10"][0]
    c = seen["objective_counters"]
    assert c["queries"] == 90 and c["max_query"] == 400
    assert c["pair_slots"] <= 12 * c["pairs"] + 192 * c["queries"]
    assert seen["counters"]["objective"] == c
    assert seen["counters"]["compiles_in_window"] == 0
    assert seen["checks"]["fused_step"]

    res = run_cell(tiny_root, CELL, 2 ** 31 + 12, 0.2, True,
                   require_tpu=False, note=note)
    assert res["correct"], seen["checks"]
    assert res["metrics"]["objective.pair_slot_share"]["value"] == \
        pytest.approx(100.0 * c["pairs"] / c["pair_slots"])
    assert res["metrics"]["objective.init_s"]["value"] > 0
    assert "entry.step_ready_s" in res["metrics"]
    # no device plane on a CPU: the trace's readers leave their metric out
    assert "builder.rowwise_share" not in res["metrics"]


def test_a_program_without_the_counters_is_refused_at_once(tiny_root, notes,
                                                           monkeypatch):
    """As a parent commit: the objective sets no counters at init, and
    the job stops before any data is made."""
    from harness.spans import Spans
    from lightgbm_tpu import ranking
    monkeypatch.setattr(
        ranking._RankingBase, "init",
        lambda self, label, weight, qb=None, position=None: None)
    man = Manifest(tiny_root)
    job = man.job("train-rank")

    def no_data(*_):
        raise AssertionError("data was made")
    monkeypatch.setattr(job, "_make_dataset", no_data)
    env = SimpleNamespace(
        manifest=man, cell=man.cell(CELL), config=man.config("msltr"),
        traffic=man.traffic("rank-steady"), chips=1, seed=1, seconds=0.1,
        trace=False, t_start=0.0, require_tpu=False, spans=Spans(),
        note=notes[1], compile_counter=lambda: SimpleNamespace(count=0))
    with pytest.raises(job.CannotRunCell, match="no layout counters"):
        job.run(env)


def test_a_pair_lattice_over_half_the_device_is_refused(tiny_root):
    import lightgbm_tpu as lgb
    man = Manifest(tiny_root)
    job = man.job("train-rank")
    cfg = man.config("msltr")
    gen = man.generator("msltr_like")
    c = job.refuse_unless_it_fits(lgb, cfg, gen, None)
    assert c["pairs"] > 0
    need = c["pair_slots"] * 4
    job.refuse_unless_it_fits(lgb, cfg, gen, 2 * need)
    with pytest.raises(job.CannotRunCell, match="over half"):
        job.refuse_unless_it_fits(lgb, cfg, gen, 2 * need - 2)


def test_a_replay_that_goes_on_from_a_start_adds_the_same_bits():
    from reference import lambdarank_reference as ref

    def stump(i, feature, tbin, values):
        return (f"Tree={i}\nnum_leaves=2\nnum_cat=0\nsplit_feature={feature}\n"
                f"split_gain=1\nthreshold={tbin + 0.5}\ndecision_type=0\n"
                "left_child=-1\nright_child=-2\n"
                f"leaf_value={values[0]!r} {values[1]!r}\nleaf_weight=1 1\n"
                "leaf_count=1 1\ninternal_value=0\ninternal_weight=0\n"
                "internal_count=2\nis_linear=0\nshrinkage=0.1\n\n")
    text = "".join(stump(i, i % 3, 2 + i, [0.013 * (i + 1), -0.021 * (i + 2)])
                   for i in range(4))
    bins = np.random.default_rng(0).integers(0, 8, size=(3, 1000),
                                             dtype=np.uint8)
    ubs = [np.append(np.arange(7) + 0.5, np.inf)] * 3
    whole = ref.replay_scores(text, 4, ubs, bins, 0.1)
    half = ref.replay_scores(text, 2, ubs, bins, 0.1)
    kept = half.copy()
    assert np.array_equal(
        whole, ref.replay_scores(text, 4, ubs, bins, 0.1, start=(2, half)))
    assert np.array_equal(half, kept) and not np.array_equal(half, whole)
