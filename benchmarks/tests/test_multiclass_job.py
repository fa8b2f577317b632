"""Job kind ``train-multiclass``, its configuration, generator, reference
and two readers, all on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_multiclass_job.py -q
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

from harness import datagen  # noqa: E402
from harness.manifest import Manifest  # noqa: E402
from harness.runner import run_cell  # noqa: E402
from harness.trace import Report  # noqa: E402
from reference import gbdt_multiclass_reference as mref  # noqa: E402

CELL = "kdd99-multiclass-train"
NEW = ("kernels.class_root_roofline", "builder.class_round_share")
# kddcup.data's label counts (the UCI page's table), largest first
PUBLISHED = (2807886, 1072017, 972781, 15892, 12481, 10413, 2316, 2203,
             1020, 979, 264, 53, 30, 21, 20, 12, 10, 9, 8, 7, 4, 3, 2)
TINY_ROWS = 60000


def _generator():
    return Manifest(ROOT).generator("kdd99_like")


# -- the manifest and the configuration ---------------------------------------

def test_manifest_has_the_cell_its_configuration_and_two_metrics():
    man = Manifest(ROOT)
    assert man.problems() == []
    cell = man.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kdd99", "train-multiclass-steady", 1)
    assert len(cell["why"]) <= 200
    entry = [c for c in man.doc["configs"] if c["name"] == "kdd99"]
    assert len(entry) == 1 and entry[0]["reduced"] == ["num_iterations"]
    assert len(entry[0]["source"]) <= 200
    mine = {m["name"]: m for m in man.doc["per_layer"] if m["name"] in NEW}
    assert set(mine) == set(NEW)
    for m in mine.values():
        assert m["workloads"] == [CELL]
        assert m["moves"] == "train_row_trees_per_s" and m["unit"] == "%"
    assert man.traffic(cell["traffic"])["job"] == "train-multiclass"
    # every metric without a list reads in the new cell too
    names = {m["name"] for m in man.metrics_for(CELL, "per_layer")}
    assert set(NEW) <= names and names >= {
        m["name"] for m in man.doc["per_layer"] if "workloads" not in m}
    assert not set(NEW) & {m["name"]
                           for m in man.metrics_for("higgs-train",
                                                    "per_layer")}


def test_configuration_is_higgs_settings_with_a_softmax_of_23():
    man = Manifest(ROOT)
    higgs, kdd = man.config("higgs"), man.config("kdd99")
    assert kdd["params"] == dict(higgs["params"], objective="multiclass",
                                 num_class=23)
    assert kdd["shape"] == {"rows": 4898431, "cols": 41, "classes": 23}
    assert kdd["expect"] == higgs["expect"]
    assert kdd["source"] == man.doc["configs"][-1]["source"]
    assert "symbolic fields" in kdd["assumed"] and kdd["guarantees"]


# -- the generator ------------------------------------------------------------

def test_the_labels_are_the_published_counts_at_every_seed():
    gen = _generator()
    assert gen.ROWS == sum(PUBLISHED) == 4898431
    assert tuple(n for _, n in gen.LABEL_COUNTS) == PUBLISHED
    for seed in (1, 2 ** 31 + 11):
        y = gen.labels(gen.ROWS, seed)
        assert tuple(np.bincount(y, minlength=23)) == PUBLISHED
    a, b = gen.labels(gen.ROWS, 1), gen.labels(gen.ROWS, 2)
    assert not np.array_equal(a, b)        # the seed places them


def test_the_columns_are_those_of_kddcup_names():
    gen = _generator()
    x, y = gen.generate(TINY_ROWS, 41, 2147483659, {})
    assert x.shape == (41, TINY_ROWS) and x.dtype == np.float32
    assert set(np.unique(y)) == set(range(23))
    assert not x[19].any()                         # num_outbound_cmds
    for j, k in gen.SYMBOLS.items():
        assert set(np.unique(x[j])) <= set(range(k))
    for j in gen.FLAGS:
        assert set(np.unique(x[j])) <= {0.0, 1.0}
    for j in gen.RATES:
        assert x[j].min() >= 0 and x[j].max() <= 1
        assert np.allclose(x[j] * 100, np.round(x[j] * 100), atol=1e-3)
    for j in gen.COUNTS_511:
        assert x[j].max() <= 511
    for j in gen.COUNTS_255:
        assert x[j].max() <= 255
    for j in gen.CONTENT:
        assert (x[j] == 0).mean() > 0.9
    # the profile is a constant: another seed draws other rows of it
    x2, _ = gen.generate(TINY_ROWS, 41, 7, {})
    assert not np.array_equal(x, x2)
    p1, p2 = gen.profiles(), gen.profiles()
    assert np.array_equal(p1["rate_mu"], p2["rate_mu"])


# -- the reference ------------------------------------------------------------

def _trained(rows=TINY_ROWS, iterations=2, leaves=15, seed=2 ** 31 + 5):
    import lightgbm_tpu as lgb
    x, y = _generator().generate(rows, 41, seed, {})
    params = dict(Manifest(ROOT).config("kdd99")["params"],
                  num_leaves=leaves, min_sum_hessian_in_leaf=20.0,
                  verbosity=-1)
    ds = lgb.Dataset(np.ascontiguousarray(x.T), label=y, params=params,
                     free_raw_data=False).construct()
    bst = lgb.Booster(params, ds)
    for _ in range(iterations):
        bst.update()
    ubs = [np.asarray(ds.bin_mappers[f].bin_upper_bound, np.float64)
           for f in ds.used_features]
    return (bst, bst.model_to_string(), ubs, datagen.bin_columns(x, ubs), y,
            params)


def test_reference_holds_the_program_and_refuses_a_changed_tree():
    bst, text, ubs, bins_cm, y, params = _trained()
    rep = mref.check_first_iteration(text, 23, ubs, bins_cm, y, params, 3,
                                     str(bst._gbdt.config.hist_dtype))
    assert rep["ok"], rep
    # the program's worst leaf under its limit, the float8 control over it
    cmp = rep["compared"]
    assert cmp["leaf_error_over_limit"] < 0.5
    assert cmp["control_error_over_limit"] > 2 and not cmp["control_ok"]
    picked = rep["picked"]
    assert picked["largest"] == [0] and len(picked["drawn"]) == 2
    assert picked["cannot_split"]          # the rarest labels
    stumps = [t for t in rep["trees"] if not t["can_split"]]
    assert stumps and all(t["num_leaves"] == 1 for t in stumps)
    # a one-leaf tree's output is the init score, to the last bit
    init = mref.init_scores(y, 23)
    k = stumps[0]["class"]
    assert stumps[0]["leaf_value"] == init[k]
    line = f"leaf_value={float(init[k])!r}\n"
    assert line in text
    bad = text.replace(line, f"leaf_value={float(init[k]) + 1e-6!r}\n", 1)
    assert not mref.check_first_iteration(bad, 23, ubs, bins_cm, y, params,
                                          3)["ok"]
    # a leaf value of the largest class off by more than its rounding
    t0 = mref.parse_any(text, 0)
    vals = t0["leaf_value"].copy()
    vals[0] += 1e-2 * abs(vals[0])
    line = "leaf_value=" + " ".join(repr(float(v)) for v in t0["leaf_value"])
    assert line in text
    moved = text.replace(line, "leaf_value=" + " ".join(
        repr(float(v)) for v in vals), 1)
    assert not mref.check_first_iteration(moved, 23, ubs, bins_cm, y,
                                          params, 3)["ok"]


def test_the_scores_follow_the_model_text_and_nothing_else():
    bst, text, ubs, bins_cm, y, params = _trained()
    scores = bst._gbdt.eval_scores(-1)
    rows = mref.sample_rows(len(y), 7, 5000)
    walk = mref.check_scores(text, 2 * 23, 23, ubs, bins_cm, scores, rows)
    assert walk["ok"] and walk["score_error_over_limit"] < 0.25
    off = scores.copy()
    off[rows[::50], 3] += 1e-3
    assert not mref.check_scores(text, 2 * 23, 23, ubs, bins_cm, off,
                                 rows)["ok"]
    assert mref.multi_logloss(y, scores) < mref.multi_logloss(
        y, np.broadcast_to(mref.init_scores(y, 23), scores.shape))


def test_second_iteration_holds_the_program_and_refuses_a_changed_tree():
    """Iteration 1's trees grow on softmax addends that differ row by row:
    the program's pass, the float8 control does not, and a leaf of the
    largest class moved by a hundredth is refused."""
    bst, text, ubs, bins_cm, y, params = _trained()
    dtype = str(bst._gbdt.config.hist_dtype)
    picked = mref.check_first_iteration(text, 23, ubs, bins_cm, y, params,
                                        3, dtype)["picked"]
    rep = mref.check_second_iteration(text, 23, ubs, bins_cm, y, params,
                                      picked, dtype)
    assert rep["ok"], [t for t in rep["trees"] if not t["ok"]]
    assert [t["tree"] for t in rep["trees"]] == [
        23 + k for k in sorted(picked["largest"] + picked["drawn"])]
    cmp = rep["compared"]
    assert cmp["leaf_error_over_limit"] < 0.5
    assert cmp["control_error_over_limit"] > 2 and not cmp["control_ok"]
    t = mref.parse_any(text, 23)
    line = "leaf_value=" + " ".join(repr(float(v)) for v in t["leaf_value"])
    assert line in text
    vals = t["leaf_value"].copy()
    vals[0] += 1e-2 * abs(vals[0])
    moved = text.replace(line, "leaf_value=" + " ".join(
        repr(float(v)) for v in vals), 1)
    assert not mref.check_second_iteration(moved, 23, ubs, bins_cm, y,
                                           params, picked, dtype)["ok"]


@pytest.mark.parametrize("value,both", [
    (0.3, False), (-0.70703125, False), (1.0 / 3.0, False), (2.5e-7, False),
    # 2^-20 off the midpoint of 254/256 and 255/256
    (-0.994140625 - 2.0 ** -20, True), (0.994140625 + 2.0 ** -20, True)])
def test_rows_round_as_the_scalar_rule_admits(value, both):
    """``round_rows`` is ``gbdt_sharded_reference.admissible`` row by row:
    the nearest bfloat16, and the other neighbour's distance where the
    value lies within the margin of a midpoint."""
    from reference import gbdt_sharded_reference as sref
    want = sref.admissible(value, "bfloat16")
    near, step = mref.round_rows(np.array([value]), "bfloat16")
    assert near[0] == want["admissible"][0]
    assert len(want["admissible"]) == 2 if both else 1
    assert (step[0] > 0) == both
    if both:
        assert step[0] == abs(want["admissible"][1] - want["admissible"][0])


# -- the two readers on hand-made inputs --------------------------------------

def _report(root_calls):
    return Report(window_s=1.0, busy_s=1.0, class_s={"kernel": 0.5},
                  root_kernel_s=list(root_calls), device_ops=[],
                  idle_gaps=[])


def _run(trace=None, log=(), trees=None, shape=None):
    return SimpleNamespace(
        spans={}, counters={"trees": len(log) if trees is None else trees},
        trace=trace, memory={},
        shape=shape or {"rows": 1000, "cols": 41, "bins": 63,
                        "classes": 23},
        device={"kind": "TPU v5 lite"}, notes={},
        program=SimpleNamespace(round_log=list(log)))


def _read(name, run):
    return Manifest(ROOT).metric_reader(name).read(run)


def test_class_root_roofline_is_the_least_time_over_the_mean_call():
    run = _run(_report([0.02, 0.04]))
    R, F, B, K = 1000, 41, 63, 23
    ops = 2.0 * R * F * B * K * 3
    byts = R * F + R * K * 3 * 4 + F * B * K * 3 * 4
    least = max(ops / 197e12, byts / 819e9)
    assert _read(NEW[0], run) == pytest.approx(100 * least / 0.03)
    note = run.notes[NEW[0]]
    assert note["calls"] == 2 and note["classes"] == K
    assert note["bound"] == ("compute" if ops / 197e12 > byts / 819e9
                             else "memory")


def test_class_root_roofline_at_the_cell_shape_is_k_times_the_one_class():
    reader = Manifest(ROOT).metric_reader(NEW[0])
    one = reader.counts(4898431, 41, 63, 1)
    k = reader.counts(4898431, 41, 63, 23)
    assert k[0] == 23 * one[0] and k[1] > one[1]


def _rec(leaves, batched=None, it=0, k=0):
    return SimpleNamespace(iteration=it, class_index=k,
                           rows=np.ones(len(leaves), np.int32),
                           leaves=np.asarray(leaves, np.int32),
                           stream_rows=np.ones(len(leaves), np.int32),
                           dense=np.zeros(len(leaves), np.int32),
                           batched_rounds=batched)


def test_class_round_share_is_built_class_rounds_over_batched_ones():
    # one iteration of three classes: the loop ran 4 rounds, the classes
    # built in 4, 2 and 0 of them
    log = [_rec([3, 1, 2, 1, 0], 4, k=0), _rec([2, 1, 0, 0, 0], 4, k=1),
           _rec([0, 0, 0, 0, 0], 4, k=2)]
    run = _run(log=log)
    assert _read(NEW[1], run) == pytest.approx(100.0 * 6 / 12)
    assert run.notes[NEW[1]] == {"class_rounds_built": 6,
                                 "class_rounds_run": 12, "trees": 3}
    # the window's trees only: the last ``trees`` records
    run = _run(log=[_rec([1, 1], 2)] * 3 + log, trees=3)
    assert _read(NEW[1], run) == pytest.approx(50.0)


@pytest.mark.parametrize("case", ["no_field", "field_none", "no_rounds",
                                  "no_trees"])
def test_class_round_share_reads_nothing_without_batched_rounds(case):
    if case == "no_field":
        rec = _rec([1, 1], 2)
        del rec.batched_rounds
        log = [rec]
    elif case == "field_none":
        log = [_rec([1, 1], None)]
    elif case == "no_rounds":
        log = [_rec([0, 0], 0)]
    else:
        log = []
    assert _read(NEW[1], _run(log=log)) is None


@pytest.mark.parametrize("case", ["no_trace", "no_root_calls", "no_classes"])
def test_class_root_roofline_reads_nothing_without_its_inputs(case):
    if case == "no_trace":
        run = _run(None)
    elif case == "no_root_calls":
        run = _run(_report([]))
    else:
        run = _run(_report([0.02]), shape={"rows": 1000, "cols": 41,
                                            "bins": 63})
    assert _read(NEW[0], run) is None


# -- the job, rehearsed -------------------------------------------------------

@pytest.fixture()
def tiny_root(tmp_path):
    """A copy of the benchmark with the configuration cut to a tiny table
    under the cell's own name; no file that was there is edited."""
    root = str(tmp_path)
    shutil.copytree(BENCH, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    cfg = json.load(open(os.path.join(BENCH, "configs", "kdd99.json")))
    cfg["shape"]["rows"] = TINY_ROWS
    cfg["params"].update(num_leaves=15, min_sum_hessian_in_leaf=20.0)
    cfg["bin_sample_rows"] = 20000
    json.dump(cfg, open(os.path.join(root, "benchmarks", "configs",
                                     "kdd99.json"), "w"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    return root


@pytest.fixture()
def notes(monkeypatch):
    for k in [k for k in os.environ if k.startswith("LIGHTGBM_TPU_")]:
        monkeypatch.delenv(k)
    seen = {}
    return seen, lambda label, obj: seen.__setitem__(label, obj)


def test_multiclass_cell_runs_as_a_rehearsal(tiny_root, notes):
    seen, note = notes
    res = run_cell(tiny_root, CELL, 2147483659, 0.3, False,
                   require_tpu=False, note=note)
    assert res["correct"], seen["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) == {"train_row_trees_per_s", "setup_s"}
    checks = seen["checks"]
    assert checks["kernel_as_stated"] is None       # off a TPU: not judged
    assert all(v for k, v in checks.items() if k != "kernel_as_stated")
    assert seen["counters"]["trees"] == 23 * res["attempted"]
    assert seen["resolved"]["class_batch_reason"] == ""
    assert seen["resolved"]["step_shape"]["shape_classes"] in (1, 23)
    assert seen["replay"]["picked"]["cannot_split"]
    assert checks["second_iteration_replay"]
    assert not seen["compared"]["iteration_1_control_ok"]
    order = list(seen["spans_s"])           # the refusal before the data
    assert order.index("setup.refusal") < order.index("setup.data")


def test_a_program_without_the_class_root_name_is_refused_before_any_data(
        tiny_root, notes, monkeypatch):
    """A parent commit's class-root call has no name the trace finds: the
    job says so at once, before the data or the chip."""
    from lightgbm_tpu.ops import pallas_histogram
    monkeypatch.delattr(pallas_histogram, "HIST_CLASS_ROOT_NAME")
    seen, note = notes
    job = Manifest(tiny_root).job("train-multiclass")
    with pytest.raises(job.CannotRunCell, match="class-root"):
        job.refuse_unless_supported()
    with pytest.raises(Exception, match="class-root") as err:
        run_cell(tiny_root, CELL, 1, 0.3, False, require_tpu=False,
                 note=note)
    assert type(err.value).__name__ == "CannotRunCell"
    assert "device" not in seen and "ingest" not in seen
