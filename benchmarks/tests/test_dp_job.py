"""Job kind ``train-dp``, its generator, its reference and its four
readers, all on the CPU, with four virtual devices standing for the chips
of a host:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_dp_job.py -q

``tests/test_dp_cell.py`` runs the reference's cases again in tier-1.
"""

import json
import os
import shutil
import sys
from types import SimpleNamespace

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    # read when JAX starts its backend, which no import here does
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4"
                               ).strip()

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness.manifest import Manifest  # noqa: E402
from harness.runner import run_cell  # noqa: E402
from reference import gbdt_reference as ref  # noqa: E402
from reference import gbdt_sharded_reference as sref  # noqa: E402

CELL = "criteo-dp-train"
NEW = ("collectives.exposed_share", "collectives.merge_roofline",
       "collectives.wire_mib_per_tree", "builder.shard_live_skew")
COLS = 12
PARAMS = {"objective": "binary", "tree_learner": "data", "num_leaves": 15,
          "learning_rate": 0.1, "max_bin": 255, "min_data_in_leaf": 20,
          "min_sum_hessian_in_leaf": 1e-3, "verbosity": -1}


@pytest.fixture()
def four_chips(monkeypatch):
    """Four of the process's virtual devices as the host's chips, and no
    pin of the program in the environment."""
    import jax
    devs = jax.devices()
    assert len(devs) >= 4, (
        "needs four virtual devices: XLA_FLAGS was set, without "
        "--xla_force_host_platform_device_count, before this file could ask")
    monkeypatch.setattr(jax, "devices", lambda *a, **k: devs[:4])
    for k in [k for k in os.environ if k.startswith("LIGHTGBM_TPU_")]:
        monkeypatch.delenv(k)
    return devs[:4]


# -- the manifest -----------------------------------------------------------------

def test_manifest_has_the_cell_its_configuration_and_four_metrics():
    man = Manifest(ROOT)
    assert man.problems() == []
    cell = man.cell(CELL)
    assert cell["config"] == "criteo" and cell["traffic"] == "train-dp-steady"
    assert cell["chips"] == 4 and len(cell["why"]) <= 200
    entry = [c for c in man.doc["configs"] if c["name"] == "criteo"]
    assert len(entry) == 1
    assert entry[0]["reduced"] == ["num_iterations", "rows"]
    assert len(entry[0]["source"]) <= 200 and "Experiments.rst" in entry[0]["source"]
    assert sum(w["chips"] == 4 for w in man.doc["workloads"]) == 1
    mine = {m["name"]: m for m in man.doc["per_layer"] if m["name"] in NEW}
    assert sorted(mine) == sorted(NEW)
    for name, m in mine.items():
        assert m["workloads"] == [CELL] and m["unit"] in ("%", "MiB")
        assert m["layer"] == ("builder" if name.startswith("builder")
                              else "collectives")
    cfg = man.config("criteo")
    assert cfg["shape"] == {"rows": 53125000, "cols": 67}
    assert cfg["shape"]["rows"] * 32 == cfg["deployment"]["source_rows"]
    p = cfg["params"]
    assert (p["tree_learner"], p["num_leaves"], p["max_bin"],
            p["learning_rate"]) == ("data", 255, 255, 0.1)
    assert cfg["reduced"] == ["num_iterations", "rows"]
    assert man.traffic(cell["traffic"])["job"] == "train-dp"
    # every metric without a list reads in the new cell too, and no old
    # cell reads a new one
    names = {m["name"] for m in man.metrics_for(CELL, "per_layer")}
    assert set(NEW) <= names and names >= {
        m["name"] for m in man.doc["per_layer"] if "workloads" not in m}
    for old in ("higgs-train", "epsilon-train", "msltr-rank-train"):
        assert not set(NEW) & {m["name"]
                               for m in man.metrics_for(old, "per_layer")}


def test_base_rate_lies_far_from_every_rounding_boundary():
    """The configuration's arithmetic: each of tree 0's three addends is
    over 4 standard deviations of the seeds' spread from a boundary."""
    cfg = Manifest(ROOT).config("criteo")
    p = cfg["generator"]["params"]["click_rate"]
    assert p == cfg["base_rate"]["click_rate"]
    sd = np.sqrt(p * (1 - p) / cfg["shape"]["rows"])
    for value, slope in ((p, 1.0), (p - 1.0, 1.0), (p * (1 - p), 1 - 2 * p)):
        a = sref.admissible(value, "bfloat16")
        assert a["boundary_distance"] * abs(value) > 4.0 * sd * slope
        assert len(a["admissible"]) == 1


# -- the generator ------------------------------------------------------------------

def test_generator_shares_its_surface_and_draws_its_rows_from_the_seed():
    gen = Manifest(ROOT).generator("criteo_like")
    p = {"click_rate": 0.0344092}
    rows = gen.BLOCK_ROWS + 1000
    x1, y1 = gen.generate(rows, 67, 2 ** 31 + 3, p)
    x2, y2 = gen.generate(rows, 67, 2 ** 31 + 4, p)
    assert x1.shape == (67, rows) and x1.dtype == np.float32
    assert not np.array_equal(x1, x2) and not np.array_equal(y1, y2)
    k1, k2 = gen.surface_constants(67), gen.surface_constants(67)
    for key in k1:          # one surface whatever the seed: no seed goes in
        assert np.array_equal(k1[key], k2[key])
    steps = k1["step_cols"]
    assert len(set(steps.tolist())) == gen.STEPS == len(k1["step_amp"])
    assert len(gen.surface_constants(12)["step_cols"]) == 3
    assert not k1["weights"][steps].any()
    assert np.count_nonzero(k1["weights"]) == 67 - gen.STEPS
    # nothing matters alike: amplitudes and weights fall off geometrically
    for v in (np.abs(k1["step_amp"]),
              np.sort(np.abs(k1["weights"][k1["weights"] != 0]))[::-1]):
        assert np.all(v[1:] < 0.98 * v[:-1])
    odds = k1["step_odds"]
    var = (float(np.sum(k1["step_amp"].astype(np.float64) ** 2
                        * odds * (1 - odds)))
           + float(np.sum(k1["weights"].astype(np.float64) ** 2))
           + float(k1["noise"]) ** 2)
    assert var == pytest.approx(1.0, abs=1e-6)
    # the threshold is the exact inverse of the click rate it gives
    cut = gen.threshold(0.0344092, 67)
    assert gen.click_rate_at(float(cut), 67) == pytest.approx(0.0344092,
                                                               abs=2e-8)
    assert gen.click_rate_at(float(cut) + 0.01, 67) < 0.0344092
    # a block is its own stream: drawn alone, short, or into a buffer
    xb, yb = gen.draw_block(1, 1000, 67, 2 ** 31 + 3, p)
    assert np.array_equal(xb, x1[:, gen.BLOCK_ROWS:]) and np.array_equal(
        yb, y1[gen.BLOCK_ROWS:])
    buf = np.empty((67, gen.BLOCK_ROWS), np.float32)
    xs, ys = gen.draw_block(0, 5000, 67, 2 ** 31 + 3, p, out=buf)
    assert np.array_equal(xs, x1[:, :5000]) and np.array_equal(ys, y1[:5000])
    assert np.shares_memory(xs, buf)
    # shapes: counts are whole and heavy-tailed, rates lie in (0, 1)
    kinds = gen.column_kinds(67)
    assert kinds.count("count") == 13 + 26 + 1 and kinds.count("rate") == 27
    for j, kind in enumerate(kinds):
        col = x1[j]
        if kind == "count":
            assert np.array_equal(col, np.floor(col)) and col.min() >= 0
            assert col.max() > 20 * np.median(col) > 0
        else:
            assert 0 < col.min() and col.max() < 1
    # the click rate is the configuration's, to the binomial's spread
    for y in (y1, y2):
        assert abs(y.mean() - 0.0344092) < 5 * np.sqrt(0.0344 * 0.9656 / rows)


# -- built cases: a tree of the program's, rows on four shards -----------------------

def rows_and_clicks(distance: float, side: int, lo: int = 36000,
                    hi: int = 60000):
    """(rows, clicks, achieved distance) whose hessian ``p (1 - p)`` lies
    as near as whole numbers allow to ``distance`` (relative) below
    (``side`` -1) or above (+1) the midpoint of two bfloat16 values."""
    n = np.arange(lo, hi, dtype=np.float64)
    best = None
    for j in range(128, 256):
        mid = (j + 0.5) * 2.0 ** -12
        h = mid * (1.0 + side * distance)
        k = np.round(n * (1.0 - np.sqrt(1.0 - 4.0 * h)) / 2.0)
        got = (k / n) * (1.0 - k / n)
        d = side * (got - mid) / mid
        off = np.where(d > 0, np.abs(np.log(np.maximum(d, 1e-300) / distance)),
                       np.inf)
        i = int(np.argmin(off))
        if best is None or off[i] < best[0]:
            best = (off[i], int(n[i]), int(k[i]), float(d[i]))
    return best[1:]


def built_case(rows: int, clicks: int, seed: int = 7):
    """([COLS, rows] uint8 bins, labels with exactly ``clicks`` ones that
    the columns partly explain, bin upper bounds)."""
    gen = Manifest(ROOT).generator("criteo_like")
    x, _ = gen.generate(rows, COLS, seed, {"click_rate": clicks / rows})
    rng = np.random.default_rng([seed, rows, clicks])
    score = (np.log1p(x[5]) - np.log1p(x[0]) * (x[2] > 3)
             + rng.standard_normal(rows))
    y = np.zeros(rows, np.float32)
    y[np.argsort(score)[-clicks:]] = 1.0
    return x, y


def program_tree(x, y, four_chips, **more):
    """Tree 0 of the program under ``tree_learner=data`` on four devices,
    through the fused step: (model text, bounds, [cols, rows] bins,
    shard rows, the trainer)."""
    import lightgbm_tpu as lgb
    params = dict(PARAMS, **more)
    ds = lgb.Dataset(np.ascontiguousarray(x.T), label=y, params=params)
    bst = lgb.Booster(params, ds)
    bst.update(defer=True)
    bst._sync_trees()
    gb = bst._gbdt
    assert gb.fused_reason == "" and gb.plan.num_shards == 4
    ds = ds.construct()
    ubs = [np.asarray(ds.bin_mappers[f].bin_upper_bound, np.float64)
           for f in ds.used_features]
    bins_cm = np.ascontiguousarray(np.asarray(ds.bins).T)
    return (bst.model_to_string(), ubs, bins_cm,
            gb.train_dd.r_pad // gb.plan.num_shards, gb)


CASES = [(d, side) for d in (1e-9, 9.4e-7, 2.0 ** -15) for side in (-1, 1)]


@pytest.mark.parametrize("distance,side", CASES)
def test_a_hessian_near_a_rounding_boundary_passes_on_either_side(
        distance, side, four_chips):
    rows, clicks, got = rows_and_clicks(distance, side)
    assert 0.2 * distance <= got <= 5 * distance
    x, y = built_case(rows, clicks)
    text, ubs, bins_cm, shard_rows, _ = program_tree(x, y, four_chips)
    rep = sref.check_first_tree(text, ubs, bins_cm, y, PARAMS, shard_rows)
    assert rep["ok"], rep
    h = rep["addends"]["h"]
    assert h["boundary_distance"] == pytest.approx(got, rel=1e-3, abs=1e-12)
    assert len(h["admissible"]) == 2 and rep["values_on_a_rounding_boundary"] >= 1
    assert rep["roundings_tried"] in (1, 2) and rep["all_rows_reach_a_leaf"]
    # four shards, the last one short: the program pads it
    assert rep["shards"] == 4 and rep["rows_by_shard"][-1] < rep["rows_by_shard"][0]
    assert sum(rep["rows_by_shard"]) == rows
    assert rep["leaves"]["worst_error_over_limit"] < 1 / 8


def stump_text(bins_cm, y, ubs, g, h, lr=0.1):
    """Model text of the exact greedy one-split tree at addends ``g`` [2]
    and ``h`` (numpy float64, sums over all rows at once): what a program
    that rounded its addends to these values would build."""
    clicked = y > 0
    nb = max(len(u) for u in ubs)
    counts = np.stack([[np.bincount(col[~clicked], minlength=nb),
                        np.bincount(col[clicked], minlength=nb)]
                       for col in bins_cm]).transpose(0, 2, 1)   # [F, B, 2]
    per = np.array([[g[0], h, 1.0], [g[1], h, 1.0]])
    gains = ref.split_gains(counts @ per, 0.0, PARAMS["min_data_in_leaf"],
                            PARAMS["min_sum_hessian_in_leaf"])
    f, b = np.unravel_index(int(np.argmax(gains)), gains.shape)
    left = bins_cm[f] <= b
    init = ref.binary_init_score(y)
    vals, cnts = [], []
    for m in (left, ~left):
        n1 = int(clicked[m].sum())
        n0 = int(m.sum()) - n1
        G, H = n0 * g[0] + n1 * g[1], (n0 + n1) * h
        vals.append(float(init - lr * G / H))
        cnts.append(n0 + n1)
    return ("Tree=0\nnum_leaves=2\nnum_cat=0\n"
            f"split_feature={f}\nsplit_gain={float(gains[f, b])!r}\n"
            f"threshold={float(ubs[f][b])!r}\ndecision_type=0\n"
            "left_child=-1\nright_child=-2\n"
            f"leaf_value={vals[0]!r} {vals[1]!r}\nleaf_weight=1 1\n"
            f"leaf_count={cnts[0]} {cnts[1]}\ninternal_value=0\n"
            f"internal_weight=0\ninternal_count={len(y)}\nis_linear=0\n"
            "shrinkage=0.1\n\n")


@pytest.mark.parametrize("distance,margin,ok", [
    (9.4e-7, sref.BOUNDARY_MARGIN, True),     # the flip PR 34 read
    (2.0 ** -15, sref.BOUNDARY_MARGIN, True),
    (9.4e-7, 0.0, False),                     # the rule is what admits it
    (2.0 ** -13, sref.BOUNDARY_MARGIN, False)])   # outside the margin
@pytest.mark.parametrize("side", (-1, 1))
def test_the_other_neighbour_is_admitted_inside_the_margin_only(
        distance, margin, ok, side):
    """A tree built, in plain numpy, with the hessian rounded the *other*
    way: what the chip's float32 logistic did at seed 2147489008."""
    rows, clicks, got = rows_and_clicks(distance, side, 8000, 20000)
    x, y = built_case(rows, clicks)
    ubs = [np.append(np.unique(np.quantile(col, np.linspace(0, 1, 65)[1:-1])),
                     np.inf) for col in x]
    bins_cm = np.stack([np.searchsorted(u, col, side="left")
                        for u, col in zip(ubs, x)]).astype(np.uint8)
    init = ref.binary_init_score(y)
    g, h = ref.binary_gradients(np.array([0.0, 1.0]), np.full(2, init))
    below, above, near = sref.neighbours(h[0], 8)
    other = above if near == below else below
    g16 = [sref.neighbours(v, 8)[2] for v in g]
    text = stump_text(bins_cm, y, ubs, g16, other)
    shard = -(-rows // 4) + 3           # the last shard short
    rep = sref.check_first_tree(text, ubs, bins_cm, y, PARAMS, shard,
                                margin=margin)
    assert rep["ok"] is ok, rep
    assert rep["roundings_tried"] == (2 if margin and got <= margin else 1)
    if ok:
        assert rep["addends_used"]["h"] == other
    else:       # one bfloat16 step of the hessian: far over the limit
        assert rep["leaves"]["worst_error_over_limit"] > 4
    # the nearest rounding's own tree passes at once, whatever the margin
    own = sref.check_first_tree(stump_text(bins_cm, y, ubs, g16, near), ubs,
                                bins_cm, y, PARAMS, shard, margin=margin)
    assert own["ok"] and own["roundings_tried"] == 1


@pytest.mark.parametrize("roundings,ok", [(16, True), (128, False)])
def test_a_leaf_of_a_few_rows_may_carry_its_parents_float32_roundings(
        roundings, ok, monkeypatch):
    """Seed 2147566002 on the chip: 31 rows cut off a large node, their sum
    of gradients one float32 step of a far larger number off, which is
    more than 2^-11 of the leaf's own scale."""
    rng = np.random.default_rng(5)
    rows = 400000
    y = (rng.random(rows) < 0.03).astype(np.float32)
    bins_cm = rng.integers(0, 8, size=(2, rows), dtype=np.uint8)
    few = rng.choice(rows, 31, replace=False)
    bins_cm[0, few], y[few] = 9, 1.0            # a tail bin, all clicks
    ubs = [np.append(np.arange(9) + 0.5, np.inf)] * 2
    init = ref.binary_init_score(y)
    g, h = ref.binary_gradients(np.array([0.0, 1.0]), np.full(2, init))
    g16 = [sref.neighbours(v, 8)[2] for v in g]
    h16 = sref.neighbours(h[0], 8)[2]
    text = stump_text(bins_cm, y, ubs, g16, h16)
    tree = ref.parse_tree(text, 0)
    assert tree["leaf_count"].tolist() == [rows - 31, 31]
    shard = rows // 4 + 1
    sound = sref.check_first_tree(text, ubs, bins_cm, y, PARAMS, shard)
    assert sound["ok"] and sound["leaves"]["held_within_twice_rtol"] == 1
    # what the right side's sums are made of: its bin at the root (its own
    # rows) and twice the root's bins, the hessian's |value| times
    clicked = y > 0
    G_bins = [(~clicked[bins_cm[0] == b]).sum() * g16[0]
              + clicked[bins_cm[0] == b].sum() * g16[1] for b in range(10)]
    value = (tree["leaf_value"][1] - init) / 0.1
    of_g = 31 * abs(g16[1]) + 2 * np.abs(G_bins).sum()
    of_h = 31 * h16 + 2 * rows * h16
    # move the small leaf's value by some float32 roundings of that
    off = roundings * 2.0 ** -24 * (of_g + abs(value) * of_h)   # of sum g
    moved = tree["leaf_value"][1] + 0.1 * off / (31 * h16)
    want = of_g + abs(value + off / (31 * h16)) * of_h
    line = "leaf_value=" + text.split("leaf_value=")[1].split("\n")[0]
    text = text.replace(line, f"leaf_value={float(tree['leaf_value'][0])!r} "
                              f"{float(moved)!r}")
    rep = sref.check_first_tree(text, ubs, bins_cm, y, PARAMS, shard)
    assert rep["ok"] is ok, rep["leaves"]
    leaf = rep["leaves"]["worst_leaf"]
    assert leaf["leaf"] == 1 and leaf["rows"] == 31
    assert leaf["carried"] == pytest.approx(want, rel=1e-3)
    assert leaf["carried"] > 100 * leaf["sum_abs_g"]
    # by the leaf's own scale alone, both would be out
    assert rep["leaves"]["worst_error_over_scale"] > sref.RTOL
    monkeypatch.setattr(sref, "ACCUMULATION", 0.0)
    assert not sref.check_first_tree(text, ubs, bins_cm, y, PARAMS, shard)["ok"]


def test_chain_counts_are_a_columns_rows_down_the_subtraction_chain(four_chips):
    """The whole numbers behind a leaf's second limit, against a count of
    each node's rows one by one."""
    x, y = built_case(40900, 1407)
    text, ubs, bins_cm, _, _ = program_tree(x, y, four_chips)
    tree = ref.parse_tree(text, 0)
    tbins = ref.threshold_bins(tree, ubs)
    clicked = y > 0
    left_built, chains = sref.subtraction_chains(tree)
    node_rows, leaf_rows = ref.replay(tree, tbins, bins_cm)
    size = lambda c: len(leaf_rows[~c] if c < 0 else node_rows[c])  # noqa: E731
    long = 0
    for p, (l, r) in enumerate(zip(tree["left_child"], tree["right_child"])):
        assert left_built[p] == (size(l) <= size(r))
        for c, built in ((l, left_built[p]), (r, not left_built[p])):
            if c >= 0:
                assert chains[c] == ([c] if built else [c] + chains[p])
                long = max(long, len(chains[c]))
    assert chains[0] == [0] and long >= 3
    counts, leaves, own, chain = sref.block_counts(
        tree, tbins, bins_cm, clicked, 256, 5)
    by_blocks = sref.shard_counts(tree, tbins, bins_cm, clicked,
                                  [(0, 30000), (30000, 40900)], 256, 5)
    for k, whole in enumerate((counts, leaves, own, chain)):
        assert np.array_equal(by_blocks[0][k] + by_blocks[1][k], whole)

    def column(i, rows):
        col = bins_cm[tree["split_feature"][i]][rows]
        return np.stack([np.bincount(col[~clicked[rows]], minlength=256),
                         np.bincount(col[clicked[rows]], minlength=256)], 1)
    for i in range(len(tbins)):
        assert np.array_equal(own[i], column(i, node_rows[i]))
        assert np.array_equal(chain[i], sum(column(i, node_rows[a])
                                            for a in chains[i]))
    assert np.array_equal(leaves.sum(axis=1), tree["leaf_count"])


def test_neighbours_are_the_bfloat16_grid():
    rng = np.random.default_rng(3)
    for v in np.concatenate([rng.normal(size=200), [0.0344092, -0.9655908,
                                                   0.5, -2.0 ** -7]]):
        lo, hi, near = sref.neighbours(v, 8)
        assert lo <= v <= hi and near in (lo, hi)
        assert near == ref.to_bfloat16(v)[0]
        assert ref.to_bfloat16(lo)[0] == lo and ref.to_bfloat16(hi)[0] == hi
    # float8-e4m3 has four significand bits
    assert sref.neighbours(0.0344092, 4) == (0.03125, 0.03515625, 0.03515625)


def test_float8_addends_and_a_coarser_program_read_incorrect(four_chips):
    """The controls: the same comparison refuses addends kept in fewer
    bits than the configuration states, whichever side computes them."""
    x, y = built_case(40900, 1407)
    text, ubs, bins_cm, shard_rows, gb = program_tree(x, y, four_chips)
    good = sref.check_first_tree(text, ubs, bins_cm, y, PARAMS, shard_rows)
    assert good["ok"] and str(gb.config.hist_dtype) == "bfloat16"
    f8 = sref.check_first_tree(text, ubs, bins_cm, y, PARAMS, shard_rows,
                               addend_dtype="float8_e4m3")
    assert not f8["ok"]
    assert f8["leaves"]["worst_error_over_limit"] > 8
    # the sound run reports that control itself, from the same counts
    assert good["control"]["ok"] is False
    assert good["control"]["worst_error_over_limit"] == \
        f8["leaves"]["worst_error_over_limit"]
    with pytest.raises(ValueError, match="no rounding rule"):
        sref.check_first_tree(text, ubs, bins_cm, y, PARAMS, shard_rows,
                              addend_dtype="int8")
    # a program that sums 4-level integer gradients where bfloat16 is stated
    coarse = program_tree(x, y, four_chips, use_quantized_grad=True,
                          num_grad_quant_bins=4,
                          quant_train_renew_leaf=False)[0]
    rep = sref.check_first_tree(coarse, ubs, bins_cm, y, PARAMS, shard_rows)
    assert not rep["ok"]


# -- the four readers on hand-made inputs ---------------------------------------------

def _rec(rows, leaves):
    return SimpleNamespace(rows=np.asarray(rows), leaves=np.asarray(leaves))


def _run(counters, log=(), trace=True, kind="TPU v5 lite"):
    return SimpleNamespace(
        spans={}, counters=counters, trace=trace, memory={},
        shape={"rows": 1000, "cols": 10, "bins": 255},
        device={"kind": kind}, notes={},
        program=SimpleNamespace(round_log=list(log)))


def _read(name, run):
    return Manifest(ROOT).metric_reader(name).read(run)


PLAN = {"plan_shards": 4, "plan_rows_per_shard": 1000,
        "plan_collectives_per_round": {"all-reduce": 6, "reduce-scatter": 1},
        "plan_round_bytes_by_stage": {"hist_merge": 3 * 2 ** 20,
                                      "winner_sync": 2 ** 19, "count": 2 ** 19},
        "plan_tree_bytes_by_stage": {"hist_merge": 2 ** 21, "update": 4}}
LOG = [_rec([[5, 4, 0], [3, 6, 0], [4, 4, 0], [4, 2, 0]], [2, 2, 0]),
       _rec([[8, 1, 1], [8, 1, 3], [8, 1, 1], [8, 1, 3]], [2, 2, 2])]


def test_wire_mib_is_rounds_times_round_bytes_plus_the_trees_own():
    run = _run({"plan": PLAN, "trees": 2}, LOG)
    want = (5 * 4 * 2 ** 20 + 2 * (2 ** 21 + 4)) / 2 / 2 ** 20
    assert _read(NEW[2], run) == pytest.approx(want)
    assert run.notes[NEW[2]]["rounds_per_tree"] == 2.5
    assert _read(NEW[2], _run({"plan": PLAN, "trees": 2}, LOG,
                              trace=None)) == pytest.approx(want)


def test_shard_live_skew_is_the_fullest_shard_a_round_over_the_mean():
    run = _run({"trees": 2}, LOG)
    fullest = (5 + 6) + (8 + 1 + 3)
    mean = (9 + 9 + 8 + 6 + 10 + 12 + 10 + 12) / 4     # shards, tree by tree
    assert _read(NEW[3], run) == pytest.approx(100 * (fullest / mean - 1))
    assert run.notes[NEW[3]]["live_rows_by_shard"] == [19, 21, 18, 18]
    flat = [_rec([5, 4, 0], [2, 2, 0])]      # one shard: no shard axis
    assert _read(NEW[3], _run({"trees": 1}, flat)) is None


def test_exposed_share_is_the_collective_stages_on_the_chip_with_most():
    by_chip = {"TPU:0": {"hist_kernel": 9.0, "hist_merge": 0.5,
                         "winner_sync": 0.5},
               "TPU:1": {"hist_kernel": 9.5, "hist_merge": 0.25,
                         "winner_sync": 0.25}}
    run = _run({"trees": 2, "stage_s_by_chip": by_chip,
                "stage_s": {"hist_kernel": 9.25}})
    assert _read(NEW[0], run) == pytest.approx(10.0)
    note = run.notes[NEW[0]]
    assert note["chip"] == "TPU:0"
    assert note["busy_s_per_tree_by_chip"]["TPU:1"] == pytest.approx(5.0)


def test_merge_roofline_is_the_wire_bytes_over_two_ports_peak():
    stage_s = {"hist_merge": 0.004, "hist_kernel": 1.0}
    run = _run({"plan": PLAN, "trees": 2, "stage_s": stage_s}, LOG)
    sent = 5 * 3 * 2 ** 20 + 2 * 2 ** 21
    assert _read(NEW[1], run) == pytest.approx(100 * sent / 100e9 / 0.004)
    note = run.notes[NEW[1]]
    assert note["ports_wired"] == 2 and note["bytes_per_s"] == 100e9
    assert note["bytes_sent_a_chip"] == sent
    with pytest.raises(LookupError, match="no published wire peak"):
        _read(NEW[1], _run({"plan": PLAN, "trees": 2, "stage_s": stage_s},
                           LOG, kind="TPU v9"))


@pytest.mark.parametrize("name,counters,trace", [
    (NEW[0], {"trees": 2}, True),
    (NEW[0], {"trees": 2, "stage_s_by_chip": {"cpu:0": {"apply": 1.0}}}, None),
    (NEW[1], {"trees": 2, "stage_s": {"hist_merge": 1.0}}, True),
    (NEW[1], {"plan": PLAN, "trees": 2, "stage_s": {"hist_merge": 1.0}}, None),
    (NEW[1], {"plan": PLAN, "trees": 2, "stage_s": {"apply": 1.0}}, True),
    (NEW[2], {"trees": 2}, True),
    (NEW[2], {"plan": {"plan_shards": 4}, "trees": 2}, True),
    (NEW[3], {}, True)])
def test_a_reader_with_nothing_to_read_returns_nothing(name, counters, trace):
    """As on a parent commit, or without a device plane: no value, no
    exception."""
    assert _read(name, _run(counters, LOG, trace=trace)) is None


# -- the job: a rehearsal, and its refusal ----------------------------------------------

TINY_ROWS = 40900


@pytest.fixture()
def tiny_root(tmp_path):
    """A copy of the benchmark with the configuration cut to a tiny shape
    under the cell's own name; no file that was there is edited."""
    root = str(tmp_path)
    shutil.copytree(BENCH, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    cfg = json.load(open(os.path.join(BENCH, "configs", "criteo.json")))
    cfg["shape"]["rows"] = TINY_ROWS
    cfg["bin_sample_rows"] = 20000
    cfg["params"].update(num_leaves=31)
    json.dump(cfg, open(os.path.join(
        root, "benchmarks", "configs", "criteo.json"), "w"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    return root


@pytest.fixture()
def notes():
    seen = {}
    return seen, lambda label, obj: seen.__setitem__(label, obj)


def test_dp_cell_runs_as_a_rehearsal(tiny_root, notes, four_chips):
    seen, note = notes
    res = run_cell(tiny_root, CELL, 2147489008, 0.3, False,
                   require_tpu=False, note=note)
    assert res["correct"], seen["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) == {"train_row_trees_per_s", "setup_s"}
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == 4
    checks = seen["checks"]
    assert checks["kernel_as_stated"] is None       # off a TPU: not judged
    assert all(v for k, v in checks.items() if k != "kernel_as_stated")
    assert "collective_stages_on_every_chip" not in checks
    place = seen["placement"]
    assert place["plan"] == "DataParallelPlan" and place["shards"] == 4
    assert place["hist_merge"] == "reduce_scatter"
    assert sum(place["live_rows"]) == TINY_ROWS
    assert place["live_rows"][-1] < place["shard_rows"][-1]
    replay = seen["replay"]
    assert replay["ok"] and replay["shards"] == 4 and len(replay["splits"]) == 5
    assert replay["rows_by_shard"] == place["live_rows"]
    c = seen["counters"]
    assert c["compiles_in_window"] == 0 and c["shards"] == 4
    assert c["plan"]["plan_shards"] == 4
    assert c["plan"]["plan_collectives_per_round"]["reduce-scatter"] == 1
    assert seen["copies"]["copies"] == 4 and seen["copies"]["ok"]
    # the job's last note: every compared number beside its limit
    assert list(seen)[-4] == "compared"     # then the runner's three
    cmp = seen["compared"]
    assert all(s["gain_short_by"] <= s["limit"] for s in cmp["splits"])
    assert cmp["leaf_error_over_limit"] <= 1 and cmp["leaf_limit"] == ref.RTOL
    assert cmp["leaf_carried"] >= cmp["leaf_sum_abs_g"]
    assert cmp["control_ok"] is False and cmp["control_error_over_limit"] > 8
    assert cmp["least_live_rows_on_a_chip"] >= cmp["least_allowed"]
    assert set(cmp["addends"]) == {"g_no_click", "g_click", "h"}
    assert seen["spans_s"]["setup.refusal"] < 1.0

    res = run_cell(tiny_root, CELL, 2 ** 31 + 12, 0.3, True,
                   require_tpu=False, note=note)
    assert res["correct"], seen["checks"]
    m = res["metrics"]
    assert m["collectives.wire_mib_per_tree"]["value"] > 0
    assert m["builder.shard_live_skew"]["value"] >= 0
    assert "entry.step_ready_s" in m and "builder.stream_row_share" in m
    # a chip's seconds by stage: the mean over the chips of the capture
    c = seen["counters"]
    chips = list(c["stage_s_by_chip"].values())
    assert c["stage_s"]["hist_merge"] == pytest.approx(
        sum(p["hist_merge"] for p in chips) / len(chips))
    # no device plane on a CPU: the trace's readers leave their metric out
    for name in ("collectives.exposed_share", "collectives.merge_roofline",
                 "builder.rowwise_share"):
        assert name not in m
    # the shape handed to the readers is a chip's: the host's share is a note
    live = seen["per_layer_notes"]["builder.live_row_share"]
    assert live["rows"] == TINY_ROWS // 4
    assert m["builder.live_row_share"]["value"] == pytest.approx(
        4 * seen["live_row_share"]["pct_of_the_hosts_rows"])


def test_a_program_without_the_counters_is_refused_before_any_data(
        tiny_root, notes, monkeypatch):
    """As a parent commit: no ``plan_counters``, and the job stops before
    JAX is asked for a device and before any data is drawn."""
    from harness import device
    from harness.spans import Spans
    from lightgbm_tpu.parallel import comms
    monkeypatch.delattr(comms, "plan_counters")
    man = Manifest(tiny_root)
    job = man.job("train-dp")

    def never(*_, **__):
        raise AssertionError("the refusal came too late")
    monkeypatch.setattr(job, "_make_dataset", never)
    monkeypatch.setattr(device, "device_info", never)
    monkeypatch.setattr(device, "require_tpu", never)
    spans = Spans()
    env = SimpleNamespace(
        manifest=man, cell=man.cell(CELL), config=man.config("criteo"),
        traffic=man.traffic("train-dp-steady"), chips=4, seed=1, seconds=0.1,
        trace=False, t_start=0.0, require_tpu=True, spans=spans,
        note=notes[1], compile_counter=never)
    with pytest.raises(job.CannotRunCell, match="no parallel/comms.plan_counters"):
        job.run(env)
    assert spans.seconds["setup.refusal"] < 1.0
    assert notes[0] == {}
