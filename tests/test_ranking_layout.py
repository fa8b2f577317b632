"""The ranking objectives on their bucketed query layout (ISSUE 28),
held to the plain reference of the benchmark
(``benchmarks/reference/lambdarank_reference.py``): gradients at uneven
query lengths and tied scores, the layout's counters and their bound, a
trained tree against the reference's tree check, and the fused step's
constants."""

import contextlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import phases, profiler
from lightgbm_tpu.ranking import (LambdaRank, QueryLayout, RankXENDCG,
                                  bucket_width)

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import lambdarank_reference as ref  # noqa: E402

TRUNC = 30


def _heavy_tail(rng, queries=60):
    """Query sizes whose longest is 40 times their median."""
    sizes = np.clip(np.round(8 * np.exp(rng.normal(0, 0.6, queries))), 1,
                    None).astype(np.int64)
    sizes[:3] = (1, 2, TRUNC + 1)
    sizes[3] = 40 * int(np.median(sizes))
    return sizes


SIZE_CASES = {
    "one_row": [1],
    "two_rows": [2],
    "one_over_the_window": [TRUNC + 1],
    "sizes_1_2_31": [1, 2, TRUNC + 1],
    "heavy_tail": None,
}


def _case(name, seed=0):
    rng = np.random.default_rng(seed)
    sizes = np.asarray(SIZE_CASES[name] or _heavy_tail(rng))
    qb = np.concatenate([[0], np.cumsum(sizes)])
    n = int(qb[-1])
    y = rng.integers(0, 5, n).astype(np.float64)
    s = np.round(rng.normal(size=n), 1).astype(np.float32)   # ties
    return sizes, qb, y, s


def _objective(cls, qb, y, **params):
    obj = cls(lgb.Config(dict({"objective": cls.name}, **params)))
    obj.init(y, None, qb)
    return obj


@pytest.mark.parametrize("name", sorted(SIZE_CASES))
@pytest.mark.parametrize("scores", ["tied", "all_equal"])
def test_lambdarank_gradients_match_the_reference(name, scores):
    _, qb, y, s = _case(name)
    if scores == "all_equal":
        s = np.zeros_like(s)
    obj = _objective(LambdaRank, qb, y)
    g, h = obj.get_gradients(jnp.asarray(s), jnp.asarray(y, jnp.float32),
                             None)
    gr, hr = ref.lambdarank_gradients(s, y, qb, {})
    scale = max(np.abs(gr).max(), 1e-3)
    assert np.abs(np.asarray(g) - gr).max() <= 1e-5 * scale
    assert np.abs(np.asarray(h) - hr).max() <= 1e-5 * scale
    if name == "one_row":
        assert not np.asarray(g).any() and not np.asarray(h).any()


@pytest.mark.parametrize("params", [
    {"lambdarank_norm": False},
    {"lambdarank_truncation_level": 3},
    {"sigmoid": 2.0},
    {"label_gain": [0, 1, 3, 7, 20]},
])
def test_lambdarank_parameters_match_the_reference(params):
    _, qb, y, s = _case("heavy_tail", seed=1)
    obj = _objective(LambdaRank, qb, y, **params)
    g, h = obj.get_gradients(jnp.asarray(s), jnp.asarray(y, jnp.float32),
                             None)
    gr, hr = ref.lambdarank_gradients(s, y, qb, params)
    scale = np.abs(gr).max()
    assert np.abs(np.asarray(g) - gr).max() <= 1e-5 * scale
    assert np.abs(np.asarray(h) - hr).max() <= 1e-5 * scale


def test_reference_block_form_equals_its_pair_loop():
    _, qb, y, s = _case("heavy_tail", seed=2)
    lg = ref.default_label_gain(4)
    for q in range(len(qb) - 1):
        sq = s[qb[q]:qb[q + 1]].astype(np.float64)
        yq = y[qb[q]:qb[q + 1]].astype(np.int64)
        for norm in (True, False):
            a = ref.query_gradients_loops(sq, yq, lg, TRUNC, norm, 1.0)
            b = ref.query_gradients(sq, yq, lg, TRUNC, norm, 1.0)
            np.testing.assert_allclose(a[0], b[0], atol=1e-12)
            np.testing.assert_allclose(a[1], b[1], atol=1e-12)


def _worst_over_query_scale(g, gr, qb):
    """The benchmark's measure: the largest error of a query over the
    query's largest reference |g|."""
    worst = 0.0
    for q in range(len(qb) - 1):
        sl = slice(qb[q], qb[q + 1])
        scale = np.abs(gr[sl]).max()
        if scale > 0:
            worst = max(worst, np.abs(g[sl] - gr[sl]).max() / scale)
    return worst


def test_a_dropped_truncation_rule_or_bfloat16_scores_fail_the_limit():
    """The two faults the benchmark's gradient limit must catch, and the
    program inside it with room."""
    _, qb, y, _ = _case("heavy_tail", seed=3)
    s = np.random.default_rng(3).normal(size=len(y)).astype(np.float32)
    gr, _ = ref.lambdarank_gradients(s, y, qb, {})
    no_window, _ = ref.lambdarank_gradients(
        s, y, qb, {"lambdarank_truncation_level": 10 ** 6})
    s16 = np.asarray(jnp.asarray(s).astype(jnp.bfloat16).astype(jnp.float32))
    coarse, _ = ref.lambdarank_gradients(s16, y, qb, {})
    for other in (no_window, coarse):
        assert _worst_over_query_scale(other, gr, qb) > 4 * ref.GRAD_RTOL
    obj = _objective(LambdaRank, qb, y)
    g, _ = obj.get_gradients(jnp.asarray(s), jnp.asarray(y, jnp.float32),
                             None)
    assert _worst_over_query_scale(np.asarray(g), gr, qb) \
        < ref.GRAD_RTOL / 16


@pytest.mark.parametrize("name", sorted(SIZE_CASES))
def test_xendcg_gradients_match_the_formula(name):
    """rho = softmax(s) a query, phi = 2^y - gamma with the iteration's
    uniform draw, g = rho - phi / sum(phi), h = rho (1 - rho)."""
    _, qb, y, s = _case(name)
    obj = _objective(RankXENDCG, qb, y)
    it = jnp.asarray(4, jnp.int32)
    g, h = obj.get_gradients(jnp.asarray(s), jnp.asarray(y, jnp.float32),
                             None, it=it)
    gamma = np.asarray(obj.gammas(it, len(s)), np.float64)
    for q in range(len(qb) - 1):
        lo, hi = qb[q], qb[q + 1]
        e = np.exp(s[lo:hi].astype(np.float64) - s[lo:hi].max())
        rho = e / e.sum()
        phi = 2.0 ** y[lo:hi] - gamma[lo:hi]
        np.testing.assert_allclose(np.asarray(g)[lo:hi],
                                   rho - phi / phi.sum(), atol=2e-6)
        np.testing.assert_allclose(np.asarray(h)[lo:hi],
                                   np.maximum(rho * (1 - rho), 1e-16),
                                   atol=2e-6)


def test_bucket_widths_are_the_ladder():
    n = np.array([1, 8, 9, 31, 100, 128, 129, 192, 193, 257, 385, 1251])
    want = [8, 8, 16, 32, 128, 128, 192, 192, 256, 384, 512, 1536]
    assert bucket_width(n).tolist() == want
    every = np.arange(1, 5000)
    w = bucket_width(every)
    assert (w >= every).all() and (w < 2 * np.maximum(every, 8)).all()


def test_layout_holds_every_row_once_and_counts_itself():
    sizes, qb, y, _ = _case("heavy_tail", seed=5)
    lay = QueryLayout(qb)
    rows = np.concatenate([b["rows"].reshape(-1) for b in lay.buckets])
    real = np.sort(rows[rows < qb[-1]])
    assert np.array_equal(real, np.arange(qb[-1]))
    assert lay.slots == len(rows)
    flat = np.concatenate([b["rows"].reshape(-1) for b in lay.buckets])
    assert np.array_equal(flat[lay.slot_of_row], np.arange(qb[-1]))
    assert lay.pairs == int((sizes ** 2).sum())
    assert lay.max_query == sizes.max() == 40 * int(np.median(sizes))
    obj = _objective(LambdaRank, qb, y)
    c = obj.counters
    assert c["queries"] == len(sizes) and c["slots"] == lay.slots
    # a query of n rows takes (2 W + min(T, W)) x W pair positions (the
    # comparison that ranks it, the read of its discounts, the window's
    # passes) with W < 2 max(n, 8)
    assert c["pair_slots"] <= 12 * c["pairs"] + 192 * c["queries"]
    assert c["pair_slots"] == sum(
        b["rows"].shape[0] * (2 * b["rows"].shape[1]
                              + min(TRUNC, b["rows"].shape[1]))
        * b["rows"].shape[1] for b in lay.buckets)
    # the longest query no longer sets the cost: the padded square is
    # queries x max^2
    assert c["pair_slots"] < 0.1 * len(sizes) * int(sizes.max()) ** 2


def test_objective_init_span_carries_the_counters():
    _, qb, y, _ = _case("heavy_tail", seed=6)
    seq = profiler.recorder.seq
    obj = _objective(LambdaRank, qb, y)
    spans = [s for s in profiler.recorder.since(seq)
             if s.name == "objective.init"]
    assert len(spans) == 1
    assert {k: spans[0].fields[k] for k in obj.counters} == obj.counters
    assert set(obj.counters) == {"pair_slots", "pairs", "slots", "queries",
                                 "max_query"}
    assert "objective.init" in phases.HOST_SPANS
    assert phases.GRADS_STAGES <= phases.KNOWN_PHASES


@contextlib.contextmanager
def _pin_fused(on):
    prev = os.environ.get("LIGHTGBM_TPU_FUSED_TRAIN")
    os.environ["LIGHTGBM_TPU_FUSED_TRAIN"] = "1" if on else "0"
    try:
        yield
    finally:
        if prev is None:
            os.environ.pop("LIGHTGBM_TPU_FUSED_TRAIN", None)
        else:
            os.environ["LIGHTGBM_TPU_FUSED_TRAIN"] = prev


def _rank_data(seed=0, queries=80, cols=6):
    rng = np.random.default_rng(seed)
    sizes = _heavy_tail(rng, queries)
    n = int(sizes.sum())
    X = rng.normal(size=(n, cols)).astype(np.float32)
    z = X[:, 0] + 0.5 * X[:, 1] * X[:, 2] + 0.4 * rng.normal(size=n)
    y = np.clip(np.round(z + 1.0), 0, 4).astype(np.float32)
    return X, y, sizes


PARAMS = dict(objective="lambdarank", num_leaves=15, max_bin=31,
              learning_rate=0.1, min_data_in_leaf=1,
              min_sum_hessian_in_leaf=1e-3, hist_dtype="float32",
              tree_learner="serial", verbosity=-1)


@pytest.fixture(scope="module")
def trained():
    X, y, sizes = _rank_data()
    with _pin_fused(True):
        ds = lgb.Dataset(X, label=y, group=sizes).construct()
        bst = lgb.Booster(dict(PARAMS), ds)
        for _ in range(3):
            bst.update(defer=True)
        bst._sync_trees()
    ubs = [np.asarray(ds.bin_mappers[f].bin_upper_bound, np.float64)
           for f in ds.used_features]
    bins_cm = np.ascontiguousarray(np.asarray(ds.bins).T)
    qb = np.concatenate([[0], np.cumsum(sizes)])
    return bst, bins_cm, ubs, y, qb


@pytest.mark.parametrize("index", [0, 2])
def test_trained_tree_passes_the_reference_tree_check(trained, index):
    """Tree 0 (all scores equal) and a later tree (scores spread), each
    against the reference's gradients at the scores replayed from the
    model text: root and next nodes' splits, leaf counts, leaf values."""
    bst, bins_cm, ubs, y, qb = trained
    assert bst._gbdt.fused_reason == ""
    text = bst.model_to_string()
    score = ref.replay_scores(text, index, ubs, bins_cm,
                              PARAMS["learning_rate"])
    g, h = ref.lambdarank_gradients(score, y, qb, PARAMS)
    rep = ref.check_tree(text, index, ubs, bins_cm, g, h, PARAMS,
                         addend_dtype="float32")
    assert rep["ok"], rep
    assert rep["leaves"]["n"] == 15 and rep["splits"][0]["ok"]
    # and not with gradients of another truncation level
    g2, h2 = ref.lambdarank_gradients(
        score, y, qb, dict(PARAMS, lambdarank_truncation_level=2))
    assert not ref.check_tree(text, index, ubs, bins_cm, g2, h2, PARAMS,
                              addend_dtype="float32")["ok"]


def test_replayed_scores_and_ndcg_follow_the_program(trained):
    bst, bins_cm, ubs, y, qb = trained
    text = bst.model_to_string()
    score = ref.replay_scores(text, 3, ubs, bins_cm, PARAMS["learning_rate"])
    got = bst._gbdt.eval_scores(-1)[:, 0]
    np.testing.assert_allclose(score, got, atol=1e-7)
    before = ref.ndcg_at_k(np.zeros(len(y)), y, qb, 10)
    after = ref.ndcg_at_k(score, y, qb, 10)
    assert after > before


def _closed_constant_bytes(gb):
    from lightgbm_tpu.analysis.doctor import _fused_trace_args
    closed = jax.make_jaxpr(gb._fused_step_entry)(*_fused_trace_args(gb))
    return max([int(np.asarray(c).nbytes) for c in closed.consts] or [0])


def test_fused_ranking_step_holds_no_large_constant():
    """The query lattices enter the fused step as arguments. The set is
    sized so that closed over they would be over 1 MiB."""
    rng = np.random.default_rng(7)
    sizes = np.full(4000, 70)
    n = int(sizes.sum())
    X = rng.normal(size=(n, 3)).astype(np.float32)
    y = rng.integers(0, 3, n).astype(np.float32)
    with _pin_fused(True):
        ds = lgb.Dataset(X, label=y, group=sizes)
        bst = lgb.Booster(dict(PARAMS, num_leaves=4), ds)
        bst.update(defer=True)
        gb = bst._gbdt
        lattice = sum(int(a.nbytes) for a in jax.tree.leaves(
            gb.objective.device_state))
        assert lattice > (1 << 20)
        assert _closed_constant_bytes(gb) < (1 << 20)
        data = gb._fused_data_args()
    assert data["rank"] is gb.objective.device_state
    puts = [s for s in profiler.recorder.spans("gbdt.to_device")]
    assert puts, "the lattices go to the device under gbdt.to_device"


def test_trace_doctor_lints_a_ranking_booster():
    from lightgbm_tpu.analysis import merge_errors
    from lightgbm_tpu.analysis.doctor import (CANONICAL_CONFIGS,
                                              doctor_fused_step,
                                              make_booster)
    assert "lambdarank" in CANONICAL_CONFIGS
    bst = make_booster("lambdarank", "serial")
    reports = doctor_fused_step(bst, compile_hlo=False)
    assert reports and not merge_errors(reports)
    assert not any(f.rule == "TD000" for r in reports for f in r.findings)


def test_grads_stages_reach_the_compiled_step(trained):
    """Every ranking stage is on some instruction's path in the compiled
    fused step, so a device event can be laid to it."""
    from lightgbm_tpu.telemetry import costmodel
    bst = trained[0]
    with _pin_fused(True):
        sm = costmodel.instruction_phase_map(
            costmodel.fused_compiled(bst, force=False).as_text())
    assert phases.GRADS_STAGES <= set(sm.stages.values())
