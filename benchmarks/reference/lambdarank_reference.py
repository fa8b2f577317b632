"""A plain reference for LambdaMART training with NDCG deltas: gradients
and hessians, NDCG@k, and a check of one boosted tree against per-row
gradients. numpy float64, a loop over queries, no padding and no
batching, written from the reference implementation's published
definition (``LambdarankNDCG``) and importing nothing from the program.
The parsing, replay and split-gain helpers of ``gbdt_reference`` are
reused as they are.

For one query with documents ``0..n-1``, scores ``s`` and integer labels
``y``:

    order     = documents by score, descending; ties keep document order
    rank(d)   = position of d in that order, from 0
    gain(d)   = label_gain[y_d]                 (default 2^y - 1)
    disc(r)   = 1 / log2(2 + r)                 (every rank, not only the
                                                 first T)
    maxDCG    = sum of the T largest gains, the k-th times disc(k)

    for i in 0 .. min(n - 1, T) - 1:            T = truncation level
      for j in i + 1 .. n - 1:                  (i, j are ranks)
        skip if the two labels are equal
        high, low = the one with the larger label, the other
        ds    = s_high - s_low
        delta = (gain_high - gain_low) * |disc(rank high) - disc(rank low)|
                / maxDCG
        if norm and the query's best score != its worst:
            delta /= 0.01 + |ds|
        p     = 1 / (1 + exp(sigmoid * ds))
        lam   = sigmoid * p * delta
        hes   = sigmoid^2 * p * (1 - p) * delta
        g[high] -= lam;  g[low] += lam;  h[high] += hes;  h[low] += hes
        total += 2 * lam
    if norm and total > 0:  g, h *= log2(1 + total) / total

The reference implementation reads its sigmoid from a table of 2^20
entries over +-50/sigmoid and accumulates in float32; this file takes
``exp`` and float64, which is what the table and the sums approximate.

PRECISION, for the tree check: as ``gbdt_reference``: each row's gradient
and hessian is rounded to the addend type the configuration states
(bfloat16) and summed exactly; ``RTOL = 2^-11`` is a quarter of one such
rounding. The gradients themselves are compared with the program's at
``GRAD_RTOL`` of the query's largest |g| (see there).
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence

import numpy as np

from reference.gbdt_reference import (RTOL, parse_tree, replay, split_gains,
                                      threshold_bins, to_bfloat16)

# The program computes the pair terms in float32 from float32 scores. A
# pair's delta holds a difference of two rank discounts, which cancels to
# 1e-3 of them at the window's edge (1/log2(31) - 1/log2(32)), so their
# float32 rounding alone is 1e-5 of the term, and the regularised delta
# divides by 0.01 + |ds|, which carries ds's rounding. PERF.md section 4
# gives the largest error seen over the seeds run on the chip and what
# the reference gives from bfloat16 scores; the limit lies between the
# two, of the query's largest |g| or |h|.
GRAD_RTOL = 2.0 ** -10


def default_label_gain(max_label: int) -> np.ndarray:
    return np.array([(1 << i) - 1 for i in range(max(max_label + 1, 2))],
                    np.float64)


def _params(params: Dict, max_label: int):
    lg = params.get("label_gain") or default_label_gain(max_label)
    return (np.asarray(lg, np.float64),
            int(params.get("lambdarank_truncation_level", 30)),
            bool(params.get("lambdarank_norm", True)),
            float(params.get("sigmoid", 1.0)))


def max_dcg(gains: np.ndarray, k: int) -> float:
    top = np.sort(gains)[::-1][:k]
    return float(np.sum(top / np.log2(np.arange(2, 2 + len(top)))))


def query_gradients_loops(s, y, label_gain, trunc, norm, sigmoid):
    """The definition above, pair by pair. Slow; the tests hold
    :func:`query_gradients` to it."""
    n = len(s)
    g, h = np.zeros(n), np.zeros(n)
    order = sorted(range(n), key=lambda d: -s[d])     # sorted() is stable
    mdcg = max_dcg(label_gain[y], trunc)
    if n < 2 or mdcg <= 0:
        return g, h
    spread = s[order[0]] != s[order[-1]]
    total = 0.0
    for i in range(min(n - 1, trunc)):
        for j in range(i + 1, n):
            a, b = order[i], order[j]
            if y[a] == y[b]:
                continue
            (hi, r_hi), (lo, r_lo) = ((a, i), (b, j)) if y[a] > y[b] \
                else ((b, j), (a, i))
            ds = s[hi] - s[lo]
            delta = ((label_gain[y[hi]] - label_gain[y[lo]])
                     * abs(1 / np.log2(2 + r_hi) - 1 / np.log2(2 + r_lo))
                     / mdcg)
            if norm and spread:
                delta /= 0.01 + abs(ds)
            p = 1.0 / (1.0 + np.exp(sigmoid * ds))
            lam = sigmoid * p * delta
            hes = sigmoid * sigmoid * p * (1 - p) * delta
            g[hi] -= lam
            g[lo] += lam
            h[hi] += hes
            h[lo] += hes
            total += 2 * lam
    if norm and total > 0:
        f = np.log2(1 + total) / total
        g, h = g * f, h * f
    return g, h


def query_gradients(s, y, label_gain, trunc, norm, sigmoid):
    """The same for one query, the rows ``i`` of the window against all
    ``j`` at once: a ``[min(n - 1, T), n]`` block in rank order."""
    n = len(s)
    g, h = np.zeros(n), np.zeros(n)
    mdcg = max_dcg(label_gain[y], trunc)
    if n < 2 or mdcg <= 0:
        return g, h
    order = np.argsort(-s, kind="stable")
    ss, ys = s[order], y[order]
    gain = label_gain[ys]
    disc = 1.0 / np.log2(2.0 + np.arange(n))
    t = min(n - 1, trunc)
    i_high = ys[:t, None] > ys[None, :]
    pair = (np.arange(t)[:, None] < np.arange(n)[None, :]) \
        & (ys[:t, None] != ys[None, :])
    sign = np.where(i_high, 1.0, -1.0)           # high minus low
    ds = sign * (ss[:t, None] - ss[None, :])
    delta = (sign * (gain[:t, None] - gain[None, :])
             * np.abs(disc[:t, None] - disc[None, :]) / mdcg)
    if norm and ss[0] != ss[-1]:
        delta = delta / (0.01 + np.abs(ds))
    with np.errstate(over="ignore"):
        p = 1.0 / (1.0 + np.exp(sigmoid * ds))
    lam = np.where(pair, sigmoid * p * delta, 0.0)
    hes = np.where(pair, sigmoid * sigmoid * p * (1 - p) * delta, 0.0)
    to_i = np.where(i_high, -lam, lam)       # the high one falls by lam
    gs = -to_i.sum(axis=0)
    gs[:t] += to_i.sum(axis=1)
    hs = hes.sum(axis=0)
    hs[:t] += hes.sum(axis=1)
    total = 2.0 * lam.sum()
    if norm and total > 0:
        f = np.log2(1 + total) / total
        gs, hs = gs * f, hs * f
    g[order], h[order] = gs, hs
    return g, h


def lambdarank_gradients(score, label, boundaries, params: Dict,
                         queries: Optional[Sequence[int]] = None):
    """[rows] g and h of every query (or of ``queries`` only, the rest
    zero), from float64 scores and integer labels."""
    score = np.asarray(score, np.float64)
    y = np.asarray(label).astype(np.int64)
    lg, trunc, norm, sig = _params(params, int(y.max()) if len(y) else 0)
    g, h = np.zeros(len(score)), np.zeros(len(score))
    qs = range(len(boundaries) - 1) if queries is None else queries
    for q in qs:
        lo, hi = int(boundaries[q]), int(boundaries[q + 1])
        g[lo:hi], h[lo:hi] = query_gradients(score[lo:hi], y[lo:hi], lg,
                                             trunc, norm, sig)
    return g, h


def ndcg_at_k(score, label, boundaries, k: int,
              label_gain: Optional[np.ndarray] = None) -> float:
    """Mean NDCG@k over the queries; a query with no relevant document
    counts 1, as the reference implementation's metric does."""
    score = np.asarray(score, np.float64)
    y = np.asarray(label).astype(np.int64)
    lg = default_label_gain(int(y.max())) if label_gain is None \
        else np.asarray(label_gain, np.float64)
    total = 0.0
    for q in range(len(boundaries) - 1):
        lo, hi = int(boundaries[q]), int(boundaries[q + 1])
        gains = lg[y[lo:hi]]
        best = max_dcg(gains, k)
        if best <= 0:
            total += 1.0
            continue
        top = gains[np.argsort(-score[lo:hi], kind="stable")[:k]]
        total += float(np.sum(top / np.log2(np.arange(2, 2 + len(top))))) / best
    return total / max(len(boundaries) - 1, 1)


# -- the model's scores, replayed ------------------------------------------------

def replay_scores(model_text: str, trees: int,
                  upper_bounds: Sequence[np.ndarray], bins_cm: np.ndarray,
                  learning_rate: float, start=None) -> np.ndarray:
    """[rows] raw score after the first ``trees`` trees of the model text,
    in the score type the configuration states: float32, a tree's leaf
    outputs (float32, what the text holds divided by the rate) times the
    float32 rate, added tree by tree in float32. The order of two
    documents whose scores differ in the last place decides their ranks,
    so the scores are replayed in the stated type and not in float64;
    the gradients from them are float64. The init score of a ranking
    objective is 0. ``start`` = (k, the scores after k trees) goes on from
    there (the same float32 additions in the same order)."""
    first, score = (0, np.zeros(bins_cm.shape[1], np.float32)) \
        if start is None else (start[0], start[1].copy())
    rate = np.float32(learning_rate)
    for t in range(first, trees):
        tree = parse_tree(model_text, t)
        _, leaf_rows = replay(tree, threshold_bins(tree, upper_bounds),
                              bins_cm)
        outputs = (tree["leaf_value"] / learning_rate).astype(np.float32)
        for rows, out in zip(leaf_rows, outputs):
            score[rows] += out * rate
    return score


# -- one tree against per-row gradients --------------------------------------------

def node_histogram(bins_cm, num_bins, g, h, rows=None) -> np.ndarray:
    """[features, num_bins, 3] sums of (g, h, 1) of the node's rows by
    bin: three ``bincount``s a column, two of them weighted."""
    out = np.empty((bins_cm.shape[0], num_bins, 3))
    gg, hh = (g, h) if rows is None else (g[rows], h[rows])
    for f in range(bins_cm.shape[0]):
        b = bins_cm[f] if rows is None else bins_cm[f][rows]
        out[f, :, 0] = np.bincount(b, weights=gg, minlength=num_bins)
        out[f, :, 1] = np.bincount(b, weights=hh, minlength=num_bins)
        out[f, :, 2] = np.bincount(b, minlength=num_bins)
    return out


def check_tree(model_text: str, index: int,
               upper_bounds: Sequence[np.ndarray], bins_cm: np.ndarray,
               g: np.ndarray, h: np.ndarray, params: Dict,
               addend_dtype: str = "bfloat16", init_score: float = 0.0,
               nodes_checked: int = 5) -> Dict:
    """Hold tree ``index`` to the exact greedy split and the leaf formula
    over per-row gradients ``g``, ``h`` (float64, as the reference gives
    them at the scores the tree was grown from). ``init_score`` is what
    tree 0's leaf values hold besides. Returns a report whose ``ok`` is
    the verdict."""
    lr = float(params["learning_rate"])
    l2 = float(params.get("lambda_l2", 0.0))
    min_data = int(params["min_data_in_leaf"])
    min_hess = float(params["min_sum_hessian_in_leaf"])
    num_bins = max(len(u) for u in upper_bounds)
    if addend_dtype == "bfloat16":
        g, h = to_bfloat16(g), to_bfloat16(h)
    elif addend_dtype == "float32":
        g = np.asarray(g, np.float32).astype(np.float64)
        h = np.asarray(h, np.float32).astype(np.float64)
    else:
        raise ValueError(f"no rounding rule for addends of {addend_dtype!r}")
    tree = parse_tree(model_text, index)
    tbins = threshold_bins(tree, upper_bounds)
    node_rows, leaf_rows = replay(tree, tbins, bins_cm)

    splits = []
    for i in range(min(nodes_checked, len(tbins))):
        hist = node_histogram(bins_cm, num_bins, g, h,
                              None if i == 0 else node_rows[i])
        gains = split_gains(hist, l2, min_data, min_hess)
        rf, rb = np.unravel_index(int(np.argmax(gains)), gains.shape)
        tf, tb = int(tree["split_feature"][i]), int(tbins[i])
        best, own = float(gains[rf, rb]), float(gains[tf, tb])
        splits.append({
            "node": i, "tree": [tf, tb], "reference": [int(rf), int(rb)],
            "tree_gain": own, "reference_gain": best,
            "recorded_gain": float(tree["split_gain"][i]),
            "ok": bool((tf, tb) == (rf, rb)
                       or best - own <= RTOL * abs(best))})

    counts = np.array([len(r) for r in leaf_rows])
    counts_ok = bool(counts.sum() == bins_cm.shape[1]
                     and np.array_equal(counts, tree["leaf_count"]))
    G = np.array([g[r].sum() for r in leaf_rows])
    H = np.array([h[r].sum() for r in leaf_rows])
    A = np.array([np.abs(g[r]).sum() for r in leaf_rows])
    got = (tree["leaf_value"] - (init_score if index == 0 else 0.0)) / lr
    scale = np.where(A > 0, A, 1.0) / (H + l2)
    worst = float(np.max(np.abs(got + G / (H + l2)) / scale))
    report = {"tree": index, "addend_dtype": addend_dtype, "rtol": RTOL,
              "splits": splits,
              "leaves": {"n": int(tree["num_leaves"]), "counts_ok": counts_ok,
                         "values_ok": bool(worst <= RTOL),
                         "worst_error_over_scale": worst}}
    report["ok"] = bool(splits and all(s["ok"] for s in splits) and counts_ok
                        and report["leaves"]["values_ok"])
    return report
