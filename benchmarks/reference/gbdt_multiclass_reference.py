"""A plain reference for the first two iterations of a multiclass
(softmax) model, and for the scores its trees give: numpy float64, written from the
reference implementation's definition and importing nothing from the
program (the exact greedy split, the gain, the leaf value and their
limits are ``gbdt_reference``'s and ``gbdt_sharded_reference``'s).

    p_k      = softmax(scores)_k
    g_k      = p_k - [label == k]
    h_k      = K / (K - 1) * p_k * (1 - p_k)     (multiclass_objective.hpp
                                                  factor_)
    init_k   = log(max(n_k / n, 1e-15))          (BoostFromScore)

and one tree a class an iteration, grown on (g_k, h_k) as a binary tree
is on its own.

ITERATION 0. The scores are the init scores, so ``p_k`` is the class
prior on every row: every row has one of two (g, h) pairs, by whether its
label is k, with the same h. Class k's tree 0 is then a binary tree 0 of
the labels "label is k" with these three addends, and it is held as
``gbdt_sharded_reference`` holds a binary tree 0, on one shard: whole-
number counts by (column, bin, label is k) at the first five nodes and by
(leaf, label is k), the best split or one within ``RTOL`` (2^-11) of its
gain, leaf counts equal, and a leaf's value within ``RTOL x sum|g|`` of
its own rows **plus** ``ACCUMULATION`` (2^-19) of the float32 numbers its
sums were carried from down its subtraction chain (``_carried``: a leaf
of a few rows cut off a node of millions inherits roundings of sums far
larger than its own; at seed 2147483661 a leaf of the label with 10,413
rows read 1.12 times the bare ``RTOL`` limit, my chip runs, PR 45).
Where an addend lies within 2^-14 of a bfloat16 rounding boundary both
neighbours are admissible (the chip rounds its float32 softmax, the
reference its float64 one); at the published counts two of the 69
addends do (label 12's g of another label's row, 5.4e-6 from a midpoint,
and label 0's g of its own rows, 4.8e-5). The control, evaluated in
every check: the same tree and counts against addends rounded to
float8-e4m3 (the nearest precision below the bfloat16 stated), which has
to read not ok. One addition to the split rule: a gain is a difference
of three terms ``G^2 / (H + l2)`` (left, right, the node), so a split
passes too where its gain is within ``ACCUMULATION x G_P^2 / (H_P + l2)``
of the best (32 float32 roundings of the node's own term; about 0 at a
root, where ``G_P`` is about 0 at iteration 0). It matters only where
the best split gains nothing measurable: a node of one label's rows
alone, whose every row has the same (g, h), has every split's gain 0 in
exact arithmetic, and float32 sums pick one of them at a gain of
rounding noise (LightGBM's double sums would too, wherever they come out
above 0).
The root's counts of every class come from one ``bincount`` of (bin,
label) a column, which also says which classes cannot split.

A CLASS WHOSE ROOT CANNOT SPLIT (no split leaves ``min_sum_hessian_in_leaf``
on both sides: the root holds ``n K / (K - 1) p (1 - p)`` of hessian,
about ``n_k``) gets a tree of one leaf, whose output is the init score:
LightGBM's ``GBDT::TrainOneIter`` keeps a tree with ``num_leaves() <= 1``
as it is (the fresh one-leaf tree's output is 0), makes the first
iteration's ``AsConstantTree(init_scores[k])``, and leaves that class's
scores as they were. Later iterations' such trees are 0, and the scores'
walk below adds them as they are written.

ITERATION 1. The scores are iteration 0's trees' (a float64 walk of
their text, :func:`walk_scores`), and they differ row by row, so every
row has its own softmax addends: this is where the objective's
dependence on the scores, the factor ``K / (K - 1)`` after iteration 0
and the class each tree is grown for are seen (a step that took its
gradients from iteration 0's scores, lost the factor after iteration 0,
or stacked another class's (g, h) passes iteration 0 and fails here).
Class k's tree ``K + k`` is held to the exact greedy split over
histograms of every row's own addends rounded to bfloat16
(:func:`check_second_iteration_tree`, weighted ``bincount`` a column):
the first five splits the best or within ``RTOL`` of its gain (and the
gain floor above), leaf counts equal, and a leaf's value within ``RTOL x
sum|g|`` of its rows plus ``ACCUMULATION`` of the float32 numbers its
sums were carried from (``gbdt_sharded_reference._carried`` with each
row's own addends). The rounding boundaries are iteration 0's rule, row
by row: a row whose g or h lies within ``BOUNDARY_MARGIN`` (2^-14,
relative) of the midpoint of its two bfloat16 neighbours may have been
rounded either way on the chip, whose float32 scores and softmax differ
from the float64 walk by a few 2^-24 of the value, so the leaf's limit
counts one rounding step of each such row (:func:`round_rows`). The
control: the same leaves against addends rounded to float8-e4m3, which
has to read not ok. A root that cannot split gives a tree of one leaf of
output 0. Held for the largest class and the two drawn ones.

SCORES. After training, the device's raw scores of a seeded sample of
rows against a float64 walk of every tree of the model text (tree ``t``
belongs to class ``t % K``), ``SCORE_RTOL_PER_TREE`` a tree of a class:
the device adds a float32 output a tree to a float32 score.
"""

from __future__ import annotations

import itertools
from typing import Dict, List, Sequence, Tuple

import numpy as np

from reference import gbdt_linear_reference as lref
from reference import gbdt_reference as ref
from reference import gbdt_sharded_reference as sref

RTOL = ref.RTOL
SCORE_RTOL_PER_TREE = lref.SCORE_RTOL_PER_TREE
# a seeded ascending sample of row ids, the last row among them
sample_rows = lref.sample_rows


def init_scores(y: np.ndarray, classes: int) -> np.ndarray:
    n = np.bincount(y.astype(np.int64), minlength=classes)
    return np.log(np.maximum(n / n.sum(), 1e-15))


def multi_logloss(y: np.ndarray, scores: np.ndarray) -> float:
    """Mean of ``logsumexp(s) - s[label]`` over the rows; ``scores`` is
    [rows, K]."""
    s = np.asarray(scores, np.float64)
    top = s.max(axis=1)
    lse = top + np.log(np.exp(s - top[:, None]).sum(axis=1))
    return float(np.mean(lse - s[np.arange(len(y)), y.astype(np.int64)]))


def first_pairs(y: np.ndarray, classes: int, addend_dtype: str
                ) -> np.ndarray:
    """[K, 2, 3]: class k's (g, h, 1) for a row of another label (0) and
    of label k (1) at iteration 0, rounded as the addends are."""
    p = np.exp(init_scores(y, classes))
    p = p / p.sum()
    h = classes / (classes - 1.0) * p * (1.0 - p)
    g = np.stack([p, p - 1.0], axis=1)                      # [K, 2]
    h = np.stack([h, h], axis=1)
    if addend_dtype == "bfloat16":
        g, h = ref.to_bfloat16(g).reshape(g.shape), \
            ref.to_bfloat16(h).reshape(h.shape)
    elif addend_dtype != "float32":
        raise ValueError(f"no rounding rule for addends of {addend_dtype!r}")
    return np.stack([g, h, np.ones_like(g)], axis=2)


def root_counts(bins_cm: np.ndarray, num_bins: int, y: np.ndarray,
                classes: int) -> np.ndarray:
    """[features, num_bins, K] rows by (bin, label), one ``bincount`` a
    column."""
    yi = y.astype(np.int64)
    out = np.empty((bins_cm.shape[0], num_bins * classes), np.int64)
    for f in range(bins_cm.shape[0]):
        out[f] = np.bincount(bins_cm[f].astype(np.int64) * classes + yi,
                             minlength=num_bins * classes)
    return out.reshape(-1, num_bins, classes)


def _one_vs_rest(counts_k: np.ndarray, total: np.ndarray) -> np.ndarray:
    """[features, num_bins, 2]: rows of another label, rows of label k."""
    return np.stack([total - counts_k, counts_k], axis=2)


def parse_any(model_text: str, index: int) -> Dict:
    """``gbdt_reference.parse_tree``, and a tree of one leaf as its
    ``num_leaves`` and ``leaf_value`` alone."""
    start = model_text.index(f"Tree={index}\n")
    end = model_text.find("\nTree=", start + 1)
    block = model_text[start:end if end > 0 else None]
    fields = dict(line.split("=", 1) for line in block.splitlines()
                  if "=" in line)
    if int(fields["num_leaves"]) > 1:
        return ref.parse_tree(model_text, index)
    return {"num_leaves": 1,
            "leaf_value": np.array(fields["leaf_value"].split(), np.float64)}


def _class_addends(y: np.ndarray, classes: int, k: int):
    """float64 (g of another label's row, g of a row of label k, h) of
    class ``k`` at iteration 0."""
    p = np.exp(init_scores(y, classes))
    p = p / p.sum()
    return (float(p[k]), float(p[k] - 1.0),
            float(classes / (classes - 1.0) * p[k] * (1.0 - p[k])))


def _with_gain_floor(cmp: Dict, per_shard, g, h, l2: float) -> Dict:
    """``sref._compare``'s verdict with this module's gain floor: a split
    also passes where it is within ``ACCUMULATION x G_P^2 / (H_P + l2)``
    of the best (the module's docstring)."""
    per_label = np.stack([g, h, np.ones(2)], axis=1)
    for s in cmp["splits"]:
        hist = sum(c[0][s["node"]] @ per_label for c in per_shard)
        G, H = hist[0].sum(axis=0)[:2]
        s["gain_floor"] = float(sref.ACCUMULATION * G * G / (H + l2))
        s["ok"] = bool(s["ok"] or s["gain_short_by"] <= s["gain_floor"])
    cmp["ok"] = bool(all(s["ok"] for s in cmp["splits"])
                     and cmp["leaves"]["counts_ok"]
                     and cmp["leaves"]["values_ok"])
    return cmp


def check_class_tree(model_text: str, k: int, classes: int, upper_bounds,
                     bins_cm: np.ndarray, y: np.ndarray, params: Dict,
                     roots: np.ndarray, addend_dtype: str = "bfloat16",
                     nodes_checked: int = 5,
                     control_dtype: str = "float8_e4m3") -> Dict:
    """Tree ``k`` of the model (iteration 0's tree of class ``k``) against
    the reference: the one-leaf rule where the reference's root cannot
    split; else the tree as a binary tree 0 of the labels "label is k"
    under ``gbdt_sharded_reference``'s comparison on one shard (the first
    ``nodes_checked`` splits, leaf counts, leaf values within ``RTOL x
    sum|g|`` plus ``ACCUMULATION`` of what they were carried from, every
    admissible rounding of addends near a bfloat16 boundary), with this
    module's gain floor, and its control: the same counts and tree
    against addends rounded to ``control_dtype``, which has to read not
    ok."""
    lr = float(params["learning_rate"])
    l2 = float(params.get("lambda_l2", 0.0))
    min_data = int(params["min_data_in_leaf"])
    min_hess = float(params["min_sum_hessian_in_leaf"])
    num_bins = roots.shape[1]
    init = float(init_scores(y, classes)[k])
    per = first_pairs(y, classes, addend_dtype)[k]           # [2, 3]
    clicked = y.astype(np.int64) == k
    root = _one_vs_rest(roots[:, :, k], roots.sum(axis=2)) @ per
    root_gains = ref.split_gains(root, l2, min_data, min_hess)
    can_split = bool(np.isfinite(root_gains).any()
                     and root_gains.max() > 0.0)
    tree = parse_any(model_text, k)
    report = {"class": int(k), "rows": int(clicked.sum()),
              "root_hessian": float(root[0, :, 1].sum()),
              "can_split": can_split, "num_leaves": int(tree["num_leaves"])}
    if not can_split:
        err = abs(float(tree["leaf_value"][0]) - init)
        report.update(leaf_value=float(tree["leaf_value"][0]),
                      init_score=init, ok=bool(
                          tree["num_leaves"] == 1
                          and err <= 1e-12 * max(1.0, abs(init))))
        return report
    if tree["num_leaves"] == 1:
        report["ok"] = False
        return report
    tbins = ref.threshold_bins(tree, upper_bounds)
    per_shard = sref.shard_counts(tree, tbins, bins_cm, clicked,
                                  [(0, len(y))], num_bins,
                                  min(nodes_checked, len(tbins)))
    g_other, g_class, h = _class_addends(y, classes, k)
    addends = {"g_other": sref.admissible(g_other, addend_dtype),
               "g_class": sref.admissible(g_class, addend_dtype),
               "h": sref.admissible(h, addend_dtype)}
    tried, chosen = 0, None
    for g0, g1, hh in itertools.product(
            *(addends[n]["admissible"] for n in ("g_other", "g_class", "h"))):
        g, hv = np.array([g0, g1]), np.array([hh, hh])
        cmp = _with_gain_floor(sref._compare(
            tree, tbins, per_shard, g, hv, init, lr, l2, min_data, min_hess),
            per_shard, g, hv, l2)
        cmp["addends_used"] = {"g_other": g0, "g_class": g1, "h": hh}
        tried += 1          # the nearest rounding of all three comes first
        if chosen is None or sref._badness(cmp) < sref._badness(chosen):
            chosen = cmp
        if cmp["ok"]:
            break
    report.update(chosen, roundings_tried=tried,
                  all_rows_reach_a_leaf=bool(
                      int(per_shard[0][1].sum()) == len(y)))
    report["ok"] = bool(chosen["ok"] and report["all_rows_reach_a_leaf"])
    bits = sref.SIGNIFICAND_BITS[control_dtype]
    cg = np.array([sref.neighbours(v, bits)[2] for v in (g_other, g_class)])
    ch = np.full(2, sref.neighbours(h, bits)[2])
    control = sref._compare(tree, tbins, per_shard, cg, ch, init, lr, l2,
                            min_data, min_hess)
    report["control"] = {
        "addend_dtype": control_dtype, "ok": control["ok"],
        "worst_error_over_limit":
            control["leaves"]["worst_error_over_limit"]}
    return report


def classes_to_check(y: np.ndarray, classes: int, roots: np.ndarray,
                     pairs: np.ndarray, params: Dict, seed: int,
                     drawn: int = 2) -> Dict[str, List[int]]:
    """Every class whose root cannot split, the largest, and ``drawn``
    more splitting classes drawn by ``seed``."""
    l2 = float(params.get("lambda_l2", 0.0))
    total = roots.sum(axis=2)
    splits = []
    for k in range(classes):
        gains = ref.split_gains(_one_vs_rest(roots[:, :, k], total)
                                @ pairs[k], l2,
                                int(params["min_data_in_leaf"]),
                                float(params["min_sum_hessian_in_leaf"]))
        splits.append(bool(np.isfinite(gains).any() and gains.max() > 0))
    n = np.bincount(y.astype(np.int64), minlength=classes)
    largest = int(np.argmax(n))
    rest = [k for k in range(classes) if splits[k] and k != largest]
    rng = np.random.default_rng([seed, classes])
    pick = sorted(int(k) for k in rng.choice(
        rest, size=min(drawn, len(rest)), replace=False))
    return {"cannot_split": [k for k in range(classes) if not splits[k]],
            "largest": [largest], "drawn": pick}


def check_first_iteration(model_text: str, classes: int, upper_bounds,
                          bins_cm: np.ndarray, y: np.ndarray, params: Dict,
                          seed: int, addend_dtype: str = "bfloat16") -> Dict:
    """Iteration 0's trees of the classes :func:`classes_to_check` names,
    each held to the reference; ``compared`` holds the worst leaf over
    its limit of the splitting classes beside the control's worst, and
    the control's verdict over them (``control_ok``), which has to be
    false."""
    num_bins = max(len(u) for u in upper_bounds)
    roots = root_counts(bins_cm, num_bins, y, classes)
    pairs = first_pairs(y, classes, addend_dtype)
    picked = classes_to_check(y, classes, roots, pairs, params, seed)
    trees = [check_class_tree(model_text, k, classes, upper_bounds, bins_cm,
                              y, params, roots, addend_dtype)
             for k in sorted({k for ks in picked.values() for k in ks})]
    grown = [t for t in trees if "control" in t]
    compared = {
        "leaf_error_over_limit": max(
            (t["leaves"]["worst_error_over_limit"] for t in grown),
            default=0.0),
        "control_error_over_limit": max(
            (t["control"]["worst_error_over_limit"] for t in grown),
            default=0.0),
        "control_ok": all(t["control"]["ok"] for t in grown),
        "roundings_tried": max((t["roundings_tried"] for t in grown),
                               default=0)}
    return {"addend_dtype": addend_dtype, "rtol": RTOL, "picked": picked,
            "compared": compared, "trees": trees,
            "ok": bool(all(t["ok"] for t in trees))}


# -- iteration 1 ----------------------------------------------------------------

def log_partition(scores: np.ndarray) -> np.ndarray:
    """[rows] ``logsumexp`` of float64 ``scores`` [rows, K]."""
    s = np.asarray(scores, np.float64)
    top = s.max(axis=1)
    return top + np.log(np.exp(s - top[:, None]).sum(axis=1))


def softmax_addends(scores: np.ndarray, y: np.ndarray, k: int,
                    lse: np.ndarray = None
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """float64 (g, h) of class ``k`` at ``scores`` [rows, K]; ``lse`` is
    :func:`log_partition` of them where the caller has it."""
    if lse is None:
        lse = log_partition(scores)
    p = np.exp(np.asarray(scores[:, k], np.float64) - lse)
    classes = scores.shape[1]
    return (p - (y.astype(np.int64) == k),
            classes / (classes - 1.0) * p * (1.0 - p))


def round_rows(x: np.ndarray, addend_dtype: str,
               margin: float = sref.BOUNDARY_MARGIN
               ) -> Tuple[np.ndarray, np.ndarray]:
    """(``x`` rounded to ``addend_dtype`` to nearest even, the other
    neighbour's distance where ``x`` lies within ``margin`` (relative) of
    the midpoint of its two neighbours, else 0): ``sref.admissible`` row
    by row."""
    x = np.asarray(x, np.float64)
    if addend_dtype == "float32":
        return x.astype(np.float32).astype(np.float64), np.zeros_like(x)
    bits = sref.SIGNIFICAND_BITS[addend_dtype]
    m, e = np.frexp(np.abs(x))                  # m in [0.5, 1)
    scaled = m * 2.0 ** bits
    lo = np.floor(scaled)
    near = np.ldexp(np.round(scaled) / 2.0 ** bits, e)
    mid = np.ldexp((lo + 0.5) / 2.0 ** bits, e)
    step = np.ldexp(np.full_like(x, 1.0) / 2.0 ** bits, e)
    with np.errstate(invalid="ignore", divide="ignore"):
        close = (np.abs(np.abs(x) - mid) <= margin * mid) & (lo != scaled)
    return np.sign(x) * near, np.where(close, step, 0.0)


def _column_sums(col: np.ndarray, w: np.ndarray, num_bins: int
                 ) -> np.ndarray:
    """[num_bins, C] sums of the columns of ``w`` [C, n] by bin
    (``bincount`` converts its input to ``intp``: once, here)."""
    col = col.astype(np.intp)
    return np.stack([np.bincount(col, weights=wc, minlength=num_bins)
                     for wc in w], axis=1)


def _node_hist(bins_cm: np.ndarray, rows, w: np.ndarray, num_bins: int
               ) -> np.ndarray:
    """[features, num_bins, C] sums of ``w`` [C, rows] by (column, bin)
    over the node's rows (all where ``rows`` is None)."""
    wr = w if rows is None else w[:, rows]
    return np.stack([_column_sums(bins_cm[f] if rows is None
                                  else bins_cm[f][rows], wr, num_bins)
                     for f in range(bins_cm.shape[0])])


def _checked_hists(tree: Dict, node_rows, root: np.ndarray,
                   bins_cm: np.ndarray, w: np.ndarray, num_bins: int,
                   nodes: int) -> Dict[int, np.ndarray]:
    """The histograms of internal nodes ``0 .. nodes - 1``: where both
    children of a node are among them, the one of fewer rows is summed
    over its rows and the other is the parent's less that one (float64:
    the difference is exact to far below ``RTOL``)."""
    hists = {0: root}
    for i in range(nodes):
        kids = [int(c) for c in (tree["left_child"][i],
                                 tree["right_child"][i])
                if 0 < c < nodes]
        kids.sort(key=lambda c: len(node_rows[c]))
        if not kids:
            continue
        hists[kids[0]] = _node_hist(bins_cm, node_rows[kids[0]], w,
                                    num_bins)
        if len(kids) == 2:
            hists[kids[1]] = hists[i] - hists[kids[0]]
    return hists


def _weighted_carried(tree: Dict, tbins: np.ndarray, bins_cm: np.ndarray,
                      node_rows, g: np.ndarray, h: np.ndarray,
                      num_bins: int) -> np.ndarray:
    """[leaves, 2] ``sref._carried`` with every row's own addends: (sum
    |g|, sum h) of the float32 numbers a leaf's two sums are made of,
    its side's bins of the parent's split column at every node of the
    parent's chain, and for a right side twice the parent's own bins."""
    _, chains = sref.subtraction_chains(tree)
    w_abs, w_own = np.stack([np.abs(g), h]), np.stack([g, h])
    out = np.zeros((tree["num_leaves"], 2))
    seen: Dict = {}

    def sums(q, f):          # a node's (sum |g|, sum h) by bin of column f
        if (q, f) not in seen:
            seen[q, f] = _column_sums(bins_cm[f][node_rows[q]],
                                      w_abs[:, node_rows[q]], num_bins)
        return seen[q, f]
    for i, t in enumerate(tbins):
        kids = (int(tree["left_child"][i]), int(tree["right_child"][i]))
        if kids[0] >= 0 and kids[1] >= 0:
            continue
        f = int(tree["split_feature"][i])
        col = bins_cm[f]
        chain = sum(sums(q, f) for q in chains[i])
        t = int(t)
        if kids[0] < 0:
            out[~kids[0]] = chain[:t + 1].sum(axis=0)
        if kids[1] < 0:
            own = _column_sums(col[node_rows[i]], w_own[:, node_rows[i]],
                               num_bins)
            out[~kids[1]] = chain[t + 1:].sum(axis=0) \
                + 2.0 * np.abs(own).sum(axis=0)
    return out


def _leaf_check(tree: Dict, leaf: np.ndarray, g: np.ndarray, h: np.ndarray,
                slack: np.ndarray, carried: np.ndarray, lr: float,
                l2: float) -> Dict:
    """Leaf values against ``-G / (H + l2)`` of the rows the tree sends
    them, within ``RTOL x sum|g|`` plus ``ACCUMULATION`` of what they were
    carried from plus ``slack`` ([leaves, 2]: the rounding steps of the
    rows whose addend may round either way, in g and in h)."""
    L = tree["num_leaves"]
    G = np.bincount(leaf, weights=g, minlength=L)
    H = np.bincount(leaf, weights=h, minlength=L)
    A = np.bincount(leaf, weights=np.abs(g), minlength=L)
    n = np.bincount(leaf, minlength=L)
    got = tree["leaf_value"] / lr
    with np.errstate(invalid="ignore", divide="ignore"):
        off = np.abs(got * (H + l2) + G)
        limit = RTOL * A + sref.ACCUMULATION * (
            carried[:, 0] + np.abs(got) * carried[:, 1]) \
            + slack[:, 0] + np.abs(got) * slack[:, 1]
        over = np.where(n == 0, np.inf, off / limit)
    worst = int(np.argmax(over))
    return {"counts_ok": bool(np.array_equal(n, tree["leaf_count"])),
            "worst_error_over_limit": float(over[worst]),
            "worst_leaf": {"leaf": worst, "rows": int(n[worst]),
                           "sum_abs_g": float(A[worst]),
                           "value": float(tree["leaf_value"][worst])},
            "values_ok": bool(over[worst] <= 1.0)}


def check_second_iteration_tree(model_text: str, k: int, classes: int,
                                upper_bounds, bins_cm: np.ndarray,
                                y: np.ndarray, params: Dict,
                                scores: np.ndarray,
                                addend_dtype: str = "bfloat16",
                                nodes_checked: int = 5,
                                control_dtype: str = "float8_e4m3",
                                lse: np.ndarray = None) -> Dict:
    """Iteration 1's tree of class ``k`` (tree ``K + k``) against the
    reference grown on softmax addends at ``scores`` [rows, K] (the
    float64 walk of iteration 0's trees), every row's own (g, h) rounded
    to ``addend_dtype``: the first ``nodes_checked`` splits the best or
    within ``RTOL`` of its gain (and this module's gain floor), leaf
    counts equal, and leaf values within ``RTOL x sum|g|`` plus
    ``ACCUMULATION`` of what they were carried from plus one rounding
    step of every row of the leaf whose addend lies within
    ``BOUNDARY_MARGIN`` of a rounding midpoint (the chip rounds its
    float32 softmax, the reference its float64 one). A root that cannot
    split has to give one leaf of output 0. The control: the same tree's
    leaves against addends rounded to ``control_dtype``, not ok."""
    lr = float(params["learning_rate"])
    l2 = float(params.get("lambda_l2", 0.0))
    min_data = int(params["min_data_in_leaf"])
    min_hess = float(params["min_sum_hessian_in_leaf"])
    num_bins = max(len(u) for u in upper_bounds)
    g64, h64 = softmax_addends(scores, y, k, lse)
    (g, sg), (h, sh) = round_rows(g64, addend_dtype), \
        round_rows(h64, addend_dtype)
    w = np.stack([g, h, np.ones_like(g)])
    root = _node_hist(bins_cm, None, w, num_bins)
    root_gains = ref.split_gains(root, l2, min_data, min_hess)
    can_split = bool(np.isfinite(root_gains).any()
                     and root_gains.max() > 0.0)
    tree = parse_any(model_text, classes + k)
    report = {"class": int(k), "tree": classes + k, "can_split": can_split,
              "num_leaves": int(tree["num_leaves"]),
              "rows_within_margin": int(np.count_nonzero(sg)
                                        + np.count_nonzero(sh))}
    if not can_split or tree["num_leaves"] == 1:
        report["ok"] = bool(not can_split and tree["num_leaves"] == 1
                            and tree["leaf_value"][0] == 0.0)
        return report
    tbins = ref.threshold_bins(tree, upper_bounds)
    node_rows, leaf_rows = ref.replay(tree, tbins, bins_cm)
    nodes = min(nodes_checked, len(tbins))
    hists = _checked_hists(tree, node_rows, root, bins_cm, w, num_bins,
                           nodes)
    splits = []
    for i in range(nodes):
        hist = hists[i]
        gains = ref.split_gains(hist, l2, min_data, min_hess)
        rf, rb = np.unravel_index(int(np.argmax(gains)), gains.shape)
        tf, tb = int(tree["split_feature"][i]), int(tbins[i])
        best, own = float(gains[rf, rb]), float(gains[tf, tb])
        G, H = hist[0].sum(axis=0)[:2]
        floor = float(sref.ACCUMULATION * G * G / (H + l2))
        splits.append({"node": i, "tree": [tf, tb],
                       "reference": [int(rf), int(rb)], "tree_gain": own,
                       "reference_gain": best, "limit": RTOL * abs(best),
                       "gain_floor": floor,
                       "ok": bool((tf, tb) == (rf, rb)
                                  or best - own <= RTOL * abs(best) + floor)})
    leaf = np.empty(len(y), np.int64)
    for j, r in enumerate(leaf_rows):
        leaf[r] = j
    L = tree["num_leaves"]
    slack = np.stack([np.bincount(leaf, weights=sg, minlength=L),
                      np.bincount(leaf, weights=sh, minlength=L)], axis=1)
    carried = _weighted_carried(tree, tbins, bins_cm, node_rows, g, h,
                                num_bins)
    leaves = _leaf_check(tree, leaf, g, h, slack, carried, lr, l2)
    cg, ch = round_rows(g64, control_dtype, 0.0)[0], \
        round_rows(h64, control_dtype, 0.0)[0]
    control = _leaf_check(tree, leaf, cg, ch, slack, carried, lr, l2)
    report.update(splits=splits, leaves=leaves, control={
        "addend_dtype": control_dtype, "ok": control["values_ok"],
        "worst_error_over_limit": control["worst_error_over_limit"]})
    report["ok"] = bool(all(s["ok"] for s in splits) and leaves["counts_ok"]
                        and leaves["values_ok"])
    return report


def check_second_iteration(model_text: str, classes: int, upper_bounds,
                           bins_cm: np.ndarray, y: np.ndarray, params: Dict,
                           picked: Dict[str, List[int]],
                           addend_dtype: str = "bfloat16") -> Dict:
    """Iteration 1's trees of the largest class and of the drawn ones
    (``picked``, :func:`classes_to_check`'s), each held to the reference
    at the scores a float64 walk of iteration 0's trees gives every row
    (:func:`check_second_iteration_tree`)."""
    scores = walk_scores(model_text, classes, classes, upper_bounds,
                         bins_cm)
    lse = log_partition(scores)
    trees = [check_second_iteration_tree(model_text, k, classes,
                                         upper_bounds, bins_cm, y, params,
                                         scores, addend_dtype, lse=lse)
             for k in sorted(set(picked["largest"] + picked["drawn"]))]
    grown = [t for t in trees if "control" in t]
    compared = {
        "leaf_error_over_limit": max(
            (t["leaves"]["worst_error_over_limit"] for t in grown),
            default=0.0),
        "control_error_over_limit": max(
            (t["control"]["worst_error_over_limit"] for t in grown),
            default=0.0),
        "control_ok": all(t["control"]["ok"] for t in grown)}
    return {"addend_dtype": addend_dtype, "compared": compared,
            "trees": trees, "ok": bool(all(t["ok"] for t in trees))}


# -- the scores ---------------------------------------------------------------

def walk_scores(model_text: str, trees: int, classes: int,
                upper_bounds: Sequence[np.ndarray], bins_cm: np.ndarray,
                rows: np.ndarray = None) -> np.ndarray:
    """[len(rows), K] float64 raw scores after the first ``trees`` trees
    of the model text (iteration 0's hold the init scores), of every row
    where ``rows`` is None: each tree's leaves as
    ``gbdt_reference.replay`` sends the rows."""
    b = bins_cm if rows is None else np.ascontiguousarray(bins_cm[:, rows])
    out = np.zeros((b.shape[1], classes))
    for t in range(trees):
        tree = parse_any(model_text, t)
        if tree["num_leaves"] == 1:
            out[:, t % classes] += tree["leaf_value"][0]
            continue
        _, leaf_rows = ref.replay(
            tree, ref.threshold_bins(tree, upper_bounds), b)
        for r, v in zip(leaf_rows, tree["leaf_value"]):
            out[r, t % classes] += v
    return out


def check_scores(model_text: str, trees: int, classes: int, upper_bounds,
                 bins_cm: np.ndarray, device_scores: np.ndarray,
                 rows: np.ndarray) -> Dict:
    """``max |device - walk| / (1 + max |walk|)`` over the sampled rows
    and every class, against ``SCORE_RTOL_PER_TREE`` times a class's
    trees."""
    walk = walk_scores(model_text, trees, classes, upper_bounds, bins_cm,
                       rows)
    gap = float(np.abs(device_scores[rows] - walk).max()) \
        / (1.0 + float(np.abs(walk).max()))
    limit = SCORE_RTOL_PER_TREE * -(-trees // classes)
    return {"trees": trees, "rows": int(len(rows)), "score_error": gap,
            "score_limit": limit, "score_error_over_limit": gap / limit,
            "ok": bool(gap <= limit)}
