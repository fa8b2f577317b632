"""The plain reference for linear leaves: the ridge fit of
``LinearTreeLearner::CalculateLinear`` (linear_tree_learner.cpp; Shi, Li &
Li, "Gradient Boosting with Piece-Wise Linear Regression Trees", IJCAI
2019) in numpy float64, over the leaves of the program's trees as its
model text states them, importing nothing from the program. Tree growth
is ``gbdt_reference``'s.

A leaf ``l`` of a tree that is not the model's first fits, over its rows
with no NaN in its branch's numerical features ``P_l`` (sorted ids),

    [beta; beta_0] = -(X~' H X~ + Lambda)^-1 X~' g,   X~ = [x_P, 1],

``Lambda`` = ``linear_lambda`` on the feature diagonal only. Fewer such
rows than ``|P_l| + 1``, or a solution that is not finite, keeps the leaf
constant (``leaf_const`` = its output, no features); a coefficient within
``K_ZERO`` (kZeroThreshold) of zero is dropped. The first tree keeps every
leaf constant. A row's output is ``leaf_const + sum coeff x`` over the
leaf's model, or the leaf's output where one of those features is NaN;
the text's values are shrunk, so the reference's are times the rate.

WHAT IS COMPARED. Outputs, row by row, not coefficients: a leaf's outputs
are well determined where its coefficients are not (two path features
that move together in a deep leaf trade coefficients at no cost to the
fit). ``output_error`` is the largest ``|program - reference|`` over the
rows, over the largest ``|reference|`` output of the tree (one scale a
tree: the size of what the tree adds to a score).

THE CONTROL. The same fit with the Gram and right-hand side accumulated
from raw values rounded to bfloat16 (the precision a careless device fit
would take them in), its outputs evaluated on the exact values: the
control's ``output_error`` against the same reference has to break
``OUTPUT_RTOL``.

THE LIMITS (PERF.md section 6 has the readings they were set from:
rehearsals of the cell through ``run_cell`` on a CPU, where the sound
program read 0.01-0.02 of ``OUTPUT_RTOL`` and the control 15-29 times it,
and two seeds on a v5e, 0.037-0.043 against the control's 7.0-7.3):

- ``OUTPUT_RTOL`` on ``output_error`` of tree 1, the first linear tree.
- ``SCORE_RTOL_PER_TREE`` on the device scores after the window against a float64
  walk of the model text of every tree on a seeded sample of rows:
  ``max |device - walk| / (1 + max |walk|)``. The device adds a float32
  output a tree (a sum of ``F`` float32 products) to a float32 score, so
  the gap grows with the trees; the limit is a bound a tree times the
  window's trees.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

from reference import gbdt_reference as ref

K_ZERO = 1e-35
OUTPUT_RTOL = 2.0 ** -14
SCORE_RTOL_PER_TREE = 2.0 ** -20
SAMPLE_ROWS = 1 << 17


def parse_linear(model_text: str, index: int) -> Dict:
    """``gbdt_reference.parse_tree`` plus the linear block: ``is_linear``,
    ``leaf_const`` [leaves], and per leaf its features and coefficients."""
    tree = ref.parse_tree(model_text, index)
    start = model_text.index(f"Tree={index}\n")
    end = model_text.find("\nTree=", start + 1)
    block = model_text[start:end if end > 0 else None]
    fields = dict(line.split("=", 1) for line in block.splitlines()
                  if "=" in line)
    n = tree["num_leaves"]
    tree["is_linear"] = fields.get("is_linear", "0") == "1"
    tree["leaf_const"] = (np.array(fields["leaf_const"].split(), np.float64)
                          if tree["is_linear"] else tree["leaf_value"].copy())
    counts = (np.array(fields["num_features"].split(), np.int64)
              if tree["is_linear"] else np.zeros(n, np.int64))
    feats = [int(v) for v in fields.get("leaf_features", "").split()]
    coefs = [float(v) for v in fields.get("leaf_coeff", "").split()]
    at = np.concatenate([[0], np.cumsum(counts)])
    tree["leaf_features"] = [feats[at[i]:at[i + 1]] for i in range(n)]
    tree["leaf_coeff"] = [np.array(coefs[at[i]:at[i + 1]]) for i in range(n)]
    return tree


def branch_features(tree: Dict) -> List[List[int]]:
    """Each leaf's sorted numerical split features along its branch."""
    out: List[List[int]] = [[] for _ in range(tree["num_leaves"])]
    stack = [(0, frozenset())]
    while stack and len(tree["split_feature"]):
        node, path = stack.pop()
        path = path | {int(tree["split_feature"][node])}
        for child in (tree["left_child"][node], tree["right_child"][node]):
            if child < 0:
                out[~child] = sorted(path)
            else:
                stack.append((int(child), path))
    return out


def fit_leaf(vals: np.ndarray, g: np.ndarray, h: np.ndarray, lam: float,
             gram: str = "float32"):
    """One leaf's model over its rows' values ``vals`` [rows, k] (float64,
    the branch's features): ``(const, coeff [k])`` unshrunk, 0 where a
    coefficient is dropped, or None where the leaf stays constant.
    ``gram`` "bfloat16" is the control: the sums taken from rounded
    values."""
    ok = ~np.isnan(vals).any(axis=1)
    d = vals.shape[1]
    if ok.sum() < d + 1:
        return None
    v = vals[ok]
    if gram == "bfloat16":
        v = ref.to_bfloat16(v.ravel()).reshape(v.shape)
    A = np.concatenate([v, np.ones((len(v), 1))], axis=1)
    M = (A * h[ok][:, None]).T @ A
    M[np.arange(d), np.arange(d)] += lam
    try:
        beta = -np.linalg.solve(M, A.T @ g[ok])
    except np.linalg.LinAlgError:
        return None
    if not np.isfinite(beta).all():
        return None
    return beta[d], np.where(np.abs(beta[:d]) > K_ZERO, beta[:d], 0.0)


def linear_output(vals: np.ndarray, const: float, coeff: np.ndarray,
                  leaf_value: float) -> np.ndarray:
    """A leaf model's outputs over ``vals`` [rows, k]: the leaf's output
    where a feature of the model (a nonzero coefficient) is NaN."""
    used = coeff != 0
    nan = np.isnan(vals[:, used]).any(axis=1)
    lin = const + np.where(np.isnan(vals), 0.0, vals) @ coeff
    return np.where(nan, leaf_value, lin)


def fit_leaves(tree: Dict, leaf_rows: Sequence[np.ndarray],
               x_cm: np.ndarray, g: np.ndarray, h: np.ndarray, lam: float,
               rate: float, first: bool, gram: str = "float32") -> Dict:
    """The reference's linear model of each leaf, shrunk by ``rate``:
    ``{"const": [leaves], "features": [...], "coeff": [...]}``."""
    n = tree["num_leaves"]
    const = tree["leaf_value"].astype(np.float64).copy()
    features: List[List[int]] = [[] for _ in range(n)]
    coeff: List[np.ndarray] = [np.zeros(0) for _ in range(n)]
    if first or n == 1:
        return {"const": const, "features": features, "coeff": coeff}
    for leaf, feats in enumerate(branch_features(tree)):
        rows = leaf_rows[leaf]
        fit = fit_leaf(x_cm[np.ix_(feats, rows)].T.astype(np.float64),
                       g[rows], h[rows], lam, gram)
        if fit is None:
            continue
        keep = fit[1] != 0
        features[leaf] = [f for f, k in zip(feats, keep) if k]
        coeff[leaf] = fit[1][keep] * rate
        const[leaf] = fit[0] * rate
    return {"const": const, "features": features, "coeff": coeff}


def leaf_outputs(model: Dict, leaf_value: np.ndarray,
                 leaf_rows: Sequence[np.ndarray], x_cm: np.ndarray,
                 n_rows: int) -> np.ndarray:
    """[rows] float64 outputs of a linear model over its leaves' rows."""
    out = np.empty(n_rows, np.float64)
    for leaf, rows in enumerate(leaf_rows):
        feats = model["features"][leaf]
        if not len(feats):
            out[rows] = model["const"][leaf]
            continue
        vals = x_cm[np.ix_(feats, rows)].T.astype(np.float64)
        nan = np.isnan(vals).any(axis=1)
        lin = model["const"][leaf] + np.where(nan[:, None], 0.0, vals) \
            @ model["coeff"][leaf]
        out[rows] = np.where(nan, leaf_value[leaf], lin)
    return out


def text_model(tree: Dict) -> Dict:
    return {"const": tree["leaf_const"], "features": tree["leaf_features"],
            "coeff": tree["leaf_coeff"]}


def replay_scores_f32(model_text: str, trees: int, upper_bounds,
                      bins_cm: np.ndarray, x_cm: np.ndarray, init: float,
                      rate: float) -> np.ndarray:
    """[rows] float32 scores after the first ``trees`` trees of the model
    text as the device adds them: the float32 init score, then a tree's
    float32 unshrunk outputs times the float32 rate, in float32. Tree 0 of
    a linear model is constant (the first-tree rule), so this replays what
    tree 1 was grown from exactly; a tree with linear leaves is replayed
    from its float64 outputs (a model that broke the rule)."""
    score = np.full(bins_cm.shape[1], np.float32(init), np.float32)
    r32 = np.float32(rate)
    for t in range(trees):
        tree = parse_linear(model_text, t)
        _, leaf_rows = ref.replay(tree, ref.threshold_bins(tree, upper_bounds),
                                  bins_cm)
        bias = init if t == 0 else 0.0
        out = leaf_outputs(text_model(tree), tree["leaf_value"], leaf_rows,
                           x_cm, len(score))
        score += ((out - bias) / rate).astype(np.float32) * r32
    return score


def check_splits(tree: Dict, tbins: np.ndarray, node_rows, bins_cm,
                 num_bins: int, group: np.ndarray, addends: np.ndarray,
                 params: Dict, nodes_checked: int = 5) -> List[Dict]:
    """The exact greedy split at the tree's first nodes, over histograms
    made from counts of rows by (bin, group): every row of a group has
    the same addends (``addends`` [groups, 3]: g, h, 1), so a node's
    histogram is one unweighted ``bincount`` a column times them. A
    node passes where its split is the reference's best or within
    ``gbdt_reference.RTOL`` of its gain."""
    l2 = float(params.get("lambda_l2", 0.0))
    n_groups = addends.shape[0]
    out = []
    for i in range(min(nodes_checked, len(tbins))):
        rows = node_rows[i]
        hist = np.empty((bins_cm.shape[0], num_bins, 3))
        for f in range(bins_cm.shape[0]):
            code = bins_cm[f][rows].astype(np.int64) * n_groups + group[rows]
            counts = np.bincount(code, minlength=num_bins * n_groups)
            hist[f] = counts.reshape(num_bins, n_groups) @ addends
        gains = ref.split_gains(hist, l2, int(params["min_data_in_leaf"]),
                                float(params["min_sum_hessian_in_leaf"]))
        rf, rb = np.unravel_index(int(np.argmax(gains)), gains.shape)
        tf, tb = int(tree["split_feature"][i]), int(tbins[i])
        best, own = float(gains[rf, rb]), float(gains[tf, tb])
        out.append({"node": i, "tree": [tf, tb],
                    "reference": [int(rf), int(rb)], "tree_gain": own,
                    "reference_gain": best,
                    "ok": bool((tf, tb) == (rf, rb)
                               or best - own <= ref.RTOL * abs(best))})
    return out


def check_first_linear_tree(model_text: str, upper_bounds, bins_cm,
                            x_cm: np.ndarray, y: np.ndarray, params: Dict,
                            addend_dtype: str = "bfloat16") -> Dict:
    """(b): tree 1 of a binary linear model. The scores tree 0 left are
    replayed from its text: one score a leaf of tree 0, so the rows fall
    in groups (tree 0's leaf, label) of equal gradients. Tree 1's splits
    are held to the exact greedy split at those scores with the addends
    rounded as the configuration states (:func:`check_splits`); its
    leaves are refitted in float64 over the rows its text sends them, and
    the program's per-row outputs from its text are compared with the
    reference's on every row, beside the bfloat16 control's."""
    rate = float(params["learning_rate"])
    lam = float(params.get("linear_lambda", 0.0))
    init = ref.binary_init_score(y)
    score = replay_scores_f32(model_text, 1, upper_bounds, bins_cm, x_cm,
                              init, rate)
    labels = np.unique(y)
    key = score.view(np.int32).astype(np.int64) * len(labels) \
        + np.searchsorted(labels, y)
    _, first, group = np.unique(key, return_index=True, return_inverse=True)
    g, h = ref.binary_gradients(y, score.astype(np.float64))
    if addend_dtype == "bfloat16":
        gq, hq = ref.to_bfloat16(g[first]), ref.to_bfloat16(h[first])
    elif addend_dtype == "float32":
        gq = g[first].astype(np.float32).astype(np.float64)
        hq = h[first].astype(np.float32).astype(np.float64)
    else:
        raise ValueError(f"no rounding rule for addends of {addend_dtype!r}")
    tree = parse_linear(model_text, 1)
    tbins = ref.threshold_bins(tree, upper_bounds)
    node_rows, leaf_rows = ref.replay(tree, tbins, bins_cm)
    splits = check_splits(tree, tbins, node_rows, bins_cm,
                          max(len(u) for u in upper_bounds),
                          group.astype(np.int64),
                          np.stack([gq, hq, np.ones_like(gq)], axis=1),
                          params)
    n = bins_cm.shape[1]
    counts = np.array([len(r) for r in leaf_rows])
    counts_ok = bool(counts.sum() == n
                     and np.array_equal(counts, tree["leaf_count"]))
    # one pass over the leaves: each leaf's values gathered once, the
    # reference's fit and the control's from them, and the three models'
    # outputs on the leaf's rows
    scale = err = ctl = 0.0
    for leaf, feats in enumerate(branch_features(tree)):
        rows = leaf_rows[leaf]
        vals = x_cm[np.ix_(feats, rows)].T.astype(np.float64)
        lv = float(tree["leaf_value"][leaf])
        outs = []
        for gram in ("float32", "bfloat16"):
            fit = fit_leaf(vals, g[rows], h[rows], lam, gram)
            outs.append(np.full(len(rows), lv) if fit is None else
                        linear_output(vals, fit[0] * rate, fit[1] * rate, lv))
        want, control = outs
        pos = {f: i for i, f in enumerate(feats)}
        coeff = np.zeros(len(feats))
        foreign = [f for f in tree["leaf_features"][leaf] if f not in pos]
        for f, c in zip(tree["leaf_features"][leaf],
                        tree["leaf_coeff"][leaf]):
            if f in pos:
                coeff[pos[f]] = c
        got = linear_output(vals, float(tree["leaf_const"][leaf]), coeff, lv)
        if foreign:     # a feature off the branch: never the reference's
            got = np.full(len(rows), np.inf)
        scale = max(scale, float(np.abs(want).max(initial=0.0)))
        err = max(err, float(np.abs(got - want).max(initial=0.0)))
        ctl = max(ctl, float(np.abs(control - want).max(initial=0.0)))
    err, ctl = err / scale, ctl / scale
    linear_leaves = sum(1 for f in tree["leaf_features"] if len(f))
    # the splits and the leaves' rows are held; a leaf's constant output
    # (the fallback of a NaN row) is not: tree 1's addends are the
    # gradients of 255 scores, and one of them on a bfloat16 boundary
    # moves a leaf by a whole step of that rounding
    splits_ok = bool(splits and all(s["ok"] for s in splits))
    report = {
        "tree": 1, "splits_ok": splits_ok, "splits": splits,
        "counts_ok": counts_ok,
        "linear_leaves": linear_leaves, "leaves": tree["num_leaves"],
        "output_scale": scale, "output_error": err,
        "output_limit": OUTPUT_RTOL,
        "output_error_over_limit": err / OUTPUT_RTOL,
        "control_output_error": ctl,
        "control_error_over_limit": ctl / OUTPUT_RTOL,
        "control_ok": bool(ctl <= OUTPUT_RTOL)}
    report["ok"] = bool(splits_ok and counts_ok
                        and linear_leaves > 0 and err <= OUTPUT_RTOL)
    return report


def check_constant_first_tree(model_text: str) -> Dict:
    """(a), the first-tree rule: tree 0 is linear in form, constant in
    fact: no leaf has a feature and ``leaf_const`` is ``leaf_value``."""
    tree = parse_linear(model_text, 0)
    ok = bool(tree["is_linear"]
              and not any(len(f) for f in tree["leaf_features"])
              and np.array_equal(tree["leaf_const"], tree["leaf_value"]))
    return {"is_linear": tree["is_linear"], "ok": ok}


def sample_rows(n_rows: int, seed: int, count: int = SAMPLE_ROWS
                ) -> np.ndarray:
    """A seeded ascending sample of row ids, the last row among them."""
    rng = np.random.default_rng([seed, n_rows])
    pick = rng.choice(n_rows, size=min(count, n_rows) - 1, replace=False)
    return np.unique(np.concatenate([pick, [n_rows - 1]]))


def walk_scores(model_text: str, trees: int, upper_bounds, bins_cm,
                x_cm: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """float64 raw scores of ``rows`` after the first ``trees`` trees of
    the model text (tree 0's values hold the init score): each tree's
    leaves from its thresholds over the binned rows, each leaf's output
    from its linear model over the raw values."""
    b = np.ascontiguousarray(bins_cm[:, rows])
    x = np.ascontiguousarray(x_cm[:, rows])
    out = np.zeros(len(rows), np.float64)
    for t in range(trees):
        tree = parse_linear(model_text, t)
        _, leaf_rows = ref.replay(tree, ref.threshold_bins(tree, upper_bounds),
                                  b)
        out += leaf_outputs(text_model(tree), tree["leaf_value"], leaf_rows,
                            x, len(rows))
    return out


def check_scores(model_text: str, trees: int, upper_bounds, bins_cm,
                 x_cm: np.ndarray, device_scores: np.ndarray,
                 rows: np.ndarray) -> Dict:
    """(c): the device's scores against the walk of the model text."""
    walk = walk_scores(model_text, trees, upper_bounds, bins_cm, x_cm, rows)
    gap = float(np.abs(device_scores[rows] - walk).max()) \
        / (1.0 + float(np.abs(walk).max()))
    limit = SCORE_RTOL_PER_TREE * trees
    return {"trees": trees, "rows": int(len(rows)), "score_error": gap,
            "score_limit": limit, "score_error_over_limit": gap / limit,
            "ok": bool(gap <= limit)}
