"""A plain reference for one boosted tree: exact greedy split finding over
histograms, and leaf values, in numpy float64, written from the reference
implementation's definition and importing nothing from the program.

    gain(split) = G_L^2/(H_L + l2) + G_R^2/(H_R + l2) - G_P^2/(H_P + l2)
    leaf value  = -G/(H + l2) * learning_rate   (+ the init score in tree 0)

over the rows that reach a node, where a split (feature, bin) sends a row
left iff its bin <= that bin, and both children must hold at least
``min_data_in_leaf`` rows and ``min_sum_hessian_in_leaf`` hessian.

What it reads of the program is the trained model as LightGBM-format text
and the bin upper bounds (a tree's thresholds are bin upper bounds; the
data here is binned).

PRECISION. The program states its histogram addends as bfloat16,
accumulated in float32, so the reference rounds each row's gradient and
hessian to bfloat16 (round to nearest even) and sums exactly: it computes
what the configuration states, and not something finer. What is left
between the two is float32 accumulation and the program's histogram
subtraction (a child's sums are its parent's less its sibling's), both
far below one bfloat16 rounding (2^-9 of an addend). ``RTOL = 2^-11`` is a
quarter of one such rounding: a program whose addends are coarser than it
states, or not rounded at all, is off by about 2^-9 and fails; the errors
seen on the chip are in PERF.md. The tree's split at a node passes when it
is the reference's best split, or when its gain, computed by the
reference, is within RTOL of the reference's best (a tie that float32 may
break either way). A leaf's output passes when it is within
``RTOL * sum|g| / (H + l2)`` of the reference's, the scale on which an
error in an addend shows.

TREE 0. Its gradients come from the init score, which the reference
recomputes from the labels, so every row of one label value has the same
gradient and hessian. A node's histogram is therefore a count of rows by
(bin, label value), times that value's gradient and hessian: one
unweighted ``bincount`` per column.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

RTOL = 2.0 ** -11


def to_bfloat16(x) -> np.ndarray:
    """float64 values of ``x`` rounded to bfloat16, to nearest even."""
    b = np.atleast_1d(np.asarray(x, np.float32)).view(np.uint32)
    b = (b + np.uint32(0x7FFF) + ((b >> np.uint32(16)) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return b.view(np.float32).astype(np.float64)


# -- the model text ---------------------------------------------------------

def parse_tree(model_text: str, index: int) -> Dict[str, np.ndarray]:
    """Arrays of tree ``index`` from LightGBM's text format."""
    start = model_text.index(f"Tree={index}\n")
    end = model_text.find("\nTree=", start + 1)
    block = model_text[start:end if end > 0 else None]
    fields = dict(line.split("=", 1) for line in block.splitlines()
                  if "=" in line)
    ints = ("split_feature", "left_child", "right_child", "leaf_count",
            "decision_type")
    floats = ("threshold", "leaf_value", "split_gain")
    tree = {k: np.array(fields[k].split(), np.int64) for k in ints}
    tree.update({k: np.array(fields[k].split(), np.float64) for k in floats})
    tree["num_leaves"] = int(fields["num_leaves"])
    if int(fields.get("num_cat", 0)) or tree["decision_type"].any():
        raise ValueError("the reference handles plain numerical splits only")
    return tree


def threshold_bins(tree: Dict, upper_bounds: Sequence[np.ndarray]) -> np.ndarray:
    """Each internal node's threshold as a bin index; a threshold that is
    no bin upper bound of its feature is an error."""
    out = np.empty(len(tree["threshold"]), np.int64)
    for i, (f, t) in enumerate(zip(tree["split_feature"], tree["threshold"])):
        ub = np.asarray(upper_bounds[f], np.float64)
        k = int(np.searchsorted(ub, t, side="left"))
        near = [j for j in (k - 1, k) if 0 <= j < len(ub)]
        k = min(near, key=lambda j: abs(ub[j] - t))
        if abs(ub[k] - t) > 1e-9 * max(1.0, abs(t)):
            raise ValueError(f"node {i}: threshold {t!r} is not an upper "
                             f"bound of feature {f}")
        out[i] = k
    return out


# -- the objective ------------------------------------------------------------

def binary_init_score(y: np.ndarray) -> float:
    p = float(np.mean(y, dtype=np.float64))
    return float(np.log(p / (1.0 - p)))


def binary_gradients(y: np.ndarray, score: np.ndarray):
    p = 1.0 / (1.0 + np.exp(-np.asarray(score, np.float64)))
    return p - y, p * (1.0 - p)


def binary_logloss(y: np.ndarray, score: np.ndarray) -> float:
    s = np.asarray(score, np.float64)
    return float(np.mean(np.logaddexp(0.0, s) - y * s))


# -- histograms and the exact greedy split -------------------------------------

def class_counts(bins_cm: np.ndarray, num_bins: int, cls: np.ndarray,
                 n_cls: int, rows=None) -> np.ndarray:
    """[features, num_bins, n_cls] rows of the node by (bin, class of
    row); all rows when ``rows`` is None. ``bins_cm`` is [features, rows]
    uint8 and ``cls`` [rows] uint8."""
    if num_bins * n_cls > 256:
        raise ValueError("bins x classes must fit uint8")
    off = cls * np.uint8(num_bins)
    if rows is not None:
        off = off[rows]
    out = np.empty((bins_cm.shape[0], n_cls * num_bins), np.int64)
    for f in range(bins_cm.shape[0]):
        b = bins_cm[f] if rows is None else bins_cm[f][rows]
        out[f] = np.bincount(b + off, minlength=n_cls * num_bins)
    return out.reshape(-1, n_cls, num_bins).transpose(0, 2, 1)


def split_gains(hist: np.ndarray, l2: float, min_data: int,
                min_hess: float) -> np.ndarray:
    """[features, num_bins - 1] gain of every split (feature, bin), -inf
    where a child would be too small."""
    left = np.cumsum(hist, axis=1)[:, :-1, :]
    total = hist.sum(axis=1, keepdims=True)
    right = total - left

    def score(s):
        return s[..., 0] ** 2 / (s[..., 1] + l2)
    with np.errstate(invalid="ignore", divide="ignore"):   # empty children
        gain = score(left) + score(right) - score(total)
    ok = ((left[..., 2] >= min_data) & (right[..., 2] >= min_data)
          & (left[..., 1] >= min_hess) & (right[..., 1] >= min_hess))
    return np.where(ok, gain, -np.inf)


# -- replaying a tree over the data --------------------------------------------

def replay(tree: Dict, tbins: np.ndarray, bins_cm: np.ndarray) -> List:
    """The rows that reach every node: (rows of internal nodes, rows of
    leaves), each a list of ascending row-index arrays."""
    n_int = len(tbins)
    node_rows: List = [None] * n_int
    leaf_rows: List = [None] * tree["num_leaves"]
    node_rows[0] = np.arange(bins_cm.shape[1], dtype=np.int64)
    for i in range(n_int):     # a child's index is above its parent's
        rows = node_rows[i]
        if rows is None:
            raise ValueError(f"node {i} is not reachable from the root")
        go_left = bins_cm[tree["split_feature"][i]][rows] <= tbins[i]
        for child, part in ((tree["left_child"][i], rows[go_left]),
                            (tree["right_child"][i], rows[~go_left])):
            if child >= 0:
                node_rows[child] = part
            else:
                leaf_rows[~child] = part
    return node_rows, leaf_rows


# -- the check -----------------------------------------------------------------

def check_first_tree(model_text: str, upper_bounds: Sequence[np.ndarray],
                     bins_cm: np.ndarray, y: np.ndarray, params: Dict,
                     addend_dtype: str = "bfloat16",
                     nodes_checked: int = 5) -> Dict:
    """Hold tree 0 of a binary-objective model to the reference on the
    binned training data. Returns a report whose ``ok`` is the verdict."""
    lr = float(params["learning_rate"])
    l2 = float(params.get("lambda_l2", 0.0))
    min_data = int(params["min_data_in_leaf"])
    min_hess = float(params["min_sum_hessian_in_leaf"])
    num_bins = max(len(u) for u in upper_bounds)
    tree = parse_tree(model_text, 0)
    tbins = threshold_bins(tree, upper_bounds)
    init = binary_init_score(y)
    labels = np.unique(y)
    cls = np.searchsorted(labels, y).astype(np.uint8)
    g, h = binary_gradients(labels, np.full(len(labels), init))
    if addend_dtype == "bfloat16":
        g, h = to_bfloat16(g), to_bfloat16(h)
    elif addend_dtype != "float32":
        raise ValueError(f"no rounding rule for addends of {addend_dtype!r}")
    per_class = np.stack([g, h, np.ones_like(g)], axis=1)   # [classes, 3]
    node_rows, leaf_rows = replay(tree, tbins, bins_cm)

    splits = []
    for i in range(min(nodes_checked, len(tbins))):
        rows = None if i == 0 else node_rows[i]
        counts = class_counts(bins_cm, num_bins, cls, len(labels), rows)
        gains = split_gains(counts @ per_class, l2, min_data, min_hess)
        rf, rb = np.unravel_index(int(np.argmax(gains)), gains.shape)
        tf, tb = int(tree["split_feature"][i]), int(tbins[i])
        best, own = float(gains[rf, rb]), float(gains[tf, tb])
        splits.append({
            "node": i, "tree": [tf, tb], "reference": [int(rf), int(rb)],
            "tree_gain": own, "reference_gain": best,
            "recorded_gain": float(tree["split_gain"][i]),
            "ok": bool((tf, tb) == (rf, rb) or best - own <= RTOL * abs(best))})

    n = np.array([np.bincount(cls[r], minlength=len(labels))
                  for r in leaf_rows], np.float64)          # [leaves, classes]
    counts_ok = bool(n.sum() == len(y)
                     and np.array_equal(n.sum(axis=1), tree["leaf_count"]))
    G, H, A = n @ g, n @ h, n @ np.abs(g)
    got = (tree["leaf_value"] - init) / lr
    worst = float(np.max(np.abs(got + G / (H + l2)) / (A / (H + l2))))
    gain_err = max(abs(s["recorded_gain"] - s["tree_gain"]) / s["tree_gain"]
                   for s in splits)
    report = {"addend_dtype": addend_dtype, "rtol": RTOL, "init_score": init,
              "splits": splits, "worst_recorded_gain_rel_error": gain_err,
              "leaves": {"n": int(tree["num_leaves"]), "counts_ok": counts_ok,
                         "values_ok": bool(worst <= RTOL),
                         "worst_error_over_scale": worst}}
    report["ok"] = bool(all(s["ok"] for s in splits) and counts_ok
                        and report["leaves"]["values_ok"])
    return report
