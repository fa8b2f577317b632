"""The plain reference of ``gbdt_reference`` for rows that are handed over
sparse and stored as bundles: numpy float64, importing nothing from the
program, working on the **stored values** of a scipy CSR matrix. No dense
``[columns, rows]`` array is made anywhere (at 13,184,290 x 4,228 it would
be 55.7 GB; ``gbdt_reference.check_first_tree`` takes one).

What it reads of the program, as data: the model text, the bin upper
bounds, the bundle plan's three tables (for every feature its stored
column, its offset there and its most frequent bin; a column's members are
written in ascending feature order: ``order``) and the ``[rows, stored
columns]`` matrix the program built.

(a) THE ENCODING. A feature's bin of a stored value is the first bin whose
upper bound is not below it (float64); every row that stores nothing in
the column holds the bin of 0.0. A stored column with one feature at offset
0 holds that feature's bins. Any other column holds 0 where all its
members are at their most frequent bin and ``offset + bin`` of a member
that is not; where two members of a column meet in a row, **the later
member (the higher feature) wins** and the row has lost a value. The
reference encodes the rows itself, member by member, and the program's
matrix has to equal it bit for bit; it counts the rows that lost a value,
and the program's ``efb.conflict_rows`` has to equal that.

(b) TREE 0, as the other cells hold it (``gbdt_reference``): whole-number
counts of rows by (feature, bin, label) at the root and the next four
nodes, taken from the stored values (a feature's zero bin is the node's
rows less its stored rows in the node), the exact greedy best split over
ALL features in feature space within ``RTOL`` = 2^-11 of the gain, leaf
counts equal a replay of the model text on the stored rows, leaf values
within ``RTOL x sum|g| / (H + l2)``. The addends are rounded to bfloat16
as the configuration states; ``gbdt_sharded_reference``'s rounding-boundary
rule holds for tree 0's three addends (within 2^-14 of the midpoint of its
two bfloat16 neighbours a value may have been rounded to either), and its
float8-e4m3 control is evaluated in every run from the same tree and
counts: it has to read not ok.

Rows are walked in blocks; a block yields whole numbers only, so the sums
do not depend on the blocks or on who counts them (threads here, worker
processes in the job).
"""

from __future__ import annotations

import itertools
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Sequence, Tuple

import numpy as np

from reference import gbdt_reference as ref
from reference import gbdt_sharded_reference as sref

RTOL = ref.RTOL
BOUNDARY_MARGIN = sref.BOUNDARY_MARGIN
BLOCK_ROWS = 1 << 18


def _threads() -> int:
    return max(1, min(12, (os.cpu_count() or 2) - 1))


# -- a block's stored values ------------------------------------------------------

def block_csc(indptr, indices, data, lo: int, hi: int, cols: int):
    """Rows ``lo:hi`` of the CSR arrays as scipy CSC."""
    import scipy.sparse as sp
    a, b = int(indptr[lo]), int(indptr[hi])
    block = sp.csr_matrix((data[a:b], indices[a:b], indptr[lo:hi + 1] - a),
                          shape=(hi - lo, cols))
    out = block.tocsc()
    out.sum_duplicates()
    return out


def stored_bins(csc, upper_bounds: Sequence[np.ndarray]):
    """(bin of every stored value, in the CSC's order; [features] bin of
    0.0): the first bin whose upper bound is not below the value."""
    if np.isnan(csc.data).any():
        raise ValueError("the reference has no rule for a stored NaN")
    v = csc.data.astype(np.float64)
    out = np.empty(len(v), np.int64)
    ptr = csc.indptr
    for f in np.flatnonzero(np.diff(ptr)):
        out[ptr[f]:ptr[f + 1]] = np.searchsorted(
            upper_bounds[f], v[ptr[f]:ptr[f + 1]], side="left")
    return out, zero_bins(upper_bounds)


def zero_bins(upper_bounds: Sequence[np.ndarray]) -> np.ndarray:
    return np.array([int(np.searchsorted(u, 0.0, side="left"))
                     for u in upper_bounds], np.int64)


def encode_block(csc, bins: np.ndarray, zero_bin: np.ndarray, plan: Dict,
                 ) -> Tuple[np.ndarray, int]:
    """([rows, stored columns] uint8 of the block under the module's rule
    (a), the rows that lost a value), member by member in ascending
    feature order (``plan["order"]``, where given, is the order the
    members are written in). ``plan``: ``column``, ``offset``,
    ``most_frequent`` [features] and ``columns``."""
    n = csc.shape[0]
    out = np.zeros((n, int(plan["columns"])), np.uint8)
    lost = np.zeros(n, bool)
    ptr, rows = csc.indptr, csc.indices
    for f in plan.get("order", range(len(plan["column"]))):
        c, off = int(plan["column"][f]), int(plan["offset"][f])
        at, b = rows[ptr[f]:ptr[f + 1]], bins[ptr[f]:ptr[f + 1]]
        if off == 0:                       # the column is this feature's bins
            out[:, c] = zero_bin[f]
            out[at, c] = b
            continue
        common = int(plan["most_frequent"][f])
        if zero_bin[f] != common:          # implied zeros are written too
            full = np.full(n, zero_bin[f], np.int64)
            full[at] = b
            at, b = np.arange(n), full
        write = b != common
        at, b = at[write], b[write]
        lost[at[out[at, c] != 0]] = True   # an earlier member was there
        out[at, c] = off + b
    return out, int(lost.sum())


# -- whole numbers a block yields -------------------------------------------------

def _bin_starts(upper_bounds) -> np.ndarray:
    return np.concatenate([[0], np.cumsum([len(u) for u in upper_bounds])])


def replay_block(tree: Dict, tbins: np.ndarray, csc, bins: np.ndarray,
                 zero_bin: np.ndarray):
    """(rows of internal nodes, rows of leaves) of the block: ascending
    row-index arrays. A row goes left iff its bin in the node's feature (a
    stored value's, else that of 0.0) is at most the node's."""
    n = csc.shape[0]
    ptr = csc.indptr
    column: Dict[int, np.ndarray] = {}

    def col(f):
        if f not in column:
            c = np.full(n, zero_bin[f], np.int64)
            c[csc.indices[ptr[f]:ptr[f + 1]]] = bins[ptr[f]:ptr[f + 1]]
            column[f] = c
        return column[f]
    n_int = len(tbins)
    node_rows: List = [None] * n_int
    leaf_rows: List = [None] * tree["num_leaves"]
    node_rows[0] = np.arange(n)
    for i in range(n_int):         # a child's index is above its parent's
        at = node_rows[i]
        if at is None:
            raise ValueError(f"node {i} is not reachable from the root")
        left = col(int(tree["split_feature"][i]))[at] <= tbins[i]
        for child, part in ((tree["left_child"][i], at[left]),
                            (tree["right_child"][i], at[~left])):
            if child >= 0:
                node_rows[child] = part
            else:
                leaf_rows[~child] = part
    return node_rows, leaf_rows


def block_counts(tree: Dict, tbins: np.ndarray, csc, bins: np.ndarray,
                 zero_bin: np.ndarray, positive: np.ndarray,
                 starts: np.ndarray, nodes: int):
    """Whole numbers of one block, int64 rows by label 0 / 1:
    [nodes, all features' bins, 2] STORED values by (feature, bin) at
    internal nodes 0 .. ``nodes`` - 1 (bins laid end to end, feature ``f``
    from ``starts[f]``); [nodes, 2] the nodes' rows; [leaves, 2] the
    leaves' rows."""
    node_rows, leaf_rows = replay_block(tree, tbins, csc, bins, zero_bin)
    feat = np.repeat(np.arange(csc.shape[1]), np.diff(csc.indptr))
    key = (starts[feat] + bins) * 2 + positive[csc.indices]
    total = int(starts[-1]) * 2
    stored = np.empty((nodes, total // 2, 2), np.int64)
    rows_of = np.empty((nodes, 2), np.int64)
    for i in range(nodes):
        if i == 0:
            k = key
        else:
            inside = np.zeros(csc.shape[0], bool)
            inside[node_rows[i]] = True
            k = key[inside[csc.indices]]
        stored[i] = np.bincount(k, minlength=total).reshape(-1, 2)
        ones = int(positive[node_rows[i]].sum())
        rows_of[i] = (len(node_rows[i]) - ones, ones)
    ones = np.array([int(positive[r].sum()) for r in leaf_rows], np.int64)
    size = np.array([len(r) for r in leaf_rows], np.int64)
    return stored, rows_of, np.stack([size - ones, ones], axis=1)


def spans_report(indptr, indices, data, positive, program_bins, tree, tbins,
                 upper_bounds, plan, spans, nodes) -> Dict:
    """What one thread or worker process does: over the row blocks
    ``spans`` = [(lo, hi)], the sums of :func:`block_counts`, the blocks
    whose encoding differs from the program's, and the rows that lost a
    value. A module-level function of plain arguments, so that a worker
    can be handed it (the five arrays come first)."""
    starts = _bin_starts(upper_bounds)
    cols = len(upper_bounds)
    sums, unequal, lost = None, [], 0
    for lo, hi in spans:
        csc = block_csc(indptr, indices, data, lo, hi, cols)
        bins, zero_bin = stored_bins(csc, upper_bounds)
        if plan is not None:
            mine, n_lost = encode_block(csc, bins, zero_bin, plan)
            lost += n_lost
            if not np.array_equal(mine, program_bins[lo:hi]):
                unequal.append([int(lo), int(hi), int(
                    (mine != program_bins[lo:hi]).sum())])
        got = block_counts(tree, tbins, csc, bins, zero_bin,
                           positive[lo:hi], starts, nodes)
        sums = got if sums is None else tuple(a + b
                                              for a, b in zip(sums, got))
    return {"sums": sums, "unequal_blocks": unequal, "rows_lost": lost}


# -- one combination of addends against the tree --------------------------------

def _histograms(stored, rows_of, starts, zero_bin, num_bins: int):
    """[nodes, features, num_bins, 2] rows by (feature, bin, label): the
    stored values' counts laid out a feature a row, and every feature's
    zero bin raised by the node's rows that store nothing there."""
    nodes, feats = stored.shape[0], len(zero_bin)
    out = np.zeros((nodes, feats, num_bins, 2), np.int64)
    width = np.diff(starts)
    f = np.repeat(np.arange(feats), width)
    b = np.arange(int(starts[-1])) - starts[f]
    out[:, f, b] = stored
    implied = rows_of[:, None, :] - out.sum(axis=2)
    if (implied < 0).any():
        raise ValueError("a node stores more values in a column than it "
                         "has rows")
    out[:, np.arange(feats), zero_bin] += implied
    return out


def _compare(tree, tbins, hist_counts, leaf_counts, g, h, init, lr, l2,
             min_data, min_hess) -> Dict:
    """The tree against the reference at addends ``g`` [2] (label 0, 1)
    and ``h`` [2]; every compared number beside its limit."""
    per_label = np.stack([g, h, np.ones(2)], axis=1)            # [2, 3]
    splits = []
    for i in range(hist_counts.shape[0]):
        gains = ref.split_gains(hist_counts[i] @ per_label, l2, min_data,
                                min_hess)
        rf, rb = np.unravel_index(int(np.argmax(gains)), gains.shape)
        tf, tb = int(tree["split_feature"][i]), int(tbins[i])
        best, own = float(gains[rf, rb]), float(gains[tf, tb])
        short = best - own
        splits.append({
            "node": i, "tree": [tf, tb], "reference": [int(rf), int(rb)],
            "tree_gain": own, "reference_gain": best,
            "recorded_gain": float(tree["split_gain"][i]),
            "gain_short_by": short, "limit": RTOL * abs(best),
            "ok": bool((tf, tb) == (rf, rb) or short <= RTOL * abs(best))})
    n = leaf_counts
    counts_ok = bool(np.array_equal(n.sum(axis=1), tree["leaf_count"]))
    G, H, A = n @ g, n @ h, n @ np.abs(g)
    got = (tree["leaf_value"] - init) / lr
    with np.errstate(invalid="ignore", divide="ignore"):
        err = np.abs(got * (H + l2) + G) / A
    err = np.where(n.sum(axis=1) == 0, np.inf, err)
    worst = int(np.argmax(err))
    leaves = {"n": int(tree["num_leaves"]), "counts_ok": counts_ok,
              "worst_error_over_scale": float(err[worst]), "limit": RTOL,
              "worst_error_over_limit": float(err[worst] / RTOL),
              "worst_leaf": {"leaf": worst, "rows": int(n[worst].sum()),
                             "sum_abs_g": float(A[worst]),
                             "value": float(tree["leaf_value"][worst])},
              "values_ok": bool(err[worst] <= RTOL)}
    return {"splits": splits, "leaves": leaves,
            "ok": bool(all(s["ok"] for s in splits) and counts_ok
                       and leaves["values_ok"])}


def _badness(cmp: Dict) -> Tuple:
    return (sum(not s["ok"] for s in cmp["splits"]),
            cmp["leaves"]["worst_error_over_limit"])


# -- the check -------------------------------------------------------------------

def check(model_text: str, upper_bounds: Sequence[np.ndarray], x_csr,
          y: np.ndarray, params: Dict, plan: Dict,
          program_bins: np.ndarray, program_rows_lost: int,
          addend_dtype: str = "bfloat16", nodes_checked: int = 5,
          margin: float = BOUNDARY_MARGIN, run=None, parts: int = 0,
          control_dtype: str = "float8_e4m3") -> Dict:
    """Hold the program's stored matrix to the module's encoding (a) and
    tree 0 of a binary-objective model to its rule (b), on the rows of the
    scipy CSR matrix ``x_csr``. ``plan``: the bundle plan's tables as data
    (:func:`encode_block`), or None for a matrix of one column a feature
    that is not checked here. ``run(groups)`` gives :func:`spans_report`
    of each group ``(tree, tbins, upper_bounds, plan, spans, nodes)`` on
    whatever it likes (the job: worker processes that hold the arrays in
    shared memory); by default on a few threads here. Returns a report
    whose ``ok`` is the verdict."""
    if addend_dtype != "float32" \
            and addend_dtype not in sref.SIGNIFICAND_BITS:
        raise ValueError(f"no rounding rule for addends of {addend_dtype!r}")
    y = np.asarray(y)
    positive = y > 0
    if not np.array_equal(y, positive.astype(y.dtype)):
        raise ValueError("binary labels are 0 and 1")
    lr = float(params["learning_rate"])
    l2 = float(params.get("lambda_l2", 0.0))
    min_data = int(params["min_data_in_leaf"])
    min_hess = float(params["min_sum_hessian_in_leaf"])
    tree = ref.parse_tree(model_text, 0)
    tbins = ref.threshold_bins(tree, upper_bounds)
    rows = x_csr.shape[0]
    nodes = min(nodes_checked, len(tbins))
    spans = [(lo, min(lo + BLOCK_ROWS, rows))
             for lo in range(0, rows, BLOCK_ROWS)]
    parts = max(1, min(parts or _threads(), len(spans)))
    groups = [(tree, tbins, list(upper_bounds), plan, spans[i::parts], nodes)
              for i in range(parts)]
    if run is None:
        with ThreadPoolExecutor(parts) as ex:
            got = list(ex.map(lambda grp: spans_report(
                x_csr.indptr, x_csr.indices, x_csr.data, positive,
                program_bins, *grp), groups))
    else:
        got = run(groups)
    sums = None
    for part in got:
        sums = part["sums"] if sums is None else tuple(
            a + b for a, b in zip(sums, part["sums"]))
    stored, rows_of, leaf_counts = sums
    unequal = sorted(b for part in got for b in part["unequal_blocks"])
    rows_lost = sum(part["rows_lost"] for part in got)
    starts, zero_bin = _bin_starts(upper_bounds), zero_bins(upper_bounds)
    num_bins = max(len(u) for u in upper_bounds)
    hist_counts = _histograms(stored, rows_of, starts, zero_bin, num_bins)

    init = ref.binary_init_score(y)
    g, h = ref.binary_gradients(np.array([0.0, 1.0]), np.full(2, init))
    addends = {"g_negative": sref.admissible(g[0], addend_dtype, margin),
               "g_positive": sref.admissible(g[1], addend_dtype, margin),
               "h": sref.admissible(h[0], addend_dtype, margin)}
    tried, chosen = 0, None
    for g0, g1, hh in itertools.product(
            *(addends[k]["admissible"] for k in addends)):
        cmp = _compare(tree, tbins, hist_counts, leaf_counts,
                       np.array([g0, g1]), np.array([hh, hh]), init, lr, l2,
                       min_data, min_hess)
        cmp["addends_used"] = {"g_negative": g0, "g_positive": g1, "h": hh}
        tried += 1          # the nearest rounding of all three comes first
        if chosen is None or _badness(cmp) < _badness(chosen):
            chosen = cmp
        if cmp["ok"]:
            break
    total = int(leaf_counts.sum())
    encoding = {"checked": plan is not None, "unequal_blocks": unequal[:8],
                "n_unequal_blocks": len(unequal),
                "rows_lost": rows_lost,
                "program_rows_lost": program_rows_lost,
                "ok": bool(not unequal and (
                    plan is None or rows_lost == program_rows_lost))}
    report = {"tree": 0, "rows": rows, "blocks": len(spans),
              "addend_dtype": addend_dtype, "rtol": RTOL, "init_score": init,
              "addends": addends, "roundings_tried": tried,
              "all_rows_reach_a_leaf": total == rows,
              "encoding": encoding,
              "features_searched": int(hist_counts.shape[1]),
              "valid_bins_searched": int(starts[-1])}
    report.update(chosen)
    report["tree_ok"] = bool(chosen["ok"] and total == rows)
    report["ok"] = bool(report["tree_ok"] and encoding["ok"])
    if control_dtype and control_dtype != addend_dtype:
        bits = sref.SIGNIFICAND_BITS[control_dtype]
        cg = np.array([sref.neighbours(v, bits)[2] for v in g])
        ch = np.full(2, sref.neighbours(h[0], bits)[2])
        cmp = _compare(tree, tbins, hist_counts, leaf_counts, cg, ch, init,
                       lr, l2, min_data, min_hess)
        report["control"] = {
            "addend_dtype": control_dtype, "ok": cmp["ok"],
            "splits_ok": sum(s["ok"] for s in cmp["splits"]),
            "worst_error_over_limit":
                cmp["leaves"]["worst_error_over_limit"]}
    return report
