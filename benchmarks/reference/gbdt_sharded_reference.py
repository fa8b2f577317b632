"""The plain reference of ``gbdt_reference`` for a tree whose rows lie on
shards (``tree_learner=data``): the same exact greedy split and leaf
values in numpy float64, importing nothing from the program, with the sums
taken **shard by shard and then merged**, as the deployment takes them.

A shard is a run of consecutive rows (``shard_rows`` each, the last one
short: the program pads it). Within a shard the rows are walked in blocks
on a few threads. What a block yields is whole numbers only, rows by
(column, bin, label) at the checked nodes and rows by (leaf, label), so a
shard's sums do not depend on the blocks or the threads; a node's rows by
bin are its parent's less its sibling's where that is the shorter count,
which is exact for whole numbers. A shard's histogram is its counts times
the three addends in float64, and the node's is the sum over shards.

LEAVES OF A FEW ROWS. The program keeps its sums in float32, and a leaf's
sums are not sums over its own rows: they are the bins of its parent's
histogram on its side of the cut (a right side: the parent's total less
the left prefix), and a node that is not the smaller child of its parent
has no histogram of its own rows either: it is its parent's less its
sibling's, bin by bin, down the **chain** from the nearest ancestor that
was built from rows (the root, or a smaller child; the smaller child is
the left one where the counts tie). Every number on that road is rounded
at its own size, so a leaf of a few rows cut off a large node inherits
roundings of sums far larger than its own. At 53M rows a leaf of 31 rows
(sum|g| 2.0) read 2^-10 off in its sum of gradients, one float32 step of a
number between 8,192 and 16,384, and so 4.889e-4 of its own scale where
``gbdt_reference``'s limit is 4.883e-4 (my chip runs, PR 35, seed
2147566002; the next worst leaf of eleven runs read 1.4e-5: the addends
are multiples of 2^-12, so float32 sums under 4,096 are exact).
``gbdt_reference`` holds its limit to be about addends ("float32
accumulation and the program's histogram subtraction, both far below one
bfloat16 rounding"), which ``min_data_in_leaf`` 20 under 53M rows does not
bear out. So a leaf's limit here is ``RTOL x sum|g|`` of its own rows
**plus** ``ACCUMULATION`` of the float32 numbers its two sums are made of
(:func:`_carried`), counted from whole-number replays:

- for each bin on its side of the cut, in the parent's split column, the
  sum|g| (an upper bound of the bin's |sum g|) and the sum h of that bin at
  every node of the parent's chain: each level's subtraction is rounded at
  that level's size, the top's build at the top's;
- for a right side, twice the parent's own bins' |sum g| and sum h in
  that column: the total and the left prefix it is taken from.

The hessian's part counts ``|value|`` times (the value is -G / H, so a
rounding of H moves it by that much in units of sum g). ``ACCUMULATION`` is
2^-19, 32 float32 roundings (2^-24 of the number each): the root's bin is
the sum of 32 chunk sums a shard (13,281,280 rows in chunks of 415,744),
the worst case of every number counted. For a leaf whose parent was built from
rows and is not far larger than it the second term is a few 2^-19 of the
first and the check is ``gbdt_reference``'s; where a leaf of a few rows
hangs off a long chain the term is what float32 arithmetic can lose
there, and the report says how many leaves are held within twice ``RTOL``
(``held_within_twice_rtol``). An addend in fewer bits than stated moves
every leaf by whole steps of its own scale and fails on the large leaves
as before: :func:`check_first_tree` evaluates that control (float8-e4m3
addends against the same tree and the same counts) in every run and
reports its worst reading beside the run's own (``control``).

ROUNDING BOUNDARIES. Tree 0 of a binary job has three addends for all
rows: ``p`` and ``p - 1`` (the gradient of a row without and with a click)
and ``p (1 - p)`` (the hessian) at the initial score, each rounded to
bfloat16 before it is summed. The reference computes them in float64 from
the labels, the program in float32 on the chip from its float32 score, and
the two differ by about 1e-6 of the value: on the chip the hessian of 53M
rows was read rounding **up** where float64 rounds **down**, 9.4e-7 of the
value under a boundary (PR 34's chip runs, seed 2147489008), and then every
sum of the tree is off by one bfloat16 step, 0.4%. So where a value lies
within ``BOUNDARY_MARGIN`` (relative) of the midpoint of its two bfloat16
neighbours, **both neighbours are admissible**, and the tree has to agree
with one combination of admissible addends, whole, within the ordinary
limits (``RTOL`` of ``gbdt_reference``, nothing looser). The margin is
2^-14: 65 times the flip that was read, so a float32 logistic of any
chip's making is covered, and a sixteenth of the least bfloat16 step
(2^-8 of the value .. 2^-7), so an addend kept in fewer bits than stated
is still off by whole steps on the other two values and fails: the
control is this reference fed float8-e4m3 addends (``addend_dtype``),
which the job's tests hold to ``ok: false``.
"""

from __future__ import annotations

import itertools
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Sequence, Tuple

import numpy as np

from reference import gbdt_reference as ref

RTOL = ref.RTOL
BOUNDARY_MARGIN = 2.0 ** -14
ACCUMULATION = 2.0 ** -19     # 32 float32 roundings (2^-24) of a carried sum
BLOCK_ROWS = 1 << 19
COUNT_CHUNK = 1 << 16     # bincount's int64 copy of a chunk stays in cache

# significand bits (the implicit one counted) by addend type
SIGNIFICAND_BITS = {"bfloat16": 8, "float8_e4m3": 4}


def _threads() -> int:
    return max(1, min(24, (os.cpu_count() or 2) - 2))


def neighbours(value: float, bits: int) -> Tuple[float, float, float]:
    """(below, above, nearest) of ``value`` on the grid of floats with
    ``bits`` significand bits (ties to even), in float64. Exponent range
    is not modelled: the addends here are far from any type's limits."""
    m, e = np.frexp(abs(float(value)))          # m in [0.5, 1)
    scaled = m * 2.0 ** bits
    lo, near = np.floor(scaled), np.round(scaled)   # np.round: half to even
    hi = lo if lo == scaled else lo + 1.0
    sign = -1.0 if value < 0 else 1.0
    back = [sign * float(np.ldexp(k / 2.0 ** bits, e)) for k in (lo, hi, near)]
    return (back[1], back[0], back[2]) if value < 0 else tuple(back)


def admissible(value: float, addend_dtype: str,
               margin: float = BOUNDARY_MARGIN) -> Dict:
    """The values the addend may have been rounded to: the nearest, and
    the other neighbour too where ``value`` is within ``margin``
    (relative) of the midpoint between the two."""
    if addend_dtype == "float32":
        return {"value": value, "admissible": [float(value)],
                "boundary_distance": None}
    lo, hi, near = neighbours(value, SIGNIFICAND_BITS[addend_dtype])
    mid = 0.5 * (lo + hi)
    dist = abs(value - mid) / abs(mid) if lo != hi else None
    out = [near]
    if dist is not None and dist <= margin:
        out.append(hi if near == lo else lo)
    return {"value": float(value), "admissible": out,
            "boundary_distance": dist, "margin": margin}


# -- whole numbers a block of rows yields ---------------------------------------

def _bincount(a: np.ndarray, num_bins: int) -> np.ndarray:
    out = np.zeros(num_bins, np.int64)
    for lo in range(0, len(a), COUNT_CHUNK):
        out += np.bincount(a[lo:lo + COUNT_CHUNK], minlength=num_bins)
    return out


def _node_counts(tile: np.ndarray, clicked: np.ndarray, rows,
                 num_bins: int) -> np.ndarray:
    """[columns, bins, 2] rows of a node by (column, bin, label 0 / 1);
    ``rows`` ascending indices into the tile, or None for all of it."""
    out = np.empty((tile.shape[0], num_bins, 2), np.int64)
    hit = clicked if rows is None else rows[clicked[rows]]
    for f in range(tile.shape[0]):
        col = tile[f]
        ones = np.bincount(col[hit], minlength=num_bins)
        out[f, :, 1] = ones
        out[f, :, 0] = _bincount(col if rows is None else col[rows],
                                 num_bins) - ones
    return out


def subtraction_chains(tree: Dict) -> Tuple[np.ndarray, List[List[int]]]:
    """(which child of every internal node the program builds from rows
    [n] bool: True the left one; for every internal node its chain: itself
    and its ancestors up to the nearest that was built from rows). The
    program streams the child with fewer rows, the left one where they
    tie, and takes the other as the parent's less that one."""
    left, right = tree["left_child"], tree["right_child"]
    n_int = len(left)
    rows = np.zeros(n_int, np.int64)
    for i in range(n_int - 1, -1, -1):     # a child's index is above its parent's
        for c in (int(left[i]), int(right[i])):
            rows[i] += tree["leaf_count"][~c] if c < 0 else rows[c]

    def count(c):
        return int(tree["leaf_count"][~c]) if c < 0 else int(rows[c])
    left_built = np.array([count(int(l)) <= count(int(r))
                           for l, r in zip(left, right)], bool)
    chains: List[List[int]] = [[0]] + [None] * (n_int - 1)
    for p in range(n_int):
        for c, built in ((int(left[p]), left_built[p]),
                         (int(right[p]), not left_built[p])):
            if c >= 0:
                chains[c] = [c] if built else [c] + chains[p]
    return left_built, chains


def block_counts(tree: Dict, tbins: np.ndarray, tile: np.ndarray,
                 clicked: np.ndarray, num_bins: int, nodes: int):
    """Whole numbers of one block (``tile`` [columns, n] uint8,
    ``clicked`` [n] bool), all int64 rows by label 0 / 1:

    - [nodes, columns, bins, 2] at internal nodes 0 .. ``nodes`` - 1;
    - [leaves, 2] at the leaves;
    - [internal nodes, bins, 2] in every internal node's own split column;
    - the same summed over the node's chain (:func:`subtraction_chains`).

    Every node's table of all columns is taken as the program takes its
    histogram: the smaller child counted from its rows, the other the
    parent's less that one (exact for whole numbers), so the rows counted
    are the root's and the smaller children's."""
    node_rows, leaf_rows = ref.replay(tree, tbins, tile)
    left_built, _ = subtraction_chains(tree)
    n_int = len(tbins)
    feat = tree["split_feature"]
    counts = np.empty((nodes,) + (tile.shape[0], num_bins, 2), np.int64)
    own = np.empty((n_int, num_bins, 2), np.int64)
    chain = np.empty((n_int, num_bins, 2), np.int64)
    # a node's table, and the sum of the tables down its chain
    table = {0: _node_counts(tile, clicked, None, num_bins)}
    summed = {0: table[0]}
    for p in range(n_int):     # a child's index is above its parent's
        t_p, s_p = table.pop(p), summed.pop(p)
        if p < nodes:
            counts[p] = t_p
        own[p], chain[p] = t_p[feat[p]], s_p[feat[p]]
        kids = (int(tree["left_child"][p]), int(tree["right_child"][p]))
        small, big = kids if left_built[p] else kids[::-1]
        if small < 0 and big < 0:
            continue
        t_small = _node_counts(
            tile, clicked, node_rows[small] if small >= 0 else leaf_rows[~small],
            num_bins)
        if small >= 0:
            table[small] = summed[small] = t_small
        if big >= 0:
            table[big] = t_p - t_small
            summed[big] = s_p + table[big]
    ones = np.array([int(clicked[r].sum()) for r in leaf_rows], np.int64)
    size = np.array([len(r) for r in leaf_rows], np.int64)
    return counts, np.stack([size - ones, ones], axis=1), own, chain


def _add(a: Tuple, b: Tuple) -> Tuple:
    return tuple(x + y for x, y in zip(a, b))


def spans_counts(bins_cm: np.ndarray, clicked: np.ndarray, tree: Dict,
                 tbins: np.ndarray, spans: Sequence[Tuple[int, int, int]],
                 num_bins: int, nodes: int) -> Dict:
    """{shard: :func:`block_counts`} summed over the blocks ``spans`` =
    [(shard, lo, hi)]: what one thread or one worker process does (a
    module-level function of plain arguments, so that a worker can be
    handed it)."""
    sums: Dict = {}
    for s, lo, hi in spans:
        got = block_counts(tree, tbins, bins_cm[:, lo:hi], clicked[lo:hi],
                           num_bins, nodes)
        sums[s] = got if s not in sums else _add(sums[s], got)
    return sums


def shard_counts(tree: Dict, tbins: np.ndarray, bins_cm: np.ndarray,
                 clicked: np.ndarray, bounds: Sequence[Tuple[int, int]],
                 num_bins: int, nodes: int, run=None, parts: int = 0):
    """Per shard: :func:`block_counts` summed over the shard's blocks. The
    blocks of all shards are cut into ``parts`` runs; ``run(groups)``
    gives :func:`spans_counts` of each (its arguments after the two
    arrays: ``(tree, tbins, spans, num_bins, nodes)``), on whatever it
    likes: by default on a few threads here, which the GIL mostly
    serialises (numpy's ``bincount`` and fancy indexing hold it); the job
    hands in worker processes."""
    spans = [(s, lo, min(lo + BLOCK_ROWS, hi))
             for s, (s_lo, hi) in enumerate(bounds)
             for lo in range(s_lo, hi, BLOCK_ROWS)]
    parts = max(1, min(parts or _threads(), len(spans)))
    cuts = [len(spans) * i // parts for i in range(parts + 1)]
    groups = [(tree, tbins, spans[lo:hi], num_bins, nodes)   # runs of blocks:
              for lo, hi in zip(cuts, cuts[1:])]             # a shard or two each
    if run is None:
        with ThreadPoolExecutor(parts) as ex:
            got = list(ex.map(
                lambda g: spans_counts(bins_cm, clicked, *g), groups))
    else:
        got = run(groups)
    sums: List = [None] * len(bounds)
    for part in got:
        for s, c in part.items():
            sums[s] = c if sums[s] is None else _add(sums[s], c)
    return sums


# -- one combination of addends against the tree --------------------------------

def _compare(tree, tbins, per_shard, g, h, init, lr, l2, min_data,
             min_hess) -> Dict:
    """The tree against the reference at addends ``g`` [2] (no click,
    click) and ``h`` [2]; every compared number beside its limit."""
    per_label = np.stack([g, h, np.ones(2)], axis=1)            # [2, 3]
    nodes = per_shard[0][0].shape[0]
    splits = []
    for i in range(nodes):
        hist = sum(c[0][i] @ per_label for c in per_shard)      # by shard
        gains = ref.split_gains(hist, l2, min_data, min_hess)
        rf, rb = np.unravel_index(int(np.argmax(gains)), gains.shape)
        tf, tb = int(tree["split_feature"][i]), int(tbins[i])
        best, own = float(gains[rf, rb]), float(gains[tf, tb])
        short = best - own
        splits.append({
            "node": i, "tree": [tf, tb], "reference": [int(rf), int(rb)],
            "tree_gain": own, "reference_gain": best,
            "recorded_gain": float(tree["split_gain"][i]),
            "gain_short_by": short, "limit": RTOL * abs(best),
            "rows_by_shard": [int(c[0][i, 0].sum()) for c in per_shard],
            "ok": bool((tf, tb) == (rf, rb) or short <= RTOL * abs(best))})
    n = sum(c[1] for c in per_shard)                            # [leaves, 2]
    counts_ok = bool(np.array_equal(n.sum(axis=1), tree["leaf_count"]))
    G = sum(c[1] @ g for c in per_shard)
    H = sum(c[1] @ h for c in per_shard)
    A = sum(c[1] @ np.abs(g) for c in per_shard)
    got = (tree["leaf_value"] - init) / lr
    carried_g, carried_h = _carried(tree, tbins, per_shard, g, h)
    carried = carried_g + np.abs(got) * carried_h
    with np.errstate(invalid="ignore", divide="ignore"):
        off = np.abs(got * (H + l2) + G)          # in units of sum g
        err = off / A
        over = off / (RTOL * A + ACCUMULATION * carried)
    empty = n.sum(axis=1) == 0
    err, over = np.where(empty, np.inf, err), np.where(empty, np.inf, over)
    worst = int(np.argmax(over))
    leaves = {"n": int(tree["num_leaves"]), "counts_ok": counts_ok,
              "worst_error_over_limit": float(over[worst]),
              "worst_error_over_scale": float(err[worst]), "limit": RTOL,
              "accumulation": ACCUMULATION,
              "largest_error_over_scale": float(err.max()),
              "largest_errors_over_limit": np.sort(over)[-5:][::-1].tolist(),
              "held_within_twice_rtol": int(
                  (ACCUMULATION * carried <= RTOL * A).sum()),
              "worst_leaf": {"leaf": worst, "rows": int(n[worst].sum()),
                             "sum_abs_g": float(A[worst]),
                             "sum_h": float(H[worst]),
                             "carried_sum_abs_g": float(carried_g[worst]),
                             "carried_sum_h": float(carried_h[worst]),
                             "carried": float(carried[worst]),
                             "value": float(tree["leaf_value"][worst])},
              "values_ok": bool(over[worst] <= 1.0)}
    return {"splits": splits, "leaves": leaves,
            "ok": bool(all(s["ok"] for s in splits) and counts_ok
                       and leaves["values_ok"])}


def _carried(tree, tbins, per_shard, g, h) -> Tuple[np.ndarray, np.ndarray]:
    """([leaves] sum|g|, [leaves] sum h) of the float32 numbers a leaf's
    two sums are made of (the module's docstring): its side's bins of the
    parent's split column at every node of the parent's chain, and for a
    right side twice the parent's own bins, whose total and left prefix
    it is the difference of. Sums by shard, then merged."""
    per = np.stack([np.abs(g), h], axis=1)                      # [2, 2]
    own = sum(c[2] @ np.stack([g, h], axis=1) for c in per_shard)
    own = np.abs(own).sum(axis=1)                 # [n_int, 2]: |sum g|, sum h
    chain = sum(c[3] @ per for c in per_shard)    # [n_int, B, 2]
    carried = np.zeros((tree["num_leaves"], 2))
    for i, t in enumerate(tbins):
        t = int(t)
        if tree["left_child"][i] < 0:
            carried[~tree["left_child"][i]] = chain[i, :t + 1].sum(axis=0)
        if tree["right_child"][i] < 0:
            carried[~tree["right_child"][i]] = (chain[i, t + 1:].sum(axis=0)
                                                + 2.0 * own[i])
    return carried[:, 0], carried[:, 1]


def _badness(cmp: Dict) -> Tuple:
    return (sum(not s["ok"] for s in cmp["splits"]),
            cmp["leaves"]["worst_error_over_limit"])


# -- the check -------------------------------------------------------------------

def check_first_tree(model_text: str, upper_bounds: Sequence[np.ndarray],
                     bins_cm: np.ndarray, y: np.ndarray, params: Dict,
                     shard_rows: int, addend_dtype: str = "bfloat16",
                     nodes_checked: int = 5,
                     margin: float = BOUNDARY_MARGIN, run=None,
                     parts: int = 0,
                     control_dtype: str = "float8_e4m3") -> Dict:
    """Hold tree 0 of a binary-objective model, built over rows that lie
    ``shard_rows`` to a shard, to the reference on the binned training
    data. Returns a report whose ``ok`` is the verdict; with several
    admissible roundings, the report is of the one that passes, else of
    the nearest miss. ``run`` and ``parts``: who counts the blocks
    (:func:`shard_counts`); the counts are whole numbers, the same
    whoever takes them. ``control``: the same tree and counts against
    addends rounded to ``control_dtype``, which has to read not ok."""
    if addend_dtype != "float32" and addend_dtype not in SIGNIFICAND_BITS:
        raise ValueError(f"no rounding rule for addends of {addend_dtype!r}")
    y = np.asarray(y)
    clicked = y > 0
    if not np.array_equal(y, clicked.astype(y.dtype)):
        raise ValueError("binary labels are 0 and 1")
    lr = float(params["learning_rate"])
    l2 = float(params.get("lambda_l2", 0.0))
    min_data = int(params["min_data_in_leaf"])
    min_hess = float(params["min_sum_hessian_in_leaf"])
    num_bins = max(len(u) for u in upper_bounds)
    tree = ref.parse_tree(model_text, 0)
    tbins = ref.threshold_bins(tree, upper_bounds)
    rows = len(y)
    bounds = [(lo, min(lo + shard_rows, rows))
              for lo in range(0, rows, int(shard_rows))]
    nodes = min(nodes_checked, len(tbins))
    per_shard = shard_counts(tree, tbins, bins_cm, clicked, bounds,
                             num_bins, nodes, run, parts)
    total = sum(int(c[1].sum()) for c in per_shard)

    init = ref.binary_init_score(y)
    g, h = ref.binary_gradients(np.array([0.0, 1.0]), np.full(2, init))
    addends = {"g_no_click": admissible(g[0], addend_dtype, margin),
               "g_click": admissible(g[1], addend_dtype, margin),
               "h": admissible(h[0], addend_dtype, margin)}
    tried, chosen = 0, None
    for g0, g1, hh in itertools.product(
            *(addends[k]["admissible"] for k in ("g_no_click", "g_click", "h"))):
        cmp = _compare(tree, tbins, per_shard, np.array([g0, g1]),
                       np.array([hh, hh]), init, lr, l2, min_data, min_hess)
        cmp["addends_used"] = {"g_no_click": g0, "g_click": g1, "h": hh}
        tried += 1          # the nearest rounding of all three comes first
        if chosen is None or _badness(cmp) < _badness(chosen):
            chosen = cmp
        if cmp["ok"]:
            break
    recorded = [abs(s["recorded_gain"] - s["tree_gain"]) / abs(s["tree_gain"])
                for s in chosen["splits"] if s["tree_gain"]]
    report = {"tree": 0, "shards": len(bounds),
              "rows_by_shard": [hi - lo for lo, hi in bounds],
              "addend_dtype": addend_dtype, "rtol": RTOL, "init_score": init,
              "addends": addends, "roundings_tried": tried,
              "values_on_a_rounding_boundary": sum(
                  len(a["admissible"]) > 1 for a in addends.values()),
              "all_rows_reach_a_leaf": total == rows,
              "worst_recorded_gain_rel_error": max(recorded, default=0.0)}
    report.update(chosen)
    report["ok"] = bool(chosen["ok"] and total == rows)
    if control_dtype and control_dtype != addend_dtype:
        bits = SIGNIFICAND_BITS[control_dtype]
        cg = np.array([neighbours(v, bits)[2] for v in g])
        ch = np.full(2, neighbours(h[0], bits)[2])
        cmp = _compare(tree, tbins, per_shard, cg, ch, init, lr, l2,
                       min_data, min_hess)
        report["control"] = {
            "addend_dtype": control_dtype, "ok": cmp["ok"],
            "splits_ok": sum(s["ok"] for s in cmp["splits"]),
            "worst_error_over_limit": cmp["leaves"]["worst_error_over_limit"],
            "held_within_twice_rtol": cmp["leaves"]["held_within_twice_rtol"]}
    return report
