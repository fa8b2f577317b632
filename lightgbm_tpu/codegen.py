"""Model code generation: C emission and the XLA ensemble tensorizer.

Two backends share this module because both lower a *whole trained
ensemble* into one standalone program:

- ``model_to_c`` — the reference's ``GBDT::SaveModelToIfElse`` /
  ``ModelToIfElse`` analog (``src/boosting/gbdt_model_text.cpp:286``,
  ``Tree::ToIfElse`` ``src/io/tree.cpp``): a self-contained C file with
  one nested if-else function per tree plus an aggregate ``PredictRaw``
  — for embedding models in environments without the framework (the
  reference CLI's ``task=convert_model``).
- ``CompiledEnsemble`` / ``tensorize_ensemble`` — the serving-side
  tensorizer (ISSUE 15): every tree is packed into dense
  ``[n_trees, max_nodes]`` node tables (feature, threshold, packed
  children, decision bits) and the whole ensemble becomes ONE jittable
  XLA program — a branchless depth-clamped gather loop vectorized over
  ``[batch, n_trees]`` (the GPU-predict layout of arXiv 1806.11248:
  level-synchronous traversal, no per-tree dispatch), with the leaf
  reduction done in one pass. One compile per (model version, ladder
  rung); ``warm()`` pre-pays every rung off the serving path.

Missing-value and categorical decision semantics match the decision_type
bit layout used everywhere else (bit0 cat, bit1 default_left, bits 2-3
missing type) — the tensorized walk is bit-compatible with the host
walk (``tree.h`` NumericalDecision / CategoricalDecision) on every
missing type and categorical bitset, and the default ``host64`` output
mode reduces per-tree leaf values on the host in float64 in tree order,
reproducing ``PredictSession.predict``'s scores bit-for-bit.
"""

from __future__ import annotations

import functools
import threading
from typing import List, NamedTuple, Optional, Sequence

import numpy as np

__all__ = ["model_to_c", "tensorize_ensemble", "TensorizedTables",
           "CompiledEnsemble"]


def _tree_fn(tree, i: int) -> str:
    lines = [f"static double PredictTree{i}(const double* f) {{"]

    def emit(node: int, depth: int):
        pad = "  " * (depth + 1)
        if node < 0:
            lines.append(f"{pad}return {float(tree.leaf_value[~node])!r};")
            return
        fidx = int(tree.split_feature[node])
        dt = int(tree.decision_type[node])
        if dt & 1:  # categorical: membership in the split's value set
            cat_idx = int(tree.threshold[node])
            lo = tree.cat_boundaries[cat_idx]
            hi = tree.cat_boundaries[cat_idx + 1]
            cats = [c for c in range((hi - lo) * 32)
                    if (tree.cat_threshold[lo + c // 32] >> (c % 32)) & 1]
            cond = " || ".join(f"(int)f[{fidx}] == {c}" for c in cats)
            lines.append(f"{pad}if (!isnan(f[{fidx}]) && f[{fidx}] >= 0 "
                         f"&& ({cond})) {{")
        else:
            thr = float(tree.threshold[node])
            mt = (dt >> 2) & 3
            defl = bool(dt & 2)
            if mt == 2:  # NaN-aware: missing follows default_left
                nan_br = "isnan(f[%d])" % fidx
                cond = (f"({nan_br} ? 1 : f[{fidx}] <= {thr!r})" if defl
                        else f"(!{nan_br} && f[{fidx}] <= {thr!r})")
                lines.append(f"{pad}if {cond} {{")
            elif mt == 1:
                # Zero-as-missing: NaN folds to 0.0 and |v| <= 1e-35
                # routes to the DEFAULT side (tree.h:359), not through
                # the threshold compare
                zv = (f"(isnan(f[{fidx}]) ? 0.0 : f[{fidx}])")
                miss = f"(fabs({zv}) <= 1e-35)"
                cond = (f"({miss} ? 1 : {zv} <= {thr!r})" if defl
                        else f"(!{miss} && {zv} <= {thr!r})")
                lines.append(f"{pad}if {cond} {{")
            else:  # None: NaN treated as 0.0
                lines.append(
                    f"{pad}if ((isnan(f[{fidx}]) ? 0.0 : f[{fidx}])"
                    f" <= {thr!r}) {{")
        emit(int(tree.left_child[node]), depth + 1)
        lines.append(f"{pad}}} else {{")
        emit(int(tree.right_child[node]), depth + 1)
        lines.append(f"{pad}}}")

    if tree.num_leaves == 1:
        lines.append(f"  return {float(tree.leaf_value[0])!r};")
    else:
        # emit() recursion depth equals TREE depth — measure it
        # (wide-but-shallow trees are fine at any leaf count)
        import sys
        depth, stack = 0, [(0, 1)]
        while stack:
            nd, d = stack.pop()
            if nd < 0:
                depth = max(depth, d)
                continue
            stack.append((int(tree.left_child[nd]), d + 1))
            stack.append((int(tree.right_child[nd]), d + 1))
        if depth > sys.getrecursionlimit() // 4:
            raise ValueError(
                f"tree too deep for if-else codegen (depth {depth})")
        emit(0, 0)
    lines.append("}")
    return "\n".join(lines)


def model_to_c(trees: List, num_class: int = 1,
               objective: str = "regression",
               average_output: bool = False) -> str:
    """Standalone C translation unit for the ensemble.

    Exposes ``void PredictRaw(const double* features, double* out)``
    (raw scores, ``out[num_class]``) — sigmoid/softmax conversion is the
    caller's job, like the reference's generated code.
    """
    K = max(1, num_class)
    parts = [
        "/* generated by lightgbm_tpu (convert_model; analog of",
        "   gbdt_model_text.cpp ModelToIfElse) */",
        "#include <math.h>",
        f"#define NUM_CLASS {K}",
        f"#define NUM_TREES {len(trees)}",
        f"/* objective: {objective} */",
        "",
    ]
    for i, t in enumerate(trees):
        if getattr(t, "is_linear", False):
            raise ValueError("convert_model does not support linear trees")
        parts.append(_tree_fn(t, i))
        parts.append("")
    calls = "\n".join(
        f"  out[{i % K}] += PredictTree{i}(features);"
        for i in range(len(trees)))
    avg = ""
    if average_output and trees:
        # RF mode: raw scores are running AVERAGES (rf.hpp)
        per_class = max(1, len(trees) // K)
        avg = (f"  for (k = 0; k < NUM_CLASS; ++k) "
               f"out[k] /= {per_class}.0;")
    parts += [
        "void PredictRaw(const double* features, double* out) {",
        "  int k;",
        "  for (k = 0; k < NUM_CLASS; ++k) out[k] = 0.0;",
        calls,
        avg,
        "}",
        "",
    ]
    return "\n".join(parts)


# ---------------------------------------------------------------------
# XLA tensorizer (ISSUE 15): ensemble -> one jittable program
# ---------------------------------------------------------------------

class TensorizedTables(NamedTuple):
    """Dense SoA node tables of a whole ensemble (host numpy; the
    :class:`CompiledEnsemble` device-places them per replica).

    ``children`` packs both child references of a node into one int32:
    ``(left & 0xffff) << 16 | (right & 0xffff)``. References use the
    writer's numbering (child >= 0 internal node, child < 0 means
    ``~leaf_index``), so each half is a SIGNED 16-bit field — unpacking
    with arithmetic shifts (``>> 16`` / ``<< 16 >> 16``) sign-extends
    negative leaf refs for free. One gather per step fetches both
    children instead of two.
    """

    feature: np.ndarray     # [T, N] int32 split feature per node
    threshold: np.ndarray   # [T, N] f32 (cat splits: cat split index)
    decision: np.ndarray    # [T, N] int32 decision_type bits
    children: np.ndarray    # [T, N] int32 packed left/right
    init_node: np.ndarray   # [T] int32 root (or ~0 for stump trees)
    leaf_value: np.ndarray  # [T, L] f32
    cat_bound: np.ndarray   # [T, C+1] int32 cat split word bounds
    cat_words: np.ndarray   # [T, W] int32 bitset words (uint32 bits)


def tensorize_ensemble(trees: List) -> "tuple[TensorizedTables, int]":
    """Host Trees -> dense tables + static max depth.

    Raises ``ValueError`` for models the dense layout cannot represent
    (linear-leaf trees; > 32767 internal nodes / 32768 leaves per tree —
    the packed int16 child fields' range).
    """
    if not trees:
        raise ValueError("tensorize_ensemble needs a nonempty ensemble")
    from .ops.predict_ensemble import _tree_depth
    for t in trees:
        if getattr(t, "is_linear", False):
            raise ValueError("linear-leaf trees are not tensorizable "
                             "(leaf outputs depend on raw features)")
        if t.num_leaves > (1 << 15):
            raise ValueError(
                f"tree with {t.num_leaves} leaves exceeds the packed "
                "int16 child range (32768)")
    T = len(trees)
    N = max(max(t.num_leaves - 1, 1) for t in trees)
    L = max(t.num_leaves for t in trees)
    C = max(t.num_cat for t in trees) + 1
    W = max(max(len(t.cat_threshold), 1) for t in trees)

    sf = np.zeros((T, N), np.int32)
    thr = np.zeros((T, N), np.float32)
    dt = np.zeros((T, N), np.int32)
    ch = np.zeros((T, N), np.int32)
    init = np.zeros(T, np.int32)
    lv = np.zeros((T, L), np.float32)
    cb = np.zeros((T, C + 1), np.int32)
    cw = np.zeros((T, W), np.int64)
    depth = 1
    for i, t in enumerate(trees):
        ni = t.num_leaves - 1
        lv[i, :t.num_leaves] = t.leaf_value
        if ni <= 0:
            init[i] = -1           # stump: start AT leaf 0 (~0)
            continue
        depth = max(depth, _tree_depth(t))
        sf[i, :ni] = t.split_feature
        thr[i, :ni] = t.threshold
        dt[i, :ni] = t.decision_type
        lc = np.asarray(t.left_child, np.int32)
        rc = np.asarray(t.right_child, np.int32)
        ch[i, :ni] = ((lc & 0xffff) << 16) | (rc & 0xffff)
        cb[i, :len(t.cat_boundaries)] = t.cat_boundaries
        if t.cat_threshold:
            cw[i, :len(t.cat_threshold)] = t.cat_threshold
    # bitset words are uint32 BIT PATTERNS; reinterpret, never convert
    cw32 = cw.astype(np.uint32).view(np.int32)
    return (TensorizedTables(sf, thr, dt, ch, init, lv, cb, cw32),
            int(depth))


def _tensor_leaves(tables: TensorizedTables, X, *, depth: int):
    """[n, T] leaf indices for X [n, F] f32 — the branchless walk.

    A ``fori_loop`` with a STATIC trip count (the ensemble's max
    root-to-leaf depth, fixed at tensorize time) instead of the packed
    walk's early-exit ``while_loop``: every step is pure gathers and
    selects over the ``[batch, trees]`` lattice, no convergence check,
    no host round-trip — the shape XLA vectorizes and pipelines best.
    Lanes that reached a leaf hold their (negative) node id; decision
    semantics are identical to ``ops.predict_ensemble._walk`` (tree.h
    NumericalDecision / CategoricalDecision incl. missing types).
    """
    import jax
    import jax.numpy as jnp
    n = X.shape[0]
    F = X.shape[1]
    T, N = tables.feature.shape
    L = tables.leaf_value.shape[1]
    Cb = tables.cat_bound.shape[1]
    W = tables.cat_words.shape[1]
    # flattened tables + per-tree offsets: one 1-D take per field
    # fetches the [n, T] lattice
    offs = jnp.arange(T, dtype=jnp.int32)[None, :] * N
    cat_offs = jnp.arange(T, dtype=jnp.int32)[None, :] * Cb
    word_offs = jnp.arange(T, dtype=jnp.int32)[None, :] * W
    feat_f = tables.feature.reshape(-1)
    thr_f = tables.threshold.reshape(-1)
    dec_f = tables.decision.reshape(-1)
    ch_f = tables.children.reshape(-1)
    cb_f = tables.cat_bound.reshape(-1)
    cw_f = tables.cat_words.reshape(-1)
    node0 = jnp.broadcast_to(tables.init_node[None, :], (n, T))

    def body(_, node):
        at_leaf = node < 0
        idx = jnp.clip(node, 0, N - 1) + offs
        feat = jnp.take(feat_f, idx)
        v = jnp.take_along_axis(X, jnp.clip(feat, 0, F - 1), axis=1)
        dt = jnp.take(dec_f, idx)
        thr = jnp.take(thr_f, idx)
        is_cat = (dt & 1) != 0
        nan = jnp.isnan(v)
        mt = (dt >> 2) & 3
        vz = jnp.where(nan & (mt != 2), 0.0, v)
        gl_num = vz <= thr
        defl = (dt & 2) != 0
        # missing -> default side: NaN under MissingType::NaN, and
        # |v| <= 1e-35 (incl. NaN folded to 0) under MissingType::Zero
        # (tree.h:359; zeros must NOT take the threshold compare)
        miss = ((nan & (mt == 2))
                | ((jnp.abs(vz) <= 1e-35) & (mt == 1)))
        gl_num = jnp.where(miss, defl, gl_num)
        # categorical: threshold holds the cat split index
        cat_idx = jnp.clip(thr.astype(jnp.int32), 0, Cb - 2)
        lo = jnp.take(cb_f, cat_idx + cat_offs)
        hi = jnp.take(cb_f, cat_idx + 1 + cat_offs)
        cval = jnp.where(nan | (v < 0), -1, v).astype(jnp.int32)
        word = jnp.clip(lo + (cval >> 5), 0, W - 1)
        wv = jnp.take(cw_f, word + word_offs)
        in_set = ((wv >> (cval & 31)) & 1) == 1
        gl_cat = (cval >= 0) & (lo + (cval >> 5) < hi) & in_set
        go_left = jnp.where(is_cat, gl_cat, gl_num)
        ch = jnp.take(ch_f, idx)
        # packed signed-int16 halves: arithmetic shifts sign-extend
        nxt = jnp.where(go_left, ch >> 16, (ch << 16) >> 16)
        return jnp.where(at_leaf, node, nxt)

    node = jax.lax.fori_loop(0, depth, body, node0)
    return jnp.clip(~node, 0, L - 1)


def _tensor_values(tables: TensorizedTables, X, *, depth: int):
    """[n, T] f32 per-tree leaf values (one fused gather epilogue)."""
    import jax.numpy as jnp
    T, _ = tables.feature.shape
    L = tables.leaf_value.shape[1]
    leaf = _tensor_leaves(tables, X, depth=depth)
    lv_f = tables.leaf_value.reshape(-1)
    offs = jnp.arange(T, dtype=jnp.int32)[None, :] * L
    return jnp.take(lv_f, leaf + offs)


def _tensor_reduced(tables: TensorizedTables, X, cls, *, depth: int,
                    num_class: int):
    """[n, K] f32 raw class sums reduced IN-program (one matmul pass).

    Accumulates in f32 on device — the TPU-throughput mode. The exact
    serving path (``CompiledEnsemble.predict``) keeps the reduction on
    host in f64 for bit-parity with ``PredictSession``; this program is
    the single-device-pass variant for accelerators without cheap
    host readback (same caveat as ``pred_early_stop``'s f32 sums).
    """
    import jax.numpy as jnp
    vals = _tensor_values(tables, X, depth=depth)
    onehot = (cls[:, None] == jnp.arange(num_class,
                                         dtype=jnp.int32)[None, :])
    return vals @ onehot.astype(jnp.float32)


class CompiledEnsemble:
    """One whole ensemble as a single jittable XLA program.

    Built from a Booster (same tree-window kwargs as
    :class:`~lightgbm_tpu.engine.PredictSession`); raises ``ValueError``
    for windows the dense layout cannot express (linear trees,
    ``pred_contrib``, early stopping) so callers can gate and fall back
    to the session path with a named reason.

    Output modes:

    - ``predict(X)`` — the serving path. Device walks all trees
      branchlessly and returns leaf indices; the per-class reduction
      runs on host in float64 IN TREE ORDER, then shares the Booster's
      ``_finalize_scores`` (RF averaging, squeeze, objective
      transform). Bit-identical to ``PredictSession.predict`` wherever
      the f32 device routing agrees with the f64 host routing — the
      same contract the packed device walk documents.
    - ``predict(X)`` with ``pred_leaf=True`` at construction — [n, T]
      leaf indices (parity with ``predict_leaf_index``).
    - ``predict_device(X)`` — raw class sums reduced in-program in f32
      (one pass, no host readback of per-tree values): the TPU
      throughput mode, with the documented f32-accumulation caveat.

    Compile discipline: one compile per (model version, batch shape,
    device). ``warm(ladder)`` pre-pays every ladder rung off the
    serving path; replicas pass ``device=`` so each mesh device holds
    its own table copy and executable.
    """

    def __init__(self, booster, *, start_iteration: int = 0,
                 num_iteration: Optional[int] = None,
                 raw_score: bool = False, pred_leaf: bool = False,
                 **kwargs):
        import jax
        if kwargs.pop("pred_contrib", False):
            raise ValueError("pred_contrib is not tensorizable "
                             "(TreeSHAP walks all paths)")
        if booster._early_stop_config(kwargs) is not None:
            raise ValueError("pred_early_stop is not tensorizable "
                             "(chunked early exit; use the session)")
        booster._sync_trees()
        K = max(1, booster._num_class)
        trees = booster._all_trees()
        ni = num_iteration
        if ni is None or ni < 0:
            ni = (booster.best_iteration if booster.best_iteration > 0
                  else len(trees) // K)
        lo = start_iteration * K
        hi = min(len(trees), (start_iteration + ni) * K)
        use = trees[lo:hi]
        tables, depth = tensorize_ensemble(use)
        self.booster = booster
        self.model_version = booster._model_version
        self.num_features = booster._max_feature_idx + 1
        self.num_class = K
        self.num_trees = len(use)
        self.depth = depth
        self.raw_score = bool(raw_score)
        self.pred_leaf = bool(pred_leaf)
        self._use = use
        self._lo = lo
        self._tables_np = tables
        # f64 leaf tables for the exact host reduction (tree order)
        self._leaf64 = [np.asarray(t.leaf_value, np.float64)
                        for t in use]
        self._cls_np = np.asarray(
            [(lo + i) % K for i in range(len(use))], np.int32)
        self._jit_leaves = jax.jit(
            functools.partial(_tensor_leaves, depth=depth))
        self._jit_reduced = jax.jit(functools.partial(
            _tensor_reduced, depth=depth, num_class=K))
        self._place_lock = threading.Lock()
        self._placed: dict = {}

    # -- device placement ---------------------------------------------
    def tables_for(self, device=None):
        """The tables as device arrays, placed (and cached) on
        ``device`` — each replica's copy lives on its own mesh
        device."""
        import jax
        import jax.numpy as jnp
        key = device
        got = self._placed.get(key)
        if got is None:
            with self._place_lock:
                got = self._placed.get(key)
                if got is None:
                    if device is None:
                        got = TensorizedTables(
                            *map(jnp.asarray, self._tables_np))
                    else:
                        got = TensorizedTables(*(
                            jax.device_put(a, device)
                            for a in self._tables_np))
                    self._placed[key] = got
        return got

    def _as_f32_matrix(self, X, device=None):
        import jax
        import jax.numpy as jnp
        X = np.asarray(X)
        if X.ndim == 1:
            X = X[None, :]
        if X.ndim != 2 or X.shape[1] != self.num_features:
            raise ValueError(
                f"CompiledEnsemble expects [rows, {self.num_features}] "
                f"features, got {X.shape}")
        Xd = jnp.asarray(X, jnp.float32)
        if device is not None:
            Xd = jax.device_put(Xd, device)
        return Xd

    def _check_version(self):
        if self.booster._model_version != self.model_version:
            raise RuntimeError(
                "model version moved under a CompiledEnsemble — "
                "registered models are serving-only; swap in a new "
                "version instead of training in place")

    # -- prediction ----------------------------------------------------
    def predict_leaf(self, X, device=None) -> np.ndarray:
        """[n, T] leaf indices (``pred_leaf`` output)."""
        self._check_version()
        tb = self.tables_for(device)
        Xd = self._as_f32_matrix(X, device)
        return np.asarray(self._jit_leaves(tb, Xd))

    def predict(self, X, device=None) -> np.ndarray:
        """The exact serving path: device walk + host f64 reduction in
        tree order + shared finalize — ``PredictSession.predict``'s
        score pipeline bit-for-bit."""
        if self.pred_leaf:
            return self.predict_leaf(X, device)
        leaf = self.predict_leaf(X, device)
        raw = np.zeros((leaf.shape[0], self.num_class))
        cls = self._cls_np
        for i, lv in enumerate(self._leaf64):
            raw[:, cls[i]] += lv[leaf[:, i]]
        return self.booster._finalize_scores(
            raw, self._use, self.num_class, self.raw_score)

    def predict_device(self, X, device=None) -> np.ndarray:
        """Raw sums reduced in-program (f32 accumulation), finalized on
        host — the no-per-tree-readback throughput mode."""
        self._check_version()
        tb = self.tables_for(device)
        Xd = self._as_f32_matrix(X, device)
        import jax.numpy as jnp
        cls = jnp.asarray(self._cls_np)
        raw = np.asarray(self._jit_reduced(tb, Xd, cls), np.float64)
        return self.booster._finalize_scores(
            raw, self._use, self.num_class, self.raw_score)

    # -- warmup / introspection ---------------------------------------
    def warm(self, rungs: Sequence[int], device=None,
             mode: str = "serving") -> "CompiledEnsemble":
        """Compile every batch-ladder rung now, off the serving path.
        ``mode="serving"`` warms the leaf-walk program ``predict`` uses;
        ``mode="device"`` additionally warms the in-program reduction.
        """
        for r in sorted(set(int(r) for r in rungs)):
            Z = np.zeros((r, self.num_features), np.float64)
            self.predict(Z, device=device)
            if mode == "device":
                self.predict_device(Z, device=device)
        return self

    def compiled_signatures(self) -> int:
        """Distinct compiled signatures of the serving walk (the
        recompile-guard bound: ladder size x replicas)."""
        from .analysis.recompile_guard import cache_size
        return cache_size(self._jit_leaves)

    def lower_serving(self, rows: int = 256):
        """AOT-compile the serving walk at one shape (the trace
        doctor's hook)."""
        import jax
        tb = self.tables_for(None)
        X = self._as_f32_matrix(
            np.zeros((rows, self.num_features), np.float32))
        return jax.jit(functools.partial(
            _tensor_leaves, depth=self.depth)).lower(tb, X).compile()

    def describe(self) -> dict:
        return {"num_trees": self.num_trees, "depth": self.depth,
                "num_class": self.num_class,
                "max_nodes": int(self._tables_np.feature.shape[1]),
                "compiled_signatures": self.compiled_signatures(),
                "placed_devices": len(self._placed)}
