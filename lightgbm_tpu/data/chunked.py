"""Out-of-core leaf-wise tree growth over a streamed bin matrix.

The resident builder (``boosting/tree_builder._build_tree_impl``)
stages the whole ``[R, F]`` bin matrix into one on-device while_loop.
When the matrix exceeds device capacity (``dataset.
check_device_capacity``), this module grows the SAME tree from a
stream of fixed-size row chunks:

- the per-row state that the loop actually mutates — ``row_leaf`` [R]
  int32 and ``gh`` [R, 3] — stays device-resident (16 bytes/row; it is
  the [R, F] bin matrix that blows the budget, not these);
- each leaf-growth round re-streams the chunks through ONE jitted
  program (:meth:`ChunkedTreeBuilder._chunk_impl`) that relabels the
  chunk's rows against the round's pending splits and folds their
  histogram contribution into a carried accumulator via
  ``build_histograms(..., init=acc)``;
- split selection / tree recording run in small jitted programs
  between sweeps, replicating the resident builder's pop→record→
  find-best round body outside the while_loop (the loop goes eager —
  chunk count is a host decision, not a traced one).

Bit-equivalence: ``build_histograms``'s ``init`` carry makes chunked
accumulation over ``block_rows``-aligned chunk boundaries add in the
SAME order as one resident pass (its docstring carries the argument),
the relabel is per-row elementwise, and the pop/record/find-best code
here mirrors the resident body line for line — so a chunked build over
matching bin boundaries produces bit-identical trees to the resident
path with ``hist_subtraction=false`` and the same pinned ``hist_impl``
(tests/test_ingest.py locks this).

Scope: the chunked path deliberately supports the SERIAL simple-branch
feature set (bagging/GOSS, quantized gradients, categoricals,
feature_fraction, gain_scale, valid-set tracking). With
``hist_subtraction`` on (the default) each round streams only the W
SMALLER siblings and derives the big ones from a per-leaf RAW parent
cache by subtraction ([L+1, F, B, 3] device state — tiny next to the
[R, F] matrix chunking exists to avoid; exact in int32 quantized mode,
f32 subtraction rounding otherwise — the resident builder's own
hist_sub caveat). ``hist_subtraction=false`` restores the full
per-round rebuild, which is what the resident-vs-chunked bitwise
parity tests pin.
Everything that bends the round body — EFB bundles, linear trees,
CEGB, forced splits, monotone constraints, interaction constraints,
per-node sampling, extra-trees, meshes — gates back to resident in
``GBDT._chunked_gate_reason``.
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..ops.histogram import HIST_CH, build_histograms, resolve_impl
from ..ops.split import SplitParams, find_best_splits, leaf_output

__all__ = ["ArraySource", "ShardSource", "ChunkedTreeBuilder"]

NEG_INF = -jnp.inf


# ----------------------------------------------------------------------
# chunk sources: host-side providers of binned rows by global row range


class ArraySource:
    """Host-resident bin matrix as a chunk source (the transparent
    fallback when a device capacity check fails but the matrix still
    fits host RAM)."""

    def __init__(self, bins: np.ndarray):
        self.bins = np.asarray(bins)

    @property
    def num_rows(self) -> int:
        return int(self.bins.shape[0])

    @property
    def num_features(self) -> int:
        return int(self.bins.shape[1])

    def read_rows(self, lo: int, hi: int) -> np.ndarray:
        return self.bins[lo:hi]

    def close(self) -> None:
        pass


class ShardSource:
    """A ``.lgbtpu`` shard directory as one contiguous global row
    stream (mmap-backed; a read only touches the pages it spans)."""

    def __init__(self, readers):
        self.readers = sorted(readers, key=lambda r: r.row0)
        if not self.readers:
            raise ValueError("ShardSource needs at least one shard")

    @property
    def num_rows(self) -> int:
        last = self.readers[-1]
        return int(last.row0 + last.num_rows)

    @property
    def num_features(self) -> int:
        return int(self.readers[0].bins.shape[1])

    def read_rows(self, lo: int, hi: int) -> np.ndarray:
        parts = []
        for r in self.readers:
            a, b = max(lo, r.row0), min(hi, r.row0 + r.num_rows)
            if a < b:
                parts.append(r.read_rows(a - r.row0, b - r.row0))
        if not parts:
            raise ValueError(f"row range [{lo}, {hi}) outside shards")
        out = parts[0] if len(parts) == 1 else np.concatenate(parts)
        if out.shape[0] != hi - lo:
            raise ValueError(
                f"shard set has a gap inside row range [{lo}, {hi})")
        return out

    def close(self) -> None:
        for r in self.readers:
            r.close()


# ----------------------------------------------------------------------
# the chunked builder


class ChunkedTreeBuilder:
    """Leaf-wise growth with the round body split into jitted pieces
    around an eager chunk sweep. Construct ONCE per booster (the four
    jitted programs cache their compilations across trees/iterations).
    """

    def __init__(self, *, num_bins_pf, nan_bin_pf, is_cat_pf,
                 num_leaves: int, leaf_batch: int, max_depth: int,
                 num_bins: int, split_params: SplitParams,
                 hist_dtype: str = "bfloat16", hist_impl: str = "auto",
                 block_rows: int = 0,
                 cat_sorted_mask: Optional[jax.Array] = None,
                 hist_sub: bool = True):
        impl = resolve_impl(hist_impl, num_bins)
        if impl not in ("scatter", "matmul"):
            # native/pallas have no carried-init formulation that is
            # bit-stable under chunking (post-add reorders f32 sums)
            impl = "scatter"
        self.impl = impl
        self.hist_dtype = hist_dtype
        self.block_rows = int(block_rows)
        self.num_bins_pf = jnp.asarray(num_bins_pf, jnp.int32)
        self.nan_bin_pf = jnp.asarray(nan_bin_pf, jnp.int32)
        self.is_cat_pf = jnp.asarray(is_cat_pf, bool)
        self.cat_sorted_mask = cat_sorted_mask
        self.sp = split_params
        self.L = int(num_leaves)
        self.W = max(1, min(int(leaf_batch), self.L - 1))
        self.MAXN = 2 * self.L - 1
        self.B = int(num_bins)
        self.F = int(self.num_bins_pf.shape[0])
        self.max_depth = int(max_depth)
        self.DUMMY_LEAF = self.L
        self.DUMMY_NODE = self.MAXN
        self.BW = (self.B + 31) // 32
        from ..boosting.tree_builder import max_rounds_for
        self.rounds_bound = max_rounds_for(self.L, self.W)
        # parent-minus-child subtraction (serial_tree_learner.cpp:567
        # Subtract analog, ROADMAP item 2 leftover): keep a per-leaf RAW
        # parent histogram cache across rounds so each sweep streams
        # only the W SMALLER siblings' histograms and derives the big
        # ones by subtraction — the cache is [L+1, F, B, 3] device
        # state, tiny next to the [R, F] matrix chunking exists to
        # avoid. Exact (bit-identical to the full rebuild) in int32
        # quantized mode; f32 differs by subtraction rounding, the same
        # accepted variance as the resident builder's hist_sub path.
        self.hist_sub = bool(hist_sub)

        self._pop_j = jax.jit(self._pop_impl)
        self._chunk_j = jax.jit(self._chunk_impl)
        self._root_j = jax.jit(self._root_impl)
        self._finish_j = jax.jit(self._finish_impl)
        self._sub_j = jax.jit(self._sub_impl)

    # -------------------------- shared pieces -------------------------

    def _dequant(self, h, quant_scales):
        if quant_scales is None:
            return h
        f32 = jnp.float32
        dq = jnp.concatenate(
            [quant_scales.astype(f32), jnp.ones((1,), f32)])
        return h.astype(f32) * dq

    def _best(self, hist2w, slot_depth, slot_valid, slots_c, tree,
              feature_mask, gain_scale):
        """The resident ``best_for`` simple branch + its gain masks."""
        S = hist2w.shape[0]
        fmask_s = jnp.broadcast_to(feature_mask[None, :], (S, self.F))
        node_of = jnp.take(tree.leaf2node, slots_c)
        parent_out = jnp.take(tree.node_value, node_of)
        bs = find_best_splits(
            hist2w, self.num_bins_pf, self.nan_bin_pf, self.is_cat_pf,
            self.sp, feature_mask=fmask_s, mono_type=None,
            leaf_lo=None, leaf_hi=None, parent_output=parent_out,
            slot_depth=slot_depth, rand_bin=None,
            cat_sorted_mask=self.cat_sorted_mask,
            gain_scale=gain_scale, gain_penalty=None, adv_bounds=None)
        g = bs["gain"]
        if self.max_depth > 0:
            g = jnp.where(slot_depth < self.max_depth, g, NEG_INF)
        g = jnp.where(slot_valid, g, NEG_INF)
        bs["gain"] = g
        return bs

    def _init_tree(self):
        from ..boosting.tree_builder import TreeArrays
        MAXN, L, BW = self.MAXN, self.L, self.BW
        f32 = jnp.float32
        tree = TreeArrays(
            split_feature=jnp.full((MAXN + 1,), -1, jnp.int32),
            threshold_bin=jnp.zeros((MAXN + 1,), jnp.int32),
            default_left=jnp.zeros((MAXN + 1,), bool),
            is_cat=jnp.zeros((MAXN + 1,), bool),
            left_child=jnp.full((MAXN + 1,), -1, jnp.int32),
            right_child=jnp.full((MAXN + 1,), -1, jnp.int32),
            gain=jnp.zeros((MAXN + 1,), f32),
            node_value=jnp.zeros((MAXN + 1,), f32),
            node_count=jnp.zeros((MAXN + 1,), f32),
            node_hess=jnp.zeros((MAXN + 1,), f32),
            cat_bitset=jnp.zeros((MAXN + 1, BW), jnp.uint32),
            leaf2node=jnp.full((L + 1,), self.DUMMY_NODE, jnp.int32),
            leaf_values=jnp.zeros((L + 1,), f32),
            num_leaves=jnp.asarray(1, jnp.int32),
            num_nodes=jnp.asarray(1, jnp.int32),
        )
        return tree._replace(leaf2node=tree.leaf2node.at[0].set(0))

    def _zero_pend(self):
        """A round's split records (``relabel_rows``' [W] arguments)
        with no lane in use: the relabel is the identity."""
        W, BW = self.W, self.BW
        i32 = jnp.zeros((W,), jnp.int32)
        no = jnp.zeros((W,), bool)
        return (jnp.full((W,), self.DUMMY_LEAF, jnp.int32), no, i32, i32,
                no, no, i32, i32, jnp.zeros((W, BW), jnp.uint32))

    # -------------------------- jitted programs ------------------------

    def _chunk_impl(self, chunk_bins, row_leaf, gh, acc, offset, slots,
                    pend):
        """One chunk of one sweep: relabel the chunk's rows against the
        round's pending splits, then fold their histogram contribution
        into the carried accumulator. Root sweeps pass an all-inactive
        ``pend`` (relabel is the identity)."""
        C = chunk_bins.shape[0]
        rl_c = jax.lax.dynamic_slice(row_leaf, (offset,), (C,))
        gh_c = jax.lax.dynamic_slice(
            gh, (offset, jnp.int32(0)), (C, gh.shape[1]))
        from ..boosting.tree_builder import relabel_rows
        rl_new = relabel_rows(chunk_bins, rl_c, *pend)
        hist = build_histograms(
            chunk_bins, gh_c, rl_new, slots, num_bins=self.B,
            block_rows=self.block_rows, hist_dtype=self.hist_dtype,
            impl=self.impl, init=acc)
        row_leaf = jax.lax.dynamic_update_slice(row_leaf, rl_new,
                                                (offset,))
        return row_leaf, hist

    def _root_impl(self, acc0, tree, feature_mask, quant_scales,
                   gain_scale):
        """Record the root and seed the best-split caches from the
        root sweep's histogram (the resident root phase)."""
        L, W = self.L, self.W
        f32 = jnp.float32
        sp = self.sp
        hist0 = self._dequant(acc0, quant_scales)
        root_sums = hist0[0, 0, :, :].sum(axis=0)
        root_val = leaf_output(root_sums[0], root_sums[1],
                               sp.lambda_l1, sp.lambda_l2,
                               sp.max_delta_step)
        tree = tree._replace(
            node_value=tree.node_value.at[0].set(root_val),
            node_count=tree.node_count.at[0].set(root_sums[2]),
            node_hess=tree.node_hess.at[0].set(root_sums[1]),
            leaf_values=tree.leaf_values.at[0].set(root_val),
        )
        slot_valid0 = jnp.zeros((2 * W,), bool).at[0].set(True)
        bs0 = self._best(hist0, jnp.zeros((2 * W,), jnp.int32),
                         slot_valid0, jnp.zeros((2 * W,), jnp.int32),
                         tree, feature_mask, gain_scale)
        caches = dict(
            gain=jnp.full((L + 1,), NEG_INF, f32).at[0]
            .set(bs0["gain"][0]),
            feat=jnp.zeros((L + 1,), jnp.int32).at[0]
            .set(bs0["feature"][0]),
            thr=jnp.zeros((L + 1,), jnp.int32).at[0]
            .set(bs0["threshold"][0]),
            dl=jnp.zeros((L + 1,), bool).at[0]
            .set(bs0["default_left"][0]),
            cat=jnp.zeros((L + 1,), bool).at[0]
            .set(bs0["is_cat_split"][0]),
            left=jnp.zeros((L + 1, HIST_CH), f32).at[0]
            .set(bs0["left_sum"][0]),
            right=jnp.zeros((L + 1, HIST_CH), f32).at[0]
            .set(bs0["right_sum"][0]),
            bits=jnp.zeros((L + 1, self.BW), jnp.uint32).at[0]
            .set(bs0["cat_bitset"][0]),
            lout=jnp.zeros((L + 1,), f32).at[0]
            .set(bs0["left_out"][0]),
            rout=jnp.zeros((L + 1,), f32).at[0]
            .set(bs0["right_out"][0]),
        )
        more = (tree.num_leaves < L) & jnp.any(caches["gain"][:L]
                                               > NEG_INF)
        return tree, caches, more

    def _pop_impl(self, tree, caches, leaf_depth, valid_bins,
                  valid_row_leaf):
        """Pop the top-W cached splits, record them in the node
        arrays, build the round's pending-split tables, and relabel
        the (resident) validation matrices — everything of the
        resident round body that does NOT touch the training bins."""
        W = self.W
        DUMMY_LEAF, DUMMY_NODE = self.DUMMY_LEAF, self.DUMMY_NODE
        t = tree
        cur = t.num_leaves
        nodes = t.num_nodes
        gains, sel = jax.lax.top_k(caches["gain"][:self.L], W)
        sel = sel.astype(jnp.int32)
        budget = self.L - cur
        valid = jnp.isfinite(gains) & (jnp.arange(W) < budget)
        n_valid = valid.sum().astype(jnp.int32)
        pos = jnp.cumsum(valid.astype(jnp.int32)) - 1
        sel_s = jnp.where(valid, sel, DUMMY_LEAF)
        right_slot = jnp.where(valid, cur + pos, DUMMY_LEAF)
        ln = jnp.where(valid, nodes + 2 * pos, DUMMY_NODE)
        rn = jnp.where(valid, nodes + 2 * pos + 1, DUMMY_NODE)
        parent = jnp.where(valid, jnp.take(t.leaf2node, sel_s),
                           DUMMY_NODE)

        sfeat = jnp.take(caches["feat"], sel_s)
        sthr = jnp.take(caches["thr"], sel_s)
        sdl = jnp.take(caches["dl"], sel_s)
        scat = jnp.take(caches["cat"], sel_s)
        sgain = jnp.take(caches["gain"], sel_s)
        slsum = jnp.take(caches["left"], sel_s, axis=0)
        srsum = jnp.take(caches["right"], sel_s, axis=0)
        sbits = jnp.take(caches["bits"], sel_s, axis=0)
        lval = jnp.take(caches["lout"], sel_s)
        rval = jnp.take(caches["rout"], sel_s)

        t = t._replace(
            split_feature=t.split_feature.at[parent].set(sfeat),
            threshold_bin=t.threshold_bin.at[parent].set(sthr),
            default_left=t.default_left.at[parent].set(sdl),
            is_cat=t.is_cat.at[parent].set(scat),
            left_child=t.left_child.at[parent].set(ln),
            right_child=t.right_child.at[parent].set(rn),
            gain=t.gain.at[parent].set(sgain),
            node_value=t.node_value.at[ln].set(lval).at[rn].set(rval),
            node_count=t.node_count.at[ln].set(slsum[:, 2])
                                     .at[rn].set(srsum[:, 2]),
            node_hess=t.node_hess.at[ln].set(slsum[:, 1])
                                    .at[rn].set(srsum[:, 1]),
            cat_bitset=t.cat_bitset.at[parent].set(sbits),
            leaf2node=t.leaf2node.at[sel_s].set(ln)
                                 .at[right_slot].set(rn),
            leaf_values=t.leaf_values.at[sel_s].set(lval)
                                     .at[right_slot].set(rval),
            num_leaves=cur + n_valid,
            num_nodes=nodes + 2 * n_valid,
        )
        new_depth = jnp.take(leaf_depth, sel_s) + 1
        leaf_depth = leaf_depth.at[sel_s].set(new_depth) \
                               .at[right_slot].set(new_depth)

        # the round's split records, one lane a split: every chunk's
        # rows (and the valid sets') select from them by their leaf
        pend = (sel_s, valid, sfeat, sthr, sdl, scat, right_slot,
                jnp.take(self.nan_bin_pf, sfeat), sbits)

        from ..boosting.tree_builder import relabel_rows
        valid_row_leaf = tuple(
            relabel_rows(vb, vrl, *pend)
            for vb, vrl in zip(valid_bins, valid_row_leaf))

        slots2w = jnp.concatenate([jnp.where(valid, sel_s, -2),
                                   jnp.where(valid, right_slot, -2)])
        slots2w_c = jnp.where(slots2w >= 0, slots2w, DUMMY_LEAF)
        depth2w = jnp.take(leaf_depth,
                           jnp.concatenate([sel_s, right_slot]))
        valid2w = jnp.concatenate([valid, valid])
        # subtraction mode sweeps only the smaller child of each split:
        # the cached split sums carry the exact per-child count channel
        # (integers in f32; the quantized count scale is 1), so the
        # choice is made before any chunk is streamed
        small_is_left = slsum[:, 2] <= srsum[:, 2]
        small_slots = jnp.where(
            valid, jnp.where(small_is_left, sel_s, right_slot), -2)
        return (t, leaf_depth, pend, slots2w, slots2w_c, depth2w,
                valid2w, small_slots, small_is_left, valid_row_leaf)

    def _sub_impl(self, acc_small, hist_cache, slots2w, small_is_left):
        """Assemble the round's full [2W, F, B, 3] RAW lattice from the
        W swept smaller children + the per-leaf parent cache (big =
        parent - small), and roll the cache forward to the children.
        Mirrors the resident builder's fused_children/hist_sub scatter:
        invalid lanes park their writes on the DUMMY_LEAF row."""
        W = self.W
        sel_s = slots2w[:W]
        right_slot = slots2w[W:]
        valid = sel_s >= 0
        parent_raw = jnp.take(hist_cache, jnp.clip(sel_s, 0, self.L),
                              axis=0)
        hbig = parent_raw - acc_small
        sil = small_is_left.reshape((W,) + (1,) * (acc_small.ndim - 1))
        left_raw = jnp.where(sil, acc_small, hbig)
        right_raw = jnp.where(sil, hbig, acc_small)
        hist_cache = hist_cache \
            .at[jnp.where(valid, sel_s, self.DUMMY_LEAF)].set(left_raw) \
            .at[jnp.where(valid, right_slot, self.DUMMY_LEAF)] \
            .set(right_raw)
        return jnp.concatenate([left_raw, right_raw]), hist_cache

    def _finish_impl(self, acc, tree, caches, slots2w_c, depth2w,
                     valid2w, feature_mask, quant_scales, gain_scale):
        """Children best-splits from the sweep's accumulated histogram,
        scattered back into the per-leaf caches."""
        hist2w = self._dequant(acc, quant_scales)
        bs = self._best(hist2w, depth2w, valid2w, slots2w_c, tree,
                        feature_mask, gain_scale)
        caches = dict(
            gain=caches["gain"].at[slots2w_c].set(bs["gain"])
            .at[self.DUMMY_LEAF].set(NEG_INF),
            feat=caches["feat"].at[slots2w_c].set(bs["feature"]),
            thr=caches["thr"].at[slots2w_c].set(bs["threshold"]),
            dl=caches["dl"].at[slots2w_c].set(bs["default_left"]),
            cat=caches["cat"].at[slots2w_c].set(bs["is_cat_split"]),
            left=caches["left"].at[slots2w_c].set(bs["left_sum"]),
            right=caches["right"].at[slots2w_c].set(bs["right_sum"]),
            bits=caches["bits"].at[slots2w_c].set(bs["cat_bitset"]),
            lout=caches["lout"].at[slots2w_c].set(bs["left_out"]),
            rout=caches["rout"].at[slots2w_c].set(bs["right_out"]),
        )
        more = (tree.num_leaves < self.L) & jnp.any(caches["gain"][:self.L]
                                                    > NEG_INF)
        return caches, more

    # -------------------------- eager driver --------------------------

    def _sweep(self, pref, row_leaf, gh, slots, pend, acc_dt):
        S = int(slots.shape[0])
        acc = jnp.zeros((S, self.F, self.B, HIST_CH), acc_dt)
        for off, dev_bins in pref.chunks():
            row_leaf, acc = self._chunk_j(dev_bins, row_leaf, gh, acc,
                                          off, slots, pend)
        return row_leaf, acc

    def build(self, pref, gh, row_leaf0, feature_mask, *,
              quant_scales: Optional[jax.Array] = None,
              gain_scale: Optional[jax.Array] = None,
              valid_bins: Tuple[jax.Array, ...] = (),
              valid_row_leaf0: Tuple[jax.Array, ...] = ()):
        """Grow one tree from the prefetcher's chunk stream. Same
        return contract as the resident builder:
        ``(TreeArrays, row_leaf, valid_row_leafs)`` — ``row_leaf`` is
        sized to the prefetcher's padded row count (pad rows carry
        -1)."""
        Rp = pref.padded_rows
        row_leaf = jnp.asarray(row_leaf0, jnp.int32)
        gh = jnp.asarray(gh)
        R0 = int(row_leaf.shape[0])
        if R0 > Rp:
            raise ValueError(
                f"row_leaf0 has {R0} rows but the chunk stream only "
                f"covers {Rp}")
        if R0 < Rp:
            row_leaf = jnp.concatenate(
                [row_leaf, jnp.full((Rp - R0,), -1, jnp.int32)])
            gh = jnp.concatenate(
                [gh, jnp.zeros((Rp - R0, gh.shape[1]), gh.dtype)])
        acc_dt = jnp.int32 if gh.dtype == jnp.int8 else jnp.float32
        feature_mask = jnp.asarray(feature_mask, bool)

        tree = self._init_tree()
        leaf_depth = jnp.zeros((self.L + 1,), jnp.int32)
        vrl = tuple(jnp.asarray(v, jnp.int32) for v in valid_row_leaf0)
        vbins = tuple(valid_bins)

        root_slots = jnp.full((2 * self.W,), -2, jnp.int32).at[0].set(0)
        row_leaf, acc0 = self._sweep(pref, row_leaf, gh, root_slots,
                                     self._zero_pend(), acc_dt)
        tree, caches, more = self._root_j(acc0, tree, feature_mask,
                                          quant_scales, gain_scale)
        hist_cache = None
        if self.hist_sub:
            hist_cache = jnp.zeros(
                (self.L + 1,) + acc0.shape[1:], acc_dt).at[0].set(acc0[0])
        r = 0
        while r < self.rounds_bound and bool(more):
            (tree, leaf_depth, pend, slots2w, slots2w_c, depth2w,
             valid2w, small_slots, small_is_left,
             vrl) = self._pop_j(tree, caches, leaf_depth, vbins, vrl)
            if self.hist_sub:
                # stream only the W smaller siblings; the big ones come
                # from the parent cache by subtraction — halves the
                # sweep's histogram lattice and skips the larger
                # child's bin traffic entirely
                row_leaf, acc_s = self._sweep(pref, row_leaf, gh,
                                              small_slots, pend, acc_dt)
                acc, hist_cache = self._sub_j(acc_s, hist_cache,
                                              slots2w, small_is_left)
            else:
                row_leaf, acc = self._sweep(pref, row_leaf, gh, slots2w,
                                            pend, acc_dt)
            caches, more = self._finish_j(acc, tree, caches, slots2w_c,
                                          depth2w, valid2w,
                                          feature_mask, quant_scales,
                                          gain_scale)
            r += 1
        return tree, row_leaf, vrl
