"""On-device leaf-wise tree growth.

TPU-native analog of the reference tree learner
(``src/treelearner/serial_tree_learner.cpp:179`` ``Train`` — the per-leaf
loop of §3.4 in SURVEY.md, with
``cuda/cuda_single_gpu_tree_learner.cpp:170-345`` as the
whole-loop-on-device architectural template).

Design (TPU-first; not a translation):
- The reference grows best-first one leaf per step with pointer-y data
  structures. Under XLA everything must be fixed-shape, so the tree lives in
  SoA node arrays sized ``2*num_leaves - 1`` (+1 dummy scatter slot) and the
  loop is a ``lax.while_loop`` whose every round:
    1. pops the top-``leaf_batch`` cached splits (``lax.top_k`` over the
       per-leaf best-gain cache — the argmax over ``best_split_per_leaf_``
       of serial_tree_learner.cpp:226, batched),
    2. applies them with one vectorized pass over ``row_leaf`` (the
       DataPartition::Split analog — no index reordering, just a dense
       leaf-id relabel; a row finds its leaf's split among the round's
       W records by W compares, ``select_by_slot``, not by a gather
       from a per-leaf table),
    3. builds the SMALLER child's histogram over a compacted,
       dynamically-bounded row stream and derives the sibling by
       parent-minus-child subtraction from a per-leaf histogram cache
       (``hist_sub=True``; serial_tree_learner.cpp:567-592 ``Subtract``
       + dense_bin.hpp:105 iterating ``data_indices`` only). The matmul
       N-dim padding argument only covers the LEAF axis; the row stream
       is the real cost — without subtraction every round re-streams all
       R rows (~13x/tree at 255 leaves, ~254x in leaf_batch=1 modes).
       With it, each round streams only the smaller children's rows.
       The builder makes the stream's INDEX and nothing else
       (``compact_small``: membership, ``n_small``, and ``c_idx`` as
       one sort of the row numbers, ``stream_index``);
       ``bins``, ``gh`` and ``row_leaf`` go to
       ops/histogram.py uncompacted with ``row_gather=c_idx,
       num_rows=n_small``, and the wrapper gathers (two gathers a trip:
       the bin rows, and one table row holding ``gh`` and the leaf),
       casts and lays them out inside one loop whose trip count is
       ``ceil(n_small / chunk)`` (a row block a trip for matmul and
       scatter; for pallas a chunk of ~R/32 rows a trip into the
       kernel's operand buffers,
       then ONE kernel call bounded by the same ``n_small``). So a
       round over a 1%-sized leaf pays about a chunk of a full pass,
       on every path; RoundLog.stream_rows counts what it touched.
       The cache holds RAW histograms ([L+1, F, B, 3] f32, int32 when
       quantized — subtraction stays exact), ~5 MB at Higgs shape;
       callers disable hist_sub when the cache would not fit
       (histogram_pool_size analog),
    4. finds the children's best splits (ops/split.py) and scatters them
       into the per-leaf caches.
  ``leaf_batch=1`` reproduces the reference's exact best-first order;
  larger batches trade exact ordering for MXU width (trees differ slightly
  but gains are leaf-local, so selection differences are second-order).
- Bagging/GOSS enter as zeroed/scaled ``gh`` rows, never as shape changes.
- Validation sets ride along: their ``row_leaf`` is co-partitioned by the
  same split applications, so per-iteration validation scores are a gather —
  the analog of ScoreUpdater over valid data.
- Multi-chip: rows are sharded; the only cross-chip traffic is the
  histogram psum inside ops/histogram.py (ReduceScatter analog) — split
  selection then runs replicated and identically on every shard, which
  replaces SyncUpGlobalBestSplit (parallel_tree_learner.h:209) since a
  deterministic replicated argmax needs no sync.

Constraint machinery (all vectorized, no data-dependent shapes):
- Monotone constraints (basic mode, monotone_constraints.hpp:465-516):
  per-leaf output bounds [leaf_lo, leaf_hi]; on a numerical split of a
  constrained feature, mid = (left_out + right_out)/2 tightens the
  children's bounds. The split finder clamps candidate outputs and rejects
  direction violations.
- Interaction constraints (col_sampler.hpp:125-180 GetByNode): per-leaf
  used-feature sets [L+1, F] bool; a feature is allowed iff some constraint
  group contains the leaf's whole branch path — two boolean matmuls
  against the static group matrix.
- Per-node feature sampling (feature_fraction_bynode) and extra-trees
  random thresholds draw from a replicated PRNG key folded with the round
  counter, so every chip samples identically.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


from .. import phases as PHS
from .. import profiler
from ..ops.histogram import (build_histograms, effective_impl, HIST_CH,
                             kernel_plan, merge_histograms,
                             stream_chunk_rows, stream_trips, _pvary)
# referenced as a module attribute (PH.build_root_histograms_classes) so
# tests can monkeypatch interpret-mode wrappers in
from ..ops import pallas_histogram as PH
from ..ops.predict import row_feature_gather
from ..ops.split import (SplitParams, find_best_splits, leaf_gain,
                         leaf_output)

__all__ = ["TreeArrays", "RoundLog", "StepShape", "build_tree",
           "build_impl", "max_rounds_for", "step_shape"]

NEG_INF = -jnp.inf
F32_MAX = 3.4e38  # monotone bounds start effectively unconstrained


class TreeArrays(NamedTuple):
    """SoA tree (tree.h:135 analog). Arrays sized max_nodes = 2L-1 (+1 dummy
    at index max_nodes, trimmed on host)."""
    split_feature: jax.Array   # [N] int32, -1 => leaf
    threshold_bin: jax.Array   # [N] int32
    default_left: jax.Array    # [N] bool
    is_cat: jax.Array          # [N] bool
    left_child: jax.Array      # [N] int32
    right_child: jax.Array     # [N] int32
    gain: jax.Array            # [N] f32 split gain of internal nodes
    node_value: jax.Array      # [N] f32 leaf output (unshrunk)
    node_count: jax.Array      # [N] f32
    node_hess: jax.Array       # [N] f32
    cat_bitset: jax.Array      # [N, ceil(B/32)] uint32 LEFT subset (cat)
    leaf2node: jax.Array       # [L+1] int32
    leaf_values: jax.Array     # [L+1] f32 output per leaf slot (unshrunk)
    num_leaves: jax.Array      # scalar int32
    num_nodes: jax.Array       # scalar int32


class RoundLog(NamedTuple):
    """What each round of the grow loop did, returned beside the tree
    and fetched with it. Arrays sized ``max_rounds_for(L, W)``; rounds
    the loop never ran stay 0."""
    rows: jax.Array     # [rounds] int32: live rows the round's histogram
    #                     stream was bounded by (n_small under
    #                     compaction; per shard under a row-sharded plan)
    leaves: jax.Array   # [rounds] int32: splits the round applied
    stream_rows: jax.Array  # [rounds] int32: stream positions the round
    #                     touched to feed the histogram: trips x chunk
    #                     of the compacted stream's loop
    #                     (ops.histogram.stream_chunk_rows), R where the
    #                     stream is unbounded (native, hist_sub off);
    #                     per shard like ``rows``


def max_rounds_for(num_leaves: int, leaf_batch: int) -> int:
    cur, r = 1, 0
    while cur < num_leaves:
        cur += min(leaf_batch, cur, num_leaves - cur)
        r += 1
    return r


class StepShape(NamedTuple):
    """What one build is sized by, on one device: the values of
    ``phases.STEP_SHAPE``, in that order (:func:`step_shape`)."""
    rows: int
    stored_columns: int
    stored_bins: int
    search_positions: int
    slots: int
    stream_chunk_rows: int
    stream_compacted: int
    kernel_row_block: int
    kernel_root_row_block: int
    kernel_feature_chunk: int
    kernel_chunks: int
    kernel_padded_bins: int
    kernel_lanes: int
    rounds_bound: int

    def fields(self) -> dict:
        """``{phases.STEP_SHAPE name: int}``: the span's fields."""
        return dict(zip(PHS.STEP_SHAPE, (int(v) for v in self)))


def feature_block(num_features: int, n_shards: int) -> int:
    """Features a chip searches where the merge is a reduce-scatter along
    the feature axis: its block of F padded to a multiple of the chips."""
    return -(-num_features // n_shards)


def step_shape(*, rows: int, stored_columns: int, features: int,
               num_bins: int, bundle_bins: int, num_leaves: int,
               leaf_batch: int, hist_impl: str, gh_dtype, hist_dtype: str,
               block_rows: int, hist_sub: bool,
               parallel_mode: Optional[str] = None,
               hist_merge: str = "allreduce",
               n_shards: int = 1) -> StepShape:
    """The sizes :func:`_build_tree_impl` builds with, from its static
    arguments alone: host integers, no trace, no device. The builder
    calls this for its own W, chunk, compaction and loop bound, and the
    driver calls it with what it hands the builder to put the same
    numbers on ``gbdt.step_ready`` (``phases.STEP_SHAPE``).

    ``rows`` and ``stored_columns`` are the bin matrix's a device
    histograms (a shard's rows; bundles under EFB; a chip's column slice
    under ``parallel_mode='feature'``), ``features`` what its search runs
    over before any reduce-scatter block (a chip's slice under
    ``feature``), ``parallel_mode`` None off a mesh."""
    nb_in = bundle_bins or num_bins
    impl = effective_impl(hist_impl, nb_in)
    W = max(1, min(leaf_batch, num_leaves - 1))
    plan_args = (impl, rows, stored_columns, nb_in)
    dtypes = (gh_dtype, hist_dtype, block_rows)
    blk, fc, n_fb, bp, lanes = kernel_plan(*plan_args, W, *dtypes)
    root_blk = kernel_plan(*plan_args, 2 * W, *dtypes)[0]
    searched = features
    if (parallel_mode == "data" and hist_merge == "reduce_scatter"
            and n_shards > 1 and not bundle_bins):
        # a bundled matrix scatters bundle columns and searches the
        # whole feature lattice, zeros outside the bundles it owns
        searched = feature_block(features, n_shards)
    return StepShape(
        rows=rows, stored_columns=stored_columns, stored_bins=nb_in,
        search_positions=searched * num_bins, slots=2 * W,
        stream_chunk_rows=stream_chunk_rows(*plan_args, W, *dtypes),
        # only the native C kernel skips compaction: its partition op
        # already keeps exact per-leaf row lists
        stream_compacted=int(bool(hist_sub) and impl != "native"),
        kernel_row_block=blk, kernel_root_row_block=root_blk,
        kernel_feature_chunk=fc, kernel_chunks=n_fb, kernel_padded_bins=bp,
        kernel_lanes=lanes, rounds_bound=max_rounds_for(num_leaves, W))


def _round_int(x):
    return jnp.floor(x + 0.5)


def select_by_slot(row_leaf, slots, lane_ok, records=()):
    """What each row reads of the round's split records: ``(hit [R]
    bool, [the record of the row's lane, 0 where none hits])``.

    A round splits at most W leaves, so all a row may need of its
    leaf's pending split is W records (``records``: [W] arrays, lane
    ``w`` describing leaf slot ``slots[w]``). A row finds its lane by
    comparing its leaf with the W slots: a compare a lane and a select
    a record, elementwise over R, which the vector unit runs as one
    fused pass. A table of ``L+1`` entries read by ``jnp.take(table,
    row_leaf)`` costs 9.7 ns a gathered element on a v5e, 1.9 s a table
    a Higgs tree, against ~1 ms a round for the whole pass (PERF.md
    section 6, PR 29): the rule ops/predict.py ``row_feature_gather``
    follows. ``lane_ok`` masks the lanes a round leaves unused: their
    slots all hold the dummy leaf, so the slot alone does not tell them
    apart. Slots are distinct where ``lane_ok``, so at most one lane
    hits a row and the selected value is exactly the table's. Rows with
    ``row_leaf < 0`` hit nothing."""
    hit = jnp.zeros(row_leaf.shape, bool)
    outs = [jnp.zeros(row_leaf.shape, r.dtype) for r in records]
    for w in range(slots.shape[0]):
        h = lane_ok[w] & (row_leaf == slots[w])
        hit = hit | h
        outs = [jnp.where(h, r[w], o) for r, o in zip(records, outs)]
    return hit, outs


def stream_index(m):
    """The compacted stream's index of a row mask: ``(c_idx [R] int32,
    n [] int32)`` with the ``n = m.sum()`` rows of ``m`` first, in row
    order, then the dead rows in no promised order.

    One ``lax.sort`` of one ``s32[R]``: a live row's key is its own
    number, a dead row's its number plus R, so the sorted keys ARE the
    index (keys are distinct, so an unstable sort keeps the live rows
    in row order) and nothing rides along; the dead tail gives its R
    back so that every entry names a row. The positions are also a
    cumsum, and ``zeros(R).at[where(m, pos, R)].set(arange(R))`` says
    so; but XLA:TPU expands that scatter into a sort of (position, row
    number) pairs and then a scatter that copies the sorted payload's
    prefix onto itself at 4.9 ns a row: 0.97 + 0.41 s of a 3.36 s Higgs
    tree, with another 0.05 s for the cumsum that fed them, where this
    sort is 0.16 s (PERF.md section 6, PR 33). Rows last, so under
    ``vmap`` (``class_batch``) the sort is batched over the class axis;
    under a row mesh each shard sorts its own rows and no collective
    enters."""
    R = m.shape[-1]
    assert R < 2 ** 30, R     # R + row fits int32
    iota = jnp.arange(R, dtype=jnp.int32)
    key = jax.lax.sort(jnp.where(m, iota, iota + R), is_stable=False)
    return jnp.where(key < R, key, key - R), m.astype(jnp.int32).sum()


def slot_counts(row_leaf, slots):
    """[S] int32 rows in each of ``slots``: one compare-and-sum over R
    (rows on the minor axis), in place of a ``segment_sum`` of R ones
    into every leaf's segment, of which S entries were read."""
    return jnp.sum(slots[:, None] == row_leaf[None, :], axis=1,
                   dtype=jnp.int32)


def relabel_rows(bmat, row_leaf, slots, lane_ok, feat, thr, default_left,
                 is_cat, right, nan_bin, bits, bin_records=(), bin_of=None):
    """``row_leaf`` after the round's splits (DataPartition::Split as a
    dense relabel): a row of leaf ``slots[w]`` (where ``lane_ok[w]``)
    whose bin of feature ``feat[w]`` goes right moves to ``right[w]``.

    Every argument from ``slots`` to ``nan_bin`` is a [W] record of the
    round's splits (``nan_bin`` = the split feature's NaN bin, -1 for
    none); ``bits`` [W, BW] is the categorical LEFT subset. A row
    selects its lane's values (``select_by_slot``) and reads its bin as
    ``bmat[r, feat[r]]`` (``row_feature_gather``: a one-hot reduce), or
    as ``bin_of(bmat, active, feat, *selected bin_records)`` where the
    matrix is not one column a feature: EFB bundles and sharded feature
    storage pass their own, with the [W] records they decode by."""
    BW = bits.shape[-1]
    active, (f_r, thr_r, dl_r, cat_r, right_r, nb_r, *rest) = \
        select_by_slot(row_leaf, slots, lane_ok,
                       [feat, thr, default_left, is_cat, right, nan_bin,
                        *(bits[..., b] for b in range(BW)), *bin_records])
    binv = (row_feature_gather(bmat, f_r) if bin_of is None
            else bin_of(bmat, active, f_r, *rest[BW:]))
    isnan = (binv == nb_r) & (nb_r >= 0)
    # categorical: bitset membership (CategoricalDecision, tree.h) in
    # the word of the row's bin, of the row's lane
    word = binv >> 5
    wval = jnp.zeros(row_leaf.shape, jnp.uint32)
    for b in range(BW):
        wval = jnp.where(word == b, rest[b], wval)
    in_set = ((wval >> (binv & 31).astype(jnp.uint32))
              & jnp.uint32(1)) == 1
    go_left = jnp.where(cat_r, in_set, binv <= thr_r)
    go_left = jnp.where(isnan & ~cat_r, dl_r, go_left)
    return jnp.where(active & ~go_left, right_r, row_leaf)


def build_impl(hist_impl: str, lattice_bins: int,
               class_batched: bool = False) -> str:
    """The histogram formulation a build runs with
    (``ops.histogram.effective_impl``'s rule over the lattice's width);
    the native FFI kernels carry no vmap batching rule, so a
    class-batched build remaps native -> scatter (bit-identical;
    tests/test_histogram.py native parity)."""
    impl = effective_impl(hist_impl, lattice_bins)
    return "scatter" if class_batched and impl == "native" else impl


def build_tree(*args, hist_impl: str = "auto", traced: bool = False,
               class_batched: bool = False, **kwargs):
    """Unjitted entry: resolves ``hist_impl='auto'`` (:func:`build_impl`)
    and dispatches to the jitted core. Same contract as :func:`_build_tree_impl` below.

    ``traced=True`` runs the plain (unjitted) core for callers that are
    ALREADY inside a trace — the fused boosting step of gbdt.py — so the
    build inlines into the enclosing program instead of nesting a pjit
    call boundary.

    ``class_batched=True`` grows ALL K per-class trees of one boosting
    iteration in one program (ISSUE 8): ``gh`` arrives [K, R, 3] (plus
    per-class ``rng_key``/``quant_scales`` when present) and the core is
    vmapped over the class axis — see
    :func:`_build_tree_class_batched` (and :func:`build_impl` for the
    kernel it then runs)."""
    impl = build_impl(hist_impl, kwargs.get("bundle_bins")
                      or kwargs["num_bins"], class_batched)
    if class_batched:
        if traced:
            return _build_tree_class_batched(*args, hist_impl=impl,
                                             **kwargs)
        return _build_tree_cb_jit(*args, hist_impl=impl, **kwargs)
    if traced:
        return _build_tree_impl(*args, hist_impl=impl, **kwargs)
    return _build_tree_jit(*args, hist_impl=impl, **kwargs)


def _build_tree_impl(bins: jax.Array, gh: jax.Array, row_leaf0: jax.Array,
               num_bins_pf: jax.Array, nan_bin_pf: jax.Array,
               is_cat_pf: jax.Array, feature_mask: jax.Array,
               *, num_leaves: int, leaf_batch: int, max_depth: int,
               num_bins: int, split_params: SplitParams,
               axis_name: Optional[str] = None,
               hist_dtype: str = "bfloat16", hist_impl: str = "auto",
               block_rows: int = 0,
               valid_bins: Tuple[jax.Array, ...] = (),
               valid_row_leaf0: Tuple[jax.Array, ...] = (),
               mono_type_pf: Optional[jax.Array] = None,
               interaction_groups: Optional[jax.Array] = None,
               rng_key: Optional[jax.Array] = None,
               feature_fraction_bynode: float = 1.0,
               cat_sorted_mask: Optional[jax.Array] = None,
               parallel_mode: str = "data", top_k: int = 20,
               local_bins: Optional[jax.Array] = None,
               local_meta: Optional[Tuple] = None,
               feat_offset: Optional[jax.Array] = None,
               gain_scale: Optional[jax.Array] = None,
               cegb: Optional[Tuple] = None,
               bundle_meta: Optional[Tuple] = None,
               bundle_bins: int = 0,
               quant_scales: Optional[jax.Array] = None,
               mono_method: str = "basic",
               forced: Optional[Tuple] = None,
               hist_sub: bool = True,
               bins_cm: Optional[jax.Array] = None,
               feature_sharded: bool = False,
               hist_merge: str = "allreduce",
               n_shards: int = 1,
               root_hist: Optional[jax.Array] = None):
    """Grow one tree. Returns (TreeArrays, row_leaf, valid_row_leafs,
    RoundLog) — and the CEGB state as a fifth element when ``cegb`` is
    given.

    ``parallel_mode`` (with ``axis_name`` set) selects the distributed
    strategy, mirroring tree_learner=data/feature/voting
    (tree_learner.cpp:15 factory):
    - "data": rows sharded. ``hist_merge`` picks the merge collective:
      * "allreduce" (psum): every chip receives the FULL merged
        histogram and split selection runs replicated (no winner sync
        needed — the original formulation, ~2x reduce-scatter's wire
        bytes and n-redundant split work);
      * "reduce_scatter" (``lax.psum_scatter`` along the feature axis,
        ``n_shards`` static): each chip receives only its F_pad/n
        feature-slot block — the reference's TRUE
        ``Network::ReduceScatter`` per-worker feature-block merge
        (data_parallel_tree_learner.cpp:284). Split finding runs on the
        local block only and winners merge SplitInfo-sized via
        ``_sync_best`` (SyncUpGlobalBestSplit). The per-leaf histogram
        cache is slot-sharded the same way, cutting its HBM footprint
        by n. EFB composes by unbundling the LOCAL histogram to feature
        space first (unbundling is linear, so it commutes with the
        scatter-sum); the cache then lives in scattered feature space.
    - "feature": rows replicated, split WORK feature-sharded
      (feature_parallel_tree_learner.cpp:38-77): each chip histograms
      only its ``local_bins`` [R, F_loc] slice (``local_meta`` = that
      slice's (num_bins_pf, nan_bin_pf, is_cat_pf, feature_mask,
      mono_type_pf-or-None); ``feat_offset`` = global id of local
      feature 0), then the winner is merged by gain-argmax across chips
      — SyncUpGlobalBestSplit (parallel_tree_learner.h:209) as a
      pmax/pmin pair + masked psum payload broadcast.
    - "voting": rows sharded, PV-Tree
      (voting_parallel_tree_learner.cpp:16-120): local histograms only;
      each chip votes its per-leaf top-``top_k`` features by local gain;
      votes are psum-merged; the global top-2k elected features' columns
      are gathered and psum'd (communication O(top_k·B), not O(F·B));
      the split is chosen from those global sub-histograms.
    """
    # trace-time availability check of the native C kernel (a missing
    # toolchain degrades it to scatter); the call also compiles and
    # REGISTERS the FFI targets
    hist_impl = effective_impl(hist_impl, bundle_bins or num_bins)
    mode = parallel_mode if axis_name is not None else "data"
    _loc = mode == "feature" and local_bins is not None \
        and local_meta is not None
    shape = step_shape(
        rows=bins.shape[0],
        stored_columns=(local_bins if _loc else bins).shape[1],
        features=(local_meta[0] if _loc else num_bins_pf).shape[0],
        num_bins=num_bins, bundle_bins=bundle_bins if bundle_meta is not None
        else 0, num_leaves=num_leaves, leaf_batch=leaf_batch,
        hist_impl=hist_impl, gh_dtype=gh.dtype, hist_dtype=hist_dtype,
        block_rows=block_rows, hist_sub=hist_sub,
        parallel_mode=mode if axis_name is not None else None,
        hist_merge=hist_merge, n_shards=n_shards)
    # Row compaction redirects the row streams through a gathered index
    # order, and everything downstream of the index is bounded by the
    # live rows: the matmul one-hot (R*F*B bf16) and the CPU scatter
    # gather a block a trip, the Pallas path lays its operands out a
    # chunk a trip and its kernel's dynamic row bound (num_rows scalar
    # prefetch) skips whole row blocks past the compacted live prefix,
    # so gathers, relayout, VMEM one-hot and MXU dot all shrink with
    # the small child's row fraction (the dense_bin.hpp:105
    # data_indices saving). Only the native C kernel skips compaction: its
    # partition op already maintains exact per-leaf row lists, so a
    # sort + gather pass over R would cost more than it saves.
    hist_compact = bool(shape.stream_compacted)
    # native CPU backend: maintain the DataPartition analog — `perm`
    # holds row indices grouped by leaf (leaf_begin/leaf_cnt segments,
    # data_partition.hpp:116 Split semantics) as loop-carried state, so
    # the partition op touches only the split leaves' rows and the
    # histogram op walks exactly the requested children's rows (no scan
    # over R, no per-row branch). Bundled matrices decode bins in
    # feature space and keep the XLA formulation.
    # sharded feature storage: no device holds the full matrix, so the
    # native CPU partition/relabel (which walk every column) cannot run
    use_native_part = (hist_impl == "native" and bundle_meta is None
                       and not feature_sharded)
    R = bins.shape[0]
    F = num_bins_pf.shape[0]   # per-FEATURE count (bins may be bundled)
    L = num_leaves
    W = shape.slots // 2
    MAXN = 2 * L - 1
    B = num_bins
    DUMMY_LEAF = L          # scatter sink for masked lanes
    DUMMY_NODE = MAXN
    R_i32 = jnp.asarray(R, jnp.int32)
    BW = (B + 31) // 32     # cat bitset words

    f32 = jnp.float32

    # EFB (efb.py): bins is a [R, G] BUNDLED matrix; histograms are
    # built in bundle space (lattice G x bundle_bins) then gathered back
    # to per-feature space, with the most-frequent bin reconstructed via
    # FixHistogram accounting (dataset.cpp:1488 analog).
    use_bundle = bundle_meta is not None
    # how the relabel reads a row's bin where the matrix is not one
    # column a feature (relabel_rows ``bin_of``, ``bin_records``)
    feature_bin_of = None

    def bin_records(sfeat):
        return []
    if use_bundle:
        b_gof, b_off, b_mfb = bundle_meta
        G = bins.shape[1]

        def unbundle(hg):
            # dtype-generic (f32 AND raw int32 quantized): every op here
            # is LINEAR in the histogram, so unbundling commutes with
            # cross-shard summation — the reduce-scatter merge unbundles
            # the LOCAL histogram first and scatters in feature space
            S = hg.shape[0]
            zero = jnp.zeros((), hg.dtype)
            hflat = hg.reshape(S, G * bundle_bins, HIST_CH)
            idx = (b_gof[:, None] * bundle_bins + b_off[:, None]
                   + jnp.arange(B, dtype=jnp.int32)[None, :])    # [F, B]
            bvalid = (jnp.arange(B, dtype=jnp.int32)[None, :]
                      < num_bins_pf[:, None])
            idx = jnp.clip(idx, 0, G * bundle_bins - 1)
            hf = jnp.take(hflat, idx.reshape(-1), axis=1).reshape(
                S, F, B, HIST_CH)
            hf = jnp.where(bvalid[None, :, :, None], hf, zero)
            totals = hg[:, 0, :, :].sum(axis=1)                  # [S, 3]
            mfb_oh = (jnp.arange(B, dtype=jnp.int32)[None, :]
                      == b_mfb[:, None])                         # [F, B]
            sum_all = hf.sum(axis=2)
            at_mfb = jnp.where(mfb_oh[None, :, :, None], hf,
                               zero).sum(axis=2)
            mfb_val = totals[:, None, :] - (sum_all - at_mfb)
            return jnp.where((mfb_oh & bvalid)[None, :, :, None],
                             mfb_val[:, :, None, :], hf)

        def bin_records(sfeat):
            # [W] each: where a split feature's bins lie in the bundled
            # matrix; a row selects its lane's (relabel_rows)
            return [jnp.take(b_gof, sfeat), jnp.take(b_off, sfeat),
                    jnp.take(num_bins_pf, sfeat), jnp.take(b_mfb, sfeat)]

        def feature_bin_of(bmat, active, feat, gof, off, nbf, mfb):
            from ..efb import decode_feature_bins
            return decode_feature_bins(row_feature_gather(bmat, gof),
                                       off, nbf, mfb, xp=jnp)
    elif feature_sharded:
        def feature_bin_of(bmat, active, feat):
            # each device holds only its [R, F_loc] column shard; the
            # split feature of a row's leaf is owned by exactly ONE
            # shard, so a masked local gather + psum over the feature
            # axis reconstructs the bin value everywhere (one [R] int32
            # all-reduce per relabel — the sharded analog of the
            # reference's full-copy re-partition,
            # feature_parallel_tree_learner.cpp:77)
            F_m = bmat.shape[1]
            fl = feat - feat_offset
            owned = active & (fl >= 0) & (fl < F_m)
            bl = row_feature_gather(bmat, jnp.clip(fl, 0, F_m - 1))
            return jax.lax.psum(jnp.where(owned, bl, 0), axis_name)
    sp = split_params
    use_mono = mono_type_pf is not None
    # monotone_constraints_method=intermediate
    # (IntermediateLeafConstraints, monotone_constraints.hpp:516): on a
    # monotone split the children's output bounds tighten to the SIBLING's
    # output (not the midpoint), and the new outputs propagate to every
    # leaf whose region is adjacent along a monotone feature. The
    # reference finds those leaves with recursive Go{Up,Down} tree walks
    # approximated by the up-path's feature/threshold lists; here each
    # leaf carries its bin-space bounding box [box_lo, box_hi] and
    # adjacency is computed exactly and vectorized: two leaf boxes
    # interact along monotone dim q iff they are separated along q and
    # overlap in every other dim (disjoint boxes are separated along
    # exactly one dim in that case). Exact geometry constrains strictly
    # less than the reference's path approximation — same soundness,
    # more admissible splits. Stale best-split caches (the reference
    # recomputes them for `leaves_to_update_`) are instead handled by
    # clamping cached outputs into the leaf's CURRENT bounds at apply
    # time; cross-leaf propagation is only sound when splits apply one
    # at a time, so callers force leaf_batch=1 in this mode.
    use_mono_inter = use_mono and mono_method == "intermediate"
    # monotone_constraints_method=advanced ("precise" mode,
    # AdvancedLeafConstraints, monotone_constraints.hpp:858): constraints
    # become per-(feature, threshold) — a candidate split's LEFT child
    # only absorbs neighbors adjacent to the left SUB-box. The reference
    # maintains lazily-recomputed piecewise threshold segments per
    # feature; here the bounds are recomputed FRESH each round from the
    # live leaves' current outputs over the dense [slots, F, B] lattice
    # (exact box adjacency, same as intermediate, restricted per
    # candidate sub-box). Fresh recomputation subsumes the reference's
    # RecomputeConstraintsIfNeeded invalidation machinery.
    use_mono_adv = use_mono and mono_method == "advanced"
    if (use_mono_inter or use_mono_adv) and leaf_batch != 1:
        raise ValueError(
            "monotone_constraints_method=intermediate/advanced requires "
            "leaf_batch=1 (sequential split application)")
    use_boxes = use_mono_inter or use_mono_adv
    # forced splits (forcedsplits_filename; SerialTreeLearner::ForceSplits,
    # serial_tree_learner.cpp:636): the first n_forced rounds apply the
    # BFS-ordered forced list regardless of gain rank. Each entry is
    # (parent_index_in_list | -1 for root, is_right_child, feature,
    # threshold_bin). Slots resolve at RUNTIME from the parent's
    # recorded apply (left child keeps the parent's slot; right child is
    # the slot recorded when the parent actually applied), so a dropped
    # forced node (negative net gain, starved side, depth limit) drops
    # its whole subtree — the reference's forceSplitMap.erase semantics.
    # leaf_batch must be 1.
    use_forced = forced is not None and len(forced[0]) > 0
    if use_forced and leaf_batch != 1:
        raise ValueError("forced splits require leaf_batch=1")
    if use_forced:
        f_parent_a = jnp.asarray(forced[0], jnp.int32)
        f_isright_a = jnp.asarray(forced[1], bool)
        f_feats_a = jnp.asarray(forced[2], jnp.int32)
        f_thrs_a = jnp.asarray(forced[3], jnp.int32)
        # categorical forced nodes: one-hot on the category's bin;
        # thr=-1 marks an invalid (unseen) category the round must drop
        f_iscat_a = jnp.asarray(forced[4], bool)
        n_forced = len(forced[0])
    use_inter = interaction_groups is not None
    use_bynode = feature_fraction_bynode < 1.0
    use_rand = bool(sp.extra_trees)
    if (use_bynode or use_rand) and rng_key is None:
        raise ValueError("feature_fraction_bynode/extra_trees need rng_key")

    # CEGB (cost_effective_gradient_boosting.hpp): per-(leaf, feature)
    # gain penalties. cegb = (tradeoff, penalty_split, coupled[F]|None,
    # lazy[F]|None, feat_used0[F] bool, used_rows0[R, F] bool|None);
    # feat_used/used_rows persist ACROSS trees (model-level state) and
    # are returned updated.
    use_cegb = cegb is not None
    if use_cegb:
        (cegb_tradeoff, cegb_split, cegb_coupled, cegb_lazy,
         feat_used0, used_rows0) = cegb
        if axis_name is not None:
            raise NotImplementedError(
                "CEGB is single-device only (the reference ties it to "
                "the serial tree learner too)")

    # reduce-scatter merge layouts (ISSUE 4): only meaningful on a mesh
    rs = (axis_name is not None and hist_merge == "reduce_scatter"
          and n_shards > 1)
    rs_data = rs and mode == "data"       # main hist feature-slot-sharded
    rs_vote = rs and mode == "voting"     # elected columns slot-sharded
    if rs_data and use_forced:
        # the forced-split gather reads a full-F histogram row from the
        # cache; callers (gbdt) route forced splits to allreduce
        raise ValueError(
            "forced splits need hist_merge=allreduce under "
            "tree_learner=data (full-feature histogram gather)")
    if rs_data and not use_bundle:
        # feature-slot shard geometry: F padded so it splits evenly;
        # pad features are trivial (1 bin, masked out), never selected
        F_loc_rs = feature_block(F, n_shards)
        F_pad_rs = F_loc_rs * n_shards
        pf_rs = F_pad_rs - F
        nb_rs = jnp.pad(num_bins_pf, (0, pf_rs), constant_values=1)
        nan_rs = jnp.pad(nan_bin_pf, (0, pf_rs), constant_values=-1)
        cat_rs = jnp.pad(is_cat_pf, (0, pf_rs))
        mono_rs = (jnp.pad(mono_type_pf, (0, pf_rs))
                   if mono_type_pf is not None else None)
        csm_rs = (jnp.pad(cat_sorted_mask, (0, pf_rs))
                  if cat_sorted_mask is not None else None)
    elif rs_data:
        # EFB: the scatter slots along the BUNDLE axis (the storage
        # lattice the histogram is built in). Scattering unbundled
        # feature space instead would NOT be bit-stable: the
        # most-frequent-bin reconstruction (totals - sum of others) is
        # linear but reassociates under per-shard unbundling, and its
        # cancellation noise can flip near-tie splits. In bundle space
        # the scatter is elementwise-identical to the psum, each chip
        # owns whole bundles (= whole features; a feature never spans
        # bundles), and the cache stays raw/exact. Chips own
        # G_pad/n bundle columns; split finding masks to owned features.
        G_pad_rs = -(-G // n_shards) * n_shards
        G_loc_rs = G_pad_rs // n_shards

        def unbundle_shard(hg):
            """unbundle for this chip's [S, G_loc, bb, CH] scattered
            block of the MERGED bundle-space histogram -> [S, F, B, CH]
            feature space, zero outside the owned-feature set. Leaf
            totals (the mfb-reconstruction minuend) are computed by
            bundle 0's owner exactly as the replicated unbundle does —
            sum over the merged column's bins — and broadcast with a
            single-contributor psum, so every reconstructed value is
            bit-identical to the allreduce path's."""
            S = hg.shape[0]
            zero = jnp.zeros((), hg.dtype)
            gl0 = jax.lax.axis_index(axis_name) * jnp.int32(G_loc_rs)
            own = (b_gof >= gl0) & (b_gof < gl0 + G_loc_rs)      # [F]
            hflat = hg.reshape(S, G_loc_rs * bundle_bins, HIST_CH)
            gof_loc = jnp.clip(b_gof - gl0, 0, G_loc_rs - 1)
            idx = (gof_loc[:, None] * bundle_bins + b_off[:, None]
                   + jnp.arange(B, dtype=jnp.int32)[None, :])    # [F, B]
            bvalid = ((jnp.arange(B, dtype=jnp.int32)[None, :]
                       < num_bins_pf[:, None]) & own[:, None])
            idx = jnp.clip(idx, 0, G_loc_rs * bundle_bins - 1)
            hf = jnp.take(hflat, idx.reshape(-1), axis=1).reshape(
                S, F, B, HIST_CH)
            hf = jnp.where(bvalid[None, :, :, None], hf, zero)
            tot_loc = jnp.where(
                gl0 == 0, hg[:, 0, :, :].sum(axis=1),
                jnp.zeros((S, HIST_CH), hg.dtype))
            totals = jax.lax.psum(tot_loc, axis_name)            # [S, 3]
            mfb_oh = (jnp.arange(B, dtype=jnp.int32)[None, :]
                      == b_mfb[:, None])                         # [F, B]
            sum_all = hf.sum(axis=2)
            at_mfb = jnp.where(mfb_oh[None, :, :, None], hf,
                               zero).sum(axis=2)
            mfb_val = totals[:, None, :] - (sum_all - at_mfb)
            return jnp.where((mfb_oh & bvalid)[None, :, :, None],
                             mfb_val[:, :, None, :], hf)

        def rs_own_mask():
            """[F] bool — features whose bundle this chip owns."""
            gl0 = jax.lax.axis_index(axis_name) * jnp.int32(G_loc_rs)
            return (b_gof >= gl0) & (b_gof < gl0 + G_loc_rs)
    if use_bundle and mode == "feature":
        # internal invariant, not a user-facing limit: GBDT decodes the
        # bundled matrix to feature space before entering this mode
        # (Dataset.unbundled_bins), so bundle_meta never reaches here
        raise ValueError(
            "feature-parallel requires an unbundled bin matrix "
            "(caller must decode EFB storage first)")
    if mode == "feature":
        if local_bins is None or local_meta is None or feat_offset is None:
            raise ValueError(
                "feature-parallel needs local_bins/local_meta/feat_offset")
        (loc_nbpf, loc_nanpf, loc_catpf, loc_fmask, loc_mono) = local_meta
        F_loc = loc_nbpf.shape[0]
    if feature_sharded and mode != "feature":
        raise ValueError("feature_sharded requires parallel_mode='feature'")

    # quantized training: histograms come back int32 (exact); descale to
    # (sum_g, sum_h, count) f32 once per build — the single-pass analog of
    # FindBestThresholdInt's per-bin descale (feature_histogram.hpp:177).
    # The [L, F, B, 3] result is tiny next to the R-sized matmul stream,
    # so all the int8 bandwidth win of the hot loop is kept.
    if quant_scales is not None:
        _dq_vec = jnp.concatenate(
            [quant_scales.astype(f32), jnp.ones((1,), f32)])

    def _dequant(h):
        if quant_scales is None:
            return h
        return h.astype(f32) * _dq_vec

    def hist_perm_for(slots, part):
        """Histogram via the partition's ordered row lists (native CPU
        custom call): walks exactly the requested slots' segments."""
        mat = local_bins if mode == "feature" else bins
        nb_in = bundle_bins if use_bundle else B
        merge = mode not in ("feature", "voting")
        q = gh.dtype == jnp.int8
        target = "lgbtpu_hist_perm_i8" if q else "lgbtpu_hist_perm_f32"
        S = slots.shape[0]
        out_sds = jax.ShapeDtypeStruct(
            (S, mat.shape[1], nb_in, HIST_CH),
            jnp.int32 if q else jnp.float32)
        bf16 = bool((not q) and jnp.dtype(hist_dtype) == jnp.bfloat16)
        with profiler.stage(PHS.HIST_KERNEL):
            h = jax.ffi.ffi_call(target, out_sds)(
                mat, gh, part[0], part[1], part[2], slots.astype(jnp.int32),
                bf16_round=bf16)
        if axis_name is not None:
            h = _pvary(h, axis_name)
            if merge:
                h = merge_histograms(
                    h, axis_name,
                    "reduce_scatter" if rs_data else True, n_shards)
        return h

    def hist_raw_for(slots, rl, row_gather=None, num_rows=None, part=None):
        """RAW histogram for the given leaf slots — before dequant and
        EFB unbundling, both of which are LINEAR, so parent-minus-child
        subtraction happens in this space (exactly, int32, when
        quantized). mode-specific shape/merge:
        - feature: [S, F_loc, B, 3], local feature slice, no collective;
        - voting: [S, F|G, B|bb, 3], LOCAL rows only (merge per elected
          feature later). EFB composes: unbundling locally commutes with
          the later psum of elected columns — votes and elections run in
          feature space, communication stays O(top_k * B);
        - data/serial, hist_merge=allreduce: [S, F|G, B|bb, 3],
          psum-merged over axis_name (replicated);
        - data, hist_merge=reduce_scatter: [S, (F|G)_pad/n, B|bb, 3] —
          this chip's slot block of the merged histogram, scattered
          along the STORAGE lattice's feature axis (bundle columns when
          EFB is on: a feature never spans bundles, so whole features
          stay chip-local and the raw cache stays exact)."""
        if use_native_part and part is not None:
            return hist_perm_for(slots, part)
        mat = local_bins if mode == "feature" else bins
        nb_in = bundle_bins if use_bundle else B
        if mode in ("feature", "voting"):
            merge = False
        elif rs_data:
            merge = "reduce_scatter"
        else:
            merge = True
        return build_histograms(
            mat, gh, rl, slots,
            num_bins=nb_in, block_rows=block_rows, axis_name=axis_name,
            merge=merge, n_shards=n_shards, hist_dtype=hist_dtype,
            impl=hist_impl, row_gather=row_gather, num_rows=num_rows)

    def compact_small(row_leaf, small_slots):
        """The compacted stream of the small children's rows:
        ``(c_idx [R] int32, n_small)`` with stream position ``p <
        n_small`` reading row ``c_idx[p]`` (row order kept; dead rows,
        in no promised order, past the live prefix). Membership is W
        compares a row, fused into the sort's key (``select_by_slot``),
        not a gather from a ``[L+2]`` lut: on the chip that gather was
        1.56 s of a Higgs tree (PERF.md section 6, PR 29). The index is
        one sort of the row numbers (``stream_index``). The rows
        themselves are gathered where the stream is consumed
        (``build_histograms(row_gather=, num_rows=)``), chunk by chunk
        and only as far as ``n_small``: a bin row and one row of the
        ``[R, 4]`` table of ``gh`` and ``row_leaf`` a position, so
        ``row_leaf`` goes there uncompacted and is never gathered alone
        (a 1-D ``s32[R]`` gather was the dearest of three, PERF.md
        section 6, PR 38)."""
        m, _ = select_by_slot(row_leaf, small_slots, small_slots >= 0)
        return stream_index(m)

    def small_child(row_leaf, sel_s, right_slot, leaf_cnt=None):
        """Which child of each lane has fewer rows, ``(small_is_left
        [W] bool, this shard's rows in that child [W])``. Only the 2W
        children are counted (``slot_counts``; ``leaf_cnt`` [L+1] where
        a partition keeps every leaf's count already). Unused lanes
        hold the dummy leaf on both sides, which no row carries: they
        tie and read as left. Under a row mesh the counts are summed
        over the shards, ``[2W]`` integers, so every shard streams the
        same child: the histogram merge sums LOCAL small-child
        histograms."""
        slots = jnp.concatenate([sel_s, right_slot])
        if leaf_cnt is not None:
            loc = jnp.take(leaf_cnt, jnp.clip(slots, 0, L))
        else:
            loc = slot_counts(row_leaf, slots)
        cnt = loc
        if axis_name is not None and mode != "feature":
            cnt = jax.lax.psum(loc, axis_name)
        small_is_left = cnt[:W] <= cnt[W:]
        return small_is_left, jnp.where(small_is_left, loc[:W], loc[W:])

    def stream_rows_for(n_live):
        """Stream positions a compacted round touches for ``n_live``
        live rows (RoundLog.stream_rows)."""
        chunk = shape.stream_chunk_rows
        return (stream_trips(n_live, chunk, R) * chunk).astype(jnp.int32)

    def hist_finish(hraw):
        """Raw -> per-feature f32 split-finding space. The scattered
        EFB layout unbundles this chip's bundle block (zeros outside
        the owned-feature set — split finding masks to owned)."""
        h = _dequant(hraw)
        if not use_bundle:
            return h
        with profiler.stage(PHS.UNBUNDLE):
            return unbundle_shard(h) if rs_data else unbundle(h)

    def hist_for(slots, rl, part=None):
        return hist_finish(hist_raw_for(slots, rl, part=part))

    def _sync_best(bs):
        """Merge per-shard best splits by gain (SyncUpGlobalBestSplit).
        SplitInfo-sized (a handful of [S]-shaped collectives) — tagged
        ``winner_sync`` so the collective auditor (parallel/comms.py)
        separates it from histogram traffic."""
        with profiler.stage(PHS.WINNER_SYNC):
            return _sync_best_impl(bs)

    def _sync_best_impl(bs):
        gain = bs["gain"]
        gmax = jax.lax.pmax(gain, axis_name)
        idx = jax.lax.axis_index(axis_name)
        big = jnp.int32(1 << 30)
        mine = jnp.where((gain == gmax) & jnp.isfinite(gain), idx, big)
        win = jax.lax.pmin(mine, axis_name)
        is_win = idx == win
        def pick(v):
            m = is_win
            while m.ndim < v.ndim:
                m = m[..., None]
            if v.dtype == jnp.bool_:
                z = jnp.where(m, v, False).astype(jnp.int32)
                return jax.lax.psum(z, axis_name) > 0
            z = jnp.where(m, v, jnp.zeros_like(v))
            return jax.lax.psum(z, axis_name)
        out = {k: pick(v) for k, v in bs.items() if k != "gain"}
        out["gain"] = gmax
        return out

    nnb_pf = num_bins_pf - (nan_bin_pf >= 0).astype(jnp.int32)

    def slot_masks_and_bins(used_feat, slots_c, key):
        """Per-slot candidate features + extra-trees random thresholds."""
        S = slots_c.shape[0]
        fmask = jnp.broadcast_to(feature_mask[None, :], (S, F))
        if use_inter:
            used = jnp.take(used_feat, slots_c, axis=0)          # [S, F]
            # group ok iff no used feature outside it: used @ ~group == 0
            viol = used.astype(f32) @ (~interaction_groups).astype(f32).T
            allowed = ((viol == 0).astype(f32)
                       @ interaction_groups.astype(f32)) > 0     # [S, F]
            fmask = fmask & allowed
        if use_bynode:
            # GetCnt over the tree-sampled set, capped by the allowed set
            # (col_sampler.hpp:190-205)
            n_tree = feature_mask.sum().astype(f32)
            n_allow = fmask.sum(axis=1).astype(f32)              # [S]
            k = _round_int(n_tree * feature_fraction_bynode)
            k = jnp.minimum(jnp.maximum(k, 1.0), n_allow)
            k = jnp.maximum(k, jnp.minimum(1.0, n_allow)).astype(jnp.int32)
            u = jax.random.uniform(jax.random.fold_in(key, 1), (S, F))
            score = jnp.where(fmask, u, -1.0)
            kth = jnp.take_along_axis(
                -jnp.sort(-score, axis=1),
                jnp.maximum(k - 1, 0)[:, None], axis=1)
            fmask = fmask & (score >= kth)
        rand_bin = None
        if use_rand:
            u2 = jax.random.uniform(jax.random.fold_in(key, 2), (S, F))
            n_num = jnp.maximum(nnb_pf - 1, 1).astype(f32)       # thresholds
            n_cat = jnp.maximum(nnb_pf, 1).astype(f32)
            n_opt = jnp.where(is_cat_pf, n_cat, n_num)[None, :]
            rand_bin = jnp.floor(u2 * n_opt).astype(jnp.int32)
        return fmask, rand_bin

    def cegb_penalty_for(slots_c, rl, t, state):
        """[S, F] CEGB DeltaGain (cost_effective_gradient_boosting.hpp:
        80-98): split cost scaled by leaf size + one-time coupled
        feature cost + per-row lazy acquisition cost."""
        node_of = jnp.take(t.leaf2node, slots_c)
        n_leaf = jnp.take(t.node_count, node_of)              # [S]
        delta = (cegb_tradeoff * cegb_split * n_leaf)[:, None] \
            * jnp.ones((1, F), f32)
        if cegb_coupled is not None:
            delta = delta + cegb_tradeoff * jnp.where(
                state["cegb_feat_used"][None, :], 0.0,
                cegb_coupled[None, :])
        if cegb_lazy is not None:
            unused_cost = jnp.where(state["cegb_used_rows"], 0.0,
                                    cegb_lazy[None, :])          # [R, F]
            # dead/padded rows (rl < 0) route to the dummy segment L
            seg = jnp.where(rl < 0, L, rl)
            per_leaf = jax.ops.segment_sum(
                unused_cost, seg, num_segments=L + 1)
            delta = delta + cegb_tradeoff * jnp.take(
                per_leaf, jnp.clip(slots_c, 0, L), axis=0)
        return delta

    if use_mono_adv:
        _m_pos = mono_type_pf > 0
        _m_neg = mono_type_pf < 0

        def adv_bounds_for(slots_c, tree_now, box_lo, box_hi):
            """Fresh advanced-mode bounds for each slot's candidate
            children: ((lo_l, hi_l, lo_r, hi_r) [S, F, B], lo_s, hi_s
            [S]). A live leaf v constrains slot s along monotone dim d
            when their boxes are separated along exactly d; for a
            candidate split on q != d the constraint reaches a child
            only if v's q-range overlaps that child's q-range (the
            per-threshold-segment logic of UpdateConstraints,
            monotone_constraints.hpp:871-975, as one dense lattice).
            The scalar (lo_s, hi_s) are whole-leaf bounds for
            categorical candidates (no numeric partition)."""
            S = slots_c.shape[0]
            v_out = tree_now.leaf_values                    # [L+1]
            live = tree_now.leaf2node != DUMMY_NODE
            s_lo = jnp.take(box_lo, slots_c, axis=0)        # [S, F]
            s_hi = jnp.take(box_hi, slots_c, axis=0)
            ovl = ((box_lo[None] <= s_hi[:, None])
                   & (s_lo[:, None] <= box_hi[None]))       # [S, V, F]
            nno = (~ovl).sum(axis=2)
            selfm = (slots_c[:, None]
                     == jnp.arange(L + 1, dtype=jnp.int32)[None, :])
            base = (nno == 1) & live[None, :] & ~selfm      # [S, V]
            above = box_lo[None] > s_hi[:, None]
            below = box_hi[None] < s_lo[:, None]
            sep = base[:, :, None] & (~ovl)                 # sep along d
            hi_d = sep & ((above & _m_pos[None, None])
                          | (below & _m_neg[None, None]))
            lo_d = sep & ((below & _m_pos[None, None])
                          | (above & _m_neg[None, None]))
            t_io = jnp.arange(B, dtype=jnp.int32)
            cat_q = is_cat_pf[None, None, :, None]

            # The naive lattice is [S, V, F, B] (V = L+1): at 255
            # leaves x 128 features x 255 bins that is ~470M bools per
            # temporary. The V axis is purely a reduction, so it is
            # processed in chunks of Vc leaves with min/max carried
            # across chunks — peak memory S*Vc*F*B, identical results.
            V = L + 1
            Vc = max(1, min(V, (1 << 23) // max(1, S * F * B)))
            nch = (V + Vc - 1) // Vc
            Vp = nch * Vc
            pad = Vp - V

            def padV(a, fill):
                cfg = [(0, 0)] * a.ndim
                cfg[1] = (0, pad)
                return jnp.pad(a, cfg, constant_values=fill)

            # padded leaves carry no constraint (mask False)
            hi_dp = padV(hi_d, False)
            lo_dp = padV(lo_d, False)
            box_lo_p = jnp.pad(box_lo, ((0, pad), (0, 0)))
            box_hi_p = jnp.pad(box_hi, ((0, pad), (0, 0)))
            v_out_p = jnp.pad(v_out, (0, pad))

            def reduce_bounds(mask_d, kind, init):
                red_ax = jnp.min if kind == "min" else jnp.max
                red_el = jnp.minimum if kind == "min" else jnp.maximum
                cnt = mask_d.sum(axis=2)                    # [S, Vp]
                any_ex = ((cnt[:, :, None]
                           - mask_d.astype(cnt.dtype)) > 0)  # [S, Vp, F]

                def chunk(i, acc):
                    b_l0, b_r0, b_s0 = acc
                    md = jax.lax.dynamic_slice(
                        mask_d, (0, i * Vc, 0), (S, Vc, F))
                    ae = jax.lax.dynamic_slice(
                        any_ex, (0, i * Vc, 0), (S, Vc, F))
                    blo = jax.lax.dynamic_slice(
                        box_lo_p, (i * Vc, 0), (Vc, F))
                    bhi = jax.lax.dynamic_slice(
                        box_hi_p, (i * Vc, 0), (Vc, F))
                    vo = jax.lax.dynamic_slice(v_out_p, (i * Vc,), (Vc,))
                    l_ok = (blo[None, :, :, None] <= t_io) | cat_q
                    r_ok = (bhi[None, :, :, None] >= t_io + 1) | cat_q
                    m_l = md[:, :, :, None] | (ae[:, :, :, None] & l_ok)
                    m_r = md[:, :, :, None] | (ae[:, :, :, None] & r_ok)
                    vals = vo[None, :, None, None]
                    return (red_el(b_l0,
                                   red_ax(jnp.where(m_l, vals, init),
                                          axis=1)),
                            red_el(b_r0,
                                   red_ax(jnp.where(m_r, vals, init),
                                          axis=1)),
                            red_el(b_s0,
                                   red_ax(jnp.where(md.any(axis=2),
                                                    vo[None, :], init),
                                          axis=1)))

                init_l = jnp.full((S, F, B), init, f32)
                init_s = jnp.full((S,), init, f32)
                return jax.lax.fori_loop(
                    0, nch, chunk, (init_l, init_l, init_s))
            hi_l, hi_r, hi_s = reduce_bounds(hi_dp, "min", F32_MAX)
            lo_l, lo_r, lo_s = reduce_bounds(lo_dp, "max", -F32_MAX)
            return (lo_l, hi_l, lo_r, hi_r), lo_s, hi_s

    def best_for(hist2w, slot_depth, slot_valid, slots_c, t, state, key,
                 rl=None):
        lo = jnp.take(state["leaf_lo"], slots_c) if use_mono else None
        hi = jnp.take(state["leaf_hi"], slots_c) if use_mono else None
        adv = None
        if use_mono_adv:
            adv, lo, hi = adv_bounds_for(
                slots_c, t, state["box_lo"], state["box_hi"])
        node_of = jnp.take(t.leaf2node, slots_c)
        parent_out = jnp.take(t.node_value, node_of)
        fmask_s, rand_bin = slot_masks_and_bins(
            state.get("used_feat"), slots_c, key)
        gain_penalty = (cegb_penalty_for(slots_c, rl, t, state)
                        if use_cegb else None)
        if mode == "feature":
            # split search over this chip's feature slice only.
            # Interaction constraints / per-node sampling / extra-trees
            # compose by slicing the GLOBAL per-slot mask at this chip's
            # window: the constraint state and PRNG are replicated, so
            # every chip computes the identical global mask and takes
            # its block (the reference composes the same way via the
            # ColSampler living inside each templated learner,
            # tree_learner.cpp:15-57).
            S = slots_c.shape[0]
            fmask_loc = jax.lax.dynamic_slice(
                fmask_s, (0, feat_offset), (S, F_loc)) & loc_fmask[None, :]
            rand_loc = (jax.lax.dynamic_slice(
                rand_bin, (0, feat_offset), (S, F_loc))
                if rand_bin is not None else None)
            cs_loc = (jax.lax.dynamic_slice(
                cat_sorted_mask, (feat_offset,), (F_loc,))
                if cat_sorted_mask is not None else None)
            # advanced monotone composes the same replicated way: the
            # bounds lattice is computed over global F (box state and
            # tree are replicated) and sliced at this chip's window
            adv_loc = (tuple(jax.lax.dynamic_slice(
                a, (0, feat_offset, 0), (S, F_loc, a.shape[2]))
                for a in adv) if adv is not None else None)
            bs = find_best_splits(
                hist2w, loc_nbpf, loc_nanpf, loc_catpf, sp,
                feature_mask=fmask_loc, mono_type=loc_mono,
                leaf_lo=lo, leaf_hi=hi, parent_output=parent_out,
                slot_depth=slot_depth, rand_bin=rand_loc,
                cat_sorted_mask=cs_loc, adv_bounds=adv_loc)
            bs["feature"] = bs["feature"] + feat_offset
        elif mode == "voting":
            S = slots_c.shape[0]
            # 1. local candidate gains per (slot, feature)
            bs_loc = find_best_splits(
                hist2w, num_bins_pf, nan_bin_pf, is_cat_pf, sp,
                feature_mask=fmask_s, mono_type=mono_type_pf,
                leaf_lo=lo, leaf_hi=hi, parent_output=parent_out,
                slot_depth=slot_depth, rand_bin=rand_bin,
                cat_sorted_mask=cat_sorted_mask, adv_bounds=adv,
                return_feature_gain=True)
            fg = bs_loc["feature_gain"]                       # [S, F]
            k = min(top_k, F)
            k2 = min(2 * top_k, F)
            topg, topi = jax.lax.top_k(fg, k)
            # 2. vote: one ballot per locally-viable top-k feature
            votes = jnp.zeros((S, F), f32).at[
                jnp.arange(S)[:, None], topi].add(
                    (topg > NEG_INF).astype(f32))
            votes = jax.lax.psum(votes, axis_name)
            # 3. elect global top-2k (ties -> lower feature id)
            score = votes * (F + 1.0) - jnp.arange(F, dtype=f32)[None, :]
            _, elected = jax.lax.top_k(score, k2)             # [S, k2]
            # 4. merge ONLY the elected columns across chips. With
            # hist_merge=reduce_scatter the merge lands slot-SHARDED
            # (each chip receives its k2_pad/n elected-column block,
            # searches it, and the winner syncs SplitInfo-sized) —
            # closing the replicated-psum TODO of data_parallel.py:
            # wire bytes halve and the sub-split search stops being
            # n-redundant. Elections are replicated (votes psum'd), so
            # every chip slices consistently.
            sub_loc = jnp.take_along_axis(
                hist2w, elected[:, :, None, None], axis=1)    # [S,k2,...]
            if rs_vote:
                k2p = -(-k2 // n_shards) * n_shards
                k2_loc = k2p // n_shards
                pe = k2p - k2
                sub_hist = merge_histograms(
                    sub_loc, axis_name, "reduce_scatter", n_shards)
                off_v = (jax.lax.axis_index(axis_name)
                         * jnp.int32(k2_loc))
                # pad lane -> elected feature 0 with its mask forced
                # False (its scattered histogram block is zero anyway)
                elected = jax.lax.dynamic_slice(
                    jnp.pad(elected, ((0, 0), (0, pe))),
                    (jnp.int32(0), off_v), (S, k2_loc))
                lane_ok = jax.lax.dynamic_slice(
                    jnp.arange(k2p, dtype=jnp.int32) < k2,
                    (off_v,), (k2_loc,))[None, :]
            else:
                sub_hist = merge_histograms(sub_loc, axis_name, True)
                lane_ok = True
            sub_fmask = (jnp.take_along_axis(fmask_s, elected, axis=1)
                         if fmask_s.ndim == 2
                         else jnp.take(fmask_s, elected)) & lane_ok
            bs = find_best_splits(
                sub_hist, jnp.take(num_bins_pf, elected),
                jnp.take(nan_bin_pf, elected),
                jnp.take(is_cat_pf, elected), sp,
                feature_mask=sub_fmask,
                mono_type=(jnp.take(mono_type_pf, elected)
                           if use_mono else None),
                leaf_lo=lo, leaf_hi=hi, parent_output=parent_out,
                slot_depth=slot_depth,
                rand_bin=(jnp.take_along_axis(rand_bin, elected, axis=1)
                          if rand_bin is not None else None),
                # sorted-subset categoricals compose: the elected-column
                # metadata is per-slot [S, k2] and both finders
                # broadcast 2-D metadata
                cat_sorted_mask=(jnp.take(cat_sorted_mask, elected)
                                 if cat_sorted_mask is not None
                                 else None),
                # advanced monotone: gather the bounds lattice at the
                # elected columns ([S, F, B] -> [S, k2, B])
                adv_bounds=(tuple(jnp.take_along_axis(
                    a, elected[:, :, None], axis=1) for a in adv)
                    if adv is not None else None))
            bs["feature"] = jnp.take_along_axis(
                elected, bs["feature"][:, None], axis=1)[:, 0] \
                .astype(jnp.int32)
        elif rs_data and use_bundle:
            # scattered EFB shard: hist2w is already unbundled to FULL
            # feature space, zero outside this chip's owned-bundle
            # features — search all F columns with the ownership mask
            # (communication is the scattered bundle block; the search
            # itself is not divided because bundle->feature ownership
            # is not a contiguous slice), then merge winners.
            bs = find_best_splits(
                hist2w, num_bins_pf, nan_bin_pf, is_cat_pf, sp,
                feature_mask=fmask_s & rs_own_mask()[None, :],
                mono_type=mono_type_pf,
                leaf_lo=lo, leaf_hi=hi, parent_output=parent_out,
                slot_depth=slot_depth, rand_bin=rand_bin,
                cat_sorted_mask=cat_sorted_mask, adv_bounds=adv)
        elif rs_data:
            # scattered-shard split search (mode == "data",
            # hist_merge=reduce_scatter): hist2w is this chip's
            # [S, F_loc, B, 3] feature-slot block of the MERGED
            # histogram. Constraint masks and PRNG are replicated, so
            # the global [S, F] candidate mask is computed identically
            # everywhere and sliced at this chip's window — the same
            # composition rule the feature-parallel branch uses.
            S = slots_c.shape[0]
            off = jax.lax.axis_index(axis_name) * jnp.int32(F_loc_rs)
            z32 = jnp.int32(0)

            def _slice1(a):
                return jax.lax.dynamic_slice(a, (off,), (F_loc_rs,))

            def _slice2(a):
                return jax.lax.dynamic_slice(
                    jnp.pad(a, ((0, 0), (0, pf_rs))), (z32, off),
                    (S, F_loc_rs))
            bs = find_best_splits(
                hist2w, _slice1(nb_rs), _slice1(nan_rs),
                _slice1(cat_rs), sp,
                feature_mask=_slice2(fmask_s),
                mono_type=(_slice1(mono_rs) if use_mono else None),
                leaf_lo=lo, leaf_hi=hi, parent_output=parent_out,
                slot_depth=slot_depth,
                rand_bin=(_slice2(rand_bin)
                          if rand_bin is not None else None),
                cat_sorted_mask=(_slice1(csm_rs)
                                 if cat_sorted_mask is not None
                                 else None),
                adv_bounds=(tuple(jax.lax.dynamic_slice(
                    jnp.pad(a, ((0, 0), (0, pf_rs), (0, 0))),
                    (z32, off, z32), (S, F_loc_rs, a.shape[2]))
                    for a in adv) if adv is not None else None))
            bs["feature"] = bs["feature"] + off
        else:
            bs = find_best_splits(
                hist2w, num_bins_pf, nan_bin_pf, is_cat_pf, sp,
                feature_mask=fmask_s, mono_type=mono_type_pf,
                leaf_lo=lo, leaf_hi=hi, parent_output=parent_out,
                slot_depth=slot_depth, rand_bin=rand_bin,
                cat_sorted_mask=cat_sorted_mask,
                gain_scale=gain_scale, gain_penalty=gain_penalty,
                adv_bounds=adv)
        g = bs["gain"]
        if max_depth > 0:
            g = jnp.where(slot_depth < max_depth, g, NEG_INF)
        g = jnp.where(slot_valid, g, NEG_INF)
        bs["gain"] = g
        if mode == "feature" or rs_data or rs_vote:
            # feature-sharded search (by plan, or by the scattered
            # histogram layout): merge winners SplitInfo-sized
            bs = _sync_best(bs)
        return bs

    # ---------------- state ----------------
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
        leaf2node=jnp.full((L + 1,), DUMMY_NODE, jnp.int32),
        leaf_values=jnp.zeros((L + 1,), f32),
        num_leaves=jnp.asarray(1, jnp.int32),
        num_nodes=jnp.asarray(1, jnp.int32),
    )
    tree = tree._replace(leaf2node=tree.leaf2node.at[0].set(0))

    # per-leaf best-split caches (best_split_per_leaf_ analog)
    bs_gain = jnp.full((L + 1,), NEG_INF, f32)
    bs_feat = jnp.zeros((L + 1,), jnp.int32)
    bs_thr = jnp.zeros((L + 1,), jnp.int32)
    bs_dl = jnp.zeros((L + 1,), bool)
    bs_cat = jnp.zeros((L + 1,), bool)
    bs_left = jnp.zeros((L + 1, HIST_CH), f32)
    bs_right = jnp.zeros((L + 1, HIST_CH), f32)
    bs_bits = jnp.zeros((L + 1, BW), jnp.uint32)
    bs_lout = jnp.zeros((L + 1,), f32)
    bs_rout = jnp.zeros((L + 1,), f32)
    leaf_depth = jnp.zeros((L + 1,), jnp.int32)

    state = dict(row_leaf=row_leaf0,
                 valid_row_leaf=tuple(valid_row_leaf0),
                 leaf_lo=jnp.full((L + 1,), -F32_MAX, f32),
                 leaf_hi=jnp.full((L + 1,), F32_MAX, f32),
                 r=jnp.asarray(0, jnp.int32))
    if use_forced:
        # per-forced-node runtime record: did it apply, at which slot,
        # and which slot its right child received
        state["f_ok"] = jnp.zeros((n_forced,), bool)
        state["f_slot_rec"] = jnp.zeros((n_forced,), jnp.int32)
        state["f_rslot"] = jnp.zeros((n_forced,), jnp.int32)
    if use_boxes:
        # inclusive bin-range box per leaf slot (feature space)
        state["box_lo"] = jnp.zeros((L + 1, F), jnp.int32)
        state["box_hi"] = jnp.full((L + 1, F), B - 1, jnp.int32)
    if use_inter:
        state["used_feat"] = jnp.zeros((L + 1, F), bool)
    if use_cegb:
        state["cegb_feat_used"] = feat_used0
        if cegb_lazy is not None:
            state["cegb_used_rows"] = used_rows0

    # ---------------- root ----------------
    with profiler.stage(PHS.ROOT_PASS):
        part0 = None
        if use_native_part:
            # DataPartition init: live rows (all slot 0 at the root) first,
            # original order preserved; dead/padded rows trail unused
            live0 = row_leaf0 >= 0
            live_i = live0.astype(jnp.int32)
            n_live0 = live_i.sum()
            # stable live-first order WITHOUT a sort (XLA's 1M-row sort
            # costs ~95 ms on one core; this is three cheap passes)
            dest = jnp.where(live0, jnp.cumsum(live_i) - 1,
                             n_live0 + jnp.cumsum(1 - live_i) - 1)
            perm0 = jnp.zeros((R,), jnp.int32).at[dest].set(
                jnp.arange(R, dtype=jnp.int32))
            lb0 = jnp.zeros((L + 1,), jnp.int32)
            lc0 = jnp.zeros((L + 1,), jnp.int32).at[0].set(
                n_live0.astype(jnp.int32))
            if axis_name is not None:
                # the loop-carried partition state is per-shard (varying)
                perm0 = _pvary(perm0, axis_name)
                lb0 = _pvary(lb0, axis_name)
                lc0 = _pvary(lc0, axis_name)
            part0 = (perm0, lb0, lc0)
            state["perm"], state["leaf_begin"], state["leaf_cnt"] = part0
        root_slots = jnp.full((2 * W,), -2, jnp.int32).at[0].set(0)
        key0 = (jax.random.fold_in(rng_key, 0) if rng_key is not None
                else None)
        if root_hist is not None:
            # class-batched root dedupe (ISSUE 14 satellite): the K classes'
            # root histograms were built pre-vmap by ONE kernel streaming
            # the bins block once; non-root lattice slots are exact zeros in
            # both formulations (no row carries the -2 sentinel)
            hraw0 = jnp.zeros((2 * W,) + root_hist.shape,
                              root_hist.dtype).at[0].set(root_hist)
        else:
            hraw0 = hist_raw_for(root_slots, row_leaf0, part=part0)
        hist0 = hist_finish(hraw0)
        if hist_sub:
            # per-leaf RAW histogram cache (HistogramPool analog): slot i
            # holds leaf i's histogram as of its creation; rows of a leaf
            # only change when IT is split, so entries stay valid until
            # popped, when the entry is the subtraction minuend
            state["hist_cache"] = jnp.zeros(
                (L + 1,) + hraw0.shape[1:],
                hraw0.dtype).at[0].set(hraw0[0])
        # all rows land in feature 0's bins
        root_sums = hist0[0, 0, :, :].sum(axis=0)
        if mode == "voting":
            # local hist -> global root sums (the Allreduce of root
            # (count, sum_g, sum_h), data_parallel_tree_learner.cpp:160-219)
            root_sums = jax.lax.psum(root_sums, axis_name)
        elif rs_data:
            # scattered layout: exactly ONE chip holds global feature 0's
            # merged column (chip 0 in the plain layout; the owner of
            # bundle b_gof[0] under EFB — hist0 is zero elsewhere), and its
            # bin sum is the global root totals. One [3]-sized psum
            # broadcasts the owner's value.
            if use_bundle:
                own0 = rs_own_mask()[0]
            else:
                own0 = jax.lax.axis_index(axis_name) == 0
            root_sums = jax.lax.psum(
                jnp.where(own0, root_sums, jnp.zeros_like(root_sums)),
                axis_name)
        root_val = leaf_output(root_sums[0], root_sums[1], sp.lambda_l1,
                               sp.lambda_l2, sp.max_delta_step)
        tree = tree._replace(
            node_value=tree.node_value.at[0].set(root_val),
            node_count=tree.node_count.at[0].set(root_sums[2]),
            node_hess=tree.node_hess.at[0].set(root_sums[1]),
            leaf_values=tree.leaf_values.at[0].set(root_val),
        )
        slot_valid0 = jnp.zeros((2 * W,), bool).at[0].set(True)
        bs0 = best_for(hist0, jnp.zeros((2 * W,), jnp.int32), slot_valid0,
                       root_slots.clip(0), tree, state, key0,
                       rl=row_leaf0)
        bs_gain = bs_gain.at[0].set(bs0["gain"][0])
        bs_feat = bs_feat.at[0].set(bs0["feature"][0])
        bs_thr = bs_thr.at[0].set(bs0["threshold"][0])
        bs_dl = bs_dl.at[0].set(bs0["default_left"][0])
        bs_cat = bs_cat.at[0].set(bs0["is_cat_split"][0])
        bs_left = bs_left.at[0].set(bs0["left_sum"][0])
        bs_right = bs_right.at[0].set(bs0["right_sum"][0])
        bs_bits = bs_bits.at[0].set(bs0["cat_bitset"][0])
        bs_lout = bs_lout.at[0].set(bs0["left_out"][0])
        bs_rout = bs_rout.at[0].set(bs0["right_out"][0])

    rounds_bound = shape.rounds_bound
    # per-round counters, fetched with the tree (RoundLog). Row-sharded
    # plans count each shard's own stream, so the carry varies over the
    # mesh axis; feature-parallel streams the same rows on every chip.
    round_rows0 = jnp.zeros((rounds_bound,), jnp.int32)
    if axis_name is not None and mode != "feature":
        round_rows0 = _pvary(round_rows0, axis_name)
    state["round_rows"] = round_rows0
    state["round_stream"] = round_rows0
    state["round_leaves"] = jnp.zeros((rounds_bound,), jnp.int32)

    state.update(tree=tree, bs_gain=bs_gain, bs_feat=bs_feat, bs_thr=bs_thr,
                 bs_dl=bs_dl, bs_cat=bs_cat, bs_left=bs_left,
                 bs_right=bs_right, bs_bits=bs_bits, bs_lout=bs_lout,
                 bs_rout=bs_rout, leaf_depth=leaf_depth)

    def cond(st):
        t = st["tree"]
        more_budget = t.num_leaves < L
        has_split = jnp.any(st["bs_gain"][:L] > NEG_INF)
        if use_forced:
            # forced rounds may proceed even when no cached candidate
            # is splittable (their gain check happens in-body)
            has_split = has_split | (st["r"] < n_forced)
        return (st["r"] < rounds_bound) & more_budget & has_split

    def body(st):
        with profiler.stage_sequence() as stg:
            return _round(st, stg)

    def _round(st, stg):
        t: TreeArrays = st["tree"]
        cur = t.num_leaves
        nodes = t.num_nodes
        # -- 1. pop top-W cached splits
        stg(PHS.POP)
        gains, sel = jax.lax.top_k(st["bs_gain"][:L], W)
        sel = sel.astype(jnp.int32)
        budget = L - cur
        valid = jnp.isfinite(gains) & (jnp.arange(W) < budget)
        n_valid = valid.sum().astype(jnp.int32)
        pos = jnp.cumsum(valid.astype(jnp.int32)) - 1
        sel_s = jnp.where(valid, sel, DUMMY_LEAF)
        right_slot = jnp.where(valid, cur + pos, DUMMY_LEAF)
        ln = jnp.where(valid, nodes + 2 * pos, DUMMY_NODE)
        rn = jnp.where(valid, nodes + 2 * pos + 1, DUMMY_NODE)
        parent = jnp.where(valid, jnp.take(t.leaf2node, sel_s), DUMMY_NODE)

        sfeat = jnp.take(st["bs_feat"], sel_s)
        sthr = jnp.take(st["bs_thr"], sel_s)
        sdl = jnp.take(st["bs_dl"], sel_s)
        scat = jnp.take(st["bs_cat"], sel_s)
        sgain = jnp.take(st["bs_gain"], sel_s)
        slsum = jnp.take(st["bs_left"], sel_s, axis=0)
        srsum = jnp.take(st["bs_right"], sel_s, axis=0)
        sbits = jnp.take(st["bs_bits"], sel_s, axis=0)
        # constrained/smoothed outputs computed by the split finder
        # (SplitInfo::left_output/right_output analog)
        lval = jnp.take(st["bs_lout"], sel_s)
        rval = jnp.take(st["bs_rout"], sel_s)

        new_state_forced = {}
        if use_forced:
            # ForceSplits rounds: override lane 0 with the forced
            # candidate computed straight from the slot's histogram
            # (GatherInfoForThreshold analog; missing routes LEFT with
            # default_left=true, feature_histogram.hpp:588).
            # A dropped forced candidate falls back to this round's
            # normal top-gain pop and poisons its forced descendants.
            fr = jnp.clip(st["r"], 0, n_forced - 1)
            in_forced = st["r"] < n_forced
            pj = jnp.take(f_parent_a, fr)
            pjc = jnp.clip(pj, 0, n_forced - 1)
            parent_ok = jnp.where(pj < 0, True, jnp.take(st["f_ok"], pjc))
            f_slot = jnp.where(
                pj < 0, 0,
                jnp.where(jnp.take(f_isright_a, fr),
                          jnp.take(st["f_rslot"], pjc),
                          jnp.take(st["f_slot_rec"], pjc)))
            f_feat = jnp.take(f_feats_a, fr)
            f_thr = jnp.take(f_thrs_a, fr)
            if hist_sub:
                # the forced leaf's full histogram is already cached
                # (GatherInfoForThreshold reads the leaf's histogram;
                # the pool makes the re-histogram pass free)
                hist_fc0 = hist_finish(
                    st["hist_cache"][jnp.clip(f_slot, 0, L)][None])[0]
            else:
                fslots = jnp.full((2 * W,), -2, jnp.int32).at[0].set(f_slot)
                part_f = ((st["perm"], st["leaf_begin"], st["leaf_cnt"])
                          if use_native_part else None)
                hist_fc0 = jax.lax.cond(
                    in_forced,
                    lambda: hist_for(fslots, st["row_leaf"], part=part_f),
                    lambda: jnp.zeros((2 * W, F, B, HIST_CH),
                                      jnp.float32))[0]
            hrow = jnp.take(hist_fc0, f_feat, axis=0)         # [B, 3]
            f_cat = jnp.take(f_iscat_a, fr)
            nb_f = jnp.take(nan_bin_pf, f_feat)
            # GatherInfoForThresholdNumericalInner accumulates the RIGHT
            # side from the top bin down to threshold+1, SKIPPING the
            # NaN bin (feature_histogram.hpp:522-526 use_na_as_missing)
            # — so missing rows land LEFT and default_left=true below.
            # (MISSING_ZERO's zero bin stays an ordinary bin here, the
            # same treatment this implementation's regular split finder
            # gives it.)
            bval = (jnp.arange(B, dtype=jnp.int32)
                    != jnp.where(nb_f >= 0, nb_f, -1))
            cum = jnp.cumsum(jnp.where(bval[:, None], hrow, 0.0), axis=0)
            tot = hrow.sum(axis=0)
            nan_row = jnp.where(
                nb_f >= 0,
                jnp.take(hrow, jnp.clip(nb_f, 0, B - 1), axis=0),
                jnp.zeros((HIST_CH,), jnp.float32))
            lsum_num = (jnp.take(cum, jnp.clip(f_thr, 0, B - 1), axis=0)
                        + nan_row)
            # categorical: one-hot — left = the category's own bin only
            # (GatherInfoForThresholdCategoricalInner,
            # feature_histogram.hpp:604); thr=-1 (unseen category) is
            # rejected below in ok_f, matching the reference's
            # "Invalid categorical threshold" rejection (hpp:613)
            lsum_cat = jnp.take(hrow, jnp.clip(f_thr, 0, B - 1), axis=0)
            lsum = jnp.where(f_cat, lsum_cat, lsum_num)
            rsum = tot - lsum
            l1_, l2_ = sp.lambda_l1, sp.lambda_l2
            node_of_f = jnp.take(t.leaf2node,
                                 jnp.clip(f_slot, 0, L))
            po_f = jnp.take(t.node_value, node_of_f)
            sm_f = ({} if sp.path_smooth <= 0.0
                    else dict(path_smooth=sp.path_smooth,
                              parent_output=po_f))
            from ..ops.split import calc_output as _calc_out
            f_lout = _calc_out(lsum[0], lsum[1], l1_, l2_,
                               sp.max_delta_step,
                               count=lsum[2] if sm_f else None, **sm_f)
            f_rout = _calc_out(rsum[0], rsum[1], l1_, l2_,
                               sp.max_delta_step,
                               count=rsum[2] if sm_f else None, **sm_f)
            # NET gain: split - parent - min_gain_to_split, the same
            # shift GatherInfoForThreshold applies before the erase test
            f_gain = (leaf_gain(lsum[0], lsum[1], l1_, l2_)
                      + leaf_gain(rsum[0], rsum[1], l1_, l2_)
                      - leaf_gain(tot[0], tot[1], l1_, l2_)
                      - sp.min_gain_to_split)
            depth_f = jnp.take(st["leaf_depth"], jnp.clip(f_slot, 0, L))
            ok_f = (in_forced & parent_ok
                    & (~f_cat | (f_thr >= 0))   # unseen category: drop
                    & (lsum[2] >= sp.min_data_in_leaf)
                    & (rsum[2] >= sp.min_data_in_leaf)
                    & (lsum[1] >= sp.min_sum_hessian_in_leaf)
                    & (rsum[1] >= sp.min_sum_hessian_in_leaf)
                    & (f_gain > 0)   # strict: gain <= min_gain_shift
                                     # is rejected (hpp:562)
                    & ((max_depth <= 0) | (depth_f < max_depth))
                    & (jnp.take(t.leaf2node, f_slot) != DUMMY_NODE))
            new_state_forced = dict(
                f_ok=st["f_ok"].at[fr].set(
                    jnp.where(in_forced, ok_f, st["f_ok"][fr])),
                f_slot_rec=st["f_slot_rec"].at[fr].set(
                    jnp.where(in_forced, f_slot, st["f_slot_rec"][fr])),
                # with W=1 an applied split's right child gets slot `cur`
                f_rslot=st["f_rslot"].at[fr].set(
                    jnp.where(in_forced, cur, st["f_rslot"][fr])))

            def _ov(arr, new):
                return arr.at[0].set(jnp.where(ok_f, new, arr[0]))
            # re-derive the lane-0 selection chain under the override
            sel_s = _ov(sel_s, f_slot)
            valid = valid.at[0].set(ok_f | valid[0])
            n_valid = valid.sum().astype(jnp.int32)
            pos = jnp.cumsum(valid.astype(jnp.int32)) - 1
            sel_s = jnp.where(valid, sel_s, DUMMY_LEAF)
            right_slot = jnp.where(valid, cur + pos, DUMMY_LEAF)
            ln = jnp.where(valid, nodes + 2 * pos, DUMMY_NODE)
            rn = jnp.where(valid, nodes + 2 * pos + 1, DUMMY_NODE)
            parent = jnp.where(valid, jnp.take(t.leaf2node, sel_s),
                               DUMMY_NODE)
            sfeat = _ov(sfeat, f_feat)
            sthr = _ov(sthr, f_thr)
            # numerical: missing left; categorical: default_left=false
            # (hpp:606) — cat routing is bitset membership anyway
            sdl = _ov(sdl, ~f_cat)
            scat = _ov(scat, f_cat)
            sgain = _ov(sgain, f_gain)
            slsum = slsum.at[0].set(jnp.where(ok_f, lsum, slsum[0]))
            srsum = srsum.at[0].set(jnp.where(ok_f, rsum, srsum[0]))
            # categorical LEFT subset = the single forced category bin
            f_bits = jnp.where(
                f_cat & (jnp.arange(BW, dtype=jnp.int32) == (f_thr >> 5)),
                jnp.uint32(1) << (f_thr & 31).astype(jnp.uint32),
                jnp.uint32(0))
            sbits = sbits.at[0].set(jnp.where(ok_f, f_bits, sbits[0]))
            lval = _ov(lval, f_lout)
            rval = _ov(rval, f_rout)

        if use_mono_inter:
            # stale-cache guard: neighbor propagation may have tightened
            # this leaf's bounds after its split was cached; clamp into
            # the CURRENT bounds (the reference instead recomputes best
            # splits for every leaf in `leaves_to_update_`)
            lo_s = jnp.take(st["leaf_lo"], sel_s)
            hi_s = jnp.take(st["leaf_hi"], sel_s)
            lval = jnp.clip(lval, lo_s, hi_s)
            rval = jnp.clip(rval, lo_s, hi_s)
        if use_mono_adv:
            # stale-cache guard, advanced form: recompute the bounds at
            # the WINNING (feature, threshold) against current outputs
            advw, lo_sw, hi_sw = adv_bounds_for(
                sel_s, t, st["box_lo"], st["box_hi"])

            def _at_win(a):
                af = jnp.take_along_axis(
                    a, sfeat[:, None, None], axis=1)[:, 0, :]
                return jnp.take_along_axis(af, sthr[:, None],
                                           axis=1)[:, 0]
            lo_lw = jnp.where(scat, lo_sw, _at_win(advw[0]))
            hi_lw = jnp.where(scat, hi_sw, _at_win(advw[1]))
            lo_rw = jnp.where(scat, lo_sw, _at_win(advw[2]))
            hi_rw = jnp.where(scat, hi_sw, _at_win(advw[3]))
            lval = jnp.clip(lval, lo_lw, hi_lw)
            rval = jnp.clip(rval, lo_rw, hi_rw)
            # re-impose the split feature's own direction if clamping
            # crossed the pair (conflicting fresh constraints; rare)
            mt_w = jnp.take(mono_type_pf, sfeat)
            lo_pair = jnp.minimum(lval, rval)
            hi_pair = jnp.maximum(lval, rval)
            lval = jnp.where(mt_w > 0, lo_pair,
                             jnp.where(mt_w < 0, hi_pair, lval))
            rval = jnp.where(mt_w > 0, hi_pair,
                             jnp.where(mt_w < 0, lo_pair, rval))

        # -- 2. record splits in node arrays
        stg(PHS.APPLY)
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
            leaf2node=t.leaf2node.at[sel_s].set(ln).at[right_slot].set(rn),
            leaf_values=t.leaf_values.at[sel_s].set(lval)
                                     .at[right_slot].set(rval),
            num_leaves=cur + n_valid,
            num_nodes=nodes + 2 * n_valid,
        )
        new_depth = jnp.take(st["leaf_depth"], sel_s) + 1
        leaf_depth = st["leaf_depth"].at[sel_s].set(new_depth) \
                                     .at[right_slot].set(new_depth)

        # -- 2b. monotone bound propagation (BasicLeafConstraints::Update,
        # monotone_constraints.hpp:488-504): numerical splits on constrained
        # features tighten children's bounds around the output midpoint
        leaf_lo, leaf_hi = st["leaf_lo"], st["leaf_hi"]
        new_state_mono = {}
        if use_mono and not use_boxes:
            mid = (lval + rval) * 0.5
            mt_s = jnp.take(mono_type_pf, sfeat)
            upd = valid & (~scat) & (mt_s != 0)
            lo_p = jnp.take(leaf_lo, sel_s)
            hi_p = jnp.take(leaf_hi, sel_s)
            hi_l = jnp.where(upd & (mt_s > 0), jnp.minimum(hi_p, mid), hi_p)
            lo_l = jnp.where(upd & (mt_s < 0), jnp.maximum(lo_p, mid), lo_p)
            lo_r = jnp.where(upd & (mt_s > 0), jnp.maximum(lo_p, mid), lo_p)
            hi_r = jnp.where(upd & (mt_s < 0), jnp.minimum(hi_p, mid), hi_p)
            leaf_lo = leaf_lo.at[sel_s].set(lo_l).at[right_slot].set(lo_r) \
                             .at[DUMMY_LEAF].set(-F32_MAX)
            leaf_hi = leaf_hi.at[sel_s].set(hi_l).at[right_slot].set(hi_r) \
                             .at[DUMMY_LEAF].set(F32_MAX)
        if use_boxes:
            # maintain leaf boxes (shared by intermediate + advanced)
            box_lo, box_hi = st["box_lo"], st["box_hi"]
            num_upd = (valid & ~scat)[:, None]                   # [W, 1]
            par_lo = jnp.take(box_lo, sel_s, axis=0)             # [W, F]
            par_hi = jnp.take(box_hi, sel_s, axis=0)
            fone = jnp.arange(F, dtype=jnp.int32)[None, :] == sfeat[:, None]
            l_hi = jnp.where(fone & num_upd,
                             jnp.minimum(par_hi, sthr[:, None]), par_hi)
            r_lo = jnp.where(fone & num_upd,
                             jnp.maximum(par_lo, sthr[:, None] + 1), par_lo)
            box_lo = box_lo.at[sel_s].set(par_lo).at[right_slot].set(r_lo)
            box_hi = box_hi.at[sel_s].set(l_hi).at[right_slot].set(par_hi)
            box_lo = box_lo.at[DUMMY_LEAF].set(0)
            box_hi = box_hi.at[DUMMY_LEAF].set(B - 1)
            new_state_mono = dict(box_lo=box_lo, box_hi=box_hi)
        if use_mono_inter:
            # -- intermediate mode (module note above): push the new
            # outputs onto every adjacent leaf. The right child first
            # CLONES the parent's accumulated bounds
            # (entries_[new_leaf].reset(entries_[leaf]->clone()),
            # monotone_constraints.hpp:548) — its region is a subset of
            # the parent's, so every constraint on the parent applies.
            lo_p = jnp.take(leaf_lo, sel_s)
            hi_p = jnp.take(leaf_hi, sel_s)
            leaf_lo = leaf_lo.at[right_slot].set(lo_p)
            leaf_hi = leaf_hi.at[right_slot].set(hi_p)

            # neighbor updates (GoUp/GoDownToFindLeavesToUpdate analog,
            # monotone_constraints.hpp:624-805, exact-geometry form):
            # for new leaf u and any live leaf v separated along exactly
            # monotone dim q, v's output bound absorbs u's output.
            # Covers the sibling too (separated along the split feature),
            # which reproduces UpdateConstraintsWithOutputs (:545-558).
            u_slots = jnp.concatenate([sel_s, right_slot])       # [2W]
            u_out = jnp.concatenate([lval, rval])
            u_ok = jnp.concatenate([valid, valid])
            u_lo = jnp.take(box_lo, u_slots, axis=0)             # [2W, F]
            u_hi = jnp.take(box_hi, u_slots, axis=0)
            ovl = ((box_lo[None, :, :] <= u_hi[:, None, :])
                   & (u_lo[:, None, :] <= box_hi[None, :, :]))   # [2W,L+1,F]
            nno = jnp.sum(~ovl, axis=2)                          # [2W, L+1]
            above = box_lo[None, :, :] > u_hi[:, None, :]
            below = box_hi[None, :, :] < u_lo[:, None, :]
            m_pos = (mono_type_pf > 0)[None, None, :]
            m_neg = (mono_type_pf < 0)[None, None, :]
            live = jnp.take(t.leaf2node, jnp.arange(L + 1)) != DUMMY_NODE
            cond = ((nno == 1)[:, :, None] & (~ovl)
                    & u_ok[:, None, None] & live[None, :, None])
            raise_lo = (cond & ((above & m_pos) | (below & m_neg))) \
                .any(axis=2)                                     # [2W, L+1]
            drop_hi = (cond & ((below & m_pos) | (above & m_neg))) \
                .any(axis=2)
            leaf_lo = jnp.maximum(
                leaf_lo, jnp.where(raise_lo, u_out[:, None], -F32_MAX)
                .max(axis=0))
            leaf_hi = jnp.minimum(
                leaf_hi, jnp.where(drop_hi, u_out[:, None], F32_MAX)
                .min(axis=0))
            leaf_lo = leaf_lo.at[DUMMY_LEAF].set(-F32_MAX)
            leaf_hi = leaf_hi.at[DUMMY_LEAF].set(F32_MAX)

        # -- 2c. CEGB bookkeeping (UpdateLeafBestSplits): applied splits
        # mark their feature model-used (coupled) and their leaf's rows
        # feature-seen (lazy)
        new_state_extra = {}
        if use_cegb:
            fu = st["cegb_feat_used"]
            fbit_c = jnp.any((jnp.arange(F)[None, :] == sfeat[:, None])
                             & valid[:, None], axis=0)
            new_state_extra["cegb_feat_used"] = fu | fbit_c
        if use_inter:
            uf = st["used_feat"]
            parent_used = jnp.take(uf, sel_s, axis=0)            # [W, F]
            fbit = ((jnp.arange(F)[None, :] == sfeat[:, None])
                    & valid[:, None])
            new_used = parent_used | fbit
            uf = uf.at[sel_s].set(new_used).at[right_slot].set(new_used) \
                   .at[DUMMY_LEAF].set(False)
            new_state_extra["used_feat"] = uf

        # -- 3. partition update (DataPartition::Split analog): a dense
        # relabel of row_leaf. What a row reads of its leaf's pending
        # split is selected from the round's W records by comparing
        # row_leaf with the W slots (select_by_slot), never gathered
        # from a per-leaf table: on the chip a gathered element costs
        # 9.7 ns, a fused compare-select pass 0.13 ns a row (PERF.md
        # section 6, PR 29); ops/predict.py row_feature_gather reads
        # the row's bin by the same rule.
        snan = jnp.take(nan_bin_pf, sfeat)
        if use_native_part:
            # the native CPU custom calls (lgbtpu_partition for the
            # train matrix, lgbtpu_relabel for valid matrices) take the
            # records as [L+1] tables: a row whose leaf is not
            # splitting short-circuits after a 4-byte read
            pend_active = jnp.zeros((L + 1,), bool).at[sel_s].set(valid) \
                .at[DUMMY_LEAF].set(False)
            pend_feat = jnp.zeros((L + 1,), jnp.int32).at[sel_s].set(sfeat)
            pend_thr = jnp.zeros((L + 1,), jnp.int32).at[sel_s].set(sthr)
            pend_dl = jnp.zeros((L + 1,), bool).at[sel_s].set(sdl)
            pend_cat = jnp.zeros((L + 1,), bool).at[sel_s].set(scat)
            pend_right = jnp.zeros((L + 1,), jnp.int32) \
                .at[sel_s].set(right_slot)
            pend_bits = jnp.zeros((L + 1, BW), jnp.uint32) \
                .at[sel_s].set(sbits)

        def relabel(bmat, rl):
            if use_native_part:
                # only VALID matrices come here: the train matrix goes
                # through lgbtpu_partition. The matrix may be narrower
                # than the padded per-feature metadata
                # (feature-parallel pads the TRAIN matrix's feature
                # axis; valid matrices stay unpadded)
                F_mat = bmat.shape[1]
                out = jax.ffi.ffi_call(
                    "lgbtpu_relabel",
                    jax.ShapeDtypeStruct(rl.shape, jnp.int32))(
                    bmat, rl.astype(jnp.int32),
                    pend_active, pend_feat, pend_thr, pend_dl, pend_cat,
                    pend_right, pend_bits,
                    nan_bin_pf[:F_mat].astype(jnp.int32),
                    col_major=False)
                if axis_name is not None:
                    out = _pvary(out, axis_name)
                return out
            return relabel_rows(
                bmat, rl, sel_s, valid, sfeat, sthr, sdl, scat, right_slot,
                snan, sbits, bin_records(sfeat), feature_bin_of)

        new_state_part = {}
        part_n = None
        if use_native_part:
            # DataPartition::Split as one custom call: stable in-place
            # partition of each split leaf's segment; only those rows
            # are touched (and only they change row_leaf)
            mat_p = bins if bins_cm is None else bins_cm
            outs = jax.ffi.ffi_call(
                "lgbtpu_partition",
                (jax.ShapeDtypeStruct((R,), jnp.int32),
                 jax.ShapeDtypeStruct((R,), jnp.int32),
                 jax.ShapeDtypeStruct((L + 1,), jnp.int32),
                 jax.ShapeDtypeStruct((L + 1,), jnp.int32)),
                # donate the carry buffers: the handler partitions the
                # split segments in place instead of copying 2x[R]
                input_output_aliases={1: 0, 2: 1, 3: 2, 4: 3})(
                mat_p, st["row_leaf"].astype(jnp.int32), st["perm"],
                st["leaf_begin"], st["leaf_cnt"], pend_active,
                pend_feat, pend_thr, pend_dl, pend_cat, pend_right,
                pend_bits, nan_bin_pf.astype(jnp.int32),
                col_major=bins_cm is not None)
            if axis_name is not None:
                outs = tuple(_pvary(o, axis_name) for o in outs)
            row_leaf, perm_n, lb_n, lc_n = outs
            part_n = (perm_n, lb_n, lc_n)
            new_state_part = dict(perm=perm_n, leaf_begin=lb_n,
                                  leaf_cnt=lc_n)
        else:
            row_leaf = relabel(bins, st["row_leaf"])
        valid_row_leaf = tuple(
            relabel(vb, vrl)
            for vb, vrl in zip(valid_bins, st["valid_row_leaf"]))

        if use_cegb and cegb_lazy is not None:
            # rows of split leaves have now "paid" for their feature
            act_r, (f_r,) = select_by_slot(st["row_leaf"], sel_s, valid,
                                           [sfeat])
            ur = st["cegb_used_rows"]
            cur = ur[jnp.arange(R), f_r]
            new_state_extra["cegb_used_rows"] = ur.at[
                jnp.arange(R), f_r].set(cur | act_r)

        # -- 4. children histograms. hist_sub: the SMALLER child (by raw
        # row count — that is what bounds the stream) is histogrammed
        # directly over a compacted, dynamically-bounded row stream; the
        # sibling is parent minus child from the raw cache
        # (serial_tree_learner.cpp:567-592 Subtract). Otherwise both
        # children are histogrammed directly over all R rows.
        slots2w = jnp.concatenate([jnp.where(valid, sel_s, -2),
                                   jnp.where(valid, right_slot, -2)])
        new_state_hist = {}
        slots2w_c = jnp.where(slots2w >= 0, slots2w, DUMMY_LEAF)
        depth2w = jnp.take(leaf_depth,
                           jnp.concatenate([sel_s, right_slot]))
        keyr = (jax.random.fold_in(rng_key, st["r"] + 1)
                if rng_key is not None else None)
        mid_state = dict(leaf_lo=leaf_lo, leaf_hi=leaf_hi,
                         **new_state_extra, **new_state_mono)
        valid2w = jnp.concatenate([valid, valid])
        # rows this round's histogram stream is bounded by, and the
        # stream positions it touches (RoundLog)
        rows_r = stream_r = R_i32
        if hist_sub:
            stg(PHS.COUNT)
            # the native partition maintains the counts
            small_is_left, small_loc = small_child(
                row_leaf, sel_s, right_slot,
                leaf_cnt=lc_n if use_native_part else None)
            small_slots = jnp.where(
                valid, jnp.where(small_is_left, sel_s, right_slot), -2)
            if hist_compact:
                stg(PHS.COMPACT)
                c_idx, n_small = compact_small(row_leaf, small_slots)
                rows_r = n_small
                stream_r = stream_rows_for(n_small)
                hsmall = hist_raw_for(small_slots, row_leaf,
                                      row_gather=c_idx, num_rows=n_small)
            else:
                # the partition's exact row lists (native): this shard's
                # rows of the small children
                rows_r = jnp.where(valid, small_loc, 0).sum() \
                    .astype(jnp.int32)
                hsmall = hist_raw_for(small_slots, row_leaf, part=part_n)
            stg(PHS.SUBTRACT)
            parent_raw = jnp.take(st["hist_cache"],
                                  jnp.clip(sel_s, 0, L), axis=0)
            hbig = parent_raw - hsmall
            sil = small_is_left.reshape((W,) + (1,) * (hsmall.ndim - 1))
            left_raw = jnp.where(sil, hsmall, hbig)
            right_raw = jnp.where(sil, hbig, hsmall)
            new_state_hist["hist_cache"] = st["hist_cache"] \
                .at[jnp.where(valid, sel_s, DUMMY_LEAF)].set(left_raw) \
                .at[jnp.where(valid, right_slot, DUMMY_LEAF)] \
                .set(right_raw)
            hist2w = hist_finish(jnp.concatenate([left_raw, right_raw]))
        else:
            hist2w = hist_for(slots2w, row_leaf, part=part_n)
        stg(PHS.FIND)
        bs = best_for(hist2w, depth2w, valid2w,
                      slots2w_c, t, mid_state, keyr, rl=row_leaf)

        scatter_slots = slots2w_c
        bs_gain = st["bs_gain"].at[scatter_slots].set(bs["gain"]) \
                               .at[DUMMY_LEAF].set(NEG_INF)
        bs_feat = st["bs_feat"].at[scatter_slots].set(bs["feature"])
        bs_thr = st["bs_thr"].at[scatter_slots].set(bs["threshold"])
        bs_dl = st["bs_dl"].at[scatter_slots].set(bs["default_left"])
        bs_cat = st["bs_cat"].at[scatter_slots].set(bs["is_cat_split"])
        bs_left = st["bs_left"].at[scatter_slots].set(bs["left_sum"])
        bs_right = st["bs_right"].at[scatter_slots].set(bs["right_sum"])
        bs_bits = st["bs_bits"].at[scatter_slots].set(bs["cat_bitset"])
        bs_lout = st["bs_lout"].at[scatter_slots].set(bs["left_out"])
        bs_rout = st["bs_rout"].at[scatter_slots].set(bs["right_out"])

        out = dict(tree=t, row_leaf=row_leaf, valid_row_leaf=valid_row_leaf,
                   bs_gain=bs_gain, bs_feat=bs_feat, bs_thr=bs_thr,
                   bs_dl=bs_dl, bs_cat=bs_cat, bs_left=bs_left,
                   bs_right=bs_right, bs_bits=bs_bits, bs_lout=bs_lout,
                   bs_rout=bs_rout,
                   leaf_depth=leaf_depth, leaf_lo=leaf_lo, leaf_hi=leaf_hi,
                   r=st["r"] + 1,
                   round_rows=st["round_rows"].at[st["r"]].set(rows_r),
                   round_stream=st["round_stream"].at[st["r"]].set(stream_r),
                   round_leaves=st["round_leaves"].at[st["r"]].set(n_valid),
                   **new_state_extra, **new_state_mono,
                   **new_state_forced, **new_state_hist,
                   **new_state_part)
        return out

    state = jax.lax.while_loop(cond, body, state)
    rounds = RoundLog(rows=state["round_rows"], leaves=state["round_leaves"],
                      stream_rows=state["round_stream"])
    if use_cegb:
        cegb_out = (state["cegb_feat_used"],
                    state.get("cegb_used_rows"))
        return (state["tree"], state["row_leaf"],
                state["valid_row_leaf"], rounds, cegb_out)
    return (state["tree"], state["row_leaf"], state["valid_row_leaf"],
            rounds)


_build_tree_jit = functools.partial(
    jax.jit,
    static_argnames=("num_leaves", "leaf_batch", "max_depth", "num_bins",
                     "split_params", "axis_name", "hist_dtype", "hist_impl",
                     "block_rows", "feature_fraction_bynode",
                     "parallel_mode", "top_k", "bundle_bins", "mono_method",
                     "forced", "hist_sub", "feature_sharded",
                     "hist_merge", "n_shards"))(
    _build_tree_impl)


def _build_tree_class_batched(bins, gh, row_leaf0, num_bins_pf,
                              nan_bin_pf, is_cat_pf, feature_mask, *,
                              rng_key=None, quant_scales=None,
                              forced=None, cegb=None,
                              hist_impl: str = "scatter", **kw):
    """Class-batched tree growth (ISSUE 8): all K per-class trees of one
    boosting iteration out of ONE staged program, by vmapping the
    leaf-wise core over the class axis.

    ``gh`` is [K, R, 3]; ``rng_key`` (when per-node sampling or
    extra-trees is on) is [K, 2] per-class keys — fold_in(it) then
    fold_in(k), the exact keys the sequential loop consumes; and
    ``quant_scales`` (quantized training) is [K, 2]. Everything else —
    the bin matrix, feature metadata, the root ``row_leaf0``, the valid
    sets — is identical across classes and rides unbatched, closed over
    by the vmapped function.

    Under vmap the class axis FUSES into the existing leaf-slot axis at
    every kernel instead of replaying the chain K times: the histogram
    one-hot/scatter/Pallas paths each lower to a single kernel whose
    slot dimension is K·S wide (the matmul becomes one dot_general with
    a K batch dim — one MXU dispatch per build round), ``lax.top_k``
    leaf selection, the partition relabel, and split finding batch
    elementwise, and the K per-class ``lax.while_loop``s collapse into
    ONE batched loop running max-over-classes rounds — a finished
    class's cond goes False and its carried state freezes, which is
    exactly the sequential fixed point (bit-parity verified in
    tests/test_class_batch.py). Data-parallel meshes compose: the
    histogram merge collective (psum / psum_scatter) and the
    ``_sync_best`` winner merge batch through their vmap rules with
    bytes-per-class unchanged.

    Returns (TreeArrays with a leading K on every field, row_leaf
    [K, R], valid_row_leafs tuple of [K, Rv] arrays, RoundLog with a
    leading K).

    Not batchable here (callers gate these to the sequential path):
    forced splits and CEGB (cross-tree host state), and the native FFI
    kernels (no vmap rule over custom calls; ``build_tree`` remaps
    native -> scatter, which is bit-identical by the native parity
    tests).
    """
    if forced is not None:
        raise ValueError(
            "class-batched build does not support forced splits; use "
            "the sequential per-class path (class_batch=off)")
    if cegb is not None:
        raise ValueError(
            "class-batched build does not support CEGB; use the "
            "sequential per-class path (class_batch=off)")
    if hist_impl == "native":
        hist_impl = "scatter"

    # Class-batched root dedupe (ISSUE 14 satellite): vmapping the core
    # makes each class's ROOT histogram launch re-stream the bins block
    # — K reads of the widest operand for K identical one-hot encodings.
    # On the Pallas serial path, build the K root histograms pre-vmap
    # with ONE kernel whose MXU N-dim is the class axis (bins read once)
    # and hand each class its slice via the builder's ``root_hist``
    # seam. Gated to plans where the root build is a plain single-device
    # Pallas launch (no EFB bundling, no mesh merge, no feature shard).
    root_hist = None
    if (hist_impl == "pallas" and kw.get("axis_name") is None
            and kw.get("bundle_meta") is None
            and kw.get("local_bins") is None
            and not kw.get("feature_sharded", False)):
        root_hist = PH.build_root_histograms_classes(
            bins, gh, row_leaf0, num_bins=kw["num_bins"],
            hist_dtype=kw.get("hist_dtype", "bfloat16"))

    def one(gh_k, key_k, qs_k, rh_k):
        return _build_tree_impl(bins, gh_k, row_leaf0, num_bins_pf,
                                nan_bin_pf, is_cat_pf, feature_mask,
                                rng_key=key_k, quant_scales=qs_k,
                                hist_impl=hist_impl, root_hist=rh_k,
                                **kw)

    return jax.vmap(
        one, in_axes=(0,
                      None if rng_key is None else 0,
                      None if quant_scales is None else 0,
                      None if root_hist is None else 0))(
        gh, rng_key, quant_scales, root_hist)


_build_tree_cb_jit = functools.partial(
    jax.jit,
    static_argnames=("num_leaves", "leaf_batch", "max_depth", "num_bins",
                     "split_params", "axis_name", "hist_dtype", "hist_impl",
                     "block_rows", "feature_fraction_bynode",
                     "parallel_mode", "top_k", "bundle_bins", "mono_method",
                     "forced", "hist_sub", "feature_sharded",
                     "hist_merge", "n_shards"))(
    _build_tree_class_batched)
