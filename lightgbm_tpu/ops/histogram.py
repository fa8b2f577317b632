"""Histogram construction — the one true hot loop.

TPU-native analog of the reference histogram kernels (LightGBM
``src/io/dense_bin.hpp`` ``ConstructHistogram``,
``src/treelearner/cuda/cuda_histogram_constructor.cu``): accumulate
(sum_grad, sum_hess, count) per (leaf, feature, bin).

Design (TPU-first, NOT a translation):
- CPUs/GPUs scatter-add into per-thread/shared-memory histograms. TPUs have
  no fast scatter; the MXU wants matmuls. We therefore compute the histogram
  as a single dense matmul per row-block:

      onehot[r, f*B + b]  = (bins[r, f] == b)                 (bf16, exact)
      ghl   [r, l*3 + c]  = (row_leaf[r] == leaf_ids[l]) * gh[r, c]
      hist  [f*B, l*3]   += onehot^T @ ghl                    (f32 accumulate)

  The leaf axis rides in the matmul N dimension: computing one leaf's
  histogram (N=3) would waste the 128-wide MXU tile, so the tree builder
  batches `leaf_batch` leaves per round and gets their histograms in the
  same pass (see boosting/tree_builder.py). This replaces the reference's
  smaller-leaf-first scheduling (serial_tree_learner.cpp:341) as the way to
  keep the hot loop saturated.
- Rows are processed in fixed-size blocks via lax.scan so the bf16 one-hot
  temporary stays bounded; all shapes static for XLA.
- Padded rows carry row_leaf == -1 and never match a leaf id.
- ops/pallas_histogram.py generates the one-hot in VMEM (skipping the
  HBM round-trip) and is what ``auto`` picks on a TPU; this XLA
  formulation is the portable baseline, the semantics oracle for it,
  and what ``auto`` picks by rule for lattices the kernel cannot take.
- Class batching (``class_batch``, boosting/tree_builder.py
  ``_build_tree_class_batched``): the multiclass trainer vmaps the whole
  build over the class axis, so these kernels run under a batching
  trace. The matmul path's ``ghl`` gains a leading K and the contraction
  becomes one batched matmul — effectively folding class into the
  leaf-slot (N) dimension, hist [K, F·B, S·3] from ONE dispatch with K×
  the MXU work per dispatch instead of K sequential calls. The scatter
  path batches the same way (one scatter-add with a class index axis).
  On one device a round's compacted stream (``row_gather``) runs one
  class at a time instead (``tree_builder._class_by_class``), whatever
  the impl: batched, the Pallas stream's lane-major operand buffers
  would be K copies of the bin matrix. The root pass of a Pallas build
  is one call for all K (``pallas_histogram.build_root_histograms_classes``,
  its custom call named ``HIST_CLASS_ROOT_NAME`` so that a trace finds
  it as the program's kernel outside every loop).
  The ``native`` FFI kernel has no vmap rule — the class-batched entry
  remaps native→scatter (bit-identical; see tests/test_histogram.py
  native↔scatter parity). ``merge_histograms`` collectives batch too:
  psum / psum_scatter carry [K, ...] operands in one collective, so
  cross-chip bytes per class are unchanged while the dispatch count
  drops K×.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

import numpy as np

from .. import phases, profiler

__all__ = ["build_histograms", "resolve_impl", "pallas_shape_reason",
           "merge_histograms", "stream_chunk_rows", "stream_trips",
           "kernel_plan", "effective_impl", "HIST_CH"]

# channels per histogram cell: (sum_grad, sum_hess, count)
HIST_CH = 3


def merge_histograms(hist: jax.Array, axis_name: Optional[str],
                     merge="allreduce", n_shards: int = 1) -> jax.Array:
    """Cross-shard merge of a ``[L, F, B, CH]`` histogram — the
    ``Network::ReduceScatter`` analog (data_parallel_tree_learner.cpp:284),
    factored out so every kernel path (matmul/scatter/native/pallas) and
    the tree builder's EFB-unbundled merge share ONE implementation.

    ``merge`` selects the collective:
    - ``False`` / ``"none"``: no collective — the histogram stays
      shard-local (feature/voting-parallel merge selectively later).
    - ``True`` / ``"allreduce"``: ``lax.psum`` — every shard receives the
      full merged histogram (replicated split finding; ~2x the wire
      bytes of reduce-scatter and n-redundant downstream work).
    - ``"reduce_scatter"``: ``lax.psum_scatter`` along the feature axis
      (dim 1, padded to a multiple of ``n_shards``): shard k receives
      ONLY its ``F_pad/n`` feature-slot block ``[k*F_pad/n, (k+1)*F_pad/n)``
      of the merged histogram — the reference's true per-worker
      feature-block merge. Split finding then runs on the local block
      and winners sync SplitInfo-sized (see tree_builder._sync_best).

    The collective is staged under the ``hist_merge`` named scope, so
    the stage map attributes its device time and the collective-traffic
    auditor (parallel/comms.py) can attribute histogram collectives by
    the ``hist_merge`` op-name prefix.
    """
    if axis_name is None or merge in (False, "none", None):
        return hist
    with profiler.stage(phases.HIST_MERGE):
        if merge == "reduce_scatter":
            F = hist.shape[1]
            F_pad = -(-F // n_shards) * n_shards
            if F_pad != F:
                cfg = [(0, 0)] * hist.ndim
                cfg[1] = (0, F_pad - F)
                hist = jnp.pad(hist, cfg)
            return jax.lax.psum_scatter(hist, axis_name,
                                        scatter_dimension=1, tiled=True)
        return jax.lax.psum(hist, axis_name)


def _pick_block_rows(num_rows: int, fb: int, dtype_bytes: int = 2,
                     budget_bytes: int = 1 << 26) -> int:
    """Row-block size so the one-hot temp stays ~<= budget (64MB)."""
    blk = budget_bytes // max(1, fb * dtype_bytes)
    blk = int(2 ** np.floor(np.log2(max(blk, 256))))
    blk = min(blk, 1 << 16)
    # avoid degenerate tiny blocks
    return max(blk, 256)


def block_rows_for(num_rows: int, num_features: int, num_bins: int) -> int:
    return _pick_block_rows(num_rows, num_features * num_bins)


def _resolve_block_rows(R: int, F: int, B: int, block_rows: int) -> int:
    """The row block :func:`build_histograms` runs with."""
    if block_rows <= 0:
        block_rows = _pick_block_rows(R, F * B)
    if R % block_rows != 0:
        # fall back: single block (caller should pad; keeps jit legal)
        block_rows = R
    return block_rows


def stream_trips(num_rows, chunk: int, R: int):
    """Trips of a loop that walks a stream of ``num_rows`` live
    positions (traced) ``chunk`` at a time, of the ``ceil(R / chunk)``
    the whole stream has."""
    return jnp.clip((num_rows + chunk - 1) // chunk, 0, -(-R // chunk))


def effective_impl(impl: str, num_bins: int) -> str:
    """:func:`resolve_impl`, and ``scatter`` for a ``native`` whose C
    toolchain is missing (what every ``native`` path degrades to). The
    call also compiles and REGISTERS the FFI targets."""
    impl = resolve_impl(impl, num_bins)
    if impl == "native":
        from .. import native as _native
        if _native.hist_lib() is None:
            return "scatter"
    return impl


def kernel_plan(impl: str, R: int, F: int, num_bins: int, num_slots: int,
                gh_dtype, hist_dtype: str, block_rows: int = 0):
    """``(row_block, feature_chunk, n_chunks, padded_bins, lanes)`` of a
    :func:`build_histograms` call with these static arguments: the Pallas
    kernel's own plan (``pallas_histogram._plan``); for matmul and scatter
    the block loop's row block over one chunk of all ``F`` columns at
    ``num_bins`` wide; for native a row block of 1 (the C loop stops at
    its last row). A call multiplies (or adds into) ``rows covered x
    n_chunks x feature_chunk x padded_bins`` one-hot elements, rows
    covered being its live rows rounded up to the row block."""
    impl = effective_impl(impl, num_bins)
    if impl == "pallas":
        from . import pallas_histogram as PH
        cdt, _ = PH._kernel_dtypes(gh_dtype, hist_dtype)
        return PH._plan(F, num_bins, num_slots * HIST_CH,
                        jnp.dtype(cdt).itemsize)
    blk = 1 if impl == "native" else _resolve_block_rows(
        R, F, num_bins, block_rows)
    return blk, F, 1, num_bins, num_slots * HIST_CH


def stream_chunk_rows(impl: str, R: int, F: int, num_bins: int,
                      num_slots: int, gh_dtype, hist_dtype: str,
                      block_rows: int = 0) -> int:
    """Rows per trip of the loop that bounds :func:`build_histograms`'
    compacted stream (``row_gather`` + ``num_rows``) for these static
    arguments: a round touches ``stream_trips(num_rows, chunk, R) *
    chunk`` stream positions. The row block for matmul and scatter, the
    layout loop's chunk for pallas, R for native (the C kernel stops at
    ``num_rows`` itself; the wrapper's compaction before it does not)."""
    impl = effective_impl(impl, num_bins)
    if impl == "native":
        return R
    blk = kernel_plan(impl, R, F, num_bins, num_slots, gh_dtype,
                      hist_dtype, block_rows)[0]
    if impl == "pallas":
        from . import pallas_histogram as PH
        return PH.stream_chunk(R, blk)
    return blk


def _pvary(x, axis_name):
    """Mark a scan carry as varying over a shard_map axis (no-op when
    it already is — pcast rejects varying->varying)."""
    if axis_name in jax.typeof(x).vma:
        return x
    return jax.lax.pcast(x, axis_name, to="varying")


# The Pallas kernel's plan pads a feature's one-hot rows to a power of
# two up to 256, the range of the uint8 bin ids the trainer stores
# (ops/pallas_histogram.py ``_plan`` and module docstring).
PALLAS_MAX_BINS = 256


def pallas_shape_reason(num_bins: int) -> str:
    """Why the Pallas kernel cannot take a ``num_bins``-wide lattice
    ('' = it can). The rule ``resolve_impl`` applies under ``auto`` and
    the message an explicit ``hist_impl=pallas`` raises with."""
    if num_bins > PALLAS_MAX_BINS:
        return (f"num_bins={num_bins} > {PALLAS_MAX_BINS} (the kernel's "
                "plan holds uint8 bin ids: at most 256 one-hot rows a "
                "feature)")
    return ""


def resolve_impl(impl: str, num_bins: int) -> str:
    """Resolve ``hist_impl='auto'`` to a concrete kernel from the
    backend and the lattice width alone — no probe compile, no
    fallback: a kernel this rule picks either compiles or its compile
    error propagates.

    - tpu: ``pallas``, or ``matmul`` when :func:`pallas_shape_reason`
      names a reason (GBDT surfaces it as ``hist_impl_reason``);
    - cpu: ``native`` (the runtime-compiled C kernel, native/hist.c —
      dense_bin.hpp ConstructHistogram cache locality, ~5x the XLA
      scatter) when a C toolchain built it, else ``scatter``;
    - anything else: ``matmul``.

    ``num_bins`` is the width of the lattice the histogram is built in
    (the bundle width under EFB)."""
    if impl != "auto":
        return impl
    backend = jax.default_backend()
    if backend == "cpu":
        from .. import native as _native
        if _native.hist_lib() is not None:
            return "native"
        return "scatter"     # XLA lowers the scatter to per-row adds
    if backend == "tpu" and not pallas_shape_reason(num_bins):
        return "pallas"
    return "matmul"


@functools.partial(
    jax.jit,
    static_argnames=("num_bins", "block_rows", "axis_name", "hist_dtype",
                     "impl", "merge", "n_shards"))
def build_histograms(bins: jax.Array, gh: jax.Array, row_leaf: jax.Array,
                     leaf_ids: jax.Array, *, num_bins: int,
                     block_rows: int = 0, axis_name: Optional[str] = None,
                     hist_dtype: str = "bfloat16",
                     impl: str = "auto", merge=True,
                     n_shards: int = 1,
                     row_gather: Optional[jax.Array] = None,
                     num_rows: Optional[jax.Array] = None,
                     init: Optional[jax.Array] = None) -> jax.Array:
    """Accumulate per-(leaf, feature, bin) sums of (grad, hess, count).

    Args:
      bins: [R, F] integer bin matrix (uint8/int32). R must be divisible by
        block_rows (caller pads; padded rows have row_leaf == -1).
      gh: [R, 3] float32 — (grad, hess, 1.0) per row; zeros for padded rows.
      row_leaf: [R] int32 current leaf slot per row (-1 = padded/dead).
      leaf_ids: [L] int32 leaf slots to build histograms for. Use a negative
        sentinel (-2) for unused slots — matches nothing.
      num_bins: static B (max bins over features).
      axis_name: if inside shard_map over a row-sharded mesh axis, the
        mapped axis name; histograms are merged over it per ``merge``
        (see :func:`merge_histograms`) — ``True``/``"allreduce"`` is the
        replicated psum, ``"reduce_scatter"`` the feature-slot-scattered
        ``lax.psum_scatter`` (the reference's true
        ``Network::ReduceScatter`` per-worker feature-block merge,
        data_parallel_tree_learner.cpp:284; result is ``[L, F_pad/n, B,
        CH]`` with ``n = n_shards``). With ``merge=False`` the result
        stays shard-LOCAL (feature/voting-parallel modes merge
        selectively later) but scan carries are still marked varying.
      impl: "matmul" (MXU one-hot formulation), "scatter" (XLA
        scatter-add), "native" (the C kernel as an XLA FFI custom call
        on CPU — the true dense_bin.hpp:105 sequential pass; bit-equal
        to scatter), "pallas" (fused TPU kernel; num_bins <= 256), or
        "auto" (:func:`resolve_impl`'s rule). All produce identical
        histograms up to f32 accumulation order.

    Quantized mode (gradient_discretizer.hpp:22 + the packed int16/int32
    histograms of cuda_histogram_constructor.cu): when ``gh`` is int8
    (stochastically-rounded grid values from GBDT._quantize_impl), the
    matmul runs as an int8 x int8 -> int32 MXU dot and the returned
    histogram is **int32** — exact integer accumulation (deterministic
    psum merge as a bonus). The caller descales the tiny [L, F, B, 3]
    result once before split finding (FindBestThresholdInt,
    feature_histogram.hpp:177, does the same descale during its bin
    scan). The bandwidth win lands where it matters: the one-hot temp
    drops bf16->int8 (2x) and gh f32->int8 (4x) in the R-sized hot
    stream. int32 accumulation bounds: |sum| <= R_leaf * nb/2 — checked
    host-side in GBDT (the analog of the reference's per-leaf
    int16->int32 escalation, which the MXU makes unnecessary).

    Dynamic row stream (the histogram-subtraction companion, VERDICT r3
    #2 — the analog of dense_bin.hpp:105 iterating ``data_indices``
    only): ``row_gather`` [R] int32 is a compacted row-index order.
    With it set, ``bins``, ``gh`` and ``row_leaf`` all arrive
    UNCOMPACTED and stream position ``p`` reads row ``row_gather[p]``
    of each; positions at or past ``num_rows`` (traced scalar) count as
    dead (leaf -1) whatever ``row_gather`` holds there. The compaction
    is this wrapper's alone and is bounded by the live rows for every
    ``impl``: matmul and scatter gather one row block per trip of a
    ``ceil(num_rows / block_rows)``-trip loop; pallas lays its
    lane-major operands out chunk by chunk in a
    ``ceil(num_rows / chunk)``-trip loop (ops/pallas_histogram.py
    ``_stream_operands``) and calls the kernel once with the same bound
    as its scalar prefetch; native compacts ``gh`` / ``row_leaf`` ahead
    of the FFI call, whose own loop stops at ``num_rows``
    (:func:`stream_chunk_rows` names each granularity). ``gh`` and
    ``row_leaf`` travel in ONE per-row table (:func:`_row_table`,
    assembled once a call: the only R-sized write), so a trip gathers
    twice, the bin rows and the table's rows, for every ``impl``. No
    R-sized gather or transpose runs on the chip's path (and no R-sized
    cast with float ``gh``). ``num_rows``
    without ``row_gather`` bounds an already-ordered stream: rows past
    it must then carry ``row_leaf == -1``. Works inside shard_map: each
    shard bounds its own stream (no collective sits inside the loops);
    the merge after the kernel re-syncs. The grow loop does not send
    every round here: with ``impl='pallas'`` on a narrow bin matrix, a
    round whose smaller children hold more rows than
    ``tree_builder.step_shape``'s ``dense_round_rows`` (a rule of the
    step's shape) skips the index and this stream and calls the kernel
    once over all rows in stored order
    (``pallas_histogram.build_histograms_pallas_dense``); the tree
    builder then merges after its choice of route, with ``merge=False``
    here.

    Carried accumulation (out-of-core, data/chunked.py): ``init``
    [L, F, B, CH] seeds the accumulator, so a row stream too large for
    device memory can be fed chunk by chunk — chunk k's result becomes
    chunk k+1's ``init``. On the matmul and scatter paths the seed IS
    the internal scan carry (re-laid-out, not post-added), so chunked
    accumulation over aligned block boundaries is bit-identical to one
    resident pass: both already reduce block-sequentially, the seed
    just replaces the zeros block. ``block_rows`` is independent of R
    (:func:`_pick_block_rows` sizes by F*B only), so a caller that pads
    every chunk to the same ``block_rows`` multiple gets identical
    block shapes — and identical addition order — in both regimes.
    Native/pallas add ``init`` after their kernel (exact for int32
    histograms, order-shifted for f32 — the chunked driver pins
    matmul/scatter). With ``axis_name`` set, ``init`` must be the
    shard-local PRE-merge accumulator (it is added before the
    collective); the chunked driver is serial-only so this does not
    arise in practice.

    Returns: [L, F, B, 3] float32 (int32 when gh is int8).
    """
    R, F = bins.shape
    B = num_bins
    block_rows = _resolve_block_rows(R, F, B, block_rows)
    impl = resolve_impl(impl, B)

    if impl == "pallas":
        from .pallas_histogram import build_histograms_pallas
        # stages hist_gather, hist_relayout and hist_kernel inside
        hist = build_histograms_pallas(
            bins, gh, row_leaf, leaf_ids, num_bins=B,
            hist_dtype=hist_dtype, num_rows=num_rows,
            row_gather=row_gather)
        if init is not None:
            hist = hist + init
        # honor merge=False: feature-parallel slots are feature-disjoint
        # and voting merges elected columns itself — an unconditional
        # psum here was a pure-waste no-op for the former and would
        # double-count for the latter
        return merge_histograms(hist, axis_name, merge, n_shards)

    # every other formulation is one stage: the block loop over the row
    # stream IS the kernel (its per-block gather keeps its own name)
    with profiler.stage(phases.HIST_KERNEL):
        return _build_histograms_xla(
            bins, gh, row_leaf, leaf_ids, B, impl, block_rows, hist_dtype,
            axis_name, merge, n_shards, row_gather, num_rows, init)


def _row_table(gh, row_leaf, acc_dt):
    """``[R, HIST_CH + 1]`` int32 per-row table of a compacted stream:
    channels 0..2 are the 32-bit words of ``gh.astype(acc_dt)`` (the
    accumulator's dtype, the cast the kernel's addends get anyway: it
    commutes with a gather), channel 3 is the row's leaf. One index
    selects both (:func:`_gather_rows`), so a stream position costs one
    gather for them and not two. The words travel as integers because
    XLA:TPU writes a concatenate as ``maximum`` over padded operands:
    exact on any int32, not on the float a leaf id's bits would spell
    (a denormal or a NaN). Assembled once a call, outside every chunk /
    block loop: the one R-sized write a round the stream makes."""
    words = gh.astype(acc_dt)
    if acc_dt != jnp.int32:
        words = jax.lax.bitcast_convert_type(words, jnp.int32)
    return jnp.concatenate([words, row_leaf.astype(jnp.int32)[:, None]],
                           axis=1)


def _gather_rows(table, idx, start, num_rows, acc_dt):
    """``gh`` (as ``acc_dt``) and ``row_leaf`` of the stream positions
    ``start + arange(len(idx))``, which read rows ``idx`` of
    :func:`_row_table`'s table in ONE ``take``; positions at or past
    ``num_rows`` are dead (leaf -1)."""
    pos = start + jnp.arange(idx.shape[0], dtype=jnp.int32)
    piece = jnp.take(table, idx, axis=0)
    ghb = piece[:, :HIST_CH]
    if acc_dt != jnp.int32:
        ghb = jax.lax.bitcast_convert_type(ghb, acc_dt)
    return ghb, jnp.where(pos < num_rows, piece[:, HIST_CH], -1)


def _build_histograms_xla(bins, gh, row_leaf, leaf_ids, B, impl, block_rows,
                          hist_dtype, axis_name, merge, n_shards,
                          row_gather, num_rows, init):
    """The native, scatter and matmul formulations of
    :func:`build_histograms` (same contract; ``impl`` resolved and
    ``block_rows`` dividing R)."""
    R, F = bins.shape
    L = leaf_ids.shape[0]
    quant = gh.dtype == jnp.int8
    nb = R // block_rows
    cdt = jnp.dtype(hist_dtype)
    if impl == "native":
        # the C kernel as an XLA FFI custom call (CPU backend): one
        # sequential pass over the row stream at memory speed — the
        # exact dense_bin.hpp:105 shape the XLA scatter can't reach —
        # executed on XLA's compute thread (no Python, no GIL; legal
        # inside jit/while_loop/shard_map). Honors the compacted
        # dynamic row stream natively: row_gather indexes bins per
        # stream position and the loop stops at num_rows.
        from .. import native as _native
        if _native.hist_lib() is None:     # trace-time check, cached
            from .. import log as _log
            _log.warning("hist_impl='native' requested but the C "
                         "toolchain is unavailable; using 'scatter'")
            impl = "scatter"
        else:
            acc_dt_n = jnp.int32 if quant else jnp.float32
            bf16_round = bool((not quant) and cdt == jnp.bfloat16)
            has_rg = row_gather is not None
            rg_in = row_gather if has_rg else jnp.zeros((1,), jnp.int32)
            nr_in = (num_rows if num_rows is not None
                     else jnp.asarray(R, jnp.int32))
            nr_in = jnp.asarray(nr_in, jnp.int32).reshape((1,))
            if has_rg:
                # the C kernel reads gh and row_leaf by stream position
                with profiler.stage(phases.HIST_GATHER):
                    ghs, row_leaf = _gather_rows(
                        _row_table(gh, row_leaf, acc_dt_n), row_gather, 0,
                        nr_in[0], acc_dt_n)
                    gh = ghs.astype(gh.dtype)
            out_sds = jax.ShapeDtypeStruct((L, F, B, HIST_CH), acc_dt_n)
            target = "lgbtpu_hist_i8" if quant else "lgbtpu_hist_f32"
            hist = jax.ffi.ffi_call(target, out_sds)(
                bins, gh, row_leaf.astype(jnp.int32),
                leaf_ids.astype(jnp.int32), rg_in, nr_in,
                bf16_round=bf16_round, use_gather=has_rg)
            if init is not None:
                hist = hist + init
            if axis_name is not None:
                # custom-call results come back unvarying; restore the
                # manual-axis type before the merge / loop carry
                hist = _pvary(hist, axis_name)
                hist = merge_histograms(hist, axis_name, merge, n_shards)
            return hist

    # quantized addend/accumulator dtypes: int8 operands, exact int32 sums
    adt = jnp.int8 if quant else cdt
    acc_dt = jnp.int32 if quant else jnp.float32

    # dynamically-bounded stream: process only the blocks that hold live
    # rows, via fori_loop; otherwise a full static scan (cheapest trace)
    dyn = (num_rows is not None) or (row_gather is not None)
    if num_rows is not None:
        nb_used = stream_trips(num_rows, block_rows, R)
    else:
        nb_used = nb

    if row_gather is not None:
        with profiler.stage(phases.HIST_GATHER):
            table = _row_table(gh, row_leaf, acc_dt)

    def _block(i):
        s = i * block_rows
        if row_gather is not None:
            idx = jax.lax.dynamic_slice(row_gather, (s,), (block_rows,))
            with profiler.stage(phases.HIST_GATHER):
                bb = jnp.take(bins, idx, axis=0)
                ghb, lb = _gather_rows(
                    table, idx, s, R if num_rows is None else num_rows,
                    acc_dt)
            return bb, ghb, lb
        bb = jax.lax.dynamic_slice(bins, (s, 0), (block_rows, F))
        ghb = jax.lax.dynamic_slice(gh, (s, 0), (block_rows, HIST_CH))
        lb = jax.lax.dynamic_slice(row_leaf, (s,), (block_rows,))
        return bb, ghb, lb

    iota_b = jnp.arange(B, dtype=jnp.int32)

    if impl == "scatter":
        iota_f = jnp.arange(F, dtype=jnp.int32)

        def accum_scatter(acc, bb, ghb, lb):
            eq = lb[:, None] == leaf_ids[None, :]
            li = jnp.argmax(eq, axis=1)
            li = jnp.where(jnp.any(eq, axis=1), li, L)  # L = spill slot
            flat = ((li[:, None] * F + iota_f[None, :]) * B
                    + bb.astype(jnp.int32))              # [blk, F]
            # round addends exactly like the matmul path's cast chain
            if quant:
                vals = ghb.astype(jnp.int32)
            else:
                vals = ghb.astype(cdt).astype(jnp.float32)
            vals = jnp.broadcast_to(
                vals[:, None, :], (block_rows, F, HIST_CH))
            return acc.at[flat.reshape(-1)].add(
                vals.reshape(block_rows * F, HIST_CH))

        if init is not None:
            # seed the real slots, keep the spill slot zeroed — spill
            # rows are dropped below so their stale sums never surface
            acc0 = jnp.concatenate(
                [init.astype(acc_dt).reshape(L * F * B, HIST_CH),
                 jnp.zeros((F * B, HIST_CH), dtype=acc_dt)], axis=0)
        else:
            acc0 = jnp.zeros(((L + 1) * F * B, HIST_CH), dtype=acc_dt)
        if axis_name is not None:
            acc0 = _pvary(acc0, axis_name)
        if dyn:
            acc = jax.lax.fori_loop(
                0, nb_used,
                lambda i, a: accum_scatter(a, *_block(i)), acc0)
        else:
            acc, _ = jax.lax.scan(
                lambda a, xs: (accum_scatter(a, *xs), None), acc0,
                (bins.reshape(nb, block_rows, F),
                 gh.reshape(nb, block_rows, HIST_CH),
                 row_leaf.reshape(nb, block_rows)))
        hist = acc[:L * F * B].reshape(L, F, B, HIST_CH)
        return merge_histograms(hist, axis_name, merge, n_shards)

    def accum(acc, bb, ghb, lb):
        onehot = (bb.astype(jnp.int32)[:, :, None] == iota_b).astype(adt)
        onehot = onehot.reshape(block_rows, F * B)
        mask = (lb[:, None] == leaf_ids[None, :]).astype(adt)
        ghl = (mask[:, :, None] * ghb.astype(adt)[:, None, :]).reshape(
            block_rows, L * HIST_CH)
        # float32 mode must not silently drop to the MXU's bf16 passes
        prec = (jax.lax.Precision.HIGHEST if cdt == jnp.float32
                else jax.lax.Precision.DEFAULT)
        return acc + jax.lax.dot(
            onehot.T, ghl,
            precision=None if quant else prec,
            preferred_element_type=acc_dt)

    if init is not None:
        # inverse of the output layout transform below: [L,F,B,CH] ->
        # [F*B, L*CH] so the seed IS the matmul accumulator carry
        acc0 = init.astype(acc_dt).transpose(1, 2, 0, 3).reshape(
            F * B, L * HIST_CH)
    else:
        acc0 = jnp.zeros((F * B, L * HIST_CH), dtype=acc_dt)
    if axis_name is not None:
        # inside shard_map the blocked inputs vary over the mapped axis;
        # the loop carry must carry the same varying-axis type
        acc0 = _pvary(acc0, axis_name)
    if dyn:
        acc = jax.lax.fori_loop(
            0, nb_used, lambda i, a: accum(a, *_block(i)), acc0)
    else:
        acc, _ = jax.lax.scan(
            lambda a, xs: (accum(a, *xs), None), acc0,
            (bins.reshape(nb, block_rows, F),
             gh.reshape(nb, block_rows, HIST_CH),
             row_leaf.reshape(nb, block_rows)))
    hist = acc.reshape(F, B, L, HIST_CH).transpose(2, 0, 1, 3)
    # cross-chip merge over ICI — Network::ReduceScatter analog; with
    # merge="reduce_scatter" this IS a reduce-scatter and each chip
    # keeps only its feature-slot block.
    return merge_histograms(hist, axis_name, merge, n_shards)


def build_histograms_reference(bins: np.ndarray, gh: np.ndarray,
                               row_leaf: np.ndarray, leaf_ids: np.ndarray,
                               num_bins: int) -> np.ndarray:
    """NumPy oracle for tests (slow, exact)."""
    R, F = bins.shape
    L = len(leaf_ids)
    out = np.zeros((L, F, num_bins, HIST_CH), dtype=np.float64)
    for li, leaf in enumerate(leaf_ids):
        rows = np.nonzero(row_leaf == leaf)[0]
        for f in range(F):
            for r in rows:
                out[li, f, bins[r, f]] += gh[r]
    return out.astype(np.float32)
