"""Pallas TPU histogram kernel — the fused hot loop.

The XLA formulation in ops/histogram.py materializes a ``[block, F*B]``
bf16 one-hot in HBM and feeds it to the MXU; at Higgs scale that is ~14GB
of HBM traffic per histogram build (reference hot loop analog:
``src/io/dense_bin.hpp:105`` ConstructHistogram,
``src/treelearner/cuda/cuda_histogram_constructor.cu`` shared-memory
kernels). This kernel builds the one-hot *in VMEM* per (feature-chunk,
row-block) grid step, multiplies on the MXU, and accumulates into a
VMEM-resident output block — the one-hot never touches HBM.

Layout (what real Mosaic on a v5e accepts — every op in the histogram
kernel body is a 2-D elementwise op, a 2-D broadcast, an iota, one
sublane-aligned join or the one matmul; no reshape, gather or
narrow-dtype arithmetic):

- **rows ride the lane axis.** Operands arrive transposed — bins
  ``[n_chunks, fc, R]``, gh ``[3, R]``, leaf ``[1, R]`` — so every HBM
  array is dense (a ``[R, 8]`` operand would pad to 128 lanes, 16x) and
  every block's last dim is the row block (a multiple of 128), its
  second-to-last the array's full extent. Any feature-chunk width is a
  legal block; F is padded up to ``n_chunks * fc`` with zero columns
  whose histogram rows are sliced away.
- **one-hot on the VPU.** Each of the chunk's ``fc`` feature rows
  ``bins[f:f+1, blk]`` is broadcast along sublanes and compared in int32
  with the bin index of its ``Bp`` one-hot rows; the ``fc`` pieces of
  ``[Bp, blk]`` are joined at sublane offsets ``f * Bp`` (``Bp`` is a
  multiple of 8) into ``onehot[fc*Bp, blk]`` in 32 bits and narrowed to
  the matmul dtype once — no 3-D broadcast + reshape, and no MXU pass:
  copying bin ids to their one-hot rows with a 0/1 matmul costs as much
  as the histogram's own (a contraction of ``fc`` pads to the array's
  128), so the MXU does the accumulation alone. ``_plan`` sizes ``Bp``
  up to 256, the range of the uint8 bin ids, which is why the kernel
  takes ``num_bins <= 256`` only (``ops.histogram.pallas_shape_reason``).
- **addends channel-major.** Output lane ``c = channel * L + slot``;
  ``ghl[c, r] = (leaf[r] == slot_of[c]) ? gh[channel_of[c], r] : 0`` is
  two selects over a sublane-broadcast row and a lane-broadcast column,
  in 32-bit, narrowed to the matmul dtype at the end (v5e has no int8
  VPU multiply). ``3 * L`` pads to 128 lanes, so a 21- or 42-slot
  build fills one MXU column tile.
- ``hist[fc*Bp, lanes] += onehot @ ghl^T`` (contract the row axis of
  both — the q @ k^T form).

Compacted streams (``row_gather`` + ``num_rows``, what a round of the
grow loop after the root pass sends unless it takes the dense route
below): the lane-major operands are
not made from a gathered copy of the whole matrix. :func:`_stream_operands`
runs ``ceil(num_rows / chunk)`` trips of gather -> transpose ->
``dynamic_update_slice`` into the operand buffers, ``chunk`` a multiple
of the row block near R / 32. A trip gathers twice by the chunk's index:
the bin rows, and the rows of one ``[R, 4]`` int32 table that holds
``gh``'s three addends (cast already, as 32-bit words) and the row's
leaf (``ops.histogram._row_table``, assembled once a call outside the
loop: on the chip ``gh`` lies channel-major in tiles of four sublanes,
so the leaf fills the sublane that was fetched and thrown away, and the
1-D ``s32[R]`` gather, the dearest of the three there were, is gone).
The kernel is called once on the buffers with ``num_rows`` as its
scalar-prefetch bound
(:func:`build_histograms_pallas_lanes` is that call, for operands laid
out already). What lies past the last chunk written is never read.

Dense rounds: where the kernel's work a row is small next to what a
gathered position costs (a narrow bin matrix such as Higgs's 28 x 63),
a round whose smaller children hold more rows than
``tree_builder.step_shape``'s ``dense_round_rows`` skips the stream's
sort and gathers and calls the kernel once over all rows in stored
order (:func:`build_histograms_pallas_dense`), on ``bins`` and ``gh``
laid out once a tree (:func:`resident_operands`, which the root pass
reads too); rows outside the round's slots add zeros. The gate and the
threshold come from the step's shape alone.

Grid: ``(feature_chunks, row_blocks)`` with rows innermost, so each
feature chunk's accumulator stays pinned in VMEM across the whole row
stream (TPU grids execute sequentially; revisiting the same out block is
the standard reduction pattern).

Numerics match ops/histogram.py's matmul path: addends cast to
``hist_dtype`` (bf16 default), accumulation in f32 on the MXU.

Class batching: the multiclass class-batched build
(boosting/tree_builder.py ``_build_tree_class_batched``) vmaps the
whole tree build. A round's compacted stream runs one class at a time
inside it (``tree_builder._class_by_class``: batched, its operand
buffers would hold K copies of the lane-major bin matrix); a call
without a stream lowers through ``pallas_call``'s batching rule, ONE
launch whose grid gains the class axis. The root instead uses
:func:`build_root_histograms_classes`, which streams the (class-shared)
bins once for all K classes, under a name of its own,
``HIST_CLASS_ROOT_NAME`` (``pallas_hist_class_root``): a trace reader
finds the program's kernels by custom-call names that hold "pallas" and
takes one that runs outside every loop as a root pass, and without a
``name`` the call would be named after its body function.

Compile verdicts on v5e (tests/test_mosaic_aot.py compiles every
variant against a v5e topology without a chip): the histogram kernel
(f32/bf16/int8, with and without ``num_rows``, under vmap and under
shard_map with check_vma) and the class-root kernel compile.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import phases, profiler
from .histogram import (HIST_CH, _gather_rows, _row_table,
                        pallas_shape_reason, stream_trips)

__all__ = ["build_histograms_pallas", "build_histograms_pallas_lanes",
           "build_histograms_pallas_dense", "resident_operands",
           "stream_chunk", "build_root_histograms_classes",
           "HIST_KERNEL_NAME", "HIST_CLASS_ROOT_NAME"]

# The histogram kernel's custom call is named after this in the compiled
# module (``%pallas_hist_kernel.N``), whatever jit wraps it: a trace
# reader can find the kernel by a name that no refactoring moves.
HIST_KERNEL_NAME = "pallas_hist_kernel"
# The class-batched build's root pass (:func:`build_root_histograms_classes`)
# under a name of its own: a trace reader classes a custom call as the
# program's kernel by a name that holds "pallas", and without a ``name``
# the call would be named after its body function, which does not.
HIST_CLASS_ROOT_NAME = "pallas_hist_class_root"

_FB_CAP = 2048            # one-hot rows (fc * Bp) per feature chunk
_VMEM_BUDGET = 24 << 20   # what _plan sizes the row block against
_VMEM_LIMIT = 64 << 20    # Mosaic's scoped-VMEM ceiling for the kernels
                          # (v5e: 128 MiB physical, 16 MiB default scope)


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def _plan(F: int, B: int, n_cols: int, cdt_bytes: int):
    """(row_block, feature_chunk, n_chunks, padded_bins, lanes).

    ``Bp`` is the power of two >= max(B, 8) (bins >= B never match), so
    a feature's one-hot rows start at a sublane multiple ``f * Bp`` and
    ``fc * Bp`` is one. Features split into the fewest balanced chunks
    with ``fc * Bp <= _FB_CAP``. The row block is sized so the step's
    32-bit intermediates (the one-hot before it is narrowed, its compare
    mask, the selected addends — Mosaic need not materialize them all,
    the estimate assumes it does), the narrow matmul operands, the
    double-buffered inputs and the resident accumulator fit
    ``_VMEM_BUDGET``."""
    Bp = max(8, 1 << (max(B, 2) - 1).bit_length())
    n_fb = -(-F // max(1, min(F, _FB_CAP // Bp)))
    fc = -(-F // n_fb)
    lanes = _ceil_to(n_cols, 128)
    fb = fc * Bp
    per_row = ((fb + lanes) * (8 + cdt_bytes)
               + 2 * 4 * (fc + HIST_CH + 1))
    blk = (_VMEM_BUDGET - 2 * fb * lanes * 4) // per_row
    blk = max(128, min(4096, blk // 128 * 128))
    return blk, fc, n_fb, Bp, lanes


def _onehot_t(bins_ref, *, Bp: int, cdt):
    """[fc*Bp, blk] one-hot of a [fc, blk] int32 bin block (row
    ``f*Bp + b`` is 1 where ``bins[f, r] == b``), made on the VPU: each
    feature row, broadcast along sublanes, is compared in int32 with the
    bin index of its ``Bp`` one-hot rows, the ``fc`` pieces are joined
    at sublane offsets ``f*Bp`` in 32 bits and the whole is narrowed to
    the matmul dtype once (a piece narrowed before the join makes Mosaic
    repack every register)."""
    fc, blk = bins_ref.shape
    wide = jnp.int32 if cdt == jnp.int8 else jnp.float32
    bin_of_row = jax.lax.broadcasted_iota(jnp.int32, (Bp, blk), 0)
    one, zero = jnp.ones((), wide), jnp.zeros((), wide)
    pieces = [jnp.where(bins_ref[f:f + 1, :] == bin_of_row, one, zero)
              for f in range(fc)]
    return jnp.concatenate(pieces, axis=0).astype(cdt)


def _accumulate(out_ref, onehot, addends, *, cdt, acc_dt):
    """out[fb, lanes] += onehot[fb, blk] @ addends[lanes, blk]^T."""
    # float32 mode must not silently drop to the MXU's bf16 passes
    prec = jax.lax.Precision.HIGHEST if cdt == jnp.float32 else None
    out_ref[:] += jax.lax.dot_general(
        onehot, addends.astype(cdt), (((1,), (1,)), ((), ())),
        precision=prec, preferred_element_type=acc_dt)


def _slot_addends(gh_ref, leaf_ref, cols_ref):
    """[lanes, blk] 32-bit addends: lane-row ``c`` holds channel
    ``cols[c, 1]`` of gh for the rows whose leaf is slot ``cols[c, 0]``
    (pad lanes carry slot -2 and never match)."""
    hit = leaf_ref[:] == cols_ref[:, 0:1]
    ch = cols_ref[:, 1:2]
    val = jnp.where(ch == 0, gh_ref[0:1, :],
                    jnp.where(ch == 1, gh_ref[1:2, :], gh_ref[2:3, :]))
    return jnp.where(hit, val, 0)


def _accumulate_step(nr_ref, bins_ref, gh_ref, leaf_ref, cols_ref,
                     acc_ref, *, Bp: int, cdt, acc_dt, blk: int):
    """One (feature-chunk, row-block) grid step of the accumulation.

    nr_ref:   scalar-prefetch [1] int32 live-row bound — row blocks at
              or past ceil(nr / blk) are SKIPPED (the index maps also
              clamp their DMAs to an already-fetched block), so a
              compacted stream pays only for its live prefix — the
              dense_bin.hpp:105 data_indices bound. An unbounded build
              passes its row count.
    bins_ref: [fc, blk] int32
    gh_ref:   [3, blk] f32 (grad, hess, in-bag count) — int32 grid
              values when quantized
    leaf_ref: [1, blk] int32 current leaf per row (-1 dead)
    cols_ref: [lanes, 2] int32 (slot, channel) per output lane
    acc_ref:  [fc*Bp, lanes] f32 (int32 when quantized) accumulator,
              the same block every row step
    """
    # program_id is read at kernel top level (inside a pl.when body it
    # misses the interpret-mode grid-env substitution)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(j * blk < nr_ref[0])
    def _():
        _accumulate(acc_ref, _onehot_t(bins_ref, Bp=Bp, cdt=cdt),
                    _slot_addends(gh_ref, leaf_ref, cols_ref),
                    cdt=cdt, acc_dt=acc_dt)


def _rows_to_lanes(a, r_pad: int, **pad_kw):
    """[R, C] -> [C, r_pad]: rows onto the lane axis, padded."""
    return jnp.pad(a, ((0, r_pad - a.shape[0]), (0, 0)), **pad_kw).T


def _bins_chunks(bins, r_pad: int, fc: int, n_fb: int):
    """[R, F] -> [n_fb, fc, r_pad] int32 (zero feature/row padding)."""
    F = bins.shape[1]
    b = jnp.pad(bins.astype(jnp.int32), ((0, 0), (0, n_fb * fc - F)))
    return _rows_to_lanes(b, r_pad).reshape(n_fb, fc, r_pad)


def _leaf_lanes(row_leaf, r_pad: int):
    """[R] -> [1, r_pad] int32; padded rows get leaf -1."""
    return _rows_to_lanes(row_leaf.astype(jnp.int32)[:, None], r_pad,
                          constant_values=-1)


def _lane_operands(bins, gh, row_leaf, r_pad: int, *, fc: int, n_fb: int,
                   acc_dt):
    """The kernel's row-stream operands from row-major ones: cast, pad
    and transpose, rows onto lanes. ``(bins [n_fb, fc, r_pad] int32,
    gh [3, r_pad] acc_dt, leaf [1, r_pad] int32)``."""
    return (_bins_chunks(bins, r_pad, fc, n_fb),
            _rows_to_lanes(gh.astype(acc_dt), r_pad),
            _leaf_lanes(row_leaf, r_pad))


# A compacted stream is laid out in at most this many chunks: the
# granularity follows the shape (R / 32 rows, rounded up to the row
# block), so the rows a round touches past its live prefix stay under
# 1/32 of R whatever the shape.
_STREAM_CHUNKS = 32


def stream_chunk(R: int, blk: int) -> int:
    """Rows per chunk of the compacted-stream layout loop: the multiple
    of the kernel's row block next above ``R / 32``."""
    return _ceil_to(-(-R // _STREAM_CHUNKS), blk)


def _vary_like(x, vma):
    """Mark ``x`` varying over the manual axes in ``vma`` it does not
    vary over yet (a loop carry must have its body's type)."""
    missing = tuple(sorted(vma - jax.typeof(x).vma))
    return jax.lax.pcast(x, missing, to="varying") if missing else x


def _stream_operands(bins, gh, row_leaf, row_gather, live, *, chunk: int,
                     fc: int, n_fb: int, acc_dt):
    """:func:`_lane_operands` of a COMPACTED stream, bounded by its live
    rows: stream position ``p`` reads row ``row_gather[p]`` of the
    uncompacted ``bins`` / ``gh`` / ``row_leaf``, positions at or past
    ``live`` count as dead (leaf -1). One loop of ``ceil(live / chunk)``
    trips gathers a chunk's rows (two gathers: ``bins``, and the
    ``[R, 4]`` table of ``gh`` and ``row_leaf``, which is assembled
    before the loop: the one R-sized write), brings them into the
    lane-major layout and writes them into the operand buffers at lane
    offset ``i * chunk``; nothing R-sized is gathered or transposed.
    The buffers start uninitialized and stay so past the last chunk
    written: the kernel skips those row blocks and clamps their DMAs
    (``chunk`` is a multiple of its row block). No collective may sit
    in the loop: under shard_map every shard runs its own trip count.

    The operands' lane extent is ``ceil(R / chunk) * chunk``.
    """
    R = bins.shape[0]
    r_pad = _ceil_to(R, chunk)
    idx_all = jnp.pad(row_gather.astype(jnp.int32), (0, r_pad - R))
    vma = _out_vma(bins, gh, row_leaf, row_gather, live)
    bufs = tuple(
        _vary_like(jax.lax.empty(shape, dt), vma) for shape, dt in (
            ((n_fb, fc, r_pad), jnp.int32), ((HIST_CH, r_pad), acc_dt),
            ((1, r_pad), jnp.int32)))
    with profiler.stage(phases.HIST_GATHER):
        table = _row_table(gh, row_leaf, acc_dt)

    def put_chunk(i, bufs):
        s = i * chunk
        with profiler.stage(phases.HIST_GATHER):
            idx = jax.lax.dynamic_slice(idx_all, (s,), (chunk,))
            bb = jnp.take(bins, idx, axis=0)
            ghb, lb = _gather_rows(table, idx, s, live[0], acc_dt)
        with profiler.stage(phases.HIST_RELAYOUT):
            piece = _lane_operands(bb, ghb, lb, chunk, fc=fc, n_fb=n_fb,
                                   acc_dt=acc_dt)
            return tuple(
                jax.lax.dynamic_update_slice(
                    buf, p, (0,) * (buf.ndim - 1) + (s,))
                for buf, p in zip(bufs, piece))

    return jax.lax.fori_loop(0, stream_trips(live[0], chunk, R), put_chunk,
                             bufs)


def resident_operands(bins, gh, r_pad: int, *, num_bins: int,
                      hist_dtype: str = "bfloat16"):
    """``(bins_t [n_fb, fc, r_pad] int32, gh_t [3, r_pad])``: the kernel's
    bin and addend operands over every row in stored order, laid out once
    for all the calls of a tree that read them so (the root pass and the
    grow loop's dense rounds: :func:`build_histograms_pallas_dense`).
    ``r_pad`` must be a multiple of each such call's row block. The
    feature chunking does not depend on the slot count, so one layout
    serves every call."""
    cdt, acc_dt = _kernel_dtypes(gh.dtype, hist_dtype)
    _, fc, n_fb, _, _ = _plan(bins.shape[1], int(num_bins), HIST_CH,
                              jnp.dtype(cdt).itemsize)
    with profiler.stage(phases.HIST_RELAYOUT):
        return (_bins_chunks(bins, r_pad, fc, n_fb),
                _rows_to_lanes(gh.astype(acc_dt), r_pad))


def build_histograms_pallas_dense(bins_t, gh_t, row_leaf, leaf_ids, *,
                                  num_features: int, num_bins: int,
                                  hist_dtype: str = "bfloat16"):
    """One kernel pass over ALL rows in stored order for ``leaf_ids``,
    from :func:`resident_operands`: only ``row_leaf`` is re-laid, and
    the kernel's slot compare zeroes every row outside the slots, so the
    result equals the compacted stream's over the same rows (f32 sums
    may differ by reassociation; integer ones are exact). The grow
    loop's route for a round whose smaller children hold so many rows
    that the stream's sort and gathers cost more than this pass
    (``tree_builder.step_shape``'s ``dense_round_rows``)."""
    R = row_leaf.shape[0]
    with profiler.stage(phases.HIST_RELAYOUT):
        leaf_t = _leaf_lanes(row_leaf, bins_t.shape[-1])
    return build_histograms_pallas_lanes(
        bins_t, gh_t, leaf_t, leaf_ids, _live_rows(None, R),
        num_features=num_features, num_bins=num_bins,
        hist_dtype=hist_dtype)


def _row_stream(bins, gh, row_leaf, row_gather, num_rows, *, blk: int,
                fc: int, n_fb: int, acc_dt):
    """``(live, operands)`` of a kernel call: the whole matrix re-laid
    (no ``row_gather``), or the compacted stream's live chunks
    (:func:`_stream_operands`)."""
    R = bins.shape[0]
    live = _live_rows(num_rows, R)
    if row_gather is None:
        with profiler.stage(phases.HIST_RELAYOUT):
            return live, _lane_operands(bins, gh, row_leaf, _ceil_to(R, blk),
                                        fc=fc, n_fb=n_fb, acc_dt=acc_dt)
    return live, _stream_operands(
        bins, gh, row_leaf, row_gather, live, chunk=stream_chunk(R, blk),
        fc=fc, n_fb=n_fb, acc_dt=acc_dt)


def _slot_cols(leaf_ids, lanes: int):
    """[lanes, 2] int32 (slot, channel) map of the channel-major output
    lanes."""
    L = leaf_ids.shape[0]
    pad = lanes - L * HIST_CH
    slot = jnp.pad(jnp.tile(leaf_ids.astype(jnp.int32), HIST_CH),
                   (0, pad), constant_values=-2)
    chan = jnp.pad(jnp.repeat(jnp.arange(HIST_CH, dtype=jnp.int32), L),
                   (0, pad))
    return jnp.stack([slot, chan], axis=1)


def _live_rows(num_rows, R: int):
    """The [1] int32 scalar-prefetch live-row bound (all R rows when the
    caller gave none)."""
    return jnp.reshape(jnp.asarray(R if num_rows is None else num_rows,
                                   jnp.int32), (1,))


def _stream_specs(fc: int, blk: int, lanes: int):
    """Block specs of the row-stream operands (bins, gh, leaf, cols).
    Steps past the live-row bound ``s`` revisit the last live row block
    (no fresh DMA)."""
    def rb(j, s):
        return jnp.minimum(j, jnp.maximum((s[0] + blk - 1) // blk - 1, 0))
    return [
        pl.BlockSpec((None, fc, blk), lambda i, j, s: (i, 0, rb(j, s))),
        pl.BlockSpec((HIST_CH, blk), lambda i, j, s: (0, rb(j, s))),
        pl.BlockSpec((1, blk), lambda i, j, s: (0, rb(j, s))),
        pl.BlockSpec((lanes, 2), lambda i, j, s: (0, 0)),
    ]


def _out_vma(*operands):
    """Varying-manual-axes of the kernel outputs: inside shard_map with
    check_vma on, pallas_call needs it spelled on each out_shape."""
    return frozenset().union(*(jax.typeof(x).vma for x in operands))


def _compiler_params():
    # feature chunks are independent; the row dim revisits the same
    # accumulator block and must stay sequential
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _check_shape(num_bins: int):
    reason = pallas_shape_reason(num_bins)
    if reason:
        raise ValueError(f"hist_impl=pallas cannot take this shape: "
                         f"{reason}")


def _unpack_hist(out, *, F: int, B: int, L: int, fc: int, n_fb: int,
                 Bp: int, lanes: int):
    """[n_fb*fc*Bp, lanes] kernel output -> [L, F, B, 3]."""
    hist = out.reshape(n_fb * fc, Bp, lanes)[:F, :B, :L * HIST_CH]
    return hist.reshape(F, B, HIST_CH, L).transpose(3, 0, 1, 2)


def _kernel_dtypes(gh_dtype, hist_dtype: str):
    """(matmul dtype, accumulator dtype) of a build: int8 / int32 for
    quantized ``gh`` (int8 rows, int32 once laid out), else
    ``hist_dtype`` / float32."""
    if gh_dtype in (jnp.int8, jnp.int32):
        return jnp.int8, jnp.int32
    return jnp.dtype(hist_dtype), jnp.float32


@functools.partial(
    jax.jit,
    static_argnames=("num_bins", "hist_dtype", "interpret"))
def build_histograms_pallas(bins: jax.Array, gh: jax.Array,
                            row_leaf: jax.Array, leaf_ids: jax.Array, *,
                            num_bins: int, hist_dtype: str = "bfloat16",
                            interpret: bool = False,
                            num_rows: Optional[jax.Array] = None,
                            row_gather: Optional[jax.Array] = None
                            ) -> jax.Array:
    """Pallas analog of ops.histogram.build_histograms.

    Same contract: bins [R, F] uint/int, gh [R, 3] f32, row_leaf [R]
    int32, leaf_ids [L] int32 -> [L, F, B, 3] f32. R is padded up to the
    row block internally (padded rows get leaf -1).
    int8 ``gh`` selects the quantized path (int8 MXU dot, exact int32
    output — see ops/histogram.py docstring).
    ``num_rows`` (traced int32 scalar): dynamic live-row bound — it
    rides in as a scalar-prefetch operand, row blocks at or past
    ``ceil(num_rows / blk)`` are skipped by ``pl.when`` and their index
    maps clamp to an already-fetched block (no fresh DMA), so histogram
    subtraction's row-stream savings survive on the chip. Without
    ``row_gather`` the rows past ``num_rows`` must carry
    ``row_leaf == -1`` (the trailing partial block is masked by leaf
    ids only).
    ``row_gather`` [R] int32: the COMPACTED stream — ``bins``, ``gh``
    and ``row_leaf`` arrive uncompacted, stream position ``p`` reads
    row ``row_gather[p]`` of each, positions at or past ``num_rows``
    are dead. The operands are then laid out by a loop over the live
    chunks only (:func:`_stream_operands`), so everything that feeds
    the kernel is bounded by ``num_rows`` as the kernel is.
    Either way the row streams are re-laid rows-onto-lanes here and the
    kernel is called once, through :func:`build_histograms_pallas_lanes`,
    the entry for operands that are laid out already.
    ``interpret=True`` runs the kernel in the Pallas interpreter —
    CPU-testable parity with the real TPU lowering.
    Raises ValueError for ``num_bins > 256`` (see module docstring).
    """
    F = bins.shape[1]
    L = int(leaf_ids.shape[0])
    B = int(num_bins)
    _check_shape(B)
    cdt, acc_dt = _kernel_dtypes(gh.dtype, hist_dtype)
    blk, fc, n_fb, _, _ = _plan(F, B, L * HIST_CH, jnp.dtype(cdt).itemsize)
    live, operands = _row_stream(
        bins, gh, row_leaf, row_gather, num_rows, blk=blk, fc=fc, n_fb=n_fb,
        acc_dt=acc_dt)
    return build_histograms_pallas_lanes(
        *operands, leaf_ids, live, num_features=F, num_bins=B,
        hist_dtype=hist_dtype, interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("num_features", "num_bins", "hist_dtype", "interpret"))
def build_histograms_pallas_lanes(bins_t: jax.Array, gh_t: jax.Array,
                                  leaf_t: jax.Array, leaf_ids: jax.Array,
                                  live: jax.Array, *, num_features: int,
                                  num_bins: int,
                                  hist_dtype: str = "bfloat16",
                                  interpret: bool = False) -> jax.Array:
    """:func:`build_histograms_pallas` for operands already in the
    kernel's layout: ``bins_t`` [n_fb, fc, r_pad] int32, ``gh_t``
    [3, r_pad] (f32, or int32 grid values: quantized), ``leaf_t``
    [1, r_pad] int32 with rows on the lane axis, ``r_pad`` a multiple of
    the plan's row block; ``live`` [1] int32 the live-row bound. Row
    blocks at or past ``ceil(live / blk)`` are never read, so they may
    hold anything. One ``pallas_call``; returns [L, F, B, 3]."""
    F, B = int(num_features), int(num_bins)
    L = int(leaf_ids.shape[0])
    _check_shape(B)
    cdt, acc_dt = _kernel_dtypes(gh_t.dtype, hist_dtype)
    blk, fc, n_fb, Bp, lanes = _plan(F, B, L * HIST_CH,
                                     jnp.dtype(cdt).itemsize)
    r_pad = bins_t.shape[-1]
    if bins_t.shape != (n_fb, fc, r_pad) or r_pad % blk:
        raise ValueError(f"bins_t {bins_t.shape} is not the plan's "
                         f"[{n_fb}, {fc}, k * {blk}] layout")
    fb = fc * Bp
    with profiler.stage(phases.HIST_KERNEL):
        out = pl.pallas_call(
            functools.partial(_accumulate_step, Bp=Bp, cdt=cdt,
                              acc_dt=acc_dt, blk=blk),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(n_fb, r_pad // blk),
                in_specs=_stream_specs(fc, blk, lanes),
                out_specs=pl.BlockSpec((fb, lanes), lambda i, j, s: (i, 0)),
            ),
            out_shape=jax.ShapeDtypeStruct(
                (n_fb * fb, lanes), acc_dt,
                vma=_out_vma(bins_t, gh_t, leaf_t, leaf_ids, live)),
            compiler_params=_compiler_params(),
            interpret=interpret,
            name=HIST_KERNEL_NAME,
        )(live, bins_t, gh_t, leaf_t, _slot_cols(leaf_ids, lanes))
        return _unpack_hist(out, F=F, B=B, L=L, fc=fc, n_fb=n_fb, Bp=Bp,
                            lanes=lanes)


# ---------------------------------------------------------------------------
# Class-shared root histogram (ISSUE-14 satellite): the class-batched
# multiclass build vmaps the whole tree build, which batches EVERY
# pallas operand — the bins matrix, logically shared across classes, is
# presented K× to the root launch. This kernel instead streams bins ONCE
# and reduces all K classes' (g, h, count) rows against the same
# one-hot: the addends are [K*3, blk] with the root-leaf row mask
# applied elementwise, so the MXU emits [fc*Bp, K*3] per chunk.
# ---------------------------------------------------------------------------


def _class_kernel(bins_ref, ghk_ref, leaf_ref, out_ref, *, Bp: int, cdt,
                  acc_dt, root_slot: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    addends = jnp.where(leaf_ref[:] == root_slot, ghk_ref[:], 0)
    # the MXU wants the full lane tile; padding here (sublane-aligned
    # concat in VMEM) keeps the HBM operand at K*3 rows
    pad = out_ref.shape[1] - addends.shape[0]
    if pad:
        addends = jnp.concatenate(
            [addends, jnp.zeros((pad, addends.shape[1]), addends.dtype)],
            axis=0)
    _accumulate(out_ref, _onehot_t(bins_ref, Bp=Bp, cdt=cdt), addends,
                cdt=cdt, acc_dt=acc_dt)


@functools.partial(
    jax.jit,
    static_argnames=("num_bins", "hist_dtype", "interpret", "root_slot"))
def build_root_histograms_classes(bins: jax.Array, gh_k: jax.Array,
                                  row_leaf: jax.Array, *, num_bins: int,
                                  hist_dtype: str = "bfloat16",
                                  interpret: bool = False,
                                  root_slot: int = 0) -> jax.Array:
    """Root histograms for all K classes with ONE pass over bins.

    bins [R, F], gh_k [K, R, 3] (f32 or int8 quantized), row_leaf [R]
    int32 → [K, F, B, 3] (f32; int32 when quantized). Bit-equal to K
    independent `build_histograms_pallas` root launches: the per-class
    rows hit the same MXU contraction against the same one-hot, in the
    same row-block order."""
    R, F = bins.shape
    K = int(gh_k.shape[0])
    B = int(num_bins)
    _check_shape(B)
    quant = gh_k.dtype == jnp.int8
    cdt = jnp.int8 if quant else jnp.dtype(hist_dtype)
    acc_dt = jnp.int32 if quant else jnp.float32
    kc = K * HIST_CH
    blk, fc, n_fb, Bp, lanes = _plan(F, B, kc, jnp.dtype(cdt).itemsize)
    fb = fc * Bp
    r_pad = _ceil_to(R, blk)
    kc8 = _ceil_to(kc, 8)
    # [kc8, r_pad]: row k*3 + channel
    with profiler.stage(phases.HIST_RELAYOUT):
        ghk_t = jnp.pad(
            gh_k.astype(acc_dt).transpose(0, 2, 1).reshape(kc, R),
            ((0, kc8 - kc), (0, r_pad - R)))
        bins_t = _bins_chunks(bins, r_pad, fc, n_fb)
        leaf_t = _leaf_lanes(row_leaf, r_pad)
    with profiler.stage(phases.HIST_KERNEL):
        out = pl.pallas_call(
            functools.partial(_class_kernel, Bp=Bp, cdt=cdt, acc_dt=acc_dt,
                              root_slot=root_slot),
            grid=(n_fb, r_pad // blk),
            in_specs=[
                pl.BlockSpec((None, fc, blk), lambda i, j: (i, 0, j)),
                pl.BlockSpec((kc8, blk), lambda i, j: (0, j)),
                pl.BlockSpec((1, blk), lambda i, j: (0, j)),
            ],
            out_specs=pl.BlockSpec((fb, lanes), lambda i, j: (i, 0)),
            out_shape=jax.ShapeDtypeStruct(
                (n_fb * fb, lanes), acc_dt,
                vma=_out_vma(bins, gh_k, row_leaf)),
            compiler_params=_compiler_params(),
            interpret=interpret,
            name=HIST_CLASS_ROOT_NAME,
        )(bins_t, ghk_t, leaf_t)

    hist = out.reshape(n_fb * fc, Bp, lanes)[:F, :B, :kc]
    return hist.reshape(F, B, K, HIST_CH).transpose(2, 0, 1, 3)
