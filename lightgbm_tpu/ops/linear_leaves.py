"""Linear leaves on the device: each leaf's ridge fit on the raw values of
the numerical features along its branch, and a linear tree's per-row
outputs for the score update.

Reference: ``LinearTreeLearner::CalculateLinear``
(``linear_tree_learner.cpp``), after Shi, Li & Li, "Gradient Boosting with
Piece-Wise Linear Regression Trees" (IJCAI 2019), eq. 3. A leaf ``l`` with
path features ``P_l`` (the numerical split features on its branch) fits

    [beta; beta_0] = -(X~' H X~ + Lambda)^-1 X~' g,   X~ = [x_P, 1]

over its rows that hold no NaN in ``P_l``, ``Lambda`` = ``linear_lambda`` on
every diagonal entry but the constant's. A leaf with fewer such rows than
``|P_l| + 1``, or whose solution is not finite, keeps its constant output;
a coefficient within ``kZeroThreshold`` (1e-35) of zero is dropped; the
first tree of a model keeps every leaf constant (``is_first_tree``). A
row's output is ``const + coeff . x``, or the leaf's constant output where
a feature of the leaf's model is NaN (``Tree::AddPredictionToScore``).

How it runs (one device; rows ride the lane axis as in
``pallas_histogram``):

- raw values live on the device feature-major, ``[Fp, R]`` float32
  (:func:`raw_lanes`), ``Fp`` = ``F + 1`` rounded up to 8: rows past ``F``
  are zero, and row ``F`` becomes the constant column inside the kernels.
  A row-major ``[R, F]`` array would pad ``F`` to 128 lanes.
- **moments** (``linear_gram``): per leaf, the h-weighted sums of the
  values, the hessian and the count of clean rows, one pass;
- **Gram** (``linear_gram``): per leaf, ``sum w z~ z~'`` and ``sum g z~``
  with ``z~ = [x - s_l, 1]``: every row is shifted by its leaf's mean
  ``s_l`` (the moments' weighted mean, rounded to bfloat16 so that the
  one-hot matmul that hands it to the row is exact) before any product is
  formed, so a deep leaf's narrow ranges do not cancel in float32. The
  upper triangle is laid out in sublane-aligned pieces (row ``i`` pairs
  with rows ``8 floor(i / 8)`` onward), each product is carried to the MXU
  as two bfloat16 halves (16 significant bits, the rounding unbiased) and
  accumulated in float32: ``[M, TB] x [Lp, TB]^T`` a row block, leaves on
  the output's lanes, the one-hot made on the VPU and never written to HBM;
- **solve** (``linear_solve``): masks from the tree arrays by pointer
  doubling, the constant column eliminated by the leaf's weighted means,
  a Jacobi-scaled float32 solve batched over the leaves, the reference's
  rules, and the constant moved back to raw coordinates;
- **outputs** (``linear_update``): a leaf's coefficients, constant, output
  and model mask reach its rows through one exact one-hot matmul
  (float32 values as three bfloat16 parts), then one multiply-add pass.

Off a TPU, and on one for a shape the kernels cannot take
(:func:`device_reason`: past ``MAX_FEATURES`` features, or rows no block
divides), the same quantities come from segment sums in XLA (``impl``
"xla"); the kernels run off a TPU only in the Pallas interpreter (tests).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import phases, profiler

__all__ = ["LinearPlan", "linear_plan", "raw_lanes", "path_masks",
           "fit_leaves", "leaf_outputs", "gram_layout", "choose_impl",
           "MAX_FEATURES"]

K_ZERO_THRESHOLD = 1e-35   # kZeroThreshold (meta.h)
MAX_FEATURES = 64          # the Gram's width is F + 1; past it VMEM runs out
_BLOCK_CAP = 2048          # rows a grid step
_VMEM_BUDGET = 40 << 20    # what the row block is sized against
_VMEM_LIMIT = 64 << 20

# names the compiled module gives the kernels' custom calls
GRAM_KERNEL_NAME = "linear_gram_kernel"
MOMENTS_KERNEL_NAME = "linear_moments_kernel"
OUTPUT_KERNEL_NAME = "linear_output_kernel"

F32, BF16 = jnp.float32, jnp.bfloat16
_NT = (((1,), (1,)), ((), ()))     # contract the row (lane) axis of both
_NN = (((1,), (0,)), ((), ()))


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def padded_features(num_features: int) -> int:
    """Rows of the device raw-value array: ``F + 1`` (room for the
    constant column) rounded up to a sublane multiple."""
    return _ceil_to(num_features + 1, 8)


def gram_layout(num_features: int) -> Tuple[Tuple[int, ...], Tuple[int, ...],
                                            int, int]:
    """``(starts, offsets, g_offset, M)`` of the Gram kernel's output rows:
    column ``i`` of ``z~`` (``0 <= i <= F``, ``F`` the constant) pairs with
    rows ``starts[i] = 8 floor(i / 8)`` .. ``Fp - 1``, at output rows
    ``offsets[i]`` on; ``sum g z~`` follows at ``g_offset``; ``M`` rows in
    all, a multiple of 16 (every piece a multiple of 8 rows: aligned
    joins; 8 zero rows close a bfloat16 tile where needed)."""
    Fp = padded_features(num_features)
    starts = tuple(8 * (i // 8) for i in range(num_features + 1))
    offsets, off = [], 0
    for s in starts:
        offsets.append(off)
        off += Fp - s
    return starts, tuple(offsets), off, _ceil_to(off + Fp, 16)


class LinearPlan(NamedTuple):
    features: int      # F: used features (tree_builder's feature order)
    padded: int        # Fp
    slots: int         # Lp: leaf slots on the kernels' lanes
    gram_rows: int     # M
    block: int         # rows a grid step (0: no kernel for this shape)


def linear_plan(num_features: int, num_slots: int, rows: int) -> LinearPlan:
    """The kernels' sizes for ``num_slots`` leaf slots (``L + 1``) and a
    row count ``rows``: the row block is the largest power of two at most
    ``_BLOCK_CAP`` that divides ``rows`` and keeps a step's VMEM under
    budget (0 where none of at least 128 does)."""
    Fp = padded_features(num_features)
    Lp = _ceil_to(num_slots, 128)
    M = gram_layout(num_features)[3]
    fixed = 2 * M * Lp * 4 * 2                  # the accumulator, twice
    per_row = M * (4 + 2 + 2) + Lp * (4 + 2) + 2 * 4 * (Fp + 4) + Fp * 4
    blk = _BLOCK_CAP
    while blk >= 128 and (rows % blk or fixed + blk * per_row
                          > _VMEM_BUDGET):
        blk //= 2
    return LinearPlan(num_features, Fp, Lp, M, blk if blk >= 128 else 0)


def device_reason(num_features: int, num_slots: int, rows: int) -> str:
    """Why the kernels cannot take this shape ('' = they can); the fit
    then runs in XLA."""
    if num_features > MAX_FEATURES:
        return (f"{num_features} features: the Gram kernel holds at most "
                f"{MAX_FEATURES} (its rows grow as F^2 / 2)")
    if not linear_plan(num_features, num_slots, rows).block:
        return (f"no row block of at least 128 divides {rows} rows and "
                "fits the kernels' VMEM")
    return ""


def choose_impl(on_tpu: bool, num_features: int, num_slots: int,
                rows) -> Tuple[str, str]:
    """``(impl, reason)``: "pallas" where on a TPU the kernels take every
    row count in ``rows`` (the training set's and each validation set's),
    else "xla" and why."""
    if not on_tpu:
        return "xla", "not a TPU"
    reason = next(filter(None, (device_reason(num_features, num_slots, r)
                                for r in rows)), "")
    return ("xla" if reason else "pallas"), reason


def raw_lanes(raw_rows: np.ndarray, used_features, r_pad: int) -> np.ndarray:
    """``[Fp, r_pad]`` float32 raw values of the used features, features on
    sublanes and rows on lanes; padded rows and rows past ``F`` hold 0.
    ``raw_rows`` is the Dataset's ``[num_data, num_total_features]``."""
    uf = np.asarray(used_features, np.int64)
    n = raw_rows.shape[0]
    out = np.zeros((padded_features(len(uf)), r_pad), np.float32)
    cols = raw_rows.T                      # a view; contiguous where the
    if not np.array_equal(uf, np.arange(cols.shape[0])):   # rows were
        cols = cols[uf]                    # handed over column-major
    out[:len(uf), :n] = cols
    return out


# ---------------------------------------------------------------------------
# masks from the tree arrays


def path_masks(tree, numerical: jax.Array, num_slots: int) -> jax.Array:
    """``[num_slots, F]`` bool: the numerical features split on along each
    leaf slot's branch (``Tree::branch_features``), zero rows past the
    tree's slots. Every node's mask is its parent's split feature; the
    branch's is the OR over its ancestors, by pointer doubling over the
    node arrays (``ceil(log2 N)`` steps)."""
    sf = tree.split_feature
    N = sf.shape[0]
    F = numerical.shape[0]
    ids = jnp.arange(N, dtype=jnp.int32)
    # the dummy node past num_nodes takes the writes of unused lanes
    internal = (sf >= 0) & (ids < tree.num_nodes)
    parent = jnp.full((N,), -1, jnp.int32)
    for child in (tree.left_child, tree.right_child):
        parent = parent.at[jnp.where(internal, child, N)].set(
            ids, mode="drop")
    pf = jnp.where(parent >= 0, sf[jnp.clip(parent, 0)], -1)
    mask = (pf[:, None] == jnp.arange(F, dtype=jnp.int32)[None, :]) \
        & numerical[None, :]
    for _ in range(max(1, int(np.ceil(np.log2(N))))):
        up = parent >= 0
        pc = jnp.clip(parent, 0)
        mask = mask | (mask[pc] & up[:, None])
        parent = jnp.where(up, parent[pc], -1)
    slots = tree.leaf2node.shape[0]
    live = jnp.arange(slots) < tree.num_leaves
    leaf = mask[tree.leaf2node] & live[:, None]
    return jnp.pad(leaf, ((0, num_slots - slots), (0, 0)))


# ---------------------------------------------------------------------------
# the kernels (rows on lanes; one grid axis over row blocks, sequential)


def _round_bf16(v):
    """float32 ``v`` rounded to the nearest bfloat16 (ties to even), still
    float32: done on the bits, because XLA may keep a float32 value where a
    ``convert`` to bfloat16 and back asks for the rounding (it allows
    excess precision), and the split into parts and the shift depend on
    the rounded value being the one the bfloat16 table holds."""
    b = jax.lax.bitcast_convert_type(v, jnp.uint32)
    b = (b + jnp.uint32(0x7FFF) + ((b >> 16) & jnp.uint32(1))) \
        & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(b, F32)


def _onehot(leaf_ref, slots: int):
    """[slots, TB] bfloat16 one-hot of the rows' leaf slots (dead rows,
    leaf -1, are all zero)."""
    leaf = leaf_ref[...]
    slot = jax.lax.broadcasted_iota(jnp.int32, (slots, leaf.shape[1]), 0)
    return jnp.where(slot == leaf, 1.0, 0.0).astype(BF16)


def _per_row(tab_ref, oh):
    """[T, TB] float32: row ``t`` of the table at each row's leaf (exact:
    one one-hot term a sum)."""
    return jax.lax.dot_general(tab_ref[...], oh, _NN,
                               preferred_element_type=F32)


def _any_rows(mask):
    """[1, TB] float32 1.0 where a column of ``mask`` holds a True."""
    return jnp.max(jnp.where(mask, 1.0, 0.0), axis=0, keepdims=True)


def _clean(x, path, aux_ref):
    """(NaN mask of x, the clean-row weight: in the sample and no NaN in a
    path feature of the row's leaf)."""
    nan = x != x
    return nan, aux_ref[2:3, :] * (1.0 - _any_rows(nan & (path > 0.5)))


def _moments_step(x_ref, aux_ref, leaf_ref, tab_ref, out_ref, *,
                  num_features: int):
    """out[Mm, Lp] += [w x~; clean] @ onehot^T: per leaf the weighted sums
    of the values (row F: of the hessian) and the clean rows (row Fp);
    ``Mm`` is ``Fp + 8`` rounded up to 16 (:func:`_moment_rows`). One
    bfloat16 pass: these only place the shift and count rows."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    Fp, tb = x_ref.shape
    oh = _onehot(leaf_ref, tab_ref.shape[1])
    x = x_ref[...]
    nan, clean = _clean(x, _per_row(tab_ref, oh)[:Fp], aux_ref)
    row = jax.lax.broadcasted_iota(jnp.int32, (Fp, tb), 0)
    xt = jnp.where(row == num_features, 1.0, jnp.where(nan, 0.0, x))
    tail = jax.lax.broadcasted_iota(jnp.int32, (out_ref.shape[0] - Fp, tb), 0)
    add = jnp.concatenate([aux_ref[1:2, :] * clean * xt,
                           jnp.where(tail == 0, clean, 0.0)], axis=0)
    out_ref[...] += jax.lax.dot_general(add.astype(BF16), oh, _NT,
                                        preferred_element_type=F32)


def _gram_step(x_ref, aux_ref, leaf_ref, tab_ref, out_ref, z_ref, *,
               num_features: int):
    """out[0] + out[1] (a sum and its compensation, [M, Lp] each) +=
    (hi, lo halves of the products) @ onehot^T, the block's sum added to
    the running one by TwoSum: a leaf's sums gather a few rows from each
    of thousands of blocks, and plain float32 addition loses up to n u of
    a sum of n terms of one sign (a leaf's sum of g, of h)."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    Fp, tb = x_ref.shape
    oh = _onehot(leaf_ref, tab_ref.shape[1])
    per_row = _per_row(tab_ref, oh)
    shift, path = per_row[:Fp], per_row[Fp:]
    x = x_ref[...]
    nan, clean = _clean(x, path, aux_ref)
    w = aux_ref[1:2, :] * clean
    row = jax.lax.broadcasted_iota(jnp.int32, (Fp, tb), 0)
    z_ref[...] = jnp.where(row == num_features, 1.0,
                           jnp.where(nan, 0.0, x) - shift)
    starts = gram_layout(num_features)[0]
    pieces = [(w * z_ref[c:c + 1, :]) * z_ref[s:, :]
              for c, s in enumerate(starts)]
    pieces.append((aux_ref[0:1, :] * clean) * z_ref[...])
    pad = out_ref.shape[1] - sum(p.shape[0] for p in pieces)
    if pad:
        pieces.append(jnp.zeros((pad, tb), F32))
    add = jnp.concatenate(pieces, axis=0)
    top = _round_bf16(add)
    hi = top.astype(BF16)
    lo = (add - top).astype(BF16)
    part = (jax.lax.dot_general(hi, oh, _NT, preferred_element_type=F32)
            + jax.lax.dot_general(lo, oh, _NT, preferred_element_type=F32))
    acc = out_ref[0]
    total = acc + part
    back = total - acc
    out_ref[1] += (acc - (total - back)) + (part - back)
    out_ref[0] = total


def _output_step(x_ref, leaf_ref, tab_ref, out_ref, *, padded: int):
    """out[1, TB] = const + coeff . x, or the leaf's output where a feature
    of its model is NaN. Table rows: three float32 parts of [coeff (Fp),
    const (row Fp), output (row Fp + 8)], then the model mask (Fp)."""
    Fp = padded
    W = Fp + 16
    oh = _onehot(leaf_ref, tab_ref.shape[1])
    t = _per_row(tab_ref, oh)
    c = t[:W] + t[W:2 * W] + t[2 * W:3 * W]
    kept = t[3 * W:3 * W + Fp]
    x = x_ref[...]
    nan = x != x
    lin = jnp.sum(c[:Fp] * jnp.where(nan, 0.0, x), axis=0,
                  keepdims=True) + c[Fp:Fp + 1]
    out_ref[...] = jnp.where(_any_rows(nan & (kept > 0.5)) > 0.5,
                             c[Fp + 8:Fp + 9], lin)


def _params():
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                vmem_limit_bytes=_VMEM_LIMIT)


def _row_specs(Fp: int, blk: int, aux: bool):
    specs = [pl.BlockSpec((Fp, blk), lambda i: (0, i))]
    if aux:
        specs.append(pl.BlockSpec((3, blk), lambda i: (0, i)))
    specs.append(pl.BlockSpec((1, blk), lambda i: (0, i)))
    return specs


def _full(shape):
    return pl.BlockSpec(shape, lambda i: (0,) * len(shape))


def _moment_rows(Fp: int) -> int:
    return _ceil_to(Fp + 8, 16)


def _moments_pallas(x, aux, leaf, path_tab, plan: LinearPlan, interpret):
    Fp, blk, Lp = plan.padded, plan.block, plan.slots
    Mm = _moment_rows(Fp)
    return pl.pallas_call(
        functools.partial(_moments_step, num_features=plan.features),
        grid=(x.shape[1] // blk,),
        in_specs=_row_specs(Fp, blk, True) + [_full(path_tab.shape)],
        out_specs=_full((Mm, Lp)),
        out_shape=jax.ShapeDtypeStruct((Mm, Lp), F32),
        compiler_params=_params(), interpret=interpret,
        name=MOMENTS_KERNEL_NAME)(x, aux, leaf, path_tab)


def _gram_pallas(x, aux, leaf, tab, plan: LinearPlan, interpret):
    Fp, blk, Lp, M = plan.padded, plan.block, plan.slots, plan.gram_rows
    return pl.pallas_call(
        functools.partial(_gram_step, num_features=plan.features),
        grid=(x.shape[1] // blk,),
        in_specs=_row_specs(Fp, blk, True) + [_full(tab.shape)],
        out_specs=_full((2, M, Lp)),
        out_shape=jax.ShapeDtypeStruct((2, M, Lp), F32),
        scratch_shapes=[pltpu.VMEM((Fp, blk), F32)],
        compiler_params=_params(), interpret=interpret,
        name=GRAM_KERNEL_NAME)(x, aux, leaf, tab)


def _output_pallas(x, leaf, tab, plan: LinearPlan, interpret):
    Fp, blk = plan.padded, plan.block
    R = x.shape[1]
    return pl.pallas_call(
        functools.partial(_output_step, padded=Fp),
        grid=(R // blk,),
        in_specs=_row_specs(Fp, blk, False) + [_full(tab.shape)],
        out_specs=pl.BlockSpec((1, blk), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, R), F32),
        compiler_params=_params(), interpret=interpret,
        name=OUTPUT_KERNEL_NAME)(x, leaf, tab)[0]


# ---------------------------------------------------------------------------
# the same sums in XLA (off a TPU): segment sums over the rows


def _segments(row_leaf, slots):
    return jnp.where(row_leaf >= 0, row_leaf, slots)


_XLA_CHUNK = 1 << 14


def _segment_sum(data, seg, slots):
    """[slots, C] sums of ``data`` [R, C] by leaf, taken a chunk of rows at
    a time and the chunks' sums then added: a leaf's float32 sum does not
    run through all of its rows one addition at a time."""
    R = data.shape[0]
    chunk = _XLA_CHUNK if R % _XLA_CHUNK == 0 else R
    per = jax.vmap(lambda d, s: jax.ops.segment_sum(d, s, slots + 1))(
        data.reshape(R // chunk, chunk, -1), seg.reshape(R // chunk, chunk))
    return per.sum(axis=0)[:slots]


def _xla_rows(x, aux, row_leaf, path, F):
    """[R, F] values, NaN mask, per-row path mask and clean weight."""
    xr = x[:F].T
    nan = jnp.isnan(xr)
    pr = jnp.take(path, jnp.clip(row_leaf, 0), axis=0) & (row_leaf >= 0)[:, None]
    clean = aux[2] * jnp.where(jnp.any(nan & pr, axis=1), 0.0, 1.0)
    return jnp.where(nan, 0.0, xr), clean


def _moments_xla(x, aux, row_leaf, path, plan: LinearPlan):
    F, Lp = plan.features, plan.slots
    x0, clean = _xla_rows(x, aux, row_leaf, path, F)
    w = aux[1] * clean
    data = jnp.concatenate([w[:, None] * x0, w[:, None], clean[:, None]],
                           axis=1)
    s = _segment_sum(data, _segments(row_leaf, Lp), Lp)
    return s[:, :F], s[:, F], s[:, F + 1]


def _gram_xla(x, aux, row_leaf, path, shift, plan: LinearPlan):
    F, Lp = plan.features, plan.slots
    x0, clean = _xla_rows(x, aux, row_leaf, path, F)
    seg = _segments(row_leaf, Lp)
    z = x0 - jnp.take(shift, jnp.clip(row_leaf, 0), axis=0)
    z = jnp.concatenate([z, jnp.ones_like(z[:, :1])], axis=1)
    w = aux[1] * clean
    # one column of z~ a step, so the program does not grow with F
    G = jax.lax.map(lambda zi: _segment_sum((w * zi)[:, None] * z, seg, Lp),
                    z.T).transpose(1, 0, 2)
    b = _segment_sum((aux[0] * clean)[:, None] * z, seg, Lp)
    return G, b


# ---------------------------------------------------------------------------
# the fit


def _unpack_gram(out, plan: LinearPlan):
    """(G [Lp, F+1, F+1], b [Lp, F+1]) from the kernel's [2, M, Lp]."""
    F = plan.features
    starts, offsets, g_off, _ = gram_layout(F)
    idx = np.empty((F + 1, F + 1), np.int64)
    for i in range(F + 1):
        for j in range(F + 1):
            a, c = min(i, j), max(i, j)
            idx[i, j] = offsets[a] + c - starts[a]
    flat = out[0] + out[1]          # the sum and its compensation
    G = jnp.take(flat, jnp.asarray(idx.reshape(-1)), axis=0)
    G = G.reshape(F + 1, F + 1, -1).transpose(2, 0, 1)
    b = flat[g_off:g_off + F + 1].T
    return G, b


def _parts(v, n: int):
    """float32 ``v`` as ``n`` bfloat16 parts whose float32 sum is ``v``
    (exactly, for n = 3 and a normal float32)."""
    out, rest = [], v.astype(F32)
    for _ in range(n):
        p = _round_bf16(rest)
        out.append(p.astype(BF16))
        rest = rest - p
    return out


def _solve(G, b, path, clean, shift, leaf_value, lam: float, first):
    """Coefficients [Lp, F] and constants [Lp] from the centred sums: the
    reference's rules (see the module docstring), float32 throughout."""
    F = path.shape[1]
    m = path.astype(F32)
    k = m.sum(axis=1)
    eye = jnp.eye(F, dtype=F32)
    A = G[:, :F, :F] * m[:, :, None] * m[:, None, :] \
        + eye * (1.0 - m)[:, None, :] + lam * eye * m[:, None, :]
    a = G[:, :F, F] * m
    s = G[:, F, F]
    bz = b[:, :F] * m
    b1 = b[:, F]
    sd = jnp.where(s > 0, s, 1.0)
    # eliminate the constant column by the leaf's weighted means
    A = A - a[:, :, None] * a[:, None, :] / sd[:, None, None]
    r = bz - a * (b1 / sd)[:, None]
    d = jnp.sqrt(jnp.maximum(jnp.diagonal(A, axis1=1, axis2=2), 0.0))
    d = jnp.where(d > 0, d, 1.0)
    # the LU's products in float32: a TPU's default for a float32 matmul
    # is one bfloat16 pass
    with jax.default_matmul_precision("highest"):
        y = jnp.linalg.solve(A / (d[:, :, None] * d[:, None, :]),
                             (-r / d)[..., None])[..., 0]
    beta = jnp.where(path, y / d, 0.0)
    beta0 = -(b1 + jnp.sum(a * beta, axis=1)) / sd
    ok = ((clean >= k + 1.0) & (s > 0) & jnp.all(jnp.isfinite(beta), axis=1)
          & jnp.isfinite(beta0) & ~first)
    beta = jnp.where(jnp.abs(beta) > K_ZERO_THRESHOLD, beta, 0.0)
    const = beta0 - jnp.sum(beta * shift, axis=1)
    return (jnp.where(ok[:, None], beta, 0.0),
            jnp.where(ok, const, leaf_value))


def fit_leaves(tree, x, row_leaf, g, h, count, *, numerical, lam: float,
               first, impl: str = "pallas", interpret: bool = False):
    """``tree`` with ``leaf_const`` [L+1] and ``leaf_coeff`` [L+1, F]
    (unshrunk, builder feature order; 0 = not in the leaf's model) filled.
    ``x`` is :func:`raw_lanes` on the device, ``row_leaf`` the build's
    [R], ``g``, ``h`` the true gradients and hessians (after sampling),
    ``count`` the in-sample mask, ``numerical`` [F] bool, ``first`` a
    (traced) bool: the model's first tree, kept constant."""
    F = numerical.shape[0]
    slots = tree.leaf_values.shape[0]
    plan = linear_plan(F, slots, x.shape[1])
    Lp, Fp = plan.slots, plan.padded
    leaf_value = jnp.pad(tree.leaf_values, (0, Lp - slots))
    with profiler.stage(phases.LINEAR_SOLVE):
        path = path_masks(tree, numerical, Lp)
        path_tab = jnp.pad(path.astype(BF16).T,
                           ((0, _ceil_to(Fp, 16) - F), (0, 0)))
    aux = jnp.stack([g, h, count]).astype(F32)
    leaf = row_leaf.astype(jnp.int32)
    with profiler.stage(phases.LINEAR_GRAM):
        if impl == "pallas":
            mom = _moments_pallas(x, aux, leaf[None, :], path_tab, plan,
                                  interpret)
            sum_wx, sum_w, clean = mom[:F].T, mom[F], mom[Fp]
        else:
            sum_wx, sum_w, clean = _moments_xla(x, aux, leaf, path, plan)
    with profiler.stage(phases.LINEAR_SOLVE):
        mean = sum_wx / jnp.where(sum_w > 0, sum_w, 1.0)[:, None]
        # bfloat16, so that the one-hot matmul hands each row its leaf's
        # shift exactly; any shift near the mean serves
        shift = jnp.where(path & (sum_w > 0)[:, None] & jnp.isfinite(mean),
                          mean, 0.0)
        shift = _round_bf16(shift)
    with profiler.stage(phases.LINEAR_GRAM):
        if impl == "pallas":
            tab = jnp.concatenate(
                [jnp.pad(shift.T, ((0, Fp - F), (0, 0))).astype(BF16),
                 path_tab[:Fp]], axis=0)
            G, b = _unpack_gram(_gram_pallas(x, aux, leaf[None, :], tab,
                                             plan, interpret), plan)
        else:
            G, b = _gram_xla(x, aux, leaf, path, shift, plan)
    with profiler.stage(phases.LINEAR_SOLVE):
        coeff, const = _solve(G, b, path, clean, shift, leaf_value,
                              float(lam), jnp.asarray(first))
        return tree._replace(leaf_const=const[:slots],
                             leaf_coeff=coeff[:slots])


def leaf_outputs(tree, x, row_leaf, *, impl: str = "pallas",
                 interpret: bool = False):
    """[R] float32 unshrunk outputs of a linear tree at each row's leaf
    (0 for dead rows)."""
    slots, F = tree.leaf_coeff.shape
    plan = linear_plan(F, slots, x.shape[1])
    Lp, Fp = plan.slots, plan.padded
    coeff = jnp.pad(tree.leaf_coeff, ((0, Lp - slots), (0, Fp - F)))
    const = jnp.pad(tree.leaf_const, (0, Lp - slots))
    value = jnp.pad(tree.leaf_values, (0, Lp - slots))
    with profiler.stage(phases.LINEAR_UPDATE):
        if impl != "pallas":
            xr = x[:F].T
            rl = jnp.clip(row_leaf, 0)
            cr = jnp.take(coeff[:, :F], rl, axis=0)
            nan = jnp.isnan(xr)
            lin = jnp.sum(cr * jnp.where(nan, 0.0, xr), axis=1) \
                + jnp.take(const, rl)
            out = jnp.where(jnp.any(nan & (cr != 0), axis=1),
                            jnp.take(value, rl), lin)
            return jnp.where(row_leaf >= 0, out, 0.0)
        z8 = jnp.zeros((7, Lp), F32)
        block = jnp.concatenate([coeff.T, const[None], z8, value[None], z8],
                                axis=0)                     # [Fp + 16, Lp]
        tab = jnp.concatenate(_parts(block, 3)
                              + [(coeff.T != 0).astype(BF16)], axis=0)
        return _output_pallas(x, row_leaf.astype(jnp.int32)[None, :], tab,
                              plan, interpret)
