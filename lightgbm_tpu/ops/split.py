"""Best-split search over histograms.

TPU-native analog of the reference split finder (LightGBM
``src/treelearner/feature_histogram.hpp:165`` ``FindBestThreshold``,
``feature_histogram.cpp:120-360`` categorical,
``cuda/cuda_best_split_finder.cu``): for each (leaf, feature) scan bin
thresholds in both missing-direction variants and keep the max-gain split.

Design: the reference scans each histogram twice (missing-left /
missing-right) in scalar loops. Here the whole search is one vectorized
cumsum + gain evaluation over a dense [leaves, features, bins, 2] lattice —
an argmax XLA reduces on-device; no data-dependent control flow.

Gain math mirrors feature_histogram.hpp exactly (output-based form, so
constraints compose):
  ThresholdL1(s, l1)  = sign(s) * max(|s| - l1, 0)
  output(G, H)        = -ThresholdL1(G) / (H + l2), clipped by
                        max_delta_step, smoothed toward the parent output
                        when path_smooth > 0 (CalculateSplittedLeafOutput,
                        feature_histogram.hpp:717-756), clamped into the
                        leaf's monotone [lo, hi] range (BasicConstraint)
  gain_given_output   = -(2*ThresholdL1(G)*w + (H + l2)*w^2)
                        (GetLeafGainGivenOutput, feature_histogram.hpp:820)
  split_gain          = gain(left) + gain(right); 0 if the two outputs
                        violate the split feature's monotone direction
                        (GetSplitGains, feature_histogram.hpp:760-798)
  net gain            = split_gain - parent_gain - min_gain_to_split,
                        multiplied by the monotone depth penalty when the
                        split feature is constrained
                        (ComputeMonotoneSplitGainPenalty,
                        monotone_constraints.hpp:357-366)
Validity: counts >= min_data_in_leaf, hessians >= min_sum_hessian_in_leaf
on both sides; net gain must be positive (the reference's
``current_gain <= min_gain_shift`` rejection).

Categorical features with few bins use the one-hot path (bin == t goes
left) with plain lambda_l2 — feature_histogram.cpp:172-238 applies cat_l2
only on the sorted-subset branch (see ops/cat_split.py).

Extra-trees mode evaluates one random threshold per (leaf, feature)
(``rand_threshold``, feature_histogram.hpp:202-205); per-node feature
sampling and interaction constraints arrive pre-baked in ``feature_mask``.
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

__all__ = ["SplitParams", "find_best_splits", "leaf_output", "leaf_gain",
           "gain_given_output", "calc_output", "monotone_penalty_factor",
           "eval_split_lattice", "pack_member_bitset"]

NEG_INF = -jnp.inf
K_EPS = 1e-15


class SplitParams(NamedTuple):
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    min_data_in_leaf: float = 20.0
    min_sum_hessian_in_leaf: float = 1e-3
    min_gain_to_split: float = 0.0
    cat_l2: float = 10.0
    cat_smooth: float = 10.0
    max_delta_step: float = 0.0
    path_smooth: float = 0.0
    monotone_penalty: float = 0.0
    extra_trees: bool = False
    max_cat_threshold: int = 32
    max_cat_to_onehot: int = 4
    min_data_per_group: float = 100.0


def _threshold_l1(s, l1):
    if l1 <= 0.0:
        return s
    return jnp.sign(s) * jnp.maximum(jnp.abs(s) - l1, 0.0)


def leaf_gain(g, h, l1, l2):
    t = _threshold_l1(g, l1)
    return jnp.where(h + l2 > 0, t * t / (h + l2), 0.0)


def leaf_output(g, h, l1, l2, max_delta_step=0.0):
    out = jnp.where(h + l2 > 0, -_threshold_l1(g, l1) / (h + l2), 0.0)
    if max_delta_step > 0.0:
        out = jnp.clip(out, -max_delta_step, max_delta_step)
    return out


def calc_output(g, h, l1, l2, max_delta_step=0.0, path_smooth=0.0,
                count=None, parent_output=None):
    """CalculateSplittedLeafOutput (feature_histogram.hpp:717-740):
    raw regularized output, max_delta_step clip, then path smoothing
    toward the parent's output weighted by leaf count."""
    out = leaf_output(g, h, l1, l2, max_delta_step)
    if path_smooth > 0.0:
        sm = count / path_smooth
        out = out * sm / (sm + 1.0) + parent_output / (sm + 1.0)
    return out


def gain_given_output(g, h, l1, l2, out):
    """GetLeafGainGivenOutput (feature_histogram.hpp:820-831)."""
    t = _threshold_l1(g, l1)
    return -(2.0 * t * out + (h + l2) * out * out)


def monotone_penalty_factor(depth, penalization):
    """ComputeMonotoneSplitGainPenalty (monotone_constraints.hpp:357-366)."""
    depth = depth.astype(jnp.float32)
    pen_le1 = 1.0 - penalization / jnp.exp2(depth) + K_EPS
    pen_gt1 = 1.0 - jnp.exp2(penalization - 1.0 - depth) + K_EPS
    pen = jnp.where(penalization <= 1.0, pen_le1, pen_gt1)
    return jnp.where(penalization >= depth + 1.0, K_EPS, pen)


def pack_member_bitset(member: jax.Array) -> jax.Array:
    """Pack a [L, B] bin-membership mask into uint32 words (tree.h cat
    bitset layout), as `find_best_splits` returns a categorical
    winner's left-side bins."""
    L, B = member.shape
    BW = (B + 31) // 32
    pad = BW * 32 - B
    member_p = jnp.pad(member, ((0, 0), (0, pad)))
    weights = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))
    return jnp.sum(
        member_p.reshape(L, BW, 32).astype(jnp.uint32) * weights[None, None],
        axis=2, dtype=jnp.uint32)


def eval_split_lattice(hist: jax.Array, num_bins_per_feat: jax.Array,
                       nan_bin: jax.Array, is_cat: jax.Array,
                       params: SplitParams,
                       feature_mask: Optional[jax.Array] = None,
                       mono_type: Optional[jax.Array] = None,
                       leaf_lo: Optional[jax.Array] = None,
                       leaf_hi: Optional[jax.Array] = None,
                       parent_output: Optional[jax.Array] = None,
                       mono_pen: Optional[jax.Array] = None,
                       rand_bin: Optional[jax.Array] = None,
                       cat_sorted_mask: Optional[jax.Array] = None,
                       gain_scale: Optional[jax.Array] = None,
                       gain_penalty: Optional[jax.Array] = None,
                       adv_bounds: Optional[tuple] = None,
                       quant_scales: Optional[jax.Array] = None
                       ) -> Dict[str, jax.Array]:
    """Dense gain-lattice evaluation of `find_best_splits`, its one
    caller — everything up to but excluding the argmax, so the same
    math can run on any block of a histogram (a kernel that keeps the
    block in VMEM would need a prefix sum Mosaic can lower: `cumsum`
    has no Pallas TPU lowering).

    Same operands/semantics as `find_best_splits` except:
      mono_pen: optional [L] f32 — precomputed
        `monotone_penalty_factor(slot_depth, params.monotone_penalty)`
        (the depth→penalty map is the caller's job here).
      quant_scales: optional [2] or [L, 2] f32 — (g_scale, h_scale) for
        int8-quantized training. When given, `hist` holds raw int32
        accumulator sums; prefix scans run EXACTLY in integers and the
        cumulative sums are rescaled to f32 grid values only at gain
        time. The builder does not pass it: it dequantizes the whole
        histogram once a build (`tree_builder._dequant`; ROADMAP D9).

    Returns dict: net [L,F,B,2] (NEG_INF where invalid), left/right
    [L,F,B,2,3] (f32 grid values), out_l/out_r [L,F,B,2], pg [L,F],
    totals [L,F,3] (f32 grid values), is_cat2 [M,F].
    """
    L, F, B, _ = hist.shape
    l1, l2 = params.lambda_l1, params.lambda_l2
    mds = params.max_delta_step
    use_mono = mono_type is not None
    use_smooth = params.path_smooth > 0.0
    bins_iota = jnp.arange(B, dtype=jnp.int32)

    def _2d(a):
        return a if a is None or a.ndim == 2 else a[None, :]

    nbpf = _2d(num_bins_per_feat)                              # [M, F]
    nan2 = _2d(nan_bin)
    cat2 = _2d(is_cat)
    mono2 = _2d(mono_type) if use_mono else None

    has_nan = nan2 >= 0                                        # [M, F]
    # zero out the nan bin so cumsums cover non-missing rows only
    nan_mask = ((bins_iota[None, None, :] == nan2[:, :, None])
                & has_nan[:, :, None])                         # [M, F, B]
    hist_nonan = jnp.where(nan_mask[:, :, :, None],
                           jnp.zeros((), hist.dtype), hist)
    nan_sum = (hist * nan_mask[:, :, :, None]).sum(axis=2)     # [L, F, 3]

    totals = hist_nonan.sum(axis=2) + nan_sum                  # [L, F, 3]
    cum = jnp.cumsum(hist_nonan, axis=2)                       # [L, F, B, 3]

    # ---- numerical thresholds: left = {bin <= t}, two missing directions
    # option 0: missing right (default_left=False); option 1: missing left
    gl0 = cum
    gl1 = cum + nan_sum[:, :, None, :]
    tot = totals[:, :, None, :]
    num_left = jnp.stack([gl0, gl1], axis=3)                   # [L,F,B,2,3]
    num_right = tot[:, :, :, None, :] - num_left

    nnb = nbpf - has_nan.astype(jnp.int32)                     # non-nan bins
    t_valid = bins_iota[None, None, :] < (nnb[:, :, None] - 1)  # [M, F, B]
    # when the feature has no nan, option 1 duplicates option 0 — mask it
    opt_valid = jnp.stack(
        [jnp.ones_like(has_nan), has_nan], axis=-1)            # [M, F, 2]
    num_valid = (t_valid[:, :, :, None] & opt_valid[:, :, None, :]
                 & (~cat2)[:, :, None, None])                  # [M, F, B, 2]

    # ---- categorical one-hot: left = {bin == t}; sorted-path features are
    # excluded here (reference picks ONE path by bin count, not best-of-both)
    onehot_f = (cat2 & ~_2d(cat_sorted_mask)) \
        if cat_sorted_mask is not None else cat2
    cat_left = hist[:, :, :, None, :]                           # reuse lattice
    cat_right = tot[:, :, :, None, :] - cat_left
    cat_ok = ((bins_iota[None, None, :] < nnb[:, :, None])
              & onehot_f[:, :, None])                          # [M, F, B]
    # option-0 selector built from an iota (not a literal [True, False]
    # constant): the body then captures no array constant, which a
    # pallas_call tracing it would reject
    opt0 = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, 2), 3) == 0
    cat_valid = cat_ok[:, :, :, None] & opt0

    catsel = cat2[:, :, None, None, None]
    left = jnp.where(catsel, cat_left, num_left)
    right = jnp.where(catsel, cat_right, num_right)
    valid = jnp.where(cat2[:, :, None, None], cat_valid, num_valid)
    if rand_bin is not None:  # extra_trees: one threshold per (leaf, feat)
        valid = valid & (bins_iota[None, None, :, None]
                         == rand_bin[:, :, None, None])

    if quant_scales is not None:
        # exact integer scan → grid-value rescale at gain time; the count
        # channel scales by 1 so min_data thresholds stay exact
        qs = quant_scales.astype(jnp.float32)
        if qs.ndim == 1:
            qv = jnp.concatenate([qs, jnp.ones((1,), jnp.float32)])
            left = left.astype(jnp.float32) * qv
            right = right.astype(jnp.float32) * qv
            totals = totals.astype(jnp.float32) * qv
        else:                                                  # [L, 2]
            qv = jnp.concatenate(
                [qs, jnp.ones((qs.shape[0], 1), jnp.float32)], axis=1)
            left = left.astype(jnp.float32) * qv[:, None, None, None, :]
            right = right.astype(jnp.float32) * qv[:, None, None, None, :]
            totals = totals.astype(jnp.float32) * qv[:, None, :]

    gL, hL, nL = left[..., 0], left[..., 1], left[..., 2]
    gR, hR, nR = right[..., 0], right[..., 1], right[..., 2]

    # one-hot categorical uses plain l2 (feature_histogram.cpp:178 — cat_l2
    # applies only to sorted-subset splits)
    sm_kw_l = {}
    sm_kw_r = {}
    if use_smooth:
        po = parent_output[:, None, None, None]
        sm_kw_l = dict(path_smooth=params.path_smooth, count=nL,
                       parent_output=po)
        sm_kw_r = dict(path_smooth=params.path_smooth, count=nR,
                       parent_output=po)
    out_l = calc_output(gL, hL, l1, l2, mds, **sm_kw_l)
    out_r = calc_output(gR, hR, l1, l2, mds, **sm_kw_r)
    if adv_bounds is not None:
        a_lo_l, a_hi_l, a_lo_r, a_hi_r = adv_bounds
        out_l = jnp.clip(out_l, a_lo_l[:, :, :, None],
                         a_hi_l[:, :, :, None])
        out_r = jnp.clip(out_r, a_lo_r[:, :, :, None],
                         a_hi_r[:, :, :, None])
    elif use_mono:
        lo = leaf_lo[:, None, None, None]
        hi = leaf_hi[:, None, None, None]
        out_l = jnp.clip(out_l, lo, hi)
        out_r = jnp.clip(out_r, lo, hi)

    gain = (gain_given_output(gL, hL, l1, l2, out_l)
            + gain_given_output(gR, hR, l1, l2, out_r))
    if use_mono:
        mt = mono2[:, :, None, None]
        viol = (((mt > 0) & (out_l > out_r)) | ((mt < 0) & (out_l < out_r)))
        gain = jnp.where(viol, 0.0, gain)  # GetSplitGains returns 0

    md, mh = params.min_data_in_leaf, params.min_sum_hessian_in_leaf
    ok = (valid & (nL >= md) & (nR >= md) & (hL >= mh) & (hR >= mh))

    # parent gain (gain_shift, BeforeNumerical feature_histogram.hpp:198):
    # plain l2 for every feature (the categorical comment at
    # feature_histogram.cpp:164-166 — min_split_gain uses the original l2)
    g_tot, h_tot, n_tot = totals[..., 0], totals[..., 1], totals[..., 2]
    if use_smooth:
        # numerical: output smoothed toward the slot's own current output;
        # categorical: gain at the current output directly
        # (feature_histogram.cpp:160-166)
        p_out_num = calc_output(g_tot, h_tot, l1, l2, mds,
                                params.path_smooth, n_tot,
                                parent_output[:, None])
        p_out = jnp.where(cat2, parent_output[:, None], p_out_num)
        pg = gain_given_output(g_tot, h_tot, l1, l2, p_out)
    elif mds > 0.0:
        p_out = calc_output(g_tot, h_tot, l1, l2, mds)
        pg = gain_given_output(g_tot, h_tot, l1, l2, p_out)
    else:
        pg = leaf_gain(g_tot, h_tot, l1, l2)                    # [L, F]

    net = gain - pg[:, :, None, None] - params.min_gain_to_split
    net = jnp.where(ok & (net > 1e-10), net, NEG_INF)

    if use_mono and params.monotone_penalty > 0.0:
        mt = mono2[:, :, None, None]
        net = jnp.where(mt != 0, net * mono_pen[:, None, None, None], net)

    if gain_scale is not None:
        gs2 = gain_scale if gain_scale.ndim == 2 else gain_scale[None, :]
        net = jnp.where(jnp.isfinite(net),
                        net * gs2[:, :, None, None], net)
    if gain_penalty is not None:
        net = jnp.where(jnp.isfinite(net),
                        net - gain_penalty[:, :, None, None], net)
    if gain_scale is not None or gain_penalty is not None:
        # scaled/penalized gains that dropped to <= 0 are no longer
        # splittable (the reference stops on gain <= 0 downstream)
        net = jnp.where(net > 1e-10, net, NEG_INF)

    if feature_mask is not None:
        fm = (feature_mask[None, :] if feature_mask.ndim == 1
              else feature_mask)                                # [L, F]
        net = jnp.where(fm[:, :, None, None], net, NEG_INF)

    return {"net": net, "left": left, "right": right,
            "out_l": out_l, "out_r": out_r, "pg": pg,
            "totals": totals, "is_cat2": cat2}


def find_best_splits(hist: jax.Array, num_bins_per_feat: jax.Array,
                     nan_bin: jax.Array, is_cat: jax.Array,
                     params: SplitParams,
                     feature_mask: Optional[jax.Array] = None,
                     mono_type: Optional[jax.Array] = None,
                     leaf_lo: Optional[jax.Array] = None,
                     leaf_hi: Optional[jax.Array] = None,
                     parent_output: Optional[jax.Array] = None,
                     slot_depth: Optional[jax.Array] = None,
                     rand_bin: Optional[jax.Array] = None,
                     cat_sorted_mask: Optional[jax.Array] = None,
                     return_feature_gain: bool = False,
                     gain_scale: Optional[jax.Array] = None,
                     gain_penalty: Optional[jax.Array] = None,
                     adv_bounds: Optional[tuple] = None,
                     quant_scales: Optional[jax.Array] = None
                     ) -> Dict[str, jax.Array]:
    """Vectorized best split per leaf.

    Args:
      hist: [L, F, B, 3] (sum_grad, sum_hess, count) per (leaf, feature, bin).
      num_bins_per_feat: [F] or [L, F] int32 — valid bins per feature
        (<= B). All per-feature metadata below likewise accepts a
        per-slot [L, F] form — the voting-parallel learner's per-leaf
        elected feature subsets remap columns per slot.
      nan_bin: [F] or [L, F] int32 — NaN bin index, -1 if none.
      is_cat: [F] or [L, F] bool — categorical feature flags.
      params: SplitParams.
      feature_mask: optional [F] or [L, F] bool — candidate features,
        applied BEFORE the argmax (per-tree sampling, per-node sampling,
        interaction constraints).
      mono_type: optional [F] or [L, F] int32 in {-1, 0, 1}.
      leaf_lo / leaf_hi: optional [L] f32 — per-leaf output bounds
        (BasicConstraint of monotone_constraints.hpp).
      parent_output: optional [L] f32 — each slot's current output
        (unshrunk), required when path_smooth > 0.
      slot_depth: optional [L] int32 — leaf depth, for monotone_penalty.
      rand_bin: optional [L, F] int32 — extra-trees random threshold;
        only this bin is evaluated per (leaf, feature).
      cat_sorted_mask: optional [F] or per-slot [L, F] bool —
        categorical features with more than max_cat_to_onehot bins;
        they take the sorted-subset path (ops/cat_split.py) instead of
        one-hot (voting-parallel passes the per-slot elected form).
      return_feature_gain: also return "feature_gain" [L, F] — the best
        net gain per (leaf, feature) — for voting-parallel vote rounds.
      gain_scale: optional [F] or [L, F] f32 — multiplies each feature's
        net gain (feature_contri, feature_histogram.hpp:174
        ``output->gain *= meta_->penalty``).
      gain_penalty: optional [L, F] f32 — subtracted from each feature's
        net gain AFTER scaling (CEGB DeltaGain,
        cost_effective_gradient_boosting.hpp:80-98).
      adv_bounds: optional (lo_l, hi_l, lo_r, hi_r), each [L, F, B] f32
        — monotone_constraints_method=advanced per-candidate output
        bounds (AdvancedConstraintEntry's per-threshold-segment
        constraints, monotone_constraints.hpp:858, in dense lattice
        form). When given, they replace the scalar leaf_lo/leaf_hi clip
        for the threshold lattice; leaf_lo/leaf_hi (scalars, computed by
        the caller for whole-leaf adjacency) still drive the sorted-cat
        path.

    Returns dict with per-leaf arrays:
      gain [L] — NET gain (split - parent - min_gain_to_split, penalized;
        -inf when no valid split), feature [L], threshold [L],
      default_left [L] bool, left_sum/right_sum [L, 3],
      left_out/right_out [L] (constrained outputs), is_cat_split [L],
      cat_bitset [L, ceil(B/32)] uint32 — bin-space LEFT subset for
        categorical winners (single bit for one-hot).

    quant_scales: optional [2] or [L, 2] f32 (g_scale, h_scale) — when
    given, `hist` holds raw int32 quantized accumulator sums and the scan
    runs exactly in integers with a grid-value rescale at gain time (see
    `eval_split_lattice`). Incompatible with `cat_sorted_mask` (the
    sorted-cat path expects dequantized histograms).
    """
    L, F, B, _ = hist.shape
    if quant_scales is not None and cat_sorted_mask is not None:
        raise ValueError("quant_scales is incompatible with cat_sorted_mask")
    mono_pen = None
    if mono_type is not None and params.monotone_penalty > 0.0:
        mono_pen = monotone_penalty_factor(slot_depth,
                                           params.monotone_penalty)
    lat = eval_split_lattice(
        hist, num_bins_per_feat, nan_bin, is_cat, params,
        feature_mask=feature_mask, mono_type=mono_type,
        leaf_lo=leaf_lo, leaf_hi=leaf_hi, parent_output=parent_output,
        mono_pen=mono_pen, rand_bin=rand_bin,
        cat_sorted_mask=cat_sorted_mask, gain_scale=gain_scale,
        gain_penalty=gain_penalty, adv_bounds=adv_bounds,
        quant_scales=quant_scales)
    net, left, right = lat["net"], lat["left"], lat["right"]
    out_l, out_r, pg, cat2 = (lat["out_l"], lat["out_r"], lat["pg"],
                              lat["is_cat2"])
    bins_iota = jnp.arange(B, dtype=jnp.int32)

    # ---- argmax over (F, B, 2) per leaf
    flat = net.reshape(L, F * B * 2)
    best = jnp.argmax(flat, axis=1)
    best_gain = jnp.take_along_axis(flat, best[:, None], axis=1)[:, 0]
    feat = (best // (B * 2)).astype(jnp.int32)
    thr = ((best // 2) % B).astype(jnp.int32)
    opt = (best % 2).astype(jnp.int32)
    default_left = opt == 1
    feature_gain = net.max(axis=(2, 3)) if return_feature_gain else None

    def take3(a):
        af = a.reshape(L, F * B * 2, 3)
        return jnp.take_along_axis(af, best[:, None, None], axis=1)[:, 0, :]

    def take1(a):
        af = a.reshape(L, F * B * 2)
        return jnp.take_along_axis(af, best[:, None], axis=1)[:, 0]

    out = {
        "gain": best_gain,
        "feature": feat,
        "threshold": thr,
        "default_left": default_left,
        "left_sum": take3(left),
        "right_sum": take3(right),
        "left_out": take1(out_l),
        "right_out": take1(out_r),
        "is_cat_split": jnp.take_along_axis(
            jnp.broadcast_to(cat2, (L, F)), feat[:, None], axis=1)[:, 0],
    }
    if return_feature_gain:
        out["feature_gain"] = feature_gain

    # one-hot winners' membership mask (single bin goes left)
    member = ((bins_iota[None, :] == thr[:, None])
              & out["is_cat_split"][:, None]
              & jnp.isfinite(best_gain)[:, None])               # [L, B]

    if cat_sorted_mask is not None:
        from .cat_split import find_best_cat_sorted
        srt = find_best_cat_sorted(
            hist, num_bins_per_feat, cat_sorted_mask, params, pg,
            feature_mask=feature_mask, leaf_lo=leaf_lo, leaf_hi=leaf_hi,
            parent_output=parent_output, rand_bin=rand_bin)
        # sorted-cat candidates compete against scaled/penalized gains —
        # charge them the same feature_contri scale and CEGB penalty
        if gain_scale is not None or gain_penalty is not None:
            sg = srt["gain"]
            sf = srt["feature"][:, None]
            if gain_scale is not None:
                gs2b = jnp.broadcast_to(
                    gain_scale if gain_scale.ndim == 2
                    else gain_scale[None, :], (L, F))
                sg = jnp.where(jnp.isfinite(sg), sg * jnp.take_along_axis(
                    gs2b, sf, axis=1)[:, 0], sg)
            if gain_penalty is not None:
                sg = jnp.where(jnp.isfinite(sg), sg - jnp.take_along_axis(
                    gain_penalty, sf, axis=1)[:, 0], sg)
            srt["gain"] = jnp.where(sg > 1e-10, sg, NEG_INF)
        if return_feature_gain:
            out["feature_gain"] = jnp.maximum(out["feature_gain"],
                                              srt["feature_gain"])
        pick = srt["gain"] > out["gain"]
        out["gain"] = jnp.where(pick, srt["gain"], out["gain"])
        out["feature"] = jnp.where(pick, srt["feature"], out["feature"])
        out["threshold"] = jnp.where(pick, 0, out["threshold"])
        out["default_left"] = jnp.where(pick, False, out["default_left"])
        out["left_sum"] = jnp.where(pick[:, None], srt["left_sum"],
                                    out["left_sum"])
        out["right_sum"] = jnp.where(pick[:, None], srt["right_sum"],
                                     out["right_sum"])
        out["left_out"] = jnp.where(pick, srt["left_out"], out["left_out"])
        out["right_out"] = jnp.where(pick, srt["right_out"],
                                     out["right_out"])
        out["is_cat_split"] = jnp.where(pick, True, out["is_cat_split"])
        member = jnp.where(pick[:, None], srt["member"], member)

    out["cat_bitset"] = pack_member_bitset(member)
    return out
