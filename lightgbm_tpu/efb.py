"""Exclusive Feature Bundling (EFB).

TPU-native analog of the reference's feature bundling
(``include/LightGBM/feature_group.h:26`` FeatureGroup;
``src/io/dataset_loader.cpp`` FindGroups/greedy bundling): features that
are (almost) never simultaneously non-default share one storage column.

Why it matters MORE on TPU than on CPU: the histogram kernel's lattice is
``stored columns x bins of the widest column`` — one dense 255-bin feature
among 4000 binary ones would blow it up to ``4000 x 255`` lanes. Bundling
packs the sparse features into a few 256-bin columns. What then scales
with the number of BUNDLES: the bin matrix in HBM (``[R, G]`` bytes), the
kernel's one-hot work (``G x 256`` lanes) and the raw histogram cache.
What still scales with FEATURES x BINS: the split search. Every round the
bundle-space histogram is gathered back to a feature-space lattice
``[slots, F, B, 3]`` (``tree_builder.py`` ``unbundle``, stage
``unbundle``) and ``ops/split.find_best_splits`` scans all of it, padding
included (with 4,212 one-hot columns beside 16 of 255 bins, 1.6% of the
positions hold a bin). ``dataset.estimate_device_bytes`` counts that
lattice; a search in bundle space is named in ROADMAP.md.

Encoding (per bundle g with members f_1..f_m at offsets o_1..o_m):
- bundle bin 0  = every member at its most-frequent bin;
- bundle bin o_j + b = member f_j at bin b (b != mfb_j never collides
  since o_j >= 1 and ranges are disjoint); when two members are
  non-default in the same row (a "conflict": none in the plan's sample
  at ``max_conflict_rate=0``, but rare values of different members can
  still meet somewhere in all the rows) the member with the HIGHER
  feature index wins: columns are written in feature order and a later
  write replaces an earlier one — the same information loss the
  reference accepts. :func:`encode_bundles` counts the rows that lost a
  value that way (``conflict_rows``).

Recovery of per-feature histograms never needs the default-bin counts
stored: ``hist_f[mfb_f] = leaf_totals - sum(other bins)`` — exactly the
reference's FixHistogram most-frequent-bin accounting
(``src/io/dataset.cpp:1488``).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Sequence

import numpy as np

__all__ = ["BundlePlan", "plan_bundles", "plan_from_masks",
           "pack_nondefault", "encode_bundles", "decode_feature_bins",
           "encode_rows"]


def decode_feature_bins(raw, off, nb, mfb, xp=np):
    """Bundle-column value -> a feature's own bin id.

    THE one decode formula (train partition, device predict, host replay
    all call this): inside the feature's range -> raw - offset; outside
    -> the feature's most-frequent bin. Singleton bundles use offset 0
    and store every row directly, so the fallback never fires for them.
    ``xp`` is numpy or jax.numpy.
    """
    return xp.where((raw >= off) & (raw < off + nb), raw - off, mfb)


@dataclass
class BundlePlan:
    """Static bundling layout shared by train/valid datasets."""
    # per original (used) feature:
    feat_bundle: np.ndarray     # [F] int32 bundle column id
    feat_offset: np.ndarray     # [F] int32 offset of the feature's range
    feat_mfb: np.ndarray        # [F] int32 most-frequent (default) bin
    # layout:
    num_bundles: int
    bundle_num_bins: np.ndarray  # [G] int32 (1 + sum of member bins)
    max_bundle_bins: int         # B_g for the histogram lattice
    # rows of the plan's sample in which two members of a bundle met
    # (admitted up to max_conflict_rate); not part of the saved layout
    sample_conflicts: int = 0

    @property
    def is_trivial(self) -> bool:
        return self.num_bundles >= len(self.feat_bundle)

    def state_arrays(self):
        return (self.feat_bundle, self.feat_offset, self.feat_mfb,
                self.bundle_num_bins,
                np.asarray([self.num_bundles, self.max_bundle_bins]))

    @classmethod
    def from_state_arrays(cls, fb, fo, fm, bnb, scal):
        return cls(feat_bundle=fb, feat_offset=fo, feat_mfb=fm,
                   num_bundles=int(scal[0]), bundle_num_bins=bnb,
                   max_bundle_bins=int(scal[1]))


def _popcount(x: np.ndarray) -> int:
    return int(np.bitwise_count(x).sum())


def pack_nondefault(num_rows: int, rows, bins: np.ndarray, zero_bin: int,
                    most_freq: int):
    """(bit set [ceil(S / 64)] uint64, count) of the sample rows at which
    a feature is NOT at its most frequent bin. ``rows`` (ascending, or
    None for every row) hold ``bins``; every other row holds
    ``zero_bin`` — which need not be the most frequent bin (a column that
    stores a value in most rows), and then every row not named counts."""
    if rows is None:
        mask = np.asarray(bins) != most_freq
    elif zero_bin == most_freq:
        mask = np.zeros(num_rows, bool)
        mask[rows[bins != most_freq]] = True
    else:
        mask = np.ones(num_rows, bool)
        mask[rows[bins == most_freq]] = False
    bits = np.zeros(-(-num_rows // 64) * 8, np.uint8)
    packed = np.packbits(mask)
    bits[:len(packed)] = packed
    return bits.view(np.uint64), int(mask.sum())


def plan_bundles(sample_bins: np.ndarray, num_bins: Sequence[int],
                 most_freq: Sequence[int], *,
                 max_conflict_rate: float = 0.0,
                 max_bundle_bins: int = 256) -> BundlePlan:
    """:func:`plan_from_masks` of a dense ``[S, F]`` sample of bins."""
    S, F = sample_bins.shape
    masks = [pack_nondefault(S, None, sample_bins[:, f], 0, most_freq[f])
             for f in range(F)]
    return plan_from_masks([m for m, _ in masks], [c for _, c in masks], S,
                           num_bins, most_freq,
                           max_conflict_rate=max_conflict_rate,
                           max_bundle_bins=max_bundle_bins)


def plan_from_masks(packed, nz_count, num_rows: int,
                    num_bins: Sequence[int], most_freq: Sequence[int], *,
                    max_conflict_rate: float = 0.0,
                    max_bundle_bins: int = 256) -> BundlePlan:
    """Greedy conflict-bounded packing (dataset_loader FindGroups).

    ``packed[f]`` / ``nz_count[f]``: :func:`pack_nondefault` of feature
    ``f`` over a sample of ``num_rows`` rows; num_bins/most_freq per
    feature. Features are ordered by non-default count (descending) and
    placed into the first bundle whose accumulated conflicts and bin
    budget allow, else open a new bundle. The cost is the sample's bit
    sets (S / 8 bytes a feature), never an ``[S, F]`` array.
    """
    S, F = num_rows, len(packed)
    nb = np.asarray(num_bins, np.int64)
    mfb = np.asarray(most_freq, np.int64)
    nz_count = np.asarray(nz_count, np.int64)
    max_conflicts = int(max_conflict_rate * S)

    order = np.argsort(-nz_count, kind="stable")
    bundles: List[dict] = []   # {members, bits, conflicts, bins}
    for f in order:
        placed = False
        # dense-ish features (no realistic exclusivity) go solo fast
        if nz_count[f] * 2 > S or nb[f] + 1 > max_bundle_bins:
            bundles.append(dict(members=[int(f)], bits=packed[f].copy(),
                                conflicts=0, bins=1 + int(nb[f])))
            continue
        for bd in bundles:
            if len(bd["members"]) == 1 and \
                    nz_count[bd["members"][0]] * 2 > S:
                continue  # don't co-bundle with dense columns
            if bd["bins"] + nb[f] > max_bundle_bins:
                continue
            c = _popcount(np.bitwise_and(bd["bits"], packed[f]))
            if bd["conflicts"] + c <= max_conflicts:
                bd["members"].append(int(f))
                bd["bits"] |= packed[f]
                bd["conflicts"] += c
                bd["bins"] += int(nb[f])
                placed = True
                break
        if not placed:
            bundles.append(dict(members=[int(f)], bits=packed[f].copy(),
                                conflicts=0, bins=1 + int(nb[f])))

    feat_bundle = np.zeros(F, np.int32)
    feat_offset = np.zeros(F, np.int32)
    bundle_bins = []
    for g, bd in enumerate(bundles):
        if len(bd["members"]) == 1:
            # singleton: store raw bins at offset 0 (no shared
            # all-default slot) — keeps a 256-bin feature inside uint8
            f = bd["members"][0]
            feat_bundle[f] = g
            feat_offset[f] = 0
            bundle_bins.append(int(nb[f]))
            continue
        off = 1
        for f in bd["members"]:
            feat_bundle[f] = g
            feat_offset[f] = off
            off += int(nb[f])
        bundle_bins.append(off)
    return BundlePlan(
        feat_bundle=feat_bundle, feat_offset=feat_offset,
        feat_mfb=mfb.astype(np.int32), num_bundles=len(bundles),
        bundle_num_bins=np.asarray(bundle_bins, np.int32),
        max_bundle_bins=int(max(bundle_bins)) if bundle_bins else 1,
        sample_conflicts=sum(bd["conflicts"] for bd in bundles))


def encode_bundles(plan: BundlePlan, col_bins_iter, num_rows: int,
                   counters: Optional[dict] = None,
                   out: Optional[np.ndarray] = None) -> np.ndarray:
    """[R, G] bundled bin matrix from per-feature DENSE bin columns, in
    the order they come (ascending feature index: a later member
    overwrites an earlier one on a conflict row).

    col_bins_iter yields (feature_index, bins[R]) — streaming, so a full
    [R, F] matrix of bins never exists. (Sparse input does not come here:
    ``Dataset._apply_sparse_block`` writes the same matrix from the
    stored values.) ``out``: a zeroed ``[num_rows, G]`` block of a larger
    matrix to write into. ``counters``, where given, has
    ``conflict_rows`` raised by the rows in which some member's
    non-default value was overwritten by a later member's.
    """
    dtype = np.uint8 if plan.max_bundle_bins <= 256 else np.int32
    if out is None:
        out = np.zeros((num_rows, plan.num_bundles), dtype)
    lost = np.zeros(num_rows, bool)
    for f, col in col_bins_iter:
        g = plan.feat_bundle[f]
        off = plan.feat_offset[f]
        if off == 0:            # singleton bundle: raw bins
            out[:, g] = col.astype(dtype)
            continue
        mfb = plan.feat_mfb[f]
        nz = col != mfb
        lost |= nz & (out[:, g] != 0)       # an earlier member was there
        out[nz, g] = (off + col[nz]).astype(dtype)
    if counters is not None:
        counters["conflict_rows"] = (counters.get("conflict_rows", 0)
                                     + int(lost.sum()))
    return out


def encode_rows(plan: BundlePlan, batch_bins: np.ndarray,
                out: np.ndarray, row0: int) -> None:
    """Encode a [r, F] per-feature bin batch into out[row0:row0+r, G]
    (streaming/Sequence ingestion path)."""
    r = batch_bins.shape[0]
    view = out[row0:row0 + r]
    view[:] = 0
    for f in range(batch_bins.shape[1]):
        g = plan.feat_bundle[f]
        off = plan.feat_offset[f]
        col = batch_bins[:, f]
        if off == 0:
            view[:, g] = col.astype(out.dtype)
            continue
        nz = col != plan.feat_mfb[f]
        view[nz, g] = (off + col[nz]).astype(out.dtype)
