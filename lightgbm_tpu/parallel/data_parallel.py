"""Data-parallel tree learning over a device mesh.

TPU-native analog of the reference distributed tree learners
(``src/treelearner/data_parallel_tree_learner.cpp`` +
``src/network/network.cpp``; SURVEY.md §2.3/§2.4):

- The reference shards rows across machines, builds local histograms for all
  features, merges them with ``Network::ReduceScatter`` (per-worker feature
  blocks), finds the best split for the local block, and syncs the winner with
  ``Allreduce(max-gain)`` (``SyncUpGlobalBestSplit``,
  ``parallel_tree_learner.h:209``).
- Here the row shard lives on each chip of a ``jax.sharding.Mesh`` axis
  (ICI within a slice, DCN across hosts). The histogram merge is
  selectable via ``hist_merge`` (``dp_hist_merge`` param /
  ``LIGHTGBM_TPU_DP_HIST_MERGE`` env):

  * ``reduce_scatter`` (the default on any multi-chip mesh): the
    reference's TRUE algorithm — ``jax.lax.psum_scatter`` along the
    feature-slot axis hands each chip only its F_pad/n block of the
    merged histogram, ``best_for`` split finding runs on the local
    block only, and winners merge with the SplitInfo-sized pmax/psum
    pair feature-parallel already uses (``SyncUpGlobalBestSplit``).
    Per-round wire bytes halve vs allreduce ((n-1)/n x payload instead
    of 2(n-1)/n), each chip materializes 1/n of the histogram, the
    per-leaf histogram-subtraction cache is slot-sharded (HBM/n), and
    split finding stops being n-redundant — the PV-Tree/DCN bottleneck
    economics (PAPERS.md: arxiv 1611.01276, 1806.11248).
  * ``allreduce``: one ``jax.lax.psum`` of the full histogram inside
    ``ops/histogram.py``. After the psum the histogram is replicated, so
    every chip runs the *same* split selection and produces the *same*
    tree — a deterministic replicated argmax needs no winner sync at
    all. Kept as the fallback formulation (forced splits pin it) and as
    the ablation baseline the collective auditor compares against.
- The machines/ports machinery (``linkers_socket.cpp``) is replaced by
  ``jax.distributed`` + the mesh; topology/algorithm selection
  (Bruck/recursive-halving, ``linker_topo.cpp``) becomes XLA's problem.

Feature-parallel and voting-parallel (SURVEY.md §2.3) remap here too:
with rows replicated and features sharded the same program becomes
feature-parallel (slot histograms are feature-disjoint, so NO histogram
collective is emitted at all — the auditor asserts zero); voting's
elected-column merge rides the same ``hist_merge`` knob — under
``reduce_scatter`` the top-2k sub-histogram merges into the scattered
slot space instead of replicating.

``parallel/comms.py`` audits the compiled HLO of these programs:
collective op counts, per-op bytes, and the allreduce-vs-reduce_scatter
byte ratio (``scripts/audit_collectives.py`` wires it into CI).
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.split import SplitParams
from ..boosting.tree_builder import RoundLog, build_tree, TreeArrays

__all__ = ["make_mesh", "shard_rows", "replicate", "build_tree_dp",
           "resolve_hist_merge",
           "DataParallelPlan", "VotingParallelPlan", "FeatureParallelPlan"]

AXIS = "data"

HIST_MERGE_MODES = ("auto", "allreduce", "reduce_scatter")


def resolve_hist_merge(mode: str, n_shards: int) -> str:
    """Resolve the ``dp_hist_merge`` knob to a concrete collective.

    ``LIGHTGBM_TPU_DP_HIST_MERGE`` overrides the param (the same env-pin
    pattern as LIGHTGBM_TPU_FUSED_TRAIN); ``auto`` picks
    ``reduce_scatter`` on any multi-chip mesh and degenerates to
    ``allreduce`` on one shard (where both lower to nothing)."""
    import os
    env = os.environ.get("LIGHTGBM_TPU_DP_HIST_MERGE", "")
    if env:
        mode = env
    if mode not in HIST_MERGE_MODES:
        raise ValueError(
            f"dp_hist_merge must be one of {HIST_MERGE_MODES}, "
            f"got {mode!r}")
    if mode == "auto":
        return "reduce_scatter" if n_shards > 1 else "allreduce"
    return mode


def make_mesh(devices: Optional[Sequence[jax.Device]] = None,
              axis_name: str = AXIS) -> Mesh:
    """1-D data mesh over all (or the given) devices."""
    devices = list(devices if devices is not None else jax.devices())
    return Mesh(np.asarray(devices), (axis_name,))


def shard_rows(mesh: Mesh, arr, axis_name: str = AXIS) -> jax.Array:
    """Place an array on the mesh sharded along its leading (row) axis."""
    spec = P(axis_name, *([None] * (np.ndim(arr) - 1)))
    return jax.device_put(arr, NamedSharding(mesh, spec))


def replicate(mesh: Mesh, arr) -> jax.Array:
    """Place ``arr`` replicated on every device of the mesh. In a
    multi-controller run the mesh spans processes, so the global array
    is assembled from each process's (identical) full copy — device_put
    cannot place onto non-addressable devices."""
    sh = NamedSharding(mesh, P())
    if jax.process_count() > 1:
        return jax.make_array_from_process_local_data(sh, np.asarray(arr))
    return jax.device_put(arr, sh)


class DataParallelPlan:
    """Holds the mesh + sharding helpers for one training run.

    The analog of the reference's ``Network::Init`` + per-machine rank state
    (``network.cpp:17-58``): constructed once, then every tree build routes
    through :meth:`build_tree` below.
    """

    parallel_mode = "data"   # tree_learner= analog (tree_learner.cpp:15)
    rows_sharded = True

    def __init__(self, devices: Optional[Sequence[jax.Device]] = None,
                 axis_name: str = AXIS, top_k: int = 20,
                 hist_merge: str = "auto"):
        self.mesh = make_mesh(devices, axis_name)
        self.axis_name = axis_name
        self.num_shards = self.mesh.devices.size
        self.top_k = top_k
        # histogram merge collective (reduce_scatter on real meshes —
        # see the module docstring); resolved once, after the mesh size
        # is known
        self.hist_merge = resolve_hist_merge(hist_merge, self.num_shards)
        # multi-host: each process feeds its own pre-partitioned row
        # shard (the rank/num_machines loading path of
        # dataset_loader.cpp:203); device_put cannot address remote
        # shards, so placement goes through
        # jax.make_array_from_process_local_data instead.
        self.num_processes = jax.process_count()
        self.multi_process = self.num_processes > 1

    def supports_fused(self) -> bool:
        """Whether gbdt's fused single-dispatch step may stage this
        plan's tree build inside its outer jit. Single-controller
        meshes compose (the shard_map build nests in the fused trace
        and the psum stays the only cross-chip traffic); multi-process
        runs assemble per-host blocks with host-side placement calls
        between phases, which the fused trace cannot contain."""
        return not self.multi_process

    def pad_to(self, num_rows: int, block: int) -> int:
        """GLOBAL padded row count. ``num_rows`` is this process's local
        row count (they differ across hosts); every process pads its
        shard to the same synced size so the global array is
        rectangular."""
        if not self.multi_process:
            unit = block * self.num_shards
            return ((num_rows + unit - 1) // unit) * unit
        from jax.experimental import multihost_utils
        d_local = self.num_shards // self.num_processes
        unit = block * d_local
        local_pad = ((num_rows + unit - 1) // unit) * unit
        all_pads = multihost_utils.process_allgather(
            np.asarray([local_pad], np.int64))
        return int(all_pads.max()) * self.num_processes

    def local_rows(self, r_pad: int) -> int:
        """Rows this process contributes to a [r_pad, ...] global array."""
        return r_pad // self.num_processes if self.multi_process else r_pad

    def shard_rows(self, arr):
        """Place rows on the mesh. Single-process: ``arr`` is the full
        array. Multi-process: ``arr`` is this process's LOCAL block of
        ``local_rows(r_pad)`` rows."""
        if not self.multi_process:
            return shard_rows(self.mesh, arr, self.axis_name)
        spec = P(self.axis_name, *([None] * (np.ndim(arr) - 1)))
        return jax.make_array_from_process_local_data(
            NamedSharding(self.mesh, spec), np.asarray(arr))

    def shard_bins(self, arr):
        """Place a [rows, features] bin matrix on the mesh. Data/voting
        plans shard its ROWS like every other per-row array."""
        return self.shard_rows(arr)

    def shard_scores(self, local_kr):
        """[K, local_rows] host block -> [K, r_pad] global, row axis 1.
        Placed as the fused step hands the scores back (rows over the
        mesh, committed), so the step that tree 0 compiles is the step
        every later tree runs."""
        sh = NamedSharding(self.mesh, P(None, self.axis_name))
        if not self.multi_process:
            return jax.device_put(local_kr, sh)
        return jax.make_array_from_process_local_data(
            sh, np.asarray(local_kr))

    def host_local_cols(self, arr, num_valid: int):
        """[K, r_pad] global -> this process's [K, num_valid] host block
        (the per-machine metric view of the reference's distributed
        learners — each machine evaluates its own rows)."""
        if not self.multi_process:
            return np.asarray(arr)[:, :num_valid]
        shards = [s for s in arr.addressable_shards]
        shards.sort(key=lambda s: s.index[1].start or 0)
        loc = np.concatenate([np.asarray(s.data) for s in shards], axis=1)
        return loc[:, :num_valid]

    def replicate(self, arr):
        return replicate(self.mesh, arr)   # module fn: multi-proc aware

    def build_tree(self, bins, gh, row_leaf0, num_bins_pf, nan_bin_pf,
                   is_cat_pf, feature_mask, *, num_leaves: int,
                   leaf_batch: int, max_depth: int, num_bins: int,
                   split_params: SplitParams, hist_dtype: str = "bfloat16",
                   hist_impl: str = "auto", block_rows: int = 0,
                   valid_bins: Tuple[jax.Array, ...] = (),
                   valid_row_leaf0: Tuple[jax.Array, ...] = (),
                   mono_type_pf=None, interaction_groups=None,
                   rng_key=None, feature_fraction_bynode: float = 1.0,
                   bundle_meta=None, bundle_bins: int = 0,
                   quant_scales=None, mono_method: str = "basic",
                   cat_sorted_mask=None, forced=None,
                   hist_sub: bool = True, class_batched: bool = False):
        return build_tree_dp(
            self.mesh, bins, gh, row_leaf0, num_bins_pf, nan_bin_pf,
            is_cat_pf, feature_mask, num_leaves=num_leaves,
            leaf_batch=leaf_batch, max_depth=max_depth, num_bins=num_bins,
            split_params=split_params, axis_name=self.axis_name,
            hist_dtype=hist_dtype, hist_impl=hist_impl,
            block_rows=block_rows,
            valid_bins=valid_bins, valid_row_leaf0=valid_row_leaf0,
            mono_type_pf=mono_type_pf,
            interaction_groups=interaction_groups, rng_key=rng_key,
            feature_fraction_bynode=feature_fraction_bynode,
            parallel_mode=self.parallel_mode, top_k=self.top_k,
            bundle_meta=bundle_meta, bundle_bins=bundle_bins,
            quant_scales=quant_scales, mono_method=mono_method,
            cat_sorted_mask=cat_sorted_mask, forced=forced,
            hist_sub=hist_sub, hist_merge=self.hist_merge,
            class_batched=class_batched)


class VotingParallelPlan(DataParallelPlan):
    """PV-Tree voting-parallel (voting_parallel_tree_learner.cpp:16-120):
    same row sharding as data-parallel, but per-round communication is
    votes + the elected feature columns only — O(top_k*B) instead of
    O(F*B). Use when F*B is large enough that the histogram merge
    dominates ICI/DCN time. Rides the same ``hist_merge`` knob: under
    ``reduce_scatter`` the elected top-2k column merge lands
    slot-SHARDED (each chip searches its elected-column block, winners
    sync SplitInfo-sized) instead of replicating — wire bytes halve
    again on top of the election saving."""
    parallel_mode = "voting"


class FeatureParallelPlan:
    """Feature-parallel (feature_parallel_tree_learner.cpp:38-77): every
    chip holds ALL rows (the reference's model — each worker has the full
    dataset), split WORK is sharded by feature, and the winning split is
    merged by a gain argmax across chips, then applied locally by every
    chip. No histogram merge at all; the per-round communication is one
    SplitInfo-sized pmax/psum pair per leaf batch."""

    parallel_mode = "feature"
    rows_sharded = False

    def __init__(self, devices: Optional[Sequence[jax.Device]] = None,
                 axis_name: str = AXIS, top_k: int = 20,
                 shard_storage: bool = False):
        self.mesh = make_mesh(devices, axis_name)
        self.axis_name = axis_name
        self.num_shards = self.mesh.devices.size
        self.top_k = top_k
        # feature_shard_storage: each device stores only its own
        # [R, F/num_shards] feature slice of the bin matrix instead of
        # a replicated copy — the split work is feature-local either
        # way; only the partition step needs the one-hot psum (see
        # build_tree(feature_sharded=True)). This is how a bin matrix
        # wider than one chip's HBM becomes trainable.
        self.shard_storage = shard_storage
        self.num_processes = jax.process_count()
        self.multi_process = self.num_processes > 1
        if self.multi_process and shard_storage:
            # cross-host column sharding would need pre-sharded loading
            # (each host materializing only its columns); today every
            # worker holds the full matrix like the reference's
            # feature_parallel_tree_learner.cpp:38 model
            raise NotImplementedError(
                "feature_shard_storage is single-host; multi-host "
                "feature-parallel replicates the full matrix per "
                "worker (set feature_shard_storage=false)")

    # same single-controller rule as the data plan: the feature-sharded
    # build (and its winner argmax-merge) nests inside the fused trace
    supports_fused = DataParallelPlan.supports_fused

    def pad_to(self, num_rows: int, block: int) -> int:
        return ((num_rows + block - 1) // block) * block

    def local_rows(self, r_pad: int) -> int:
        return r_pad

    def shard_rows(self, arr):
        # rows live whole on every chip
        return replicate(self.mesh, arr)

    def shard_bins(self, arr):
        """Bin matrices: replicated normally; column-sharded (feature
        axis padded host-side to a multiple of the shard count) with
        ``shard_storage`` so each device holds [R, F_pad/n]."""
        if not self.shard_storage:
            return replicate(self.mesh, arr)
        n = self.num_shards
        F = arr.shape[1]
        F_pad = -(-F // n) * n
        if F_pad != F:
            arr = np.pad(np.asarray(arr), ((0, 0), (0, F_pad - F)))
        return jax.device_put(
            arr, NamedSharding(self.mesh, P(None, self.axis_name)))

    def shard_scores(self, local_kr):
        # every worker holds the full score block, placed as the fused
        # step hands it back (replicated, committed: one compile);
        # multi-controller runs assemble it into a GLOBAL array
        return replicate(self.mesh, np.asarray(local_kr))

    def host_local_cols(self, arr, num_valid: int):
        return np.asarray(arr)[:, :num_valid]

    def replicate(self, arr):
        return replicate(self.mesh, arr)   # module fn: multi-proc aware

    def build_tree(self, bins, gh, row_leaf0, num_bins_pf, nan_bin_pf,
                   is_cat_pf, feature_mask, *, num_leaves: int,
                   leaf_batch: int, max_depth: int, num_bins: int,
                   split_params: SplitParams, hist_dtype: str = "bfloat16",
                   hist_impl: str = "auto", block_rows: int = 0,
                   valid_bins: Tuple[jax.Array, ...] = (),
                   valid_row_leaf0: Tuple[jax.Array, ...] = (),
                   mono_type_pf=None, interaction_groups=None,
                   rng_key=None, feature_fraction_bynode: float = 1.0,
                   quant_scales=None, mono_method: str = "basic",
                   cat_sorted_mask=None, hist_sub: bool = True):
        has_mono = mono_type_pf is not None
        mono_arr = (mono_type_pf if has_mono
                    else jnp.zeros_like(num_bins_pf))
        return _build_tree_fp_jit(
            self.mesh, bins, gh, row_leaf0, num_bins_pf, nan_bin_pf,
            is_cat_pf, feature_mask,
            tuple(valid_bins) + tuple(valid_row_leaf0), mono_arr,
            (quant_scales, interaction_groups, rng_key, cat_sorted_mask),
            num_leaves=num_leaves, leaf_batch=leaf_batch,
            max_depth=max_depth, num_bins=num_bins,
            split_params=split_params, axis_name=self.axis_name,
            hist_dtype=hist_dtype, hist_impl=hist_impl,
            block_rows=block_rows, n_shards=self.num_shards,
            has_mono=has_mono, mono_method=mono_method,
            feature_fraction_bynode=feature_fraction_bynode,
            hist_sub=hist_sub, sharded=self.shard_storage)


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "num_leaves", "leaf_batch", "max_depth",
                     "num_bins", "split_params", "axis_name", "hist_dtype",
                     "hist_impl", "block_rows", "n_shards", "has_mono",
                     "mono_method", "feature_fraction_bynode", "hist_sub",
                     "sharded"))
def _build_tree_fp_jit(mesh, bins, gh, row_leaf0, num_bins_pf, nan_bin_pf,
                       is_cat_pf, feature_mask, valid_flat, mono_arr,
                       fp_extras, *,
                       num_leaves, leaf_batch, max_depth, num_bins,
                       split_params, axis_name, hist_dtype, hist_impl,
                       block_rows, n_shards, has_mono, mono_method="basic",
                       feature_fraction_bynode=1.0, hist_sub=True,
                       sharded=False):
    R = bins.shape[0]
    F = num_bins_pf.shape[0]
    # pad the feature axis so it splits evenly; pad features are trivial
    # (1 bin, masked out) and never selected
    F_pad = ((F + n_shards - 1) // n_shards) * n_shards
    pf = F_pad - F
    if sharded:
        # shard_bins already padded + column-sharded the matrix
        assert bins.shape[1] == F_pad, (bins.shape, F_pad)
        bins_p = bins
    else:
        bins_p = jnp.pad(bins, ((0, 0), (0, pf)))
    num_bins_p = jnp.pad(num_bins_pf, (0, pf), constant_values=1)
    nan_bin_p = jnp.pad(nan_bin_pf, (0, pf), constant_values=-1)
    is_cat_p = jnp.pad(is_cat_pf, (0, pf))
    fmask_p = jnp.pad(feature_mask, (0, pf))
    mono_p = jnp.pad(mono_arr, (0, pf))

    rep = P()
    fsh = P(axis_name)       # 1-D per-feature arrays, feature-sharded
    fsh2 = P(None, axis_name)
    n_valid = len(valid_flat) // 2

    def step(b_full, b_loc, g, rl, nbpf, nanpf, catpf, fmask,
             loc_nbpf, loc_nanpf, loc_catpf, loc_fmask, loc_mono,
             mono_full, vflat, extra):
        vbins = tuple(vflat[:n_valid])
        vrl = tuple(vflat[n_valid:])
        qs, groups, key, csm = extra
        offset = (jax.lax.axis_index(axis_name)
                  * jnp.int32(b_loc.shape[1]))
        return build_tree(
            b_full, g, rl, nbpf, nanpf, catpf, fmask,
            num_leaves=num_leaves, leaf_batch=leaf_batch,
            max_depth=max_depth, num_bins=num_bins,
            split_params=split_params, axis_name=axis_name,
            hist_dtype=hist_dtype, hist_impl=hist_impl,
            block_rows=block_rows, valid_bins=vbins, valid_row_leaf0=vrl,
            mono_type_pf=mono_full if has_mono else None,
            interaction_groups=groups, rng_key=key,
            feature_fraction_bynode=feature_fraction_bynode,
            cat_sorted_mask=csm,
            parallel_mode="feature", local_bins=b_loc,
            local_meta=(loc_nbpf, loc_nanpf, loc_catpf, loc_fmask,
                        loc_mono if has_mono else None),
            feat_offset=offset, quant_scales=qs,
            mono_method=mono_method, hist_sub=hist_sub,
            feature_sharded=sharded)

    # replicated extras padded to the sharded feature width
    qs, groups, key, csm = fp_extras
    if groups is not None:
        groups = jnp.pad(groups, ((0, 0), (0, pf)))
    if csm is not None:
        csm = jnp.pad(csm, (0, pf))
    fp_extras = (qs, groups, key, csm)

    tree_specs = jax.tree.map(lambda _: rep, TreeArrays(
        *([0] * len(TreeArrays._fields))))
    extras_specs = jax.tree.map(lambda _: rep, fp_extras)

    if sharded:
        # valid matrices are column-sharded like the train matrix (their
        # relabel resolves split-feature bins with the same psum); their
        # feature axes are padded to F_pad here — tiny next to training
        # data, and pad features are never selected
        valid_flat = tuple(
            jnp.pad(v, ((0, 0), (0, F_pad - v.shape[1])))
            if i < n_valid and v.shape[1] != F_pad else v
            for i, v in enumerate(valid_flat))
        valid_in_specs = tuple([fsh2] * n_valid + [rep] * n_valid)
        mat_spec = fsh2
    else:
        valid_in_specs = tuple([rep] * (2 * n_valid))
        mat_spec = rep

    fn = jax.shard_map(
        step, mesh=mesh,
        in_specs=(mat_spec, fsh2, rep, rep, rep, rep, rep, rep,
                  fsh, fsh, fsh, fsh, fsh, rep, valid_in_specs,
                  extras_specs),
        out_specs=(tree_specs, rep, tuple([rep] * n_valid),
                   RoundLog(rep, rep, rep)),
        check_vma=False)
    return fn(bins_p, bins_p, gh, row_leaf0, num_bins_p, nan_bin_p,
              is_cat_p, fmask_p, num_bins_p, nan_bin_p, is_cat_p, fmask_p,
              mono_p, mono_p, valid_flat, fp_extras)


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "num_leaves", "leaf_batch", "max_depth",
                     "num_bins", "split_params", "axis_name", "hist_dtype", "hist_impl",
                     "block_rows", "n_valid", "feature_fraction_bynode",
                     "parallel_mode", "top_k", "bundle_bins",
                     "mono_method", "forced", "hist_sub", "hist_merge",
                     "class_batched"))
def _build_tree_dp_jit(mesh, bins, gh, row_leaf0, num_bins_pf, nan_bin_pf,
                       is_cat_pf, feature_mask, valid_flat, extras, *,
                       num_leaves, leaf_batch, max_depth, num_bins,
                       split_params, axis_name, hist_dtype, hist_impl, block_rows,
                       n_valid, feature_fraction_bynode,
                       parallel_mode="data", top_k=20, bundle_bins=0,
                       mono_method="basic", forced=None, hist_sub=True,
                       hist_merge="allreduce", class_batched=False):
    row = P(axis_name)
    row2 = P(axis_name, None)
    rep = P()
    n_shards = int(mesh.devices.size)

    def step(b, g, rl, nbpf, nanpf, catpf, fmask, vflat, extra):
        vbins = tuple(vflat[:n_valid])
        vrl = tuple(vflat[n_valid:])
        mono, groups, key, bmeta, qs, csm = extra
        tree, rl_out, vrl_out, rounds = build_tree(
            b, g, rl, nbpf, nanpf, catpf, fmask,
            num_leaves=num_leaves, leaf_batch=leaf_batch,
            max_depth=max_depth, num_bins=num_bins,
            split_params=split_params, axis_name=axis_name,
            hist_dtype=hist_dtype, hist_impl=hist_impl,
            block_rows=block_rows,
            valid_bins=vbins, valid_row_leaf0=vrl,
            mono_type_pf=mono, interaction_groups=groups, rng_key=key,
            feature_fraction_bynode=feature_fraction_bynode,
            parallel_mode=parallel_mode, top_k=top_k,
            bundle_meta=bmeta, bundle_bins=bundle_bins,
            quant_scales=qs, mono_method=mono_method,
            cat_sorted_mask=csm, forced=forced, hist_sub=hist_sub,
            hist_merge=hist_merge, n_shards=n_shards,
            class_batched=class_batched)
        # each shard counts its own row stream: a shard axis of one,
        # laid out along the mesh axis ([.., n_shards, rounds] outside)
        return tree, rl_out, vrl_out, rounds._replace(
            rows=rounds.rows[..., None, :],
            stream_rows=rounds.stream_rows[..., None, :])

    tree_specs = jax.tree.map(lambda _: rep, TreeArrays(
        *([0] * len(TreeArrays._fields))))
    valid_in_specs = tuple([row2] * n_valid + [row] * n_valid)
    # constraint metadata and PRNG key are replicated: every chip samples
    # and constrains identically, keeping the replicated argmax in sync
    extras_specs = jax.tree.map(lambda _: rep, extras)

    # reduce-scatter layout: the scattered shard and the axis-indexed
    # metadata slices VARY across shards on purpose; _sync_best restores
    # replicated tree outputs. The static replication checker cannot
    # prove that through the while_loop (the feature-parallel build
    # disables it for the same reason), so turn it off here too.
    rs = hist_merge == "reduce_scatter" and n_shards > 1
    # class-batched build: gh arrives [K, R, 3] and row→leaf outputs come
    # back [K, R] — the class axis is replicated (axis 0 of every spec
    # below stays None), only the row axis shards. The per-class trees
    # stack into one TreeArrays with leading K, still replicated.
    gh_spec = P(None, axis_name, None) if class_batched else row2
    rl_spec = P(None, axis_name) if class_batched else row
    out_valid_specs = tuple([rl_spec] * n_valid)
    per_shard = (P(None, axis_name, None) if class_batched
                 else P(axis_name, None))
    rounds_specs = RoundLog(rows=per_shard, leaves=rep,
                            stream_rows=per_shard)
    fn = jax.shard_map(
        step, mesh=mesh,
        in_specs=(row2, gh_spec, row, rep, rep, rep, rep, valid_in_specs,
                  extras_specs),
        out_specs=(tree_specs, rl_spec, out_valid_specs, rounds_specs),
        check_vma=not rs)
    return fn(bins, gh, row_leaf0, num_bins_pf, nan_bin_pf, is_cat_pf,
              feature_mask, valid_flat, extras)


def build_tree_dp(mesh: Mesh, bins, gh, row_leaf0, num_bins_pf, nan_bin_pf,
                  is_cat_pf, feature_mask, *, num_leaves: int,
                  leaf_batch: int, max_depth: int, num_bins: int,
                  split_params: SplitParams, axis_name: str = AXIS,
                  hist_dtype: str = "bfloat16", hist_impl: str = "auto",
               block_rows: int = 0,
                  valid_bins: Tuple[jax.Array, ...] = (),
                  valid_row_leaf0: Tuple[jax.Array, ...] = (),
                  mono_type_pf=None, interaction_groups=None, rng_key=None,
                  feature_fraction_bynode: float = 1.0,
                  parallel_mode: str = "data", top_k: int = 20,
                  bundle_meta=None, bundle_bins: int = 0,
                  quant_scales=None, mono_method: str = "basic",
                  cat_sorted_mask=None, forced=None,
                  hist_sub: bool = True, hist_merge: str = "allreduce",
                  class_batched: bool = False):
    """Grow one tree with rows sharded over ``axis_name``.

    Same contract as :func:`..boosting.tree_builder.build_tree`; the
    returned TreeArrays are replicated (identical on every chip), the
    returned row→leaf assignments stay row-sharded, and the RoundLog's
    ``rows`` and ``stream_rows`` are per shard, ``[n_shards, rounds]``
    along the mesh axis
    (no collective is added for them). ``hist_merge``
    selects the histogram merge collective (module docstring).

    ``class_batched``: grow all K per-class trees in one call — ``gh``
    is [K, R, 3] (rows sharded on axis 1), ``rng_key``/``quant_scales``
    carry a leading K, and the returned TreeArrays / row→leaf
    assignments gain a leading class axis. Every collective the build
    emits (psum histogram merge, reduce-scatter, winner pmax/pmin)
    batches over the class axis inside ONE collective per round, so
    wire bytes per class are unchanged while dispatch count drops K×.
    """
    valid_flat = tuple(valid_bins) + tuple(valid_row_leaf0)
    extras = (mono_type_pf, interaction_groups, rng_key, bundle_meta,
              quant_scales, cat_sorted_mask)
    return _build_tree_dp_jit(
        mesh, bins, gh, row_leaf0, num_bins_pf, nan_bin_pf, is_cat_pf,
        feature_mask, valid_flat, extras, num_leaves=num_leaves,
        leaf_batch=leaf_batch, max_depth=max_depth, num_bins=num_bins,
        split_params=split_params, axis_name=axis_name,
        hist_dtype=hist_dtype, hist_impl=hist_impl,
            block_rows=block_rows,
        n_valid=len(valid_bins),
        feature_fraction_bynode=feature_fraction_bynode,
        parallel_mode=parallel_mode, top_k=top_k,
        bundle_bins=bundle_bins, mono_method=mono_method, forced=forced,
        hist_sub=hist_sub, hist_merge=hist_merge,
        class_batched=class_batched)
