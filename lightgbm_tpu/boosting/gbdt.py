"""GBDT training loop.

TPU-native analog of the reference boosting layer
(``src/boosting/gbdt.cpp``: ``Train`` :237, ``TrainOneIter`` :344,
``BoostFromAverage`` :319, ``UpdateScore`` :491; sampling strategies
``bagging.hpp`` / ``goss.hpp``).

Structure (TPU-first):
- Scores live on device as [num_class, padded_rows] f32; the default
  driver is the FUSED step (_fused_step_impl): grad/hess -> sampling ->
  quantize -> per-class build_tree -> score update chained into ONE
  jitted program per iteration, score buffers donated, and the built
  TreeArrays kept on device in a pending ring. Host materialization
  (Tree.from_device) happens in batches at sync points only — eval
  cadence boundaries and end of training — so the steady-state loop
  dispatches ahead with zero host syncs between eval points. Configs
  that need per-iteration host work (custom fobj, linear trees, CEGB,
  multi-process meshes, position-bias ranking) fall back to the legacy
  loop (_train_one_iter_legacy: ~5 dispatches + a per-tree sync,
  mirroring the CUDA learner's scalars-only host boundary,
  cuda_single_gpu_tree_learner.cpp:246-273); LIGHTGBM_TPU_FUSED_TRAIN=0
  or fused_train=false pin the legacy loop everywhere.
- Bagging/GOSS produce a row mask/scale, never a data subset: fixed shapes
  keep one compiled program alive. The mask rides in the histogram count
  channel so min_data_in_leaf counts in-bag rows like the reference.
- The init score (BoostFromAverage) is added into the first tree per class
  via AddBias, exactly like gbdt.cpp:416 — saved models are self-contained.
- Validation sets are co-partitioned during growth (see tree_builder), so
  validation scores update with a gather, no full predict pass.
"""

from __future__ import annotations

import collections
import functools
import weakref
from typing import (Any, Dict, List, NamedTuple, Optional, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from .. import phases, profiler
from ..config import Config
from ..dataset import Dataset
from ..objectives import Objective
from ..ops.histogram import (block_rows_for, pallas_shape_reason,
                             resolve_impl)
from ..ops.split import SplitParams
from ..tree import Tree
from .tree_builder import (StepShape, TreeArrays, build_impl, build_tree,
                           feature_block, step_shape)

__all__ = ["GBDT"]

kEpsilon = 1e-15
# more than any benchmark window holds (83 trees of 0.55 s at MS-LTR
# since PR 33; at 64 the window's readers saw only its last 64 trees)
ROUND_LOG_TREES = 256


class RoundRecord(NamedTuple):
    """One tree's entry of ``GBDT.round_log``."""
    iteration: int
    class_index: int
    rows: np.ndarray     # [rounds] int32 ([n_shards, rounds] when sharded)
    leaves: np.ndarray   # [rounds] int32
    stream_rows: np.ndarray  # like ``rows``: stream positions touched


def _pad_rows(arr: np.ndarray, r_pad: int, fill=0):
    if arr.shape[0] == r_pad:
        return arr
    pad = [(0, r_pad - arr.shape[0])] + [(0, 0)] * (arr.ndim - 1)
    return np.pad(arr, pad, constant_values=fill)


class _DeviceData:
    """Device-resident binned matrix + co-partition state for one dataset.

    With a data-parallel plan, rows are sharded across the mesh's data axis
    (the per-machine row partition of data_parallel_tree_learner.cpp, done
    by jax.sharding instead of pre_partition'd files)."""

    def __init__(self, ds: Dataset, block: int, plan=None,
                 unbundle: bool = False):
        # num_data is PER-PROCESS under pre-partitioned multi-host
        # loading (each host's Dataset holds its own row shard); r_pad is
        # the GLOBAL padded row count, r_local this process's slice of it
        self.num_data = ds.num_data
        if plan is not None:
            self.r_pad = plan.pad_to(ds.num_data, block)
            self.r_local = plan.local_rows(self.r_pad)
        else:
            self.r_pad = ((ds.num_data + block - 1) // block) * block
            self.r_local = self.r_pad
        src = ds.unbundled_bins() if unbundle else ds.bins
        bins = _pad_rows(src, self.r_local)
        row_leaf0 = np.where(np.arange(self.r_local) < ds.num_data, 0, -1) \
            .astype(np.int32)
        with profiler.span("gbdt.to_device"):
            if plan is not None:
                self.bins = plan.shard_bins(bins)
                self.row_leaf0 = plan.shard_rows(row_leaf0)
            else:
                self.bins = jnp.asarray(bins)
                self.row_leaf0 = jnp.asarray(row_leaf0)
            jax.block_until_ready((self.bins, self.row_leaf0))


class _ChunkedDeviceData:
    """Device-data stand-in for the out-of-core chunked driver: the
    row bookkeeping of :class:`_DeviceData` without a resident matrix
    (``bins`` stays None — the prefetcher streams it). Geometry follows
    the prefetcher's chunk lattice so the [R]-shaped score/gradient
    arrays line up with the streamed chunks."""

    def __init__(self, ds: Dataset, prefetcher):
        self.num_data = ds.num_data
        self.r_pad = int(prefetcher.padded_rows)
        self.r_local = self.r_pad
        self.bins = None
        self.row_leaf0 = jnp.asarray(
            np.where(np.arange(self.r_pad) < ds.num_data, 0, -1)
            .astype(np.int32))


class GBDT:
    # subclasses that replay past trees (DART) keep them on device;
    # plain gbdt/rf retain only the host Tree models
    keep_device_trees = False

    def __init__(self, config: Config, train_set: Dataset,
                 objective: Optional[Objective],
                 valid_sets: Sequence[Dataset] = (),
                 init_row_scores: Optional[np.ndarray] = None,
                 valid_init_row_scores: Sequence[np.ndarray] = (),
                 num_init_iteration: int = 0):
        self.config = config
        self.train_set = train_set.construct()
        self.objective = objective
        self.iter_ = 0
        self.num_init_iteration = num_init_iteration  # gbdt.h analog
        self.models: List[Tree] = []
        # (TreeArrays, weight) per trained tree, kept on device for DART
        # drop/restore, rollback and refit (HistogramPool-sized: ~KBs/tree)
        self.device_trees: List[Tuple[TreeArrays, float]] = []
        self.num_class = config.num_class
        self.K = (objective.num_model_per_iteration
                  if objective is not None else max(1, config.num_class))
        self.shrinkage = config.learning_rate
        self._init_scores = np.zeros(self.K)
        self._boosted_from_average = False

        F = self.train_set.num_features
        self.B = int(self.train_set.max_num_bin)
        # EFB: bins are bundled [R, G]; histogram sizing follows the
        # bundle lattice, split finding stays in feature space
        bp = self.train_set.bundle_plan
        self._bundle_meta = None
        self._bundle_bins = 0
        self._unbundle_feature = False   # tree_learner=feature w/ EFB
        if bp is not None:
            self._bundle_meta = (jnp.asarray(bp.feat_bundle),
                                 jnp.asarray(bp.feat_offset),
                                 jnp.asarray(bp.feat_mfb))
            self._bundle_bins = int(bp.max_bundle_bins)
            self.block = block_rows_for(
                self.train_set.num_data, bp.num_bundles,
                bp.max_bundle_bins)
        else:
            self.block = block_rows_for(self.train_set.num_data, F, self.B)
        # histogram-subtraction gate: the per-leaf raw cache (the
        # HistogramPool analog) must fit the pool budget
        pool_budget = (config.histogram_pool_size
                       if config.histogram_pool_size > 0 else 512.0)

        def _hist_sub_gate(lattice: int) -> bool:
            cache_mb = ((config.num_leaves + 1) * lattice * 3 * 4
                        / 2 ** 20)
            ok = bool(config.hist_subtraction) and cache_mb <= pool_budget
            if bool(config.hist_subtraction) and not ok:
                from .. import log as _log
                _log.warning(
                    f"per-leaf histogram cache would need {cache_mb:.0f}"
                    f" MB (> histogram_pool_size budget "
                    f"{pool_budget:.0f} MB); disabling histogram "
                    "subtraction")
            return ok
        # gate evaluated ONCE, below, after the tree_learner plan is
        # known (tree_learner=feature may unbundle and change the
        # lattice; gating here first would warn for the wrong one)
        # data-parallel over every local device (tree_learner param,
        # tree_learner.cpp:15 factory analog; "serial" pins one device)
        if bool(config.linear_tree):
            for ds_ in (self.train_set, *[v.construct()
                                          for v in valid_sets]):
                if getattr(ds_, "raw_values", None) is None:
                    raise ValueError(
                        "linear_tree needs raw feature values for every "
                        "dataset; binary dataset caches do not retain "
                        "them — construct Datasets from arrays or text "
                        "files")
        if int(config.num_machines) > 1:
            # multi-host bootstrap (Network::Init analog): after this,
            # jax.devices() spans every host and the mesh plans below
            # cover DCN transparently
            from ..parallel.distributed import maybe_init_distributed
            maybe_init_distributed(config)
        n_dev = len(jax.devices())
        self.plan = None
        # CEGB and feature_contri run on the serial learner only — the
        # reference ties CEGB to SerialTreeLearner; we follow its
        # force-serial-with-warning pattern (config.cpp:434-437 style)
        needs_serial = bool(
            config.cegb_tradeoff < 1.0 or config.cegb_penalty_split > 0.0
            or config.cegb_penalty_feature_coupled
            or config.cegb_penalty_feature_lazy or config.feature_contri)
        if needs_serial and n_dev > 1 and config.tree_learner != "serial":
            from .. import log as _log
            _log.warning("CEGB/feature_contri require the serial tree "
                         "learner; forcing tree_learner=serial")
        if not needs_serial and n_dev > 1 \
                and config.tree_learner != "serial":
            from ..parallel.data_parallel import (
                DataParallelPlan, FeatureParallelPlan, VotingParallelPlan)
            plan_cls = {"feature": FeatureParallelPlan,
                        "voting": VotingParallelPlan}.get(
                            config.tree_learner, DataParallelPlan)
            if self._bundle_meta is not None and \
                    plan_cls is FeatureParallelPlan:
                # feature mode shards FEATURES, so the bundled storage
                # is decoded back to per-feature columns (bundle
                # histograms unbundled == per-feature histograms, so
                # training is identical). Rows are replicated on every
                # chip in this mode anyway — the reference's model
                # (feature_parallel_tree_learner.cpp:38: each worker
                # holds the full dataset) — so the width saving EFB
                # gave up is the mode's own storage model.
                self._bundle_meta = None
                self._bundle_bins = 0
                self._unbundle_feature = True
                self.block = block_rows_for(
                    self.train_set.num_data, F, self.B)
            plan_kw = {}
            if plan_cls is FeatureParallelPlan:
                plan_kw["shard_storage"] = bool(
                    config.feature_shard_storage)
            elif config.feature_shard_storage:
                from .. import log as _log
                _log.warning("feature_shard_storage only applies with "
                             "tree_learner=feature; ignoring")
            if plan_cls is not FeatureParallelPlan:
                hm = str(config.dp_hist_merge)
                if config.forcedsplits_filename and hm != "allreduce":
                    # the forced-split gather reads full-feature
                    # histogram rows from the per-leaf cache, which the
                    # scattered layout shards by feature slot
                    from .. import log as _log
                    if hm == "reduce_scatter":
                        _log.warning(
                            "forced splits need the full-histogram "
                            "merge; pinning dp_hist_merge=allreduce")
                    hm = "allreduce"
                plan_kw["hist_merge"] = hm
            self.plan = plan_cls(top_k=int(config.top_k), **plan_kw)
            if (plan_cls is FeatureParallelPlan
                    and getattr(self.plan, "multi_process", False)):
                # feature-parallel needs the FULL dataset replicated on
                # every worker (feature_parallel_tree_learner.cpp:38).
                # Two ways a worker's copy can silently differ: the
                # loader auto-partitioned rows, or the caller fed each
                # host its own shard under pre_partition=true. Both
                # produce diverging replicas (or a cross-process trace
                # mismatch), so verify the copies agree up front.
                for ds_ in (train_set, *[v.construct()
                                         for v in valid_sets]):
                    if getattr(ds_, "auto_partitioned", False):
                        raise ValueError(
                            "tree_learner=feature across machines "
                            "requires every worker to load the FULL "
                            "dataset: pass the whole data on each "
                            "machine with pre_partition=true (the "
                            "loader auto-partitioned rows because "
                            "pre_partition was false)")
                from ..parallel.distributed import \
                    check_replicas_identical
                check_replicas_identical(
                    [train_set] + [v for v in valid_sets])
            if self.plan.rows_sharded:
                # keep the scan block well under the per-shard row count
                # so shard-granular padding stays a small fraction
                per_shard = -(-self.train_set.num_data // n_dev)
                cap = max(256, 1 << int(np.floor(np.log2(
                    max(1, per_shard // 4)))))
                self.block = min(self.block, cap)
        elif config.feature_shard_storage:
            from .. import log as _log
            _log.warning(
                "feature_shard_storage needs tree_learner=feature and "
                "more than one device "
                f"({n_dev} visible); storing the matrix unsharded")
        # resolve hist_impl='auto' by rule (backend + the FINAL lattice
        # width — feature mode may have unbundled above) and keep why a
        # TPU run is NOT on the Pallas kernel, next to the other gate
        # reasons
        requested, lattice_bins = config.hist_impl, self._bundle_bins or self.B
        config._values["hist_impl"] = resolve_impl(requested, lattice_bins)
        self.hist_impl_reason = (
            pallas_shape_reason(lattice_bins)
            if requested == "auto" and config.hist_impl == "matmul"
            else "")
        # column-sharded storage keeps only the local feature slice of
        # the matrix AND the hist cache per device: one divisor feeds
        # both the hist-sub gate and the capacity gate below
        n_fs = (self.plan.num_shards
                if self.plan is not None
                and getattr(self.plan, "shard_storage", False) else 1)
        # single hist-sub gate on the FINAL device lattice (bundle
        # lattice, or F*B after the feature-mode unbundle above)
        _lattice = (self._bundle_bins * bp.num_bundles
                    if self._bundle_meta is not None else F * self.B)
        # reduce-scatter data-parallel slot-shards the per-leaf raw
        # cache by feature slot (and stores it in UNBUNDLED feature
        # space): each chip budgets 1/n of the feature lattice
        self._dp_rs = bool(
            self.plan is not None and self.plan.parallel_mode == "data"
            and getattr(self.plan, "hist_merge", "") == "reduce_scatter"
            and self.plan.num_shards > 1)
        if self._dp_rs:
            _lattice = -(-(F * self.B) // self.plan.num_shards)
        self._hist_sub = _hist_sub_gate(-(-_lattice // n_fs))
        # capacity gate BEFORE the device transfer (VERDICT r4 #5):
        # fail with sized guidance, not a mid-training device OOM — or,
        # when the chunked out-of-core driver can take the run, degrade
        # to streaming row chunks instead of failing (PR 13)
        from ..dataset import check_device_capacity
        self.chunked = False
        self._chunk_source = None
        self._prefetcher = None
        self._chunked_builder = None       # built at the end of __init__
        oc = str(getattr(config, "out_of_core", "auto"))
        chunk_reason = self._chunked_gate_reason()
        shard_src = getattr(self.train_set, "chunk_source", None)
        if oc == "on" or (oc == "auto" and shard_src is not None):
            if chunk_reason:
                if oc == "on":
                    raise ValueError(
                        "out_of_core=on but chunked training cannot "
                        f"drive this run: {chunk_reason}")
                # shard-backed dataset with a feature the chunked
                # builder gates out: fall through to the resident path
                # (Dataset.bins materializes the matrix lazily)
            else:
                self.chunked = True
                self._chunk_source = shard_src
        # multi-process: num_data is this process's LOCAL rows and they
        # spread over the process's own devices only — dividing by the
        # GLOBAL device count would understate the per-chip footprint
        if self.plan is not None and self.plan.rows_sharded:
            n_row_shards = max(1, self.plan.num_shards
                               // getattr(self.plan, "num_processes", 1))
        else:
            n_row_shards = 1
        if not self.chunked:
            if self._unbundle_feature:
                # the device holds the UNBUNDLED matrix: per-feature
                # width and the (possibly narrower) per-feature dtype
                cap_width = F
                cap_itemsize = 1 if self.B <= 256 else 4  # unbundled dtype
            else:
                cap_width = self.train_set.bins.shape[1]
                cap_itemsize = self.train_set.bins.dtype.itemsize
            # feature_shard_storage: each device stores only its own
            # column slice of the (padded) matrix
            cap_width = -(-cap_width // n_fs)
            try:
                # the search scans feature space (a column-sharded plan:
                # its own features) whatever the stored columns are
                w = max(1, min(int(config.leaf_batch),
                               int(config.num_leaves) - 1))
                check_device_capacity(
                    self.train_set.num_data, cap_width, cap_itemsize,
                    config.num_leaves, self._bundle_bins or self.B,
                    self._hist_sub, n_row_shards=n_row_shards,
                    search_lattice=(2 * w, -(-F // n_fs), self.B))
            except MemoryError:
                if oc == "off" or chunk_reason:
                    raise
                # the resident matrix does not fit but the run is
                # chunkable: degrade transparently (shard-backed data
                # keeps its mmap stream; in-memory data streams the
                # host matrix)
                from .. import log as _log
                _log.warning(
                    "binned matrix exceeds device capacity; streaming "
                    "it in row chunks (out_of_core) instead")
                self.chunked = True
                self._chunk_source = shard_src
        if self.chunked:
            from ..data.chunked import ArraySource
            from ..data.prefetch import ChunkPrefetcher, chunk_rows_for
            if self._chunk_source is None:
                self._chunk_source = ArraySource(
                    np.asarray(self.train_set.bins))
            itemsize = int(
                self._chunk_source.read_rows(0, 1).dtype.itemsize)
            c_rows = chunk_rows_for(
                self.train_set.num_data,
                self._chunk_source.num_features, itemsize,
                config.chunk_budget_mb, self.block)
            self._prefetcher = ChunkPrefetcher(self._chunk_source, c_rows)
            self.train_dd = _ChunkedDeviceData(self.train_set,
                                               self._prefetcher)
        else:
            self.train_dd = _DeviceData(self.train_set, self.block,
                                        self.plan,
                                        unbundle=self._unbundle_feature)
        self._bins_cm = None            # lazy column-major copy (native)
        self.valid_dd = [
            _DeviceData(v.construct(), self.block, self.plan,
                        unbundle=self._unbundle_feature)
            for v in valid_sets]
        self.valid_sets = list(valid_sets)

        R = self.train_dd.r_pad
        R_loc = self.train_dd.r_local
        lbl = self.train_set.get_label()
        self._mp = bool(self.plan is not None
                        and getattr(self.plan, "multi_process", False))
        if self._mp and bool(config.linear_tree):
            # reference parity: "linear tree learner must be serial
            # type" (config.cpp:429-437 forces tree_learner=serial), so
            # distributed linear trees do not exist there either
            raise NotImplementedError(
                "linear_tree requires single-host training (the "
                "reference forces tree_learner=serial for linear trees "
                "too, config.cpp:429)")
        # multi-host ranking (VERDICT r4 #4): the padded-query lattice
        # holds LOCAL row ids, so ranking gradients are computed PER
        # PROCESS on the host's own score block (each host owns whole
        # queries under pre-partitioned loading — the reference
        # pre-partitions lambdarank by query the same way,
        # src/io/metadata.cpp partitioned loading) and re-placed into
        # the sharded global array. The reference's objective also runs
        # host-side per machine; only histogram/split sync crosses hosts.
        self._mp_ranking = bool(self._mp and objective is not None
                                and objective.is_ranking)

        def _row_put(a):
            with profiler.span("gbdt.to_device"):
                return jax.block_until_ready(
                    self.plan.shard_rows(a) if self.plan is not None
                    else jnp.asarray(a))
        self.label_dev = _row_put(
            _pad_rows(np.asarray(lbl, np.float32), R_loc))
        # global row count for GOSS's top-k over the global score sort
        self._num_data_global = self.train_dd.num_data
        if self._mp:
            from jax.experimental import multihost_utils
            self._num_data_global = int(multihost_utils.process_allgather(
                np.asarray([self.train_dd.num_data], np.int64)).sum())
        w = self.train_set.get_weight()
        self.weight_dev = None if w is None else _row_put(
            _pad_rows(np.asarray(w, np.float32), R_loc))
        if self._mp_ranking:
            # per-process gradient computation needs LOCAL label/weight
            # blocks next to the local score slice (see _grads)
            self._label_local = jnp.asarray(
                _pad_rows(np.asarray(lbl, np.float32), R_loc))
            self._weight_local = None if w is None else jnp.asarray(
                _pad_rows(np.asarray(w, np.float32), R_loc))

        if objective is not None:
            okw = {}
            if (objective.is_ranking
                    and getattr(self.train_set, "position", None) is not None):
                okw["position"] = self.train_set.position
            objective.init(lbl, w, self.train_set.query_boundaries(), **okw)
            if objective.is_ranking:
                # the query lattices go to the device once, here, and
                # reach the fused step as an argument (_fused_data_args)
                with profiler.span("gbdt.to_device"):
                    jax.block_until_ready(objective.device_state)
            if objective.label is not lbl:
                # init() may retarget training to a transformed label
                # space (reg_sqrt trains on sign(y)*sqrt(|y|),
                # regression_objective.hpp sqrt_); gradients must see
                # the SAME label the init score was derived from
                self.label_dev = _row_put(_pad_rows(
                    np.asarray(objective.label, np.float32), R_loc))
            self._init_scores = np.asarray(objective.boost_from_score(),
                                           dtype=np.float64).reshape(-1)
            if len(self._init_scores) != self.K:
                self._init_scores = np.resize(self._init_scores, self.K)
            if self._mp:
                # per-process automatic init scores are averaged across
                # hosts — Network::GlobalSyncUpByMean in BoostFromAverage
                # (gbdt.cpp:313)
                from ..parallel.distributed import global_mean_init_scores
                self._init_scores = global_mean_init_scores(
                    self._init_scores)

        def _put_scores(local_kr):
            return (self.plan.shard_scores(local_kr)
                    if self.plan is not None
                    else jnp.asarray(local_kr))

        if init_row_scores is not None:
            # continued training (init_model): scores resume from the
            # loaded model's per-row predictions; no BoostFromAverage
            # (gbdt.cpp only boosts from average when models_.empty()).
            # Multi-host: each host predicted its own pre-partitioned
            # rows with the base model, so the [K, R_loc] block shards
            # into the global score array like any other score field.
            def to_kr(a, r_loc):
                a = np.asarray(a, np.float32)
                if a.ndim == 1:
                    a = a[:, None]
                return _pad_rows(a, r_loc).T  # [K, R_loc]
            self.scores = _put_scores(to_kr(init_row_scores, R_loc))
            self.valid_scores = [
                _put_scores(to_kr(v, dd.r_local))
                for v, dd in zip(valid_init_row_scores, self.valid_dd)]
            self._init_scores = np.zeros(self.K)
        # NOTE: when init_row_scores (init_model) is present it takes
        # precedence over Dataset.init_score — same as the reference,
        # where the predictor path overrides a user init_score
        # (basic.py:2219-2223 `elif init_score is not None`).
        elif self.train_set.get_init_score() is not None:
            # Metadata init_score: per-row base offsets added to scores
            # before any boosting (ScoreUpdater ctor / dataset.h:126);
            # BoostFromAverage is skipped (gbdt.cpp:319 has_init_score
            # guard) and no AddBias folds into the first tree, so
            # prediction excludes the offset exactly like the reference.
            # Under multi-process each host's Metadata holds its LOCAL
            # rows; the local block is placed into the sharded array.
            self.scores = _put_scores(self._field_init_scores(
                self.train_set.get_init_score(), self.train_set.num_data,
                self.train_dd.r_local))
            self.valid_scores = []
            for v, dd in zip(self.valid_sets, self.valid_dd):
                vi = v.get_init_score()
                if vi is not None:
                    self.valid_scores.append(_put_scores(
                        self._field_init_scores(vi, v.num_data,
                                                dd.r_local)))
                else:
                    self.valid_scores.append(_put_scores(
                        np.zeros((self.K, dd.r_local), np.float32)))
            self._init_scores = np.zeros(self.K)
        else:
            if not (self.config.boost_from_average
                    and objective is not None):
                self._init_scores = np.zeros(self.K)
            else:
                self._boosted_from_average = True
            base = (self._init_scores.astype(np.float32)[:, None]
                    if self._boosted_from_average else 0.0)

            def _mk_scores(dd):
                local = np.zeros((self.K, dd.r_local), np.float32) + base
                return (self.plan.shard_scores(local)
                        if self.plan is not None else jnp.asarray(local))
            self.scores = _mk_scores(self.train_dd)
            self.valid_scores = [_mk_scores(dd) for dd in self.valid_dd]

        # static metadata for the tree builder
        # multi-process jit rejects committed single-device inputs next
        # to global-mesh arrays; plain numpy inputs are auto-replicated
        _meta_put = np.asarray if self._mp else jnp.asarray
        self.num_bins_pf = _meta_put(self.train_set.per_feature_num_bins())
        self.nan_bin_pf = _meta_put(self.train_set.per_feature_nan_bins())
        self.is_cat_pf = _meta_put(
            self.train_set.per_feature_is_categorical())
        # sorted-subset categorical splits: features with more than
        # max_cat_to_onehot bins leave the one-hot path
        # (feature_histogram.cpp:172 `num_bin <= max_cat_to_onehot`)
        self._cat_sorted_mask = None
        _csm = (np.asarray(self.train_set.per_feature_is_categorical())
                & (np.asarray(self.train_set.per_feature_num_bins())
                   > int(config.max_cat_to_onehot)))
        if _csm.any():
            self._cat_sorted_mask = _meta_put(_csm)
        self.split_params = SplitParams(
            lambda_l1=float(config.lambda_l1),
            lambda_l2=float(config.lambda_l2),
            min_data_in_leaf=float(config.min_data_in_leaf),
            min_sum_hessian_in_leaf=float(config.min_sum_hessian_in_leaf),
            min_gain_to_split=float(config.min_gain_to_split),
            cat_l2=float(config.cat_l2),
            cat_smooth=float(config.cat_smooth),
            max_delta_step=float(config.max_delta_step),
            path_smooth=float(config.path_smooth),
            monotone_penalty=float(config.monotone_penalty),
            extra_trees=bool(config.extra_trees),
            max_cat_threshold=int(config.max_cat_threshold),
            max_cat_to_onehot=int(config.max_cat_to_onehot),
            min_data_per_group=float(config.min_data_per_group))

        self.mono_type_pf = self._parse_monotone_constraints()
        self.interaction_groups = self._parse_interaction_constraints()
        # replicated PRNG driving per-node feature sampling (ColSampler,
        # feature_fraction_seed) and extra-trees thresholds (extra_seed)
        self._ffbn = float(config.feature_fraction_bynode)
        if self._ffbn < 1.0 or config.extra_trees:
            seed = (int(config.feature_fraction_seed) * 2654435761
                    + int(config.extra_seed)) & 0x7FFFFFFF
            self._tree_key = jax.random.PRNGKey(seed)
        else:
            self._tree_key = None

        self._rng_feature = np.random.RandomState(config.feature_fraction_seed)
        self._rng_bagging = np.random.RandomState(config.bagging_seed)
        self._bag_mask = None  # device [R] f32, regenerated per bagging_freq
        self._goss = (config.data_sample_strategy == "goss")
        if self._goss:
            if config.top_rate + config.other_rate > 1.0:
                raise ValueError("top_rate + other_rate must be <= 1")

        self._update_score_jit = jax.jit(self._update_score_impl)
        self._goss_jit = jax.jit(self._goss_impl)

        # fused boosting step state (see train_one_iter): the pending
        # ring of (iteration, shrinkage, device TreeArrays per class,
        # device should_continue flag), materialized in batches by
        # sync(); host_sync_count is what the benchmark's
        # driver.host_syncs_per_tree reads
        self._pending: List[Tuple] = []
        self._fused_jit = None
        self._full_mask_cache: Optional[Tuple] = None
        self.host_sync_count = 0
        # the last ROUND_LOG_TREES trees' per-round counters on the host
        # (tree_builder.RoundLog as numpy, plus iteration and class):
        # fetched by the transfer that brings the trees, never by one of
        # their own
        self.round_log: collections.deque = collections.deque(
            maxlen=ROUND_LOG_TREES)
        GBDT._latest = weakref.ref(self)
        # the fused step's shape (phases.STEP_SHAPE) and, under a
        # parallel plan, the plan's counters (phases.PLAN_COUNTERS): set
        # where the step is made (_step_ready), fields of its span too
        self.step_shape: Optional[Dict[str, int]] = None
        self.plan_counters: Dict[str, Any] = {}

        # numeric-divergence guard (resilience subsystem): the fused
        # step ALWAYS computes the finiteness flag (one program shape
        # regardless of policy — the flag is ignored when off, so the
        # default stays bit-identical); sync()/the legacy driver act on
        # it only when the policy arms it
        self._nan_guard = str(getattr(config, "nan_guard", "off"))

        # quantized-gradient training (GradientDiscretizer,
        # gradient_discretizer.hpp:22/.cpp:55-140): gradients are
        # stochastically rounded onto an int8 grid and the histogram runs
        # as an int8 x int8 -> int32 MXU matmul (ops/histogram.py quant
        # path — the analog of the packed int16/int32 histograms of
        # cuda_histogram_constructor.cu, with the MXU's native int32
        # accumulation replacing the per-leaf bit-width escalation).
        # Split finding descales the tiny integer histogram once
        # (FindBestThresholdInt, feature_histogram.hpp:177).
        self._quant = bool(config.use_quantized_grad)
        if self._quant:
            nbq = int(config.num_grad_quant_bins)
            if not 2 <= nbq <= 127:
                raise ValueError(
                    "num_grad_quant_bins must be in [2, 127] (int8 grid)")
            # int32 accumulator bound: the hessian channel quantizes onto
            # [0, nb] (hs = max|h|/nb), so a leaf's bin sum can reach
            # rows * nb — the binding constraint (grads only reach nb/2).
            # GLOBAL rows: the per-shard int32 histograms are psum-merged
            # in int32, so sharding does not relieve the bound.
            if self._num_data_global * nbq >= 2 ** 31:
                raise ValueError(
                    "use_quantized_grad: num_data * num_grad_quant_bins "
                    "overflows the int32 histogram accumulator; lower "
                    "num_grad_quant_bins")
            self._quant_key = jax.random.PRNGKey(
                (int(config.data_random_seed) * 65537 + 17) & 0x7FFFFFFF)
            self._quantize_jit = jax.jit(self._quantize_impl)
            self._renew_jit = jax.jit(self._renew_leaf_impl)
            # class-batched legacy driver: renew all K trees in one
            # dispatch (vmap over the class axis; see ISSUE 8)
            self._renew_batch_jit = jax.jit(
                jax.vmap(self._renew_leaf_impl))

        # feature_contri: per-feature split-gain multiplier
        # (feature_histogram.hpp:174)
        self._gain_scale = None
        fc = config.feature_contri
        if fc:
            fc = np.asarray(fc, np.float32)
            ntf = self.train_set.num_total_features
            if len(fc) != ntf:
                raise ValueError(
                    f"feature_contri has {len(fc)} entries but the "
                    f"dataset has {ntf} features")
            # plan is always None here: needs_serial forced serial
            self._gain_scale = jnp.asarray(
                fc[self.train_set.used_features])

        # forced splits (forcedsplits_filename;
        # SerialTreeLearner::ForceSplits, serial_tree_learner.cpp:636):
        # BFS over the JSON tree, thresholds mapped to bins, slots
        # assigned under our numbering (round r: left keeps the slot,
        # right becomes slot r+1)
        self._forced_splits = None
        if config.forcedsplits_filename:
            self._forced_splits = self._parse_forced_splits(
                config.forcedsplits_filename)

        # CEGB (cost_effective_gradient_boosting.hpp IsEnable)
        self._cegb = None
        self._cegb_feat_used = None
        self._cegb_used_rows = None
        coupled_in = config.cegb_penalty_feature_coupled
        lazy_in = config.cegb_penalty_feature_lazy
        if (config.cegb_tradeoff < 1.0 or config.cegb_penalty_split > 0.0
                or coupled_in or lazy_in):
            F_used = self.train_set.num_features
            uf = self.train_set.used_features

            def per_feat(vals, name):
                if not vals:
                    return None
                vals = np.asarray(vals, np.float32)
                if len(vals) != self.train_set.num_total_features:
                    raise ValueError(
                        f"{name} should be the same size as feature "
                        "number")
                return jnp.asarray(vals[uf])
            coupled = per_feat(coupled_in, "cegb_penalty_feature_coupled")
            lazy = per_feat(lazy_in, "cegb_penalty_feature_lazy")
            self._cegb = (float(config.cegb_tradeoff),
                          float(config.cegb_penalty_split), coupled, lazy)
            self._cegb_feat_used = jnp.zeros((F_used,), bool)
            if lazy is not None:
                self._cegb_used_rows = jnp.zeros(
                    (self.train_dd.r_pad, F_used), bool)

        # class-batched multiclass build (ISSUE 8): decided before the
        # driver gate, because BOTH drivers route the per-iteration K
        # tree builds through the batched builder when it clears
        self.class_batch_reason = self._class_batch_reason()
        self.class_batch_ok = not self.class_batch_reason
        if self.class_batch_ok and self.K > 1 and self._hist_sub:
            # the vmapped builder carries the per-leaf histogram cache
            # PER CLASS ([K, L+1, lattice, 3]): re-gate the pool budget
            # at K x the lattice (falls back to no-subtraction, not to
            # the sequential path — subtraction is an optimization, the
            # batched build stays bit-identical without it)
            self._hist_sub = _hist_sub_gate(
                self.K * (-(-_lattice // n_fs)))

        # decide the iteration driver LAST (the gate reads _cegb/_mp/...)
        self.fused_reason = self._fused_gate_reason()
        self.fused_ok = not self.fused_reason

        if self.chunked:
            # built HERE (not at the capacity gate) because it consumes
            # the per-feature metadata and split params assembled above;
            # one builder per booster — its four jitted round programs
            # cache their compilations across trees and iterations
            from ..data.chunked import ChunkedTreeBuilder
            self._chunked_builder = ChunkedTreeBuilder(
                num_bins_pf=self.num_bins_pf,
                nan_bin_pf=self.nan_bin_pf,
                is_cat_pf=self.is_cat_pf,
                num_leaves=config.num_leaves,
                leaf_batch=config.leaf_batch,
                max_depth=config.max_depth,
                num_bins=self.B,
                split_params=self.split_params,
                hist_dtype=config.hist_dtype,
                hist_impl=config.hist_impl,
                block_rows=self.block,
                cat_sorted_mask=self._cat_sorted_mask,
                hist_sub=self._hist_sub)

    # ------------------------------------------------------------------
    def _field_init_scores(self, init, n: int, r_pad: int) -> np.ndarray:
        """Metadata init_score -> [K, r_pad] f32.

        Accepts [n], [n, K], or flat [n*K] laid out class-major (the
        reference's per-class contiguous blocks, metadata.cpp:120-129)."""
        a = np.asarray(init, np.float32)
        if a.ndim == 2:
            a = a.T  # [K, n]
        elif a.size == n * self.K and self.K > 1:
            a = a.reshape(self.K, n)
        else:
            if a.size != n:
                raise ValueError(
                    f"init_score size {a.size} does not match num_data {n}"
                    f" (num_model_per_iteration={self.K})")
            a = np.broadcast_to(a.reshape(1, n), (self.K, n))
        return _pad_rows(np.ascontiguousarray(a.T), r_pad).T

    # ------------------------------------------------------------------
    def _parse_monotone_constraints(self) -> Optional[jax.Array]:
        """[F_used] int32 in {-1,0,1} or None (config.h monotone_constraints;
        applied via BasicLeafConstraints semantics — basic mode only)."""
        mc = self.config.monotone_constraints
        if not mc:
            return None
        if isinstance(mc, str):
            mc = [int(x) for x in mc.replace("(", "").replace(")", "")
                  .split(",")]
        mc = np.asarray(list(mc), np.int32)
        ntf = self.train_set.num_total_features
        if len(mc) != ntf:
            raise ValueError(
                f"monotone_constraints has {len(mc)} entries but the "
                f"dataset has {ntf} features")
        if not np.isin(mc, (-1, 0, 1)).all():
            raise ValueError("monotone_constraints values must be in "
                             "{-1, 0, 1}")
        used = mc[self.train_set.used_features]
        if not used.any():
            return None
        is_cat = np.asarray(self.train_set.per_feature_is_categorical())
        if (used != 0)[is_cat].any():
            raise ValueError("monotone_constraints cannot be used with "
                             "categorical features (config.cpp check)")
        method = self.config.monotone_constraints_method
        if method not in ("basic", "intermediate", "advanced"):
            raise ValueError(f"unknown monotone_constraints_method {method}")
        return jnp.asarray(used)

    def _parse_interaction_constraints(self) -> Optional[jax.Array]:
        """[G, F_used] bool group matrix or None (col_sampler.hpp:28
        interaction_constraints_vector)."""
        ic = self.config.interaction_constraints
        if not ic:
            return None
        if isinstance(ic, str):
            import json
            s = ic.strip().replace("(", "[").replace(")", "]")
            try:
                parsed = json.loads(s)
            except json.JSONDecodeError:
                parsed = json.loads("[" + s + "]")
            if parsed and all(isinstance(x, (int, float)) for x in parsed):
                parsed = [parsed]  # single flat group
            ic = parsed
        groups = [list(g) for g in ic]
        ntf = self.train_set.num_total_features
        F = self.train_set.num_features
        used_pos = {f: i for i, f in enumerate(self.train_set.used_features)}
        mat = np.zeros((len(groups), F), bool)
        for gi, g in enumerate(groups):
            for f in g:
                f = int(f)
                if f < 0 or f >= ntf:
                    raise ValueError(
                        f"interaction_constraints feature index {f} out of "
                        f"range [0, {ntf})")
                if f in used_pos:
                    mat[gi, used_pos[f]] = True
        return jnp.asarray(mat)

    # ------------------------------------------------------------------
    def _grads(self, it: int,
               scores: Optional[jax.Array] = None
               ) -> Tuple[jax.Array, jax.Array]:
        """[K, R] grad and hess from the objective at ``scores``
        (defaults to the live training scores; the fused step passes its
        traced score carry instead)."""
        obj = self.objective
        if scores is None:
            scores = self.scores
        if obj.num_model_per_iteration > 1:
            g, h = obj.get_gradients(scores.T, self.label_dev,
                                     self.weight_dev)
            return g.T, h.T
        kwargs = {}
        if obj.is_ranking:
            kwargs["it"] = jnp.asarray(it, jnp.int32)
        if self._mp_ranking:
            # per-process: the padded-query lattice indexes LOCAL rows,
            # so gather the host's own score block, compute there, and
            # re-place the result into the sharded global array (the
            # reference's objective is likewise machine-local)
            loc = self.plan.host_local_cols(scores,
                                            self.train_dd.r_local)
            g, h = obj.get_gradients(jnp.asarray(loc[0]),
                                     self._label_local,
                                     self._weight_local, **kwargs)
            return (self.plan.shard_scores(
                        np.asarray(g, np.float32)[None, :]),
                    self.plan.shard_scores(
                        np.asarray(h, np.float32)[None, :]))
        g, h = obj.get_gradients(scores[0], self.label_dev,
                                 self.weight_dev, **kwargs)
        return g[None, :], h[None, :]

    @staticmethod
    def _update_score_impl(scores_k, leaf_values, row_leaf, lr):
        rlc = jnp.where(row_leaf >= 0, row_leaf, leaf_values.shape[0] - 1)
        add = jnp.take(leaf_values, rlc) * lr
        return scores_k + jnp.where(row_leaf >= 0, add, 0.0)

    def _goss_impl(self, g, h, key):
        """GOSS mask+amplify (goss.hpp Helper): keep top `top_rate` rows by
        sum_k |g*h|, sample `other_rate` of the rest, amplify their grads."""
        cfg = self.config
        R = g.shape[1]
        n_real = self._num_data_global
        real = (self.train_dd.row_leaf0 >= 0).astype(jnp.float32)
        # padded rows DO carry gradients (label 0 vs init score) — mask them
        # out of the ranking or they displace real rows from the top set
        # padded rows must be UNSELECTABLE, not merely zero-scored: a
        # real row tied at 0 could otherwise lose its top slot to a
        # lower-index padded row (multi-host padding sits at each
        # host's local tail, below later hosts' real rows)
        score = jnp.where(real > 0,
                          jnp.sum(jnp.abs(g * h), axis=0), -jnp.inf)
        top_k = max(1, int(n_real * cfg.top_rate))
        other_k = max(1, int(n_real * cfg.other_rate))
        # exact arg-partition (goss.hpp:30 ArgMaxAtK): lax.top_k keeps
        # exactly top_k rows even on tied scores
        _, top_idx = jax.lax.top_k(score, top_k)
        is_top = jnp.zeros((R,), bool).at[top_idx].set(True)
        u = jax.random.uniform(key, (R,))
        rest = ~is_top & (self.train_dd.row_leaf0 >= 0)
        p_keep = other_k / max(1, n_real - top_k)
        sampled = rest & (u < p_keep)
        amp = (1.0 - cfg.top_rate) / cfg.other_rate
        mask = is_top.astype(jnp.float32) + sampled.astype(jnp.float32)
        scale = jnp.where(sampled, amp, 1.0) * mask
        return g * scale[None, :], h * scale[None, :], mask

    def _bagging_active(self) -> bool:
        cfg = self.config
        balanced = (cfg.pos_bagging_fraction < 1.0
                    or cfg.neg_bagging_fraction < 1.0)
        return (not self._goss and cfg.bagging_freq > 0
                and (cfg.bagging_fraction < 1.0 or balanced))

    def _host_bag_mask(self, it: int) -> Optional[jax.Array]:
        """Regenerate/return the device bagging mask for iteration
        ``it`` (host RNG draws, no device sync), or None when bagging is
        off. Shared by the legacy loop and the fused dispatcher so both
        consume the identical ``_rng_bagging`` stream."""
        cfg = self.config
        if not self._bagging_active():
            return None
        if it % cfg.bagging_freq == 0 or self._bag_mask is None:
            R = self.train_dd.r_local
            balanced = (cfg.pos_bagging_fraction < 1.0
                        or cfg.neg_bagging_fraction < 1.0)
            n = self.train_dd.num_data
            m = np.zeros(R, np.float32)
            if balanced:
                # balanced bagging (bagging.hpp:146-165): positives
                # and negatives subsampled at their own rates
                lbl = np.asarray(self.train_set.get_label())[:n]
                pos = np.nonzero(lbl > 0)[0]
                neg = np.nonzero(lbl <= 0)[0]
                for rows, frac in ((pos, cfg.pos_bagging_fraction),
                                   (neg, cfg.neg_bagging_fraction)):
                    if len(rows) == 0:
                        continue
                    cnt = max(1, int(len(rows) * frac))
                    m[self._rng_bagging.choice(rows, cnt,
                                               replace=False)] = 1.0
            elif cfg.bagging_by_query:
                if self.train_set.group is None:
                    raise ValueError(
                        "bagging_by_query needs query/group data on "
                        "the training Dataset")
                # sample whole queries (bagging_by_query,
                # bagging.hpp:36,169) so ranking lists stay intact
                bounds = self.train_set.query_boundaries()
                nq = len(bounds) - 1
                cnt = max(1, int(nq * cfg.bagging_fraction))
                qs = self._rng_bagging.choice(nq, cnt, replace=False)
                for q in qs:
                    m[bounds[q]:bounds[q + 1]] = 1.0
            else:
                cnt = max(1, int(n * cfg.bagging_fraction))
                idx = self._rng_bagging.choice(n, cnt, replace=False)
                m[idx] = 1.0
            self._bag_mask = (self.plan.shard_rows(m)
                              if self.plan is not None
                              else jnp.asarray(m))
        return self._bag_mask

    def _sampling(self, it: int, g: jax.Array, h: jax.Array):
        """Returns (g, h, count_mask [R] f32). Bagging masks are built
        per process over local rows (the reference's bagging runs on
        each machine's own partition too)."""
        cfg = self.config
        real = self.train_dd.row_leaf0 >= 0
        base_mask = real.astype(jnp.float32)
        if self._goss:
            # reference skips GOSS for the first 1/learning_rate iterations
            if it >= int(1.0 / cfg.learning_rate):
                key = jax.random.fold_in(
                    jax.random.PRNGKey(cfg.bagging_seed), it)
                return self._goss_jit(g, h, key)
            return g, h, base_mask
        mask = self._host_bag_mask(it)
        if mask is not None:
            return g * mask, h * mask, mask
        return g, h, base_mask

    def _feature_mask(self) -> jax.Array:
        cfg = self.config
        F = self.train_set.num_features
        put = np.asarray if self._mp else jnp.asarray
        if cfg.feature_fraction >= 1.0:
            return put(np.ones((F,), bool))
        k = max(1, int(F * cfg.feature_fraction))
        idx = self._rng_feature.choice(F, k, replace=False)
        m = np.zeros(F, bool)
        m[idx] = True
        return put(m)

    # ------------------------------------------------------------------
    def _prep_custom_gh(self, gradients, hessians):
        """Custom fobj arrays: flat [K*num_data] class-major
        (LGBM_BoosterUpdateOneIterCustom layout) or [num_data, K].
        Multi-host: the caller supplies THIS process's rows; placement
        goes through the plan so the global array assembles from the
        per-host blocks."""
        R_loc = self.train_dd.r_local

        def prep(a):
            a = np.asarray(a, np.float32)
            n = self.train_dd.num_data
            if a.ndim == 1:
                a = a.reshape(self.K, n)
            else:
                a = a.T
            kr = _pad_rows(a.T, R_loc).T
            return (self.plan.shard_scores(kr) if self.plan is not None
                    else jnp.asarray(kr))
        return prep(gradients), prep(hessians)

    def _build_one_tree(self, gh: jax.Array, fmask: jax.Array, k: int = 0,
                        quant_scales: Optional[jax.Array] = None,
                        it=None, traced: bool = False):
        """One tree on the current gradients; returns device results
        (TreeArrays, row_leaf, valid_row_leafs, RoundLog — None from the
        chunked builder). ``it`` overrides the iteration index (the
        fused step passes a
        traced scalar); ``traced`` inlines the builder into an ambient
        trace instead of dispatching its jit."""
        cfg = self.config
        if it is None:
            it = self.iter_
        if self.chunked:
            # out-of-core: stream the bin matrix through the chunked
            # builder. Its gate already pinned every feature the kw
            # plumbing below would add (quant/gain_scale ride through).
            kwc = {}
            if quant_scales is not None:
                kwc["quant_scales"] = quant_scales
            if self._gain_scale is not None:
                kwc["gain_scale"] = self._gain_scale
            # host-driven chunk sweeps keep no per-round counters
            return self._chunked_builder.build(
                self._prefetcher, gh, self.train_dd.row_leaf0, fmask,
                valid_bins=tuple(dd.bins for dd in self.valid_dd),
                valid_row_leaf0=tuple(dd.row_leaf0
                                      for dd in self.valid_dd), **kwc
            ) + (None,)
        builder = (self.plan.build_tree if self.plan is not None
                   else functools.partial(build_tree, traced=traced))
        # fold both iteration and class index: multiclass trees of one
        # iteration must sample independently (the reference's shared RNG
        # advances per tree)
        key = (jax.random.fold_in(
            jax.random.fold_in(self._tree_key, it), k)
            if self._tree_key is not None else None)
        kw = {}
        if quant_scales is not None:
            kw["quant_scales"] = quant_scales
        if self._cat_sorted_mask is not None:
            kw["cat_sorted_mask"] = self._cat_sorted_mask
        if self._bundle_meta is not None:
            kw["bundle_meta"] = self._bundle_meta
            kw["bundle_bins"] = self._bundle_bins
        if self.plan is None:
            # single-device extras (reference ties CEGB to the serial
            # learner; feature_contri follows for simplicity)
            if self._gain_scale is not None:
                kw["gain_scale"] = self._gain_scale
            if self._cegb is not None:
                t, ps, coupled, lazy = self._cegb
                kw["cegb"] = (t, ps, coupled, lazy,
                              self._cegb_feat_used, self._cegb_used_rows)
        if (self.plan is None and self._bundle_meta is None
                and cfg.hist_impl == "native"):
            # column-major copy of the bin matrix for the native
            # PARTITION custom call (dense_bin.hpp stores per-feature
            # columns for the same reason: the split feature's column is
            # read contiguously); built once, reused every tree
            if self._bins_cm is None:
                self._bins_cm = jnp.asarray(self.train_dd.bins.T)
            kw["bins_cm"] = self._bins_cm
        kw["mono_method"] = self._mono_method()
        if self._forced_splits is not None:
            kw["forced"] = self._forced_splits
        leaf_batch = self._leaf_batch()
        out = builder(
            self.train_dd.bins, gh, self.train_dd.row_leaf0,
            self.num_bins_pf, self.nan_bin_pf, self.is_cat_pf, fmask,
            num_leaves=cfg.num_leaves, leaf_batch=leaf_batch,
            max_depth=cfg.max_depth, num_bins=self.B,
            split_params=self.split_params,
            hist_dtype=cfg.hist_dtype, hist_impl=cfg.hist_impl,
            hist_sub=self._hist_sub, block_rows=self.block,
            valid_bins=tuple(dd.bins for dd in self.valid_dd),
            valid_row_leaf0=tuple(dd.row_leaf0 for dd in self.valid_dd),
            mono_type_pf=self.mono_type_pf,
            interaction_groups=self.interaction_groups,
            rng_key=key, feature_fraction_bynode=self._ffbn, **kw)
        if "cegb" in kw:
            tree_arrays, row_leaf, valid_rls, rounds, cegb_state = out
            self._cegb_feat_used, self._cegb_used_rows = cegb_state
            return tree_arrays, row_leaf, valid_rls, rounds
        return out

    def _mono_method(self) -> str:
        return (self.config.monotone_constraints_method
                if self.mono_type_pf is not None else "basic")

    def _leaf_batch(self) -> int:
        """The ``leaf_batch`` a build runs with: 1 where splits must
        apply one at a time. Cross-leaf bound propagation is only sound
        so (see tree_builder.py; the reference learner is sequential
        there anyway), and forced splits assign node slots in order
        (the class-batched build never carries them)."""
        if (self._mono_method() in ("intermediate", "advanced")
                or self._forced_splits is not None):
            return 1
        return self.config.leaf_batch

    def _step_shape(self) -> StepShape:
        """``tree_builder.step_shape`` of what :meth:`_build_one_tree`
        (or its class-batched twin) hands the builder: the sizes the
        traced build takes for itself, for one device. Host integers
        only."""
        cfg, plan = self.config, self.plan
        n = plan.num_shards if plan is not None else 1
        mode = plan.parallel_mode if plan is not None else None
        rows = self.train_dd.r_pad
        columns = self.train_dd.bins.shape[1]
        features = int(self.num_bins_pf.shape[0])
        if mode == "feature":
            # rows replicated, each chip histograms and searches its
            # slice of the columns (F padded to a multiple of the chips)
            columns = features = feature_block(features, n)
        elif plan is not None and plan.rows_sharded:
            rows //= n
        bundled = self._bundle_meta is not None
        return step_shape(
            rows=rows, stored_columns=columns, features=features,
            num_bins=self.B,
            bundle_bins=self._bundle_bins if bundled else 0,
            num_leaves=cfg.num_leaves, leaf_batch=self._leaf_batch(),
            hist_impl=build_impl(
                cfg.hist_impl, (self._bundle_bins if bundled else 0)
                or self.B, self.class_batch_ok),
            gh_dtype=jnp.int8 if self._quant else jnp.float32,
            hist_dtype=cfg.hist_dtype, block_rows=self.block,
            hist_sub=self._hist_sub, parallel_mode=mode,
            hist_merge=getattr(plan, "hist_merge", "allreduce"),
            n_shards=n)

    def stage_work(self, n: Optional[int] = None, *,
                   fullest: bool = False) -> Dict[str, Tuple[float, str]]:
        """``{stage: (count, unit)}`` of the last ``n`` trees of
        ``round_log`` (all it holds by default): what each stage of the
        fused step worked through, in the units of ``phases.STAGE_WORK``
        (``telemetry/costmodel.stage_work`` has the counting). One
        device's: under a row-sharded plan the mean over the shards, or
        the fullest shard's with ``fullest``. Empty before the first
        fused step has been made or the first tree fetched."""
        from ..telemetry.costmodel import stage_work
        log = list(self.round_log)
        log = log[-n:] if n else log
        if self.step_shape is None or not log:
            return {}
        return stage_work(
            self.step_shape, log, plan_bytes=self.plan_counters,
            pair_slots=(getattr(self.objective, "counters", None)
                        or {}).get("pair_slots", 0),
            fullest=fullest)

    # -- out-of-core chunked training gate (ISSUE 13) ------------------

    def _chunked_gate_reason(self) -> str:
        """Why the out-of-core chunked driver cannot grow this run's
        trees ('' = it can). The chunked builder replays the serial
        builder's simple round body over streamed row chunks; anything
        that bends that body — whole-matrix device state, per-node host
        coordination, cross-leaf bound propagation — pins the resident
        path. Evaluated at the capacity gate, so it reads raw config
        (``_cegb``/``_forced_splits`` are assembled later)."""
        cfg = self.config
        if type(self) is not GBDT:
            return "boosting mode replays resident device trees"
        if self.plan is not None:
            return "parallel plans place the full device matrix"
        if self._bundle_meta is not None:
            return "EFB bundles bin in device bundle space"
        if bool(cfg.linear_tree):
            return "linear leaves read resident raw feature values"
        if cfg.monotone_constraints:
            return "monotone constraints propagate cross-leaf bounds"
        if cfg.interaction_constraints:
            return "interaction constraints thread per-node ancestry"
        if cfg.forcedsplits_filename:
            return "forced splits assign node slots sequentially"
        if (cfg.cegb_tradeoff < 1.0 or cfg.cegb_penalty_split > 0.0
                or cfg.cegb_penalty_feature_coupled
                or cfg.cegb_penalty_feature_lazy):
            return "CEGB tracks per-row feature-use device state"
        if float(cfg.feature_fraction_bynode) < 1.0:
            return "per-node feature sampling draws inside the builder"
        if bool(cfg.extra_trees):
            return "extra-trees thresholds draw inside the builder"
        return ""

    # -- class-batched multiclass build (ISSUE 8) ----------------------

    def _class_batch_reason(self) -> str:
        """Why the class-batched build cannot drive this run ('' = it
        can). Unlike the fused gate this applies to BOTH drivers: when
        it clears, the legacy loop and the fused step each grow all K
        per-class trees of an iteration through ONE
        :func:`tree_builder._build_tree_class_batched` program instead
        of K sequential builds. Anything threading per-class host state
        between builds, or assigning tree structure sequentially, pins
        the per-class loop."""
        import os
        cfg = self.config
        env = os.environ.get("LIGHTGBM_TPU_CLASS_BATCH", "")
        if env == "0":
            return "LIGHTGBM_TPU_CLASS_BATCH=0"
        if self.chunked:
            return "out-of-core training streams row chunks per tree"
        mode = "on" if env == "1" else str(cfg.class_batch)
        if mode == "off":
            return "class_batch=off"
        if self.K <= 1 and mode != "on":
            # one model per iteration: nothing to batch (class_batch=on
            # still exercises the K=1 batched path — the parity tests
            # rely on that)
            return "single model per iteration"
        if type(self) is not GBDT:
            return "boosting mode overrides the iteration loop"
        if bool(cfg.linear_tree):
            return "linear leaves solve per-class on host raw values"
        if self._forced_splits is not None:
            return "forced splits assign node slots sequentially"
        if self._cegb is not None:
            return "CEGB threads per-class model state across builds"
        if self.plan is not None and self.plan.parallel_mode == "feature":
            return "feature-parallel plan builds per-class"
        if self._mp:
            return "multi-process meshes place per-host blocks"
        return ""

    def _class_batch_keys(self, it):
        """[K, 2] per-class builder PRNG keys — fold_in(it) then
        fold_in(k), bit-identical to the keys the sequential loop's
        ``_build_one_tree(.., k)`` consumes — or None when per-node
        sampling and extra-trees are off."""
        if self._tree_key is None:
            return None
        it_key = jax.random.fold_in(self._tree_key, it)
        return jax.vmap(lambda k: jax.random.fold_in(it_key, k))(
            jnp.arange(self.K, dtype=jnp.int32))

    def _build_one_tree_batched(self, gh_k: jax.Array, fmask: jax.Array,
                                quant_scales_k: Optional[jax.Array] = None,
                                it=None, traced: bool = False):
        """All K trees of one iteration in ONE class-batched build.
        ``gh_k`` is [K, R, 3] (grad/hess/count channels per class);
        ``quant_scales_k`` is [K, 2]. Returns (stacked TreeArrays with
        a leading K axis, row_leaf [K, R], valid_row_leafs tuple of
        [K, Rv], RoundLog with a leading K). Only reachable when
        :meth:`_class_batch_reason`
        cleared, so the forced/CEGB/linear extras of
        :meth:`_build_one_tree` never arise here."""
        cfg = self.config
        if it is None:
            it = self.iter_
        if self.plan is not None:
            builder = functools.partial(self.plan.build_tree,
                                        class_batched=True)
        else:
            builder = functools.partial(build_tree, traced=traced,
                                        class_batched=True)
        kw = {}
        if quant_scales_k is not None:
            kw["quant_scales"] = quant_scales_k
        if self._cat_sorted_mask is not None:
            kw["cat_sorted_mask"] = self._cat_sorted_mask
        if self._bundle_meta is not None:
            kw["bundle_meta"] = self._bundle_meta
            kw["bundle_bins"] = self._bundle_bins
        if self.plan is None and self._gain_scale is not None:
            kw["gain_scale"] = self._gain_scale
        kw["mono_method"] = self._mono_method()
        return builder(
            self.train_dd.bins, gh_k, self.train_dd.row_leaf0,
            self.num_bins_pf, self.nan_bin_pf, self.is_cat_pf, fmask,
            num_leaves=cfg.num_leaves, leaf_batch=self._leaf_batch(),
            max_depth=cfg.max_depth, num_bins=self.B,
            split_params=self.split_params,
            hist_dtype=cfg.hist_dtype, hist_impl=cfg.hist_impl,
            hist_sub=self._hist_sub, block_rows=self.block,
            valid_bins=tuple(dd.bins for dd in self.valid_dd),
            valid_row_leaf0=tuple(dd.row_leaf0 for dd in self.valid_dd),
            mono_type_pf=self.mono_type_pf,
            interaction_groups=self.interaction_groups,
            rng_key=self._class_batch_keys(it),
            feature_fraction_bynode=self._ffbn, **kw)

    def _stack_gh_k(self, g, h, count_mask):
        """[K, R, 3] batched gh for the class-batched build — the
        per-class analog of the sequential loop's
        ``jnp.stack([g[k], h[k], count_mask], axis=1)``."""
        return jnp.stack([g, h, jnp.broadcast_to(count_mask, g.shape)],
                         axis=2)

    def _parse_forced_splits(self, path):
        """JSON forced-split tree -> (parents, isright, feats, thrs,
        is_cat) static tuples in BFS order (ForceSplits queue
        semantics). Each node records its parent's index in the list
        (-1 for the root) and which side it forces — slots resolve at
        runtime inside the builder so a dropped forced node drops its
        subtree. Feature indices are ORIGINAL column ids; thresholds
        are raw values mapped through the feature's BinMapper. A
        categorical node forces the one-hot split on its category
        (GatherInfoForThresholdCategoricalInner,
        feature_histogram.hpp:604: left = rows equal to the category,
        default_left=false)."""
        import json as _json
        from collections import deque
        with open(path) as fh:
            root = _json.load(fh)
        if self.plan is not None and self.plan.parallel_mode != "data":
            raise NotImplementedError(
                "forced splits support the serial/data tree learners")
        uf = list(self.train_set.used_features)
        parents, isright, feats, thrs, iscat = [], [], [], [], []
        q = deque([(root, -1, False)])
        while q:
            node, pj, is_r = q.popleft()
            if not node:
                continue
            f_orig = int(node["feature"])
            if f_orig not in uf:
                raise ValueError(
                    f"forced split feature {f_orig} is not a used "
                    "feature of the dataset")
            f_inner = uf.index(f_orig)
            m = self.train_set.bin_mappers[f_orig]
            if m.bin_type == "categorical":
                # reference: ValueToBin of an unseen/negative category
                # returns the reserved bin and the gather rejects it
                # ("Invalid categorical threshold split",
                # feature_histogram.hpp:613). Our bin 0 is the most
                # frequent REAL category, so the miss must be caught
                # here: thr_bin=-1 makes the builder drop the node.
                cv = int(float(node["threshold"]))
                thr_bin = m._cat_to_bin.get(cv, -1) if cv >= 0 else -1
                if thr_bin < 0:
                    from .. import log as _log
                    _log.warning(
                        "Invalid categorical threshold split: category "
                        f"{cv} of feature {f_orig} was not seen in "
                        "training; the forced node will be skipped")
            else:
                thr_bin = int(m.values_to_bins(
                    np.asarray([float(node["threshold"])]))[0])
            me = len(parents)
            parents.append(pj)
            isright.append(is_r)
            feats.append(f_inner)
            thrs.append(thr_bin)
            iscat.append(m.bin_type == "categorical")
            if node.get("left"):
                q.append((node["left"], me, False))
            if node.get("right"):
                q.append((node["right"], me, True))
        return (tuple(parents), tuple(isright), tuple(feats),
                tuple(thrs), tuple(iscat))

    def _quantize_impl(self, g, h, key):
        """Stochastic rounding onto the int8 quant grid
        (DiscretizeGradients, gradient_discretizer.cpp:68-140).
        g, h: [K, R] f32 -> int8 grid values [K, R] + per-class scales
        (gs, hs) [K]. The int8 values feed the integer MXU histogram; the
        scales descale histogram sums at split-find time."""
        cfg = self.config
        nb = int(cfg.num_grad_quant_bins)
        gs = jnp.maximum(jnp.max(jnp.abs(g), axis=1, keepdims=True),
                         1e-30) / (nb // 2)
        hs = jnp.maximum(jnp.max(jnp.abs(h), axis=1, keepdims=True),
                         1e-30) / nb
        if bool(cfg.stochastic_rounding):
            # the rounding stream is defined on LOGICAL rows, not the
            # padded layout: threefry output depends on the draw shape,
            # and r_pad differs between serial and mesh runs (the mesh
            # pads to block*num_shards) — drawing at [K, num_data] and
            # padding with the deterministic 0.5 offset makes every
            # real row consume identical randomness under any sharding,
            # the bit-parity precondition of serial-vs-data training.
            # (Multi-HOST runs interleave per-process pads, so only
            # same-process-count runs are bit-comparable there.)
            n = min(self._num_data_global, g.shape[1])

            def draws(salt, width):
                u = jax.random.uniform(jax.random.fold_in(key, salt),
                                       (g.shape[0], n))
                return jnp.pad(u, ((0, 0), (0, width - n)),
                               constant_values=0.5)
            u1 = draws(0, g.shape[1])
            u2 = draws(1, h.shape[1])
        else:
            u1 = jnp.full_like(g, 0.5)
            u2 = jnp.full_like(h, 0.5)
        # int8 cast truncates toward zero; the random offset is applied
        # away from zero (gradient_discretizer.cpp:124-131)
        qg = jnp.trunc(g / gs + jnp.where(g >= 0, u1, -u1))
        qh = jnp.trunc(h / hs + u2)
        return (qg.astype(jnp.int8), qh.astype(jnp.int8),
                gs[:, 0], hs[:, 0])

    def _renew_leaf_impl(self, tree_arrays: TreeArrays, row_leaf, g, h):
        """RenewIntGradTreeOutput (gradient_discretizer.cpp:208-258):
        after a quantized build, leaf outputs are recomputed from the
        TRUE float grad/hess sums per leaf."""
        from ..ops.split import calc_output
        sp = self.split_params
        L1 = tree_arrays.leaf_values.shape[0]      # L + 1 (dummy slot)
        rlc = jnp.clip(row_leaf, 0, L1 - 1)
        dead = row_leaf < 0
        gz = jnp.where(dead, 0.0, g)
        hz = jnp.where(dead, 0.0, h)
        sum_g = jnp.zeros((L1,), jnp.float32).at[rlc].add(gz)
        sum_h = jnp.zeros((L1,), jnp.float32).at[rlc].add(hz)
        cnt = jnp.zeros((L1,), jnp.float32).at[rlc].add(
            jnp.where(dead, 0.0, 1.0))
        # NOTE: no path smoothing here — the reference's renewal calls
        # CalculateSplittedLeafOutput<USE_L1=true, USE_MAX_OUTPUT=true,
        # USE_SMOOTHING=false> (gradient_discretizer.cpp:231,254)
        out = calc_output(sum_g, sum_h, sp.lambda_l1, sp.lambda_l2,
                          sp.max_delta_step)
        live = (jnp.arange(L1) < tree_arrays.num_leaves) & (sum_h > 0)
        new_leaf = jnp.where(live, out, tree_arrays.leaf_values)
        node_value = tree_arrays.node_value.at[tree_arrays.leaf2node].set(
            jnp.where(live, new_leaf, jnp.take(
                tree_arrays.node_value, tree_arrays.leaf2node)))
        return tree_arrays._replace(leaf_values=new_leaf,
                                    node_value=node_value)

    # ------------------------------------------------------------------
    def _fit_linear_leaves(self, tree, row_leaf, g, h, shrink: float):
        """Per-leaf ridge solve on raw feature values
        (LinearTreeLearner::CalculateLinear, linear_tree_learner.cpp:
        280-385): for each leaf, regress -g on the raw values of the
        features along its path, weighted by h, ridge linear_lambda.
        Host NumPy: the solves are tiny ((d+1)^2 per leaf); the heavy
        segment sums vectorize over rows per leaf."""
        raw = self.train_set.raw_values
        lam = float(self.config.linear_lambda)
        n = self.train_set.num_data
        rl = np.asarray(row_leaf)[:n]
        g = np.asarray(g)[:n].astype(np.float64)
        h = np.asarray(h)[:n].astype(np.float64)

        # path features per leaf (global ids, first-use order)
        paths = [[] for _ in range(tree.num_leaves)]
        if tree.num_leaves > 1:
            stack = [(0, [])]
            while stack:
                node, feats = stack.pop()
                if node < 0:
                    paths[~node] = feats
                    continue
                f = int(tree.split_feature[node])
                nf = feats if f in feats else feats + [f]
                stack.append((int(tree.left_child[node]), nf))
                stack.append((int(tree.right_child[node]), nf))

        tree.is_linear = True
        for s in range(tree.num_leaves):
            feats = paths[s]
            rows = np.nonzero(rl == s)[0]
            tree.leaf_features[s] = []
            tree.leaf_coeff[s] = []
            tree.leaf_const[s] = tree.leaf_value[s]
            if not feats or len(rows) == 0:
                continue
            vals = raw[np.ix_(rows, feats)].astype(np.float64)
            ok = ~np.isnan(vals).any(axis=1)
            if ok.sum() < len(feats) + 1:
                continue  # too few clean rows: constant leaf
            X = np.concatenate([vals[ok], np.ones((ok.sum(), 1))], axis=1)
            hw = h[rows][ok]
            gw = g[rows][ok]
            A = (X * hw[:, None]).T @ X
            d = len(feats)
            A[np.arange(d), np.arange(d)] += lam
            b = X.T @ gw
            try:
                beta = -np.linalg.solve(A, b)
            except np.linalg.LinAlgError:
                continue
            if not np.isfinite(beta).all():
                continue
            keep = np.abs(beta[:d]) > 1e-35   # kZeroThreshold
            tree.leaf_features[s] = [feats[i] for i in range(d) if keep[i]]
            tree.leaf_coeff[s] = [float(beta[i] * shrink)
                                  for i in range(d) if keep[i]]
            tree.leaf_const[s] = float(beta[d] * shrink)

    def _linear_score_delta(self, tree, raw, row_leaf, r_pad):
        """Per-row SHRUNK outputs of a linear tree (AddPredictionToScore
        linear path, tree.cpp:120-149) for the score update."""
        n = raw.shape[0]
        rl = np.asarray(row_leaf)[:n]
        out = np.zeros(r_pad, np.float32)
        for s in range(tree.num_leaves):
            rows = np.nonzero(rl == s)[0]
            if len(rows) == 0:
                continue
            feats = tree.leaf_features[s]
            if not feats:
                out[rows] = tree.leaf_const[s]
                continue
            vals = raw[np.ix_(rows, feats)].astype(np.float64)
            nan = np.isnan(vals).any(axis=1)
            lin = tree.leaf_const[s] + vals @ np.asarray(tree.leaf_coeff[s])
            out[rows] = np.where(nan, tree.leaf_value[s], lin)
        return out

    def _bias_adjust_device(self, tree_arrays: TreeArrays, bias: float,
                            shrink: float) -> TreeArrays:
        """Fold an output bias into the stored device tree so that
        weight * node_value includes it (AddBias, tree.h; keeps DART /
        rollback / init_model score arithmetic consistent with the
        host-side first-tree bias of gbdt.cpp:416)."""
        adj = jnp.float32(bias / shrink)
        return tree_arrays._replace(
            node_value=tree_arrays.node_value + adj,
            leaf_values=tree_arrays.leaf_values + adj)

    # -- fused boosting step (ISSUE 3) ---------------------------------
    # One jitted program per iteration: grads -> sampling -> quantize ->
    # K tree builds -> score updates, with donated score buffers. Built
    # TreeArrays stay ON DEVICE in the pending ring and materialize to
    # host Tree objects in batches at sync points only (engine.train's
    # eval cadence), so the steady-state inner loop runs dispatch-ahead
    # with zero host syncs between eval points — the whole-round
    # on-device shape of the CUDA learner, now including the outer loop.

    def _fused_gate_reason(self) -> str:
        """Why the fused single-dispatch step cannot drive this run
        ('' = it can). Anything needing per-iteration HOST work — host
        gradients, host leaf solves, cross-tree host state — pins the
        legacy loop; host-RNG sampling masks do NOT (they are generated
        sync-free at dispatch time and passed in)."""
        import os
        cfg = self.config
        if os.environ.get("LIGHTGBM_TPU_FUSED_TRAIN", "") == "0":
            return "LIGHTGBM_TPU_FUSED_TRAIN=0"
        if not bool(cfg.fused_train):
            return "fused_train=false"
        if self.chunked:
            return "out-of-core chunk sweeps are host-driven"
        if type(self) is not GBDT:
            return "boosting mode overrides the iteration loop"
        if self.objective is None:
            return "custom objective gradients are host-supplied"
        if bool(cfg.linear_tree):
            return "linear leaves solve on host raw values"
        if self._cegb is not None:
            return "CEGB threads model-level host state"
        if self._mp:
            return "multi-process meshes place per-host blocks"
        if self.plan is not None and not self.plan.supports_fused():
            return "parallel plan pins the legacy loop"
        if self.objective.is_ranking and getattr(
                self.objective, "num_position_ids", 0):
            return "position-bias estimation updates host state"
        return ""

    def _fused_step_impl(self, scores, valid_scores, bag_mask, fmask,
                         it, lr):
        """The traced iteration body. Pure function of its inputs plus
        static self state; numerically identical to the legacy loop
        (same ops, one program). Returns (scores, valid_scores, trees,
        should_continue flag, finite flag, round logs) — all on
        device. ``trees`` is
        one stacked TreeArrays (leading K axis) when the class-batched
        build drives the iteration, else the per-class [TreeArrays]*K
        list; sync() materializes both forms, and the RoundLog(s) beside
        them in the same shape. The finite flag is the
        NaN guard's deferred device check (same mechanism as the
        no-split stop): NaN gradients produce -inf gains and a
        no-split tree, so without the explicit g/h check divergence
        would masquerade as a clean early stop."""
        cfg = self.config
        with profiler.stage("grads"):
            g, h = self._grads(it, scores)
        with profiler.stage("sampling"):
            if self._goss:
                # GOSS starts after 1/learning_rate iterations
                # (goss.hpp); a traced-iteration cond replaces the
                # legacy host branch
                thresh = int(1.0 / cfg.learning_rate)
                key = jax.random.fold_in(
                    jax.random.PRNGKey(cfg.bagging_seed), it)
                base = (self.train_dd.row_leaf0 >= 0).astype(jnp.float32)
                g, h, count_mask = jax.lax.cond(
                    it >= thresh,
                    lambda gg, hh: self._goss_impl(gg, hh, key),
                    lambda gg, hh: (gg, hh, base), g, h)
            elif self._bagging_active():
                g, h, count_mask = g * bag_mask, h * bag_mask, bag_mask
            else:
                count_mask = bag_mask    # base real-row mask
            g_true, h_true = g, h
            if self._quant:
                qg, qh, q_gs, q_hs = self._quantize_impl(
                    g, h, jax.random.fold_in(self._quant_key, it))
                count_i8 = count_mask.astype(jnp.int8)
            # the NaN guard's reductions are collectives under a
            # row-sharded plan: staged, so no collective is nameless
            finite = jnp.all(jnp.isfinite(g)) & jnp.all(jnp.isfinite(h))
        new_scores = scores
        new_valid = list(valid_scores)
        if self.class_batch_ok:
            # class-batched build (ISSUE 8): ONE program grows all K
            # trees — the class axis rides the leaf-slot axis through
            # every kernel, so the staged equations and the histogram
            # dispatches per round stop scaling with K
            if self._quant:
                gh_k = self._stack_gh_k(qg, qh, count_i8)
                qsk_b = jnp.stack([q_gs, q_hs], axis=1)     # [K, 2]
            else:
                gh_k = self._stack_gh_k(g, h, count_mask)
                qsk_b = None
            with profiler.stage("build"):
                trees_k, row_leaf_k, valid_rls_k, rounds_k = \
                    self._build_one_tree_batched(
                        gh_k, fmask, quant_scales_k=qsk_b, it=it,
                        traced=self.plan is None)
                if self._quant and bool(cfg.quant_train_renew_leaf):
                    trees_k = jax.vmap(self._renew_leaf_impl)(
                        trees_k, row_leaf_k, g_true, h_true)
            grew_k = trees_k.num_leaves > 1                 # [K] bool
            with profiler.stage("update"):
                # per-class rows are independent, so the batched
                # where() equals the sequential .at[k].set chain
                upd = jax.vmap(self._update_score_impl,
                               in_axes=(0, 0, 0, None))(
                    new_scores, trees_k.leaf_values, row_leaf_k, lr)
                new_scores = jnp.where(grew_k[:, None], upd, new_scores)
                for vi, vrl_k in enumerate(valid_rls_k):
                    vupd = jax.vmap(self._update_score_impl,
                                    in_axes=(0, 0, 0, None))(
                        new_valid[vi], trees_k.leaf_values, vrl_k, lr)
                    new_valid[vi] = jnp.where(grew_k[:, None], vupd,
                                              new_valid[vi])
                finite = finite & jnp.all(jnp.isfinite(new_scores))
            return (new_scores, tuple(new_valid), trees_k,
                    jnp.any(grew_k), finite, rounds_k)
        trees = []
        grews = []
        rounds = []
        for k in range(self.K):
            if self._quant:
                gh = jnp.stack([qg[k], qh[k], count_i8], axis=1)
                qsk = {"quant_scales": jnp.stack([q_gs[k], q_hs[k]])}
            else:
                gh = jnp.stack([g[k], h[k], count_mask], axis=1)
                qsk = {}
            with profiler.stage("build"):
                tree_arrays, row_leaf, valid_rls, rounds_1 = \
                    self._build_one_tree(gh, fmask, k, it=it,
                                         traced=self.plan is None, **qsk)
                if self._quant and bool(cfg.quant_train_renew_leaf):
                    tree_arrays = self._renew_leaf_impl(
                        tree_arrays, row_leaf, g_true[k], h_true[k])
            grew = tree_arrays.num_leaves > 1
            with profiler.stage("update"):
                # score updates apply only when the tree grew — the
                # device form of the legacy num_leaves>1 host check
                upd = self._update_score_impl(
                    new_scores[k], tree_arrays.leaf_values, row_leaf, lr)
                new_scores = new_scores.at[k].set(
                    jnp.where(grew, upd, new_scores[k]))
                for vi, vrl in enumerate(valid_rls):
                    vupd = self._update_score_impl(
                        new_valid[vi][k], tree_arrays.leaf_values, vrl,
                        lr)
                    new_valid[vi] = new_valid[vi].at[k].set(
                        jnp.where(grew, vupd, new_valid[vi][k]))
            trees.append(tree_arrays)
            grews.append(grew)
            rounds.append(rounds_1)
        cont = jnp.any(jnp.stack(grews))
        with profiler.stage("update"):
            finite = finite & jnp.all(jnp.isfinite(new_scores))
        return new_scores, tuple(new_valid), trees, cont, finite, rounds

    def _fused_data_args(self):
        """The large per-instance device arrays the fused step reads,
        as a pytree jit ARGUMENT. Closed-over concrete arrays
        would be embedded into the lowered module as dense HLO
        constants — a multi-MB (at Higgs scale, multi-hundred-MB)
        constant per dataset that XLA then burns compile time
        constant-folding over. Passing them as arguments keeps the
        program data-free like the legacy build_tree jit."""
        return dict(
            bins=self.train_dd.bins,
            row_leaf0=self.train_dd.row_leaf0,
            label=self.label_dev,
            weight=self.weight_dev,
            bins_cm=self._bins_cm,
            valid_bins=tuple(dd.bins for dd in self.valid_dd),
            valid_rl0=tuple(dd.row_leaf0 for dd in self.valid_dd),
            rank=(self.objective.device_state
                  if self.objective.is_ranking else None))

    def _fused_step_entry(self, scores, valid_scores, bag_mask, fmask,
                          it, lr, data):
        """jit entry point: rebinds ``data``'s tracers onto self for
        the duration of the trace (restored in finally), so every read
        the step body makes of the big arrays resolves to a program
        argument instead of a closure constant. Runs only while
        TRACING — steady-state dispatches hit the compiled cache and
        never re-enter Python here."""
        saved = (self.train_dd.bins, self.train_dd.row_leaf0,
                 self.label_dev, self.weight_dev, self._bins_cm,
                 [dd.bins for dd in self.valid_dd],
                 [dd.row_leaf0 for dd in self.valid_dd])
        ranking = data["rank"] is not None
        if ranking:
            rank_saved = self.objective.bind_device_state(data["rank"])
        try:
            self.train_dd.bins = data["bins"]
            self.train_dd.row_leaf0 = data["row_leaf0"]
            self.label_dev = data["label"]
            self.weight_dev = data["weight"]
            self._bins_cm = data["bins_cm"]
            for dd, b, rl in zip(self.valid_dd, data["valid_bins"],
                                 data["valid_rl0"]):
                dd.bins, dd.row_leaf0 = b, rl
            return self._fused_step_impl(scores, valid_scores, bag_mask,
                                         fmask, it, lr)
        finally:
            (self.train_dd.bins, self.train_dd.row_leaf0, self.label_dev,
             self.weight_dev, self._bins_cm, vb, vr) = saved
            for dd, b, rl in zip(self.valid_dd, vb, vr):
                dd.bins, dd.row_leaf0 = b, rl
            if ranking:
                self.objective.bind_device_state(rank_saved)

    def _full_row_mask(self) -> jax.Array:
        """All-real-rows bagging mask, ``(row_leaf0 >= 0)`` as f32,
        cached by buffer identity — ``row_leaf0`` is static across
        iterations, and recomputing eagerly cost two extra device
        dispatches (greater_equal + convert) per fused iteration."""
        rl0 = self.train_dd.row_leaf0
        cached = self._full_mask_cache
        if cached is None or cached[0] is not rl0:
            self._full_mask_cache = (rl0, (rl0 >= 0).astype(jnp.float32))
        return self._full_mask_cache[1]

    def _fused_dispatch(self):
        """Enqueue one fused iteration: a single jit dispatch, no host
        sync. Host-RNG inputs (bagging mask, feature mask) are drawn
        here — pure host computation — so fused and legacy consume the
        identical RNG streams in the identical order. The whole of it
        is one ``gbdt.dispatch`` span: the driver's busy time a tree."""
        profiler.recorder.iteration = self.iter_
        with profiler.span("gbdt.dispatch"):
            self._fused_dispatch_impl()

    def _fused_dispatch_impl(self):
        it = self.iter_
        mask = self._host_bag_mask(it)
        if mask is None:
            mask = self._full_row_mask()
        fmask = self._feature_mask()
        if (self._bins_cm is None and self.plan is None
                and self._bundle_meta is None
                and self.config.hist_impl == "native"):
            # the lazy column-major copy must exist BEFORE tracing: a
            # trace-time build inside _build_one_tree would store a
            # tracer on self
            self._bins_cm = jnp.asarray(self.train_dd.bins.T)
        if self._fused_jit is None:
            # donate the score carries on accelerators: each iteration
            # writes into the previous buffers instead of allocating
            # K*R fresh. The CPU backend pins NO-donation: np.asarray
            # of a CPU jax array is zero-copy, so metric/eval code can
            # still hold views of the previous score buffers when the
            # next donated in-place write lands (observed as corrupted
            # valid metrics + runtime aborts).
            donate = (0, 1) if jax.default_backend() != "cpu" else ()
            self._fused_jit = jax.jit(self._fused_step_entry,
                                      donate_argnums=donate)
            step = self._step_ready
        else:
            step = self._fused_jit
        scores, valid_scores, trees, cont, ok, rounds = step(
            self.scores, tuple(self.valid_scores), mask, fmask,
            jnp.asarray(it, jnp.int32),
            jnp.asarray(self.shrinkage, jnp.float32),
            self._fused_data_args())
        self.scores = scores
        self.valid_scores = list(valid_scores)
        self._pending.append((it, float(self.shrinkage), trees, cont, ok,
                              rounds))
        self.iter_ += 1

    def _step_ready(self, *args):
        """The first call of the fused step, as the ``gbdt.step_ready``
        span: trace, lowering, and the backend compile or the load from
        the persistent cache. JAX's own monitoring durations of those
        parts, and its cache hit/miss events, ride on the span as
        fields (summed over every program the call compiles)."""
        import jax.monitoring as mon
        names = {"/jax/core/compile/jaxpr_trace_duration": "trace_s",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration":
                     "lowering_s",
                 "/jax/core/compile/backend_compile_duration":
                     "backend_compile_s",
                 "/jax/compilation_cache/cache_hits": "cache_hits",
                 "/jax/compilation_cache/cache_misses": "cache_misses"}
        with profiler.span("gbdt.step_ready") as fields:
            self.step_shape = self._step_shape().fields()
            fields.update(self.step_shape)

            def on_duration(event, duration, **_):
                if event in names:
                    key = names[event]
                    fields[key] = fields.get(key, 0.0) + duration

            def on_event(event, **_):
                if event in names:
                    fields[names[event]] = fields.get(names[event], 0) + 1

            mon.register_event_duration_secs_listener(on_duration)
            mon.register_event_listener(on_event)
            try:
                if self.plan is not None:
                    # the plan's counters, from the text of the very
                    # executable the call below runs: lowering is
                    # cached on the arguments' types, so this is the
                    # step's one compile and the call finds it made
                    from ..parallel.comms import plan_counters
                    self.plan_counters = plan_counters(
                        self._fused_jit.lower(*args).compile(),
                        self.plan.num_shards,
                        self.step_shape[phases.SHAPE_ROWS])
                    fields.update(self.plan_counters)
                return self._fused_jit(*args)
            finally:
                mon.unregister_event_duration_listener(on_duration)
                mon.unregister_event_listener(on_event)

    def sync(self) -> bool:
        """Materialize every deferred iteration's device trees into host
        ``Tree`` models with ONE device transfer, and run the deferred
        stop check (the device should_continue flags of the pending
        ring). Returns True when training must stop — a no-split
        iteration was found; it and everything dispatched after it are
        dropped (their score updates were device no-ops, so the live
        scores are already correct). No-op False when nothing pends."""
        if not self._pending:
            return False
        pending, self._pending = self._pending, []
        try:
            with profiler.span("gbdt.sync.wait"):
                host = jax.device_get([p[2:] for p in pending])
        except jax.errors.JaxRuntimeError as e:
            # an XLA execution error surfacing at the ring drain means
            # a device (or its collective partner) went away mid-step
            from ..resilience.guards import DeviceLossError
            raise DeviceLossError(pending[0][0], detail=str(e)) from e
        self.host_sync_count += 1
        with profiler.span("gbdt.sync.trees"):
            return self._sync_trees(pending, host)

    def _sync_trees(self, pending, host) -> bool:
        """Host ``Tree`` models (and ``round_log`` entries) from the
        fetched ring; the deferred stop and divergence checks."""
        bm = self.train_set.bin_mappers
        uf = self.train_set.used_features
        stop = False
        kept = 0
        for (it, shrink, *_), (trees_h, cont, ok, rounds_h) in zip(
                pending, host):
            if self._nan_guard != "off" and not bool(ok):
                # divergence check BEFORE the no-split stop: NaN grads
                # build a no-split tree, which would otherwise read as
                # a clean early stop. iter_ rewinds to the last good
                # iteration so a checkpoint restore / re-raise sees a
                # consistent counter.
                from ..resilience.guards import NumericDivergenceError
                self.iter_ = pending[0][0] + kept
                raise NumericDivergenceError(it)
            if not bool(cont) and it > 0:
                # drop the no-op iteration (and its dispatch-ahead
                # successors, which trained on unchanged scores),
                # reference gbdt.cpp:441-447
                stop = True
                break
            if isinstance(trees_h, TreeArrays):
                # class-batched iteration: ONE stacked TreeArrays with
                # a leading K axis; unstack into per-class host views
                # (zero-copy numpy slices)
                trees_h = [jax.tree.map(lambda a: a[k], trees_h)
                           for k in range(self.K)]
                rounds_h = [jax.tree.map(lambda a: a[k], rounds_h)
                            for k in range(self.K)]
            for k, rl in enumerate(rounds_h):
                self._log_rounds(it, k, rl)
            for k, tree in enumerate(Tree.from_device_batch(
                    trees_h, bm, uf, shrink)):
                bias = self._init_scores[k]
                if it == 0 and abs(bias) > kEpsilon:
                    # AddBias (gbdt.cpp:416): fold init score into the
                    # first tree. Only the host model needs it here —
                    # the fused path never keeps device trees (DART,
                    # which does, is legacy-only).
                    tree.leaf_value += bias
                    tree.internal_value += bias
                self.models.append(tree)
            kept += 1
        self.iter_ = pending[0][0] + kept
        return stop

    _latest: Optional["weakref.ref"] = None

    @property
    def ingest_counters(self) -> dict:
        """The training Dataset's layout counters
        (``Dataset.ingest_counters``): stored columns, bundle bins used and
        offered, valid feature bins and the positions the search scans,
        ``efb.conflict_rows``."""
        return self.train_set.ingest_counters

    @classmethod
    def latest(cls) -> Optional["GBDT"]:
        """The most recently constructed trainer still alive in this
        process: how a reader that was handed no booster (a metric of the
        benchmark, a debugger) finds ``round_log``."""
        ref = GBDT._latest
        return ref() if ref is not None else None

    def _log_rounds(self, it: int, k: int, rounds) -> None:
        if rounds is not None:
            self.round_log.append(RoundRecord(
                int(it), int(k), np.asarray(rounds.rows),
                np.asarray(rounds.leaves), np.asarray(rounds.stream_rows)))

    def train_one_iter(self, gradients: Optional[np.ndarray] = None,
                       hessians: Optional[np.ndarray] = None, *,
                       defer: bool = False):
        """One boosting iteration.

        Default (eager) contract: dispatch AND materialize, returning
        True when training should stop (no splits possible).

        ``defer=True`` with the fused step active: dispatch the whole
        iteration as one jitted program and return None with ZERO host
        syncs; trees stay on device until :meth:`sync` (engine.train
        syncs on its ``eval_period`` cadence). Custom gradients and
        fallback configs run the legacy loop eagerly either way.
        """
        self._maybe_chaos_poison()
        try:
            self._maybe_chaos_devloss()
            if gradients is not None or hessians is not None \
                    or not self.fused_ok:
                if self.sync():    # drain any deferred work first
                    return True
                return self._train_one_iter_legacy(gradients, hessians)
            self._fused_dispatch()
        except jax.errors.JaxRuntimeError as e:
            # runtime failures from collectives/XLA at the dispatch
            # site are device loss, not a bug in the traced program —
            # type them so the supervisor (on_device_loss=degrade) can
            # restore + re-plan instead of dying on a raw XLA error.
            # (NumericDivergenceError is a plain RuntimeError and
            # passes through untouched.)
            from ..resilience.guards import DeviceLossError
            raise DeviceLossError(self.iter_, detail=str(e)) from e
        if defer:
            return None
        return self.sync()

    def _maybe_chaos_poison(self) -> None:
        """Fault-injection hook (scripts/chaos_train.py): when armed via
        LIGHTGBM_TPU_CHAOS_POISON_ITER, overwrite one score entry with
        NaN before the matching iteration dispatches — the NaN
        propagates through the gradients so the divergence guard must
        catch it. A marker file (LIGHTGBM_TPU_CHAOS_POISON_ONCE) makes
        the fault transient: the rollback policy's re-run then
        succeeds. Inert (two env reads) outside the harness."""
        import os
        it_s = os.environ.get("LIGHTGBM_TPU_CHAOS_POISON_ITER")
        if it_s is None or self.iter_ != int(it_s):
            return
        marker = os.environ.get("LIGHTGBM_TPU_CHAOS_POISON_ONCE")
        if marker:
            if os.path.exists(marker):
                return      # already fired once; fault was transient
            with open(marker, "w") as f:
                f.write("poisoned\n")
        poisoned = np.asarray(self.scores).copy()
        poisoned[0, 0] = np.nan
        self.scores = (self.plan.shard_scores(poisoned)
                       if self.plan is not None else jnp.asarray(poisoned))

    def _maybe_chaos_devloss(self) -> None:
        """Fault-injection hook (scripts/chaos_train.py): when armed
        via LIGHTGBM_TPU_CHAOS_DEVLOSS_ITER, raise a real
        ``jax.errors.JaxRuntimeError`` at the matching iteration —
        exercising the same classify-and-retype path a genuine XLA
        collective failure takes. LIGHTGBM_TPU_CHAOS_DEVLOSS_ONCE
        (marker file) makes the fault transient; _DEVLOSS_MODE=mesh
        fires only while a parallel plan is active, so shrink-to-serial
        recovery can be proven. Inert (one env read) outside the
        harness."""
        import os
        it_s = os.environ.get("LIGHTGBM_TPU_CHAOS_DEVLOSS_ITER")
        if it_s is None or self.iter_ != int(it_s):
            return
        if (os.environ.get("LIGHTGBM_TPU_CHAOS_DEVLOSS_MODE") == "mesh"
                and self.plan is None):
            return
        marker = os.environ.get("LIGHTGBM_TPU_CHAOS_DEVLOSS_ONCE")
        if marker:
            if os.path.exists(marker):
                return      # already fired once; fault was transient
            with open(marker, "w") as f:
                f.write("device lost\n")
        raise jax.errors.JaxRuntimeError(
            "chaos: injected device loss (collective partner gone)")

    def _train_one_iter_legacy(self,
                               gradients: Optional[np.ndarray] = None,
                               hessians: Optional[np.ndarray] = None
                               ) -> bool:
        """Per-iteration host loop (~5 dispatches + a per-tree sync);
        returns True when training should stop (no splits possible)."""
        profiler.recorder.iteration = self.iter_
        with profiler.phase("grads"):
            if gradients is None or hessians is None:
                g, h = self._grads(self.iter_)
            else:
                g, h = self._prep_custom_gh(gradients, hessians)
        with profiler.phase("sampling"):
            g, h, count_mask = self._sampling(self.iter_, g, h)
            g_true, h_true = g, h
            if self._quant:
                qg, qh, q_gs, q_hs = self._quantize_jit(
                    g, h, jax.random.fold_in(self._quant_key, self.iter_))
                count_i8 = count_mask.astype(jnp.int8)
        if self._nan_guard != "off":
            # eager form of the fused step's deferred finite flag (the
            # legacy loop syncs every iteration anyway); checked BEFORE
            # the build so a corrupt tree is never appended
            if not (bool(jnp.all(jnp.isfinite(g)))
                    and bool(jnp.all(jnp.isfinite(h)))):
                from ..resilience.guards import NumericDivergenceError
                raise NumericDivergenceError(self.iter_)

        fmask = self._feature_mask()
        linear = bool(self.config.linear_tree)
        should_continue = False
        trees_k = None
        if self.class_batch_ok:
            # hoisted class-batched build (ISSUE 8 satellite): ONE
            # dispatch grows all K trees; the per-class loop below then
            # just slices host/device views out of the stacked result —
            # both drivers share the same build path
            if self._quant:
                gh_k = self._stack_gh_k(qg, qh, count_i8)
                qsk_b = jnp.stack([q_gs, q_hs], axis=1)     # [K, 2]
            else:
                gh_k = self._stack_gh_k(g, h, count_mask)
                qsk_b = None
            with profiler.phase("build"):
                trees_k, row_leaf_k, valid_rls_k, rounds_k = \
                    self._build_one_tree_batched(gh_k, fmask,
                                                 quant_scales_k=qsk_b)
                if self._quant and bool(self.config.quant_train_renew_leaf):
                    trees_k = self._renew_batch_jit(trees_k, row_leaf_k,
                                                    g_true, h_true)
            trees_k_host, rounds_k_host = jax.tree.map(
                np.asarray, (trees_k, rounds_k))
        for k in range(self.K):
            if trees_k is not None:
                tree_arrays = jax.tree.map(lambda a: a[k], trees_k)
                host = jax.tree.map(lambda a: a[k], trees_k_host)
                rounds_host = jax.tree.map(lambda a: a[k], rounds_k_host)
                row_leaf = row_leaf_k[k]
                valid_rls = tuple(v[k] for v in valid_rls_k)
            else:
                if self._quant:
                    gh = jnp.stack([qg[k], qh[k], count_i8], axis=1)
                    qsk = {"quant_scales": jnp.stack([q_gs[k], q_hs[k]])}
                else:
                    gh = jnp.stack([g[k], h[k], count_mask], axis=1)
                    qsk = {}
                with profiler.phase("build"):
                    tree_arrays, row_leaf, valid_rls, rounds_1 = \
                        self._build_one_tree(gh, fmask, k, **qsk)
                    if self._quant and bool(
                            self.config.quant_train_renew_leaf):
                        tree_arrays = self._renew_jit(
                            tree_arrays, row_leaf, g_true[k], h_true[k])
                host, rounds_host = jax.tree.map(
                    np.asarray, (tree_arrays, rounds_1))
            self._log_rounds(self.iter_, k, rounds_host)
            num_leaves_trained = int(host.num_leaves)
            shrink = self.shrinkage
            tree = Tree.from_device(host, self.train_set.bin_mappers,
                                    self.train_set.used_features, shrink)
            if linear and num_leaves_trained > 1:
                self._fit_linear_leaves(tree, row_leaf, g_true[k],
                                        h_true[k], shrink)
            if num_leaves_trained > 1:
                should_continue = True
                with profiler.phase("update"):
                    if linear:
                        # linear outputs live on host (raw feature
                        # values); scores updated from the per-row
                        # linear deltas
                        delta = self._linear_score_delta(
                            tree, self.train_set.raw_values, row_leaf,
                            self.train_dd.r_pad)
                        self.scores = self.scores.at[k].add(
                            jnp.asarray(delta))
                        for vi, vrl in enumerate(valid_rls):
                            vds = self.valid_sets[vi]
                            vdelta = self._linear_score_delta(
                                tree, vds.raw_values, vrl,
                                self.valid_dd[vi].r_pad)
                            self.valid_scores[vi] = self.valid_scores[vi] \
                                .at[k].add(jnp.asarray(vdelta))
                    else:
                        lr = jnp.asarray(shrink, jnp.float32)
                        self.scores = self.scores.at[k].set(
                            self._update_score_jit(
                                self.scores[k], tree_arrays.leaf_values,
                                row_leaf, lr))
                        for vi, vrl in enumerate(valid_rls):
                            self.valid_scores[vi] = \
                                self.valid_scores[vi].at[k].set(
                                    self._update_score_jit(
                                        self.valid_scores[vi][k],
                                        tree_arrays.leaf_values, vrl, lr))
            bias = self._init_scores[k]
            if self.iter_ == 0 and abs(bias) > kEpsilon:
                # AddBias (gbdt.cpp:416): fold init score into first tree
                tree.leaf_value += bias
                tree.internal_value += bias
                if tree.is_linear:  # AddBias touches leaf_const too
                    tree.leaf_const += bias
                # scores already start at the init score; only the STORED
                # device tree carries the bias so later per-tree score
                # arithmetic (DART drop, rollback, refit) stays consistent
                tree_arrays = self._bias_adjust_device(tree_arrays, bias,
                                                       shrink)
            self.models.append(tree)
            if self.keep_device_trees:
                self.device_trees.append((tree_arrays, shrink))

        if not should_continue and self.iter_ > 0:
            # drop the no-op iteration, reference gbdt.cpp:441-447
            for _ in range(self.K):
                self.models.pop()
                if self.keep_device_trees:
                    self.device_trees.pop()
            return True
        self.iter_ += 1
        return False

    # ------------------------------------------------------------------
    def predict_device_tree(self, idx: int, which: int = -1) -> jax.Array:
        """[R] unshrunk per-row output of stored tree `idx` on the train
        (which=-1) or valid dataset's binned rows."""
        tree_arrays, _ = self.device_trees[idx]
        dd = self.train_dd if which < 0 else self.valid_dd[which]
        from ..ops.predict import predict_bins_value
        return predict_bins_value(tree_arrays, self.nan_bin_pf, dd.bins,
                                  bundle_meta=self._bundle_meta,
                                  num_bins_pf=self.num_bins_pf)

    # ------------------------------------------------------------------
    def rollback_one_iter(self):
        """RollbackOneIter (gbdt.cpp:454): subtract the last iteration's
        trees from every score and drop them. Replays the host trees over
        the binned matrix (threshold_bin traversal — the same decisions the
        device builder made), so repeated rollbacks work without keeping
        per-tree device state."""
        self.sync()        # deferred trees must exist before undoing one
        if self.iter_ <= 0:
            return
        if self.chunked:
            raise NotImplementedError(
                "rollback_one_iter replays trees over the resident "
                "binned matrix, which out-of-core chunked training "
                "never materializes")
        uf = self.train_set.used_features
        nan_bins = np.asarray(self.nan_bin_pf)
        bins_h = self._host_feature_bins(np.asarray(self.train_dd.bins))
        vbins_h = [self._host_feature_bins(np.asarray(dd.bins))
                   for dd in self.valid_dd]

        def row_outputs(tree, binned, raw, r_pad):
            # linear trees carry per-row outputs that the binned replay
            # cannot reproduce — replay them from raw feature values
            if tree.is_linear:
                out = np.zeros(r_pad, np.float32)
                out[:raw.shape[0]] = tree.predict(raw)
                return out
            return tree.predict_binned(binned, uf, nan_bins)

        for k in range(self.K):
            tree = self.models[-(self.K - k)]
            pred = row_outputs(tree, bins_h, self.train_set.raw_values,
                               self.train_dd.r_pad)
            self.scores = self.scores.at[k].add(
                -jnp.asarray(pred, jnp.float32))
            for vi, vb in enumerate(vbins_h):
                vpred = row_outputs(tree, vb,
                                    self.valid_sets[vi].raw_values,
                                    self.valid_dd[vi].r_pad)
                self.valid_scores[vi] = self.valid_scores[vi].at[k].add(
                    -jnp.asarray(vpred, jnp.float32))
        for _ in range(self.K):
            self.models.pop()
            if self.keep_device_trees:
                self.device_trees.pop()
        self.iter_ -= 1

    # ------------------------------------------------------------------
    # full-state checkpoint capture/restore (resilience subsystem)
    # ------------------------------------------------------------------
    def training_state(self) -> Tuple[dict, dict]:
        """Capture the complete mutable training state for a
        bit-identical-resume checkpoint: iteration counter, the two host
        RNG streams, the device score accumulators, and the cached
        bagging mask. Drains pending fused iterations first, so after
        this call ``iter_`` == materialized trees == host-RNG draws
        consumed — the invariant resume depends on. (Device PRNG keys
        are stateless ``fold_in(key, it)`` derivations, nothing to
        capture.)"""
        self.sync()
        if self.plan is not None and self.plan.multi_process:
            raise NotImplementedError(
                "full-state checkpoints are single-process only: "
                "multi-process meshes place per-host score blocks")
        if self.keep_device_trees:
            raise NotImplementedError(
                "full-state checkpoints do not capture per-tree device "
                "state (boosting=dart/goss with kept device trees); "
                "disable resume for this boosting mode")
        from ..resilience.checkpoint import _rng_state_to_json
        state = {
            "iter": int(self.iter_),
            "rng_bagging": _rng_state_to_json(
                self._rng_bagging.get_state()),
            "rng_feature": _rng_state_to_json(
                self._rng_feature.get_state()),
            "has_bag_mask": self._bag_mask is not None,
            # real-row counts: the saved score arrays are [K, r_pad]
            # with topology-dependent padding; restore onto a different
            # mesh keeps only these leading columns (elastic resume)
            "num_data": int(self.train_dd.num_data),
            "valid_num_data": [int(dd.num_data) for dd in self.valid_dd],
        }
        arrays = {"scores": np.asarray(self.scores)}
        for vi, vs in enumerate(self.valid_scores):
            arrays[f"valid_scores_{vi}"] = np.asarray(vs)
        if self._bag_mask is not None:
            arrays["bag_mask"] = np.asarray(self._bag_mask)
        return state, arrays

    def load_training_state(self, state: dict, arrays: dict,
                            trees: List[Tree]) -> None:
        """Restore a :meth:`training_state` capture into this live
        instance. Trees replace ``models`` IN PLACE so the engine's
        ``Booster._trees`` alias keeps pointing at the live list; score
        arrays are re-placed through the parallel plan's sharding.

        The capture's padded width is topology-dependent (serial pads
        to the scan block, a rows-sharded plan to ``block * shards``),
        so a checkpoint written on a different mesh arrives with the
        wrong trailing padding. Padded rows are initialized once and
        never mutated (``_update_score_impl`` gates on ``row_leaf >=
        0``; the bagging mask sets only real-row indices), so elastic
        restore is exact: keep the saved real-row columns, take the
        padding from this instance's freshly-initialized arrays.
        """
        if self.plan is not None and self.plan.multi_process:
            raise NotImplementedError(
                "full-state checkpoint restore is single-process only")
        from ..resilience.checkpoint import _rng_state_from_json
        self._pending.clear()
        self.models[:] = trees
        self.iter_ = int(state["iter"])
        self._rng_bagging.set_state(
            _rng_state_from_json(state["rng_bagging"]))
        self._rng_feature.set_state(
            _rng_state_from_json(state["rng_feature"]))
        rec_n = state.get("num_data")
        if rec_n is not None and int(rec_n) != self.train_dd.num_data:
            raise ValueError(
                f"checkpoint was written for {rec_n} training rows, "
                f"this run has {self.train_dd.num_data}: same config "
                "fingerprint but a different dataset")

        def _place_scores(a):
            return (self.plan.shard_scores(a) if self.plan is not None
                    else jnp.asarray(a))

        def _repad(saved, fresh, n):
            # fresh init already carries the correct values for every
            # padded row at THIS topology (init score broadcast); only
            # the real rows carry trained state worth restoring
            if saved.shape == fresh.shape:
                return saved
            merged = np.array(fresh, copy=True)
            merged[..., :n] = saved[..., :n]
            return merged

        n = int(self.train_dd.num_data)
        scores = _repad(arrays["scores"], np.asarray(self.scores), n)
        if scores is not arrays["scores"]:
            from .. import log as _log
            shards = (self.plan.num_shards if self.plan is not None
                      else 1)
            _log.info(
                "resume: re-sharding checkpoint state onto the current "
                f"topology (saved scores {arrays['scores'].shape} -> "
                f"{scores.shape}, {shards} shard(s))")
        self.scores = _place_scores(scores)
        self.valid_scores = [
            _place_scores(_repad(arrays[f"valid_scores_{vi}"],
                                 np.asarray(self.valid_scores[vi]),
                                 int(self.valid_dd[vi].num_data)))
            for vi in range(len(self.valid_scores))]
        if state.get("has_bag_mask") and "bag_mask" in arrays:
            m = arrays["bag_mask"]
            if m.shape[0] != scores.shape[-1]:
                # padded-row mask entries are always zero on every
                # topology (_host_bag_mask sets only real-row indices)
                m2 = np.zeros(scores.shape[-1], m.dtype)
                m2[:n] = m[:n]
                m = m2
            self._bag_mask = (self.plan.shard_rows(m)
                              if self.plan is not None
                              else jnp.asarray(m))
        else:
            self._bag_mask = None

    # ------------------------------------------------------------------
    def _host_feature_bins(self, bins_h: np.ndarray) -> np.ndarray:
        """Decode an EFB-bundled host bins matrix back to per-feature
        bins (identity when unbundled) — for host-side binned replay.
        Gated on the DEVICE layout (_bundle_meta), not the dataset's
        bundle_plan: tree_learner=feature stores the device matrix
        already unbundled and must not decode twice."""
        bp = self.train_set.bundle_plan
        if bp is None or self._bundle_meta is None:
            return bins_h
        from ..efb import decode_feature_bins
        nb = np.asarray(self.num_bins_pf)
        F = len(bp.feat_bundle)
        out = np.empty((bins_h.shape[0], F), np.int32)
        for f in range(F):
            raw = bins_h[:, bp.feat_bundle[f]].astype(np.int64)
            out[:, f] = decode_feature_bins(
                raw, int(bp.feat_offset[f]), int(nb[f]),
                int(bp.feat_mfb[f]))
        return out

    # ------------------------------------------------------------------
    def get_training_scores(self) -> np.ndarray:
        """Scores handed to custom objectives (GetTrainingScore analog,
        boosting.h; DART overrides to apply its dropout first)."""
        return self.eval_scores(-1)

    # ------------------------------------------------------------------
    def eval_scores(self, which: int = -1) -> np.ndarray:
        """Raw scores: which=-1 train, else valid index. [num_data, K].
        Multi-host: this process's rows only — per-machine metrics,
        exactly the reference's distributed-learner behavior."""
        dd = self.train_dd if which < 0 else self.valid_dd[which]
        arr = self.scores if which < 0 else self.valid_scores[which]
        self.host_sync_count += 1      # device -> host copy = one sync
        if self.plan is not None:
            return self.plan.host_local_cols(arr, dd.num_data).T
        return np.asarray(arr)[:, :dd.num_data].T

    def current_iteration(self) -> int:
        return self.iter_

    def num_trees(self) -> int:
        return len(self.models)
