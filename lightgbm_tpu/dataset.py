"""Dataset: binned feature matrix + metadata, resident in HBM.

TPU-native analog of the reference data layer (LightGBM
``include/LightGBM/dataset.h:487`` ``Dataset``, ``dataset.h:48`` ``Metadata``,
``src/io/dataset_loader.cpp`` ``DatasetLoader``).

Design differences (TPU-first):
- The reference stores per-feature-group packed columns (dense/sparse bins,
  EFB bundles) tuned for CPU cache behavior. On TPU the histogram kernel
  wants one dense row-major bin matrix in HBM (uint8 when bins <= 256)
  feeding the MXU one-hot matmul — sparse storage would force gathers.
  For high-dimensional sparse data, EFB (efb.py) packs mutually-exclusive
  features into shared columns so the matrix (and the matmul lattice)
  scales with bundles, not features.
- Rows are padded to a multiple of the histogram row-block so every shape
  under jit is static; padded rows carry ``row_leaf = -1`` and zero
  grad/hess weight so they never contribute.
- Binning runs on host NumPy over a sample (``bin_construct_sample_cnt``,
  config.h analog) exactly like DatasetLoader's two-round sampling load.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
import numpy as np
from typing import Dict, List, Optional

from . import profiler
from .binning import BinMapper
from .config import Config

__all__ = ["Dataset", "Sequence", "estimate_device_bytes",
           "check_device_capacity"]

_APPLY_BLOCK_ROWS = 1 << 18    # rows of a block of Dataset._apply_blocks


# float32 arrays of the search lattice's size the compiled round holds at
# once: the unbundled histogram, the gather it came from, the two scan
# directions' prefix sums and their gains. Set from the chip's reading at
# 13,184,290 x 79 stored columns, F 4,228, B 255, 32 slots (PERF.md section
# 4): 7.55 GB of temporaries at the peak, 4.03 GB of them the kernel's
# int32 copy of the bin matrix, so ~3.5 GB = 8.5 lattices of 414 MB
SEARCH_LATTICE_COPIES = 8


def estimate_device_bytes(num_rows: int, width: int, itemsize: int,
                          num_leaves: int, max_bin: int,
                          hist_cache: bool, n_row_shards: int = 1,
                          search_lattice: Optional[tuple] = None) -> int:
    """Per-device bytes of the training working set (capacity model,
    VERDICT r4 #5). Device storage is the DENSE bundled bin matrix
    sharded over data-parallel rows — the reference instead has
    per-feature sparse storage (src/io/sparse_bin.hpp:1,
    multi_val_sparse_bin.hpp:1) so its footprint scales with non-zeros.
    Dominant terms per chip:
      bins [R/shards, width] itemsize   (the matrix itself)
      gh/scores/row_leaf ~ 4 x [R/shards] f32
      hist cache [(L+1), width*B', 3] f32 when hist_subtraction is on
      the split search's lattice of a round, ``search_lattice`` =
      (slots, features, bins): [slots, F, B, 3] f32 in FEATURE space
      however the matrix is stored (a bundled matrix is unbundled into
      it every round), times SEARCH_LATTICE_COPIES for its temporaries
    """
    r_local = -(-num_rows // max(1, n_row_shards))
    bins_b = r_local * width * itemsize
    per_row = 4 * 4 * r_local                    # gh(3) + scores/row_leaf
    cache_b = ((num_leaves + 1) * width * max_bin * 3 * 4
               if hist_cache else 0)
    search_b = 0
    if search_lattice is not None:
        slots, feats, fbins = search_lattice
        search_b = SEARCH_LATTICE_COPIES * slots * feats * fbins * 3 * 4
    return int(bins_b + per_row + cache_b + search_b)


def check_device_capacity(num_rows: int, width: int, itemsize: int,
                          num_leaves: int, max_bin: int,
                          hist_cache: bool, n_row_shards: int = 1,
                          headroom: float = 0.85,
                          search_lattice: Optional[tuple] = None) -> None:
    """Raise MemoryError with sized guidance when the dense working set
    cannot fit a device (instead of an opaque device OOM mid-training).

    The budget comes from the backend's per-device memory when the
    runtime reports one (TPU HBM), else from
    ``LIGHTGBM_TPU_DEVICE_MEM_GB`` (also the test hook); with neither,
    the check is skipped (CPU hosts page).
    """
    budget = None
    env = os.environ.get("LIGHTGBM_TPU_DEVICE_MEM_GB")
    if env:
        budget = float(env) * (1 << 30)
    else:
        try:
            import jax
            stats = jax.devices()[0].memory_stats()
            if stats and stats.get("bytes_limit"):
                budget = float(stats["bytes_limit"])
        except Exception:
            budget = None
    if not budget:
        return
    need = estimate_device_bytes(num_rows, width, itemsize, num_leaves,
                                 max_bin, hist_cache, n_row_shards,
                                 search_lattice)
    if need <= budget * headroom:
        return
    gib = 1 << 30
    search = ""
    if search_lattice is not None:
        slots, feats, fbins = search_lattice
        lattice = slots * feats * fbins * 3 * 4
        search = (
            f" Of that, {SEARCH_LATTICE_COPIES * lattice / gib:.1f} GiB is "
            f"the split search: a lattice of {slots} slots x {feats:,} "
            f"features x {fbins} bins x 3 sums in float32 "
            f"({lattice / gib:.2f} GiB) and its temporaries, in FEATURE "
            "space whatever the stored columns are (a smaller leaf_batch "
            "or max_bin shrinks it).")
    raise MemoryError(
        f"training working set ~{need / gib:.1f} GiB per device exceeds "
        f"{budget * headroom / gib:.1f} GiB available "
        f"({num_rows:,} rows x {width:,} stored columns x {itemsize} B "
        f"over {n_row_shards} row shard(s)).{search} Device storage is the "
        "DENSE bundled bin matrix — wide sparse data fits only when its "
        "columns are mutually exclusive enough to bundle (EFB). "
        "Options: enable_bundle=true with a larger max_conflict_rate; "
        "max_bin<=255 keeps columns uint8; shard rows over more "
        "devices/hosts (tree_learner=data); shard COLUMNS over devices "
        "(tree_learner=feature with feature_shard_storage=true — each "
        "chip then stores only width/devices columns); or reduce "
        "features up-front. The reference's sparse_bin.hpp per-feature "
        "sparse storage maps to the column-sharded mode here (README "
        "'Sparse data').")


class Sequence:
    """Generic batched-row data access (basic.py:915 Sequence analog).

    Subclass and implement ``__getitem__`` (int -> 1-D row, slice -> 2-D
    batch) and ``__len__``. Dataset streams rows through it in
    ``batch_size`` chunks — the raw matrix never materializes, the analog
    of the reference's two-round loading + LGBM_DatasetPushRows
    streaming ingestion (c_api).
    """

    batch_size = 4096

    def __getitem__(self, idx):
        raise NotImplementedError("Sequence must implement __getitem__")

    def __len__(self):
        raise NotImplementedError("Sequence must implement __len__")


def _is_sequence_input(data) -> bool:
    if isinstance(data, Sequence):
        return True
    return (isinstance(data, list) and len(data) > 0
            and all(isinstance(s, Sequence) for s in data))


def _is_sparse(data) -> bool:
    return hasattr(data, "tocsc") and hasattr(data, "tocsr")


def _is_arrow(data) -> bool:
    return hasattr(data, "column_names") and hasattr(data, "num_rows")


def _is_pandas_df(data) -> bool:
    return (hasattr(data, "dtypes") and hasattr(data, "columns")
            and hasattr(data, "values") and not _is_arrow(data))


def _data_from_pandas(df, align_categories=None):
    """DataFrame -> (f64 matrix, category column indices, category
    lists). The reference's ``_data_from_pandas``
    (python-package/lightgbm/basic.py): ``category``-dtype columns map
    to their codes (missing -> NaN), every other column must be
    int/float/bool, and at valid/predict time the codes are ALIGNED to
    the training category lists (``align_categories``)."""
    import pandas as pd

    def _is_cat(dt):
        return isinstance(dt, pd.CategoricalDtype) or str(dt) == "category"

    cat_idx = [i for i, dt in enumerate(df.dtypes) if _is_cat(dt)]
    bad = [str(c) for c, dt in zip(df.columns, df.dtypes)
           if not _is_cat(dt) and getattr(dt, "kind", "O") not in "iufb"]
    if bad:
        raise ValueError(
            "DataFrame.dtypes for data must be int, float or bool.\n"
            "Did not expect the data types in the following fields: "
            + ", ".join(bad))
    if align_categories is not None and len(align_categories) != len(
            cat_idx):
        raise ValueError(
            "train and valid dataset categorical_feature do not match.")
    out = np.empty(df.shape, np.float64)
    cats_out = []
    cat_set = set(cat_idx)
    j = 0
    for i, col in enumerate(df.columns):
        s = df[col]
        if i in cat_set:
            if align_categories is not None:
                s = s.cat.set_categories(align_categories[j])
            cats_out.append(list(s.cat.categories))
            codes = np.asarray(s.cat.codes, np.float64)
            codes[codes < 0] = np.nan
            out[:, i] = codes
            j += 1
        else:
            out[:, i] = np.asarray(s, np.float64)
    return out, cat_idx, cats_out


def _to_2d_float(data) -> np.ndarray:
    if _is_arrow(data):
        # pyarrow Table (arrow.h ArrowChunkedArray ingestion analog):
        # column-wise conversion; chunked arrays concatenate
        cols = [np.asarray(data.column(i).to_numpy(zero_copy_only=False),
                           dtype=np.float64)
                for i in range(data.num_columns)]
        return np.ascontiguousarray(np.column_stack(cols))
    if hasattr(data, "values") and hasattr(data, "columns"):  # DataFrame
        arr = data.values
    else:
        arr = np.asarray(data)
    if arr.ndim == 1:
        arr = arr[:, None]
    return np.ascontiguousarray(arr, dtype=np.float64)


class _Columns:
    """The columns of a block of rows, dense ``[n, F]`` or scipy CSC,
    behind one reading. A sparse column is read as its stored values and
    the COUNT of its implied zeros, so fitting, planning and encoding
    cost the stored values and no ``[n, F]`` array is ever made (what the
    reference's ``BinMapper::FindBin`` takes: the non-zero sample values
    and ``total_sample_cnt``)."""

    def __init__(self, data):
        self.sparse = _is_sparse(data)
        if self.sparse:
            # duplicate entries add up, as a dense conversion adds them
            # (in a copy where the matrix is the caller's own)
            if data.format != "csc":
                data = data.tocsc()
            elif not data.has_canonical_format:
                data = data.copy()
            data.sum_duplicates()
        self.data = data
        self.num_rows = data.shape[0]

    def stored(self, f: int):
        """(rows, float64 values): ``rows`` ascending row numbers of the
        stored values, or None where the column is dense."""
        if not self.sparse:
            return None, self.data[:, f]
        lo, hi = self.data.indptr[f], self.data.indptr[f + 1]
        return (self.data.indices[lo:hi],
                self.data.data[lo:hi].astype(np.float64))

    def summary(self, f: int):
        """(sorted distinct non-NaN values, counts, NaN count) of column
        ``f``, implied zeros counted: ``BinMapper.from_distinct``'s
        arguments."""
        rows, v = self.stored(f)
        v = np.asarray(v, np.float64)
        nan = np.isnan(v)
        dv, cnts = np.unique(v[~nan], return_counts=True)
        zeros = 0 if rows is None else self.num_rows - len(v)
        if zeros:
            i = int(np.searchsorted(dv, 0.0))
            if i < len(dv) and dv[i] == 0.0:
                cnts[i] += zeros
            else:
                dv, cnts = np.insert(dv, i, 0.0), np.insert(cnts, i, zeros)
        return dv, cnts, int(nan.sum())

    def binned(self, f: int, mapper: BinMapper):
        """(rows, bins at those rows, the bin every other row holds: that
        of 0.0, which need not be the column's most frequent)."""
        rows, v = self.stored(f)
        zero_bin = (0 if rows is None
                    else int(mapper.values_to_bins(np.zeros(1))[0]))
        return rows, mapper.values_to_bins(v), zero_bin


class Dataset:
    """Binned training data.

    Mirrors the construction flow of DatasetLoader::ConstructFromSampleData
    (dataset_loader.cpp:593): sample rows -> fit BinMappers -> map all rows.
    """

    def __init__(self, data, label=None, weight=None, group=None,
                 init_score=None, feature_name="auto",
                 categorical_feature="auto", params: Optional[Dict] = None,
                 reference: Optional["Dataset"] = None,
                 free_raw_data: bool = True, position=None):
        self.params = dict(params or {})
        self.config = Config(self.params)
        self._raw_data = data
        self.label = None if label is None else np.asarray(
            label, dtype=np.float64).reshape(-1)
        self.weight = None if weight is None else np.asarray(
            weight, dtype=np.float64).reshape(-1)
        self.group = None if group is None else np.asarray(
            group, dtype=np.int64).reshape(-1)
        self.init_score = None if init_score is None else np.asarray(
            init_score, dtype=np.float64)
        # per-row result positions for unbiased lambdarank
        # (Metadata::positions, src/io/metadata.cpp; ids or names)
        self.position = (None if position is None
                         else np.asarray(position).reshape(-1))
        self.feature_name = feature_name
        self.categorical_feature = categorical_feature
        self.reference = reference
        self.free_raw_data = free_raw_data

        self.bin_mappers: List[BinMapper] = []
        self.pandas_categorical = None   # per-cat-column category lists
        self.raw_values: Optional[np.ndarray] = None  # kept for linear_tree
        self.bundle_plan = None                     # EFB layout (efb.py)
        self.efb_conflict_rows: Optional[int] = None  # rows of the encode
        # that lost a value to a later member of their bundle
        self.bins = None                            # [num_data, F|G] int
        self.chunk_source = None   # shard-backed row stream (data/)
        self.num_data: int = 0
        # True once the multi-host loader kept only this process's row
        # block (learners that need FULL rows per worker check this)
        self.auto_partitioned = False
        self.num_total_features: int = 0
        self.used_features: Optional[np.ndarray] = None  # indices of
        # non-trivial features actually trained on
        self._constructed = False

    # ------------------------------------------------------------------
    @property
    def bins(self) -> Optional[np.ndarray]:
        """[num_data, F|G] binned matrix. Shard-backed datasets keep it
        on disk (``chunk_source``) and materialize HERE, lazily, only
        when a resident consumer (save_binary, subset, a non-chunked
        trainer fallback) actually reads it — the chunked trainer never
        does."""
        if self._bins is None and self.chunk_source is not None:
            src = self.chunk_source
            step = 1 << 16
            self._bins = np.concatenate(
                [np.asarray(src.read_rows(lo, min(lo + step,
                                                  src.num_rows)))
                 for lo in range(0, src.num_rows, step)])
        return self._bins

    @bins.setter
    def bins(self, value) -> None:
        self._bins = value

    # ------------------------------------------------------------------
    def construct(self) -> "Dataset":
        if self._constructed:
            return self
        # params may have been merged from the Booster since __init__
        # (reference _update_params flow, basic.py) — refresh the config
        self.config = Config(self.params)
        if self.reference is not None:
            # a valid set needs its train set's bin mappers (and, for
            # LibSVM, its width) before anything else happens
            self.reference.construct()
        if _is_sequence_input(self._raw_data):
            return self._construct_from_sequences()
        file_names: Optional[List[str]] = None
        from_file = isinstance(self._raw_data, (str, os.PathLike))
        if from_file:
            from .data.shardfile import is_shard_path
            if is_shard_path(self._raw_data):
                # pre-binned .lgbtpu shard dataset (`python -m
                # lightgbm_tpu ingest` output): metadata restores from
                # the shard headers, rows stream from the mmaps
                return self._construct_from_shards(self._raw_data)
        if from_file and self._is_binary_file(self._raw_data):
            # binary dataset cache (LoadFromBinFile analog): restores
            # the constructed state directly, no parsing or re-binning
            self._load_binary(self._raw_data)
            sl = self._auto_partition_slice(self.bins.shape[0])
            if sl is not None:
                self.bins = self.bins[sl]
                self.num_data = len(sl)
                self._apply_partition(sl)
            if self.label is None and not self.params.get("_allow_no_label"):
                raise ValueError("Dataset has no label")
            return self
        if from_file:
            # text-file path: CSV/TSV/LibSVM autodetect + sidecars
            # (DatasetLoader::LoadFromFile, dataset_loader.cpp:203)
            from .io import load_data_file
            hint = (self.reference.num_total_features
                    if self.reference is not None else 0)
            loaded = load_data_file(self._raw_data, self.config,
                                    num_features_hint=hint)
            self._raw_data = loaded.X
            file_names = loaded.feature_names
            if self.label is None and loaded.label is not None:
                self.label = loaded.label
            if self.weight is None and loaded.weight is not None:
                self.weight = loaded.weight
            if self.group is None and loaded.group is not None:
                self.group = loaded.group
            if self.init_score is None and loaded.init_score is not None:
                self.init_score = loaded.init_score
            if self.position is None and loaded.position is not None:
                self.position = loaded.position
        sparse = _is_sparse(self._raw_data)
        pd_cat_idx = None
        if sparse:
            # scipy CSR/CSC input: rows are sampled from CSR, everything
            # else reads CSC columns as stored values + a count of implied
            # zeros (_Columns) — no dense [R, F] or [sample, F] array is
            # ever made (SparseBin/CSR ingestion analog)
            data = self._raw_data.tocsr()
        elif _is_pandas_df(self._raw_data):
            # a valid set aligns to its train set's category lists; a
            # train set trained WITHOUT pandas gets [] so a categorical
            # frame against it raises the reference's mismatch error
            ref_cats = None
            if self.reference is not None:
                ref_cats = self.reference.pandas_categorical
                if ref_cats is None:
                    ref_cats = []
            data, pd_cat_idx, cats = _data_from_pandas(
                self._raw_data, ref_cats)
            self.pandas_categorical = cats
        else:
            data = _to_2d_float(self._raw_data)
        if (self.reference is not None
                and data.shape[1] != self.reference.num_total_features):
            if from_file and data.shape[1] < \
                    self.reference.num_total_features:
                # LibSVM valid file whose max feature index is below the
                # train set's: right-pad with zeros to align (CreateValid
                # semantics — absent sparse entries are zero)
                pad = self.reference.num_total_features - data.shape[1]
                data = np.concatenate(
                    [data, np.zeros((data.shape[0], pad))], axis=1)
            else:
                raise ValueError(
                    f"validation data has {data.shape[1]} features but "
                    f"training data has "
                    f"{self.reference.num_total_features}")
        sl = self._auto_partition_slice(data.shape[0])
        if sl is not None:
            data = data[sl]
            self._apply_partition(sl)
        self.num_data, self.num_total_features = data.shape
        cfg = self.config

        if isinstance(self.feature_name, (list, tuple)) and self.feature_name:
            names = list(self.feature_name)
        elif _is_arrow(self._raw_data):
            names = [str(c) for c in self._raw_data.column_names]
        elif hasattr(self._raw_data, "columns"):
            names = [str(c) for c in self._raw_data.columns]
        elif file_names and len(file_names) == self.num_total_features:
            names = file_names
        else:
            names = [f"Column_{i}" for i in range(self.num_total_features)]
        self.feature_name = names

        cat_idx = self._resolve_categoricals(names)
        if pd_cat_idx and self.categorical_feature in ("auto", None):
            # categorical_feature='auto': pandas category dtypes become
            # categorical features (basic.py _data_from_pandas)
            cat_idx = cat_idx | set(pd_cat_idx)

        if self.reference is not None:
            # validation set: reuse the training bin mappers
            # (dataset.h CreateValid / align-with-train semantics)
            ref = self.reference.construct()
            self.bin_mappers = ref.bin_mappers
            self.used_features = ref.used_features
            self.max_num_bin = ref.max_num_bin
        else:
            sample_cnt = min(cfg.bin_construct_sample_cnt, self.num_data)
            if sample_cnt < self.num_data:
                rng = np.random.RandomState(cfg.data_random_seed)
                sample_idx = rng.choice(self.num_data, sample_cnt,
                                        replace=False)
                sample = data[sample_idx]
            else:
                sample = data
            with profiler.span("dataset.fit_bins") as fields:
                sample = _Columns(sample)
                self._fit_mappers(sample, cat_idx, cfg)
                fields.update(sample_rows=sample.num_rows,
                              features_used=len(self.used_features))

        F = len(self.used_features)
        # -- EFB: pack mutually-exclusive sparse features (efb.py) ----
        if self.reference is not None:
            self.bundle_plan = self.reference.bundle_plan
        elif self._multi_process():
            # pre-partitioned multi-host: a bundle plan built from the
            # LOCAL sample would differ across hosts (different conflict
            # counts -> different column layouts); skip EFB until the
            # plan itself is synced like the mappers are
            self.bundle_plan = None
        elif cfg.enable_bundle and F > 4:
            with profiler.span("dataset.plan_bundles") as fields:
                self.bundle_plan = self._plan_bundles(sample, cfg)
                fields.update(self._plan_counters())
        else:
            self.bundle_plan = None

        # binning every row with the fitted mappers (and EFB packing)
        with profiler.span("dataset.apply_bins") as fields:
            if sparse:
                fields["stored_values"] = int(data.nnz)
            bp = self.bundle_plan
            if bp is not None:
                dtype = np.uint8 if bp.max_bundle_bins <= 256 else np.int32
                with profiler.span("dataset.encode_bundles") as enc:
                    self.bins = np.zeros((self.num_data, bp.num_bundles),
                                         dtype)
                    self.efb_conflict_rows = enc["conflict_rows"] = \
                        self._apply_blocks(data)
            else:
                dtype = np.uint8 if self.max_num_bin <= 256 else np.int32
                fast = None
                if not sparse:
                    # accelerator fast path: one jitted searchsorted over the
                    # whole [R, F] matrix (ops/binning_device.py)
                    from .ops.binning_device import (device_bin_dense,
                                                     want_device_binning)
                    if want_device_binning(self.num_data, F):
                        fast = device_bin_dense(
                            data, self.bin_mappers, self.used_features, dtype)
                if fast is not None:
                    self.bins = fast
                else:
                    self.bins = np.empty((self.num_data, F), dtype=dtype)
                    self._apply_blocks(data)

        if self.label is None and not self.params.get("_allow_no_label"):
            raise ValueError("Dataset has no label")
        # linear trees regress on raw feature values; keep them resident
        # (the reference keeps raw data when linear_tree, dataset.cpp)
        self.raw_values = None
        ref_cfg = (self.reference.config if self.reference is not None
                   else None)
        if self.config.linear_tree or (
                ref_cfg is not None and ref_cfg.linear_tree):
            if sparse:
                raise ValueError(
                    "linear_tree needs dense raw feature values; sparse "
                    "input is not supported with linear trees")
            self.raw_values = np.ascontiguousarray(data, np.float32)
        if self.free_raw_data:
            self._raw_data = None
        self._constructed = True
        return self

    def _construct_from_shards(self, path) -> "Dataset":
        """Construct from a ``.lgbtpu`` shard directory: every shard is
        validated (checksum + set completeness), BinMappers restore from
        the shard headers, and the binned rows stay mmap-backed behind
        ``chunk_source`` for the chunked trainer."""
        from .data.chunked import ShardSource
        from .data.shardfile import open_shard_dir
        if self._multi_process():
            raise NotImplementedError(
                "shard datasets load single-host (the chunked trainer "
                "is serial; pre-partition shards per host instead)")
        readers, h0 = open_shard_dir(str(path))
        self.bin_mappers = readers[0].mappers()
        self.num_total_features = int(h0["num_total_features"])
        self.used_features = np.asarray(h0["used_features"], np.int64)
        self.max_num_bin = int(h0["max_num_bin"])
        if not (isinstance(self.feature_name, (list, tuple))
                and self.feature_name):
            self.feature_name = list(h0["feature_names"])
        self.num_data = int(h0["total_rows"])
        if self.label is None and h0.get("has_label"):
            self.label = np.concatenate(
                [np.asarray(r.label, np.float64) for r in readers])
        if self.weight is None and h0.get("has_weight"):
            self.weight = np.concatenate(
                [np.asarray(r.weight, np.float64) for r in readers])
        self.bundle_plan = None   # shards store unbundled feature space
        self.chunk_source = ShardSource(readers)
        if self.label is None and not self.params.get("_allow_no_label"):
            raise ValueError("Dataset has no label")
        if self.config.linear_tree:
            raise ValueError(
                "linear_tree needs dense raw feature values; shard "
                "datasets carry only binned rows")
        self.raw_values = None
        if self.free_raw_data:
            self._raw_data = None
        self._constructed = True
        return self

    def _construct_from_sequences(self) -> "Dataset":
        """Two-round streaming load from Sequence objects: a sampled
        pass fits BinMappers, then blocks stream through the shared
        chunked reader (:class:`lightgbm_tpu.data.reader.
        SequenceChunkReader`) and are binned row-block by row-block —
        the full raw matrix never exists in memory (basic.py
        _init_from_sample + _push_rows flow)."""
        cfg = self.config
        if self._multi_process() and not bool(cfg.pre_partition):
            raise NotImplementedError(
                "multi-host Sequence ingestion requires pre-partitioned "
                "sequences per host (pre_partition=true)")
        from .data.reader import DEFAULT_CHUNK_ROWS, SequenceChunkReader
        reader = SequenceChunkReader(self._raw_data)
        self.num_data = int(reader.num_rows)
        self.num_total_features = int(reader.num_features)
        if self.reference is not None:
            ref = self.reference
            if self.num_total_features != ref.num_total_features:
                raise ValueError(
                    f"validation data has {self.num_total_features} "
                    f"features but training data has "
                    f"{ref.num_total_features}")
            self.bin_mappers = ref.bin_mappers
            self.used_features = ref.used_features
            self.max_num_bin = ref.max_num_bin
            self.bundle_plan = ref.bundle_plan
            names = list(ref.feature_name)
        else:
            names = [f"Column_{i}" for i in range(self.num_total_features)]
        self.feature_name = names
        cat_idx = self._resolve_categoricals(names)

        if self.reference is None:
            sample_cnt = min(cfg.bin_construct_sample_cnt, self.num_data)
            rng = np.random.RandomState(cfg.data_random_seed)
            sample_idx = np.sort(rng.choice(self.num_data, sample_cnt,
                                            replace=False))
            sample = reader.read_rows_at(sample_idx)
            self._fit_mappers(_Columns(sample), cat_idx, cfg)
            self.bundle_plan = None  # streaming path stays unbundled

        F = len(self.used_features)
        if self.bundle_plan is not None:
            # valid set against an EFB-bundled train set: encode into
            # the same bundle layout so the trainer's decode matches
            from .efb import encode_rows
            dtype = (np.uint8 if self.bundle_plan.max_bundle_bins <= 256
                     else np.int32)
            self.bins = np.zeros(
                (self.num_data, self.bundle_plan.num_bundles), dtype)
        else:
            dtype = np.uint8 if self.max_num_bin <= 256 else np.int32
            self.bins = np.empty((self.num_data, F), dtype=dtype)
        row0 = 0
        for chunk in reader.iter_chunks(DEFAULT_CHUNK_ROWS):
            batch = chunk.X
            r = batch.shape[0]
            batch_bins = np.empty((r, F), np.int64)
            for j, f in enumerate(self.used_features):
                batch_bins[:, j] = self.bin_mappers[f].values_to_bins(
                    batch[:, f])
            if self.bundle_plan is not None:
                from .efb import encode_rows
                encode_rows(self.bundle_plan, batch_bins, self.bins,
                            row0)
            else:
                self.bins[row0:row0 + r] = batch_bins.astype(dtype)
            row0 += r
        assert row0 == self.num_data

        if self.label is None and not self.params.get("_allow_no_label"):
            raise ValueError("Dataset has no label")
        if self.config.linear_tree:
            raise ValueError(
                "linear_tree needs dense raw feature values; Sequence "
                "streaming input is not supported with linear trees")
        self.raw_values = None
        if self.free_raw_data:
            self._raw_data = None
        self._constructed = True
        return self

    def _fit_mappers(self, sample: "_Columns", cat_idx: set, cfg) -> None:
        """Fit per-feature BinMappers from a row sample's columns
        (ConstructBinMappersFromTextData / ConstructFromSampleData
        analog), honoring max_bin_by_feature and forcedbins_filename
        (dataset_loader.cpp:619-653)."""
        mbf = list(cfg.max_bin_by_feature or [])
        if mbf and len(mbf) != self.num_total_features:
            raise ValueError(
                f"max_bin_by_feature has {len(mbf)} entries but the "
                f"dataset has {self.num_total_features} features")
        forced: Dict[int, list] = {}
        if cfg.forcedbins_filename:
            import json as _json
            with open(cfg.forcedbins_filename) as fh:
                for item in _json.load(fh):
                    forced[int(item["feature"])] = [
                        float(x) for x in item["bin_upper_bound"]]
        self.bin_mappers = []
        # pre-partitioned multi-host: each process fits only its OWNED
        # feature block (the reference fits len/num_machines features per
        # machine, dataset_loader.cpp:1070); sync_bin_mappers fills the
        # rest from the other hosts' blocks
        owned = None
        if self._sync_mappers_needed:
            import jax
            from .parallel.distributed import feature_blocks
            blocks = feature_blocks(self.num_total_features,
                                    jax.process_count())
            owned = set(int(f) for f in blocks[jax.process_index()])
        for f in range(self.num_total_features):
            if owned is not None and f not in owned:
                self.bin_mappers.append(BinMapper())  # filled by sync
                continue
            bt = "categorical" if f in cat_idx else "numerical"
            m = BinMapper.from_distinct(
                *sample.summary(f),
                max_bin=int(mbf[f]) if mbf else cfg.max_bin,
                min_data_in_bin=cfg.min_data_in_bin, bin_type=bt,
                use_missing=cfg.use_missing,
                zero_as_missing=cfg.zero_as_missing,
                forced_bounds=forced.get(f))
            self.bin_mappers.append(m)
        if self._sync_mappers_needed:
            # pre-partitioned multi-host loading: every process holds a
            # DIFFERENT row shard, so mappers fitted from local samples
            # would disagree; merge the per-process feature blocks
            # (ConstructBinMappersFromTextData's Allgather,
            # dataset_loader.cpp:1070).
            from .parallel.distributed import sync_bin_mappers
            self.bin_mappers = sync_bin_mappers(self.bin_mappers)
        self.used_features = np.asarray(
            [f for f, m in enumerate(self.bin_mappers)
             if not m.is_trivial], dtype=np.int32)
        if len(self.used_features) == 0:
            raise ValueError("Cannot construct Dataset: all features are "
                             "trivial (single value)")
        self.max_num_bin = max(
            self.bin_mappers[f].num_bin for f in self.used_features)

    def _apply_blocks(self, data) -> int:
        """Fill ``self.bins`` from ``data`` (dense rows or CSR), a block of
        ``_APPLY_BLOCK_ROWS`` rows at a time on a few threads. A dense
        block goes column by column through ``encode_bundles`` (or one
        column a feature); a CSR block through :meth:`_apply_sparse_block`,
        which costs its stored values. Returns the rows that lost a value
        to a later member of their bundle."""
        from .efb import encode_bundles
        bp = self.bundle_plan
        tables = self._sparse_tables() if _is_sparse(data) else None

        def one(lo) -> int:
            view = self.bins[lo:lo + _APPLY_BLOCK_ROWS]
            block = data[lo:lo + _APPLY_BLOCK_ROWS]
            if tables is not None:
                return self._apply_sparse_block(block.tocsc(), view, tables)
            cols = ((j, self.bin_mappers[f].values_to_bins(block[:, f]))
                    for j, f in enumerate(self.used_features))
            if bp is None:
                for j, col in cols:
                    view[:, j] = col
                return 0
            mine: dict = {}
            encode_bundles(bp, cols, len(view), counters=mine, out=view)
            return mine["conflict_rows"]
        starts = range(0, self.num_data, _APPLY_BLOCK_ROWS)
        with ThreadPoolExecutor(max(1, min(8, os.cpu_count() or 1,
                                           len(starts)))) as ex:
            return sum(ex.map(one, starts))

    def _sparse_tables(self) -> dict:
        """By raw feature, what :meth:`_apply_sparse_block` needs of a
        stored value's column: whether it is used, its bin bounds (None:
        categorical), the bin of NaN and of an implied zero, and where the
        feature's bins go in ``self.bins``: stored column, offset (0 = the
        column holds raw bins) and ``default`` = offset + most frequent
        bin, the value a member of a bundle does not write (-1 where the
        column holds raw bins: every value is written)."""
        n, uf, bp = self.num_total_features, self.used_features, self.bundle_plan
        ms = [self.bin_mappers[f] for f in uf]
        t = {k: np.zeros(n, np.int32)
             for k in ("column", "offset", "zero_bin", "nan_bin")}
        t["used"] = np.zeros(n, bool)
        t["used"][uf] = True
        t["column"][uf] = np.arange(len(uf)) if bp is None else bp.feat_bundle
        t["default"] = np.full(n, -1, np.int32)
        if bp is not None:
            t["offset"][uf] = bp.feat_offset
            t["default"][uf] = np.where(bp.feat_offset == 0, -1,
                                        bp.feat_offset + bp.feat_mfb)
        t["zero_bin"][uf] = [int(m.values_to_bins(np.zeros(1))[0])
                             for m in ms]
        t["nan_bin"][uf] = [m.num_bin - 1 if m.nan_bin >= 0
                            else m.default_bin for m in ms]
        t["bounds"] = [None] * n
        for f, m in zip(uf, ms):
            if m.bin_type != "categorical":
                t["bounds"][f] = m.bin_upper_bound
        return t

    def _apply_sparse_block(self, csc, view: np.ndarray, t: dict) -> int:
        """Write a block's rows (``csc``: its CSC form) into ``view``, its
        rows of ``self.bins`` (zeroed), in O(stored values): a column's
        stored values are binned by one search, a column that holds raw
        bins is filled with the bin of 0.0 first (which need not be its
        most frequent bin), and a member of a bundle writes ``offset +
        bin`` where it is not at its most frequent bin. Where two members
        of a bundle meet in a row the higher feature wins; returns the
        number of rows that lost a value so."""
        if not csc.has_canonical_format:
            csc.sum_duplicates()        # as a dense conversion adds them
        n, width = view.shape
        ptr, rows = csc.indptr, csc.indices
        counts = np.diff(ptr)
        v = csc.data.astype(np.float64)
        nan = np.isnan(v)
        if nan.any():       # ValueToBin: searched as 0.0, then the NaN bin
            v[nan] = 0.0
        value = np.zeros(len(v), np.int32)      # offset + bin
        for f in np.flatnonzero(t["used"] & (counts > 0)):
            seg, ub = slice(ptr[f], ptr[f + 1]), t["bounds"][f]
            value[seg] = (ub.searchsorted(v[seg]) if ub is not None else
                          self.bin_mappers[f].values_to_bins(csc.data[seg]))
        if nan.any():
            value[nan] = np.repeat(t["nan_bin"], counts)[nan]
        value += np.repeat(t["offset"], counts)
        col = np.repeat(t["column"], counts)
        default = np.repeat(t["default"], counts)
        raw = t["default"] < 0
        filled = raw & t["used"]
        view[:, t["column"][filled]] = t["zero_bin"][filled][None, :]
        # a member whose implied zeros are not its most frequent bin is
        # non-default in every row that stores nothing: name those rows,
        # and keep the arrays in feature order (a stable sort)
        feat = None
        for f in np.flatnonzero(t["used"] & ~raw & (
                t["offset"] + t["zero_bin"] != t["default"])):
            rest = np.setdiff1d(np.arange(n, dtype=rows.dtype),
                                rows[ptr[f]:ptr[f + 1]], assume_unique=True)
            if feat is None:
                feat = np.repeat(np.arange(len(counts)), counts)
            feat = np.concatenate([feat, np.full(len(rest), f)])
            rows = np.concatenate([rows, rest])
            value, col, default = (
                np.concatenate([a, np.full(len(rest), x, a.dtype)])
                for a, x in ((value, t["offset"][f] + t["zero_bin"][f]),
                             (col, t["column"][f]),
                             (default, t["default"][f])))
        keep = value != default
        if not t["used"].all():
            keep &= (np.repeat(t["used"], counts) if feat is None
                     else t["used"][feat])
        if feat is not None:
            order = np.argsort(feat, kind="stable")
            rows, value, col, default, keep = (
                a[order] for a in (rows, value, col, default, keep))
        if not keep.all():
            rows, value, col, default = (
                a[keep] for a in (rows, value, col, default))
        wide = np.int64 if n * width >= 2 ** 31 else np.int32
        cell = rows.astype(wide) * wide(width) + col
        value = value.astype(view.dtype)
        flat = view.reshape(-1)
        flat[cell] = value
        shared = np.flatnonzero(np.bincount(
            t["column"][t["used"] & ~raw], minlength=width))
        if np.count_nonzero(view[:, shared]) == np.count_nonzero(default >= 0):
            return 0
        # some cell was written twice: the cell's last writer (features
        # ascend along the arrays) is the one that stays
        _, first = np.unique(cell[::-1], return_index=True)
        last = len(cell) - 1 - first
        flat[cell[last]] = value[last]
        lost = np.ones(len(cell), bool)
        lost[last] = False
        return len(np.unique(rows[lost]))

    def _plan_bundles(self, sample: "_Columns", cfg):
        """The EFB plan (efb.py) from the sample's columns: each used
        feature's set of non-default sample rows as bits, from its stored
        values; kept only where it genuinely shrinks the matrix."""
        from .efb import pack_nondefault, plan_from_masks
        mappers = [self.bin_mappers[f] for f in self.used_features]
        masks = [pack_nondefault(sample.num_rows, *sample.binned(f, m),
                                 m.most_freq_bin)
                 for f, m in zip(self.used_features, mappers)]
        plan = plan_from_masks(
            [m for m, _ in masks], [c for _, c in masks], sample.num_rows,
            [m.num_bin for m in mappers], [m.most_freq_bin for m in mappers],
            max_conflict_rate=cfg.max_conflict_rate,
            max_bundle_bins=cfg.max_bundle_bins)
        return plan if plan.num_bundles <= int(0.75 * len(mappers)) else None

    def _plan_counters(self) -> dict:
        """What the stored layout holds and what the split search scans:
        fields of the ``dataset.plan_bundles`` span, and
        ``ingest_counters``."""
        nb = self.per_feature_num_bins()
        out = {"features_used": len(nb),
               "valid_feature_bins": int(nb.sum()),
               "scanned_positions": int(len(nb) * self.max_num_bin)}
        bp = self.bundle_plan
        if bp is not None:
            out.update(stored_columns=int(bp.num_bundles),
                       bundle_bins_used=int(bp.bundle_num_bins.sum()),
                       bundle_bins_offered=int(bp.num_bundles
                                               * bp.max_bundle_bins),
                       sample_conflicts=int(bp.sample_conflicts))
        return out

    @property
    def ingest_counters(self) -> dict:
        """:meth:`_plan_counters` and, of a bundled Dataset, the rows of
        the encode that lost a value to a later member of their bundle
        (``efb.conflict_rows``)."""
        out = self._plan_counters()
        if self.bundle_plan is not None:
            out["efb.conflict_rows"] = self.efb_conflict_rows
        return out

    def _resolve_categoricals(self, names) -> set:
        cat = self.categorical_feature
        if cat == "auto" or cat is None:
            cfg_cat = self.config.categorical_feature
            if not cfg_cat:
                return set()
            cat = [tok for tok in str(cfg_cat).split(",") if tok]
        out = set()
        for c in cat:
            if isinstance(c, str) and not c.lstrip("-").isdigit():
                if c in names:
                    out.add(names.index(c))
            else:
                out.add(int(c))
        return out

    # ------------------------------------------------------------------
    # accessors used by the trainer
    def _multi_process(self) -> bool:
        """True under a multi-host runtime: this Dataset holds (or will
        hold) one row shard — bin mappers must be synced, EFB skipped."""
        try:
            import jax
            return jax.process_count() > 1
        except Exception:
            return False

    @property
    def _sync_mappers_needed(self) -> bool:
        return self._multi_process()

    def _auto_partition_slice(self, n: int):
        """Rows this process keeps when the caller did NOT pre-partition:
        the loader's rank/num_machines row split
        (DatasetLoader::LoadFromFile, dataset_loader.cpp:203). With
        pre_partition=true the caller's data is already this host's
        shard and no slicing happens."""
        if not self._multi_process() or bool(self.config.pre_partition):
            return None
        self.auto_partitioned = True
        if self.group is not None:
            raise NotImplementedError(
                "multi-host auto-partition does not support query/group "
                "data; pre-partition queries per host and set "
                "pre_partition=true")
        import jax
        from .parallel.distributed import feature_blocks as _blocks
        return _blocks(n, jax.process_count())[jax.process_index()]

    def _apply_partition(self, sl) -> None:
        for fld in ("label", "weight", "position"):
            v = getattr(self, fld)
            if v is not None:
                setattr(self, fld, v[sl])
        if self.init_score is not None:
            isc = np.asarray(self.init_score)
            self.init_score = isc[sl] if isc.ndim == 1 else isc[sl, :]

    @property
    def num_features(self) -> int:
        return len(self.used_features)

    def per_feature_num_bins(self) -> np.ndarray:
        return np.asarray([self.bin_mappers[f].num_bin
                           for f in self.used_features], dtype=np.int32)

    def unbundled_bins(self) -> np.ndarray:
        """Per-feature [R, F] bin matrix decoded from EFB bundle storage
        (decode_feature_bins applied column-wise); ``self.bins`` itself
        when no bundling. tree_learner=feature uses this: it shards
        FEATURES and replicates rows, so it needs per-feature columns
        and gives up nothing (each worker holds the full dataset in the
        reference too, feature_parallel_tree_learner.cpp:38)."""
        bp = self.bundle_plan
        if bp is None:
            return self.bins
        from .efb import decode_feature_bins
        nb = self.per_feature_num_bins()
        # int32 (not uint16) above 256 bins: every downstream bins
        # consumer — including the native FFI dispatch, which reads
        # "uint8 else int32" (native/hist_ffi.cc) — handles exactly
        # those two dtypes
        dt = np.uint8 if int(nb.max()) <= 256 else np.int32
        R, F = self.bins.shape[0], len(nb)
        out = np.empty((R, F), dt)
        # decode in row blocks: the int32 gather/compare intermediates
        # are ~8 bytes/cell, so a whole-matrix pass would spike host
        # memory ~10x over the final matrix at EFB-wide shapes
        blk = max(1, (64 << 20) // max(1, 8 * F))
        for r0 in range(0, R, blk):
            raw = self.bins[r0:r0 + blk, bp.feat_bundle].astype(np.int32)
            out[r0:r0 + blk] = decode_feature_bins(
                raw, bp.feat_offset[None, :], nb[None, :],
                bp.feat_mfb[None, :])
        return out

    def per_feature_nan_bins(self) -> np.ndarray:
        """nan bin index per used feature; -1 when the feature has none."""
        return np.asarray([self.bin_mappers[f].nan_bin
                           for f in self.used_features], dtype=np.int32)

    def per_feature_is_categorical(self) -> np.ndarray:
        return np.asarray(
            [self.bin_mappers[f].bin_type == "categorical"
             for f in self.used_features], dtype=bool)

    def get_label(self):
        return self.label

    def get_weight(self):
        return self.weight

    def get_group(self):
        return self.group

    def get_init_score(self):
        return self.init_score

    def query_boundaries(self) -> Optional[np.ndarray]:
        """Cumulative query boundaries from per-query sizes (Metadata
        query_boundaries_, dataset.h:48)."""
        if self.group is None:
            return None
        return np.concatenate([[0], np.cumsum(self.group)]).astype(np.int64)

    def set_field(self, name, value):
        if name == "label":
            self.label = np.asarray(value, dtype=np.float64).reshape(-1)
        elif name == "weight":
            self.weight = None if value is None else np.asarray(
                value, dtype=np.float64).reshape(-1)
        elif name == "group":
            self.group = None if value is None else np.asarray(
                value, dtype=np.int64).reshape(-1)
        elif name == "init_score":
            self.init_score = None if value is None else np.asarray(
                value, dtype=np.float64)
        elif name == "position":
            self.position = (None if value is None
                             else np.asarray(value).reshape(-1))
        else:
            raise ValueError(f"Unknown field {name}")

    def __len__(self):
        return self.num_data

    def subset(self, used_indices, params: Optional[Dict] = None
               ) -> "Dataset":
        """Row-subset view sharing this dataset's bin mappers
        (Dataset::CopySubrow, dataset.cpp:836 / basic.py subset): the
        child is already constructed — no re-binning."""
        self.construct()
        idx = np.sort(np.asarray(used_indices, np.int64))
        child = Dataset.__new__(Dataset)
        child.params = {**self.params, **(params or {})}
        child.config = Config(child.params)
        child._raw_data = None
        child.feature_name = list(self.feature_name)
        child.categorical_feature = self.categorical_feature
        child.reference = self
        child.free_raw_data = True
        child.bin_mappers = self.bin_mappers
        child.bundle_plan = self.bundle_plan
        child.used_features = self.used_features
        child.max_num_bin = self.max_num_bin
        child.num_total_features = self.num_total_features
        child.bins = self.bins[idx]
        child.num_data = len(idx)
        child.label = None if self.label is None else self.label[idx]
        child.weight = None if self.weight is None else self.weight[idx]
        child.init_score = None
        if self.init_score is not None:
            isc = np.asarray(self.init_score)
            child.init_score = (isc[idx] if isc.ndim == 1
                                else isc[idx, :])
        child.group = None
        if self.group is not None:
            # rows of a query stay together or the subset is per-row;
            # recompute sizes from membership (used_indices sorted)
            bounds = self.query_boundaries()
            qid = np.searchsorted(bounds, idx, side="right") - 1
            change = np.nonzero(np.diff(qid))[0] + 1
            child.group = np.diff(np.concatenate(
                [[0], change, [len(idx)]])).astype(np.int64)
        child.raw_values = (None if self.raw_values is None
                            else self.raw_values[idx])
        child.position = (None if self.position is None
                          else self.position[idx])
        child.pandas_categorical = self.pandas_categorical
        child._constructed = True
        return child

    def add_features_from(self, other: "Dataset") -> "Dataset":
        """Append ``other``'s features to this dataset in place
        (Dataset::AddFeaturesFrom, dataset.cpp:1586). Both datasets must
        be constructed with the same ``num_data``; ``other``'s metadata
        (label/weight/group) is discarded, matching the reference."""
        self.construct()
        other.construct()
        if self.num_data != other.num_data:
            raise ValueError(
                f"cannot add features: num_data differs "
                f"({self.num_data} vs {other.num_data})")
        if self.bundle_plan is not None or other.bundle_plan is not None:
            raise ValueError(
                "add_features_from does not support EFB-bundled datasets "
                "(set enable_bundle=false on both)")
        if self.bins.dtype != other.bins.dtype:
            wide = np.int32
            self.bins = self.bins.astype(wide)
            other_bins = other.bins.astype(wide)
        else:
            other_bins = other.bins
        base = self.num_total_features
        self.bins = np.concatenate([self.bins, other_bins], axis=1)
        self.bin_mappers = list(self.bin_mappers) + list(other.bin_mappers)
        self.used_features = np.concatenate(
            [self.used_features, other.used_features + base])
        # de-duplicate colliding names the way pandas would
        names = list(self.feature_name)
        taken = set(names)
        for nm in other.feature_name:
            new = nm
            i = 1
            while new in taken:
                new = f"{nm}_{i}"
                i += 1
            taken.add(new)
            names.append(new)
        self.feature_name = names
        self.num_total_features = base + other.num_total_features
        self.max_num_bin = max(self.max_num_bin, other.max_num_bin)
        if self.raw_values is not None and other.raw_values is not None:
            self.raw_values = np.concatenate(
                [self.raw_values, other.raw_values], axis=1)
        else:
            self.raw_values = None
        return self

    # ------------------------------------------------------------------
    # binary dataset cache (Dataset::SaveBinaryFile dataset.cpp:1018 /
    # DatasetLoader::LoadFromBinFile dataset_loader.cpp:417): persist the
    # CONSTRUCTED state — binned matrix + mappers + metadata — so reloads
    # skip parsing and re-binning entirely.
    _BINARY_KEY = "lightgbm_tpu_dataset_v1"

    def save_binary(self, filename) -> "Dataset":
        self.construct()
        payload = {
            self._BINARY_KEY: np.asarray(1),
            "bins": self.bins,
            "used_features": self.used_features,
            "max_num_bin": np.asarray(self.max_num_bin),
            "feature_name": np.asarray(self.feature_name),
        }
        for field in ("label", "weight", "group", "init_score",
                      "position"):
            v = getattr(self, field)
            if v is not None:
                payload[field] = v
        if self.pandas_categorical is not None:
            import json as _json

            def _py(o):
                if isinstance(o, np.integer):
                    return int(o)
                if isinstance(o, np.floating):
                    return float(o)
                if isinstance(o, np.bool_):
                    return bool(o)
                return str(o)
            payload["pandas_categorical"] = np.asarray(_json.dumps(
                self.pandas_categorical, default=_py))
        scal, ubs, cats = [], [], []
        ub_off, cat_off = [0], [0]
        for m in self.bin_mappers:
            s, ub, ct = m.state_arrays()
            scal.append(s)
            ubs.append(ub)
            cats.append(ct)
            ub_off.append(ub_off[-1] + len(ub))
            cat_off.append(cat_off[-1] + len(ct))
        payload.update(
            mapper_scalars=np.stack(scal),
            mapper_ub=np.concatenate(ubs) if ubs else np.empty(0),
            mapper_ub_off=np.asarray(ub_off, np.int64),
            mapper_cats=np.concatenate(cats) if cats else np.empty(0,
                                                                   np.int64),
            mapper_cat_off=np.asarray(cat_off, np.int64))
        if self.bundle_plan is not None:
            fb, fo, fm, bnb, bscal = self.bundle_plan.state_arrays()
            payload.update(efb_feat_bundle=fb, efb_feat_offset=fo,
                           efb_feat_mfb=fm, efb_bundle_bins=bnb,
                           efb_scalars=bscal)
        with open(filename, "wb") as f:
            np.savez_compressed(f, **payload)
        return self

    @staticmethod
    def _is_binary_file(path) -> bool:
        try:
            with open(path, "rb") as f:
                return f.read(2) == b"PK"  # npz = zip container
        except OSError:
            return False

    def _load_binary(self, path):
        from .binning import BinMapper
        with np.load(path, allow_pickle=False) as z:
            if self._BINARY_KEY not in z:
                raise ValueError(
                    f"{path} is not a lightgbm_tpu binary dataset")
            self.bins = z["bins"]
            self.used_features = z["used_features"]
            self.max_num_bin = int(z["max_num_bin"])
            self.feature_name = [str(s) for s in z["feature_name"]]
            for field in ("label", "weight", "group", "init_score",
                          "position"):
                if field in z and getattr(self, field) is None:
                    setattr(self, field, z[field])
            if "pandas_categorical" in z:
                import json as _json
                self.pandas_categorical = _json.loads(
                    str(z["pandas_categorical"]))
            scal = z["mapper_scalars"]
            ub, ub_off = z["mapper_ub"], z["mapper_ub_off"]
            cats, cat_off = z["mapper_cats"], z["mapper_cat_off"]
            if "efb_scalars" in z:
                from .efb import BundlePlan
                self.bundle_plan = BundlePlan.from_state_arrays(
                    z["efb_feat_bundle"], z["efb_feat_offset"],
                    z["efb_feat_mfb"], z["efb_bundle_bins"],
                    z["efb_scalars"])
        self.bin_mappers = [
            BinMapper.from_state_arrays(
                scal[i], ub[ub_off[i]:ub_off[i + 1]],
                cats[cat_off[i]:cat_off[i + 1]])
            for i in range(scal.shape[0])]
        self.num_data, _ = self.bins.shape
        self.num_total_features = len(self.bin_mappers)
        self._raw_data = None
        self._constructed = True
