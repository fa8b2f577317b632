"""Booster + train()/cv() — the user-facing training entry points.

Analog of the reference Python package (``python-package/lightgbm/
engine.py:109`` ``train``, ``engine.py:354,625`` ``CVBooster``/``cv``;
``basic.py:3586`` ``Booster``). There is no C-API boundary here: the
Booster drives the JAX GBDT directly (SURVEY.md §7.7 — Python-first API,
no ctypes).
"""

from __future__ import annotations

import collections
import copy
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from .boosting import create_boosting
from .boosting.gbdt import GBDT
from . import log, profiler
from .callback import CallbackEnv, EarlyStopException
from .config import Config
from .dataset import Dataset
from .metrics import create_metrics, Metric
from .objectives import create_objective, Objective
from .tree import Tree

__all__ = ["Booster", "PredictSession", "train", "cv", "CVBooster",
           "enable_compilation_cache"]


def enable_compilation_cache():
    """Point jax's persistent XLA compilation cache at a place that
    outlives the process, so the compile of the training/predict
    programs (seconds with the Pallas kernel, minutes for the full-width
    matmul formulation) is paid once per checkout. ONE rule, shared by
    :func:`train`, the CLI and the prediction server:

    - ``JAX_COMPILATION_CACHE_DIR`` set: jax already reads it; nothing
      is set in code.
    - otherwise ``<checkout>/.xla_cache`` — a fixed path beside the
      code (the path is part of the cache key, so a directory that
      moves never hits).
    - the CPU backend stays off unless the variable is set: jaxlib
      0.9.0 has segfaulted (de)serializing CPU executables (see
      tests/conftest.py).

    Safe to call repeatedly. Returns the cache dir in force, or None."""
    import os
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax
    if jax.default_backend() == "cpu":
        return None
    from .native import cache_root
    d = os.path.join(cache_root(), ".xla_cache")
    if jax.config.jax_compilation_cache_dir != d:
        os.makedirs(d, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", d)
        # cache every program: the helper jits are small and fast to
        # compile, but a warm process should pay ZERO recompiles
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          0.0)
    return d


class Booster:
    """Trained/trainable model handle (basic.py:3586 analog)."""

    def __init__(self, params: Optional[Dict] = None,
                 train_set: Optional[Dataset] = None,
                 model_file: Optional[str] = None,
                 model_str: Optional[str] = None):
        self.params = dict(params or {})
        self.best_iteration = -1
        # bumped on every tree-set mutation; keys the packed-ensemble
        # prediction cache (stale packs otherwise survive rollback+retrain)
        self._model_version = 0
        # native-predictor handle state, initialized EAGERLY: a lazy
        # check-then-act would let two first-predict threads build
        # different locks and then free a handle mid-walk
        import threading as _threading
        self._capi_lock = _threading.Lock()
        self._capi_inflight = 0
        self._capi_retired: List = []
        self._capi_handle = None
        self._capi_key = None
        self.best_score: Dict = {}
        self._valid_names: List[str] = []
        self._gbdt: Optional[GBDT] = None
        self._trees: List[Tree] = []
        # continued training (init_model): trees of the loaded base model
        # (num_init_iteration of gbdt.h) + pending per-row init scores
        self._base_trees: List[Tree] = []
        self._pending_init_scores = None
        self._pending_valid_init_scores: List = []
        self._num_class = 1
        self._objective_name = "regression"
        self._feature_names: List[str] = []
        self._feature_infos: List[str] = []
        self._max_feature_idx = 0
        self._metrics: List[Metric] = []
        self._train_metrics_data = None
        self._average_output = False  # RF mode (rf.hpp average_output_)
        self._pandas_categorical = None  # train-time category lists

        if model_file is not None:
            with open(model_file) as f:
                self._load_from_string(f.read())
            return
        if model_str is not None:
            self._load_from_string(model_str)
            return
        if train_set is None:
            raise ValueError("Booster needs train_set, model_file or "
                             "model_str")
        if not isinstance(train_set, Dataset):
            raise TypeError("train_set should be a Dataset instance")

        self.config = Config(self.params)
        train_set.params = {**self.params, **train_set.params}
        train_set.construct()
        self._objective: Optional[Objective] = create_objective(self.config)
        self._objective_name = (self._objective.name if self._objective
                                else "custom")
        self._num_class = self.config.num_class
        self.train_set = train_set
        self._valid_sets: List[Dataset] = []
        self._metrics = create_metrics(self.config)
        self._feature_names = list(train_set.feature_name)
        self._max_feature_idx = train_set.num_total_features - 1
        self._pandas_categorical = train_set.pandas_categorical

    # -- training ------------------------------------------------------
    def _all_trees(self) -> List[Tree]:
        return self._base_trees + self._trees

    def _set_init_model(self, base: "Booster", train_scores=None,
                        valid_scores=None):
        """Continued training: resume scores from `base`'s predictions
        (engine.py:234-246 _set_predictor / init-score flow). Score arrays
        may be precomputed (train() does, before raw data is freed);
        otherwise the datasets must still hold their raw matrices
        (free_raw_data=False)."""
        if self._gbdt is not None:
            raise RuntimeError("init_model must be set before training")

        def raw_of(ds: Dataset, what: str):
            if ds._raw_data is None:
                raise ValueError(
                    f"Continued training needs the {what} raw data; "
                    "construct the Dataset with free_raw_data=False")
            return ds._raw_data
        if train_scores is None:
            train_scores = base.predict(raw_of(self.train_set, "training"),
                                        raw_score=True)
        if valid_scores is None:
            valid_scores = [
                base.predict(raw_of(vs, "validation"), raw_score=True)
                for vs in self._valid_sets]
        self._pending_init_scores = train_scores
        self._pending_valid_init_scores = list(valid_scores)
        self._base_trees = [copy.deepcopy(t) for t in base._all_trees()]
        self._average_output = base._average_output

    def _ensure_gbdt(self):
        if self._gbdt is None:
            self._gbdt = create_boosting(
                self.config, self.train_set, self._objective,
                self._valid_sets,
                init_row_scores=self._pending_init_scores,
                valid_init_row_scores=self._pending_valid_init_scores,
                num_init_iteration=(len(self._base_trees)
                                    // max(1, self._num_class)))
            if not self._base_trees:
                self._average_output = getattr(
                    self._gbdt, "average_output", False)
            self._trees = self._gbdt.models
            for m in self._metrics:
                m.init(self.train_set.get_label(),
                       self.train_set.get_weight(),
                       self.train_set.query_boundaries())
            self._valid_metrics = []
            for vs in self._valid_sets:
                ms = create_metrics(self.config)
                for m in ms:
                    m.init(vs.get_label(), vs.get_weight(),
                           vs.query_boundaries())
                self._valid_metrics.append(ms)

    def add_valid(self, data: Dataset, name: str):
        if self._gbdt is not None:
            raise RuntimeError("add_valid must be called before training "
                               "starts (fixed-shape device state)")
        data.reference = self.train_set
        data.params = {**self.params, **data.params}
        data.construct()
        self._valid_sets.append(data)
        self._valid_names.append(name)
        return self

    def update(self, train_set=None, fobj: Optional[Callable] = None, *,
               defer: bool = False):
        """One boosting iteration; True if stopped (no more splits).

        ``defer=True`` lets the fused trainer dispatch the iteration
        without materializing its trees (returns None); they land in
        ``self._trees`` at the next sync point — engine.train's eval
        cadence, or any model-reading call (predict/save/dump), which
        sync transparently. Legacy/fallback configs ignore ``defer``
        and return the stop bool eagerly."""
        self._ensure_gbdt()
        self._model_version += 1
        if fobj is not None:
            if self._objective is not None:
                raise ValueError(
                    "Custom objective requires objective='custom' in params "
                    "(c_api LGBM_BoosterUpdateOneIterCustom contract)")
            grad, hess = fobj(self._current_pred_for_fobj(), self.train_set)
            return self._gbdt.train_one_iter(grad, hess)
        return self._gbdt.train_one_iter(defer=defer)

    def _sync_trees(self):
        """Materialize any trees the fused trainer deferred (no-op when
        nothing pends) so model readers see the full ensemble."""
        if self._gbdt is not None:
            self._gbdt.sync()

    def _current_pred_for_fobj(self):
        # get_training_scores (not eval_scores): DART applies its dropout
        # here so custom gradients see the dropped ensemble (dart.hpp
        # GetTrainingScore)
        return self._gbdt.get_training_scores().squeeze()

    def reset_parameter(self, params: Dict):
        self.params.update(params)
        self.config.set(**params)
        if self._gbdt is not None:
            self._gbdt.shrinkage = self.config.learning_rate

    def rollback_one_iter(self):
        """Undo the newest iteration (LGBM_BoosterRollbackOneIter /
        gbdt.cpp:454)."""
        self._ensure_gbdt()
        self._model_version += 1
        self._gbdt.rollback_one_iter()
        return self

    def refit(self, data, label, decay_rate: Optional[float] = None,
              **kwargs) -> "Booster":
        """New Booster with this model's tree STRUCTURES and leaf values
        re-fit to `data`/`label` (basic.py Booster.refit +
        gbdt.cpp:258 RefitTree + serial_tree_learner.cpp:248
        FitByExistingTree): per tree, gradients at the running score,
        per-leaf grad/hess sums, new output = decay*old +
        (1-decay)*shrinkage*CalculateSplittedLeafOutput."""
        from .ops.split import leaf_output as _leaf_output_fn
        import jax.numpy as jnp

        if decay_rate is None:
            decay_rate = float(Config(self.params).refit_decay_rate)
        X = self._as_matrix(data)
        y = np.asarray(label, np.float64).reshape(-1)
        cfg = Config(self.params)
        objective = create_objective(cfg)
        if objective is None:
            raise ValueError("Cannot refit with a custom objective")
        new_booster = Booster(model_str=self.model_to_string(),
                              params=dict(self.params))
        trees = new_booster._all_trees()
        K = max(1, self._num_class)
        objective.init(y, kwargs.get("weight"), None)
        scores = np.zeros((len(y), K), np.float64)
        l1, l2 = cfg.lambda_l1, cfg.lambda_l2
        for it in range(len(trees) // K):
            # gradients at the current cumulative score (RefitTree loop)
            for k in range(K):
                tree = trees[it * K + k]
                if K > 1:
                    g, h = objective.get_gradients(
                        jnp.asarray(scores, jnp.float32),
                        jnp.asarray(y, jnp.float32), None)
                    g, h = np.asarray(g)[:, k], np.asarray(h)[:, k]
                else:
                    g, h = objective.get_gradients(
                        jnp.asarray(scores[:, 0], jnp.float32),
                        jnp.asarray(y, jnp.float32), None)
                    g, h = np.asarray(g), np.asarray(h)
                leaves = tree.predict_leaf_index(X)
                nl = tree.num_leaves
                sg = np.bincount(leaves, weights=g, minlength=nl)
                sh = np.bincount(leaves, weights=h, minlength=nl) + 1e-15
                new_out = np.asarray(_leaf_output_fn(
                    jnp.asarray(sg), jnp.asarray(sh), l1, l2,
                    cfg.max_delta_step)) * tree.shrinkage
                tree.leaf_value = (decay_rate * tree.leaf_value
                                   + (1.0 - decay_rate) * new_out)
                scores[:, k] += tree.leaf_value[leaves]
        return new_booster

    # -- evaluation ----------------------------------------------------
    def _converted(self, raw: np.ndarray) -> np.ndarray:
        if self._objective is not None and self._objective.needs_convert:
            return self._objective.convert_output(raw)
        return raw

    def eval_train(self, feval=None):
        return self._eval_set(-1, "training", feval)

    def eval_valid(self, feval=None):
        out = []
        for i in range(len(self._valid_sets)):
            out.extend(self._eval_set(i, self._valid_names[i], feval))
        return out

    def _eval_set(self, which: int, name: str, feval=None):
        self._ensure_gbdt()
        raw = self._gbdt.eval_scores(which)
        if raw.shape[1] == 1:
            raw = raw[:, 0]
        pred = self._converted(raw)
        metrics = self._metrics if which < 0 else self._valid_metrics[which]
        out = []
        for m in metrics:
            # metrics like auc_mu rank by linear combinations of RAW
            # scores (the reference passes raw + objective to every
            # metric; we only fork where the distinction matters)
            inp = raw if getattr(m, "needs_raw_score", False) else pred
            for mname, value, bigger in m.eval(np.asarray(inp, np.float64)):
                out.append((name, mname, value, bigger))
        if feval is not None:
            ds = self.train_set if which < 0 else self._valid_sets[which]
            for fm in (feval if isinstance(feval, list) else [feval]):
                res = fm(raw, ds)
                if isinstance(res, list):
                    for mname, value, bigger in res:
                        out.append((name, mname, value, bigger))
                else:
                    mname, value, bigger = res
                    out.append((name, mname, value, bigger))
        return out

    # -- prediction ----------------------------------------------------
    def predict(self, data, start_iteration: int = 0,
                num_iteration: Optional[int] = None,
                raw_score: bool = False, pred_leaf: bool = False,
                pred_contrib: bool = False, **kwargs) -> np.ndarray:
        """Batch prediction on raw features
        (gbdt_prediction.cpp / predictor.hpp analog)."""
        self._sync_trees()
        from .dataset import Dataset
        # scipy sparse rides the native CSR predictor on the CPU
        # backend without ever densifying; all other paths (and route
        # fallbacks) materialize the dense matrix as before
        sp = (data if hasattr(data, "tocsr")
              and not isinstance(data, Dataset) else None)
        X = self._as_matrix(data) if sp is None else None
        ncol = (sp if sp is not None else X).shape[1]
        if ncol != self._max_feature_idx + 1 and not (
                kwargs.get("predict_disable_shape_check")
                or self.params.get("predict_disable_shape_check")):
            raise ValueError(
                f"The number of features in data ({ncol}) is not the "
                f"same as it was in training data "
                f"({self._max_feature_idx + 1}).\nYou can set "
                "predict_disable_shape_check=true to discard this error")
        K = max(1, self._num_class)
        trees = self._all_trees()
        if num_iteration is None or num_iteration < 0:
            num_iteration = (self.best_iteration
                             if self.best_iteration > 0 else
                             len(trees) // K)
        lo = start_iteration * K
        hi = min(len(trees), (start_iteration + num_iteration) * K)
        use = trees[lo:hi]
        if pred_leaf:
            if X is None:
                X = self._as_matrix(data)
            nat = self._native_leaf_indices(X, use, lo, K)
            if nat is not None:
                return nat
            out = np.stack([t.predict_leaf_index(X) for t in use], axis=1)
            return out
        if pred_contrib:
            if X is None:
                X = self._as_matrix(data)
            # TreeSHAP (tree.h:141 PredictContrib): per-class
            # [n, n_features+1] blocks, last column = expected value
            nf = X.shape[1]
            out = np.zeros((X.shape[0], K * (nf + 1)))
            for i, t in enumerate(use):
                k = (lo + i) % K
                out[:, k * (nf + 1):(k + 1) * (nf + 1)] += \
                    t.predict_contrib(X)
            if self._average_output and use:
                out /= len(use) // K
            return out
        es = self._early_stop_config(kwargs)
        raw = None
        if sp is not None and es is None:
            raw = self._native_raw_scores_csr(sp, use, lo, K)
        if raw is None:
            if X is None:
                X = self._as_matrix(data)
            raw = self._predict_raw_scores(X, use, lo, K, early_stop=es)
        return self._finalize_scores(raw, use, K, raw_score)

    def _finalize_scores(self, raw, use, K, raw_score):
        """RAW [n, K] -> user-facing predictions: RF averaging, class
        squeeze, objective transform (shared with PredictSession)."""
        if self._average_output and use:
            raw /= len(use) // K
        if K == 1:
            raw = raw[:, 0]
        if raw_score:
            return raw
        return self._converted(raw)

    def _native_route_lib(self, use, n, *, need_raw_sums=True):
        """The capi library when the native predictor applies to this
        call, else None (callers fall through to the device/host
        paths): CPU backend, non-linear trees, enough work to amortize,
        and — for score predictions — no in-walk RF averaging."""
        import jax
        if (not use or jax.default_backend() != "cpu"
                or (need_raw_sums and self._average_output)
                or any(t.is_linear for t in use)
                or n * len(use) < (1 << 14)):
            return None
        from .native import capi_lib
        return capi_lib()

    def _native_raw_scores(self, X, use, lo, K):
        """RAW [n, K] scores via the native C predictor (capi.c — the
        reference predictor.hpp model: per-row double-precision tree
        walks in compiled code). Used on the CPU backend where the XLA
        lock-step ensemble walk is gather-bound; the TPU backend keeps
        the device path. Returns None when the route does not apply —
        callers fall through to the device/host paths. RAW only: the
        Python side applies objective transforms, so objective coverage
        never diverges. Handle cached per model version; invalidated by
        training/rollback like the packed device ensemble."""
        n = X.shape[0]
        lib = self._native_route_lib(use, n)
        if lib is None:
            return None
        return self._native_mat_call(X, use, lo, K, predict_type=1,
                                     width=K, lib=lib)

    def _native_leaf_indices(self, X, use, lo, K):
        """pred_leaf via the native predictor: [n, len(use)] leaf ids in
        one threaded pass instead of a host walk per tree. None when the
        route does not apply."""
        lib = self._native_route_lib(use, X.shape[0],
                                     need_raw_sums=False)
        if lib is None:
            return None
        out = self._native_mat_call(X, use, lo, K, predict_type=2,
                                    width=len(use), lib=lib)
        return None if out is None else out.astype(np.int32)

    def _native_mat_call(self, X, use, lo, K, *, predict_type, width,
                         lib):
        """Shared dense call: [n, width] result of PredictForMat with
        the iteration window mapped from predict's [lo:hi] slice (whole
        iterations by contract). None on any native-side failure.

        Zero-copy handoff: C-contiguous float64 AND float32 matrices go
        straight into the kernel (the C side widens f32 per value —
        exact — inside its row blocks), so the serving path never
        duplicates the feature matrix."""
        import ctypes
        n = X.shape[0]
        if X.dtype == np.float32 and X.flags.c_contiguous:
            Xc, dtype_flag = X, 0
        else:
            Xc, dtype_flag = np.ascontiguousarray(X, np.float64), 1
        out = np.zeros(n * width, np.float64)
        out_len = ctypes.c_int64()
        rc = self._with_capi_handle(
            lib, lambda h: lib.LGBM_BoosterPredictForMat(
                h, Xc.ctypes.data_as(ctypes.c_void_p),
                dtype_flag, n, X.shape[1], 1, predict_type,
                lo // K, len(use) // K, b"",
                ctypes.byref(out_len), out))
        if rc != 0 or out_len.value != n * width:
            return None
        return out.reshape(n, width)

    def _native_raw_scores_csr(self, sp, use, lo, K):
        """RAW [n, K] scores straight from a scipy CSR/CSC matrix via
        LGBM_BoosterPredictForCSR — absent entries are 0.0 exactly like
        the densify-then-predict path, but the dense matrix never
        materializes. None when the route does not apply."""
        n = sp.shape[0]
        lib = self._native_route_lib(use, n)
        if lib is None:
            return None
        import ctypes
        csr = sp.tocsr()
        if not csr.has_canonical_format:
            # duplicate (row, col) entries: todense() SUMS them, while
            # the C densify loop would keep the last — canonicalize a
            # COPY so both paths agree without mutating caller data
            csr = csr.copy()
            csr.sum_duplicates()
        indptr = np.ascontiguousarray(csr.indptr, np.int64)
        indices = np.ascontiguousarray(csr.indices, np.int32)
        data = np.ascontiguousarray(csr.data, np.float64)
        out = np.zeros(n * K, np.float64)
        out_len = ctypes.c_int64()
        rc = self._with_capi_handle(lib, lambda h: lib.LGBM_BoosterPredictForCSR(
            h, indptr.ctypes.data_as(ctypes.c_void_p), 3,
            indices.ctypes.data_as(ctypes.c_void_p),
            data.ctypes.data_as(ctypes.c_void_p), 1,
            ctypes.c_int64(len(indptr)), ctypes.c_int64(len(data)),
            ctypes.c_int64(sp.shape[1]), 1,    # RAW
            lo // K, len(use) // K, b"",
            ctypes.byref(out_len), out))
        if rc != 0 or out_len.value != n * K:
            return None
        return out.reshape(n, K)

    def _with_capi_handle(self, lib, fn):
        """Run ``fn(handle)`` against the cached native model handle.

        Handle lifecycle: ctypes calls release the GIL, so another
        thread may rebuild the cache mid-predict — never free a handle
        that could be in flight; retire it and free when the in-flight
        count drains (the reference's C API guards its predict path
        with a lock for the same reason, c_api.cpp SingleRowPredictor).
        Returns fn's result, or -1 when the handle cannot be built."""
        import ctypes
        key = ("native", self._model_version)
        with self._capi_lock:
            if getattr(self, "_capi_key", None) != key:
                import os
                import tempfile
                fd, path = tempfile.mkstemp(suffix=".txt",
                                            prefix="lgbtpu_capi_")
                try:
                    with os.fdopen(fd, "w") as f:
                        f.write(self.model_to_string())
                    handle = ctypes.c_void_p()
                    iters = ctypes.c_int()
                    rc = lib.LGBM_BoosterCreateFromModelfile(
                        path.encode(), ctypes.byref(iters),
                        ctypes.byref(handle))
                finally:
                    os.unlink(path)
                if rc != 0:
                    return -1
                old = getattr(self, "_capi_handle", None)
                if old:
                    self._capi_retired.append(old)
                self._capi_handle = handle
                self._capi_key = key
                if self._capi_inflight == 0:
                    for h in self._capi_retired:
                        lib.LGBM_BoosterFree(h)
                    self._capi_retired.clear()
            h = self._capi_handle
            self._capi_inflight += 1
        try:
            return fn(h)
        finally:
            with self._capi_lock:
                self._capi_inflight -= 1
                if self._capi_inflight == 0 and self._capi_retired:
                    for hr in self._capi_retired:
                        lib.LGBM_BoosterFree(hr)
                    self._capi_retired.clear()

    def __del__(self):
        try:
            if getattr(self, "_capi_handle", None):
                from .native import capi_lib
                lib = capi_lib()
                if lib is not None:
                    lib.LGBM_BoosterFree(self._capi_handle)
                    for h in getattr(self, "_capi_retired", []):
                        lib.LGBM_BoosterFree(h)
        except Exception:
            pass

    def _predict_host_early_stop(self, X, use, lo, K, freq, margin):
        """Host path of GBDT::PredictRaw's early-stop loop
        (gbdt_prediction.cpp:13-31): rows that clear the margin every
        ``freq`` iterations drop out of the remaining tree walks."""
        n = X.shape[0]
        raw = np.zeros((n, K))
        active = np.arange(n)
        n_iters = len(use) // K
        counter = 0
        for it in range(n_iters):
            if len(active) == 0:
                break
            Xa = X[active]
            for k in range(K):
                t = use[it * K + k]
                raw[active, (lo + it * K + k) % K] += t.predict(Xa)
            counter += 1
            if counter == freq:
                counter = 0
                if K == 1:
                    m = 2.0 * np.abs(raw[active, 0])
                else:
                    srt = np.sort(raw[active], axis=1)
                    m = srt[:, -1] - srt[:, -2]
                active = active[m <= margin]
        # trailing partial iterations (len(use) % K trees) never happen:
        # callers slice whole iterations
        return raw

    # objectives whose predictions tolerate early stopping — the ones
    # overriding NeedAccuratePrediction() to false (binary_objective.hpp
    # :188, multiclass_objective.hpp:153,259, rank_objective.hpp:108);
    # Predictor then picks binary/multiclass by class count
    # (predictor.hpp:46-58)
    _EARLY_STOP_OBJECTIVES = ("binary", "multiclass", "multiclassova",
                              "lambdarank", "rank_xendcg")

    def _early_stop_config(self, kwargs):
        """(freq, margin) when pred_early_stop applies, else None."""
        def get(name, default):
            if name in kwargs:
                return kwargs[name]
            return self.params.get(name, default)
        if not get("pred_early_stop", False):
            return None
        if self._objective_name not in self._EARLY_STOP_OBJECTIVES:
            return None
        freq = int(get("pred_early_stop_freq", 10))
        margin = float(get("pred_early_stop_margin", 10.0))
        if freq <= 0 or margin < 0:
            raise ValueError(
                "pred_early_stop_freq must be > 0 and "
                "pred_early_stop_margin >= 0")
        return freq, margin

    def _predict_raw_scores(self, X: np.ndarray, use, lo: int,
                            K: int, early_stop=None) -> np.ndarray:
        """[n, K] raw scores. Large batches run the whole ensemble
        on-device (ops/predict_ensemble — predictor.hpp's OpenMP batch
        path, recast as a [rows, trees] lock-step walk); small ones and
        linear trees take the host path."""
        n = X.shape[0]
        # NOTE contract divergence from the reference: the device path
        # walks trees in float32 (X, thresholds, leaf values), the host
        # path in float64 — a value within f32 eps of a threshold can
        # route differently across the batch-size cutover. Per-class
        # accumulation runs in f64 on both paths.
        if early_stop is None:
            raw = self._native_raw_scores(X, use, lo, K)
            if raw is not None:
                return raw
        use_device = (len(use) > 0
                      and not any(t.is_linear for t in use)
                      and n * len(use) >= (1 << 16))
        if not use_device:
            if early_stop is not None and len(use) >= K:
                return self._predict_host_early_stop(X, use, lo, K,
                                                     *early_stop)
            raw = np.zeros((n, K))
            for i, t in enumerate(use):
                raw[:, (lo + i) % K] += t.predict(X)
            return raw
        import jax.numpy as jnp
        from .ops.predict_ensemble import (pack_ensemble,
                                           predict_raw_device,
                                           predict_raw_device_early_stop)
        key = (self._model_version, lo, lo + len(use))
        if getattr(self, "_packed_key", None) != key:
            self._packed = pack_ensemble(use)
            self._packed_key = key

        def run_chunked(kernel, out_cols):
            """Fixed-shape row chunks (pad ragged tails so repeat batch
            sizes hit one compiled program); kernel: f32 [chunk, F] ->
            [chunk, out_cols]."""
            out = np.zeros((n, out_cols))
            chunk = max(1024, (1 << 22) // max(len(use), 1))
            chunk = min(chunk, -(-n // 1024) * 1024)
            for s0 in range(0, n, chunk):
                Xc = X[s0:s0 + chunk]
                real = Xc.shape[0]
                if real < chunk:
                    Xc = np.concatenate(
                        [Xc, np.zeros((chunk - real, X.shape[1]))])
                res = np.asarray(kernel(jnp.asarray(Xc, jnp.float32)),
                                 np.float64)
                out[s0:s0 + real] = res[:real]
            return out

        if early_stop is not None and len(use) >= K:
            # NOTE: this path accumulates per-class sums in f32 ON
            # DEVICE (the margin test needs the running total inside the
            # loop; TPUs have no f64) — unlike the plain device path,
            # whose per-class accumulation runs in f64 on host. Turning
            # pred_early_stop on can therefore shift predictions by f32
            # accumulation rounding even with an unreachable margin.
            freq, margin = early_stop
            mj = jnp.asarray(margin, jnp.float32)
            return run_chunked(
                lambda Xc: predict_raw_device_early_stop(
                    self._packed, Xc, mj, K=K, freq=freq), K)

        cls = np.asarray([(lo + i) % K for i in range(len(use))])

        def plain_kernel(Xc):
            # per-chunk [chunk, T] -> [chunk, K] immediately (f64 on
            # host, and the per-tree matrix never exceeds one chunk)
            outs = np.asarray(predict_raw_device(self._packed, Xc),
                              np.float64)
            return np.stack([outs[:, cls == k].sum(axis=1)
                             for k in range(K)], axis=1)

        return run_chunked(plain_kernel, K)

    def predict_session(self, **kwargs) -> "PredictSession":
        """A persistent :class:`PredictSession` bound to this model —
        the serving entry point for repeated predict() calls."""
        return PredictSession(self, **kwargs)

    def _as_matrix(self, data) -> np.ndarray:
        if isinstance(data, Dataset):
            raise TypeError("Cannot predict on a Dataset; pass the raw "
                            "matrix (reference basic.py behavior)")
        import os as _os
        if isinstance(data, (str, _os.PathLike)):
            # predict straight from a data file (Predictor's file path,
            # predictor.hpp:30); label column is dropped by the loader
            from .io import load_data_file
            data = load_data_file(
                data, num_features_hint=len(self._feature_names)).X
        if hasattr(data, "tocsr"):  # scipy sparse: densify for traversal
            data = np.asarray(data.todense())
        from .dataset import _to_2d_float, _is_pandas_df, _data_from_pandas
        if _is_pandas_df(data):
            # category columns align to the TRAINING category lists so
            # codes mean the same thing (basic.py _data_from_pandas
            # predict path); a model never trained from pandas aligns
            # against [] -> categorical frames raise the mismatch error
            arr, _, _ = _data_from_pandas(
                data, self._pandas_categorical or [])
            return arr
        return _to_2d_float(data)

    # -- model IO (gbdt_model_text.cpp analog) -------------------------
    def model_to_string(self, num_iteration: Optional[int] = None,
                        start_iteration: int = 0,
                        importance_type: str = "split") -> str:
        self._sync_trees()
        K = max(1, self._num_class)
        trees = self._all_trees()
        if num_iteration is not None and num_iteration > 0:
            trees = trees[: num_iteration * K]
        header = [
            "tree",
            "version=v4",
            f"num_class={self._num_class}",
            f"num_tree_per_iteration={K}",
            "label_index=0",
            f"max_feature_idx={self._max_feature_idx}",
            f"objective={self._objective_text()}",
        ]
        if self._average_output:
            header.append("average_output")  # gbdt_model_text.cpp RF marker
        header += [
            "feature_names=" + " ".join(self._feature_names),
            "feature_infos=" + " ".join(self._feature_infos_list()),
            "",
        ]
        blocks = [t.to_text(i) for i, t in enumerate(trees)]
        sizes = [len(b.encode()) + 1 for b in blocks]
        header.insert(-1, "tree_sizes=" + " ".join(str(s) for s in sizes))
        body = "\n".join(blocks)
        tail = ["", "end of trees", ""]
        imp = self.feature_importance(importance_type)
        order = np.argsort(-imp, kind="stable")
        tail.append("feature_importances:")
        for i in order:
            if imp[i] > 0:
                tail.append(f"{self._feature_names[i]}={imp[i]:g}")
        tail += ["", "parameters:"]
        for key, val in sorted(self.params.items()):
            tail.append(f"[{key}: {val}]")
        import json as _json

        def _py(o):
            if isinstance(o, (np.integer,)):
                return int(o)
            if isinstance(o, (np.floating,)):
                return float(o)
            if isinstance(o, (np.bool_,)):
                return bool(o)
            return str(o)
        pc = (_json.dumps(self._pandas_categorical, default=_py)
              if self._pandas_categorical else "null")
        tail += ["end of parameters", "", "pandas_categorical:" + pc, ""]
        return "\n".join(header) + "\n" + body + "\n".join(tail)

    def dump_model(self, num_iteration: Optional[int] = None,
                   start_iteration: int = 0,
                   importance_type: str = "split") -> Dict[str, Any]:
        """Model as a JSON-ready dict (GBDT::DumpModel,
        gbdt_model_text.cpp:21; same schema as the reference python
        Booster.dump_model)."""
        self._sync_trees()
        K = max(1, self._num_class)
        trees = self._all_trees()
        total_iter = len(trees) // K
        start_iteration = min(max(start_iteration, 0), total_iter)
        start = start_iteration * K
        end = len(trees)
        if num_iteration is not None and num_iteration > 0:
            end = min(start + num_iteration * K, end)
        feature_infos = {}
        for name, info in zip(self._feature_names,
                              self._feature_infos_list()):
            if info == "none":
                continue
            if info.startswith("["):
                lo, hi = info[1:-1].split(":")
                feature_infos[name] = {"min_value": float(lo),
                                       "max_value": float(hi),
                                       "values": []}
            else:
                vals = [int(v) for v in info.split(":")]
                feature_infos[name] = {"min_value": min(vals),
                                       "max_value": max(vals),
                                       "values": vals}
        imp = self.feature_importance(importance_type)
        return {
            "name": "tree",
            "version": "v4",
            "num_class": self._num_class,
            "num_tree_per_iteration": K,
            "label_index": 0,
            "max_feature_idx": self._max_feature_idx,
            "objective": self._objective_text(),
            "average_output": bool(self._average_output),
            "feature_names": list(self._feature_names),
            "monotone_constraints": [
                int(v) for v in
                (Config(self.params).monotone_constraints or [])],
            "feature_infos": feature_infos,
            "tree_info": [
                dict(tree_index=i, **t.to_json())
                for i, t in enumerate(trees[start:end], start=start)],
            "feature_importances": {
                self._feature_names[i]: float(imp[i])
                for i in np.argsort(-imp, kind="stable") if imp[i] > 0},
            "pandas_categorical": self._pandas_categorical,
        }

    def save_model(self, filename: str, num_iteration: Optional[int] = None,
                   start_iteration: int = 0,
                   importance_type: Optional[str] = None):
        if importance_type is None:
            # saved_feature_importance_type (gbdt_model_text.cpp / config)
            importance_type = ("gain" if int(Config(self.params)
                               .saved_feature_importance_type) == 1
                               else "split")
        # atomic write (tmp + fsync + os.replace): a SIGKILL mid-write
        # must never leave a truncated model under the final name that
        # init_model/resume then half-parses
        from .resilience import atomic_write_text
        atomic_write_text(filename,
                          self.model_to_string(num_iteration,
                                               start_iteration,
                                               importance_type))
        return self

    def model_from_string(self, model_str: str):
        self._load_from_string(model_str)
        return self

    def _objective_text(self) -> str:
        name = self._objective_name
        if name == "binary":
            return f"binary sigmoid:{Config(self.params).sigmoid:g}"
        if name == "multiclass":
            return f"multiclass num_class:{self._num_class}"
        if name == "multiclassova":
            # MulticlassOVA::ToString also records the per-class sigmoid
            # (multiclass_objective.hpp:249)
            return (f"multiclassova num_class:{self._num_class} "
                    f"sigmoid:{Config(self.params).sigmoid:g}")
        if name == "lambdarank":
            return "lambdarank"
        if name == "regression" and Config(self.params).reg_sqrt:
            # RegressionL2loss::ToString appends " sqrt"
            # (regression_objective.hpp:160); dropping it loses the
            # output square transform on reload
            return "regression sqrt"
        return name

    def _feature_infos_list(self) -> List[str]:
        if self._feature_infos:
            return self._feature_infos
        if hasattr(self, "train_set") and self.train_set._constructed:
            return [m.feature_info_str()
                    for m in self.train_set.bin_mappers]
        return ["none"] * (self._max_feature_idx + 1)

    def _load_from_string(self, s: str):
        self._model_version += 1
        lines = s.splitlines()
        header: Dict[str, str] = {}
        i = 0
        while i < len(lines) and not lines[i].startswith("Tree="):
            ln = lines[i]
            if "=" in ln:
                k, v = ln.split("=", 1)
                header[k] = v
            elif ln.strip() == "average_output":
                header["average_output"] = "1"
            i += 1
        self._average_output = "average_output" in header
        for ln in reversed(lines[-8:]):
            if ln.startswith("pandas_categorical:"):
                import json as _json
                val = ln.split(":", 1)[1]
                try:
                    self._pandas_categorical = _json.loads(val)
                except Exception:
                    self._pandas_categorical = None
                break
        self._num_class = int(header.get("num_class", "1"))
        self._max_feature_idx = int(header.get("max_feature_idx", "0"))
        obj = header.get("objective", "regression").split()
        self._objective_name = obj[0] if obj else "regression"
        self._feature_names = header.get("feature_names", "").split()
        self._feature_infos = header.get("feature_infos", "").split()
        self.params.setdefault("objective", self._objective_name)
        # objective SUFFIX tokens carry transform state the reloaded
        # predictor needs (ObjectiveFunction::ToString grammar):
        # "sigmoid:2" / "sqrt" / "tweedie_variance_power:p"
        for tok in obj[1:]:
            if tok == "sqrt":
                self.params.setdefault("reg_sqrt", True)
            elif ":" in tok:
                k, v = tok.split(":", 1)
                if k in ("sigmoid", "tweedie_variance_power", "alpha",
                         "fair_c", "poisson_max_delta_step"):
                    try:
                        self.params.setdefault(k, float(v))
                    except ValueError:
                        pass
        if self._num_class > 1:
            self.params["num_class"] = self._num_class
        self.config = Config({k: v for k, v in self.params.items()})
        self._objective = create_objective(self.config) \
            if self._objective_name != "custom" else None
        # split tree blocks
        rest = "\n".join(lines[i:])
        blocks = rest.split("Tree=")[1:]
        trees = []
        for b in blocks:
            b = b.split("end of trees")[0]
            trees.append(Tree.from_text("Tree=" + b))
        self._trees = trees

    # -- introspection -------------------------------------------------
    def num_trees(self) -> int:
        return len(self._all_trees())

    def current_iteration(self) -> int:
        return len(self._all_trees()) // max(1, self._num_class)

    def num_feature(self) -> int:
        return self._max_feature_idx + 1

    def num_model_per_iteration(self) -> int:
        """LGBM_BoosterNumModelPerIteration analog."""
        return max(1, self._num_class)

    def get_leaf_output(self, tree_id: int, leaf_id: int) -> float:
        """LGBM_BoosterGetLeafValue analog (shrinkage included)."""
        return float(self._all_trees()[tree_id].leaf_value[leaf_id])

    def set_leaf_output(self, tree_id: int, leaf_id: int,
                        value: float) -> "Booster":
        """LGBM_BoosterSetLeafValue analog: overwrite one leaf's output
        (model-surgery tools use this; prediction caches invalidate)."""
        self._all_trees()[tree_id].leaf_value[leaf_id] = float(value)
        self._model_version += 1
        return self

    def shuffle_models(self, start_iteration: int = 0,
                       end_iteration: int = -1) -> "Booster":
        """Randomly permute tree ITERATIONS in [start, end) —
        basic.py Booster.shuffle_models (LGBM_BoosterShuffleModels).
        Multiclass iterations move as whole per-class groups."""
        K = max(1, self._num_class)
        trees = self._all_trees()
        n_iter = len(trees) // K
        lo = max(0, start_iteration)
        hi = n_iter if end_iteration < 0 else min(end_iteration, n_iter)
        if hi - lo > 1:
            order = np.arange(lo, hi)
            np.random.shuffle(order)
            groups = [trees[i * K:(i + 1) * K] for i in range(n_iter)]
            shuffled = (groups[:lo] + [groups[i] for i in order]
                        + groups[hi:])
            flat = [t for g in shuffled for t in g]
            nb = len(self._base_trees)
            self._base_trees = flat[:nb]
            self._trees[:] = flat[nb:]
            self._model_version += 1
        return self

    def lower_bound(self) -> float:
        """Minimum possible raw output: sum of per-tree min leaf values
        (LGBM_BoosterGetLowerBoundValue)."""
        return float(sum(t.leaf_value.min() for t in self._all_trees()
                         if t.num_leaves > 0))

    def upper_bound(self) -> float:
        """Maximum possible raw output (LGBM_BoosterGetUpperBoundValue)."""
        return float(sum(t.leaf_value.max() for t in self._all_trees()
                         if t.num_leaves > 0))

    def trees_to_dataframe(self):
        """Model structure as a pandas DataFrame — same columns and node
        naming as the reference ``Booster.trees_to_dataframe``, built on
        top of ``dump_model()`` exactly like the reference (basic.py):
        one decoder, so categorical thresholds ("0||2||5") and
        missing_type strings match the JSON dump by construction."""
        import pandas as pd
        dump = self.dump_model()
        feat_names = dump["feature_names"]
        rows = []
        for tinfo in dump["tree_info"]:
            ti = tinfo["tree_index"]
            stack = [(tinfo["tree_structure"], 1, None)]
            while stack:
                node, depth_, parent_name = stack.pop()
                if "split_index" in node:
                    my = f"{ti}-S{node['split_index']}"

                    def cname(c):
                        return (f"{ti}-S{c['split_index']}"
                                if "split_index" in c
                                else f"{ti}-L{c.get('leaf_index', 0)}")
                    rows.append(dict(
                        tree_index=ti, node_depth=depth_, node_index=my,
                        left_child=cname(node["left_child"]),
                        right_child=cname(node["right_child"]),
                        parent_index=parent_name,
                        split_feature=feat_names[node["split_feature"]],
                        split_gain=node["split_gain"],
                        threshold=node["threshold"],
                        decision_type=node["decision_type"],
                        missing_direction=("left" if node["default_left"]
                                           else "right"),
                        missing_type=node["missing_type"],
                        value=node["internal_value"],
                        weight=node["internal_weight"],
                        count=node["internal_count"]))
                    stack.append((node["right_child"], depth_ + 1, my))
                    stack.append((node["left_child"], depth_ + 1, my))
                else:
                    rows.append(dict(
                        tree_index=ti, node_depth=depth_,
                        node_index=f"{ti}-L{node.get('leaf_index', 0)}",
                        left_child=None, right_child=None,
                        parent_index=parent_name, split_feature=None,
                        split_gain=None, threshold=None,
                        decision_type=None, missing_direction=None,
                        missing_type=None,
                        value=node["leaf_value"],
                        weight=node.get("leaf_weight"),
                        count=node.get("leaf_count")))
        return pd.DataFrame(rows)

    def feature_name(self) -> List[str]:
        return list(self._feature_names)

    def feature_importance(self, importance_type: str = "split",
                           iteration: Optional[int] = None) -> np.ndarray:
        nf = self._max_feature_idx + 1
        out = np.zeros(nf)
        for t in self._all_trees():
            if importance_type == "gain":
                out += t.feature_importance_gain(nf)
            else:
                out += t.feature_importance_split(nf)
        return out

    def free_dataset(self):
        return self

    def __copy__(self):
        return self.__deepcopy__(None)

    def __deepcopy__(self, memo):
        return Booster(model_str=self.model_to_string(),
                       params=dict(self.params))


class PredictSession:
    """Persistent prediction handle for the serving pattern: many
    ``predict()`` calls against one (slowly-mutating) model.

    What it caches, keyed by the Booster's model version:

    - the resolved tree window (``start_iteration``/``num_iteration`` →
      tree slice), computed once instead of per call;
    - the packed device ensemble and its jit-compiled executable (the
      Booster's ``(version, lo, hi)``-keyed pack plus XLA's trace
      cache), so repeated device predictions never re-pack or re-trace;
    - the native C model handle (via the Booster's version-keyed handle
      cache), whose flattened node layout is built once at load.

    Every cache invalidates when the model version moves (training,
    rollback, leaf surgery, model reload) — the next ``predict()``
    transparently rebuilds against the new trees.

    On the CPU backend, C-contiguous float32/float64 matrices of the
    training width hand off zero-copy into the native blocked kernel
    (``capi.c``); everything else falls back to ``Booster.predict``
    with identical results.

    Thread-safety contract (the serving micro-batcher relies on this):
    every version-dependent piece of state — model version, class
    count, window offset, tree slice — lives in ONE immutable snapshot
    tuple. ``predict()`` reads that reference exactly once and serves
    the whole call from it; ``_refresh()`` builds a complete new tuple
    and publishes it with a single reference assignment (atomic under
    the GIL). Concurrent ``predict()`` calls racing a version movement
    (train / rollback / model reload) therefore each resolve to one
    WHOLE snapshot — never an old window over new trees, which the
    previous field-at-a-time reads (`self._use` after
    ``b._model_version``) allowed. The snapshot's tree list is a slice
    copy, so later mutations of the Booster's tree list cannot reach
    it; in-place leaf surgery (``set_leaf_output``) concurrent with a
    predict remains outside the contract — the serving registry never
    mutates a registered model, it swaps in a new one.
    """

    def __init__(self, booster: Booster, *, start_iteration: int = 0,
                 num_iteration: Optional[int] = None,
                 raw_score: bool = False, pred_leaf: bool = False,
                 pred_contrib: bool = False, **kwargs):
        self.booster = booster
        self._start_iteration = start_iteration
        self._num_iteration = num_iteration
        self._raw_score = raw_score
        self._pred_leaf = pred_leaf
        self._pred_contrib = pred_contrib
        self._extra = dict(kwargs)
        self._refresh()

    def _refresh(self):
        """Resolve the tree window against the current model into a
        fresh ``(version, K, lo, trees)`` snapshot; publish and return
        it. Reads the version FIRST: if the model moves mid-build, the
        stale snapshot self-heals on the next predict's version check
        (worst case one extra refresh, never a mixed window)."""
        b = self.booster
        b._sync_trees()    # materialize any deferred fused-train trees
        version = b._model_version
        K = max(1, b._num_class)
        trees = b._all_trees()
        ni = self._num_iteration
        if ni is None or ni < 0:
            ni = (b.best_iteration if b.best_iteration > 0
                  else len(trees) // K)
        lo = self._start_iteration * K
        hi = min(len(trees), (self._start_iteration + ni) * K)
        snap = (version, K, lo, trees[lo:hi])
        self._snapshot = snap
        return snap

    # introspection views of the current snapshot (tests, debugging);
    # serving code must read self._snapshot once instead
    @property
    def _version(self):
        return self._snapshot[0]

    @property
    def _K(self):
        return self._snapshot[1]

    @property
    def _lo(self):
        return self._snapshot[2]

    @property
    def _use(self):
        return self._snapshot[3]

    def warmup(self, n_rows: int = 1024) -> "PredictSession":
        """Build every lazy cache now (native handle / packed ensemble /
        compiled executable) so the first real request pays nothing."""
        X = np.zeros((n_rows, self.booster._max_feature_idx + 1),
                     np.float32)
        self.predict(X)
        return self

    def predict(self, data) -> np.ndarray:
        b = self.booster
        snap = self._snapshot          # ONE read; see class contract
        if b._model_version != snap[0]:
            snap = self._refresh()
        _version, K, lo, use = snap
        fast = (not self._pred_leaf and not self._pred_contrib
                and isinstance(data, np.ndarray) and data.ndim == 2
                and data.dtype in (np.float32, np.float64)
                and data.flags.c_contiguous
                and data.shape[1] == b._max_feature_idx + 1
                and b._early_stop_config(self._extra) is None)
        if fast:
            raw = b._native_raw_scores(data, use, lo, K)
            if raw is not None:
                return b._finalize_scores(raw, use, K, self._raw_score)
        return b.predict(data, start_iteration=self._start_iteration,
                         num_iteration=self._num_iteration,
                         raw_score=self._raw_score,
                         pred_leaf=self._pred_leaf,
                         pred_contrib=self._pred_contrib, **self._extra)

    __call__ = predict


def train(params: Dict, train_set: Dataset, num_boost_round: int = 100,
          valid_sets: Optional[Sequence[Dataset]] = None,
          valid_names: Optional[Sequence[str]] = None,
          feval=None, init_model=None, keep_training_booster: bool = False,
          callbacks: Optional[Sequence[Callable]] = None,
          fobj=None) -> Booster:
    """Main training loop (engine.py:109 analog).

    Eval-cadence contract: callbacks and early stopping observe metrics
    every ``eval_period`` iterations (config.py; default 1 preserves
    per-iteration semantics exactly). Between eval points the fused
    trainer (boosting/gbdt.py) runs dispatch-ahead — one jit dispatch
    per iteration, zero host syncs — and no-split stop detection rides
    a device flag checked only at those sync points.

    Multi-chip merge contract: with ``tree_learner=data/voting`` on a
    multi-device mesh the per-round histogram merge defaults to the
    feature-slot reduce-scatter (``dp_hist_merge=auto``; see
    parallel/data_parallel.py). The scattered build nests inside the
    fused single-dispatch trace unchanged — the plan's shard_map
    program, its ``lax.psum_scatter`` and its SplitInfo winner sync are
    all staged into the one jitted iteration, so dispatch-ahead and the
    halved histogram traffic compose. ``dp_hist_merge=allreduce`` (or
    ``LIGHTGBM_TPU_DP_HIST_MERGE=allreduce``) pins the replicated-psum
    baseline; results are bit-identical either way.
    """
    params = dict(params or {})
    cfg = Config(params)
    log.set_verbosity(int(cfg.verbosity))
    if str(cfg.on_device_loss) == "degrade":
        # supervised mode: each attempt re-enters train() with
        # on_device_loss=fail (set by the supervisor), so this gate
        # fires exactly once per user call
        from .resilience.supervisor import supervised_train
        return supervised_train(
            train, params, train_set, num_boost_round,
            valid_sets=valid_sets, valid_names=valid_names, feval=feval,
            init_model=init_model,
            keep_training_booster=keep_training_booster,
            callbacks=callbacks, fobj=fobj)
    enable_compilation_cache()
    if "num_iterations" in cfg.explicit():  # any registered alias resolves
        num_boost_round = cfg.num_iterations
    if callable(params.get("objective")):
        fobj = params["objective"]
        params["objective"] = "custom"

    # continued training: predict init scores BEFORE Dataset.construct
    # frees the raw matrices (predictor flow of engine.py:234-246)
    base = None
    base_train_scores = None
    base_valid_scores = None
    if init_model is not None:
        base = (init_model if isinstance(init_model, Booster)
                else Booster(model_file=str(init_model)))
        if train_set._raw_data is None:
            raise ValueError(
                "init_model needs the training Dataset's raw data; use "
                "free_raw_data=False or an unconstructed Dataset")
        base_train_scores = base.predict(train_set._raw_data,
                                         raw_score=True)
        base_valid_scores = []
        for vs in (valid_sets or []):
            if vs is train_set:
                continue
            if vs._raw_data is None:
                raise ValueError(
                    "init_model needs each validation Dataset's raw data; "
                    "use free_raw_data=False or an unconstructed Dataset")
            base_valid_scores.append(base.predict(vs._raw_data,
                                                  raw_score=True))

    booster = Booster(params=params, train_set=train_set)
    if valid_sets:
        valid_names = list(valid_names or [])
        for i, vs in enumerate(valid_sets):
            if vs is train_set:
                continue  # training data is evaluated anyway
            name = valid_names[i] if i < len(valid_names) else f"valid_{i}"
            booster.add_valid(vs, name)
    if base is not None:
        booster._set_init_model(base, base_train_scores, base_valid_scores)

    callbacks = list(callbacks or [])
    if cfg.early_stopping_round and cfg.early_stopping_round > 0:
        from .callback import early_stopping
        callbacks.append(early_stopping(
            cfg.early_stopping_round,
            first_metric_only=cfg.first_metric_only,
            min_delta=cfg.early_stopping_min_delta))
    if cfg.verbosity >= 1 and not any(
            getattr(cb, "order", None) == 10 and
            not getattr(cb, "before_iteration", False)
            for cb in callbacks):
        pass  # reference only logs when log_evaluation is requested
    callbacks_before = [cb for cb in callbacks
                        if getattr(cb, "before_iteration", False)]
    callbacks_after = [cb for cb in callbacks
                       if not getattr(cb, "before_iteration", False)]
    callbacks_before.sort(key=lambda cb: getattr(cb, "order", 0))
    callbacks_after.sort(key=lambda cb: getattr(cb, "order", 0))

    # metric-consumption (callback.py contract): skip metric work no
    # after-callback will read. Train-set eval additionally requires a
    # callback that consumes TRAINING entries — early stopping never
    # does — so is_provide_training_metric with only early stopping
    # active no longer pays a full train eval per eval point.
    eval_consumers = [cb for cb in callbacks_after
                      if getattr(cb, "needs_eval", True)]
    train_metric_consumers = [
        cb for cb in callbacks_after
        if getattr(cb, "consumes_train_metrics", True)]
    eval_period = max(1, int(cfg.eval_period))

    # continued training iterates [init_iteration, init_iteration + rounds)
    # (reference engine.py:309 `range(init_iteration, init_iteration +
    # num_boost_round)`) so best_iteration indexes the FULL ensemble —
    # predict()'s _all_trees() slice depends on this.
    init_iteration = booster.current_iteration()
    end_iteration = init_iteration + num_boost_round

    # -- fault tolerance (resilience subsystem) ----------------------
    from .resilience import (
        NumericDivergenceError, PreemptionGuard, TrainingPreempted,
        checkpoint_path, config_fingerprint, find_resume_checkpoint,
        prune_numbered, read_checkpoint, restore_training_checkpoint,
        topology_descriptor, write_training_checkpoint)
    resume = str(cfg.resume)
    resume_on = resume != "off"
    nan_guard = str(cfg.nan_guard)
    # -- runtime telemetry (telemetry subsystem) ---------------------
    # None unless telemetry_port/event_log (or the env var) opt in; all
    # session hooks below run at points that have already synced, so a
    # telemetry-enabled run issues the same device syncs as a bare one.
    from .telemetry import TelemetrySession
    tele = TelemetrySession.from_config(cfg, params)
    fingerprint = (config_fingerprint(params)
                   if resume_on or tele is not None else None)
    # cadence_base anchors the eval/snapshot cadence. A resumed run
    # must reuse the ORIGINAL run's anchor — recomputing it from the
    # restored iteration would shift every sync point and early
    # stopping would observe different metrics than the uninterrupted
    # run.
    cadence_base = init_iteration

    reshard_from = None   # checkpoint topology, when it differed

    def _restore(state, arrays, texts):
        nonlocal cadence_base, end_iteration, reshard_from
        booster._ensure_gbdt()
        restore_training_checkpoint(booster, callbacks, state, arrays,
                                    texts)
        cadence_base = int(state.get("begin_iteration", cadence_base))
        rec_end = int(state.get("end_iteration", end_iteration))
        if rec_end != end_iteration:
            log.info(f"resume: continuing to the original run's "
                     f"end_iteration={rec_end} "
                     f"(num_boost_round ignored)")
            end_iteration = rec_end
        # elastic resume: the checkpoint records the topology it was
        # written under; when this process runs a different one the
        # restore above already re-sharded — record the transition
        rec_topo = state.get("topology")
        cur_topo = topology_descriptor(booster._gbdt)
        if rec_topo and rec_topo != cur_topo:
            reshard_from = rec_topo
            log.info(
                "resume: topology changed since the checkpoint "
                f"({rec_topo.get('parallel_mode')}x"
                f"{rec_topo.get('num_shards')} "
                f"{rec_topo.get('dp_hist_merge') or 'serial'} -> "
                f"{cur_topo.get('parallel_mode')}x"
                f"{cur_topo.get('num_shards')} "
                f"{cur_topo.get('dp_hist_merge') or 'serial'}); "
                "state re-sharded onto the current mesh")

    # periodic checkpoint-write failures (ENOSPC, EROFS) must not kill
    # a healthy run: warn + record, skip `streak - 1` boundaries as
    # backoff, and only raise once _CKPT_FAIL_LIMIT consecutive writes
    # failed. The preemption-path write stays fatal (the process is
    # about to exit; losing that write loses the drained state).
    _CKPT_FAIL_LIMIT = 3
    ckpt_fail_streak = 0
    ckpt_skip = 0

    def _write_ckpt(iteration: int, final: bool = False):
        nonlocal ckpt_fail_streak, ckpt_skip
        if ckpt_skip > 0 and not final:
            ckpt_skip -= 1
            return None
        path = checkpoint_path(cfg.output_model, iteration)
        try:
            with profiler.span("engine.checkpoint"):
                write_training_checkpoint(
                    path, booster, callbacks, begin_iteration=cadence_base,
                    end_iteration=end_iteration, params=params)
        except OSError as e:
            ckpt_fail_streak += 1
            if final or ckpt_fail_streak >= _CKPT_FAIL_LIMIT:
                raise
            ckpt_skip = ckpt_fail_streak - 1
            log.warning(
                f"checkpoint write failed ({e}); continuing and "
                f"retrying at a later snapshot boundary "
                f"({ckpt_fail_streak}/{_CKPT_FAIL_LIMIT} consecutive "
                "failures before this becomes fatal)")
            if tele is not None:
                tele.on_checkpoint("write", iteration, path, ok=False)
            return None
        ckpt_fail_streak = 0
        ckpt_skip = 0
        prune_numbered(cfg.output_model + ".ckpt_iter_",
                       cfg.snapshot_keep)
        if tele is not None:
            tele.on_checkpoint("write", iteration, path)
        return path

    resumed_from = None
    if resume_on:
        if init_model is not None:
            raise ValueError(
                "resume cannot be combined with init_model: the "
                "checkpoint already carries the full ensemble and "
                "training state")
        if resume == "auto":
            ckpt = find_resume_checkpoint(cfg.output_model, fingerprint)
        else:
            ckpt = resume  # explicit path: read below (raises if corrupt)
        if ckpt is not None:
            state, arrays, texts = read_checkpoint(ckpt)
            _restore(state, arrays, texts)
            resumed_from = (str(ckpt), booster.current_iteration())
            log.info(f"resume: restored {ckpt} at iteration "
                     f"{booster.current_iteration()}")
    elif nan_guard == "rollback":
        log.warning("nan_guard=rollback needs resume checkpoints to "
                    "roll back to (resume=off); divergence will raise "
                    "instead")

    if tele is not None:
        # after any resume restore: begin_run splices the event log to
        # the restored iteration, then re-emits the run header (same
        # fingerprint) so the resumed record chain reads uninterrupted
        tele.begin_run(booster, cfg, params, fingerprint,
                       resumed_from=resumed_from)
        if reshard_from is not None:
            tele.on_reshard(booster.current_iteration(), reshard_from,
                            topology_descriptor(booster._gbdt))

    import os as _os
    chaos_kill_iter = _os.environ.get("LIGHTGBM_TPU_CHAOS_KILL_ITER")
    chaos_kill_iter = (int(chaos_kill_iter)
                       if chaos_kill_iter is not None else None)

    def _chaos_kill(iteration: int) -> None:
        # fault-injection hook (scripts/chaos_train.py): die right
        # after the iteration's work — including any snapshot/
        # checkpoint persistence — finishes
        if chaos_kill_iter is None or iteration + 1 != chaos_kill_iter:
            return
        import signal as _signal
        sig = (_signal.SIGTERM
               if _os.environ.get("LIGHTGBM_TPU_CHAOS_KILL_SIGNAL",
                                  "KILL") == "TERM"
               else _signal.SIGKILL)
        _os.kill(_os.getpid(), sig)

    rollback_budget = 2

    guard = PreemptionGuard(enabled=resume_on)
    ok = False
    try:
        with guard:
            i = booster.current_iteration()
            while i < end_iteration:
                if guard.fired:
                    # SIGTERM/SIGINT: drain the pending device ring (the
                    # checkpoint capture syncs), persist, exit cleanly
                    path = _write_ckpt(booster.current_iteration(),
                                       final=True)
                    if guard.deadline_exceeded():
                        log.warning("preemption drain exceeded the "
                                    f"{guard.deadline_s:g}s deadline")
                    if tele is not None:
                        tele.on_preemption(guard.signum,
                                           booster.current_iteration())
                    raise TrainingPreempted(guard.signum,
                                            booster.current_iteration(),
                                            path)
                env_before = CallbackEnv(booster, params, i, cadence_base,
                                         end_iteration, None)
                for cb in callbacks_before:
                    cb(env_before)
                snapshot_here = (cfg.snapshot_freq > 0
                                 and (i + 1) % cfg.snapshot_freq == 0)
                # sync points: every eval_period-th iteration, the final
                # one, and snapshot boundaries. Between them the fused
                # trainer defers — trees stay on device, no host syncs.
                sync_here = ((i - cadence_base + 1) % eval_period == 0
                             or i == end_iteration - 1 or snapshot_here)
                try:
                    # step marker for jax.profiler traces (profiler.trace)
                    # — the per-iteration timing hook of gbdt.cpp:246-249
                    with profiler.step_annotation("boost_iter", step_num=i):
                        stop = booster.update(fobj=fobj, defer=not sync_here)
                except NumericDivergenceError as e:
                    if nan_guard != "rollback" or not resume_on:
                        if tele is not None:
                            tele.on_nan_guard(getattr(e, "iteration", i + 1),
                                              nan_guard, "raise")
                        raise
                    ckpt = find_resume_checkpoint(cfg.output_model,
                                                  fingerprint)
                    if ckpt is None or rollback_budget <= 0:
                        log.warning(
                            "nan_guard: no checkpoint to roll back to"
                            if ckpt is None else
                            "nan_guard: rollback budget exhausted "
                            "(deterministic divergence)")
                        if tele is not None:
                            tele.on_nan_guard(getattr(e, "iteration", i + 1),
                                              nan_guard, "raise")
                        raise
                    rollback_budget -= 1
                    state, arrays, texts = read_checkpoint(ckpt)
                    _restore(state, arrays, texts)
                    log.warning(
                        f"nan_guard incident: {e}; rolled back to {ckpt} "
                        f"(iteration {booster.current_iteration()}) and "
                        "re-running")
                    if tele is not None:
                        tele.on_nan_guard(getattr(e, "iteration", i + 1),
                                          nan_guard, "rollback")
                        tele.on_checkpoint("restore",
                                           booster.current_iteration(),
                                           str(ckpt))
                    i = booster.current_iteration()
                    continue
                if not (sync_here or stop):
                    _chaos_kill(i)
                    i += 1
                    continue
                evals = []
                need_eval = bool(eval_consumers) or cfg.early_stopping_round > 0
                if need_eval:
                    with profiler.span("engine.eval"), \
                            profiler.stage("eval"):
                        if cfg.is_provide_training_metric and (
                                train_metric_consumers or not callbacks_after):
                            evals.extend(booster.eval_train(feval))
                        evals.extend(booster.eval_valid(feval))
                if tele is not None:
                    # the eval-cadence sync point: booster.update just
                    # drained the ring, evals are host floats — the
                    # iteration record costs no extra device sync
                    tele.on_sync(i + 1, evals)
                env = CallbackEnv(booster, params, i, cadence_base,
                                  end_iteration, evals)
                try:
                    for cb in callbacks_after:
                        cb(env)
                except EarlyStopException as e:
                    booster.best_iteration = e.best_iteration + 1
                    for name, metric, value, _ in (e.best_score or []):
                        booster.best_score.setdefault(name, {})[metric] = value
                    if tele is not None:
                        tele.on_early_stop(i + 1, booster.best_iteration)
                    break
                if snapshot_here:
                    # periodic checkpoint (gbdt.cpp:250-254): full model
                    # text, resumable via init_model (atomic since the
                    # resilience PR), with snapshot_keep retention
                    booster.save_model(
                        f"{cfg.output_model}.snapshot_iter_{i + 1}")
                    prune_numbered(cfg.output_model + ".snapshot_iter_",
                                   cfg.snapshot_keep)
                    if resume_on:
                        _write_ckpt(i + 1)
                _chaos_kill(i)
                if stop:
                    break
                i += 1
        ok = True
    finally:
        if tele is not None:
            # ended=False (fault unwinding) suppresses train_end
            # so the fault record stays the log's last word
            tele.close(ended=ok)
    return booster


class CVBooster:
    """Container of per-fold boosters (engine.py:354 analog)."""

    def __init__(self):
        self.boosters: List[Booster] = []
        self.best_iteration = -1

    def append(self, booster: Booster):
        self.boosters.append(booster)

    def __getattr__(self, name):
        def handler(*args, **kwargs):
            return [getattr(b, name)(*args, **kwargs)
                    for b in self.boosters]
        return handler


def cv(params: Dict, train_set: Dataset, num_boost_round: int = 100,
       folds=None, nfold: int = 5, stratified: bool = True, shuffle: bool = True,
       metrics=None, seed: int = 0, callbacks=None,
       eval_train_metric: bool = False,
       return_cvbooster: bool = False) -> Dict[str, List[float]]:
    """K-fold cross-validation (engine.py:625 analog)."""
    params = dict(params or {})
    if metrics is not None:
        params["metric"] = metrics
    train_set.construct()
    label = train_set.get_label()
    n = train_set.num_data
    rng = np.random.RandomState(seed)

    weight = train_set.get_weight()
    group = train_set.get_group()
    init_score = train_set.get_init_score()

    if folds is None:
        if group is not None:
            # group-aware folds: split whole queries (engine.py _make_n_folds
            # uses GroupKFold semantics for ranking)
            qb = train_set.query_boundaries()
            qidx = np.arange(len(group))
            if shuffle:
                rng.shuffle(qidx)
            qparts = np.array_split(qidx, nfold)
            folds = []
            for f in range(nfold):
                te_q = np.sort(qparts[f])
                te = np.concatenate([np.arange(qb[q], qb[q + 1])
                                     for q in te_q])
                folds.append((np.setdiff1d(np.arange(n), te), te))
        elif stratified and Config(params).objective in ("binary",
                                                         "multiclass",
                                                         "multiclassova"):
            idx = np.arange(n)
            folds_idx = [[] for _ in range(nfold)]
            for cls in np.unique(label):
                ci = idx[label == cls]
                if shuffle:
                    rng.shuffle(ci)
                for f in range(nfold):
                    folds_idx[f].extend(ci[f::nfold])
            folds = [(np.setdiff1d(idx, np.asarray(te)), np.asarray(te))
                     for te in folds_idx]
        else:
            idx = np.arange(n)
            if shuffle:
                rng.shuffle(idx)
            parts = np.array_split(idx, nfold)
            folds = [(np.concatenate([parts[j] for j in range(nfold)
                                      if j != f]), parts[f])
                     for f in range(nfold)]

    raw = train_set._raw_data
    if raw is None:
        raise ValueError("cv requires train_set with free_raw_data=False")
    from .dataset import _is_pandas_df as _is_pd
    if _is_pd(raw):
        def X_rows(ix):   # keep the frame: category dtypes must survive
            return raw.iloc[ix]
    else:
        _X = np.asarray(raw, dtype=np.float64)

        def X_rows(ix):
            return _X[ix]

    def _group_sizes(row_idx):
        if group is None:
            return None
        qb = train_set.query_boundaries()
        qid = np.searchsorted(qb, row_idx, side="right") - 1
        _, sizes = np.unique(qid, return_counts=True)
        return sizes

    # per-fold boosters train in LOCKSTEP, one round each per cv round,
    # so callbacks (and early stopping in particular) see the
    # cross-fold AGGREGATED metrics — the reference's design
    # (engine.py:625 cv loop + _agg_cv_result)
    cvb = CVBooster()
    for tr_idx, te_idx in folds:
        dtrain = Dataset(X_rows(tr_idx), label=label[tr_idx],
                         weight=None if weight is None else weight[tr_idx],
                         group=_group_sizes(tr_idx),
                         init_score=None if init_score is None
                         else init_score[tr_idx],
                         params=dict(train_set.params))
        dvalid = Dataset(X_rows(te_idx), label=label[te_idx],
                         weight=None if weight is None else weight[te_idx],
                         group=_group_sizes(te_idx),
                         init_score=None if init_score is None
                         else init_score[te_idx], reference=dtrain)
        bst = Booster(dict(params), dtrain)
        bst.add_valid(dvalid, "valid")
        cvb.append(bst)

    cbs = list(callbacks or [])
    cfg_cv = Config(params)
    if cfg_cv.early_stopping_round and cfg_cv.early_stopping_round > 0 \
            and not any(getattr(c, "order", 0) == 30 for c in cbs):
        from .callback import early_stopping as _es
        cbs.append(_es(cfg_cv.early_stopping_round,
                       first_metric_only=bool(cfg_cv.first_metric_only),
                       min_delta=cfg_cv.early_stopping_min_delta))
    cbs = sorted(cbs, key=lambda c: getattr(c, "order", 0))
    cbs_before = [c for c in cbs if getattr(c, "before_iteration", False)]
    cbs_after = [c for c in cbs if not getattr(c, "before_iteration",
                                               False)]
    results: Dict[str, List[float]] = {}
    name_map = {"training": "train"}  # reference cv key naming
    for it in range(num_boost_round):
        for cb in cbs_before:
            cb(CallbackEnv(cvb, params, it, 0, num_boost_round, None))
        finished = True
        for bst in cvb.boosters:
            finished = bst.update() and finished
        # aggregate fold metrics: mean/stdv per (dataset, metric)
        agg = collections.OrderedDict()
        for bst in cvb.boosters:
            res = list(bst.eval_valid())
            if eval_train_metric:
                res = list(bst.eval_train()) + res
            for nm, metric, value, bigger in res:
                nm = name_map.get(nm, nm)
                agg.setdefault((nm, metric), ([], bigger))[0].append(value)
        eval_list = []
        for (nm, metric), (vals, bigger) in agg.items():
            mean, std = float(np.mean(vals)), float(np.std(vals))
            results.setdefault(f"{nm} {metric}-mean", []).append(mean)
            results.setdefault(f"{nm} {metric}-stdv", []).append(std)
            eval_list.append(("cv_agg", f"{nm} {metric}", mean, bigger))
        try:
            for cb in cbs_after:
                cb(CallbackEnv(cvb, params, it, 0, num_boost_round,
                               eval_list))
        except EarlyStopException as e:
            cvb.best_iteration = e.best_iteration + 1
            for k in list(results):
                results[k] = results[k][:cvb.best_iteration]
            for bst in cvb.boosters:
                bst.best_iteration = cvb.best_iteration
            break
        if finished:
            break
    if return_cvbooster:
        results["cvbooster"] = cvb
    return results
