"""Job kind ``train-sparse``: one closed-loop training job on rows handed
over as a ``scipy.sparse`` CSR matrix, trees back to back. As ``train``
(same window, same two end-to-end metrics, the same
``Booster.update(defer=True)`` loop), but the Dataset is the program's own
work from first to last: ``lgb.Dataset(csr, label=...)`` through the public
constructor over **all** rows. The job neither bins nor encodes for the
program (the dense jobs bin their columns themselves because host binning
is slow; here the sparse ingest *is* the mechanism, and ``setup_s`` is what
the user waits for), and the Dataset **must** come back bundled.

Before JAX touches a device the job asks the program for what the cell
reads: the ``unbundle`` stage and the two ingest spans
(``dataset.plan_bundles``, ``dataset.encode_bundles``). A program without
them (a parent commit) densifies every column of every row on the host and
would not finish: it is refused with :class:`CannotRunCell` within a
second, with no data drawn and the chip never taken.

``correct`` is made of answers only:

``encoding``  the program's ``[rows, stored columns]`` matrix equals the
    plain reference's own encoding of the CSR rows bit for bit, and its
    ``efb.conflict_rows`` the reference's count
    (``reference/gbdt_sparse_reference.py``, rule (a)).
``tree_replay``  tree 0 against the reference on all rows, from the stored
    values: root and next four nodes the best split over all features or
    within 2^-11 of its gain, leaf counts equal a replay, leaf values
    within 2^-11 x sum|g| / (H + l2), bfloat16 addends, the 2^-14
    rounding-boundary rule (rule (b)).
``loss_fell``, ``no_failed_tree``, ``no_compile_in_window``, ``no_pins``  as
    ``train``.
``bundled``, ``stored_columns_as_stated``, ``fused_step``,
    ``hist_subtraction_on``, ``kernel_as_stated``  what the program resolved
    is what the configuration states under ``expect`` (the kernel only on a
    TPU).

The yardstick's own control is a **note**: the reference fed float8-e4m3
addends against the same tree and counts has to read not ok
(``compared.control_*``). Every number ``correct`` compared is printed
beside its limit in the job's last note, ``compared``.
"""

from __future__ import annotations

import math
import os
import shutil
import time

import numpy as np

from harness import device, trace as trace_mod, workers
from harness.manifest import _load_module
from reference import gbdt_reference as ref
from reference import gbdt_sparse_reference as spref

# the compile-cache rule, the model text's leaf counts and the pin prefix
# are job kind ``train``'s
_train = _load_module(os.path.join(os.path.dirname(__file__), "train.py"),
                      "job kind 'train'")
_compile_cache = _train._compile_cache
_tree_leaves = _train._tree_leaves
PIN_PREFIX = _train.PIN_PREFIX

INGEST_SPANS = ("dataset.fit_bins", "dataset.plan_bundles",
                "dataset.apply_bins", "dataset.encode_bundles")
SHARED = ("indptr", "indices", "values", "positive", "bins")


class CannotRunCell(RuntimeError):
    """The program cannot run this cell; said before any data is drawn."""


def refuse_unless_supported() -> None:
    """The program names the unbundling and the sparse ingest's two spans,
    or :class:`CannotRunCell`. Looks at one module; JAX's backend is not
    started by it."""
    from lightgbm_tpu import phases
    if not getattr(phases, "UNBUNDLE", None) \
            or not {"dataset.plan_bundles",
                    "dataset.encode_bundles"} <= set(phases.HOST_SPANS):
        raise CannotRunCell(
            "the program has no stage 'unbundle' and no spans "
            "dataset.plan_bundles / dataset.encode_bundles: its sparse "
            "ingest densifies every column of every row on the host "
            "(13,184,290 x 4,228 would not finish), and the cell's "
            "metrics have no source")


def _make_dataset(env, lgb, params):
    """(the program's Dataset, the CSR matrix, the labels, every column's
    bin upper bounds). The rows are drawn as CSR (into the arrays the
    worker processes share, where there are any) and handed to
    ``lgb.Dataset`` whole; everything else is the program's."""
    import scipy.sparse as sp
    cfg = env.config
    rows, cols = cfg["shape"]["rows"], cfg["shape"]["cols"]
    gen = env.manifest.generator(cfg["generator"]["name"])
    gp = cfg["generator"].get("params", {})
    held = {k: workers.SHARED.get(k) for k in ("indices", "values", "y")}
    out = None
    if all(v is not None for v in held.values()) and len(held["y"]) == rows:
        out = (held["indices"], held["values"], held["y"])
    x, y = gen.generate_csr(rows, cols, env.seed, gp, out=out)
    if not sp.isspmatrix_csr(x) or x.dtype != np.float32 \
            or x.indices.dtype != np.int32:
        raise RuntimeError("the generator hands over float32 CSR with "
                           "int32 indices")
    spans = getattr(env, "spans", None)
    t0 = time.perf_counter()
    ds = lgb.Dataset(x, label=y, params=params).construct()
    if spans is not None:
        spans.seconds["setup.data.dataset"] = time.perf_counter() - t0
    if ds.bundle_plan is None:
        raise RuntimeError("the Dataset came back one column a feature: "
                           f"{ds.bins.shape[1]} stored columns")
    ubs = [np.asarray(m.bin_upper_bound, np.float64)
           for m in ds.bin_mappers]
    return ds, x, y, ubs


def _plan_tables(ds) -> dict:
    """The bundle plan as data for the reference: by raw column (every
    column is used, or the check of the stored columns fails) its stored
    column, its offset there and its most frequent bin, and the order the
    members are written in."""
    bp = ds.bundle_plan
    return {"columns": int(bp.num_bundles),
            # a stored column's members are written in this order
            "order": np.arange(len(bp.feat_bundle)),
            "column": np.asarray(bp.feat_bundle, np.int64),
            "offset": np.asarray(bp.feat_offset, np.int64),
            "most_frequent": np.asarray(bp.feat_mfb, np.int64)}


def _program_spans(names) -> dict:
    """Seconds and fields of the program's newest span of each name."""
    from lightgbm_tpu import profiler
    out = {}
    for n in names:
        got = profiler.recorder.spans(n)
        if got:
            out[n] = dict(got[-1].fields, seconds=got[-1].seconds)
    return out


def _stage_seconds(trace_dir: str, maps) -> dict:
    """Device self seconds by the program's stage, from the program's own
    reduction of the capture and the fused step's stage map."""
    from lightgbm_tpu.telemetry import xprof
    prof = xprof.parse_trace(trace_dir, phase_maps=maps)
    return {k: float(v) for k, v in prof.device_phase_s.items()}


def run(env) -> dict:
    spans = env.spans
    cfg, mix = env.config, env.traffic
    if mix.get("sync_between_trees") or mix.get("valid_sets"):
        raise NotImplementedError("this job kind runs trees back to back, "
                                  "with no sync and no validation set")
    rows = cfg["shape"]["rows"]
    per_row = int(cfg["shape"]["stored_values_per_row"])
    with spans.span("setup.import"):   # the program and JAX; no device yet
        try:
            import lightgbm_tpu as lgb
        except ImportError as e:
            raise CannotRunCell(f"the program cannot be imported: {e}") from e
    with spans.span("setup.refusal"):
        refuse_unless_supported()
    with spans.span("setup.workers"):
        # forked now, before JAX asks for a device: they walk the
        # reference's blocks after the window, in numpy alone, over the
        # CSR arrays and the program's matrix in shared memory
        workers.shared_empty("indices", (rows * per_row,), np.int32)
        workers.shared_empty("values", (rows * per_row,), np.float32)
        workers.shared_empty("y", (rows,), np.float32)
        workers.shared_empty("positive", (rows,), np.bool_)
        workers.shared_empty(
            "bins", (rows, int(cfg["expect"]["stored_columns"])), np.uint8)
        workers.shared_empty("indptr", (rows + 1,), np.int64)
        pool = workers.pool(spref._threads())
    try:
        return _run(env, lgb, pool)
    finally:
        pool.terminate()
        workers.SHARED.clear()


def _run(env, lgb, pool) -> dict:
    spans, note = env.spans, env.note
    cfg = env.config
    rows, cols = cfg["shape"]["rows"], cfg["shape"]["cols"]
    params = dict(cfg["params"], verbosity=-1)
    expect = cfg["expect"]
    with spans.span("setup.jax_init"):
        import jax
        info = device.require_tpu(env.chips) if env.require_tpu \
            else device.device_info()
        cache_dir = _compile_cache(lgb, jax)
    compiles = env.compile_counter()
    note("device", info)
    note("compile_cache", {"dir": cache_dir, "env_set": bool(
        os.environ.get("JAX_COMPILATION_CACHE_DIR"))})

    with spans.span("setup.data"):
        ds, x, y, ubs = _make_dataset(env, lgb, params)
    ingest = dict(ds.ingest_counters, stored_values=int(x.nnz))
    ingest_spans = _program_spans(INGEST_SPANS)
    plan = _plan_tables(ds)
    stored_columns = int(ds.bins.shape[1])
    with spans.span("setup.share"):
        # what the reference's workers read after the window
        workers.SHARED["indptr"][:] = x.indptr
        workers.SHARED["positive"][:] = y > 0
        shared_bins = workers.SHARED["bins"]
        if shared_bins.shape == ds.bins.shape:
            shared_bins[:] = ds.bins
    rows_lost = int(ds.efb_conflict_rows)

    with spans.span("setup.first_dispatch"):
        bst = lgb.Booster(params, ds)
        bst.update(defer=True)
        t_dispatched = time.perf_counter()
        gb = bst._gbdt
        jax.block_until_ready(gb.scores)
        t_tree = time.perf_counter() - t_dispatched
    with spans.span("setup.loss"):
        loss = [ref.binary_logloss(y, gb.eval_scores(-1)[:, 0])]
    trees = max(1, math.ceil(env.seconds / t_tree))

    trace_dir = os.path.join(env.manifest.root, ".bench_cache", "trace",
                             env.cell["name"])
    maps = {}
    if env.trace:
        with spans.span("setup.stage_map"):
            # the compiled step's text once more, for the stage map: the
            # executable is the one tree 0 made
            from lightgbm_tpu.telemetry import costmodel
            maps = costmodel.booster_phase_maps(bst, force=False)
        with spans.span("setup.trace_start"):
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        spans.annotate = True
    compiles_setup = compiles.count

    syncs0 = gb.host_sync_count
    setup_s = time.time() - env.t_start
    t0 = time.perf_counter()
    with spans.span("window"):
        with spans.span("window.update"):
            for _ in range(trees):
                bst.update(defer=True)
        with spans.span("window.block"):
            jax.block_until_ready(gb.scores)
    window_s = time.perf_counter() - t0
    compiles_window = compiles.count - compiles_setup
    spans.annotate = False

    report, stage_s = None, None
    if env.trace:
        with spans.span("after.trace"):
            jax.profiler.stop_trace()
            # the lattice the kernel is asked for: stored columns
            report = trace_mod.reduce_xplane(
                trace_dir, rows, stored_columns, expect["kernel_op_pattern"])
            stage_s = _stage_seconds(trace_dir, maps)
            shutil.rmtree(trace_dir, ignore_errors=True)

    with spans.span("after.sync_trees"):
        bst._sync_trees()
    host_syncs = gb.host_sync_count - syncs0
    memory = device.memory_by_device()
    with spans.span("after.loss"):
        loss.append(ref.binary_logloss(y, gb.eval_scores(-1)[:, 0]))
    model_text = bst.model_to_string()
    addends = str(gb.config.hist_dtype)
    with spans.span("after.reference"):
        # the workers read the program's matrix from shared memory; with
        # another count of stored columns than stated it is not there,
        # and the reference walks the blocks on threads here
        run = None
        if shared_bins.shape == ds.bins.shape:
            def run(groups):
                return workers.starmap(
                    pool, workers.call_on_shared,
                    [(spref.spans_report, SHARED) + g for g in groups])
        replay = spref.check(model_text, ubs, x, y, params, plan, ds.bins,
                             rows_lost, addend_dtype=addends,
                             parts=4 * spref._threads(), run=run)

    leaves = _tree_leaves(model_text)
    done = sum(1 for n in leaves[1:1 + trees] if n > 1)
    resolved = {"hist_impl": gb.config.hist_impl,
                "hist_impl_reason": gb.hist_impl_reason,
                "fused_reason": gb.fused_reason,
                "hist_subtraction": bool(gb._hist_sub),
                "tree_learner": gb.config.tree_learner,
                "leaf_batch": int(gb.config.leaf_batch),
                "hist_dtype": addends}
    pins = sorted(k for k in os.environ if k.startswith(PIN_PREFIX))
    checks = {
        "encoding": replay["encoding"]["ok"],
        "tree_replay": replay["tree_ok"],
        "loss_fell": bool(loss[1] < loss[0]),
        "no_failed_tree": done == trees and leaves[0] > 1,
        "no_compile_in_window": compiles_window == 0,
        "bundled": ds.bundle_plan is not None,
        "stored_columns_as_stated":
            stored_columns == expect["stored_columns"]
            and int(ds.bundle_plan.max_bundle_bins)
            == expect["max_bundle_bins"]
            and ingest["features_used"] == cols,
        "fused_step": resolved["fused_reason"] == "",
        "hist_subtraction_on": resolved["hist_subtraction"],
        # off a TPU (a rehearsal) the program picks its CPU kernel by rule
        "kernel_as_stated": (resolved["hist_impl"] == expect["hist_impl"]
                             if info["platform"] == "tpu" else None),
        "no_pins": not pins,
    }
    note("checks", checks)
    note("resolved", resolved)
    note("pins", pins)
    note("ingest", ingest)
    note("ingest_spans", ingest_spans)
    note("loss", loss)
    note("window", {"trees": trees, "window_s": window_s,
                    "first_tree_device_s": t_tree, "leaves": leaves})
    note("replay", replay)
    note("compared", {
        "encoding_unequal_blocks": replay["encoding"]["n_unequal_blocks"],
        "rows_lost": replay["encoding"]["rows_lost"],
        "program_rows_lost": rows_lost,
        "splits": [{k: s[k] for k in ("node", "gain_short_by", "limit", "ok")}
                   for s in replay["splits"]],
        "leaf_counts_equal": replay["leaves"]["counts_ok"],
        "all_rows_reach_a_leaf": replay["all_rows_reach_a_leaf"],
        "leaf_error_over_limit": replay["leaves"]["worst_error_over_limit"],
        "leaf_error_over_scale": replay["leaves"]["worst_error_over_scale"],
        "leaf_limit": replay["leaves"]["limit"],
        "leaf_rows": replay["leaves"]["worst_leaf"]["rows"],
        # the control has to read over 1: float8 addends, same tree
        "control_error_over_limit":
            replay["control"]["worst_error_over_limit"],
        "control_ok": replay["control"]["ok"],
        "addends": {k: {"value": a["value"],
                        "boundary_distance": a["boundary_distance"],
                        "margin": a.get("margin"),
                        "used": replay["addends_used"][k]}
                    for k, a in replay["addends"].items()},
        "roundings_tried": replay["roundings_tried"],
        "features_searched": replay["features_searched"],
        "loss": loss, "compiles_in_window": compiles_window,
        "trees_grown": done, "trees": trees,
        "stored_columns": stored_columns,
        "stored_columns_stated": expect["stored_columns"],
        "max_bundle_bins": int(ds.bundle_plan.max_bundle_bins),
        "pins": len(pins)})
    return {
        "correct": all(v is not False for v in checks.values()),
        "attempted": trees,
        "failed": trees - done,
        "end_to_end": {"train_row_trees_per_s": rows * trees / window_s,
                       "setup_s": setup_s},
        "counters": {"host_syncs": host_syncs, "trees": trees,
                     "compiles_in_window": compiles_window,
                     "compile_events": compiles.count,
                     "stage_s": stage_s, "ingest": ingest,
                     "ingest_spans": ingest_spans},
        # the lattice the kernel is asked for: the stored columns at the
        # plan's width (it follows the bundle plan: PERF.md section 7)
        "shape": {"rows": rows, "cols": stored_columns,
                  "bins": int(ds.bundle_plan.max_bundle_bins)},
        "memory": memory,
        "trace": report,
        "device": info,
    }
