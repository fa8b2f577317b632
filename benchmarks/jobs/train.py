"""Job kind ``train``: one closed-loop training job, trees back to back.

Set-up makes the data from the seed, hands it to the program binned, and
runs the first tree (which compiles the step, or loads it from the cache).
The window then drives ``Booster.update(defer=True)`` as ``engine.train``
does between its sync points: no host sync between trees, and one
``block_until_ready`` on the last tree's scores at the end. After the
window the trees are fetched and tree 0 is held to the plain reference.

A window holds whole trees only: ``max(1, ceil(seconds / t))`` of them,
where ``t`` is the device time of the first tree (from the return of its
dispatch, which is when compiling is over, to its scores being ready).
"""

from __future__ import annotations

import math
import os
import shutil
import time

import numpy as np

from harness import datagen, device, trace as trace_mod
from reference import gbdt_reference as ref

PIN_PREFIX = "LIGHTGBM_TPU_"


def _compile_cache(lgb, jax):
    """The program's own rule decides the directory
    (``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.xla_cache``; off on
    a CPU). Every program is kept, however small, so that a second run
    in a checkout compiles nothing."""
    path = lgb.enable_compilation_cache()
    if path:
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def _make_dataset(env, lgb, params):
    """The program's Dataset over the full binned matrix. The program fits
    its bin mappers on a sample of raw rows through its public
    constructor; the benchmark then bins every column with those bounds
    itself (the program's host binning takes ~4 s per million rows at 28
    columns) and hands the Dataset the result."""
    cfg = env.config
    rows, cols = cfg["shape"]["rows"], cfg["shape"]["cols"]
    gen = env.manifest.generator(cfg["generator"]["name"])
    x_cm, y = gen.generate(rows, cols, env.seed, cfg["generator"].get("params", {}))
    s = min(int(cfg["bin_sample_rows"]), rows)
    ds = lgb.Dataset(np.ascontiguousarray(x_cm[:, :s].T), label=y[:s],
                     params=params).construct()
    if len(ds.used_features) != cols or ds.bundle_plan is not None:
        raise RuntimeError("the sample left columns unused or bundled: "
                           f"{len(ds.used_features)} of {cols} used")
    ubs = [np.asarray(ds.bin_mappers[f].bin_upper_bound, np.float64)
           for f in ds.used_features]
    bins_cm = datagen.bin_columns(x_cm, ubs)
    del x_cm
    ds.bins = datagen.to_row_major(bins_cm)
    ds.num_data = rows
    ds.label = y.astype(np.float64)
    return ds, bins_cm, y, ubs


def _tree_leaves(model_text: str):
    return [int(line.split("=", 1)[1]) for line in model_text.splitlines()
            if line.startswith("num_leaves=")]


def run(env) -> dict:
    spans, note = env.spans, env.note
    cfg, mix = env.config, env.traffic
    if mix.get("sync_between_trees") or mix.get("valid_sets"):
        raise NotImplementedError("this job kind runs trees back to back, "
                                  "with no sync and no validation set")
    rows, cols = cfg["shape"]["rows"], cfg["shape"]["cols"]
    params = dict(cfg["params"], verbosity=-1)

    with spans.span("setup.jax_init"):
        import jax
        info = device.require_tpu(env.chips) if env.require_tpu \
            else device.device_info()
        import lightgbm_tpu as lgb
        cache_dir = _compile_cache(lgb, jax)
    compiles = env.compile_counter()
    note("device", info)
    note("compile_cache", {"dir": cache_dir, "env_set": bool(
        os.environ.get("JAX_COMPILATION_CACHE_DIR"))})

    with spans.span("setup.data"):
        ds, bins_cm, y, ubs = _make_dataset(env, lgb, params)

    with spans.span("setup.first_dispatch"):
        bst = lgb.Booster(params, ds)
        bst.update(defer=True)
        t_dispatched = time.perf_counter()
        gb = bst._gbdt
        jax.block_until_ready(gb.scores)
        t_tree = time.perf_counter() - t_dispatched
    compiles_setup = compiles.count
    with spans.span("setup.loss"):
        loss = [ref.binary_logloss(y, gb.eval_scores(-1)[:, 0])]
    trees = max(1, math.ceil(env.seconds / t_tree))

    trace_dir = os.path.join(env.manifest.root, ".bench_cache", "trace",
                             env.cell["name"])
    if env.trace:
        with spans.span("setup.trace_start"):
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        spans.annotate = True

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

    report = None
    if env.trace:
        with spans.span("after.trace"):
            jax.profiler.stop_trace()
            report = trace_mod.reduce_xplane(
                trace_dir, rows, cols, cfg["expect"]["kernel_op_pattern"])
            shutil.rmtree(trace_dir, ignore_errors=True)

    with spans.span("after.sync_trees"):
        bst._sync_trees()
    host_syncs = gb.host_sync_count - syncs0
    memory = device.memory_by_device()
    with spans.span("after.loss"):
        loss.append(ref.binary_logloss(y, gb.eval_scores(-1)[:, 0]))
    model_text = bst.model_to_string()
    with spans.span("after.reference"):
        replay = ref.check_first_tree(model_text, ubs, bins_cm, y, params,
                                      str(gb.config.hist_dtype))

    leaves = _tree_leaves(model_text)
    done = sum(1 for n in leaves[1:1 + trees] if n > 1)
    resolved = {"hist_impl": gb.config.hist_impl,
                "hist_impl_reason": gb.hist_impl_reason,
                "fused_reason": gb.fused_reason,
                "tree_learner": gb.config.tree_learner,
                "leaf_batch": int(gb.config.leaf_batch),
                "hist_dtype": str(gb.config.hist_dtype)}
    pins = sorted(k for k in os.environ if k.startswith(PIN_PREFIX))
    want_kernel = cfg["expect"]["hist_impl"]
    checks = {
        "tree_replay": replay["ok"],
        "loss_fell": bool(loss[1] < loss[0]),
        "no_compile_in_window": compiles_window == 0,
        "no_failed_tree": done == trees and leaves[0] > 1,
        # off a TPU (a rehearsal) the program picks its CPU kernel by rule
        "kernel_as_stated": (resolved["hist_impl"] == want_kernel
                             if info["platform"] == "tpu" else None),
        "no_pins": not pins,
    }
    note("checks", checks)
    note("resolved", resolved)
    note("pins", pins)
    note("loss", loss)
    note("window", {"trees": trees, "window_s": window_s,
                    "first_tree_device_s": t_tree, "leaves": leaves})
    note("replay", replay)
    return {
        "correct": all(v is not False for v in checks.values()),
        "attempted": trees,
        "failed": trees - done,
        "end_to_end": {"train_row_trees_per_s": rows * trees / window_s,
                       "setup_s": setup_s},
        "counters": {"host_syncs": host_syncs, "trees": trees,
                     "compiles_in_window": compiles_window,
                     "compile_events": compiles.count},
        "shape": {"rows": rows, "cols": cols, "bins": int(params["max_bin"])},
        "memory": memory,
        "trace": report,
        "device": info,
    }
