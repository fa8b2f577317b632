"""Job kind ``train-linear``: job kind ``train`` with linear leaves
(``linear_tree``): one closed-loop training job, trees back to back
through ``Booster.update(defer=True)``, no host sync between trees, a
window of ``max(1, ceil(seconds / t))`` whole trees, ``t`` the device
seconds of tree 0. The Dataset is handed the full raw matrix beside its
bins (``raw_values``: a view of the generator's column-major array, which
the program lays on the device feature-major at Booster construction, a
part of set-up). A traced run keeps the capture's per-stage seconds as
``counters["stage_s"]``, as ``train-rank`` does.

``correct``, after the window, on the timed run's own trees
(``reference/gbdt_linear_reference.py`` has the limits and why):
(a) tree 0 held to the plain reference as in ``train``, and constant by
the first-tree rule; (b) tree 1, the first linear tree: its splits at the
scores replayed from tree 0's text, and its per-row outputs on every row
against a float64 refit of its leaves, beside the bfloat16 control, which
has to fail; (c) the device scores after the window against a float64
walk of the model text of every tree, on a seeded sample of rows;
(d) as ``train``: the fused step, no compile in the window, no stump, no
pin, kernel as stated.

A program without the linear-leaf stages (a parent commit, which fits
linear leaves on the host after every tree, in the legacy loop) is
refused with :class:`CannotRunCell` once the program is imported, before
any data is drawn or the chip is taken: its trees are not the cell's, and
its legacy loop trains a tree inside ``update`` so that the window rule
(tree 0's seconds after the call returns) would ask for thousands.
"""

from __future__ import annotations

import math
import os
import shutil
import time

import numpy as np

from harness import datagen, device, trace as trace_mod
from harness.manifest import _load_module
from reference import gbdt_linear_reference as lref
from reference import gbdt_reference as ref

_train = _load_module(os.path.join(os.path.dirname(__file__), "train.py"),
                      "job kind 'train'")
_compile_cache = _train._compile_cache
_tree_leaves = _train._tree_leaves
PIN_PREFIX = _train.PIN_PREFIX
_rank = _load_module(os.path.join(os.path.dirname(__file__),
                                  "train-rank.py"), "job kind 'train-rank'")
_stage_seconds = _rank._stage_seconds
CannotRunCell = _rank.CannotRunCell


def refuse_unless_supported() -> None:
    """The program names the three linear-leaf stages, or
    :class:`CannotRunCell`. Looks at one module; JAX's backend is not
    started by it."""
    from lightgbm_tpu import phases
    if not {"linear_gram", "linear_solve", "linear_update"} <= set(
            getattr(phases, "KNOWN_PHASES", ())):
        raise CannotRunCell(
            "the program has no stages linear_gram / linear_solve / "
            "linear_update: it fits linear leaves on the host after every "
            "tree, outside the fused step, and the cell's metrics have no "
            "source")


def _make_dataset(env, lgb, params):
    """Job kind ``train``'s Dataset (mappers fitted by the program on a
    sample of raw rows, every column binned here with those bounds), with
    the full raw matrix as its ``raw_values``: ``[rows, cols]``, a view of
    the ``[cols, rows]`` float32 columns (``ds.raw_values.T`` is them)."""
    cfg = env.config
    rows, cols = cfg["shape"]["rows"], cfg["shape"]["cols"]
    gen = env.manifest.generator(cfg["generator"]["name"])
    x_cm, y = gen.generate(rows, cols, env.seed,
                           cfg["generator"].get("params", {}))
    s = min(int(cfg["bin_sample_rows"]), rows)
    ds = lgb.Dataset(np.ascontiguousarray(x_cm[:, :s].T), label=y[:s],
                     params=params).construct()
    if len(ds.used_features) != cols or ds.bundle_plan is not None:
        raise RuntimeError("the sample left columns unused or bundled: "
                           f"{len(ds.used_features)} of {cols} used")
    if getattr(ds, "raw_values", None) is None:
        raise CannotRunCell("the program's Dataset keeps no raw values "
                            "under linear_tree")
    ubs = [np.asarray(ds.bin_mappers[f].bin_upper_bound, np.float64)
           for f in ds.used_features]
    bins_cm = datagen.bin_columns(x_cm, ubs)
    ds.bins = datagen.to_row_major(bins_cm)
    ds.num_data = rows
    ds.label = y.astype(np.float64)
    ds.raw_values = x_cm.T
    return ds, bins_cm, y, ubs


def run(env) -> dict:
    spans, note = env.spans, env.note
    cfg, mix = env.config, env.traffic
    if mix.get("sync_between_trees") or mix.get("valid_sets"):
        raise NotImplementedError("this job kind runs trees back to back, "
                                  "with no sync and no validation set")
    rows, cols = cfg["shape"]["rows"], cfg["shape"]["cols"]
    params = dict(cfg["params"], verbosity=-1)

    with spans.span("setup.refusal"):
        refuse_unless_supported()
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
    x_cm = ds.raw_values.T

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
            # the compiled step once more (from the cache) for its text:
            # a compile event, so before the window
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
            report = trace_mod.reduce_xplane(
                trace_dir, rows, cols, cfg["expect"]["kernel_op_pattern"])
            stage_s = _stage_seconds(trace_dir, maps)
            shutil.rmtree(trace_dir, ignore_errors=True)

    with spans.span("after.sync_trees"):
        bst._sync_trees()
    host_syncs = gb.host_sync_count - syncs0
    memory = device.memory_by_device()
    with spans.span("after.loss"):
        scores = gb.eval_scores(-1)[:, 0]
        loss.append(ref.binary_logloss(y, scores))
    model_text = bst.model_to_string()
    addends = str(gb.config.hist_dtype)
    with spans.span("after.reference"):
        first = ref.check_first_tree(model_text, ubs, bins_cm, y, params,
                                     addends)
        constant = lref.check_constant_first_tree(model_text)
        linear = lref.check_first_linear_tree(model_text, ubs, bins_cm,
                                              x_cm, y, params, addends)
        walked = lref.check_scores(model_text, trees + 1, ubs, bins_cm,
                                   x_cm, scores,
                                   lref.sample_rows(rows, env.seed))

    leaves = _tree_leaves(model_text)
    done = sum(1 for n in leaves[1:1 + trees] if n > 1)
    resolved = {"hist_impl": gb.config.hist_impl,
                "hist_impl_reason": gb.hist_impl_reason,
                "fused_reason": gb.fused_reason,
                "tree_learner": gb.config.tree_learner,
                "leaf_batch": int(gb.config.leaf_batch),
                "hist_dtype": addends,
                "linear_impl": getattr(gb, "_linear_impl", None)}
    pins = sorted(k for k in os.environ if k.startswith(PIN_PREFIX))
    want_kernel = cfg["expect"]["hist_impl"]
    checks = {
        "tree_replay": first["ok"],
        "first_tree_constant": constant["ok"],
        "first_linear_tree": linear["ok"],
        "scores_follow_the_model_text": walked["ok"],
        "loss_fell": bool(loss[1] < loss[0]),
        "fused_step": resolved["fused_reason"] == "",
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
    note("replay", first)
    note("compared", {k: v for k, v in linear.items() if k != "splits"})
    note("splits", linear["splits"])
    note("scores", walked)
    return {
        "correct": all(v is not False for v in checks.values()),
        "attempted": trees,
        "failed": trees - done,
        "end_to_end": {"train_row_trees_per_s": rows * trees / window_s,
                       "setup_s": setup_s},
        "counters": {"host_syncs": host_syncs, "trees": trees,
                     "compiles_in_window": compiles_window,
                     "compile_events": compiles.count, "stage_s": stage_s},
        "shape": {"rows": rows, "cols": cols, "bins": int(params["max_bin"])},
        "memory": memory,
        "trace": report,
        "device": info,
    }
