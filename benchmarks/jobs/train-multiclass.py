"""Job kind ``train-multiclass``: one closed-loop multiclass training job,
whole iterations of ``num_class`` trees back to back through
``Booster.update(defer=True)``, with no host sync between them, no
validation set, no callbacks and no checkpoints. The window holds
``max(1, ceil(seconds / t))`` whole iterations, ``t`` the device seconds
of iteration 0 (from the return of its dispatch to its scores being
ready). ``counters["trees"]`` is ``num_class`` x iterations, so
``train_row_trees_per_s`` is rows x trees / window seconds, as in every
other training cell. The Dataset is built as job kind ``train``'s
(mappers fitted by the program on a sample of raw rows, every column
binned here with those bounds), with one difference: the sample is the
first ``bin_sample_rows`` rows the generator draws at
:data:`BIN_SAMPLE_SEED`, not at the run's seed. The mappers, and so
every column's bin count, are then a constant of the configuration, as
the label surface is. A seed's own sample moves the bin counts of the
rates at two decimals and of the heavy-tailed counts by a few bins from
seed to seed; the step holds the counts as constants, so every seed
would compile a program of its own (~172 s on a v5e). A traced run keeps the capture's
per-stage seconds as ``counters["stage_s"]``, as ``train-rank`` does.

``correct``, after the window, on the timed run's own trees
(``reference/gbdt_multiclass_reference.py`` has the rules and limits):
(a) iteration 0's trees of every class whose root cannot split, of the
largest class and of two more splitting classes drawn by the seed, held
to the plain reference; (a') iteration 1's trees of the largest class
and of the two drawn ones, held to the reference grown on the softmax
addends of the scores a float64 walk of iteration 0's trees gives every
row (the note ``compared`` holds each iteration's worst leaf over its
limit beside the float8 control's, which has to fail); (b) the model
text holds ``num_class`` trees an iteration; (c) the device scores
``[rows, num_class]`` after the window against a float64 walk of the
model text of every tree, on a seeded sample of rows; (d)
``multi_logloss`` fell over the window; (e) the class-batched build
drove the fused step (``class_batch_reason`` and ``fused_reason``
empty), the kernel is as stated, nothing compiled in the window, no
``LIGHTGBM_TPU_*`` pin.

A program without the class-root kernel's name
(``pallas_histogram.HIST_CLASS_ROOT_NAME``) or the step-shape field
``shape_classes`` (a parent commit) is refused with
:class:`CannotRunCell` once the program is imported, before any data is
drawn or the chip is taken: its root pass is invisible to the trace as a
kernel, and the line it printed would lack the cell's metrics.
"""

from __future__ import annotations

import math
import os
import shutil
import time

import numpy as np

from harness import datagen, device, trace as trace_mod
from harness.manifest import _load_module
from reference import gbdt_multiclass_reference as mref

_train = _load_module(os.path.join(os.path.dirname(__file__), "train.py"),
                      "job kind 'train'")
_rank = _load_module(os.path.join(os.path.dirname(__file__),
                                  "train-rank.py"), "job kind 'train-rank'")
_compile_cache = _train._compile_cache
_tree_leaves = _train._tree_leaves
PIN_PREFIX = _train.PIN_PREFIX
_stage_seconds = _rank._stage_seconds
CannotRunCell = _rank.CannotRunCell
# the seed of the rows the bin mappers are fitted on, whatever the run's
BIN_SAMPLE_SEED = 0


def refuse_unless_supported() -> None:
    """The program names its class-root kernel and puts ``shape_classes``
    on the step's shape, or :class:`CannotRunCell`. JAX's backend is not
    started by the imports."""
    from lightgbm_tpu import phases
    from lightgbm_tpu.ops import pallas_histogram
    if not getattr(pallas_histogram, "HIST_CLASS_ROOT_NAME", None):
        raise CannotRunCell(
            "the program's class-root kernel has no name of its own: the "
            "trace cannot class the class-batched build's root pass as a "
            "kernel, and the cell's root rooflines have nothing to read")
    if "shape_classes" not in getattr(phases, "STEP_SHAPE", ()):
        raise CannotRunCell("the program's step shape has no shape_classes")


def _make_dataset(env, lgb, params):
    """Job kind ``train``'s Dataset, the mappers fitted on the first
    ``bin_sample_rows`` rows drawn at :data:`BIN_SAMPLE_SEED` (the
    module's docstring)."""
    cfg = env.config
    rows, cols = cfg["shape"]["rows"], cfg["shape"]["cols"]
    gen = env.manifest.generator(cfg["generator"]["name"])
    gp = cfg["generator"].get("params", {})
    s = min(int(cfg["bin_sample_rows"]), rows)
    x_cm, y = gen.generate(rows, cols, BIN_SAMPLE_SEED, gp)
    sample, label = np.ascontiguousarray(x_cm[:, :s].T), y[:s]
    del x_cm
    ds = lgb.Dataset(sample, label=label, params=params).construct()
    if len(ds.used_features) != cols or ds.bundle_plan is not None:
        raise RuntimeError("the sample left columns unused or bundled: "
                           f"{len(ds.used_features)} of {cols} used")
    ubs = [np.asarray(ds.bin_mappers[f].bin_upper_bound, np.float64)
           for f in ds.used_features]
    x_cm, y = gen.generate(rows, cols, env.seed, gp)
    bins_cm = datagen.bin_columns(x_cm, ubs)
    del x_cm
    ds.bins = datagen.to_row_major(bins_cm)
    ds.num_data = rows
    ds.label = y.astype(np.float64)
    return ds, bins_cm, y, ubs


def run(env) -> dict:
    spans, note = env.spans, env.note
    cfg, mix = env.config, env.traffic
    if mix.get("sync_between_trees") or mix.get("valid_sets"):
        raise NotImplementedError("this job kind runs trees back to back, "
                                  "with no sync and no validation set")
    rows, cols = cfg["shape"]["rows"], cfg["shape"]["cols"]
    params = dict(cfg["params"], verbosity=-1)
    K = int(params["num_class"])

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
    note("ingest", ds.ingest_counters)

    with spans.span("setup.first_dispatch"):
        bst = lgb.Booster(params, ds)
        bst.update(defer=True)
        t_dispatched = time.perf_counter()
        gb = bst._gbdt
        jax.block_until_ready(gb.scores)
        t_iter = time.perf_counter() - t_dispatched
    with spans.span("setup.loss"):
        loss = [mref.multi_logloss(y, gb.eval_scores(-1))]
    iterations = max(1, math.ceil(env.seconds / t_iter))

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
            for _ in range(iterations):
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
        scores = gb.eval_scores(-1)
        loss.append(mref.multi_logloss(y, scores))
    model_text = bst.model_to_string()
    addends = str(gb.config.hist_dtype)
    trees = K * (iterations + 1)
    with spans.span("after.reference"):
        first = mref.check_first_iteration(model_text, K, ubs, bins_cm, y,
                                           params, env.seed, addends)
        second = mref.check_second_iteration(
            model_text, K, ubs, bins_cm, y, params, first["picked"],
            addends)
        walked = mref.check_scores(model_text, trees, K, ubs, bins_cm,
                                   scores, mref.sample_rows(rows, env.seed))

    leaves = _tree_leaves(model_text)
    grown = [sum(1 for n in leaves[i * K:(i + 1) * K] if n > 1)
             for i in range(len(leaves) // K)]
    done = sum(1 for g in grown[1:1 + iterations] if g > 0)
    resolved = {"hist_impl": gb.config.hist_impl,
                "hist_impl_reason": gb.hist_impl_reason,
                "fused_reason": gb.fused_reason,
                "class_batch_reason": gb.class_batch_reason,
                "tree_learner": gb.config.tree_learner,
                "leaf_batch": int(gb.config.leaf_batch),
                "hist_dtype": addends,
                "bundled": gb.train_set.bundle_plan is not None,
                "step_shape": gb.step_shape}
    pins = sorted(k for k in os.environ if k.startswith(PIN_PREFIX))
    want_kernel = cfg["expect"]["hist_impl"]
    checks = {
        "first_iteration_replay": first["ok"],
        "second_iteration_replay": second["ok"],
        "trees_per_iteration": len(leaves) == trees,
        "scores_follow_the_model_text": walked["ok"],
        "loss_fell": bool(loss[1] < loss[0]),
        "class_batched": resolved["class_batch_reason"] == "",
        "fused_step": resolved["fused_reason"] == "",
        "no_compile_in_window": compiles_window == 0,
        "no_failed_iteration": done == iterations and grown[0] > 0,
        # off a TPU (a rehearsal) the program picks its CPU kernel by rule
        "kernel_as_stated": (resolved["hist_impl"] == want_kernel
                             if info["platform"] == "tpu" else None),
        "no_pins": not pins,
    }
    note("checks", checks)
    note("resolved", resolved)
    note("pins", pins)
    note("loss", loss)
    note("window", {"iterations": iterations, "trees": K * iterations,
                    "window_s": window_s, "first_iteration_device_s": t_iter,
                    "grown_trees_by_iteration": grown})
    note("replay", first)
    note("replay_iteration_1", second)
    note("compared", dict(
        first["compared"],
        iteration_1_leaf_error_over_limit=second["compared"][
            "leaf_error_over_limit"],
        iteration_1_control_error_over_limit=second["compared"][
            "control_error_over_limit"],
        iteration_1_control_ok=second["compared"]["control_ok"],
        score_error_over_limit=walked["score_error_over_limit"]))
    note("scores", walked)
    return {
        "correct": all(v is not False for v in checks.values()),
        "attempted": iterations,
        "failed": iterations - done,
        "end_to_end": {"train_row_trees_per_s":
                       rows * K * iterations / window_s,
                       "setup_s": setup_s},
        "counters": {"host_syncs": host_syncs, "trees": K * iterations,
                     "iterations": iterations,
                     "compiles_in_window": compiles_window,
                     "compile_events": compiles.count, "stage_s": stage_s},
        "shape": {"rows": rows, "cols": cols,
                  "bins": int(params["max_bin"]), "classes": K},
        "memory": memory,
        "trace": report,
        "device": info,
        # the readers find the trainer through GBDT.latest(), a weak
        # reference: held here, it outlives the job until they have read
        # it, whenever the collector runs
        "booster": bst,
    }
