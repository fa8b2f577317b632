"""Job kind ``train-rank``: one closed-loop learning-to-rank training
job, trees back to back. As ``train`` (same window, same two end-to-end
metrics), with the query sizes handed to the Dataset and the trees held
to the plain lambdarank reference.

Before any data is made the job builds the program's ranking objective on
the configuration's query sizes alone and reads its counters
(``pair_slots``, ``pairs``, ``slots``, ``queries``, ``max_query``). A
program that has none, or whose pair lattice could not fit the device,
is refused there with a plain error: it does not get to compile for
minutes first.

``correct``, after the window, on the timed run's own trees:
(a) tree 0 (all scores equal: tie order and rank discounts decide it)
and the window's last tree (scores spread: sort, sigmoid, truncation,
normalisation), each against reference gradients at the scores replayed
from the earlier trees' model text; (b) the program's own gradients at
the last scores against the reference's on a seeded sample of queries
that holds the longest and a one-row query; (c) training NDCG@10 by the
reference rises over the window; (d) as ``train``: no compile in the
window, no stump, no pin, kernel as stated, a TPU.
"""

from __future__ import annotations

import math
import os
import shutil
import time

import numpy as np

from harness import datagen, device, trace as trace_mod
from harness.manifest import _load_module
from reference import lambdarank_reference as ref

# the compile-cache rule, the model text's leaf counts and the pin prefix
# are job kind ``train``'s; scripts/stage_trace.py reads ``_compile_cache``
# and ``_make_dataset`` off whichever job kind a cell names
_train = _load_module(os.path.join(os.path.dirname(__file__), "train.py"),
                      "job kind 'train'")
_compile_cache = _train._compile_cache
_tree_leaves = _train._tree_leaves
PIN_PREFIX = _train.PIN_PREFIX


class CannotRunCell(RuntimeError):
    """The program cannot run this cell; said before anything compiles."""


def objective_counters(objective) -> dict:
    counters = getattr(objective, "counters", None)
    want = ("pair_slots", "pairs", "slots", "queries", "max_query")
    if not isinstance(counters, dict) or any(k not in counters for k in want):
        raise CannotRunCell(
            "the program's ranking objective reports no layout counters "
            f"({', '.join(want)}): it pads every query to the longest, "
            "which this cell's sizes do not survive")
    return {k: int(counters[k]) for k in want}


def refuse_unless_it_fits(lgb, cfg, generator, bytes_limit) -> dict:
    """The objective's counters on the configuration's query sizes (the
    generator's table; no data, labels all zero), or
    :class:`CannotRunCell`."""
    gen = cfg["generator"]["params"]
    sizes = generator.query_sizes(cfg["shape"]["rows"], gen["queries"],
                                  gen["min_query"], gen["max_query"])
    from lightgbm_tpu.objectives import create_objective
    objective = create_objective(lgb.Config(dict(cfg["params"])))
    objective.init(np.zeros(cfg["shape"]["rows"]), None,
                   np.concatenate([[0], np.cumsum(sizes)]))
    counters = objective_counters(objective)
    # one float32 temporary over the pair positions has to fit beside
    # the builder: a layout that fuses every such tensor away is not
    # something to count on before compiling
    need = counters["pair_slots"] * 4
    if bytes_limit and need > bytes_limit // 2:
        raise CannotRunCell(
            f"pair_slots {counters['pair_slots']:,} as one float32 tensor "
            f"is {need:,} bytes, over half the device's {bytes_limit:,}")
    return counters


def _make_dataset(env, lgb, params):
    """The program's Dataset over the full binned matrix, as job kind
    ``train`` makes it (mappers fitted by the program on a sample of raw
    rows, columns binned here with those bounds), with the query sizes
    set on it."""
    cfg = env.config
    rows, cols = cfg["shape"]["rows"], cfg["shape"]["cols"]
    gen = env.manifest.generator(cfg["generator"]["name"])
    x_cm, y, sizes = gen.generate(rows, cols, env.seed,
                                  cfg["generator"].get("params", {}))
    # whole queries of the first rows: the sample is a ranking set too
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    n_q = max(int(np.searchsorted(bounds, int(cfg["bin_sample_rows"]),
                                  side="right")) - 1, 1)
    s = int(bounds[n_q])
    ds = lgb.Dataset(np.ascontiguousarray(x_cm[:, :s].T), label=y[:s],
                     group=sizes[:n_q], params=params).construct()
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
    ds.set_field("group", sizes)
    return ds, bins_cm, y, ubs


def _sample_queries(sizes: np.ndarray, count: int, seed: int) -> np.ndarray:
    """The longest query, a one-row query (the shortest there is) and a
    seeded draw of the rest."""
    rng = np.random.default_rng([seed, len(sizes)])
    picked = {int(np.argmax(sizes)), int(np.argmin(sizes))}
    picked.update(int(q) for q in rng.choice(
        len(sizes), size=min(count, len(sizes)), replace=False))
    return np.array(sorted(picked))


def _gradient_check(gb, jax, y, bounds, params, queries) -> dict:
    """The program's gradients at its own last scores against the
    reference's, on ``queries``; and what the reference gives from the
    same scores rounded to bfloat16, which the limit has to refuse."""
    jnp = jax.numpy
    score = gb.scores[0]
    g, h = gb.objective.get_gradients(score, gb.label_dev, gb.weight_dev)
    s32 = np.asarray(score)[: len(y)]
    g, h = np.asarray(g)[: len(y)], np.asarray(h)[: len(y)]
    s16 = np.asarray(score.astype(jnp.bfloat16).astype(jnp.float32))[: len(y)]
    gr, hr = ref.lambdarank_gradients(s32, y, bounds, params, queries)
    gc, hc = ref.lambdarank_gradients(s16, y, bounds, params, queries)
    worst = coarse = 0.0
    for q in queries:
        sl = slice(int(bounds[q]), int(bounds[q + 1]))
        scale = max(float(np.abs(gr[sl]).max()), float(np.abs(hr[sl]).max()))
        if scale <= 0:          # one row, or one grade: all must be zero
            worst = max(worst, float(np.abs(g[sl]).max()),
                        float(np.abs(h[sl]).max()))
            continue
        worst = max(worst, float(np.abs(g[sl] - gr[sl]).max()) / scale,
                    float(np.abs(h[sl] - hr[sl]).max()) / scale)
        coarse = max(coarse, float(np.abs(gc[sl] - gr[sl]).max()) / scale,
                     float(np.abs(hc[sl] - hr[sl]).max()) / scale)
    return {"queries": len(queries), "limit": ref.GRAD_RTOL,
            "worst_error_over_scale": worst,
            "bfloat16_scores_error_over_scale": coarse,
            "ok": bool(worst <= ref.GRAD_RTOL)}


def _stage_seconds(trace_dir: str, maps) -> dict:
    """Device self seconds by the program's stage, from the program's own
    reduction of the capture and the fused step's stage map."""
    from lightgbm_tpu.telemetry import xprof
    prof = xprof.parse_trace(trace_dir, phase_maps=maps)
    return {k: float(v) for k, v in prof.device_phase_s.items()}


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

    with spans.span("setup.refusal"):
        limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit")
        note("objective_counters", refuse_unless_it_fits(
            lgb, cfg, env.manifest.generator(cfg["generator"]["name"]),
            limit))

    with spans.span("setup.data"):
        ds, bins_cm, y, ubs = _make_dataset(env, lgb, params)
    bounds = ds.query_boundaries()
    sizes = np.diff(bounds)

    with spans.span("setup.first_dispatch"):
        bst = lgb.Booster(params, ds)
        bst.update(defer=True)
        t_dispatched = time.perf_counter()
        gb = bst._gbdt
        jax.block_until_ready(gb.scores)
        t_tree = time.perf_counter() - t_dispatched
    counters_obj = objective_counters(gb.objective)
    with spans.span("setup.loss"):
        ndcg = [ref.ndcg_at_k(gb.eval_scores(-1)[:, 0], y, bounds, 10)]
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
            # the per-stage seconds are kept for the readers; the capture
            # itself goes
            stage_s = _stage_seconds(trace_dir, maps)
            shutil.rmtree(trace_dir, ignore_errors=True)

    with spans.span("after.sync_trees"):
        bst._sync_trees()
    host_syncs = gb.host_sync_count - syncs0
    memory = device.memory_by_device()
    with spans.span("after.loss"):
        ndcg.append(ref.ndcg_at_k(gb.eval_scores(-1)[:, 0], y, bounds, 10))
    model_text = bst.model_to_string()
    addends = str(gb.config.hist_dtype)
    lr = float(params["learning_rate"])
    with spans.span("after.reference"):
        replays, upto = [], None    # (k, the scores after k trees)
        for index in sorted({0, trees}):
            score = ref.replay_scores(model_text, index, ubs, bins_cm, lr,
                                      start=upto)
            upto = (index, score)
            g, h = ref.lambdarank_gradients(score, y, bounds, params)
            replays.append(ref.check_tree(model_text, index, ubs, bins_cm,
                                          g, h, params, addends))
        replayed = ref.replay_scores(model_text, trees + 1, ubs, bins_cm, lr,
                                     start=upto)
        score_gap = float(np.abs(
            replayed - gb.eval_scores(-1)[:, 0]).max())
    with spans.span("after.gradients"):
        grads = _gradient_check(
            gb, jax, y, bounds, params, _sample_queries(
                sizes, int(mix.get("gradient_check_queries", 64)), env.seed))

    leaves = _tree_leaves(model_text)
    done = sum(1 for n in leaves[1:1 + trees] if n > 1)
    resolved = {"hist_impl": gb.config.hist_impl,
                "hist_impl_reason": gb.hist_impl_reason,
                "fused_reason": gb.fused_reason,
                "tree_learner": gb.config.tree_learner,
                "leaf_batch": int(gb.config.leaf_batch),
                "hist_dtype": addends}
    pins = sorted(k for k in os.environ if k.startswith(PIN_PREFIX))
    want_kernel = cfg["expect"]["hist_impl"]
    checks = {
        "tree_replay_first": replays[0]["ok"],
        "tree_replay_last": replays[-1]["ok"],
        # the replay adds in float32 as the program does; an operation
        # fused otherwise may move a last place (2^-24 of a score)
        "scores_follow_the_model_text": score_gap <= 1e-6,
        "gradients": grads["ok"],
        "ndcg_rose": bool(ndcg[1] > ndcg[0]),
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
    note("ndcg_at_10", ndcg)
    note("window", {"trees": trees, "window_s": window_s,
                    "first_tree_device_s": t_tree, "leaves": leaves})
    note("replay", replays)
    note("score_gap", score_gap)
    note("gradient_check", grads)
    return {
        "correct": all(v is not False for v in checks.values()),
        "attempted": trees,
        "failed": trees - done,
        "end_to_end": {"train_row_trees_per_s": rows * trees / window_s,
                       "setup_s": setup_s},
        "counters": {"host_syncs": host_syncs, "trees": trees,
                     "compiles_in_window": compiles_window,
                     "compile_events": compiles.count,
                     "objective": counters_obj, "stage_s": stage_s},
        "shape": {"rows": rows, "cols": cols, "bins": int(params["max_bin"])},
        "memory": memory,
        "trace": report,
        "device": info,
    }
