"""Job kind ``train-dp``: one closed-loop training job whose rows are
spread over the chips of a host under ``tree_learner=data``, trees back to
back. As ``train`` (same window, same two end-to-end metrics, the same
``Booster.update(defer=True)`` loop), with the data drawn and binned in row
blocks so that the float table never exists whole, the plan's counters
read from the program, and tree 0 held to the plain reference over rows
that lie on shards (``reference/gbdt_sharded_reference.py``).

Before JAX touches a device the job asks the program for the entry point of
its plan counters (``parallel/comms.plan_counters``). A program without it
(a parent commit) counts no collectives and compiles its step a second
time inside the window: it is refused there with :class:`CannotRunCell`,
within a second, with no data drawn and the chips never taken.

``correct`` is made of answers only. Each check, and what on a sound
program could make it false:

``tree_replay``  tree 0 (built by the one compiled step the window drives)
    against the reference on all rows: the root and the next four nodes are
    the reference's best split or within 2^-11 of its gain, leaf counts
    equal a replay, leaf values within 2^-11 x sum|g| / (H + l2) plus 2^-19
    of the float32 numbers the leaf's sums are made of (its side's bins
    down its parent's subtraction chain, a right side's total and prefix:
    the reference's docstring; without it a leaf of 31 rows cut off a
    large node flipped the check at one seed in eleven). False
    only for a tree that is not the exact greedy tree of bfloat16 addends:
    an addend on a bfloat16 rounding boundary, which float32 and float64
    round apart, is covered by the reference's margin rule (both
    neighbours admissible within 2^-14 of the boundary), so no seed can
    flip it; nothing here reads a clock, a cache or the capture.
``every_chip_same_tree``  the copies of every tree of the run that the
    chips hold are equal, bit for bit. False only if the chips disagree.
``loss_fell``  training log-loss by the reference after the window is
    below the one after tree 0. A function of the data and the trees.
``no_failed_tree``  every tree of the window and tree 0 has more than one
    leaf. A function of the data.
``no_compile_in_window``  JAX compiled (or loaded) no program between the
    window's start and its end. Tree 0 runs the very step the window
    runs, so a cold cache compiles before the window and never in it;
    false only if a shape or a placement changes between two trees.
``fused_step``, ``tree_learner_as_stated``, ``plan_as_stated``,
    ``kernel_as_stated``  what the program resolved is what the
    configuration states under ``expect`` (the kernel only on a TPU:
    elsewhere the program picks its CPU kernel by rule). Functions of the
    program and the configuration alone.
``rows_on_every_chip``  every shard holds its share of the rows, less the
    padding of the last: at least 99% of rows / shards. The plan's padding
    rule decides it, no run does.
``no_pins``  no ``LIGHTGBM_TPU_*`` variable is set.

What describes the machine or the capture is a **note** and not a check:
whether every chip's trace shows both collective stages
(``capture.collective_stages_on_every_chip``), the padding's share, the
compile cache's hit or miss, seconds by span. So is the yardstick's own
control (``compared.control_*``: the reference fed float8-e4m3 addends
against the same tree and counts has to read over its limit; it says
whether the comparison can still tell a coarser addend, not whether the
program is sound). Every number ``correct`` compared is printed beside its
limit in the job's last note, ``compared``.
"""

from __future__ import annotations

import math
import os
import shutil
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from harness import device, trace as trace_mod, workers
from harness.manifest import _load_module
from reference import gbdt_reference as ref
from reference import gbdt_sharded_reference as sref

# the compile-cache rule, the model text's leaf counts and the pin prefix
# are job kind ``train``'s
_train = _load_module(os.path.join(os.path.dirname(__file__), "train.py"),
                      "job kind 'train'")
_compile_cache = _train._compile_cache
_tree_leaves = _train._tree_leaves
PIN_PREFIX = _train.PIN_PREFIX

COLLECTIVE_STAGES = ("hist_merge", "winner_sync")


class CannotRunCell(RuntimeError):
    """The program cannot run this cell; said before any data is drawn."""


def refuse_unless_supported() -> None:
    """The program has the plan counters' entry point, or
    :class:`CannotRunCell`. Looks at two modules and compiles nothing;
    JAX's backend is not started by it."""
    from lightgbm_tpu import phases
    from lightgbm_tpu.parallel import comms
    if not callable(getattr(comms, "plan_counters", None)) \
            or not getattr(phases, "PLAN_COUNTERS", None):
        raise CannotRunCell(
            "the program counts no collectives of a row-sharded plan's "
            "compiled step (no parallel/comms.plan_counters, no "
            "phases.PLAN_COUNTERS): the cell's wire metrics have no source, "
            "and such a program compiles its step a second time inside "
            "the window")


_threads = sref._threads     # one count of threads and of workers


def _each(items, fn) -> None:
    """fn(item) over the items on a few threads; every result is read so
    that an exception is not lost."""
    with ThreadPoolExecutor(_threads()) as ex:
        for _ in ex.map(fn, items):
            pass


def _make_dataset(env, lgb, params):
    """The program's Dataset over the full binned matrix, as job kind
    ``train`` makes it (mappers fitted by the program on the first rows,
    every column binned here with those bounds in float32), but block by
    block: a block of rows is drawn, binned and written into the
    row-major matrix the program takes and the column-major one the
    reference reads, and its floats are dropped. The host's peak is the
    two binned tables plus a block a thread."""
    cfg = env.config
    rows, cols = cfg["shape"]["rows"], cfg["shape"]["cols"]
    gen = env.manifest.generator(cfg["generator"]["name"])
    gp = cfg["generator"].get("params", {})
    spans = gen.blocks(rows)
    s = min(int(cfg["bin_sample_rows"]), rows)
    n_first = -(-s // gen.BLOCK_ROWS)
    first = [gen.draw_block(b, hi - lo, cols, env.seed, gp)
             for b, (lo, hi) in enumerate(spans[:n_first])]
    x_s = np.concatenate([x for x, _ in first], axis=1)[:, :s]
    y_s = np.concatenate([y for _, y in first])[:s]
    ds = lgb.Dataset(np.ascontiguousarray(x_s.T), label=y_s,
                     params=params).construct()
    if len(ds.used_features) != cols or ds.bundle_plan is not None:
        raise RuntimeError("the sample left columns unused or bundled: "
                           f"{len(ds.used_features)} of {cols} used")
    ubs = [np.asarray(ds.bin_mappers[f].bin_upper_bound, np.float64)
           for f in ds.used_features]
    if max(len(u) for u in ubs) > 256:
        raise ValueError("more than 256 bins do not fit uint8")
    ub32 = [u.astype(np.float32) for u in ubs]
    # the reference's two arrays may have been made before this call, in
    # memory the worker processes share
    bins_cm = workers.SHARED.get("bins_cm")
    if bins_cm is None or bins_cm.shape != (cols, rows):
        bins_cm = np.empty((cols, rows), np.uint8)
    clicked = workers.SHARED.get("clicked")
    bins_rm = np.empty((rows, cols), np.uint8)
    y = np.empty(rows, np.float32)

    mine = threading.local()     # a thread's float block, touched once

    def fill(item):
        b, (lo, hi) = item
        if b < n_first:
            x, y[lo:hi] = first[b]
        else:
            if not hasattr(mine, "x"):
                mine.x = np.empty((cols, gen.BLOCK_ROWS), np.float32)
            x, y[lo:hi] = gen.draw_block(b, hi - lo, cols, env.seed, gp,
                                         out=mine.x)
        if clicked is not None and len(clicked) == rows:
            clicked[lo:hi] = y[lo:hi] > 0
        tile = bins_cm[:, lo:hi]
        for j in range(cols):
            tile[j] = np.searchsorted(ub32[j], x[j], side="left")
        bins_rm[lo:hi] = tile.T
    _each(list(enumerate(spans)), fill)
    ds.bins = bins_rm
    ds.num_data = rows
    ds.label = y.astype(np.float64)
    return ds, bins_cm, y, ubs


def _logloss(y: np.ndarray, score: np.ndarray) -> float:
    """``gbdt_reference.binary_logloss`` in float64, its sum taken in
    blocks on a few threads."""
    n = len(y)
    block = 1 << 20
    sums = np.zeros(-(-n // block))

    def one(i):
        sl = slice(i * block, (i + 1) * block)
        sums[i] = ref.binary_logloss(y[sl], score[sl]) * len(y[sl])
    _each(range(len(sums)), one)
    return float(sums.sum() / n)


def _plan_counters(names) -> dict:
    """The plan's counters off the program's newest ``gbdt.step_ready``
    span, under the names ``phases.PLAN_COUNTERS`` gives."""
    from lightgbm_tpu import profiler
    spans = profiler.recorder.spans("gbdt.step_ready")
    fields = dict(spans[-1].fields) if spans else {}
    return {k: fields[k] for k in names if k in fields}


def _placement(gb, rows: int) -> dict:
    """Where the rows lie: the plan, and the rows of every shard of the
    bin matrix as the device holds it."""
    plan = gb.plan
    if plan is None:
        return {"plan": None, "shards": 1, "shard_rows": [rows],
                "live_rows": [rows], "padded_rows": rows}
    held = sorted(((s.index[0].start or 0, s.data.shape[0], str(s.device))
                   for s in gb.train_dd.bins.addressable_shards))
    live = [int(min(max(rows - lo, 0), n)) for lo, n, _ in held]
    return {"plan": type(plan).__name__, "shards": int(plan.num_shards),
            "hist_merge": getattr(plan, "hist_merge", None),
            "padded_rows": int(gb.train_dd.r_pad),
            "devices": [d for _, _, d in held],
            "shard_rows": [n for _, n, _ in held], "live_rows": live,
            "pad_share_of_a_shard":
                (gb.train_dd.r_pad - rows) / max(held[-1][1], 1)}


def _copies_agree(jax, pending) -> dict:
    """Every replicated array of every pending tree, copy against copy,
    bit for bit, over the chips that hold one."""
    arrays = copies = 0
    unequal = []
    for it, _shrink, trees, *_ in pending:
        for leaf in jax.tree.leaves(trees):
            if not getattr(leaf, "is_fully_replicated", False):
                continue
            held = [np.asarray(s.data) for s in leaf.addressable_shards]
            arrays += 1
            copies = max(copies, len(held))
            if any(not np.array_equal(held[0], h, equal_nan=True)
                   for h in held[1:]):
                unequal.append(int(it))
    return {"trees": len(pending), "arrays": arrays, "copies": copies,
            "unequal_trees": sorted(set(unequal)),
            "ok": not unequal and arrays > 0}


def _stage_seconds(trace_dir: str, maps):
    """(device self seconds by the program's stage, the mean over the
    chips: a chip's seconds, as on one chip; the same by chip), from the
    program's own reduction of the capture and the fused step's stage
    map."""
    from lightgbm_tpu.telemetry import xprof
    prof = xprof.parse_trace(trace_dir, phase_maps=maps)
    by_chip = {d: {k: float(v) for k, v in p.items()}
               for d, p in prof.per_device.items()}
    stages = sorted({k for p in by_chip.values() for k in p})
    mean = {k: sum(p.get(k, 0.0) for p in by_chip.values()) / len(by_chip)
            for k in stages} if by_chip else {}
    return mean, by_chip


def run(env) -> dict:
    spans, note = env.spans, env.note
    cfg, mix = env.config, env.traffic
    if mix.get("sync_between_trees") or mix.get("valid_sets"):
        raise NotImplementedError("this job kind runs trees back to back, "
                                  "with no sync and no validation set")
    rows, cols = cfg["shape"]["rows"], cfg["shape"]["cols"]
    params = dict(cfg["params"], verbosity=-1)
    expect = cfg["expect"]

    with spans.span("setup.import"):   # the program and JAX; no device yet
        try:
            import lightgbm_tpu as lgb
        except ImportError as e:
            raise CannotRunCell(f"the program cannot be imported: {e}") from e
    with spans.span("setup.refusal"):
        refuse_unless_supported()
    with spans.span("setup.workers"):
        # forked now, before JAX asks for a device: they count the
        # reference's blocks after the window, in numpy alone
        workers.shared_empty("bins_cm", (cols, rows), np.uint8)
        workers.shared_empty("clicked", (rows,), np.bool_)
        pool = workers.pool(_threads())
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
        from lightgbm_tpu import phases
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
    place = _placement(gb, rows)
    plan = _plan_counters(phases.PLAN_COUNTERS)
    with spans.span("setup.loss"):
        loss = [_logloss(y, gb.eval_scores(-1)[:, 0])]
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

    report, stage_s, stage_s_by_chip = None, None, None
    if env.trace:
        with spans.span("after.trace"):
            jax.profiler.stop_trace()
            # a chip's rows: the shapes the trace's operations carry
            report = trace_mod.reduce_xplane(
                trace_dir, rows // place["shards"], cols,
                expect["kernel_op_pattern"])
            stage_s, stage_s_by_chip = _stage_seconds(trace_dir, maps)
            shutil.rmtree(trace_dir, ignore_errors=True)

    with spans.span("after.copies"):
        copies = _copies_agree(jax, list(gb._pending))
    with spans.span("after.sync_trees"):
        bst._sync_trees()
    host_syncs = gb.host_sync_count - syncs0
    memory = device.memory_by_device()
    with spans.span("after.loss"):
        loss.append(_logloss(y, gb.eval_scores(-1)[:, 0]))
    model_text = bst.model_to_string()
    addends = str(gb.config.hist_dtype)
    with spans.span("after.reference"):
        replay = sref.check_first_tree(
            model_text, ubs, bins_cm, y, params,
            shard_rows=max(place["shard_rows"]), addend_dtype=addends,
            parts=_threads(), run=lambda groups: workers.starmap(
                pool, workers.call_on_shared,
                [(sref.spans_counts, ("bins_cm", "clicked")) + g
                 for g in groups]))

    leaves = _tree_leaves(model_text)
    done = sum(1 for n in leaves[1:1 + trees] if n > 1)
    resolved = {"hist_impl": gb.config.hist_impl,
                "hist_impl_reason": gb.hist_impl_reason,
                "fused_reason": gb.fused_reason,
                "tree_learner": gb.config.tree_learner,
                "leaf_batch": int(gb.config.leaf_batch),
                "hist_dtype": addends}
    pins = sorted(k for k in os.environ if k.startswith(PIN_PREFIX))
    fair_share = rows / place["shards"]
    checks = {
        "tree_replay": replay["ok"],
        "every_chip_same_tree": copies["ok"],
        "loss_fell": bool(loss[1] < loss[0]),
        "no_failed_tree": done == trees and leaves[0] > 1,
        "no_compile_in_window": compiles_window == 0,
        "fused_step": resolved["fused_reason"] == "" and bool(plan),
        "tree_learner_as_stated":
            resolved["tree_learner"] == expect["tree_learner"],
        "plan_as_stated": (place["plan"] == expect["plan"]
                           and place.get("hist_merge") == expect["hist_merge"]
                           and place["shards"] == expect["shards"]
                           and addends == expect["addends"]),
        # off a TPU (a rehearsal) the program picks its CPU kernel by rule
        "kernel_as_stated": (resolved["hist_impl"] == expect["hist_impl"]
                             if info["platform"] == "tpu" else None),
        "rows_on_every_chip": min(place["live_rows"]) >= 0.99 * fair_share,
        "no_pins": not pins,
    }
    capture = {"collective_stages_on_every_chip": None if not stage_s_by_chip
               else all(p.get(s, 0.0) > 0 for p in stage_s_by_chip.values()
                        for s in COLLECTIVE_STAGES),
               "chips_in_the_capture": len(stage_s_by_chip or {})}
    rounds = [int((rec.leaves > 0).sum())
              for rec in list(gb.round_log)[-trees:]]
    live = sum(int(rec.rows.sum()) for rec in list(gb.round_log)[-trees:])
    note("checks", checks)
    note("resolved", resolved)
    note("placement", place)
    note("pins", pins)
    note("loss", loss)
    note("window", {"trees": trees, "window_s": window_s,
                    "first_tree_device_s": t_tree, "leaves": leaves,
                    "host_rows": rows, "rows_per_chip": fair_share})
    note("capture", capture)
    # builder.live_row_share divides all shards' live rows by one chip's
    # rows (the shape the rooflines need): this is the share it means
    note("live_row_share", {
        "pct_of_the_hosts_rows": 100.0 * live / max(sum(rounds) * rows, 1),
        "rounds": sum(rounds),
        "the_reader_reads_it_times": place["shards"]})
    note("copies", copies)
    note("replay", replay)
    note("compared", {
        "splits": [{k: s[k] for k in ("node", "gain_short_by", "limit", "ok")}
                   for s in replay["splits"]],
        "leaf_counts_equal": replay["leaves"]["counts_ok"],
        "all_rows_reach_a_leaf": replay["all_rows_reach_a_leaf"],
        "leaf_error_over_limit": replay["leaves"]["worst_error_over_limit"],
        "leaf_error_over_scale": replay["leaves"]["worst_error_over_scale"],
        "leaf_limit": replay["leaves"]["limit"],
        "leaf_accumulation": replay["leaves"]["accumulation"],
        "leaf_carried": replay["leaves"]["worst_leaf"]["carried"],
        "leaf_sum_abs_g": replay["leaves"]["worst_leaf"]["sum_abs_g"],
        "leaf_rows": replay["leaves"]["worst_leaf"]["rows"],
        "leaves_held_within_twice_rtol":
            replay["leaves"]["held_within_twice_rtol"],
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
        "loss": loss, "compiles_in_window": compiles_window,
        "trees_grown": done, "trees": trees,
        "unequal_copies": copies["unequal_trees"],
        "least_live_rows_on_a_chip": min(place["live_rows"]),
        "least_allowed": 0.99 * fair_share,
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
                     "shards": place["shards"], "host_rows": rows,
                     "plan": plan, "stage_s": stage_s,
                     "stage_s_by_chip": stage_s_by_chip},
        # a chip's rows: what the trace's shapes and the rooflines mean
        "shape": {"rows": rows // place["shards"], "cols": cols,
                  "bins": int(params["max_bin"])},
        "memory": memory,
        "trace": report,
        "device": info,
    }
