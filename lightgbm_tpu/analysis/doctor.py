"""The trace doctor: run every static pass over the canonical entry
points.

Entry points (per canonical config):

- **fused step** — trains a tiny booster with the fused driver pinned
  on, then re-traces ``gbdt._fused_step_entry`` with the exact argument
  pytree ``_fused_dispatch`` passes. The jaxpr pass sees closure
  constants / callbacks / widenings; the HLO pass (of the SAME jit the
  trainer dispatches, donation flags included) sees donation, lowered
  constants and collectives.
- **tree builder** — the data-parallel plan's ``build_tree`` on the
  local mesh over synthetic inputs (the comms auditor's program);
  collectives must carry the ``hist_merge`` / ``winner_sync`` phases.
- **round body** — the fused step of a booster trained with
  ``hist_impl=scatter``, so that the program holds the grow round's XLA
  formulation (what a TPU runs around its kernel; ``auto`` on a CPU
  hands relabel and partition to the native custom calls), and the
  data-parallel ``build_tree`` the same way: no per-row read of a small
  table in the ``build`` stage (TD008).
- **predict ensemble** — ``ops.predict_ensemble._walk`` over the packed
  trained ensemble; the serving walk must stage NO collectives and no
  host work at all.
- **serving batcher** — a mixed-size request burst through
  :class:`~..serving.batcher.MicroBatcher`; the jitted predict path
  must stay within the power-of-two bucket ladder
  (``log2(max_batch_rows) + 1`` signatures, TD201) and its program
  lints clean.
- **serving compiled** — the tensorized whole-ensemble program
  (``codegen.CompiledEnsemble``, ISSUE 15): no collectives, no host
  callbacks (TD002), and a full ladder warm must leave exactly one
  compiled signature per rung (TD201 — the registry's zero-on-path-
  compiles publish gate).

Canonical configs are the feature matrix the repo actually ships:
plain / EFB / quantized / categorical / multiclass / lambdarank, each
under serial and (when the host exposes a multi-device mesh)
data-parallel learners.
``scripts/lint_traces.py`` runs the full battery as the CI gate;
``python -m lightgbm_tpu trace-doctor`` is the user-facing form;
``tests/test_trace_doctor.py`` runs a tier-1 subset.
"""

from __future__ import annotations

import contextlib
import math
import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..phases import COLLECTIVE_PHASES
from .hlo_lint import lint_hlo
from .hlo_walk import lower_hlo
from .jaxpr_lint import lint_deferred_guard, lint_jaxpr
from .recompile_guard import cache_size
from .report import TraceReport, merge_errors

__all__ = ["CANONICAL_CONFIGS", "PARALLEL_MODES", "ROUND_BODY_CELLS",
           "make_booster", "doctor_fused_step", "doctor_round_body",
           "doctor_tree_builder", "doctor_predict",
           "doctor_batcher", "doctor_serving", "run_doctor",
           "doctor_main"]

# name -> (train-param overrides, dataset kwargs)
CANONICAL_CONFIGS: Dict[str, Tuple[dict, dict]] = {
    "plain": ({}, {}),
    "efb": ({"enable_bundle": True}, {}),
    "quantized": ({"use_quantized_grad": True,
                   "num_grad_quant_bins": 4}, {}),
    "categorical": ({}, {"categorical_feature": [0]}),
    # class-batched multiclass: the fused step must stage ONE build
    # (TD005), not num_class unrolled copies
    "multiclass": ({"objective": "multiclass", "num_class": 3,
                    "metric": "multi_logloss", "num_leaves": 5}, {}),
    # armed NaN guard over the RNG-stream-sensitive bagging config: the
    # divergence flag must stay a deferred program output (TD006), not
    # an eager per-iteration host check
    "nan_guard": ({"nan_guard": "rollback", "bagging_fraction": 0.8,
                   "bagging_freq": 2, "bagging_seed": 7}, {}),
    # full telemetry stack armed (event log + live endpoints + armed
    # guard): the sync-free contract must survive observation — no host
    # callbacks enter the staged program (TD002) and the deferred guard
    # flag stays a program output (TD006). event_log="auto" is rerouted
    # to a scratch dir by make_booster.
    "telemetry": ({"nan_guard": "rollback", "event_log": "auto",
                   "telemetry_port": 0}, {}),
    # a ranking objective: its query lattices are device arrays and must
    # reach the fused step as arguments, not closure constants (TD001;
    # at MS-LTR's size they are tens of megabytes). Uneven queries, so
    # several buckets of the layout are in the program.
    "lambdarank": ({"objective": "lambdarank", "metric": "ndcg",
                    "min_data_in_leaf": 2},
                   {"group": [1, 2, 31, 40, 6, 80]}),
}
PARALLEL_MODES = ("serial", "data")
# (config, mode) cells whose round body TD008 reads: every way a row
# reads its pending split (numerical, bitset, bundle decode, per-row
# gradients around it) and the row mesh
ROUND_BODY_CELLS = (("plain", "serial"), ("categorical", "serial"),
                    ("efb", "serial"), ("lambdarank", "serial"),
                    ("plain", "data"))

_BASE_PARAMS = dict(objective="binary", metric="auc", num_leaves=7,
                    learning_rate=0.2, min_data_in_leaf=5, verbosity=-1)


@contextlib.contextmanager
def _pin_fused(on: bool):
    prev = os.environ.get("LIGHTGBM_TPU_FUSED_TRAIN")
    os.environ["LIGHTGBM_TPU_FUSED_TRAIN"] = "1" if on else "0"
    try:
        yield
    finally:
        if prev is None:
            os.environ.pop("LIGHTGBM_TPU_FUSED_TRAIN", None)
        else:
            os.environ["LIGHTGBM_TPU_FUSED_TRAIN"] = prev


def _synth(config: str, *, n: int = 160, f: int = 8, seed: int = 0):
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(n, f)).astype(np.float32)
    if config == "categorical":
        X[:, 0] = rng.randint(0, 5, size=n)
    if config == "efb":
        # four mutually-exclusive sparse columns so a bundle actually
        # forms AND the plan is kept (it has to shrink the matrix to 3/4:
        # an exclusive pair among 8 columns made 7 bundles and was dropped,
        # so this cell ran unbundled until PR 37)
        which = rng.randint(0, 4, size=n)
        for k in range(4):
            X[:, f - 4 + k] = np.where(which == k, X[:, f - 4 + k], 0.0)
    if config == "multiclass":
        y = (X[:, :3] + 0.5 * rng.normal(size=(n, 3))).argmax(1) \
            .astype(np.float32)
    elif config == "lambdarank":
        y = np.clip(np.round(X[:, 1] + 0.5 * X[:, 2] * X[:, 3] + 1.0),
                    0, 4).astype(np.float32)
    else:
        y = (X[:, 1] + 0.5 * X[:, 2] * X[:, 3]
             + 0.3 * rng.normal(size=n) > 0).astype(np.float32)
    return X, y


def make_booster(config: str = "plain", mode: str = "serial", *,
                 rounds: int = 2, n: int = 160, f: int = 8,
                 fused: bool = True, **params_over):
    """Train the tiny canonical booster for one (config, mode) cell
    (``params_over``: train params on top of the cell's)."""
    import lightgbm_tpu as lgb
    overrides, ds_kw = CANONICAL_CONFIGS[config]
    X, y = _synth(config, n=n, f=f)
    # explicit even for serial: on a multi-device host the trainer
    # otherwise auto-selects a parallel plan
    params = dict(_BASE_PARAMS, **overrides, tree_learner=mode,
                  **params_over)
    if params.get("event_log"):
        # telemetry cell: keep the event log (and auto's output_model
        # anchor) out of the caller's cwd
        import tempfile
        scratch = tempfile.mkdtemp(prefix="lgbtpu_doctor_")
        params["event_log"] = os.path.join(scratch,
                                           "doctor.events.jsonl")
    with _pin_fused(fused):
        ds = lgb.Dataset(X, label=y, **ds_kw)
        return lgb.train(params, ds, num_boost_round=rounds)


def _fused_trace_args(gb):
    """The exact argument pytree ``_fused_dispatch`` passes (bag mask
    drawn the no-bagging way; masks are data, not structure)."""
    import jax.numpy as jnp
    mask = gb._host_bag_mask(gb.iter_)
    if mask is None:
        mask = (gb.train_dd.row_leaf0 >= 0).astype(jnp.float32)
    return (gb.scores, tuple(gb.valid_scores), mask, gb._feature_mask(),
            jnp.asarray(gb.iter_, jnp.int32),
            jnp.asarray(gb.shrinkage, jnp.float32),
            gb._fused_data_args())


def doctor_fused_step(bst, *, label: str = "fused_step",
                      compile_hlo: bool = True, round_body: bool = False,
                      allow: Sequence[Tuple[str, str]] = ()
                      ) -> List[TraceReport]:
    """Lint the fused boosting step of a trained booster. Returns []
    with an info report when the fused gate pins the legacy driver for
    this config (the legacy phases dispatch separate small programs —
    the builder/predict targets cover them). ``round_body`` adds TD008
    (:func:`doctor_round_body`)."""
    import jax
    gb = bst._gbdt
    reports: List[TraceReport] = []
    with _pin_fused(True):
        reason = gb._fused_gate_reason()
    if reason:
        rep = TraceReport(label=label)
        rep.add("TD000", "info", "fused_gate",
                f"fused driver unavailable for this config: {reason}")
        return [rep]
    args = _fused_trace_args(gb)
    closed = jax.make_jaxpr(gb._fused_step_entry)(*args)
    # TD005 budget: one build per program when single-class or when the
    # class-batch gate is open; a config the gate excludes (linear /
    # forced / CEGB) legitimately unrolls, so the rule is skipped
    build_budget = 1 if (gb.K == 1 or gb.class_batch_ok) else None
    build_rows = None
    if round_body:
        # TD008 sizes the build stage's gathers by the rows a device
        # holds, which must exceed every lattice the split search
        # gathers over (12 slots x 54 bins pass 256 rows a shard in the
        # categorical cell under a row mesh: not in ROUND_BODY_CELLS)
        shards = (gb.plan.num_shards
                  if gb.plan is not None and gb.plan.rows_sharded else 1)
        build_rows = int(gb.train_dd.row_leaf0.shape[0]) // shards
    reports.append(lint_jaxpr(
        closed, label=f"{label}/jaxpr", max_build_programs=build_budget,
        build_rows=build_rows, allow=allow))
    if getattr(gb, "_nan_guard", "off") != "off":
        # TD006: armed guard — the finite flag must be a deferred
        # program output next to the no-split stop flag
        reports.append(lint_deferred_guard(
            closed, label=f"{label}/guard", expect_flags=2,
            allow=allow))
    if compile_hlo:
        # lower through the trainer's own jit wrapper (donation flags
        # and all), not a fresh jax.jit — TD004 must see what dispatch
        # compiles
        if gb._fused_jit is None:
            gb._fused_dispatch()
            gb.sync()
            args = _fused_trace_args(gb)
        hlo = gb._fused_jit.lower(*args).compile().as_text()
        reports.append(lint_hlo(
            hlo, label=f"{label}/hlo",
            allowed_phases=COLLECTIVE_PHASES, allow=allow))
    return reports


def doctor_round_body(config: str = "plain", mode: str = "serial", *,
                      label: Optional[str] = None,
                      allow: Sequence[Tuple[str, str]] = ()
                      ) -> List[TraceReport]:
    """The jaxpr rules (TD008 among them) over the fused step of one
    (config, mode) cell, traced with the grow round's XLA formulation
    in it (module docstring). Nothing here depends on the backend."""
    bst = make_booster(config, mode, hist_impl="scatter")
    return doctor_fused_step(
        bst, label=label or f"round_body[{config}/{mode}]",
        compile_hlo=False, round_body=True, allow=allow)


def doctor_tree_builder(*, label: str = "tree_builder",
                        R: int = 256, F: int = 8, B: int = 16,
                        allow: Sequence[Tuple[str, str]] = ()
                        ) -> List[TraceReport]:
    """Lint the data-parallel tree-build program (the comms auditor's
    synthetic target) on the local mesh."""
    import jax
    if len(jax.devices()) < 2:
        rep = TraceReport(label=label)
        rep.add("TD000", "info", "mesh",
                "single-device host: data-parallel build not lintable")
        return [rep]
    from ..ops.split import SplitParams
    from ..parallel.comms import _synthetic_inputs
    from ..parallel.data_parallel import DataParallelPlan
    plan = DataParallelPlan(hist_merge="reduce_scatter")
    bins, gh, rl0, meta = _synthetic_inputs(R, F, B)
    kw = dict(num_leaves=7, leaf_batch=4, max_depth=-1, num_bins=B,
              hist_dtype="float32", block_rows=R // plan.num_shards,
              split_params=SplitParams(min_data_in_leaf=2,
                                       min_sum_hessian_in_leaf=1e-3))

    def fn(b, g, rl):
        return plan.build_tree(b, g, rl, *meta, **kw)[0]
    sharded = (plan.shard_bins(bins), plan.shard_rows(gh),
               plan.shard_rows(rl0))
    closed = jax.make_jaxpr(fn)(*sharded)
    hlo = lower_hlo(fn, *sharded)
    # TD008 reads the round's XLA formulation (``auto`` is the native
    # custom calls on a CPU); traced only, so any R costs the same
    closed_xla = jax.make_jaxpr(
        lambda b, g, rl: plan.build_tree(b, g, rl, *meta,
                                         hist_impl="scatter", **kw)[0])(
        *sharded)
    return [lint_jaxpr(closed, label=f"{label}/jaxpr", allow=allow),
            lint_jaxpr(closed_xla, label=f"{label}/round_body",
                       build_rows=R // plan.num_shards, allow=allow),
            lint_hlo(hlo, label=f"{label}/hlo",
                     allowed_phases=COLLECTIVE_PHASES, allow=allow)]


def _packed_ensemble(bst):
    from ..ops.predict_ensemble import pack_ensemble
    return pack_ensemble(bst._trees)


def doctor_predict(bst, *, label: str = "predict_ensemble",
                   rows: int = 16,
                   allow: Sequence[Tuple[str, str]] = ()
                   ) -> List[TraceReport]:
    """Lint the packed-ensemble device walk: no collectives, no host
    work, no embedded model constants (the ensemble is an argument)."""
    import jax
    import jax.numpy as jnp
    from ..ops.predict_ensemble import _walk
    ens = _packed_ensemble(bst)
    X = jnp.zeros((rows, bst.num_feature()), jnp.float32)
    closed = jax.make_jaxpr(_walk)(ens, X)
    hlo = lower_hlo(_walk, ens, X)
    return [lint_jaxpr(closed, label=f"{label}/jaxpr", allow=allow),
            lint_hlo(hlo, label=f"{label}/hlo",
                     allowed_phases=frozenset(), allow=allow)]


def doctor_batcher(bst, *, label: str = "serving_batcher",
                   max_batch_rows: int = 64, min_bucket: int = 8,
                   burst: Sequence[int] = (3, 5, 8, 13, 21, 40, 64,
                                           7, 9, 33),
                   allow: Sequence[Tuple[str, str]] = ()
                   ) -> List[TraceReport]:
    """Run a mixed-size burst through the micro-batcher over the jitted
    ensemble walk: the ladder bound caps compiled signatures (TD201),
    and the program compiled for one bucket lints clean."""
    import jax
    import jax.numpy as jnp
    from ..ops.predict_ensemble import _walk
    from ..serving.batcher import MicroBatcher
    ens = _packed_ensemble(bst)
    F = bst.num_feature()
    jit_walk = jax.jit(_walk)

    def predict_fn(Xb):
        out = jit_walk(ens, jnp.asarray(Xb, jnp.float32))
        return np.asarray(out).reshape(len(Xb), -1)[:, 0]

    mb = MicroBatcher(predict_fn, max_batch_rows=max_batch_rows,
                      max_wait_us=100, min_bucket=min_bucket)
    try:
        for n in burst:
            mb.submit(np.zeros((n, F), np.float64))
    finally:
        mb.close()
    rep = TraceReport(label=label)
    bound = int(math.log2(max_batch_rows)) + 1
    sigs = cache_size(jit_walk)
    if sigs > bound:
        rep.add("TD201", "error", "bucket_ladder",
                f"{sigs} compiled signatures after a mixed burst; the "
                f"power-of-two ladder bounds the batcher to {bound}")
    hlo = lower_hlo(_walk, ens,
                    jnp.zeros((min_bucket, F), jnp.float32))
    return [rep.apply_allowlist(allow),
            lint_hlo(hlo, label=f"{label}/hlo",
                     allowed_phases=frozenset(), allow=allow)]


def doctor_serving(bst, *, label: str = "serving_compiled",
                   max_batch_rows: int = 64, min_bucket: int = 8,
                   allow: Sequence[Tuple[str, str]] = ()
                   ) -> List[TraceReport]:
    """Lint the tensorized compiled-ensemble serving program (ISSUE
    15): the whole-ensemble gather walk must stage no collectives and
    no host callbacks (TD002 — one self-contained XLA program per
    request batch is the fleet's latency contract), and warming the
    full batch ladder must leave exactly one compiled signature per
    rung (TD201: the registry publishes a version only after ``warm``,
    so any signature beyond the ladder is an on-path compile waiting
    to happen)."""
    from ..codegen import CompiledEnsemble

    rep = TraceReport(label=label)
    try:
        ce = CompiledEnsemble(bst)
    except (ValueError, TypeError) as e:
        rep.add("TD000", "info", "tensorize",
                f"ensemble not tensorizable: {e}")
        return [rep]
    rungs = []
    r = min_bucket
    while r < max_batch_rows:
        rungs.append(r)
        r *= 2
    rungs.append(max_batch_rows)
    ce.warm(rungs)
    bound = len(rungs)
    sigs = ce.compiled_signatures()
    if sigs > bound:
        rep.add("TD201", "error", "bucket_ladder",
                f"{sigs} compiled signatures after warming the "
                f"{bound}-rung ladder; the registry's publish gate "
                "promises zero on-path compiles beyond it")
    hlo = ce.lower_serving(rows=min_bucket).as_text()
    return [rep.apply_allowlist(allow),
            lint_hlo(hlo, label=f"{label}/hlo",
                     allowed_phases=frozenset(), allow=allow)]


def run_doctor(configs: Optional[Sequence[str]] = None,
               modes: Optional[Sequence[str]] = None, *,
               compile_hlo: bool = True,
               allow: Sequence[Tuple[str, str]] = (),
               verbose: bool = False) -> List[TraceReport]:
    """The full battery: per (config, mode) cell the fused step, plus
    the mode-independent builder / predict / batcher targets once."""
    reports: List[TraceReport] = []
    configs = list(configs or CANONICAL_CONFIGS)
    modes = list(modes or PARALLEL_MODES)
    first_bst = None
    for cfg in configs:
        for mode in modes:
            cell = f"{cfg}/{mode}"
            bst = make_booster(cfg, mode)
            if first_bst is None:
                first_bst = bst
            reports += doctor_fused_step(
                bst, label=f"fused_step[{cell}]",
                compile_hlo=compile_hlo, allow=allow)
    for cfg, mode in ROUND_BODY_CELLS:
        if cfg in configs and mode in modes:
            reports += doctor_round_body(cfg, mode, allow=allow)
    reports += doctor_tree_builder(allow=allow)
    if first_bst is not None:
        reports += doctor_predict(first_bst, allow=allow)
        reports += doctor_batcher(first_bst, allow=allow)
        reports += doctor_serving(first_bst, allow=allow)
    return reports


def doctor_main(argv: Optional[Sequence[str]] = None) -> int:
    """CLI driver (``python -m lightgbm_tpu trace-doctor``). Exit 0
    when every report is clean, 1 otherwise."""
    import argparse
    p = argparse.ArgumentParser(
        prog="lightgbm_tpu trace-doctor",
        description="static analysis over the hot-path programs "
                    "(jaxpr lint, HLO lint, recompile bounds)")
    p.add_argument("--config", action="append", dest="configs",
                   choices=sorted(CANONICAL_CONFIGS),
                   help="canonical config(s); default: all")
    p.add_argument("--mode", action="append", dest="modes",
                   choices=PARALLEL_MODES,
                   help="tree-learner mode(s); default: all")
    p.add_argument("--no-hlo", action="store_true",
                   help="skip the compiled-HLO passes (faster)")
    p.add_argument("--allow", action="append", default=[],
                   metavar="RULE:PATTERN",
                   help="waive findings, e.g. TD103:'*iota*'")
    p.add_argument("-v", "--verbose", action="store_true")
    ns = p.parse_args(argv)
    allow = tuple(a.split(":", 1) for a in ns.allow)
    reports = run_doctor(ns.configs, ns.modes,
                         compile_hlo=not ns.no_hlo, allow=allow)
    for r in reports:
        print(r.render(verbose=ns.verbose))
    errs = merge_errors(reports)
    print(f"trace-doctor: {len(reports)} report(s), "
          f"{len(errs)} error(s)")
    return 1 if errs else 0
