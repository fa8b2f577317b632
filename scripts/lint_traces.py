#!/usr/bin/env python
"""CI gate: the trace-doctor battery over the canonical configs.

Runs the static-analysis passes (``lightgbm_tpu/analysis/``) over the
repo's hot-path entry points — fused boosting step, data-parallel tree
builder, packed-ensemble predict walk, serving micro-batcher, and the
tensorized compiled-ensemble serving program (no host callbacks
(TD002), ladder-bounded signatures (TD201)) — for
every canonical config cell (plain / EFB / quantized / categorical /
multiclass / nan_guard / telemetry / lambdarank × serial /
data-parallel) on the 8-virtual-device CPU mesh. The lambdarank cell
guards the ranking objective's query lattices: they must reach the fused
step as arguments (TD001; 41 MB at MS-LTR's size). The telemetry cell trains with the full
observation stack armed (event log + live introspection server) and
must lint identically — the subsystem's zero-host-callback contract
(TD002) and the deferred guard flag (TD006) survive being watched.
Exit 0 when
every report is clean, 1 with a diagnostic when any error-severity
finding survives.

Self-test modes (``--seed <class>``) deliberately inject one regression
of each rule class the doctor exists to catch and run the matching pass
over it — the gate must exit NON-zero, proving the rule still fires:

- ``closure-const``  — a >=1 MiB dense array closed over by a jitted fn
                       (TD001, the fused-step ~300 MB incident class)
- ``cpu-donation``   — ``donate_argnums`` compiled on the CPU backend
                       (TD004, the corrupted-valid-metrics incident)
- ``phase-collective`` — an untagged multi-MB ``psum`` on the mesh
                       (TD103, the feature-parallel hidden-psum class)
- ``recompile-blowout`` — a shape-unstable fn recompiling per call
                       (TD201, ladder/steady-state discipline)
- ``class-unroll``   — a program staging one grow loop per class under
                       the ``build`` phase, the K-unrolled multiclass
                       iteration shape (TD005, the class_batch knob's
                       regression class)
- ``nan-guard-sync`` — a boosting step that checks its NaN flag eagerly
                       instead of returning it as a deferred device
                       output (TD006, the resilience PR's
                       host-sync-per-iteration regression class)
- ``row-table-read`` — a round body that reads a row's pending split by
                       ``jnp.take`` from an ``[L+1]`` table and counts
                       children by ``segment_sum`` (TD008, the 12.0 s
                       of an 18.3 s Higgs tree that PR 29 removed)

Run: python scripts/lint_traces.py [--fast] [--seed CLASS]
(CPU-only, no hardware needed; ``--fast`` lints one config cell and
skips compiled-HLO passes — the pre-push smoke form.)
"""

import argparse
import importlib.util
import os
import sys


def _load_probe():
    here = os.path.dirname(os.path.abspath(__file__))
    spec = importlib.util.spec_from_file_location(
        "_probe", os.path.join(here, "_probe.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


SEED_CLASSES = ("closure-const", "cpu-donation", "phase-collective",
                "recompile-blowout", "class-unroll", "nan-guard-sync",
                "row-table-read")


def _seed_closure_const() -> list:
    import jax
    import numpy as np
    from lightgbm_tpu.analysis import lint_jaxpr
    big = np.ones((512, 1024), np.float32)          # 2 MiB

    def f(x):
        return (x[None, :] * big).sum()
    closed = jax.make_jaxpr(f)(np.ones(1024, np.float32))
    return [lint_jaxpr(closed, label="seed/closure_const")]


def _seed_cpu_donation() -> list:
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.analysis import lint_hlo

    def f(x):
        return x * 2.0
    hlo = jax.jit(f, donate_argnums=(0,)).lower(
        jnp.ones((256, 256), jnp.float32)).compile().as_text()
    return [lint_hlo(hlo, label="seed/cpu_donation", backend="cpu")]


def _seed_phase_collective() -> list:
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from jax.experimental.shard_map import shard_map
    from lightgbm_tpu.analysis import lint_hlo, lower_hlo
    n = len(jax.devices())
    mesh = Mesh(jax.devices(), ("d",))

    def body(x):
        return jax.lax.psum(x, "d")                 # no phase tag
    f = shard_map(body, mesh=mesh, in_specs=P("d"), out_specs=P())
    hlo = lower_hlo(f, jnp.ones((n, 1 << 18), jnp.float32))
    return [lint_hlo(hlo, label="seed/phase_collective")]


def _seed_recompile_blowout() -> list:
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.analysis import RecompileGuard
    f = jax.jit(lambda x: x * 2.0)
    with RecompileGuard(max_compiles=2, label="seed/recompile_blowout",
                        strict=False) as g:
        for n in (8, 16, 24, 32, 40):               # every shape novel
            f(jnp.ones(n, jnp.float32)).block_until_ready()
    return [g.report]


def _seed_class_unroll() -> list:
    """Plant the exact regression shape the class_batch work removed:
    one ``build``-tagged grow loop traced per class (K=3 unrolled),
    linted with the class-batched budget of ONE build per program."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu import profiler
    from lightgbm_tpu.analysis import lint_jaxpr

    def grow_one(gh_k):
        def body(c):
            i, acc = c
            return i + 1, acc + gh_k.sum()
        return jax.lax.while_loop(lambda c: c[0] < 4, body,
                                  (jnp.int32(0), jnp.float32(0.0)))[1]

    def step(gh):                       # gh [K, R]: per-class grads
        outs = []
        for k in range(gh.shape[0]):    # the K-unrolled anti-pattern
            with profiler.stage("build"):
                outs.append(grow_one(gh[k]))
        return jnp.stack(outs)
    closed = jax.make_jaxpr(step)(jnp.ones((3, 64), jnp.float32))
    return [lint_jaxpr(closed, label="seed/class_unroll",
                       max_build_programs=1)]


def _seed_nan_guard_sync() -> list:
    """Plant the eager-guard regression TD006 exists for: a boosting
    step that device_get()s its finite flag inside the step (host sync
    per iteration) and therefore returns only data — NO scalar-bool
    flags reach the program interface."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.analysis import lint_deferred_guard

    def step(scores, g):
        new_scores = scores - 0.1 * g
        # the anti-pattern: the finite check never becomes an output
        # (a real implementation would bool() it right here, forcing
        # the sync); the traced program exposes zero deferred flags
        _ = jnp.all(jnp.isfinite(new_scores))
        return new_scores
    closed = jax.make_jaxpr(step)(jnp.ones((2, 64), jnp.float32),
                                  jnp.ones((2, 64), jnp.float32))
    return [lint_deferred_guard(closed, label="seed/nan_guard_sync",
                                expect_flags=2)]


def _seed_row_table_read() -> list:
    """Plant the round body as it stood before PR 29: the round's
    records scattered into per-leaf tables and read back by R rows, the
    children counted by a ``segment_sum`` of R ones."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu import profiler
    from lightgbm_tpu.analysis import lint_jaxpr
    R, L = 4096, 15

    def body(row_leaf, sel, feat):
        with profiler.stage("build"):
            pend_feat = jnp.zeros((L + 1,), jnp.int32).at[sel].set(feat)
            f_r = jnp.take(pend_feat, row_leaf)
            cnt = jax.ops.segment_sum(jnp.ones((R,), jnp.int32), row_leaf,
                                      num_segments=L + 1)
            return f_r, jnp.take(cnt, sel)
    closed = jax.make_jaxpr(body)(jnp.zeros((R,), jnp.int32),
                                  jnp.arange(4, dtype=jnp.int32),
                                  jnp.arange(4, dtype=jnp.int32))
    return [lint_jaxpr(closed, label="seed/row_table_read", build_rows=R)]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--seed", choices=SEED_CLASSES,
                   help="inject one deliberate regression and verify "
                        "the matching rule fires (self-test; the run "
                        "exits non-zero when the rule works)")
    p.add_argument("--fast", action="store_true",
                   help="one config cell, jaxpr passes only")
    p.add_argument("--config", action="append", dest="configs")
    p.add_argument("--mode", action="append", dest="modes")
    p.add_argument("-v", "--verbose", action="store_true")
    ns = p.parse_args(argv)

    probe = _load_probe()
    probe.pin_virtual_mesh(int(os.environ.get("AUDIT_DEVICES", "8")))
    sys.path.insert(0, probe.REPO_ROOT)
    from lightgbm_tpu.analysis import merge_errors

    if ns.seed:
        reports = {
            "closure-const": _seed_closure_const,
            "cpu-donation": _seed_cpu_donation,
            "phase-collective": _seed_phase_collective,
            "recompile-blowout": _seed_recompile_blowout,
            "class-unroll": _seed_class_unroll,
            "nan-guard-sync": _seed_nan_guard_sync,
            "row-table-read": _seed_row_table_read,
        }[ns.seed]()
        for r in reports:
            print(r.render(verbose=True))
        errs = merge_errors(reports)
        if errs:
            print(f"seeded regression '{ns.seed}' DETECTED "
                  f"({len(errs)} error(s)) — the rule works",
                  file=sys.stderr)
            return 1
        print(f"seeded regression '{ns.seed}' NOT detected — "
              "the rule is broken", file=sys.stderr)
        return 2

    from lightgbm_tpu.analysis import run_doctor
    configs = ns.configs or (["plain"] if ns.fast else None)
    modes = ns.modes or (["serial"] if ns.fast else None)
    reports = run_doctor(configs, modes, compile_hlo=not ns.fast)
    for r in reports:
        print(r.render(verbose=ns.verbose))
    errs = merge_errors(reports)
    print(f"lint_traces: {len(reports)} report(s), {len(errs)} "
          f"error(s)")
    if errs:
        print("TRACE LINT FAILED", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
