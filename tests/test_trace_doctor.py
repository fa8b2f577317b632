"""Trace doctor: the static-analysis rules, in-suite (ISSUE 6).

The same rules ``scripts/lint_traces.py`` gates CI on, run here over
tiny programs so tier-1 catches a regression without the full canonical
battery: each TD rule fires on a seeded violation and stays silent on
the clean form; the recompile guard enforces the fused-step
one-compile-per-booster contract over 20 iterations and the serving
batcher's power-of-two ladder bound; the doctor's entry-point targets
lint clean at HEAD.
"""

import contextlib
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu.analysis import (Finding, RecompileError,
                                   RecompileGuard, TraceReport,
                                   cache_size, lint_hlo, lint_jaxpr,
                                   lower_hlo, merge_errors)
from lightgbm_tpu.analysis.doctor import (doctor_batcher,
                                          doctor_fused_step,
                                          doctor_predict, make_booster)


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


# ---------------------------------------------------------------- report

def test_finding_rejects_unknown_severity():
    with pytest.raises(ValueError):
        Finding(rule="TD001", severity="fatal", label="l", op_path="p",
                message="m")


def test_allowlist_waives_but_keeps_finding():
    rep = TraceReport(label="prog")
    rep.add("TD103", "error", "some/iota/op", "untagged collective")
    rep.add("TD103", "error", "other/op", "untagged collective")
    rep.apply_allowlist([("TD103", "*iota*")])
    assert len(rep.findings) == 2
    assert [f.waived for f in rep.findings] == [True, False]
    assert len(rep.errors) == 1          # only the unwaived one gates
    assert not rep.ok
    rep.apply_allowlist([("TD103", "prog:*")])   # label-anchored waiver
    assert rep.ok
    assert merge_errors([rep]) == []


# ----------------------------------------------------------- jaxpr rules

def test_td001_closure_constant_fires_and_argument_form_is_clean():
    big = np.ones((512, 1024), np.float32)           # 2 MiB

    def closes(x):
        return (x[None, :] * big).sum()

    def takes(x, b):
        return (x[None, :] * b).sum()
    x = np.ones(1024, np.float32)
    bad = lint_jaxpr(jax.make_jaxpr(closes)(x), label="closes")
    assert [f.rule for f in bad.errors] == ["TD001"]
    assert bad.errors[0].nbytes == big.nbytes
    good = lint_jaxpr(jax.make_jaxpr(takes)(x, big), label="takes")
    assert good.ok


def test_td002_host_callback_fires_unless_allowed():
    def f(x):
        jax.debug.print("x0={v}", v=x[0])
        return x * 2
    closed = jax.make_jaxpr(f)(np.ones(4, np.float32))
    rep = lint_jaxpr(closed, label="cb")
    assert any(f.rule == "TD002" for f in rep.errors)
    assert lint_jaxpr(closed, label="cb", allow_callbacks=True).ok


def test_td003_f64_widening_fires_only_under_widening():
    with jax.enable_x64(True):
        closed = jax.make_jaxpr(
            lambda x: x.astype(jnp.float64) + 1.0)(
                np.ones(4, np.float32))
    rep = lint_jaxpr(closed, label="widen")
    assert any(f.rule == "TD003" for f in rep.errors)
    clean = jax.make_jaxpr(
        lambda x: x.astype(jnp.bfloat16))(np.ones(4, np.float32))
    assert lint_jaxpr(clean, label="narrow").ok


def test_td004_cpu_donation_fires_on_hlo_and_accelerator_is_exempt():
    hlo = jax.jit(lambda x: x * 2.0, donate_argnums=(0,)).lower(
        jnp.ones((64, 64), jnp.float32)).compile().as_text()
    rep = lint_hlo(hlo, label="donate", backend="cpu")
    assert any(f.rule == "TD004" for f in rep.errors)
    assert lint_hlo(hlo, label="donate", backend="tpu").ok


# ------------------------------------------------------------- hlo rules

def test_td101_oversized_lowered_constant_fires():
    # random data: XLA folds a splat (all-ones) constant to a scalar
    # broadcast, which is exactly the benign form TD101 must NOT flag
    big = np.random.RandomState(0).rand(512, 1024).astype(np.float32)
    hlo = lower_hlo(lambda x: x + big,
                    jnp.ones((512, 1024), jnp.float32))
    rep = lint_hlo(hlo, label="const")
    assert any(f.rule == "TD101" for f in rep.errors)


def test_td103_untagged_collective_fires_tagged_is_clean():
    from jax.experimental.shard_map import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    n = len(jax.devices())
    if n < 2:
        pytest.skip("needs a multi-device mesh")
    mesh = Mesh(jax.devices(), ("d",))

    def untagged(x):
        return jax.lax.psum(x, "d")

    def tagged(x):
        with jax.named_scope("hist_merge"):
            return jax.lax.psum(x, "d")
    rows = 1 << 14                                   # 64 KiB result
    for body, expect_ok in ((untagged, False), (tagged, True)):
        f = shard_map(body, mesh=mesh, in_specs=P("d"), out_specs=P())
        hlo = lower_hlo(f, jnp.ones((n, rows), jnp.float32))
        rep = lint_hlo(hlo, label=body.__name__)
        assert rep.ok == expect_ok, rep.render(verbose=True)
        if not expect_ok:
            assert [f.rule for f in rep.errors] == ["TD103"]


# -------------------------------------------------------- recompile guard

def test_recompile_guard_trips_on_shape_unstable_fn(recompile_guard):
    f = jax.jit(lambda x: x * 2.0)
    with pytest.raises(RecompileError) as ei:
        with recompile_guard(max_compiles=1, label="unstable"):
            for n in (4, 8, 12, 16):                 # every shape novel
                f(jnp.ones(n, jnp.float32)).block_until_ready()
    assert any(fd.rule == "TD201" for fd in ei.value.report.findings)


def test_recompile_guard_quiet_on_stable_shapes():
    f = jax.jit(lambda x: x + 1.0)
    f(jnp.ones(8, jnp.float32)).block_until_ready()  # warm
    with RecompileGuard(max_compiles=0, label="steady"):
        for _ in range(5):
            f(jnp.ones(8, jnp.float32)).block_until_ready()


def test_recompile_guard_does_not_mask_inner_errors():
    with pytest.raises(ValueError, match="inner"):
        with RecompileGuard(max_compiles=0, label="masked"):
            jax.jit(lambda x: x * 3.0)(
                jnp.ones(16, jnp.float32)).block_until_ready()
            raise ValueError("inner")


def test_fused_step_compiles_once_per_booster_over_20_iters():
    """Satellite: steady-state fused training never recompiles — one
    signature per booster, zero compiles after warmup across 20 more
    iterations (dispatch + sync)."""
    bst = make_booster("plain", "serial", rounds=2, fused=True)
    gb = bst._gbdt
    assert gb._fused_jit is not None, "fused driver did not engage"
    with _pin_fused(True):
        for _ in range(2):                           # warm this process
            bst.update()
        gb.sync()
        with RecompileGuard(max_compiles=0, label="fused_steady"):
            for _ in range(20):
                bst.update()
            gb.sync()
    assert cache_size(gb._fused_jit) == 1


def test_batcher_ladder_bounds_compiled_signatures():
    """Satellite: a mixed-size burst through the micro-batcher stays
    within the power-of-two ladder bound of compiled signatures."""
    from lightgbm_tpu.serving.batcher import MicroBatcher
    jit_f = jax.jit(lambda X: X.sum(axis=1))

    def predict_fn(Xb):
        return np.asarray(jit_f(jnp.asarray(Xb, jnp.float32)))

    max_rows, min_bucket = 64, 8
    mb = MicroBatcher(predict_fn, max_batch_rows=max_rows,
                      max_wait_us=100, min_bucket=min_bucket)
    try:
        for n in (1, 3, 5, 8, 9, 13, 17, 21, 33, 40, 64, 2, 7, 50):
            out = mb.submit(np.zeros((n, 4), np.float64))
            assert out.shape == (n,)
    finally:
        mb.close()
    bound = int(math.log2(max_rows)) + 1
    assert 1 <= cache_size(jit_f) <= bound


# ------------------------------------------------------- doctor entry pts

def test_doctor_head_targets_are_clean():
    """The doctor's entry-point lints pass at HEAD: fused-step jaxpr,
    packed-ensemble walk (jaxpr + HLO, zero collectives), serving
    batcher ladder + program."""
    bst = make_booster("plain", "serial", rounds=2, fused=True)
    reports = doctor_fused_step(bst, compile_hlo=False)
    reports += doctor_predict(bst)
    reports += doctor_batcher(bst)
    errs = merge_errors(reports)
    assert not errs, "\n".join(r.render(verbose=True) for r in reports)


def test_profiler_phase_asserts_membership():
    from lightgbm_tpu import profiler
    from lightgbm_tpu.phases import KNOWN_PHASES
    with profiler.phase("build"):
        pass
    assert "build" in KNOWN_PHASES
    with pytest.raises(ValueError, match="phases.py"):
        with profiler.phase("not_a_phase"):
            pass
