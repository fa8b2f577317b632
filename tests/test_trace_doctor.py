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
from lightgbm_tpu.analysis.doctor import (ROUND_BODY_CELLS,
                                          doctor_batcher,
                                          doctor_fused_step,
                                          doctor_predict,
                                          doctor_round_body,
                                          doctor_tree_builder,
                                          make_booster)


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


# ---- TD008: per-row reads of a small table in the build stage (PR 29)

_R, _L, _W = 4096, 15, 4


def _round_inputs():
    rng = np.random.RandomState(0)
    rl = jnp.asarray(rng.randint(-1, 8, size=_R).astype(np.int32))
    sel = jnp.asarray(np.array([1, 3, 5, _L], np.int32))
    ok = jnp.asarray(np.array([True, True, True, False]))
    feat = jnp.asarray(np.array([2, 0, 1, 3], np.int32))
    return rl, sel, ok, feat


def _table_round(rl, sel, ok, feat):
    """The round body as it stood at PR 28: the W records scattered
    into ``[L+1]`` tables, read by R rows; children counted by a
    ``segment_sum`` of R ones."""
    with jax.named_scope("build"):
        with jax.named_scope("apply"):
            rlc = jnp.where(rl < 0, _L, rl)
            pend_active = jnp.zeros((_L + 1,), bool).at[sel].set(ok) \
                .at[_L].set(False)
            pend_feat = jnp.zeros((_L + 1,), jnp.int32).at[sel].set(feat)
            active = jnp.take(pend_active, rlc)
            f_r = jnp.take(pend_feat, rlc)
        with jax.named_scope("count"):
            cnt = jax.ops.segment_sum(jnp.ones((_R,), jnp.int32), rlc,
                                      num_segments=_L + 1)
        return active, f_r, jnp.take(cnt, sel)


def _select_round(rl, sel, ok, feat):
    from lightgbm_tpu.boosting.tree_builder import (select_by_slot,
                                                    slot_counts,
                                                    stream_index)
    with jax.named_scope("build"):
        with jax.named_scope("apply"):
            active, (f_r,) = select_by_slot(rl, sel, ok, [feat])
        with jax.named_scope("count"):
            cnt = slot_counts(rl, sel)
        with jax.named_scope("compact"):
            # the stream's index: one sort over R, no gather and no
            # scatter at all
            c_idx, _ = stream_index(active)
            with jax.named_scope("hist_gather"):
                rl_c = jnp.take(rl, c_idx)
            with jax.named_scope("hist_kernel"):
                # the histogram itself adds rows into few bins
                hist = jnp.zeros((8,), jnp.int32).at[
                    jnp.clip(rl_c, 0, 7)].add(1)
        return active, f_r, cnt, hist


def test_td008_fires_on_the_table_round_and_not_on_the_select_round():
    args = _round_inputs()
    bad = lint_jaxpr(jax.make_jaxpr(_table_round)(*args), label="tables",
                     build_rows=_R)
    assert [f.rule for f in bad.errors] == ["TD008"] * 3
    assert sorted(f.op_path.rsplit("/", 1)[1] for f in bad.errors) == [
        "gather", "gather", "scatter-add"]
    assert all("build" in f.op_path for f in bad.errors)
    good = lint_jaxpr(jax.make_jaxpr(_select_round)(*args),
                      label="selects", build_rows=_R)
    assert good.ok, good.render(verbose=True)
    # the same reads outside the build stage are another rule's business
    # (``update`` gathers leaf values by row_leaf), and without
    # ``build_rows`` the rule is off
    assert lint_jaxpr(jax.make_jaxpr(_table_round)(*args),
                      label="tables").ok

    def elsewhere(rl, sel, ok, feat):
        with jax.named_scope("update"):
            return jnp.take(jnp.zeros((_L + 1,)), jnp.clip(rl, 0, _L))
    assert lint_jaxpr(jax.make_jaxpr(elsewhere)(*args), label="update",
                      build_rows=_R).ok
    # both rounds give the same answers on the used lanes
    a0, f0, c0 = _table_round(*args)
    a1, f1, c1, _ = _select_round(*args)
    np.testing.assert_array_equal(np.asarray(a0), np.asarray(a1))
    # (where no lane hits, a table holds what an unused lane wrote)
    np.testing.assert_array_equal(np.where(a0, f0, 0), np.asarray(f1))
    np.testing.assert_array_equal(np.asarray(c0)[:3], np.asarray(c1)[:3])


@pytest.mark.parametrize("config,mode", ROUND_BODY_CELLS)
def test_td008_round_body_of_the_fused_step_is_clean(config, mode):
    """No per-row read of a small table in the ``build`` stage of the
    fused step, traced with the round's XLA formulation in it."""
    if mode == "data" and len(jax.devices()) < 2:
        pytest.skip("needs a multi-device mesh")
    reports = doctor_round_body(config, mode)
    assert not merge_errors(reports), "\n".join(
        r.render(verbose=True) for r in reports)
    assert not any(f.rule == "TD000" for r in reports for f in r.findings)


def test_td008_data_parallel_tree_builder_is_clean():
    if len(jax.devices()) < 2:
        pytest.skip("needs a multi-device mesh")
    reports = doctor_tree_builder()
    assert any(r.label.endswith("/round_body") for r in reports)
    assert not merge_errors(reports), "\n".join(
        r.render(verbose=True) for r in reports)


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
