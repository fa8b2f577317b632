"""Performance-observability subsystem: xprof trace parsing against the
golden fixture, phase-totals thread safety and capture retention."""

import json
import os
import shutil
import threading
import urllib.error
import urllib.request

import pytest

from lightgbm_tpu import profiler
from lightgbm_tpu.telemetry import costmodel, xprof
from lightgbm_tpu.telemetry.core import MetricsRegistry
from lightgbm_tpu.telemetry.exporter import (CaptureError,
                                             IntrospectionServer)
from lightgbm_tpu.telemetry.monitor import (find_captures, monitor_main,
                                            render_perf)

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "trace_events.json")
GOLDEN_MAP = {"jit_train_step": {
    "dot_general.5": "build", "fusion.1": "grads", "add.9": "update",
    "dot.1": "build", "add.3": "apply"}}
US = 1e-6  # golden timestamps are micros; profiles are seconds
BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
XPLANE = os.path.join(BENCH, "tests", "fixtures", "trace_tiny.xplane.pb.gz")


# ----------------------------------------------------------------------
# xprof.parse_trace over the golden fixture


def golden_profile():
    return xprof.parse_trace(GOLDEN, phase_maps=GOLDEN_MAP)


def test_golden_phase_attribution():
    """Both attribution paths land in the right buckets: the stage map
    by (module, instruction) for events named by instruction text
    (TPU:0), by bare name (TPU:1) and by hlo_op (cpu:0), and host-span
    overlap (custom-call inside the host build span)."""
    prof = golden_profile()
    assert prof.steps == 2
    merged = prof.device_phase_s
    assert merged["build"] == pytest.approx(240 * US)
    assert merged["grads"] == pytest.approx(30 * US)
    assert merged["update"] == pytest.approx(25 * US)
    assert merged["apply"] == pytest.approx(50 * US)


def test_golden_unknown_bucket():
    """Unattributable device time lands in the explicit unknown
    bucket — the orphan copy, the while container's own overhead, the
    unmapped body op and the wrapper's scheduling self-time — never
    silently dropped."""
    prof = golden_profile()
    assert prof.device_phase_s[xprof.UNKNOWN] == pytest.approx(220 * US)
    # accounting identity: every counted microsecond is in some bucket
    assert sum(prof.device_phase_s.values()) == pytest.approx(
        (240 + 30 + 25 + 50 + 220) * US)


def test_golden_multi_device_merge():
    prof = golden_profile()
    assert set(prof.per_device) == {"TPU:0", "TPU:1", "cpu:0"}
    assert prof.per_device["TPU:0"]["build"] == pytest.approx(90 * US)
    assert prof.per_device["TPU:0"]["grads"] == pytest.approx(30 * US)
    assert prof.per_device["TPU:1"]["update"] == pytest.approx(25 * US)
    assert prof.per_device["cpu:0"]["build"] == pytest.approx(150 * US)
    # merged == sum over devices, bucket by bucket
    for ph, tot in prof.device_phase_s.items():
        assert tot == pytest.approx(sum(
            p.get(ph, 0.0) for p in prof.per_device.values()))


def test_golden_containment_no_double_count():
    """Self time by nesting: while.2 keeps only its own 90us, its body
    ops (add.3 mapped, mul.4 not) are counted where they ran, and the
    ThunkExecutor wrapper is transparent: cpu:0 accounts exactly the
    wrapper's 400us window, not 400 + body."""
    prof = golden_profile()
    assert sum(prof.per_device["cpu:0"].values()) == pytest.approx(
        400 * US)
    assert prof.per_device["cpu:0"]["apply"] == pytest.approx(50 * US)
    by_name = {n: s for n, _st, _sc, s in prof.top_ops}
    assert by_name["while.2"] == pytest.approx(90 * US)


def test_golden_without_phase_map():
    """No stage map: only host-span overlap attributes (the three TPU:0
    ops inside the host build span); everything else degrades to
    unknown instead of vanishing."""
    prof = xprof.parse_trace(GOLDEN)
    assert prof.device_phase_s["build"] == pytest.approx(120 * US)
    assert prof.device_phase_s[xprof.UNKNOWN] == pytest.approx(
        (20 + 25 + 400) * US)


def test_golden_summary_and_render():
    prof = golden_profile()
    s = prof.summary_dict()
    assert s["steps"] == 2
    assert "device_s_per_iter" in s
    assert s["device_s_per_iter"]["build"] == pytest.approx(
        120 * US, rel=1e-3)
    out = prof.render()
    assert "build" in out and "longest instructions" in out
    assert "longest idle gaps" in out


def test_phase_map_save_load_find(tmp_path):
    cap = tmp_path / "capture" / "plugins" / "profile" / "t1"
    cap.mkdir(parents=True)
    trace = cap / "host.trace.json"
    shutil.copy(GOLDEN, trace)
    xprof.save_phase_map(str(tmp_path / "capture"), GOLDEN_MAP)
    assert xprof.find_phase_map(str(trace)) == GOLDEN_MAP
    # parse_trace discovers the sidecar on its own
    prof = xprof.parse_trace(str(tmp_path / "capture"))
    assert prof.per_device["cpu:0"]["build"] == pytest.approx(150 * US)
    # a StageMap round-trips with its scopes and its mixed-fusion count
    sm = costmodel.StageMap("m", {"fusion.1": "apply"},
                            {"fusion.1": "jit(f)/build/apply/gather"}, 2)
    xprof.save_phase_map(str(tmp_path), {"m": sm})
    back = xprof.load_phase_map(str(tmp_path / xprof.PHASE_MAP_NAME))
    assert back["m"]["stages"] == sm.stages
    assert back["m"]["mixed_fusions"] == 2


# ----------------------------------------------------------------------
# the reduction on the recorded chip trace and on hand-made events


def _bench_trace():
    import sys
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    from harness import trace as bench_trace
    return bench_trace


def test_xplane_stage_seconds_equal_benchmark_busy_self_time():
    """The program's reducer on the chip trace the benchmark recorded:
    stage seconds (with the unknown remainder) sum to the benchmark
    reducer's busy self time, and the grow ``while`` is nested — it
    keeps its own overhead, its body's instructions are counted."""
    bt = _bench_trace()
    cap = xprof.load_xplane(XPLANE)
    assert list(cap.tracks) == ["TPU:0"]
    step = [e for e in cap.tracks["TPU:0"]
            if e.module == "jit__fused_step_entry"]
    assert len(step) > 1000
    # a stage map made up for the recorded module: fusions to one stage,
    # everything else left to the unknown remainder
    table = {xprof.instruction_of(e.name): "apply"
             for e in step if " fusion(" in e.name}
    prof = xprof.reduce_capture(cap, {"jit__fused_step_entry": table})
    devices, spans = bt.load_xplane(XPLANE)
    rep = bt.reduce_plane(devices["/device:TPU:0"], spans, 65536, 28)
    assert sum(prof.device_phase_s.values()) == pytest.approx(
        sum(rep.class_s.values()), abs=1e-6)
    assert prof.device_busy_s == pytest.approx(rep.busy_s, abs=1e-6)
    assert prof.device_phase_s["apply"] > 0
    assert prof.device_phase_s[xprof.UNKNOWN] > 0
    whiles = [e for e in step
              if bt.parse_op(e.name).opcode == "while"]
    assert whiles
    w = max(whiles, key=lambda e: e.dur)
    self_s = dict((n, s) for n, _st, _sc, s in xprof.reduce_capture(
        xprof.Capture({"TPU:0": step}, [], len(step), []), {}).top_ops)
    # the container's self time is a sliver of its duration
    assert self_s.get(w.name[:xprof.TEXT_CHARS], 0.0) < 0.2 * w.dur
    assert cap.epoch_ns == 1790468477433782443


def test_reduce_capture_module_lookup_and_unknown_remainder():
    """Hand-made events: the same instruction name in two modules takes
    each module's stage; an event whose module has no map is unknown
    (with several maps none is guessed); the while keeps its self time;
    the longest idle gap names the innermost program span over it."""
    ev = xprof.OpEvent
    events = [
        ev("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 0.0, 1.0, "mod_a"),
        ev("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 1.0, 2.0, "mod_b"),
        ev("%while.3 = (s32[]) while((s32[]) %t)", 4.0, 4.0, "mod_a"),
        ev("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %q)", 4.5, 3.0, "mod_a"),
        ev("%copy.9 = f32[8]{0} copy(f32[8]{0} %p)", 8.0, 0.5, "mod_c"),
    ]
    spans = [xprof.HostSpan("gbdt.dispatch", 2.5, 2.0),
             xprof.HostSpan("gbdt.step_ready", 3.2, 0.5)]
    maps = {"mod_a": {"fusion.1": "grads", "fusion.2": "hist_gather",
                      "while.3": "build"},
            "mod_b": {"fusion.1": "update"}}
    prof = xprof.reduce_capture(
        xprof.Capture({"TPU:0": events}, spans, len(events), []), maps)
    assert prof.device_phase_s == pytest.approx({
        "grads": 1.0, "update": 2.0, "build": 1.0, "hist_gather": 3.0,
        xprof.UNKNOWN: 0.5})
    assert prof.unknown_share() == pytest.approx(0.5 / 7.5)
    assert prof.dispatches == 1 and prof.iterations() == 1
    # one gap, 3.0 -> 4.0; its midpoint lies in both spans
    assert prof.idle_gaps == [("TPU:0", "gbdt.step_ready",
                               pytest.approx(1.0))]
    top = prof.top_ops[0]
    assert top[1] == "hist_gather" and top[3] == pytest.approx(3.0)


HLO_SAMPLE = """HloModule jit_step, is_scheduled=true

%fused_computation.1 (p0: s32[64]) -> s32[64] {
  %p0 = s32[64]{0:T(128)} parameter(0)
  %g.1 = s32[64]{0:T(128)} gather(%p0), metadata={op_name="jit(step)/build/while/body/apply/gather"}
  %g.2 = s32[64]{0:T(128)} add(%g.1, %g.1), metadata={op_name="jit(step)/build/while/body/apply/add"}
  ROOT %s.3 = s32[64]{0:T(128)} select(%g.2), metadata={op_name="jit(step)/build/while/body/compact/select_n"}
}

%body.2 (t: (s32[], s32[64])) -> (s32[], s32[64]) {
  %t = (s32[]{:T(128)}, s32[64]{0:T(128)}) parameter(0)
  %x = s32[64]{0:T(128)} get-tuple-element(%t), index=1
  %copy.7 = s32[64]{0:T(128)S(1)} copy(%x)
  %fusion.5 = s32[64]{0:T(128)} fusion(%copy.7), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/build/while/body/compact/select_n"}
  %pallas_hist_kernel.4 = f32[8,128]{1,0:T(8,128)} custom-call(%fusion.5), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/build/while/body/compact/hist_kernel/pallas_hist_kernel/pallas_call"}
  %plain.6 = s32[64]{0:T(128)} negate(%x)
  ROOT %tup = (s32[]{:T(128)}, s32[64]{0:T(128)}) tuple(%x, %fusion.5)
}

ENTRY %main.9 (a: s32[64]) -> s32[64] {
  %a = s32[64]{0:T(128)} parameter(0)
  %while.8 = (s32[]{:T(128)}, /*index=1*/s32[64]{0:T(128)}) while(%a), condition=%cond.1, body=%body.2, metadata={op_name="jit(step)/build/while"}
  ROOT %out = s32[64]{0:T(128)} get-tuple-element(%while.8), index=1
}
"""


def test_stage_map_deepest_stage_fusions_and_plumbing():
    """The deepest canonical name on the op_name path wins; a fusion
    whose fused instructions disagree takes the stage holding most of
    them and is counted; a copy without metadata takes its user's
    stage; what nothing names falls back to the loop's own stage."""
    sm = costmodel.instruction_phase_map(HLO_SAMPLE)
    assert sm.module == "jit_step"
    assert sm.stages["pallas_hist_kernel.4"] == "hist_kernel"
    assert sm.stages["fusion.5"] == "apply" and sm.mixed_fusions == 1
    assert sm.stages["copy.7"] == "apply"          # its user's
    assert sm.stages["plain.6"] == "build"         # the while's
    assert sm.stages["while.8"] == "build"
    assert sm.scopes["fusion.5"].endswith("compact/select_n")
    assert set(sm.stages.values()) <= set(profiler.KNOWN_PHASES)
    assert xprof.stage_of_path("jit(f)/build/while/body/find") == "find"
    assert xprof.stage_of_path("jit(f)/reshape") is None


# ----------------------------------------------------------------------
# the per-round counters, fetched with the trees


def _replay_round_rows(tree):
    """With leaf_batch=1 round r applies split r: the rows its histogram
    streams are the smaller child's, by the host Tree's own counts."""
    def count(c):
        return (tree.internal_count[c] if c >= 0
                else tree.leaf_count[~c])
    return [min(count(int(tree.left_child[i])),
                count(int(tree.right_child[i])))
            for i in range(tree.num_leaves - 1)]


def _round_log_run(extra, rounds=3):
    import numpy as np

    import lightgbm_tpu as lgb
    rng = np.random.RandomState(7)
    X = rng.normal(size=(4096, 10)).astype(np.float32)
    y = (X[:, 0] + X[:, 1] * X[:, 2] + 0.3 * rng.normal(size=4096)
         > 0).astype(np.float32)
    params = dict({"objective": "binary", "num_leaves": 31,
                   "leaf_batch": 1, "min_data_in_leaf": 5,
                   "verbosity": -1}, **extra)
    bst = lgb.Booster(params, lgb.Dataset(X, label=y))
    for _ in range(rounds):
        bst.update(defer=True)
    gb = bst._gbdt
    syncs0 = gb.host_sync_count
    assert len(gb.round_log) == 0           # nothing fetched yet
    bst._sync_trees()
    assert gb.host_sync_count - syncs0 == 1  # the trees' own fetch
    return gb


def test_round_log_matches_host_tree_replay(monkeypatch):
    import numpy as np
    monkeypatch.setenv("LIGHTGBM_TPU_FUSED_TRAIN", "1")
    gb = _round_log_run({"tree_learner": "serial"})
    assert gb.fused_ok and len(gb.round_log) == len(gb.models) == 3
    for rec, tree in zip(gb.round_log, gb.models):
        assert rec.leaves.sum() == tree.num_leaves - 1
        want = _replay_round_rows(tree)
        n = len(want)
        assert rec.rows.shape == rec.leaves.shape
        assert list(rec.rows[:n]) == want
        assert not np.any(rec.rows[n:]) and not np.any(rec.leaves[n:])
    assert [r.iteration for r in gb.round_log] == [0, 1, 2]


def test_round_log_per_shard_rows_on_data_mesh(monkeypatch):
    """tree_learner=data over four virtual devices: rows are counted per
    shard (no collective added), and the shards sum to the replay."""
    import jax
    monkeypatch.setenv("LIGHTGBM_TPU_FUSED_TRAIN", "1")
    four = jax.devices()[:4]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: four)
    gb = _round_log_run({"tree_learner": "data"})
    assert gb.plan is not None and gb.plan.num_shards == 4
    for rec, tree in zip(gb.round_log, gb.models):
        assert rec.rows.shape == (4,) + rec.leaves.shape
        assert rec.leaves.sum() == tree.num_leaves - 1
        want = _replay_round_rows(tree)
        assert list(rec.rows.sum(axis=0)[:len(want)]) == want
        assert (rec.rows > 0).sum(axis=0).max() > 1   # really per shard


# ----------------------------------------------------------------------
# the span record under threads


def test_phase_totals_two_threads():
    """Spans recorded from two threads at once are all counted: the
    recorder's sequence number and ring are updated under one lock."""
    rec = profiler.SpanRecorder(capacity=50_000)
    col = profiler.PhaseTotals(rec)
    n, dt = 20_000, 1_000_000        # ns

    def hammer():
        for _ in range(n):
            rec.record("build", 0, dt)

    threads = [threading.Thread(target=hammer) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert rec.seq == 2 * n
    assert col.count("build") == 2 * n
    assert col.total_s("build") == pytest.approx(2 * n * dt * 1e-9)


def test_phase_spans_from_two_threads():
    """The real phase() entry point records into the one ring from
    concurrent threads without dropping spans, and a collector sees
    exactly the spans of its block."""
    with profiler.phase("build"):
        pass                          # before the block: not counted
    with profiler.collect_phase_totals() as col:
        def work():
            for _ in range(50):
                with profiler.phase("build"):
                    pass

        threads = [threading.Thread(target=work) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    with profiler.phase("build"):
        pass                          # after the block: not counted
    assert col.count("build") == 100


# ----------------------------------------------------------------------
# exporter: capture retention + stop_trace failure


def _quiet_profiler(monkeypatch):
    import jax
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda log_dir, **kw: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)


def test_capture_retention(tmp_path, monkeypatch):
    _quiet_profiler(monkeypatch)
    srv = IntrospectionServer(MetricsRegistry(),
                              capture_root=str(tmp_path),
                              keep_captures=2)
    for _ in range(4):
        resp = srv.capture_trace(duration_ms=1)
        assert os.path.isdir(resp["log_dir"])
    caps = sorted(os.listdir(tmp_path))
    assert caps == ["capture_0003", "capture_0004"]


def test_capture_stop_failure_cleans_up(tmp_path, monkeypatch):
    import jax
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda log_dir, **kw: None)

    def boom():
        raise RuntimeError("serialization exploded")

    monkeypatch.setattr(jax.profiler, "stop_trace", boom)
    srv = IntrospectionServer(MetricsRegistry(),
                              capture_root=str(tmp_path))
    with pytest.raises(CaptureError, match="serialization exploded"):
        srv.capture_trace(duration_ms=1)
    assert os.listdir(tmp_path) == []  # no dangling capture dir
    # and the lock was released: the next capture still works
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    assert "log_dir" in srv.capture_trace(duration_ms=1)


def test_trace_endpoint_500_on_capture_error(monkeypatch, tmp_path):
    import jax
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda log_dir, **kw: None)

    def boom():
        raise RuntimeError("no serializer")

    monkeypatch.setattr(jax.profiler, "stop_trace", boom)
    srv = IntrospectionServer(MetricsRegistry(),
                              capture_root=str(tmp_path))
    port = srv.start()
    try:
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/trace?duration_ms=1",
                timeout=10)
        assert exc.value.code == 500
        assert "no serializer" in json.load(exc.value)["error"]
    finally:
        srv.stop()


# ----------------------------------------------------------------------
# monitor --perf over a synthetic run dir


def _fake_run_dir(tmp_path):
    cap = tmp_path / "traces" / "capture_0001"
    cap.mkdir(parents=True)
    shutil.copy(GOLDEN, cap / "host.trace.json")
    xprof.save_phase_map(str(cap), GOLDEN_MAP)
    log = tmp_path / "run.events.jsonl"
    recs = [
        {"event": "run_header", "ts": 1.0, "seq": 0, "fingerprint": "f",
         "driver": "fused", "versions": {}},
        {"event": "iteration", "ts": 2.0, "seq": 1, "iter": 2,
         "ms_per_tree": 1.0, "metrics": {}, "phase_s": {}},
    ]
    log.write_text("".join(json.dumps(r) + "\n" for r in recs))
    return tmp_path


def test_find_captures(tmp_path):
    assert find_captures(str(tmp_path)) == []
    run = _fake_run_dir(tmp_path)
    caps = find_captures(str(run))
    assert len(caps) == 1 and caps[0].endswith("capture_0001")


def test_render_perf_compares_against_event_log(tmp_path):
    run = _fake_run_dir(tmp_path)
    cap = find_captures(str(run))[0]
    recs = [json.loads(ln) for ln in
            (run / "run.events.jsonl").read_text().splitlines()]
    out = render_perf(cap, recs)
    # golden: 565us device time over 2 steps vs 1.0 ms/tree in the log
    assert "phase device sum 0.28 ms/iter" in out
    assert "ratio 0.28" in out


def test_monitor_perf_cli(tmp_path, capsys):
    run = _fake_run_dir(tmp_path)
    assert monitor_main(["--perf", str(run)]) == 0
    out = capsys.readouterr().out
    assert "capture_0001" in out and "phase device sum" in out
    # no captures → actionable failure, not a stack trace
    bare = tmp_path / "empty"
    bare.mkdir()
    assert monitor_main(["--perf", str(bare)]) == 1
