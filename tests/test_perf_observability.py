"""Performance-observability subsystem: xprof trace parsing against the
golden fixture, phase-totals thread safety, capture retention, the
cost-model cross-check, and the perf-gate tolerance semantics."""

import json
import os
import shutil
import threading
import urllib.error
import urllib.request

import pytest

from lightgbm_tpu import profiler
from lightgbm_tpu.telemetry import costmodel, perf, xprof
from lightgbm_tpu.telemetry.core import MetricsRegistry
from lightgbm_tpu.telemetry.exporter import (CaptureError,
                                             IntrospectionServer)
from lightgbm_tpu.telemetry.monitor import (find_captures, monitor_main,
                                            render_perf)

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "trace_events.json")
GOLDEN_MAP = {"jit_train_step": {"dot.1": "build"}}
US = 1e-6  # golden timestamps are micros; profiles are seconds


# ----------------------------------------------------------------------
# xprof.parse_trace over the golden fixture


def golden_profile():
    return xprof.parse_trace(GOLDEN, phase_maps=GOLDEN_MAP)


def test_golden_phase_attribution():
    """All three attribution paths land in the right buckets: scope
    prefix (build/grads), phase map (build on cpu:0), host-span
    overlap (custom-call inside the host build span)."""
    prof = golden_profile()
    assert prof.steps == 2
    merged = prof.device_phase_s
    assert merged["build"] == pytest.approx(240 * US)
    assert merged["grads"] == pytest.approx(30 * US)
    assert merged["update"] == pytest.approx(25 * US)


def test_golden_unknown_bucket():
    """Unattributable device time lands in the explicit unknown
    bucket — the orphan copy, the while container, and the wrapper's
    scheduling self-time — never silently dropped."""
    prof = golden_profile()
    assert prof.device_phase_s[xprof.UNKNOWN] == pytest.approx(270 * US)
    # accounting identity: every counted microsecond is in some bucket
    assert sum(prof.device_phase_s.values()) == pytest.approx(
        (240 + 30 + 25 + 270) * US)


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
    """The while.2 body ops (add.3, mul.4) are covered by the counted
    container and the ThunkExecutor wrapper is transparent: cpu:0
    accounts exactly the wrapper's 400us window, not 400 + body."""
    prof = golden_profile()
    assert sum(prof.per_device["cpu:0"].values()) == pytest.approx(
        400 * US)


def test_golden_without_phase_map():
    """No phase map: the cpu:0 executor events have no scope prefix,
    so dot.1's time degrades to unknown instead of vanishing."""
    prof = xprof.parse_trace(GOLDEN)
    assert prof.device_phase_s[xprof.UNKNOWN] == pytest.approx(
        (270 + 150) * US)


def test_golden_summary_and_render():
    prof = golden_profile()
    s = prof.summary_dict()
    assert s["steps"] == 2
    assert "device_s_per_iter" in s
    assert s["device_s_per_iter"]["build"] == pytest.approx(
        120 * US, rel=1e-3)
    assert "build" in prof.render()


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


# ----------------------------------------------------------------------
# profiler.PhaseTotals thread safety


def test_phase_totals_two_threads():
    """+= on the accumulator is a read-modify-write; without the lock
    two recording threads silently lose spans."""
    col = profiler.PhaseTotals()
    n, dt = 20_000, 0.001

    def hammer():
        for _ in range(n):
            col._record("build", dt)

    threads = [threading.Thread(target=hammer) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert col.count("build") == 2 * n
    assert col.total_s("build") == pytest.approx(2 * n * dt)


def test_phase_spans_from_two_threads():
    """The real phase() entry point records into stacked collectors
    from concurrent threads without dropping spans."""
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
# perf gate: tolerance semantics + baseline round trip


def test_tolerance_kinds():
    t = perf.Tolerance("time", 1.5)
    assert t.check(1.4, 1.0)[0] and not t.check(1.6, 1.0)[0]
    assert t.check(0.1, 1.0)[0]  # faster never regresses
    t = perf.Tolerance("throughput", 1.5)
    assert t.check(0.7, 1.0)[0] and not t.check(0.6, 1.0)[0]
    assert t.check(99.0, 1.0)[0]
    t = perf.Tolerance("static", 2.0)
    assert t.check(1.9, 1.0)[0] and t.check(0.51, 1.0)[0]
    assert not t.check(2.1, 1.0)[0] and not t.check(0.4, 1.0)[0]
    with pytest.raises(ValueError):
        perf.Tolerance("speed", 1.5)
    with pytest.raises(ValueError):
        perf.Tolerance("time", 0.5)


def test_compare_pass_fail_missing_new_skip():
    base = {"ms_per_tree": 10.0, "cost_fused_step_flops": 1000.0,
            "gone": 5.0, "timing_skipped": 3.0}
    cur = {"ms_per_tree": 11.0, "cost_fused_step_flops": 2000.0,
           "fresh": 1.0}
    res = perf.compare(cur, base, skipped=["timing_skipped"])
    by = {c.metric: c for c in res.checks}
    assert by["ms_per_tree"].status == "pass"          # within 1.6x
    assert by["cost_fused_step_flops"].status == "fail"  # 2x static
    assert by["gone"].status == "missing"
    assert by["timing_skipped"].status == "skip"
    assert by["fresh"].status == "new"
    assert not res.ok
    assert set(res.failed) == {"cost_fused_step_flops", "gone"}
    assert "FAIL" in res.render()


def test_compare_all_green():
    base = {"a": 1.0, "b": 2.0}
    res = perf.compare({"a": 1.0, "b": 2.0}, base)
    assert res.ok and res.failed == []
    assert "PASS" in res.render()


def test_baseline_round_trip(tmp_path):
    path = str(tmp_path / "PERF_BASELINE.json")
    metrics = {"ms_per_tree": 12.5, "cost_fused_step_flops": 7e7}
    perf.save_baseline(path, metrics, meta={"note": "test"})
    obj = perf.load_baseline(path)
    assert obj["metrics"] == metrics
    assert obj["meta"]["note"] == "test"
    assert obj["host"]["cpu_count"] == os.cpu_count()
    assert perf.compare(metrics, obj["metrics"]).ok


def test_load_baseline_rejects_garbage(tmp_path):
    path = tmp_path / "bad.json"
    path.write_text(json.dumps({"not_metrics": 1}))
    with pytest.raises(ValueError):
        perf.load_baseline(str(path))


# ----------------------------------------------------------------------
# cost model: the XLA-vs-analytical histogram cross-check


def test_hist_xla_flops_within_2x_of_analytical():
    from lightgbm_tpu.telemetry import costmodel
    R, F, B, L = 4096, 8, 16, 7
    xla = costmodel.hist_xla_cost(R, F, B, L, impl="matmul")
    ana_flops, ana_bytes = costmodel.analytical_hist_counts(R, F, B, L)
    assert xla["flops"] > 0 and ana_flops > 0
    ratio = xla["flops"] / ana_flops
    assert 0.5 <= ratio <= 2.0, (
        f"XLA prices the one-hot hist matmul at {ratio:.2f}x the "
        "analytical count — one of the two models is wrong")
    assert xla["bytes_accessed"] >= ana_bytes  # analytical is the floor


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


class _FakeDev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


def test_chip_peaks_exact_kind_lookup(monkeypatch):
    """The installed runtime names a v5e chip "TPU v5 lite": the table
    is keyed by that exact string (a "v5e" substring match returned
    None on the very chip builders have)."""
    import jax
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_FakeDev("tpu", "TPU v5 lite")])
    pk = costmodel.chip_peaks()
    assert (pk.kind, pk.bf16_tflops, pk.int8_tops, pk.hbm_gbps) == (
        "TPU v5 lite", 197.0, 393.0, 819.0)
    util = costmodel.roofline_utilization(98.5, 409.5)
    assert util == {"hist_mfu": 0.5, "hist_hbm_util": 0.5,
                    "chip": "TPU v5 lite"}


def test_chip_peaks_unknown_tpu_raises_and_cpu_has_none(monkeypatch):
    """A TPU kind the table does not know is an error on every path
    that computes a roofline field; a CPU host simply has no peaks."""
    import jax
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_FakeDev("tpu", "TPU v9 mega")])
    with pytest.raises(LookupError, match="TPU v9 mega"):
        costmodel.chip_peaks()
    with pytest.raises(LookupError):
        costmodel.kernel_roofline_fields("tpu", 1e-3, 1024, 8, 16, 4)
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_FakeDev("cpu", "cpu")])
    assert costmodel.chip_peaks() is None
    assert "hist_mfu" not in costmodel.kernel_roofline_fields(
        "cpu", 1e-3, 1024, 8, 16, 4)


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


# ----------------------------------------------------------------------
# perf-gate end to end (trains the canonical booster: slow lane)


def _gate_main():
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "perf_gate.py")
    spec = importlib.util.spec_from_file_location("perf_gate", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.main


@pytest.mark.slow
def test_perf_gate_update_then_green_then_seeded(tmp_path, capsys):
    main = _gate_main()
    baseline = str(tmp_path / "PERF_BASELINE.json")
    events = str(tmp_path / "gate.events.jsonl")
    assert main(["--update", "--baseline", baseline,
                 "--skip-timing"]) == 0
    assert main(["--baseline", baseline, "--skip-timing",
                 "--event-log", events]) == 0
    assert main(["--baseline", baseline, "--skip-timing",
                 "--seed-regression"]) == 1
    recs = [json.loads(ln) for ln in open(events)]
    assert recs[-1]["event"] == "perf_gate"
    assert recs[-1]["status"] == "pass"
    # a missing baseline is its own exit code (2): "create one", not
    # "regression"
    assert main(["--baseline", str(tmp_path / "nope.json"),
                 "--skip-timing"]) == 2
