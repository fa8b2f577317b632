"""Runtime telemetry subsystem: metrics registry render, event-log
append/splice/schema, engine wiring (eval-cadence records, fault
records, log routing), live introspection endpoints, the serving
render's byte-compat with the pre-registry format, and the monitor
CLI."""

import http.client
import json
import re

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import log
from lightgbm_tpu.telemetry import active_session
from lightgbm_tpu.telemetry.core import (Counter, Gauge, MetricsRegistry,
                                         RingHistogram)
from lightgbm_tpu.telemetry.events import (EventLog, check_records,
                                           read_events, set_active)
from lightgbm_tpu.telemetry.exporter import IntrospectionServer
from lightgbm_tpu.telemetry.monitor import monitor_main


def _data(rng, n=400, f=8):
    X = rng.normal(size=(n, f)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2]
         + 0.3 * rng.normal(size=n) > 0).astype(np.float32)
    return X, y


PARAMS = {"objective": "binary", "metric": "auc", "num_leaves": 7,
          "learning_rate": 0.2, "min_data_in_leaf": 5, "verbosity": -1,
          "eval_period": 2, "is_provide_training_metric": True,
          "output_model": "m.txt"}


def _train(rounds=6, extra=None, callbacks=None, seed=3):
    rng = np.random.RandomState(seed)
    X, y = _data(rng)
    ds = lgb.Dataset(X, label=y)
    # a no-op after-callback is an eval consumer (needs_eval defaults
    # True), so sync points carry metric values for the event log
    cbs = callbacks if callbacks is not None else [lambda env: None]
    return lgb.train(dict(PARAMS, **(extra or {})), ds,
                     num_boost_round=rounds, callbacks=cbs)


# ------------------------------------------------------------- registry
def test_registry_counter_gauge_summary_render():
    reg = MetricsRegistry()
    c = reg.counter("t_ops_total", "ops")
    c.inc()
    c.inc(2)
    reg.gauge("t_level", "level").set(1.5)
    fam = reg.counter("t_by_kind_total", "per kind", labels=("kind",))
    fam.labels("a").inc(4)
    h = reg.summary("t_lat_seconds", "latency", size=16)
    for v in (0.1, 0.2, 0.3):
        h.observe(v)
    text = reg.render()
    assert "# TYPE t_ops_total counter\nt_ops_total 3\n" in text
    assert "t_level 1.5" in text
    assert 't_by_kind_total{kind="a"} 4' in text
    assert 't_lat_seconds{quantile="0.5"} 0.2' in text
    assert "t_lat_seconds_count 3" in text


def test_registry_idempotent_families_and_collectors():
    reg = MetricsRegistry()
    a = reg.counter("t_x_total", "x")
    assert reg.counter("t_x_total", "x") is a
    with pytest.raises(ValueError):
        reg.gauge("t_x_total", "x")           # kind mismatch
    reg.register_collector("extra", lambda: "extra_metric 1\n")
    reg.register_collector("extra", lambda: "extra_metric 2\n")
    assert reg.render().count("extra_metric") == 1   # replaced, not stacked
    assert "extra_metric 2" in reg.render()
    reg.register_collector("boom", lambda: 1 / 0)    # swallowed at render
    assert "t_x_total 0" in reg.render()


def test_gauge_callback_and_counter_inc():
    g = Gauge(fn=lambda: 42.0)
    assert g.value == 42.0
    assert Gauge(fn=lambda: 1 / 0).value == 0.0      # callback error -> 0
    c = Counter()
    c.inc(5)
    assert c.value == 5
    h = RingHistogram(4)
    for v in (1.0, 2.0, 3.0, 4.0, 5.0):              # ring: keeps last 4
        h.observe(v)
    assert h.count == 5 and h.window().min() == 2.0


# ---------------------------------------------- serving render pinned
def test_serving_metrics_render_byte_compat():
    """Satellite 1 pin: the registry-backed ServingMetrics must render
    the exact pre-refactor bytes — families, ordering, label and
    quantile formatting (the two wall-clock gauges checked by shape)."""
    from lightgbm_tpu.serving.metrics import ServingMetrics
    m = ServingMetrics()
    m.on_request("default", 4)
    m.on_request("default", 4)
    m.on_request("alt", 2)
    m.on_error("alt")
    m.on_overload()
    m.swaps_total.inc()
    m.rollbacks_total.inc()
    m.on_batch(8, 0.002, 0.010)
    m.on_batch(16, 0.004, 0.020)
    golden = (
        '# HELP serve_requests_total Accepted predict requests\n'
        '# TYPE serve_requests_total counter\n'
        'serve_requests_total{model="alt"} 1\n'
        'serve_requests_total{model="default"} 2\n'
        '# HELP serve_errors_total Requests that raised\n'
        '# TYPE serve_errors_total counter\n'
        'serve_errors_total{model="alt"} 1\n'
        '# HELP serve_overload_total Requests fast-failed at admission '
        'control\n'
        '# TYPE serve_overload_total counter\n'
        'serve_overload_total 1\n'
        '# HELP serve_rows_total Rows predicted (pre-padding)\n'
        '# TYPE serve_rows_total counter\n'
        'serve_rows_total 24\n'
        '# HELP serve_batches_total Coalesced kernel calls\n'
        '# TYPE serve_batches_total counter\n'
        'serve_batches_total 2\n'
        '# HELP serve_swaps_total Model hot-swaps\n'
        '# TYPE serve_swaps_total counter\n'
        'serve_swaps_total 1\n'
        '# HELP serve_rollbacks_total Model rollbacks\n'
        '# TYPE serve_rollbacks_total counter\n'
        'serve_rollbacks_total 1\n'
        '# HELP serve_batch_rows Rows per coalesced batch\n'
        '# TYPE serve_batch_rows summary\n'
        'serve_batch_rows{quantile="0.5"} 12\n'
        'serve_batch_rows{quantile="0.95"} 15.6\n'
        'serve_batch_rows{quantile="0.99"} 15.92\n'
        'serve_batch_rows_count 2\n'
        'serve_batch_rows_mean 12\n'
        '# HELP serve_queue_wait_seconds Enqueue to batch start\n'
        '# TYPE serve_queue_wait_seconds summary\n'
        'serve_queue_wait_seconds{quantile="0.5"} 0.003\n'
        'serve_queue_wait_seconds{quantile="0.95"} 0.0039\n'
        'serve_queue_wait_seconds{quantile="0.99"} 0.00398\n'
        'serve_queue_wait_seconds_count 2\n'
        'serve_queue_wait_seconds_mean 0.003\n'
        '# HELP serve_compute_seconds Kernel call duration\n'
        '# TYPE serve_compute_seconds summary\n'
        'serve_compute_seconds{quantile="0.5"} 0.015\n'
        'serve_compute_seconds{quantile="0.95"} 0.0195\n'
        'serve_compute_seconds{quantile="0.99"} 0.0199\n'
        'serve_compute_seconds_count 2\n'
        'serve_compute_seconds_mean 0.015\n'
        '# HELP serve_rows_per_s Window throughput\n'
        '# TYPE serve_rows_per_s gauge\n')
    text = m.render()
    assert text.startswith(golden)
    tail = text[len(golden):].splitlines()
    assert re.fullmatch(r"serve_rows_per_s \S+", tail[0])
    assert tail[1:3] == ["# HELP serve_uptime_seconds Seconds since "
                        "start", "# TYPE serve_uptime_seconds gauge"]
    assert re.fullmatch(r"serve_uptime_seconds \d+\.\d{3}", tail[3])
    assert text.endswith("\n")


def test_prediction_server_metrics_mount_identical():
    """The server's /metrics body (registry render) must equal the bare
    ServingMetrics render when the registry has no own families."""
    from lightgbm_tpu.serving import PredictionServer
    srv = PredictionServer(port=0)
    srv.metrics.on_request("default", 4)
    a = srv.telemetry.render()
    b = srv.metrics.render()
    # identical modulo the two wall-clock gauge values sampled ~us apart
    strip = re.compile(r"^(serve_uptime_seconds|serve_rows_per_s) .*$",
                      re.M)
    assert strip.sub(r"\1", a) == strip.sub(r"\1", b)


# ------------------------------------------------------------ event log
def test_event_log_append_read_tail_check(tmp_path):
    p = str(tmp_path / "r.events.jsonl")
    ev = EventLog(p)
    ev.append("run_header", fingerprint="abc", driver="fused",
              versions={})
    for i in (2, 4):
        ev.append("iteration", iter=i, ms_per_tree=1.0, metrics={},
                  phase_s={})
    ev.append("train_end", iter=4, trees=4, wall_s=0.1)
    recs = read_events(p)
    assert [r["event"] for r in recs] == ["run_header", "iteration",
                                         "iteration", "train_end"]
    assert [r["seq"] for r in recs] == [0, 1, 2, 3]
    assert check_records(recs) == []
    assert [r["iter"] for r in ev.tail(2)] == [4, 4]
    # a fresh handle on the same file continues seq monotonically
    ev2 = EventLog(p)
    rec = ev2.append("log", level="warning", msg="x")
    assert rec["seq"] == 4


def test_event_log_torn_tail_and_corruption(tmp_path):
    p = str(tmp_path / "r.events.jsonl")
    ev = EventLog(p)
    ev.append("run_header", fingerprint="abc", driver="f", versions={})
    ev.append("iteration", iter=2, ms_per_tree=1.0, metrics={},
              phase_s={})
    with open(p, "a") as f:
        f.write('{"event": "iteration", "it')     # SIGKILL mid-write
    assert len(read_events(p)) == 2               # torn FINAL line skipped
    with open(p, "a") as f:                       # interior damage raises
        f.write('\n{"event": "train_end", "ts": 0, "seq": 9, '
                '"iter": 2, "trees": 2, "wall_s": 0.1}\n')
    with pytest.raises(ValueError):
        read_events(p)


def test_check_records_flags_schema_violations():
    base = {"ts": 0.0}
    recs = [dict(base, event="iteration", seq=0, iter=2,
                 ms_per_tree=1.0, metrics={}, phase_s={})]
    assert any("run_header" in e for e in check_records(recs))
    recs = [dict(base, event="run_header", seq=0, fingerprint="a",
                 driver="f", versions={}),
            dict(base, event="iteration", seq=0, iter=2,
                 ms_per_tree=1.0, metrics={}, phase_s={})]
    assert any("seq" in e for e in check_records(recs))
    recs = [dict(base, event="run_header", seq=0, fingerprint="a",
                 driver="f", versions={}),
            dict(base, event="wat", seq=1)]
    assert any("wat" in e for e in check_records(recs))


def test_event_log_splice(tmp_path):
    p = str(tmp_path / "r.events.jsonl")
    ev = EventLog(p)
    ev.append("run_header", fingerprint="abc", driver="f", versions={})
    ev.append("iteration", iter=2, ms_per_tree=1.0, metrics={},
              phase_s={})
    ev.append("checkpoint", action="write", iter=2, path="c2")
    ev.append("nan_guard", iter=3, policy="rollback", action="rollback")
    ev.append("iteration", iter=4, ms_per_tree=1.0, metrics={},
              phase_s={})
    ev.append("checkpoint", action="write", iter=4, path="c4")
    ev.append("train_end", iter=4, trees=4, wall_s=0.1)
    dropped = ev.splice_to_iteration(2)
    assert dropped == 3         # iteration 4, ckpt write 4, train_end
    kinds = [(r["event"], r.get("iter")) for r in read_events(p)]
    assert kinds == [("run_header", None), ("iteration", 2),
                     ("checkpoint", 2), ("nan_guard", 3)]


# -------------------------------------------------------- engine wiring
def test_train_event_log_cadence(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    _train(rounds=6, extra={"event_log": "run.events.jsonl"})
    recs = read_events("run.events.jsonl")
    assert check_records(recs) == []
    assert recs[0]["event"] == "run_header"
    assert recs[0]["driver"] in ("fused", "legacy")
    assert recs[0]["versions"]["lightgbm_tpu"] == lgb.__version__
    iters = [r["iter"] for r in recs if r["event"] == "iteration"]
    assert iters == [2, 4, 6]                 # the eval_period=2 cadence
    it = next(r for r in recs if r["event"] == "iteration")
    assert it["ms_per_tree"] > 0 and "training:auc" in it["metrics"]
    # the span record's names: canonical phases (legacy driver) and the
    # fixed list of host spans (fused driver, engine, dataset)
    from lightgbm_tpu import phases
    assert set(it["phase_s"]) <= phases.KNOWN_PHASES | phases.HOST_SPANS
    assert "engine.eval" in it["phase_s"]
    assert recs[-1]["event"] == "train_end"
    assert active_session() is None           # closed after train returns


def test_train_resume_splices_event_log(tmp_path, monkeypatch):
    """A faulted run resumed in place must splice its log: the combined
    record chain reads like an uninterrupted run's (iterations [2,4,6,8]
    exactly once, one train_end, one fingerprint across the re-emitted
    headers) plus the fault history."""
    monkeypatch.chdir(tmp_path)
    # transient NaN fault: fires once (marker file), so the resumed run
    # sails past the poisoned iteration
    monkeypatch.setenv("LIGHTGBM_TPU_CHAOS_POISON_ITER", "3")
    monkeypatch.setenv("LIGHTGBM_TPU_CHAOS_POISON_ONCE",
                       str(tmp_path / "poison.marker"))
    from lightgbm_tpu.resilience import NumericDivergenceError
    extra = {"event_log": "run.events.jsonl", "resume": "auto",
             "snapshot_freq": 2, "snapshot_keep": 50,
             "nan_guard": "raise"}
    with pytest.raises(NumericDivergenceError):
        _train(rounds=8, extra=extra)
    recs = read_events("run.events.jsonl")
    assert recs[-1]["event"] == "nan_guard"   # no train_end after fault
    _train(rounds=8, extra=extra)
    recs = read_events("run.events.jsonl")
    assert check_records(recs) == []
    headers = [r for r in recs if r["event"] == "run_header"]
    assert len(headers) == 2
    assert len({h["fingerprint"] for h in headers}) == 1
    assert [r["iter"] for r in recs if r["event"] == "iteration"] == \
        [2, 4, 6, 8]
    assert sum(1 for r in recs if r["event"] == "train_end") == 1
    assert any(r["event"] == "resume" for r in recs)
    assert any(r["event"] == "nan_guard" for r in recs)  # history kept
    assert recs[-1]["event"] == "train_end" and recs[-1]["iter"] == 8
    assert active_session() is None


def test_train_nan_guard_raise_last_record(tmp_path, monkeypatch):
    """Satellite 6 acceptance: a nan_guard=raise abort leaves the
    nan_guard event as the log's LAST record (no train_end after it),
    and the routed log.warning record precedes it."""
    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv("LIGHTGBM_TPU_CHAOS_POISON_ITER", "3")
    from lightgbm_tpu.resilience import NumericDivergenceError
    with pytest.raises(NumericDivergenceError):
        _train(rounds=6, extra={"event_log": "run.events.jsonl",
                                "nan_guard": "raise"})
    recs = read_events("run.events.jsonl")
    assert recs[-1]["event"] == "nan_guard"
    assert recs[-1]["policy"] == "raise"
    assert not any(r["event"] == "train_end" for r in recs)
    assert active_session() is None


def test_log_warning_routed_to_active_event_log(tmp_path):
    p = str(tmp_path / "r.events.jsonl")
    ev = EventLog(p)
    try:
        set_active(ev)
        log.warning("something odd")
        with pytest.raises(RuntimeError):
            log.fatal("boom")
    finally:
        set_active(None)
    log.warning("not recorded")               # no active run -> no-op
    recs = read_events(p)
    assert [(r["level"], r["event"]) for r in recs] == \
        [("warning", "log"), ("fatal", "log")]
    assert "something odd" in recs[0]["msg"]


# ------------------------------------------------- exporter / endpoints
def _get(port, path):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
    try:
        conn.request("GET", path)
        r = conn.getresponse()
        return r.status, r.read().decode()
    finally:
        conn.close()


def test_introspection_server_endpoints(tmp_path):
    reg = MetricsRegistry()
    reg.counter("t_ops_total", "ops").inc(7)
    ev = EventLog(str(tmp_path / "r.events.jsonl"))
    ev.append("run_header", fingerprint="abc", driver="f", versions={})
    ev.append("iteration", iter=2, ms_per_tree=1.0, metrics={},
              phase_s={})
    srv = IntrospectionServer(reg, event_log=ev,
                              health_fn=lambda: {"iteration": 2})
    port = srv.start()
    try:
        st, body = _get(port, "/metrics")
        assert st == 200 and "t_ops_total 7" in body
        st, body = _get(port, "/healthz")
        assert st == 200
        h = json.loads(body)
        assert h["status"] == "ok" and h["iteration"] == 2
        st, body = _get(port, "/events?n=1")
        assert st == 200
        assert json.loads(body.strip())["event"] == "iteration"
        st, _ = _get(port, "/nope")
        assert st == 404
    finally:
        srv.stop()


def test_live_metrics_scrape_during_train(tmp_path, monkeypatch):
    """The live-introspection acceptance path: scrape /metrics from a
    callback while train() is inside its loop — training counters and
    device gauges must be live, and the port must be gone after."""
    monkeypatch.chdir(tmp_path)
    seen = {}

    def scrape(env):
        if env.iteration != 3 or seen:        # the iter-4 sync point
            return
        tele = active_session()
        assert tele is not None and tele.server is not None
        st, body = _get(tele.server.port, "/metrics")
        assert st == 200
        seen["port"] = tele.server.port
        seen["families"] = {ln.split("{")[0].split(" ")[0]
                            for ln in body.splitlines()
                            if ln and not ln.startswith("#")}
    _train(rounds=6, extra={"telemetry_port": 0}, callbacks=[scrape])
    assert {"train_iterations_total", "train_ms_per_tree",
            "train_host_syncs_total", "train_eval_metric",
            "device_hbm_bytes_in_use",
            "xla_compiles_total"} <= seen["families"]
    with pytest.raises(OSError):              # server gone after close
        _get(seen["port"], "/metrics")


def test_telemetry_port_env_spelling(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv("LIGHTGBM_TPU_TELEMETRY_PORT", "0")
    ports = []

    def scrape(env):
        tele = active_session()
        if tele is not None and tele.server is not None:
            ports.append(tele.server.port)
    _train(rounds=2, callbacks=[scrape])
    assert ports and ports[0] > 0


# ---------------------------------------------------------- monitor CLI
def test_monitor_cli_report_and_check(tmp_path, capsys):
    p = str(tmp_path / "run.events.jsonl")
    ev = EventLog(p)
    ev.append("run_header", fingerprint="abc", driver="fused",
              versions={"lightgbm_tpu": "0.1.0", "jax": "x"},
              objective="binary", parallel_mode="serial", num_shards=1,
              class_batch=True, eval_period=2)
    ev.append("iteration", iter=2, ms_per_tree=3.5,
              metrics={"train:auc": 0.9},
              phase_s={"build": {"s_per_iter": 0.001,
                                 "spans_per_iter": 1.0}})
    ev.append("nan_guard", iter=3, policy="rollback", action="rollback")
    ev.append("train_end", iter=4, trees=4, wall_s=0.5)
    assert monitor_main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "fingerprint: abc" in out
    assert "train:auc=0.9" in out
    assert "nan_guard rollback at iteration 3" in out
    assert "ended: iteration 4" in out
    assert monitor_main(["--check", p]) == 0
    assert "OK (4 records)" in capsys.readouterr().out
    # schema violation -> rc 1
    with open(p, "a") as f:
        f.write(json.dumps({"event": "wat", "ts": 0.0, "seq": 99})
                + "\n# force parse of the bogus line\n")
    assert monitor_main(["--check", p]) == 1
    assert monitor_main([str(tmp_path / "missing")]) == 1


# ------------------------------------------------------ the span record
def test_span_ring_is_bounded_and_sequenced():
    from lightgbm_tpu import profiler
    rec = profiler.SpanRecorder(capacity=8)
    for i in range(20):
        rec.record("gbdt.dispatch", i, i + 1)
    assert len(rec) == 8 and rec.seq == 20
    assert [s.seq for s in rec.since(0)] == list(range(12, 20))
    assert [s.seq for s in rec.since(18)] == [18, 19]
    # the always-on recorder is a ring too, and span names are a fixed
    # list: an unknown one is an error at the site, not a silent record
    assert profiler.recorder._ring.maxlen == profiler.RING_SPANS
    with pytest.raises(ValueError, match="unknown profiler phase"):
        with profiler.span("not.a.span"):
            pass


def test_span_parent_iteration_and_fields():
    from lightgbm_tpu import profiler
    seq0 = profiler.recorder.seq
    profiler.recorder.iteration = 41
    with profiler.span("gbdt.dispatch"):
        with profiler.span("gbdt.step_ready") as fields:
            fields["cache_hits"] = 1
    inner, outer = profiler.recorder.since(seq0)
    assert (inner.name, inner.parent) == ("gbdt.step_ready",
                                          "gbdt.dispatch")
    assert (outer.name, outer.parent) == ("gbdt.dispatch", "")
    assert inner.fields == {"cache_hits": 1} and inner.iteration == 41
    assert outer.start_ns <= inner.start_ns <= inner.end_ns <= outer.end_ns
    # inside traced code a stage is a named scope and records nothing
    import jax
    seq1 = profiler.recorder.seq

    @jax.jit
    def f(x):
        with profiler.stage("build"), profiler.stage("hist_kernel"):
            return x + 1
    f(1)
    assert profiler.recorder.seq == seq1


def test_per_iteration_from_ring_on_legacy_driver():
    """Legacy driver: per_iteration computed from the ring equals the
    plain sums over the spans the run recorded (what the old
    accumulator, fed by phase() itself, used to hold), with one
    grads/sampling span and K build spans an iteration."""
    from lightgbm_tpu import profiler
    seq0 = profiler.recorder.seq
    rounds = 4
    with profiler.collect_phase_totals() as col:
        bst = _train(rounds=rounds, extra={"fused_train": False})
    assert not bst._gbdt.fused_ok
    spans = [s for s in profiler.recorder.since(seq0)
             if s.seq < col._stop]
    want = {}
    for sp in spans:
        tot, cnt = want.get(sp.name, (0.0, 0))
        want[sp.name] = (tot + sp.seconds, cnt + 1)
    per = col.per_iteration(rounds)
    assert set(per) == set(want)
    for name, (tot, cnt) in want.items():
        assert per[name]["total_s"] == pytest.approx(tot)
        assert per[name]["count"] == cnt
        assert per[name]["s_per_iter"] == pytest.approx(tot / rounds)
    assert per["grads"]["spans_per_iter"] == 1
    assert per["build"]["spans_per_iter"] == 1      # binary: K = 1
    assert "gbdt.dispatch" not in per               # no fused dispatch
    # the legacy driver fetches one tree a sync and logs its rounds too
    assert len(bst._gbdt.round_log) == rounds


def test_device_reserved_pool_gauge(monkeypatch):
    """device_hbm_bytes_peak is in-use plus reserved, as the benchmark
    reckons the chip's high-water mark; the pool alone has a gauge."""
    from lightgbm_tpu.telemetry import device as tdev
    monkeypatch.setattr(tdev, "device_memory_bytes", lambda: {
        "tpu:0": {"bytes_in_use": 100, "peak_bytes_in_use": 500,
                  "peak_bytes_reserved": 5000}})
    reg = MetricsRegistry()
    tdev.DeviceWatch(reg).sample()
    text = reg.render()
    assert 'device_hbm_bytes_peak{device="tpu:0"} 5500' in text
    assert 'device_hbm_bytes_reserved_peak{device="tpu:0"} 5000' in text
    assert 'device_hbm_bytes_in_use{device="tpu:0"} 100' in text


# -------------------------------------------------------- device gauges
def test_device_memory_and_collective_gauges():
    from lightgbm_tpu.telemetry.device import (CollectiveWatch,
                                               device_memory_bytes)
    mem = device_memory_bytes()
    assert mem and all("bytes_in_use" in v for v in mem.values())
    reg = MetricsRegistry()
    watch = CollectiveWatch(reg, trees_fn=lambda: 3)
    text = reg.render()                        # unattached -> 0, no raise
    assert "train_collective_hist_bytes_per_tree 0" in text

    class _Gb:                                 # serial booster: no plan
        plan = None
    watch.attach(_Gb())
    assert "train_collective_hist_bytes_total 0" in reg.render()
