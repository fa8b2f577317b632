"""A stage's work beside its seconds: the step's shape on
``gbdt.step_ready`` (``phases.STEP_SHAPE``) against what the traced build
sized itself with, the work function's counts (``GBDT.stage_work``,
``phases.STAGE_WORK``) against a hand count from the round log, the
compiles of a start, and ``monitor --perf`` with and without the sidecar's
``step_work`` entry."""

import json
import os
import shutil
import sys

import numpy as np
import pytest

import jax
import jax.monitoring

import lightgbm_tpu as lgb
from lightgbm_tpu import phases, profiler
from lightgbm_tpu.boosting import tree_builder
from lightgbm_tpu.telemetry import costmodel, xprof
from lightgbm_tpu.telemetry.monitor import monitor_main, render_perf

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "trace_events.json")
GOLDEN_MAP = {"jit_train_step": {
    "dot_general.5": "build", "fusion.1": "grads", "add.9": "update",
    "dot.1": "build", "add.3": "apply"}}
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# run again here, so that tier-1 holds them: the seven readers' own cases
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(_ROOT, "benchmarks", "tests"),
           os.path.join(_ROOT, "benchmarks")):
    if _p not in sys.path:
        sys.path.insert(0, _p)
from test_stage_cost_metrics import (  # noqa: E402,F401  (collected here too)
    test_manifest_has_the_seven_entries_appended_for_their_cells,
    test_a_reader_gives_the_hand_arithmetic,
    test_a_reader_takes_no_shape_from_the_configuration,
    test_a_reader_with_nothing_to_read_returns_nothing,
    test_the_stage_without_seconds_or_without_a_count_reads_nothing,
    test_on_several_chips_seconds_and_counts_are_both_a_chips)
BASE = {"objective": "binary", "num_leaves": 15, "leaf_batch": 4,
        "max_bin": 31, "min_data_in_leaf": 5, "verbosity": -1,
        "tree_learner": "serial"}


def _dense(rng, n=4000, f=6):
    X = rng.normal(size=(n, f))
    return X, (X[:, 0] + X[:, 1] ** 2 > 0.5).astype(float)


def _exclusive(rng, n=2048, f=12):
    X = np.zeros((n, f))
    perm = rng.permutation(n)
    for j in range(f):      # strictly exclusive columns -> bundles form
        rows = perm[j * (n // f):(j + 1) * (n // f)]
        X[rows, j] = rng.normal(size=len(rows)) + 1.0
    return X, (X[:, 0] - X[:, 1] + 0.3 * X[:, 2] > 0.2).astype(float)


def _train(X, y, trees=3, **more):
    params = dict(BASE, **more)
    bst = lgb.Booster(params, lgb.Dataset(X, label=y, params=params))
    for _ in range(trees):
        bst.update(defer=True)
    bst._sync_trees()
    return bst._gbdt


@pytest.fixture()
def traced_shapes(monkeypatch):
    """Every ``StepShape`` the builder sizes a trace with."""
    seen = []
    real = tree_builder.step_shape

    def spy(**kw):
        seen.append(real(**kw))
        return seen[-1]
    monkeypatch.setattr(tree_builder, "step_shape", spy)
    return seen


@pytest.mark.parametrize("case", ["one_device", "data_mesh", "bundled"])
def test_step_ready_carries_the_shape_the_traced_build_used(
        case, traced_shapes, monkeypatch):
    rng = np.random.default_rng(3)
    monkeypatch.setenv("LIGHTGBM_TPU_FUSED_TRAIN", "1")
    jax.clear_caches()      # so that the build is traced here, under the spy
    if case == "data_mesh":
        four = jax.devices()[:4]
        monkeypatch.setattr(jax, "devices", lambda *a, **k: four)
        gb = _train(*_dense(rng), tree_learner="data")
        assert gb.plan is not None and gb.plan.num_shards == 4
    elif case == "bundled":
        gb = _train(*_exclusive(rng), enable_bundle=True)
        assert gb._bundle_meta is not None
    else:
        gb = _train(*_dense(rng))
        assert gb.plan is None
    assert gb.fused_ok
    fields = profiler.recorder.spans("gbdt.step_ready")[-1].fields
    got = {k: fields[k] for k in phases.STEP_SHAPE}
    assert got == gb.step_shape and all(type(v) is int for v in got.values())
    # gbdt.py calls step_shape through its own import: the spy sees the
    # builder's calls alone, the sizes the traced code took for itself
    assert traced_shapes and {s.fields() == got for s in traced_shapes} == {
        True}
    W = min(BASE["leaf_batch"], BASE["num_leaves"] - 1)
    assert got[phases.SHAPE_SLOTS] == 2 * W
    assert got[phases.SHAPE_ROUNDS_BOUND] == tree_builder.max_rounds_for(
        BASE["num_leaves"], W)
    r_pad = gb.train_dd.r_pad
    F = int(gb.num_bins_pf.shape[0])
    if case == "data_mesh":
        assert got[phases.SHAPE_ROWS] == r_pad // 4
        # reduce-scatter merge: a chip searches its block of the features
        assert gb.plan.hist_merge == "reduce_scatter"
        assert got[phases.SHAPE_SEARCH_POSITIONS] == -(-F // 4) * gb.B
        assert fields[phases.PLAN_ROWS_PER_SHARD] == r_pad // 4
    else:
        assert got[phases.SHAPE_ROWS] == r_pad
        assert got[phases.SHAPE_SEARCH_POSITIONS] == F * gb.B
    if case == "bundled":
        bp = gb.train_set.bundle_plan
        assert got[phases.SHAPE_STORED_COLUMNS] == bp.num_bundles < F
        assert got[phases.SHAPE_STORED_BINS] == bp.max_bundle_bins
    else:
        assert got[phases.SHAPE_STORED_COLUMNS] == F
        assert got[phases.SHAPE_STORED_BINS] == gb.B
    # every round's stream positions are whole trips of the stated chunk
    chunk = got[phases.SHAPE_STREAM_CHUNK_ROWS]
    for rec in gb.round_log:
        assert not np.any(rec.stream_rows % chunk)


def _hand_count(gb, log, pick):
    """The work function's counts, from the log by hand. ``pick`` reduces
    a per-shard vector (mean or max)."""
    sh = gb.step_shape
    rows, cols = sh["shape_rows"], sh["shape_stored_columns"]
    blk, root_blk = sh["shape_kernel_row_block"], \
        sh["shape_kernel_root_row_block"]
    a_row = (sh["shape_kernel_chunks"] * sh["shape_kernel_feature_chunk"]
             * sh["shape_kernel_padded_bins"])
    scan = sh["shape_slots"] * sh["shape_search_positions"]
    rounds, positions, covered = 0, 0.0, 0.0
    for rec in log:
        shards = rec.rows.reshape(-1, rec.leaves.shape[0])
        stream = rec.stream_rows.reshape(-1, rec.leaves.shape[0])
        pos_s = np.zeros(len(shards))
        cov_s = np.zeros(len(shards))
        for r, n_leaves in enumerate(rec.leaves):
            if n_leaves <= 0:
                continue
            rounds += 1
            for s in range(len(shards)):
                pos_s[s] += int(stream[s, r])
                cov_s[s] += -(-int(shards[s, r]) // blk) * blk
        positions += pick(pos_s)
        covered += pick(cov_s)
    trees = len(log)
    root_rows = -(-rows // root_blk) * root_blk
    return rounds, {
        "hist_gather": positions,
        "hist_relayout": (positions + trees * rows) * cols,
        "hist_kernel": (covered + trees * root_rows) * a_row,
        "compact": rounds * rows * sh["shape_stream_compacted"],
        "apply": rounds * rows, "count": rounds * rows,
        "find": rounds * scan, "subtract": rounds * scan,
        "unbundle": (rounds + trees) * scan, "root_pass": trees * scan,
        "update": trees * rows, "grads": trees * rows}


@pytest.mark.parametrize("impl,compacted", [("scatter", 1), ("native", 0)])
def test_stage_work_is_the_round_logs_hand_count(impl, compacted,
                                                 monkeypatch):
    if impl == "native":
        from lightgbm_tpu import native
        if native.hist_lib() is None:
            pytest.skip("no C toolchain: native degrades to scatter")
    monkeypatch.setenv("LIGHTGBM_TPU_FUSED_TRAIN", "1")
    gb = _train(*_dense(np.random.default_rng(5)), trees=4, hist_impl=impl)
    assert gb.step_shape[phases.SHAPE_STREAM_COMPACTED] == compacted
    log = list(gb.round_log)[-3:]
    rounds, want = _hand_count(gb, log, np.mean)
    assert rounds == sum(int((r.leaves > 0).sum()) for r in log) > 3
    got = gb.stage_work(3)
    assert {k: c for k, (c, _) in got.items()} == {
        k: v for k, v in want.items() if v}
    assert all(u == phases.STAGE_WORK[k] for k, (_, u) in got.items())
    assert ("compact" in got) == bool(compacted)
    # no plan: nothing on a wire; no ranking objective: no pair slots
    assert not {"hist_merge", "winner_sync", "rank_pairs"} & set(got)
    # all the log holds by default, and nothing before a step is made
    assert gb.stage_work()["update"][0] == 4 * gb.step_shape["shape_rows"]
    params = dict(BASE)
    fresh = lgb.Booster(params, lgb.Dataset(
        *_dense(np.random.default_rng(5)), params=params))
    fresh._ensure_gbdt()
    assert fresh._gbdt.stage_work() == {}


def test_stage_work_on_a_data_mesh_is_a_shards(monkeypatch):
    monkeypatch.setenv("LIGHTGBM_TPU_FUSED_TRAIN", "1")
    four = jax.devices()[:4]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: four)
    gb = _train(*_dense(np.random.default_rng(7)), tree_learner="data")
    log = list(gb.round_log)
    assert log[0].rows.shape[0] == 4
    for fullest, pick in ((False, np.mean), (True, np.max)):
        rounds, want = _hand_count(gb, log, pick)
        got = gb.stage_work(fullest=fullest)
        for k, v in want.items():
            if v:       # (a native build sorts nothing: no ``compact``)
                assert got[k][0] == pytest.approx(v, rel=1e-12), k
        # bytes on the wire: the plan's a round and a tree, one chip's
        for stage in ("hist_merge", "winner_sync"):
            a_round = gb.plan_counters[phases.PLAN_ROUND_BYTES_BY_STAGE]
            a_tree = gb.plan_counters[phases.PLAN_TREE_BYTES_BY_STAGE]
            assert got[stage] == (
                rounds * a_round[stage] + len(log) * a_tree.get(stage, 0),
                phases.UNIT_BYTES)
    mean, full = gb.stage_work(), gb.stage_work(fullest=True)
    assert full["hist_kernel"][0] >= mean["hist_kernel"][0]
    assert full["apply"] == mean["apply"]      # every shard passes its rows


def test_a_ranking_objective_counts_its_pair_slots():
    shape = dict.fromkeys(phases.STEP_SHAPE, 1)
    rec = type("R", (), {})()
    rec.leaves = np.array([1, 2, 0])
    rec.rows = np.array([5, 3, 0])
    rec.stream_rows = np.array([8, 8, 0])
    got = costmodel.stage_work(shape, [rec, rec], pair_slots=1000)
    assert got["rank_pairs"] == (2000, phases.UNIT_PAIR_SLOTS)
    assert got["hist_gather"] == (32.0, phases.UNIT_POSITIONS)
    assert "rank_pairs" not in costmodel.stage_work(shape, [rec])


_START = """
import numpy as np, jax, jax.monitoring
import lightgbm_tpu as lgb
seen = []
jax.monitoring.register_event_duration_secs_listener(
    lambda e, d, **_: seen.append(e))
rng = np.random.default_rng(0)
X = rng.normal(size=(4000, 6)); y = (X[:, 0] + X[:, 1] ** 2 > 0.5) * 1.0
p = dict(objective="binary", num_leaves=15, leaf_batch=4, verbosity=-1,
         max_bin=31, tree_learner="serial")
ds = lgb.Dataset(X, label=y, params=p).construct()
bst = lgb.Booster(p, ds)
n0 = seen.count("%s")
bst.update(defer=True)
jax.block_until_ready(bst._gbdt.scores)
n1 = seen.count("%s")
for _ in range(2):
    bst.update(defer=True)
jax.block_until_ready(bst._gbdt.scores)
n2, events = seen.count("%s"), len(seen)
again = bst._gbdt._step_shape().fields()
assert again == bst._gbdt.step_shape
print("COUNTS", n1 - n0, n2 - n1, len(seen) - events)
""" % ((COMPILE_EVENT,) * 3)


def test_a_start_compiles_as_many_programs_as_before():
    """The shape fields are host arithmetic. In a fresh process the first
    dispatch compiles the 6 programs it compiled at the parent commit
    (1252c4c: the fused step and five helpers), the trees after it none,
    and computing the shape again compiles, lowers and traces nothing."""
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               LIGHTGBM_TPU_FUSED_TRAIN="1")
    out = subprocess.run(
        [sys.executable, "-c", _START], env=env, capture_output=True,
        text=True, timeout=600,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-2000:]
    line = next(ln for ln in out.stdout.splitlines()
                if ln.startswith("COUNTS"))
    assert line.split()[1:] == ["6", "0", "0"]


# ----------------------------------------------------------------------
# monitor --perf: count, unit and cost a stage with the sidecar's entry


STEP_WORK = {
    "step_shape": dict.fromkeys(phases.STEP_SHAPE, 2), "trees": 2,
    "stage_work": {"build": [0, "x"],       # no count: no columns
                   "grads": [60000, phases.UNIT_ROWS],
                   "apply": [25, phases.UNIT_ROW_PASSES],
                   "find": [7, phases.UNIT_LATTICE]}}   # no seconds


def _capture(tmp_path, step_work):
    cap = tmp_path / "traces" / "capture_0001"
    cap.mkdir(parents=True)
    shutil.copy(GOLDEN, cap / "host.trace.json")
    xprof.save_phase_map(str(cap), GOLDEN_MAP, step_work)
    return cap


def test_monitor_perf_prints_count_unit_and_cost_with_the_sidecar(
        tmp_path, capsys):
    cap = _capture(tmp_path, STEP_WORK)
    # the stage maps are found as before, the entry is no module
    assert xprof.find_phase_map(str(cap / "host.trace.json")) == GOLDEN_MAP
    assert xprof.load_phase_map(
        str(cap / xprof.PHASE_MAP_NAME)) == GOLDEN_MAP
    prof = xprof.parse_trace(str(cap))
    # golden: grads 30 us and apply 50 us over 3 devices
    devices = len(prof.per_device)
    costs = prof.stage_costs()
    assert set(costs) == {"grads", "apply"}
    assert costs["grads"] == (60000, "rows", pytest.approx(
        30e-6 / devices / 60000))
    assert costs["apply"][2] == pytest.approx(50e-6 / devices / 25)
    out = render_perf(str(cap))
    head = next(ln for ln in out.splitlines() if "device ms" in ln)
    assert "count" in head and "unit" in head and "a unit" in head
    row = next(ln for ln in out.splitlines() if ln.startswith("  grads"))
    assert "60000" in row and "rows" in row and row.rstrip().endswith("ps")
    row = next(ln for ln in out.splitlines() if ln.startswith("  apply"))
    assert "row_passes" in row and row.rstrip().endswith("ns")
    assert "over 2 tree(s)" in out
    summary = prof.summary_dict()
    assert summary["work_trees"] == 2
    assert summary["step_shape"] == STEP_WORK["step_shape"]
    assert summary["stage_work"]["grads"]["unit"] == "rows"
    assert summary["stage_work"]["grads"]["ns_per_unit"] == pytest.approx(
        30e-6 / devices / 60000 * 1e9)
    json.dumps(summary)
    assert monitor_main(["--perf", str(tmp_path)]) == 0
    assert "a unit" in capsys.readouterr().out


def test_monitor_perf_without_the_entry_prints_what_it_printed(tmp_path):
    with_entry = render_perf(str(_capture(tmp_path / "a", STEP_WORK)))
    plain = render_perf(str(_capture(tmp_path / "b", None)))
    assert "count" not in plain and "a unit" not in plain
    assert "stage_work" not in xprof.parse_trace(
        str(tmp_path / "b")).summary_dict()
    # the same table, less the three columns and their footnote
    a = [ln for ln in with_entry.splitlines()[1:] if "counts:" not in ln]
    b = plain.splitlines()[1:]
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x.startswith(y)


def test_step_work_of_takes_the_newest_logged_trees(monkeypatch):
    monkeypatch.setenv("LIGHTGBM_TPU_FUSED_TRAIN", "1")
    gb = _train(*_dense(np.random.default_rng(13)), trees=3)
    entry = xprof.step_work_of(gb, 2)
    assert entry["trees"] == 2 and entry["step_shape"] == gb.step_shape
    assert entry["stage_work"] == {
        k: [c, u] for k, (c, u) in gb.stage_work(2).items()}
    assert xprof.step_work_of(gb, 50)["trees"] == 3     # all it holds
    json.dumps(entry)
    assert xprof.step_work_of(object(), 2) is None


def test_the_trace_endpoint_writes_and_answers_with_the_work(
        tmp_path, monkeypatch):
    """``/trace``: the capture's trees are counted once it is parsed, then
    the sidecar gets their work and the answer its three columns."""
    from lightgbm_tpu.telemetry.core import MetricsRegistry
    from lightgbm_tpu.telemetry.exporter import IntrospectionServer
    monkeypatch.setattr(
        jax.profiler, "start_trace",
        lambda d, **k: shutil.copy(GOLDEN, os.path.join(
            d, "host.trace.json")))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    asked = []

    def step_work(iterations):
        asked.append(iterations)
        return dict(STEP_WORK, trees=iterations)
    srv = IntrospectionServer(
        MetricsRegistry(), capture_root=str(tmp_path),
        phase_map_fn=lambda: GOLDEN_MAP, step_work_fn=step_work)
    resp = srv.capture_trace(duration_ms=1)
    assert asked == [2] and resp["work_trees"] == 2     # golden: 2 steps
    assert set(resp["stage_work"]) == {"grads", "apply"}
    side = os.path.join(resp["log_dir"], xprof.PHASE_MAP_NAME)
    with open(side) as f:
        assert json.load(f)[xprof.STEP_WORK_KEY]["trees"] == 2
    assert xprof.load_phase_map(side) == GOLDEN_MAP
    # a session with no trainer yet answers as before
    srv.step_work_fn = lambda n: None
    assert "stage_work" not in srv.capture_trace(duration_ms=1)
