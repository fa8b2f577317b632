"""Multi-host distributed training.

Mirrors the reference's tests/distributed/_test_distributed.py
``DistributedMockup``: N worker processes on localhost, pre-partitioned
data, tree_learner=data — except the transport is jax.distributed (gloo
on CPU standing in for DCN) instead of the socket Linkers mesh.
Also unit-tests the machines-string bootstrap (linkers_socket.cpp:24
parsing analog) with a mocked jax.distributed.initialize.
"""

import json
import os
import socket
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.parallel import distributed as dist


@pytest.fixture(autouse=True)
def _reset_init_flag():
    dist._initialized = False
    yield
    dist._initialized = False


def test_maybe_init_parses_machines(monkeypatch):
    calls = {}

    def fake_init(coordinator_address=None, num_processes=None,
                  process_id=None):
        calls.update(coordinator=coordinator_address, n=num_processes,
                     rank=process_id)

    monkeypatch.setattr("jax.distributed.initialize", fake_init)
    monkeypatch.setenv("LIGHTGBM_TPU_RANK", "1")
    cfg = lgb.Config({"num_machines": 2,
                      "machines": "10.0.0.5:12400,10.0.0.6:12400"})
    assert dist.maybe_init_distributed(cfg) is True
    assert calls == {"coordinator": "10.0.0.5:12400", "n": 2, "rank": 1}


def test_maybe_init_machine_list_file(monkeypatch, tmp_path):
    calls = {}

    def fake_init(coordinator_address=None, num_processes=None,
                  process_id=None):
        calls.update(coordinator=coordinator_address, n=num_processes)

    monkeypatch.setattr("jax.distributed.initialize", fake_init)
    monkeypatch.delenv("LIGHTGBM_TPU_RANK", raising=False)
    mlist = tmp_path / "mlist.txt"
    mlist.write_text("host-a:1234\nhost-b:1234\n")
    cfg = lgb.Config({"num_machines": 2,
                      "machine_list_filename": str(mlist)})
    assert dist.maybe_init_distributed(cfg) is True
    assert calls["coordinator"] == "host-a:1234"
    assert calls["n"] == 2


def test_maybe_init_single_machine_noop(monkeypatch):
    def boom(**kw):  # pragma: no cover
        raise AssertionError("must not initialize for num_machines=1")

    monkeypatch.setattr("jax.distributed.initialize", boom)
    assert dist.maybe_init_distributed(lgb.Config({})) is False


def test_sync_bin_mappers_single_process_noop(rng):
    X = rng.normal(size=(200, 4))
    ds = lgb.Dataset(X, label=rng.rand(200),
                     params={"pre_partition": True}).construct()
    # jax.process_count() == 1 here: sync must be the identity
    assert dist.sync_bin_mappers(ds.bin_mappers) is ds.bin_mappers


def test_global_mean_init_scores_mocked(monkeypatch):
    monkeypatch.setattr("jax.process_count", lambda: 2)
    monkeypatch.setattr(
        "jax.experimental.multihost_utils.process_allgather",
        lambda a: np.stack([np.asarray(a), np.asarray(a) + 1.0]))
    out = dist.global_mean_init_scores(np.asarray([1.0, 3.0]))
    np.testing.assert_allclose(out, [1.5, 3.5])


# ---------------------------------------------------------------------------
# Real two-process smoke (DistributedMockup analog). Each worker loads a
# DIFFERENT row shard, bin mappers sync across processes, and
# tree_learner=data trains over the 2-process x 4-virtual-device global
# mesh. The trees must come out IDENTICAL on both workers.
# ---------------------------------------------------------------------------

_WORKER = textwrap.dedent("""
    import os, sys, json
    rank, port, outdir, repo, mode = (int(sys.argv[1]), sys.argv[2],
                                      sys.argv[3], sys.argv[4],
                                      sys.argv[5])
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, repo)
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(coordinator_address="127.0.0.1:" + port,
                               num_processes=2, process_id=rank)
    import numpy as np
    import lightgbm_tpu as lgb

    rng = np.random.RandomState(0)
    n = 4000
    X = rng.normal(size=(n, 6))
    y = (X[:, 0] - 0.8 * X[:, 1] ** 2 + 0.5 * X[:, 2]
         + rng.normal(scale=0.3, size=n) > 0).astype(float)
    if mode == "pre_partition":
        # uneven pre-partitioned shards: worker 0 gets 2200 rows,
        # worker 1 the rest — mapper sync must still give identical bins
        cut = 2200
        sl = slice(0, cut) if rank == 0 else slice(cut, n)
        ds = lgb.Dataset(X[sl], label=y[sl],
                         params={"pre_partition": True})
        params = {"pre_partition": True}
    elif mode == "auto":
        # auto-partition: both workers load the FULL data; the loader
        # keeps this rank's row block (dataset_loader.cpp:203 path)
        sl = slice(0, n)
        ds = lgb.Dataset(X, label=y)
        params = {}
    if mode == "feature":
        # multi-host feature-parallel (round 5): every worker loads the
        # FULL dataset (feature_parallel_tree_learner.cpp:38 model —
        # pre_partition=true with the whole data), split work shards
        # over the 8 devices spanning both processes, and the gain
        # argmax crosses hosts
        sl = slice(0, n)
        ds = lgb.Dataset(X, label=y, params={"pre_partition": True})
        params = {"pre_partition": True, "tree_learner": "feature"}
    if mode == "feature_bad":
        # guard: auto-partitioned rows (pre_partition=false) are NOT a
        # full copy per worker — feature mode must refuse with guidance
        ds = lgb.Dataset(X, label=y)          # loader keeps rank's block
        try:
            lgb.train({"objective": "binary", "tree_learner": "feature",
                       "num_leaves": 15, "min_data_in_leaf": 5,
                       "verbosity": -1}, ds, 2)
            raise SystemExit("expected ValueError for auto-partition")
        except ValueError as e:
            assert "pre_partition" in str(e), e
        with open(os.path.join(outdir, f"out_{rank}.json"), "w") as f:
            json.dump({"auc": 1.0}, f)
        with open(os.path.join(outdir, f"model_{rank}.txt"), "w") as f:
            f.write("guard ok")
        sys.exit(0)
    if mode == "ranking":
        # lambdarank across hosts (VERDICT r4 #4): each worker owns
        # WHOLE queries (the reference pre-partitions by query);
        # gradients are per-process, histogram sync is global
        rngq = np.random.RandomState(7)
        nq = 120
        sizes = rngq.randint(5, 20, size=nq)
        bounds = np.concatenate([[0], np.cumsum(sizes)])
        nr = int(bounds[-1])
        Xq = rngq.normal(size=(nr, 6))
        rel = (Xq[:, 0] + 0.6 * Xq[:, 1]
               + rngq.normal(scale=0.6, size=nr))
        yq = np.zeros(nr)
        for q in range(nq):
            r = rel[bounds[q]:bounds[q + 1]]
            yq[bounds[q]:bounds[q + 1]] = np.clip(
                np.searchsorted(np.sort(r), r) * 4 // max(1, len(r)),
                0, 3)
        qcut = 60
        qs = slice(0, qcut) if rank == 0 else slice(qcut, nq)
        rs = slice(int(bounds[qs.start]), int(bounds[qs.stop]))
        ds = lgb.Dataset(Xq[rs], label=yq[rs], group=sizes[qs],
                         params={"pre_partition": True})
        bst = lgb.train({"objective": "lambdarank", "metric": "ndcg",
                         "eval_at": [5], "num_leaves": 15,
                         "tree_learner": "data", "min_data_in_leaf": 5,
                         "pre_partition": True, "verbosity": -1},
                        ds, num_boost_round=10)
        txt = bst.model_to_string()
        ndcg = float(bst.eval_train()[0][2])
        with open(os.path.join(outdir, f"out_{rank}.json"), "w") as f:
            json.dump({"ndcg": ndcg}, f)
        with open(os.path.join(outdir, f"model_{rank}.txt"), "w") as f:
            f.write(txt)
        sys.exit(0)
    if mode == "reduce_scatter":
        # ISSUE 4: the feature-slot-scattered histogram merge crosses
        # PROCESSES here (2 hosts x 4 devices: psum_scatter rides the
        # inter-process link, winner sync merges cross-host). auto must
        # resolve to reduce_scatter on the 8-shard mesh and the result
        # must be bit-equal to the allreduce merge on the same shards.
        cut = 2200
        sl = slice(0, cut) if rank == 0 else slice(cut, n)
        common = {"objective": "binary", "num_leaves": 15,
                  "tree_learner": "data", "min_data_in_leaf": 5,
                  "pre_partition": True, "verbosity": -1}
        bst = lgb.train(common, lgb.Dataset(
            X[sl], label=y[sl], params={"pre_partition": True}), 8)
        assert bst._gbdt.plan.hist_merge == "reduce_scatter", \
            bst._gbdt.plan.hist_merge
        bst_ar = lgb.train(dict(common, dp_hist_merge="allreduce"),
                           lgb.Dataset(X[sl], label=y[sl],
                                       params={"pre_partition": True}),
                           8)
        np.testing.assert_array_equal(bst.predict(X[sl]),
                                      bst_ar.predict(X[sl]))
        txt = bst.model_to_string()
        from sklearn.metrics import roc_auc_score
        auc = roc_auc_score(y[sl], bst.predict(X[sl]))
        with open(os.path.join(outdir, f"out_{rank}.json"), "w") as f:
            json.dump({"auc": auc}, f)
        with open(os.path.join(outdir, f"model_{rank}.txt"), "w") as f:
            f.write(txt)
        sys.exit(0)
    if mode == "init_model":
        # continued training across hosts (VERDICT r4 #4 remainder):
        # each host predicts its own pre-partitioned rows with the
        # base model; scores resume sharded
        cut = 2000
        sl = slice(0, cut) if rank == 0 else slice(cut, n)
        ds = lgb.Dataset(X[sl], label=y[sl],
                         params={"pre_partition": True},
                         free_raw_data=False)
        bst = lgb.train({"objective": "binary", "num_leaves": 15,
                         "tree_learner": "data", "min_data_in_leaf": 5,
                         "pre_partition": True, "verbosity": -1},
                        ds, num_boost_round=6,
                        init_model=os.path.join(outdir, "base.txt"))
        txt = bst.model_to_string()
        from sklearn.metrics import roc_auc_score
        auc = roc_auc_score(y[sl], bst.predict(X[sl]))
        with open(os.path.join(outdir, f"out_{rank}.json"), "w") as f:
            json.dump({"auc": auc, "n_trees": bst.num_trees()}, f)
        with open(os.path.join(outdir, f"model_{rank}.txt"), "w") as f:
            f.write(txt)
        sys.exit(0)
    bst = lgb.train({"objective": "binary", "num_leaves": 15,
                     "tree_learner": "data",
                     "min_data_in_leaf": 5, "verbosity": -1, **params},
                    ds, num_boost_round=8)
    txt = bst.model_to_string()
    from sklearn.metrics import roc_auc_score
    auc = roc_auc_score(y[sl], bst.predict(X[sl]))
    with open(os.path.join(outdir, f"out_{rank}.json"), "w") as f:
        json.dump({"model_len": len(txt), "auc": auc}, f)
    with open(os.path.join(outdir, f"model_{rank}.txt"), "w") as f:
        f.write(txt)
""")


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _run_two_workers(tmp_path, mode: str):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = tmp_path / "worker.py"
    script.write_text(_WORKER)
    port = str(_free_port())
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    procs = [subprocess.Popen(
        [sys.executable, str(script), str(r), port, str(tmp_path), repo,
         mode],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        for r in range(2)]
    try:
        outs = [p.communicate(timeout=420)[0].decode() for p in procs]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    for p, out in zip(procs, outs):
        assert p.returncode == 0, out[-3000:]
    m0 = (tmp_path / "model_0.txt").read_text()
    m1 = (tmp_path / "model_1.txt").read_text()
    assert m0 == m1, "workers must produce the identical model"
    r0 = json.loads((tmp_path / "out_0.json").read_text())
    r1 = json.loads((tmp_path / "out_1.json").read_text())
    assert r0["auc"] > 0.9 and r1["auc"] > 0.9, (r0, r1)


@pytest.mark.slow
def test_two_process_data_parallel_training(tmp_path):
    _run_two_workers(tmp_path, "pre_partition")


@pytest.mark.slow
def test_two_process_auto_partition_training(tmp_path):
    _run_two_workers(tmp_path, "auto")


@pytest.mark.slow
def test_two_process_reduce_scatter_training(tmp_path):
    """ISSUE 4: the scattered histogram merge over a 2-process x
    4-device global mesh — auto resolves to reduce_scatter, workers
    produce the identical model, and predictions are bit-equal to the
    allreduce merge on the same shards."""
    _run_two_workers(tmp_path, "reduce_scatter")


@pytest.mark.slow
def test_two_process_feature_parallel_training(tmp_path):
    """Multi-host feature-parallel (round 5): full data on every
    worker, split work feature-sharded across the processes' devices,
    winner synced by the cross-host gain argmax. Models must be
    identical on both workers."""
    _run_two_workers(tmp_path, "feature")


@pytest.mark.slow
def test_two_process_feature_parallel_rejects_auto_partition(tmp_path):
    """The loader's auto-partition keeps only this rank's rows; feature
    mode (full copy per worker) must refuse it with pre_partition
    guidance instead of silently training on mismatched replicas."""
    _run_two_workers(tmp_path, "feature_bad")


@pytest.mark.slow
def test_two_process_lambdarank_matches_single_process(tmp_path):
    """VERDICT r4 #4: distributed lambdarank. Both workers must emit
    the identical model, and its quality must match a single-process
    run on the same data (NDCG@5 within binning-sync tolerance)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = tmp_path / "worker.py"
    script.write_text(_WORKER)
    port = str(_free_port())
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    procs = [subprocess.Popen(
        [sys.executable, str(script), str(r), port, str(tmp_path), repo,
         "ranking"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        for r in range(2)]
    try:
        outs = [p.communicate(timeout=420)[0].decode() for p in procs]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    for p, out in zip(procs, outs):
        assert p.returncode == 0, out[-3000:]
    m0 = (tmp_path / "model_0.txt").read_text()
    m1 = (tmp_path / "model_1.txt").read_text()
    assert m0 == m1, "workers must produce the identical model"
    # single-process run over the SAME generated data (worker rngq=7)
    rngq = np.random.RandomState(7)
    nq = 120
    sizes = rngq.randint(5, 20, size=nq)
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    nr = int(bounds[-1])
    Xq = rngq.normal(size=(nr, 6))
    rel = Xq[:, 0] + 0.6 * Xq[:, 1] + rngq.normal(scale=0.6, size=nr)
    yq = np.zeros(nr)
    for q in range(nq):
        r = rel[bounds[q]:bounds[q + 1]]
        yq[bounds[q]:bounds[q + 1]] = np.clip(
            np.searchsorted(np.sort(r), r) * 4 // max(1, len(r)), 0, 3)
    import lightgbm_tpu as lgb
    bst = lgb.train({"objective": "lambdarank", "metric": "ndcg",
                     "eval_at": [5], "num_leaves": 15,
                     "min_data_in_leaf": 5, "verbosity": -1},
                    lgb.Dataset(Xq, label=yq, group=sizes), 10)
    ndcg_sp = float(bst.eval_train()[0][2])
    nd0 = json.loads((tmp_path / "out_0.json").read_text())["ndcg"]
    nd1 = json.loads((tmp_path / "out_1.json").read_text())["ndcg"]
    # per-host NDCG over each host's own queries; the mean stands in
    # for the global number (equal-ish query counts)
    ndcg_mp = 0.5 * (nd0 + nd1)
    assert ndcg_sp > 0.7, ndcg_sp
    assert abs(ndcg_mp - ndcg_sp) < 0.05, (ndcg_mp, ndcg_sp, nd0, nd1)


@pytest.mark.slow
def test_two_process_init_model_continuation(tmp_path):
    """Continued training (init_model) across 2 processes: both workers
    resume from the same base model over pre-partitioned shards, emit
    the identical continued model, and improve on the base AUC."""
    rng = np.random.RandomState(0)
    n = 4000
    X = rng.normal(size=(n, 6))
    y = (X[:, 0] - 0.8 * X[:, 1] ** 2 + 0.5 * X[:, 2]
         + rng.normal(scale=0.3, size=n) > 0).astype(float)
    base = lgb.train({"objective": "binary", "num_leaves": 15,
                      "min_data_in_leaf": 5, "verbosity": -1},
                     lgb.Dataset(X, label=y), 4)
    base.save_model(str(tmp_path / "base.txt"))
    from sklearn.metrics import roc_auc_score
    base_auc = roc_auc_score(y, base.predict(X))

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = tmp_path / "worker.py"
    script.write_text(_WORKER)
    port = str(_free_port())
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    procs = [subprocess.Popen(
        [sys.executable, str(script), str(r), port, str(tmp_path), repo,
         "init_model"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        for r in range(2)]
    try:
        outs = [p.communicate(timeout=420)[0].decode() for p in procs]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    for p, out in zip(procs, outs):
        assert p.returncode == 0, out[-3000:]
    m0 = (tmp_path / "model_0.txt").read_text()
    m1 = (tmp_path / "model_1.txt").read_text()
    assert m0 == m1, "workers must produce the identical continued model"
    r0 = json.loads((tmp_path / "out_0.json").read_text())
    r1 = json.loads((tmp_path / "out_1.json").read_text())
    assert r0["n_trees"] == 10         # 4 base + 6 continued
    # continued model must beat the base on each host's own rows
    assert min(r0["auc"], r1["auc"]) > base_auc - 0.005, (
        r0, r1, base_auc)


_LAUNCH_WORKER = textwrap.dedent("""
    import os, sys
    outdir, repo = sys.argv[1], sys.argv[2]
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    sys.path.insert(0, repo)
    import jax
    jax.config.update("jax_platforms", "cpu")
    from lightgbm_tpu.parallel.distributed import init_distributed
    init_distributed()          # picks up the launcher's env vars
    assert jax.process_count() == 2
    import numpy as np
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(1)
    X = rng.normal(size=(1200, 4))
    y = (X[:, 0] > 0).astype(float)
    bst = lgb.train({"objective": "binary", "num_leaves": 7,
                     "tree_learner": "data", "verbosity": -1,
                     "min_data_in_leaf": 5}, lgb.Dataset(X, label=y), 4)
    rank = jax.process_index()
    with open(os.path.join(outdir, f"launch_{rank}.txt"), "w") as f:
        f.write(bst.model_to_string())
""")


@pytest.mark.slow
def test_launcher_spawns_coordinated_workers(tmp_path):
    """python -m lightgbm_tpu.launch (the dask.py orchestration analog):
    workers coordinate via env vars and train the identical model."""
    from lightgbm_tpu.launch import launch
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = tmp_path / "lw.py"
    script.write_text(_LAUNCH_WORKER)
    env_clean = {k: v for k, v in os.environ.items()
                 if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    old = dict(os.environ)
    os.environ.clear()
    os.environ.update(env_clean)
    try:
        rc = launch([str(script), str(tmp_path), repo], num_processes=2)
    finally:
        os.environ.clear()
        os.environ.update(old)
    assert rc == 0
    m0 = (tmp_path / "launch_0.txt").read_text()
    m1 = (tmp_path / "launch_1.txt").read_text()
    assert m0 == m1


def test_launcher_refuses_local_workers_that_would_share_chips(
        monkeypatch):
    """A chip belongs to one process: N local workers on a TPU host
    are refused (one process drives all chips) unless they are pinned
    to the CPU backend. The launcher itself never imports jax."""
    from lightgbm_tpu import launch as L
    monkeypatch.setattr(L, "_local_tpu_chips",
                        lambda: ["/dev/accel0", "/dev/accel1"])
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(RuntimeError, match="one process"):
        L.launch(["t.py"], num_processes=2)
    with pytest.raises(RuntimeError, match="one process"):
        L.launch_hosts(["t.py"], [("localhost", 2)],
                       _popen=lambda *a, **k: None)
    L._refuse_shared_chips(1)                  # one worker owns them all
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    L._refuse_shared_chips(4)                  # CPU mesh workers


def test_launcher_fail_fast(tmp_path):
    from lightgbm_tpu.launch import launch
    bad = tmp_path / "bad.py"
    bad.write_text("import sys; sys.exit(3)\n")
    rc = launch([str(bad)], num_processes=2)
    assert rc == 3


def test_parse_hostfile(tmp_path):
    from lightgbm_tpu.launch import parse_hostfile
    hf = tmp_path / "hosts.txt"
    hf.write_text(
        "# cluster A\n"
        "10.0.0.1 slots=2\n"
        "\n"
        "10.0.0.2   # head node comment\n"
        "localhost slots=3\n")
    assert parse_hostfile(str(hf)) == [
        ("10.0.0.1", 2), ("10.0.0.2", 1), ("localhost", 3)]
    bad = tmp_path / "bad.txt"
    bad.write_text("10.0.0.1 cpus=4\n")
    with pytest.raises(ValueError, match="unrecognized token"):
        parse_hostfile(str(bad))
    empty = tmp_path / "empty.txt"
    empty.write_text("# nothing\n")
    with pytest.raises(ValueError, match="no hosts"):
        parse_hostfile(str(empty))


def test_launch_hosts_builds_ssh_and_local_commands(monkeypatch):
    """Remote ranks wrap in ssh with exported rank env; local ranks
    spawn directly; ranks number across hosts in hostfile order."""
    from lightgbm_tpu import launch as L
    spawned = []

    class FakeProc:
        def __init__(self, cmd, env=None):
            spawned.append((cmd, env))
        def poll(self):
            return 0
        def kill(self):
            pass
        def wait(self):
            return 0
        def send_signal(self, sig):
            pass

    rc = L.launch_hosts(
        ["train.py", "--foo"], [("10.0.0.1", 2), ("localhost", 1)],
        port=4001, ssh="ssh", python_exe="python3", _popen=FakeProc)
    assert rc == 0
    with pytest.raises(ValueError, match="routable"):
        L.launch_hosts(["t.py"], [("localhost", 1), ("10.0.0.2", 1)],
                       _popen=FakeProc)
    assert len(spawned) == 3
    # remote ranks 0,1 on 10.0.0.1 via ssh
    for r in (0, 1):
        cmd, env = spawned[r]
        assert cmd[0] == "ssh" and cmd[4] == "10.0.0.1"
        assert "-tt" in cmd and "BatchMode=yes" in cmd
        inner = cmd[5]
        assert f"LIGHTGBM_TPU_RANK={r}" in inner
        assert "LIGHTGBM_TPU_COORDINATOR=10.0.0.1:4001" in inner
        assert "LIGHTGBM_TPU_NUM_PROCESSES=3" in inner
        assert inner.endswith("python3 train.py --foo")
    # local rank 2 spawns directly with env vars
    cmd, env = spawned[2]
    assert cmd == ["python3", "train.py", "--foo"]
    assert env["LIGHTGBM_TPU_RANK"] == "2"
    assert env["LIGHTGBM_TPU_COORDINATOR"] == "10.0.0.1:4001"
    assert env["LIGHTGBM_TPU_NUM_PROCESSES"] == "3"


_VOTING_WORKER = textwrap.dedent("""
    import os, sys
    outdir, repo = sys.argv[1], sys.argv[2]
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    sys.path.insert(0, repo)
    import jax
    jax.config.update("jax_platforms", "cpu")
    from lightgbm_tpu.parallel.distributed import init_distributed
    init_distributed()
    assert jax.process_count() == 4
    import numpy as np
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(1)
    X = rng.normal(size=(800, 6))
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(float)
    bst = lgb.train({"objective": "binary", "num_leaves": 7,
                     "tree_learner": "voting", "top_k": 3,
                     "verbosity": -1, "min_data_in_leaf": 5},
                    lgb.Dataset(X, label=y), 3)
    rank = jax.process_index()
    with open(os.path.join(outdir, f"vote_{rank}.txt"), "w") as f:
        f.write(bst.model_to_string())
""")


@pytest.mark.slow
def test_four_process_voting_parallel(tmp_path):
    """PV-Tree voting across 4 REAL processes (1 device each): every
    rank must elect/merge identically and emit the same model."""
    from lightgbm_tpu.launch import launch
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = tmp_path / "vw.py"
    script.write_text(_VOTING_WORKER)
    env_clean = {k: v for k, v in os.environ.items()
                 if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    old = dict(os.environ)
    os.environ.clear()
    os.environ.update(env_clean)
    try:
        rc = launch([str(script), str(tmp_path), repo], num_processes=4)
    finally:
        os.environ.clear()
        os.environ.update(old)
    assert rc == 0
    models = [(tmp_path / f"vote_{r}.txt").read_text() for r in range(4)]
    assert all(m == models[0] for m in models[1:])
