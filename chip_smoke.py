#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, in ONE process, through the entry points a user
calls (``lgb.Dataset``, ``lgb.train``, ``Booster.update``/``predict``,
``serving.PredictionServer`` over HTTP), at the full width of the Higgs
configuration: 28 features, 255 leaves, 63 bins, default kernel selection.
Depth is cut (a few trees, not 500); the data is ``make_higgs_like`` from
a seed.

Phases (any failure raises; nothing is caught and carried past):
  a. report jax, the device, the compile cache in force, native helpers;
     exit 3 — with no result line — when the platform is not ``tpu``
  b. train at full width; the resolved histogram kernel must be ``pallas``
  c. parity: the resolved kernel against the XLA matmul formulation on the
     same device (bf16 / f32 / int8, with and without a live-row bound, at
     the smoke's own lattice and at a chunked 255-bin one), and the first
     tree of a kernel-trained booster against a ``hist_impl=matmul`` one
  d. the verify skill's default-params flow, with a save/load round trip
  e. serve the booster from (b) over HTTP: default walker and
     ``compiled_predict`` replicas, 1 / 16 / 4096 / 8192-row requests
  f. (>1 device) ``tree_learner=data``: the bin matrix is sharded over all
     devices, every device holds ~1/N of (b)'s peak, results agree

Times printed here are seconds of a smoke, not a benchmark.

The last line of stdout is ``{"ok": true, "device": {...}}`` with the device
as JAX reports it. ``--cpu-dry-run`` (never the default) skips (a)'s platform
check and shrinks every size so the script can be debugged where there is no
chip; it then says so in that line.
"""

import argparse
import collections
import contextlib
import io
import json
import os
import sys
import tempfile
import time
import urllib.request

import numpy as np

FULL_ROWS = 10_500_000      # the reference's Higgs row count
MIN_CHIP_ROWS = 2_097_152


class SmokeFailure(AssertionError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def say(msg):
    print(msg, flush=True)


@contextlib.contextmanager
def phase(name):
    say(f"\n== {name}")
    t0 = time.time()
    yield
    say(f"== {name}: ok ({time.time() - t0:.1f} s)")


def make_higgs_like(n_rows: int, n_feat: int = 28, seed: int = 7):
    """Synthetic stand-in with Higgs-like shape: dense floats, a nonlinear
    decision surface, balanced classes."""
    rng = np.random.RandomState(seed)
    X = rng.normal(size=(n_rows, n_feat)).astype(np.float32)
    w = rng.normal(size=n_feat) / np.sqrt(n_feat)
    logit = (X @ w + 0.7 * X[:, 0] * X[:, 1]
             - 0.4 * X[:, 2] ** 2 + 0.3 * np.abs(X[:, 3]))
    y = (logit + rng.logistic(size=n_rows) * 0.5 > 0).astype(np.float32)
    return X, y


def cache_entries(path):
    if not path or not os.path.isdir(path):
        return 0
    return sum(len(files) for _, _, files in os.walk(path))


def peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def grid(X):
    """Snap features to multiples of 1/8: an f32 device threshold and an
    f64 host threshold can then never straddle a row, so every walker must
    agree exactly (tests/test_compiled_predict.py's construction)."""
    return np.round(np.asarray(X, np.float64) * 8.0) / 8.0


def held_out_auc(bst):
    (_, metric, value, _), = bst.eval_valid()
    check(metric == "auc", f"expected auc, got {metric}")
    return float(value)


def first_tree(bst):
    """Tree 0 through the public model dump: its (feature, threshold)
    splits — root first, the rest sorted — and its sorted leaf values."""
    df = bst.trees_to_dataframe()
    df = df[df.tree_index == 0]
    splits = df[df.split_feature.notna()]
    leaves = df[df.split_feature.isna()]
    pairs = list(zip(splits.split_feature, splits.threshold))
    return ([pairs[0]] + sorted(pairs[1:]),
            np.sort(leaves.value.to_numpy(np.float64)))


def http_predict(base, X):
    buf = io.BytesIO()
    np.save(buf, np.ascontiguousarray(X, np.float64))
    req = urllib.request.Request(
        base + "/predict", data=buf.getvalue(),
        headers={"Content-Type": "application/x-npy"})
    with urllib.request.urlopen(req, timeout=300) as resp:
        return np.load(io.BytesIO(resp.read()))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=FULL_ROWS,
                    help="training rows for (b)/(f); on a chip never "
                         f"fewer than {MIN_CHIP_ROWS}")
    ap.add_argument("--cpu-dry-run", action="store_true",
                    help="debugging only: skip the platform check and "
                         "run every phase at a tiny size")
    args = ap.parse_args(argv)
    dry = args.cpu_dry_run
    t_start = time.time()

    # ------------------------------------------------------------- (a)
    import jax
    import jaxlib
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    say(f"jax {jax.__version__} jaxlib {jaxlib.__version__} "
        f"python {sys.version.split()[0]}")
    say(f"device: {device}")
    if device["platform"] != "tpu" and not dry:
        print("chip_smoke: no TPU (platform "
              f"{device['platform']!r}); nothing was run", file=sys.stderr)
        return 3

    import jax.numpy as jnp

    import lightgbm_tpu as lgb
    from lightgbm_tpu import native
    from lightgbm_tpu.ops.histogram import build_histograms
    from lightgbm_tpu.serving import PredictionServer

    cache_dir = lgb.enable_compilation_cache()
    entries_before = cache_entries(cache_dir)
    say(f"compile cache: {cache_dir} "
        f"(JAX_COMPILATION_CACHE_DIR "
        f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'}"
        f"), {entries_before} entries before")
    say("native helpers: " + ", ".join(
        f"{name}={'loaded' if fn() is not None else 'UNAVAILABLE'}"
        for name, fn in (("parser", native.native_lib),
                         ("hist_ffi", native.hist_lib),
                         ("capi", native.capi_lib))))

    if dry:
        rows, n_valid, leaves = min(args.rows, 1 << 15), 1 << 12, 31
        warm, timed = 2, 5
        kern_rows, sample_rows, req_rows = 1 << 12, 1 << 13, (1, 16, 256)
    else:
        rows, n_valid, leaves = args.rows, 1 << 17, 255
        check(rows >= MIN_CHIP_ROWS,
              f"--rows {rows} < {MIN_CHIP_ROWS}: too small to mean anything")
        warm, timed = 3, 6
        kern_rows, sample_rows = 1 << 16, 1 << 17
        # 8192 rows x 9 trees reaches Booster.predict's device walk
        # (rows * trees >= 2^16); the smaller ones take its host walk
        req_rows = (1, 16, 4096, 8192)
    n_feat, max_bin = 28, 63
    facts = {"rows": rows, "full_rows": rows == FULL_ROWS,
             "num_leaves": leaves, "max_bin": max_bin,
             "cache_dir": cache_dir, "cache_entries_before": entries_before}

    # ------------------------------------------------------------- (b)
    with phase(f"b. train {rows} x {n_feat}, {leaves} leaves, "
               f"{max_bin} bins, tree_learner=serial"):
        t0 = time.time()
        X_all, y_all = make_higgs_like(rows + n_valid)
        X, y = X_all[:rows], y_all[:rows]
        Xv, yv = X_all[rows:], y_all[rows:]
        del X_all, y_all
        t_gen = time.time() - t0
        t0 = time.time()
        ds = lgb.Dataset(X, label=y,
                         params={"max_bin": max_bin}).construct()
        dsv = lgb.Dataset(Xv, label=yv, reference=ds).construct()
        t_bin = time.time() - t0
        say(f"rows={rows} ({'the full' if rows == FULL_ROWS else 'NOT the'}"
            f" {FULL_ROWS}); generate {t_gen:.1f} s, bin {t_bin:.1f} s")
        params = dict(objective="binary", metric="auc", num_leaves=leaves,
                      max_bin=max_bin, tree_learner="serial", verbosity=-1)
        t0 = time.time()
        bst = lgb.train(params, ds, num_boost_round=warm,
                        valid_sets=[dsv], valid_names=["held-out"])
        gb = bst._gbdt
        jax.block_until_ready(gb.scores)
        t_first = time.time() - t0
        auc_warm = held_out_auc(bst)
        t0 = time.time()
        for _ in range(timed):
            bst.update()
        jax.block_until_ready(gb.scores)
        t_timed = time.time() - t0
        auc_end = held_out_auc(bst)
        resolved = {
            "hist_impl": gb.config.hist_impl,
            "hist_impl_reason": gb.hist_impl_reason,
            "fused_reason": gb.fused_reason,
            "class_batch_reason": gb.class_batch_reason}
        serial_peak = peak_bytes(devs[0])
        say(f"compile + {warm} warm-up iterations: {t_first:.1f} s; "
            f"{timed} more: {t_timed:.1f} s (seconds, not a benchmark)")
        say(f"held-out AUC {auc_warm:.5f} -> {auc_end:.5f}")
        say(f"resolved: {resolved}")
        say(f"peak HBM device 0: {serial_peak} bytes")
        check(bst.num_trees() == warm + timed,
              f"{bst.num_trees()} trees, expected {warm + timed}")
        check(np.isfinite(auc_end) and auc_end > auc_warm > 0.5,
              f"held-out AUC did not rise: {auc_warm} -> {auc_end}")
        n_leaves = [t.num_leaves for t in bst._all_trees()]
        check(min(n_leaves) > 1, f"stump in the ensemble: {n_leaves}")
        check(dry or resolved["hist_impl"] == "pallas",
              f"hist_impl resolved to {resolved['hist_impl']!r}, not the "
              f"Pallas kernel: {resolved['hist_impl_reason']!r}")
        facts.update(resolved, compile_warmup_s=round(t_first, 1),
                     timed_iters=timed, timed_s=round(t_timed, 1),
                     auc_warm=round(auc_warm, 5), auc_end=round(auc_end, 5),
                     peak_hbm_bytes=serial_peak, bin_s=round(t_bin, 1))
    impl = resolved["hist_impl"]

    # ------------------------------------------------------------- (c)
    with phase(f"c. parity: {impl} vs matmul on {devs[0]}"):
        W = int(gb.config.leaf_batch)
        rng = np.random.RandomState(11)
        live = kern_rows * 3 // 5

        def kernel_case(B, L, variant):
            bins = jnp.asarray(rng.randint(0, B, size=(kern_rows, n_feat)),
                               jnp.uint8)
            leaf = rng.randint(0, L + 1, size=kern_rows).astype(np.int32)
            kw = dict(num_bins=B, hist_dtype="float32"
                      if variant == "f32" else "bfloat16")
            if variant.endswith("_rows"):
                leaf[live:] = -1        # caller contract past num_rows
                kw["num_rows"] = jnp.asarray(live, jnp.int32)
            if variant.startswith("int8"):
                gh = rng.randint(-127, 128, size=(kern_rows, 3)) \
                    .astype(np.int8)
            else:
                gh = np.stack([rng.normal(size=kern_rows),
                               rng.uniform(0.1, 1.0, size=kern_rows),
                               np.ones(kern_rows)], 1).astype(np.float32)
            ops = (bins, jnp.asarray(gh), jnp.asarray(leaf),
                   jnp.arange(L, dtype=jnp.int32))
            t0 = time.time()
            got = np.asarray(build_histograms(*ops, impl=impl, **kw))
            t_got = time.time() - t0
            ref = np.asarray(build_histograms(*ops, impl="matmul", **kw))
            t_ref = time.time() - t0 - t_got
            check(got.shape == ref.shape == (L, n_feat, B, 3)
                  and got.dtype == ref.dtype, "histogram shape/dtype")
            if variant.startswith("int8"):
                err, ok = int(np.abs(got - ref).max()), \
                    np.array_equal(got, ref)
            else:
                # same addends, f32 accumulation in another order
                err = float(np.abs(got - ref).max() / np.abs(ref).max())
                ok = np.isfinite(got).all() and err < 1e-5
            say(f"  F={n_feat} B={B} L={L} {variant}: "
                f"{'max |diff|' if variant.startswith('int8') else 'rel'}"
                f" {err} (compile+run {impl} {t_got:.1f} s, "
                f"matmul {t_ref:.1f} s)")
            check(ok, f"{impl} != matmul at B={B} L={L} {variant}: {err}")

        # the smoke's own lattice: one child per split (W slots), both
        # children (2W), the root (1); then a chunked plan (fc < F)
        for L in (W, 2 * W, 1):
            kernel_case(max_bin, L, "bf16")
        for variant in ("f32", "int8", "bf16_rows", "int8_rows"):
            kernel_case(max_bin, W, variant)
        for variant in ("bf16", "int8", "bf16_rows"):
            kernel_case(255, W, variant)

        # first tree of a kernel-trained booster vs a matmul-trained one
        # on a seeded row sample (one Dataset: identical bin boundaries)
        ds_s = lgb.Dataset(X[:sample_rows], label=y[:sample_rows],
                           params={"max_bin": max_bin}).construct()
        splits_k, leaves_k = first_tree(lgb.train(params, ds_s, 1))
        splits_m, leaves_m = first_tree(
            lgb.train(dict(params, hist_impl="matmul"), ds_s, 1))
        shared = sum((collections.Counter(splits_k)
                      & collections.Counter(splits_m)).values())
        same = shared / max(len(splits_k), len(splits_m))
        say(f"  first tree on {sample_rows} rows: {len(splits_k)} vs "
            f"{len(splits_m)} splits, {same:.1%} identical "
            f"(feature, threshold), exact={splits_k == splits_m}")
        check(len(splits_k) > 1, "kernel-trained first tree is a stump")
        # a near-tie can legitimately resolve differently under another
        # f32 summation order; a wrong histogram moves most splits
        check(same >= 0.9, f"only {same:.1%} of first-tree splits agree")
        if splits_k == splits_m:
            np.testing.assert_allclose(leaves_k, leaves_m, rtol=1e-3,
                                       atol=1e-6)
        facts["first_tree_split_agreement"] = round(same, 4)

    # ------------------------------------------------------------- (d)
    with phase("d. default-params flow (verify skill): 20k x 12, 31 leaves"):
        np.random.seed(0)
        n, f = 20000, 12
        Xd = np.random.normal(size=(n, f))
        yd = (Xd[:, 0] * 1.5 - Xd[:, 1]**2 * 0.7 + np.sin(Xd[:, 2])
              + np.random.normal(scale=0.5, size=n) > 0).astype(float)
        train = lgb.Dataset(Xd[:16000], label=yd[:16000])
        valid = lgb.Dataset(Xd[16000:], label=yd[16000:], reference=train)
        bst_d = lgb.train({"objective": "binary", "num_leaves": 31,
                           "metric": ["auc"]}, train, 60,
                          valid_sets=[valid], valid_names=["test"],
                          callbacks=[lgb.log_evaluation(20)])
        pred = bst_d.predict(Xd[16000:])
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as td:
            path = os.path.join(td, "model.txt")
            bst_d.save_model(path)
            bst_2 = lgb.Booster(model_file=path)
            diff = float(np.abs(bst_2.predict(Xd[16000:]) - pred).max())
        auc_d = held_out_auc(bst_d)
        say(f"hist_impl={bst_d._gbdt.config.hist_impl} "
            f"(max_bin default, B={bst_d._gbdt.B}); valid AUC {auc_d:.4f}; "
            f"save/load round-trip diff {diff}")
        check(dry or bst_d._gbdt.config.hist_impl == "pallas",
              "default-params flow left the Pallas kernel: "
              f"{bst_d._gbdt.hist_impl_reason!r}")
        check(auc_d > 0.95, f"valid AUC {auc_d} <= 0.95")
        check(diff == 0.0, f"save/load round-trip diff {diff}")

    # ------------------------------------------------------------- (e)
    with phase("e. serve the booster from (b) over HTTP"):
        queries = [grid(Xv[i:i + n]) for i, n in enumerate(req_rows)]
        want = [bst.predict(q) for q in queries]
        for label, kw in (
                ("default walker", {}),
                (f"compiled_predict x {len(devs)} replica(s)",
                 dict(compiled_predict=True, replicas=len(devs)))):
            srv = PredictionServer(port=0, max_batch_rows=max(req_rows),
                                   **kw)
            try:
                mv = srv.registry.register("higgs", bst)
                base = f"http://127.0.0.1:{srv.start()}"
                if kw:
                    check(mv.compiled is not None,
                          f"not tensorized: {mv.compiled_fallback}")
                    on = [r.device for r in mv.replicas.replicas]
                    check(len(set(on)) == len(devs),
                          f"replicas share devices: {on}")
                for q, w in zip(queries, want):
                    got = http_predict(base, q)
                    err = float(np.abs(got - w).max())
                    say(f"  {label}: {len(q)} rows, max |diff| vs "
                        f"Booster.predict {err:.2e}")
                    check(got.shape == w.shape and np.isfinite(got).all()
                          and err <= 1e-6,
                          f"{label}: {len(q)}-row answer off by {err}")
            finally:
                srv.stop()
                srv.registry.close()

    # ------------------------------------------------------------- (f)
    if len(devs) > 1:
        n_dev = len(devs)
        with phase(f"f. tree_learner=data over {n_dev} devices"):
            before = [peak_bytes(d) for d in devs]
            t0 = time.time()
            dp = lgb.train(dict(params, tree_learner="data"), ds,
                           num_boost_round=warm + timed,
                           valid_sets=[dsv], valid_names=["held-out"])
            gd = dp._gbdt
            jax.block_until_ready(gd.scores)
            say(f"compile + {warm + timed} iterations: "
                f"{time.time() - t0:.1f} s (seconds, not a benchmark); "
                f"plan {type(gd.plan).__name__} "
                f"hist_merge={gd.plan.hist_merge} "
                f"hist_impl={gd.config.hist_impl}")
            bins = gd.train_dd.bins
            shard_rows = sorted({s.data.shape[0]
                                 for s in bins.addressable_shards})
            on = {s.device for s in bins.addressable_shards}
            say(f"bin matrix {bins.shape} sharding {bins.sharding}: "
                f"{len(on)} devices x {shard_rows} rows")
            check(on == set(devs), f"bin matrix on {len(on)} of {n_dev}")
            check(shard_rows == [bins.shape[0] // n_dev],
                  f"uneven row shards: {shard_rows}")
            check(dry or gd.config.hist_impl == "pallas",
                  "data-parallel run left the Pallas kernel")
            peaks = [peak_bytes(d) for d in devs]
            say(f"peak HBM per device: {peaks} (device 0 carries (b)'s "
                f"{serial_peak}; before this phase: {before})")
            if serial_peak:      # the CPU backend reports no stats
                # devices 1.. only ever ran this phase: each holds at
                # least its shard of the matrix and nowhere near all of
                # what the serial run kept on device 0
                shard_bytes = bins.nbytes // n_dev
                for d, p in zip(devs[1:], peaks[1:]):
                    say(f"  {d}: peak is {p / serial_peak:.2f} of the "
                        f"serial run's (1/{n_dev} = {1 / n_dev:.2f})")
                    check(shard_bytes <= p <= 2.0 * serial_peak / n_dev,
                          f"{d}: peak {p} vs serial {serial_peak}: not "
                          f"~1/{n_dev}")
            auc_dp = held_out_auc(dp)
            p_serial, p_dp = bst.predict(Xv), dp.predict(Xv)
            delta = np.abs(p_serial - p_dp)
            strict = bool(np.allclose(p_dp, p_serial, rtol=1e-5,
                                      atol=1e-6))
            say(f"held-out AUC serial {auc_end:.5f} data {auc_dp:.5f}; "
                f"|dp - serial| median {np.median(delta):.2e} "
                f"p99 {np.quantile(delta, 0.99):.2e} max {delta.max():.2e}"
                f"; allclose(rtol=1e-5, atol=1e-6)={strict}")
            # float histograms summed in another order move near-tie
            # splits, so the float run is held to AUC and bulk agreement
            check(abs(auc_dp - auc_end) < 2e-3, "data-parallel AUC drifted")
            check(np.quantile(delta, 0.99) < 2e-2,
                  "data-parallel predictions drifted")
            check(first_tree(dp)[0][0] == first_tree(bst)[0][0],
                  "first trees split their roots differently")
            # ... and the exact (int32-histogram) pair to the CPU-mesh
            # tests' tolerance: any collective or sharding error shows
            q_rows = min(rows, MIN_CHIP_ROWS)
            ds_q = lgb.Dataset(X[:q_rows], label=y[:q_rows],
                               params={"max_bin": max_bin}).construct()
            qp = dict(params, use_quantized_grad=True,
                      stochastic_rounding=False)
            q_serial = lgb.train(qp, ds_q, 3).predict(Xv)
            q_dp = lgb.train(dict(qp, tree_learner="data"), ds_q,
                             3).predict(Xv)
            say(f"quantized pair on {q_rows} rows: max |dp - serial| "
                f"{np.abs(q_dp - q_serial).max():.2e}")
            np.testing.assert_allclose(q_dp, q_serial, rtol=1e-5,
                                       atol=1e-6)
            facts.update(dp_peak_hbm_bytes=peaks, dp_auc=round(auc_dp, 5),
                         dp_strict_allclose=strict)

    entries_after = cache_entries(cache_dir)
    facts.update(cache_entries_after=entries_after,
                 total_s=round(time.time() - t_start, 1))
    say(f"\ncompile cache: {entries_before} entries before, "
        f"{entries_after} after "
        f"({'warm' if entries_before else 'cold'} run)")
    say("facts: " + json.dumps(facts))
    result = {"ok": True, "device": device}
    if dry:
        result["cpu_dry_run"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
