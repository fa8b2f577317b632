#!/usr/bin/env python3
"""``correct`` of the four-chip cell over several seeds in ONE process:

    chiprun --chips 4 -- python3 scripts/dp_check_seeds.py \
        --seeds 2147489008,2147566002 --out chiprun_out/seeds.jsonl

For each seed: the cell's data drawn and binned as the job draws it
(``benchmarks/jobs/train-dp.py::_make_dataset``), tree 0 through
``Booster.update(defer=True)`` under the configuration's parameters, and
the job's own comparison (``gbdt_sharded_reference.check_first_tree``, the
counts on worker processes forked before JAX starts). No window: a seed
costs its data (~17 s, drawn while the seed before it compiles), its
compile (~20 s, no seed shares it) and tree 0, a third of a run of the
cell, so a change to the builder or to the reference can be held to
``tree_replay`` and ``every_chip_same_tree`` at many seeds for the chip
time of a few runs. One line of JSON a seed (to ``--out`` too): the
verdict, every compared number beside its limit, the float8 control's
reading, seconds by step. Exit code 1 if a seed read not ok.

``--deadline-s``: no seed's data is drawn later than this many seconds
after the start, so a call's time limit is not met half way through one.
``--cpu``: four virtual CPU devices and ``--rows`` rows, a rehearsal of
the script itself."""

import argparse
import gc
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from types import SimpleNamespace

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "benchmarks"), ROOT]
CELL = "criteo-dp-train"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--out", default="")
    ap.add_argument("--deadline-s", type=float, default=float("inf"))
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--rows", type=int, default=0)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4").strip()

    import numpy as np
    from harness import device, workers
    from harness.manifest import Manifest
    from reference import gbdt_sharded_reference as sref
    import lightgbm_tpu as lgb

    man = Manifest(ROOT)
    cell = man.cell(CELL)
    cfg = man.config(cell["config"])
    if args.rows:
        cfg["shape"]["rows"] = args.rows
        cfg["bin_sample_rows"] = min(cfg["bin_sample_rows"], args.rows)
    job = man.job(man.traffic(cell["traffic"])["job"])
    job.refuse_unless_supported()
    rows, cols = cfg["shape"]["rows"], cfg["shape"]["cols"]
    params = dict(cfg["params"], verbosity=-1)
    n_workers = job._threads()
    # two sets of the reference's arrays: a seed's data is drawn while the
    # seed before it compiles, and counted after that one's tree is read
    for k in (0, 1):
        workers.shared_empty(f"bins_cm{k}", (cols, rows), np.uint8)
        workers.shared_empty(f"clicked{k}", (rows,), np.bool_)
    pool = workers.pool(n_workers)

    import jax
    info = (device.device_info() if args.cpu
            else device.require_tpu(cell["chips"]))
    from lightgbm_tpu.parallel import comms
    from lightgbm_tpu.telemetry import costmodel
    job._compile_cache(lgb, jax)
    print(json.dumps({"device": info, "rows": rows, "workers": n_workers,
                      "start_s": time.perf_counter() - T0}), flush=True)

    def draw(i):
        k = i % 2
        t = time.perf_counter()
        workers.SHARED["bins_cm"] = workers.SHARED[f"bins_cm{k}"]
        workers.SHARED["clicked"] = workers.SHARED[f"clicked{k}"]
        env = SimpleNamespace(config=cfg, manifest=man, seed=seeds[i])
        return job._make_dataset(env, lgb, params), time.perf_counter() - t

    bad = 0
    out = open(args.out, "a") if args.out else None
    with ThreadPoolExecutor(1) as ahead:
        nxt = ahead.submit(draw, 0)
        for i, seed in enumerate(seeds):
            if nxt is None:
                print(json.dumps({"seed": seed, "skipped": "deadline"}),
                      flush=True)
                continue
            (ds, bins_cm, y, ubs), data_s = nxt.result()
            late = time.perf_counter() - T0 > args.deadline_s
            nxt = (ahead.submit(draw, i + 1)
                   if i + 1 < len(seeds) and not late else None)
            t = time.perf_counter()
            bst = lgb.Booster(params, ds)
            bst.update(defer=True)
            gb = bst._gbdt
            jax.block_until_ready(gb.scores)
            tree_s = time.perf_counter() - t
            line = {"seed": seed, "data_s": data_s, "first_dispatch_s": tree_s}
            if i == 0:      # what the eager counters cost a start
                compiled = costmodel.fused_compiled(bst, force=False)
                t = time.perf_counter()
                text = compiled.as_text()
                line["step_text_s"] = time.perf_counter() - t
                line["step_text_bytes"] = len(text)
                t = time.perf_counter()
                comms.plan_counters(text, 4, 1)
                line["plan_counters_parse_s"] = time.perf_counter() - t
                del compiled, text
            place = job._placement(gb, rows)
            copies = job._copies_agree(jax, list(gb._pending))
            bst._sync_trees()
            t = time.perf_counter()
            k = i % 2
            rep = sref.check_first_tree(
                bst.model_to_string(), ubs, bins_cm, y, params,
                shard_rows=max(place["shard_rows"]),
                addend_dtype=str(gb.config.hist_dtype), parts=n_workers,
                run=lambda groups: workers.starmap(
                    pool, workers.call_on_shared,
                    [(sref.spans_counts, (f"bins_cm{k}", f"clicked{k}")) + g
                     for g in groups]))
            line.update(
                reference_s=time.perf_counter() - t,
                ok=bool(rep["ok"] and copies["ok"]),
                tree_replay=rep["ok"], every_chip_same_tree=copies["ok"],
                roundings_tried=rep["roundings_tried"],
                addends={a: [v["boundary_distance"], rep["addends_used"][a]]
                         for a, v in rep["addends"].items()},
                splits=[[s["gain_short_by"], s["limit"], s["ok"]]
                        for s in rep["splits"]],
                leaves=rep["leaves"], control=rep["control"],
                memory_peak_bytes=max(
                    (m["peak_bytes"] for m in device.memory_by_device()),
                    default=None),
                elapsed_s=time.perf_counter() - T0)
            bad += not line["ok"]
            text = json.dumps(line)
            print(text, flush=True)
            if out:
                out.write(text + "\n")
                out.flush()
            del bst, gb, ds, bins_cm, y, rep, copies
            gc.collect()
    pool.terminate()
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
