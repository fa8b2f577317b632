#!/usr/bin/env python3
"""Trace one training cell's shape on the chip and reduce it by stage.

    python3 scripts/stage_trace.py --workload higgs-train --seed 7 \
        --trees 1 --out chiprun_out/stage/higgs-train

Makes the cell's data as the benchmark does (its generator, its Dataset),
trains tree 0 (compile or cache load), builds the fused step's stage map
from the compiled module, traces ``--trees`` more trees under the
profiler, and prints what ``python -m lightgbm_tpu monitor --perf``
prints for the capture: device seconds by stage, the ten longest
instructions with stage and source scope, the longest idle gaps with the
program span over each, and beside a stage's seconds the count of its work over
the traced trees (``GBDT.stage_work``), its unit and what one unit costs.
Also checked, and printed as one JSON line
(``stage_trace:``): that the compiled step's text is the same inside and
outside a profiler session, the offset between each
``lgbtpu:gbdt.dispatch`` annotation in the xplane's host plane and the
span ring's record of the same span, the round log's live-row share and
the share of the touched stream positions that were live, how many
``gather`` instructions the stage map puts under ``hist_gather``
(``hist_gather_ops``: what a trip of the compacted stream's chunk loop
fetches by the chunk's index; 3 before the row's leaf rode ``gh``'s
table, 2 since), and the SHA-256 of the model text (tree 0 and the
traced trees; two commits that grow the same trees print the same
one). The capture (xplane and ``phase_map.json``, which also holds the
step's shape and that work), ``model.txt`` and the
compiled step's text
(``step.hlo.txt.gz``: which instruction a device op of the capture is,
its operands and their memory-space marks) stay under ``--out``.
"""

import argparse
import glob
import gzip
import hashlib
import json
import os
import shutil
import sys
from types import SimpleNamespace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "benchmarks"), ROOT]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--trees", type=int, default=1)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    from harness.manifest import Manifest
    man = Manifest(ROOT)
    cell = man.cell(args.workload)
    cfg = man.config(cell["config"])
    job = man.job(man.traffic(cell["traffic"])["job"])
    env = SimpleNamespace(manifest=man, config=cfg, seed=args.seed)
    params = dict(cfg["params"], verbosity=-1)

    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu import phases, profiler
    from lightgbm_tpu.telemetry import costmodel, xprof
    from lightgbm_tpu.telemetry.monitor import render_perf
    job._compile_cache(lgb, jax)
    ds, _bins_cm, _y, _ubs = job._make_dataset(env, lgb, params)
    bst = lgb.Booster(params, ds)
    bst.update(defer=True)
    gb = bst._gbdt
    jax.block_until_ready(gb.scores)

    def step_text():
        return costmodel.fused_compiled(bst, force=False).as_text()

    text = step_text()
    maps = {}
    sm = costmodel.instruction_phase_map(text)
    if sm.stages:
        maps[sm.module] = sm

    shutil.rmtree(args.out, ignore_errors=True)
    os.makedirs(args.out)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(args.out, profiler_options=opts)
    seq0 = profiler.recorder.seq
    for _ in range(args.trees):
        bst.update(defer=True)
    jax.block_until_ready(gb.scores)
    same_text = hashlib.sha256(step_text().encode()).hexdigest() == \
        hashlib.sha256(text.encode()).hexdigest()
    jax.profiler.stop_trace()
    bst._sync_trees()
    xprof.save_phase_map(args.out, maps,
                         xprof.step_work_of(gb, args.trees))
    prof = xprof.parse_trace(args.out)
    print(render_perf(args.out, prof=prof), flush=True)
    costs = prof.stage_costs()

    # the annotations against the ring, span by span
    plane = sorted(glob.glob(os.path.join(
        args.out, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    cap = xprof.load_xplane(plane)
    ring = [s for s in profiler.recorder.since(seq0)
            if s.name == "gbdt.dispatch"]
    ann = sorted((s for s in cap.host_spans if s.name == "gbdt.dispatch"),
                 key=lambda s: s.start)
    offsets = []
    if cap.epoch_ns is not None:
        for a, r in zip(ann, ring):
            offsets.append({
                "start_ns": cap.epoch_ns + a.start * 1e9 - r.start_ns,
                "end_ns": cap.epoch_ns + (a.start + a.dur) * 1e9 - r.end_ns})
    log = list(gb.round_log)[-args.trees:]
    rounds = sum(int((r.leaves > 0).sum()) for r in log)
    live = sum(int(r.rows.sum()) for r in log)
    # None on a commit whose round log has no stream_rows yet
    touched = sum(int(getattr(r, "stream_rows", r.rows * 0).sum())
                  for r in log)
    model_text = bst.model_to_string()
    with open(os.path.join(args.out, "model.txt"), "w") as f:
        f.write(model_text)
    print("stage_trace: " + json.dumps({
        "workload": args.workload, "trees": args.trees,
        "device": str(jax.devices()[0].device_kind),
        "module": sm.module, "map_instructions": len(sm.stages),
        "mixed_fusions": sm.mixed_fusions,
        "hist_gather_ops": sum(
            o.op.opcode == "gather" and o.stage == phases.HIST_GATHER
            for o in costmodel.staged_ops(text)),
        "step_text_same_under_profiler": same_text,
        "annotations": len(ann), "ring_dispatches": len(ring),
        "annotation_minus_ring": offsets[:4],
        "rounds_per_tree": rounds / max(len(log), 1),
        "live_row_share_pct": 100.0 * live / max(
            rounds * cfg["shape"]["rows"], 1),
        "stream_row_share_pct": 100.0 * live / touched if touched else None,
        "step_shape": gb.step_shape,
        "stage_count_unit_ns": {k: [c, u, s * 1e9]
                                for k, (c, u, s) in costs.items()},
        "model_sha256": hashlib.sha256(model_text.encode()).hexdigest(),
        "host_sync_count": gb.host_sync_count}), flush=True)
    with gzip.open(os.path.join(args.out, "step.hlo.txt.gz"), "wt") as g:
        g.write(text)
    with open(plane, "rb") as f, gzip.open(plane + ".gz", "wb") as g:
        shutil.copyfileobj(f, g)
    for p in glob.glob(os.path.join(os.path.dirname(plane), "*")):
        if not p.endswith(".xplane.pb.gz"):
            os.remove(p)
    return 0


if __name__ == "__main__":
    sys.exit(main())
