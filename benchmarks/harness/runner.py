"""One run of one cell: find the cell's files by name, hand them to its
job kind, read the per-layer metrics, and build the line the contract asks
for. ``run.py`` is the command around this; the tests and a rehearsal call
it directly with another root and ``require_tpu=False``."""

from __future__ import annotations

import json
import time
from types import SimpleNamespace
from typing import Callable, Optional

from . import device
from .manifest import Manifest
from .spans import CompileCounter, Spans


def print_note(label: str, obj) -> None:
    print(f"{label}: {json.dumps(obj)}", flush=True)


def run_cell(root: str, workload: str, seed: int, seconds: float, trace: bool,
             *, t_start: Optional[float] = None, require_tpu: bool = True,
             note: Callable = print_note) -> dict:
    """The result object of one run. ``root`` holds BENCHMARK.json, and a
    traced run puts its profile under ``root``'s ``.bench_cache``."""
    t_start = time.time() if t_start is None else t_start
    man = Manifest(root)
    cell = man.cell(workload)
    traffic = man.traffic(cell["traffic"])
    spans = Spans()
    counters = []

    def compile_counter():
        counters.append(CompileCounter())
        return counters[-1]

    env = SimpleNamespace(
        manifest=man, cell=cell, config=man.config(cell["config"]),
        traffic=traffic, chips=cell["chips"], seed=int(seed),
        seconds=float(seconds), trace=bool(trace), t_start=t_start,
        require_tpu=require_tpu,
        spans=spans, note=note, compile_counter=compile_counter)
    try:
        rec = man.job(traffic["job"]).run(env)
    finally:
        for c in counters:
            c.close()
    note("spans_s", spans.seconds)
    note("counters", rec["counters"])
    note("memory", rec["memory"])
    fullest = device.fullest(rec["memory"])
    dev = dict(rec["device"], memory_peak_bytes=fullest["peak_bytes"])
    result = {"correct": bool(rec["correct"]), "attempted": rec["attempted"],
              "failed": rec["failed"], "metrics": {}, "device": dev}
    if not trace:
        for m in man.metrics_for(workload, "end_to_end"):
            result["metrics"][m["name"]] = {
                "value": rec["end_to_end"][m["name"]], "unit": m["unit"]}
        return result
    run = SimpleNamespace(spans=spans.seconds, counters=rec["counters"],
                          trace=rec["trace"], memory=fullest,
                          shape=rec["shape"], device=rec["device"], notes={})
    for m in man.metrics_for(workload, "per_layer"):
        value = man.metric_reader(m["name"]).read(run)
        if value is not None:
            result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    rep = rec["trace"]
    if rep is not None:
        dev.update(busy_s=rep.busy_s, window_s=rep.window_s)
        run.notes["op_class_share_pct"] = {
            c: rep.class_share(c) for c in sorted(rep.class_s)}
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in rep.device_ops],
            "idle_gaps": [[n, s] for n, s in rep.idle_gaps]}
    note("per_layer_notes", run.notes)
    return result
