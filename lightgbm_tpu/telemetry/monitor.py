"""``python -m lightgbm_tpu monitor <run_dir|events.jsonl>`` — render a
run-event log into a phase/throughput/faults report, or ``--check`` its
schema.

The offline half of the telemetry subsystem: the event log
(telemetry/events.py) is what a run leaves behind; this turns it back
into the operational picture — what the run was (header), how fast it
went (ms/tree trajectory, per-span seconds of the span record), and
what went wrong (preemptions,
nan-guard trips, rollbacks, routed warnings). ``--check`` validates
every record against the schema table (``events.EVENT_TYPES``) and the
ordering invariants (monotone seq, no duplicate iteration records,
consistent header fingerprints) — the same self-check the chaos
harness applies to spliced resume logs.
"""

from __future__ import annotations

import argparse
import glob
import os
from typing import Any, Dict, List, Optional

from .events import check_records, read_events

__all__ = ["monitor_main", "find_event_logs", "render_report",
           "find_captures", "render_perf"]


def find_event_logs(target: str) -> List[str]:
    """A file is used as-is; a directory is scanned for
    ``*.events.jsonl`` (the ``event_log=auto`` naming) and
    ``events.jsonl``."""
    if os.path.isfile(target):
        return [target]
    if os.path.isdir(target):
        hits = sorted(glob.glob(os.path.join(target, "*.events.jsonl")))
        plain = os.path.join(target, "events.jsonl")
        if os.path.isfile(plain):
            hits.append(plain)
        return hits
    return []


def _topo_str(t: Any) -> str:
    """Compact one-line form of a checkpoint topology descriptor."""
    if not isinstance(t, dict):
        return str(t)
    merge = t.get("dp_hist_merge") or ""
    return (f"{t.get('tree_learner', '?')}x{t.get('num_shards', '?')}"
            + (f"/{merge}" if merge else "")
            + f" ({t.get('num_devices', '?')} dev)")


def _fmt_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024 or unit == "GiB":
            return f"{n:.1f} {unit}"
        n /= 1024
    return f"{n:.1f} GiB"


def render_report(path: str, records: List[Dict[str, Any]]) -> str:
    out: List[str] = [f"== {path} ({len(records)} records) =="]
    headers = [r for r in records if r["event"] == "run_header"]
    iters = [r for r in records if r["event"] == "iteration"]
    if headers:
        h = headers[-1]
        ver = h.get("versions", {})
        out.append(
            f"run: {h.get('objective', '?')} driver={h.get('driver')} "
            f"mode={h.get('parallel_mode')}x{h.get('num_shards')} "
            f"class_batch={h.get('class_batch')} "
            f"eval_period={h.get('eval_period')}")
        out.append(
            f"fingerprint: {h.get('fingerprint')}  "
            f"(lightgbm_tpu {ver.get('lightgbm_tpu')}, "
            f"jax {ver.get('jax')})")
        if len(headers) > 1:
            out.append(f"segments: {len(headers)} "
                       "(resumed run, spliced log)")
    if iters:
        last = iters[-1]
        ms = [r.get("ms_per_tree", 0.0) for r in iters
              if r.get("ms_per_tree")]
        out.append(f"progress: {last.get('iter')} iterations over "
                   f"{len(iters)} eval points; ms/tree last="
                   f"{(ms[-1] if ms else 0):.2f} "
                   f"mean={(sum(ms) / len(ms) if ms else 0):.2f}")
        if last.get("metrics"):
            out.append("metrics @ last eval: " + "  ".join(
                f"{k}={v:.6g}" for k, v in
                sorted(last["metrics"].items())))
        # per-phase seconds: mean s_per_iter across eval points
        phases: Dict[str, List[float]] = {}
        for r in iters:
            for name, d in (r.get("phase_s") or {}).items():
                phases.setdefault(name, []).append(
                    float(d.get("s_per_iter", 0.0)))
        if phases:
            out.append("phase seconds/iter (mean over eval points):")
            for name in sorted(phases):
                vals = phases[name]
                out.append(f"  {name:<12} "
                           f"{sum(vals) / len(vals) * 1e3:9.2f} ms/iter")
    faults: List[str] = []
    for r in records:
        ev = r["event"]
        if ev == "preemption":
            faults.append(f"preemption (signal {r.get('signum')}) at "
                          f"iteration {r.get('iter')}")
        elif ev == "nan_guard":
            faults.append(f"nan_guard {r.get('action', '?')} at "
                          f"iteration {r.get('iter')}")
        elif ev == "checkpoint" and r.get("action") == "restore":
            faults.append(f"checkpoint restore to iteration "
                          f"{r.get('iter')}")
        elif ev == "checkpoint" and r.get("ok") is False:
            faults.append(f"checkpoint {r.get('action', 'write')} "
                          f"FAILED at iteration {r.get('iter')} "
                          "(run continued)")
        elif ev == "resume":
            faults.append(f"resumed at iteration {r.get('iter')} from "
                          f"{os.path.basename(str(r.get('path')))}")
        elif ev == "reshard":
            faults.append(
                f"resharded at iteration {r.get('iter')}: "
                f"{_topo_str(r.get('from'))} -> "
                f"{_topo_str(r.get('to'))}")
        elif ev == "degraded":
            faults.append(
                f"device loss at iteration {r.get('iter')}: "
                f"{r.get('action')} (attempt {r.get('attempt')})")
        elif ev == "log" and r.get("level") == "warning":
            faults.append(f"warning: {str(r.get('msg'))[:90]}")
    writes = sum(1 for r in records if r["event"] == "checkpoint"
                 and r.get("action") == "write")
    out.append(f"checkpoints: {writes} written")
    out.append("faults: " + (f"{len(faults)}" if faults else "none"))
    out.extend(f"  - {f}" for f in faults)
    ends = [r for r in records if r["event"] == "train_end"]
    if ends:
        e = ends[-1]
        out.append(f"ended: iteration {e.get('iter')}, "
                   f"{e.get('trees')} trees, "
                   f"wall {e.get('wall_s'):.1f}s")
    else:
        out.append("ended: NO train_end record (run killed or still "
                   "running)")
    return "\n".join(out)


def find_captures(target: str) -> List[str]:
    """Profiler capture dirs associated with a run: ``<run_dir>/traces/
    capture_NNNN`` (where the telemetry server lands them), or
    ``target`` itself when it directly holds ``capture_*`` dirs or is a
    single capture dir."""
    if not os.path.isdir(target):
        return []
    for root in (os.path.join(target, "traces"), target):
        caps = sorted(glob.glob(os.path.join(root, "capture_*")))
        caps = [c for c in caps if os.path.isdir(c)]
        if caps:
            return caps
    # a capture dir itself (holds plugins/profile/... capture files)
    from .xprof import find_trace_files
    return [target] if find_trace_files(target) else []


def render_perf(capture: str,
                records: Optional[List[Dict[str, Any]]] = None,
                prof=None) -> str:
    """``monitor --perf``: one capture reduced by ``xprof.parse_trace``
    with the saved ``phase_map.json`` — device seconds by stage (a tree,
    where the capture holds step markers or dispatch spans), the ten
    longest instructions with stage and source scope, the longest idle
    gaps with the program span that covers each — crossed against the
    event log's measured ms/tree when one is available.

    The comparison target is the log's UNPROFILED steady-state ms/tree
    — on CPU the per-event tracing tax inflates the profiled wall
    clock, so the capture's own step span is not an honest baseline."""
    from . import xprof
    out: List[str] = [f"-- capture {capture} --"]
    try:
        if prof is None:    # (a caller that parsed it already hands it in)
            prof = xprof.parse_trace(capture)
    except (FileNotFoundError, ValueError) as e:
        return "\n".join(out + [f"  unparseable: {e}"])
    out.append(prof.render())
    dev_iter = prof.device_s_per_iter()
    fused_ms = sum(v for k, v in dev_iter.items()) * 1e3
    ms = [r.get("ms_per_tree", 0.0) for r in (records or [])
          if r.get("event") == "iteration" and r.get("ms_per_tree")]
    if fused_ms > 0 and ms:
        mean_ms = sum(ms) / len(ms)
        out.append(
            f"  phase device sum {fused_ms:.2f} ms/iter vs event-log "
            f"ms/tree mean {mean_ms:.2f} "
            f"(ratio {fused_ms / mean_ms:.3f}; <1 means host-side "
            "time the device never saw, >1 means tracing overhead "
            "landed inside op windows)")
    elif fused_ms > 0:
        out.append(f"  phase device sum {fused_ms:.2f} ms/iter "
                   "(no event log to compare against)")
    return "\n".join(out)


def monitor_main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m lightgbm_tpu monitor",
        description="Render a telemetry event log into a "
                    "phase/throughput/faults report.")
    ap.add_argument("target", nargs="?", default=".",
                    help="run directory or events.jsonl file "
                         "(default: cwd)")
    ap.add_argument("--check", action="store_true",
                    help="events-schema self-check: validate every "
                         "record and the ordering invariants; rc=1 on "
                         "any problem")
    ap.add_argument("--perf", action="store_true",
                    help="reduce the run's profiler captures "
                         "(<run_dir>/traces/capture_*, or a profiler "
                         "log dir) to device seconds by stage, the "
                         "longest instructions and idle gaps, and "
                         "compare the stage sum against the event "
                         "log's measured ms/tree")
    ns = ap.parse_args(argv)
    paths = find_event_logs(ns.target)
    if ns.perf:
        captures = find_captures(ns.target if os.path.isdir(ns.target)
                                 else os.path.dirname(ns.target) or ".")
        if not captures and not paths:
            print(f"no captures or event logs under {ns.target!r} "
                  "(looked for traces/capture_* and *.events.jsonl)")
            return 1
        records: List[Dict[str, Any]] = []
        for path in paths:
            try:
                records.extend(read_events(path))
            except ValueError:
                pass  # --perf only borrows ms/tree; --check owns schema
        if not captures:
            print(f"no profiler captures under {ns.target!r} — "
                  "capture one via GET /trace?duration_ms=... or "
                  "profiler.trace()")
            return 1
        for cap in captures:
            print(render_perf(cap, records))
            print()
        return 0
    if not paths:
        print(f"no event logs found under {ns.target!r} "
              "(looked for *.events.jsonl / events.jsonl)")
        return 1
    rc = 0
    for path in paths:
        try:
            records = read_events(path)
        except ValueError as e:
            print(f"{path}: CORRUPT — {e}")
            rc = 1
            continue
        if ns.check:
            problems = check_records(records)
            if problems:
                rc = 1
                print(f"{path}: {len(problems)} problem(s)")
                for p in problems:
                    print(f"  - {p}")
            else:
                print(f"{path}: OK ({len(records)} records)")
        else:
            print(render_report(path, records))
            print()
    return rc
