"""Device-side accounting with zero device readbacks.

Three gauge groups, all host-side:

- **HBM/memory watermarks** — ``device.memory_stats()`` where the
  backend provides it. On a TPU ``peak_bytes_in_use`` counts live
  buffers only; a running program's temporaries sit in the allocator's
  reserved pool (``peak_bytes_reserved``), which the program cannot run
  without, so the chip's high-water mark is the two together (11x the
  live buffers at Higgs, PERF.md section 3). The CPU backend reports nothing, so the
  fallback is a live-buffer census over ``jax.live_arrays()``
  (addressable shards summed per device). Both are host bookkeeping —
  neither touches device queues, so sampling at sync points or scrape
  time cannot break dispatch-ahead.
- **Compile counters** — the same ``jax.monitoring`` event stream the
  recompile guard counts (``analysis/recompile_guard.COMPILE_EVENT``
  fires once per actual backend compile; cache hits don't fire).
  Steady-state training must hold these flat; a climbing compile count
  mid-run is the TD201 shape-leak signature, now visible on a live
  dashboard instead of only in tests.
- **Collective traffic** — the trace-time cost model made a run-time
  number: ``parallel/comms.py`` audits the compiled tree program once
  (static per-tree bytes, ``hist_bytes_per_tree``) and the gauge
  multiplies by trees built. Exact by construction — the program's
  collectives are fixed at compile time — with no per-iteration work
  and no device readback. The audit compile itself is lazy (first
  scrape that asks) and cached.
"""

from __future__ import annotations

import threading
from typing import Callable, Dict, Optional

from .core import MetricsRegistry

__all__ = ["DeviceWatch", "CollectiveWatch", "device_memory_bytes"]


def device_memory_bytes() -> Dict[str, Dict[str, int]]:
    """{device_label: {"bytes_in_use": n, "peak_bytes_in_use": n,
    "peak_bytes_reserved": n}} via ``memory_stats()``, falling back to a live-buffer census (peak not
    tracked by the census itself — DeviceWatch accumulates it)."""
    import jax
    out: Dict[str, Dict[str, int]] = {}
    devices = jax.devices()
    census_needed = []
    for d in devices:
        label = f"{d.platform}:{d.id}"
        stats = None
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if stats and "bytes_in_use" in stats:
            out[label] = {
                "bytes_in_use": int(stats["bytes_in_use"]),
                "peak_bytes_in_use": int(stats.get("peak_bytes_in_use",
                                                   0)),
                "peak_bytes_reserved": int(stats.get(
                    "peak_bytes_reserved", 0))}
        else:
            census_needed.append((d, label))
    if census_needed:
        by_dev: Dict[object, int] = {}
        try:
            for arr in jax.live_arrays():
                try:
                    for shard in arr.addressable_shards:
                        nbytes = getattr(shard.data, "nbytes", 0)
                        by_dev[shard.device] = (by_dev.get(shard.device, 0)
                                                + int(nbytes))
                except Exception:
                    continue
        except Exception:
            pass
        for d, label in census_needed:
            out[label] = {"bytes_in_use": int(by_dev.get(d, 0)),
                          "peak_bytes_in_use": 0,
                          "peak_bytes_reserved": 0}
    return out


class DeviceWatch:
    """HBM gauges + compile counters on a registry.

    ``sample()`` refreshes the in-use numbers and accumulates the peak
    watermark; it runs at engine sync points and at scrape time, never
    on the dispatch path. ``start()``/``stop()`` bound the monitoring
    listener's lifetime to the telemetry session."""

    def __init__(self, registry: MetricsRegistry):
        self._lock = threading.Lock()
        self._peaks: Dict[str, int] = {}
        self._compiles = 0
        self._compile_s = 0.0
        self._cb = None
        self._in_use = registry.gauge(
            "device_hbm_bytes_in_use",
            "Per-device bytes in use (memory_stats or live-buffer "
            "census)", labels=("device",))
        self._peak = registry.gauge(
            "device_hbm_bytes_peak",
            "Per-device high-water mark: peak live buffers (runtime "
            "watermark, or max over samples) PLUS the peak reserved "
            "pool, where a running program's temporaries sit",
            labels=("device",))
        self._reserved = registry.gauge(
            "device_hbm_bytes_reserved_peak",
            "Per-device peak of the allocator's reserved pool alone "
            "(peak_bytes_reserved; 0 where the backend reports none)",
            labels=("device",))
        registry.gauge("xla_compiles_total",
                       "Backend compiles since telemetry start "
                       "(steady state must hold this flat)",
                       fn=lambda: self._compiles)
        registry.gauge("xla_compile_seconds_total",
                       "Seconds spent in backend compiles",
                       fn=lambda: self._compile_s)

    def _on_event(self, event, duration, **kw) -> None:
        from ..analysis.recompile_guard import COMPILE_EVENT
        if event == COMPILE_EVENT:
            with self._lock:
                self._compiles += 1
                self._compile_s += float(duration)

    def start(self) -> None:
        if self._cb is None:
            import jax
            self._cb = self._on_event
            jax.monitoring.register_event_duration_secs_listener(self._cb)

    def stop(self) -> None:
        if self._cb is not None:
            import jax
            jax.monitoring.unregister_event_duration_listener(self._cb)
            self._cb = None

    def sample(self) -> Dict[str, Dict[str, int]]:
        mem = device_memory_bytes()
        with self._lock:
            for label, stats in mem.items():
                peak = max(self._peaks.get(label, 0),
                           stats["peak_bytes_in_use"],
                           stats["bytes_in_use"])
                self._peaks[label] = peak
                self._in_use.labels(label).set(stats["bytes_in_use"])
                self._peak.labels(label).set(
                    peak + stats["peak_bytes_reserved"])
                self._reserved.labels(label).set(
                    stats["peak_bytes_reserved"])
        return mem

    @property
    def compiles(self) -> int:
        return self._compiles


class CollectiveWatch:
    """Collective-traffic gauges: static per-tree bytes (comms audit of
    the sharding plan's tree program) × trees built.

    The audit compiles one synthetic tree-build program the first time
    a scrape asks (cached thereafter; serial runs short-circuit to 0),
    so the training path never pays for it and no device readback ever
    happens — invocation counts come from the host-side model list."""

    def __init__(self, registry: MetricsRegistry,
                 trees_fn: Callable[[], int]):
        self._lock = threading.Lock()
        self._gb = None
        self._per_tree: Optional[int] = None
        self._wire_per_tree: Optional[int] = None
        self._trees_fn = trees_fn
        registry.gauge(
            "train_collective_hist_bytes_per_tree",
            "Per-chip histogram-merge bytes for one tree (static comms "
            "audit of the compiled program)",
            fn=self._bytes_per_tree)
        registry.gauge(
            "train_collective_hist_bytes_total",
            "Per-chip histogram-merge bytes so far (static per-tree "
            "bytes x trees built; exact, no device readback)",
            fn=lambda: self._bytes_per_tree() * self._trees_fn())

    def attach(self, gbdt) -> None:
        """Bind the booster whose plan/shape the audit should mirror."""
        with self._lock:
            if gbdt is not self._gb:
                self._gb = gbdt
                self._per_tree = None

    def _bytes_per_tree(self) -> int:
        with self._lock:
            if self._per_tree is not None:
                return self._per_tree
            gb = self._gb
            if gb is None or getattr(gb, "plan", None) is None:
                self._per_tree = 0
                return 0
            try:
                import numpy as np

                from ..parallel.comms import (audit_tree_program,
                                              hist_bytes_per_tree)
                cfg = gb.config
                num_leaves = int(cfg.num_leaves)
                leaf_batch = max(1, min(int(cfg.leaf_batch),
                                        num_leaves - 1))
                report = audit_tree_program(
                    gb.plan, F=int(np.asarray(gb.num_bins_pf).shape[0]),
                    B=int(gb.B), num_leaves=num_leaves,
                    leaf_batch=leaf_batch,
                    hist_dtype=str(cfg.hist_dtype))
                self._per_tree = int(hist_bytes_per_tree(
                    report, num_leaves, leaf_batch))
            except Exception:
                self._per_tree = 0  # audit failure must not kill scrape
            return self._per_tree
