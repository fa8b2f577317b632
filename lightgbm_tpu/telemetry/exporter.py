"""Opt-in live introspection during training.

A long TPU training run is a black box between eval points; this module
makes it a server. ``engine.train`` starts one when ``telemetry_port``
is set (param or ``LIGHTGBM_TPU_TELEMETRY_PORT``; port 0 picks a free
port), serving:

- ``GET /metrics``  — Prometheus text render of the run's registry
  (training counters + device gauges; serving mounts its families the
  same way on its own server).
- ``GET /events?n=`` — tail of the run-event log as JSONL.
- ``GET /healthz``  — run liveness: current iteration, trees, state.
- ``GET /trace?duration_ms=`` — on-demand ``jax.profiler`` capture of
  the next N ms. The response carries the parsed per-phase device/host
  summary (``xprof.parse_trace``) plus the capture dir for
  ``tensorboard --logdir`` / Perfetto / ``monitor --perf``. Captures
  land as numbered ``capture_NNNN`` dirs under one tracked root with
  keep-last-N retention (older captures pruned, nothing leaks), the
  session's instruction→phase map is saved alongside as
  ``phase_map.json``, and a failed ``stop_trace`` returns a 500 error
  body — never a 200 naming a dangling dir. One capture at a time.
- ``SIGUSR1`` — dump the metrics snapshot + phase totals through
  ``log.info`` (the kill -USR1 runbook for a run with no port open).

Stdlib-only, same ThreadingHTTPServer shape as ``serving/server.py``.
Scrapes read host-side state exclusively (counters, gauges, the event
log file) — a scrape can never add a device sync to the training loop.
"""

from __future__ import annotations

import json
import os
import shutil
import signal
import tempfile
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Callable, Dict, Optional
from urllib.parse import parse_qs, urlparse

from .core import MetricsRegistry
from .events import EventLog

__all__ = ["IntrospectionServer", "CaptureError", "install_sigusr1"]

_MAX_TRACE_MS = 60_000


class CaptureError(RuntimeError):
    """A profiler capture failed AFTER starting (stop_trace raised) —
    distinct from the 409 capture-already-running RuntimeError so the
    handler can answer 500 with the failure instead of a dangling
    log_dir."""


class IntrospectionServer:
    """Background HTTP server over one registry + event log."""

    def __init__(self, registry: MetricsRegistry,
                 event_log: Optional[EventLog] = None,
                 health_fn: Optional[Callable[[], dict]] = None,
                 host: str = "127.0.0.1", port: int = 0,
                 capture_root: Optional[str] = None,
                 phase_map_fn: Optional[
                     Callable[[], Dict[str, Dict[str, str]]]] = None,
                 keep_captures: int = 4,
                 step_work_fn: Optional[
                     Callable[[int], Optional[dict]]] = None):
        self.registry = registry
        self.event_log = event_log
        self.health_fn = health_fn
        self.host, self.port = host, int(port)
        # profiler captures nest under one tracked root as
        # capture_NNNN dirs with keep-last-N retention; the telemetry
        # session points this at <run dir>/traces so monitor --perf
        # finds them next to the event log
        self.capture_root = capture_root
        # returns the session's instruction→phase maps, saved next to
        # each capture as phase_map.json. MUST only hand back maps
        # already built at a training sync point — building one lowers
        # the fused jit, and doing that from this HTTP thread would
        # race a concurrent dispatch's trace-time attribute rebinding.
        self.phase_map_fn = phase_map_fn
        # the sidecar's step_work entry for a capture of N iterations
        # (the step's shape and the work of its trees); host state only
        self.step_work_fn = step_work_fn
        self.keep_captures = max(1, int(keep_captures))
        self._httpd: Optional[ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None
        self._trace_lock = threading.Lock()
        self._capture_seq = 0

    def start(self) -> int:
        """Bind + serve from a daemon thread; returns the bound port."""
        if self._httpd is not None:
            return self.port
        app = self

        class Handler(_Handler):
            server_app = app

        class _Server(ThreadingHTTPServer):
            daemon_threads = True
            request_queue_size = 32

        self._httpd = _Server((self.host, self.port), Handler)
        self.port = self._httpd.server_address[1]
        # tight poll: shutdown() blocks a serve_forever poll period, and
        # the default 0.5 s would bill every telemetry session close
        # (train return) half a second of wall clock
        self._thread = threading.Thread(
            target=lambda: self._serve(self._httpd),
            name="telemetry-http", daemon=True)
        self._thread.start()
        return self.port

    @staticmethod
    def _serve(httpd: ThreadingHTTPServer) -> None:
        try:
            httpd.serve_forever(poll_interval=0.05)
        except Exception:  # noqa: BLE001 — the server must die quietly
            pass

    def stop(self) -> None:
        httpd, self._httpd = self._httpd, None
        if httpd is not None:
            httpd.shutdown()
            httpd.server_close()
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None

    def _capture_dir(self) -> str:
        if self.capture_root is None:
            self.capture_root = tempfile.mkdtemp(
                prefix="lgbtpu_traces_")
        os.makedirs(self.capture_root, exist_ok=True)
        self._capture_seq += 1
        d = os.path.join(self.capture_root,
                         f"capture_{self._capture_seq:04d}")
        os.makedirs(d, exist_ok=True)
        return d

    def _prune_captures(self) -> None:
        try:
            caps = sorted(e for e in os.listdir(self.capture_root)
                          if e.startswith("capture_"))
        except OSError:
            return
        for stale in caps[:-self.keep_captures]:
            shutil.rmtree(os.path.join(self.capture_root, stale),
                          ignore_errors=True)

    def capture_trace(self, duration_ms: int) -> dict:
        """Synchronous jax.profiler capture of the next N ms, parsed
        into the per-phase device/host summary before answering."""
        import time

        import jax

        from . import xprof
        duration_ms = max(1, min(int(duration_ms), _MAX_TRACE_MS))
        if not self._trace_lock.acquire(blocking=False):
            raise RuntimeError("a trace capture is already running")
        try:
            log_dir = self._capture_dir()
            jax.profiler.start_trace(log_dir)
            try:
                time.sleep(duration_ms / 1e3)
            finally:
                try:
                    jax.profiler.stop_trace()
                except Exception as e:  # noqa: BLE001
                    # a 200 naming this dir would hand the caller a
                    # capture that was never serialized
                    shutil.rmtree(log_dir, ignore_errors=True)
                    raise CaptureError(
                        f"stop_trace failed: {type(e).__name__}: {e}"
                    ) from e
            self._prune_captures()
            resp = {"log_dir": log_dir, "duration_ms": duration_ms}
            try:
                maps = self.phase_map_fn() if self.phase_map_fn else {}
                if maps:
                    xprof.save_phase_map(log_dir, maps)
                prof = xprof.parse_trace(log_dir,
                                         phase_maps=maps or None)
                if maps and self.step_work_fn is not None:
                    # how many trees the capture holds is known only
                    # now: the sidecar is written again with their work
                    prof.step_work = self.step_work_fn(
                        prof.iterations()) or {}
                    xprof.save_phase_map(log_dir, maps, prof.step_work)
                resp.update(prof.summary_dict())
            except Exception as e:  # noqa: BLE001 — the capture is
                # still on disk and usable offline even if parsing it
                # inline failed
                resp["parse_error"] = f"{type(e).__name__}: {e}"
            return resp
        finally:
            self._trace_lock.release()


class _Handler(BaseHTTPRequestHandler):
    server_app: IntrospectionServer = None  # bound per-server subclass
    protocol_version = "HTTP/1.1"

    def log_message(self, fmt, *args):  # route through our logger
        from .. import log
        log.debug(f"telemetry: {self.address_string()} {fmt % args}")

    def _send(self, code: int, body: bytes, ctype: str):
        self.send_response(code)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _send_json(self, code: int, obj):
        self._send(code, json.dumps(obj).encode(), "application/json")

    def do_GET(self):  # noqa: N802 (http.server API)
        app = self.server_app
        parsed = urlparse(self.path)
        path = parsed.path.rstrip("/") or "/"
        try:
            if path == "/metrics":
                self._send(200, app.registry.render().encode(),
                           "text/plain; version=0.0.4")
            elif path == "/healthz":
                health = {"status": "ok"}
                if app.health_fn is not None:
                    health.update(app.health_fn() or {})
                self._send_json(200, health)
            elif path == "/events":
                if app.event_log is None:
                    self._send_json(404, {"error": "no event log active"})
                    return
                q = parse_qs(parsed.query)
                n = int((q.get("n") or ["50"])[0])
                body = "".join(json.dumps(r, sort_keys=True) + "\n"
                               for r in app.event_log.tail(n))
                self._send(200, body.encode(), "application/x-ndjson")
            elif path == "/trace":
                q = parse_qs(parsed.query)
                ms = int((q.get("duration_ms") or ["1000"])[0])
                self._send_json(200, app.capture_trace(ms))
            else:
                self._send_json(404, {"error": f"unknown path {path}"})
        except CaptureError as e:
            self._send_json(500, {"error": str(e)})
        except RuntimeError as e:
            self._send_json(409, {"error": str(e)})
        except Exception as e:  # noqa: BLE001 — a scrape must not kill
            self._send_json(500, {"error": f"{type(e).__name__}: {e}"})


def install_sigusr1(dump_fn: Callable[[], None]):
    """Install a SIGUSR1 dump handler; returns a restore() callable.

    Signals can only be installed from the main thread — elsewhere
    (e.g. a test driving train() from a worker thread) this is a no-op
    whose restore() does nothing, matching PreemptionGuard's posture.
    """
    if threading.current_thread() is not threading.main_thread() \
            or not hasattr(signal, "SIGUSR1") or os.name == "nt":
        return lambda: None

    def _handler(signum, frame):
        try:
            dump_fn()
        except Exception:
            pass  # a dump must never take down training

    prev = signal.signal(signal.SIGUSR1, _handler)

    def restore():
        try:
            signal.signal(signal.SIGUSR1, prev)
        except (ValueError, TypeError):
            pass

    return restore
