"""Shared subprocess-probe harness.

Every tool that measures or audits jax programs out-of-process —
``scripts/lint_traces.py``, ``scripts/audit_collectives.py``,
``scripts/chaos_train.py`` — needs the same three things:

1. **env pinning**: the virtual-device count must be in ``XLA_FLAGS``
   and ``JAX_PLATFORMS=cpu`` set BEFORE jax initializes, so mesh-shaped
   probes run in a fresh subprocess (the parent process owns the real
   backend) or pin in-process before the first jax import;
2. **timeout discipline**: a wedged compile degrades to an error field,
   never hangs the caller;
3. **result contract**: the child prints one ``TAG=<json>`` line on
   stdout; everything else (jax chatter, warnings) is ignored.

Consumers load this file by path (``scripts/`` is not a package)::

    _probe = load_probe_module()   # see _load() in each consumer, or:
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "_probe", os.path.join(scripts_dir, "_probe.py"))

"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
from typing import Dict, Optional, Sequence, Tuple

__all__ = ["REPO_ROOT", "pin_virtual_mesh", "mesh_env", "run_probe",
           "run_code_probe"]

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _with_device_count(flags: str, n: int) -> str:
    if "xla_force_host_platform_device_count" in flags:
        return flags
    return (flags + f" --xla_force_host_platform_device_count={n}").strip()


def pin_virtual_mesh(n: int = 8) -> None:
    """In-process pinning: call before the first ``import jax``. Appends
    the virtual-device flag (unless one is already pinned) and forces
    the CPU backend."""
    os.environ["XLA_FLAGS"] = _with_device_count(
        os.environ.get("XLA_FLAGS", ""), n)
    os.environ["JAX_PLATFORMS"] = "cpu"


def mesh_env(n: int = 8, *, fused: Optional[bool] = None,
             extra: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    """Subprocess environment for an ``n``-virtual-device CPU-mesh
    probe: inherits the caller's env, pins the mesh + CPU backend, puts
    the repo root on ``PYTHONPATH`` (so ``import lightgbm_tpu`` works
    from any cwd), optionally pins the fused-train driver."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = _with_device_count(env.get("XLA_FLAGS", ""), n)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = (REPO_ROOT + os.pathsep
                         + env.get("PYTHONPATH", "")).rstrip(os.pathsep)
    if fused is not None:
        env["LIGHTGBM_TPU_FUSED_TRAIN"] = "1" if fused else "0"
    if extra:
        env.update(extra)
    return env


def run_probe(cmd: Sequence[str], tag: str, *,
              env: Optional[Dict[str, str]] = None,
              timeout: float = 900.0, cwd: str = REPO_ROOT,
              decode=json.loads) -> Tuple[Optional[object],
                                          Optional[str]]:
    """Run ``cmd``; scan stdout for the LAST ``tag=<payload>`` line and
    return ``(decode(payload), None)``, or ``(None, error)`` on
    timeout / crash / missing tag. The error string carries the tail of
    stderr — enough to diagnose, small enough to embed in a result
    dict."""
    try:
        r = subprocess.run(list(cmd), cwd=cwd, env=env,
                           capture_output=True, text=True,
                           timeout=timeout)
    except subprocess.TimeoutExpired:
        return None, "timeout"
    payload = None
    for ln in r.stdout.splitlines():
        if ln.startswith(tag + "="):
            payload = ln.split("=", 1)[1]
    if payload is None:
        err = (r.stderr or "no output").strip()[-300:]
        return None, (err if r.returncode != 0
                      else f"no {tag}= line in output: {err}")
    try:
        return decode(payload), None
    except (ValueError, TypeError) as e:
        return None, f"bad {tag}= payload: {e}"


def run_code_probe(code: str, tag: str, *,
                   env: Optional[Dict[str, str]] = None,
                   timeout: float = 900.0, cwd: str = REPO_ROOT,
                   decode=json.loads) -> Tuple[Optional[object],
                                               Optional[str]]:
    """``run_probe`` for an inline script: writes ``code`` to a temp
    file (not ``-c``, so tracebacks carry real line numbers) and runs
    it under the probe contract."""
    with tempfile.NamedTemporaryFile("w", suffix=".py",
                                     delete=False) as f:
        f.write(code)
        path = f.name
    try:
        return run_probe([sys.executable, path], tag, env=env,
                         timeout=timeout, cwd=cwd, decode=decode)
    finally:
        os.unlink(path)
