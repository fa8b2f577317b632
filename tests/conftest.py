"""Test configuration: run JAX on a virtual 8-device CPU mesh.

Mirrors the reference's distributed-test strategy
(tests/distributed/_test_distributed.py launches N CLI processes on
localhost): here N virtual CPU devices stand in for TPU chips so sharding
tests exercise real collectives without hardware.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags = (flags + " --xla_force_host_platform_device_count=8").strip()
# Pin the CPU codegen ISA: LLVM's host-feature detection is
# per-process state (AMX needs an arch_prctl opt-in some processes
# make and others don't), so without a pin two test processes write
# persistent-cache entries with INCOMPATIBLE feature sets — loading the
# other's AOT result then warns "machine feature not supported on the
# host" and can segfault outright (observed once in-suite, round 5).
# AVX2 is universally present on the fleet and plenty for tests.
if "xla_cpu_max_isa" not in flags:
    flags = (flags + " --xla_cpu_max_isa=AVX2").strip()
os.environ["XLA_FLAGS"] = flags
# The chip is never reached from here: tests run on the CPU backend
# (JAX_PLATFORMS=cpu is all it takes), the chip only through
# `python chip_smoke.py` under the chip tool.
os.environ["JAX_PLATFORMS"] = "cpu"
# Suite default: pin the LEGACY training driver. The fused
# single-dispatch step (ISSUE 3) jit-closes over each booster's device
# data, so it compiles one program PER BOOSTER — correct, and the right
# trade on real workloads (hundreds of iterations amortize one
# compile), but this suite constructs hundreds of tiny boosters and on
# the 1-core CI host those per-booster compiles roughly double suite
# wall-clock, past the tier-1 budget. The legacy driver shares its
# module-level build_tree jit across boosters. Fused coverage is
# concentrated in tests/test_fused_train.py, which opts back in
# per-train (parity across configs, eval cadence, deferred stop flag,
# mesh nesting).
os.environ.setdefault("LIGHTGBM_TPU_FUSED_TRAIN", "0")
# No persistent XLA compilation cache here: engine.enable_compilation_cache
# leaves the CPU backend off because jaxlib 0.9.0 has segfaulted inside
# CPU executable (de)serialization on the 8-virtual-device shard_map
# programs (two full-suite runs, round 5 — one on write with a fresh
# dir and no concurrent writers). A slow suite beats a crashing one;
# JAX_COMPILATION_CACHE_DIR opts in at your own risk.

import subprocess  # noqa: E402
import sys  # noqa: E402

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.RandomState(42)


@pytest.fixture
def interp(monkeypatch):
    """Route every Pallas entry point through the interpreter, so that
    ``hist_impl=pallas`` trains on the CPU with the chip's kernels."""
    import functools
    from lightgbm_tpu.ops import pallas_histogram as PH
    for name in ("build_histograms_pallas", "build_histograms_pallas_lanes",
                 "build_root_histograms_classes"):
        monkeypatch.setattr(
            PH, name, functools.partial(getattr(PH, name), interpret=True))
    return PH


def _map_count() -> int:
    try:
        with open("/proc/self/maps", "rb") as f:
            return sum(1 for _ in f)
    except OSError:          # not Linux: no limit to stay under
        return 0


@pytest.fixture(autouse=True, scope="module")
def _bounded_map_count():
    """Drop jax's compiled-program caches at a module boundary once the
    process is halfway to ``vm.max_map_count`` (65530).

    Each XLA:CPU executable keeps its code pages mmap'd for as long as
    a jit cache entry holds it — one mapping per parallel-codegen
    shard, so the cost scales with the host's core count — and this
    suite compiles thousands. On an 8-core host one process crossed the
    limit around the 200th test and the next compile died inside LLVM
    (SIGSEGV or SIGABRT in ``backend_compile_and_load``, at whichever
    test compiled next: the "nondeterministic jaxlib segfault" earlier
    rounds worked around with xdist and subprocess isolation).
    Measured there: /proc/self/maps grew ~300 lines per test to 63k at
    the crash; with this fixture it stays under 40k and the suite
    finishes. A 1-core host never gets near the threshold, clears
    nothing and pays nothing."""
    yield
    if _map_count() > 30000:
        import jax
        jax.clear_caches()


@pytest.fixture
def recompile_guard():
    """Compile-cache discipline guard (analysis/recompile_guard.py):
    ``with recompile_guard(max_compiles=N, label=...): ...`` raises
    RecompileError when XLA compiles more than N programs in the
    scope."""
    from lightgbm_tpu.analysis import RecompileGuard
    return RecompileGuard


# XLA:CPU in jaxlib 0.9.0 segfaults NONdeterministically while COMPILING
# the column-sharded feature_shard_storage programs late in a long suite
# process: three full-suite runs died with SIGSEGV (twice inside the
# persistent-cache serialize/deserialize, once inside
# backend_compile_and_load with the cache off), each at a DIFFERENT test
# of the family, while every one passes reliably in a fresh process.
# Until jaxlib moves, the compiling tests of the family self-isolate:
# the in-suite run spawns a fresh pytest process for the real body.
SHARDED_IN_PROC = os.environ.get("LGBTPU_SHARDED_IN_PROC") == "1"


def run_isolated(test_file, name, timeout=900):
    env = dict(os.environ, LGBTPU_SHARDED_IN_PROC="1")
    # a CI-level PYTEST_ADDOPTS (e.g. --collect-only) must not rewrite
    # the child invocation into a no-op that exits 0
    env.pop("PYTEST_ADDOPTS", None)
    cmd = [sys.executable, "-m", "pytest", "-q", "-x", "-p",
           "no:cacheprovider", os.path.abspath(test_file) + "::" + name]
    try:  # if xdist is active in the parent, pin the child inline
        import xdist  # noqa: F401
        cmd[4:4] = ["-n", "0"]
    except ImportError:
        pass
    try:
        r = subprocess.run(cmd, capture_output=True, text=True,
                           timeout=timeout, env=env)
    except subprocess.TimeoutExpired as e:
        # CPython attaches the partial output as BYTES even with
        # text=True — decode so the child's traceback stays readable
        so = (e.stdout or b"").decode(errors="replace")
        se = (e.stderr or b"").decode(errors="replace")
        raise AssertionError(
            f"isolated test {name} hung past {timeout}s;\n"
            f"stdout:\n{so[-3000:]}\nstderr:\n{se[-2000:]}") from None
    assert r.returncode == 0, (r.stdout[-3000:] + "\n" + r.stderr[-2000:])


def sharded_isolated(fn):
    """Decorator form of the isolation shim: runs the body in-process
    only inside the child (LGBTPU_SHARDED_IN_PROC), else spawns it.
    Derives file and test name from the function, so renames cannot
    desynchronize a retyped string."""
    import functools
    import inspect

    test_file = inspect.getfile(fn)

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        if SHARDED_IN_PROC:
            return fn(*args, **kwargs)
        run_isolated(test_file, fn.__name__)

    return wrapper


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multi-process / long-running tests")
