"""Runtime-compiled native helpers (the C side of the data loader).

The reference ships its parser as part of the C++ core
(``src/io/parser.cpp``); here ``parser.c`` is compiled ON FIRST USE with
``gcc -O3 -shared -fPIC`` into a content-hashed cache file under
``<checkout>/.native_cache`` and loaded via ctypes — no install-time
build step, and every caller keeps a pure Python fallback, so a
missing/broken toolchain only costs speed (~10-40x on large text
files), never functionality.

Set ``LIGHTGBM_TPU_NO_NATIVE=1`` to force the Python paths.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
from typing import Optional

import numpy as np

__all__ = ["native_lib", "capi_lib", "hist_lib", "cache_root",
           "parse_delimited", "parse_libsvm"]


_LIB = None
_TRIED = False
_CAPI = None
_CAPI_TRIED = False
_HIST = None
_HIST_TRIED = False

_DOUBLE_P = np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS")




def cache_root() -> str:
    """The checkout: the directory that holds the ``lightgbm_tpu``
    package. Everything the program builds at run time lives beside the
    code — native .so files in ``.native_cache/``, XLA executables in
    ``.xla_cache/`` (engine.enable_compilation_cache) — never under
    ``~`` or a temp name, so a fresh machine that receives the checkout
    receives (or rebuilds in place) exactly what it needs."""
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def _compile_and_load(src_name: str, so_prefix: str, extra_gcc=(),
                      compiler: str = "gcc"):
    """Compile a bundled C/C++ source into the content-hashed cache
    (0700 — a predictable /tmp path would let another local user
    pre-plant a malicious .so) and ctypes-load it. Raises on failure."""
    src = os.path.join(os.path.dirname(__file__), src_name)
    with open(src, "rb") as f:
        code = f.read()
    tag = hashlib.sha256(code + repr(extra_gcc).encode()).hexdigest()[:16]
    cache_dir = os.path.join(cache_root(), ".native_cache")
    os.makedirs(cache_dir, mode=0o700, exist_ok=True)
    so = os.path.join(cache_dir, f"{so_prefix}_{tag}.so")
    if not os.path.exists(so):
        tmp = f"{so}.{os.getpid()}.tmp"
        subprocess.run(
            [compiler, "-O3", "-shared", "-fPIC", "-o", tmp, src,
             *extra_gcc],
            check=True, capture_output=True, timeout=300)
        os.replace(tmp, so)  # atomic: concurrent builders both win
    return ctypes.CDLL(so)

def native_lib():
    """The loaded CDLL, or None when native helpers are unavailable."""
    global _LIB, _TRIED
    if _TRIED:
        return _LIB
    _TRIED = True
    if os.environ.get("LIGHTGBM_TPU_NO_NATIVE"):
        return None
    try:
        lib = _compile_and_load("parser.c", "lightgbm_tpu_parser")
        lib.lgbtpu_max_cols.restype = ctypes.c_long
        lib.lgbtpu_max_cols.argtypes = [ctypes.c_char_p, ctypes.c_long,
                                        ctypes.c_char]
        lib.lgbtpu_parse_delimited.restype = ctypes.c_int
        lib.lgbtpu_parse_delimited.argtypes = [
            ctypes.c_char_p, ctypes.c_long, ctypes.c_char, ctypes.c_long,
            ctypes.c_long, _DOUBLE_P]
        lib.lgbtpu_libsvm_max_index.restype = ctypes.c_long
        lib.lgbtpu_libsvm_max_index.argtypes = [ctypes.c_char_p,
                                                ctypes.c_long]
        lib.lgbtpu_parse_libsvm.restype = ctypes.c_int
        lib.lgbtpu_parse_libsvm.argtypes = [
            ctypes.c_char_p, ctypes.c_long, ctypes.c_long, ctypes.c_long,
            _DOUBLE_P, _DOUBLE_P]
        lib.lgbtpu_greedy_bounds.restype = ctypes.c_long
        lib.lgbtpu_greedy_bounds.argtypes = [
            _DOUBLE_P, np.ctypeslib.ndpointer(np.int64,
                                              flags="C_CONTIGUOUS"),
            ctypes.c_long, ctypes.c_long, ctypes.c_double, ctypes.c_long,
            _DOUBLE_P]
        lib.lgbtpu_values_to_bins.restype = None
        lib.lgbtpu_values_to_bins.argtypes = [
            _DOUBLE_P, ctypes.c_long, _DOUBLE_P, ctypes.c_long,
            ctypes.c_long,
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")]
        _LIB = lib
    except Exception:
        _LIB = None
    return _LIB


def capi_lib():
    """The native C inference API (capi.c), runtime-compiled and loaded
    via ctypes like :func:`native_lib`. Returns None when unavailable.
    C consumers build the .so directly (see capi.h); this loader exists
    for the test suite and for Python-side smoke use."""
    global _CAPI, _CAPI_TRIED
    if _CAPI_TRIED:
        return _CAPI
    _CAPI_TRIED = True
    if os.environ.get("LIGHTGBM_TPU_NO_NATIVE"):
        return None
    try:
        lib = _compile_and_load("capi.c", "lightgbm_tpu_capi",
                                extra_gcc=("-pthread", "-lm"))
        lib.LGBM_GetLastError.restype = ctypes.c_char_p
        lib.LGBM_BoosterCreateFromModelfile.restype = ctypes.c_int
        lib.LGBM_BoosterCreateFromModelfile.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.c_void_p)]
        lib.LGBM_BoosterFree.argtypes = [ctypes.c_void_p]
        lib.LGBM_BoosterGetNumClasses.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int)]
        lib.LGBM_BoosterGetNumFeature.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int)]
        lib.LGBM_BoosterPredictForMat.restype = ctypes.c_int
        lib.LGBM_BoosterPredictForMat.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int,
            ctypes.c_int32, ctypes.c_int32, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int, ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_int64), _DOUBLE_P]
        lib.LGBM_BoosterPredictForMatSingleRow.restype = ctypes.c_int
        lib.LGBM_BoosterPredictForMatSingleRow.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int,
            ctypes.c_int32, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int, ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_int64), _DOUBLE_P]
        lib.LGBM_BoosterPredictForCSR.restype = ctypes.c_int
        lib.LGBM_BoosterPredictForCSR.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_int64), _DOUBLE_P]
        for g in ("LGBM_BoosterGetCurrentIteration",
                  "LGBM_BoosterNumModelPerIteration",
                  "LGBM_BoosterNumberOfTotalModel",
                  "LGBM_BoosterGetPredictLayout"):
            fn = getattr(lib, g)
            fn.restype = ctypes.c_int
            fn.argtypes = [ctypes.c_void_p,
                           ctypes.POINTER(ctypes.c_int)]
        _CAPI = lib
    except Exception:
        _CAPI = None
    return _CAPI


_INT32_P = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")


def hist_lib():
    """True when the native histogram kernel is compiled AND registered
    as an XLA FFI custom-call pair ("lgbtpu_hist_f32"/"lgbtpu_hist_i8",
    platform cpu); None when unavailable.

    The kernel (hist.c loops wrapped by hist_ffi.cc) is the CPU-backend
    analog of the device kernels in ops/histogram.py — dense_bin.hpp:105
    ConstructHistogram cache locality — and runs on XLA's compute thread
    with no GIL or host round-trip (a jax.pure_callback would deadlock a
    single-threaded CPU client waiting on its own executor)."""
    global _HIST, _HIST_TRIED
    if _HIST_TRIED:
        return _HIST
    _HIST_TRIED = True
    if os.environ.get("LIGHTGBM_TPU_NO_NATIVE"):
        return None
    try:
        from jax import ffi
        inc = ffi.include_dir()
        lib = _compile_and_load(
            "hist_ffi.cc", "lightgbm_tpu_hist_ffi",
            extra_gcc=("-std=c++17", "-pthread", f"-I{inc}"),
            compiler="g++")
        ffi.register_ffi_target(
            "lgbtpu_hist_f32", ffi.pycapsule(lib.LgbtpuHistF32),
            platform="cpu")
        ffi.register_ffi_target(
            "lgbtpu_hist_i8", ffi.pycapsule(lib.LgbtpuHistI8),
            platform="cpu")
        ffi.register_ffi_target(
            "lgbtpu_relabel", ffi.pycapsule(lib.LgbtpuRelabel),
            platform="cpu")
        ffi.register_ffi_target(
            "lgbtpu_partition", ffi.pycapsule(lib.LgbtpuPartition),
            platform="cpu")
        ffi.register_ffi_target(
            "lgbtpu_hist_perm_f32",
            ffi.pycapsule(lib.LgbtpuHistPermF32), platform="cpu")
        ffi.register_ffi_target(
            "lgbtpu_hist_perm_i8",
            ffi.pycapsule(lib.LgbtpuHistPermI8), platform="cpu")
        _HIST = lib
    except Exception:
        _HIST = None
    return _HIST


def greedy_bounds(distinct: np.ndarray, counts: np.ndarray,
                  max_bin: int, total_cnt: float,
                  min_data_in_bin: int) -> Optional[np.ndarray]:
    """Fast path for binning._greedy_find_bin. None -> caller falls
    back to the (exact-identical) Python loop."""
    lib = native_lib()
    if lib is None:
        return None
    distinct = np.ascontiguousarray(distinct, np.float64)
    counts = np.ascontiguousarray(counts, np.int64)
    out = np.empty(max(int(max_bin), 1) + 1, np.float64)
    n = lib.lgbtpu_greedy_bounds(distinct, counts, len(distinct),
                                 int(max_bin), float(total_cnt),
                                 int(min_data_in_bin), out)
    return out[:n]


def values_to_bins(values: np.ndarray, upper_bounds: np.ndarray,
                   nan_bin: int) -> Optional[np.ndarray]:
    """Fast path for BinMapper.values_to_bins (numerical features).
    None -> caller falls back to searchsorted."""
    lib = native_lib()
    if lib is None:
        return None
    values = np.ascontiguousarray(values, np.float64)
    upper_bounds = np.ascontiguousarray(upper_bounds, np.float64)
    out = np.empty(len(values), np.int32)
    lib.lgbtpu_values_to_bins(values, len(values), upper_bounds,
                              len(upper_bounds), int(nan_bin), out)
    return out


def parse_delimited(lines, delim: str) -> Optional[np.ndarray]:
    """Fast path for io._parse_delimited. None -> caller falls back."""
    lib = native_lib()
    if lib is None or not lines:
        return None
    body = "\n".join(lines).encode("utf-8", errors="strict")
    n = len(body)
    width = int(lib.lgbtpu_max_cols(body, n, delim.encode()[:1]))
    if width <= 0:
        return None
    out = np.full((len(lines), width), np.nan, dtype=np.float64)
    rc = lib.lgbtpu_parse_delimited(body, n, delim.encode()[:1],
                                    len(lines), width, out)
    return out if rc == 0 else None


def parse_libsvm(lines, num_features_hint: int = 0):
    """Fast path for io._parse_libsvm. None -> caller falls back."""
    lib = native_lib()
    if lib is None or not lines:
        return None
    body = "\n".join(lines).encode("utf-8", errors="strict")
    n = len(body)
    mx = int(lib.lgbtpu_libsvm_max_index(body, n))
    if mx == -2:
        return None
    if mx < 0 and num_features_hint <= 0:
        # label-only file with no width hint: the Python fallback
        # produces a 0-column matrix here; defer to it rather than
        # invent a clamped 1-column shape
        return None
    ncols = max(mx + 1, num_features_hint, 1)
    labels = np.empty(len(lines), dtype=np.float64)
    out = np.zeros((len(lines), ncols), dtype=np.float64)
    rc = lib.lgbtpu_parse_libsvm(body, n, len(lines), ncols, labels, out)
    return (labels, out) if rc == 0 else None
