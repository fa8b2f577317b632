"""Seeded dense columns, drawn and binned in column blocks across a few
threads. The stream of a column depends on the seed and the column's index
alone, so the data is the same whatever the number of threads."""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, List, Sequence

import numpy as np


def threads() -> int:
    return max(1, min(8, os.cpu_count() or 1))


def _each_block(n: int, fn: Callable[[int, int], None], block: int = 0) -> None:
    """fn(lo, hi) over [0, n) in blocks (four to a thread unless a size is
    given), on a few threads; the result of every future is read so that
    an exception is not lost."""
    block = block or max(1, n // (4 * threads()))
    spans = [(lo, min(lo + block, n)) for lo in range(0, n, block)]
    with ThreadPoolExecutor(threads()) as ex:
        for fut in [ex.submit(fn, lo, hi) for lo, hi in spans]:
            fut.result()


def normal_columns(cols: int, rows: int, seed: int) -> np.ndarray:
    """[cols, rows] float32, unit normal, column j from the stream
    ``default_rng([j, seed])``."""
    out = np.empty((cols, rows), np.float32)

    def fill(lo, hi):
        for j in range(lo, hi):
            np.random.default_rng([j, seed]).standard_normal(
                out=out[j], dtype=np.float32)
    _each_block(cols, fill)
    return out


def bin_columns(x_cm: np.ndarray, upper_bounds: Sequence[np.ndarray]
                ) -> np.ndarray:
    """[cols, rows] uint8 bins: the first bin whose upper bound is not
    below the value, compared in float32 (as the program's own device
    binning does). The bounds are the program's, fitted on a sample."""
    cols, rows = x_cm.shape
    out = np.empty((cols, rows), np.uint8)
    ubs: List[np.ndarray] = [np.asarray(u, np.float32) for u in upper_bounds]
    if max(len(u) for u in ubs) > 256:
        raise ValueError("more than 256 bins do not fit uint8")

    def fill(lo, hi):
        for j in range(lo, hi):
            out[j] = np.searchsorted(ubs[j], x_cm[j], side="left")
    _each_block(cols, fill)
    return out


def to_row_major(cm: np.ndarray) -> np.ndarray:
    """[cols, rows] -> a contiguous [rows, cols], transposed in row blocks
    on a few threads."""
    cols, rows = cm.shape
    out = np.empty((rows, cols), cm.dtype)

    def fill(lo, hi):
        out[lo:hi] = cm[:, lo:hi].T
    _each_block(rows, fill, block=1 << 16)
    return out
