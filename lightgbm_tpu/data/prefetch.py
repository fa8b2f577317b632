"""Double-buffered host→device chunk staging for out-of-core training.

The chunked tree builder (:mod:`.chunked`) consumes the binned row
stream once per leaf-growth round. Each sweep walks the fixed chunk
sequence ``[0, C), [C, 2C), ...``; while the device accumulates
histograms over chunk k, chunk k+1 is already being read from its
shard (host mmap) and copied host→device on a staging thread — the
transfer overlaps the compute, so steady-state wall clock per sweep is
``max(compute, transfer)``, not their sum.

Device footprint is bounded by TWO chunk buffers (the one being
consumed and the one in flight) regardless of dataset size — that is
what ``chunk_budget_mb`` budgets.

Overlap accounting: the consumer records how long it BLOCKED waiting
for a staged chunk (``wait_s``) against the staging thread's total
work time (``stage_s``); ``overlap_fraction = 1 - wait_s / stage_s``.
1.0 means every read+copy hid completely behind compute; 0.0 means
fully serialized (the first chunk of every sweep always serializes —
there is nothing to hide it behind).
"""

from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor
from typing import Iterator, Tuple

import numpy as np

__all__ = ["ChunkPrefetcher", "PrefetchStats", "chunk_rows_for"]


def chunk_rows_for(num_rows: int, num_features: int, itemsize: int,
                   budget_mb: float, block_rows: int) -> int:
    """Chunk size from the staging budget: two in-flight ``[C, F]``
    bin buffers must fit in ``budget_mb``. C is rounded DOWN to a
    multiple of ``block_rows`` so the chunked histogram walks the same
    row-block sequence as a resident pass — that alignment is what
    makes carried accumulation bit-identical (see
    ``ops.histogram.build_histograms``'s ``init`` contract)."""
    block = max(1, int(block_rows))
    budget = int(float(budget_mb) * (1 << 20))
    c = budget // max(1, 2 * int(num_features) * int(itemsize))
    c = max(block, (c // block) * block)
    # no point chunking finer than the block-padded dataset
    r_pad = -(-max(1, int(num_rows)) // block) * block
    return int(min(c, r_pad))


class PrefetchStats:
    """Cumulative staging counters across sweeps (one prefetcher
    serves every round of every tree)."""

    __slots__ = ("wait_s", "stage_s", "chunks", "bytes")

    def __init__(self):
        self.wait_s = 0.0
        self.stage_s = 0.0
        self.chunks = 0
        self.bytes = 0

    def overlap_fraction(self) -> float:
        if self.stage_s <= 0.0:
            return 1.0
        return float(min(1.0, max(0.0, 1.0 - self.wait_s / self.stage_s)))

    def as_dict(self) -> dict:
        return {"wait_s": round(self.wait_s, 6),
                "stage_s": round(self.stage_s, 6),
                "chunks": int(self.chunks), "bytes": int(self.bytes),
                "overlap_fraction": round(self.overlap_fraction(), 4)}


class ChunkPrefetcher:
    """Sweep a :class:`~.chunked.ChunkSource` as fixed-shape device
    chunks, staging one chunk ahead on a worker thread.

    Every chunk has the STATIC shape ``[chunk_rows, F]`` (the tail is
    zero-padded; padded rows carry ``row_leaf == -1`` on the consumer
    side, a histogram/relabel no-op), so the per-chunk jitted program
    compiles once."""

    def __init__(self, source, chunk_rows: int):
        self.source = source
        self.chunk_rows = int(chunk_rows)
        if self.chunk_rows <= 0:
            raise ValueError("chunk_rows must be positive")
        self.num_chunks = max(
            1, -(-int(source.num_rows) // self.chunk_rows))
        self.padded_rows = self.num_chunks * self.chunk_rows
        self.stats = PrefetchStats()
        self._pool = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="lgbtpu-prefetch")

    def _stage(self, k: int):
        import jax

        from .. import phases, profiler
        t0 = time.perf_counter()
        with profiler.phase(phases.PREFETCH):
            lo = k * self.chunk_rows
            hi = min(lo + self.chunk_rows, int(self.source.num_rows))
            X = np.ascontiguousarray(self.source.read_rows(lo, hi))
            if X.shape[0] < self.chunk_rows:
                X = np.concatenate(
                    [X, np.zeros((self.chunk_rows - X.shape[0],
                                  X.shape[1]), X.dtype)])
            dev = jax.device_put(X)
        self.stats.stage_s += time.perf_counter() - t0
        self.stats.bytes += X.nbytes
        return dev

    def chunks(self) -> Iterator[Tuple[int, object]]:
        """One sequential sweep: yields ``(row_offset, device_bins)``
        with the next chunk's stage already in flight."""
        fut = self._pool.submit(self._stage, 0)
        for k in range(self.num_chunks):
            t0 = time.perf_counter()
            dev = fut.result()
            self.stats.wait_s += time.perf_counter() - t0
            self.stats.chunks += 1
            if k + 1 < self.num_chunks:
                fut = self._pool.submit(self._stage, k + 1)
            yield k * self.chunk_rows, dev

    def close(self) -> None:
        self._pool.shutdown(wait=True)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
