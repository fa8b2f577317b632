"""Worker processes for the host's share of a job that the GIL would
serialise (numpy's ``bincount`` and fancy indexing hold it), and arrays
they share with the parent.

The pool is the standard library's, **forked before JAX starts its
backend**: a child of a process that holds the chips may not touch them,
and these run numpy alone, on arrays in anonymous shared memory
(:func:`shared_empty`, made before the fork, filled by the parent whenever
it likes) and on the small arguments a task brings."""

from __future__ import annotations

import mmap
import multiprocessing
from typing import Callable, Dict, Sequence

import numpy as np

SHARED: Dict[str, np.ndarray] = {}
ANSWER_WITHIN_S = 600     # a worker that hangs fails the run, not the session


def shared_empty(name: str, shape, dtype) -> np.ndarray:
    """An uninitialised array over anonymous shared memory, kept under
    ``name`` for the workers forked after this call."""
    count = int(np.prod(shape, dtype=np.int64))
    buf = mmap.mmap(-1, max(count * np.dtype(dtype).itemsize, 1))
    SHARED[name] = np.frombuffer(buf, dtype=dtype, count=count).reshape(shape)
    return SHARED[name]


def call_on_shared(fn: Callable, names: Sequence[str], *args):
    """``fn(the shared arrays of these names..., *args)``: how a task
    names arrays that are too large to send."""
    return fn(*(SHARED[n] for n in names), *args)


def pool(n: int):
    """``n`` workers forked now, holding :data:`SHARED` as it is."""
    return multiprocessing.get_context("fork").Pool(max(1, n))


def starmap(workers, fn: Callable, items: Sequence[tuple]) -> list:
    """``[fn(*item) for item in items]`` on the workers, one item a task;
    a worker's exception is raised here."""
    return workers.starmap_async(fn, list(items), chunksize=1).get(
        ANSWER_WITHIN_S)
