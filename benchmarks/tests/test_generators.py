"""What a seed decides in the three generators, all on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_generators.py -q

The label surface is a constant of the configuration: it is read back
from the data (least squares of the centred label on the columns the
surface reads), so the case holds the generator to it whatever its code
calls the weights. The seed draws the rows: every column and every label.
"""

import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import datagen  # noqa: E402
from harness.manifest import Manifest  # noqa: E402

ROWS = 50_000
SEEDS = (2 ** 31 + 11, 2 ** 31 + 12)

# generator, columns, its params, the columns the linear term reads, the
# generator's own name for the term's weights, whether the labels are binary
CASES = {
    "higgs_like": dict(
        cols=28, params={}, reads=lambda c: np.arange(28),
        weights=lambda g: g.surface_weights(28), binary=True),
    "epsilon_like": dict(
        cols=400, params={"informative": 200, "pairs": 16, "noise": 0.5},
        reads=lambda c: (np.arange(200) * c) // 200,
        weights=lambda g: g.surface_weights(200), binary=True),
    "msltr_like": dict(
        cols=30, params={"queries": 700, "min_query": 1, "max_query": 400},
        reads=lambda c: np.arange(24),
        weights=lambda g: g.relevance_weights(), binary=False),
}


def _fitted_weights(x, y):
    """The linear term as the data shows it: least squares of the centred
    label on the columns, scaled to unit norm."""
    w = np.linalg.lstsq(x.T.astype(np.float64), y - y.mean(), rcond=None)[0]
    return w / np.linalg.norm(w)


def _cosine(a, b):
    return float(a @ b / (np.linalg.norm(a) * np.linalg.norm(b)))


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_seed_draws_the_rows_and_not_the_task(name, monkeypatch):
    case = CASES[name]
    gen = Manifest(ROOT).generator(name)
    cols, reads = case["cols"], case["reads"](case["cols"])

    def draw(seed):
        out = gen.generate(ROWS, cols, seed, case["params"])
        return out[0], np.asarray(out[1], np.float64), out[2:]

    (xa, ya, ra), (xb, yb, _) = draw(SEEDS[0]), draw(SEEDS[1])
    assert xa.shape == (cols, ROWS) and xa.dtype == np.float32
    # another seed, other arrays: every column and the labels differ
    assert all(not np.array_equal(xa[j], xb[j]) for j in range(cols))
    assert not np.array_equal(ya, yb)
    # one task: the surface read back from either seed's data is the same,
    # and is the constant the generator names (seeds that drew their own
    # weights read a cosine near 0)
    wa, wb = _fitted_weights(xa[reads], ya), _fitted_weights(xb[reads], yb)
    assert _cosine(wa, wb) > 0.9
    named = np.asarray(case["weights"](gen), np.float64)
    assert named.shape == (len(reads),)
    assert _cosine(wa, named) > 0.9 and _cosine(wb, named) > 0.9
    if case["binary"]:
        for y in (ya, yb):
            assert set(np.unique(y)) == {0.0, 1.0}
            assert 0.45 <= y.mean() <= 0.55
    else:
        shares = np.bincount(ya.astype(int), minlength=5) / ROWS
        np.testing.assert_allclose(shares, gen.GRADE_SHARES, atol=0.005)
    # one seed, the same bytes on one thread and on eight
    del xb, yb
    for n in (1, 8):
        monkeypatch.setattr(datagen, "threads", lambda n=n: n)
        x, y, rest = draw(SEEDS[0])
        assert x.tobytes() == xa.tobytes() and y.tobytes() == ya.tobytes()
        assert all(np.array_equal(r, r0) for r, r0 in zip(rest, ra))
