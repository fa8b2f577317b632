"""MS-LTR-shaped seeded data (MSLR-WEB30K fold 1's training part): dense
columns, queries of very uneven length, five relevance grades.

The **multiset of query sizes is a constant of the configuration**
(:func:`query_sizes`: no random draw, a heavy-tailed quantile curve fitted
to the configuration's row count, minimum and maximum), so every seed
compiles the same program: the program's query layout, and with it the
shapes of the fused step, follow the sizes. The seed orders the sizes,
draws the columns (``harness/datagen.normal_columns``), a per-query
offset on the first columns (a query's documents share its features), and
the labels: grades 0 to 4 at about 52 / 32 / 13 / 2 / 1% of the rows, cut
from a relevance surface over the informative columns (taken before the
offset, so relevance is relative to the query) plus noise. The surface
itself is a constant too (:func:`relevance_weights`).
"""

import numpy as np

from harness import datagen

GRADE_SHARES = (0.52, 0.32, 0.13, 0.02, 0.01)
INFORMATIVE = 24        # columns the relevance surface reads
OFFSET_COLUMNS = 48     # columns that carry the per-query offset
SHAPE = 3.6             # tail exponent of the size curve (log-logistic)


def query_sizes(rows: int, queries: int, smallest: int, largest: int
                ) -> np.ndarray:
    """[queries] int64 sizes, ascending: ``floor(m (u / (1 - u))^(1/SHAPE))``
    at ``u = (k + 1/2) / queries``, clipped to [smallest, largest], with
    the median ``m`` found by bisection so that the sizes sum to ``rows``;
    what rounding leaves over goes one row at a time to the sizes in the
    middle. The first is ``smallest`` and the last ``largest``."""
    if not queries * smallest <= rows <= queries * largest:
        raise ValueError("no such size table")
    u = (np.arange(queries) + 0.5) / queries
    curve = (u / (1.0 - u)) ** (1.0 / SHAPE)

    def table(m):
        s = np.clip(np.floor(m * curve), smallest, largest).astype(np.int64)
        s[0], s[-1] = smallest, largest
        return s
    lo, hi = 0.0, float(largest)
    for _ in range(80):
        mid = 0.5 * (lo + hi)
        lo, hi = (mid, hi) if table(mid).sum() <= rows else (lo, mid)
    sizes = table(lo)
    left = rows - int(sizes.sum())
    inner = np.flatnonzero((sizes > smallest) & (sizes < largest - 1))
    inner = inner[inner > 0][: -1]
    if left < 0 or left > len(inner):
        raise ValueError("the size curve does not reach the row count")
    sizes[inner[len(inner) // 2 - left // 2:][:left]] += 1
    return sizes


def relevance_weights() -> np.ndarray:
    """[INFORMATIVE] float32 weights of the surface's linear part:
    alternating in sign, falling as 1/sqrt(rank), unit norm. A constant
    like the size table: were they drawn from the seed, which columns
    matter most would change with it, and with that the shape of every
    tree (the share of rows in the smaller child of a split, which is
    what a round's histogram pass costs)."""
    k = np.arange(INFORMATIVE)
    w = (-1.0) ** k / np.sqrt(k + 1.0)
    return (w / np.linalg.norm(w)).astype(np.float32)


def generate(rows: int, cols: int, seed: int, params: dict):
    """([cols, rows] float32 columns, [rows] float32 grades, [queries]
    int64 sizes in the order of the rows)."""
    if cols < INFORMATIVE:
        raise ValueError(f"the surface needs at least {INFORMATIVE} columns")
    rng = np.random.default_rng([cols, seed])
    sizes = rng.permutation(query_sizes(
        rows, int(params["queries"]), int(params["min_query"]),
        int(params["max_query"])))
    x = datagen.normal_columns(cols, rows, seed)
    z = relevance_weights() @ x[:INFORMATIVE]
    z += 0.5 * x[0] * x[1]
    z -= 0.3 * x[2] ** 2
    z += 0.3 * np.abs(x[3])
    z += 0.6 * rng.standard_normal(rows, dtype=np.float32)
    cuts = np.quantile(z, np.cumsum(GRADE_SHARES)[:-1])
    y = np.searchsorted(cuts, z).astype(np.float32)
    query_of_row = np.repeat(np.arange(len(sizes)), sizes)
    for j in range(min(OFFSET_COLUMNS, cols)):
        x[j] += (0.7 * rng.standard_normal(len(sizes), dtype=np.float32)
                 )[query_of_row]
    return x, y, sizes
