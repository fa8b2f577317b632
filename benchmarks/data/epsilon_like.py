"""Epsilon-shaped seeded data: many dense unit-variance columns, and a
binary label from a sparse linear term plus a few pairwise products, so
that a tree's splits spread over many columns and no single one decides.

``params``: ``informative`` columns carry the linear term (spread evenly
over the width), ``pairs`` products of two informative columns are added,
``noise`` scales the logistic noise."""

import numpy as np

from harness import datagen


def generate(rows: int, cols: int, seed: int, params: dict):
    """([cols, rows] float32 columns, [rows] float32 labels)."""
    k = min(int(params["informative"]), cols)
    x = datagen.normal_columns(cols, rows, seed)
    rng = np.random.default_rng([cols, seed])
    idx = (np.arange(k) * cols) // k
    w = (rng.standard_normal(k) / np.sqrt(k)).astype(np.float32)
    logit = w @ x[idx]
    for p in range(int(params["pairs"])):
        a, b = idx[(2 * p) % k], idx[(2 * p + 1) % k]
        logit += 0.5 * x[a] * x[b]
    logit += float(params["noise"]) * rng.logistic(size=rows).astype(np.float32)
    return x, (logit > 0).astype(np.float32)
