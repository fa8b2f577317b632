"""Higgs-shaped seeded data: dense unit-normal columns and a balanced
binary label from a nonlinear surface. The surface is copied from
``bench.make_higgs_like`` (a linear term over every column, one product,
one square, one absolute value, logistic noise); the columns are drawn in
float32 across threads, not by one core in float64, which was most of the
31 s the original took at 10.5M rows (PR 23's run)."""

import numpy as np

from harness import datagen


def generate(rows: int, cols: int, seed: int, params: dict):
    """([cols, rows] float32 columns, [rows] float32 labels)."""
    if cols < 4:
        raise ValueError("the surface needs at least 4 columns")
    x = datagen.normal_columns(cols, rows, seed)
    rng = np.random.default_rng([cols, seed])
    w = (rng.standard_normal(cols) / np.sqrt(cols)).astype(np.float32)
    logit = w @ x
    logit += 0.7 * x[0] * x[1]
    logit -= 0.4 * x[2] ** 2
    logit += 0.3 * np.abs(x[3])
    logit += 0.5 * rng.logistic(size=rows).astype(np.float32)
    return x, (logit > 0).astype(np.float32)
