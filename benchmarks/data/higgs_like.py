"""Higgs-shaped seeded data: dense unit-normal columns and a balanced
binary label from a nonlinear surface. The surface is copied from
``bench.make_higgs_like`` (a linear term over every column, one product,
one square, one absolute value, logistic noise); the columns are drawn in
float32 across threads, not by one core in float64, which was most of the
31 s the original took at 10.5M rows (PR 23's run).

The **label surface is a constant of the configuration**
(:func:`surface_weights`), as ``msltr_like.relevance_weights`` is: every
seed draws new rows of one task. The seed draws every column
(``harness/datagen.normal_columns``) and the logistic noise, and so every
row and every label."""

import numpy as np

from harness import datagen


def surface_weights(cols: int) -> np.ndarray:
    """[cols] float32 weights of the surface's linear term: one fixed draw
    of ``standard_normal(cols) / sqrt(cols)``, a function of the width
    alone. It is the draw the generator made at seed 0 while it drew the
    weights from the seed (until PR 31), so the task is one of those the
    cell had measured. Were they drawn from the seed, every seed would be
    another task: the squared norm of 28 such weights moves by a quarter of
    itself, with it the weight of the linear term against the fixed terms
    and the noise, and so how lopsided a tree's splits are (the share of
    rows in the smaller child of a split, 14.6 to 15.6% by seed then,
    which is what three quarters of a tree cost)."""
    rng = np.random.default_rng([cols, 0])
    return (rng.standard_normal(cols) / np.sqrt(cols)).astype(np.float32)


def generate(rows: int, cols: int, seed: int, params: dict):
    """([cols, rows] float32 columns, [rows] float32 labels)."""
    if cols < 4:
        raise ValueError("the surface needs at least 4 columns")
    x = datagen.normal_columns(cols, rows, seed)
    rng = np.random.default_rng([cols, seed])
    logit = surface_weights(cols) @ x
    logit += 0.7 * x[0] * x[1]
    logit -= 0.4 * x[2] ** 2
    logit += 0.3 * np.abs(x[3])
    logit += 0.5 * rng.logistic(size=rows).astype(np.float32)
    return x, (logit > 0).astype(np.float32)
