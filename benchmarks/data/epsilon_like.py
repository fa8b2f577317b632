"""Epsilon-shaped seeded data: many dense unit-variance columns, and a
binary label from a sparse linear term plus a few pairwise products, so
that a tree's splits spread over many columns and no single one decides.

``params``: ``informative`` columns carry the linear term (spread evenly
over the width), ``pairs`` products of two informative columns are added,
``noise`` scales the logistic noise.

The **label surface is a constant of the configuration**
(:func:`surface_weights` and the fixed pairs), as ``higgs_like``'s is and
for its reason; the seed draws every column and the noise."""

import numpy as np

from harness import datagen


def surface_weights(informative: int) -> np.ndarray:
    """[informative] float32 weights of the linear term: one fixed draw of
    ``standard_normal(k) / sqrt(k)``, a function of ``informative`` alone,
    a constant for the reason ``higgs_like.surface_weights`` gives."""
    rng = np.random.default_rng([informative, 0])
    return (rng.standard_normal(informative)
            / np.sqrt(informative)).astype(np.float32)


def generate(rows: int, cols: int, seed: int, params: dict):
    """([cols, rows] float32 columns, [rows] float32 labels)."""
    k = min(int(params["informative"]), cols)
    x = datagen.normal_columns(cols, rows, seed)
    rng = np.random.default_rng([cols, seed])
    idx = (np.arange(k) * cols) // k
    logit = surface_weights(k) @ x[idx]
    for p in range(int(params["pairs"])):
        a, b = idx[(2 * p) % k], idx[(2 * p + 1) % k]
        logit += 0.5 * x[a] * x[b]
    logit += float(params["noise"]) * rng.logistic(size=rows).astype(np.float32)
    return x, (logit > 0).astype(np.float32)
