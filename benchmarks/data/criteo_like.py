"""Criteo-shaped seeded data: the click logs of the reference's parallel
experiment as its preprocessing leaves them, 13 integer columns and 26
categorical ones turned into a click rate and a count each (65 columns),
and two more to make the source's 67 (one rate, one count: ``assumed`` in
the configuration). Counts are heavy-tailed whole numbers
(``floor(exp(mu + sigma n))``), rates lie in (0, 1)
(``sigmoid(a + b n)``), each a monotone function of one latent unit
normal ``n`` a column.

The **label surface is a constant of the configuration**, as
``higgs_like.surface_weights`` is. A click is a logit over a fixed
threshold; the logit is ``STEPS`` step effects (column ``j`` beyond a fixed
value, as "over 30 earlier clicks" is: amplitudes falling off
geometrically), a linear term over the latents of the other columns
(weights falling off geometrically in a fixed order) and unit-normal
noise. Nothing in it is drawn alike: with random normal weights and no
steps (this generator's first form) the six heaviest columns lay within 8%
of one another, the best cut of a smooth effect is flat around its optimum,
and so the first splits of a tree, or their thresholds, moved from seed to
seed, and with them the rows a round streams, which the chip bills in
whole chunks (PR 35's first six seeds: 0.77% between the quartiles of
``train_row_trees_per_s``). A step has one best cut, and it is the same
for every seed.

The steps are independent events of known probability and the rest of the
logit is exactly normal, so the click rate of a threshold is a finite sum
of normal tails (:func:`click_rate_at`), and :func:`threshold` inverts it:
the rate is exactly ``params["click_rate"]`` for every row, the number of
clicks in ``rows`` rows is binomial, and the seed moves the rate by
``sqrt(p (1 - p) / rows)`` and no more (2.5e-5 at 53,125,000 rows). The
configuration chooses the rate with that spread in mind (its
``base_rate`` block).

The seed draws the rows. Rows come in **blocks** of ``BLOCK_ROWS``: the
stream of column ``j`` in block ``b`` is ``default_rng([j, seed, b])`` and
the noise of block ``b`` is ``default_rng([cols, seed, b])``, so a block is
drawn without the blocks before it, by any number of threads, and the
whole table never has to exist as floats: :func:`draw_block` gives one
block, :func:`generate` all of them (small shapes, tests)."""

import math
from statistics import NormalDist

import numpy as np

BLOCK_ROWS = 1 << 19
INTEGER_COLS = 13        # the source's integer features
NOISE_SHARE = 0.5        # of the logit's variance
STEP_SHARE = 0.45        # the step effects'; the linear term has the rest
STEPS = 14
STEP_DECAY = 0.85        # a step's amplitude over the one before it
WEIGHT_DECAY = 0.97      # a linear weight over the one before it
# how often each step's event happens (a fixed list, none alike)
STEP_ODDS = (0.30, 0.18, 0.24, 0.12, 0.36, 0.09, 0.27, 0.15, 0.21, 0.33, 0.14,
             0.26, 0.11, 0.38)


def column_kinds(cols: int) -> list:
    """'count' or 'rate' by column: 13 counts, then (rate, count) pairs."""
    if cols < 8:
        raise ValueError("the surface needs at least 8 columns")
    return ["count" if j < INTEGER_COLS or (j - INTEGER_COLS) % 2 else "rate"
            for j in range(cols)]


def surface_constants(cols: int) -> dict:
    """What every seed shares, one fixed draw, a function of the width
    alone: ``step_cols`` (the first ``min(STEPS, cols // 4)`` columns of a
    fixed order),
    ``step_at`` (the latent value each step lies at), ``step_amp`` (signed
    amplitudes), [cols] float32 ``weights`` of the linear term (0 on the
    step columns), the noise's weight, and each column's two transform
    constants. The logit has variance 1."""
    rng = np.random.default_rng([cols, 0])
    order = rng.permutation(cols)
    sign = np.where(rng.random(cols) < 0.5, -1.0, 1.0)
    steps = min(STEPS, cols // 4)
    odds = np.array(STEP_ODDS[:steps])
    amp = STEP_DECAY ** np.arange(steps) * sign[:steps]
    amp *= np.sqrt(STEP_SHARE / np.sum(amp * amp * odds * (1.0 - odds)))
    w = np.zeros(cols)
    w[order[steps:]] = WEIGHT_DECAY ** np.arange(cols - steps)
    w *= sign
    w *= np.sqrt((1.0 - NOISE_SHARE - STEP_SHARE) / np.sum(w * w))
    lo = np.where(np.array(column_kinds(cols)) == "count", 1.5, -4.0)
    hi = np.where(np.array(column_kinds(cols)) == "count", 4.0, -2.5)
    return {"step_cols": order[:steps], "step_odds": odds,
            "step_at": np.array([NormalDist().inv_cdf(1.0 - q) for q in odds],
                                np.float32),
            "step_amp": amp.astype(np.float32),
            "weights": w.astype(np.float32),
            "noise": np.float32(np.sqrt(NOISE_SHARE)),
            "shift": rng.uniform(lo, hi).astype(np.float32),
            "scale": rng.uniform(
                np.where(lo > 0, 1.0, 0.4), np.where(lo > 0, 2.0, 1.0)
            ).astype(np.float32)}


def _step_patterns(cols: int):
    """(probability, summed amplitude) of every pattern of the steps'
    events, and the standard deviation of the rest of the logit; float64,
    from the float32 constants the rows are drawn with."""
    k = surface_constants(cols)
    amp, odds = k["step_amp"].astype(np.float64), k["step_odds"]
    steps = len(amp)
    on = (np.arange(1 << steps)[:, None] >> np.arange(steps)) & 1 > 0
    prob = np.prod(np.where(on, odds, 1.0 - odds), axis=1)
    rest = float(np.sqrt(np.sum(k["weights"].astype(np.float64) ** 2)
                         + float(k["noise"]) ** 2))
    return prob, on @ amp, rest


def click_rate_at(cut: float, cols: int, patterns=None) -> float:
    """P(logit > cut): over every pattern of the steps' events, the
    pattern's probability times the normal tail of the rest of the
    logit."""
    prob, level, rest = patterns or _step_patterns(cols)
    tail = [0.5 * math.erfc(v) for v in (cut - level) / (rest * math.sqrt(2))]
    return float(prob @ np.array(tail))


_CUTS: dict = {}


def threshold(click_rate: float, cols: int) -> np.float32:
    """The logit a click lies above: :func:`click_rate_at` inverted by
    bisection (it falls as the cut rises)."""
    key = (float(click_rate), cols)
    if key not in _CUTS:
        patterns = _step_patterns(cols)
        lo, hi = -10.0, 10.0
        for _ in range(50):
            mid = 0.5 * (lo + hi)
            lo, hi = (mid, hi) if click_rate_at(mid, cols, patterns) \
                > click_rate else (lo, mid)
        _CUTS[key] = np.float32(0.5 * (lo + hi))
    return _CUTS[key]


def blocks(rows: int) -> list:
    """[(lo, hi)] of the row blocks."""
    return [(lo, min(lo + BLOCK_ROWS, rows))
            for lo in range(0, rows, BLOCK_ROWS)]


def draw_block(block: int, n: int, cols: int, seed: int, params: dict,
               out=None):
    """([cols, n] float32 columns, [n] float32 labels) of row block
    ``block``: its first ``n`` rows (``n <= BLOCK_ROWS``). ``out``, a
    [cols, BLOCK_ROWS] float32 buffer, is filled and a view of it returned:
    a thread that draws block after block touches its pages once."""
    k = surface_constants(cols)
    kinds = column_kinds(cols)
    x = np.empty((cols, n), np.float32) if out is None else out[:, :n]
    logit = np.random.default_rng([cols, seed, block]).standard_normal(
        n, dtype=np.float32)
    logit *= k["noise"]
    tmp = np.empty(n, np.float32)
    step = {int(j): i for i, j in enumerate(k["step_cols"])}
    for j in range(cols):
        lat = x[j]
        np.random.default_rng([j, seed, block]).standard_normal(
            out=lat, dtype=np.float32)
        if j in step:
            i = step[j]
            np.multiply(lat > k["step_at"][i], k["step_amp"][i], out=tmp)
        else:
            np.multiply(lat, k["weights"][j], out=tmp)
        logit += tmp
    for j in range(cols):    # latent -> feature, in place
        col = x[j]
        col *= k["scale"][j]
        col += k["shift"][j]
        if kinds[j] == "count":
            np.floor(np.exp(col, out=col), out=col)
        else:
            np.negative(col, out=col)
            np.exp(col, out=col)
            col += 1.0
            np.reciprocal(col, out=col)
    y = (logit > threshold(params["click_rate"], cols)).astype(np.float32)
    return x, y


def generate(rows: int, cols: int, seed: int, params: dict):
    """([cols, rows] float32 columns, [rows] float32 labels): every block,
    one after the other."""
    x = np.empty((cols, rows), np.float32)
    y = np.empty(rows, np.float32)
    for b, (lo, hi) in enumerate(blocks(rows)):
        x[:, lo:hi], y[lo:hi] = draw_block(b, hi - lo, cols, seed, params)
    return x, y
