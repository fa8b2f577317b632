"""Allstate-shaped seeded data: the one-hot coded insurance table of the
reference's own experiment (13,184,290 rows x 4,228 columns), handed over
the way its users hand it over: as **CSR rows**, 32 stored values a row.
The dense table (223 GB of float32) is never made; the whole CSR is 3.4 GB.

THE FIELD TABLE is a constant of the configuration (``FIELDS``, columns in
this order):

- 16 numeric columns: every row stores a value (a unit normal, float32), so
  they are dense and continuous: 255 bins each, a stored column each.
- 16 categorical fields, one-hot coded into 4,212 columns, exactly one level
  a field a row. Three vehicle fields are **nested**: a submodel (2,765)
  belongs to one model (1,303), a model to one make (75); then twelve small
  fields of 10, 3, 6, 3, 3, 5, 4, 3, 2, 3, 6, 6 levels, and one of 15.

Level popularity is heavy-tailed over a floor (:func:`field_table`): a
Zipf head (exponent ``ZIPF``) laid over ``floor`` for every level, so the
rarest submodel is still expected 50 times in the program's bin sample of
200,000 rows.

WHY THE TABLE LOOKS AS IT DOES: the program's bundle plan is greedy over a
sample, and the compiled step's shape hangs on the number of bundles it
makes, so the table is built so that no seed can change that number
(``PERF.md`` section 4 has the argument in full):

- every level of a small field has a popularity of at least 2% and at most
  45% (the field of two levels: 56 / 44): popular levels of different
  fields always meet in the sample, so each small field fills a bundle of
  its own, which then covers every row and admits nothing else;
- the nesting makes every meeting among the three vehicle fields a matter
  of structure and not of chance: a model meets its own make in every one
  of its rows and no other make in any, a submodel likewise its own model;
- every model has at least two submodels, so it is twice as popular as the
  floor and is always placed before the last few hundred (floor-level)
  submodels, which then find their parents in bundles that are full, meet
  nothing, and fill the open bundles in turn: the count is the bin
  budget's, ``ceil(4,068 / 85)`` bundles for models and submodels.

THE LABEL is a claim (under 1% of rows). Its surface is a constant: a step
on each of four numeric columns, and effects of the two most popular levels
of the field of 15, of three small fields and of the make, with amplitudes
that fall off rung by rung (``STEPS``, ``LEVEL_EFFECTS``), and unit noise. The
**number of positives is exact**: block ``b`` labels its ``k_b`` highest
logits, with ``sum k_b = round(rate x rows)``, so the initial score and
tree 0's three addends are the same at every seed (the rate is chosen far
from a bfloat16 rounding boundary for all three; the configuration's
``base_rate`` block). The seed draws the rows.

Rows come in blocks of ``BLOCK_ROWS``; block ``b`` is drawn from
``default_rng([seed, b])`` alone, by any number of threads.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK_ROWS = 1 << 19
NUMERIC = 16
VEHICLE = (75, 1303, 2765)                # make, model, submodel (nested)
SMALL = (10, 3, 6, 3, 3, 5, 4, 3, 2, 3, 6, 6, 15)
FIELDS = VEHICLE + SMALL                  # 16 fields, 4,212 levels
COLS = NUMERIC + sum(FIELDS)              # 4,228
SAMPLE_ROWS = 200_000                     # the program's bin sample
FLOOR_EXPECTED = 50                       # a submodel, in the bin sample
SMALL_FLOOR = 0.02
ZIPF = 1.0
# popularity of the levels of the smallest fields, stated outright so that
# no level comes near one half (a one-hot column that is 1 in half its rows
# has no steady most frequent bin)
SMALL_TABLES = {2: (0.56, 0.44), 3: (0.42, 0.33, 0.25),
                4: (0.40, 0.28, 0.19, 0.13)}
NOISE_SHARE = 0.55       # of the logit's variance


def _zipf_over_floor(n: int, floor: float, s: float) -> np.ndarray:
    z = 1.0 / np.arange(1, n + 1) ** s
    return floor + (1.0 - n * floor) * z / z.sum()


_TABLES: dict = {}


def spec_of(params: dict) -> tuple:
    """(numeric columns, the three nested cardinalities, the small
    fields' cardinalities): the configuration's constants, which a test
    may shrink through ``params`` (``numeric``, ``vehicle``, ``small``,
    ``sample_rows``)."""
    return (int(params.get("numeric", NUMERIC)),
            tuple(params.get("vehicle", VEHICLE)),
            tuple(params.get("small", SMALL)),
            int(params.get("sample_rows", SAMPLE_ROWS)))


def columns(params: dict) -> int:
    numeric, vehicle, small, _ = spec_of(params)
    return numeric + sum(vehicle) + sum(small)


def field_table(params: dict = {}) -> dict:
    """What every seed shares: ``offsets`` first column of each field,
    ``cdf_sub`` / ``cdf_small`` (the cumulative popularity of a drawn
    field's levels), ``model_of`` [submodels], ``make_of`` [models], and
    ``popularity`` of every one-hot column. The submodel is drawn; model
    and make follow."""
    spec = spec_of(params)
    if spec in _TABLES:
        return _TABLES[spec]
    numeric, vehicle, small_cards, sample_rows = spec
    fields = vehicle + small_cards
    n_make, n_model, n_sub = vehicle
    p_sub = _zipf_over_floor(n_sub, FLOOR_EXPECTED / sample_rows, ZIPF)
    # every model has two submodels (ranks m and 1303 + m) and the most
    # popular 159 a third
    model_of = np.arange(n_sub) % n_model
    p_model = np.bincount(model_of, weights=p_sub, minlength=n_model)
    # makes: sizes fall off like 1 / rank (at least three models each),
    # models dealt round robin over the makes that still have room
    size = np.maximum(3, np.floor(
        (n_model - 3 * n_make) / np.sum(1.0 / np.arange(1, n_make + 1))
        / np.arange(1, n_make + 1)).astype(int) + 3)
    size[0] += n_model - size.sum()
    make_of = np.empty(n_model, np.int64)
    room, k = size.copy(), 0
    for m in range(n_model):
        while room[k % n_make] == 0:
            k += 1
        make_of[m] = k % n_make
        room[k % n_make] -= 1
        k += 1
    p_make = np.bincount(make_of, weights=p_model, minlength=n_make)
    small = [np.asarray(SMALL_TABLES[c]) if c in SMALL_TABLES
             else _zipf_over_floor(c, SMALL_FLOOR, 0.8) for c in small_cards]
    offsets = numeric + np.concatenate([[0], np.cumsum(fields)[:-1]])
    table = _TABLES[spec] = dict(
        numeric=numeric, fields=fields,
        offsets=offsets.astype(np.int32),
        model_of=model_of.astype(np.int32), make_of=make_of.astype(np.int32),
        cdf_sub=np.cumsum(p_sub), cdf_small=[np.cumsum(p) for p in small],
        popularity=np.concatenate([p_make, p_model, p_sub] + small))
    return table


# the label surface's effects, strongest first: (what, where, amplitude).
# Amplitudes fall by about three quarters a rung, so that a tree's first
# cuts have one order at every seed (with effects drawn alike, two of
# nearly equal gain swapped places between seeds, the rows a round streams
# moved by 5% of the table with them, and one seed in six read 1.1% under
# the others: PERF.md section 2)
STEPS = ((1, 0.6, 0.60), (14, 0.2, 0.34), (4, -0.3, 0.11), (9, 1.1, -0.08))
LEVEL_EFFECTS = ((15, 0.45), (5, 0.25), (8, 0.19), (0, 0.14), (3, 0.06))


def surface(params: dict = {}) -> dict:
    """The label surface, a constant: ``steps`` [(numeric column, value the
    step lies at, amplitude)] and ``effects`` by field index (the field of
    15, two small fields, the make, one more small field): the field's most
    popular level raises the logit by the amplitude, its second lowers it
    by half of that, every other level leaves it. Unit noise on top; only
    the logit's order matters."""
    t = field_table(params)
    numeric, last = t["numeric"], len(t["fields"]) - 1
    effects = {}
    for f, amp in LEVEL_EFFECTS:
        e = np.zeros(t["fields"][min(f, last)], np.float32)
        e[:2] = amp, -amp / 2
        effects.setdefault(min(f, last), e)
    return {"steps": [(j % numeric, np.float32(at), np.float32(amp))
                      for j, at, amp in STEPS],
            "effects": effects, "noise": np.float32(np.sqrt(NOISE_SHARE))}


def blocks(rows: int) -> list:
    """[(lo, hi)] of the row blocks."""
    return [(lo, min(lo + BLOCK_ROWS, rows))
            for lo in range(0, rows, BLOCK_ROWS)]


def positives(rows: int, rate: float) -> int:
    return int(round(rate * rows))


def draw_block(block: int, lo: int, hi: int, rows: int, seed: int,
               params: dict):
    """(indices [n, 32] int32 ascending along a row, values [n, 32]
    float32, labels [n] float32) of rows ``lo:hi``, which are block
    ``block`` of a table of ``rows`` rows."""
    t, s = field_table(params), surface(params)
    numeric, fields = t["numeric"], t["fields"]
    n = hi - lo
    rng = np.random.default_rng([seed, block])
    values = np.ones((n, numeric + len(fields)), np.float32)
    indices = np.empty((n, numeric + len(fields)), np.int32)
    x = rng.standard_normal((n, numeric), dtype=np.float32)
    x[x == 0] = np.float32(1e-6)          # a stored value is never 0.0
    values[:, :numeric] = x
    indices[:, :numeric] = np.arange(numeric, dtype=np.int32)
    level = np.empty((n, len(fields)), np.int32)
    sub = np.searchsorted(t["cdf_sub"], rng.random(n), side="right")
    level[:, 2] = np.minimum(sub, fields[2] - 1)
    level[:, 1] = t["model_of"][level[:, 2]]
    level[:, 0] = t["make_of"][level[:, 1]]
    for i, cdf in enumerate(t["cdf_small"]):
        lv = np.searchsorted(cdf, rng.random(n), side="right")
        level[:, 3 + i] = np.minimum(lv, len(cdf) - 1)
    indices[:, numeric:] = level + t["offsets"][None, :]

    logit = rng.standard_normal(n, dtype=np.float32) * s["noise"]
    for j, at, amp in s["steps"]:
        logit += amp * (x[:, j] > at)
    for f, e in s["effects"].items():
        logit += e[level[:, f]]
    total = positives(rows, params["positive_rate"])
    k = total * hi // rows - total * lo // rows
    y = np.zeros(n, np.float32)
    if k:
        y[np.argpartition(logit, n - k)[n - k:]] = 1.0
    return indices, values, y


def threads() -> int:
    return max(1, min(12, (os.cpu_count() or 2) - 1))


def generate_csr(rows: int, cols: int, seed: int, params: dict, out=None):
    """(scipy.sparse.csr_matrix [rows, cols] float32 with int32 indices,
    32 stored values a row in ascending column order; labels [rows]
    float32). Blocks are drawn on a few threads straight into the
    matrix's own arrays: ``out`` = (indices, values, labels), flat, where
    the caller wants them in memory of its own."""
    import scipy.sparse as sp
    if cols != columns(params):
        raise ValueError(f"the field table has {columns(params)} columns, "
                         f"not {cols}")
    per_row = spec_of(params)[0] + len(field_table(params)["fields"])
    if out is None:
        out = (np.empty(rows * per_row, np.int32),
               np.empty(rows * per_row, np.float32),
               np.empty(rows, np.float32))
    flat_i, flat_v, y = out
    indices = flat_i.reshape(rows, per_row)
    values = flat_v.reshape(rows, per_row)

    def fill(item):
        b, (lo, hi) = item
        indices[lo:hi], values[lo:hi], y[lo:hi] = draw_block(
            b, lo, hi, rows, seed, params)
    with ThreadPoolExecutor(threads()) as ex:
        for _ in ex.map(fill, enumerate(blocks(rows))):
            pass
    indptr = np.arange(0, rows * per_row + 1, per_row,
                       dtype=np.int32 if rows * per_row < 2 ** 31
                       else np.int64)
    x = sp.csr_matrix((flat_v, flat_i, indptr), shape=(rows, cols))
    x.has_sorted_indices = True
    return x, y


def generate(rows: int, cols: int, seed: int, params: dict):
    """The entry every generator has (the harness looks for it by this
    name). Here it hands back :func:`generate_csr`'s CSR matrix and not the
    dense ``[cols, rows]`` table of the other generators, which at this
    shape would be 223 GB of float32: job kind ``train-sparse`` is the
    caller."""
    return generate_csr(rows, cols, seed, params)
