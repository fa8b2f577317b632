"""Ranking objectives: LambdaRank and XE-NDCG.

TPU-native analog of the reference ranking objectives
(``src/objective/rank_objective.hpp``: ``LambdarankNDCG``,
``RankXENDCG``).

Design (TPU-first): the reference loops per query over doc pairs with
OpenMP. Here the queries are laid out once, at init, in **buckets by
length** (:class:`QueryLayout`): a bucket of width ``W`` holds the
queries of ``W/2 < n <= W`` rows (half steps from 128 up) as a dense
``[Q_b, W]`` row-index lattice, so the slots an iteration touches are a
small multiple of the rows there are, whatever the longest query. Per
bucket an iteration is one gather of the scores into the lattice and
dense vector work on it; at the end one gather, by an index fixed at
init, takes all buckets' gradients back to rows. Nothing is sorted and
nothing scattered by a computed index: a doc's rank in its query's score
order is a count over a ``[Q_b, W, W]`` comparison (ties by row order,
like the reference's stable sort).

LambdaRank's pair set is the reference's own loop: ``i`` inside the
truncation window of the score order, ``j`` behind it:
``T = min(lambdarank_truncation_level, W)`` passes over the ``[Q_b, W]``
lattice, not a ``[W, W]`` tensor. Pass ``t`` picks the doc at rank ``t``
out of every query (a one-hot sum), pairs it with the docs behind it,
which stay in row order, and its own lambda goes back the same way.

The index lattices and per-query constants are device arrays, placed
once (``_RankingBase.device_state``, first read under the trainer's
``gbdt.to_device`` span) and handed to the fused step as arguments
(``device_state``), never closed over: at MS-LTR's size they are tens of
megabytes.
"""

from __future__ import annotations

import functools
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from . import phases, profiler
from .objectives import Objective

__all__ = ["LambdaRank", "RankXENDCG", "QueryLayout", "bucket_width"]

# what a pad slot holds for a row index: past any score vector, so a
# gather fills it
_PAD_ROW = 1 << 30
_MIN_WIDTH = 8
_HALF_STEPS_FROM = 128


def bucket_width(n: np.ndarray) -> np.ndarray:
    """Smallest width of the ladder 8, 16, 32, 64, 128, 192, 256, 384,
    512, 768, 1024, 1536, ... (powers of two, and from 128 up their
    half steps) that holds ``n`` rows."""
    n = np.maximum(np.asarray(n, np.int64), 1)
    p = np.maximum(1 << np.ceil(np.log2(n)).astype(np.int64), _MIN_WIDTH)
    p = np.where(p < n, p * 2, p)       # log2 rounding at exact powers
    half = 3 * p // 4
    return np.where((half >= n) & (half > _HALF_STEPS_FROM), half, p)


class QueryLayout:
    """Queries bucketed by length. ``buckets`` is a list of host dicts,
    one per width in use, ascending: ``rows`` ``[Q_b, W]`` int32 (row id,
    ``_PAD_ROW`` in pad slots), ``n`` ``[Q_b]`` int32 rows a query,
    ``query`` ``[Q_b]`` the queries' ids. ``slot_of_row`` ``[rows]`` is
    each row's slot in the buckets' lattices laid end to end. Empty
    queries hold no row and are in no bucket."""

    def __init__(self, query_boundaries: np.ndarray):
        qb = np.asarray(query_boundaries, np.int64)
        sizes = np.diff(qb)
        if len(sizes) and int(qb[-1]) >= _PAD_ROW:
            raise ValueError("ranking supports up to 2^30 rows")
        self.num_queries = int(len(sizes))
        self.max_query = int(sizes.max()) if len(sizes) else 0
        self.pairs = int(np.sum(sizes * sizes))
        widths = bucket_width(sizes)
        self.buckets: List[Dict[str, np.ndarray]] = []
        self.slot_of_row = np.zeros(int(qb[-1]) if len(qb) else 0, np.int32)
        self.slots = 0
        for w in np.unique(widths[sizes > 0]):
            qs = np.flatnonzero((widths == w) & (sizes > 0))
            lane = np.arange(w, dtype=np.int64)[None, :]
            rows = qb[qs][:, None] + lane
            real = lane < sizes[qs][:, None]
            slot = self.slots + np.arange(rows.size).reshape(rows.shape)
            self.slot_of_row[rows[real]] = slot[real]
            rows[~real] = _PAD_ROW
            self.slots += rows.size
            self.buckets.append({
                "rows": rows.astype(np.int32),
                "n": sizes[qs].astype(np.int32), "query": qs})

    def lattice(self, per_row: np.ndarray, bucket: dict, fill) -> np.ndarray:
        """``per_row`` in the bucket's ``[Q_b, W]`` layout."""
        rows = bucket["rows"]
        pad = rows == _PAD_ROW
        out = np.asarray(per_row)[np.where(pad, 0, rows)]
        out[pad] = fill
        return out


def _gather(per_row, rows, fill):
    """``per_row`` into a bucket's lattice; pad slots read ``fill``."""
    with profiler.stage(phases.RANK_GATHER):
        return per_row.at[rows].get(mode="fill", fill_value=fill)


def _to_rows(parts, slot_of_row, num_rows: int, weight):
    """Every bucket's ``[Q_b, W]`` ``(g, h)`` back to ``[R]``: each row
    reads its own slot of the lattices laid end to end; rows past the
    data (the trainer's padding) read zero."""
    with profiler.stage(phases.RANK_SCATTER):
        out = []
        for k in (0, 1):
            v = jnp.concatenate([p[k].reshape(-1) for p in parts])
            v = jnp.pad(v[slot_of_row], (0, num_rows - slot_of_row.shape[0]))
            out.append(v if weight is None else v * weight)
        return out[0], out[1]


def _gain(y, label_gain):
    """``label_gain[y]`` as a chain of selects: the table has a handful
    of entries and a lookup per slot would be a gather."""
    gain = jnp.zeros(y.shape, jnp.float32)
    for k, v in enumerate(label_gain):
        if v:
            gain = jnp.where(y == k, jnp.float32(v), gain)
    return gain


def _lambdarank_bucket(score, b, trunc, norm, sig, label_gain):
    """One bucket's lambdas and hessians, ``[Q_b, W]`` each, in the
    lattice's own (row) order."""
    rows, n, y = b["rows"], b["n"], b["label"]
    W = rows.shape[1]
    s = _gather(score, rows, -jnp.inf)
    lane = jnp.arange(W, dtype=jnp.int32)
    valid = lane[None, :] < n[:, None]
    with profiler.stage(phases.RANK_SORT):
        # rank = docs ahead in score order: a higher score, or the same
        # score and an earlier row (the reference's stable sort); pad
        # slots (-inf) are ahead of no one
        ahead = ((s[:, None, :] > s[:, :, None])
                 | ((s[:, None, :] == s[:, :, None])
                    & (lane[None, None, :] < lane[None, :, None])))
        rank = ahead.sum(axis=2, dtype=jnp.int32)
        # 1 / log2(2 + rank) from a table made on the host: a pair's
        # delta is a difference of two discounts, which cancels to 1e-3
        # of them at the window's edge, and the device's log2 is good to
        # about 1e-6. Read by a one-hot sum like the count above, not by
        # a gather a slot.
        table = jnp.asarray(1.0 / np.log2(2.0 + np.arange(W)), s.dtype)
        disc = jnp.where(rank[:, :, None] == lane[None, None, :],
                         table[None, None, :], 0.0).sum(axis=2)
    with profiler.stage(phases.RANK_PAIRS):
        s = jnp.where(valid, s, 0.0)
        gain = _gain(y, label_gain)
        inv = b["inv_max_dcg"][:, None]
        # regularise by the score distance unless the query's scores are
        # all equal (best_score != worst_score)
        spread = (jnp.max(jnp.where(valid, s, -jnp.inf), axis=1)
                  != jnp.min(jnp.where(valid, s, jnp.inf), axis=1))[:, None]

        def window_doc(t, carry):
            """The pairs of the doc at rank ``t`` (``i``) with every doc
            behind it (``j``), all queries at once: ``[Q_b, W]``. The doc
            is picked out of its query by a one-hot sum, and its own
            lambda goes back the same way."""
            g, h, total = carry
            at = rank == t

            def pick(x):
                return jnp.where(at, x, 0.0).sum(axis=1, keepdims=True)
            s_i, y_i, gain_i = pick(s), pick(y), pick(gain)
            i_up = y_i > y                          # i has the label
            pair = (t < rank) & valid & (y_i != y)
            ds = jnp.where(i_up, s_i - s, s - s_i)  # high minus low
            dgain = jnp.where(i_up, gain_i - gain, gain - gain_i)
            delta = dgain * jnp.abs(table[t] - disc) * inv
            if norm:
                delta = jnp.where(spread, delta / (0.01 + jnp.abs(ds)),
                                  delta)
            rho = 1.0 / (1.0 + jnp.exp(sig * ds))
            lam = jnp.where(pair, sig * rho * delta, 0.0)
            hes = jnp.where(pair, sig * sig * rho * (1.0 - rho) * delta,
                            0.0)
            # the high doc's lambda falls by lam, the low one's rises
            lam_i = jnp.where(i_up, -lam, lam)
            g = g - lam_i + jnp.where(
                at, lam_i.sum(axis=1, keepdims=True), 0.0)
            h = h + hes + jnp.where(at, hes.sum(axis=1, keepdims=True), 0.0)
            return g, h, total + 2.0 * lam.sum(axis=1, keepdims=True)

        zero = jnp.zeros(s.shape, s.dtype)
        g, h, sum_lam = jax.lax.fori_loop(
            0, min(trunc, W), window_doc,
            (zero, zero, jnp.zeros((s.shape[0], 1), s.dtype)))
        if norm:
            nf = jnp.where(
                sum_lam > 0,
                jnp.log2(1.0 + sum_lam) / jnp.where(sum_lam > 0, sum_lam, 1.0),
                1.0)
            g, h = g * nf, h * nf
    return g, h


# One program a call, shared by every objective of one configuration and
# layout; inside the fused step it is inlined. The layout is an argument.
@functools.partial(jax.jit,
                   static_argnames=("trunc", "norm", "sig", "label_gain"))
def _lambdarank_gradients(score, weight, state, *, trunc, norm, sig,
                          label_gain):
    parts = [_lambdarank_bucket(score, b, trunc, norm, sig, label_gain)
             for b in state["buckets"]]
    return _to_rows(parts, state["slot_of_row"], score.shape[0], weight)


@jax.jit
def _xendcg_gradients(score, weight, state, gam):
    parts = []
    for b in state["buckets"]:
        rows = b["rows"]
        s = _gather(score, rows, -jnp.inf)
        gamma = _gather(gam, rows, 0.0)
        # the per-query lattice of this objective: a softmax and a sum
        # along the query, no pairs
        with profiler.stage(phases.RANK_PAIRS):
            valid = (jnp.arange(rows.shape[1], dtype=jnp.int32)[None, :]
                     < b["n"][:, None])
            rho = jnp.where(valid, jax.nn.softmax(s, axis=1), 0.0)
            phi = jnp.where(valid, jnp.exp2(b["label"]) - gamma, 0.0)
            denom = jnp.maximum(phi.sum(axis=1, keepdims=True), 1e-20)
            g = jnp.where(valid, rho - phi / denom, 0.0)
            h = jnp.where(valid, jnp.maximum(rho * (1.0 - rho), 1e-16), 0.0)
        parts.append((g, h))
    return _to_rows(parts, state["slot_of_row"], score.shape[0], weight)


class _RankingBase(Objective):
    is_ranking = True

    def init(self, label, weight, query_boundaries=None, position=None):
        if query_boundaries is None:
            raise ValueError(
                f"{self.name} objective requires query/group information")
        super().init(label, weight, query_boundaries)
        self._dev = None
        with profiler.span("objective.init") as fields:
            self._init_positions(position, len(label))
            self.layout = QueryLayout(query_boundaries)
            self._host_state = [
                {"rows": b["rows"], "n": b["n"],
                 "label": self.layout.lattice(label, b, 0).astype(np.float32)}
                for b in self.layout.buckets]
            self.counters = {
                "queries": self.layout.num_queries,
                "max_query": self.layout.max_query,
                "slots": self.layout.slots,
                "pairs": self.layout.pairs,
                "pair_slots": 0}
            self._init_tables(np.asarray(label))
            fields.update(self.counters)

    def _init_tables(self, label: np.ndarray) -> None:
        """Per-query constants of the subclass, added to ``_host_state``;
        runs inside the ``objective.init`` span."""

    def _init_positions(self, position, num_rows: int) -> None:
        # unbiased lambdarank positions (Metadata::positions): factorize
        # arbitrary ids/names into [n] int32 indices + the id table
        if position is not None:
            position = np.asarray(position).reshape(-1)
            if len(position) != num_rows:
                raise ValueError(
                    f"positions has {len(position)} entries but the "
                    f"dataset has {num_rows} rows (Metadata positions "
                    "size check)")
            self.position_ids, pos_idx = np.unique(
                position, return_inverse=True)
            self.positions = pos_idx.astype(np.int32)
            self.num_position_ids = int(len(self.position_ids))
        else:
            self.position_ids = None
            self.positions = None
            self.num_position_ids = 0

    # -- the device's copy of the layout -----------------------------------
    @property
    def device_state(self):
        """What :meth:`get_gradients` reads of the layout: a pytree of
        device arrays, placed at the first read (the trainer's, under
        ``gbdt.to_device``). The fused step takes it as an argument and
        rebinds it (``bind_device_state``) while it traces."""
        if self._dev is None:
            self._dev = {
                "buckets": [{k: jnp.asarray(v) for k, v in b.items()}
                            for b in self._host_state],
                "slot_of_row": jnp.asarray(self.layout.slot_of_row)}
        return self._dev

    def bind_device_state(self, state):
        prev, self._dev = self._dev, state
        return prev


class LambdaRank(_RankingBase):
    """LambdaMART gradients with NDCG deltas
    (rank_objective.hpp LambdarankNDCG)."""

    name = "lambdarank"

    def _init_tables(self, label):
        cfg = self.cfg
        # position-bias factors (RankingObjective, rank_objective.hpp:30-68:
        # pos_biases_ + learning_rate_ + position_bias_regularization_)
        if self.num_position_ids:
            self.pos_biases = jnp.zeros((self.num_position_ids,),
                                        jnp.float32)
            self._pb_lr = float(cfg.learning_rate)
            self._pb_reg = float(
                cfg.lambdarank_position_bias_regularization)
        max_label = int(np.max(label)) if len(label) else 0
        lg = list(cfg.label_gain)
        if not lg:
            # default label gain: 2^i - 1 (config.h label_gain default)
            lg = [(1 << i) - 1 for i in range(max(max_label + 1, 2))]
        if max_label >= len(lg):
            raise ValueError("label_gain table shorter than max label")
        self.label_gain = np.asarray(lg, dtype=np.float64)
        self.trunc = int(cfg.lambdarank_truncation_level)
        self.norm = bool(cfg.lambdarank_norm)
        self.sig = float(cfg.sigmoid)
        # per-query inverse max DCG at truncation (DCGCalculator analog):
        # a bucket's gains sorted descending, the first `trunc` discounted
        self.inverse_max_dcg = np.zeros(self.layout.num_queries)
        for b, host in zip(self.layout.buckets, self._host_state):
            gains = np.where(
                b["rows"] != _PAD_ROW,
                self.label_gain[host["label"].astype(np.int64)], 0.0)
            top = -np.sort(-gains, axis=1)[:, : self.trunc]
            dcg = top @ (1.0 / np.log2(np.arange(2, 2 + top.shape[1])))
            inv = np.where(dcg > 0, 1.0 / np.where(dcg > 0, dcg, 1.0), 0.0)
            self.inverse_max_dcg[b["query"]] = inv
            host["inv_max_dcg"] = inv.astype(np.float32)
            # pair positions an iteration evaluates for this bucket: the
            # [W, W] comparison that ranks a query's docs, the [W, W]
            # one-hot that reads their discounts, and the [T, W] lattice
            # of the lambdas
            width = b["rows"].shape[1]
            self.counters["pair_slots"] += (
                len(inv) * (2 * width + min(self.trunc, width)) * width)

    def get_gradients(self, score, label, weight, it=None):
        if self.num_position_ids:
            # score_adjusted = score + pos_biases[position]
            # (rank_objective.hpp:69-75)
            pos = jnp.asarray(self.positions)
            score = score.at[: pos.shape[0]].add(self.pos_biases[pos])
        g, h = _lambdarank_gradients(
            score, weight, self.device_state, trunc=self.trunc,
            norm=self.norm, sig=self.sig,
            label_gain=tuple(float(v) for v in self.label_gain))
        if self.num_position_ids:
            self._update_position_bias(g, h)
        return g, h

    def _update_position_bias(self, g, h):
        """Newton-Raphson step on the per-position bias factors
        (UpdatePositionBiasFactors, rank_objective.hpp:296-334):
        d(utility)/d(bias_p) = -sum of lambdas at position p, minus L2
        regularization scaled by the instance count. Runs eagerly once
        per iteration; the segment sums are on-device."""
        n = len(self.positions)
        P = self.num_position_ids
        pos = jnp.asarray(self.positions)
        first = -jax.ops.segment_sum(g[:n], pos, num_segments=P)
        second = -jax.ops.segment_sum(h[:n], pos, num_segments=P)
        count = jax.ops.segment_sum(jnp.ones((n,), jnp.float32), pos,
                                    num_segments=P)
        first = first - self.pos_biases * self._pb_reg * count
        second = second - self._pb_reg * count
        self.pos_biases = self.pos_biases + (
            self._pb_lr * first / (jnp.abs(second) + 0.001))


class RankXENDCG(_RankingBase):
    """Cross-entropy NDCG surrogate (rank_objective.hpp RankXENDCG)."""

    name = "rank_xendcg"

    def _init_tables(self, label):
        # positions are accepted but bias factors stay zero — the
        # reference only learns them for lambdarank (the base-class
        # UpdatePositionBiasFactors is a no-op, rank_objective.hpp:98)
        self.seed = int(self.cfg.objective_seed)

    def gammas(self, it, num_rows: int, dtype=jnp.float32):
        """The iteration's uniform draw, one a row (so the draw does not
        depend on the layout)."""
        if it is None:
            it = jnp.asarray(0, jnp.int32)
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed), it)
        return jax.random.uniform(key, (num_rows,), dtype=dtype)

    def get_gradients(self, score, label, weight, it=None):
        return _xendcg_gradients(
            score, weight, self.device_state,
            self.gammas(it, score.shape[0], score.dtype))
