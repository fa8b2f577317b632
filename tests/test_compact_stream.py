"""The compacted stream's index (``tree_builder.stream_index``): one
sort of the row numbers, live rows first in row order.

The kernel adds a leaf's rows in stream order, so the ORDER of the live
prefix decides the float32 sums and with them bit-identity; what lies
past the prefix is read by nobody (``build_histograms(row_gather=,
num_rows=)`` counts those positions as dead). Whole trainings are held
to their model text by ``tests/test_round_select.py``'s five families.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.boosting.tree_builder import stream_index


def _mask(kind, R):
    rng = np.random.RandomState(R)
    m = np.zeros(R, bool)
    if kind == "all":
        m[:] = True
    elif kind == "one":
        m[R // 3] = True
    elif kind == "last":
        m[-1] = True
    elif kind != "none":
        m = rng.rand(R) < float(kind)
    return m


def _check(m, c_idx, n):
    want = np.flatnonzero(m)
    assert int(n) == want.size == int(m.sum())
    c = np.asarray(c_idx)
    assert c.dtype == np.int32 and c.shape == m.shape
    np.testing.assert_array_equal(c[:want.size], want)
    # the tail is dead rows in no promised order: each row once
    np.testing.assert_array_equal(np.sort(c[want.size:]),
                                  np.flatnonzero(~m))


@pytest.mark.parametrize("kind,R", [
    ("none", 4096), ("all", 4096), ("one", 4096), ("last", 4096),
    ("0.01", 8192), ("0.15", 8192), ("0.5", 8192),
    ("0.15", 5003), ("last", 5003)])
def test_prefix_is_the_live_rows_in_row_order(kind, R):
    m = _mask(kind, R)
    _check(m, *jax.jit(stream_index)(jnp.asarray(m)))


def test_under_vmap_over_a_class_axis():
    """``class_batch``: the grow loop runs under ``vmap`` over K, so the
    sort is batched over the class axis, rows last."""
    R = 3001
    ms = np.stack([_mask(k, R) for k in ("0.15", "none", "0.5")])
    c, n = jax.jit(jax.vmap(stream_index))(jnp.asarray(ms))
    assert c.shape == (3, R) and n.shape == (3,)
    for k in range(3):
        _check(ms[k], c[k], n[k])


def test_under_shard_map_each_shard_indexes_its_own_rows():
    """``tree_learner=data``: a shard's index counts its own rows from
    0 and no collective enters (the lowered text holds none)."""
    from jax.sharding import Mesh, PartitionSpec as P
    if len(jax.devices()) < 4:
        pytest.skip("needs four virtual devices")
    n_sh, R = 4, 2048
    m = _mask("0.15", n_sh * R)
    m[R:2 * R] = False            # one shard with no live row
    mesh = Mesh(np.array(jax.devices()[:n_sh]), ("d",))

    def f(mm):
        c, n = stream_index(mm)
        return c, n[None]
    g = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("d"),
                              out_specs=(P("d"), P("d"))))
    c, n = g(jnp.asarray(m))
    for s in range(n_sh):
        _check(m[s * R:(s + 1) * R], c[s * R:(s + 1) * R], n[s])
    text = g.lower(jnp.asarray(m)).as_text()
    for op in ("all_reduce", "all_gather", "all_to_all",
               "collective_permute", "reduce_scatter"):
        assert op not in text, op


def test_fused_step_makes_the_index_with_one_sort_and_nothing_else():
    """Under the ``compact`` scope of the fused step of a small binary
    booster: one ``sort``, and no scatter, cumsum or ``reduce_window``
    (the formulation PR 33 deleted made the positions by a cumsum and
    wrote the index by an R-sized scatter, which XLA:TPU lowers through
    a sort of its own)."""
    from lightgbm_tpu.analysis.doctor import (_fused_trace_args,
                                              make_booster)
    from lightgbm_tpu.analysis.jaxpr_lint import _iter_scoped
    from lightgbm_tpu.phases import COMPACT
    from lightgbm_tpu.telemetry.xprof import stage_of_path
    bst = make_booster("plain", "serial", hist_impl="scatter")
    gb = bst._gbdt
    closed = jax.make_jaxpr(gb._fused_step_entry)(*_fused_trace_args(gb))
    prims = [eqn.primitive.name for eqn, stack in _iter_scoped(closed.jaxpr)
             if stage_of_path(stack) == COMPACT]
    assert prims.count("sort") == 1, prims
    banned = [p for p in prims
              if p.startswith(("scatter", "cumsum", "cumlogsumexp",
                               "cummax", "cumprod", "reduce_window"))]
    assert not banned, banned
    # the stage is the membership compares, the sort and the count
    assert "reduce_sum" in prims and "eq" in prims, prims
