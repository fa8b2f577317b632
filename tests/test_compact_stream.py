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


@pytest.fixture(scope="module")
def fused_step_eqns():
    """``stage -> [equation]`` of the fused step of a small binary
    booster (traced with ``hist_impl=scatter``: no kernel to lower)."""
    from lightgbm_tpu.analysis.doctor import (_fused_trace_args,
                                              make_booster)
    from lightgbm_tpu.analysis.jaxpr_lint import _iter_scoped
    from lightgbm_tpu.telemetry.xprof import stage_of_path
    gb = make_booster("plain", "serial", hist_impl="scatter")._gbdt
    closed = jax.make_jaxpr(gb._fused_step_entry)(*_fused_trace_args(gb))
    by_stage = {}
    for eqn, stack in _iter_scoped(closed.jaxpr):
        by_stage.setdefault(stage_of_path(stack), []).append(eqn)
    return by_stage


def test_fused_step_makes_the_index_with_one_sort_and_nothing_else(
        fused_step_eqns):
    """Under the ``compact`` scope of the fused step of a small binary
    booster: one ``sort``, and no scatter, cumsum or ``reduce_window``
    (the formulation PR 33 deleted made the positions by a cumsum and
    wrote the index by an R-sized scatter, which XLA:TPU lowers through
    a sort of its own)."""
    from lightgbm_tpu.phases import COMPACT
    prims = [e.primitive.name for e in fused_step_eqns[COMPACT]]
    assert prims.count("sort") == 1, prims
    banned = [p for p in prims
              if p.startswith(("scatter", "cumsum", "cumlogsumexp",
                               "cummax", "cumprod", "reduce_window"))]
    assert not banned, banned
    # the stage is the membership compares, the sort and the count
    assert "reduce_sum" in prims and "eq" in prims, prims


def test_fused_step_gathers_twice_a_trip_under_hist_gather(fused_step_eqns):
    """Under the ``hist_gather`` scope of the same fused step: exactly
    two ``gather`` equations (the bin rows, and the per-row table that
    carries ``gh`` and the row's leaf together), none of them reading a
    1-D row-sized operand, and the table's one concatenate."""
    from lightgbm_tpu.phases import HIST_GATHER
    eqns = fused_step_eqns[HIST_GATHER]
    gathers = [e for e in eqns if e.primitive.name == "gather"]
    assert len(gathers) == 2, gathers
    assert [e.invars[0].aval.ndim for e in gathers] == [2, 2]
    assert {e.outvars[0].aval.shape[1] for e in gathers} >= {4}
    prims = [e.primitive.name for e in eqns]
    assert prims.count("concatenate") == 1, prims


def test_chunk_loop_under_shard_map_holds_no_collective():
    """``tree_learner=data``: every shard assembles its table from its
    own rows and runs its own trip count, so the lowered text of the
    stream's layout loop names no collective."""
    from jax.sharding import Mesh, PartitionSpec as P
    from lightgbm_tpu.ops import pallas_histogram as PH
    if len(jax.devices()) < 4:
        pytest.skip("needs four virtual devices")
    n_sh, R, F, chunk = 4, 2048, 5, 256
    mesh = Mesh(np.array(jax.devices()[:n_sh]), ("d",))

    def f(bins, gh, rl, rg, n):
        return PH._stream_operands(bins, gh, rl, rg, n, chunk=chunk, fc=8,
                                   n_fb=1, acc_dt=jnp.float32)
    g = jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=(P("d"), P("d"), P("d"), P("d"), P("d")),
        out_specs=(P(None, None, "d"), P(None, "d"), P(None, "d"))))
    sds = jax.ShapeDtypeStruct
    text = g.lower(sds((n_sh * R, F), jnp.uint8),
                   sds((n_sh * R, 3), jnp.float32),
                   sds((n_sh * R,), jnp.int32), sds((n_sh * R,), jnp.int32),
                   sds((n_sh,), jnp.int32)).as_text()
    assert "stablehlo.while" in text and "stablehlo.gather" in text
    for op in ("all_reduce", "all_gather", "all_to_all",
               "collective_permute", "reduce_scatter"):
        assert op not in text, op
