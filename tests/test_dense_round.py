"""The dense route of a round's histogram (``tree_builder.dense_round_rows``):
where the rule of the step's shape compiles it, the histograms it gives
beside the compacted stream's, the trees it grows on one device and on a
row mesh, and what it leaves out under class batching."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.boosting import tree_builder
from lightgbm_tpu.boosting.tree_builder import (DENSE_GATHER_ELEMENTS,
                                                build_tree, step_shape)
from lightgbm_tpu.ops.split import SplitParams

# run again here, so that tier-1 holds them: the reader's own cases
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(_ROOT, "benchmarks", "tests"),
           os.path.join(_ROOT, "benchmarks")):
    if _p not in sys.path:
        sys.path.insert(0, _p)
from test_dense_round_metric import (  # noqa: E402,F401  (collected here too)
    NAME as _DENSE_METRIC,
    test_dense_rounds_over_built_rounds_of_the_windows_trees,
    test_a_step_without_the_route_reads_zero,
    test_dense_round_metric_is_none_without_the_counter,
    test_dense_round_metric_without_a_program_is_none)


def test_dense_round_metric_is_in_the_manifest_for_every_cell():
    """The metric's entry as it was written, reported in every cell (it
    lists none), and every per-layer entry after it one appended later
    for the cells it names, which moves nothing of it (the benchmark's
    own copy of this case reads it as the list's last)."""
    from harness.manifest import Manifest
    man = Manifest(_ROOT)
    assert man.problems() == []
    layer = man.doc["per_layer"]
    at = [i for i, m in enumerate(layer) if m["name"] == _DENSE_METRIC]
    assert len(at) == 1
    assert layer[at[0]] == {"name": _DENSE_METRIC, "unit": "%",
                            "better": "higher", "source": "program_counter",
                            "layer": "builder",
                            "moves": "train_row_trees_per_s"}
    assert all("workloads" in m for m in layer[at[0] + 1:])
    for w in man.doc["workloads"]:
        assert _DENSE_METRIC in {
            m["name"] for m in man.metrics_for(w["name"], "per_layer")}


# one device's shape of each benchmark configuration as the trainer hands
# it to the tree builder: (rows, stored columns, features, bins, bundle bins,
# parallel mode, shards)
CELLS = {
    "higgs": (10_502_144, 28, 28, 63, 0, None, 1),
    "higgs-linear": (10_502_144, 28, 28, 63, 0, None, 1),
    "epsilon": (401_408, 2000, 2000, 63, 0, None, 1),
    "msltr": (2_277_376, 137, 137, 63, 0, None, 1),
    "criteo": (13_281_280, 67, 67, 255, 0, "data", 4),
    "allstate": (13_185_024, 79, 4228, 255, 256, None, 1),
}


def _shape(cell, impl="pallas", class_batched=False):
    rows, cols, feats, B, bb, mode, n = CELLS[cell]
    return step_shape(
        rows=rows, stored_columns=cols, features=feats, num_bins=B,
        bundle_bins=bb, num_leaves=255, leaf_batch=16, hist_impl=impl,
        gh_dtype=jnp.float32, hist_dtype="bfloat16", block_rows=0,
        hist_sub=True, parallel_mode=mode,
        hist_merge="reduce_scatter" if mode else "allreduce", n_shards=n,
        linear_columns=29 if cell == "higgs-linear" else 0,
        class_batched=class_batched)


@pytest.mark.parametrize("cell", list(CELLS))
def test_the_route_is_compiled_at_the_narrow_cells_only(cell):
    sh = _shape(cell)
    a_row = sh.kernel_chunks * sh.kernel_feature_chunk \
        * sh.kernel_padded_bins
    if cell.startswith("higgs"):
        assert a_row == 28 * 64 < DENSE_GATHER_ELEMENTS
        # about 13% of the rows: a round over that pays more for the
        # sort and the gathers than one pass of the kernel over all rows
        assert 0.12 < sh.dense_round_rows / sh.rows < 0.15
    else:
        assert a_row >= DENSE_GATHER_ELEMENTS
        assert sh.dense_round_rows == 0
    assert sh.fields()["shape_dense_round_rows"] == sh.dense_round_rows
    assert sh.fields()["shape_classes"] == 1       # a root pass a tree
    # no other kernel, and no class-batched build, has the route
    for impl in ("matmul", "scatter"):
        assert _shape(cell, impl).dense_round_rows == 0
    assert _shape(cell, class_batched=True).dense_round_rows == 0


def test_the_threshold_is_the_break_even_of_the_two_routes():
    G, S = DENSE_GATHER_ELEMENTS, tree_builder.DENSE_SORT_ELEMENTS
    n = tree_builder.dense_round_rows("pallas", True, False, 1792, 1_000_000)
    # one row over it the stream costs more than the pass; at it, not
    assert (n + 1) * (1792 + G) > 1_000_000 * (1792 - S) >= n * (1792 + G)
    # a kernel cheaper than the sort: every round goes dense, and the
    # threshold still reads as a compiled route
    assert tree_builder.dense_round_rows("pallas", True, False, 96, 4096) == 1
    assert tree_builder.dense_round_rows("pallas", False, False, 96,
                                         4096) == 0


def _round_case(rng, R, F, B, addends):
    """bins, gh (float / whole numbers / int8) and a round's leaves: six
    leaves, of which the round's small slots are three."""
    bins = rng.randint(0, B, size=(R, F)).astype(np.uint8)
    if addends == "int8":
        gh = rng.randint(-127, 128, size=(R, 3)).astype(np.int8)
    elif addends == "whole":
        gh = rng.randint(-64, 65, size=(R, 3)).astype(np.float32)
    else:
        gh = rng.normal(size=(R, 3)).astype(np.float32)
    row_leaf = rng.randint(-1, 6, size=R).astype(np.int32)
    slots = np.array([4, 1, -2, 5], np.int32)
    return bins, gh, row_leaf, slots


@pytest.mark.parametrize("addends", ["float", "whole", "int8"])
def test_one_pass_over_all_rows_equals_the_compacted_stream(rng, interp,
                                                            addends):
    """At one round's mask the kernel over all rows in stored order sums
    what the compacted stream sums: exactly with whole-number or int8
    addends, within f32 reassociation with float ones."""
    from lightgbm_tpu.ops.histogram import stream_chunk_rows
    R, F, B = 3000, 8, 63
    bins, gh, row_leaf, slots = _round_case(rng, R, F, B, addends)
    m = np.isin(row_leaf, slots[slots >= 0])
    c_idx, n = tree_builder.stream_index(jnp.asarray(m))
    assert int(n) == m.sum()
    hd = "float32" if addends == "float" else "bfloat16"
    compacted = interp.build_histograms_pallas(
        jnp.asarray(bins), jnp.asarray(gh), jnp.asarray(row_leaf),
        jnp.asarray(slots), num_bins=B, hist_dtype=hd,
        row_gather=c_idx, num_rows=n)
    blk = stream_chunk_rows("pallas", R, F, B, 4, gh.dtype, hd)
    ops = interp.resident_operands(jnp.asarray(bins), jnp.asarray(gh),
                                   -(-R // blk) * blk, num_bins=B,
                                   hist_dtype=hd)
    dense = interp.build_histograms_pallas_dense(
        *ops, jnp.asarray(row_leaf), jnp.asarray(slots), num_features=F,
        num_bins=B, hist_dtype=hd)
    got, want = np.asarray(dense), np.asarray(compacted)
    assert got.shape == want.shape == (4, F, B, 3)
    assert not got[2].any()            # the unused lane's slot
    if addends == "float":
        scale = np.abs(gh[m]).sum(axis=0)
        assert np.all(np.abs(got - want) <= 1e-6 * scale)
    else:
        np.testing.assert_array_equal(got, want)
    # and both are the rows' sums
    for s, slot in enumerate(slots):
        rows = row_leaf == slot
        for f in range(F):
            ref = np.zeros((B, 3))
            np.add.at(ref, bins[rows, f], gh[rows].astype(np.float64))
            np.testing.assert_allclose(got[s, f], ref, rtol=1e-5,
                                       atol=1e-4)


def _tree_case(R=2048, F=8, B=255, seed=0):
    """A tree whose first round's smaller child is over the threshold of
    a 8 x 255 lattice and whose later rounds are under it."""
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, B, size=(R, F)).astype(np.uint8)
    y = bins[:, 0] / 255.0 + 0.5 * (bins[:, 1] > 100) \
        + 0.1 * rng.normal(size=R)
    g = np.round((y - y.mean()) * 8) / 8      # whole eighths: exact sums
    gh = np.stack([g, np.ones(R), np.ones(R)], axis=1).astype(np.float32)
    meta = (jnp.full((F,), B, jnp.int32), jnp.full((F,), -1, jnp.int32),
            jnp.zeros((F,), bool), jnp.ones((F,), bool))
    kw = dict(num_leaves=15, leaf_batch=4, max_depth=-1, num_bins=B,
              split_params=SplitParams(min_data_in_leaf=5,
                                       min_sum_hessian_in_leaf=1e-3),
              hist_dtype="float32", block_rows=256, hist_sub=True)
    return bins, gh, meta, kw


def _fields(tree, rl):
    return [np.asarray(a) for a in (tree.split_feature, tree.threshold_bin,
                                    tree.leaf_values, tree.node_count, rl)]


def test_a_tree_takes_both_routes_and_grows_the_scatter_tree(interp):
    bins, gh, meta, kw = _tree_case()
    R = bins.shape[0]
    out = {}
    for impl in ("pallas", "scatter"):
        t, rl, _, log = build_tree(
            jnp.asarray(bins), jnp.asarray(gh), jnp.zeros((R,), jnp.int32),
            *meta, hist_impl=impl, **kw)
        out[impl] = (_fields(t, rl), log)
    for a, b in zip(out["pallas"][0], out["scatter"][0]):
        np.testing.assert_array_equal(a, b)
    log = out["pallas"][1]
    ran = np.asarray(log.leaves) > 0
    dense = np.asarray(log.dense)[ran]
    assert dense.any() and not dense.all()
    # a dense round touches every row up to the kernel's row block; the
    # live rows it counts are the small children's either way
    sh = step_shape(rows=R, stored_columns=8, features=8, num_bins=255,
                    bundle_bins=0, num_leaves=15, leaf_batch=4,
                    hist_impl="pallas", gh_dtype=jnp.float32,
                    hist_dtype="float32", block_rows=256, hist_sub=True)
    rows = np.asarray(log.rows)[ran]
    stream = np.asarray(log.stream_rows)[ran]
    blk = sh.kernel_row_block
    assert (stream[dense == 1] == -(-R // blk) * blk).all()
    assert (rows[dense == 1] > sh.dense_round_rows).all()
    assert (rows[dense == 0] <= sh.dense_round_rows).all()
    assert (rows == np.asarray(out["scatter"][1].rows)[ran]).all()
    assert not np.asarray(out["scatter"][1].dense).any()


def _walk(jaxpr, inside_cond=False):
    """(primitive name, inside a cond branch) of every equation, nested
    jaxprs (jit, while, cond, shard_map, pallas bodies aside) included."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        yield name, inside_cond
        if name == "pallas_call":
            continue
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _walk(sub, inside_cond or name == "cond")


def _route_conds(jaxpr):
    """Every cond that calls the histogram kernel in a branch."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        if eqn.primitive.name == "cond" and any(
                n == "pallas_call" for br in eqn.params["branches"]
                for n, _ in _walk(br.jaxpr)):
            found.append(eqn)
            continue
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found.extend(_route_conds(sub))
    return found


COLLECTIVES = ("psum", "pmax", "pmin", "all_gather", "all_to_all",
               "ppermute", "reduce_scatter", "psum_scatter")


def test_the_class_batched_build_compiles_no_route():
    """Under vmap a cond with a batched predicate runs both branches, so
    the class-batched build leaves the route out statically: one kernel
    call a round (the compacted stream's), no cond around it."""
    bins, gh, meta, kw = _tree_case(R=1024)
    R = bins.shape[0]
    args = (jnp.asarray(bins), jnp.asarray(np.stack([gh, gh, gh])),
            jnp.zeros((R,), jnp.int32), *meta)

    def grow(class_batched, *a):
        return build_tree(*a, hist_impl="pallas", traced=True,
                          class_batched=class_batched, **kw)
    batched = jax.make_jaxpr(functools.partial(grow, True))(*args).jaxpr
    assert not _route_conds(batched)
    assert "cond" not in {n for n, _ in _walk(batched)}
    single = jax.make_jaxpr(functools.partial(grow, False))(
        *args[:1], args[1][0], *args[2:]).jaxpr
    assert len(_route_conds(single)) == 1
    count = sum(n == "pallas_call" for n, _ in _walk(batched))
    assert count == sum(n == "pallas_call" for n, _ in _walk(single)) - 1


@pytest.mark.parametrize("merge", ["allreduce", "reduce_scatter"])
def test_on_a_row_mesh_the_branches_hold_no_collective(monkeypatch, merge):
    """tree_learner=data over four devices: shards may take different
    routes, so each branch gives a shard's LOCAL histogram and the merge
    follows the cond; the tree is the scatter tree."""
    from lightgbm_tpu.ops import pallas_histogram as PH
    from lightgbm_tpu.parallel.data_parallel import DataParallelPlan
    four = jax.devices()[:4]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: four)
    plan = DataParallelPlan(hist_merge=merge)
    assert plan.num_shards == 4
    bins, gh, meta, kw = _tree_case(R=4096)
    R = bins.shape[0]
    args = (plan.shard_rows(bins), plan.shard_rows(gh),
            plan.shard_rows(np.zeros(R, np.int32)))
    jaxpr = jax.make_jaxpr(lambda *a: plan.build_tree(
        *a, *meta, hist_impl="pallas", **kw))(*args).jaxpr
    conds = _route_conds(jaxpr)
    assert len(conds) == 1
    for br in conds[0].params["branches"]:
        inside = {n for n, _ in _walk(br.jaxpr)}
        assert not {n for n in inside if n.startswith(COLLECTIVES)}, inside
    # the merge itself is still there, after the cond
    assert any(n.startswith(COLLECTIVES) for n, _ in _walk(jaxpr))
    if merge == "allreduce":
        # the Pallas interpreter cannot evaluate an index map of a varying
        # scalar prefetch under check_vma, which this plan turns on: the
        # run is the reduce-scatter case's (a v5e compiles both plans,
        # tests/test_mosaic_aot.py)
        return
    for name in ("build_histograms_pallas", "build_histograms_pallas_lanes"):
        monkeypatch.setattr(PH, name, functools.partial(getattr(PH, name),
                                                        interpret=True))
    jax.clear_caches()      # the trace above is the chip's kernel's
    out = {}
    for impl in ("pallas", "scatter"):
        t, rl, _, log = plan.build_tree(*args, *meta, hist_impl=impl, **kw)
        out[impl] = (_fields(t, rl), log)
    for a, b in zip(out["pallas"][0], out["scatter"][0]):
        np.testing.assert_array_equal(a, b)
    log = out["pallas"][1]
    dense = np.asarray(log.dense)
    assert dense.shape == (4, np.asarray(log.leaves).shape[0])
    assert dense.any() and not dense[:, np.asarray(log.leaves) > 0].all()


HLO_COND = """HloModule jit_step, is_scheduled=true

%branch_0.1 (p: s32[64]) -> s32[64] {
  %p = s32[64]{0:T(128)} parameter(0)
  %sort.2 = s32[64]{0:T(128)} sort(%p), dimensions={0}, metadata={op_name="jit(step)/build/while/body/compact/cond/branch_0_fun/sort"}
  ROOT %copy.3 = s32[64]{0:T(128)} copy(%sort.2)
}

%branch_1.4 (q: s32[64]) -> s32[64] {
  %q = s32[64]{0:T(128)} parameter(0)
  ROOT %pallas_hist_kernel.5 = s32[64]{0:T(128)} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/build/while/body/compact/cond/branch_1_fun/hist_kernel/pallas_call"}
}

%body.6 (t: (s32[], s32[64])) -> (s32[], s32[64]) {
  %t = (s32[]{:T(128)}, s32[64]{0:T(128)}) parameter(0)
  %i = s32[]{:T(128)} get-tuple-element(%t), index=0
  %x = s32[64]{0:T(128)} get-tuple-element(%t), index=1
  %conditional.7 = s32[64]{0:T(128)} conditional(%i, %x, %x), branch_computations={%branch_0.1, %branch_1.4}, metadata={op_name="jit(step)/build/while/body/compact/cond"}
  ROOT %tup = (s32[]{:T(128)}, s32[64]{0:T(128)}) tuple(%i, %conditional.7)
}

ENTRY %main.9 (a: (s32[], s32[64])) -> (s32[], s32[64]) {
  %a = (s32[]{:T(128)}, s32[64]{0:T(128)}) parameter(0)
  ROOT %while.8 = (s32[]{:T(128)}, s32[64]{0:T(128)}) while(%a), condition=%cond.1, body=%body.6, metadata={op_name="jit(step)/build/while"}
}
"""


def test_the_route_s_branches_are_staged_and_in_the_loop():
    """The round's ``lax.cond`` compiles to a ``conditional`` whose
    branches are computations of their own: the stage map and
    ``staged_ops`` follow ``branch_computations``, so an instruction
    there without a stage of its own takes the conditional's and counts
    as in the loop (the collective auditors count a round's
    instructions by that)."""
    from lightgbm_tpu.telemetry import costmodel
    rows = costmodel._instructions(HLO_COND)
    cond = next(r for r in rows if r.op.opcode == "conditional")
    assert cond.branches == ("branch_0.1", "branch_1.4")
    assert cond.called == cond.branches
    sm = costmodel.instruction_phase_map(HLO_COND)
    assert sm.stages["sort.2"] == "compact"
    assert sm.stages["pallas_hist_kernel.5"] == "hist_kernel"
    assert sm.stages["copy.3"] == "compact"      # no metadata: its caller's
    staged = {o.op.name: o for o in costmodel.staged_ops(HLO_COND)}
    assert staged["sort.2"].in_loop and staged["pallas_hist_kernel.5"] \
        .in_loop
    assert not staged["while.8"].in_loop
