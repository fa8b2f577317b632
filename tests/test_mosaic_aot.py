"""Real-Mosaic compile checks with no chip (slow; tier-1 skips them).

libtpu can describe a v5e topology without a TPU attached, and
``jax.jit(f).trace(*ShapeDtypeStructs).lower().compile()`` against its
devices runs the real Mosaic + XLA:TPU compilers. The Pallas interpreter
(what every other test uses) accepts programs Mosaic rejects, so this is
the only sandbox check that a kernel change still lowers on the chip.
Recipe for a one-off check: README "Checking a kernel change without a
chip".
"""

import functools as ft

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow

F32, I32, I8, U8 = jnp.float32, jnp.int32, jnp.int8, jnp.uint8


@pytest.fixture(scope="module")
def v5e():
    """The four devices of a v5e 2x2 topology (compile targets only)."""
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — libtpu absent or too old
        pytest.skip(f"libtpu cannot describe a v5e topology: {e}")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return list(topo.devices)


def _compile(fn, *args):
    return jax.jit(fn).trace(*args).lower().compile()


def _sds(dev):
    sh = jax.sharding.SingleDeviceSharding(dev)
    return lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sh)


def _hist_args(sds, R, F, L, quant):
    return [sds((R, F), U8), sds((R, 3), I8 if quant else F32),
            sds((R,), I32), sds((L,), I32)]


# (F, B, L): Higgs at 63 and 255 bins with a 21-slot and a 42-slot
# build, the verify skill's 12-feature default-params flow (leaf_batch
# 16, both children = 32 slots), MS-LTR and Expo widths, and the
# `criteo` cell's plan (67 columns at 255 bins: 8 features of 256
# one-hot rows a chunk)
SHAPES = [(28, 63, 21), (28, 63, 42), (28, 255, 21), (12, 255, 16),
          (12, 255, 32), (137, 63, 21), (700, 63, 21), (67, 255, 16)]


@pytest.mark.parametrize("F,B,L", SHAPES)
@pytest.mark.parametrize("variant", ["bf16", "f32", "int8", "bf16_rows",
                                     "int8_rows", "bf16_stream",
                                     "int8_stream", "bf16_lanes",
                                     "int8_lanes"])
def test_histogram_kernel_compiles(v5e, F, B, L, variant):
    """``_rows``: a live-row bound; ``_stream``: the compacted stream
    (``row_gather`` + bound: the chunk loop that lays the operands
    out); ``_lanes``: the entry for operands already laid out."""
    from lightgbm_tpu.ops import pallas_histogram as PH
    build_histograms_pallas = PH.build_histograms_pallas
    sds = _sds(v5e[0])
    quant = variant.startswith("int8")
    R = 1 << 16
    args = _hist_args(sds, R, F, L, quant)
    kw = dict(num_bins=B,
              hist_dtype="float32" if variant == "f32" else "bfloat16")
    if variant.endswith("_lanes"):
        blk, fc, n_fb, _, _ = PH._plan(F, B, L * 3, 1 if quant else 2)
        r_pad = PH._ceil_to(R, PH.stream_chunk(R, blk))
        _compile(lambda b, g, r, l, n: PH.build_histograms_pallas_lanes(
            b, g, r, l, n, num_features=F, **kw),
            sds((n_fb, fc, r_pad), I32),
            sds((3, r_pad), I32 if quant else F32), sds((1, r_pad), I32),
            sds((L,), I32), sds((1,), I32))
    elif variant.endswith("_stream"):
        _compile(lambda b, g, r, l, n, c: build_histograms_pallas(
            b, g, r, l, num_rows=n, row_gather=c, **kw), *args,
            sds((), I32), sds((R,), I32))
    elif variant.endswith("_rows"):
        _compile(lambda b, g, r, l, n: build_histograms_pallas(
            b, g, r, l, num_rows=n, **kw), *args, sds((), I32))
    else:
        _compile(lambda b, g, r, l: build_histograms_pallas(
            b, g, r, l, **kw), *args)


@pytest.mark.parametrize("bounded", [False, True])
def test_histogram_kernel_compiles_under_vmap(v5e, bounded):
    """The class-batched multiclass build vmaps the kernel (and its
    scalar-prefetch row bound) over the class axis."""
    from lightgbm_tpu.ops.pallas_histogram import build_histograms_pallas
    sds = _sds(v5e[0])
    K, R, F, B, L = 5, 1 << 16, 28, 63, 16
    args = [sds((R, F), U8), sds((K, R, 3), F32), sds((K, R), I32),
            sds((K, L), I32)]
    if bounded:
        _compile(lambda b, g, r, l, n: jax.vmap(
            lambda g1, r1, l1, n1: build_histograms_pallas(
                b, g1, r1, l1, num_bins=B, num_rows=n1))(g, r, l, n),
            *args, sds((K,), I32))
    else:
        _compile(lambda b, g, r, l: jax.vmap(
            lambda g1, r1, l1: build_histograms_pallas(
                b, g1, r1, l1, num_bins=B))(g, r, l), *args)


@pytest.mark.parametrize("quant", [False, True])
def test_class_root_kernel_compiles(v5e, quant):
    from lightgbm_tpu.ops.pallas_histogram import (
        build_root_histograms_classes)
    sds = _sds(v5e[0])
    K, R, F, B = 10, 1 << 16, 28, 255
    _compile(lambda b, g, r: build_root_histograms_classes(
        b, g, r, num_bins=B), sds((R, F), U8),
        sds((K, R, 3), I8 if quant else F32), sds((R,), I32))


def test_class_batched_build_names_its_root_pass_outside_every_loop(
        v5e, monkeypatch):
    """The class-batched tree build at KDD Cup 1999's width (41 columns,
    63 bins) for 5 classes, compiled for a described v5e: its one root
    pass is a custom call named ``pallas_hist_class_root`` that runs in
    no ``while`` (the benchmark's trace takes a kernel call outside every
    loop as a root pass, and a kernel by a name holding "pallas"); the
    stage map puts it under ``root_pass`` as ``hist_kernel``; the grow
    loop's kernel is ``pallas_hist_kernel`` inside the loop."""
    from lightgbm_tpu import phases
    from lightgbm_tpu.boosting.tree_builder import _build_tree_cb_jit
    from lightgbm_tpu.ops.pallas_histogram import (HIST_CLASS_ROOT_NAME,
                                                   HIST_KERNEL_NAME)
    from lightgbm_tpu.ops.split import SplitParams
    from lightgbm_tpu.telemetry import costmodel
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    sds = _sds(v5e[0])
    K, R, F = 5, 1 << 16, 41
    args = _tree_args(lambda s, d, _: sds(s, d), R, F)
    args[1] = sds((K, R, 3), F32)
    text = _build_tree_cb_jit.trace(
        *args, split_params=SplitParams(min_data_in_leaf=1,
                                        min_sum_hessian_in_leaf=100.0),
        hist_impl="pallas", **dict(_HIGGS, block_rows=1 << 13)
    ).lower().compile().as_text()
    rows = costmodel._instructions(text)
    bodies = {r.callee for r in rows if r.op.opcode == "while"}
    called_from = {c: r.comp for r in rows for c in r.called}

    def in_loop(comp):
        while comp is not None:
            if comp in bodies:
                return True
            comp = called_from.get(comp)
        return False
    roots = [r for r in rows if r.op.name.startswith(HIST_CLASS_ROOT_NAME)]
    inner = [r for r in rows if r.op.name.startswith(HIST_KERNEL_NAME)]
    assert len(roots) == 1 and roots[0].op.opcode == "custom-call"
    assert "pallas" in roots[0].op.name and not in_loop(roots[0].comp)
    assert inner and all(in_loop(r.comp) for r in inner)
    sm = costmodel.instruction_phase_map(text)
    assert sm.stages[roots[0].op.name] == phases.HIST_KERNEL
    assert phases.ROOT_PASS + "/" in sm.scopes[roots[0].op.name]


def _tree_args(make, R, F):
    return [make((R, F), U8, 2), make((R, 3), F32, 2), make((R,), I32, 1),
            make((F,), I32, 0), make((F,), I32, 0),
            make((F,), jnp.bool_, 0), make((F,), jnp.bool_, 0)]


_HIGGS = dict(num_leaves=255, leaf_batch=16, max_depth=-1, num_bins=63,
              hist_dtype="bfloat16", block_rows=1 << 14)


def test_serial_tree_build_compiles_at_higgs_width(v5e, monkeypatch):
    """The whole tree-build program (while_loop, compaction, split
    search) around the kernel ``auto`` resolves to on a TPU."""
    from lightgbm_tpu.boosting.tree_builder import _build_tree_jit
    from lightgbm_tpu.ops.histogram import resolve_impl
    from lightgbm_tpu.ops.split import SplitParams
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    impl = resolve_impl("auto", _HIGGS["num_bins"])
    assert impl == "pallas"
    sds = _sds(v5e[0])
    _build_tree_jit.trace(
        *_tree_args(lambda s, d, _: sds(s, d), 1 << 18, 28),
        split_params=SplitParams(), hist_impl=impl,
        **_HIGGS).lower().compile()


def _bench_shape(name):
    """(padded rows, cols, builder keywords) of a benchmark
    configuration, as the trainer lays it out on one chip."""
    import json
    import os
    from lightgbm_tpu.ops.histogram import block_rows_for
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "configs", name + ".json")
    with open(path) as f:
        cfg = json.load(f)
    rows, cols = cfg["shape"]["rows"], cfg["shape"]["cols"]
    p = cfg["params"]
    block = block_rows_for(rows, cols, p["max_bin"])
    return -(-rows // block) * block, cols, dict(
        num_leaves=p["num_leaves"], leaf_batch=16, max_depth=-1,
        num_bins=p["max_bin"], hist_dtype="bfloat16", block_rows=block)


@pytest.mark.parametrize("config", ["higgs", "epsilon"])
def test_stage_map_names_the_grow_loop_at_benchmark_shapes(v5e, config,
                                                           monkeypatch):
    """The tree build compiled for the described v5e at a benchmark
    cell's shape: every instruction of the grow ``while`` (and of the
    compacted stream's chunk loop nested in it) that touches a
    row-sized array has a stage deeper than ``build``, none of the chunk
    loop's is a row-sized gather, the Pallas custom call is the kernel
    stage, and no name outside phases.py appears."""
    import re

    from lightgbm_tpu import phases
    from lightgbm_tpu.boosting import tree_builder
    from lightgbm_tpu.boosting.tree_builder import _build_tree_jit
    from lightgbm_tpu.ops.pallas_histogram import HIST_KERNEL_NAME
    from lightgbm_tpu.ops.split import SplitParams
    from lightgbm_tpu.telemetry import costmodel
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    R, F, kw = _bench_shape(config)
    sds = _sds(v5e[0])
    text = _build_tree_jit.trace(
        *_tree_args(lambda s, d, _: sds(s, d), R, F),
        split_params=SplitParams(min_data_in_leaf=1,
                                 min_sum_hessian_in_leaf=100.0),
        hist_impl="pallas", **kw).lower().compile().as_text()
    sm = costmodel.instruction_phase_map(text)
    assert set(sm.stages.values()) <= phases.KNOWN_PHASES
    rows = costmodel._instructions(text)
    whiles = {r.callee: r.comp for r in rows if r.op.opcode == "while"}
    called_from = {c: r.comp for r in rows for c in r.called}

    def loop_of(comp):
        """The body of the innermost while that runs ``comp``."""
        while comp is not None and comp not in whiles:
            comp = called_from.get(comp)
        return comp
    # the chunk loop is the while that runs inside another's body (since
    # the dense route, inside a branch of the round's conditional)
    chunk_body = next(c for c, parent in whiles.items()
                      if loop_of(parent) is not None)
    body = loop_of(whiles[chunk_body])
    # the round: the grow loop's body and its conditional's branches
    round_comps = {body} | {b for r in rows if r.comp == body
                            for b in r.branches}
    shape = re.compile(r"\b(?:pred|bf16|[sufc]\d+)\[([0-9,]*)\]")
    lines = {m.group(1): ln.split(", metadata=")[0]
             for ln in text.splitlines()
             for m in [re.match(r"\s*(?:ROOT\s+)?%?([\w.-]+)\s*=", ln)] if m}
    deep = phases.BUILD_STAGES
    seen = {body: 0, chunk_body: 0}
    for r in rows:
        comp = body if r.comp in round_comps else r.comp
        if comp not in seen or r.op.opcode in costmodel._NOOP_OPCODES:
            continue
        elems = max((int(np.prod([int(x) for x in d.split(",") if x]))
                     for d in shape.findall(lines[r.op.name])), default=0)
        if elems >= R:
            if (not r.op.op_name and sm.stages.get(r.op.name) is None
                    and (r.op.opcode in ("copy-start", "copy-done",
                                         "slice-start", "slice-done")
                         or r.op.custom_call_target == "ConcatBitcast")):
                # the compiler's own prefetch into fast memory for the
                # NEXT round (its user is the body's root tuple), whole
                # or in slices that a ConcatBitcast joins: no source
                # line to name, bare ``build`` in a stage table
                continue
            seen[comp] += 1
            assert sm.stages.get(r.op.name) in deep, (
                r.op.name, sm.stages.get(r.op.name), r.op.op_name)
            longest = max(int(x) for d in shape.findall(lines[r.op.name])
                          for x in d.split(",") if x)
            if r.comp == chunk_body and longest >= R:
                # only the operand buffers' in-place updates (and what
                # the compiler moves between memories) span all rows
                assert (sm.stages[r.op.name] == phases.HIST_RELAYOUT
                        or r.op.opcode.startswith("copy")), (
                    r.op.name, r.op.opcode, sm.stages[r.op.name])
    # (the walk found the round: 12 row-sized instructions at Higgs since
    # PR 33 made the stream's index one sort, 18 before)
    assert seen[body] > 10 and seen[chunk_body] >= 3
    staged = {sm.stages.get(r.op.name) for r in rows if r.comp == chunk_body}
    assert {phases.HIST_GATHER, phases.HIST_RELAYOUT} <= staged
    kernels = [r for r in rows if r.op.name.startswith(HIST_KERNEL_NAME)]
    # the root pass and the compacted stream's call; at Higgs's narrow
    # lattice also the dense route's pass over all rows
    # (tree_builder.dense_round_rows), and only there
    dense = tree_builder.step_shape(
        rows=R, stored_columns=F, features=F, num_bins=kw["num_bins"],
        bundle_bins=0, num_leaves=kw["num_leaves"],
        leaf_batch=kw["leaf_batch"], hist_impl="pallas",
        gh_dtype=jnp.float32, hist_dtype=kw["hist_dtype"],
        block_rows=kw["block_rows"], hist_sub=True).dense_round_rows
    assert bool(dense) == (config == "higgs")
    assert len(kernels) == (3 if dense else 2)
    assert all(sm.stages[r.op.name] == phases.HIST_KERNEL for r in kernels)


@pytest.mark.parametrize("merge", ["reduce_scatter", "allreduce"])
def test_data_parallel_tree_build_compiles_on_four_chips(v5e, merge,
                                                         monkeypatch):
    """tree_learner=data over the 2x2 mesh: the kernel inside shard_map
    (check_vma on under allreduce), rows sharded, both merge plans."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from lightgbm_tpu.ops.histogram import resolve_impl
    from lightgbm_tpu.ops.split import SplitParams
    from lightgbm_tpu.parallel.data_parallel import DataParallelPlan
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    plan = DataParallelPlan(devices=v5e, hist_merge=merge)
    assert plan.num_shards == 4

    def make(shape, dt, row_dims):
        spec = (P(plan.axis_name, *([None] * (row_dims - 1)))
                if row_dims else P())
        return jax.ShapeDtypeStruct(
            shape, dt, sharding=NamedSharding(plan.mesh, spec))

    kw = dict(_HIGGS, block_rows=1 << 12, split_params=SplitParams(),
              hist_impl=resolve_impl("auto", _HIGGS["num_bins"]))
    _compile(ft.partial(plan.build_tree, **kw),
             *_tree_args(make, 1 << 18, 28))


def test_bundled_search_lays_the_features_on_the_lanes(v5e, monkeypatch):
    """Allstate's bundled build (79 stored columns of 256 bins; 4,228
    features: 4,212 one-hot members of 3 bins beside 16 columns of 255)
    at a cut row count: the search runs over its two width classes, the
    padded [32, 4228, 255, 3] lattice is never built, and no array of the
    narrow class has its 3 channels on the lanes (gathered as
    ``[S, n x w, 3]`` they padded to 128 lanes: 276 MB for 6.5, PR 40)."""
    import re
    from lightgbm_tpu.boosting.tree_builder import (BundleMeta,
                                                    _build_tree_jit,
                                                    search_plan)
    from lightgbm_tpu.ops.split import SplitParams
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    sds = _sds(v5e[0])
    R, G, F = 1 << 18, 79, 4228
    plan = search_plan([255] * 16 + [3] * 4212, 255)
    assert plan.widths == (4, 255)
    meta = BundleMeta(sds((F,), I32), sds((F,), I32), sds((F,), I32),
                      search=plan)
    args = _tree_args(lambda s, d, _: sds(s, d), R, F)
    args[0] = sds((R, G), U8)
    text = _build_tree_jit.trace(
        *args, split_params=SplitParams(min_data_in_leaf=1,
                                        min_sum_hessian_in_leaf=100.0),
        hist_impl="pallas", num_leaves=255, leaf_batch=16, max_depth=-1,
        num_bins=255, hist_dtype="bfloat16", block_rows=1 << 14,
        bundle_meta=meta, bundle_bins=256).lower().compile().as_text()
    assert "f32[32,4228,255" not in text
    narrow = {4212, 4212 * 4, 4212 * 4 * 2}
    seen, bad = 0, []
    for m in re.finditer(r"f32\[([0-9,]+)\]\{([0-9,]+)", text):
        dims = [int(x) for x in m.group(1).split(",")]
        if narrow & set(dims):
            seen += 1
            if dims[int(m.group(2).split(",")[0])] == 3:
                bad.append(m.group(0))
    assert seen and not bad, bad[:5]


# (F, L, R): the higgs-linear cell's plan (28 features, 255 leaves, its
# padded rows), a narrow tree, the 64-feature ceiling of the device Gram,
# and 1023 leaves (four lane tiles of leaf slots)
LINEAR_SHAPES = [(28, 255, 10502144), (5, 7, 4096), (64, 255, 1 << 16),
                 (12, 1023, 1 << 16)]


@pytest.mark.parametrize("F,L,R", LINEAR_SHAPES)
def test_linear_leaf_kernels_compile(v5e, F, L, R):
    """The moments, Gram and output kernels of ops/linear_leaves.py with
    the fit's solve between them, as the fused step runs them."""
    from lightgbm_tpu.boosting.tree_builder import TreeArrays
    from lightgbm_tpu.ops import linear_leaves as LL
    sds = _sds(v5e[0])
    N = 2 * L
    tree = TreeArrays(
        sds((N,), I32), sds((N,), I32), sds((N,), bool), sds((N,), bool),
        sds((N,), I32), sds((N,), I32), sds((N,), F32), sds((N,), F32),
        sds((N,), F32), sds((N,), F32), sds((N, 2), jnp.uint32),
        sds((L + 1,), I32), sds((L + 1,), F32), sds((), I32), sds((), I32))
    assert LL.device_reason(F, L + 1, R) == ""

    def fit(t, x, rl, g, h, c, num):
        t = LL.fit_leaves(t, x, rl, g, h, c, numerical=num, lam=0.0,
                          first=False)
        return t, LL.leaf_outputs(t, x, rl)
    text = _compile(fit, tree, sds((LL.padded_features(F), R), F32),
                    sds((R,), I32), sds((R,), F32), sds((R,), F32),
                    sds((R,), F32), sds((F,), bool)).as_text()
    for name in (LL.MOMENTS_KERNEL_NAME, LL.GRAM_KERNEL_NAME,
                 LL.OUTPUT_KERNEL_NAME):
        assert name in text
