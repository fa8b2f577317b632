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
    # the chunk loop is the while that runs inside another's body
    chunk_body = next(c for c, parent in whiles.items() if parent in whiles)
    body = whiles[chunk_body]
    shape = re.compile(r"\b(?:pred|bf16|[sufc]\d+)\[([0-9,]*)\]")
    lines = {m.group(1): ln.split(", metadata=")[0]
             for ln in text.splitlines()
             for m in [re.match(r"\s*(?:ROOT\s+)?%?([\w.-]+)\s*=", ln)] if m}
    deep = phases.BUILD_STAGES
    seen = {body: 0, chunk_body: 0}
    for r in rows:
        if r.comp not in seen or r.op.opcode in costmodel._NOOP_OPCODES:
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
            seen[r.comp] += 1
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
    assert len(kernels) == 2      # the root pass and the in-loop call
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
