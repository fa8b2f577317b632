#!/usr/bin/env python3
"""Time the forms the compacted stream's per-row fetch can take, alone, on the chip.

    python3 scripts/stream_gather_forms.py --out chiprun_out/pr38/forms.json

A call is one round's stream: ``stream_trips(live, chunk)`` trips, each
fetching a chunk's rows by the chunk's slice of an ascending index
(``tree_builder.stream_index`` of a random mask of ``--live`` of the
rows), as ``ops/pallas_histogram._stream_operands`` does before it re-lays
them. What a trip fetches is summed to a word a piece so that nothing
is optimised away, and every form must give the same three sums.

``three``    the form before PR 38: ``take`` of ``bins u8[R, F]``, of
             ``gh f32[R, 3]`` and of ``row_leaf s32[R]``.
``two``      what ships: ``take`` of ``bins`` and one of
             ``ops.histogram._row_table``'s ``s32[R, 4]`` (gh's words and
             the leaf), the table assembled once in the call as it is
             once a round; ``two_table`` is that assembly alone.
``packed``   not shipped: one ``take`` of a packed row ``u8[R, F + 16]``
             (the bin row, then the table's sixteen bytes).
             ``packed_rebuild`` is the packing of all R rows (once a
             tree: ``gh`` changes) and ``packed_leaf`` the rewrite of
             the four leaf bytes of all R rows (once a round).

At the four shapes a cell streams (rows a chip x stored columns, chunk).
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# R (padded rows a chip), F (stored columns), chunk: higgs-train,
# criteo-dp-train, allstate-efb-train, msltr-rank-train
SHAPES = [(10_502_144, 28, 328_320), (13_281_280, 67, 415_744),
          (13_185_024, 79, 412_672), (2_271_232, 137, 71_424)]


def forms():
    """name -> function; the streams take ``chunk`` by keyword."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops.histogram import (HIST_CH, _gather_rows,
                                            _row_table, stream_trips)
    f32, i32, u8 = jnp.float32, jnp.int32, jnp.uint8
    W = 4 * (HIST_CH + 1)                  # the table's bytes a row

    def words(x):                          # any 32-bit array -> one s32
        if x.dtype != i32:
            x = jax.lax.bitcast_convert_type(x, i32)
        return x.sum(dtype=i32)

    def stream(fetch, idx, n, chunk):
        """Sum over the live chunks of ``fetch(idx_chunk, start)``'s
        (bins, gh, leaf) pieces, a word each."""
        R = idx.shape[0]
        idx_all = jnp.pad(idx, (0, -R % chunk))

        def trip(i, acc):
            s = i * chunk
            bb, ghb, lb = fetch(jax.lax.dynamic_slice(idx_all, (s,),
                                                      (chunk,)), s)
            return (acc[0] + bb.astype(i32).sum(dtype=i32),
                    acc[1] + words(ghb), acc[2] + lb.sum(dtype=i32))
        zero = jnp.zeros((), i32)
        return jax.lax.fori_loop(0, stream_trips(n, chunk, R), trip,
                                 (zero, zero, zero))

    def dead_tail(leaf, s, n):
        pos = s + jnp.arange(leaf.shape[0], dtype=i32)
        return jnp.where(pos < n, leaf, -1)

    def three(bins, gh, leaf, idx, n, *, chunk):
        return stream(lambda ix, s: (
            jnp.take(bins, ix, axis=0), jnp.take(gh, ix, axis=0),
            dead_tail(jnp.take(leaf, ix), s, n)), idx, n, chunk)

    def two(bins, gh, leaf, idx, n, *, chunk):
        table = _row_table(gh, leaf, f32)
        return stream(lambda ix, s: (
            jnp.take(bins, ix, axis=0),
            *_gather_rows(table, ix, s, n, f32)), idx, n, chunk)

    def two_table(bins, gh, leaf, idx, n, *, chunk):
        return _row_table(gh, leaf, f32)

    def as_bytes(x):                       # s32[R, k] -> u8[R, 4 k]
        return jax.lax.bitcast_convert_type(x, u8).reshape(x.shape[0], -1)

    def packed_rebuild(bins, gh, leaf, idx, n, *, chunk):
        return jnp.concatenate([bins, as_bytes(_row_table(gh, leaf, f32))],
                               axis=1)

    def packed_leaf(rows, leaf):
        F = rows.shape[1] - W
        return jax.lax.dynamic_update_slice(rows, as_bytes(leaf[:, None]),
                                            (0, F + 4 * HIST_CH))

    def packed(rows, idx, n, *, chunk):
        F = rows.shape[1] - W

        def fetch(ix, s):
            piece = jnp.take(rows, ix, axis=0)
            t = jax.lax.bitcast_convert_type(
                piece[:, F:].reshape(-1, HIST_CH + 1, 4), i32)
            return (piece[:, :F],
                    jax.lax.bitcast_convert_type(t[:, :HIST_CH], f32),
                    dead_tail(t[:, HIST_CH], s, n))
        return stream(fetch, idx, n, chunk)

    return {"three": three, "two": two, "two_table": two_table,
            "packed_rebuild": packed_rebuild, "packed": packed,
            "packed_leaf": packed_leaf}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", type=int, nargs="+", default=None,
                    help="R F chunk [R F chunk ...]")
    ap.add_argument("--live", type=float, default=0.15)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    shapes = SHAPES if args.shapes is None else list(
        zip(*[iter(args.shapes)] * 3))

    import functools
    import jax
    import jax.numpy as jnp
    import numpy as np
    from lightgbm_tpu.boosting.tree_builder import stream_index
    from lightgbm_tpu.ops.histogram import HIST_CH
    fm = forms()

    def timed(fn, *a, in_place=False):
        """(result, ms a call, compile s); ``in_place`` donates the first
        argument and feeds each call's result to the next."""
        t0 = time.perf_counter()
        g = jax.jit(fn, donate_argnums=(0,) if in_place else ()).lower(
            *a).compile()
        compile_s = time.perf_counter() - t0
        out = jax.block_until_ready(g(*a))
        t0 = time.perf_counter()
        for _ in range(args.reps):
            out = g(out, *a[1:]) if in_place else g(*a)
        jax.block_until_ready(out)
        return out, 1e3 * (time.perf_counter() - t0) / args.reps, compile_s

    res = {"device": str(jax.devices()[0].device_kind), "live": args.live,
           "reps": args.reps, "shapes": []}
    for R, F, chunk in shapes:
        rng = np.random.default_rng(R)
        bins = jnp.asarray(rng.integers(0, 256, (R, F), dtype=np.uint8))
        gh = jnp.asarray(rng.standard_normal((R, HIST_CH),
                                             dtype=np.float32))
        leaf = jnp.asarray(rng.integers(-1, 255, R, dtype=np.int32))
        idx, n = jax.jit(stream_index)(jnp.asarray(rng.random(R)
                                                   < args.live))
        positions = -(-int(n) // chunk) * chunk
        row = {"rows": R, "cols": F, "chunk": chunk, "live_rows": int(n),
               "positions": positions, "ms_per_call": {}, "compile_s": {},
               "ns_per_position": {}}
        sums = {}

        def note(name, fn, *a, **kw):
            out, ms, cs = timed(fn, *a, **kw)
            row["ms_per_call"][name], row["compile_s"][name] = ms, cs
            if name in ("three", "two", "packed"):
                sums[name] = [int(x) for x in out]
                row["ns_per_position"][name] = 1e6 * ms / positions
            return out

        for name in ("three", "two", "two_table", "packed_rebuild"):
            rows = note(name, functools.partial(fm[name], chunk=chunk),
                        bins, gh, leaf, idx, n)
        del bins, gh                       # rows: the packed table
        rows = note("packed_leaf", fm["packed_leaf"], rows, leaf,
                    in_place=True)
        note("packed", functools.partial(fm["packed"], chunk=chunk),
             rows, idx, n)
        assert sums["three"] == sums["two"] == sums["packed"], sums
        row["sums"] = sums["three"]
        res["shapes"].append(row)
        print("stream_gather_forms: " + json.dumps(row), flush=True)
        del rows, leaf, idx
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(res, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
