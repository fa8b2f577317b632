#!/usr/bin/env python3
"""Time the forms the compacted stream's index can take, alone, on the chip.

    python3 scripts/compact_key_forms.py --out chiprun_out/key_forms.json

``self_key`` is what ``tree_builder.stream_index`` runs (one ``s32[R]``
sorted: a live row's key is its own number, a dead row's its number plus
R); ``s32_key`` sorts the row numbers as the payload of the key
``where(m, iota, R)``, unstable; ``u8_key_stable`` sorts them by ``~m``
as ``uint8`` with ``is_stable=True``; ``scatter`` is the form PR 33
deleted (cumsum, then ``zeros(R).at[where(m, pos, R)].set(iota)``).
Each at the row counts of the benchmark's row-bound cells, on a mask of
``--live`` random rows, checked against ``np.flatnonzero`` on the prefix.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, nargs="+",
                    default=[10_502_144, 2_271_232])
    ap.add_argument("--live", type=float, default=0.15)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from lightgbm_tpu.boosting.tree_builder import stream_index

    def u8_key_stable(m):
        iota = jnp.arange(m.shape[-1], dtype=jnp.int32)
        _, c = jax.lax.sort(((~m).astype(jnp.uint8), iota), num_keys=1,
                            is_stable=True)
        return c, m.astype(jnp.int32).sum()

    def s32_key(m):
        R = m.shape[-1]
        iota = jnp.arange(R, dtype=jnp.int32)
        _, c = jax.lax.sort((jnp.where(m, iota, R), iota), num_keys=1,
                            is_stable=False)
        return c, m.astype(jnp.int32).sum()

    def scatter(m):
        R = m.shape[-1]
        pos = jnp.cumsum(m.astype(jnp.int32)) - 1
        c = jnp.zeros((R,), jnp.int32).at[jnp.where(m, pos, R)].set(
            jnp.arange(R, dtype=jnp.int32), mode="drop")
        return c, m.astype(jnp.int32).sum()

    forms = {"self_key": stream_index, "s32_key": s32_key,
             "u8_key_stable": u8_key_stable, "scatter": scatter}
    out = {"device": str(jax.devices()[0].device_kind), "live": args.live,
           "reps": args.reps, "ms_per_call": {}, "compile_s": {}}
    for R in args.rows:
        m_np = np.random.default_rng(R).random(R) < args.live
        want = np.flatnonzero(m_np)
        m = jnp.asarray(m_np)
        for name, f in forms.items():
            t0 = time.perf_counter()
            g = jax.jit(f).lower(m).compile()
            out["compile_s"][f"{name}@{R}"] = time.perf_counter() - t0
            c, n = g(m)
            assert int(n) == want.size, (name, R)
            np.testing.assert_array_equal(np.asarray(c)[:want.size], want)
            t0 = time.perf_counter()
            for _ in range(args.reps):
                c, n = g(m)
            jax.block_until_ready((c, n))
            out["ms_per_call"][f"{name}@{R}"] = \
                1e3 * (time.perf_counter() - t0) / args.reps
    print("compact_key_forms: " + json.dumps(out), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
