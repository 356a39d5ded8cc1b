"""Time the HLL epilogue's two modes on the chip: in-kernel one-hot walk
against the kernel-emitted hashes' XLA scatter-max.

    python3 benchmarks/hll_split.py [--reps 10]

For each (L, n, b) case, the plan kernel runs one call at the statistics
cell's shape (1536 rows of 2048 tokens after a 12-token tail) with the
HLL epilogue forced to each mode through ``plan.HLL_IN_KERNEL_MAX_CELLS``:
HLL alone, and at n = 13, L = 64 also beside the statistics cell's
CountMin 4 x 2^20 with seeded random constants (its scatter path emits the
hashes anyway). Prints one JSON line per case and mode with the median
milliseconds of ``reps`` calls after a warm one, and the one-hot cells a
window costs, ``(rank_bits + 1) * 2^b``, which the split reads.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

# (L, n, b, beside the cell's CountMin)
CASES = [(64, 13, 12, False), (64, 13, 13, False), (64, 13, 14, False),
         (64, 13, 13, True), (64, 13, 14, True),
         (32, 8, 12, False), (32, 8, 14, False)]
COLS = 2048 + 12


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--reps", type=int, default=30)
    ap.add_argument("--rows", type=int, default=1536)
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    from repro.kernels import api, plan as plan_mod
    from repro.kernels.plan import (CountMinSpec, HashSpec, HLLSpec,
                                    SketchPlan)
    key, ka, kb = jax.random.split(jax.random.PRNGKey(0), 3)
    for L, n, b, with_cms in CASES:
        hs = HashSpec(family="cyclic", n=n, L=L)
        shape = (args.rows, COLS)
        h1v = jax.random.bits(key, shape if hs.words == 1 else (2,) + shape,
                              jnp.uint32)
        cms = CountMinSpec(depth=4, log2_width=20)
        ops = {}
        if with_cms:
            ops["cms"] = {
                "a": jax.random.bits(ka, (4, cms.key_pieces(hs)), jnp.uint32),
                "b": jax.random.bits(kb, (4,), jnp.uint32)}
        hll = HLLSpec(b=b)
        plan = SketchPlan(hs, (("hll", hll),) + ((("cms", cms),) if with_cms
                                                 else ()))
        cells = (hll.resolve_rank_bits(hs) + 1) << b
        compiled, compile_s = {}, {}
        for mode, cap in (("in_kernel", 1 << 40), ("scatter", 0)):
            # plans are jit trace keys: drop traces made under another split
            plan_mod.HLL_IN_KERNEL_MAX_CELLS = cap
            jax.clear_caches()
            t0 = time.perf_counter()
            try:
                compiled[mode] = jax.jit(lambda x, o: api.run(
                    plan, x, operands=o, impl="pallas")).lower(
                        h1v, ops).compile()
                jax.block_until_ready(compiled[mode](h1v, ops))
            except Exception as e:     # a mode the chip refuses
                print(json.dumps({"L": L, "n": n, "b": b, "cms": with_cms,
                                  "mode": mode, "error": str(e)[:300]}),
                      flush=True)
                continue
            compile_s[mode] = time.perf_counter() - t0
        # the modes alternate call by call, so drift reaches both alike
        ms = {mode: [] for mode in compiled}
        for _ in range(args.reps):
            for mode, fn in compiled.items():
                t0 = time.perf_counter()
                jax.block_until_ready(fn(h1v, ops))
                ms[mode].append(1e3 * (time.perf_counter() - t0))
        for mode, times in ms.items():
            print(json.dumps({
                "L": L, "n": n, "b": b, "cms": with_cms, "mode": mode,
                "cells": cells, "median_ms": statistics.median(times),
                "min_ms": min(times), "quartiles_ms": statistics.quantiles(
                    times, n=4), "compile_s": compile_s[mode],
                "device": jax.devices()[0].device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
