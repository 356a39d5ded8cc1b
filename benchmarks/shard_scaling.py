"""Multi-device scaling of the sharded sketch data-plane (PR 3).

Two measurements, both parity-asserted before timing so a speedup is never
measured against a semantically different computation:

* **sharded signing sweep** — one MinHash plan over a (B, S) batch through
  ``shard.run_sharded`` at 1/2/4/8 data shards (the 8 virtual CPU devices
  ``test.sh``/``benchmarks/run.py`` expose; on real hardware the same knob
  sweeps TPU cores). Outputs are bit-identical at every device count.
* **batched dedup** — ``MinHashDeduper.add_batch`` with the ``data_shards``
  knob on vs off (sharded signing + band-sharded LSH probing vs the
  single-device path), identical flags asserted.

Virtual CPU devices share the host's physical cores, so CPU wall-clock
scaling is bounded by core count (this container has few); the sweep still
proves the partitioning is real (per-shard work drops with d) and records
the trajectory for real-TPU runs.
"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.dedup import DedupConfig, MinHashDeduper
from repro.kernels import api, shard
from repro.kernels.plan import HashSpec, MinHashSpec, SketchPlan


def _timeit(fn, reps=5):
    fn()
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _sign_sweep(B: int, S: int):
    plan = SketchPlan(HashSpec(family="cyclic", n=8, L=32),
                      (("sig", MinHashSpec(k=64)),))
    key = jax.random.PRNGKey(0)
    kx, ka, kb = jax.random.split(key, 3)
    h1v = jax.random.bits(kx, (B, S), dtype=jnp.uint32)
    a = jax.random.bits(ka, (64,), dtype=jnp.uint32) | np.uint32(1)
    b = jax.random.bits(kb, (64,), dtype=jnp.uint32)
    operands = {"sig": {"a": a, "b": b}}
    want = np.asarray(api.run(plan, h1v, operands=operands)["sig"])

    rows, t1 = [], None
    for d in (1, 2, 4, 8):
        if d > len(jax.devices()):
            continue
        run = lambda d=d: shard.run_sharded(plan, h1v, operands=operands,
                                            data_shards=d)["sig"]
        np.testing.assert_array_equal(np.asarray(run()), want)  # bit-exact
        t = _timeit(lambda: jax.block_until_ready(run()))
        t1 = t1 or t
        rows.append({"name": f"shard_sign_d{d}_{B}x{S}",
                     "us_per_call": t * 1e6,
                     "derived": f"{B / t:.1f} docs/s; {t1 / t:.2f}x vs d=1"})
    return rows


def _timed_add_batch(cfg, docs, reps: int = 3):
    """Steady-state add_batch time: each rep builds a fresh deduper (an
    add_batch mutates the index, so it cannot repeat on one instance),
    warms the per-instance jit via signature_many (same trace keys, no
    index mutation), then times one add_batch with the cyclic GC parked
    (a collection inside the ~100ms window is pure noise); best-of-``reps``
    damps what async-dispatch jitter remains."""
    best, flags = float("inf"), None
    for _ in range(reps):
        dd = MinHashDeduper(cfg)
        dd.signature_many(docs)
        gc.collect()
        gc.disable()
        try:
            t0 = time.perf_counter()
            flags = dd.add_batch(docs)
            best = min(best, time.perf_counter() - t0)
        finally:
            gc.enable()
        dd.close()
    return best, flags


def _dedup_rows(n_docs: int = 512, doc_len: int = 1024):
    rng = np.random.default_rng(0)
    lens = rng.integers(doc_len // 2 + 1, doc_len + 1, size=n_docs)
    docs = [rng.integers(0, 65536, size=int(n)).astype(np.int32)
            for n in lens]
    dmax = min(8, len(jax.devices()))
    cfg1 = DedupConfig(vocab=65536)
    cfgd = DedupConfig(vocab=65536, data_shards=dmax, lsh_workers=4)
    t1, f1 = _timed_add_batch(cfg1, docs)
    td, fd = _timed_add_batch(cfgd, docs)
    np.testing.assert_array_equal(f1, fd)                       # same flags
    # the PR 5 regression stays fixed: sharded end-to-end dedup must not be
    # slower than single-device (the per-chunk shard_map dispatches that
    # caused the 3.1x inversion are now folded into one scan per block).
    # 15% headroom: both sides are ~100ms host-loop measurements.
    assert td <= t1 * 1.15, (
        f"sharded dedup regressed: d{dmax} {td * 1e3:.1f}ms vs "
        f"d1 {t1 * 1e3:.1f}ms")
    return [
        {"name": f"shard_dedup_batch_d1_{n_docs}docs",
         "us_per_call": t1 * 1e6, "derived": f"{n_docs / t1:.1f} docs/s"},
        {"name": f"shard_dedup_batch_d{dmax}_{n_docs}docs",
         "us_per_call": td * 1e6,
         "derived": f"{n_docs / td:.1f} docs/s; {t1 / td:.2f}x vs d=1 "
                    f"(scan-executor sharded signing + band-sharded LSH "
                    f"probe; asserted <= 1.15x d1 time)"},
    ]


def run(n_docs: int = 512, sign_B: int = 256, sign_S: int = 2048,
        scale: float = 1.0):
    """``scale`` (run.py passes REPRO_BENCH_CHARS / 4.3M) shrinks the
    workloads for smoke runs; floors keep every measurement meaningful.

    The sign-sweep floor is 128 rows: BENCH_pr4 was recorded at a smoke
    scale that shrank the batch to 25 rows, where per-shard dispatch
    overhead dwarfs the 3-row shards and inverts the d1-vs-d2/4/8 ordering
    (2555us vs ~6000us) that the full-size sweep shows at 2.7-3.1x. 128
    rows keeps >= 16 rows per shard at d=8 — small enough for smoke, large
    enough that the sweep measures scaling rather than dispatch floor."""
    scale = min(1.0, max(scale, 0.0))
    # dedup floor 256: the sharded signing win comes from shard-scaled
    # groups (stream_rows per shard), which need >= 4 groups' worth of
    # docs to engage — a smaller smoke corpus would measure the fallback
    n_docs = max(256, int(n_docs * scale))
    sign_B = max(128, int(sign_B * scale))
    return _sign_sweep(sign_B, sign_S) + _dedup_rows(n_docs)


if __name__ == "__main__":
    for r in run():
        print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
