"""Smoke run of the n-gram data plane and decode plane on TPU.

Drives the library's own entry points once at production widths and checks
every result bit-for-bit against the jnp reference graph (``impl="ref"``)
on the same chip:

* dedup — a replicated ``DedupService`` signs and deduplicates four
  batches of 4096 documents (256 to 8192 tokens, 2^17 vocabulary, 20%
  planted near-duplicates);
* streams — ``NgramStats`` (HLL b=12, CountMin 4 x 2^16) and
  ``Decontaminator`` (Bloom 2^22 bits) each fold eight (8, 64, 4096) token
  blocks through ``update_stream_many``;
* decode — a 1024-session ``SessionPool`` over the Llama 3.2 vocabulary
  (128256) primes 512-token prompts and takes 32 decode steps.

With ``--chips 4`` it runs only the multi-chip paths — ``run_sharded`` of a
four-sketch plan, ``NgramStats`` and ``SessionPool`` at ``data_shards=4`` —
each against the same call on one device.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips

Every phase prints the device, its wall time with JAX's compile time shown
apart, the items it processed, its parity verdict, whether its jitted step
holds the Pallas kernel (``tpu_custom_call``) and the device's peak memory.
Without a TPU it exits nonzero before any phase. The last line of standard
output is ``{"ok": true, "device": {...}}`` only when every phase passed.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0


class Clock:
    """Wall time of a block, with the compile time JAX reports inside it."""

    compile_s = 0.0

    @classmethod
    def listen(cls, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            cls.compile_s += duration

    def __enter__(self):
        self.t0, self.c0 = time.perf_counter(), Clock.compile_s
        return self

    def __exit__(self, *exc):
        self.wall = time.perf_counter() - self.t0
        self.compile = Clock.compile_s - self.c0


def has_kernel(jitted, *args) -> bool:
    """The lowered program of ``jitted(*args)`` holds a Pallas TPU kernel."""
    return "tpu_custom_call" in jitted.lower(*args).as_text()


def same(a, b) -> bool:
    """Bit-identical pytrees of arrays."""
    import jax
    import numpy as np
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


def report(name, clock, items, checks, kernel, dev, ref_clock=None):
    stats = dev.memory_stats() or {}
    parity = all(checks.values())
    ref = ("" if ref_clock is None else
           f"ref_run_s={ref_clock.wall - ref_clock.compile:.3f} ")
    print(f"[{name}] platform={dev.platform} kind={dev.device_kind!r} "
          f"wall_s={clock.wall:.3f} compile_s={clock.compile:.3f} "
          f"run_s={clock.wall - clock.compile:.3f} {ref}items={items} "
          f"parity={'bit-identical' if parity else 'MISMATCH'} "
          f"checks={json.dumps(checks)} tpu_custom_call={kernel} "
          f"peak_bytes_in_use={stats.get('peak_bytes_in_use', 'n/a')}",
          flush=True)
    return parity and kernel


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------


def phase_dedup(dev, n_batches=4, batch=4096, doc_len=(256, 8192)):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.data.corpus import CorpusSpec, documents
    from repro.data.dedup import DedupConfig
    from repro.data.service import DedupService, ServiceConfig
    from repro.kernels import api, stream

    # The gate runs at 1% token flips, which keep the planted pairs near
    # Jaccard 0.86. The corpus default (2%) plants them at ~0.74, on the
    # 0.7 verdict threshold; its recall is printed, not gated.
    spec = CorpusSpec(n_docs=n_batches * batch, doc_len=doc_len,
                      vocab=1 << 17, dup_rate=0.2, mutate_frac=0.01,
                      seed=SEED)
    docs, dup_of = documents(spec)
    truth = dup_of >= 0
    svc_cfg = ServiceConfig(n_workers=4, replication=2)
    batches = [docs[i : i + batch] for i in range(0, len(docs), batch)]
    with DedupService(DedupConfig(), svc_cfg) as svc, \
            DedupService(DedupConfig(impl="ref"), svc_cfg) as ref:
        with Clock() as clock:
            flags = np.concatenate([svc.add_batch(b) for b in batches])
        with Clock() as ref_clock:
            want = np.concatenate([ref.add_batch(b) for b in batches])
        tele = svc.telemetry()
        cfg, dd = svc.dd.cfg, svc.dd
        T, B, C = cfg.stream_block_chunks, cfg.stream_rows, cfg.stream_chunk_s
        ops = api._check_operands(
            dd.plan, {"sig": {"a": dd.mh_params["a"],
                              "b": dd.mh_params["b"]}}, None)
        kernel = has_kernel(
            stream._scan_donated, dd.plan, False, None, (), None,
            stream.init_state(dd.plan, B),
            jax.ShapeDtypeStruct((T, B, C), jnp.uint32), None,
            jax.ShapeDtypeStruct((T, B), jnp.int32), ops)
        sigs_same = same(svc._sigs, ref._sigs)
        default = dataclasses.replace(
            spec, mutate_frac=CorpusSpec.mutate_frac)
        docs_d, dup_of_d = documents(default)
        with DedupService(DedupConfig(), svc_cfg) as svc_d:
            flags_d = np.concatenate(
                [svc_d.add_batch(docs_d[i : i + batch])
                 for i in range(0, len(docs_d), batch)])
    recall, precision = _recall_precision(flags, truth)
    tokens = sum(len(d) for d in docs)
    print(f"[dedup] planted={int(truth.sum())} flagged={int(flags.sum())} "
          f"recall={recall:.4f} precision={precision:.4f} "
          f"recall_loss={tele['recall_loss']} "
          f"dead_replicas={tele['dead_replicas']} "
          f"dropped_inserts={tele['dropped_inserts']}", flush=True)
    print("[dedup] not gated: corpus default mutate_frac={} recall={:.4f} "
          "precision={:.4f}".format(
              default.mutate_frac,
              *_recall_precision(flags_d, dup_of_d >= 0)), flush=True)
    checks = {"flags": bool(np.array_equal(flags, want)),
              "signatures": sigs_same,
              "recall>=0.9": recall >= 0.9, "precision>=0.9": precision >= 0.9,
              "recall_loss==0": tele["recall_loss"] == 0,
              "no_dead_replica": tele["dead_replicas"] == 0,
              "no_dropped_insert": tele["dropped_inserts"] == 0}
    return report("dedup", clock, f"{len(docs)} docs / {tokens} tokens",
                  checks, kernel, dev, ref_clock)


def _recall_precision(flags, truth):
    tp = int((flags & truth).sum())
    return tp / max(int(truth.sum()), 1), tp / max(int(flags.sum()), 1)


def _zipf_blocks(n_blocks, shape, vocab):
    import numpy as np
    from repro.data.corpus import zipf_tokens
    rng = np.random.default_rng(SEED + 1)
    size = int(np.prod(shape))
    return [zipf_tokens(size, vocab, rng=rng).reshape(shape)
            for _ in range(n_blocks)]


def phase_streams(dev, n_blocks=8, T=8, B=64, C=4096):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.data.decontam import DecontamConfig, Decontaminator
    from repro.data.stats import NgramStats, StatsConfig
    from repro.kernels import api, stream

    blocks = _zipf_blocks(n_blocks, (T, B, C), StatsConfig().vocab)
    # the protected eval set is cut from the stream itself, so the Bloom
    # hit counts being compared are far from zero
    evals = np.stack([blocks[i % n_blocks][i % T, i % B, :1024]
                      for i in range(64)])
    st, st_ref = NgramStats(StatsConfig()), NgramStats(StatsConfig(impl="ref"))
    dc = Decontaminator(DecontamConfig())
    dc_ref = Decontaminator(DecontamConfig(impl="ref"))
    for d in (dc, dc_ref):
        d.add_eval_set(evals)

    def run(stats, decon):
        s, d = stats.init_stream(B), decon.init_stream(B)
        for blk in blocks:
            s = stats.update_stream_many(s, blk)
            d = decon.update_stream_many(d, blk)
        hits = stream.finalize(decon.plan, d["stream"], batch=B)["bloom"]
        return stats.finalize_stream(s), np.asarray(hits)

    with Clock() as clock:
        (got, hits) = run(st, dc)
        jax.block_until_ready(got)
    with Clock() as ref_clock:
        want, want_hits = jax.block_until_ready(run(st_ref, dc_ref))

    chunks = jax.ShapeDtypeStruct((T, B, C), jnp.uint32)
    lens = jax.ShapeDtypeStruct((T, B), jnp.int32)
    st_ops = api._check_operands(
        st.plan, {"cms": {"a": st._cms_params["a"],
                          "b": st._cms_params["b"]}}, None)
    dc_ops = api._check_operands(dc.plan, {"bloom": {"bits": dc.bits}}, None)
    kernel = (has_kernel(stream._scan_donated, st.plan, False, None, (),
                         None, stream.init_state(st.plan, B), chunks, None,
                         lens, st_ops)
              and has_kernel(stream._scan_donated, dc.plan, False, None, (),
                             None, stream.init_state(dc.plan, B), chunks,
                             chunks, lens, dc_ops))
    print(f"[streams] distinct_ngrams~{st.distinct_ngrams(got):.0f} "
          f"bloom_hits={int(hits.sum())}", flush=True)
    checks = {"hll": same(got["hll"], want["hll"]),
              "countmin": same(got["cms"], want["cms"]),
              "bloom_hits": same(hits, want_hits),
              "bloom_hits>0": int(hits.sum()) > 0}
    return report("streams", clock,
                  f"2 x {n_blocks * T * B * C} tokens", checks, kernel, dev,
                  ref_clock)


def _decode_setup(capacity, prompt, seed):
    import numpy as np
    from repro.configs.llama3p2_3b import CONFIG
    from repro.data.corpus import zipf_tokens
    from repro.kernels.plan import DecodeSpec
    spec = DecodeSpec(n=4, log2_m=14, k=2, canary_log2_m=20)
    V = CONFIG.vocab
    rng = np.random.default_rng(seed)
    h1 = rng.integers(0, 2**32, size=V, dtype=np.uint32)
    canary = rng.integers(0, 2**32, size=spec.canary_words, dtype=np.uint32)
    prompts = zipf_tokens(capacity * prompt, V, rng=rng).reshape(
        capacity, prompt)
    return spec, V, h1, canary, prompts


def _decode_run(pools, prompts, steps, V):
    """Prime every pool, then step them all on the same seeded logits;
    returns the per-pool (steps, C) sampled tokens."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    C = prompts.shape[0]
    key = jax.random.PRNGKey(SEED)
    logits_at = jax.jit(lambda t: jax.random.normal(
        jax.random.fold_in(key, t), (C, V), jnp.float32))
    for p in pools:
        p.admit(C)
        p.prime(prompts)
    toks = [[] for _ in pools]
    for t in range(steps):
        lg = logits_at(t)
        for i, p in enumerate(pools):
            toks[i].append(p.step(lg, key=key))
    return [np.asarray(jnp.stack(tk)) for tk in toks]


def phase_decode(dev, capacity=1024, prompt=512, steps=32):
    import jax
    import jax.numpy as jnp
    from repro.serve import sessions
    from repro.serve.sessions import SessionPool

    spec, V, h1, canary, prompts = _decode_setup(capacity, prompt, SEED + 2)
    pool = SessionPool(spec, capacity, h1, canary_bits=canary)
    ref = SessionPool(spec, capacity, h1, canary_bits=canary, impl="ref")
    with Clock() as clock:
        (got,) = _decode_run([pool], prompts, steps, V)
    with Clock() as ref_clock:
        (want,) = _decode_run([ref], prompts, steps, V)
    kernel = has_kernel(
        sessions._step_donated, spec, False, None, (), 1.0, 0, pool.state,
        jax.ShapeDtypeStruct((capacity, V), jnp.float32), pool.h1,
        pool.canary_bits, jax.random.PRNGKey(SEED), jnp.int32(0))
    banned = int(pool.state["banned_lo"].sum())
    canary_hits = int(pool.state["canary_lo"].sum())
    print(f"[decode] vocab={V} sessions={capacity} steps={steps} "
          f"banned_candidates={banned} canary_hits={canary_hits}", flush=True)
    checks = {"tokens": same(got, want), "carry": same(pool.state, ref.state),
              "banned>0": banned > 0}
    return report("decode", clock,
                  f"{capacity * steps} tokens x {V} candidates", checks,
                  kernel, dev, ref_clock)


# ---------------------------------------------------------------------------
# four chips: data_shards=4 against one device
# ---------------------------------------------------------------------------


def phase_run_sharded(dev, d=4, B=256, S=4096):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import api, shard
    from repro.kernels.plan import (BloomSpec, CountMinSpec, HashSpec,
                                    HLLSpec, MinHashSpec, SketchPlan)

    plan = SketchPlan(HashSpec(family="cyclic", n=8),
                      (("sig", MinHashSpec(k=64)), ("card", HLLSpec(b=12)),
                       ("freq", CountMinSpec(depth=4, log2_width=16)),
                       ("decontam", BloomSpec(k=4, log2_m=22))))
    rng = np.random.default_rng(SEED + 3)
    u32 = lambda *shape: jnp.asarray(
        rng.integers(0, 2**32, size=shape, dtype=np.uint32))
    bits = np.zeros((plan.sketches[3][1].n_words,), np.uint32)
    bits[rng.integers(0, bits.size, bits.size // 2)] = 0xFFFFFFFF
    kw = dict(h1v_b=u32(B, S),
              n_windows=jnp.asarray(rng.integers(0, S - 6, B), jnp.int32),
              operands={"sig": {"a": u32(64) | 1, "b": u32(64)},
                        "freq": {"a": u32(4) | 1, "b": u32(4)},
                        "decontam": {"bits": jnp.asarray(bits)}})
    x = u32(B, S)
    with Clock() as clock:
        got = jax.block_until_ready(
            shard.run_sharded(plan, x, data_shards=d, **kw))
    want = api.run(plan, x, **kw)
    mesh = shard.data_mesh(d)
    xs, xb, nw, ws, ops, _, _ = api.validate(
        plan, x, kw["h1v_b"], kw["n_windows"], kw["operands"], "auto")
    kernel = has_kernel(shard._run_sharded, plan, mesh, False, (), xs, xb,
                        nw, ws, ops)
    checks = {name: same(got[name], want[name]) for name in plan.names}
    return report("run_sharded x4", clock, f"{B * S} symbols", checks,
                  kernel, dev)


def phase_stats_sharded(dev, d=4, n_blocks=4, T=8, B=64, C=4096):
    import jax
    import jax.numpy as jnp
    from repro.data.stats import NgramStats, StatsConfig
    from repro.kernels import api, shard, stream

    blocks = _zipf_blocks(n_blocks, (T, B, C), StatsConfig().vocab)

    def run(stats):
        s = stats.init_stream(B)
        for blk in blocks:
            s = stats.update_stream_many(s, blk)
        return jax.block_until_ready(stats.finalize_stream(s))

    stats = NgramStats(StatsConfig(data_shards=d))
    with Clock() as clock:
        got = run(stats)
    want = run(NgramStats(StatsConfig()))
    ops = api._check_operands(
        stats.plan, {"cms": {"a": stats._cms_params["a"],
                             "b": stats._cms_params["b"]}}, None)
    kernel = has_kernel(
        stream._scan_donated, stats.plan, False, shard.data_mesh(d), (),
        None, stream.init_state(stats.plan, B, data_shards=d),
        jax.ShapeDtypeStruct((T, B, C), jnp.uint32), None,
        jax.ShapeDtypeStruct((T, B), jnp.int32), ops)
    checks = {"hll": same(got["hll"], want["hll"]),
              "countmin": same(got["cms"], want["cms"])}
    return report("stats x4", clock, f"{n_blocks * T * B * C} tokens",
                  checks, kernel, dev)


def phase_pool_sharded(dev, d=4, capacity=1024, prompt=512, steps=8):
    import jax
    import jax.numpy as jnp
    from repro.kernels import shard
    from repro.serve import sessions
    from repro.serve.sessions import SessionPool

    spec, V, h1, canary, prompts = _decode_setup(capacity, prompt, SEED + 4)
    pool = SessionPool(spec, capacity, h1, canary_bits=canary,
                       data_shards=d)
    one = SessionPool(spec, capacity, h1, canary_bits=canary)
    with Clock() as clock:
        (got,) = _decode_run([pool], prompts, steps, V)
    (want,) = _decode_run([one], prompts, steps, V)
    kernel = has_kernel(
        sessions._step_donated, spec, False, shard.data_mesh(d), (), 1.0, 0,
        pool.state, jax.ShapeDtypeStruct((capacity, V), jnp.float32),
        pool.h1, pool.canary_bits, jax.random.PRNGKey(SEED), jnp.int32(0))
    checks = {"tokens": same(got, want), "carry": same(pool.state, one.state)}
    return report("session_pool x4", clock,
                  f"{capacity * steps} tokens x {V} candidates", checks,
                  kernel, dev)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    try:
        import jax
        from repro.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the program: {e}", file=sys.stderr)
        return 2
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: needs a TPU, JAX's backend is "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 1
    devices = jax.devices()
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    jax.monitoring.register_event_duration_secs_listener(Clock.listen)
    dev = devices[0]
    phases = ((phase_dedup, phase_streams, phase_decode) if args.chips == 1
              else (phase_run_sharded, phase_stats_sharded,
                    phase_pool_sharded))
    ok = True
    for phase in phases:
        try:
            ok &= phase(dev)
        except Exception as e:  # noqa: BLE001 - report, then fail the run
            print(f"[{phase.__name__}] FAILED: {type(e).__name__}: {e}",
                  flush=True)
            ok = False
    if not ok:
        print("chip_smoke: a phase failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
