"""Where one decode step's time goes: ``SessionPool.step`` cut into parts.

At the decode shape ``chip_smoke.py`` runs (1024 sessions, the Llama 3.2
vocabulary, ``DecodeSpec(n=4, log2_m=14, k=2, canary_log2_m=20)``, 512-token
prompts primed), times each part of one step alone, as its own jitted call
on the primed pool's carry:

* ``step`` / ``step_ref`` — the whole ``SessionPool.step``, kernel path
  (``impl="auto"``) and jnp reference graph (``impl="ref"``);
* ``decode`` / ``decode_ref`` — ``api.decode`` alone: candidate hashes,
  Bloom probes, logit masking, mask packing;
* ``probe_norepeat`` / ``probe_canary`` — candidate hashes and the probes
  alone (``ref.bloom_probe_hits``) against the per-session filters / the
  shared canary filter;
* ``sample`` — the per-row categorical draw over the masked logits;
* ``advance`` — the carry update (ring, prefix, Bloom insert) and the
  banned/canary counters.

The last line is ``step - decode - sample - advance``: the part of the
step that no piece timed alone accounts for.

    PYTHONPATH=src python benchmarks/decode_breakdown.py
    PYTHONPATH=src python benchmarks/decode_breakdown.py --sessions 16 \
        --vocab 4096 --prompt 32        # small enough for a CPU

Each line is the median of ``--reps`` timed calls after one warm-up call,
in milliseconds of host wall time.
"""
from __future__ import annotations

import argparse
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs.llama3p2_3b import CONFIG
from repro.data.corpus import zipf_tokens
from repro.kernels import api
from repro.kernels import ref as kref
from repro.kernels.plan import DecodeSpec
from repro.serve import sessions
from repro.serve.sessions import SessionPool


def _median_ms(fn, reps):
    jax.block_until_ready(fn())
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    return 1e3 * statistics.median(times)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--sessions", type=int, default=1024)
    ap.add_argument("--vocab", type=int, default=CONFIG.vocab)
    ap.add_argument("--prompt", type=int, default=512)
    ap.add_argument("--reps", type=int, default=3)
    args = ap.parse_args(argv)
    enable_compile_cache()

    spec = DecodeSpec(n=4, log2_m=14, k=2, canary_log2_m=20)
    C, V = args.sessions, args.vocab
    rng = np.random.default_rng(0)
    h1 = rng.integers(0, 2**32, size=V, dtype=np.uint32)
    canary = rng.integers(0, 2**32, size=spec.canary_words, dtype=np.uint32)
    prompts = zipf_tokens(C * args.prompt, V, rng=rng).reshape(C, args.prompt)
    pools = {}
    for impl in ("auto", "ref"):
        pools[impl] = SessionPool(spec, C, h1, canary_bits=canary, impl=impl)
        pools[impl].admit(C)
        pools[impl].prime(prompts)
    pool = pools["auto"]
    # a copy: the pool donates its own carry to every step
    st = jax.tree_util.tree_map(jnp.copy, pool.state)
    ph1, bits = pool.h1, pool.canary_bits
    key = jax.random.PRNGKey(0)
    logits = jax.random.normal(key, (C, V), jnp.float32)
    keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
        key, jnp.arange(C, dtype=jnp.int32))
    live = st["active"] != 0
    ready = (st["count"] >= spec.n - 1) & live

    def cand(prefix):
        h = kref._rotl_const(prefix, 1, spec.L)[:, None] ^ ph1[None, :]
        return h & np.uint32(spec.hash_mask)

    decode = {impl: jax.jit(lambda lg, s, impl=impl: api.decode(
        spec, lg, s["prefix"], ready, s["bloom"], ph1, canary_bits=bits,
        impl=impl)) for impl in ("auto", "ref")}
    probe_norepeat = jax.jit(lambda s: kref.bloom_probe_hits(
        cand(s["prefix"]), s["bloom"], spec.k, spec.log2_m))
    probe_canary = jax.jit(lambda s: kref.bloom_probe_hits(
        cand(s["prefix"]), bits, spec.canary_k, spec.canary_log2_m))
    sample = jax.jit(lambda lg: jax.vmap(
        lambda k, l: jax.random.categorical(k, l / 1.0))(keys, lg))

    @jax.jit
    def advance(s, token, banned, canary_mask):
        out = sessions._advance_rows(spec, s, ph1[token], live)
        zero = np.uint32(0)
        for name, mask in (("banned", banned), ("canary", canary_mask)):
            inc = jnp.where(live, sessions._popcount_rows(mask), zero)
            out[f"{name}_lo"], out[f"{name}_hi"] = sessions._accum_u64(
                s[f"{name}_lo"], s[f"{name}_hi"], inc)
        return out

    masked = decode["auto"](logits, st)
    token = sample(masked["logits"])
    rows = {
        "step": lambda: pools["auto"].step(logits, key=key),
        "step_ref": lambda: pools["ref"].step(logits, key=key),
        "decode": lambda: decode["auto"](logits, st),
        "decode_ref": lambda: decode["ref"](logits, st),
        "probe_norepeat": lambda: probe_norepeat(st),
        "probe_canary": lambda: probe_canary(st),
        "sample": lambda: sample(masked["logits"]),
        "advance": lambda: advance(st, token, masked["banned"],
                                   masked["canary"]),
    }
    print(f"decode_breakdown platform={jax.default_backend()} "
          f"kind={jax.devices()[0].device_kind!r} sessions={C} vocab={V} "
          f"reps={args.reps}", flush=True)
    ms = {}
    for name, fn in rows.items():
        ms[name] = _median_ms(fn, args.reps)
        print(f"{name:16s} {ms[name]:.1f} ms", flush=True)
    rest = ms["step"] - ms["decode"] - ms["sample"] - ms["advance"]
    print(f"{'unaccounted':16s} {rest:.1f} ms", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
