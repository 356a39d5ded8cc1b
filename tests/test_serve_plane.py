"""The decode-time n-gram serving plane (PR 7).

Three layers under test:

* kernel — ``api.decode`` fused Pallas epilogue (interpret mode on CPU) is
  bit-identical to the jnp oracle ``ref.decode_masks_ref`` across n
  (including the degraded n > L regime), vocab sizes (non-multiples of 32
  included), canary on/off, and runs as ONE pallas_call (jaxpr-asserted);
* session pool — the donated carry advances the recursion exactly (checked
  against from-scratch window hashes, n = 33 included), churn
  (evict + re-admit mid-generation) never corrupts surviving sessions and
  never retraces, one device dispatch per decode step;
* scale — 1/2/4/8 vdevs produce bit-identical tokens AND carries, with
  zero collective primitives in the sharded jaxpr.
"""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gf2
from repro import obs
from repro.kernels import api, decode, ref, shard
from repro.kernels.plan import DecodeSpec
from repro.serve import sessions as sess
from repro.serve import telemetry
from repro.serve.engine import NoRepeatNgram, SamplerConfig, ServeEngine

from repro.analysis.jaxpr import assert_no_collectives, count_primitive


def _rand_inputs(rng, spec, B, V, fill=0.3):
    logits = rng.standard_normal((B, V)).astype(np.float32)
    prefix = rng.integers(0, 2**32, size=B, dtype=np.uint32)
    ready = rng.integers(0, 2, size=B).astype(bool)
    bloom = (rng.random((B, spec.n_words)) < fill).astype(np.uint32)
    bloom = sum((bloom * rng.integers(0, 2**32, size=(B, spec.n_words),
                                      dtype=np.uint32)) for _ in range(1))
    h1 = rng.integers(0, 2**32, size=V, dtype=np.uint32)
    canary = (rng.integers(0, 2**32, size=spec.canary_words, dtype=np.uint32)
              if spec.has_canary else None)
    return logits, prefix, ready, bloom.astype(np.uint32), h1, canary


# ---------------------------------------------------------------------------
# layer 1: the fused kernel vs the jnp oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [2, 5, 33])
@pytest.mark.parametrize("V", [77, 512, 4096])
@pytest.mark.parametrize("canary", [0, 10])
def test_fused_bitparity_vs_oracle(n, V, canary):
    spec = DecodeSpec(n=n, L=32, log2_m=10, k=2, canary_log2_m=canary)
    rng = np.random.default_rng(n * 1000 + V + canary)
    logits, prefix, ready, bloom, h1, cb = _rand_inputs(rng, spec, 9, V)
    a = api.decode(spec, logits, prefix, ready, bloom, h1, canary_bits=cb,
                   impl="ref")
    b = api.decode(spec, logits, prefix, ready, bloom, h1, canary_bits=cb,
                   impl="pallas")
    assert set(a) == set(b)
    for key in a:
        np.testing.assert_array_equal(np.asarray(a[key]), np.asarray(b[key]),
                                      err_msg=key)


# the kernel probes filters of up to decode.VMEM_PROBE_MAX_WORDS words from
# VMEM (one lane gather per 128-word chunk); larger ones through XLA
# gathers ahead of the kernel: (n, log2_m, canary_log2_m, V, block_b)
VMEM_CASES = {
    "m5-one-word-bb8": (4, 5, 0, 300, 8),
    "m12-canary10-bb16": (4, 12, 10, 4096 + 77, 16),
    "m14-canary20-bb8": (4, 14, 20, 4096 + 300, 8),
    "m14-canary20-bb16": (4, 14, 20, 2 * 4096 + 5, 16),
    "degraded-n33-m14-canary10": (33, 14, 10, 1000, 8),
    "session-past-budget": (4, 21, 10, 700, 8),
    "canary-past-budget": (4, 14, 21, 700, 16),
    "both-past-budget": (4, 21, 21, 700, 8),
}


@pytest.mark.parametrize("case", sorted(VMEM_CASES))
def test_fused_vmem_probes_bitparity_vs_oracle(case):
    n, log2_m, canary, V, block_b = VMEM_CASES[case]
    spec = DecodeSpec(n=n, L=32, log2_m=log2_m, k=2, canary_log2_m=canary)
    assert decode.all_probes_in_vmem(spec) == ("past-budget" not in case)
    rng = np.random.default_rng(log2_m * 100 + canary + V)
    logits, prefix, ready, bloom, h1, cb = _rand_inputs(rng, spec, 19, V)
    a = api.decode(spec, logits, prefix, ready, bloom, h1, canary_bits=cb,
                   impl="ref")
    b = api.decode(spec, logits, prefix, ready, bloom, h1, canary_bits=cb,
                   impl="pallas", block_b=block_b)
    assert set(a) == set(b)
    for key in a:
        np.testing.assert_array_equal(np.asarray(a[key]), np.asarray(b[key]),
                                      err_msg=key)
    assert np.asarray(a["banned"]).any()
    if canary:
        assert np.asarray(a["canary"]).any()


@pytest.mark.parametrize("L", [16, 32])
def test_fused_bitparity_narrow_hash(L):
    spec = DecodeSpec(n=4, L=L, log2_m=8, k=3)
    rng = np.random.default_rng(L)
    logits, prefix, ready, bloom, h1, _ = _rand_inputs(rng, spec, 5, 200)
    a = api.decode(spec, logits, prefix, ready, bloom, h1, impl="ref")
    b = api.decode(spec, logits, prefix, ready, bloom, h1, impl="pallas")
    for key in a:
        np.testing.assert_array_equal(np.asarray(a[key]), np.asarray(b[key]))


def test_packed_mask_matches_logit_substitution():
    spec = DecodeSpec(n=3, log2_m=8)
    rng = np.random.default_rng(0)
    logits, prefix, ready, bloom, h1, _ = _rand_inputs(rng, spec, 4, 100)
    out = api.decode(spec, logits, prefix, ready, bloom, h1, impl="ref")
    packed = np.asarray(out["banned"])
    banned = np.asarray(out["logits"]) == ref.NEG_LOGIT
    # unpack word w bit i -> column 32w+i
    cols = np.arange(100)
    got = (packed[:, cols // 32] >> (cols % 32).astype(np.uint32)) & 1
    # -1e30 could collide with a real logit only by construction; randn can't
    np.testing.assert_array_equal(got.astype(bool), banned)


def test_theorem2_discard_high_bits_never_probed():
    """Flipping only the n-1 dependent high bits of every candidate hash
    must not change a single probe: banned masks are identical."""
    spec = DecodeSpec(n=6, L=32, log2_m=10)
    assert spec.out_bits == 32 - 6 + 1
    high = np.uint32(~spec.hash_mask & 0xFFFFFFFF)
    rng = np.random.default_rng(7)
    logits, prefix, ready, bloom, h1, _ = _rand_inputs(rng, spec, 6, 300)
    flip = rng.integers(0, 2**32, size=300, dtype=np.uint32) & high
    a = api.decode(spec, logits, prefix, ready, bloom, h1, impl="ref")
    b = api.decode(spec, logits, prefix, ready, bloom, h1 ^ flip, impl="ref")
    np.testing.assert_array_equal(np.asarray(a["banned"]),
                                  np.asarray(b["banned"]))


def test_not_ready_rows_ban_nothing():
    spec = DecodeSpec(n=3, log2_m=6)
    rng = np.random.default_rng(1)
    logits, prefix, _, _, h1, _ = _rand_inputs(rng, spec, 3, 64)
    bloom = np.full((3, spec.n_words), 0xFFFFFFFF, np.uint32)  # bans all
    ready = np.array([True, False, True])
    out = api.decode(spec, logits, prefix, ready, bloom, h1, impl="ref")
    packed = np.asarray(out["banned"])
    assert packed[0].all() and packed[2].all()
    assert not packed[1].any()
    np.testing.assert_array_equal(np.asarray(out["logits"])[1], logits[1])


def test_decode_one_pallas_call_in_jaxpr():
    spec = DecodeSpec(n=4, log2_m=8, canary_log2_m=8)
    rng = np.random.default_rng(2)
    logits, prefix, ready, bloom, h1, cb = _rand_inputs(rng, spec, 4, 128)
    jx = jax.make_jaxpr(
        lambda *a: api.decode(spec, *a, canary_bits=cb, impl="pallas"))(
            logits, prefix, ready, bloom, h1)
    assert count_primitive(jx.jaxpr, "pallas_call") == 1


def test_decode_spec_validation():
    with pytest.raises(ValueError, match="n must be >= 2"):
        DecodeSpec(n=1)
    with pytest.raises(ValueError, match="log2_m"):
        DecodeSpec(log2_m=3)
    with pytest.raises(ValueError, match="L must be"):
        DecodeSpec(L=33)
    s = DecodeSpec(n=33, L=32)
    assert s.degraded and s.out_bits == 32        # falls back to full L
    assert not DecodeSpec(n=5).degraded
    assert DecodeSpec(n=5).out_bits == 28


def test_decode_api_rejects_bad_args():
    spec = DecodeSpec(n=3, log2_m=6)
    rng = np.random.default_rng(3)
    logits, prefix, ready, bloom, h1, _ = _rand_inputs(rng, spec, 2, 40)
    with pytest.raises(TypeError, match="DecodeSpec"):
        api.decode(object(), logits, prefix, ready, bloom, h1)
    with pytest.raises(ValueError, match="bloom words shape"):
        api.decode(spec, logits, prefix, ready, bloom[:, :-1], h1)
    with pytest.raises(ValueError, match="prefix shape"):
        api.decode(spec, logits, prefix[:-1], ready, bloom, h1)
    with pytest.raises(ValueError, match="canary_bits given"):
        api.decode(spec, logits, prefix, ready, bloom, h1,
                   canary_bits=np.zeros(2, np.uint32))
    cspec = DecodeSpec(n=3, log2_m=6, canary_log2_m=8)
    with pytest.raises(ValueError, match="pass"):
        api.decode(cspec, logits, prefix, ready, bloom, h1)


# ---------------------------------------------------------------------------
# layer 2: the session pool carry
# ---------------------------------------------------------------------------


def _window_hash(h1, toks, L):
    """From-scratch CYCLIC hash of a window (the recursion's ground truth)."""
    h = 0
    for t in toks:
        h = gf2.rotl(jnp.uint32(h), 1, L) ^ np.uint32(h1[t])
        h = int(h)
    return h


@pytest.mark.parametrize("n", [2, 5, 33])
def test_pool_recursion_exact_vs_from_scratch(n):
    """The rolling prefix (rotate, XOR, expire-oldest) equals a from-scratch
    hash of the last n-1 symbols at every step — n = 33 (> L) included:
    the (n-1) mod L expiry is exact because rotl is L-periodic."""
    spec = DecodeSpec(n=n, L=32, log2_m=6)
    V, C, T = 97, 4, 80
    rng = np.random.default_rng(n)
    h1 = rng.integers(0, 2**32, size=V, dtype=np.uint32)
    pool = sess.SessionPool(spec, C, h1)
    pool.admit(C)
    streams = rng.integers(0, V, size=(C, T), dtype=np.int32)
    for t in range(T):
        pool.prime(streams[:, t : t + 1])
        for i in range(C):
            want = _window_hash(h1, streams[i, max(0, t + 1 - (n - 1)):t + 1],
                                spec.L)
            assert int(pool.state["prefix"][i]) == want, (t, i)


def test_pool_prime_one_dispatch_any_length():
    spec = DecodeSpec(n=4, log2_m=6)
    rng = np.random.default_rng(5)
    h1 = rng.integers(0, 2**32, size=50, dtype=np.uint32)
    pool = sess.SessionPool(spec, 4, h1)
    pool.admit(4)
    d0 = sess.dispatch_count()
    pool.prime(rng.integers(0, 50, size=(4, 37), dtype=np.int32))
    assert sess.dispatch_count() == d0 + 1


def test_pool_ragged_prime_matches_per_row():
    """lengths= raggedness: each row advances exactly its own prefix."""
    spec = DecodeSpec(n=3, log2_m=6)
    rng = np.random.default_rng(6)
    V = 64
    h1 = rng.integers(0, 2**32, size=V, dtype=np.uint32)
    toks = rng.integers(0, V, size=(3, 10), dtype=np.int32)
    lens = np.array([10, 4, 0], np.int32)
    pool = sess.SessionPool(spec, 3, h1)
    pool.admit(3)
    pool.prime(toks, lens)
    for i, ln in enumerate(lens):
        want = _window_hash(h1, toks[i, max(0, ln - 2):ln], 32)
        assert int(pool.state["prefix"][i]) == want
        assert int(pool.state["count"][i]) == min(ln, spec.n)


def test_pool_step_one_dispatch_and_oracle_parity():
    spec = DecodeSpec(n=3, log2_m=10)
    V, C = 129, 6
    rng = np.random.default_rng(8)
    h1 = rng.integers(0, 2**32, size=V, dtype=np.uint32)
    pool = sess.SessionPool(spec, C, h1)
    pool.admit(C)
    pool.prime(rng.integers(0, V, size=(C, 6), dtype=np.int32))
    st = jax.device_get(pool.state)
    logits = rng.standard_normal((C, V)).astype(np.float32)
    d0 = sess.dispatch_count()
    tok = pool.step(logits, temperature=0.0)
    assert sess.dispatch_count() == d0 + 1
    ref_out = api.decode(spec, logits, st["prefix"],
                         (st["count"] >= spec.n - 1) & (st["active"] != 0),
                         st["bloom"], h1, impl="ref")
    np.testing.assert_array_equal(
        np.asarray(tok), np.asarray(jnp.argmax(ref_out["logits"], axis=-1)))


@pytest.mark.parametrize("log2_m,vmem_steps", [(14, 3), (21, 0)])
def test_pool_counts_vmem_probe_steps(log2_m, vmem_steps):
    """``sessions.vmem_probe_steps`` counts the decode steps whose kernel
    probes both filters from VMEM: every step at the decode cell's spec,
    none once the no-repeat filter is past the VMEM bound."""
    spec = DecodeSpec(n=4, log2_m=log2_m, k=2, canary_log2_m=20)
    V, C = 300, 8
    rng = np.random.default_rng(log2_m)
    h1 = rng.integers(0, 2**32, size=V, dtype=np.uint32)
    canary = rng.integers(0, 2**32, size=spec.canary_words, dtype=np.uint32)
    pool = sess.SessionPool(spec, C, h1, canary_bits=canary, impl="pallas")
    ref_pool = sess.SessionPool(spec, C, h1, canary_bits=canary, impl="ref")
    prompts = rng.integers(0, V, size=(C, 6), dtype=np.int32)
    for p in (pool, ref_pool):
        p.admit(C)
        p.prime(prompts)
    d0 = sess.dispatch_count()
    v0 = obs.counter("sessions.vmem_probe_steps")
    for _ in range(3):
        logits = rng.standard_normal((C, V)).astype(np.float32)
        np.testing.assert_array_equal(
            np.asarray(pool.step(logits, temperature=0.0)),
            np.asarray(ref_pool.step(logits, temperature=0.0)))
    assert obs.counter("sessions.vmem_probe_steps") - v0 == vmem_steps
    assert sess.dispatch_count() - d0 == 6
    for k in pool.state:
        np.testing.assert_array_equal(np.asarray(pool.state[k]),
                                      np.asarray(ref_pool.state[k]),
                                      err_msg=k)


def test_pool_greedy_never_repeats_ngram():
    spec = DecodeSpec(n=3, log2_m=14)
    V, C, T = 83, 5, 60
    rng = np.random.default_rng(9)
    h1 = rng.integers(0, 2**32, size=V, dtype=np.uint32)
    pool = sess.SessionPool(spec, C, h1)
    pool.admit(C)
    prompts = rng.integers(0, V, size=(C, 4), dtype=np.int32)
    pool.prime(prompts)
    seqs = [list(prompts[i]) for i in range(C)]
    for _ in range(T):
        tok = np.asarray(pool.step(
            rng.standard_normal((C, V)).astype(np.float32), temperature=0.0))
        for i in range(C):
            seqs[i].append(int(tok[i]))
    for i in range(C):
        grams = [tuple(seqs[i][j : j + 3]) for j in range(len(seqs[i]) - 2)]
        assert len(grams) == len(set(grams)), f"row {i} repeated a trigram"


def test_pool_churn_evict_readmit_mid_generation():
    """Evicting + re-admitting slots mid-stream must not disturb surviving
    sessions (bit-compared against an undisturbed twin pool) and the
    re-admitted slots start from clean state."""
    spec = DecodeSpec(n=3, log2_m=8)
    V, C = 67, 6
    rng = np.random.default_rng(10)
    h1 = rng.integers(0, 2**32, size=V, dtype=np.uint32)
    prompts = rng.integers(0, V, size=(C, 5), dtype=np.int32)
    steps = [rng.standard_normal((C, V)).astype(np.float32) for _ in range(8)]
    key = jax.random.PRNGKey(4)

    a = sess.SessionPool(spec, C, h1)   # churned
    b = sess.SessionPool(spec, C, h1)   # undisturbed twin
    for p in (a, b):
        p.admit(C)
        p.prime(prompts)
    for lg in steps[:4]:
        ta = a.step(lg, key=key, temperature=0.7)
        tb = b.step(lg, key=key, temperature=0.7)
        np.testing.assert_array_equal(np.asarray(ta), np.asarray(tb))
    a.evict([1, 4])
    got = a.admit(2)
    assert sorted(got) == [1, 4]
    st = jax.device_get(a.state)
    assert st["count"][1] == 0 and st["prefix"][4] == 0
    survivors = [0, 2, 3, 5]
    for lg in steps[4:]:
        ta = a.step(lg, key=key, temperature=0.7)
        tb = b.step(lg, key=key, temperature=0.7)
        np.testing.assert_array_equal(np.asarray(ta)[survivors],
                                      np.asarray(tb)[survivors])
    for k in a.state:
        np.testing.assert_array_equal(
            np.asarray(a.state[k])[survivors], np.asarray(b.state[k])[survivors],
            err_msg=k)


def test_pool_admit_exhaustion_and_free_accounting():
    spec = DecodeSpec(n=2, log2_m=5)
    pool = sess.SessionPool(spec, 3, np.arange(10, dtype=np.uint32))
    s = pool.admit(2)
    assert pool.free_count == 1 and sorted(s) == [0, 1]
    with pytest.raises(ValueError, match="only 1 free"):
        pool.admit(2)
    pool.evict([0])
    assert pool.free_count == 2
    assert sorted(pool.active_slots) == [1]


def test_pool_never_retraces_across_steps_and_churn():
    spec = DecodeSpec(n=3, log2_m=7)
    V, C = 40, 4
    rng = np.random.default_rng(11)
    h1 = rng.integers(0, 2**32, size=V, dtype=np.uint32)
    pool = sess.SessionPool(spec, C, h1)
    pool.admit(C)
    key = jax.random.PRNGKey(0)
    pool.step(rng.standard_normal((C, V)).astype(np.float32), key=key)
    n0 = sess._step_plain._cache_size()
    for _ in range(4):
        pool.step(rng.standard_normal((C, V)).astype(np.float32), key=key)
    pool.evict([0, 2])
    pool.admit(2)
    pool.reset([1])
    pool.step(rng.standard_normal((C, V)).astype(np.float32), key=key)
    # a second pool with identical geometry shares the compiled step
    pool2 = sess.SessionPool(spec, C, h1)
    pool2.admit(1)
    pool2.step(rng.standard_normal((C, V)).astype(np.float32), key=key)
    assert sess._step_plain._cache_size() == n0


def test_accum_u64_carries_across_2_32():
    lo = jnp.asarray([0xFFFFFFF0], jnp.uint32)
    hi = jnp.asarray([3], jnp.uint32)
    lo1, hi1 = sess._accum_u64(lo, hi, jnp.asarray([0x20], jnp.uint32))
    assert int(telemetry.u64(lo1, hi1)[0]) == (3 << 32) + 0xFFFFFFF0 + 0x20


def test_telemetry_snapshot_matches_manual_counts():
    spec = DecodeSpec(n=3, log2_m=9, canary_log2_m=7)
    V, C = 50, 3
    rng = np.random.default_rng(12)
    h1 = rng.integers(0, 2**32, size=V, dtype=np.uint32)
    canary = rng.integers(0, 2**32, size=spec.canary_words, dtype=np.uint32)
    pool = sess.SessionPool(spec, C, h1, canary_bits=canary)
    pool.admit(C)
    pool.prime(rng.integers(0, V, size=(C, 4), dtype=np.int32))
    want_banned = want_canary = 0
    for _ in range(6):
        st = jax.device_get(pool.state)
        logits = rng.standard_normal((C, V)).astype(np.float32)
        out = api.decode(spec, logits, st["prefix"],
                         (st["count"] >= spec.n - 1) & (st["active"] != 0),
                         st["bloom"], h1, canary_bits=canary, impl="ref")
        unpack = lambda p: np.unpackbits(
            np.asarray(p).view(np.uint8), axis=-1).sum()
        want_banned += unpack(out["banned"])
        want_canary += unpack(out["canary"])
        pool.step(logits, temperature=0.0)
    snap = telemetry.snapshot(pool)
    assert snap["banned_candidates"] == want_banned
    assert snap["canary_hits"] == want_canary
    assert snap["decode_steps"] == 6 * C
    assert 0 < snap["bloom_fill_mean"] <= snap["bloom_fill_max"] < 1


# ---------------------------------------------------------------------------
# layer 3: row-wise sharding over the data mesh
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("d", [1, 2, 4, 8])
def test_pool_sharded_bitparity_any_device_count(d):
    if len(jax.devices()) < d:
        pytest.skip(f"needs {d} devices")
    spec = DecodeSpec(n=4, log2_m=9)
    V, C = 96, 8
    rng = np.random.default_rng(13)
    h1 = rng.integers(0, 2**32, size=V, dtype=np.uint32)
    prompts = rng.integers(0, V, size=(C, 5), dtype=np.int32)
    key = jax.random.PRNGKey(21)
    ref_pool = sess.SessionPool(spec, C, h1)
    shd_pool = sess.SessionPool(spec, C, h1, mesh=shard.data_mesh(d))
    for p in (ref_pool, shd_pool):
        p.admit(C)
        p.prime(prompts)
    for _ in range(5):
        lg = rng.standard_normal((C, V)).astype(np.float32)
        ta = ref_pool.step(lg, key=key, temperature=0.9, top_k=7)
        tb = shd_pool.step(lg, key=key, temperature=0.9, top_k=7)
        np.testing.assert_array_equal(np.asarray(ta), np.asarray(tb))
    for k in ref_pool.state:
        np.testing.assert_array_equal(np.asarray(ref_pool.state[k]),
                                      np.asarray(shd_pool.state[k]),
                                      err_msg=k)


def test_pool_sharded_zero_collectives():
    """The decode step is purely per-row: the sharded jaxpr must contain no
    collective primitive at all."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    spec = DecodeSpec(n=3, log2_m=8)
    V, C = 64, 8
    rng = np.random.default_rng(14)
    h1 = jnp.asarray(rng.integers(0, 2**32, size=V, dtype=np.uint32))
    state = sess.init_state(spec, C)
    logits = jnp.asarray(rng.standard_normal((C, V)), jnp.float32)
    mesh = shard.data_mesh(4)
    jx = jax.make_jaxpr(
        lambda st, lg, h, k, t: sess._step_body(
            spec, True, mesh, (), 0.8, 5, st, lg, h, None, k, t))(
        state, logits, h1, jax.random.PRNGKey(0), jnp.int32(0))
    assert_no_collectives(jx)
    assert count_primitive(jx.jaxpr, "shard_map") == 1


def test_pool_sharded_step_is_one_pallas_call():
    """Sharded or not, the fused epilogue stays ONE kernel dispatch per
    decode step."""
    spec = DecodeSpec(n=3, log2_m=8)
    V, C = 64, 8
    rng = np.random.default_rng(15)
    h1 = jnp.asarray(rng.integers(0, 2**32, size=V, dtype=np.uint32))
    state = sess.init_state(spec, C)
    logits = jnp.asarray(rng.standard_normal((C, V)), jnp.float32)
    for mesh in (None, shard.data_mesh(2)):
        jx = jax.make_jaxpr(
            lambda st, lg, h, k, t: sess._step_body(
                spec, False, mesh, (), 0.0, 0, st, lg, h, None, k, t))(
            state, logits, h1, jax.random.PRNGKey(0), jnp.int32(0))
        assert count_primitive(jx.jaxpr, "pallas_call") == 1, mesh


def test_pool_capacity_must_divide_mesh():
    spec = DecodeSpec(n=3, log2_m=6)
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    with pytest.raises(ValueError, match="must divide"):
        sess.SessionPool(spec, 6, np.arange(8, dtype=np.uint32),
                         mesh=shard.data_mesh(4))


def test_rowwise_requires_replicated_args():
    with pytest.raises(ValueError, match="only 1 argument"):
        shard.rowwise(lambda x, y: x, shard.data_mesh(1), n_row=1)(
            jnp.zeros((4,)))


# ---------------------------------------------------------------------------
# engine integration (fused plane vs the legacy oracle)
# ---------------------------------------------------------------------------


def _tiny_engine(scfg, **kw):
    from repro.configs.registry import get_config
    from repro.nn import lm
    cfg = get_config("paper-tiny").smoke()
    params, _ = lm.init(jax.random.PRNGKey(0), cfg)
    return cfg, ServeEngine(cfg, params, scfg, **kw)


@pytest.mark.parametrize("n", [2, 5])
def test_engine_fused_matches_legacy_greedy(n):
    scfg = SamplerConfig(temperature=0.0, no_repeat_ngram=n, seed=3)
    cfg, fused = _tiny_engine(scfg)
    _, legacy = _tiny_engine(dataclasses.replace(scfg, ngram_plane="legacy"))
    assert fused.plane == "fused" and legacy.plane == "legacy"
    prompts = jax.random.randint(jax.random.PRNGKey(2), (2, 5), 0, cfg.vocab)
    a, sa = fused.generate(prompts, 12)
    b, sb = legacy.generate(prompts, 12)
    np.testing.assert_array_equal(a, b)
    assert sa["banned_candidates"] == sb["banned_candidates"]
    assert sa["telemetry"]["decode_steps"] == 2 * 12


def test_engine_degraded_n33_warns_and_matches_legacy():
    """The satellite regression: n = 33 > L used to crash (family gate) /
    silently alias (hard-coded mod 32). Lifted: warns, runs, and the fused
    and legacy planes still agree bit-for-bit."""
    scfg = SamplerConfig(temperature=0.0, no_repeat_ngram=33, seed=3)
    with pytest.warns(UserWarning, match="exceeds the hash width"):
        cfg, fused = _tiny_engine(scfg)
    with pytest.warns(UserWarning, match="exceeds the hash width"):
        _, legacy = _tiny_engine(dataclasses.replace(scfg,
                                                     ngram_plane="legacy"))
    prompts = jax.random.randint(jax.random.PRNGKey(2), (2, 4), 0, cfg.vocab)
    a, _ = fused.generate(prompts, 8)
    b, _ = legacy.generate(prompts, 8)
    np.testing.assert_array_equal(a, b)


def test_legacy_pair_jitted_no_per_step_retrace():
    """The satellite: banned/update are jitted once — repeated decode steps
    hit the same executable (cache size stable)."""
    from repro.configs.registry import get_config
    cfg = get_config("paper-tiny").smoke()
    scfg = SamplerConfig(no_repeat_ngram=3, seed=0)
    nrn = NoRepeatNgram(cfg, scfg)
    state = nrn.init_state(2)
    tok = jnp.zeros((2,), jnp.int32)
    state = nrn.update(state, tok)
    nrn.banned(state)
    from repro.serve.engine import _legacy_banned, _legacy_update
    nb, nu = _legacy_banned._cache_size(), _legacy_update._cache_size()
    for _ in range(5):
        state = nrn.update(state, tok)
        nrn.banned(state)
    assert _legacy_banned._cache_size() == nb
    assert _legacy_update._cache_size() == nu


def test_engine_rejects_bad_plane_and_canary_misuse():
    scfg = SamplerConfig(no_repeat_ngram=3, ngram_plane="nope")
    with pytest.raises(ValueError, match="ngram_plane"):
        _tiny_engine(scfg)
    scfg = SamplerConfig(no_repeat_ngram=3, canary_log2_m=8)
    with pytest.raises(ValueError, match="canary_bits"):
        _tiny_engine(scfg)
    with pytest.raises(ValueError, match="canary_bits"):
        _tiny_engine(SamplerConfig(), canary_bits=np.zeros(8, np.uint32))


def test_engine_sharded_fused_matches_unsharded():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    scfg = SamplerConfig(temperature=0.0, no_repeat_ngram=3, seed=3)
    cfg, d1 = _tiny_engine(scfg)
    _, d8 = _tiny_engine(scfg, data_shards=8)
    prompts = jax.random.randint(jax.random.PRNGKey(2), (3, 6), 0, cfg.vocab)
    a, _ = d1.generate(prompts, 10)
    b, _ = d8.generate(prompts, 10)    # B=3 padded to C=8 inactive rows
    np.testing.assert_array_equal(a, b)
