"""Pure-jnp oracles for every Pallas kernel (independent of repro.core).

These are deliberately naive re-implementations of the defining formulas —
the kernels and `repro.core.families` are each validated against these, so a
shared bug between kernel and library would still be caught by the paper's
enumeration tests in `tests/test_independence.py`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_U32 = jnp.uint32


def _rotl_const(v: jnp.ndarray, r: int, L: int) -> jnp.ndarray:
    r %= L
    m = np.uint32((1 << L) - 1) if L < 32 else np.uint32(0xFFFFFFFF)
    v = v.astype(_U32) & m
    if r == 0:
        return v
    return ((v << np.uint32(r)) | (v >> np.uint32(L - r))) & m


def cyclic_ref(h1v: jnp.ndarray, n: int, L: int = 32) -> jnp.ndarray:
    """CYCLIC window hashes: H_j = XOR_k rotl(h1v[j+k], n-1-k). (..., S) -> (..., S-n+1)."""
    S = h1v.shape[-1]
    W = S - n + 1
    acc = jnp.zeros(h1v.shape[:-1] + (W,), dtype=_U32)
    for k in range(n):
        acc = acc ^ _rotl_const(h1v[..., k : k + W], (n - 1 - k) % L, L)
    return acc


def general_ref(h1v: jnp.ndarray, n: int, p: int, L: int = 32) -> jnp.ndarray:
    """GENERAL window hashes mod irreducible p (given WITH top bit)."""
    S = h1v.shape[-1]
    W = S - n + 1
    macc = np.uint32((1 << L) - 1) if L < 32 else np.uint32(0xFFFFFFFF)

    def mul_const(v, c):
        v = v.astype(_U32) & macc
        acc = jnp.zeros_like(v)
        while c:
            if c & 1:
                acc = acc ^ v
            c >>= 1
            if c:
                msb = (v >> np.uint32(L - 1)) & np.uint32(1)
                v = ((v << np.uint32(1)) & macc) ^ (msb * np.uint32(p & ((1 << L) - 1)))
        return acc

    # x^k mod p on host ints
    xpow = [1]
    for _ in range(n):
        c = xpow[-1] << 1
        if c >> L:
            c ^= p
        xpow.append(c & ((1 << L) - 1))

    acc = jnp.zeros(h1v.shape[:-1] + (W,), dtype=_U32)
    for k in range(n):
        acc = acc ^ mul_const(h1v[..., k : k + W], xpow[n - 1 - k])
    return acc


def lookup_ref(tokens: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    """Plain-gather h1 lookup oracle for the fused kernel."""
    return table[tokens.astype(jnp.int32)]


def cyclic_fused_ref(tokens: jnp.ndarray, table: jnp.ndarray, n: int, L: int = 32) -> jnp.ndarray:
    return cyclic_ref(lookup_ref(tokens, table), n, L)


# ---------------------------------------------------------------------------
# Fused sketch-epilogue oracles (mirror kernels/sketch_fused.py). These are
# also the fast-CPU production path behind api.run / the deprecated
# ops.cyclic_{minhash,hll,bloom} shims — one fused jit per plan, no
# window-hash round trip through host memory. The per-sketch reductions are
# shared helpers so the single-sketch oracles and the multi-sketch plan
# executor are the same code (and therefore bit-identical).
# ---------------------------------------------------------------------------

_SENTINEL = np.uint32(0xFFFFFFFF)


def window_hashes_ref(h1v, *, family: str, n: int, L: int,
                      p: int = 0) -> jnp.ndarray:
    """Family-generic rolling window hashes: (..., S) -> (..., S-n+1)."""
    if family == "cyclic":
        return cyclic_ref(h1v, n, L)
    if family == "general":
        return general_ref(h1v, n, p, L)
    raise ValueError(f"unknown hash family {family!r}")


def _masked_windows(h1v, n: int, L: int, hash_mask: int, n_windows,
                    family: str = "cyclic", p: int = 0, w_start=None):
    """(B, S) -> (B, W) window hashes with the discard mask applied and a
    (B, W) bool validity mask (``w_start <= global window index <
    n_windows``; ``w_start=None`` means 0)."""
    h = window_hashes_ref(h1v, family=family, n=n, L=L, p=p)
    h = h & np.uint32(hash_mask)
    idx = jnp.arange(h.shape[-1], dtype=jnp.int32)
    valid = idx[None, :] < n_windows.astype(jnp.int32)[:, None]
    if w_start is not None:
        valid &= idx[None, :] >= w_start.astype(jnp.int32)[:, None]
    return h, valid


def minhash_reduce(h, valid, a, b, k_chunk: int = 16, init=None) -> jnp.ndarray:
    """(B, W) masked hashes -> (B, k) signatures; invalid windows excluded
    from the min entirely (post-remix sentinel substitution). The remix is
    evaluated in k-chunks so the full (B, W, k) expansion never materialises
    on the CPU path. ``init`` is an optional (B, k) carry of running minima
    folded in with ``min`` (the MinHash merge operator) — uint32 min is
    associative/commutative, so carrying across chunks is bit-exact."""
    outs = []
    k = a.shape[0]
    for s in range(0, k, k_chunk):
        ac, bc = a[s : s + k_chunk], b[s : s + k_chunk]
        mixed = ac[None, None, :] * h[:, :, None] + bc[None, None, :]
        mixed = jnp.where(valid[:, :, None], mixed, _SENTINEL)
        outs.append(jnp.min(mixed, axis=1))
    out = jnp.concatenate(outs, axis=-1)
    return out if init is None else jnp.minimum(out, init)


def _hll_reduce(h, valid, b: int, rank_bits: int, init=None) -> jnp.ndarray:
    """(B, W) masked hashes -> (2^b,) int32 registers over valid windows;
    ``init`` optionally carries a register file in (merged by max)."""
    h, valid = h.reshape(-1), valid.reshape(-1)
    m = 1 << b
    idx = (h & np.uint32(m - 1)).astype(jnp.int32)
    rest = h >> np.uint32(b)
    isolated = rest & (~rest + np.uint32(1))
    tz = jax.lax.population_count(isolated - np.uint32(1))
    rank = (jnp.minimum(tz, np.uint32(rank_bits)) + 1).astype(jnp.int32)
    rank = jnp.where(valid, rank, 0)
    out = jnp.zeros((m,), jnp.int32).at[idx].max(rank)
    return out if init is None else jnp.maximum(out, init)


def cms_reduce(h, valid, a, b, log2_width: int, init=None) -> jnp.ndarray:
    """(B, W) masked hashes -> (depth, 2^log2_width) int32 partial counts.

    Row d's column is the top ``log2_width`` bits of the affine remix
    ``a[d]*h + b[d]`` (mod 2^32) — bit-identical to
    ``repro.core.CountMinSketch._cols`` — and invalid (padded) windows add
    0. Integer scatter-add is exact and order-free, so this is also the
    Pallas fallback epilogue for tables too wide for VMEM scratch. ``init``
    optionally carries a running table in (counts merge by ``+``).
    """
    hf = h.astype(_U32).reshape(-1)
    vf = valid.reshape(-1).astype(jnp.int32)
    depth = a.shape[0]
    mixed = a[:, None] * hf[None, :] + b[:, None]
    cols = (mixed >> np.uint32(32 - log2_width)).astype(jnp.int32)
    rows = jnp.broadcast_to(jnp.arange(depth, dtype=jnp.int32)[:, None],
                            cols.shape)
    table = (jnp.zeros((depth, 1 << log2_width), jnp.int32) if init is None
             else init)
    return table.at[rows, cols].add(
        jnp.broadcast_to(vf[None, :], cols.shape))


def _bloom_reduce(ha, hb, valid, bits, k: int, log2_m: int,
                  init=None) -> jnp.ndarray:
    """Two (B, W) masked hash draws + packed filter -> (B,) hit counts;
    ``init`` optionally carries running counts in (merged by ``+``)."""
    hb = hb | np.uint32(1)                       # odd probe stride
    i = jnp.arange(k, dtype=_U32)
    probes = (ha[..., None] + i * hb[..., None]) & np.uint32((1 << log2_m) - 1)
    word = (probes >> np.uint32(5)).astype(jnp.int32)
    bit = probes & np.uint32(31)
    hit = jnp.all(((bits[word] >> bit) & np.uint32(1)) == 1, axis=-1)
    out = jnp.sum(hit & valid, axis=-1, dtype=jnp.int32)
    return out if init is None else out + init


def minhash_fused_ref(h1v, n_windows, a, b, *, n: int, L: int = 32,
                      hash_mask: int = 0xFFFFFFFF,
                      k_chunk: int = 16) -> jnp.ndarray:
    """(B, S) h1v + (B,) n_windows -> (B, k) MinHash signatures."""
    h, valid = _masked_windows(h1v, n, L, hash_mask, n_windows)
    return minhash_reduce(h, valid, a, b, k_chunk)


def hll_fused_ref(h1v, n_windows, *, n: int, b: int, rank_bits: int,
                  L: int = 32, hash_mask: int = 0xFFFFFFFF) -> jnp.ndarray:
    """(B, S) h1v -> (2^b,) int32 HLL registers over all valid windows."""
    h, valid = _masked_windows(h1v, n, L, hash_mask, n_windows)
    return _hll_reduce(h, valid, b, rank_bits)


def bloom_fused_ref(h1va, h1vb, n_windows, bits, *, n: int, k: int,
                    log2_m: int, L: int = 32,
                    hash_mask: int = 0xFFFFFFFF) -> jnp.ndarray:
    """Two h1v draws + packed filter -> (B,) int32 valid-window hit counts."""
    ha, valid = _masked_windows(h1va, n, L, hash_mask, n_windows)
    hb = cyclic_ref(h1vb, n, L) & np.uint32(hash_mask)
    return _bloom_reduce(ha, hb, valid, bits, k, log2_m)


# ---------------------------------------------------------------------------
# Decode-time n-gram plane oracle (mirrors kernels/decode.py). The fused
# Pallas decode epilogue is validated bit-for-bit against these; off-TPU
# they are also the production path behind ``api.decode`` (one jit per
# DecodeSpec, fused into the sampling graph).
# ---------------------------------------------------------------------------

# double-hashing stride constant (golden-ratio odd multiplier), shared by
# oracle and kernel so the probe sequences are bit-identical
BLOOM_STRIDE = np.uint32(0x9E3779B9)

NEG_LOGIT = np.float32(-1e30)


def pack_mask_u32(mask: jnp.ndarray) -> jnp.ndarray:
    """(..., V) bool -> (..., ceil(V/32)) uint32, bit i of word w = column
    32*w + i. V is padded with zero bits up to the word boundary."""
    V = mask.shape[-1]
    pad = -V % 32
    if pad:
        mask = jnp.pad(mask, ((0, 0),) * (mask.ndim - 1) + ((0, pad),))
    m = mask.reshape(mask.shape[:-1] + (-1, 32)).astype(_U32)
    weights = (np.uint32(1) << np.arange(32, dtype=np.uint32))
    return jnp.sum(m * weights, axis=-1).astype(_U32)


def bloom_probe_hits(h, words, k: int, log2_m: int) -> jnp.ndarray:
    """All-k-probes-set membership of masked hashes ``h`` (..., V) against
    packed filters ``words`` — per-row filters (B, m/32) probed row-wise, or
    one shared (m/32,) filter probed globally. Probe i is
    ``(h + i * ((h * BLOOM_STRIDE) | 1)) & (m - 1)`` — double hashing with
    an odd stride derived from the already-discarded hash, so the probe
    sequence never touches the n-1 dependent bits."""
    h = h.astype(_U32)
    stride = (h * BLOOM_STRIDE) | np.uint32(1)
    m_mask = np.uint32((1 << log2_m) - 1)
    hit = jnp.ones(h.shape, jnp.bool_)
    # one gather per probe: a single gather over all k probes at once takes
    # the TPU compiler minutes at a 128k vocabulary, k gathers take seconds
    for i in range(k):
        probe = (h + np.uint32(i) * stride) & m_mask
        word = (probe >> np.uint32(5)).astype(jnp.int32)
        if words.ndim == 1:                   # shared filter
            got = words[word]
        else:                                 # per-row filters
            got = jnp.take_along_axis(
                words, word.reshape(word.shape[0], -1),
                axis=1).reshape(word.shape)
        hit = hit & (((got >> (probe & np.uint32(31))) & np.uint32(1)) == 1)
    return hit


def decode_masks_ref(logits, prefix, ready, bloom, h1, *, n: int, L: int,
                     hash_mask: int, log2_m: int, k: int,
                     canary_bits=None, canary_log2_m: int = 0,
                     canary_k: int = 4) -> dict:
    """Decode-plane oracle: one candidate hash per (session, token), probed
    against the session's no-repeat filter and (optionally) the shared
    decontam canary filter.

    logits (B, V) f32, prefix (B,) uint32 rolling prefix hashes, ready (B,)
    bool (the session has consumed >= n-1 symbols), bloom (B, 2^log2_m/32)
    uint32 per-session filters, h1 (V,) uint32 symbol hashes ->
    ``{"logits": (B, V) banned-masked logits, "banned": (B, ceil(V/32))
    uint32 packed mask[, "canary": packed canary-hit mask]}``.

    ``h_cand = rotl(prefix, 1) XOR h1[v]`` is the full-width recursive hash;
    probes derive from ``h_cand & hash_mask`` (the Theorem-2 discard).
    """
    V = logits.shape[-1]
    cand = _rotl_const(prefix.astype(_U32), 1, L)[:, None] ^ h1[None, :]
    h = cand & np.uint32(hash_mask)
    rdy = ready.astype(jnp.bool_)[:, None]      # a full n-gram needs n-1 history
    banned = bloom_probe_hits(h, bloom, k, log2_m) & rdy
    out = {"logits": jnp.where(banned, NEG_LOGIT, logits),
           "banned": pack_mask_u32(banned)}
    if canary_bits is not None:
        out["canary"] = pack_mask_u32(
            bloom_probe_hits(h, canary_bits, canary_k, canary_log2_m) & rdy)
    return out


def sketch_plan_ref(plan, h1v, h1v_b, n_windows, operands,
                    w_start=None) -> dict:
    """Single-jnp-graph executor for a SketchPlan: ONE rolling-hash
    evaluation (per stream) feeds every requested sketch epilogue.

    Mirrors ``sketch_fused.sketch_plan_fused`` bit-for-bit; ``api.run``
    wraps it in one jit per plan so the whole multi-sketch graph is a
    single device dispatch on the CPU path. A sketch's optional ``init``
    operand carries its running state in; each epilogue folds it with its
    own merge operator (min / max / + / +) — all exact on integers, so a
    chunked run that threads the carry is bit-identical to one shot.
    """
    from repro.kernels.plan import (BloomSpec, CountMinSpec, HLLSpec,
                                    MinHashSpec)

    hs = plan.hash
    h, valid = _masked_windows(h1v, hs.n, hs.L, hs.hash_mask, n_windows,
                               family=hs.family, p=hs.p, w_start=w_start)
    hb = None
    if plan.needs_second_stream:
        hb = window_hashes_ref(h1v_b, family=hs.family, n=hs.n, L=hs.L,
                               p=hs.p) & np.uint32(hs.hash_mask)
    out = {}
    for name, spec in plan.sketches:
        ops_nm = operands.get(name, {})
        init = ops_nm.get("init")
        if isinstance(spec, MinHashSpec):
            out[name] = minhash_reduce(h, valid, ops_nm["a"], ops_nm["b"],
                                       init=init)
        elif isinstance(spec, HLLSpec):
            out[name] = _hll_reduce(h, valid, spec.b,
                                    spec.resolve_rank_bits(hs), init=init)
        elif isinstance(spec, BloomSpec):
            out[name] = _bloom_reduce(h, hb, valid, ops_nm["bits"],
                                      spec.k, spec.log2_m, init=init)
        elif isinstance(spec, CountMinSpec):
            out[name] = cms_reduce(h, valid, ops_nm["a"], ops_nm["b"],
                                   spec.log2_width, init=init)
        else:  # pragma: no cover - SketchPlan validates spec types
            raise TypeError(f"unknown sketch spec {type(spec)}")
    return out
