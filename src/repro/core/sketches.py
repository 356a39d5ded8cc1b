"""Probabilistic sketches built on the paper's hash families.

These are the *consumers* of pairwise / trailing-zero independence inside the
data pipeline:

* :class:`HyperLogLog` — distinct-n-gram counting (the paper's §2 motivation:
  requires trailing-zero independence, which recursive families provide at
  the pairwise level).
* :class:`BloomFilter` — train/eval decontamination membership. Uses two
  independent family draws + Kirsch–Mitzenmacher double hashing (the analysis
  of which needs exactly pairwise independence).
* :class:`MinHash` — document-level near-dedup signatures over n-gram sets;
  unbiased Jaccard estimation relies on (pairwise) independent permutations.
* :class:`CountMinSketch` — heavy-hitter n-gram statistics; error bound is a
  pairwise-independence argument.

All update/query paths are pure ``jnp`` (jit/vmap/pjit-safe); state is a
pytree so sketches can live inside training-step carries and be checkpointed.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import gf2

_U32 = jnp.uint32


def _ctz(v: jnp.ndarray) -> jnp.ndarray:
    """int32 trailing zeros of uint32 lanes, 32 for a zero lane."""
    isolated = v & (~v + np.uint32(1))
    return jax.lax.population_count(isolated - np.uint32(1)).astype(jnp.int32)


def trailing_zeros(v: jnp.ndarray, L: int = 32) -> jnp.ndarray:
    """ctz(v) with ctz(0) = L (paper §2 'zeros'), branch-free:
    popcount((v & -v) - 1)."""
    return jnp.minimum(_ctz(v.astype(_U32)), np.int32(L))


def _key_words(hashes: jnp.ndarray):
    """(2, ...) two-word keys -> flat (lo, hi) uint32 words."""
    h = hashes.astype(_U32).reshape(2, -1)
    return h[0], h[1]


# The key -> register and key -> column rules below are the one definition
# of each, shared by the sketches' query side, the plan engine's XLA
# epilogues and its Pallas tiles: plain uint32 ``jnp`` ops on broadcastable
# operands. A key is one uint32 array, or past L = 32 a ``(lo, hi)`` pair of
# word arrays (low word first).


def hll_index_rank(key, b: int, rank_bits: int):
    """HyperLogLog register index (the key's low ``b`` bits) and rank (the
    trailing zeros of the bits above them, across both words of a two-word
    key, plus one; at most ``rank_bits + 1``), both int32."""
    if isinstance(key, tuple):
        lo, hi = key
        rest_lo, rest_hi = gf2.shr_words(lo, hi, b)
        tz = jnp.where(rest_lo == 0, 32 + _ctz(rest_hi), _ctz(rest_lo))
    else:
        lo = key
        tz = _ctz(key >> np.uint32(b))
    idx = (lo & np.uint32((1 << b) - 1)).astype(jnp.int32)
    return idx, jnp.minimum(tz, np.int32(rank_bits)) + 1


def cms_column(key, a, b, log2_width: int):
    """One CountMin row's int32 column of a key.

    A one-word key takes the multiply-shift ``(a key + b) mod 2^32 >> (32 -
    l)`` with odd ``a``. A two-word key takes Dietzfelbinger's vector
    multiply-add-shift ``(sum_i a_i x_i + b) mod 2^32 >> (32 - l)`` over its
    pieces ``x_i`` of ``33 - l`` bits, low piece first, with ``a`` the
    sequence of per-piece multipliers: pairwise independent onto the ``l``
    column bits, and every bit of the key reaches the column.
    """
    if isinstance(key, tuple):
        w = 33 - log2_width
        acc = b
        for i, a_i in enumerate(a):
            piece = gf2.shr_words(*key, i * w)[0] & np.uint32((1 << w) - 1)
            acc = acc + a_i * piece
    else:
        acc = a * key + b
    return (acc >> np.uint32(32 - log2_width)).astype(jnp.int32)


def cms_pieces(key_bits: int, log2_width: int) -> int:
    """Pieces of ``33 - log2_width`` bits that :func:`cms_column` cuts a
    ``key_bits``-wide two-word key into."""
    return -(-key_bits // (33 - log2_width))


def cms_columns(hashes: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray,
                log2_width: int, words: int = 1) -> jnp.ndarray:
    """(depth, N) columns of flat keys under every row's constants: ``a``
    (depth,) and keys of any shape, or ``a`` (depth, pieces) and ``(2,
    ...)`` two-word keys when ``words == 2``; ``b`` (depth,)."""
    if words == 2:
        lo, hi = _key_words(hashes)
        key = (lo[None, :], hi[None, :])
        a = [a[:, i : i + 1] for i in range(a.shape[1])]
    else:
        key = hashes.astype(_U32).reshape(-1)[None, :]
        a = a[:, None]
    return cms_column(key, a, b[:, None], log2_width)


# ---------------------------------------------------------------------------
# HyperLogLog
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class HyperLogLog:
    """Flajolet-style distinct counting from L-bit hash values.

    ``b`` index bits -> m = 2^b registers; rank = trailing zeros of the
    remaining bits + 1 (trailing-zero convention of the paper §2).
    ``hash_bits`` must be the *usable* bits of the producing family — e.g.
    ``Cyclic.out_bits`` after the Theorem-1 discard. ``words=2`` takes the
    two-word keys of a family past L = 32 (``(2, ...)``, low word first):
    the rank then reads the trailing zeros of all ``hash_bits - b`` bits
    above the index, across both words.
    """

    b: int = 10
    hash_bits: int = 32
    words: int = 1

    @property
    def m(self) -> int:
        return 1 << self.b

    def init(self) -> jnp.ndarray:
        return jnp.zeros((self.m,), dtype=jnp.int32)

    def update(self, regs: jnp.ndarray, hashes: jnp.ndarray) -> jnp.ndarray:
        key = (_key_words(hashes) if self.words == 2
               else hashes.astype(_U32).reshape(-1))
        idx, rank = hll_index_rank(key, self.b, self.hash_bits - self.b)
        return regs.at[idx].max(rank)

    def update_split(self, regs: jnp.ndarray, h_idx: jnp.ndarray,
                     h_rank: jnp.ndarray, rank_bits: int) -> jnp.ndarray:
        """Two-draw update (paper §11 adaptation): CYCLIC's Theorem-1 discard
        leaves only L-n+1 usable bits — too few for large cardinalities at
        fixed 32-bit lanes. Register index comes from one independent family
        draw, the rank from a second; the pair is jointly pairwise
        independent because the draws are independent."""
        hi = h_idx.astype(_U32).reshape(-1)
        hr = h_rank.astype(_U32).reshape(-1)
        idx = (hi & np.uint32(self.m - 1)).astype(jnp.int32)
        rank = trailing_zeros(hr, rank_bits) + 1
        return regs.at[idx].max(rank)

    def merge(self, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
        return jnp.maximum(a, b)

    def estimate(self, regs: jnp.ndarray) -> jnp.ndarray:
        m = self.m
        alpha = {16: 0.673, 32: 0.697, 64: 0.709}.get(m, 0.7213 / (1 + 1.079 / m))
        raw = alpha * m * m / jnp.sum(jnp.exp2(-regs.astype(jnp.float32)))
        zeros = jnp.sum(regs == 0)
        linear = m * (jnp.log(jnp.float32(m)) - jnp.log(jnp.maximum(zeros, 1).astype(jnp.float32)))
        use_linear = (raw <= 2.5 * m) & (zeros > 0)
        return jnp.where(use_linear, linear, raw)


# ---------------------------------------------------------------------------
# Bloom filter
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BloomFilter:
    """m-bit Bloom filter with k probes via double hashing.

    Callers supply *two* independent 32-bit hash streams (two family draws);
    probe_i = h_a + i * h_b mod m. State is a packed uint32 bit array.
    """

    log2_m: int = 20
    k: int = 4

    @property
    def m(self) -> int:
        return 1 << self.log2_m

    def init(self) -> jnp.ndarray:
        return jnp.zeros((self.m // 32,), dtype=_U32)

    def _probes(self, h_a: jnp.ndarray, h_b: jnp.ndarray) -> jnp.ndarray:
        i = jnp.arange(self.k, dtype=_U32)
        # force h_b odd so the probe stride is invertible mod the power-of-2 m
        hb = h_b.astype(_U32) | np.uint32(1)
        return (h_a.astype(_U32)[..., None] + i * hb[..., None]) & np.uint32(self.m - 1)

    def add(self, bits: jnp.ndarray, h_a: jnp.ndarray, h_b: jnp.ndarray) -> jnp.ndarray:
        probes = self._probes(h_a, h_b).reshape(-1)
        word, bit = probes >> np.uint32(5), probes & np.uint32(31)
        return _scatter_or(bits, word, bit)

    def contains(self, bits: jnp.ndarray, h_a: jnp.ndarray, h_b: jnp.ndarray) -> jnp.ndarray:
        probes = self._probes(h_a, h_b)
        word, bit = probes >> np.uint32(5), probes & np.uint32(31)
        hit = (bits[word] >> bit) & np.uint32(1)
        return jnp.all(hit == 1, axis=-1)

    def fill_fraction(self, bits: jnp.ndarray) -> jnp.ndarray:
        return jnp.sum(jax.lax.population_count(bits)) / self.m


def _scatter_or(bits: jnp.ndarray, word: jnp.ndarray, bit: jnp.ndarray) -> jnp.ndarray:
    """OR-scatter: set bit ``bit[i]`` of ``bits[word[i]]`` for all i (jit-safe).

    XLA scatter has add/max but no bitwise-OR combiner, and ``at[].max`` of the
    multi-bit masks is wrong under collisions (max(2, 1) != 2|1). So we scatter
    into a (words, 32) boolean *bit-plane* view with ``at[].max`` — exact OR
    semantics per plane — then fold the planes back into packed uint32 words.
    """
    planes = jnp.zeros((bits.shape[0], 32), dtype=jnp.bool_)
    planes = planes.at[word, bit].max(jnp.ones_like(bit, dtype=jnp.bool_))
    merged = jnp.sum(planes.astype(_U32) << jnp.arange(32, dtype=_U32)[None, :],
                     axis=-1, dtype=_U32)
    return bits | merged


# ---------------------------------------------------------------------------
# MinHash
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MinHash:
    """k-signature MinHash over a set of window hashes.

    Rather than k full re-hashes of the stream, we use the standard
    pairwise-independent affine re-mix of one base hash: sig_i = min_x (a_i *
    h(x) + b_i mod 2^32) — each (a_i odd, b_i) pair is a strongly universal
    remix, so the collision analysis inherits the base family's pairwise
    independence.
    """

    k: int = 64

    def init(self, key) -> Dict[str, jnp.ndarray]:
        ka, kb = jax.random.split(key)
        a = jax.random.bits(ka, (self.k,), dtype=_U32) | np.uint32(1)
        b = jax.random.bits(kb, (self.k,), dtype=_U32)
        return {"a": a, "b": b}

    def signature(self, params, window_hashes: jnp.ndarray) -> jnp.ndarray:
        h = window_hashes.astype(_U32).reshape(-1)
        mixed = params["a"][:, None] * h[None, :] + params["b"][:, None]
        return jnp.min(mixed, axis=-1)

    @staticmethod
    def jaccard(sig_a: jnp.ndarray, sig_b: jnp.ndarray) -> jnp.ndarray:
        return jnp.mean((sig_a == sig_b).astype(jnp.float32))


# ---------------------------------------------------------------------------
# Count-Min sketch
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CountMinSketch:
    """depth x 2^log2_width counts; row d's column of a key is a
    multiply-shift of it.

    One-word keys take ``(a_d h + b_d) >> (32 - l)`` with odd ``a_d``.
    Two-word keys (``words=2``: ``(2, ...)``, low word first, ``key_bits``
    wide) take Dietzfelbinger's vector multiply-add-shift ``(sum_i a_di x_i
    + b_d) mod 2^32 >> (32 - l)`` over the key's pieces ``x_i`` of ``33 -
    l`` bits, low piece first: pairwise independent onto the ``l`` column
    bits, with every bit of the key reaching the column. ``a`` is then
    (depth, pieces).
    """

    depth: int = 4
    log2_width: int = 16
    key_bits: int = 32
    words: int = 1

    @property
    def width(self) -> int:
        return 1 << self.log2_width

    @property
    def pieces(self) -> int:
        """Key pieces of the vector form; 0 for one-word keys."""
        if self.words == 1:
            return 0
        return cms_pieces(self.key_bits, self.log2_width)

    def init(self, key) -> Dict[str, jnp.ndarray]:
        ka, kb = jax.random.split(key)
        if self.pieces:
            a = jax.random.bits(ka, (self.depth, self.pieces), dtype=_U32)
        else:
            a = jax.random.bits(ka, (self.depth,), dtype=_U32) | np.uint32(1)
        return {
            "a": a,
            "b": jax.random.bits(kb, (self.depth,), dtype=_U32),
            "table": jnp.zeros((self.depth, self.width), dtype=jnp.int32),
        }

    def _cols(self, params, hashes: jnp.ndarray) -> jnp.ndarray:
        return cms_columns(hashes, params["a"], params["b"], self.log2_width,
                           self.words)

    def add(self, params, hashes: jnp.ndarray):
        cols = self._cols(params, hashes)  # (depth, N)
        rows = jnp.arange(self.depth, dtype=jnp.int32)[:, None]
        table = params["table"].at[rows, cols].add(1)
        return {**params, "table": table}

    def query(self, params, hashes: jnp.ndarray) -> jnp.ndarray:
        cols = self._cols(params, hashes)
        rows = jnp.arange(self.depth, dtype=jnp.int32)[:, None]
        return jnp.min(params["table"][rows, cols], axis=0)
