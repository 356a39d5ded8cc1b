"""The paper's recursive n-gram hash families, in three evaluation forms.

Every family hashes all length-``n`` windows of a token stream to ``L``-bit
values (uint32 lanes). CYCLIC also runs at ``32 < L <= 64``: a lane is then
two uint32 words (x64 stays off) and every lane array carries a leading
word axis, ``(2, ...)`` with the low word first. Three mathematically identical evaluation forms are
provided per family:

* ``hash_stream``   — the paper's character-at-a-time *recursive* algorithm
  (Algorithms 1–4), as an ``lax.scan``. This is the faithful CPU form.
* ``hash_windows_direct`` — the defining per-window formula, O(n) work per
  window. Used as the oracle in tests.
* ``hash_windows`` — the TPU-native parallel form (associative-scan prefix
  trick for CYCLIC/ID37, unrolled constant-multiply window for GENERAL,
  gather+XOR for THREEWISE). See DESIGN.md §3 for the algebra.

Families
--------
- :class:`ThreeWise`        — Algorithm 1, non-recursive, exactly 3-wise independent.
- :class:`ID37`             — Algorithm 2, randomized Karp–Rabin (uniform, not pairwise).
- :class:`General`          — Algorithm 3, irreducible p(x): pairwise independent.
- :class:`BufferedGeneral`  — §8, Lemma 2: GENERAL with O(2^n) (or K·2^(n/K)) shift tables.
- :class:`Cyclic`           — Algorithm 4, p(x)=x^L+1: pairwise independent on any
  L-n+1 consecutive bits (Theorem 1); :meth:`Cyclic.pairwise_bits` applies the
  n-1-bit discard.

The symbol hash ``h1`` is a single random table over the alphabet — for
distinct symbols its values are i.i.d. uniform, i.e. the *fully independent*
family the paper assumes.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import gf2

Params = Dict[str, Any]
_U32 = jnp.uint32


def _as_u32(tokens) -> jnp.ndarray:
    return jnp.asarray(tokens).astype(_U32)


def init_h1(key, sigma: int, words: int = 1) -> jnp.ndarray:
    """Fully independent symbol hash: i.i.d. uniform uint32 words per
    symbol, ``(sigma,)`` for one-word lanes and ``(words, sigma)`` else."""
    shape = (sigma,) if words == 1 else (words, sigma)
    return jax.random.bits(key, shape, dtype=_U32)


def _word_masks(L: int) -> np.ndarray:
    """Per-word keep masks of an L-bit two-word lane, shaped to broadcast
    over the leading word axis."""
    return np.array([0xFFFFFFFF, gf2.mask(L - 32)], np.uint32)


@dataclasses.dataclass(frozen=True)
class _Family:
    n: int
    L: int = 32

    MAX_L = 32          # CYCLIC alone takes two-word lanes

    @property
    def name(self) -> str:
        return type(self).__name__.upper()

    @property
    def out_bits(self) -> int:
        return self.L

    @property
    def words(self) -> int:
        """uint32 words per lane: 1 up to L = 32, else 2."""
        return 1 if self.L <= 32 else 2

    def __post_init__(self):
        if not 1 <= self.L <= self.MAX_L:
            raise ValueError(f"L must be in [1, {self.MAX_L}]")
        if self.n < 1:
            raise ValueError("n must be >= 1")

    # -- shared helpers ----------------------------------------------------
    def _mask(self):
        return np.uint32(gf2.mask(self.L))

    def _lookup(self, params: Params, tokens) -> jnp.ndarray:
        if self.words == 1:
            return params["h1"][_as_u32(tokens)] & self._mask()
        t = _as_u32(tokens)
        masks = _word_masks(self.L).reshape((2,) + (1,) * t.ndim)
        return params["h1"][:, t] & masks

    def init(self, key, sigma: int) -> Params:
        return {"h1": init_h1(key, sigma, self.words)}

    def hash_ngram(self, params: Params, ngram) -> jnp.ndarray:
        """Hash a single n-gram (length-n token array) -> scalar uint32
        (its (2,) words for two-word lanes)."""
        out = self.hash_windows_direct(params, ngram)
        return out[..., 0]

    def hash_windows(self, params: Params, tokens) -> jnp.ndarray:
        return self.hash_windows_direct(params, tokens)

    def hash_windows_batched(self, params: Params, tokens) -> jnp.ndarray:
        """tokens: (..., S) -> (..., S-n+1); vmaps over leading dims.
        Two-word lanes come back as (2, ..., S-n+1)."""
        fn = self.hash_windows
        t = _as_u32(tokens)
        for _ in range(t.ndim - 1):
            fn = jax.vmap(fn, in_axes=(None, 0))
        out = fn(params, t)
        return out if self.words == 1 else jnp.moveaxis(out, -2, 0)


# ---------------------------------------------------------------------------
# Algorithm 1 — non-recursive 3-wise independent family
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ThreeWise(_Family):
    """h(x) = h_1(x_1) XOR ... XOR h_n(x_n), one independent table per position."""

    def init(self, key, sigma: int) -> Params:
        keys = jax.random.split(key, self.n)
        return {"h1": jnp.stack([init_h1(k, sigma) for k in keys])}  # (n, sigma)

    def _lookup_pos(self, params, k, tokens):
        return params["h1"][k][_as_u32(tokens)] & self._mask()

    def hash_windows_direct(self, params: Params, tokens) -> jnp.ndarray:
        t = _as_u32(tokens)
        W = t.shape[-1] - self.n + 1
        acc = jnp.zeros((W,), dtype=_U32)
        for k in range(self.n):
            acc = acc ^ self._lookup_pos(params, k, t[k : k + W])
        return acc

    def hash_stream(self, params: Params, tokens) -> jnp.ndarray:
        # Algorithm 1 keeps a FIFO; positionally that is exactly the direct
        # form. We still express it as a scan over characters for parity with
        # the other families (the FIFO is a length-n rolling buffer).
        t = _as_u32(tokens)
        n, W = self.n, t.shape[-1] - self.n + 1

        def step(buf, c):
            buf = jnp.concatenate([buf[1:], c[None]])
            h = jnp.zeros((), dtype=_U32)
            for k in range(n):
                h = h ^ self._lookup_pos(params, k, buf[k])
            return buf, h

        _, hs = jax.lax.scan(step, jnp.zeros((n,), dtype=_U32), t)
        return hs[n - 1 :]


# ---------------------------------------------------------------------------
# Algorithm 2 — Randomized Karp-Rabin (Integer Division), "ID37"
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ID37(_Family):
    """h = sum_k B^{n-1-k} h1(x_k) mod 2^L, default B=37 (paper §5)."""

    B: int = 37

    def hash_windows_direct(self, params: Params, tokens) -> jnp.ndarray:
        t = _as_u32(tokens)
        W = t.shape[-1] - self.n + 1
        h1v = self._lookup(params, t)
        acc = jnp.zeros((W,), dtype=_U32)
        for k in range(self.n):
            c = np.uint32(pow(self.B, self.n - 1 - k, 1 << 32))
            acc = acc + c * h1v[k : k + W]
        return acc & self._mask()

    def hash_stream(self, params: Params, tokens) -> jnp.ndarray:
        # Algorithm 2: x <- B x - B^n z + h1(c); z <- h1(oldest).
        t = _as_u32(tokens)
        n = self.n
        h1v = self._lookup(params, t)
        # h1 of the character leaving the window at each step (0 during warmup).
        lag = jnp.concatenate([jnp.zeros((n,), dtype=_U32), h1v[:-n]]) if t.shape[-1] > n \
            else jnp.zeros_like(h1v)
        B = np.uint32(self.B)
        Bn = np.uint32(pow(self.B, n, 1 << 32))

        def step(x, inp):
            c, z = inp
            x = B * x - Bn * z + c
            return x, x

        _, xs = jax.lax.scan(step, jnp.zeros((), _U32), (h1v, lag))
        return xs[n - 1 :] & self._mask()

    def hash_windows(self, params: Params, tokens) -> jnp.ndarray:
        # Parallel prefix form: B odd => B invertible mod 2^32.
        # P_i = B^{-i} h1(x_i); S = cumsum(P); H_j = B^{j+n-1}(S_{j+n-1}-S_{j-1}).
        if self.B % 2 == 0:  # pragma: no cover - B=37 default is odd
            return self.hash_windows_direct(params, tokens)
        t = _as_u32(tokens)
        S = t.shape[-1]
        n, W = self.n, S - self.n + 1
        h1v = self._lookup(params, t)
        Binv = pow(self.B, -1, 1 << 32)
        ipow = _int_pows(Binv, S)          # B^{-i}
        fpow = _int_pows(self.B, S)        # B^{i}
        P = ipow * h1v
        csum = jnp.cumsum(P, dtype=_U32)
        left = jnp.concatenate([jnp.zeros((1,), _U32), csum[: W - 1]])
        windowed = csum[n - 1 :] - left
        out = fpow[n - 1 :] * windowed
        return out & self._mask()


@functools.lru_cache(maxsize=64)
def _int_pows_host(base: int, S: int) -> np.ndarray:
    out = np.empty(S, dtype=np.uint32)
    v = 1
    m = (1 << 32) - 1
    for i in range(S):
        out[i] = v & m
        v = (v * base) & m
    return out


def _int_pows(base: int, S: int) -> jnp.ndarray:
    return jnp.asarray(_int_pows_host(int(base), int(S)))


# ---------------------------------------------------------------------------
# Algorithm 3 — GENERAL (irreducible p(x)) and §8 RAM-buffered variant
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class General(_Family):
    """Polynomial hashing mod an irreducible p(x): pairwise independent (Lemma 1)."""

    p: int = 0  # degree-L irreducible, WITH top bit; 0 = auto from table

    def __post_init__(self):
        super().__post_init__()
        if self.L < self.n:
            raise ValueError("GENERAL requires L >= n (paper Table 1)")
        if self.p == 0:
            object.__setattr__(self, "p", gf2.find_irreducible_host(self.L))
        if self.p.bit_length() - 1 != self.L:
            raise ValueError("p must have degree exactly L")

    @functools.cached_property
    def _xpows(self) -> tuple:
        return tuple(gf2.x_pow_mod_host(k, self.p, self.L) for k in range(self.n + 1))

    def hash_windows_direct(self, params: Params, tokens) -> jnp.ndarray:
        t = _as_u32(tokens)
        W = t.shape[-1] - self.n + 1
        h1v = self._lookup(params, t)
        acc = jnp.zeros((W,), dtype=_U32)
        for k in range(self.n):
            acc = acc ^ gf2.mul_by_const(h1v[k : k + W], self._xpows[self.n - 1 - k],
                                         self.p, self.L)
        return acc

    # The window form above *is* the TPU-parallel form for GENERAL (DESIGN §3).
    hash_windows = hash_windows_direct

    def _shift_n(self, z: jnp.ndarray) -> jnp.ndarray:
        p_low = self.p & gf2.mask(self.L)
        for _ in range(self.n):
            z = gf2.xtimes(z, p_low, self.L)
        return z

    def hash_stream(self, params: Params, tokens) -> jnp.ndarray:
        # Algorithm 3: x <- shift(x); x <- x XOR shift^n(z) XOR h1(c).
        t = _as_u32(tokens)
        n = self.n
        h1v = self._lookup(params, t)
        lag = jnp.concatenate([jnp.zeros((n,), dtype=_U32), h1v[:-n]]) if t.shape[-1] > n \
            else jnp.zeros_like(h1v)
        p_low = self.p & gf2.mask(self.L)

        def step(x, inp):
            c, z = inp
            x = gf2.xtimes(x, p_low, self.L)
            x = x ^ self._shift_n(z) ^ c
            return x, x

        _, xs = jax.lax.scan(step, jnp.zeros((), _U32), (h1v, lag))
        return xs[n - 1 :]


@dataclasses.dataclass(frozen=True)
class BufferedGeneral(General):
    """GENERAL with the Lemma-2 precomputed shift table (k_split=1) or the §8
    K-split trade-off (k_split=K): shift^n(z) becomes table lookups."""

    k_split: int = 1

    def __post_init__(self):
        super().__post_init__()
        if self.n % self.k_split:
            raise ValueError("k_split must divide n")

    @functools.cached_property
    def _tables(self) -> tuple:
        return tuple(
            jnp.asarray(tbl)
            for tbl in gf2.build_shiftn_table_host(self.n, self.p, self.L, self.k_split)
        )

    def _shift_n(self, z: jnp.ndarray) -> jnp.ndarray:
        n, L = self.n, self.L
        chunk = n // self.k_split
        low = (z & np.uint32((1 << (L - n)) - 1)).astype(_U32)
        out = (low << np.uint32(n)) & np.uint32(gf2.mask(L))
        for j, tbl in enumerate(self._tables):
            idx = (z >> np.uint32(L - n + j * chunk)) & np.uint32((1 << chunk) - 1)
            out = out ^ tbl[idx]
        return out


# ---------------------------------------------------------------------------
# Algorithm 4 — CYCLIC (p(x) = x^L + 1, multiplication by x = rotl)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Cyclic(_Family):
    """Rotation-based rolling hash. Not uniform on all L bits (Lemma 3), but
    pairwise independent on any L-n+1 consecutive bits (Theorem 1).

    Takes ``L`` up to 64: x^L + 1 is the modulus at any width, and past 32
    bits a lane is two uint32 words (hashes come back as (2, W))."""

    MAX_L = 64

    def __post_init__(self):
        super().__post_init__()
        if self.L < self.n:
            raise ValueError("CYCLIC requires L >= n (paper Table 1)")

    @property
    def out_bits(self) -> int:
        """Bits that survive the Theorem-1 discard."""
        return self.L - self.n + 1

    def hash_windows_direct(self, params: Params, tokens) -> jnp.ndarray:
        t = _as_u32(tokens)
        W = t.shape[-1] - self.n + 1
        h1v = self._lookup(params, t)
        if self.words == 2:
            return self._direct_words(h1v, W)
        acc = jnp.zeros((W,), dtype=_U32)
        for k in range(self.n):
            acc = acc ^ gf2.rotl(h1v[k : k + W], (self.n - 1 - k) % self.L, self.L)
        return acc

    def _direct_words(self, h1v: jnp.ndarray, W: int) -> jnp.ndarray:
        """The window formula on (2, S) two-word lanes -> (2, W)."""
        lo = jnp.zeros((W,), dtype=_U32)
        hi = jnp.zeros((W,), dtype=_U32)
        for k in range(self.n):
            rl, rh = gf2.rotl_words(h1v[0, k : k + W], h1v[1, k : k + W],
                                    self.n - 1 - k, self.L)
            lo, hi = lo ^ rl, hi ^ rh
        return jnp.stack([lo, hi])

    def _stream_words(self, h1v: jnp.ndarray) -> jnp.ndarray:
        """Algorithm 4 on (2, S) two-word lanes -> (2, S-n+1)."""
        n, L = self.n, self.L
        lag = (jnp.concatenate([jnp.zeros((2, n), _U32), h1v[:, :-n]], axis=1)
               if h1v.shape[-1] > n else jnp.zeros_like(h1v))

        def step(x, inp):
            c, z = inp
            xl, xh = gf2.rotl_words(x[0], x[1], 1, L)
            zl, zh = gf2.rotl_words(z[0], z[1], n, L)
            x = jnp.stack([xl ^ zl ^ c[0], xh ^ zh ^ c[1]])
            return x, x

        _, xs = jax.lax.scan(step, jnp.zeros((2,), _U32), (h1v.T, lag.T))
        return xs[n - 1 :].T

    def hash_stream(self, params: Params, tokens) -> jnp.ndarray:
        # Algorithm 4: rotate x by 1, rotate z by n, x <- x XOR z XOR h1(c).
        t = _as_u32(tokens)
        n = self.n
        h1v = self._lookup(params, t)
        if self.words == 2:
            return self._stream_words(h1v)
        lag = jnp.concatenate([jnp.zeros((n,), dtype=_U32), h1v[:-n]]) if t.shape[-1] > n \
            else jnp.zeros_like(h1v)

        def step(x, inp):
            c, z = inp
            x = gf2.rotl(x, 1, self.L) ^ gf2.rotl(z, n % self.L, self.L) ^ c
            return x, x

        _, xs = jax.lax.scan(step, jnp.zeros((), _U32), (h1v, lag))
        return xs[n - 1 :]

    def hash_windows(self, params: Params, tokens) -> jnp.ndarray:
        """Parallel prefix form (DESIGN §3):

        H_j = rotl(X_{j+n-1} XOR X_{j-1}, (j+n-1) mod L), with
        X_k the prefix-XOR of P_i = rotl(h1(x_i), -i mod L). XOR is its own
        inverse, so the sliding window collapses to two prefix lookups; the
        prefix itself is an associative scan (O(log S) depth on TPU).
        """
        if self.words == 2:       # per-lane rotations are one-word ops
            return self.hash_windows_direct(params, tokens)
        t = _as_u32(tokens)
        S = t.shape[-1]
        n, L, W = self.n, self.L, t.shape[-1] - self.n + 1
        h1v = self._lookup(params, t)
        idx = jnp.arange(S, dtype=_U32)
        P = gf2.rotr(h1v, idx % np.uint32(L), L)
        X = jax.lax.associative_scan(jnp.bitwise_xor, P)
        left = jnp.concatenate([jnp.zeros((1,), _U32), X[: W - 1]])
        windowed = X[n - 1 :] ^ left
        rot = (jnp.arange(W, dtype=_U32) + np.uint32(n - 1)) % np.uint32(L)
        return gf2.rotl(windowed, rot, L)

    def pairwise_bits(self, h: jnp.ndarray, *, keep_low: bool = True) -> jnp.ndarray:
        """Discard n-1 consecutive bits (Theorem 1) -> pairwise-independent
        (L-n+1)-bit values. ``keep_low`` keeps bits [0, L-n+1). Two-word
        hashes (2, ...) keep their word axis."""
        if self.words == 2:
            lo, hi = h[0], h[1]
            if not keep_low:
                lo, hi = gf2.shr_words(lo, hi, self.n - 1)
            keep = (1 << self.out_bits) - 1
            return jnp.stack([lo & np.uint32(keep & 0xFFFFFFFF),
                              hi & np.uint32(keep >> 32)])
        if keep_low:
            return h & np.uint32(gf2.mask(self.out_bits))
        return (h >> np.uint32(self.n - 1)) & np.uint32(gf2.mask(self.out_bits))


FAMILIES = {
    "threewise": ThreeWise,
    "id37": ID37,
    "general": General,
    "buffered_general": BufferedGeneral,
    "cyclic": Cyclic,
}


def make_family(name: str, n: int, L: int = 32, **kw) -> _Family:
    return FAMILIES[name](n=n, L=L, **kw)
