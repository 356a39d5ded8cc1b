"""GF(2)[x] polynomial arithmetic on integer lanes.

Polynomials of degree < L are stored as the L low bits of a ``uint32`` (the
coefficient of ``x^i`` is bit ``i``), exactly as in the paper (§6): addition
is XOR, multiplication by ``x`` is a left shift followed by a conditional XOR
with the modulus.

Two mirrored implementations live here:

* **host** functions (``_host`` suffix) on Python ints — used at setup time
  (finding irreducible polynomials, building shift tables) and inside the
  exact-enumeration independence tests;
* **device** functions on ``jnp`` arrays — vectorized over arbitrary lane
  shapes, used by the hash families and the Pallas kernel references.

The modulus ``p(x)`` of degree exactly ``L`` is stored *without* its top bit
(``p_low``): the reduction step XORs ``p_low`` after the overflowing shift, so
all arithmetic stays within ``L <= 32`` bits of a uint32.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax.numpy as jnp
import numpy as np

__all__ = [
    "mask",
    "xtimes",
    "mul_by_const",
    "x_pow_mod_host",
    "mulmod_host",
    "xtimes_host",
    "is_irreducible_host",
    "find_irreducible_host",
    "rotl",
    "rotr",
    "rotl_words",
    "shl_words",
    "shr_words",
    "PAPER_TABLE2",
    "PAPER_GENERAL_L19_AS_PRINTED",
    "GENERAL_L19",
]

# Irreducible polynomials from the paper, Table 2 (degree: coefficient ints,
# *including* the top bit -- host representation).
PAPER_TABLE2 = {
    10: (1 << 10) | (1 << 3) | 1,
    15: (1 << 15) | (1 << 1) | 1,
    20: (1 << 20) | (1 << 3) | 1,
    25: (1 << 25) | (1 << 3) | 1,
    30: (1 << 30) | (1 << 6) | (1 << 4) | (1 << 1) | 1,
}

# The degree-19 polynomial printed for GENERAL in the paper's experiments
# (§11): x^19+x^18+x^17+x^16+x^12+x^7+x^6+x^5+x^3+x^2+1. ERRATUM: as printed
# it is divisible by x^2+x+1 (check exponents mod 3), hence NOT irreducible —
# almost certainly a typo in the text. We keep the constant for the record
# but `find_irreducible_host(19)` returns a verified irreducible instead.
PAPER_GENERAL_L19_AS_PRINTED = (
    (1 << 19) | (1 << 18) | (1 << 17) | (1 << 16) | (1 << 12)
    | (1 << 7) | (1 << 6) | (1 << 5) | (1 << 3) | (1 << 2) | 1
)
# Verified irreducible degree-19 polynomial (deterministic first hit of the
# low-weight scan): x^19 + x^5 + x^2 + x + 1.
GENERAL_L19 = (1 << 19) | (1 << 5) | (1 << 2) | (1 << 1) | 1


def mask(L: int) -> int:
    """All-ones mask over the L low bits."""
    if not 1 <= L <= 32:
        raise ValueError(f"L must be in [1, 32], got {L}")
    return (1 << L) - 1


# ---------------------------------------------------------------------------
# Host (Python int) arithmetic
# ---------------------------------------------------------------------------

def xtimes_host(v: int, p: int, L: int) -> int:
    """Multiply v(x) by x modulo p(x) (p given WITH its top bit)."""
    v <<= 1
    if v >> L:
        v ^= p
    return v & mask(L)


def mulmod_host(a: int, b: int, p: int, L: int) -> int:
    """Carry-less multiply a(x)*b(x) mod p(x) (p WITH top bit)."""
    res = 0
    while b:
        if b & 1:
            res ^= a
        b >>= 1
        a = xtimes_host(a, p, L)
    return res


def x_pow_mod_host(k: int, p: int, L: int) -> int:
    """x^k mod p(x) by repeated squaring (p WITH top bit)."""
    result, base = 1, 2  # 1 and x
    while k:
        if k & 1:
            result = mulmod_host(result, base, p, L)
        base = mulmod_host(base, base, p, L)
        k >>= 1
    return result


def _gcd_host(a: int, b: int, *_unused) -> int:
    """Polynomial GCD over GF(2)[x] on int representations."""
    while b:
        # reduce a mod b
        da, db = a.bit_length() - 1, b.bit_length() - 1
        while da >= db and a:
            a ^= b << (da - db)
            da = a.bit_length() - 1
        a, b = b, a
    return a


def is_irreducible_host(p: int) -> bool:
    """Rabin's irreducibility test for p(x) over GF(2).

    p of degree L is irreducible iff x^(2^L) == x (mod p) and
    gcd(x^(2^(L/q)) - x, p) == 1 for every prime divisor q of L.
    """
    L = p.bit_length() - 1
    if L < 1:
        return False

    def x_pow_pow2(e: int) -> int:
        # x^(2^e) mod p via e successive squarings of x.
        r = 2
        for _ in range(e):
            r = mulmod_host(r, r, p, L)
        return r

    if x_pow_pow2(L) != 2:  # x^(2^L) must equal x
        return False
    # prime divisors of L
    primes, m = [], L
    d = 2
    while d * d <= m:
        if m % d == 0:
            primes.append(d)
            while m % d == 0:
                m //= d
        d += 1
    if m > 1:
        primes.append(m)
    for q in primes:
        h = x_pow_pow2(L // q) ^ 2  # x^(2^(L/q)) - x
        if _gcd_host(h, p) != 1:
            return False
    return True


@functools.lru_cache(maxsize=None)
def find_irreducible_host(L: int) -> int:
    """Deterministically find an irreducible polynomial of degree L.

    Prefers the paper's Table 2 entries, then scans low-weight candidates.
    Returns the int WITH the top bit set.
    """
    if L in PAPER_TABLE2:
        return PAPER_TABLE2[L]
    if L == 19:
        return GENERAL_L19
    top = 1 << L
    # Scan candidates in increasing integer order; constant term must be 1
    # (else divisible by x). This is setup-time-only work.
    for low in range(1, 1 << min(L, 20), 2):
        cand = top | low
        if is_irreducible_host(cand):
            return cand
    raise RuntimeError(f"no irreducible polynomial found for L={L}")


# ---------------------------------------------------------------------------
# Device (jnp) arithmetic — vectorized over lanes
# ---------------------------------------------------------------------------

_U32 = jnp.uint32


def xtimes(v: jnp.ndarray, p_low: int, L: int) -> jnp.ndarray:
    """Multiply by x mod p(x), vectorized. p_low excludes the top bit."""
    v = v.astype(_U32)
    msb = (v >> np.uint32(L - 1)) & np.uint32(1)
    shifted = (v << np.uint32(1)) & np.uint32(mask(L))
    return shifted ^ (msb * np.uint32(p_low & mask(L)))


def mul_by_const(v: jnp.ndarray, c: int, p: int, L: int) -> jnp.ndarray:
    """Multiply lanes v(x) by the trace-time constant polynomial c(x) mod p(x).

    Unrolled over the set bits of ``c`` — O(popcount(c)) XORs and O(deg(c))
    xtimes steps, all vectorized across lanes. ``p`` is given WITH its top
    bit; ``c`` has degree < L.
    """
    v = v.astype(_U32)
    p_low = p & mask(L)
    acc = jnp.zeros_like(v)
    bit = 0
    while c:
        if c & 1:
            acc = acc ^ v
        c >>= 1
        bit += 1
        if c:
            v = xtimes(v, p_low, L)
    return acc


def rotl(v: jnp.ndarray, r, L: int) -> jnp.ndarray:
    """Rotate-left within the L low bits. ``r`` may be a traced array."""
    v = v.astype(_U32)
    m = np.uint32(mask(L))
    r = jnp.asarray(r, dtype=_U32) % np.uint32(L)
    left = (v << r) & m
    # (L - r) == L when r == 0 → shift-by-width is undefined; guard it.
    right = jnp.where(r == 0, jnp.zeros_like(v), (v & m) >> (np.uint32(L) - r))
    return left | right


def rotr(v: jnp.ndarray, r, L: int) -> jnp.ndarray:
    r = jnp.asarray(r, dtype=_U32) % np.uint32(L)
    return rotl(v, (np.uint32(L) - r) % np.uint32(L), L)


def shl_words(lo, hi, s: int):
    """The two-word value hi:lo shifted left by a static ``0 <= s < 64``."""
    if s == 0:
        return lo, hi
    if s >= 32:
        return jnp.zeros_like(lo), lo << np.uint32(s - 32)
    return lo << np.uint32(s), (hi << np.uint32(s)) | (lo >> np.uint32(32 - s))


def shr_words(lo, hi, s: int):
    """The two-word value hi:lo shifted right by a static ``0 <= s < 64``."""
    if s == 0:
        return lo, hi
    if s >= 32:
        return hi >> np.uint32(s - 32), jnp.zeros_like(hi)
    return (lo >> np.uint32(s)) | (hi << np.uint32(32 - s)), hi >> np.uint32(s)


def rotl_words(lo: jnp.ndarray, hi: jnp.ndarray, r: int, L: int):
    """Rotate-left the L-bit value ``hi:lo`` (``32 < L <= 64``, two uint32
    words, low word first) by a static ``r``; x^L + 1 is CYCLIC's modulus
    at any width, so this is multiplication by x^r in its ring."""
    r %= L
    hm = np.uint32(mask(L - 32))
    lo, hi = lo.astype(_U32), hi.astype(_U32) & hm
    if r == 0:
        return lo, hi
    a_lo, a_hi = shl_words(lo, hi, r)
    b_lo, b_hi = shr_words(lo, hi, L - r)
    return a_lo | b_lo, (a_hi | b_hi) & hm


def build_shiftn_table_host(n: int, p: int, L: int, k_split: int = 1) -> list[np.ndarray]:
    """RAM-buffered GENERAL (paper §8, Lemma 2) shift tables.

    Returns ``k_split`` numpy uint32 tables; table ``j`` maps the j-th chunk of
    the top-n bits of ``h`` to ``x^n * (chunk << position)``. ``k_split=1``
    is Lemma 2's single O(2^n) table; ``k_split=K`` is the §8 trade-off with
    ``K * 2^(n/K)`` entries total.
    """
    if n % k_split:
        raise ValueError("k_split must divide n")
    chunk = n // k_split
    tables = []
    for j in range(k_split):
        # chunk j covers bit positions [L-n + j*chunk, L-n + (j+1)*chunk)
        base = L - n + j * chunk
        tab = np.zeros(1 << chunk, dtype=np.uint32)
        for val in range(1 << chunk):
            poly = val << base
            tab[val] = mulmod_host(poly, x_pow_mod_host(n, p, L), p, L)
        tables.append(tab)
    return tables
