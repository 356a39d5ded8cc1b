"""Plain reference of streaming n-gram statistics, and its control.

Written from the definitions on the host in NumPy uint64, independent of
the program under test (it imports nothing from it and takes none of its
tables). Over rows of token ids, each row one stream that continues from
call to call:

* the CYCLIC hash of the n-gram ending at position j is the
  non-recursive ``XOR_t rotl(h1[x_{j-n+1+t}], n-1-t)`` over ``t < n``, with
  L-bit rotations of the seed's L-bit symbol hashes ``h1``; it keeps its
  low ``L-n+1`` bits (Theorem 1's discard). A window exists once its row
  has seen n tokens, so an n-gram that spans two calls is hashed once, in
  the call that holds its last token;
* HyperLogLog with ``2^b`` registers: register ``key mod 2^b`` keeps the
  largest ``rank = min(ctz(key >> b), L-n+1-b) + 1`` (``np.maximum.at``);
* CountMin row d counts column ``(sum_i a[d, i] x_i + b[d]) mod 2^32 >>
  (32 - l)`` over the key's pieces ``x_i`` of ``33 - l`` bits, low piece
  first (Dietzfelbinger's vector multiply-add-shift, ``np.bincount``).

The rotations are read from ``n`` tables ``rotl(h1, r)``, one per
rotation, so each window costs n table reads and XORs. A call's tokens are
a step of the traffic's ring relabelled by its pass's affine map, made
here from the ring and the maps. Calls are checked in blocks on fresh
worker processes (spawned: they import NumPy and this module only), each
block's rows starting from the previous call's last ``n-1`` tokens.

The control is the same reference at 32-bit lanes: h1 cut to its low 32
bits and 32-bit rotations, ``32-n+1`` usable bits, the width the program
had before two-word lanes.
"""
from __future__ import annotations

import importlib
import multiprocessing
import os
from concurrent.futures import ProcessPoolExecutor
from typing import Dict, List, Sequence, Tuple

import numpy as np

# exact comparisons: no mismatch is allowed
LIMITS = {"register_mismatches": 0, "cell_mismatches": 0,
          "token_count_error": 0}
_WORKERS = max(1, min(12, (os.cpu_count() or 2) - 1))
# tokens a worker is worth starting for
_TOKENS_PER_WORKER = 32 << 20


def draw_params(cfg: dict, seed: int) -> Dict[str, np.ndarray]:
    """The L-bit symbol hashes and the CountMin constants, from the seed.

    ``h1`` is (vocab,) uint64; the program takes it as two uint32 words.
    ``cms_a`` is (depth, pieces) uint32 and ``cms_b`` (depth,) uint32."""
    rng = np.random.default_rng([seed, 0x57A7])
    L = int(cfg["L"])
    h1 = rng.integers(0, 1 << 63, size=int(cfg["vocab"]), dtype=np.uint64)
    h1 = (h1 << np.uint64(1)) | rng.integers(0, 2, size=h1.shape,
                                             dtype=np.uint64)
    if L < 64:
        h1 &= np.uint64((1 << L) - 1)
    depth = int(cfg["cms_depth"])
    a = rng.integers(0, 1 << 32, size=(depth, pieces(cfg)), dtype=np.uint32)
    b = rng.integers(0, 1 << 32, size=depth, dtype=np.uint32)
    return {"h1": h1, "cms_a": a, "cms_b": b}


def pieces(cfg: dict, L: int = None) -> int:
    """Key pieces of ``33 - l`` bits that cover the ``L-n+1`` usable bits."""
    L = int(cfg["L"]) if L is None else L
    bits = L - int(cfg["ngram_n"]) + 1
    return -(-bits // (33 - int(cfg["cms_log2_width"])))


def h1_words(h1: np.ndarray) -> np.ndarray:
    """(vocab,) uint64 -> (2, vocab) uint32, low word first."""
    return np.stack([(h1 & np.uint64(0xFFFFFFFF)).astype(np.uint32),
                     (h1 >> np.uint64(32)).astype(np.uint32)])


def _rotl(v: np.ndarray, r: int, L: int) -> np.ndarray:
    m = np.uint64((1 << L) - 1) if L < 64 else np.uint64(0xFFFFFFFFFFFFFFFF)
    r %= L
    if r == 0:
        return v & m
    return ((v << np.uint64(r)) | ((v & m) >> np.uint64(L - r))) & m


class Stats:
    """Registers, table and token count of the windows folded so far."""

    def __init__(self, cfg: dict, params: Dict[str, np.ndarray], L: int):
        self.n = int(cfg["ngram_n"])
        self.L = L
        self.b = int(cfg["hll_b"])
        self.l = int(cfg["cms_log2_width"])
        self.bits = L - self.n + 1
        h1 = params["h1"] & np.uint64((1 << L) - 1 if L < 64
                                      else 0xFFFFFFFFFFFFFFFF)
        # table t holds rotl(h1, n-1-t): window position t's term
        self.rot = [_rotl(h1, self.n - 1 - t, L) for t in range(self.n)]
        self.a = params["cms_a"][:, : pieces(cfg, L)]
        self.cb = params["cms_b"]
        self.regs = np.zeros(1 << self.b, np.int64)
        self.table = np.zeros((len(self.cb), 1 << self.l), np.int64)
        self.tokens = 0

    def keys(self, x: np.ndarray) -> np.ndarray:
        """(R, S) tokens -> (R, S-n+1) masked uint64 window hashes."""
        W = x.shape[1] - self.n + 1
        acc = np.zeros((x.shape[0], W), np.uint64)
        for t in range(self.n):
            acc ^= self.rot[t][x[:, t : t + W]]
        return acc & np.uint64((1 << self.bits) - 1)

    def fold(self, x: np.ndarray, new_tokens: int) -> None:
        """Fold the windows of (R, S) tokens, ``new_tokens`` of them new."""
        key = self.keys(x).reshape(-1)
        self.tokens += new_tokens
        idx = (key & np.uint64((1 << self.b) - 1)).astype(np.int64)
        rest = key >> np.uint64(self.b)
        tz = np.bitwise_count((rest & (~rest + np.uint64(1))) - np.uint64(1))
        rank = np.minimum(tz, self.bits - self.b).astype(np.int64) + 1
        np.maximum.at(self.regs, idx, rank)
        w = 33 - self.l
        parts = [((key >> np.uint64(i * w)) & np.uint64((1 << w) - 1)
                  ).astype(np.uint32) for i in range(self.a.shape[1])]
        for d in range(len(self.cb)):
            acc = np.full(key.shape, self.cb[d], np.uint32)
            for i, part in enumerate(parts):
                acc += self.a[d, i] * part
            col = (acc >> np.uint32(32 - self.l)).astype(np.int64)
            self.table[d] += np.bincount(col, minlength=1 << self.l)

    def merge(self, other: "Stats") -> None:
        np.maximum(self.regs, other.regs, out=self.regs)
        self.table += other.table
        self.tokens += other.tokens


def call_tokens(ring: np.ndarray, maps: Sequence[Tuple[int, int]],
                vocab: int, i: int) -> np.ndarray:
    """Call i's (rows, row_len) tokens: ring step ``i % steps`` with token
    t mapped to ``(a * t + c) mod vocab``, ``(a, c) = maps[i // steps]``."""
    a, c = maps[i // len(ring)]
    return (ring[i % len(ring)].astype(np.int64) * a + c) & (vocab - 1)


def _block(job) -> Stats:
    """Fold calls [lo, hi), the first one's rows starting from the call
    before it."""
    cfg, params, L, ring, maps, lo, hi = job
    st = Stats(cfg, params, L)
    n, vocab = st.n, int(cfg["vocab"])
    prev = (call_tokens(ring, maps, vocab, lo - 1)[:, -(n - 1):] if lo > 0
            else None)
    for i in range(lo, hi):
        x = call_tokens(ring, maps, vocab, i)
        # a row's first call has no history: its first n-1 tokens end no
        # window; later calls continue from the previous call's tail
        st.fold(x if prev is None else np.concatenate([prev, x], axis=1),
                x.size)
        prev = x[:, -(n - 1):]
    return st


def run(cfg: dict, params: Dict[str, np.ndarray], ring: np.ndarray,
        maps: Sequence[Tuple[int, int]], calls: int, L: int = None) -> Stats:
    """Registers, table and token count after ``calls`` calls of the
    (steps, rows, row_len) ``ring`` relabelled by ``maps`` (one (a, c) per
    pass, :func:`call_tokens`). ``L`` cuts the lanes (the control); by
    default the configuration's."""
    L = int(cfg["L"]) if L is None else L
    tokens = calls * ring[0].size
    workers = max(1, min(_WORKERS, calls, tokens // _TOKENS_PER_WORKER))
    cuts = np.linspace(0, calls, workers + 1).round().astype(int)
    maps = [tuple(int(v) for v in m) for m in maps]
    jobs = [(cfg, params, L, ring, maps, int(a), int(b))
            for a, b in zip(cuts[:-1], cuts[1:]) if b > a]
    if workers <= 1:
        parts: List[Stats] = [_block(j) for j in jobs]
    else:
        # fresh processes that import NumPy and this module by its package
        # name only: nothing of the caller's process (its JAX runtime, its
        # device) is inherited
        mod = importlib.import_module("bench.references.ngram_stats")
        ctx = multiprocessing.get_context("spawn")
        with ProcessPoolExecutor(workers, mp_context=ctx) as pool:
            parts = list(pool.map(mod._block, jobs))
    out = parts[0]
    for p in parts[1:]:
        out.merge(p)
    return out


def compare(prog: dict, ref: Stats) -> Dict[str, int]:
    """Mismatch counts between a run's final state and the reference's:
    ``prog`` holds ``hll`` (2^b,), ``cms`` (depth, 2^l) and ``tokens``."""
    return {
        "register_mismatches": int(
            (np.asarray(prog["hll"], np.int64) != ref.regs).sum()),
        "cell_mismatches": int(
            (np.asarray(prog["cms"], np.int64) != ref.table).sum()),
        "token_count_error": abs(int(prog["tokens"]) - ref.tokens),
    }


def as_program(ref: Stats) -> dict:
    """A reference result in the program's form, for the control's
    comparison."""
    return {"hll": ref.regs, "cms": ref.table, "tokens": ref.tokens}
