"""Plain reference of the decode-time n-gram plane, and its control.

Written from the definitions on the host in NumPy, independent of the
program under test (it imports nothing from it and takes none of its
tables). Per session, with ``rotl`` an L-bit rotation:

* the rolling prefix ``p`` is the CYCLIC hash of the last ``n-1`` tokens;
  consuming token ``x`` forms ``w = rotl(p, 1) ^ h1[x]``; once ``n``
  tokens are in, ``w`` is a whole n-gram: it is added to the session's
  Bloom filter and the oldest token leaves the prefix,
  ``p = w ^ rotl(h1[oldest], n-1)``;
* every filter probe derives from the hash's low ``L-n+1`` bits ``h``
  (Theorem 2's discard): probe ``i`` is bit ``(h + i*s) mod m`` with
  ``s = (h * 0x9E3779B9) | 1``; a key is present iff all ``k`` bits are set;
* a decode step, for a session that has seen at least ``n-1`` tokens, bans
  every candidate ``v`` whose n-gram ``rotl(p, 1) ^ h1[v]`` is present in
  its filter (logit ``-1e30``) and counts those present in the shared
  canary filter; the token is the first maximum of the masked logits
  (greedy), and the step consumes it.

Every probe is computed modulo 2^32 and then masked to ``log2_m`` bits, and
the low bits of a sum or a product modulo 2^32 depend only on the low bits
of its operands: so every probe of ``h``, and whether ``h`` is present,
depends only on ``h mod 2^log2_m``. A step therefore fills a table of
membership over all ``2^log2_m`` residues with the direct test
(``Plane._present``), once per run for the canary filter and once per step
for each session's filter, and answers each candidate with one lookup
(``present_table``, ``lookup``). The first step of every run also takes
the direct test over every candidate and counts where the two forms
differ (``table_mismatches``).

The control is the same plane with half the no-repeat probes
(``k // 2`` bits set and tested per n-gram): the cut that would tempt a
faster plane, whose probes are its cost.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

_U32 = np.uint32
# sessions whose every token and final carry are compared, drawn from the seed
CHECK_ROWS = 32
# exact comparisons: no mismatch is allowed
LIMITS = {"token_mismatches": 0, "carry_mismatch_rows": 0,
          "table_mismatches": 0}
_STRIDE = _U32(0x9E3779B9)
_NEG = np.float32(-1e30)
CARRY = ("prefix", "ring", "pos", "bloom", "count", "active", "steps",
         "banned", "canary")


def draw_params(cfg: dict, seed: int) -> Dict[str, np.ndarray]:
    """The symbol table and the shared canary filter, from the seed alone."""
    rng = np.random.default_rng([seed, 0xDEC0])
    plane = cfg["decode_plane"]
    h1 = rng.integers(0, 1 << 32, size=int(cfg["vocab_size"]), dtype=_U32)
    words = (1 << int(plane["canary_log2_m"])) // 32
    canary = rng.integers(0, 1 << 32, size=words, dtype=_U32)
    return {"h1": h1, "canary": canary}


def _rotl(v, r: int, L: int):
    r %= L
    m = _U32((1 << L) - 1)
    v = np.asarray(v, _U32) & m
    if r == 0:
        return v
    return ((v << _U32(r)) | (v >> _U32(L - r))) & m


def _probes(h, k: int, log2_m: int):
    """(..., ) hashes -> k arrays of bit positions."""
    h = np.asarray(h, _U32)
    stride = (h * _STRIDE) | _U32(1)
    m = _U32((1 << log2_m) - 1)
    return [(h + _U32(i) * stride) & m for i in range(k)]


def present_table(filt, k: int, log2_m: int, per_row: bool) -> np.ndarray:
    """Membership of every residue ``r < 2^log2_m`` in ``filt``: a bool
    table of ``2^log2_m`` entries, one row of them per filter row if
    ``per_row``. ``h`` is present iff its table entry at ``h mod 2^log2_m``
    is set (the module's docstring says why)."""
    r = np.arange(1 << log2_m, dtype=_U32)
    if per_row:
        r = np.broadcast_to(r, (len(filt), r.size))
    return Plane._present(r, filt, k, log2_m, per_row)


def lookup(table: np.ndarray, prefix, h1, bits) -> np.ndarray:
    """Whether the hash ``prefix[i] ^ h1[v]`` is present, for every row ``i``
    and symbol ``v``, from :func:`present_table`'s table (of one filter for
    every row, or one per row). Only the residue is formed, row by row:
    ``(prefix[i] ^ h1[v]) & bits``, with ``bits`` the table's ``2^log2_m - 1``
    and any mask the hash carries."""
    low = (np.asarray(h1, _U32) & _U32(bits)).astype(np.intp)
    out = np.empty((len(prefix), len(low)), bool)
    for i, p in enumerate(np.asarray(prefix, _U32) & _U32(bits)):
        out[i] = (table if table.ndim == 1 else table[i])[low ^ int(p)]
    return out


class Plane:
    """``rows`` sessions of the plane, stepped as the definitions say."""

    def __init__(self, plane: dict, h1, canary, rows: int, *, k=None):
        self.n, self.L = int(plane["n"]), int(plane["L"])
        self.log2_m = int(plane["log2_m"])
        self.k = int(plane["k"]) if k is None else k
        self.canary_log2_m = int(plane["canary_log2_m"])
        self.canary_k = int(plane["canary_k"])
        self.mask = _U32((1 << (self.L - self.n + 1)) - 1)
        self.h1 = np.asarray(h1, _U32) & _U32((1 << self.L) - 1)
        self.canary = np.asarray(canary, _U32)
        self.canary_table = present_table(self.canary, self.canary_k,
                                          self.canary_log2_m, False)
        # set by the first step: where the table form and the direct differ
        self.table_mismatches = None
        R = rows
        self.s = {"prefix": np.zeros(R, _U32),
                  "ring": np.zeros((R, self.n - 1), _U32),
                  "pos": np.zeros(R, np.int64),
                  "bloom": np.zeros((R, 1 << (self.log2_m - 5)), _U32),
                  "count": np.zeros(R, np.int64),
                  "active": np.ones(R, np.int64),
                  "steps": np.zeros(R, np.uint64),
                  "banned": np.zeros(R, np.uint64),
                  "canary": np.zeros(R, np.uint64)}

    def consume(self, tokens) -> None:
        """Every session consumes one token."""
        s, n, L = self.s, self.n, self.L
        hv = self.h1[np.asarray(tokens)]
        w = _rotl(s["prefix"], 1, L) ^ hv
        count = np.minimum(s["count"] + 1, n)
        full = count >= n
        rows = np.flatnonzero(full)
        for p in _probes(w[rows] & self.mask, self.k, self.log2_m):
            s["bloom"][rows, (p >> _U32(5)).astype(np.int64)] |= (
                _U32(1) << (p & _U32(31)))
        oldest = s["ring"][np.arange(len(w)), s["pos"]]
        s["prefix"] = np.where(full, w ^ _rotl(oldest, n - 1, L), w)
        s["ring"][np.arange(len(w)), s["pos"]] = hv
        s["pos"] = (s["pos"] + 1) % (n - 1)
        s["count"] = count

    def prime(self, prompts) -> None:
        for t in range(prompts.shape[1]):
            self.consume(prompts[:, t])

    @staticmethod
    def _present(h, filt, k: int, log2_m: int, per_row: bool):
        hit = np.ones(h.shape, bool)
        for p in _probes(h, k, log2_m):
            word = (p >> _U32(5)).astype(np.int64)
            got = (np.take_along_axis(filt, word, axis=1) if per_row
                   else filt[word])
            hit &= ((got >> (p & _U32(31))) & _U32(1)) == 1
        return hit

    def step(self, logits) -> np.ndarray:
        """One greedy decode step over (rows, V) logits; returns tokens."""
        s = self.s
        waiting = s["count"] < self.n - 1
        prefix = _rotl(s["prefix"], 1, self.L)
        bits = self.mask & _U32((1 << self.log2_m) - 1)
        banned = lookup(present_table(s["bloom"], self.k, self.log2_m, True),
                        prefix, self.h1, bits)
        bits = self.mask & _U32((1 << self.canary_log2_m) - 1)
        canary = lookup(self.canary_table, prefix, self.h1, bits)
        if self.table_mismatches is None:
            h = (prefix[:, None] ^ self.h1[None, :]) & self.mask
            direct_b = self._present(h, s["bloom"], self.k, self.log2_m, True)
            direct_c = self._present(h, self.canary, self.canary_k,
                                     self.canary_log2_m, False)
            self.table_mismatches = int((banned != direct_b).sum()
                                        + (canary != direct_c).sum())
        banned[waiting] = False
        canary[waiting] = False
        tokens = np.argmax(np.where(banned, _NEG, logits), axis=1)
        s["banned"] += np.count_nonzero(banned, axis=1).astype(np.uint64)
        s["canary"] += np.count_nonzero(canary, axis=1).astype(np.uint64)
        s["steps"] += np.uint64(1)
        self.consume(tokens)
        return tokens.astype(np.int32)


def carry_rows(state: Dict[str, np.ndarray], rows) -> Dict[str, np.ndarray]:
    """A session pool's carry in the reference's layout, at ``rows``."""
    u64 = lambda lo, hi: (np.asarray(state[hi], np.uint64)[rows]
                          << np.uint64(32)) | np.asarray(state[lo],
                                                         np.uint64)[rows]
    return {"prefix": np.asarray(state["prefix"], _U32)[rows],
            "ring": np.asarray(state["ring"], _U32)[rows],
            "pos": np.asarray(state["pos"], np.int64)[rows],
            "bloom": np.asarray(state["bloom"], _U32)[rows],
            "count": np.asarray(state["count"], np.int64)[rows],
            "active": np.asarray(state["active"], np.int64)[rows],
            "steps": np.asarray(state["steps"], np.uint64)[rows],
            "banned": u64("banned_lo", "banned_hi"),
            "canary": u64("canary_lo", "canary_hi")}


def run(cfg: dict, params, prompts, logits_at, n_steps: int, *, k=None):
    """Prime ``prompts`` (rows, T) and take ``n_steps`` greedy steps, step
    ``t`` on ``logits_at(t)`` (rows, V). Returns the ``tokens`` (n_steps,
    rows), the final ``carry`` and the first step's ``table_mismatches``."""
    plane = Plane(cfg["decode_plane"], params["h1"], params["canary"],
                  prompts.shape[0], k=k)
    plane.prime(prompts)
    toks = np.stack([plane.step(logits_at(t)) for t in range(n_steps)])
    return {"tokens": toks, "carry": plane.s,
            "table_mismatches": plane.table_mismatches}


def compare(prog: dict, ref: dict) -> Dict[str, int]:
    """Mismatch counts between a run's tokens and carry and the reference's,
    and the reference's own ``table_mismatches``.

    Each of ``prog`` and ``ref`` holds ``tokens`` (steps, rows) and
    ``carry`` in the reference's layout; ``ref`` is :func:`run`'s result.
    """
    rows = len(ref["carry"]["prefix"])
    bad = np.zeros(rows, bool)
    for key in CARRY:
        a, b = prog["carry"][key], ref["carry"][key]
        bad |= (a != b).reshape(rows, -1).any(axis=1)
    return {"token_mismatches": int((prog["tokens"] != ref["tokens"]).sum()),
            "carry_mismatch_rows": int(bad.sum()),
            "table_mismatches": ref["table_mismatches"]}
