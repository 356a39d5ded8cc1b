"""The one traffic generator: reads a mix's parameters and makes its inputs.

Every input is a function of ``--seed`` and the mix's data file
(``bench/traffic/<traffic>.json``), never of the program under test.

``documents`` mixes (dedup cells): a pool of document batches with
log-normal lengths, Zipf token ids and planted near-duplicates, in the
manner of the program's ``data/corpus.py``. A closed loop cycles the pool;
each pass after the first relabels every token by a bijective affine map of
the vocabulary drawn for that pass, so a pass duplicates nothing an earlier
pass indexed, except its own planted duplicates.

``decode`` mixes (session-pool cells): Zipf prompts, one per session.
"""
from __future__ import annotations

from typing import List, Tuple

import numpy as np


class ZipfTokens:
    """Token ids ``(rank - 1) % vocab`` with ``rank ~ Zipf(alpha)``.

    The same distribution as the program's ``data/corpus.zipf_tokens``
    (numpy's Zipf draw folded onto the vocabulary), sampled by Vose's alias
    method from its exact folded mass function,
    ``p(t) ~ vocab**-alpha * hurwitz_zeta(alpha, (t + 1) / vocab)``:
    two table reads per token in place of a rejection loop.
    """

    def __init__(self, vocab: int, alpha: float):
        from scipy.special import zeta
        p = zeta(alpha, (np.arange(vocab, dtype=np.float64) + 1.0) / vocab)
        p /= p.sum()
        self.vocab = vocab
        self.prob, self.alias = _alias_table(p)

    def __call__(self, rng: np.random.Generator, n: int) -> np.ndarray:
        u = rng.random(n) * self.vocab
        idx = u.astype(np.int64)
        np.minimum(idx, self.vocab - 1, out=idx)
        keep = (u - idx) < self.prob[idx]
        return np.where(keep, idx, self.alias[idx]).astype(np.int32)


def _alias_table(p: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Vose's alias table for the mass function ``p``."""
    n = len(p)
    q = (p * n).tolist()
    prob = [1.0] * n
    alias = list(range(n))
    small = [i for i, x in enumerate(q) if x < 1.0]
    large = [i for i, x in enumerate(q) if x >= 1.0]
    while small and large:
        s, g = small.pop(), large.pop()
        prob[s], alias[s] = q[s], g
        q[g] += q[s] - 1.0
        (small if q[g] < 1.0 else large).append(g)
    return np.asarray(prob), np.asarray(alias, np.int64)


class DocumentPool:
    """``pool_batches`` batches of ``batch_docs`` documents, made once.

    ``dup_rate`` of the documents copy an earlier pool document with
    ``mutate_frac`` of their tokens redrawn; ``dup_of[i]`` names the source
    (-1 for originals). ``batch(i)`` is the i-th batch of the endless
    closed-loop stream: pool batch ``i % pool_batches`` relabelled for pass
    ``i // pool_batches`` (pass 0 unchanged).
    """

    def __init__(self, traffic: dict, vocab: int, seed: int):
        if vocab & (vocab - 1):
            raise ValueError(f"relabelling needs a power-of-two vocabulary, "
                             f"got {vocab}")
        self.vocab = vocab
        self.batch_docs = int(traffic["batch_docs"])
        self.pool_batches = int(traffic["pool_batches"])
        rng = np.random.default_rng([seed, 0x0D0C])
        zipf = ZipfTokens(vocab, float(traffic["zipf_alpha"]))
        n = self.batch_docs * self.pool_batches
        ln = traffic["length"]
        lengths = np.clip(
            np.round(rng.lognormal(np.log(ln["median"]), ln["sigma"], n)),
            ln["min"], ln["max"]).astype(np.int64)
        is_dup = rng.random(n) < float(traffic["dup_rate"])
        is_dup[0] = False
        # a duplicate's source is any earlier document, as in data/corpus.py
        src = (rng.random(n) * np.arange(n)).astype(np.int64)
        self.dup_of = np.where(is_dup, src, -1)
        # a duplicate is as long as its source (sources come first)
        for i in np.flatnonzero(is_dup):
            lengths[i] = lengths[self.dup_of[i]]
        self.offsets = np.zeros(n + 1, np.int64)
        np.cumsum(lengths, out=self.offsets[1:])
        tokens = zipf(rng, int(self.offsets[-1]))
        flips = rng.random(len(tokens)) < float(traffic["mutate_frac"])
        redraw = zipf(rng, int(flips.sum()))
        pos = 0
        for i in np.flatnonzero(is_dup):
            lo, hi = self.offsets[i], self.offsets[i + 1]
            s = self.offsets[self.dup_of[i]]
            tokens[lo:hi] = tokens[s : s + (hi - lo)]
            f = np.flatnonzero(flips[lo:hi])
            tokens[lo + f] = redraw[pos : pos + len(f)]
            pos += len(f)
        self.tokens = tokens
        # pass p > 0 maps token t to (a_p * t + c_p) % vocab, a_p odd
        self._pass_rng = np.random.default_rng([seed, 0x9A55])
        self._maps = [(1, 0)]

    def _map(self, p: int) -> Tuple[int, int]:
        while len(self._maps) <= p:
            a, c = self._pass_rng.integers(0, self.vocab, 2)
            self._maps.append((int(a) | 1, int(c)))
        return self._maps[p]

    def batch_range(self, b: int) -> Tuple[int, int]:
        """Token span [lo, hi) of pool batch ``b``."""
        d0 = b * self.batch_docs
        return int(self.offsets[d0]), int(self.offsets[d0 + self.batch_docs])

    def batch(self, i: int) -> List[np.ndarray]:
        """The ``i``-th batch of the stream, as a list of int32 arrays."""
        p, b = divmod(i, self.pool_batches)
        lo, hi = self.batch_range(b)
        toks = self.tokens[lo:hi]
        a, c = self._map(p)
        if (a, c) != (1, 0):
            toks = ((toks.astype(np.int64) * a + c)
                    & (self.vocab - 1)).astype(np.int32)
        d0 = b * self.batch_docs
        cuts = self.offsets[d0 + 1 : d0 + self.batch_docs] - lo
        return np.split(toks, cuts)

    def batch_dup_of(self, i: int) -> np.ndarray:
        """Planted sources of the ``i``-th batch's documents, as positions in
        the pool (-1 for originals)."""
        b = i % self.pool_batches
        return self.dup_of[b * self.batch_docs : (b + 1) * self.batch_docs]


def prompts(traffic: dict, vocab: int, seed: int) -> np.ndarray:
    """(sessions, prompt_len) int32 Zipf prompts of a ``decode`` mix."""
    rng = np.random.default_rng([seed, 0x9307])
    zipf = ZipfTokens(vocab, float(traffic["zipf_alpha"]))
    shape = (int(traffic["sessions"]), int(traffic["prompt_len"]))
    return zipf(rng, shape[0] * shape[1]).reshape(shape)
