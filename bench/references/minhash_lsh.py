"""Plain reference of MinHash-LSH near-duplicate detection, and its control.

Written from the definitions, independent of the program under test (it
imports nothing from it and takes none of its tables):

* the CYCLIC n-gram hash of window ``j`` is
  ``XOR_t rotl(h1[x_{j+t}], n-1-t)`` over ``t < n`` (L-bit rotations), and
  keeps its low ``L-n+1`` bits (Theorem 1's discard);
* lane ``l`` of a signature is ``min_j (a_l * h_j + b_l) mod 2^32`` over
  the document's windows, ``0xFFFFFFFF`` for a document with none;
* band ``b`` of a signature is its ``rows`` lanes ``[b*rows, (b+1)*rows)``;
  a document is a duplicate iff some earlier *kept* document shares one of
  its bands and agrees with it on at least ``threshold`` of the lanes. Its
  candidates are every earlier kept document that shares a band, numbered
  by their order among the kept documents.

The signatures run on whatever JAX device is present, in blocks of rows;
the banding and verify run on the host. The control is the same reference
with b-bit lanes (``lane_bits=16``: each remixed hash keeps its high 16
bits before the min), the precision step that would tempt a faster signer.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Set

import jax
import jax.numpy as jnp
import numpy as np

_SENTINEL = np.uint32(0xFFFFFFFF)
# tokens per reference block: rows * padded length
_BLOCK_TOKENS = 1 << 21
_MIN_S = 512
# exact comparisons: no mismatch is allowed
LIMITS = {"sig_mismatch_rows": 0, "cand_mismatch_docs": 0, "flag_mismatches": 0}


def draw_params(cfg: dict, seed: int) -> Dict[str, np.ndarray]:
    """The symbol table and remix lanes, drawn from the seed alone."""
    rng = np.random.default_rng([seed, 0x51C0])
    h1 = rng.integers(0, 1 << 32, size=int(cfg["vocab"]), dtype=np.uint32)
    k = int(cfg["n_signatures"])
    a = rng.integers(0, 1 << 32, size=k, dtype=np.uint32) | np.uint32(1)
    b = rng.integers(0, 1 << 32, size=k, dtype=np.uint32)
    return {"h1": h1, "a": a, "b": b}


def _rotl(v, r: int, L: int):
    r %= L
    m = np.uint32((1 << L) - 1)
    v = v & m
    if r == 0:
        return v
    return ((v << np.uint32(r)) | (v >> np.uint32(L - r))) & m


@functools.partial(jax.jit, static_argnames=("n", "L", "lane_bits"))
def _sign_block(h1v, n_windows, a, b, *, n: int, L: int, lane_bits: int):
    """(R, S) symbol hashes + (R,) window counts -> (R, k) signatures."""
    W = h1v.shape[1] - n + 1
    h = jnp.zeros((h1v.shape[0], W), jnp.uint32)
    for t in range(n):
        h = h ^ _rotl(h1v[:, t : t + W], n - 1 - t, L)
    h = h & np.uint32((1 << (L - n + 1)) - 1)
    valid = jnp.arange(W)[None, :, None] < n_windows[:, None, None]
    out = []
    for s in range(0, a.shape[0], 16):
        mixed = a[None, None, s : s + 16] * h[:, :, None] + b[None, None,
                                                              s : s + 16]
        if lane_bits < 32:
            mixed = mixed >> np.uint32(32 - lane_bits)
        out.append(jnp.min(jnp.where(valid, mixed, _SENTINEL), axis=1))
    return jnp.concatenate(out, axis=1)


def signatures(docs: Sequence[np.ndarray], params: Dict[str, np.ndarray],
               n: int, L: int, lane_bits: int = 32) -> np.ndarray:
    """(D, k) uint32 signatures of ``docs``, in blocks grouped by length."""
    k = len(params["a"])
    out = np.empty((len(docs), k), np.uint32)
    a, b = jnp.asarray(params["a"]), jnp.asarray(params["b"])
    h1 = params["h1"]
    lengths = np.array([len(d) for d in docs], np.int64)
    width = np.maximum(_MIN_S, 1 << np.ceil(
        np.log2(np.maximum(lengths, n))).astype(np.int64))
    for S in np.unique(width):
        idx = np.flatnonzero(width == S)
        full = max(1, _BLOCK_TOKENS // int(S))
        for s in range(0, len(idx), full):
            rows = idx[s : s + full]
            # full blocks, then one power-of-two tail: few shapes to compile
            R = min(full, 1 << int(np.ceil(np.log2(len(rows)))))
            h1v = np.zeros((R, int(S)), np.uint32)
            nw = np.zeros((R,), np.int32)
            for r, i in enumerate(rows):
                h1v[r, : lengths[i]] = h1[docs[i]]
                nw[r] = max(0, lengths[i] - n + 1)
            sig = _sign_block(jnp.asarray(h1v), jnp.asarray(nw), a, b, n=n,
                              L=L, lane_bits=lane_bits)
            out[rows] = np.asarray(sig)[: len(rows)]
    return out


def dedup(sigs: np.ndarray, bands: int, threshold: float):
    """Flags and candidate sets of documents signed ``sigs``, in order.

    Returns ``(flags, cands)``: (D,) bool, and ``{i: set of kept ids}`` for
    every document with a candidate.
    """
    D, k = sigs.shape
    rows = k // bands
    keys = np.ascontiguousarray(sigs.reshape(D, bands, rows)).view(
        np.dtype((np.void, 4 * rows)))[..., 0]
    pairs = []
    for band in range(bands):
        _, inv = np.unique(keys[:, band], return_inverse=True)
        order = np.argsort(inv, kind="stable")
        grp = inv[order]
        d = 1
        while True:
            same = grp[d:] == grp[:-d] if d < D else np.zeros(0, bool)
            if not same.any():
                break
            pairs.append(np.stack([order[:-d][same], order[d:][same]], 1))
            d += 1
    flags = np.zeros(D, bool)
    cands: Dict[int, Set[int]] = {}
    if not pairs:
        return flags, cands
    pairs = np.unique(np.concatenate(pairs), axis=0)     # (j, i), j < i
    pairs = pairs[np.lexsort((pairs[:, 0], pairs[:, 1]))]
    agree = (sigs[pairs[:, 0]] == sigs[pairs[:, 1]]).mean(axis=1) >= threshold
    kept = np.ones(D, bool)
    starts = np.flatnonzero(np.r_[True, pairs[1:, 1] != pairs[:-1, 1]])
    ends = np.r_[starts[1:], len(pairs)]
    # the candidate lists of document i only name documents before i, whose
    # verdicts are settled when i is reached
    for s, e in zip(starts, ends):
        i = int(pairs[s, 1])
        js = pairs[s:e, 0]
        live = kept[js]
        if live.any():
            cands[i] = set(js[live].tolist())
        if (agree[s:e] & live).any():
            flags[i] = True
            kept[i] = False
    kept_id = np.cumsum(kept) - 1
    cands = {i: {int(kept_id[j]) for j in js} for i, js in cands.items()}
    return flags, cands


def compare(prog: dict, ref: dict) -> Dict[str, int]:
    """Mismatch counts between a run's outputs and the reference's.

    Each of ``prog`` and ``ref`` holds ``sigs`` (D, k), ``flags`` (D,) and
    ``cands`` ({doc: set of kept ids}).
    """
    docs = set(prog["cands"]) | set(ref["cands"])
    return {
        "sig_mismatch_rows": int(
            (prog["sigs"] != ref["sigs"]).any(axis=1).sum()),
        "cand_mismatch_docs": sum(
            prog["cands"].get(i, set()) != ref["cands"].get(i, set())
            for i in docs),
        "flag_mismatches": int((prog["flags"] != ref["flags"]).sum()),
    }


def outputs(docs: Sequence[np.ndarray], params: Dict[str, np.ndarray],
            cfg: dict, lane_bits: int = 32) -> dict:
    """Signatures, flags and candidates of the reference (or, with
    ``lane_bits < 32``, of its control) over ``docs``."""
    sigs = signatures(docs, params, int(cfg["ngram_n"]), int(cfg["L"]),
                      lane_bits=lane_bits)
    flags, cands = dedup(sigs, int(cfg["lsh_bands"]), float(cfg["threshold"]))
    return {"sigs": sigs, "flags": flags, "cands": cands}


def program_candidates(batches: List[tuple], flags: np.ndarray
                       ) -> Dict[int, Set[int]]:
    """A run's candidate sets in the reference's numbering.

    ``batches`` holds, per ``add_batch`` call, its first document's global
    position and the probe's ``(index_cand, batch_cand)``: kept ids from the
    index, and earlier batch positions that resolve to kept ids once their
    verdict is known (``flags`` are the run's own verdicts).
    """
    kept_id = np.cumsum(~flags) - 1
    out: Dict[int, Set[int]] = {}
    for start, (index_cand, batch_cand) in batches:
        for pos, (ic, bc) in enumerate(zip(index_cand, batch_cand)):
            if not ic and not bc:
                continue
            c = set(int(x) for x in ic)
            c.update(int(kept_id[start + j]) for j in bc
                     if not flags[start + j])
            if c:
                out[start + pos] = c
    return out
