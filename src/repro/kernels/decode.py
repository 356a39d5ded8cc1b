"""Pallas decode epilogue: fused no-repeat/decontam masking over the logits
tile.

The paper's recursive CYCLIC family makes decode-time n-gram control nearly
free: with the rolling prefix hash ``h_prefix`` of the last n-1 generated
tokens in hand, the hash of EVERY candidate continuation is

    h_cand(v) = rotl(h_prefix, 1) XOR h1[v]          for all v at once

— one rotate, one XOR-broadcast, O(vocab) bitwise ops instead of a re-hash
of the window per candidate. :func:`decode_masks_fused` runs the whole
epilogue as one jit graph with one kernel pass over the logits tile:

* the candidate hashes, with the Theorem-2 discard — probes derive from
  ``h_cand & hash_mask``, never from the n-1 dependent high bits
  (``DecodeSpec.out_bits``),
* k double-hashed probes against the session's packed no-repeat Bloom row,
  and optionally against a SHARED decontam canary filter (training-set
  leakage telemetry on live traffic),
* the banned-logit substitution itself (``-1e30`` where banned & ready),

emitting the masked logits plus bit-packed banned/canary masks (uint32, 32
candidates per word — the masks round-trip HBM at 1/32nd the logits size).

The kernel hashes and probes in VMEM. Its inputs are the row's prefix
hash, a (1, block_v) tile of h1, the session filter rows as 128-word
chunks and the whole shared canary filter (one block, fetched once). Per
128-candidate slice it forms ``h = (rotl(prefix, 1) ^ h1) & hash_mask``
and each probe's word index; Mosaic gathers across lanes within one vreg
only, so a word is read by one lane gather per 128-word chunk (the
session filter row-wise, one session per sublane; each canary row
broadcast to every sublane) and kept where the chunk is the word's own.
The cost of that lookup grows linearly with the filter, so a filter of
more than ``VMEM_PROBE_MAX_WORDS`` words (2^20 bits) is probed instead by
XLA gathers ahead of the kernel, inside the same jit graph, and reaches it
as a per-candidate hit word (bit 0 no-repeat filter, bit 1 canary). The
kernel then does the logits-sized pass: validity (vocabulary padding,
session readiness), the substitution, and the 32:1 packing. Mosaic cannot
split the lane axis into words, so the packing is a matmul on the MXU: the
0/1 mask against a constant matrix of powers of two gives the low and high
16 bits of every word, exactly (0/1 products, integer sums below 2^24).

Grid/tiling: ``(B/block_b, V/block_v)``; every tile is independent (the
hit scratch is rewritten by each step — the plane is embarrassingly
parallel over sessions AND candidates). ``block_v`` defaults to 4096, so a
tile's packed words fill 128 lanes.

The jnp oracle is :func:`repro.kernels.ref.decode_masks_ref`; bit-parity is
asserted across n (including the degraded n > L regime), vocab sizes and
device counts in ``tests/test_serve_plane.py``. Dispatch through
:func:`repro.kernels.api.decode` (impl="auto" keeps CPU hosts on the oracle
graph, exactly like the sketch engine).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref as _kref
from repro.kernels.plan import DecodeSpec

_U32 = jnp.uint32
_WORD = 32


def _pack_weights(block_v: int) -> jnp.ndarray:
    """(block_v, 2 * block_v/32) bf16: column w sums the low 16 bits of
    packed word w, column block_v/32 + w its high 16 bits. Powers of two up
    to 2^15 are exact in bf16."""
    lane = jnp.arange(block_v, dtype=jnp.int32)[:, None]
    col = jnp.arange(2 * (block_v // _WORD), dtype=jnp.int32)[None, :]
    words = block_v // _WORD
    bit = lane % _WORD
    hit = (lane // _WORD == col % words) & (bit // 16 == col // words)
    return jnp.where(hit, jnp.left_shift(1, bit % 16), 0).astype(jnp.bfloat16)


def _pack_tile(mask, weights):
    """(block_b, block_v) bool -> (block_b, block_v/32) uint32 words, bit i
    of word w = column 32*w + i, via one MXU matmul."""
    words = mask.shape[1] // _WORD
    halves = jnp.dot(mask.astype(jnp.bfloat16), weights,
                     preferred_element_type=jnp.float32).astype(jnp.int32)
    lo = halves[:, :words].astype(_U32)
    hi = halves[:, words:].astype(_U32)
    return lo | (hi << np.uint32(16))


# A filter of at most this many uint32 words (2^20 bits, 128 KiB a row) is
# probed inside the kernel, from VMEM. The lookup takes one lane gather per
# 128-word chunk of the filter and probe, unrolled (a loop over chunks
# would stall the gathers' pipeline at every step), so its cost and its
# code grow linearly with the table; a larger filter is probed by XLA
# gathers ahead of the kernel.
VMEM_PROBE_MAX_WORDS = 1 << 15
_LANES = 128


def probes_in_vmem(n_words: int) -> bool:
    """Whether a filter of ``n_words`` uint32 words is probed in the kernel."""
    return n_words <= VMEM_PROBE_MAX_WORDS


def all_probes_in_vmem(spec: DecodeSpec) -> bool:
    """Every filter of ``spec`` is probed inside the kernel."""
    return probes_in_vmem(spec.n_words) and (
        not spec.has_canary or probes_in_vmem(spec.canary_words))


def _chunk_count(n_words: int) -> int:
    return -(-n_words // _LANES)


# one lane gather: out[r, l] = tile[r, idx[r, l, 0]], the form Mosaic
# lowers to an in-vreg dynamic gather
_LANE_GATHER = jax.lax.GatherDimensionNumbers(
    offset_dims=(), collapsed_slice_dims=(1,), start_index_map=(1,),
    operand_batching_dims=(0,), start_indices_batching_dims=(0,))


def _vmem_probe_hits(h, k: int, log2_m: int, tiles):
    """:func:`repro.kernels.ref.bloom_probe_hits` of a (rows, 128) tile of
    masked hashes against a filter in VMEM, given as its 128-word chunks
    ``tiles``, each a (rows, 128) uint32 tile. Each probe's word is read by
    one lane gather per chunk (Mosaic gathers within one vreg only) and
    kept where the chunk is the word's own. The unrolled lookup is written
    in ``lax`` ops: the ``jnp`` wrappers would trace a nested jit per op."""
    stride = (h * _kref.BLOOM_STRIDE) | np.uint32(1)
    m_mask = np.uint32((1 << log2_m) - 1)
    hit = jnp.ones(h.shape, jnp.bool_)
    for i in range(k):
        p = (h + np.uint32(i) * stride) & m_mask
        word = (p >> np.uint32(5)).astype(jnp.int32)
        lane = (word & (_LANES - 1))[..., None]
        chunk = word >> 7
        # every chunk's gather first, then the selects: the gathers are
        # independent and pipeline, a select after each would wait on it
        got = [jax.lax.gather(t, lane, _LANE_GATHER, (1, 1),
                              mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)
               for t in tiles]
        w = got[0]
        for c in range(1, len(got)):
            w = jax.lax.select(jax.lax.eq(chunk, np.int32(c)), got[c], w)
        hit = hit & (((w >> (p & np.uint32(31))) & np.uint32(1)) == 1)
    return hit


def _decode_kernel(prefix_ref, h1_ref, ready_ref, weights_ref, logits_ref,
                   probe_refs, out_logits_ref, banned_ref, canary_ref,
                   *scratch, spec: DecodeSpec, V: int, block_v: int):
    j = pl.program_id(1)
    session_ref = probe_refs.get("session")
    canary_bits_ref = probe_refs.get("canary")
    if scratch:
        (hits_scr,) = scratch
        rot = _kref._rotl_const(prefix_ref[...], 1, spec.L)      # (bb, 1)

        def slice_hits(s, carry):
            off = pl.multiple_of(s * _LANES, _LANES)
            # the candidate hashes of 128 columns, Theorem-2 discard applied
            h = (rot ^ h1_ref[:, pl.ds(off, _LANES)]) & np.uint32(
                spec.hash_mask)
            hits = jnp.zeros(h.shape, jnp.int32)
            if session_ref is not None:
                hits = hits | _vmem_probe_hits(
                    h, spec.k, spec.log2_m, list(session_ref[...])
                ).astype(jnp.int32)
            if canary_bits_ref is not None:
                # the shared filter: each row on every sublane
                rows = canary_bits_ref[...]
                hits = hits | (_vmem_probe_hits(
                    h, spec.canary_k, spec.canary_log2_m,
                    [jax.lax.broadcast_in_dim(
                        jax.lax.slice_in_dim(rows, c, c + 1), h.shape, (0, 1))
                     for c in range(rows.shape[0])]
                ).astype(jnp.int32) << 1)
            hits_scr[:, pl.ds(off, _LANES)] = hits
            return carry

        jax.lax.fori_loop(0, block_v // _LANES, slice_hits, 0)
        hits = hits_scr[...]
        if "xla" in probe_refs:
            hits = hits | probe_refs["xla"][...]
    else:
        hits = probe_refs["xla"][...]
    # candidates beyond the true vocab are padding: never banned, never hits
    col = j * block_v + jax.lax.broadcasted_iota(jnp.int32, hits.shape, 1)
    live = (col < V) & (ready_ref[...] != 0)                 # (bb, bv)
    banned = ((hits & 1) != 0) & live
    out_logits_ref[...] = jnp.where(banned, _kref.NEG_LOGIT, logits_ref[...])
    weights = weights_ref[...]
    banned_ref[...] = _pack_tile(banned, weights)
    if canary_ref is not None:
        canary_ref[...] = _pack_tile(((hits & 2) != 0) & live, weights)


@functools.partial(jax.jit, static_argnames=("spec", "block_b", "block_v",
                                             "interpret"))
def decode_masks_fused(logits, prefix, ready, bloom, h1, *,
                       spec: DecodeSpec, canary_bits=None, block_b: int = 8,
                       block_v: int = None, interpret: bool = False) -> dict:
    """Candidate hashing + Bloom probing + logit masking: one jit graph,
    one kernel pass over the logits.

    logits (B, V) f32, prefix (B,) uint32, ready (B,) bool/int, bloom
    (B, 2^log2_m/32) uint32 per-session filters, h1 (V,) uint32 (pre-masked
    to L bits by ``api.decode``), canary_bits (2^canary_log2_m/32,) uint32
    shared filter iff ``spec.has_canary`` -> ``{"logits", "banned"[,
    "canary"]}`` exactly as :func:`repro.kernels.ref.decode_masks_ref`.
    """
    B, V = logits.shape
    if block_v is None:
        block_v = min(4096, max(_LANES, 1 << int(np.ceil(np.log2(max(V, 1))))))
    if block_v % _LANES:
        raise ValueError(f"block_v must be a multiple of {_LANES} (one lane "
                         f"gather per 128 candidates), got {block_v}")
    Bp = -(-B // block_b) * block_b
    Vp = -(-V // block_v) * block_v
    pad = ((0, Bp - B), (0, Vp - V))
    tile = pl.BlockSpec((block_b, block_v), lambda bi, j: (bi, j),
                        memory_space=pltpu.VMEM)
    row = pl.BlockSpec((block_b, 1), lambda bi, j: (bi, 0),
                       memory_space=pltpu.VMEM)

    session_vmem = probes_in_vmem(spec.n_words)
    canary_vmem = spec.has_canary and probes_in_vmem(spec.canary_words)
    operands, specs = {}, {}
    if session_vmem:
        # chunk-major: chunk c of every row of a block is one (block_b, 128)
        # tile; the lanes past the filter's words are never indexed
        nc = _chunk_count(spec.n_words)
        words = jnp.pad(bloom.astype(_U32),
                        ((0, Bp - B), (0, nc * _LANES - spec.n_words)))
        operands["session"] = words.reshape(Bp, nc, _LANES).transpose(1, 0, 2)
        specs["session"] = pl.BlockSpec((nc, block_b, _LANES),
                                        lambda bi, j: (0, bi, 0),
                                        memory_space=pltpu.VMEM)
    if canary_vmem:
        nc = _chunk_count(spec.canary_words)
        operands["canary"] = jnp.pad(
            canary_bits.astype(_U32),
            (0, nc * _LANES - spec.canary_words)).reshape(nc, _LANES)
        specs["canary"] = pl.BlockSpec((nc, _LANES), lambda bi, j: (0, 0),
                                       memory_space=pltpu.VMEM)
    if not session_vmem or (spec.has_canary and not canary_vmem):
        # a filter past the VMEM bound: XLA gathers ahead of the kernel,
        # its verdict a per-candidate hit word (bit 0 no-repeat, bit 1
        # canary)
        cand = (_kref._rotl_const(prefix.astype(_U32), 1, spec.L)[:, None]
                ^ h1[None, :])
        h = cand & np.uint32(spec.hash_mask)
        hits = jnp.zeros(h.shape, jnp.int32)
        if not session_vmem:
            hits = hits | _kref.bloom_probe_hits(
                h, bloom.astype(_U32), spec.k, spec.log2_m).astype(jnp.int32)
        if spec.has_canary and not canary_vmem:
            hits = hits | (_kref.bloom_probe_hits(
                h, canary_bits.astype(_U32), spec.canary_k,
                spec.canary_log2_m).astype(jnp.int32) << 1)
        operands["xla"] = jnp.pad(hits, pad)
        specs["xla"] = tile

    lg = jnp.pad(logits.astype(jnp.float32), pad)
    pf = jnp.pad(prefix.astype(_U32), (0, Bp - B))[:, None]
    hv = jnp.pad(h1.astype(_U32), (0, Vp - V))[None, :]
    rd = jnp.pad(ready.astype(jnp.int32), (0, Bp - B))[:, None]
    weights = _pack_weights(block_v)
    wspec = pl.BlockSpec(weights.shape, lambda bi, j: (0, 0),
                         memory_space=pltpu.VMEM)
    h1spec = pl.BlockSpec((1, block_v), lambda bi, j: (0, j),
                          memory_space=pltpu.VMEM)
    ptile = pl.BlockSpec((block_b, block_v // _WORD), lambda bi, j: (bi, j),
                         memory_space=pltpu.VMEM)
    packed = jax.ShapeDtypeStruct((Bp, Vp // _WORD), _U32)
    scratch = ([pltpu.VMEM((block_b, block_v), jnp.int32)]
               if session_vmem or canary_vmem else [])

    outs = pl.pallas_call(
        functools.partial(_decode_kernel, spec=spec, V=V, block_v=block_v),
        name="decode_masks",
        grid=(Bp // block_b, Vp // block_v),
        in_specs=[row, h1spec, row, wspec, tile, specs],
        out_specs=(tile, ptile, ptile if spec.has_canary else None),
        out_shape=(jax.ShapeDtypeStruct((Bp, Vp), jnp.float32), packed,
                   packed if spec.has_canary else None),
        scratch_shapes=scratch,
        interpret=interpret,
    )(pf, hv, rd, weights, lg, operands)

    W = -(-V // _WORD)
    results = {"logits": outs[0][:B, :V], "banned": outs[1][:B, :W]}
    if spec.has_canary:
        results["canary"] = outs[2][:B, :W]
    return results
