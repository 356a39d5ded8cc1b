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

The probes are gathers into the filters, and Mosaic has no gather from a
VMEM array, so the candidate hashing and the probes run in XLA ahead of the
kernel, inside the same jit graph (the plan kernel treats Bloom sketches
the same way). Their verdict reaches the kernel as a per-candidate hit
word (bit 0 no-repeat filter, bit 1 canary). The kernel does the
logits-sized pass: validity (vocabulary padding, session readiness), the
substitution, and the 32:1 packing. Mosaic cannot split the lane axis into
words, so the packing is a matmul on the MXU: the 0/1 mask against a
constant matrix of powers of two gives the low and high 16 bits of every
word, exactly (0/1 products, integer sums below 2^24).

Grid/tiling: ``(B/block_b, V/block_v)``; every tile is independent (no
cross-step scratch — the plane is embarrassingly parallel over sessions AND
candidates). ``block_v`` defaults to 4096, so a tile's packed words fill
128 lanes.

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


def _decode_kernel(logits_ref, hits_ref, ready_ref, weights_ref,
                   out_logits_ref, banned_ref, canary_ref=None, *, V: int,
                   block_v: int):
    j = pl.program_id(1)
    hits = hits_ref[...]
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
        block_v = min(4096, max(_WORD, 1 << int(np.ceil(np.log2(max(V, 1))))))
    if block_v % _WORD:
        raise ValueError(f"block_v must be a multiple of 32 (packed-mask "
                         f"words), got {block_v}")
    Bp = -(-B // block_b) * block_b
    Vp = -(-V // block_v) * block_v

    # the probes (XLA gathers): candidate hashes, Theorem-2 discard, k
    # double-hashed probes per filter -> one hit word per candidate
    cand = _kref._rotl_const(prefix.astype(_U32), 1, spec.L)[:, None] ^ h1[None, :]
    h = cand & np.uint32(spec.hash_mask)
    with jax.named_scope("decode.probe.session"):
        hits = _kref.bloom_probe_hits(h, bloom.astype(_U32), spec.k,
                                      spec.log2_m).astype(jnp.int32)
    if spec.has_canary:
        assert canary_bits is not None
        with jax.named_scope("decode.probe.canary"):
            hits = hits | (_kref.bloom_probe_hits(
                h, canary_bits.astype(_U32), spec.canary_k,
                spec.canary_log2_m).astype(jnp.int32) << 1)

    pad = ((0, Bp - B), (0, Vp - V))
    lg = jnp.pad(logits.astype(jnp.float32), pad)
    hw = jnp.pad(hits, pad)
    rd = jnp.pad(ready.astype(jnp.int32), (0, Bp - B))[:, None]
    weights = _pack_weights(block_v)

    tile = pl.BlockSpec((block_b, block_v), lambda bi, j: (bi, j),
                        memory_space=pltpu.VMEM)
    row = pl.BlockSpec((block_b, 1), lambda bi, j: (bi, 0),
                       memory_space=pltpu.VMEM)
    wspec = pl.BlockSpec(weights.shape, lambda bi, j: (0, 0),
                         memory_space=pltpu.VMEM)
    ptile = pl.BlockSpec((block_b, block_v // _WORD), lambda bi, j: (bi, j),
                         memory_space=pltpu.VMEM)
    packed = jax.ShapeDtypeStruct((Bp, Vp // _WORD), _U32)
    n_masks = 2 if spec.has_canary else 1

    outs = pl.pallas_call(
        functools.partial(_decode_kernel, V=V, block_v=block_v),
        name="decode_masks",
        grid=(Bp // block_b, Vp // block_v),
        in_specs=[tile, tile, row, wspec],
        out_specs=(tile,) + (ptile,) * n_masks,
        out_shape=(jax.ShapeDtypeStruct((Bp, Vp), jnp.float32),)
        + (packed,) * n_masks,
        interpret=interpret,
    )(lg, hw, rd, weights)

    W = -(-V // _WORD)
    results = {"logits": outs[0][:B, :V], "banned": outs[1][:B, :W]}
    if spec.has_canary:
        results["canary"] = outs[2][:B, :W]
    return results
