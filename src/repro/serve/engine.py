"""Batched serving engine: prefill + decode with hash-based no-repeat-ngram.

`no_repeat_ngram` is the paper's rolling hash at serving time: per sequence
we keep a tiny Bloom filter of the n-grams generated so far. At each step the
*recursive* structure of CYCLIC gives the hash of every candidate
continuation in O(vocab) bitwise ops — h_cand = rotl(h_prefix, 1) XOR
h1[v] for all v simultaneously — so banning repeats costs one rotate, one
XOR-broadcast and one Bloom probe per candidate, not a re-hash of the window.
(Bloom false positives over-ban slightly; rate is set by log2_m/bloom_k.)

Two implementations of that epilogue live here:

* the **fused plane** (default, ``ngram_plane="auto"``): a
  :class:`~repro.serve.sessions.SessionPool` runs hash + probe + mask +
  sample + state-advance as ONE device dispatch per decode step, with the
  per-session carry donated in place, optional row-wise sharding over the
  data mesh, and on-device telemetry (no per-step host syncs);
* the **legacy path** (``ngram_plane="legacy"``): the original readable
  per-step jnp chain, kept as the bit-level oracle for the fused plane —
  its probe derivation is literally ``ref.bloom_probe_hits``, the same
  helper the fused kernel's oracle uses, and its ``banned``/``update``
  pair is jitted once (no per-step retracing, no per-step h1 re-lookup).

Both apply the paper's Theorem-2 discard: a CYCLIC window hash has only
``L - n + 1`` pairwise-independent consecutive bits, so Bloom probes (adds
AND lookups) derive from ``h & spec.hash_mask``, never from the n-1
dependent high bits. ``n > L`` is accepted but warns: rotations alias mod L
(the recursion stays exact — the expiry term is ``rotl(h1[oldest],
(n-1) mod L)`` because rotl is L-periodic — but the pairwise FP guarantee
is gone; see ``DecodeSpec.degraded``).
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import families, gf2, make_family
from repro.kernels import ref as _kref
from repro.kernels.plan import DecodeSpec
from repro.nn import lm
from repro.serve import telemetry
from repro.serve.sessions import SessionPool, _bloom_add_rows

_PLANES = ("auto", "fused", "legacy")


@dataclasses.dataclass(frozen=True)
class SamplerConfig:
    temperature: float = 1.0
    top_k: int = 0                   # 0 = full softmax
    no_repeat_ngram: int = 0         # 0 = disabled
    bloom_log2_m: int = 14
    bloom_k: int = 2                 # double-hashed probes per candidate
    hash_bits: int = 32              # CYCLIC hash width L
    ngram_plane: str = "auto"        # auto | fused | legacy
    canary_log2_m: int = 0           # decontam canary filter (fused plane)
    canary_k: int = 4
    seed: int = 0


@functools.partial(jax.jit, static_argnames=("spec",))
def _legacy_banned(spec: DecodeSpec, state, h1):
    """(B, V) bool: would token v complete an already-seen n-gram?

    Probing is ``ref.bloom_probe_hits`` — the exact helper behind the fused
    kernel's oracle — on Theorem-2-masked candidate hashes.
    """
    cand = gf2.rotl(state["prefix_hash"], 1, spec.L)[:, None] ^ h1[None, :]
    hits = _kref.bloom_probe_hits(cand & np.uint32(spec.hash_mask),
                                  state["bloom"], spec.k, spec.log2_m)
    ready = state["count"] >= (spec.n - 1)
    return hits & ready[:, None]


@functools.partial(jax.jit, static_argnames=("spec",))
def _legacy_update(spec: DecodeSpec, state, h1, token):
    """Advance the rolling window with the sampled token (B,)."""
    h1v = h1[token]
    new_hash = gf2.rotl(state["prefix_hash"], 1, spec.L) ^ h1v
    count = state["count"] + 1
    # when the window is full, `new_hash` is a complete n-gram hash:
    # record it (discarded to the pairwise-independent bits, matching the
    # probe side), then expire the oldest symbol from the rolling prefix.
    full = count >= spec.n
    bloom = jnp.where(
        full[:, None],
        _bloom_add_rows(state["bloom"], new_hash & np.uint32(spec.hash_mask),
                        spec.k, spec.log2_m),
        state["bloom"])
    # expire the oldest symbol once the window is full (recursive update);
    # the rotation amount is (n-1) mod L — mod the hash width, not a
    # hard-coded 32 — exact for every n because rotl is L-periodic
    oldest = state["window"][:, 0]
    expired = new_hash ^ gf2.rotl(oldest, (spec.n - 1) % spec.L, spec.L)
    prefix = jnp.where(full, expired, new_hash)
    window = jnp.concatenate([state["window"][:, 1:], h1v[:, None]], axis=1)
    return {"prefix_hash": prefix, "window": window, "bloom": bloom,
            "count": count}


class NoRepeatNgram:
    """Per-sequence Bloom state over generated n-gram fingerprints.

    The readable per-step implementation — and the bit-level oracle the
    fused decode plane (:mod:`repro.serve.sessions`) is tested against.
    The ``banned``/``update`` pair is jitted once at module level (keyed on
    the static :class:`DecodeSpec`), and the h1 table is hoisted to an
    attribute: nothing is re-traced or re-fetched per decode step.
    """

    def __init__(self, cfg: ModelConfig, scfg: SamplerConfig):
        self.n = scfg.no_repeat_ngram
        # DecodeSpec centralizes validation (n >= 2, L in [1,32], filter
        # geometry) and the Theorem-2 discard mask; n > L is the degraded
        # regime — legal, exact on true repeats, no pairwise FP bound
        self.spec = DecodeSpec(n=self.n, L=scfg.hash_bits,
                               log2_m=scfg.bloom_log2_m, k=scfg.bloom_k)
        key = jax.random.PRNGKey(scfg.seed + 99)
        if self.spec.degraded:
            warnings.warn(
                f"no_repeat_ngram n={self.n} exceeds the hash width "
                f"L={self.spec.L}: rotations alias mod L, so the pairwise-"
                f"independence FP bound is void (banning stays exact on "
                f"true repeats). Prefer n <= L.", UserWarning, stacklevel=2)
            # the family constructor enforces the paper's L >= n (Table 1);
            # the lifted serving regime only needs the symbol table, which
            # is family-independent — same draw, no gate
            self.fam = None
            self.params = {"h1": families.init_h1(key, lm.padded_vocab(cfg))}
        else:
            self.fam = make_family("cyclic", n=self.n, L=self.spec.L)
            self.params = self.fam.init(key, lm.padded_vocab(cfg))
        self.m = self.spec.m
        h1 = jnp.asarray(self.params["h1"], jnp.uint32)
        if self.spec.L < 32:
            h1 = h1 & np.uint32((1 << self.spec.L) - 1)
        self.h1 = h1

    def init_state(self, batch: int) -> Dict[str, jnp.ndarray]:
        return {
            # rolling hash of the last n-1 tokens, advanced recursively
            "prefix_hash": jnp.zeros((batch,), jnp.uint32),
            # h1 values of the last n-1 tokens (to expire the oldest term)
            "window": jnp.zeros((batch, self.n - 1), jnp.uint32),
            "bloom": jnp.zeros((batch, self.spec.n_words), jnp.uint32),
            "count": jnp.zeros((batch,), jnp.int32),
        }

    def banned(self, state) -> jnp.ndarray:
        """(B, V) bool: would token v complete an already-seen n-gram?"""
        return _legacy_banned(self.spec, state, self.h1)

    def update(self, state, token: jnp.ndarray) -> Dict[str, jnp.ndarray]:
        """Advance the rolling window with the sampled token (B,)."""
        return _legacy_update(self.spec, state, self.h1, token)


class ServeEngine:
    """Prefill + decode with the decode-time n-gram plane.

    ``scfg.ngram_plane`` picks the epilogue: ``"auto"``/``"fused"`` run the
    one-dispatch :class:`SessionPool` step (sharded over ``data_shards``
    when given); ``"legacy"`` runs the original jnp chain. Greedy
    (temperature=0) outputs are identical between the planes; sampled runs
    draw from the same masked distribution but use per-session PRNG streams
    on the fused plane (device-count invariant) vs one batch stream on the
    legacy path.
    """

    def __init__(self, cfg: ModelConfig, params,
                 scfg: SamplerConfig = SamplerConfig(), *,
                 canary_bits=None, impl: str = "auto",
                 mesh=None, data_shards: Optional[int] = None):
        if scfg.ngram_plane not in _PLANES:
            raise ValueError(f"ngram_plane must be one of {_PLANES}, got "
                             f"{scfg.ngram_plane!r}")
        self.cfg, self.params, self.scfg = cfg, params, scfg
        self.plane = ("fused" if scfg.ngram_plane == "auto"
                      else scfg.ngram_plane)
        self.impl, self.mesh, self.data_shards = impl, mesh, data_shards
        self.nrn = (NoRepeatNgram(cfg, scfg)
                    if scfg.no_repeat_ngram >= 2 else None)
        self.decode_spec = None
        self.canary_bits = None
        if self.nrn is not None and self.plane == "fused":
            self.decode_spec = dataclasses.replace(
                self.nrn.spec, canary_log2_m=scfg.canary_log2_m,
                canary_k=scfg.canary_k)
            if self.decode_spec.has_canary:
                if canary_bits is None:
                    raise ValueError("canary_log2_m set: pass canary_bits")
                self.canary_bits = jnp.asarray(canary_bits, jnp.uint32)
        elif canary_bits is not None:
            raise ValueError("canary_bits needs no_repeat_ngram >= 2 and "
                             "the fused plane (plus canary_log2_m)")
        self._decode = jax.jit(functools.partial(lm.decode_step, cfg=cfg))

    def _make_pool(self, batch: int) -> Tuple[SessionPool, int]:
        """A fresh pool sized for this generate() call: capacity is the
        batch rounded up to the mesh shard count (pad rows stay inactive).
        With a canary, each call thus builds the filter's membership table
        once (about 35 ms at 2^20 bits on a TPU v5e), ahead of its steps and
        outside them."""
        mesh = self.mesh
        if mesh is None and self.data_shards is not None:
            from repro.kernels import shard
            mesh = shard.data_mesh(self.data_shards)
        d = mesh.devices.size if mesh is not None else 1
        C = -(-batch // d) * d
        pool = SessionPool(self.decode_spec, C, self.nrn.h1,
                           canary_bits=self.canary_bits, impl=self.impl,
                           mesh=mesh)
        pool.admit(batch)
        return pool, C

    def generate(self, prompts: jnp.ndarray, max_new_tokens: int,
                 prefix_embeds=None) -> Tuple[np.ndarray, Dict]:
        cfg, scfg = self.cfg, self.scfg
        B, P = prompts.shape
        pfx = cfg.prefix_len if prefix_embeds is not None else 0
        max_len = P + pfx + max_new_tokens
        last_logits, caches = lm.prefill(self.params, cfg, prompts, max_len,
                                         prefix_embeds)
        key = jax.random.PRNGKey(scfg.seed)
        if self.nrn is not None and self.plane == "fused":
            return self._generate_fused(prompts, max_new_tokens, last_logits,
                                        caches, key)
        nrn_state = None
        if self.nrn is not None:
            nrn_state = self.nrn.init_state(B)
            for t in range(P):   # charge the filter with the prompt
                nrn_state = self.nrn.update(nrn_state, prompts[:, t])
        out = []
        banned_count = 0
        logits = last_logits
        for step in range(max_new_tokens):
            logits = lm.mask_pad_logits(cfg, logits.astype(jnp.float32))
            if self.nrn is not None:
                banned = self.nrn.banned(nrn_state)
                banned = banned[:, : logits.shape[-1]]
                banned_count += int(banned.sum())
                logits = jnp.where(banned, -1e30, logits)
            if scfg.top_k:
                kth = jax.lax.top_k(logits, scfg.top_k)[0][:, -1:]
                logits = jnp.where(logits < kth, -1e30, logits)
            if scfg.temperature == 0.0:
                token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            else:
                key, sub = jax.random.split(key)
                token = jax.random.categorical(
                    sub, logits / scfg.temperature, axis=-1).astype(jnp.int32)
            out.append(token)
            if self.nrn is not None:
                nrn_state = self.nrn.update(nrn_state, token)
            logits, caches = self._decode(params=self.params,
                                          token=token[:, None], caches=caches)
        tokens = jnp.stack(out, axis=1)
        return np.asarray(tokens), {"banned_candidates": banned_count}

    def _generate_fused(self, prompts, max_new_tokens, last_logits, caches,
                        key):
        """The decode loop on the fused plane: per step, ONE pool dispatch
        (mask + sample + state advance, telemetry accumulated on device)
        plus the model's own decode step — no per-step host syncs."""
        cfg, scfg = self.cfg, self.scfg
        B, P = prompts.shape
        pool, C = self._make_pool(B)
        toks = jnp.zeros((C, P), jnp.int32).at[:B].set(prompts)
        lens = jnp.zeros((C,), jnp.int32).at[:B].set(P)
        pool.prime(toks, lens)     # charge the filters with the prompt
        out = []
        logits = last_logits
        for step in range(max_new_tokens):
            logits = lm.mask_pad_logits(cfg, logits.astype(jnp.float32))
            if C > B:              # inactive pad rows (mesh divisibility)
                logits = jnp.pad(logits, ((0, C - B), (0, 0)))
            token = pool.step(logits, key=key,
                              temperature=scfg.temperature,
                              top_k=scfg.top_k)[:B]
            out.append(token)
            logits, caches = self._decode(params=self.params,
                                          token=token[:, None], caches=caches)
        tokens = jnp.stack(out, axis=1)
        snap = telemetry.snapshot(pool)
        # prompt charging advances no decode step, so rates cover exactly
        # the generated tokens
        return np.asarray(tokens), {
            "banned_candidates": snap["banned_candidates"],
            "telemetry": snap}
