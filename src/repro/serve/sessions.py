"""Fixed-capacity session pool: decode-plane state as a donated carry.

Serving at scale means thousands of concurrent sequences, each carrying the
tiny per-session sketch state the paper's recursive CYCLIC family needs at
decode time:

* ``prefix`` — the rolling hash of the last n-1 sampled tokens,
* ``ring``   — the h1 values of those tokens (to expire the oldest term
  recursively: ``prefix' = (rotl(prefix,1) ^ h1[new]) ^ rotl(h1[old],
  (n-1) mod L)``),
* ``bloom``  — the packed no-repeat Bloom filter of n-grams generated so
  far,

plus saturating warm-up counters and telemetry accumulators. The pool holds
this state for a fixed ``capacity`` of session slots as ONE carry pytree of
(C, ...) arrays, exactly like the streaming executor's sketch carry
(``kernels/stream.py``): every decode step is one jitted call that fuses
the decode epilogue (:func:`repro.kernels.api.decode`), top-k/temperature
sampling and the state advance, with the carry **donated** back into place
on backends that support it.

Churn never retraces: ``admit``/``evict``/``reset`` are fixed-shape masked
updates over the same (C, ...) arrays — admitting session 17 and evicting
session 3 runs the same compiled program as any other churn set, and the
decode step's trace is keyed only on (spec, mesh, sampler statics, shapes),
which churn does not touch. The never-retrace property is asserted in
``tests/test_serve_plane.py`` via the jit cache size, mirroring the
streaming executor's regression tests.

Scale-out is :func:`repro.kernels.shard.rowwise`: the carry and the logits
are pure row state, so the whole fused step shards over the 1-D data mesh
with ZERO collectives (jaxpr-asserted) — ``capacity`` must divide the shard
count, which the constructor enforces. Sampling stays bit-identical at any
device count because the per-row PRNG keys are derived (fold_in by slot
index) before the shard region.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.analysis.contracts import kernel_contract
from repro.core import gf2
from repro.kernels import api, shard
from repro.kernels import ref as _kref
from repro.kernels.decode import all_probes_in_vmem
from repro.kernels.plan import DecodeSpec
from repro.kernels.stream import _resolve_donate

_U32 = jnp.uint32

# device dispatches issued by this module (one jitted call = one XLA
# execution): decode steps, prompt primes and churn ops all count, as
# ``sessions.dispatches`` in the program's recorder (context-local, the
# same contract as kernels.stream.dispatch_count), so the
# one-dispatch-per-decode-step property is assertable against it; decode
# steps whose kernel probes every filter from VMEM also count as
# ``sessions.vmem_probe_steps``, and each build of a canary filter's
# membership table as ``sessions.canary_table_builds`` (one per pool
# construction or import, none per step)


def dispatch_count() -> int:
    """Session-pool device dispatches issued in this context."""
    return obs.counter("sessions.dispatches")


def init_state(spec: DecodeSpec, capacity: int) -> Dict[str, jnp.ndarray]:
    """The pool's carry pytree: every leaf is (C, ...) row state."""
    C = capacity
    return {
        "prefix": jnp.zeros((C,), _U32),
        "ring": jnp.zeros((C, spec.n - 1), _U32),
        "pos": jnp.zeros((C,), jnp.int32),
        "bloom": jnp.zeros((C, spec.n_words), _U32),
        # symbols consumed, saturating at n (only >= n-1 / >= n are read,
        # so saturation keeps the state bounded on unbounded streams)
        "count": jnp.zeros((C,), jnp.int32),
        "active": jnp.zeros((C,), jnp.int32),
        # decode steps taken and banned/canary candidate totals as uint32
        # (lo, hi) pairs with explicit carry — the stats-plane idiom; a
        # 128k-vocab session wraps a lone uint32 banned counter in ~9 hours
        "steps": jnp.zeros((C,), _U32),
        "banned_lo": jnp.zeros((C,), _U32),
        "banned_hi": jnp.zeros((C,), _U32),
        "canary_lo": jnp.zeros((C,), _U32),
        "canary_hi": jnp.zeros((C,), _U32),
    }


def _bloom_add_rows(words, h, k: int, log2_m: int):
    """Set the k probe bits of one masked hash per row: (C, m/32) | h (C,).

    Probe derivation is identical to ``ref.bloom_probe_hits`` — double
    hashing with the odd stride — so membership is exact for inserted keys.
    """
    stride = (h * _kref.BLOOM_STRIDE) | np.uint32(1)
    m_mask = np.uint32((1 << log2_m) - 1)
    W = words.shape[-1]
    lanes = jnp.arange(W, dtype=jnp.int32)[None, :]
    out = words
    for i in range(k):
        probe = (h + np.uint32(i) * stride) & m_mask
        word = (probe >> np.uint32(5)).astype(jnp.int32)
        bit = (probe & np.uint32(31)).astype(_U32)
        onehot = lanes == word[:, None]
        out = out | jnp.where(onehot, np.uint32(1) << bit[:, None],
                              np.uint32(0))
    return out


def _advance_rows(spec: DecodeSpec, state: Dict, h1v, live) -> Dict:
    """Consume one symbol per live row: roll the prefix, record the
    completed n-gram in the Bloom filter, expire the oldest term.

    ``h1v`` (C,) uint32 must already be masked to L bits; ``live`` (C,)
    bool gates which rows consume (inactive slots and ragged prompt tails
    pass through untouched). The expiry rotation is ``(n-1) mod L`` — mod
    the *hash width*, not a hard-coded 32 — which is exact for every n
    because rotl is L-periodic (the n > L regime degrades the pairwise
    guarantee, never the recursion; see ``DecodeSpec.degraded``).
    """
    n, L = spec.n, spec.L
    new_hash = gf2.rotl(state["prefix"], 1, L) ^ h1v
    count1 = jnp.minimum(state["count"] + 1, n)
    full = count1 >= n
    # a full window means new_hash is a complete n-gram hash: record it
    # (Theorem-2 discard applied — the filter only ever sees masked bits,
    # matching the probe side of the fused kernel bit-for-bit)
    add = full & live
    bloom = jnp.where(
        add[:, None],
        _bloom_add_rows(state["bloom"], new_hash & np.uint32(spec.hash_mask),
                        spec.k, spec.log2_m),
        state["bloom"])
    # expire the oldest symbol from the rolling prefix (recursive update)
    oldest = jnp.take_along_axis(state["ring"], state["pos"][:, None],
                                 axis=1)[:, 0]
    expired = new_hash ^ gf2.rotl(oldest, (n - 1) % L, L)
    prefix1 = jnp.where(full, expired, new_hash)
    slot = jnp.arange(n - 1, dtype=jnp.int32)[None, :] == state["pos"][:, None]
    ring1 = jnp.where(slot & live[:, None], h1v[:, None], state["ring"])
    out = dict(state)
    out["prefix"] = jnp.where(live, prefix1, state["prefix"])
    out["ring"] = ring1
    out["pos"] = jnp.where(live, (state["pos"] + 1) % (n - 1), state["pos"])
    out["bloom"] = bloom
    out["count"] = jnp.where(live, count1, state["count"])
    return out


def _accum_u64(lo, hi, inc):
    """(lo, hi) uint32 pair += inc, with carry (the stats-plane idiom)."""
    lo1 = lo + inc
    return lo1, hi + (lo1 < lo).astype(_U32)


def _popcount_rows(packed):
    """(C, W) uint32 packed mask -> (C,) uint32 set-bit count."""
    return jnp.sum(jax.lax.population_count(packed), axis=-1,
                   dtype=jnp.uint32)


def _step_core(spec: DecodeSpec, ref_path: bool, tile, temperature: float,
               top_k: int, state, logits, keys, h1, canary_table):
    """The whole decode step, purely per-row: fused epilogue -> sample ->
    advance -> telemetry. ``canary_table`` is the canary filter's
    membership table, probed once (``spec.canary_k == 1``). Traceable;
    embedded either directly in the jitted step or inside its shard_map
    region."""
    live = state["active"] != 0
    ready = (state["count"] >= spec.n - 1) & live
    out = api.decode(spec, logits, state["prefix"], ready, state["bloom"],
                     h1, canary_bits=canary_table,
                     impl="ref" if ref_path else "pallas", **dict(tile))
    masked = out["logits"]
    with jax.named_scope("decode.sample"):
        if top_k:
            kth = jax.lax.top_k(masked, top_k)[0][:, -1:]
            masked = jnp.where(masked < kth, _kref.NEG_LOGIT, masked)
        if temperature == 0.0:
            token = jnp.argmax(masked, axis=-1).astype(jnp.int32)
        else:
            # per-row categorical with per-row keys: the sample a session
            # draws depends only on its own slot, never on batch layout or
            # mesh size
            token = jax.vmap(
                lambda k, l: jax.random.categorical(k, l / temperature)
            )(keys, masked).astype(jnp.int32)
    with jax.named_scope("decode.advance"):
        new_state = _advance_rows(spec, state, h1[token], live)
        inc = jnp.where(live, _popcount_rows(out["banned"]), np.uint32(0))
        (new_state["banned_lo"],
         new_state["banned_hi"]) = _accum_u64(state["banned_lo"],
                                              state["banned_hi"], inc)
        if spec.has_canary:
            cinc = jnp.where(live, _popcount_rows(out["canary"]),
                             np.uint32(0))
            (new_state["canary_lo"],
             new_state["canary_hi"]) = _accum_u64(state["canary_lo"],
                                                  state["canary_hi"], cinc)
        new_state["steps"] = state["steps"] + live.astype(_U32)
    return token, new_state


def _step_body(spec, ref_path, mesh, tile, temperature, top_k,
               state, logits, h1, canary_table, key, t):
    """One decode step = ONE device dispatch. Per-row keys are derived from
    (key, step, slot) BEFORE the shard region so sampling is bit-identical
    at any device count; under a mesh the entire core runs shard_map'd
    row-wise with zero collectives."""
    C = logits.shape[0]
    base = jax.random.fold_in(key, t)
    keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
        base, jnp.arange(C, dtype=jnp.int32))
    core = functools.partial(_step_core, spec, ref_path, tile, temperature,
                             top_k)
    if mesh is None:
        return core(state, logits, keys, h1, canary_table)
    return shard.rowwise(core, mesh, n_row=3)(state, logits, keys, h1,
                                              canary_table)


# donation twins (the stream.py idiom): the carry (arg 6) is donated in
# steady state so the pool's buffers are reused in place; both expose
# _cache_size() for the never-retrace regression tests
_step_plain = jax.jit(_step_body, static_argnums=(0, 1, 2, 3, 4, 5))
_step_donated = jax.jit(_step_body, static_argnums=(0, 1, 2, 3, 4, 5),
                        donate_argnums=(6,))


def _prime_core(spec: DecodeSpec, T: int, state, tokens, lengths, h1):
    """Charge prompt symbols into the carry: lax.scan over the T prompt
    positions, each a masked `_advance_rows` (rows past their own length
    idle). One dispatch for the whole prompt, any raggedness."""

    def body(st, xs):
        tok, t = xs
        live = (st["active"] != 0) & (t < lengths)
        return _advance_rows(spec, st, h1[tok], live), ()

    xs = (tokens.T, jnp.arange(T, dtype=jnp.int32))
    state, _ = jax.lax.scan(body, state, xs)
    return state


def _prime_body(spec, mesh, T, state, tokens, lengths, h1):
    core = functools.partial(_prime_core, spec, T)
    if mesh is None:
        return core(state, tokens, lengths, h1)
    return shard.rowwise(core, mesh, n_row=3)(state, tokens, lengths, h1)


_prime_plain = jax.jit(_prime_body, static_argnums=(0, 1, 2))
_prime_donated = jax.jit(_prime_body, static_argnums=(0, 1, 2),
                         donate_argnums=(3,))


def _churn_body(op: str, state, mask):
    """Fixed-shape masked churn: the SAME compiled program serves any
    admit/evict/reset set, so session turnover never retraces."""
    if op == "evict":
        out = dict(state)
        out["active"] = jnp.where(mask, 0, state["active"])
        return out
    # "reset": zero every leaf for the masked rows, then (re)activate
    out = {k: jnp.where(mask.reshape((-1,) + (1,) * (v.ndim - 1)),
                        jnp.zeros_like(v), v)
           for k, v in state.items()}
    out["active"] = jnp.where(mask, 1, out["active"])
    return out


_churn = jax.jit(_churn_body, static_argnums=(0,))


class SessionPool:
    """Fixed-capacity pool of decode-plane sessions.

    Args:
      spec: static :class:`~repro.kernels.plan.DecodeSpec`.
      capacity: number of session slots C (must divide the mesh shard
        count when a mesh is given — the carry is row-sharded unpadded).
      h1: (V,) uint32 symbol hash table (one family draw); masked to L
        bits once here, so the recursion and the kernel agree bit-for-bit.
      canary_bits: shared decontam canary filter iff ``spec.has_canary``.
        The pool builds its membership table
        (:func:`repro.kernels.ref.bloom_membership_table`) once, here and
        in :meth:`import_state`, and every step answers a candidate with
        one probe of the table (``step_spec``, ``canary_k=1``) where the
        filter takes ``canary_k``: the same answers.
      impl / donate / mesh / data_shards / tile_kw: the engine-wide knobs,
        same contract as the streaming executor.
    """

    def __init__(self, spec: DecodeSpec, capacity: int, h1, *,
                 canary_bits=None, impl: str = "auto", donate="auto",
                 mesh=None, data_shards: Optional[int] = None, **tile_kw):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.spec = spec
        self.capacity = int(capacity)
        if mesh is None and data_shards is not None:
            mesh = shard.data_mesh(data_shards)
        if mesh is not None:
            d = mesh.devices.size
            if self.capacity % d:
                raise ValueError(
                    f"capacity={capacity} must divide the data mesh "
                    f"({d} shards): the session carry is row-sharded "
                    f"without padding")
        self.mesh = mesh
        self._ref_path = api.use_ref(impl)
        self._vmem_probes = (not self._ref_path
                             and all_probes_in_vmem(spec))
        self._donate = _resolve_donate(donate)
        self._tile = tuple(sorted(tile_kw.items()))
        h1 = jnp.asarray(h1, _U32)
        if h1.ndim != 1:
            raise ValueError(f"h1 must be (V,), got shape {h1.shape}")
        if spec.L < 32:
            h1 = h1 & np.uint32((1 << spec.L) - 1)
        self.h1 = h1
        self.vocab = int(h1.shape[0])
        # the spec the step's kernel runs: the canary, if any, answered by
        # one probe of its membership table
        self.step_spec = dataclasses.replace(spec, canary_k=1)
        self.canary_bits = self.canary_table = None
        if spec.has_canary:
            if canary_bits is None:
                raise ValueError("spec has a canary filter: pass canary_bits")
            self._set_canary(canary_bits)
        elif canary_bits is not None:
            raise ValueError("canary_bits given but spec.canary_log2_m == 0")
        self.state = init_state(spec, self.capacity)
        self._free = list(range(self.capacity - 1, -1, -1))  # pop() -> slot 0 first
        self._t = 0

    def _set_canary(self, canary_bits) -> None:
        """Adopt a canary filter and build its membership table, together,
        so the table the steps probe is never stale against the filter."""
        bits = jnp.asarray(canary_bits, _U32)
        if bits.shape != (self.spec.canary_words,):
            raise ValueError(f"canary_bits shape {bits.shape} != "
                             f"({self.spec.canary_words},) for "
                             f"canary_log2_m={self.spec.canary_log2_m}")
        self.canary_bits = bits
        self.canary_table = _kref.bloom_membership_table(
            bits, self.spec.canary_k, self.spec.canary_log2_m)
        obs.count("sessions.canary_table_builds")

    # -- churn ------------------------------------------------------------
    def _mask(self, slots) -> jnp.ndarray:
        mask = np.zeros((self.capacity,), dtype=bool)
        mask[np.asarray(slots, dtype=np.int64)] = True
        return jnp.asarray(mask)

    def admit(self, count: int = 1) -> np.ndarray:
        """Allocate ``count`` free slots, zero their state, mark active.
        Returns the slot ids (the caller's session handles)."""
        if count > len(self._free):
            raise ValueError(f"admit({count}): only {len(self._free)} free "
                             f"slot(s) of {self.capacity}")
        slots = np.array([self._free.pop() for _ in range(count)],
                         dtype=np.int64)
        obs.count("sessions.dispatches")
        self.state = _churn("reset", self.state, self._mask(slots))
        return slots

    def evict(self, slots: Sequence[int]) -> None:
        """Deactivate sessions and return their slots to the free list.
        State (telemetry included) survives until the slot is re-admitted."""
        slots = np.atleast_1d(np.asarray(slots, dtype=np.int64))
        obs.count("sessions.dispatches")
        self.state = _churn("evict", self.state, self._mask(slots))
        self._free.extend(int(s) for s in slots)

    def reset(self, slots: Sequence[int]) -> None:
        """Zero the state of live sessions in place (fresh conversation,
        same slot)."""
        slots = np.atleast_1d(np.asarray(slots, dtype=np.int64))
        obs.count("sessions.dispatches")
        self.state = _churn("reset", self.state, self._mask(slots))

    # -- the decode plane -------------------------------------------------
    def prime(self, tokens, lengths=None) -> None:
        """Charge prompt tokens into the pool: ``tokens`` (C, T) int32,
        optional per-row ``lengths`` for ragged prompts (rows advance only
        their own first ``lengths[i]`` symbols). One device dispatch."""
        tokens = jnp.asarray(tokens, jnp.int32)
        if tokens.ndim != 2 or tokens.shape[0] != self.capacity:
            raise ValueError(f"tokens must be ({self.capacity}, T), got "
                             f"shape {tokens.shape}")
        T = int(tokens.shape[1])
        if lengths is None:
            lengths = jnp.full((self.capacity,), T, jnp.int32)
        else:
            lengths = jnp.asarray(lengths, jnp.int32)
            if lengths.shape != (self.capacity,):
                raise ValueError(f"lengths shape {lengths.shape} != "
                                 f"({self.capacity},)")
        fn = _prime_donated if self._donate else _prime_plain
        obs.count("sessions.dispatches")
        self.state = fn(self.spec, self.mesh, T, self.state, tokens,
                        lengths, self.h1)

    @kernel_contract(pallas_calls=1, scans=0, while_loops=0,
                     collectives="none", donated=("state",))
    def step(self, logits, *, key=None, temperature: float = 1.0,
             top_k: int = 0) -> jnp.ndarray:
        """One decode step for every active session — ONE device dispatch.

        ``logits`` (C, V) raw logits (pad-token masking is the caller's
        job); returns (C,) int32 sampled tokens (inactive rows emit a
        token too — callers index by their slot ids). The fused epilogue,
        top-k/temperature sampling, Bloom/ring advance and telemetry
        accumulation all live in the one jitted graph; the carry is
        donated on TPU/GPU.
        """
        logits = jnp.asarray(logits)
        if logits.shape != (self.capacity, self.vocab):
            raise ValueError(f"logits shape {logits.shape} != "
                             f"({self.capacity}, {self.vocab})")
        if key is None:
            key = jax.random.PRNGKey(0)
        fn = _step_donated if self._donate else _step_plain
        obs.count("sessions.dispatches")
        if self._vmem_probes:
            obs.count("sessions.vmem_probe_steps")
        token, self.state = fn(self.step_spec, self._ref_path, self.mesh,
                               self._tile, float(temperature), int(top_k),
                               self.state, logits, self.h1,
                               self.canary_table, key,
                               jnp.int32(self._t))
        self._t += 1
        return token

    # -- durability --------------------------------------------------------

    def export_state(self) -> Dict:
        """Snapshot the pool: the (C, ...) carry pytree PLUS the hash draw
        it was accumulated under (h1 table, canary filter) and the host-side
        slot allocator/clock. The no-repeat Bloom rows and n-gram ring
        tails are functions of this process's h1 draw — restoring them
        under a re-drawn table would silently corrupt every subsequent
        membership probe — so params travel with state (the durable-state
        contract; see ``data/durable.py``)."""
        params = {"h1": np.asarray(self.h1)}
        if self.canary_bits is not None:
            params["canary_bits"] = np.asarray(self.canary_bits)
        return {"params": params,
                "carry": jax.tree_util.tree_map(np.asarray, self.state),
                "free": np.asarray(self._free, np.int64),
                "t": np.int64(self._t)}

    def import_state(self, tree: Dict) -> None:
        """Adopt a snapshot (params first, then the carry accumulated under
        them). Elastic across meshes: the exported carry is unpadded host
        rows; the capacity (and spec) of THIS pool must match, the device
        layout need not — h1/canary ride the step calls as arguments, so no
        re-trace is needed."""
        params = tree["params"]
        h1 = jnp.asarray(params["h1"], _U32)
        if int(h1.shape[0]) != self.vocab:
            raise ValueError(f"snapshot h1 has vocab {h1.shape[0]}, pool "
                             f"expects {self.vocab}")
        self.h1 = h1
        if self.spec.has_canary:
            if "canary_bits" not in params:
                raise ValueError("spec has a canary filter but the snapshot "
                                 "carries no canary_bits")
            self._set_canary(params["canary_bits"])
        carry = jax.tree_util.tree_map(jnp.asarray, tree["carry"])
        if int(carry["active"].shape[0]) != self.capacity:
            raise ValueError(
                f"snapshot capacity {carry['active'].shape[0]} != pool "
                f"capacity {self.capacity} (session slots are identity, "
                f"not layout — restore into an equal-capacity pool)")
        self.state = carry
        self._free = [int(s) for s in np.asarray(tree["free"], np.int64)]
        self._t = int(tree["t"])

    # -- introspection ----------------------------------------------------
    @property
    def active_slots(self) -> np.ndarray:
        return np.flatnonzero(np.asarray(self.state["active"]))

    @property
    def free_count(self) -> int:
        return len(self._free)
