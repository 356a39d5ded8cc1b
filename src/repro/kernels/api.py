"""The plan engine: one validated entry point for the hash->sketch data-plane.

Callers build a declarative :class:`~repro.kernels.plan.SketchPlan` (hash
family + named sketches) once and execute it with :func:`run`. The engine
centralizes everything the legacy per-sketch entry points re-implemented —
leading-dim flattening, impl validation/dispatch, the Theorem-1 discard
mask, per-row ``n_windows`` normalization, operand shape checks — and runs
**all requested sketches in one rolling-hash device pass**:

* ``impl="pallas"`` (or ``"auto"`` on TPU) — one multi-output Pallas kernel
  (``sketch_fused.sketch_plan_fused``): the tile's window hashes are
  computed once and folded into every sketch's VMEM scratch accumulator.
* ``impl="ref"`` (or ``"auto"`` off-TPU) — the matching single-jit jnp
  graph (``ref.sketch_plan_ref``), one compiled executor per distinct plan.

Both paths are bit-identical to each other and to the legacy single-sketch
entry points (``ops.cyclic_minhash`` / ``cyclic_hll`` / ``cyclic_bloom``,
now deprecation shims over this engine).

A plan is also the natural unit for multi-device sharding: ``run`` is pure
in its array arguments, so :func:`repro.kernels.shard.run_sharded` wraps the
same executor in ``shard_map`` over the batch dimension (row-parallel
MinHash/Bloom outputs, a ``pmax`` combine for the HLL register file) with
bit-identical outputs at any device count.

Example::

    from repro.kernels import api
    from repro.kernels.plan import (BloomSpec, HashSpec, HLLSpec,
                                    MinHashSpec, SketchPlan)

    plan = SketchPlan(
        hash=HashSpec(family="cyclic", n=8, L=32),        # Theorem-1 discard
        sketches={"sig": MinHashSpec(k=64),
                  "card": HLLSpec(b=12),
                  "decontam": BloomSpec(k=4, log2_m=22)})
    out = api.run(plan, h1v, h1v_b=h1v_second_draw, n_windows=nw,
                  operands={"sig": {"a": a, "b": b},
                            "decontam": {"bits": bloom_bits}})
    out["sig"], out["card"], out["decontam"]
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.contracts import kernel_contract
from repro.kernels import ref as _ref
from repro.kernels import sketch_fused as _sf
from repro.kernels.plan import (BloomSpec, CountMinSpec, DecodeSpec, HashSpec,
                                HLLSpec, MinHashSpec, SketchPlan)

_IMPLS = ("auto", "pallas", "ref")


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def use_ref(impl: str) -> bool:
    """Validate ``impl`` and decide the dispatch (jnp graph vs Pallas)."""
    if impl not in _IMPLS:
        raise ValueError(f"unknown impl={impl!r}; expected one of {_IMPLS}")
    return impl == "ref" or (impl == "auto" and not on_tpu())


def flatten(x: jnp.ndarray, words: int = 1):
    """(..., S) -> ((B, S), leading-shape) for batch tiling; two-word lanes
    (2, ..., S) -> ((2, B, S), leading-shape)."""
    if words == 1:
        lead = x.shape[:-1]
        return x.reshape((-1, x.shape[-1])), lead
    if x.ndim < 2 or x.shape[0] != words:
        raise ValueError(f"two-word lanes are ({words}, ..., S) uint32 word "
                         f"planes, low word first; got shape {x.shape}")
    lead = x.shape[1:-1]
    return x.reshape((words, -1, x.shape[-1])), lead


def lead_shape(out: jnp.ndarray, lead, words: int = 1):
    """(B, W) or (2, B, W) -> the caller's (..., W) or (2, ..., W)."""
    return out.reshape(out.shape[:words - 1] + lead + (out.shape[-1],))


def prepare(h1v: jnp.ndarray, *, n: int, impl: str, allow_short: bool = False,
            words: int = 1):
    """The one validated prologue every kernel entry point shares: flatten
    leading dims, check the window fits, resolve the impl dispatch.

    ``allow_short=True`` (the sketch engine) accepts ``S < n`` — a short row
    is legal in a padded/chunked batch and simply has ``n_windows = 0`` — by
    zero-padding up to ``S = n`` so the kernels have one physical window to
    tile over (fully masked by the W=0 clamp in :func:`validate`). The
    plain-hash entry points keep the hard error: their *output* is the
    window-hash array, which has no rows to return when S < n.

    ``words=2`` takes two-word lanes (2, ..., S) (``HashSpec.words``).

    Returns (x (B, max(S, n)) — (2, B, max(S, n)) for two-word lanes —,
    lead shape, use_ref flag)."""
    ref_path = use_ref(impl)        # validates impl before any shape work
    x, lead = flatten(jnp.asarray(h1v), words)
    S = x.shape[-1]
    if S < n:
        if not allow_short:
            raise ValueError(f"sequence length {S} < window n={n}")
        x = jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, n - S),))
    return x, lead, ref_path


def check_row_counts(counts, what: str, upper: Optional[int] = None) -> None:
    """Reject out-of-range concrete per-row counts with the offending row
    index: negative always (a negative count would otherwise flow silently
    into the mask iota compare), and above ``upper`` when one is given.
    Under a caller's jit trace the values are abstract and the check is
    skipped (the engine's clamps still treat any negative as "none")."""
    if isinstance(counts, jax.core.Tracer):
        return
    vals = np.asarray(counts)
    flat = vals.reshape(-1)

    def where(i):          # multi-dim counts (e.g. (T, B) chunk stacks)
        if vals.ndim <= 1:
            return f"row {i}"
        return f"row {np.unravel_index(i, vals.shape)}"

    neg = flat < 0
    if neg.any():
        i = int(np.argmax(neg))
        raise ValueError(
            f"{what} must be non-negative; {where(i)} has {int(flat[i])}")
    if upper is not None:
        over = flat > upper
        if over.any():
            i = int(np.argmax(over))
            raise ValueError(
                f"{what} must be <= {upper}; {where(i)} has {int(flat[i])}")


def norm_windows(n_windows, B: int, W: int) -> jnp.ndarray:
    """-> (B,) int32 valid-window counts, clamped to the physical W
    (over-long counts are legal and clamped — a padded batch's rows may all
    declare "every window"); negative concrete counts are rejected with the
    offending row index (:func:`check_row_counts`)."""
    if n_windows is None:
        return jnp.full((B,), W, jnp.int32)
    nw = jnp.asarray(n_windows, jnp.int32).reshape(-1)
    if nw.shape != (B,):
        raise ValueError(f"n_windows shape {nw.shape} != batch ({B},)")
    check_row_counts(nw, "n_windows")
    return jnp.minimum(nw, np.int32(W))


def norm_w_start(w_start, B: int, W: int):
    """-> (B,) int32 first-valid-window indices (or None = 0 everywhere).

    ``w_start`` is the lower edge of the per-row validity range — window
    ``j`` of row ``i`` counts iff ``w_start[i] <= j < n_windows[i]``. The
    streaming executor uses it to exclude windows that would span a chunk's
    zero-filled history at the very start of a stream."""
    if w_start is None:
        return None
    ws = jnp.asarray(w_start, jnp.int32).reshape(-1)
    if ws.shape != (B,):
        raise ValueError(f"w_start shape {ws.shape} != batch ({B},)")
    return jnp.clip(ws, 0, np.int32(W))


def _check_operands(plan: SketchPlan, operands,
                    batch: Optional[int] = None) -> Dict[str, dict]:
    """Every sketch gets exactly the operand arrays its spec declares, plus
    an optional ``init`` carry-in of its running state (validated against
    the spec's ``state_struct`` when the flattened batch size is known)."""
    operands = dict(operands or {})
    unknown = set(operands) - set(plan.names)
    if unknown:
        raise ValueError(f"operands for sketches not in plan: {sorted(unknown)}")
    for name, spec in plan.sketches:
        got = {k: jnp.asarray(v) for k, v in operands.get(name, {}).items()}
        want = spec.operand_names
        if set(got) - {"init"} != set(want):
            raise ValueError(
                f"sketch {name!r} ({type(spec).__name__}) needs operands "
                f"{list(want)}, got {sorted(got)}")
        if "init" in got and batch is not None:
            shape, dtype, _ = spec.state_struct(batch)
            if got["init"].shape != shape:
                raise ValueError(
                    f"sketch {name!r}: init carry shape {got['init'].shape} "
                    f"!= state shape {shape} (flattened batch {batch})")
            got["init"] = got["init"].astype(dtype)
        if isinstance(spec, MinHashSpec):
            for op in ("a", "b"):
                if got[op].shape != (spec.k,):
                    raise ValueError(
                        f"sketch {name!r}: operand {op!r} shape "
                        f"{got[op].shape} != (k={spec.k},)")
        elif isinstance(spec, BloomSpec):
            if got["bits"].shape != (spec.n_words,):
                raise ValueError(
                    f"sketch {name!r}: packed filter shape "
                    f"{got['bits'].shape} != ({spec.n_words},) for "
                    f"log2_m={spec.log2_m}")
        elif isinstance(spec, CountMinSpec):
            # over two-word hashes ``a`` holds one multiplier per key piece
            pieces = spec.key_pieces(plan.hash)
            for op in ("a", "b"):
                if op == "a" and pieces:
                    want, text = (spec.depth, pieces), (
                        f"(depth={spec.depth}, pieces={pieces})")
                else:
                    want, text = (spec.depth,), f"(depth={spec.depth},)"
                if got[op].shape != want:
                    raise ValueError(
                        f"sketch {name!r}: operand {op!r} shape "
                        f"{got[op].shape} != {text}")
        operands[name] = got
    return operands


@functools.partial(jax.jit, static_argnums=(0,))
def _run_ref(plan, x, xb, nw, ws, operands):
    """One jit per distinct plan: the whole multi-sketch graph is a single
    device dispatch on the CPU path."""
    return _ref.sketch_plan_ref(plan, x, xb, nw, operands, w_start=ws)


def validate(plan: SketchPlan, h1v, h1v_b, n_windows, operands, impl: str,
             w_start=None):
    """The shared front half of :func:`run`: validate + normalize everything.

    Returns ``(x (B, S), xb (B, S) | None, nw (B,), ws (B,) | None,
    operands, lead, ref_path)`` ready for :func:`execute`. Kept separate so
    the sharded entry point (:func:`repro.kernels.shard.run_sharded`) raises
    exactly the same errors and feeds exactly the same normalized arrays as
    the single-device path.

    ``S < n`` inputs are legal here (every row simply has zero valid
    windows): the rows are zero-padded to ``S = n`` and the window clamp
    masks everything, so e.g. a dedup chunk of documents all shorter than
    the n-gram window signs to sentinel signatures instead of raising.
    """
    if not isinstance(plan, SketchPlan):
        raise TypeError(f"plan must be a SketchPlan, got {type(plan)}")
    n = plan.hash.n
    h1v = jnp.asarray(h1v)
    S0 = h1v.shape[-1]
    x, lead, ref_path = prepare(h1v, n=n, impl=impl, allow_short=True,
                                words=plan.hash.words)
    B, S = x.shape[-2:]
    operands = _check_operands(plan, operands, B)
    xb = None
    if plan.needs_second_stream:
        if h1v_b is None:
            raise ValueError("plan contains a BloomSpec: the double-hashing "
                             "probe stride needs a second stream h1v_b")
        xbf, _ = flatten(jnp.asarray(h1v_b))
        if xbf.shape != (B, S0):
            raise ValueError(f"h1v_b shape {xbf.shape} != h1v shape {(B, S0)}")
        xb = jnp.pad(xbf, ((0, 0), (0, S - S0))) if S0 < S else xbf
    elif h1v_b is not None:
        raise ValueError("h1v_b given but no sketch in the plan consumes a "
                         "second hash stream")
    W = max(0, S0 - n + 1)          # windows of the *caller's* rows
    nw = norm_windows(n_windows, B, W)
    ws = norm_w_start(w_start, B, W)
    return x, xb, nw, ws, operands, lead, ref_path


def execute(plan: SketchPlan, x, xb, nw, operands, ref_path: bool,
            w_start=None, **tile_kw) -> Dict[str, jnp.ndarray]:
    """The shared back half: dispatch validated (B, S) arrays to the fused
    Pallas kernel or the single-jit jnp executor. Pure in its array
    arguments — safe to call under ``shard_map`` on a per-device shard."""
    if ref_path:
        return _run_ref(plan, x, xb, nw, w_start, operands)
    return _sf.sketch_plan_fused(x, xb, nw, operands, plan=plan,
                                 w_start=w_start,
                                 interpret=not on_tpu(), **tile_kw)


def shape_outputs(plan: SketchPlan, out: Dict[str, jnp.ndarray],
                  lead) -> Dict[str, jnp.ndarray]:
    """Restore the caller's leading dims on per-row outputs (HLL registers
    and CountMin tables are corpus-level and pass through unchanged)."""
    results = {}
    for name, spec in plan.sketches:
        o = out[name]
        if isinstance(spec, MinHashSpec):
            results[name] = o.reshape(lead + (spec.k,))
        elif isinstance(spec, BloomSpec):
            results[name] = o.reshape(lead)
        else:                        # HLL registers / CountMin partial table
            results[name] = o
    return results


@kernel_contract(pallas_calls=1, scans=0, while_loops=0, collectives="none")
def decode(spec: DecodeSpec, logits, prefix, ready, bloom, h1, *,
           canary_bits=None, impl: str = "auto", **tile_kw) -> Dict[str, jnp.ndarray]:
    """Decode-time n-gram plane: hash every candidate continuation, probe
    the per-session no-repeat filter (and the optional shared decontam
    canary), and mask the logits — ONE fused device pass.

    Args:
      spec: static :class:`~repro.kernels.plan.DecodeSpec` (trace key).
      logits: (B, V) float logits tile for this decode step.
      prefix: (B,) uint32 rolling prefix hashes (last n-1 tokens).
      ready: (B,) bool/int — session has >= n-1 symbols of history (a
        not-ready session bans nothing and registers no canary hits).
      bloom: (B, 2^log2_m/32) uint32 packed per-session filters.
      h1: (V,) uint32 symbol hashes (masked to L bits here).
      canary_bits: (2^canary_log2_m/32,) uint32 shared filter, required iff
        ``spec.has_canary``.
      impl: ``"auto"`` (Pallas on TPU, jnp oracle elsewhere) / ``"pallas"``
        / ``"ref"`` — same dispatch contract as :func:`run`.

    Returns ``{"logits": (B, V) banned-masked logits, "banned":
    (B, ceil(V/32)) uint32 packed mask[, "canary": packed hit mask]}``.
    Traceable: safe to call inside a caller's jit / shard_map region (shape
    checks only — they see concrete shapes under tracing too).
    """
    if not isinstance(spec, DecodeSpec):
        raise TypeError(f"spec must be a DecodeSpec, got {type(spec)}")
    ref_path = use_ref(impl)
    logits = jnp.asarray(logits)
    if logits.ndim != 2:
        raise ValueError(f"logits must be (B, V), got shape {logits.shape}")
    B, V = logits.shape
    prefix = jnp.asarray(prefix, jnp.uint32)
    ready = jnp.asarray(ready)
    for name, arr in (("prefix", prefix), ("ready", ready)):
        if arr.shape != (B,):
            raise ValueError(f"{name} shape {arr.shape} != batch ({B},)")
    bloom = jnp.asarray(bloom, jnp.uint32)
    if bloom.shape != (B, spec.n_words):
        raise ValueError(f"bloom words shape {bloom.shape} != "
                         f"({B}, {spec.n_words}) for log2_m={spec.log2_m}")
    h1 = jnp.asarray(h1, jnp.uint32)
    if h1.shape != (V,):
        raise ValueError(f"h1 shape {h1.shape} != vocab ({V},)")
    if spec.L < 32:
        h1 = h1 & np.uint32((1 << spec.L) - 1)
    if spec.has_canary:
        if canary_bits is None:
            raise ValueError("spec has a decontam canary filter: pass "
                             "canary_bits (2^canary_log2_m/32,)")
        canary_bits = jnp.asarray(canary_bits, jnp.uint32)
        if canary_bits.shape != (spec.canary_words,):
            raise ValueError(f"canary_bits shape {canary_bits.shape} != "
                             f"({spec.canary_words},) for canary_log2_m="
                             f"{spec.canary_log2_m}")
    elif canary_bits is not None:
        raise ValueError("canary_bits given but spec.canary_log2_m == 0")
    if ref_path:
        return _ref.decode_masks_ref(
            logits, prefix, ready, bloom, h1, n=spec.n, L=spec.L,
            hash_mask=spec.hash_mask, log2_m=spec.log2_m, k=spec.k,
            canary_bits=canary_bits, canary_log2_m=spec.canary_log2_m,
            canary_k=spec.canary_k)
    from repro.kernels import decode as _dk
    return _dk.decode_masks_fused(logits, prefix, ready, bloom, h1,
                                  spec=spec, canary_bits=canary_bits,
                                  interpret=not on_tpu(), **tile_kw)


@kernel_contract(pallas_calls=1, scans=0, while_loops=0, collectives="none")
def run(plan: SketchPlan, h1v: jnp.ndarray, *, h1v_b=None, n_windows=None,
        operands=None, impl: str = "auto", w_start=None,
        **tile_kw) -> Dict[str, jnp.ndarray]:
    """Execute a :class:`SketchPlan` over (..., S) h1-mapped values.

    Args:
      plan: hash family + named sketch specs (static; one compiled executor
        per distinct plan).
      h1v: (..., S) uint32 h1-mapped token values; leading dims are
        flattened to a batch and restored on return. Past ``L = 32`` the
        lanes are two words with a leading word axis, (2, ..., S).
      h1v_b: second independent family draw, required iff the plan contains
        a :class:`BloomSpec` (double-hashing probe stride).
      n_windows: optional (...,) per-row valid-window counts for padded
        batches; ``None`` means every window of every row is valid.
      operands: ``{sketch_name: {operand_name: array}}`` runtime inputs —
        MinHash remix lanes ``a``/``b`` (k,), the packed Bloom filter
        ``bits`` (2^log2_m/32,), the CountMin row remix constants
        ``a``/``b`` (depth,). Each sketch also accepts an optional ``init``
        carry-in of its running state (see the spec's ``state_struct``);
        the executors initialize from it and fold new windows in with the
        sketch's own merge operator instead of resetting — the streaming
        executor's cross-chunk seam.
      impl: ``"auto"`` (Pallas on TPU, jnp graph elsewhere), ``"pallas"``
        (force the kernel; interpret-mode off-TPU), ``"ref"`` (force jnp).
      w_start: optional (...,) per-row *first* valid window index (window j
        counts iff ``w_start <= j < n_windows``); ``None`` means 0. Used by
        the streaming executor to mask windows spanning a chunk's
        zero-filled pre-stream history.
      **tile_kw: ``block_b`` / ``block_s`` overrides for the Pallas path.

    Returns:
      ``{sketch_name: result}`` — MinHash (..., k) uint32, HLL (2^b,) int32
      (reduced over the whole batch), Bloom (...,) int32 hit counts,
      CountMin (depth, 2^log2_width) int32 batch partial counts (additive:
      fold into running state with ``+``).
    """
    x, xb, nw, ws, operands, lead, ref_path = validate(
        plan, h1v, h1v_b, n_windows, operands, impl, w_start)
    out = execute(plan, x, xb, nw, operands, ref_path, w_start=ws, **tile_kw)
    return shape_outputs(plan, out, lead)
