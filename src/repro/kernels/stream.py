"""Chunked streaming executor: one compiled shape, cross-chunk carry.

The paper's recursive families make n-gram hashing a *streaming* operation —
O(1) work per symbol with constant state — and Lemire & Kaser's companion
work ("One-Pass, One-Hash n-Gram Statistics Estimation") frames every sketch
this engine runs as a single pass over unbounded input. This module gives
the data-plane that shape: :func:`update` drives the existing fused plan
kernel over fixed ``(B, chunk_S)`` tiles with an explicit **carry**, so any
stream length — ragged corpora, documents longer than a device buffer,
genuinely unbounded token feeds — flows through ONE compiled executor
instead of one jit shape per length bucket.

How a chunk becomes windows, exactly once:

* The carry holds each row's last ``n-1`` consumed h1 values (``tail``).
  A chunk is hashed as ``concat([tail, chunk])`` — shape ``(B, n-1+C)`` —
  so the ``C`` windows of that array are precisely the windows *ending at*
  this chunk's symbols::

      tail (n-1)   chunk (C)
      [t t t t t | c0 c1 c2 ...]     window j spans x[j : j+n]
                                     and ends at chunk symbol j

  A boundary-spanning window is hashed in exactly one chunk (the one its
  last symbol lands in); no window is hashed twice.
* At the very start of a stream the tail is zero-filled history that no
  window may span: the per-row ``w_start = max(0, n-1 - seen)`` lower mask
  bound (threaded through ``api.execute`` into the kernels) excludes those
  leading windows, where ``seen`` saturates at ``n-1`` — constant state, as
  the paper promises.
* Every sketch's state rides the carry through its ``init`` operand and is
  folded with its own merge operator inside the kernel scratch (MinHash
  per-row running min, HLL register max, Bloom hit-count add, CountMin
  table add) — all exact on integers, so a chunked run is bit-identical to
  one-shot :func:`repro.kernels.api.run`.
* The per-chunk update is one jitted call with the carried state **donated**
  (``jax.jit(donate_argnums=...)``): in steady state the tail/seen/sketch
  buffers are reused in place instead of reallocated per chunk.

Rows advance independently: per-chunk ``lengths`` mark how many of a row's
chunk symbols are real, a row whose stream has ended just submits 0, and an
idle row's tail is preserved verbatim (the tail refresh gathers at the
row's own fill level), so ragged document batches and multi-tenant streams
share one executor shape.

Sharding composes: pass ``mesh``/``data_shards`` and the executor runs
under ``shard_map`` on the data mesh — for the scan executor ONE partitioned
region wraps the whole chunk loop (row state scans shard-locally;
corpus-level state accumulates per-shard partials merged exactly once after
the loop, legal because the merge operators are associative/commutative) —
bit-identical at any device count.

The chunk loop itself lives **on device** (PR 6): the host-driven
one-jit-call-per-chunk loop paid one dispatch per chunk — exactly the O(1)
-per-symbol budget the recursive families buy back in recurrence cost —
so the executors below fold the loop into the compiled graph and a whole
stream becomes ONE device dispatch:

* **scan executor** — ``lax.scan`` over pre-tiled ``(num_chunks, B, C)``
  chunk tiles with the carry pytree (tail + seen + every sketch's state) as
  the loop state. The scanned carry is donated, so in steady state the
  loop runs entirely in place on device.
* **in-kernel chunk grid** — on the fused path the chunk loop is pushed
  into the kernel itself: the plan kernel's sequence-block grid dimension
  *is* a chunk loop (``block_s``-wide steps over the tail-concatenated
  stream) with every sketch's accumulator resident in VMEM scratch across
  grid steps — init-from-carry at step 0, flush at the last (the PR 4/5
  scratch lifecycle) — so the carry never round-trips HBM between chunks
  and a multi-chunk stream is exactly one ``pallas_call``.

Entry points:

* :func:`init_state` / :func:`update` / :func:`finalize` — the stateful
  API for unbounded streams (stats/decontam telemetry); one dispatch per
  chunk.
* :func:`update_many` — fold a whole ``(T, B, C)`` block of chunks in ONE
  dispatch (the scan executor under the stateful API). A fixed ``T`` gives
  a single compiled shape for any stream length — the executor never
  retraces, however long the feed runs.
* :func:`export_state` / :func:`import_state` — the durability contract:
  snapshot a carry as a mesh-independent host pytree and rebuild it on ANY
  device count (shard padding is sliced off / re-applied with identity
  fill), so corpus jobs checkpoint mid-stream and resume bit-identical —
  even elastically onto a different mesh (``data/durable.py`` is the
  file-format layer on top).
* :func:`feed` — drive :func:`update_many` over an unbounded host iterator
  with the next block's host->device transfer overlapped with the current
  block's compute (double buffering).
* :func:`run_stream` — a drop-in chunked ``api.run``: same arguments plus
  ``chunk_s``, same outputs. ``executor="scan"`` (default) runs the whole
  stream in one dispatch; ``"grid"`` runs it in one ``pallas_call`` on the
  fused path; ``"host"`` keeps the PR 5 one-dispatch-per-chunk loop (the
  benchmark baseline).
"""
from __future__ import annotations

import contextvars
import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.analysis.contracts import kernel_contract
from repro.kernels import api, shard
from repro.kernels.plan import CountMinSpec, HLLSpec, SketchPlan

_EXECUTORS = ("scan", "grid", "host")

# device dispatches issued by this module's executors (one jitted call = one
# XLA execution); the one-dispatch-per-stream property is asserted against
# this counter in tests and reported by the benchmarks. Context-local
# (contextvars): concurrent streams — asyncio servers, parallel test
# workers — each observe only their own dispatches instead of racing on a
# module global
_dispatches = contextvars.ContextVar("repro.kernels.stream._dispatches",
                                     default=0)


def dispatch_count() -> int:
    """Chunk-executor device dispatches issued in this context."""
    return _dispatches.get()


def _dispatched(n: int = 1) -> None:
    _dispatches.set(_dispatches.get() + n)

# backends whose runtime implements buffer donation; elsewhere "auto" skips
# the request (XLA would silently ignore it — harmless, but explicit beats
# a warning per compile on older jaxlibs)
_DONATABLE_BACKENDS = ("tpu", "gpu")


def _resolve_donate(donate) -> bool:
    if donate in (None, "auto"):
        return jax.default_backend() in _DONATABLE_BACKENDS
    return bool(donate)


def _resolve_mesh(mesh, data_shards):
    if mesh is None and data_shards is None:
        return None
    if mesh is None:
        mesh = shard.data_mesh(data_shards)
    if len(mesh.axis_names) != 1:
        raise ValueError(f"streaming needs a 1-D data mesh, got axes "
                         f"{mesh.axis_names}")
    return mesh


def state_batch(plan: SketchPlan, state: Dict) -> int:
    """The (possibly shard-padded) batch size a stream state was built for."""
    return state["seen"].shape[0]


def init_state(plan: SketchPlan, batch: int, *, carry: Optional[Dict] = None,
               mesh=None, data_shards: Optional[int] = None) -> Dict:
    """Fresh carry for ``batch`` parallel streams under ``plan``.

    The state is a flat pytree of device arrays (donate-able, checkpoint-
    able): ``tail`` (B, n-1) uint32 last-consumed h1 values (plus ``tail_b``
    for Bloom plans' second stream), ``seen`` (B,) int32 consumed-symbol
    count saturating at ``n-1`` (constant state: only the window-completion
    threshold matters), and ``sketch`` — one array per sketch, at the
    sketch's identity (sentinel minima / zero registers / zero counts) or
    seeded from ``carry[name]`` to continue existing state.

    With ``mesh``/``data_shards`` the batch is padded up to a multiple of
    the shard count (padded rows never submit symbols); pass the same mesh
    to every :func:`update` and :func:`finalize` slices the pads off.
    """
    if not isinstance(plan, SketchPlan):
        raise TypeError(f"plan must be a SketchPlan, got {type(plan)}")
    if batch < 1:
        raise ValueError(f"batch must be >= 1, got {batch}")
    mesh = _resolve_mesh(mesh, data_shards)
    Bp = batch if mesh is None else batch + (-batch % mesh.devices.size)
    n = plan.hash.n
    state = {"tail": jnp.zeros((Bp, n - 1), jnp.uint32),
             "seen": jnp.zeros((Bp,), jnp.int32)}
    if plan.needs_second_stream:
        state["tail_b"] = jnp.zeros((Bp, n - 1), jnp.uint32)
    sketch = {}
    carry = carry or {}
    unknown = set(carry) - set(plan.names)
    if unknown:
        raise ValueError(f"carry for sketches not in plan: {sorted(unknown)}")
    for name, spec in plan.sketches:
        shape, dtype, fill = spec.state_struct(Bp)
        if name in carry:
            # a copy: the state is donated to the executors, and the
            # caller's array must outlive it
            got = jnp.array(carry[name], dtype, copy=True)
            want = spec.state_struct(batch)[0]
            if got.shape != want:
                raise ValueError(
                    f"carry[{name!r}] shape {got.shape} != state shape {want}")
            if Bp != batch and spec.state_kind == "row":
                pad = jnp.full((Bp - batch,) + want[1:], fill, dtype)
                got = jnp.concatenate([got, pad], axis=0)
            sketch[name] = got
        else:
            sketch[name] = jnp.full(shape, fill, dtype)
    state["sketch"] = sketch
    return state


def _update_body(plan, ref_path, mesh, tile, state, chunk, chunk_b, lengths,
                 operands):
    """One chunk through the fused engine, carry in / carry out."""
    hs = plan.hash
    n = hs.n
    seen = state["seen"]
    # the clip backstops traced callers the concrete check can't see
    v = jnp.clip(jnp.asarray(lengths, jnp.int32), 0, chunk.shape[1])

    def cat(tail, c):
        c = c.astype(jnp.uint32)
        return jnp.concatenate([tail, c], axis=1) if n > 1 else c

    x = cat(state["tail"], chunk)
    xb = cat(state["tail_b"], chunk_b) if "tail_b" in state else None
    # window j of x ends at chunk symbol j: valid iff that symbol is real
    # (j < v) and the window's history is (j >= n-1 - seen, i.e. it does not
    # reach into the zero-filled pre-stream tail)
    nw = v
    ws = jnp.maximum(np.int32(n - 1) - seen, 0)
    operands = {name: dict(operands.get(name, {}))
                for name, _ in plan.sketches}
    for name, _ in plan.sketches:
        operands[name]["init"] = state["sketch"][name]
    if mesh is None:
        out = api.execute(plan, x, xb, nw, operands, ref_path, w_start=ws,
                          **dict(tile))
    else:
        out = shard.sharded_execute(plan, mesh, ref_path, tile, x, xb, nw,
                                    ws, operands)

    # tail refresh: the last n-1 *consumed* symbols end at the row's fill
    # level, so gather columns [v, v + n-1) of x — for an idle row (v = 0)
    # that is exactly the old tail, preserved verbatim
    new = {"seen": jnp.minimum(seen + v, np.int32(n - 1))}
    if n > 1:
        cols = v[:, None] + jnp.arange(n - 1, dtype=jnp.int32)[None, :]
        new["tail"] = jnp.take_along_axis(x, cols, axis=1)
        if xb is not None:
            new["tail_b"] = jnp.take_along_axis(xb, cols, axis=1)
    else:
        new["tail"] = state["tail"]
        if "tail_b" in state:
            new["tail_b"] = state["tail_b"]
    new["sketch"] = {name: out[name] for name, _ in plan.sketches}
    return new


# two jit twins so the donation choice is a dispatch decision, not a trace
# key hack: state (arg 4) is donated in the steady-state loop, and both
# expose _cache_size() for the no-retrace regression tests
_update_plain = jax.jit(
    _update_body, static_argnums=(0, 1, 2, 3))
_update_donated = jax.jit(
    _update_body, static_argnums=(0, 1, 2, 3), donate_argnums=(4,))


def _scan_body(plan, ref_path, mesh, tile, n_chunks, state, x, xb, lens,
               operands):
    """The whole chunk loop inside the compiled graph: ``lax.scan`` over
    chunk tiles with the carry pytree as the loop state.

    Two input layouts, selected by the static ``n_chunks``:

    * ``n_chunks=None`` — pre-tiled: ``x``/``xb`` are (T, B, C) chunk
      stacks and ``lens`` is (T, B) per-chunk real-symbol counts (the
      :func:`update_many` contract).
    * ``n_chunks=T`` — flat: ``x``/``xb`` are (B, T*C) whole streams and
      ``lens`` is the (B,) *total* symbol budget; the tiling and the
      per-chunk length split ``clip(lens - t*C, 0, C)`` happen inside the
      jit so :func:`run_stream` is one dispatch end to end.

    Every scan step is exactly :func:`_update_body` — same tail seam, same
    ``w_start`` masking, same per-sketch merge — so the scan executor is
    bit-identical to the host loop by construction.

    Under a mesh the ``shard_map`` wraps the WHOLE scan (not one region per
    chunk): row state scans shard-locally, and each shard accumulates its
    own "global" (HLL/CMS) partial from the sketch's identity, merged
    across shards and with the incoming carry exactly once after the loop —
    legal because both merge operators (max, integer add) are associative
    and commutative, so end-merging the per-shard partials is bit-identical
    to merging every chunk.
    """
    if n_chunks is None:
        xs_x, xs_b, xs_len = x, xb, lens
    else:
        B = x.shape[0]
        C = x.shape[1] // n_chunks
        xs_x = x.reshape(B, n_chunks, C).swapaxes(0, 1)
        xs_b = (xb.reshape(B, n_chunks, C).swapaxes(0, 1)
                if xb is not None else None)
        lo = jnp.arange(n_chunks, dtype=jnp.int32)[:, None] * np.int32(C)
        xs_len = jnp.clip(lens[None, :].astype(jnp.int32) - lo, 0,
                          np.int32(C))

    def step(st, xs):
        ck, ckb, ln = xs
        return _update_body(plan, ref_path, None, tile, st, ck, ckb, ln,
                            operands), None

    if mesh is None:
        state, _ = jax.lax.scan(step, state, (xs_x, xs_b, xs_len))
        return state

    # pop the global carries: each shard scans from the sketch identity
    # (zeros — max and add both start there) so the replicated carry cannot
    # be multiplied by the cross-shard merge
    carry = {}
    sk = dict(state["sketch"])
    for name, spec in plan.sketches:
        if spec.state_kind == "global":
            carry[name] = (sk[name], shard._GLOBAL_MERGE[type(spec)])
            sk[name] = jnp.zeros_like(sk[name])
    state = dict(state, sketch=sk)

    def local(st, xs_x, xs_b, xs_len):
        st, _ = jax.lax.scan(step, st, (xs_x, xs_b, xs_len))
        out = dict(st["sketch"])
        for name, spec in plan.sketches:
            if isinstance(spec, HLLSpec):
                out[name] = jax.lax.pmax(out[name], shard.AXIS)
            elif isinstance(spec, CountMinSpec):
                out[name] = jax.lax.psum(out[name], shard.AXIS)
        return dict(st, sketch=out)

    row = P(shard.AXIS)
    chunk_axis = P(None, shard.AXIS)
    st_spec = {k: row for k in state if k != "sketch"}
    st_spec["sketch"] = {name: P() if spec.state_kind == "global" else row
                         for name, spec in plan.sketches}
    state = shard_map(
        local, mesh=mesh,
        in_specs=(st_spec, chunk_axis,
                  chunk_axis if xs_b is not None else None, chunk_axis),
        out_specs=st_spec, check_vma=False)(state, xs_x, xs_b, xs_len)
    out = dict(state["sketch"])
    for name, (init, merge) in carry.items():
        out[name] = merge(out[name], init)
    return dict(state, sketch=out)


# the scan executor's jit twins: the carry (arg 5) is donated so the loop
# state lives in place on device across the whole stream; statics mirror
# _update_plain/_update_donated plus the chunk-count layout selector
_scan_plain = jax.jit(
    _scan_body, static_argnums=(0, 1, 2, 3, 4))
_scan_donated = jax.jit(
    _scan_body, static_argnums=(0, 1, 2, 3, 4), donate_argnums=(5,))


def update(plan: SketchPlan, state: Dict, chunk, *, chunk_b=None,
           lengths=None, operands=None, impl: str = "auto", donate="auto",
           mesh=None, data_shards: Optional[int] = None,
           **tile_kw) -> Dict:
    """Fold one ``(B, C)`` h1 chunk into the stream carry; returns the new
    carry (same shapes/dtypes — with donation the buffers are reused).

    Args:
      plan: the :class:`SketchPlan` the state was initialised for.
      state: carry from :func:`init_state` / a previous :func:`update`.
        When donation is active the passed-in state is consumed.
      chunk: (B, C) uint32 h1-mapped values, any fixed C >= 1 (each distinct
        C is one compiled shape; keep it constant for a single-trace loop).
      chunk_b: second family draw's chunk, required iff the plan has a
        BloomSpec.
      lengths: (B,) count of *real* symbols per row in this chunk (default:
        all C). Rows advance independently; finished or idle rows submit 0
        and their carry rides through untouched.
      operands: the per-sketch runtime operands of ``api.run`` (remix lanes,
        packed filter, CMS constants) — WITHOUT ``init``; the carry supplies
        every sketch's state.
      donate: True/False/"auto" — donate the carry buffers to the update
        (auto: on for backends with donation support).
      mesh / data_shards: run the chunk under ``shard_map`` on the 1-D data
        mesh the state was initialised with.
    """
    mesh = _resolve_mesh(mesh, data_shards)
    ref_path = api.use_ref(impl)
    chunk = jnp.asarray(chunk)
    if chunk.ndim != 2:
        raise ValueError(f"chunk must be (B, C), got shape {chunk.shape}")
    B, C = chunk.shape
    Bp = state_batch(plan, state)
    if B > Bp:
        raise ValueError(f"chunk rows {B} > stream state rows {Bp}")
    for name in (operands or {}):
        if "init" in (operands[name] or {}):
            raise ValueError(
                f"sketch {name!r}: do not pass 'init' to stream.update — "
                f"the stream carry supplies every sketch's state")
    operands = api._check_operands(plan, operands, None)
    if plan.needs_second_stream:
        if chunk_b is None:
            raise ValueError("plan contains a BloomSpec: the double-hashing "
                             "probe stride needs a second stream chunk_b")
        chunk_b = jnp.asarray(chunk_b)
        if chunk_b.shape != chunk.shape:
            raise ValueError(f"chunk_b shape {chunk_b.shape} != chunk shape "
                             f"{chunk.shape}")
    elif chunk_b is not None:
        raise ValueError("chunk_b given but no sketch in the plan consumes "
                         "a second hash stream")
    if lengths is None:
        lengths = jnp.full((B,), C, jnp.int32)
    else:
        lengths = jnp.asarray(lengths, jnp.int32).reshape(-1)
        if lengths.shape != (B,):
            raise ValueError(f"lengths shape {lengths.shape} != batch ({B},)")
        # out-of-range lengths silently corrupt downstream state — negative
        # drives `seen` backwards and re-gathers the tail at wrong columns,
        # oversize desyncs callers' own symbol accounting (e.g. decontam's
        # window totals) from the clipped count the engine actually consumes
        api.check_row_counts(lengths, "lengths", upper=C)
    if B < Bp:            # shard padding rows: no symbols, carry untouched
        chunk = jnp.pad(chunk, ((0, Bp - B), (0, 0)))
        if chunk_b is not None:
            chunk_b = jnp.pad(chunk_b, ((0, Bp - B), (0, 0)))
        lengths = jnp.pad(lengths, (0, Bp - B))
    tile = tuple(sorted(tile_kw.items()))
    fn = _update_donated if _resolve_donate(donate) else _update_plain
    _dispatched()
    return fn(plan, ref_path, mesh, tile, state, chunk, chunk_b, lengths,
              operands)


def update_many(plan: SketchPlan, state: Dict, chunks, *, chunk_b=None,
                lengths=None, operands=None, impl: str = "auto",
                donate="auto", mesh=None, data_shards: Optional[int] = None,
                **tile_kw) -> Dict:
    """Fold a ``(T, B, C)`` block of T chunks into the carry in ONE device
    dispatch: the chunk loop runs as ``lax.scan`` inside the compiled graph
    with the carry pytree as the loop state.

    Semantically exactly T successive :func:`update` calls (bit-identical
    carry out), but the host pays one dispatch per *block* instead of one
    per chunk — and a fixed ``(T, B, C)`` is a single compiled shape, so an
    unbounded feed never retraces however long it runs.

    Args mirror :func:`update` with a leading chunk axis:
      chunks: (T, B, C) uint32 h1 chunk stack, scanned in order.
      chunk_b: (T, B, C) second family draw, iff the plan has a BloomSpec.
      lengths: (T, B) real-symbol counts per chunk (default: all C). A
        finished row submits 0 from some chunk on and its carry rides
        through untouched, so ragged streams pad with zero-length chunks.
    """
    mesh = _resolve_mesh(mesh, data_shards)
    ref_path = api.use_ref(impl)
    chunks = jnp.asarray(chunks)
    if chunks.ndim != 3:
        raise ValueError(f"chunks must be (T, B, C), got shape "
                         f"{chunks.shape}")
    T, B, C = chunks.shape
    if T < 1:
        raise ValueError(f"need at least one chunk, got T={T}")
    Bp = state_batch(plan, state)
    if B > Bp:
        raise ValueError(f"chunk rows {B} > stream state rows {Bp}")
    for name in (operands or {}):
        if "init" in (operands[name] or {}):
            raise ValueError(
                f"sketch {name!r}: do not pass 'init' to stream.update_many "
                f"— the stream carry supplies every sketch's state")
    operands = api._check_operands(plan, operands, None)
    if plan.needs_second_stream:
        if chunk_b is None:
            raise ValueError("plan contains a BloomSpec: the double-hashing "
                             "probe stride needs a second stream chunk_b")
        chunk_b = jnp.asarray(chunk_b)
        if chunk_b.shape != chunks.shape:
            raise ValueError(f"chunk_b shape {chunk_b.shape} != chunks "
                             f"shape {chunks.shape}")
    elif chunk_b is not None:
        raise ValueError("chunk_b given but no sketch in the plan consumes "
                         "a second hash stream")
    if lengths is None:
        lengths = jnp.full((T, B), C, jnp.int32)
    else:
        lengths = jnp.asarray(lengths, jnp.int32)
        if lengths.shape != (T, B):
            raise ValueError(f"lengths shape {lengths.shape} != chunk stack "
                             f"({T}, {B})")
        api.check_row_counts(lengths, "lengths", upper=C)
    if B < Bp:            # shard padding rows: no symbols, carry untouched
        chunks = jnp.pad(chunks, ((0, 0), (0, Bp - B), (0, 0)))
        if chunk_b is not None:
            chunk_b = jnp.pad(chunk_b, ((0, 0), (0, Bp - B), (0, 0)))
        lengths = jnp.pad(lengths, ((0, 0), (0, Bp - B)))
    tile = tuple(sorted(tile_kw.items()))
    fn = _scan_donated if _resolve_donate(donate) else _scan_plain
    _dispatched()
    return fn(plan, ref_path, mesh, tile, None, state, chunks, chunk_b,
              lengths, operands)


def feed(plan: SketchPlan, blocks, state: Dict, *, operands=None,
         impl: str = "auto", donate="auto", mesh=None,
         data_shards: Optional[int] = None, **tile_kw) -> Dict:
    """Drive :func:`update_many` over a host iterator of chunk blocks with
    the host->device transfer double-buffered: each scan dispatch is
    asynchronous, so block t+1 is pulled from the iterator and its
    ``device_put`` enqueued while block t is still computing on device —
    the feed never serializes transfer behind compute.

    ``blocks`` yields either a ``(T, B, C)`` chunk stack, or a tuple
    ``(chunks, lengths)`` / ``(chunks, lengths, chunk_b)`` with ``lengths``
    (T, B). Keep one (T, B, C) shape for the whole feed (pad the final
    short block with zero-length chunks) and the executor compiles once.
    """
    def _put(blk):
        if blk is None:
            return None
        if not isinstance(blk, (tuple, list)):
            blk = (blk,)
        blk = tuple(blk) + (None,) * (3 - len(blk))
        chunks, lens, chunk_b = blk[:3]
        dev = lambda a: None if a is None else jax.device_put(jnp.asarray(a))
        return dev(chunks), dev(lens), dev(chunk_b)

    it = iter(blocks)
    cur = _put(next(it, None))
    while cur is not None:
        chunks, lens, chunk_b = cur
        state = update_many(plan, state, chunks, chunk_b=chunk_b,
                            lengths=lens, operands=operands, impl=impl,
                            donate=donate, mesh=mesh,
                            data_shards=data_shards, **tile_kw)
        cur = _put(next(it, None))   # H2D overlaps the in-flight scan
    return state


def finalize(plan: SketchPlan, state: Dict,
             batch: Optional[int] = None) -> Dict[str, jnp.ndarray]:
    """Extract the sketch results from a stream carry — the same outputs
    one-shot ``api.run`` would have produced over the concatenated stream.
    ``batch`` slices shard-padding rows off per-row ("row" state) outputs.
    """
    out = {}
    for name, spec in plan.sketches:
        o = state["sketch"][name]
        if batch is not None and spec.state_kind == "row":
            o = o[:batch]
        out[name] = o
    return out


def export_state(plan: SketchPlan, state: Dict,
                 batch: Optional[int] = None) -> Dict:
    """Snapshot a stream carry as a **mesh-independent** host-side pytree.

    ``batch`` slices shard-padding rows off the per-row leaves (tail(s),
    seen, "row"-kind sketch states); global sketch states pass through
    whole. Padding rows carry only identity state (zero tails, sentinel
    minima, zero counts), so slicing them is lossless and the exported tree
    is the same whatever mesh the stream ran on — the property that makes
    a checkpoint restorable onto a *different* device/worker count
    (:func:`import_state`). All leaves are materialized to host numpy so
    the tree is safe to hand to ``train.checkpoint`` / ``data.durable``
    even while the live carry keeps being donated.
    """
    if batch is None:
        batch = state_batch(plan, state)
    out = {k: np.asarray(state[k][:batch])
           for k in ("tail", "tail_b", "seen") if k in state}
    sk = {}
    for name, spec in plan.sketches:
        a = state["sketch"][name]
        sk[name] = np.asarray(a[:batch] if spec.state_kind == "row" else a)
    out["sketch"] = sk
    return out


def import_state(plan: SketchPlan, tree: Dict, *, mesh=None,
                 data_shards: Optional[int] = None) -> Dict:
    """Rebuild a live stream carry from :func:`export_state`'s tree,
    re-padded for the *target* mesh — the elastic-restore half of the
    contract: a stream checkpointed at one device count resumes on any
    other, bit-identical, because padding rows are (re)filled with each
    sketch's identity and never submit symbols.
    """
    if not isinstance(plan, SketchPlan):
        raise TypeError(f"plan must be a SketchPlan, got {type(plan)}")
    mesh = _resolve_mesh(mesh, data_shards)
    n = plan.hash.n
    seen = np.asarray(tree["seen"])
    batch = int(seen.shape[0])
    Bp = batch if mesh is None else batch + (-batch % mesh.devices.size)
    pad = Bp - batch

    def rowpad(a, fill, dtype):
        a = jnp.asarray(a, dtype)
        if pad:
            a = jnp.concatenate(
                [a, jnp.full((pad,) + a.shape[1:], fill, dtype)], axis=0)
        return a

    tail = np.asarray(tree["tail"])
    if tail.shape != (batch, n - 1):
        raise ValueError(f"tail shape {tail.shape} != ({batch}, {n - 1}) — "
                         f"was this state exported under a different plan?")
    state = {"tail": rowpad(tail, 0, jnp.uint32),
             "seen": rowpad(seen, 0, jnp.int32)}
    if plan.needs_second_stream:
        if "tail_b" not in tree:
            raise ValueError("plan contains a BloomSpec but the exported "
                             "state has no tail_b — family mismatch")
        state["tail_b"] = rowpad(np.asarray(tree["tail_b"]), 0, jnp.uint32)
    elif "tail_b" in tree:
        raise ValueError("exported state has tail_b but the plan has no "
                         "BloomSpec — family mismatch")
    missing = set(plan.names) - set(tree["sketch"])
    if missing:
        raise ValueError(f"exported state lacks sketches {sorted(missing)}")
    sketch = {}
    for name, spec in plan.sketches:
        shape, dtype, fill = spec.state_struct(batch)
        got = np.asarray(tree["sketch"][name])
        if got.shape != shape:
            raise ValueError(f"sketch {name!r} state shape {got.shape} != "
                             f"{shape}")
        sketch[name] = (rowpad(got, fill, dtype)
                        if spec.state_kind == "row" else jnp.asarray(got, dtype))
    state["sketch"] = sketch
    return state


@kernel_contract(variant="scan", pallas_calls=1, scans=1, while_loops=0,
                 collectives="global-sketch-merge", donated=("state",))
@kernel_contract(variant="grid", pallas_calls=1, scans=0, while_loops=0,
                 collectives="none", donated=("state",))
@kernel_contract(variant="host", pallas_calls=1, scans=0, while_loops=0,
                 collectives="none", donated=("state",))
def run_stream(plan: SketchPlan, h1v, *, chunk_s: int, h1v_b=None,
               n_windows=None, operands=None, impl: str = "auto",
               donate="auto", mesh=None, data_shards: Optional[int] = None,
               executor: str = "scan", n_chunks: Optional[int] = None,
               **tile_kw) -> Dict[str, jnp.ndarray]:
    """Chunked drop-in for :func:`repro.kernels.api.run`: identical
    arguments (plus ``chunk_s``) and bit-identical outputs, but the stream
    is consumed in fixed ``chunk_s``-symbol steps with the cross-chunk
    carry — O(B * chunk_s) live window state regardless of S.

    ``executor`` picks how the chunk loop runs:

    * ``"scan"`` (default) — the loop lives inside the compiled graph
      (``lax.scan`` over chunk tiles, carry as loop state): the whole
      stream is ONE device dispatch. Each distinct chunk *count* is one
      compiled shape; pass ``n_chunks`` >= ``ceil(S/chunk_s)`` to pin the
      count (shorter streams pad with zero-length chunks) and share one
      trace across stream lengths.
    * ``"grid"`` — the loop lives inside the kernel itself: the whole
      stream goes through one :func:`update` call, and on the fused path
      (``impl="pallas"``) the plan kernel's sequence-block grid dimension
      *is* the chunk loop — ``block_s``-wide steps with every sketch's
      accumulator resident in VMEM scratch across grid steps (init at step
      0, flush at the last), so a multi-chunk stream is exactly one
      ``pallas_call``. ``chunk_s`` becomes the ``block_s`` hint.
    * ``"host"`` — the PR 5 baseline: a host loop of one-chunk
      :func:`update` dispatches, one jit call per chunk.

    All three are bit-identical to one-shot ``api.run``.
    """
    if executor not in _EXECUTORS:
        raise ValueError(f"unknown executor={executor!r}; expected one of "
                         f"{_EXECUTORS}")
    if chunk_s < 1:
        raise ValueError(f"chunk_s must be >= 1, got {chunk_s}")
    if not isinstance(plan, SketchPlan):
        raise TypeError(f"plan must be a SketchPlan, got {type(plan)}")
    mesh = _resolve_mesh(mesh, data_shards)
    ref_path = api.use_ref(impl)
    n = plan.hash.n
    x, lead = api.flatten(jnp.asarray(h1v))
    B, S = x.shape
    xb = None
    if h1v_b is not None:
        xb, _ = api.flatten(jnp.asarray(h1v_b))
        if xb.shape != x.shape:
            raise ValueError(f"h1v_b shape {xb.shape} != h1v shape {x.shape}")
    if plan.needs_second_stream and xb is None:
        raise ValueError("plan contains a BloomSpec: the double-hashing "
                         "probe stride needs a second stream h1v_b")
    if xb is not None and not plan.needs_second_stream:
        raise ValueError("h1v_b given but no sketch in the plan consumes a "
                         "second hash stream")
    for name in (operands or {}):
        if "init" in (operands[name] or {}):
            raise ValueError(
                f"sketch {name!r}: do not pass 'init' to run_stream — the "
                f"stream carry supplies every sketch's state")
    # api.run's n_windows contract (count of valid windows) -> per-row
    # symbol budget: nw valid windows consume nw + n - 1 leading symbols
    nw = api.norm_windows(n_windows, B, max(0, S - n + 1))
    sym = jnp.where(nw > 0, nw + np.int32(n - 1), 0)
    state = init_state(plan, B, mesh=mesh, data_shards=data_shards)
    nc = max(1, -(-S // chunk_s))
    if n_chunks is not None:
        if n_chunks < nc:
            raise ValueError(f"n_chunks={n_chunks} < ceil(S/chunk_s)={nc}")
        nc = n_chunks

    if executor == "host":
        for c in range(nc):
            lo = c * chunk_s
            ck = x[:, lo : lo + chunk_s]
            ckb = xb[:, lo : lo + chunk_s] if xb is not None else None
            if ck.shape[1] < chunk_s:   # ragged tail: same compiled shape
                pad = chunk_s - ck.shape[1]
                ck = jnp.pad(ck, ((0, 0), (0, pad)))
                if ckb is not None:
                    ckb = jnp.pad(ckb, ((0, 0), (0, pad)))
            lengths = jnp.clip(sym - np.int32(lo), 0, np.int32(chunk_s))
            state = update(plan, state, ck, chunk_b=ckb, lengths=lengths,
                           operands=operands, impl=impl, donate=donate,
                           mesh=mesh, data_shards=data_shards, **tile_kw)
    elif executor == "grid":
        # one update over the whole stream: the fused kernel's sequence
        # grid is the chunk loop, scratch carried across steps
        tile_kw = dict(tile_kw)
        if "block_s" not in tile_kw and chunk_s >= max(n - 1, 8):
            tile_kw["block_s"] = chunk_s
        state = update(plan, state, x, chunk_b=xb, lengths=sym,
                       operands=operands, impl=impl, donate=donate,
                       mesh=mesh, data_shards=data_shards, **tile_kw)
    else:                               # "scan": one dispatch, loop inside
        pad = nc * chunk_s - S
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad)))
            if xb is not None:
                xb = jnp.pad(xb, ((0, 0), (0, pad)))
        operands_n = api._check_operands(plan, operands, None)
        Bp = state_batch(plan, state)
        lens = sym
        if B < Bp:        # shard padding rows: no symbols, carry untouched
            x = jnp.pad(x, ((0, Bp - B), (0, 0)))
            if xb is not None:
                xb = jnp.pad(xb, ((0, Bp - B), (0, 0)))
            lens = jnp.pad(lens, (0, Bp - B))
        tile = tuple(sorted(tile_kw.items()))
        fn = _scan_donated if _resolve_donate(donate) else _scan_plain
        _dispatched()
        state = fn(plan, ref_path, mesh, tile, nc, state, x, xb, lens,
                   operands_n)
    out = finalize(plan, state, batch=B)
    return api.shape_outputs(plan, out, lead)
