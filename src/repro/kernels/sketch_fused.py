"""Pallas TPU kernels: rolling n-gram hash with *fused sketch epilogues*,
driven by a :class:`repro.kernels.plan.SketchPlan`.

The unfused data-plane computes the full ``(B, S-n+1)`` window-hash array,
writes it to HBM, and then every sketch re-reads it — MinHash expands it
k=64x (one affine remix per signature lane), HLL re-reads it for the
gather/scatter-max register chain, the Bloom scan re-reads it twice (two
family draws). :func:`sketch_plan_fused` instead *reduces the hashes inside
the grid loop*: the rolling hash of each tile is computed **once** and
consumed immediately by every sketch epilogue the plan requests, and only
the small sketch states (a ``(k,)`` signature row, a register file, a
CountMin table) leave the chip. Bloom probes and wide CountMin tables are
the exception: they read the emitted window hashes in XLA (below).

Design (the grid-carried scratch-accumulator idiom):

* The grid is ``(B/block_b, S/block_s)`` exactly as in ``cyclic.py``; each
  step loads its tile plus an (n-1)-element halo from the next block —
  expressed as a second BlockSpec view of the same operand.
* The tile's window hashes are family-generic: CYCLIC unrolls constant
  rotations (O(L+n) bit-ops per element), GENERAL unrolls the clmul
  shift-reduce against trace-time ``x^k mod p(x)`` constants from
  ``kernels/general.py`` (O(Ln), the paper's bound) — same grid, same
  epilogues, so plans are family-generic.
* Each in-kernel sketch's state lives in its own VMEM ``scratch_shapes``
  buffer. TPU grids execute sequentially with the last grid dimension
  innermost, so for each batch block the sequence blocks ``j = 0..gs-1``
  arrive in order: the MinHash epilogue initialises its scratch at
  ``j == 0``, folds each tile with min, and flushes on the final block.
  The HLL registers and the CountMin table reduce across the *whole* grid
  (batch blocks too), so they initialise at the very first grid step and
  flush at the very last.
* Masking of padded windows: callers pass per-row valid-window counts
  (``n_windows``); a window whose global index falls at or beyond that count
  is *excluded from the reduction outright* — MinHash replaces its remixed
  values with the ``0xFFFFFFFF`` sentinel AFTER the affine step (pre-remix
  sentinel substitution would let ``a*SENTINEL+b`` undercut the true min),
  HLL, CountMin and Bloom zero the window's contribution. A padded row's
  sketch is therefore bit-identical to the unpadded document's and
  independent of bucket size. Rows padded up to the batch tile get
  ``n_windows = 0`` and are sliced off on return.
* The Theorem-1 discard is fused too: ``HashSpec.hash_mask`` keeps the low
  ``L-n+1`` bits inline (CYCLIC), so the full-width hash never exists
  outside a vector register. GENERAL keeps all L bits (pairwise independent
  as-is).
* Two-word lanes: past ``L = 32`` (CYCLIC only) a lane is two uint32
  words, low word first, and every lane array carries a leading word axis
  — the h1 input ``(2, B, S)`` in ``(2, block_b, block_s)`` blocks, the
  emitted hashes likewise. The tile's window formula rotates word pairs,
  the discard masks each word, the HLL rank counts trailing zeros across
  both words, and CountMin takes the vector multiply-add-shift over the
  key's pieces (``CountMinSpec.key_pieces``). The word count is static in
  ``HashSpec.L``, so one-word plans trace exactly the one-word code.

What the TPU compiler (Mosaic) accepts shapes every epilogue:

* MinHash takes its remix constants as SMEM scalars, so each of the k
  signature lanes is one dense ``(block_b, block_s)`` pass reduced over the
  window lanes. Mosaic has no unsigned min, so the minima are taken in the
  order-preserving signed view (flip the sign bit, bitcast to int32).
* HLL and in-kernel CountMin keep their buckets in a lane-aligned
  ``(rows, 128)`` layout (bucket ``i`` at ``(i // 128, i % 128)``) and
  histogram each tile row on the MXU: the contraction over the window
  lanes of a row one-hot against a lane one-hot counts the windows per
  bucket exactly (0/1 products, integer sums). CountMin weights the row
  one-hot by validity and adds; HLL stacks one row one-hot per rank value
  and keeps, per register, the largest rank with a nonzero count. Live
  tiles are ``(rows * ranks, block_s)`` and ``(128, block_s)``,
  independent of the batch tile; ``_resolve_block_s`` caps ``block_s`` so
  the HLL stack stays near 4 MB.
* Bloom filters are probed by a gather, and Mosaic has no gather from a
  VMEM filter; CountMin tables wider than ``2^in_kernel_max_log2_width``
  columns are faster as XLA's scatter-add than as a VMEM histogram, and
  HLL register files whose one-hot walk passes
  ``plan.HLL_IN_KERNEL_MAX_CELLS`` cells a window are faster as XLA's
  scatter-max. For all three, the kernel emits its masked window-hash
  tiles (and the second stream's, for Bloom's probe stride) and the probe,
  scatter-add or scatter-max runs *inside the same jit graph* — still one
  ``pallas_call`` per plan.
* Carries: the MinHash carry seeds its scratch at ``j == 0``; the HLL,
  CountMin and Bloom carries merge with their own operator (max, add, add)
  in the XLA epilogue, which is exact on integers.

The legacy single-sketch entry points (``cyclic_minhash_fused`` /
``cyclic_hll_fused`` / ``cyclic_bloom_fused``) are thin wrappers that build
a one-sketch plan — one implementation, bit-identical by construction.

This module is also the home of the *other* fused kernel,
:func:`cyclic_rolling_fused` (byte->fingerprint: one-hot MXU h1 lookup +
rolling CYCLIC window hash), folded in from the former
``kernels/cyclic_fused.py`` so there is exactly one fused-kernel module;
``repro.kernels.cyclic_fused`` remains as a deprecation shim.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.sketches import cms_column, hll_index_rank
from repro.kernels import ref as _kref
from repro.kernels.cyclic import _rotl_const, _window_words
from repro.kernels.general import _mul_const, _xpows_host
from repro.kernels.plan import (BloomSpec, CountMinSpec, HashSpec, HLLSpec,
                                MinHashSpec, SketchPlan)

_U32 = jnp.uint32
_SENTINEL = np.uint32(0xFFFFFFFF)
# flipping the sign bit maps uint32 order onto int32 order: Mosaic reduces
# and compares signed integers only, so uint32 minima go through this view
_SIGN = np.uint32(0x80000000)
_LANES = 128

# per-sketch default sequence tiles (a multi-sketch plan takes the min)
_BLOCK_S_DEFAULTS = {MinHashSpec: 1024, HLLSpec: 512, BloomSpec: 1024,
                     CountMinSpec: 512}


def _tile_window_hashes(x, halo_src, *, hs: HashSpec, block_s: int):
    """Rolling window hashes of one (block_b, block_s) tile, family-generic:
    CYCLIC unrolls constant rotations, GENERAL the clmul shift-reduce.
    Two-word lanes come and go as ``(lo, hi)`` word tiles."""
    n, L = hs.n, hs.L
    if hs.words == 2:
        return _window_words(*x, *halo_src, n=n, L=L, block_s=block_s)
    if n > 1:
        cat = jnp.concatenate([x, halo_src[:, : n - 1]], axis=1)
    else:
        cat = x
    acc = jnp.zeros_like(x)
    if hs.family == "cyclic":
        for k in range(n):
            acc = acc ^ _rotl_const(cat[:, k : k + block_s], (n - 1 - k) % L, L)
    else:
        xpow = _xpows_host(n, hs.p, L)
        for k in range(n):
            acc = acc ^ _mul_const(cat[:, k : k + block_s], xpow[n - 1 - k],
                                   hs.p, L)
    return acc


def _valid_mask(nw_col, ws_col, j, shape):
    """(block_b, block_s) bool: window's global index in the row's valid
    range ``[w_start, n_windows)`` (``ws_col=None`` means 0 — the
    non-streaming paths, where validity is a pure prefix)."""
    widx = j * shape[1] + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    ok = widx < nw_col
    if ws_col is not None:
        ok &= widx >= ws_col
    return ok


def _signed(u):
    """uint32 -> int32 with the same order (sign-bit flip, then bitcast)."""
    return jax.lax.bitcast_convert_type(u ^ _SIGN, jnp.int32)


def _unsigned(s):
    """Inverse of :func:`_signed`."""
    return jax.lax.bitcast_convert_type(s, _U32) ^ _SIGN


def _hist_rows(n_buckets: int) -> int:
    """Sublane rows of the lane-aligned (rows, 128) bucket layout: bucket i
    lives at (i // 128, i % 128); rows are padded to the 8-row tile."""
    return -(-max(1, n_buckets // _LANES) // 8) * 8


def _nt_dot(a, b):
    """(M, S) x (N, S) -> (M, N): contract the window (lane) axis of both
    one-hot factors on the MXU. Exact: 0/1 products, integer sums < 2^24."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _lo_onehot(idx_row):
    """(1, S) bucket ids -> (128, S) f32 one-hot of ``idx % 128``."""
    return ((idx_row & (_LANES - 1)) == jax.lax.broadcasted_iota(
        jnp.int32, (_LANES, idx_row.shape[1]), 0)).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Per-sketch tile epilogues (shared by every plan containing the sketch)
# ---------------------------------------------------------------------------


def _minhash_tile(h, valid, a_ref, b_ref, o_ref, acc_ref, j, init_ref=None):
    """Per-row running minima of the k affine remixes ``a[i]*h + b[i]``.

    The remix constants are SMEM scalars, so each signature lane is one
    dense (block_b, block_s) pass reduced over the window lanes; the minima
    live in the order-preserving signed view (Mosaic has no unsigned min).
    Invalid (padded) windows are excluded from the min entirely (post-remix
    sentinel substitution), so a padded row's signature is bit-identical
    to the unpadded one."""
    @pl.when(j == 0)
    def _init():
        # carry-in scratch init: a chunked/streaming caller seeds the
        # accumulator with its running state instead of the identity, so
        # the grid reduction continues the stream's min exactly
        acc_ref[...] = _signed(jnp.full(acc_ref.shape, _SENTINEL, _U32)
                               if init_ref is None else init_ref[...])

    k = acc_ref.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, acc_ref.shape, 1)
    cand = acc_ref[...]
    for i in range(k):
        mixed = jnp.where(valid, a_ref[i] * h + b_ref[i], _SENTINEL)
        col = jnp.min(_signed(mixed), axis=1, keepdims=True)
        cand = jnp.where(lane == i, jnp.minimum(cand, col), cand)
    acc_ref[...] = cand

    @pl.when(j == pl.num_programs(1) - 1)
    def _flush():
        o_ref[...] = _unsigned(acc_ref[...])


def _hll_tile(h, valid, b: int, rank_bits: int, acc_ref, bi, j):
    """HLL register maxima in the lane-aligned (rows, 128) layout.

    Per tile row, one MXU contraction over the window lanes counts the
    windows of every (rank, register) pair: the left factor stacks one
    register-row one-hot per rank value, the right factor is the register
    lane one-hot. A register's new value is the largest rank with a
    nonzero count. The registers reduce across the WHOLE grid (batch
    blocks too); the flush writes them at the last grid step and the
    wrapper merges any carry-in with max, outside the kernel."""
    @pl.when((bi == 0) & (j == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    rows = acc_ref.shape[0]
    n_rank = rank_bits + 1
    idx, rank = hll_index_rank(h, b, rank_bits)
    rank = jnp.where(valid, rank, 0)                    # rank 0 never wins
    S = valid.shape[1]
    stack = jax.lax.broadcasted_iota(jnp.int32, (n_rank * rows, S), 0)
    reg_row, rank_of = stack % rows, stack // rows + 1
    rank_col = (jax.lax.broadcasted_iota(jnp.int32, (n_rank * rows, _LANES), 0)
                // rows + 1)
    acc = acc_ref[...]
    for r in range(valid.shape[0]):
        ir, rr = idx[r : r + 1], rank[r : r + 1]
        left = (((ir >> 7) == reg_row) & (rr == rank_of)).astype(jnp.float32)
        seen = jnp.where(_nt_dot(left, _lo_onehot(ir)) > 0, rank_col, 0)
        acc = jnp.maximum(acc, jnp.max(seen.reshape(n_rank, rows, _LANES),
                                       axis=0))
    acc_ref[...] = acc


def _cms_tile(h, valid, a_ref, b_ref, log2_width: int, pieces: int, acc_ref,
              bi, j):
    """Depth-major in-kernel CountMin histogram in the lane-aligned
    (depth*rows, 128) layout: per tile row and table row d, one MXU
    contraction of the column's row one-hot (weighted by validity) against
    its lane one-hot adds the tile's counts. Counts are additive, so the
    table reduces across the WHOLE grid; any carry-in table is added by the
    wrapper, outside the kernel. Invalid (padded) windows add 0."""
    @pl.when((bi == 0) & (j == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    depth = b_ref.shape[0]
    rows = acc_ref.shape[0] // depth
    vf = valid.astype(jnp.float32)
    reg_row = jax.lax.broadcasted_iota(jnp.int32, (rows, valid.shape[1]), 0)
    for d in range(depth):
        # two-word keys' (depth, pieces) constants ride flat in SMEM
        a_d = (a_ref[d] if not pieces else
               [a_ref[d * pieces + i] for i in range(pieces)])
        cols = cms_column(h, a_d, b_ref[d], log2_width)
        part = jnp.zeros((rows, _LANES), jnp.float32)
        for r in range(valid.shape[0]):
            cr = cols[r : r + 1]
            left = ((cr >> 7) == reg_row).astype(jnp.float32) * vf[r : r + 1]
            part = part + _nt_dot(left, _lo_onehot(cr))
        sl = slice(d * rows, (d + 1) * rows)
        acc_ref[sl, :] = acc_ref[sl, :] + part.astype(jnp.int32)


# ---------------------------------------------------------------------------
# The plan kernel: one rolling-hash tile, every requested epilogue
# ---------------------------------------------------------------------------


def _in_kernel(spec, hs: HashSpec) -> bool:
    """Sketches reduced inside the kernel; the others (Bloom, wide CountMin,
    HLL past its one-hot budget) consume the emitted window hashes in the
    XLA epilogue."""
    if isinstance(spec, HLLSpec):
        return spec.use_in_kernel(hs)
    return isinstance(spec, MinHashSpec) or (
        isinstance(spec, CountMinSpec) and spec.use_in_kernel)


def _plan_kernel(*refs, plan: SketchPlan, block_s: int, has_ws: bool,
                 has_init: bool, emit_h: bool):
    """Refs: x, halo[, xb, xb halo], n_windows[, w_start], then per
    in-kernel sketch its operands (MinHash a, b[, init]; CountMin a, b);
    outputs: one per in-kernel sketch, then the emitted h[, hb] tiles;
    scratch: one accumulator per in-kernel sketch."""
    hs = plan.hash
    specs = [spec for _, spec in plan.sketches if _in_kernel(spec, hs)]
    needs_b = plan.needs_second_stream
    it = iter(refs)
    x_ref, xh_ref = next(it), next(it)
    if needs_b:
        xb_ref, xbh_ref = next(it), next(it)
    nw_ref = next(it)
    ws_ref = next(it) if has_ws else None
    op_refs = []
    for spec in specs:
        n_ops = {MinHashSpec: 2 + int(has_init), HLLSpec: 0,
                 CountMinSpec: 2}[type(spec)]
        op_refs.append([next(it) for _ in range(n_ops)])
    out_refs = [next(it) for _ in specs]
    h_ref = next(it) if emit_h else None
    hb_ref = next(it) if needs_b else None
    acc_refs = [next(it) for _ in specs]

    bi, j = pl.program_id(0), pl.program_id(1)
    if hs.words == 2:
        x, halo = (x_ref[0], x_ref[1]), (xh_ref[0], xh_ref[1])
        shape = x[0].shape
        # ONE rolling-hash evaluation per tile, the discard per word
        h = tuple(hw & np.uint32(m) for hw, m in zip(
            _tile_window_hashes(x, halo, hs=hs, block_s=block_s),
            hs.word_masks))
    else:
        x = x_ref[...]
        shape = x.shape
        mask = np.uint32(hs.hash_mask)
        # ONE rolling-hash evaluation per tile, shared by every epilogue below
        h = _tile_window_hashes(x, xh_ref[...], hs=hs, block_s=block_s) & mask
    valid = _valid_mask(nw_ref[...], ws_ref[...] if has_ws else None, j,
                        shape)
    if emit_h:
        if hs.words == 2:
            h_ref[0], h_ref[1] = h
        else:
            h_ref[...] = h
    if needs_b:
        hb_ref[...] = _tile_window_hashes(xb_ref[...], xbh_ref[...], hs=hs,
                                          block_s=block_s) & mask

    for spec, o_ref, acc_ref, oprs in zip(specs, out_refs, acc_refs, op_refs):
        if isinstance(spec, MinHashSpec):
            _minhash_tile(h, valid, oprs[0], oprs[1], o_ref, acc_ref, j,
                          oprs[2] if has_init else None)
            continue
        if isinstance(spec, HLLSpec):
            _hll_tile(h, valid, spec.b, spec.resolve_rank_bits(hs), acc_ref,
                      bi, j)
        else:
            _cms_tile(h, valid, oprs[0], oprs[1], spec.log2_width,
                      spec.key_pieces(hs), acc_ref, bi, j)

        @pl.when((bi == pl.num_programs(0) - 1)
                 & (j == pl.num_programs(1) - 1))
        def _flush(o_ref=o_ref, acc_ref=acc_ref):
            o_ref[...] = acc_ref[...]


def _budget_cap(rows: int, n: int) -> int:
    """Largest pow2 block_s keeping a (rows, block_s) 4-byte tile within
    ~4 MB of VMEM (the halo still sets a floor)."""
    cap = max(32, (4 << 20) // (4 * rows))
    cap = 1 << int(np.floor(np.log2(cap)))
    if n > 1 and n - 1 > cap:
        cap = 1 << int(np.ceil(np.log2(n - 1)))
    return cap


def _resolve_block_s(plan: SketchPlan, S: int, block_s):
    """Sequence-tile width honouring every requested sketch's VMEM budget,
    rounded up to whole 128-lane vregs: Mosaic refuses blocks whose last
    dimension is not a multiple of 128, and the interpreter runs the same
    tiles the chip compiles."""
    if block_s is None:
        block_s = min(_BLOCK_S_DEFAULTS[type(spec)]
                      for _, spec in plan.sketches)
    block_s = min(block_s, max(256, 1 << int(np.ceil(np.log2(max(S, 1))))))
    n = plan.hash.n
    for _, spec in plan.sketches:
        if isinstance(spec, HLLSpec) and spec.use_in_kernel(plan.hash):
            # the HLL walk's (ranks * rows, block_s) stacked one-hot
            stacked = ((spec.resolve_rank_bits(plan.hash) + 1)
                       * _hist_rows(1 << spec.b))
            block_s = min(block_s, _budget_cap(stacked, n))
    block_s = -(-block_s // _LANES) * _LANES
    if n - 1 > block_s:
        raise ValueError(f"halo n-1={n-1} exceeds block_s={block_s}")
    return block_s


@functools.partial(jax.jit, static_argnames=("plan", "block_b", "block_s",
                                             "interpret"))
def sketch_plan_fused(h1v: jnp.ndarray, h1v_b, n_windows: jnp.ndarray,
                      operands, *, plan: SketchPlan, w_start=None,
                      block_b: int = 8, block_s: int = None,
                      interpret: bool = False) -> dict:
    """Execute every sketch in ``plan`` in ONE rolling-hash device pass.

    h1v (B, S) uint32 — (2, B, S) two-word lanes past L = 32 — h1v_b
    (B, S) or None (required iff the plan holds a BloomSpec), n_windows
    (B,) int32, operands {sketch_name: {operand: array}} -> {sketch_name:
    result} with MinHash (B, k) uint32, HLL (2^b,) int32 (reduced over the
    whole batch; in VMEM scratch while ``HLLSpec.use_in_kernel``,
    else scatter-maxed from kernel-emitted hashes in the same jit graph),
    Bloom (B,) int32 hit counts, CountMin (depth, 2^log2_width) int32 batch
    partial counts (in VMEM scratch up to the spec's
    ``in_kernel_max_log2_width``; wider tables are scatter-added from
    kernel-emitted hashes in the same jit graph).

    A sketch's optional ``init`` operand (its ``state_struct`` shape)
    continues a running state instead of starting from the identity: the
    MinHash carry seeds the kernel's scratch at the first grid step, the
    corpus-level carries (HLL, CountMin) and the Bloom counts merge with
    their own operator in the XLA epilogue — which is what makes the
    chunked streaming executor bit-exact. ``w_start`` (B,) int32
    optionally sets the per-row FIRST valid window (the mask becomes the
    range ``[w_start, n_windows)``), masking windows that would span a
    stream chunk's zero-filled pre-history.
    """
    hs = plan.hash
    wide = hs.words == 2
    assert h1v.ndim == 1 + hs.words and (not wide or h1v.shape[0] == 2)
    B, S = h1v.shape[-2:]
    assert n_windows.shape == (B,)
    block_s = _resolve_block_s(plan, S, block_s)
    Bp = -(-B // block_b) * block_b
    Sp = -(-S // block_s) * block_s
    x = jnp.pad(h1v.astype(_U32), ((0, 0),) * wide + ((0, Bp - B), (0, Sp - S)))
    nw = jnp.pad(n_windows.astype(jnp.int32), (0, Bp - B))[:, None]
    grid = (Bp // block_b, Sp // block_s)
    nsb = grid[1]
    has_ws = w_start is not None
    operands = operands or {}

    tile = pl.BlockSpec((block_b, block_s), lambda bi, j: (bi, j),
                        memory_space=pltpu.VMEM)
    halo = pl.BlockSpec((block_b, block_s),
                        lambda bi, j, _n=nsb: (bi, jnp.minimum(j + 1, _n - 1)),
                        memory_space=pltpu.VMEM)
    # lane tiles: two-word lanes take (2, block_b, block_s) blocks
    lane_tile, lane_halo = tile, halo
    if wide:
        lane_tile = pl.BlockSpec((2, block_b, block_s),
                                 lambda bi, j: (0, bi, j),
                                 memory_space=pltpu.VMEM)
        lane_halo = pl.BlockSpec(
            (2, block_b, block_s),
            lambda bi, j, _n=nsb: (0, bi, jnp.minimum(j + 1, _n - 1)),
            memory_space=pltpu.VMEM)
    row = lambda w: pl.BlockSpec((block_b, w), lambda bi, j: (bi, 0),
                                 memory_space=pltpu.VMEM)
    whole = lambda shape: pl.BlockSpec(shape, lambda bi, j: (0, 0),
                                       memory_space=pltpu.VMEM)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    in_specs, inputs = [lane_tile, lane_halo], [x, x]
    if plan.needs_second_stream:
        assert h1v_b is not None and h1v_b.shape == h1v.shape, \
            "plans with a BloomSpec need a second hash stream h1v_b"
        xb = jnp.pad(h1v_b.astype(_U32), ((0, Bp - B), (0, Sp - S)))
        in_specs += [tile, halo]
        inputs += [xb, xb]
    in_specs.append(row(1))
    inputs.append(nw)
    ws = None
    if has_ws:
        assert w_start.shape == (B,)
        ws = jnp.pad(w_start.astype(jnp.int32), (0, Bp - B))[:, None]
        in_specs.append(row(1))
        inputs.append(ws)

    # the MinHash carry rides into the kernel (row state, seeded at j == 0);
    # every other carry merges in the XLA epilogue below
    has_init = any("init" in operands.get(name, {})
                   for name, spec in plan.sketches
                   if isinstance(spec, MinHashSpec))
    out_specs, out_shapes, scratches = [], [], []
    for name, spec in plan.sketches:
        ops_nm = operands.get(name, {})
        if isinstance(spec, MinHashSpec):
            in_specs += [smem, smem]
            inputs += [ops_nm["a"].astype(_U32), ops_nm["b"].astype(_U32)]
            if has_init:
                init = ops_nm.get("init")
                init = (jnp.full((B, spec.k), _SENTINEL, _U32) if init is None
                        else init.astype(_U32))
                in_specs.append(row(spec.k))
                inputs.append(jnp.pad(init, ((0, Bp - B), (0, 0))))
            out_specs.append(row(spec.k))
            out_shapes.append(jax.ShapeDtypeStruct((Bp, spec.k), _U32))
            scratches.append(pltpu.VMEM((block_b, spec.k), jnp.int32))
        elif isinstance(spec, HLLSpec):
            if not _in_kernel(spec, hs):
                continue
            shape = (_hist_rows(1 << spec.b), _LANES)
            out_specs.append(whole(shape))
            out_shapes.append(jax.ShapeDtypeStruct(shape, jnp.int32))
            scratches.append(pltpu.VMEM(shape, jnp.int32))
        elif _in_kernel(spec, hs):                   # CountMin in VMEM
            in_specs += [smem, smem]
            # two-word keys' (depth, pieces) constants ride flat in SMEM
            inputs += [ops_nm["a"].astype(_U32).reshape(-1),
                       ops_nm["b"].astype(_U32)]
            shape = (spec.depth * _hist_rows(spec.width), _LANES)
            out_specs.append(whole(shape))
            out_shapes.append(jax.ShapeDtypeStruct(shape, jnp.int32))
            scratches.append(pltpu.VMEM(shape, jnp.int32))
    # Bloom probes, wide CountMin tables and costly HLL walks consume the
    # tile's masked window hashes in XLA (Mosaic has no gather from a VMEM
    # filter, and XLA's scatter-add and scatter-max beat a VMEM histogram
    # there): the kernel emits them, the one plan output that round-trips
    # hashes through HBM
    emit_h = not all(_in_kernel(spec, hs) for _, spec in plan.sketches)
    if emit_h:
        out_specs.append(lane_tile)
        out_shapes.append(jax.ShapeDtypeStruct(x.shape, _U32))
    if plan.needs_second_stream:
        out_specs.append(tile)
        out_shapes.append(jax.ShapeDtypeStruct((Bp, Sp), _U32))

    outs = list(pl.pallas_call(
        functools.partial(_plan_kernel, plan=plan, block_s=block_s,
                          has_ws=has_ws, has_init=has_init, emit_h=emit_h),
        name="sketch_plan",
        grid=grid,
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        out_shape=tuple(out_shapes),
        scratch_shapes=scratches,
        interpret=interpret,
    )(*inputs))

    hb_out = outs.pop() if plan.needs_second_stream else None
    h_out = outs.pop() if emit_h else None
    if h_out is not None:
        # only the caller's rows and the S-n+1 columns a window can start
        # at are ever valid; validity re-derived from n_windows exactly as
        # in-kernel (out-of-range columns are >= nw)
        W = S - hs.n + 1
        h_out = h_out[..., :B, :W]
        hb_out = None if hb_out is None else hb_out[:B, :W]
        idx = jnp.arange(W, dtype=jnp.int32)
        valid = idx[None, :] < nw[:B]
        if has_ws:
            valid &= idx[None, :] >= ws[:B]
    kernel_outs = iter(outs)
    results = {}
    for name, spec in plan.sketches:
        ops_nm = operands.get(name, {})
        init = ops_nm.get("init")
        if isinstance(spec, MinHashSpec):
            results[name] = next(kernel_outs)[:B]
        elif isinstance(spec, HLLSpec):
            init = None if init is None else init.astype(jnp.int32)
            if _in_kernel(spec, hs):
                regs = next(kernel_outs).reshape(-1)[: 1 << spec.b]
                results[name] = (regs if init is None
                                 else jnp.maximum(regs, init))
            else:
                with jax.named_scope("sketch.hll.scatter"):
                    results[name] = _kref._hll_reduce(
                        h_out, valid, spec.b, spec.resolve_rank_bits(hs),
                        init=init)
        elif isinstance(spec, CountMinSpec):
            init = None if init is None else init.astype(jnp.int32)
            if spec.use_in_kernel:
                table = next(kernel_outs).reshape(spec.depth, -1)
                table = table[:, : spec.width]
                results[name] = table if init is None else table + init
            else:
                with jax.named_scope("sketch.cms.scatter"):
                    results[name] = _kref.cms_reduce(
                        h_out, valid, ops_nm["a"].astype(_U32),
                        ops_nm["b"].astype(_U32), spec.log2_width,
                        init=init)
        else:
            hits = _kref._bloom_reduce(h_out, hb_out, valid,
                                       ops_nm["bits"].astype(_U32), spec.k,
                                       spec.log2_m)[:B]
            results[name] = (hits if init is None
                             else hits + init.astype(jnp.int32))
    return results


# ---------------------------------------------------------------------------
# Legacy single-sketch entry points — one-sketch plans over the same kernel
# ---------------------------------------------------------------------------


def _legacy_hash_spec(n: int, L: int, hash_mask: int) -> HashSpec:
    """Map a legacy raw ``hash_mask`` back onto the declarative discard flag.

    Window hashes already fit in L bits, so the legacy default mask
    0xFFFFFFFF is a no-op AND for any L — same bits as ``discard=False``.
    """
    if hash_mask == (1 << (L - n + 1)) - 1:
        return HashSpec(family="cyclic", n=n, L=L, discard=True)
    if hash_mask in ((1 << L) - 1, 0xFFFFFFFF):
        return HashSpec(family="cyclic", n=n, L=L, discard=False)
    raise ValueError(
        f"hash_mask {hash_mask:#x} matches neither the Theorem-1 discard "
        f"mask nor the full width for n={n}, L={L}")


def cyclic_minhash_fused(h1v: jnp.ndarray, n_windows: jnp.ndarray,
                         a: jnp.ndarray, b: jnp.ndarray, *, n: int,
                         L: int = 32, hash_mask: int = 0xFFFFFFFF,
                         block_b: int = 8, block_s: int = 512,
                         interpret: bool = False) -> jnp.ndarray:
    """h1v (B, S) uint32, n_windows (B,) int32, a/b (k,) -> (B, k) uint32."""
    plan = SketchPlan(_legacy_hash_spec(n, L, hash_mask),
                      (("minhash", MinHashSpec(k=int(a.shape[0]))),))
    return sketch_plan_fused(h1v, None, n_windows,
                             {"minhash": {"a": a, "b": b}}, plan=plan,
                             block_b=block_b, block_s=block_s,
                             interpret=interpret)["minhash"]


def cyclic_hll_fused(h1v: jnp.ndarray, n_windows: jnp.ndarray, *, n: int,
                     b: int, rank_bits: int, L: int = 32,
                     hash_mask: int = 0xFFFFFFFF, block_b: int = 8,
                     block_s: int = 256, interpret: bool = False) -> jnp.ndarray:
    """h1v (B, S) uint32, n_windows (B,) int32 -> (2^b,) int32 registers."""
    plan = SketchPlan(_legacy_hash_spec(n, L, hash_mask),
                      (("hll", HLLSpec(b=b, rank_bits=rank_bits)),))
    return sketch_plan_fused(h1v, None, n_windows, {}, plan=plan,
                             block_b=block_b, block_s=block_s,
                             interpret=interpret)["hll"]


def cyclic_bloom_fused(h1va: jnp.ndarray, h1vb: jnp.ndarray,
                       n_windows: jnp.ndarray, bits: jnp.ndarray, *, n: int,
                       k: int, log2_m: int, L: int = 32,
                       hash_mask: int = 0xFFFFFFFF, block_b: int = 8,
                       block_s: int = 1024, interpret: bool = False) -> jnp.ndarray:
    """Two h1v draws (B, S) + packed filter (2^log2_m/32,) -> (B,) int32
    counts of valid windows whose double-hashed probes all hit."""
    assert bits.shape == (1 << (log2_m - 5),)
    plan = SketchPlan(_legacy_hash_spec(n, L, hash_mask),
                      (("bloom", BloomSpec(k=k, log2_m=log2_m)),))
    return sketch_plan_fused(h1va, h1vb, n_windows,
                             {"bloom": {"bits": bits}}, plan=plan,
                             block_b=block_b, block_s=block_s,
                             interpret=interpret)["bloom"]


# ---------------------------------------------------------------------------
# Fused byte->fingerprint kernel (h1 lookup + rolling CYCLIC hash), folded in
# from the former kernels/cyclic_fused.py
# ---------------------------------------------------------------------------
#
# The paper's inner loop is `h1[c]` — an L1 table lookup on a CPU. TPUs have
# no cheap per-lane gather, but they have an idle MXU during this
# memory-bound pass, so we ADAPT: the 256-entry table lookup becomes a
# one-hot matmul. The uint32 table is split into two 16-bit halves (exactly
# representable in f32), the one-hot (T x 256) activation matrix hits the
# MXU once per half, and the halves are reassembled with integer ops. The
# rolling window XOR then proceeds exactly as in `cyclic.py` — the entire
# byte->fingerprint path stays in one VMEM-resident kernel: tokens in,
# window hashes out.

SIGMA = 256  # byte alphabet


def _lookup_mxu(tokens, table_lo, table_hi):
    """Per-lane gather via one-hot MXU matmul: values < 2^16 are f32-exact."""
    flat = tokens.reshape(-1)                          # (T,)
    onehot = (flat[:, None] == jax.lax.broadcasted_iota(
        jnp.int32, (flat.shape[0], SIGMA), 1)).astype(jnp.float32)
    lo = jax.lax.dot(onehot, table_lo[:, None], precision="highest",
                     preferred_element_type=jnp.float32)
    hi = jax.lax.dot(onehot, table_hi[:, None], precision="highest",
                     preferred_element_type=jnp.float32)
    v = lo[:, 0].astype(_U32) | (hi[:, 0].astype(_U32) << np.uint32(16))
    return v.reshape(tokens.shape)


def _lookup_fused_kernel(tok_ref, nxt_ref, tlo_ref, thi_ref, o_ref, *, n: int,
                         L: int, block_s: int):
    toks = tok_ref[...]
    if n > 1:
        cat = jnp.concatenate([toks, nxt_ref[...][:, : n - 1]], axis=1)
    else:
        cat = toks
    v = _lookup_mxu(cat, tlo_ref[...], thi_ref[...])
    m = np.uint32((1 << L) - 1) if L < 32 else np.uint32(0xFFFFFFFF)
    v = v & m
    acc = jnp.zeros_like(toks, dtype=_U32)
    for k in range(n):
        acc = acc ^ _rotl_const(v[:, k : k + block_s], (n - 1 - k) % L, L)
    o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("n", "L", "block_b", "block_s",
                                             "interpret"))
def cyclic_rolling_fused(tokens: jnp.ndarray, table: jnp.ndarray, *, n: int,
                         L: int = 32, block_b: int = 8, block_s: int = 1024,
                         interpret: bool = False) -> jnp.ndarray:
    """Fused byte->fingerprint pipeline. tokens (B, S) int32 in [0, 256),
    table (256,) uint32 -> (B, S-n+1) uint32."""
    assert tokens.ndim == 2
    assert table.shape == (SIGMA,)
    B, S = tokens.shape
    block_s = min(block_s, max(256, 1 << int(np.ceil(np.log2(max(S, 1))))))
    if n - 1 > block_s:
        raise ValueError(f"halo n-1={n-1} exceeds block_s={block_s}")
    Bp = -(-B // block_b) * block_b
    Sp = -(-S // block_s) * block_s
    t = jnp.pad(tokens.astype(jnp.int32), ((0, Bp - B), (0, Sp - S)))
    table_lo = (table & np.uint32(0xFFFF)).astype(jnp.float32)
    table_hi = (table >> np.uint32(16)).astype(jnp.float32)
    grid = (Bp // block_b, Sp // block_s)
    nsb = grid[1]

    out = pl.pallas_call(
        functools.partial(_lookup_fused_kernel, n=n, L=L, block_s=block_s),
        name="lookup_fused",
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, block_s), lambda b, j: (b, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_b, block_s),
                         lambda b, j, _n=nsb: (b, jnp.minimum(j + 1, _n - 1)),
                         memory_space=pltpu.VMEM),
            # the 1 KiB table is resident in VMEM for every grid step
            pl.BlockSpec((SIGMA,), lambda b, j: (0,), memory_space=pltpu.VMEM),
            pl.BlockSpec((SIGMA,), lambda b, j: (0,), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((block_b, block_s), lambda b, j: (b, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((Bp, Sp), _U32),
        interpret=interpret,
    )(t, t, table_lo, table_hi)
    return out[:B, : S - n + 1]
