"""Pallas TPU kernel: rolling CYCLIC n-gram hash (paper Algorithm 4, TPU form).

The paper's recursive update (1 rotate + 2 XOR per character, *serial*) is
re-expressed for the VPU as either

* ``direct`` — the window formula ``H_j = XOR_k rotl(v_{j+k}, n-1-k)``:
  n rotate+XOR steps, each fully vectorized across an (8×128)-lane tile; or
* ``prefix`` — the parallel-prefix form (DESIGN.md §3): a Hillis–Steele XOR
  scan across the tile (log2(T) steps) followed by a two-point combine. Wins
  once n outgrows log2(tile).

Tiling: the sequence axis is cut into ``block_s`` chunks; each grid step loads
its chunk plus an (n-1)-element halo from the *next* chunk — expressed as a
second BlockSpec view of the same operand, offset by one block — into VMEM.
All compute is uint32 bitwise ops on VMEM tiles.

Past ``L = 32`` a lane is two uint32 words (x64 stays off): the input and
output carry a leading word axis, ``(2, B, S)`` low word first, the blocks
are ``(2, block_b, block_s)``, and the window formula rotates word pairs
(``core.gf2.rotl_words``). The word count is static in ``L``: one-word lanes
trace exactly the one-word code.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.gf2 import rotl_words

_U32 = jnp.uint32


def _rotl_const(v, r: int, L: int):
    r %= L
    m = np.uint32((1 << L) - 1) if L < 32 else np.uint32(0xFFFFFFFF)
    v = v & m
    if r == 0:
        return v
    return ((v << np.uint32(r)) | (v >> np.uint32(L - r))) & m


def _rotl_var(v, r, L: int):
    """Rotate-left by per-lane amounts r (traced)."""
    m = np.uint32((1 << L) - 1) if L < 32 else np.uint32(0xFFFFFFFF)
    v = v & m
    r = r % np.uint32(L)
    left = (v << r) & m
    right = jnp.where(r == 0, jnp.zeros_like(v), (v & m) >> (np.uint32(L) - r))
    return left | right


def _window_words(lo, hi, halo_lo, halo_hi, *, n: int, L: int,
                  block_s: int):
    """The direct window formula on (block_b, block_s) two-word tiles, the
    next tile's first n - 1 columns as halo: XOR_k rotl(h1[j + k], n-1-k)
    on word pairs. Returns the (lo, hi) hash tiles."""
    shape = lo.shape
    if n > 1:
        lo = jnp.concatenate([lo, halo_lo[:, : n - 1]], axis=1)
        hi = jnp.concatenate([hi, halo_hi[:, : n - 1]], axis=1)
    acc_lo = jnp.zeros(shape, _U32)
    acc_hi = jnp.zeros(shape, _U32)
    for k in range(n):
        rl, rh = rotl_words(lo[:, k : k + block_s], hi[:, k : k + block_s],
                            n - 1 - k, L)
        acc_lo, acc_hi = acc_lo ^ rl, acc_hi ^ rh
    return acc_lo, acc_hi


def _cyclic_words_kernel(x_ref, nxt_ref, o_ref, *, n: int, L: int,
                         block_s: int):
    """:func:`_window_words` on (2, block_b, block_s) blocks."""
    o_ref[0], o_ref[1] = _window_words(x_ref[0], x_ref[1], nxt_ref[0],
                                       nxt_ref[1], n=n, L=L, block_s=block_s)


def _cyclic_kernel(x_ref, nxt_ref, o_ref, *, n: int, L: int, block_s: int,
                   mode: str):
    x = x_ref[...]            # (block_b, block_s)
    if n > 1:
        halo = nxt_ref[...][:, : n - 1]
        cat = jnp.concatenate([x, halo], axis=1)      # (block_b, T)
    else:
        cat = x
    if mode == "direct":
        acc = jnp.zeros_like(x)
        for k in range(n):
            acc = acc ^ _rotl_const(cat[:, k : k + block_s], (n - 1 - k) % L, L)
        o_ref[...] = acc
    else:  # prefix (Hillis–Steele XOR scan, then two-point combine)
        j = pl.program_id(1)
        T = cat.shape[1]
        # absolute element index of each lane in the stream
        base = (j * block_s).astype(_U32)
        idx = base + jax.lax.broadcasted_iota(_U32, cat.shape, 1)
        P = _rotl_var(cat, (np.uint32(L) - idx % np.uint32(L)) % np.uint32(L), L)
        # inclusive prefix XOR across the tile
        X = P
        d = 1
        while d < T:
            shifted = jnp.pad(X, ((0, 0), (d, 0)))[:, :T]
            X = X ^ shifted
            d *= 2
        # W_j = X[j+n-1] ^ X[j-1]; local window w needs X[w+n-1] and X[w-1]
        hi = X[:, n - 1 : n - 1 + block_s]
        lo = jnp.pad(X, ((0, 0), (1, 0)))[:, :T][:, :block_s]
        W = hi ^ lo
        # final rotation by (global_window + n - 1) mod L
        widx = base + jax.lax.broadcasted_iota(_U32, W.shape, 1) + np.uint32(n - 1)
        o_ref[...] = _rotl_var(W, widx % np.uint32(L), L)


@functools.partial(jax.jit, static_argnames=("n", "L", "block_b", "block_s",
                                             "mode", "interpret"))
def cyclic_rolling(h1v: jnp.ndarray, *, n: int, L: int = 32,
                   block_b: int = 8, block_s: int = 2048,
                   mode: str = "auto", interpret: bool = False) -> jnp.ndarray:
    """Rolling CYCLIC hash of every n-window. (B, S) uint32 -> (B, S-n+1);
    past L = 32, (2, B, S) two-word lanes -> (2, B, S-n+1).

    ``mode='auto'`` picks ``direct`` for small n and ``prefix`` once the
    window outgrows the scan depth (n > log2(block_s)+4). Two-word lanes
    take ``direct`` only (the prefix form rotates by per-lane amounts).
    """
    if L > 32:
        return _cyclic_words(h1v, n=n, L=L, block_b=block_b, block_s=block_s,
                             mode=mode, interpret=interpret)
    assert h1v.ndim == 2, "use ops.cyclic (handles reshaping)"
    B, S = h1v.shape
    if mode == "auto":
        mode = "direct" if n <= 24 else "prefix"
    block_s = min(block_s, max(256, 1 << int(np.ceil(np.log2(max(S, 1))))))
    if n - 1 > block_s:
        raise ValueError(f"halo n-1={n-1} exceeds block_s={block_s}")
    # pad to full tiles
    Bp = -(-B // block_b) * block_b
    Sp = -(-S // block_s) * block_s
    x = jnp.pad(h1v.astype(_U32), ((0, Bp - B), (0, Sp - S)))
    grid = (Bp // block_b, Sp // block_s)
    nsb = grid[1]

    out = pl.pallas_call(
        functools.partial(_cyclic_kernel, n=n, L=L, block_s=block_s, mode=mode),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, block_s), lambda b, j: (b, j),
                         memory_space=pltpu.VMEM),
            # halo view: same operand, shifted one block (clamped at the tail
            # where the halo is never consumed by a valid window)
            pl.BlockSpec((block_b, block_s),
                         lambda b, j, _n=nsb: (b, jnp.minimum(j + 1, _n - 1)),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((block_b, block_s), lambda b, j: (b, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((Bp, Sp), _U32),
        interpret=interpret,
    )(x, x)
    return out[:B, : S - n + 1]


def _cyclic_words(h1v, *, n: int, L: int, block_b: int, block_s: int,
                  mode: str, interpret: bool):
    """:func:`cyclic_rolling` on (2, B, S) two-word lanes."""
    assert h1v.ndim == 3 and h1v.shape[0] == 2, \
        "two-word lanes are (2, B, S); use ops.cyclic (handles reshaping)"
    if mode not in ("auto", "direct"):
        raise ValueError(f"two-word lanes (L={L}) take mode='direct', "
                         f"got {mode!r}")
    _, B, S = h1v.shape
    block_s = min(block_s, max(256, 1 << int(np.ceil(np.log2(max(S, 1))))))
    if n - 1 > block_s:
        raise ValueError(f"halo n-1={n-1} exceeds block_s={block_s}")
    Bp = -(-B // block_b) * block_b
    Sp = -(-S // block_s) * block_s
    x = jnp.pad(h1v.astype(_U32), ((0, 0), (0, Bp - B), (0, Sp - S)))
    grid = (Bp // block_b, Sp // block_s)
    nsb = grid[1]
    blk = (2, block_b, block_s)
    out = pl.pallas_call(
        functools.partial(_cyclic_words_kernel, n=n, L=L, block_s=block_s),
        grid=grid,
        in_specs=[
            pl.BlockSpec(blk, lambda b, j: (0, b, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec(blk,
                         lambda b, j, _n=nsb: (0, b, jnp.minimum(j + 1, _n - 1)),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(blk, lambda b, j: (0, b, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((2, Bp, Sp), _U32),
        interpret=interpret,
    )(x, x)
    return out[:, :B, : S - n + 1]
