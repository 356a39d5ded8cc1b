"""Multi-device execution of a :class:`~repro.kernels.plan.SketchPlan`.

The sketches this engine runs are *mergeable reductions* (Lemire & Kaser,
"One-Pass, One-Hash n-Gram Statistics Estimation"): a MinHash signature row
and a Bloom hit count depend only on their own document's windows, and an
HLL register file merges by elementwise max. That makes the whole
hash->sketch data-plane embarrassingly parallel over documents with a tiny
combine step — so :func:`run_sharded` is just :func:`repro.kernels.api.run`
wrapped in ``shard_map`` over the batch dimension of a 1-D ``data`` mesh:

* the (B, S) h1v batch (and the second Bloom stream) is row-sharded,
* sketch operands (MinHash remix lanes, the packed Bloom filter, the
  CountMin row constants) are replicated,
* MinHash signatures and Bloom counts come back row-sharded (no combine),
* the HLL register file gets a single ``pmax`` over the mesh axis — the
  sketch's own merge operator, so the combine is exact, not approximate,
* the CountMin partial table gets a single ``psum`` — counts are additive,
  and integer addition re-brackets exactly, so the sharded table is
  bit-identical too.

Bit-identical outputs at any device count: a batch that does not divide the
shard count is padded with rows whose ``n_windows`` is 0 — the same masking
the kernels already honor for bucket padding — so padded rows contribute a
sentinel signature (sliced off), a zero Bloom count (sliced off), and rank-0
HLL updates (no register effect). Min and max are associative and
commutative on integers, so re-bracketing the reduction across devices
cannot change a single bit.

Off-TPU the per-shard executor is the same single-jit jnp graph ``api.run``
uses (``impl="auto"``), so 8 virtual CPU devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``) exercise the real
partitioning in CI; on a TPU mesh each shard runs the fused Pallas plan
kernel natively.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.analysis.contracts import kernel_contract
from repro.kernels import api
from repro.kernels.plan import CountMinSpec, HLLSpec, SketchPlan

AXIS = "data"

# the sketch's own merge operator, used to fold a replicated carry into the
# combined corpus-level ("global" state_kind) output OUTSIDE the shard_map:
# a replicated carry must not enter the per-shard reduction, or the psum
# would add it once per shard (HLL's max is idempotent, but CMS counts are
# not — one rule for both keeps the carry exactly-once by construction)
_GLOBAL_MERGE = {HLLSpec: jnp.maximum, CountMinSpec: jnp.add}


@functools.lru_cache(maxsize=None)
def _cached_mesh(devices: tuple, d: int) -> Mesh:
    return Mesh(np.array(devices[:d]), (AXIS,))


def data_mesh(data_shards: Optional[int] = None) -> Mesh:
    """A 1-D mesh over the first ``data_shards`` devices (default: all).

    The Mesh is cached per (device-tuple, shard-count): ``mesh`` is a
    static argument of the jit'd ``_run_sharded`` executor, and per-batch
    ``run_auto(..., data_shards=...)`` service calls construct their mesh
    here every step. Current JAX interns ``Mesh`` by value, which already
    makes equal meshes one object — the explicit cache makes the
    one-compile property independent of that implementation detail (it is
    asserted directly in ``tests/test_shard.py``).
    """
    devs = jax.devices()
    d = len(devs) if data_shards is None else int(data_shards)
    if not 1 <= d <= len(devs):
        raise ValueError(
            f"data_shards={data_shards} not in [1, {len(devs)}] "
            f"(available devices: {len(devs)})")
    return _cached_mesh(tuple(devs), d)


def _pad_rows(x: jnp.ndarray, rows: int) -> jnp.ndarray:
    return jnp.pad(x, ((0, rows),) + ((0, 0),) * (x.ndim - 1))


def sharded_execute(plan: SketchPlan, mesh: Mesh, ref_path: bool, tile,
                    x, xb, nw, ws, operands):
    """shard_map'd executor over the padded (Bp, S) batch (Bp % d == 0).

    Traceable (not jitted) so both the jitted :func:`_run_sharded` wrapper
    and the streaming executor's per-chunk update can embed it in their own
    jit graphs. Per-sketch ``init`` carries are honored with exactly-once
    semantics: "row" state (MinHash, Bloom) is row-sharded alongside the
    batch and rides into the kernel; "global" state (HLL, CMS) is held out
    of the per-shard pass and folded into the combined output with the
    sketch's own merge operator.
    """
    carry = {}
    opd = {name: dict(v) for name, v in (operands or {}).items()}
    for name, spec in plan.sketches:
        if spec.state_kind == "global" and "init" in opd.get(name, {}):
            carry[name] = (opd[name].pop("init"), _GLOBAL_MERGE[type(spec)])

    def local(x, xb, nw, ws, operands):
        out = api.execute(plan, x, xb, nw, operands, ref_path, w_start=ws,
                          **dict(tile))
        for name, spec in plan.sketches:
            if isinstance(spec, HLLSpec):
                # the HLL merge operator IS elementwise max, so one pmax
                # over the mesh axis reproduces the global register file
                out[name] = jax.lax.pmax(out[name], AXIS)
            elif isinstance(spec, CountMinSpec):
                # CountMin counts merge additively, so one psum over the
                # mesh axis reproduces the global partial table exactly
                # (integer add is associative/commutative: bit-identical)
                out[name] = jax.lax.psum(out[name], AXIS)
        return out

    row = P(AXIS)
    # two-word lanes (2, B, S) shard their rows, axis 1
    lanes = row if plan.hash.words == 1 else P(None, AXIS)
    out_specs = {name: P() if spec.state_kind == "global" else row
                 for name, spec in plan.sketches}
    op_specs = {name: {k: (row if k == "init" else P()) for k in v}
                for name, v in opd.items()}
    out = shard_map(
        local, mesh=mesh,
        in_specs=(lanes, row if xb is not None else None, row,
                  row if ws is not None else None, op_specs),
        out_specs=out_specs, check_vma=False)(x, xb, nw, ws, opd)
    for name, (init, merge) in carry.items():
        out[name] = merge(out[name], init)
    return out


@functools.partial(jax.jit, static_argnames=("plan", "mesh", "ref_path",
                                             "tile"))
def _run_sharded(plan: SketchPlan, mesh: Mesh, ref_path: bool, tile,
                 x, xb, nw, ws, operands):
    return sharded_execute(plan, mesh, ref_path, tile, x, xb, nw, ws,
                           operands)


@kernel_contract(pallas_calls=1, scans=0, while_loops=0,
                 collectives="global-sketch-merge")
def run_sharded(plan: SketchPlan, h1v: jnp.ndarray, *, h1v_b=None,
                n_windows=None, operands=None, impl: str = "auto",
                w_start=None, mesh: Optional[Mesh] = None,
                data_shards: Optional[int] = None,
                **tile_kw) -> Dict[str, jnp.ndarray]:
    """Multi-device :func:`repro.kernels.api.run`; same arguments, same
    outputs, bit-identical at any device count.

    Extra knobs:
      mesh: an explicit 1-D :class:`jax.sharding.Mesh` whose (single) axis
        the batch dimension is sharded over. Takes precedence over
        ``data_shards``.
      data_shards: shortcut — build a 1-D mesh over the first ``data_shards``
        devices (default: every device).

    The batch is padded to a multiple of the shard count with ``n_windows=0``
    rows (excluded from every sketch reduction by the kernels' own masking)
    and the padding is sliced off on return.
    """
    if mesh is None:
        mesh = data_mesh(data_shards)
    if len(mesh.axis_names) != 1:
        raise ValueError(f"run_sharded needs a 1-D data mesh, got axes "
                         f"{mesh.axis_names}")
    x, xb, nw, ws, operands, lead, ref_path = api.validate(
        plan, h1v, h1v_b, n_windows, operands, impl, w_start)
    B = x.shape[-2]
    d = mesh.devices.size
    pad = -B % d
    if pad:
        # padded rows are fully masked (n_windows=0): sentinel MinHash rows
        # and zero Bloom counts are sliced off below; HLL contributions are
        # rank 0, which never wins a register max. Row-level carries pad
        # alongside their rows (the pad values are sliced off with them).
        x = (_pad_rows(x, pad) if plan.hash.words == 1
             else jnp.pad(x, ((0, 0), (0, pad), (0, 0))))
        if xb is not None:
            xb = _pad_rows(xb, pad)
        nw = jnp.pad(nw, (0, pad))
        if ws is not None:
            ws = jnp.pad(ws, (0, pad))
        operands = {name: dict(v) for name, v in operands.items()}
        for name, spec in plan.sketches:
            if spec.state_kind == "row" and "init" in operands.get(name, {}):
                operands[name]["init"] = _pad_rows(operands[name]["init"],
                                                   pad)
    tile = tuple(sorted(tile_kw.items()))
    out = _run_sharded(plan, mesh, ref_path, tile, x, xb, nw, ws, operands)
    out = {name: (out[name] if spec.state_kind == "global"
                  else out[name][:B])
           for name, spec in plan.sketches}
    return api.shape_outputs(plan, out, lead)


@kernel_contract(collectives="none")
def rowwise(fn, mesh: Mesh, n_row: int):
    """Wrap a purely per-row function in ``shard_map`` over the data mesh.

    ``fn(*args)`` must treat every leading array axis as independent rows:
    the first ``n_row`` arguments (each may be a pytree of row-major arrays)
    are sharded over the mesh axis, the remaining arguments are replicated,
    and every output leaf comes back row-sharded. Because ``fn`` is per-row
    by contract, no collective is needed (or emitted — the decode-plane
    tests assert zero collective primitives in the jaxpr); ``check_vma`` is
    off for the same reason the plan executor's is.

    This is the serving plane's scale-out primitive: a session pool's carry
    pytree and per-step logits are pure row state, so thousands of
    concurrent sessions spread over the mesh with no combine step at all —
    the one shape the sketch executor above (pmax/psum global state) does
    not cover. Row counts must divide the shard count; callers own padding
    (the pool sizes its capacity to the mesh at construction).
    """
    row, rep = P(AXIS), P()

    def wrapped(*args):
        if len(args) <= n_row:
            raise ValueError(f"rowwise(fn, n_row={n_row}) called with only "
                             f"{len(args)} argument(s)")
        in_specs = tuple(row if i < n_row else rep for i in range(len(args)))
        return shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=row,
                         check_vma=False)(*args)

    return wrapped


def run_auto(plan: SketchPlan, h1v: jnp.ndarray, *,
             mesh: Optional[Mesh] = None,
             data_shards: Optional[int] = None,
             **kw) -> Dict[str, jnp.ndarray]:
    """Single-device ``api.run`` unless a mesh or shard count was requested —
    the one dispatch the data-plane services (dedup/stats/decontam) thread
    their ``mesh``/``data_shards`` knobs through."""
    if mesh is None and data_shards is None:
        return api.run(plan, h1v, **kw)
    return run_sharded(plan, h1v, mesh=mesh, data_shards=data_shards, **kw)
