"""Public jit'd wrappers for the rolling-hash kernels + deprecated shims.

On TPU the Pallas kernels run natively; on CPU (this container, and any
host-side data tooling) the same kernels execute under ``interpret=True`` or
fall back to the pure-jnp reference — selectable via ``impl=``:

* ``"auto"``    — Pallas on TPU, jnp reference elsewhere (fast CPU path).
* ``"pallas"``  — force the kernel (interpret-mode off-TPU; used in tests).
* ``"ref"``     — force the jnp oracle.

All entry points accept (..., S) inputs; leading dims are flattened to a
batch for tiling and restored on return. Validation (impl names, the
``S >= n`` window check) is centralized in ``api.prepare`` so every entry
point — plain hash or fused sketch — raises the same errors.

The fused hash->sketch data-plane lives behind ``repro.kernels.api.run``
and declarative ``SketchPlan`` objects (see ``kernels/plan.py``): one
rolling-hash device pass feeds any number of MinHash/HLL/Bloom epilogues,
for both the CYCLIC and GENERAL families.

DEPRECATED: ``cyclic_minhash`` / ``cyclic_hll`` / ``cyclic_bloom`` predate
the plan engine. They are kept as thin shims — each builds the equivalent
one-sketch CYCLIC plan and calls ``api.run`` — with bit-identical outputs.
New code should build a ``SketchPlan`` (which can also request several
sketches in one pass, and the GENERAL family).
"""
from __future__ import annotations

import warnings

import jax.numpy as jnp

from repro.kernels import api
from repro.kernels import ref as _ref
from repro.kernels.cyclic import cyclic_rolling
from repro.kernels.general import general_rolling
from repro.kernels.sketch_fused import cyclic_rolling_fused
from repro.kernels.plan import (BloomSpec, HashSpec, HLLSpec, MinHashSpec,
                                SketchPlan)


def cyclic(h1v: jnp.ndarray, *, n: int, L: int = 32, impl: str = "auto",
           mode: str = "auto", **tile_kw) -> jnp.ndarray:
    """Rolling CYCLIC hash of h1-mapped values. (..., S) -> (..., S-n+1);
    past L = 32, two-word lanes (2, ..., S) -> (2, ..., S-n+1)."""
    words = 1 if L <= 32 else 2
    x, lead, ref_path = api.prepare(h1v, n=n, impl=impl, words=words)
    if ref_path:
        out = _ref.cyclic_ref(x, n, L)
    else:
        out = cyclic_rolling(x, n=n, L=L, mode=mode,
                             interpret=not api.on_tpu(), **tile_kw)
    return api.lead_shape(out, lead, words)


def general(h1v: jnp.ndarray, *, n: int, p: int, L: int = 32,
            impl: str = "auto", **tile_kw) -> jnp.ndarray:
    """Rolling GENERAL hash mod irreducible p. (..., S) -> (..., S-n+1)."""
    x, lead, ref_path = api.prepare(h1v, n=n, impl=impl)
    if ref_path:
        out = _ref.general_ref(x, n, p, L)
    else:
        out = general_rolling(x, n=n, p=p, L=L, interpret=not api.on_tpu(),
                              **tile_kw)
    return out.reshape(lead + (out.shape[-1],))


def cyclic_fused(tokens: jnp.ndarray, table: jnp.ndarray, *, n: int,
                 L: int = 32, impl: str = "auto", **tile_kw) -> jnp.ndarray:
    """Fused byte->fingerprint: h1 table lookup + rolling CYCLIC hash."""
    x, lead, ref_path = api.prepare(tokens, n=n, impl=impl)
    if ref_path:
        out = _ref.cyclic_fused_ref(x, table, n, L)
    else:
        out = cyclic_rolling_fused(x, table, n=n, L=L,
                                   interpret=not api.on_tpu(), **tile_kw)
    return out.reshape(lead + (out.shape[-1],))


# ---------------------------------------------------------------------------
# DEPRECATED single-sketch shims (use api.run with a SketchPlan instead)
# ---------------------------------------------------------------------------


def _cyclic_spec(n: int, L: int, discard: bool, shim: str) -> HashSpec:
    warnings.warn(
        f"ops.{shim} is deprecated; build a SketchPlan and call "
        f"repro.kernels.api.run (which can also batch several sketches "
        f"into one pass, and the GENERAL family)",
        DeprecationWarning, stacklevel=3)
    return HashSpec(family="cyclic", n=n, L=L, discard=discard)


def cyclic_minhash(h1v: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray, *,
                   n: int, L: int = 32, n_windows=None, discard: bool = True,
                   impl: str = "auto", **tile_kw) -> jnp.ndarray:
    """DEPRECATED: fused rolling CYCLIC hash -> MinHash signatures.

    Shim over ``api.run`` with a one-sketch plan; bit-identical to the
    pre-plan entry point. h1v (..., S), a/b (k,) -> (..., k) uint32.
    """
    plan = SketchPlan(_cyclic_spec(n, L, discard, "cyclic_minhash"),
                      (("minhash", MinHashSpec(k=int(a.shape[0]))),))
    return api.run(plan, h1v, n_windows=n_windows,
                   operands={"minhash": {"a": a, "b": b}}, impl=impl,
                   **tile_kw)["minhash"]


def cyclic_hll(h1v: jnp.ndarray, *, n: int, b: int, L: int = 32,
               rank_bits=None, n_windows=None, discard: bool = True,
               impl: str = "auto", **tile_kw) -> jnp.ndarray:
    """DEPRECATED: fused rolling CYCLIC hash -> HLL registers (2^b,) int32.

    Shim over ``api.run``; ``rank_bits`` defaults to the usable bits after
    index extraction ((L-n+1) - b under the Theorem-1 discard).
    """
    plan = SketchPlan(_cyclic_spec(n, L, discard, "cyclic_hll"),
                      (("hll", HLLSpec(b=b, rank_bits=rank_bits)),))
    return api.run(plan, h1v, n_windows=n_windows, impl=impl,
                   **tile_kw)["hll"]


def cyclic_bloom(h1va: jnp.ndarray, h1vb: jnp.ndarray, bits: jnp.ndarray, *,
                 n: int, k: int, log2_m: int, L: int = 32, n_windows=None,
                 discard: bool = True, impl: str = "auto",
                 **tile_kw) -> jnp.ndarray:
    """DEPRECATED: fused double rolling CYCLIC hash -> Bloom hit counts.

    Shim over ``api.run``; counts, per row, the valid windows whose k
    double-hashed probes all hit the packed filter.
    """
    plan = SketchPlan(_cyclic_spec(n, L, discard, "cyclic_bloom"),
                      (("bloom", BloomSpec(k=k, log2_m=log2_m)),))
    return api.run(plan, h1va, h1v_b=h1vb, n_windows=n_windows,
                   operands={"bloom": {"bits": bits}}, impl=impl,
                   **tile_kw)["bloom"]
