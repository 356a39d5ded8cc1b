"""Jaxpr/HLO introspection: the shared walker under every invariant check.

The repo's performance claims are *structural* contracts on the compiled
graph — one ``pallas_call`` per plan execution, one ``lax.scan`` per stream,
zero collectives in row-sharded serving, a donated carry — and its
correctness claims are bit-level (the Theorem-1/2 discard, no silent x64
widening). Until PR 9 those were enforced by ~86 ad-hoc assertions spread
over nine test files, each with its own copy of the recursion into nested
jaxprs. This module is the one walker they all share:

* :func:`count_primitive` / :func:`primitive_census` — primitive counts,
  recursing through every nested jaxpr (jit bodies, ``shard_map`` regions,
  scan/while bodies, custom calls, the pallas kernel jaxpr itself);
* :func:`collective_census` / :func:`assert_no_collectives` — the SPMD
  primitives (``pmax``/``psum``/``all_gather``/...) the serving plane must
  never emit and the sketch combine must emit exactly once per global
  sketch;
* :func:`donated_marker_count` / :func:`donation_is_lowered` — verify a
  ``donate_argnums`` request actually survived to the lowered StableHLO as
  an input/output aliasing attribute (XLA silently drops donation it cannot
  honor — the lint's "donate without a lowering check" rule exists because
  of exactly that silence);
* :func:`x64_leaks` / :func:`dtype_promotions` — 64-bit avals appearing in
  a graph that pins 32-bit dtypes (a stray ``JAX_ENABLE_X64`` leak doubles
  every buffer), and ``convert_element_type`` widenings;
* :func:`pallas_vmem_bytes` / :func:`max_pallas_vmem_bytes` — a static
  per-``pallas_call`` VMEM residency estimate (the kernel jaxpr's block and
  scratch refs), checked against each entry point's declared budget by
  ``analysis.contracts``;
* the compiled-HLO layer re-exported from :mod:`repro.launch.hlo_analysis`
  (:func:`count_collectives_hlo`, :func:`collective_bytes_hlo`) for the
  contracts that only exist after partitioning (per-device collective
  traffic in bytes, async ``-start``/``-done`` pairs counted exactly once).

Everything accepts a ``ClosedJaxpr``, a raw ``Jaxpr``, or anything with a
``.jaxpr`` attribute (the object ``jax.make_jaxpr`` returns), so call sites
never unwrap by hand.
"""
from __future__ import annotations

from typing import Dict, Iterator, List, Optional

import numpy as np

from repro.launch.hlo_analysis import (async_collective_pairs,
                                       collective_bytes as collective_bytes_hlo,
                                       count_collectives as count_collectives_hlo)

__all__ = [
    "COLLECTIVE_PRIMS", "as_jaxpr", "iter_eqns", "count_primitive",
    "primitive_census", "collective_census", "assert_no_collectives",
    "assert_counts", "donated_marker_count", "donation_is_lowered",
    "x64_leaks", "dtype_promotions", "pallas_vmem_bytes",
    "max_pallas_vmem_bytes", "count_collectives_hlo", "collective_bytes_hlo",
    "async_collective_pairs",
]

# jaxpr-level SPMD collectives (the HLO layer has its own list — these are
# the primitive names jax emits before partitioning)
COLLECTIVE_PRIMS = ("pmax", "pmin", "psum", "all_gather", "all_to_all",
                    "ppermute", "psum_scatter", "reduce_scatter")

# StableHLO markers that prove a donation request survived lowering; which
# one appears depends on the jax version, so both are recognized
_ALIAS_MARKERS = ("tf.aliasing_output", "jax.buffer_donor")


def as_jaxpr(obj):
    """Normalize fn-traces/ClosedJaxpr/Jaxpr to the raw ``Jaxpr``."""
    seen = set()
    while hasattr(obj, "jaxpr") and id(obj) not in seen:
        seen.add(id(obj))
        obj = obj.jaxpr
    if not hasattr(obj, "eqns"):
        raise TypeError(f"not a jaxpr (no .eqns): {type(obj)}")
    return obj


def _sub_jaxprs(eqn):
    """Every nested jaxpr an equation carries (jit/scan/while bodies,
    shard_map regions, custom-call and pallas kernel jaxprs)."""
    for v in eqn.params.values():
        for u in (v if isinstance(v, (list, tuple)) else [v]):
            if hasattr(u, "jaxpr"):
                yield as_jaxpr(u)
            elif hasattr(u, "eqns"):
                yield u


def iter_eqns(jaxpr) -> Iterator:
    """Depth-first over every equation, recursing into nested jaxprs."""
    jaxpr = as_jaxpr(jaxpr)
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub)


def count_primitive(jaxpr, name: str) -> int:
    """Occurrences of primitive ``name``, recursing into nested jaxprs."""
    return sum(1 for eqn in iter_eqns(jaxpr) if eqn.primitive.name == name)


def primitive_census(jaxpr) -> Dict[str, int]:
    """``{primitive_name: count}`` over the whole (recursive) jaxpr."""
    census: Dict[str, int] = {}
    for eqn in iter_eqns(jaxpr):
        census[eqn.primitive.name] = census.get(eqn.primitive.name, 0) + 1
    return census


def collective_census(jaxpr) -> Dict[str, int]:
    """Counts of every jaxpr-level collective primitive (0-filled)."""
    census = primitive_census(jaxpr)
    return {p: census.get(p, 0) for p in COLLECTIVE_PRIMS}


def assert_no_collectives(jaxpr, allow: Dict[str, int] = None) -> None:
    """Raise ``AssertionError`` unless every collective count matches
    ``allow`` (missing keys mean 0 — the zero-collective serving contract)."""
    allow = allow or {}
    got = collective_census(jaxpr)
    bad = {p: c for p, c in got.items() if c != allow.get(p, 0)}
    assert not bad, (f"collective census mismatch: got {bad}, "
                     f"expected {allow or 'none'}")


def assert_counts(jaxpr, **expected: int) -> None:
    """``assert_counts(jx, pallas_call=1, scan=0)`` — exact primitive
    counts with a diagnostic census on failure."""
    jaxpr = as_jaxpr(jaxpr)
    for name, want in expected.items():
        got = count_primitive(jaxpr, name)
        assert got == want, (
            f"primitive {name!r}: counted {got}, contract says {want} "
            f"(census: { {k: v for k, v in primitive_census(jaxpr).items() if v} })")


# ---------------------------------------------------------------------------
# donation / aliasing: the lowering-level half of the donated-carry contract
# ---------------------------------------------------------------------------


def donated_marker_count(lowered_text: str) -> int:
    """Number of input/output aliasing markers in lowered StableHLO text.

    A ``donate_argnums`` request only becomes an in-place buffer reuse when
    the lowering records the alias; counting the markers (rather than just
    grepping for one) lets contracts assert the donated twin strictly
    exceeds the plain twin."""
    return sum(lowered_text.count(m) for m in _ALIAS_MARKERS)


def donation_is_lowered(lowered) -> bool:
    """True when a ``.lower(...)`` result carries at least one aliased
    output (accepts the Lowered object or its ``as_text()`` string)."""
    text = lowered if isinstance(lowered, str) else lowered.as_text()
    return donated_marker_count(text) > 0


# ---------------------------------------------------------------------------
# dtype hygiene: x64 leaks and widening promotions
# ---------------------------------------------------------------------------

_WIDE_DTYPES = ("float64", "int64", "uint64", "complex128")


def _avals(eqn):
    for v in list(eqn.invars) + list(eqn.outvars):
        aval = getattr(v, "aval", None)
        if aval is not None and hasattr(aval, "dtype"):
            yield aval


def x64_leaks(jaxpr) -> List[str]:
    """Equations touching a 64-bit aval — the engine pins 32-bit dtypes
    (uint32 lanes, int32 counters), so ANY 64-bit value in a traced graph
    is an environment leak (``JAX_ENABLE_X64``) or an accidental promotion
    that silently doubles buffer sizes. Returns human-readable findings."""
    out = []
    for eqn in iter_eqns(jaxpr):
        for aval in _avals(eqn):
            if str(aval.dtype) in _WIDE_DTYPES:
                out.append(f"{eqn.primitive.name}: 64-bit aval {aval}")
                break
    return out


def dtype_promotions(jaxpr) -> List[str]:
    """``convert_element_type`` equations that *widen* (itemsize grows) —
    each one is either a deliberate accumulator widening (declare it) or an
    accidental promotion burning bandwidth."""
    out = []
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name != "convert_element_type":
            continue
        src = eqn.invars[0].aval
        dst = eqn.params.get("new_dtype")
        if dst is None or not hasattr(src, "dtype"):
            continue
        if np.dtype(dst).itemsize > np.dtype(src.dtype).itemsize:
            out.append(f"convert_element_type: {src.dtype} -> {np.dtype(dst)}")
    return out


# ---------------------------------------------------------------------------
# VMEM residency: static per-pallas_call footprint estimate
# ---------------------------------------------------------------------------


def _ref_bytes(aval) -> int:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        inner = getattr(aval, "inner_aval", None)
        if inner is not None:
            return _ref_bytes(inner)
        return 0
    n = 1
    for d in shape:
        n *= int(d)
    return n * np.dtype(dtype).itemsize


def pallas_vmem_bytes(jaxpr) -> List[int]:
    """Per-``pallas_call`` VMEM residency estimate, in encounter order.

    The kernel jaxpr's refs are exactly what lives in VMEM for one grid
    step: the input/output block tiles plus every scratch accumulator. The
    estimate sums their aval sizes (deduplicated by var identity — pallas
    passes outputs as in-place refs), which upper-bounds the steady-state
    footprint the contract's ``vmem_budget`` guards."""
    sizes = []
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name != "pallas_call":
            continue
        kernel = eqn.params.get("jaxpr")
        if kernel is None:
            sizes.append(0)
            continue
        kernel = as_jaxpr(kernel)
        seen, total = set(), 0
        for v in list(kernel.invars) + list(kernel.outvars)       \
                + list(kernel.constvars):
            if id(v) in seen:
                continue
            seen.add(id(v))
            total += _ref_bytes(getattr(v, "aval", None))
        sizes.append(total)
    return sizes


def max_pallas_vmem_bytes(jaxpr) -> int:
    """The largest per-kernel VMEM estimate in the graph (0 when no
    ``pallas_call`` is present — the ref path has no VMEM residency)."""
    sizes = pallas_vmem_bytes(jaxpr)
    return max(sizes) if sizes else 0
