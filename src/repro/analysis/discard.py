"""Theorem-1/2 discard checking: no consumer may touch the dependent bits.

The paper's central caveat is *bit-level*: a recursive n-gram hash is
pairwise independent **at best**, and for CYCLIC only on ``L - n + 1``
consecutive bits — the other ``n - 1`` bits are linear functions of the kept
ones (Theorems 1–2), so any probe, bucket index, or filter position derived
from them silently loses the pairwise guarantee every false-positive bound
in this repo is priced on. The engine encodes the discard as
``HashSpec.hash_mask`` / ``DecodeSpec.hash_mask`` (low-bit keep) and every
consumer is *supposed* to route through it. This module checks that they
actually do, two ways:

**Statically** (:func:`static_findings`): an AST pass over the consumer
layers (``data/``, ``serve/``, ``kernels/decode.py``) with two rules:

* ``DS1`` — a right-shift whose amount is written in terms of ``out_bits``
  or ``L - n`` is extracting exactly the discarded high bits; the engine's
  own shifts (probe word index ``>> 5``, HLL rank split) use constants or
  unrelated widths and never match.
* ``DS2`` — the known probe-derivation entry points
  (``ref.bloom_probe_hits``, ``sessions._bloom_add_rows``) must receive a
  *masked* hash argument: the
  argument expression (or the local name it was assigned from, tracked to a
  fixpoint inside the enclosing function) must route through ``hash_mask``.

**At trace time** (:func:`trace_findings`): a mask-propagation pass over the
jaxpr. Every ``and``-with-``hash_mask``-literal equation marks its other
operand as a *raw* window hash; the raw value may feed the rolling
recursion (xor/rotate/select — full-width state is the recursion's
contract) but must never feed a probe-shaped consumer (multiply/add for the
double-hashing stride, shifts for word indices, gathers for filter lookups).
:func:`verify_decode_discard` drives this over the decode plane's actual
traces (fused + oracle + session step), where Theorem 2 is load-bearing.

Both halves return findings (empty = the discard holds); the
``python -m repro.analysis`` driver folds them into the repo-wide report.
"""
from __future__ import annotations

import ast
import dataclasses
from pathlib import Path
from typing import Dict, List, Optional

from repro.analysis import jaxpr as jxa

__all__ = ["DiscardFinding", "static_findings", "trace_findings",
           "verify_decode_discard", "SCOPE", "PROBE_CALLEES"]

# the consumer layers Theorems 1-2 bind (the hash *producers* in kernels/
# legitimately hold full-width state for the recursion)
SCOPE = ("src/repro/data", "src/repro/serve", "src/repro/kernels/decode.py")

# probe-derivation entry points and which positional argument must be the
# masked hash
PROBE_CALLEES: Dict[str, int] = {
    "bloom_probe_hits": 0,      # ref.py — the probe oracle
    "_bloom_add_rows": 1,       # serve/sessions.py — filter insert
}

# jaxpr primitives a raw (pre-mask) window hash may legitimately feed: the
# rolling recursion and layout plumbing. Anything else — mul/add (the
# double-hashing stride), shifts (word/bit indices), gather/dynamic_slice
# (filter lookups) — is a probe derived from undiscarded bits.
ALLOWED_RAW_CONSUMERS = frozenset({
    "and", "or", "xor", "not", "select_n", "broadcast_in_dim", "reshape",
    "squeeze", "expand_dims", "convert_element_type", "copy", "transpose",
    # call-like region boundaries: passing a raw hash *into* a sub-region is
    # plumbing, not a probe — each region is analyzed independently (a
    # discard site inside the callee re-marks its own raw operand there)
    "jit", "closed_call", "core_call", "custom_jvp_call", "custom_vjp_call",
    "remat", "checkpoint", "scan", "while", "cond", "shard_map",
    "pallas_call",
})


@dataclasses.dataclass(frozen=True)
class DiscardFinding:
    rule: str       # "DS1" | "DS2" | "trace"
    path: str       # repo-relative file ("<trace>" for trace-time)
    line: int
    message: str

    def __str__(self):
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


# ---------------------------------------------------------------------------
# static half: AST over the consumer layers
# ---------------------------------------------------------------------------


def _names_in(node) -> set:
    out = set()
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name):
            out.add(sub.id)
        elif isinstance(sub, ast.Attribute):
            out.add(sub.attr)
    return out


def _is_masked_expr(node, masked_names: set) -> bool:
    """The expression routes through the discard: it mentions ``hash_mask``
    (``spec.hash_mask``, a ``hash_mask`` parameter) or a local name that was
    assigned from such an expression."""
    names = _names_in(node)
    return bool(names & ({"hash_mask"} | masked_names))


def _masked_locals(fn: ast.AST) -> set:
    """Names assigned (to a fixpoint) from hash_mask-routed expressions
    inside one function body."""
    masked: set = set()
    changed = True
    while changed:
        changed = False
        for sub in ast.walk(fn):
            if not isinstance(sub, ast.Assign):
                continue
            if not _is_masked_expr(sub.value, masked):
                continue
            for tgt in sub.targets:
                if isinstance(tgt, ast.Name) and tgt.id not in masked:
                    masked.add(tgt.id)
                    changed = True
    return masked


def _callee_name(call: ast.Call) -> Optional[str]:
    f = call.func
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return None


def _check_function(fn, rel: str, findings: List[DiscardFinding]) -> None:
    masked = _masked_locals(fn)
    for node in ast.walk(fn):
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.RShift):
            # DS1: shifting by out_bits / (L - n)-shaped amounts reads the
            # dependent high bits the theorems discard
            amt_names = _names_in(node.right)
            ln_shaped = any(
                isinstance(s, ast.BinOp) and isinstance(s.op, ast.Sub)
                and {"L", "n"} <= _names_in(s)
                for s in ast.walk(node.right))
            if "out_bits" in amt_names or ln_shaped:
                findings.append(DiscardFinding(
                    "DS1", rel, node.lineno,
                    "right-shift by an out_bits/(L - n)-derived amount "
                    "extracts the discarded dependent high bits; derive "
                    "from `h & hash_mask` instead"))
        elif isinstance(node, ast.Call):
            name = _callee_name(node)
            if name not in PROBE_CALLEES:
                continue
            idx = PROBE_CALLEES[name]
            if idx >= len(node.args):
                continue           # keyword/odd call shape: not the idiom
            arg = node.args[idx]
            if not _is_masked_expr(arg, masked):
                findings.append(DiscardFinding(
                    "DS2", rel, node.lineno,
                    f"{name}() probe hash argument does not route through "
                    f"spec.hash_mask — probes from undiscarded bits void "
                    f"the pairwise-independence bound (Theorems 1-2)"))


def static_findings(root: Optional[Path] = None) -> List[DiscardFinding]:
    """Run DS1/DS2 over every file in :data:`SCOPE`."""
    root = Path(root) if root else _repo_root()
    findings: List[DiscardFinding] = []
    for path in _scope_files(root):
        rel = str(path.relative_to(root))
        tree = ast.parse(path.read_text(), filename=rel)
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                _check_function(node, rel, findings)
    return findings


def _repo_root() -> Path:
    return Path(__file__).resolve().parents[3]


def _scope_files(root: Path):
    for entry in SCOPE:
        p = root / entry
        if p.is_file():
            yield p
        elif p.is_dir():
            yield from sorted(p.rglob("*.py"))


# ---------------------------------------------------------------------------
# trace-time half: mask propagation over the jaxpr
# ---------------------------------------------------------------------------


def _regions(jaxpr):
    """The top jaxpr and every nested one (vars are region-local)."""
    jaxpr = jxa.as_jaxpr(jaxpr)
    yield jaxpr
    for eqn in jaxpr.eqns:
        for sub in jxa._sub_jaxprs(eqn):
            yield from _regions(sub)


def _literal_val(v):
    val = getattr(v, "val", None)
    if val is None:
        return None
    try:
        return int(val)
    except (TypeError, ValueError):
        return None


def trace_findings(jaxpr, hash_mask: int) -> List[str]:
    """Raw-hash escape analysis on one traced graph.

    Each ``and`` equation with the ``hash_mask`` literal is a discard site;
    its non-literal operand is a *raw* window hash. Raw hashes may feed the
    recursion (:data:`ALLOWED_RAW_CONSUMERS`) but any probe-shaped consumer
    (stride multiply, index shift, filter gather) is a Theorem-1/2
    violation. Regions are analyzed independently (jaxpr vars are local to
    their region)."""
    findings: List[str] = []
    for region in _regions(jaxpr):
        raw = set()
        mask_eqns = []
        for eqn in region.eqns:
            if eqn.primitive.name != "and":
                continue
            vals = [_literal_val(v) for v in eqn.invars]
            if hash_mask not in [v for v in vals if v is not None]:
                continue
            mask_eqns.append(eqn)
            for v, lit in zip(eqn.invars, vals):
                if lit is None and hasattr(v, "count"):   # a real Var
                    raw.add(v)
        if not raw:
            continue
        for eqn in region.eqns:
            if eqn in mask_eqns:
                continue
            if eqn.primitive.name in ALLOWED_RAW_CONSUMERS:
                continue
            for v in eqn.invars:
                if hasattr(v, "count") and v in raw:
                    findings.append(
                        f"raw (pre-discard) hash feeds `{eqn.primitive.name}`"
                        f" — probe derivation must come from the masked "
                        f"value (hash_mask={hash_mask:#x})")
    return findings


def verify_decode_discard(spec=None) -> List[DiscardFinding]:
    """Trace the decode plane (fused kernel, jnp oracle, session step) and
    run :func:`trace_findings` with the spec's Theorem-2 mask. Skipped for
    degraded/full-width specs (mask covers all L bits — nothing to check)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import api
    from repro.kernels.plan import DecodeSpec
    from repro.serve import sessions as sess

    spec = spec or DecodeSpec(n=4, log2_m=8, canary_log2_m=8)
    if spec.hash_mask == (1 << spec.L) - 1:
        return []
    rng = np.random.default_rng(3)
    B, V = 4, 64
    logits = jnp.asarray(rng.standard_normal((B, V)), jnp.float32)
    prefix = jnp.asarray(rng.integers(0, 2**32, B, dtype=np.uint32))
    ready = jnp.ones((B,), jnp.int32)
    bloom = jnp.asarray(
        rng.integers(0, 2**32, (B, spec.n_words), dtype=np.uint32))
    h1 = jnp.asarray(rng.integers(0, 2**32, V, dtype=np.uint32))
    cb = (jnp.asarray(rng.integers(0, 2**32, spec.canary_words,
                                   dtype=np.uint32))
          if spec.has_canary else None)

    findings: List[DiscardFinding] = []

    def check(tag, jx):
        for msg in trace_findings(jx, spec.hash_mask):
            findings.append(DiscardFinding("trace", f"<{tag}>", 0, msg))

    for impl in ("pallas", "ref"):
        jx = jax.make_jaxpr(
            lambda *a: api.decode(spec, *a, canary_bits=cb, impl=impl))(
                logits, prefix, ready, bloom, h1)
        check(f"api.decode impl={impl}", jx)

    state = sess.init_state(spec, B)
    key, t = jax.random.PRNGKey(0), jnp.int32(0)
    jx = jax.make_jaxpr(
        lambda st, lg, h, k, tt: sess._step_body(
            spec, False, None, (), 0.8, 5, st, lg, h, cb, k, tt))(
        state, logits, h1, key, t)
    check("SessionPool.step", jx)
    return findings
