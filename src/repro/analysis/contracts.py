"""Plan-contract registry: declared structural invariants, one verifier.

Every performance claim this engine makes is a *structural* property of the
compiled graph, promised in a docstring and (pre-PR 9) re-asserted by hand
in whichever test file happened to care:

* one ``pallas_call`` per plan execution (the fused-epilogue claim),
* one ``lax.scan`` per stream (the on-device chunk loop claim),
* zero collectives in the row-sharded serving plane,
* exactly one ``pmax``/``psum`` per global sketch in the sharded combine,
* the carry really donated at the lowering level,
* VMEM scratch residency under the per-core budget.

This module makes the contract a first-class object declared **next to the
entry point it governs** (``@kernel_contract(...)`` above ``api.run``,
``stream.run_stream``, ``SessionPool.step``, ``shard.run_sharded`` /
``rowwise``) and verified by one driver — :func:`verify_contracts` — that
traces each registered entry across a plan/spec/device-count matrix and
diffs the traced graph against the declaration. The test suites import the
same checker instead of re-counting primitives locally, so when the
ROADMAP's new hash families (Thorup double tabulation, Lemire iterated
hashing) land as plan-engine citizens, their executors inherit the whole
contract matrix by registering one declaration.

Collective expectations are a *rule*, not a number, because the exact
counts depend on the plan being traced:

* ``"none"`` — no collective primitive at all (serving plane, single-device
  ``api.run``);
* ``"global-sketch-merge"`` — exactly one ``pmax`` per HLL sketch and one
  ``psum`` per CountMin sketch in the traced plan when a mesh is involved,
  zero otherwise (the sharded combine claim: each global sketch merges with
  its own operator, exactly once).

``kernel_contract`` never wraps the function — it attaches the declaration
and registers the entry, so jit statics/signatures are untouched.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Mapping, Optional, Tuple, Union

from repro.analysis import jaxpr as jxa

__all__ = ["KernelContract", "kernel_contract", "registry", "contract_for",
           "check_contract", "verify_contracts", "Violation",
           "expected_collectives", "DEFAULT_VMEM_BUDGET"]

# per-core VMEM on current TPU generations is 16 MiB; a kernel whose
# per-grid-step residency estimate exceeds this cannot stay resident
DEFAULT_VMEM_BUDGET = 16 * 1024 * 1024

_COLLECTIVE_RULES = ("none", "global-sketch-merge")


@dataclasses.dataclass(frozen=True)
class KernelContract:
    """Declared structural invariants of one entry point (``None`` field =
    not checked for that entry)."""

    pallas_calls: Optional[int] = None   # exact count on the fused path
    # loops are counted outside pallas_call bodies (check_contract)
    scans: Optional[int] = None          # exact lax.scan count
    while_loops: Optional[int] = None    # exact while count
    collectives: Union[str, Mapping[str, int]] = "none"
    donated: Tuple[str, ...] = ()        # arg names whose buffers must alias
    vmem_budget: Optional[int] = DEFAULT_VMEM_BUDGET
    variant: str = ""                    # e.g. the stream executor name

    def __post_init__(self):
        if isinstance(self.collectives, str):
            if self.collectives not in _COLLECTIVE_RULES:
                raise ValueError(
                    f"unknown collective rule {self.collectives!r}; expected "
                    f"one of {_COLLECTIVE_RULES} or an explicit dict")
        else:
            object.__setattr__(self, "collectives",
                               tuple(sorted(dict(self.collectives).items())))


_REGISTRY: Dict[str, Callable] = {}


def kernel_contract(**fields):
    """Attach a :class:`KernelContract` to an entry point and register it.

    Stacks: an entry with several execution modes declares one contract per
    ``variant`` (``stream.run_stream`` does this for its scan/grid/host
    executors). The function object is returned unchanged."""
    contract = KernelContract(**fields)

    def deco(fn):
        contracts = dict(getattr(fn, "__kernel_contracts__", {}))
        if contract.variant in contracts:
            raise ValueError(
                f"{fn.__qualname__}: duplicate contract variant "
                f"{contract.variant!r}")
        contracts[contract.variant] = contract
        fn.__kernel_contracts__ = contracts
        _REGISTRY[f"{fn.__module__}.{fn.__qualname__}"] = fn
        return fn

    return deco


def registry() -> Dict[str, Dict[str, KernelContract]]:
    """``{entry_name: {variant: contract}}`` of everything registered."""
    return {name: dict(fn.__kernel_contracts__)
            for name, fn in _REGISTRY.items()}


def contract_for(fn, variant: str = "") -> KernelContract:
    """The declared contract of ``fn`` (unwrapping bound methods)."""
    fn = getattr(fn, "__func__", fn)
    contracts = getattr(fn, "__kernel_contracts__", None)
    if not contracts or variant not in contracts:
        raise KeyError(f"{getattr(fn, '__qualname__', fn)!r} declares no "
                       f"kernel contract (variant={variant!r})")
    return contracts[variant]


def expected_collectives(contract: KernelContract, plan=None,
                         mesh=None) -> Dict[str, int]:
    """Resolve the contract's collective rule against the traced config."""
    rule = contract.collectives
    if rule == "none":
        return {}
    if rule == "global-sketch-merge":
        if plan is None or mesh is None:
            return {}
        from repro.kernels.plan import CountMinSpec, HLLSpec
        counts = {"pmax": 0, "psum": 0}
        for _, spec in plan.sketches:
            if isinstance(spec, HLLSpec):
                counts["pmax"] += 1
            elif isinstance(spec, CountMinSpec):
                counts["psum"] += 1
        return {k: v for k, v in counts.items() if v}
    return dict(rule)


def check_contract(contract: KernelContract, jaxpr, *,
                   expected_collectives: Optional[Dict[str, int]] = None,
                   donated_text: Optional[str] = None,
                   plain_text: Optional[str] = None) -> List[str]:
    """Diff one traced graph against one declaration; returns findings
    (empty = the contract holds). Used both by :func:`verify_contracts`
    and directly by test suites on seeded-violation fixtures."""
    findings: List[str] = []
    jaxpr = jxa.as_jaxpr(jaxpr)
    # loops are counted outside kernel bodies: a loop inside a kernel runs
    # within its one launch, an XLA-level loop is a loop of device work
    for field, prim, kernels in (("pallas_calls", "pallas_call", True),
                                 ("scans", "scan", False),
                                 ("while_loops", "while", False)):
        want = getattr(contract, field)
        if want is None:
            continue
        got = jxa.count_primitive(jaxpr, prim, kernels)
        if got != want:
            findings.append(f"{prim}: counted {got}, contract says {want}")
    allow = expected_collectives or {}
    census = jxa.collective_census(jaxpr)
    for prim, got in census.items():
        want = allow.get(prim, 0)
        if got != want:
            findings.append(f"collective {prim}: counted {got}, contract "
                            f"says {want}")
    if contract.vmem_budget is not None:
        vmem = jxa.max_pallas_vmem_bytes(jaxpr)
        if vmem > contract.vmem_budget:
            findings.append(f"VMEM estimate {vmem} bytes exceeds budget "
                            f"{contract.vmem_budget}")
    leaks = jxa.x64_leaks(jaxpr)
    if leaks:
        findings.append(f"x64 leak: {leaks[0]} (+{len(leaks) - 1} more)"
                        if len(leaks) > 1 else f"x64 leak: {leaks[0]}")
    if contract.donated:
        if donated_text is None:
            findings.append("contract declares donated args but the harness "
                            "provided no donated lowering to verify")
        else:
            got = jxa.donated_marker_count(donated_text)
            base = (jxa.donated_marker_count(plain_text)
                    if plain_text is not None else 0)
            if got <= base:
                findings.append(
                    f"donation of {contract.donated} not visible in the "
                    f"lowering (aliasing markers: donated={got}, "
                    f"plain={base})")
    return findings


@dataclasses.dataclass(frozen=True)
class Violation:
    entry: str      # registry name, e.g. "repro.kernels.api.run"
    variant: str    # contract variant ("" for the only one)
    config: str     # which matrix cell, e.g. "family=cyclic d=4"
    message: str

    def __str__(self):
        v = f"[{self.variant}]" if self.variant else ""
        return f"{self.entry}{v} ({self.config}): {self.message}"


# ---------------------------------------------------------------------------
# the verification matrix: one harness per registered entry point
# ---------------------------------------------------------------------------


def _sketch_plan(family: str):
    from repro.kernels.plan import (BloomSpec, CountMinSpec, HashSpec,
                                    HLLSpec, MinHashSpec, SketchPlan)
    return SketchPlan(
        HashSpec(family=family, n=8, L=32),
        (("sig", MinHashSpec(k=16)), ("card", HLLSpec(b=4)),
         ("dec", BloomSpec(k=3, log2_m=14)),
         ("freq", CountMinSpec(depth=3, log2_width=8))))


def _sketch_args(plan, B=4, S=320, seed=0):
    import jax
    import jax.numpy as jnp
    from repro.core import CountMinSketch, MinHash

    def h1v(shape, s):
        return jax.random.bits(jax.random.PRNGKey(s), shape,
                               dtype=jnp.uint32)

    p = MinHash(k=16).init(jax.random.PRNGKey(seed + 1))
    cp = CountMinSketch(depth=3, log2_width=8).init(
        jax.random.PRNGKey(seed + 2))
    operands = {"sig": {"a": p["a"], "b": p["b"]},
                "dec": {"bits": h1v((1 << 9,), seed + 3)},
                "freq": {"a": cp["a"], "b": cp["b"]}}
    return h1v((B, S), seed), h1v((B, S), seed + 7), operands


def _avail_devices(device_counts):
    import jax
    have = len(jax.devices())
    out = [d for d in device_counts if d <= have]
    return out or [1]


def _check(results: List[Violation], fn, variant, config, contract, jaxpr,
           **kw) -> None:
    name = f"{fn.__module__}.{fn.__qualname__}"
    for msg in check_contract(contract, jaxpr, **kw):
        results.append(Violation(name, variant, config, msg))


def _verify_api_run(results, families, device_counts):
    import jax
    from repro.kernels import api
    contract = contract_for(api.run)
    for family in families:
        plan = _sketch_plan(family)
        x, xb, ops = _sketch_args(plan)

        jx = jax.make_jaxpr(
            lambda a, b: api.run(plan, a, h1v_b=b, operands=ops,
                                 impl="pallas"))(x, xb)
        _check(results, api.run, "", f"family={family}", contract, jx,
               expected_collectives=expected_collectives(contract, plan))


def _verify_run_stream(results, families, device_counts):
    import jax
    import jax.numpy as jnp
    from repro.kernels import api, shard, stream

    for family in families:
        plan = _sketch_plan(family)
        x, xb, ops = _sketch_args(plan, B=4, S=512)

        # scan executor: whole stream in one dispatch, one scan + one kernel
        contract = contract_for(stream.run_stream, "scan")
        for d in [None] + _avail_devices(device_counts):
            cfg = f"family={family} d={d or 'single'}"
            mesh = None if d is None else shard.data_mesh(d)
            jx = jax.make_jaxpr(
                lambda a, b: stream.run_stream(
                    plan, a, chunk_s=64, h1v_b=b, operands=ops,
                    executor="scan", impl="pallas", donate=False,
                    mesh=mesh))(x, xb)
            _check(results, stream.run_stream, "scan", cfg, contract, jx,
                   expected_collectives=expected_collectives(
                       contract, plan, mesh),
                   **_stream_scan_lowerings(plan, ops))

        # grid executor: the chunk loop IS the kernel grid — one pallas_call
        contract = contract_for(stream.run_stream, "grid")
        jx = jax.make_jaxpr(
            lambda a, b: stream.run_stream(
                plan, a, chunk_s=256, h1v_b=b, operands=ops,
                executor="grid", impl="pallas", donate=False))(x, xb)
        _check(results, stream.run_stream, "grid", f"family={family}",
               contract, jx,
               expected_collectives=expected_collectives(contract, plan),
               **_stream_update_lowerings(plan, ops))

        # host executor: one dispatch per chunk, each exactly one kernel
        contract = contract_for(stream.run_stream, "host")
        state = stream.init_state(plan, 4)
        chunk = x[:, :64]
        lens = jnp.full((4,), 64, jnp.int32)
        opsn = api._check_operands(plan, ops, None)
        jx = jax.make_jaxpr(
            lambda st, ck, ckb, ln: stream._update_body(
                plan, False, None, (), st, ck, ckb, ln, opsn))(
            state, chunk, xb[:, :64], lens)
        _check(results, stream.run_stream, "host", f"family={family}",
               contract, jx,
               expected_collectives=expected_collectives(contract, plan),
               **_stream_update_lowerings(plan, ops))


def _stream_scan_lowerings(plan, ops):
    import jax.numpy as jnp
    from repro.kernels import api, stream
    opsn = api._check_operands(plan, ops, None)
    state = stream.init_state(plan, 4)
    x = jnp.zeros((4, 320), jnp.uint32)
    xb = jnp.zeros((4, 320), jnp.uint32) if "tail_b" in state else None
    lens = jnp.full((4,), 320, jnp.int32)
    args = (plan, True, None, (), 5, state, x, xb, lens, opsn)
    return {"donated_text": stream._scan_donated.lower(*args).as_text(),
            "plain_text": stream._scan_plain.lower(*args).as_text()}


def _stream_update_lowerings(plan, ops):
    import jax.numpy as jnp
    from repro.kernels import api, stream
    opsn = api._check_operands(plan, ops, None)
    state = stream.init_state(plan, 4)
    chunk = jnp.zeros((4, 64), jnp.uint32)
    ckb = jnp.zeros((4, 64), jnp.uint32) if "tail_b" in state else None
    lens = jnp.full((4,), 64, jnp.int32)
    args = (plan, True, None, (), state, chunk, ckb, lens, opsn)
    return {"donated_text": stream._update_donated.lower(*args).as_text(),
            "plain_text": stream._update_plain.lower(*args).as_text()}


def _verify_run_sharded(results, families, device_counts):
    import jax
    from repro.kernels import shard
    contract = contract_for(shard.run_sharded)
    for family in families:
        plan = _sketch_plan(family)
        x, xb, ops = _sketch_args(plan)
        for d in _avail_devices(device_counts):
            mesh = shard.data_mesh(d)
            jx = jax.make_jaxpr(
                lambda a, b: shard.run_sharded(
                    plan, a, h1v_b=b, operands=ops, impl="pallas",
                    mesh=mesh))(x, xb)
            _check(results, shard.run_sharded, "",
                   f"family={family} d={d}", contract, jx,
                   expected_collectives=expected_collectives(
                       contract, plan, mesh))


def _decode_spec():
    from repro.kernels.plan import DecodeSpec
    return DecodeSpec(n=4, log2_m=8, canary_log2_m=8)


def _verify_decode(results, families, device_counts):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import api
    contract = contract_for(api.decode)
    spec = _decode_spec()
    rng = np.random.default_rng(2)
    B, V = 4, 128
    logits = jnp.asarray(rng.standard_normal((B, V)), jnp.float32)
    prefix = jnp.asarray(rng.integers(0, 2**32, B, dtype=np.uint32))
    ready = jnp.ones((B,), jnp.int32)
    bloom = jnp.asarray(
        rng.integers(0, 2**32, (B, spec.n_words), dtype=np.uint32))
    h1 = jnp.asarray(rng.integers(0, 2**32, V, dtype=np.uint32))
    cb = jnp.asarray(
        rng.integers(0, 2**32, spec.canary_words, dtype=np.uint32))
    jx = jax.make_jaxpr(
        lambda *a: api.decode(spec, *a, canary_bits=cb, impl="pallas"))(
            logits, prefix, ready, bloom, h1)
    _check(results, api.decode, "", f"spec={spec.n}-gram", contract, jx,
           expected_collectives=expected_collectives(contract))


def _verify_session_step(results, families, device_counts):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import shard
    from repro.serve import sessions as sess
    contract = contract_for(sess.SessionPool.step)
    spec = _decode_spec()
    V, C = 64, 8
    rng = np.random.default_rng(15)
    h1 = jnp.asarray(rng.integers(0, 2**32, V, dtype=np.uint32))
    cb = jnp.asarray(
        rng.integers(0, 2**32, spec.canary_words, dtype=np.uint32))
    state = sess.init_state(spec, C)
    logits = jnp.asarray(rng.standard_normal((C, V)), jnp.float32)
    key, t = jax.random.PRNGKey(0), jnp.int32(0)
    for d in [None] + [d for d in _avail_devices(device_counts) if C % d == 0]:
        mesh = None if d is None else shard.data_mesh(d)
        cfg = f"d={d or 'single'}"
        jx = jax.make_jaxpr(
            lambda st, lg, h, k, tt: sess._step_body(
                spec, False, mesh, (), 0.8, 5, st, lg, h, cb, k, tt))(
            state, logits, h1, key, t)
        args = (spec, False, mesh, (), 0.8, 5, state, logits, h1, cb, key, t)
        _check(results, sess.SessionPool.step, "", cfg, contract, jx,
               expected_collectives=expected_collectives(contract),
               donated_text=sess._step_donated.lower(*args).as_text(),
               plain_text=sess._step_plain.lower(*args).as_text())


def _verify_rowwise(results, families, device_counts):
    import jax
    import jax.numpy as jnp
    from repro.kernels import shard
    contract = contract_for(shard.rowwise)

    def per_row(rows, scale):
        return {"y": rows["a"] * scale + rows["b"]}

    for d in _avail_devices(device_counts):
        mesh = shard.data_mesh(d)
        rows = {"a": jnp.zeros((8, 4), jnp.float32),
                "b": jnp.zeros((8, 4), jnp.float32)}
        jx = jax.make_jaxpr(
            lambda r, s: shard.rowwise(per_row, mesh, n_row=1)(r, s))(
            rows, jnp.float32(2.0))
        _check(results, shard.rowwise, "", f"d={d}", contract, jx,
               expected_collectives=expected_collectives(contract))


_HARNESSES = (_verify_api_run, _verify_run_stream, _verify_run_sharded,
              _verify_decode, _verify_session_step, _verify_rowwise)


def verify_contracts(device_counts=(1, 2, 4, 8),
                     families=("cyclic", "general"),
                     harnesses=None) -> List[Violation]:
    """Trace every registered entry point across the plan/spec/device-count
    matrix and diff each graph against its declared contract. Returns the
    violations (empty list = every contract holds).

    Importing the entry-point modules here (not at module import) keeps the
    decorator importable from inside ``repro.kernels`` without a cycle.
    """
    # importing registers the decorated entry points
    from repro.kernels import api, shard, stream     # noqa: F401
    from repro.serve import sessions                 # noqa: F401

    results: List[Violation] = []
    for harness in (harnesses or _HARNESSES):
        harness(results, tuple(families), tuple(device_counts))
    return results
