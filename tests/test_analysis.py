"""The analyzer analyzed: seeded violations MUST be flagged, the clean tree
MUST be silent.

A static analyzer that never fires is indistinguishable from one that works;
every checker here is exercised from both sides:

* seeded-violation fixtures — a second pallas_call, a dropped-donation
  carry, a probe derived from undiscarded high bits, a uint64-unsafe
  ``np.bincount``, an int32 stream counter, unseeded randomness — each must
  produce its finding with the right rule tag;
* the clean tree — lint, the Theorem-1/2 discard checker (both halves) and
  the contract matrix must all come back empty, which is exactly what
  ``python -m repro.analysis`` (CI: ``./test.sh --analyze``) enforces.
"""
import dataclasses
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import contracts, discard, lint
from repro.analysis.jaxpr import (as_jaxpr, assert_counts, collective_census,
                                  count_primitive, donated_marker_count,
                                  max_pallas_vmem_bytes, primitive_census,
                                  x64_leaks)
from repro.core import MinHash
from repro.kernels import api
from repro.kernels.plan import HashSpec, MinHashSpec, SketchPlan


def _plan(family="cyclic"):
    return SketchPlan(HashSpec(family=family, n=8),
                      (("sig", MinHashSpec(k=16)),))


def _inputs(B=3, S=256, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.integers(0, 2**32, (B, S), dtype=np.uint32))
    p = MinHash(k=16).init(jax.random.PRNGKey(1))
    return x, {"sig": {"a": p["a"], "b": p["b"]}}


# ---------------------------------------------------------------------------
# jaxpr walker basics
# ---------------------------------------------------------------------------


def test_census_recurses_into_nested_regions():
    x, ops = _inputs()

    def fn(x):
        return api.run(_plan(), x, operands=ops, impl="pallas")

    jx = jax.make_jaxpr(fn)(x)
    census = primitive_census(jx)
    assert census.get("pallas_call") == 1
    # the fused kernel's body is reached through the jit/pallas nesting
    assert count_primitive(jx, "pallas_call") == 1
    assert not any(collective_census(jx).values())


def test_x64_leak_detection():
    jx_clean = jax.make_jaxpr(lambda x: x + jnp.uint32(1))(jnp.uint32(0))
    assert x64_leaks(jx_clean) == []
    with jax.enable_x64(True):
        jx_wide = jax.make_jaxpr(lambda x: x.astype(jnp.float64) * 2.0)(
            jnp.float32(0))
    assert x64_leaks(jx_wide)


def test_pallas_vmem_estimate_positive():
    x, ops = _inputs()
    jx = jax.make_jaxpr(
        lambda x: api.run(_plan(), x, operands=ops, impl="pallas"))(x)
    vmem = max_pallas_vmem_bytes(jx)
    assert 0 < vmem < contracts.DEFAULT_VMEM_BUDGET


# ---------------------------------------------------------------------------
# seeded contract violations
# ---------------------------------------------------------------------------


def test_second_pallas_call_is_flagged():
    """api.run's contract pins ONE fused kernel dispatch; a graph that
    dispatches twice (the pre-PR 4 duplicated-rolling-hash shape) must
    violate it."""
    x, ops = _inputs()
    contract = contracts.contract_for(api.run)

    def doubled(x):
        a = api.run(_plan(), x, operands=ops, impl="pallas")
        b = api.run(_plan(), x, operands=ops, impl="pallas")
        return a["sig"] ^ b["sig"]

    jx = jax.make_jaxpr(doubled)(x)
    findings = contracts.check_contract(contract, jx,
                                        expected_collectives={})
    assert any("pallas_call" in f for f in findings), findings

    # and the true graph passes the same check
    jx_ok = jax.make_jaxpr(
        lambda x: api.run(_plan(), x, operands=ops, impl="pallas"))(x)
    assert contracts.check_contract(contract, jx_ok,
                                    expected_collectives={}) == []


def test_dropped_donation_carry_is_flagged():
    """A 'donated' lowering with no more aliasing markers than the plain
    twin means XLA dropped the donation — the contract must refuse it."""
    from repro.kernels import stream
    plan = _plan()
    x, ops = _inputs(B=4, S=320)
    opsn = api._check_operands(plan, ops, None)
    state = stream.init_state(plan, 4)
    lens = jnp.full((4,), 320, jnp.int32)
    donated = stream._scan_donated.lower(
        plan, True, None, (), 5, state, x, None, lens, opsn).as_text()
    plain = stream._scan_plain.lower(
        plan, True, None, (), 5, state, x, None, lens, opsn).as_text()
    assert donated_marker_count(donated) > donated_marker_count(plain)

    contract = contracts.contract_for(stream.run_stream, variant="scan")
    jx = jax.make_jaxpr(
        lambda xx: stream.run_stream(plan, xx, chunk_s=64, operands=ops,
                                     executor="scan", impl="pallas",
                                     donate=False))(x)

    # the donation check runs on lowered text alone: feeding the PLAIN text
    # as the donated lowering simulates the dropped carry
    findings = contracts.check_contract(
        contract, jx, expected_collectives={},
        donated_text=plain, plain_text=plain)
    assert any("donation" in f or "aliasing" in f for f in findings), findings

    # the real pair passes
    assert contracts.check_contract(
        contract, jx, expected_collectives={},
        donated_text=donated, plain_text=plain) == []


def test_unexpected_collective_is_flagged():
    """A collective in a contract declared collectives='none' must fire."""
    contract = contracts.contract_for(api.run)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def with_psum(x):
        return shard_map(lambda v: jax.lax.psum(v, "data"), mesh=mesh,
                         in_specs=P("data"), out_specs=P(),
                         check_vma=False)(x)

    jx = jax.make_jaxpr(with_psum)(jnp.ones((4,), jnp.float32))
    findings = contracts.check_contract(contract, jx,
                                        expected_collectives={})
    assert any("psum" in f for f in findings), findings


def _session_step_jaxpr(spec, steps=None):
    """The session pool's decode step on the kernel path; with ``steps``,
    that many steps as one XLA-level ``lax.scan``."""
    from repro.serve import sessions as sess
    B, V = 8, 256
    rng = np.random.default_rng(0)
    state = sess.init_state(spec, B)
    logits = jnp.asarray(rng.standard_normal((B, V)), jnp.float32)
    h1 = jnp.asarray(rng.integers(0, 2**32, V, dtype=np.uint32))
    cb = jnp.zeros((spec.canary_words,), jnp.uint32)

    def step(st, t):
        return sess._step_body(spec, False, None, (), 0.0, 0, st, logits,
                               h1, cb, jax.random.PRNGKey(0), t)

    if steps is None:
        return jax.make_jaxpr(step)(state, jnp.int32(0))
    return jax.make_jaxpr(lambda st: jax.lax.scan(
        lambda c, t: step(c, t)[::-1], st,
        jnp.arange(steps, dtype=jnp.int32)))(state)


def test_xla_level_scan_in_session_step_is_flagged():
    """``SessionPool.step`` declares no loop: the kernel's own loops over
    candidate slices and filter chunks run inside its one launch and are
    not counted, a ``lax.scan`` of steps around it is."""
    from repro.kernels.plan import DecodeSpec
    from repro.serve import sessions as sess
    spec = DecodeSpec(n=4, log2_m=14, k=2, canary_log2_m=20)
    # the donation half needs lowerings; the loop counts need only jaxprs
    contract = dataclasses.replace(
        contracts.contract_for(sess.SessionPool.step), donated=())
    jx = _session_step_jaxpr(spec)
    assert count_primitive(jx, "scan") > 0            # inside the kernel
    assert count_primitive(jx, "scan", kernels=False) == 0
    assert contracts.check_contract(contract, jx,
                                    expected_collectives={}) == []
    findings = contracts.check_contract(contract,
                                        _session_step_jaxpr(spec, steps=2),
                                        expected_collectives={})
    assert any(f.startswith("scan: counted 1") for f in findings), findings


# ---------------------------------------------------------------------------
# seeded discard violations (Theorems 1-2)
# ---------------------------------------------------------------------------


def test_probe_from_undiscarded_bits_is_flagged():
    """A probe stride derived from the raw (pre-mask) hash voids the
    pairwise-independence bound; the trace checker must catch it."""
    mask = 0x1FFFFFFF

    def bad(cand):
        masked = cand & np.uint32(mask)          # the discard site
        stride = cand * np.uint32(0x9E3779B1)    # ...but probes from raw!
        return masked ^ stride

    jx = jax.make_jaxpr(bad)(jnp.uint32(7))
    findings = discard.trace_findings(jx, mask)
    assert findings and "mul" in findings[0], findings

    def good(cand):
        masked = cand & np.uint32(mask)
        stride = masked * np.uint32(0x9E3779B1)  # derived from masked: fine
        return masked ^ stride

    assert discard.trace_findings(jax.make_jaxpr(good)(jnp.uint32(7)),
                                  mask) == []


@pytest.mark.parametrize("masked", [True, False])
def test_kernel_probe_from_undiscarded_bits_is_flagged(masked):
    """The trace pass enters ``pallas_call`` bodies: a decode kernel that
    masks the candidate hash but probes its VMEM filter with the raw value
    is flagged; the same kernel probing the masked hash is clean."""
    from jax.experimental import pallas as pl
    from repro.kernels import decode
    from repro.kernels import ref as kref
    from repro.kernels.plan import DecodeSpec
    spec = DecodeSpec(n=4, log2_m=8)

    def kernel(prefix_ref, h1_ref, bloom_ref, out_ref):
        cand = kref._rotl_const(prefix_ref[...], 1, spec.L) ^ h1_ref[...]
        h = cand & np.uint32(spec.hash_mask)
        out_ref[...] = decode._vmem_probe_hits(
            h if masked else cand, spec.k, spec.log2_m,
            [bloom_ref[...]]).astype(jnp.int32)

    call = pl.pallas_call(kernel, interpret=True,
                          out_shape=jax.ShapeDtypeStruct((8, 128), jnp.int32))
    jx = jax.make_jaxpr(call)(jnp.zeros((8, 1), jnp.uint32),
                              jnp.zeros((1, 128), jnp.uint32),
                              jnp.zeros((8, 128), jnp.uint32))
    findings = discard.trace_findings(jx, spec.hash_mask)
    if masked:
        assert findings == []
    else:
        assert findings and "mul" in findings[0], findings


def test_two_word_probe_from_dependent_bits_is_flagged():
    """At two-word lanes the discard masks the high word: a rank read from
    the raw high word (its dependent top bits) is flagged, one read from
    the masked word is not, and the statistics plan at L = 64 is clean."""
    from repro.kernels.plan import HashSpec
    mask = HashSpec(family="cyclic", n=13, L=64).word_masks[1]

    def bad(lo, hi):
        kept = hi & np.uint32(mask)               # the discard site
        return lo ^ kept ^ (hi >> np.uint32(14))  # ...but a rank from raw

    def good(lo, hi):
        kept = hi & np.uint32(mask)
        return lo ^ (kept >> np.uint32(14))

    args = (jnp.uint32(7), jnp.uint32(9))
    findings = discard.trace_findings(jax.make_jaxpr(bad)(*args), mask)
    assert findings and "shift_right" in findings[0], findings
    assert discard.trace_findings(jax.make_jaxpr(good)(*args), mask) == []
    assert discard.verify_stats_discard() == []


def test_static_discard_rules_on_fixture(tmp_path):
    """DS1 (out_bits-shaped shift) and DS2 (unmasked probe argument) fire on
    a seeded consumer file placed inside the checker's scope."""
    root = tmp_path
    bad = root / "src" / "repro" / "data" / "bad_consumer.py"
    bad.parent.mkdir(parents=True)
    bad.write_text(textwrap.dedent("""
        def probe(h, spec, L, n):
            high = h >> (L - n)                       # DS1: dependent bits
            hits = bloom_probe_hits(h, spec.bits)     # DS2: unmasked probe
            return high ^ hits

        def ok(h, spec):
            hm = h & spec.hash_mask
            return bloom_probe_hits(hm, spec.bits)
    """))
    findings = discard.static_findings(root)
    rules = sorted(f.rule for f in findings)
    assert rules == ["DS1", "DS2"], findings
    assert all(f.path.endswith("bad_consumer.py") for f in findings)


# ---------------------------------------------------------------------------
# seeded lint violations
# ---------------------------------------------------------------------------


def _lint_fixture_tree(tmp_path, rel, body):
    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(body))
    return tmp_path


def test_uint64_unsafe_bincount_is_flagged(tmp_path):
    root = _lint_fixture_tree(tmp_path, "src/repro/data/fix.py", """
        import numpy as np

        def collide(keys):
            combined = keys.astype(np.uint64) << np.uint64(32)
            return np.bincount(combined)            # refuses/truncates u64

        def collide_ok(keys):
            combined = keys.astype(np.uint64) << np.uint64(32)
            return np.bincount(combined.astype(np.int64))
    """)
    findings = lint.lint_tree(root)
    assert [f.rule for f in findings] == ["U64-BINCOUNT"], findings


def test_int32_stream_counter_is_flagged(tmp_path):
    root = _lint_fixture_tree(tmp_path, "src/repro/serve/fix.py", """
        import jax.numpy as jnp

        def init():
            tokens = jnp.zeros((), jnp.int32)       # wraps at ~2.1B
            ring = jnp.zeros((8,), jnp.int32)       # bounded: not a counter
            return tokens, ring
    """)
    findings = lint.lint_tree(root)
    assert [f.rule for f in findings] == ["I32-COUNTER"], findings


def test_donate_without_evidence_is_flagged(tmp_path):
    root = _lint_fixture_tree(tmp_path, "src/repro/kernels/fix.py", """
        import jax

        step = jax.jit(lambda s, b: s, donate_argnums=(0,))
    """)
    findings = lint.lint_tree(root)
    assert [f.rule for f in findings] == ["DONATE-UNCHECKED"], findings

    # the same file with a lowering probe is evidence enough
    root2 = _lint_fixture_tree(tmp_path / "ok", "src/repro/kernels/fix.py", """
        import jax
        from repro.analysis.jaxpr import donation_is_lowered

        step = jax.jit(lambda s, b: s, donate_argnums=(0,))
        assert donation_is_lowered(step.lower(1.0, 2.0).as_text()) or True
    """)
    assert lint.lint_tree(root2) == []


def test_shim_import_is_flagged(tmp_path):
    root = _lint_fixture_tree(tmp_path, "src/repro/data/fix.py", """
        from repro.kernels import cyclic_fused
    """)
    # ImportFrom of the shim module's *name* lives under repro.kernels —
    # flag the attribute form too
    root = _lint_fixture_tree(root, "src/repro/data/fix2.py", """
        import repro.kernels.cyclic_fused
    """)
    findings = lint.lint_tree(root)
    assert findings and all(f.rule == "SHIM-IMPORT" for f in findings)

    marked = _lint_fixture_tree(tmp_path / "ok", "src/repro/data/fix.py", """
        # lint: allow-deprecated-shims — certification oracle
        import repro.kernels.cyclic_fused
    """)
    assert lint.lint_tree(marked) == []


def test_swallowed_fault_is_flagged(tmp_path):
    root = _lint_fixture_tree(tmp_path, "src/repro/data/fix.py", """
        from repro.train.fault import WorkerCrash, ProbeTimeout

        def probe(worker):
            try:
                return worker.call()
            except WorkerCrash:
                pass                        # typed failure dropped silently
            try:
                return worker.call()
            except (ProbeTimeout, ValueError):
                '''even a docstring body observes nothing'''
            try:
                return worker.call()
            except Exception:
                ...
    """)
    findings = lint.lint_tree(root)
    assert [f.rule for f in findings] == ["SWALLOWED-FAULT"] * 3, findings

    # counted, re-raised, or non-fault handlers are all fine
    ok = _lint_fixture_tree(tmp_path / "ok", "src/repro/train/fix.py", """
        from repro.train.fault import WorkerCrash

        def probe(worker, t):
            try:
                return worker.call()
            except WorkerCrash:
                t["failed"] += 1            # observable: counted
            try:
                return worker.call()
            except WorkerCrash:
                raise
            try:
                return worker.call()
            except KeyError:
                pass                        # not a fault-plane type
    """)
    assert lint.lint_tree(ok) == []


def test_unseeded_rng_is_flagged(tmp_path):
    root = _lint_fixture_tree(tmp_path, "src/repro/core/fix.py", """
        import numpy as np

        def tabulate():
            t = np.random.randint(0, 2**32, 256)    # global unseeded RNG
            rng = np.random.default_rng()           # seedless generator
            ok = np.random.default_rng(7)           # explicit seed: fine
            return t, rng, ok
    """)
    findings = lint.lint_tree(root)
    assert sorted(f.rule for f in findings) == ["UNSEEDED-RNG"] * 2, findings


# ---------------------------------------------------------------------------
# the clean tree is silent (the CI gate's exact condition)
# ---------------------------------------------------------------------------


def test_clean_tree_zero_lint_findings():
    assert lint.lint_tree() == []


def test_clean_tree_zero_discard_findings():
    assert discard.static_findings() == []
    assert discard.verify_decode_discard() == []


def test_decode_cell_spec_zero_discard_findings():
    """The decode cell's spec: both filters probed inside the kernel."""
    from repro.kernels.decode import all_probes_in_vmem
    from repro.kernels.plan import DecodeSpec
    spec = DecodeSpec(n=4, log2_m=14, k=2, canary_log2_m=20)
    assert all_probes_in_vmem(spec)
    assert discard.verify_decode_discard(spec) == []


def test_registry_covers_every_entry_point():
    reg = contracts.registry()
    names = {k.rsplit(".", 1)[-1] for k in reg}
    assert {"run", "decode", "run_stream", "run_sharded", "rowwise",
            "step"} <= names
    # run_stream declares all three executor variants
    rs = next(v for k, v in reg.items() if k.endswith("run_stream"))
    assert set(rs) == {"scan", "grid", "host"}


def test_contract_matrix_single_device_clean():
    """The 1-device slice of the matrix (the full 1/2/4/8 sweep runs under
    ``python -m repro.analysis`` / ``./test.sh --analyze``)."""
    violations = contracts.verify_contracts(device_counts=(1,))
    assert violations == [], [str(v) for v in violations]
