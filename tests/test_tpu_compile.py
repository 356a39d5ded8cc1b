"""The main path's kernels compile for a TPU v5e, with no chip attached.

JAX describes a ``v5e:2x2`` topology and the installed TPU compiler
compiles for one of its devices: what Mosaic refuses (unaligned blocks,
unsigned reductions, gathers from VMEM) fails here, at no chip time. Only
shapes are passed, nothing runs; the interpret-mode parity suites decide
correctness. Each test asserts that the compiled program holds the kernel
(``tpu_custom_call``), so a jnp fallback cannot pass for it.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import api, stream
from repro.kernels.decode import decode_masks_fused
from repro.kernels.plan import (BloomSpec, CountMinSpec, DecodeSpec, HashSpec,
                                HLLSpec, MinHashSpec, SketchPlan)
from repro.kernels.sketch_fused import sketch_plan_fused

B, S = 64, 4096
U32 = jnp.uint32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_as_tpu(monkeypatch):
    """The engine's dispatch sees a TPU (interpret=False) while the process
    stays on the CPU backend."""
    monkeypatch.setattr(api, "on_tpu", lambda: True)


def _sds(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _assert_kernel(lowered):
    assert "tpu_custom_call" in lowered.compile().as_text()


def _plan(family="cyclic", *names, k=64, b=12, log2_width=12):
    specs = {"mh": MinHashSpec(k=k), "hll": HLLSpec(b=b),
             "cms": CountMinSpec(depth=4, log2_width=log2_width),
             "bloom": BloomSpec(k=4, log2_m=22)}
    return SketchPlan(HashSpec(family=family, n=8),
                      tuple((n, specs[n]) for n in names))


def _operands(plan):
    ops = {}
    for name, spec in plan.sketches:
        if isinstance(spec, MinHashSpec):
            ops[name] = {"a": jnp.zeros((spec.k,), U32),
                         "b": jnp.zeros((spec.k,), U32)}
        elif isinstance(spec, CountMinSpec):
            ops[name] = {"a": jnp.zeros((spec.depth,), U32),
                         "b": jnp.zeros((spec.depth,), U32)}
        elif isinstance(spec, BloomSpec):
            ops[name] = {"bits": jnp.zeros((spec.n_words,), U32)}
    return ops


PLAN_CASES = {
    "all-cyclic": (_plan("cyclic", "mh", "hll", "cms", "bloom"), None),
    "all-general": (_plan("general", "mh", "hll", "cms", "bloom"), None),
    "minhash-k64-bs4096": (_plan("cyclic", "mh"), 4096),
    "hll-b12": (_plan("cyclic", "hll"), None),
    "countmin-w12": (_plan("cyclic", "cms"), None),
}


@pytest.mark.parametrize("case", sorted(PLAN_CASES))
def test_plan_kernel_compiles(one_chip, case):
    plan, block_s = PLAN_CASES[case]
    x = jax.ShapeDtypeStruct((B, S), U32, sharding=one_chip)
    xb = x if plan.needs_second_stream else None
    nw = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
    fn = functools.partial(sketch_plan_fused, plan=plan, block_s=block_s,
                           interpret=False)
    _assert_kernel(jax.jit(fn).lower(x, xb, nw,
                                     _sds(_operands(plan), one_chip)))


def test_scan_stream_compiles(one_chip, compiled_as_tpu):
    # the statistics stream: update_many over (T, B, C) chunk stacks, with
    # the carry donated as on the chip
    plan = _plan("cyclic", "hll", "cms", log2_width=16)
    T, C = 8, 4096
    state = _sds(jax.eval_shape(lambda: stream.init_state(plan, B)), one_chip)
    chunks = jax.ShapeDtypeStruct((T, B, C), U32, sharding=one_chip)
    lens = jax.ShapeDtypeStruct((T, B), jnp.int32, sharding=one_chip)
    ops = _sds(api._check_operands(plan, _operands(plan), None), one_chip)
    _assert_kernel(stream._scan_donated.lower(
        plan, False, None, (), None, state, chunks, None, lens, ops))


def test_grid_stream_compiles(one_chip, compiled_as_tpu):
    # executor="grid": the whole stream is one update whose kernel grid is
    # the chunk loop
    plan = _plan("cyclic", "mh", "hll", "cms", "bloom")
    state = _sds(jax.eval_shape(lambda: stream.init_state(plan, B)), one_chip)
    x = jax.ShapeDtypeStruct((B, S), U32, sharding=one_chip)
    lens = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
    ops = _sds(api._check_operands(plan, _operands(plan), None), one_chip)
    _assert_kernel(stream._update_donated.lower(
        plan, False, None, (("block_s", 512),), state, x, x, lens, ops))


def test_decode_kernel_compiles(one_chip):
    # the Llama 3 vocabulary with the shared decontam canary
    spec = DecodeSpec(n=4, log2_m=14, k=2, canary_log2_m=20)
    V = 128256
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    fn = functools.partial(decode_masks_fused, spec=spec, interpret=False)
    _assert_kernel(jax.jit(
        lambda lg, p, r, bl, h, c: fn(lg, p, r, bl, h, canary_bits=c)).lower(
            s((B, V), jnp.float32), s((B,), U32), s((B,), jnp.int32),
            s((B, spec.n_words), U32), s((V,), U32),
            s((spec.canary_words,), U32)))
