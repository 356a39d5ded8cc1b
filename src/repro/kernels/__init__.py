"""Pallas TPU kernels for the paper's compute hot-spots (the rolling hash
itself) and their data-plane consumers.

- plan.py          declarative SketchPlan: HashSpec (cyclic|general, n, L,
                   discard, p) + named MinHash/HLL/Bloom/CountMin sketch
                   specs; frozen/hashable, i.e. jit static trace keys
- api.py           the plan engine: api.run(plan, h1v, ...) executes every
                   requested sketch in ONE rolling-hash device pass; also
                   the shared validated prologue (flatten, impl dispatch,
                   S >= n check, n_windows normalization)
- shard.py         multi-device plan execution: api.run wrapped in shard_map
                   over a 1-D data mesh (row-parallel MinHash/Bloom outputs,
                   one pmax combine for HLL registers, one psum for the
                   CountMin table; bit-identical at any device count via
                   n_windows=0 padding rows; Mesh cached per device set)
- stream.py        chunked streaming executor: fixed (B, chunk_S) tiles with
                   an explicit carry (rolling-hash tail + every sketch's
                   state via its `init` operand), donated between chunks —
                   ONE compiled shape for any stream length, bit-identical
                   to one-shot api.run; composes with shard.py's data mesh
- cyclic.py        rolling CYCLIC hash: direct-window + parallel-prefix modes
- general.py       rolling GENERAL hash (clmul shift-reduce, trace-time consts)
- sketch_fused.py  THE fused-kernel module: the plan kernel (family-generic
                   tile hashes feeding every requested sketch epilogue;
                   MinHash/HLL/narrow-CountMin state reduced in VMEM scratch
                   inside the grid loop, Bloom probes and wide CountMin
                   tables in XLA over the emitted hashes, same jit graph)
                   plus the fused byte->fingerprint kernel (one-hot MXU
                   table lookup + window); cyclic_fused.py is a deprecation
                   shim over the latter
- decode.py        decode-time epilogue: XLA candidate hashing + probes, one
                   kernel pass masking the logits and packing the masks
- bloom.py         Bloom membership probes (standalone decontamination scan)
- hll.py           HyperLogLog register update (standalone telemetry)
- ops.py           jit wrappers for the plain hash kernels + DEPRECATED
                   cyclic_{minhash,hll,bloom} shims over the plan engine
- ref.py           pure-jnp oracles, incl. the single-jit plan executor

All kernels use pl.pallas_call with explicit BlockSpec VMEM tiling and are
validated in interpret mode against ref.py across shape/dtype sweeps
(tests/test_kernels.py, tests/test_sketch_fused.py, tests/test_plan_api.py).
"""
