"""Quickstart: the paper's hash families in 60 seconds.

Run: PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import CountMinSketch, HyperLogLog, MinHash, make_family
from repro.core import independence as ind
from repro.kernels import api
from repro.kernels.plan import (CountMinSpec, HashSpec, HLLSpec, MinHashSpec,
                                SketchPlan)
from repro.compile_cache import enable_compile_cache

enable_compile_cache()

key = jax.random.PRNGKey(0)
text = b"recursive n-gram hashing is pairwise independent, at best"
tokens = jnp.asarray(np.frombuffer(text, dtype=np.uint8))
n = 5

print("=== 1. One family, three mathematically identical evaluation forms ===")
fam = make_family("cyclic", n=n, L=32)
params = fam.init(key, 256)
direct = fam.hash_windows_direct(params, tokens)
stream = fam.hash_stream(params, tokens)          # paper Algorithm 4 (scan)
parallel = fam.hash_windows(params, tokens)       # TPU prefix-XOR form
assert bool(jnp.all(direct == stream) and jnp.all(direct == parallel))
print(f"hashed {len(text)} chars -> {direct.shape[0]} {n}-gram fingerprints")
print("first 4:", [hex(int(h)) for h in direct[:4]])

print("\n=== 2. The paper's theorems, exactly (enumeration, small L) ===")
gen = make_family("general", n=2, L=4)
print("GENERAL pairwise independent:",
      ind.is_kwise_independent(gen, [[0, 0], [1, 1]], sigma=2))
print("GENERAL 3-wise (Prop 1 says impossible):",
      ind.is_kwise_independent(gen, [[0, 0], [0, 1], [1, 1]], sigma=2))
cyc = make_family("cyclic", n=2, L=4)
print("CYCLIC uniform on raw bits (Lemma 3 says no):",
      ind.is_uniform(cyc, [0, 0], sigma=1))
print("CYCLIC pairwise after dropping n-1 bits (Thm 1):",
      ind.is_kwise_independent(cyc, [[0, 0], [1, 1]], sigma=2,
                               transform=cyc.pairwise_bits, bits=cyc.out_bits))

print("\n=== 3. Why it matters: count distinct n-grams without storing them ===")
rng = np.random.default_rng(0)
big = jnp.asarray(rng.integers(0, 256, size=200_000), jnp.uint32)
fam8 = make_family("cyclic", n=8, L=32)
p8 = fam8.init(key, 256)
hashes = fam8.pairwise_bits(fam8.hash_windows(p8, big))
hll = HyperLogLog(b=10, hash_bits=fam8.out_bits)
est = float(hll.estimate(hll.update(hll.init(), hashes)))
wins = np.lib.stride_tricks.sliding_window_view(np.asarray(big), 8)
truth = len({w.tobytes() for w in wins})
print(f"HLL estimate: {est:,.0f}   exact: {truth:,}   "
      f"error: {abs(est-truth)/truth:.2%}  (1KB of state vs {truth*8/1e6:.1f}MB)")

print("\n=== 4. The production data-plane: one pass, every sketch ===")
# Declarative SketchPlan: the family is a parameter (cyclic | general), and
# MinHash signatures + HLL registers come out of ONE rolling-hash device
# pass (api.run) instead of one pass per sketch.
mh = MinHash(k=16)
mhp = mh.init(jax.random.PRNGKey(1))
cms = CountMinSketch(depth=4, log2_width=12)
cmsp = cms.init(jax.random.PRNGKey(2))
plan = SketchPlan(hash=HashSpec(family="cyclic", n=8, L=32),
                  sketches={"sig": MinHashSpec(k=16), "card": HLLSpec(b=10),
                            "freq": CountMinSpec(depth=4, log2_width=12)})
out = api.run(plan, fam8._lookup(p8, big[None, :]),
              operands={"sig": {"a": mhp["a"], "b": mhp["b"]},
                        "freq": {"a": cmsp["a"], "b": cmsp["b"]}})
est_plan = float(hll.estimate(out["card"]))
heavy = int(out["freq"].max())             # most counted column per CMS row
print(f"plan {plan.hash.family}/n={plan.hash.n}: MinHash sig {out['sig'].shape}, "
      f"HLL estimate {est_plan:,.0f}, CMS heaviest cell {heavy} — one fused "
      f"pass for all three")
assert est_plan == est                     # same registers as the §3 pass
gplan = SketchPlan(hash=HashSpec(family="general", n=8, L=32),
                   sketches={"sig": MinHashSpec(k=16)})
gfam = make_family("general", n=8, L=32)
gp = gfam.init(key, 256)
gout = api.run(gplan, gfam._lookup(gp, big[None, :]),
               operands={"sig": {"a": mhp["a"], "b": mhp["b"]}})
print(f"same plan, GENERAL family (p={hex(gplan.hash.p)}): "
      f"sig {gout['sig'].shape} — swap the family, keep the pipeline")

print("\n=== 5. Scaling out: the same plan over every device ===")
# shard.run_sharded is api.run wrapped in shard_map over a 1-D data mesh:
# signature rows are row-parallel, HLL registers merge with one pmax (max
# IS the HLL merge), CountMin tables with one psum (counts are additive),
# and ragged batches are padded with n_windows=0 rows — so the outputs
# below are bit-identical to the single-device ones at any device count.
from repro.kernels import shard

docs = jnp.asarray(rng.integers(0, 256, size=(5, 4096)), jnp.uint32)  # ragged vs d
plan_ops = {"sig": {"a": mhp["a"], "b": mhp["b"]},
            "freq": {"a": cmsp["a"], "b": cmsp["b"]}}
sharded = shard.run_sharded(plan, fam8._lookup(p8, docs), operands=plan_ops)
single = api.run(plan, fam8._lookup(p8, docs), operands=plan_ops)
assert (sharded["sig"] == single["sig"]).all()
assert (sharded["card"] == single["card"]).all()
assert (sharded["freq"] == single["freq"]).all()   # one psum, same counts
print(f"{len(jax.devices())} device(s), batch of {docs.shape[0]}: "
      f"sharded sig/registers/counts bit-identical to api.run")
