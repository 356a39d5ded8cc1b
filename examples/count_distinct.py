"""The paper's §2 motivation, at corpus scale: estimate the number of
DISTINCT 15-grams in a 4.3-Mchar corpus with 16 KB of state.

(The paper: "Shakespeare's First Folio has over 3 million distinct
15-grams" — our KJB-sized corpus has ~4.3M.)

Subtlety reproduced here: Theorem 1 costs n-1 bits, so at n=15 a 32-bit
CYCLIC hash keeps only 18 pairwise-independent bits — enough for at most
~2^18 distinct values. The paper sizes hashes as 19+n bits (§11); the
fixed-lane-width equivalent is TWO independent CYCLIC draws — register
index from one, trailing-zero rank from the other — jointly pairwise
independent because the draws are independent.

Run: PYTHONPATH=src python examples/count_distinct.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import HyperLogLog, make_family
from repro.data.corpus import bench_corpus
from repro.compile_cache import enable_compile_cache

enable_compile_cache()

N = 15
corpus = bench_corpus(4_300_000)
print(f"corpus: {len(corpus):,} chars; counting distinct {N}-grams")

fam = make_family("cyclic", n=N, L=32)
ka, kb = jax.random.split(jax.random.PRNGKey(0))
pa, pb = fam.init(ka, 256), fam.init(kb, 256)
hll = HyperLogLog(b=12)

t0 = time.perf_counter()
tokens = jnp.asarray(corpus)
h_idx = fam.pairwise_bits(fam.hash_windows(pa, tokens))
h_rank = fam.pairwise_bits(fam.hash_windows(pb, tokens))
regs = hll.update_split(hll.init(), h_idx, h_rank, rank_bits=fam.out_bits)
est = float(hll.estimate(regs))
t_hash = time.perf_counter() - t0
print(f"HLL estimate: {est:,.0f} distinct {N}-grams "
      f"({t_hash:.2f}s, {len(corpus)/t_hash/1e6:.1f} Mchar/s, "
      f"{hll.m * 4} bytes of state)")

t0 = time.perf_counter()
wins = np.lib.stride_tricks.sliding_window_view(np.asarray(corpus, np.uint8), N)
truth = len({w.tobytes() for w in wins})
t_exact = time.perf_counter() - t0
print(f"exact count:  {truth:,} ({t_exact:.2f}s, "
      f"{truth * N / 1e6:.0f} MB of set keys)")
print(f"relative error: {abs(est - truth) / truth:.2%}")
assert abs(est - truth) / truth < 0.1
print("OK")
