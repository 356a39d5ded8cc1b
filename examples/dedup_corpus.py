"""Near-duplicate detection over a corpus with planted duplicates —
MinHash over pairwise-independent CYCLIC fingerprints (Theorem-1 bits).

Run: PYTHONPATH=src python examples/dedup_corpus.py
"""
import time

import numpy as np

from repro.data.corpus import CorpusSpec, documents
from repro.data.dedup import DedupConfig, MinHashDeduper
from repro.compile_cache import enable_compile_cache

enable_compile_cache()

spec = CorpusSpec(n_docs=500, dup_rate=0.25, mutate_frac=0.015, seed=42,
                  vocab=8192)
docs, dup_of = documents(spec)
truth = dup_of >= 0
print(f"{len(docs)} documents, {truth.sum()} planted near-duplicates "
      f"(~{spec.mutate_frac:.1%} token mutations each)")

dd = MinHashDeduper(DedupConfig(vocab=8192, threshold=0.5, ngram_n=8))
t0 = time.perf_counter()
# batched data-plane: one fused signing pass per shape bucket + vectorized
# LSH band probing (same decisions as the streaming check_and_add loop)
flagged = dd.add_batch(docs)
dt = time.perf_counter() - t0

tp = (flagged & truth).sum()
fp = (flagged & ~truth).sum()
fn = (~flagged & truth).sum()
tokens = sum(len(d) for d in docs)
print(f"flagged {flagged.sum()} docs in {dt:.2f}s "
      f"({tokens / dt / 1e3:.0f} ktok/s)")
print(f"recall {tp / truth.sum():.1%}  precision {tp / max(tp + fp, 1):.1%}  "
      f"missed {fn}")
assert tp / truth.sum() > 0.9 and tp / max(tp + fp, 1) > 0.9
print("OK")
