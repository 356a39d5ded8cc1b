"""Batched serving with the rolling-hash no-repeat-ngram sampler.

Run: PYTHONPATH=src python examples/serve_lm.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_config
from repro.nn import lm
from repro.serve.engine import SamplerConfig, ServeEngine
from repro.compile_cache import enable_compile_cache

enable_compile_cache()

cfg = get_config("paper-tiny").smoke()
params, _ = lm.init(jax.random.PRNGKey(0), cfg)
prompts = jax.random.randint(jax.random.PRNGKey(1), (4, 8), 0, cfg.vocab)

print(f"serving {cfg.name}-smoke: batch=4, prompt_len=8, greedy decode\n")

plain = ServeEngine(cfg, params, SamplerConfig(temperature=0.0))
out_plain, _ = plain.generate(prompts, 32)

guarded = ServeEngine(cfg, params,
                      SamplerConfig(temperature=0.0, no_repeat_ngram=3))
out_guard, stats = guarded.generate(prompts, 32)


def repeated_ngrams(row, n=3):
    grams = [tuple(row[i:i+n]) for i in range(len(row) - n + 1)]
    return len(grams) - len(set(grams))

for b in range(4):
    print(f"seq {b}: unconstrained repeats {repeated_ngrams(out_plain[b])} "
          f"3-grams; with hash filter {repeated_ngrams(out_guard[b])}")
print(f"\ncandidates banned by the rolling-hash filter: "
      f"{stats['banned_candidates']}")
t = stats["telemetry"]   # accumulated on device by the fused decode plane
print(f"decode-plane telemetry: banned_rate={t['banned_rate']:.2e} "
      f"bloom_fill_mean={t['bloom_fill_mean']:.4f} "
      f"pool dispatches={t['dispatches']}")
assert all(repeated_ngrams(out_guard[b]) == 0 for b in range(4))
print("OK — no 3-gram repeated under the filter")
