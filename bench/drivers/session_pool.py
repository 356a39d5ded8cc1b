"""Driver: a ``SessionPool`` taking greedy decode steps in a closed loop.

Set-up builds the pool of the configuration's decode plane over the
configuration's vocabulary with one slot per session of the traffic, hands
it the symbol table and canary filter drawn from the seed, makes a ring of
seeded (sessions, vocab) float32 logits on the device in one call (they
stand in for the model's output), admits every session, primes the
prompts and takes one warm step.

The window steps the pool on the ring, one logits array after another,
until ``seconds`` have passed; each step ends when its tokens are on the
host, as a server needs them. The last step runs to its end. The wall
time of every window step is kept; its quantiles are facts for the log,
not metrics.
"""
from __future__ import annotations

import time

import numpy as np

from bench import generate
from bench.spans import Spans


def step_quantiles(ms: np.ndarray) -> dict:
    """Median, 99th percentile and longest of the window's step times
    (ms), the steps over twice the median, and the mean of the first 8."""
    p50 = float(np.median(ms))
    return {"step_ms_p50": p50, "step_ms_p99": float(np.quantile(ms, 0.99)),
            "step_ms_max": float(ms.max()),
            "steps_over_2x_median": int((ms > 2 * p50).sum()),
            "step_ms_first8_mean": float(ms[:8].mean())}


class Driver:
    def __init__(self, cell, seed: int):
        self.cell = cell
        self.plane = cell.config["decode_plane"]
        self.traffic = cell.traffic
        self.seed = seed
        self.ref = cell.reference()
        self.params = self.ref.draw_params(cell.config, seed)
        self.vocab = int(cell.config["vocab_size"])
        self.sessions = int(self.traffic["sessions"])
        rng = np.random.default_rng([seed, 0x5A3])
        self.rows = np.sort(rng.choice(
            self.sessions, min(self.sessions, self.ref.CHECK_ROWS),
            replace=False))

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp
        from repro.kernels.plan import DecodeSpec
        from repro.serve.sessions import SessionPool
        p = self.plane
        t0 = time.perf_counter()
        spec = DecodeSpec(n=p["n"], L=p["L"], log2_m=p["log2_m"], k=p["k"],
                          canary_log2_m=p["canary_log2_m"],
                          canary_k=p["canary_k"])
        self.pool = SessionPool(spec, self.sessions, self.params["h1"],
                                canary_bits=self.params["canary"])
        shape = (self.sessions, self.vocab)
        ring = int(self.traffic["logit_ring"])
        key = jax.random.PRNGKey(int(np.random.default_rng(
            [self.seed, 0x1061]).integers(1 << 31)))
        # one device call makes the whole ring, one array per step slot
        self.ring = jax.jit(lambda k: tuple(
            jax.random.normal(kk, shape, jnp.float32)
            for kk in jax.random.split(k, ring)))(key)
        self.key = jax.random.PRNGKey(0)
        jax.block_until_ready(self.ring)
        t1 = time.perf_counter()
        self.prompts = generate.prompts(self.traffic, self.vocab, self.seed)
        self.pool.admit(self.sessions)
        self.pool.prime(self.prompts)
        jax.block_until_ready(self.pool.state)
        t2 = time.perf_counter()
        self.tokens = [self._step(0)]
        self.setup_parts = {"pool_and_ring": t1 - t0, "prime": t2 - t1,
                            "warm_step": time.perf_counter() - t2}

    def _step(self, t: int) -> np.ndarray:
        tok = self.pool.step(self.ring[t % len(self.ring)], key=self.key,
                             temperature=0.0)
        return np.asarray(tok)

    def window(self, seconds: float, spans: Spans) -> dict:
        t = len(self.tokens)
        ends = [time.perf_counter()]
        deadline = ends[0] + seconds
        while True:
            with spans.span("bench.step"):
                self.tokens.append(self._step(t))
            t += 1
            ends.append(time.perf_counter())
            if ends[-1] >= deadline:
                break
        t0, t1 = ends[0], ends[-1]
        steps = t - 1
        carry = sum(int(np.prod(v.shape)) * v.dtype.itemsize
                    for v in self.pool.state.values())
        return {"window_s": t1 - t0, "steps": steps,
                "units": steps * self.sessions, "sessions": self.sessions,
                "vocab": self.vocab, "carry_bytes": carry,
                "n": int(self.plane["n"]), "k": int(self.plane["k"]),
                "canary_k": int(self.plane["canary_k"]),
                **step_quantiles(np.diff(ends) * 1e3)}

    @staticmethod
    def end_to_end(facts: dict) -> dict:
        return {"decode_step_ms": 1e3 * facts["window_s"] / facts["steps"]}

    @staticmethod
    def op_counts(facts: dict) -> dict:
        """Integer work of the plane per window, from the shapes: one
        candidate hash per (session, token), each probed k + canary_k
        times (a multiply-add, a shift, a read and a test per probe)."""
        cand = facts["steps"] * facts["sessions"] * facts["vocab"]
        return {"candidates": cand,
                "probe_reads": cand * (facts["k"] + facts["canary_k"]),
                "probe_ops": 4 * cand * (facts["k"] + facts["canary_k"])}

    def release(self) -> None:
        """Keep the run's tokens, its carry at the checked rows and the
        logits those rows saw; free the pool and the ring."""
        self.out = {"tokens": np.stack(self.tokens)[:, self.rows],
                    "carry": self.ref.carry_rows(
                        {k: np.asarray(v) for k, v in
                         self.pool.state.items()}, self.rows)}
        import jax.numpy as jnp
        rows = jnp.asarray(self.rows)
        self.logits = np.stack([np.asarray(lg[rows]) for lg in self.ring])
        del self.pool, self.ring

    def _reference(self, k=None) -> dict:
        return self.ref.run(
            self.cell.config, self.params, self.prompts[self.rows],
            lambda t: self.logits[t % len(self.logits)], len(self.tokens),
            k=k)

    def check(self) -> dict:
        return self.ref.compare(self.out, self._reference())

    def control(self) -> dict:
        return self.ref.compare(self._reference(k=self.plane["k"] // 2),
                                self._reference())

    def info(self) -> dict:
        banned = self.out["carry"]["banned"]
        return {"checked_rows": len(self.rows),
                "steps_checked": len(self.tokens),
                "banned_candidates_checked_rows": int(banned.sum())}
