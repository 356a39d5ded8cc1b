"""Driver: an ``NgramStats`` stream folding a packed training stream.

Set-up builds the statistics pass of the configuration (which raises at
once where the program cannot run its lanes), hands it the symbol table
and CountMin constants drawn from the seed, makes the traffic's document
pool, packs it end to end with one EOS token after each document into
``rows`` streams, cuts each row into ``ring_steps`` calls of ``row_len``
tokens (row r of call s continues row r of call s-1), puts that ring on
the device, and folds one warm call so nothing compiles in the window.

The window opens a fresh stream (``init_stream``) and folds one call per
step, closed loop: call i is ring step ``i % ring_steps`` relabelled on
the device by the pool's affine bijection of pass ``i // ring_steps``,
and each call ends when its state is ready on the device. The last call
runs to its end. The check replays every call of the window.
"""
from __future__ import annotations

import time

import numpy as np

from bench import generate
from bench.spans import Spans

# the program's span around each update_stream_many call
UPDATE_SPAN = "stats.update_stream"


def packed_ring(traffic: dict, vocab: int, seed: int):
    """(ring_steps, rows, row_len) int32 packed calls and the pool they
    came from. Documents follow each other with one EOS (the last id of
    the vocabulary) after each; a pool shorter than the ring repeats."""
    pool = generate.DocumentPool(traffic, vocab, seed)
    steps, rows, row_len = (int(traffic["ring_steps"]), int(traffic["rows"]),
                            int(traffic["row_len"]))
    n_docs = len(pool.offsets) - 1
    packed = np.empty(len(pool.tokens) + n_docs, np.int32)
    eos = pool.offsets[1:] + np.arange(n_docs)
    is_doc = np.ones(len(packed), bool)
    is_doc[eos] = False
    packed[is_doc] = pool.tokens
    packed[eos] = vocab - 1
    need = steps * rows * row_len
    if len(packed) < need:
        packed = np.resize(packed, need)
    ring = packed[:need].reshape(rows, steps, row_len).swapaxes(0, 1)
    return np.ascontiguousarray(ring), pool


def _obs_total(path: str) -> float:
    """The program's total seconds under span ``path`` so far (0.0 where it
    has no recorder or no such span)."""
    try:
        from repro import obs
    except ImportError:
        return 0.0
    return obs.totals()["spans"].get(path, {}).get("total_s", 0.0)


class Driver:
    def __init__(self, cell, seed: int):
        self.cell = cell
        self.cfg = cell.config["stats"]
        self.traffic = cell.traffic
        self.seed = seed
        self.ref = cell.reference()
        self.params = self.ref.draw_params(self.cfg, seed)
        self.vocab = int(self.cfg["vocab"])
        self.steps = int(self.traffic["ring_steps"])

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp
        from repro.data.stats import NgramStats, StatsConfig
        cfg = self.cfg
        t0 = time.perf_counter()
        self.stats = NgramStats(StatsConfig(**{k: cfg[k] for k in (
            "ngram_n", "L", "hll_b", "cms_depth", "cms_log2_width", "vocab",
            "family")}))
        self.stats.rebind_params({
            "fam": {"h1": self.ref.h1_words(self.params["h1"])},
            "cms": {"a": self.params["cms_a"], "b": self.params["cms_b"],
                    "table": np.zeros((cfg["cms_depth"],
                                       1 << cfg["cms_log2_width"]),
                                      np.int32)}})
        t1 = time.perf_counter()
        self.ring_host, self.pool = packed_ring(self.traffic, self.vocab,
                                                self.seed)
        t2 = time.perf_counter()
        self.ring = jax.device_put(self.ring_host)
        mask = np.uint32(self.vocab - 1)
        # pass p maps token t to (a_p * t + c_p) mod vocab on the device
        self._relabel = jax.jit(
            lambda t, a, c: (t.astype(jnp.uint32) * a + c) & mask)
        st = self.stats.init_stream(int(self.traffic["rows"]))
        st = self.stats.update_stream_many(st, self._call(0)[None])
        jax.block_until_ready(self.stats.finalize_stream(st))
        self.setup_parts = {"stats": t1 - t0, "pool_and_pack": t2 - t1,
                            "ring_and_warm": time.perf_counter() - t2}

    def _map(self, i: int):
        return self.pool._map(i // self.steps)

    def _call(self, i: int):
        """Call i's (rows, row_len) tokens, relabelled on the device."""
        a, c = self._map(i)
        return self._relabel(self.ring[i % self.steps], np.uint32(a),
                             np.uint32(c))

    def window(self, seconds: float, spans: Spans) -> dict:
        import jax
        stats = self.stats
        rows, row_len = int(self.traffic["rows"]), int(self.traffic["row_len"])
        state = stats.init_stream(rows)
        jax.block_until_ready(state["stream"])
        span0 = _obs_total(UPDATE_SPAN)
        t0 = time.perf_counter()
        deadline = t0 + seconds
        i = 0
        while True:
            with spans.span("bench.relabel"):
                toks = self._call(i)
            with spans.span("bench.update"):
                state = stats.update_stream_many(state, toks[None])
                jax.block_until_ready(state["stream"])
            i += 1
            if time.perf_counter() >= deadline:
                break
        t1 = time.perf_counter()
        self.state, self.calls = state, i
        cfg = self.cfg
        return {"window_s": t1 - t0, "calls": i, "units": i,
                "tokens": i * rows * row_len, "rows": rows,
                "row_len": row_len, "hll_b": int(cfg["hll_b"]),
                "cms_depth": int(cfg["cms_depth"]),
                "cms_log2_width": int(cfg["cms_log2_width"]),
                "update_s": spans.seconds["bench.update"],
                "relabel_s": spans.seconds["bench.relabel"],
                "stats_update_s0": span0}

    @staticmethod
    def end_to_end(facts: dict) -> dict:
        return {"tokens_per_s": facts["tokens"] / facts["window_s"]}

    def op_counts(self, facts: dict) -> dict:
        """Integer work per window, from the shapes: per token one h1 read
        of two words and n two-word rotate-and-xor terms; per window one
        register update and ``depth`` counter updates."""
        n = int(self.cfg["ngram_n"])
        return {"h1_reads": 2 * facts["tokens"],
                "hash_ops": 2 * 2 * n * facts["tokens"],
                "register_updates": facts["tokens"],
                "counter_updates": facts["cms_depth"] * facts["tokens"]}

    def release(self) -> None:
        """Keep the window's final registers, table and token count on the
        host; free the stream and the ring."""
        final = self.stats.finalize_stream(self.state)
        self.out = {"hll": np.asarray(final["hll"]),
                    "cms": np.asarray(final["cms"]),
                    "tokens": self.stats.token_count(final)}
        self.distinct = self.stats.distinct_ngrams(final)
        del self.state, self.ring, self.stats

    def _reference(self, L: int = None):
        maps = [self.pool._map(p)
                for p in range((self.calls - 1) // self.steps + 1)]
        return self.ref.run(self.cfg, self.params, self.ring_host, maps,
                            self.calls, L=L)

    def check(self) -> dict:
        return self.ref.compare(self.out, self._reference())

    def control(self) -> dict:
        ref = self._reference()
        ctl = self._reference(L=32)
        return self.ref.compare(self.ref.as_program(ctl), ref)

    def info(self) -> dict:
        hll = self.out["hll"]
        return {"calls_checked": self.calls,
                "distinct_estimate": self.distinct,
                "registers_set": int((hll > 0).sum()),
                "max_rank": int(hll.max()),
                "cms_row0_max": int(self.out["cms"][0].max())}
