"""Driver: a ``DedupService`` deduplicating a closed loop of document batches.

Set-up builds the service of the configuration, hands it the symbol table
and remix lanes drawn from the seed, makes the traffic's document pool and
signs four warm-up groups whose lengths take every block shape the
streaming signer uses (1, 2, 4 and 8 chunks), so nothing compiles in the
window. The warm-up signs only: the index starts the window empty.

The window calls ``add_batch`` back to back, as a batch job does, until
``seconds`` have passed; the last call runs to its end. Each call's
signatures, probe candidates and flags are kept for the check.
"""
from __future__ import annotations

import time

import numpy as np

from bench import generate
from bench.spans import Spans


class Driver:
    def __init__(self, cell, seed: int):
        self.cell = cell
        self.cfg = cell.config["dedup"]
        self.seed = seed
        self.ref = cell.reference()
        self.params = self.ref.draw_params(self.cfg, seed)

    def setup(self) -> None:
        from repro.data.dedup import DedupConfig
        from repro.data.service import DedupService, ServiceConfig
        cfg = self.cfg
        t0 = time.perf_counter()
        self.svc = DedupService(
            DedupConfig(**{k: cfg[k] for k in (
                "ngram_n", "n_signatures", "lsh_bands", "threshold",
                "family", "L", "vocab")}),
            ServiceConfig(**self.cell.config["service"]))
        self.svc.dd.import_params(
            {"fam": {"h1": self.params["h1"]},
             "mh": {"a": self.params["a"], "b": self.params["b"]}})
        t1 = time.perf_counter()
        self.pool = generate.DocumentPool(self.cell.traffic, cfg["vocab"],
                                          self.seed)
        t2 = time.perf_counter()
        chunk = self.svc.dd.cfg.stream_chunk_s
        rows = self.svc.dd.cfg.stream_rows
        rng = np.random.default_rng([self.seed, 0xAA])
        warm = [rng.integers(0, cfg["vocab"], c * chunk, dtype=np.int32)
                for c in (1, 2, 3, 5) for _ in range(rows)]
        self.svc.dd.signature_many(warm)
        self.setup_parts = {"service": t1 - t0, "pool": t2 - t1,
                            "warm_sign": time.perf_counter() - t2}

    def window(self, seconds: float, spans: Spans) -> dict:
        from repro.kernels import stream
        svc = self.svc
        self.sigs, self.probes, self.flags, self.batches = [], [], [], []
        spans.wrap(svc.dd, "signature_many", "bench.sign",
                   keep=self.sigs.append)
        spans.wrap(svc, "_probe_batch", "bench.probe",
                   keep=self.probes.append)
        spans.wrap(svc, "_insert_bands", "bench.insert")
        tokens = docs = 0
        d0 = stream.dispatch_count()
        t0 = time.perf_counter()
        deadline = t0 + seconds
        i = 0
        while True:
            with spans.span("bench.traffic"):
                batch = self.pool.batch(i)
            with spans.span("bench.add_batch"):
                self.flags.append(svc.add_batch(batch))
            self.batches.append(i)
            tokens += sum(len(d) for d in batch)
            docs += len(batch)
            i += 1
            if time.perf_counter() >= deadline:
                break
        t1 = time.perf_counter()
        return {"window_s": t1 - t0, "tokens": tokens,
                "docs": docs, "units": docs, "batches": i,
                "k": int(self.cfg["n_signatures"]),
                "sign_dispatches": stream.dispatch_count() - d0,
                "add_batch_s": spans.seconds["bench.add_batch"],
                "sign_s": spans.seconds["bench.sign"],
                "probe_s": spans.seconds["bench.probe"],
                "insert_s": spans.seconds["bench.insert"]}

    @staticmethod
    def end_to_end(facts: dict) -> dict:
        return {"tokens_per_s": facts["tokens"] / facts["window_s"]}

    def op_counts(self, facts: dict) -> dict:
        """Integer work of signing, from the shapes: per window hash, n
        rotate-and-xor terms; per lane, a multiply, an add and a min."""
        n = int(self.cfg["ngram_n"])
        return {"window_hashes": facts["tokens"],
                "lane_ops": 3 * facts["k"] * facts["tokens"],
                "hash_ops": 2 * n * facts["tokens"]}

    def release(self) -> None:
        """Keep the run's outputs on the host; free the service."""
        self.svc.close()
        self.out_flags = np.concatenate(self.flags)
        starts = np.cumsum([0] + [len(f) for f in self.flags])[:-1]
        self.out = {
            "sigs": np.concatenate(self.sigs),
            "flags": self.out_flags,
            "cands": self.ref.program_candidates(
                list(zip(starts, self.probes)), self.out_flags)}
        del self.svc, self.sigs, self.probes

    def _docs(self):
        return [d for i in self.batches for d in self.pool.batch(i)]

    def check(self) -> dict:
        ref = self.ref.outputs(self._docs(), self.params, self.cfg)
        return self.ref.compare(self.out, ref)

    def control(self) -> dict:
        docs = self._docs()
        ref = self.ref.outputs(docs, self.params, self.cfg)
        ctl = self.ref.outputs(docs, self.params, self.cfg, lane_bits=16)
        return self.ref.compare(ctl, ref)

    def info(self) -> dict:
        dup = np.concatenate([self.pool.batch_dup_of(i) >= 0
                              for i in self.batches])
        flags = self.out_flags
        return {"flagged": int(flags.sum()), "planted": int(dup.sum()),
                "flagged_planted": int((flags & dup).sum())}
