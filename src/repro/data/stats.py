"""Streaming corpus telemetry — the paper's §2 application, productionized.

Per training batch (on-device, jit): rolling hashes -> HyperLogLog
distinct-n-gram registers + CountMin heavy-hitter counts. State is a small
pytree that lives beside the train state and is checkpointed with it.

Both sketch legs ride the fused hash->sketch engine in ONE pass: a
two-sketch (HLL + CountMin) :class:`SketchPlan` is built once at
construction and executed per batch with ``shard.run_auto`` — the rolling
hash, the Theorem-1 discard, the register maxima AND the CountMin partial
histogram all come from a single plan execution (one Pallas kernel on TPU;
one jit graph on CPU), so the window-hash array is computed exactly once
per batch and the per-batch outputs are just the (m,) register file and the
(depth, width) count table. Sharded execution combines them with the
sketches' own merge operators (``pmax`` / ``psum``) — bit-identical at any
device count. :meth:`heavy_hitter_count` queries through the *same* plan
hash graph, so query columns can never drift from update columns.

The token counter accumulates as a uint32 (lo, hi) pair: a plain int32
counter wraps negative at ~2.1B tokens — a few hours of production traffic
— and jnp.int64 silently downcasts when x64 is off, so neither is safe.

Long n-grams want wide lanes: at ``L = 32`` and ``n = 13`` the Theorem-1
discard leaves 20 usable bits, so HLL and CountMin could tell at most 2^20
distinct 13-grams apart. CYCLIC runs up to ``L = 64`` (52 usable bits at
n = 13) on two uint32 words per lane, decided by ``L`` alone: the h1
table, the window hashes and the stream's tail then carry a leading word
axis, HLL ranks span both words, and CountMin takes the vector
multiply-add-shift over the key's pieces.

Observability (``repro.obs``): the span ``stats.update_stream`` covers each
:meth:`NgramStats.update_stream_many` call and the counter ``stats.tokens``
counts the tokens every update folds; on the device, ``stats.lookup``
names the h1 lookup (the plan's scatter epilogues are
``sketch.hll.scatter`` and ``sketch.cms.scatter``).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import CountMinSketch, HyperLogLog, make_family
from repro.kernels import ops, shard, stream
from repro.kernels.plan import CountMinSpec, HashSpec, HLLSpec, SketchPlan


@dataclasses.dataclass
class StatsConfig:
    ngram_n: int = 8
    L: int = 32
    hll_b: int = 12
    cms_depth: int = 4
    cms_log2_width: int = 16
    vocab: int = 1 << 17
    seed: int = 11
    family: str = "cyclic"       # rolling family: cyclic | general (fused);
                                 # other paper families take the unfused path
    impl: str = "auto"           # kernel dispatch: auto | pallas | ref
    # shard the per-batch sketch pass over this many devices (None = single
    # device). HLL registers merge by elementwise max and CountMin counts
    # add, so the sharded pass's single pmax + psum combine is bit-identical
    # to the unsharded sketch states.
    data_shards: Optional[int] = None


def _hash_spec(family: str, n: int, L: int) -> Optional[HashSpec]:
    """Fused-engine HashSpec for the family, or None (unfused fallback)."""
    if family == "cyclic":
        return HashSpec(family="cyclic", n=n, L=L, discard=True)
    if family == "general":
        return HashSpec(family="general", n=n, L=L)
    return None


class NgramStats:
    def __init__(self, cfg: StatsConfig = None, mesh=None):
        self.cfg = cfg = cfg or StatsConfig()
        self.mesh = mesh
        key = jax.random.PRNGKey(cfg.seed)
        kf, kc = jax.random.split(key)
        self.fam = make_family(cfg.family, n=cfg.ngram_n, L=cfg.L)
        self.fp = self.fam.init(kf, cfg.vocab)
        self.hll = HyperLogLog(b=cfg.hll_b,
                               hash_bits=self.fam.out_bits,
                               words=self.fam.words)
        self.cms = CountMinSketch(depth=cfg.cms_depth,
                                  log2_width=cfg.cms_log2_width,
                                  key_bits=self.fam.out_bits,
                                  words=self.fam.words)
        self._cms_params = self.cms.init(kc)
        # the fused HLL+CMS plan, built ONCE (hoisted out of the per-batch
        # update; it is the jit trace key). One plan execution per batch is
        # the whole sketch data-plane.
        hs = _hash_spec(cfg.family, cfg.ngram_n, cfg.L)
        self.plan = None
        if hs is not None:
            self.plan = SketchPlan(
                hs, (("hll", HLLSpec(b=cfg.hll_b)),
                     ("cms", CountMinSpec(depth=cfg.cms_depth,
                                          log2_width=cfg.cms_log2_width))))
            # Theorem-1 consistency: the plan's post-discard width must be
            # the hash_bits the HLL's rank extraction assumes, or the two
            # sketches would disagree on the usable-bit budget
            assert self.plan.hash.out_bits == self.hll.hash_bits, (
                self.plan.hash.out_bits, self.hll.hash_bits)
        self._update = jax.jit(self._update_impl)
        self._lookup = jax.jit(self._lookup_impl)

    def _lookup_impl(self, tokens):
        with jax.named_scope("stats.lookup"):
            return self.fam._lookup(self.fp, tokens)

    def init_state(self) -> Dict:
        # token counter: uint32 (lo, hi) pair — int32 wraps negative at
        # ~2.1B tokens and int64 needs x64; the pair is exact to 2^64
        return {"hll": self.hll.init(), "cms": self._cms_params["table"],
                "tokens": jnp.zeros((2,), jnp.uint32)}

    @staticmethod
    def _count_tokens(tokens_state: jnp.ndarray, added: int) -> jnp.ndarray:
        """(lo, hi) uint32 pair + host-int batch size, with carry."""
        lo0 = tokens_state[0]
        lo = lo0 + np.uint32(added)
        hi = tokens_state[1] + (lo < lo0).astype(jnp.uint32)
        return jnp.stack([lo, hi])

    @staticmethod
    def token_count(state: Dict) -> int:
        """Total tokens seen, as an exact Python int (safe past 2^32)."""
        t = np.asarray(state["tokens"], np.uint32)
        return (int(t[1]) << 32) | int(t[0])

    def _unfused_hashes(self, tokens) -> jnp.ndarray:
        """Fallback-family masked window hashes — the ONE definition shared
        by update and query, so the two legs cannot drift."""
        h = self.fam.hash_windows_batched(self.fp, tokens)
        if hasattr(self.fam, "pairwise_bits"):
            h = self.fam.pairwise_bits(h)
        return h

    def _update_impl(self, state, tokens):
        if self.plan is not None:
            # ONE fused pass: rolling hash + discard + HLL register max +
            # CountMin histogram, all from the same plan execution
            h1v = self._lookup_impl(tokens)
            out = shard.run_auto(
                self.plan, h1v,
                operands={"cms": {"a": self._cms_params["a"],
                                  "b": self._cms_params["b"]}},
                impl=self.cfg.impl, mesh=self.mesh,
                data_shards=self.cfg.data_shards)
            hll_regs = self.hll.merge(state["hll"], out["hll"])
            cms_table = state["cms"] + out["cms"]
        else:
            h = self._unfused_hashes(tokens).reshape(-1)
            hll_regs = self.hll.update(state["hll"], h)
            cms_table = self.cms.add(
                {**self._cms_params, "table": state["cms"]}, h)["table"]
        return {"hll": hll_regs, "cms": cms_table,
                "tokens": self._count_tokens(state["tokens"], tokens.size)}

    def update(self, state: Dict, tokens: jnp.ndarray) -> Dict:
        tokens = jnp.asarray(tokens, jnp.uint32)
        obs.count("stats.tokens", int(tokens.size))
        return self._update(state, tokens)

    # -- true streaming (unbounded token streams, fixed chunk shape) --------

    def init_stream(self, batch: int, state: Optional[Dict] = None) -> Dict:
        """Open ``batch`` parallel unbounded token streams, continuing from
        ``state`` (default: a fresh :meth:`init_state`).

        The whole-batch :meth:`update` recomputes a (B, S) batch's windows
        from scratch each call and cannot span batch boundaries; the stream
        API instead carries the rolling-hash tail and the sketch states
        across arbitrarily many fixed-shape chunks (donated buffers, one
        compiled executor), so an n-gram spanning two chunks of a stream is
        still counted — the paper's one-pass shape. Fused families only.
        """
        if self.plan is None:
            raise ValueError(
                f"streaming stats needs a fused family (cyclic|general), "
                f"not {self.cfg.family!r}")
        state = state or self.init_state()
        sstate = stream.init_state(
            self.plan, batch, carry={"hll": state["hll"],
                                     "cms": state["cms"]},
            mesh=self.mesh, data_shards=self.cfg.data_shards)
        # the true (unpadded) batch rides along so a checkpoint can slice
        # shard padding off and restore elastically onto any device count
        return {"stream": sstate, "tokens": state["tokens"],
                "batch": int(batch)}

    def update_stream(self, sstate: Dict, tokens, lengths=None) -> Dict:
        """Fold one (B, C) token chunk into the stream (rows advance
        independently; ``lengths`` marks the real symbols per row)."""
        tokens = jnp.asarray(tokens, jnp.uint32)
        st = stream.update(
            self.plan, sstate["stream"], self._lookup(tokens),
            lengths=lengths,
            operands={"cms": {"a": self._cms_params["a"],
                              "b": self._cms_params["b"]}},
            impl=self.cfg.impl, mesh=self.mesh,
            data_shards=self.cfg.data_shards)
        added = (int(tokens.shape[0]) * int(tokens.shape[1])
                 if lengths is None else int(np.sum(np.asarray(lengths))))
        obs.count("stats.tokens", added)
        return {**sstate, "stream": st,
                "tokens": self._count_tokens(sstate["tokens"], added)}

    def update_stream_many(self, sstate: Dict, tokens, lengths=None) -> Dict:
        """Fold a (T, B, C) block of T chunks into the stream in ONE device
        dispatch (the scan executor: the chunk loop runs as ``lax.scan``
        inside the compiled graph with the sketch state as the loop carry).
        Bit-identical to T successive :meth:`update_stream` calls, at
        1/T of the dispatch overhead; a fixed block shape never retraces.
        Recorded as the span ``stats.update_stream``."""
        with obs.span("stats.update_stream"):
            tokens = jnp.asarray(tokens, jnp.uint32)
            st = stream.update_many(
                self.plan, sstate["stream"], self._lookup(tokens),
                lengths=lengths,
                operands={"cms": {"a": self._cms_params["a"],
                                  "b": self._cms_params["b"]}},
                impl=self.cfg.impl, mesh=self.mesh,
                data_shards=self.cfg.data_shards)
            added = (int(tokens.size) if lengths is None
                     else int(np.sum(np.asarray(lengths))))
            obs.count("stats.tokens", added)
        return {**sstate, "stream": st,
                "tokens": self._count_tokens(sstate["tokens"], added)}

    def finalize_stream(self, sstate: Dict) -> Dict:
        """Close the stream into an ordinary stats state (the carried HLL
        registers and CMS table ARE the running state — no re-merge)."""
        out = stream.finalize(self.plan, sstate["stream"])
        return {"hll": out["hll"], "cms": out["cms"],
                "tokens": sstate["tokens"]}

    # -- durability ---------------------------------------------------------

    def export_params(self) -> Dict:
        """The sampled draw every estimate depends on (h1/remix tables, CMS
        row constants) as a host pytree — the ``params`` subtree of every
        durable snapshot; :meth:`rebind_params` is its inverse."""
        return {"fam": jax.tree_util.tree_map(np.asarray, self.fp),
                "cms": jax.tree_util.tree_map(np.asarray, self._cms_params)}

    def export_stream(self, sstate: Dict) -> Dict:
        """Snapshot an open stream + the sampled hash params as one host
        pytree. The params MUST persist with the state: HLL register
        indices and CMS columns are functions of this process's h1 / remix
        draw, so a restart that re-draws against a checkpointed table
        silently voids every estimate bound (the restore re-binds them
        first). Mesh-independent — restorable onto any device count."""
        return {"params": self.export_params(),
                "stream": stream.export_state(self.plan, sstate["stream"],
                                              batch=sstate.get("batch")),
                "tokens": np.asarray(sstate["tokens"])}

    def rebind_params(self, params: Dict) -> None:
        """Adopt checkpointed hash params (before importing state). The
        jitted update/lookup closures baked the old arrays as constants,
        so they are re-wrapped."""
        self.fp = jax.tree_util.tree_map(jnp.asarray, params["fam"])
        self._cms_params = jax.tree_util.tree_map(jnp.asarray, params["cms"])
        self._update = jax.jit(self._update_impl)
        self._lookup = jax.jit(self._lookup_impl)

    def import_stream(self, tree: Dict) -> Dict:
        """Rebuild a live stream state from :meth:`export_stream`'s tree on
        THIS instance's mesh (elastic: the exported tree is unpadded, the
        import re-pads for the current device count)."""
        self.rebind_params(tree["params"])
        sstate = stream.import_state(self.plan, tree["stream"],
                                     mesh=self.mesh,
                                     data_shards=self.cfg.data_shards)
        batch = int(np.asarray(tree["stream"]["seen"]).shape[0])
        return {"stream": sstate,
                "tokens": jnp.asarray(tree["tokens"], jnp.uint32),
                "batch": batch}

    def distinct_ngrams(self, state: Dict) -> float:
        return float(self.hll.estimate(state["hll"]))

    def query_hashes(self, tokens: jnp.ndarray) -> jnp.ndarray:
        """(..., S) tokens -> (..., S-n+1) masked window hashes on the SAME
        graph the fused update feeds to CountMin — the query side of the
        sketch must remix bit-identical hashes or frequency estimates
        silently corrupt (asserted in tests/test_data.py). Two-word lanes
        come back as (2, ..., S-n+1)."""
        if self.plan is not None:
            h1v = self.fam._lookup(self.fp, tokens)
            hs = self.plan.hash
            if hs.family == "cyclic":
                h = ops.cyclic(h1v, n=hs.n, L=hs.L, impl=self.cfg.impl)
            else:
                h = ops.general(h1v, n=hs.n, p=hs.p, L=hs.L,
                                impl=self.cfg.impl)
            if hs.words == 2:
                masks = np.array(hs.word_masks, np.uint32)
                return h & masks.reshape((2,) + (1,) * (h.ndim - 1))
            return h & np.uint32(hs.hash_mask)
        return self._unfused_hashes(tokens)

    def heavy_hitter_count(self, state: Dict, tokens: np.ndarray) -> np.ndarray:
        """Estimated frequency of the first window of each given sequence."""
        h = self.query_hashes(jnp.asarray(tokens, jnp.uint32))
        return np.asarray(self.cms.query(
            {**self._cms_params, "table": state["cms"]}, h[..., 0]))
