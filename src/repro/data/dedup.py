"""Hash-based near-duplicate detection — the paper's families in production.

Per document: rolling CYCLIC hashes of every n-gram (Theorem-1 bits only)
feed a MinHash signature; Jaccard over signatures >= `threshold` flags a
near-duplicate. Pairwise independence of the window hashes is exactly what
makes the MinHash collision estimator unbiased, and it is the property the
paper proves CYCLIC (after the (n-1)-bit discard) to have.

The data-plane is *streamed, batched and fused*: a one-MinHash
:class:`SketchPlan` is built once at construction and documents are signed
by the on-device streaming scan executor (:mod:`repro.kernels.stream`) —
groups of ``stream_rows`` documents advance through fixed
``(stream_block_chunks, stream_rows, stream_chunk_s)`` chunk blocks, each
block folded by ONE device dispatch (``stream.update_many``: the chunk
loop is a ``lax.scan`` inside the compiled graph, the signature state is
the scan carry, donated in place), with the next block's host->device
transfer double-buffered behind the in-flight scan (``stream.feed``). The
whole corpus signs through ONE compiled executor shape — any document
length, including documents longer than one device buffer — where the old
shape-bucket group-by paid one jit compile and one dispatch per
power-of-two length bucket. The rolling hash (CYCLIC or GENERAL), the
Theorem-1 discard, and the k-lane affine remix + min still all happen in a
single fused device pass per chunk; masked windows are excluded from the
min outright, so signatures are independent of chunking and bit-identical
to the one-shot bucketed path (demoted to :meth:`_signature_many_bucketed`
— a test-only parity oracle that doubles as the fallback for families
outside the fused engine).

Scaling out (two independent axes):
* **signing** — a ``mesh``/``data_shards`` knob routes the bucket batches
  through :func:`repro.kernels.shard.run_sharded`: the same plan executes
  under ``shard_map`` over the batch dimension of a 1-D data mesh
  (signature rows are row-parallel; bit-identical at any device count).
* **the LSH index** — :class:`BandShardedLSHIndex` partitions the band->key
  map by band id. Every band's shard is probed/inserted independently, so
  probes fan out across bands (optionally on a thread pool via
  ``lsh_workers``, or across hosts in a service deployment) while the
  sequential candidate-verify loop keeps streaming first-wins order exact.

Operating modes:
* :meth:`MinHashDeduper.add_batch`  — batched corpus dedup: one signing pass
  per bucket, then a vectorized NumPy group-by over LSH band keys generates
  candidates; only candidate pairs are verified, sequentially, preserving
  streaming first-wins semantics exactly.
* :meth:`MinHashDeduper.check_and_add` — per-document streaming API (kept
  for online ingest; same index state as add_batch, so the two compose).
* :func:`signature_batch` — the *unfused* reference signature computation
  (hash array materialised, then re-mixed); kept as the parity oracle.
* :func:`signature_batch_fused` — the fused device-side equivalent for
  (B, S) batches inside the training input pipeline.
"""
from __future__ import annotations

import dataclasses
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import Cyclic, General, MinHash, make_family
from repro.kernels import api, shard, stream
from repro.kernels import ref as kref
from repro.kernels.plan import HashSpec, MinHashSpec, SketchPlan

_SENTINEL = np.uint32(0xFFFFFFFF)


def _plan_for_family(fam, k: int) -> Optional[SketchPlan]:
    """One-MinHash SketchPlan for a fused-capable family, else None.

    CYCLIC and GENERAL ride the fused engine (``api.run``); other paper
    families (THREEWISE, ID37, ...) keep the generic unfused fallback.
    """
    if isinstance(fam, Cyclic):
        hs = HashSpec(family="cyclic", n=fam.n, L=fam.L, discard=True)
    elif isinstance(fam, General):
        hs = HashSpec(family="general", n=fam.n, L=fam.L, p=fam.p)
    else:
        return None
    return SketchPlan(hs, (("sig", MinHashSpec(k=k)),))


@dataclasses.dataclass
class DedupConfig:
    ngram_n: int = 8
    L: int = 32
    n_signatures: int = 64
    lsh_bands: int = 16          # bands x rows = n_signatures
    threshold: float = 0.7
    family: str = "cyclic"
    vocab: int = 1 << 17
    seed: int = 0
    impl: str = "auto"           # kernel dispatch: auto | pallas | ref
    # multi-device signing: shard the bucket batches over the first
    # data_shards devices (None = single-device api.run; a Deduper can also
    # be handed an explicit mesh at construction)
    data_shards: Optional[int] = None
    # probe the band-sharded LSH index on a thread pool of this many workers
    # (0/1 = in-line; band shards are independent either way)
    lsh_workers: int = 0
    # chunked streaming signing: documents advance through fixed
    # (stream_rows, stream_chunk_s) tiles — ONE compiled shape for the
    # whole corpus, any document length
    stream_rows: int = 64
    stream_chunk_s: int = 512
    # chunks folded per device dispatch: the scan executor runs blocks of
    # this many chunks inside one compiled lax.scan, so the host pays
    # 1/stream_block_chunks of the old per-chunk dispatch overhead
    stream_block_chunks: int = 8
    # donate the carried signature state between chunks ("auto": on for
    # backends with donation support)
    stream_donate: object = "auto"


def pack_band(shard: Dict[bytes, List[int]]) -> Dict[str, np.ndarray]:
    """One LSH band shard -> a flat pytree of arrays (checkpointable).

    Keys and id lists are variable-length, so both are stored flattened
    with offset vectors; insertion order is preserved exactly, which is
    what makes a packed->unpacked index *bit-identical* in behaviour (probe
    results are sets, but candidate id order feeds the first-wins verify
    loop through ``sorted``, and future inserts must append in the same
    order the live index would have).
    """
    keys = list(shard.keys())
    key_off = np.zeros(len(keys) + 1, np.int64)
    np.cumsum([len(k) for k in keys], out=key_off[1:])
    ids = [shard[k] for k in keys]
    id_off = np.zeros(len(keys) + 1, np.int64)
    np.cumsum([len(v) for v in ids], out=id_off[1:])
    return {
        "key_bytes": (np.frombuffer(b"".join(keys), np.uint8)
                      if keys else np.zeros((0,), np.uint8)),
        "key_offsets": key_off,
        "ids": (np.concatenate([np.asarray(v, np.int64) for v in ids])
                if keys else np.zeros((0,), np.int64)),
        "id_offsets": id_off,
    }


def unpack_band(tree) -> Dict[bytes, List[int]]:
    """Inverse of :func:`pack_band` (order-preserving)."""
    kb = np.asarray(tree["key_bytes"], np.uint8).tobytes()
    ko = np.asarray(tree["key_offsets"], np.int64)
    ids = np.asarray(tree["ids"], np.int64)
    io = np.asarray(tree["id_offsets"], np.int64)
    return {kb[ko[i]:ko[i + 1]]: [int(x) for x in ids[io[i]:io[i + 1]]]
            for i in range(len(ko) - 1)}


def _bucket(n: int) -> int:
    """Next power-of-two length >= n: O(log) distinct jit shapes (the
    bucketed fallback/baseline path only; the min-64 floor that papered
    over the engine's old S < n rejection is gone — short rows are legal
    and simply carry n_windows = 0)."""
    return 1 << int(np.ceil(np.log2(max(n, 2))))


class BandShardedLSHIndex:
    """The LSH band->key map, partitioned by band id.

    Each band owns an independent ``{band_key: [doc_id, ...]}`` shard, so a
    probe (or insert) decomposes into ``n_bands`` disjoint lookups that can
    run concurrently — on a thread pool here, or one shard per host in a
    service deployment (shard b of a multi-host index lives on host
    ``b % n_hosts``; probes are scatter/gather RPCs). Correctness does not
    depend on the schedule: shard results are combined into per-document
    candidate *sets* before any Jaccard verification, and the verify loop
    itself stays sequential in document order, so streaming first-wins
    semantics are reproduced exactly.
    """

    # below this many batch rows a pooled probe loses to its own task
    # handoffs (each shard's np.unique group-by is microseconds)
    _POOL_MIN_ROWS = 64

    def __init__(self, n_bands: int, workers: int = 0):
        self.n_bands = n_bands
        self.workers = workers
        # one pool for the index's lifetime, created lazily on the first
        # batched probe — per-probe pool setup/teardown would eat the
        # cross-band parallelism on small batches; close() releases it
        self._pool: Optional[ThreadPoolExecutor] = None
        self.shards: List[Dict[bytes, List[int]]] = [
            {} for _ in range(n_bands)]

    def close(self) -> None:
        """Release the probe thread pool (the index stays usable; a later
        pooled probe recreates it). Idempotent."""
        if self._pool is not None:
            self._pool.shutdown()
            self._pool = None

    # long-running services leak the lazily-created pool if they rely on
    # GC (ThreadPoolExecutor threads keep the interpreter referencing it);
    # `with BandShardedLSHIndex(...)` scopes it deterministically
    def __enter__(self) -> "BandShardedLSHIndex":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def insert(self, doc_id: int, keys: Sequence[bytes]) -> None:
        """Register a kept document under its band keys (one per shard)."""
        for shard_b, kb in zip(self.shards, keys):
            shard_b.setdefault(kb, []).append(doc_id)

    def pack(self) -> Dict[str, Dict[str, np.ndarray]]:
        """All band shards as a checkpointable pytree of arrays."""
        return {f"band_{b:04d}": pack_band(s)
                for b, s in enumerate(self.shards)}

    @classmethod
    def unpack(cls, tree, workers: int = 0) -> "BandShardedLSHIndex":
        """Rebuild an index from :meth:`pack`'s tree. ``workers`` is a
        runtime knob of the *new* process, not part of the state."""
        idx = cls(len(tree), workers=workers)
        idx.shards = [unpack_band(tree[f"band_{b:04d}"])
                      for b in range(len(tree))]
        return idx

    def probe(self, keys: Sequence[bytes]) -> set:
        """Union of the doc ids colliding with ``keys`` in any band."""
        out: set = set()
        for shard_b, kb in zip(self.shards, keys):
            out.update(shard_b.get(kb, ()))
        return out

    def _probe_shard(self, b: int, col: np.ndarray):
        """One band shard's group-by: (D,) void keys -> [(members, hits)].

        ``members`` are batch positions sharing a band key (ascending, so
        earlier-in-batch candidates are recoverable) and ``hits`` the index
        doc ids already stored under that key. Pure function of one shard —
        the unit of cross-band parallelism.
        """
        shard_b = self.shards[b]
        uniq, inv = np.unique(col, return_inverse=True)
        hits = [shard_b.get(u.tobytes()) for u in uniq]
        order = np.argsort(inv, kind="stable")       # groups, ids ascending
        sorted_inv = inv[order]
        starts = np.flatnonzero(
            np.r_[True, sorted_inv[1:] != sorted_inv[:-1]])
        ends = np.r_[starts[1:], len(order)]
        return [(order[s:e], hits[sorted_inv[s]])
                for s, e in zip(starts, ends)]

    def probe_batch(self, kb: np.ndarray) -> Tuple[List[set], List[set]]:
        """(D, n_bands) void band keys -> per-doc candidate sets.

        Returns ``(index_cand, batch_cand)``: doc ids already in the index
        whose band keys collide with doc i, and *earlier batch positions*
        colliding with doc i (their verdicts are not known yet — the verify
        loop resolves them to kept doc ids in order).
        """
        D = kb.shape[0]
        cols = [np.ascontiguousarray(kb[:, b]) for b in range(self.n_bands)]
        # pool fan-out only pays when each shard's group-by is bigger than
        # a task handoff; small probes (streaming check_and_add, smoke
        # batches) run inline even when workers were requested
        if self.workers > 1 and D >= self._POOL_MIN_ROWS:
            if self._pool is None:
                self._pool = ThreadPoolExecutor(self.workers)
            per_band = list(self._pool.map(self._probe_shard,
                                           range(self.n_bands), cols))
        else:
            per_band = [self._probe_shard(b, col)
                        for b, col in enumerate(cols)]
        index_cand: List[set] = [set() for _ in range(D)]
        batch_cand: List[set] = [set() for _ in range(D)]
        for groups in per_band:
            for members, hit in groups:
                for pos, i in enumerate(members):
                    if hit:
                        index_cand[i].update(hit)
                    if pos:                          # earlier batch docs
                        batch_cand[i].update(members[:pos].tolist())
        return index_cand, batch_cand


class MinHashDeduper:
    """Near-dedup with a band-sharded LSH index; batched (optionally
    multi-device) signing, vectorized cross-band probing."""

    def __init__(self, cfg: DedupConfig, mesh=None):
        self.cfg = cfg
        assert cfg.n_signatures % cfg.lsh_bands == 0
        self.rows = cfg.n_signatures // cfg.lsh_bands
        key = jax.random.PRNGKey(cfg.seed)
        k1, k2 = jax.random.split(key)
        self.fam = make_family(cfg.family, n=cfg.ngram_n, L=cfg.L)
        self.fam_params = self.fam.init(k1, cfg.vocab)
        self.mh = MinHash(k=cfg.n_signatures)
        self.mh_params = self.mh.init(k2)
        # the fused hash->sketch plan, built ONCE (it is the jit trace key);
        # None for families the fused engine does not cover
        self.plan = _plan_for_family(self.fam, cfg.n_signatures)
        # signing mesh: an explicit mesh wins; else data_shards devices
        self.mesh = mesh
        self._index = BandShardedLSHIndex(cfg.lsh_bands,
                                          workers=cfg.lsh_workers)
        self._sigs: List[np.ndarray] = []
        self._batches = 0                 # add_batch ordinal, for traces
        self._sig_fn = jax.jit(self._signature_batch_impl)
        self._sig_one_fn = jax.jit(self._signature_unfused_impl)
        # streaming signing: the h1 lookup for one fixed-shape token chunk
        # (one trace; the chunk then flows through stream.update)
        self._lookup_fn = jax.jit(self._lookup_impl)

    @property
    def _bands(self) -> List[Dict[bytes, List[int]]]:
        """Legacy view of the index state (shard list, one dict per band)."""
        return self._index.shards

    def close(self) -> None:
        """Release the index's probe thread pool (long-running services that
        build dedupers per corpus should call this; the deduper stays
        usable). Idempotent."""
        self._index.close()

    def __enter__(self) -> "MinHashDeduper":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- durability ---------------------------------------------------------

    def export_state(self) -> Dict:
        """Everything a restart needs to continue *bit-identically*: the
        sampled hash parameters (h1 table + MinHash remix lanes — the
        paper's pairwise-independence guarantees hold only for THIS draw;
        re-drawing against existing signatures silently voids the Jaccard
        estimator) together with the signature store and the packed band
        index. Host-side pytree of arrays — feed to ``data.durable``.
        """
        sigs = (np.stack([np.asarray(s, np.uint32) for s in self._sigs])
                if self._sigs
                else np.zeros((0, self.cfg.n_signatures), np.uint32))
        return {"params": {
                    "fam": jax.tree_util.tree_map(np.asarray, self.fam_params),
                    "mh": jax.tree_util.tree_map(np.asarray, self.mh_params)},
                "sigs": sigs,
                "index": self._index.pack()}

    def import_params(self, params: Dict) -> None:
        """Re-bind the sampled hash parameters (BEFORE any state import —
        signatures computed after restore must come from the checkpointed
        draw, not this process's seed). The jitted signing closures captured
        the old arrays as constants, so they are re-wrapped here."""
        self.fam_params = jax.tree_util.tree_map(jnp.asarray, params["fam"])
        self.mh_params = jax.tree_util.tree_map(jnp.asarray, params["mh"])
        self._sig_fn = jax.jit(self._signature_batch_impl)
        self._sig_one_fn = jax.jit(self._signature_unfused_impl)
        self._lookup_fn = jax.jit(self._lookup_impl)

    def import_state(self, tree: Dict) -> None:
        """Restore from :meth:`export_state`'s tree: params first, then the
        signature store and band index (insertion order preserved, so the
        restored deduper's future verdicts are bit-identical to one that
        never restarted)."""
        self.import_params(tree["params"])
        sigs = np.asarray(tree["sigs"], np.uint32)
        if sigs.ndim != 2 or sigs.shape[1] != self.cfg.n_signatures:
            raise ValueError(f"sigs shape {sigs.shape} != (D, "
                             f"{self.cfg.n_signatures})")
        self._sigs = [sigs[i] for i in range(sigs.shape[0])]
        if len(tree["index"]) != self.cfg.lsh_bands:
            raise ValueError(f"index has {len(tree['index'])} bands, config "
                             f"expects {self.cfg.lsh_bands}")
        self._index.close()
        self._index = BandShardedLSHIndex.unpack(tree["index"],
                                                 workers=self.cfg.lsh_workers)

    # -- signing ------------------------------------------------------------

    def _lookup_impl(self, tokens: jnp.ndarray) -> jnp.ndarray:
        """h1 of every token of a chunk block (the streaming signer's
        lookup, jitted as ``_lookup_fn``)."""
        with jax.named_scope("dedup.sign.lookup"):
            return self.fam._lookup(self.fam_params, tokens)

    def _signature_batch_impl(self, tokens: jnp.ndarray,
                              n_windows: jnp.ndarray) -> jnp.ndarray:
        """(D, S) bucket-padded batch + (D,) valid-window counts -> (D, k)."""
        if self.plan is not None:
            h1v = self.fam._lookup(self.fam_params, tokens)
            return shard.run_auto(
                self.plan, h1v, n_windows=n_windows,
                operands={"sig": {"a": self.mh_params["a"],
                                  "b": self.mh_params["b"]}},
                impl=self.cfg.impl, mesh=self.mesh,
                data_shards=self.cfg.data_shards)["sig"]
        # generic-family fallback: unfused hash, then the engine's own
        # masked-min epilogue (k-chunked; sentinel applied post-remix)
        h = self.fam.hash_windows_batched(self.fam_params, tokens)
        if hasattr(self.fam, "pairwise_bits"):
            h = self.fam.pairwise_bits(h)
        idx = jnp.arange(h.shape[-1], dtype=jnp.int32)
        valid = idx[None, :] < n_windows.astype(jnp.int32)[:, None]
        return kref.minhash_reduce(h, valid, self.mh_params["a"],
                                   self.mh_params["b"])

    def _signature_unfused_impl(self, tokens: jnp.ndarray,
                                n_windows) -> jnp.ndarray:
        """Seed-architecture per-document path (one jit call per doc) — the
        unfused baseline for the sketch_fusion benchmark."""
        h = self.fam.hash_windows(self.fam_params, tokens)
        if hasattr(self.fam, "pairwise_bits"):
            h = self.fam.pairwise_bits(h)
        idx = jnp.arange(h.shape[-1], dtype=jnp.int32)
        mixed = (self.mh_params["a"][:, None] * h[None, :]
                 + self.mh_params["b"][:, None])
        mixed = jnp.where(idx[None, :] < n_windows, mixed, _SENTINEL)
        return jnp.min(mixed, axis=-1)

    def signature_many(self, docs: Sequence[np.ndarray]) -> np.ndarray:
        """Sign a whole document list: (D, k) uint32 through the on-device
        streaming scan executor — ONE compiled shape for the entire corpus,
        one device dispatch per ``stream_block_chunks`` chunks.

        Documents are grouped ``stream_rows`` at a time *by descending
        length* (signatures are per-row and order-independent, so packing
        similar lengths together just minimizes masked-row waste); each
        group advances through ``(T, stream_rows, stream_chunk_s)`` token
        blocks fed to ``stream.feed`` — full ``stream_block_chunks``-chunk
        blocks plus one pow2-sized tail block, so the executor compiles at
        most ``log2(stream_block_chunks)+1`` block shapes EVER, whatever
        the corpus length mix. Inside a block the chunk loop runs as a
        ``lax.scan`` in the compiled graph with the signature state as the
        (donated) loop carry, and the next block's host->device transfer
        overlaps the in-flight scan. A row that runs out of symbols submits
        0-length chunks, and a document shorter than the n-gram window
        signs to the sentinel signature, exactly as the one-shot path masks
        it. Non-fused families fall back to the bucketed oracle.
        """
        with obs.span("dedup.sign"):
            if self.plan is None:
                return self._signature_many_bucketed(docs)
            return self._signature_many_streamed(docs)

    def _signature_many_streamed(self, docs: Sequence[np.ndarray]
                                 ) -> np.ndarray:
        cfg = self.cfg
        D = len(docs)
        out = np.empty((D, cfg.n_signatures), np.uint32)
        Bt, Cs = cfg.stream_rows, cfg.stream_chunk_s
        # stream_rows is a PER-SHARD tile budget: under a data mesh a group
        # spans up to stream_rows * shards rows (power-of-two, capped by
        # corpus size so a small corpus never pays masked-row waste), so
        # sharding cuts the dispatch count instead of slicing each group
        # into 8-row shards that lose to dispatch overhead. The row-shape
        # set stays finite ({1,2,..,shards} * stream_rows), so the compile
        # bound is still corpus-independent.
        d = (self.mesh.devices.size if self.mesh is not None
             else cfg.data_shards or 1)
        if d > 1 and len(docs) >= 2 * Bt:
            Bt *= 1 << int(np.log2(min(d, len(docs) // Bt)))
        T0 = max(1, cfg.stream_block_chunks)
        operands = {"sig": {"a": self.mh_params["a"],
                            "b": self.mh_params["b"]}}
        order = np.argsort([-len(d) for d in docs], kind="stable")
        for g in range(0, D, Bt):
            sel = order[g : g + Bt]
            group = [np.asarray(docs[i]) for i in sel]
            max_len = max((len(d) for d in group), default=0)
            n_chunks = max(1, -(-max_len // Cs))

            def blocks():
                # full T0-chunk blocks, then one pow2-sized tail block: the
                # executor sees at most log2(T0)+1 distinct block shapes
                # EVER (corpus-independent), and a short group never pays
                # for T0 chunks of masked compute when it only has one
                done = 0
                while done < n_chunks:
                    rem = n_chunks - done
                    T = T0 if rem >= T0 else 1 << int(np.ceil(np.log2(rem)))
                    with obs.span("dedup.sign.pack"):
                        toks = np.zeros((T, Bt, Cs), np.uint32)
                        lengths = np.zeros((T, Bt), np.int32)
                        for t in range(T):
                            lo = (done + t) * Cs
                            for r, d in enumerate(group):
                                v = int(np.clip(len(d) - lo, 0, Cs))
                                if v:
                                    toks[t, r, :v] = d[lo : lo + v]
                                    lengths[t, r] = v
                    done += T
                    # h1 lookup dispatches async; the block rides to the
                    # device already hash-mapped
                    yield self._lookup_fn(jnp.asarray(toks)), lengths

            state = stream.init_state(self.plan, Bt, mesh=self.mesh,
                                      data_shards=cfg.data_shards)
            state = stream.feed(self.plan, blocks(), state,
                                operands=operands, impl=cfg.impl,
                                donate=cfg.stream_donate, mesh=self.mesh,
                                data_shards=cfg.data_shards)
            # the group's one blocking device-to-host fetch
            with obs.span("dedup.sign.fetch"):
                sigs = np.asarray(stream.finalize(self.plan, state,
                                                  batch=Bt)["sig"])
            out[sel] = sigs[: len(group)]
        return out

    def _signature_many_bucketed(self, docs: Sequence[np.ndarray]) -> np.ndarray:
        """The pre-streaming signing path, demoted from production: one
        device call per (length-bucket, row-bucket) shape — O(log) distinct
        jit shapes. Kept ONLY as the parity/test oracle the scan executor
        is validated against and as the fallback for families outside the
        fused engine (THREEWISE, ID37, ...)."""
        D = len(docs)
        out = np.empty((D, self.cfg.n_signatures), np.uint32)
        groups: Dict[int, List[int]] = {}
        for i, d in enumerate(docs):
            groups.setdefault(_bucket(len(d)), []).append(i)
        for bucket, idxs in sorted(groups.items()):
            # the unfused fallback families roll their hash over the padded
            # width directly, so it must admit at least one physical window
            width = max(bucket, self.cfg.ngram_n)
            # cap rows so the CPU path's (rows, bucket, k_chunk) remix tile
            # stays bounded (~64 MB) regardless of bucket size
            max_rows = max(8, (1 << 20) // bucket)
            for s in range(0, len(idxs), max_rows):
                chunk = idxs[s : s + max_rows]
                Dp = max(8, 1 << int(np.ceil(np.log2(len(chunk)))))
                toks = np.zeros((Dp, width), np.uint32)
                nw = np.zeros((Dp,), np.int32)
                for r, i in enumerate(chunk):
                    d = np.asarray(docs[i])
                    toks[r, : len(d)] = d
                    nw[r] = max(0, len(d) - self.cfg.ngram_n + 1)
                sigs = np.asarray(self._sig_fn(jnp.asarray(toks),
                                               jnp.asarray(nw)))
                out[np.asarray(chunk)] = sigs[: len(chunk)]
        return out

    def signature(self, tokens: np.ndarray) -> np.ndarray:
        return self.signature_many([tokens])[0]

    def signature_unfused(self, tokens: np.ndarray) -> np.ndarray:
        """Per-document unfused signature (benchmark baseline; bit-identical
        to :meth:`signature`)."""
        n = len(tokens)
        # the unfused hash needs at least one physical window to roll over
        padded = np.zeros(max(_bucket(n), self.cfg.ngram_n), dtype=np.uint32)
        padded[:n] = tokens
        n_windows = max(0, n - self.cfg.ngram_n + 1)
        return np.asarray(self._sig_one_fn(jnp.asarray(padded), n_windows))

    # -- LSH band index -----------------------------------------------------

    def _band_keys(self, sigs: np.ndarray) -> np.ndarray:
        """(D, k) uint32 -> (D, bands) void scalars; .tobytes() of a key
        equals the legacy per-band row-bytes dict key."""
        D = sigs.shape[0]
        blocks = np.ascontiguousarray(
            sigs.reshape(D, self.cfg.lsh_bands, self.rows))
        return blocks.view(np.dtype((np.void, self.rows * 4)))[..., 0]

    def _insert(self, sig: np.ndarray, keys: Sequence[bytes]) -> int:
        doc_id = len(self._sigs)
        self._sigs.append(sig)
        self._index.insert(doc_id, keys)
        return doc_id

    def _best_match(self, sig: np.ndarray,
                    candidates: Sequence[int]) -> Tuple[float, Optional[int]]:
        if not candidates:
            return 0.0, None
        cand_sigs = np.stack([self._sigs[c] for c in candidates])
        jac = (cand_sigs == sig[None, :]).mean(axis=1)
        best = int(np.argmax(jac))
        return float(jac[best]), candidates[best]

    def add_batch(self, docs: Sequence[np.ndarray]) -> np.ndarray:
        """Dedup a document batch; returns (D,) bool duplicate flags.

        Signing streams fixed-shape chunks through ONE compiled fused
        (optionally shard_map'd) executor, carrying signature state across
        chunks; candidate generation probes every shard of the band-sharded
        LSH index — a vectorized group-by per band, fanned out across bands
        — against both the batch and the existing index. Only candidate
        pairs are Jaccard-verified, sequentially in document order, so the
        kept/duplicate decisions match the streaming per-document path
        exactly (a doc is only compared against *kept* predecessors).
        """
        D = len(docs)
        flags = np.zeros(D, bool)
        if D == 0:
            return flags
        self._batches += 1
        with obs.span("dedup.add_batch", batch=self._batches):
            sigs = self.signature_many(docs)
            kb = self._band_keys(sigs)                   # (D, bands) void
            with obs.span("dedup.probe"):
                index_cand, batch_cand = self._index.probe_batch(kb)
            gid: List[Optional[int]] = [None] * D
            with obs.span("dedup.verify"):
                for i in range(D):
                    cands = set(index_cand[i])
                    cands.update(gid[j] for j in batch_cand[i]
                                 if gid[j] is not None)
                    best_j, best_id = self._best_match(sigs[i], sorted(cands))
                    if best_id is not None and best_j >= self.cfg.threshold:
                        flags[i] = True
                    else:
                        gid[i] = len(self._sigs)
                        self._sigs.append(sigs[i])
            # the kept documents enter the index after the batch is
            # verified: the loop reads candidates from the probe alone
            with obs.span("dedup.insert"):
                for i in np.flatnonzero(~flags):
                    self._index.insert(gid[i], [k.tobytes() for k in kb[i]])
        return flags

    def check_and_add(self, tokens: np.ndarray) -> Tuple[bool, Optional[int], float]:
        """Streaming API: returns (is_duplicate, matched_doc_id,
        best_jaccard); adds the doc to the index if it is not a duplicate."""
        sig = self.signature(tokens)
        keys = [sig[b * self.rows : (b + 1) * self.rows].tobytes()
                for b in range(self.cfg.lsh_bands)]
        candidates = self._index.probe(keys)
        best_j, best_id = self._best_match(sig, sorted(candidates))
        if best_id is not None and best_j >= self.cfg.threshold:
            return True, best_id, best_j
        self._insert(sig, keys)
        return False, None, best_j

    def __len__(self):
        return len(self._sigs)


def signature_batch(fam, fam_params, mh: MinHash, mh_params,
                    tokens: jnp.ndarray) -> jnp.ndarray:
    """Unfused reference: (B, S) -> (B, k) uint32. Materialises the window
    hashes and re-mixes them (the seed data-plane); the fused paths are
    validated bit-identical against this."""
    def one(t):
        h = fam.hash_windows(fam_params, t)
        if hasattr(fam, "pairwise_bits"):
            h = fam.pairwise_bits(h)
        return mh.signature(mh_params, h)
    return jax.vmap(one)(tokens)


def signature_batch_fused(fam, fam_params, mh: MinHash, mh_params,
                          tokens: jnp.ndarray, n_windows=None,
                          impl: str = "auto") -> jnp.ndarray:
    """Fused device-side batched signatures: (B, S) -> (B, k) uint32.

    CYCLIC and GENERAL families route through the plan engine (``api.run``,
    single device pass); other families fall back to the unfused reference.
    Bit-identical to :func:`signature_batch` for unpadded input.
    """
    plan = _plan_for_family(fam, mh.k)
    if plan is not None:
        h1v = fam._lookup(fam_params, tokens)
        return api.run(plan, h1v, n_windows=n_windows,
                       operands={"sig": {"a": mh_params["a"],
                                         "b": mh_params["b"]}},
                       impl=impl)["sig"]
    return signature_batch(fam, fam_params, mh, mh_params, tokens)


# Hoisted constants for exact_duplicate_mask: the k=4 sketch and its fixed-
# key params are identical on every call, so build them once (lazily — no
# device work at import time).
_EXACT_MH = MinHash(k=4)
_EXACT_MH_PARAMS: Optional[Dict[str, jnp.ndarray]] = None


def _exact_mh_params() -> Dict[str, jnp.ndarray]:
    global _EXACT_MH_PARAMS
    if _EXACT_MH_PARAMS is None:
        _EXACT_MH_PARAMS = _EXACT_MH.init(jax.random.PRNGKey(0))
    return _EXACT_MH_PARAMS


def exact_duplicate_mask(fam, fam_params, tokens: jnp.ndarray) -> jnp.ndarray:
    """(B, S) batch -> (B,) bool; True where a sequence's full-content hash
    collides with an earlier sequence in the batch (exact-dedup pass)."""
    sigs = signature_batch_fused(fam, fam_params, _EXACT_MH,
                                 _exact_mh_params(), tokens)
    # two sequences identical => identical signatures; compare lexicographically
    B = sigs.shape[0]
    eq = jnp.all(sigs[:, None, :] == sigs[None, :, :], axis=-1)  # (B, B)
    earlier = jnp.tril(jnp.ones((B, B), bool), k=-1)
    return jnp.any(eq & earlier, axis=1)
