"""Two-word lanes: CYCLIC past L = 32 through the plan engine, the stream
executor and ``NgramStats``.

Every hash is checked against a NumPy uint64 XOR of rotations written
here from the definition; the plan's HLL (both epilogue modes) and
CountMin (both modes) against ``kernels/ref.py`` and against registers and
tables computed here in NumPy; streams chunked and ragged against one
shot; the sharded combine at 1 and 4 devices against one device.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CountMinSketch, HyperLogLog, make_family
from repro.data.stats import NgramStats, StatsConfig
from repro.kernels import api, ops, plan as plan_mod, ref, shard, stream
from repro.kernels.plan import (BloomSpec, CountMinSpec, HashSpec, HLLSpec,
                                MinHashSpec, SketchPlan)

N_DEV = len(jax.devices())
SEED = 2 ** 31 + 17
CASES = [(n, L) for n in (2, 13, 33) for L in (33, 52, 64) if L >= n]


def _h1(L, vocab, seed=SEED):
    """(vocab,) uint64 L-bit symbol hashes and their (2, vocab) words."""
    rng = np.random.default_rng([seed, L])
    h = rng.integers(0, 1 << 63, vocab, dtype=np.uint64) << np.uint64(1)
    h |= rng.integers(0, 2, vocab, dtype=np.uint64)
    h &= np.uint64((1 << L) - 1) if L < 64 else np.uint64(2 ** 64 - 1)
    return h, np.stack([(h & np.uint64(0xFFFFFFFF)).astype(np.uint32),
                        (h >> np.uint64(32)).astype(np.uint32)])


def _np_hashes(h1, toks, n, L):
    """uint64 XOR of rotations: window j = XOR_t rotl(h1[x_{j+t}], n-1-t)."""
    m = np.uint64((1 << L) - 1) if L < 64 else np.uint64(2 ** 64 - 1)
    v = h1[toks]
    W = toks.shape[-1] - n + 1
    acc = np.zeros(toks.shape[:-1] + (W,), np.uint64)
    for t in range(n):
        x, r = v[..., t : t + W], (n - 1 - t) % L
        if r:
            x = ((x << np.uint64(r)) | (x >> np.uint64(L - r))) & m
        acc ^= x
    return acc


def _as64(words):
    w = np.asarray(words)
    return w[0].astype(np.uint64) | (w[1].astype(np.uint64) << np.uint64(32))


def _np_hll(keys, b, rank_bits):
    regs = np.zeros(1 << b, np.int64)
    rest = keys >> np.uint64(b)
    tz = np.bitwise_count((rest & (~rest + np.uint64(1))) - np.uint64(1))
    np.maximum.at(regs, (keys & np.uint64((1 << b) - 1)).astype(np.int64),
                  np.minimum(tz, rank_bits).astype(np.int64) + 1)
    return regs


def _np_cms(keys, a, b, log2_width):
    w = 33 - log2_width
    table = np.zeros((len(b), 1 << log2_width), np.int64)
    for d in range(len(b)):
        acc = np.full(keys.shape, b[d], np.uint32)
        for i in range(a.shape[1]):
            part = (keys >> np.uint64(i * w)) & np.uint64((1 << w) - 1)
            acc += a[d, i] * part.astype(np.uint32)
        col = (acc >> np.uint32(32 - log2_width)).astype(np.int64)
        table[d] += np.bincount(col, minlength=1 << log2_width)
    return table


@pytest.mark.parametrize("n, L", CASES)
def test_window_hashes_match_numpy(n, L):
    h1, words = _h1(L, 256)
    toks = np.random.default_rng(n).integers(0, 256, (3, 80))
    want = _np_hashes(h1, toks, n, L)
    got = ops.cyclic(jnp.asarray(words[:, toks]), n=n, L=L, impl="ref")
    assert (_as64(got) == want).all()
    fam = make_family("cyclic", n=n, L=L)
    p = {"h1": jnp.asarray(words)}
    for form in (fam.hash_windows_direct, fam.hash_stream, fam.hash_windows):
        assert (_as64(form(p, toks[0])) == want[0]).all(), form.__name__


@pytest.mark.parametrize("n, L", [(2, 33), (13, 64), (33, 64)])
def test_rolling_kernel_matches_numpy(n, L):
    h1, words = _h1(L, 256)
    toks = np.random.default_rng(n).integers(0, 256, (5, 300))
    got = ops.cyclic(jnp.asarray(words[:, toks]), n=n, L=L, impl="pallas")
    assert (_as64(got) == _np_hashes(h1, toks, n, L)).all()


@pytest.fixture
def hll_split(monkeypatch):
    """Sets the HLL epilogue's split for one test. Plans are jit trace
    keys, so traces made under another split are dropped each time."""
    def set_split(cells):
        monkeypatch.setattr(plan_mod, "HLL_IN_KERNEL_MAX_CELLS", cells)
        jax.clear_caches()
    yield set_split
    jax.clear_caches()


PLAN_CASES = [(13, 64, "in_kernel"), (33, 64, "in_kernel"),
              (2, 52, "scatter"), (13, 64, "scatter"), (13, 33, "scatter")]


@pytest.mark.parametrize("n, L, mode", PLAN_CASES)
def test_plan_sketches_match_reference_and_numpy(n, L, mode, hll_split):
    """HLL and CountMin at two-word lanes: the kernel (interpret) in the
    given epilogue mode, the jnp oracle and NumPy agree register for
    register and cell for cell, padded rows and ragged lengths included."""
    h1, words = _h1(L, 512)
    rng = np.random.default_rng([n, L])
    toks = rng.integers(0, 512, (3, 300))
    nw = np.array([300 - n + 1, 0, 150])
    hs = HashSpec(family="cyclic", n=n, L=L)
    b = min(6, hs.out_bits - 1)
    in_k = mode == "in_kernel"
    cms = CountMinSpec(depth=3, log2_width=10,
                       in_kernel_max_log2_width=12 if in_k else 0)
    hll_split((1 << 30) if in_k else 0)
    plan = SketchPlan(hs, (("hll", HLLSpec(b=b)), ("cms", cms)))
    assert plan.sketches[0][1].use_in_kernel(hs) == in_k
    a = rng.integers(0, 1 << 32, (3, cms.key_pieces(hs)), dtype=np.uint32)
    cb = rng.integers(0, 1 << 32, 3, dtype=np.uint32)
    operands = {"cms": {"a": a, "b": cb}}
    x = jnp.asarray(words[:, toks])
    got = api.run(plan, x, n_windows=nw, operands=operands, impl="pallas")
    want = api.run(plan, x, n_windows=nw, operands=operands, impl="ref")
    keys = _np_hashes(h1, toks, n, L) & np.uint64(hs.hash_mask)
    keys = np.concatenate([keys[r, : nw[r]] for r in range(3)])
    assert (np.asarray(got["hll"]) == np.asarray(want["hll"])).all()
    assert (np.asarray(got["cms"]) == np.asarray(want["cms"])).all()
    assert (np.asarray(want["hll"]) ==
            _np_hll(keys, b, hs.out_bits - b)).all()
    assert (np.asarray(want["cms"]) == _np_cms(keys, a, cb, 10)).all()


@pytest.mark.parametrize("impl", ["ref", "pallas"])
@pytest.mark.parametrize("executor", ["scan", "host"])
def test_stream_chunks_and_ragged_rows_equal_one_shot(impl, executor):
    """Chunk boundaries every 48 symbols (the 12-symbol tail carried in two
    words) and ragged rows give the one-shot registers and table."""
    hs = HashSpec(family="cyclic", n=13, L=64)
    cms = CountMinSpec(depth=2, log2_width=14)
    plan = SketchPlan(hs, (("hll", HLLSpec(b=8)), ("cms", cms)))
    _, words = _h1(64, 1024)
    rng = np.random.default_rng(5)
    x = jnp.asarray(words[:, rng.integers(0, 1024, (4, 200))])
    nw = np.array([188, 0, 40, 100])
    operands = {"cms": {"a": rng.integers(0, 1 << 32, (2, cms.key_pieces(hs)),
                                          dtype=np.uint32),
                        "b": rng.integers(0, 1 << 32, 2, dtype=np.uint32)}}
    one = api.run(plan, x, n_windows=nw, operands=operands, impl="ref")
    got = stream.run_stream(plan, x, chunk_s=48, n_windows=nw,
                            operands=operands, impl=impl, executor=executor)
    for name in ("hll", "cms"):
        assert (np.asarray(got[name]) == np.asarray(one[name])).all(), name


def _shards(*counts):
    return [pytest.param(d, marks=pytest.mark.skipif(
        d > N_DEV, reason=f"needs {d} devices")) for d in counts]


@pytest.mark.parametrize("d", _shards(1, 4))
def test_sharded_pmax_psum_equal_one_device(d):
    """HLL registers merge by one pmax and CountMin by one psum at 1 and 4
    devices, in one call and in a sharded stream, as on one device."""
    hs = HashSpec(family="cyclic", n=13, L=64)
    cms = CountMinSpec(depth=2, log2_width=16)
    plan = SketchPlan(hs, (("hll", HLLSpec(b=10)), ("cms", cms)))
    _, words = _h1(64, 2048)
    rng = np.random.default_rng(d)
    x = jnp.asarray(words[:, rng.integers(0, 2048, (6, 128))])
    operands = {"cms": {"a": rng.integers(0, 1 << 32, (2, cms.key_pieces(hs)),
                                          dtype=np.uint32),
                        "b": rng.integers(0, 1 << 32, 2, dtype=np.uint32)}}
    one = api.run(plan, x, operands=operands, impl="ref")
    got = shard.run_sharded(plan, x, operands=operands, impl="ref",
                            data_shards=d)
    st = stream.run_stream(plan, x, chunk_s=32, operands=operands,
                           impl="ref", data_shards=d)
    for name in ("hll", "cms"):
        assert (np.asarray(got[name]) == np.asarray(one[name])).all(), name
        assert (np.asarray(st[name]) == np.asarray(one[name])).all(), name


@pytest.mark.parametrize("family, L, sketch", [
    ("general", 33, None), ("general", 64, None),
    ("cyclic", 64, MinHashSpec(k=4)), ("cyclic", 40, BloomSpec(k=2)),
])
def test_two_word_lanes_are_refused_where_unsupported(family, L, sketch):
    """GENERAL needs a degree-L carry-less multiply: L > 32 is refused with
    a clear error; MinHash and Bloom take one-word hashes."""
    with pytest.raises(ValueError, match="L <= 32|one-word"):
        hs = HashSpec(family=family, n=8, L=L)
        SketchPlan(hs, (("s", sketch),))


def test_core_sketches_match_the_plan_oracle():
    """``core``'s two-word HyperLogLog and CountMinSketch (the query side)
    agree with the plan's epilogues and with NumPy over the same keys."""
    _, words = _h1(64, 4096)
    keys = jnp.asarray(words[:, :4000]) & jnp.asarray(
        np.array([[0xFFFFFFFF], [0xFFFFF]], np.uint32))
    keys64 = _as64(keys)
    hll = HyperLogLog(b=12, hash_bits=52, words=2)
    valid = jnp.ones((4000,), bool)
    regs = np.asarray(hll.update(hll.init(), keys))
    assert (regs == np.asarray(ref._hll_reduce(keys, valid, 12, 40))).all()
    assert (regs == _np_hll(keys64, 12, 40)).all()
    cms = CountMinSketch(depth=4, log2_width=20, key_bits=52, words=2)
    p = cms.init(jax.random.PRNGKey(3))
    assert p["a"].shape == (4, 4)
    table = np.asarray(cms.add(p, keys)["table"])
    assert (table == np.asarray(
        ref.cms_reduce(keys, valid, p["a"], p["b"], 20))).all()
    assert (table == _np_cms(keys64, np.asarray(p["a"]), np.asarray(p["b"]),
                             20)).all()


def test_stats_query_hashes_match_update_at_64_bits():
    """The query side's hashes are the update's: every window of a fresh
    stream is counted in every CountMin row at its queried column."""
    st = NgramStats(StatsConfig(ngram_n=13, L=64, hll_b=8, cms_depth=3,
                                cms_log2_width=16, vocab=1 << 12))
    toks = np.random.default_rng(9).integers(0, 1 << 12, (2, 200))
    state = st.update(st.init_state(), toks)
    h = st.query_hashes(jnp.asarray(toks, jnp.uint32))
    assert h.shape == (2, 2, 188)
    assert (_as64(h) < (1 << 52)).all()
    counts = st.heavy_hitter_count(state, toks[:, :13])
    assert (counts >= 1).all()
    cols = st.cms._cols(st._cms_params, h)
    table = np.asarray(state["cms"])
    for d in range(3):
        assert (table[d][np.asarray(cols[d])] >= 1).all()
    assert table.sum() == 3 * 2 * 188


@pytest.mark.parametrize("L, within", [(64, True), (32, False)])
def test_distinct_count_saturates_only_at_32_bits(L, within):
    """About 4.2 M distinct 13-grams: estimated within 3% on 52-bit keys,
    off by more than half on 32-bit lanes' 20 usable bits."""
    st = NgramStats(StatsConfig(ngram_n=13, L=L, hll_b=14, cms_depth=1,
                                cms_log2_width=8, vocab=1 << 16,
                                impl="ref"))
    toks = np.random.default_rng(11).integers(0, 1 << 16, (64, 65536))
    state = st.update(st.init_state(), toks)
    true = 64 * (65536 - 12)
    err = abs(st.distinct_ngrams(state) - true) / true
    assert (err < 0.03) if within else (err > 0.5), err
