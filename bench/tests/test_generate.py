"""The traffic generator: its Zipf sampler, its planted duplicates, and the
relabelling pass that keeps a cycled pool from duplicating itself."""
import numpy as np
import pytest

from bench import generate

VOCAB = 1 << 12


def _traffic(**kw):
    t = {"kind": "documents", "batch_docs": 48, "pool_batches": 2,
         "length": {"dist": "lognormal", "median": 96, "sigma": 0.5,
                    "min": 32, "max": 400},
         "zipf_alpha": 1.1, "dup_rate": 0.25, "mutate_frac": 0.0,
         "loop": "closed"}
    t.update(kw)
    return t


def test_zipf_sampler_matches_folded_zipf():
    rng = np.random.default_rng(0)
    got = np.bincount(generate.ZipfTokens(VOCAB, 1.1)(rng, 400_000),
                      minlength=VOCAB) / 400_000
    want = np.bincount((rng.zipf(1.1, 400_000) - 1) % VOCAB,
                       minlength=VOCAB) / 400_000
    # the head carries most of the mass; 400k draws pin it to ~1e-3
    np.testing.assert_allclose(got[:8], want[:8], atol=3e-3)
    assert abs(got[:64].sum() - want[:64].sum()) < 5e-3


def test_pool_is_a_function_of_the_seed():
    a = generate.DocumentPool(_traffic(), VOCAB, 2 ** 31 + 5)
    b = generate.DocumentPool(_traffic(), VOCAB, 2 ** 31 + 5)
    c = generate.DocumentPool(_traffic(), VOCAB, 6)
    assert np.array_equal(a.tokens, b.tokens)
    assert not np.array_equal(a.tokens[:50], c.tokens[:50])
    for x, y in zip(a.batch(3), b.batch(3)):
        assert np.array_equal(x, y)


def test_planted_duplicates_copy_their_source():
    pool = generate.DocumentPool(_traffic(), VOCAB, 3)
    docs = pool.batch(0) + pool.batch(1)
    dups = np.flatnonzero(pool.dup_of >= 0)
    assert len(dups) > 5
    for i in dups:
        assert np.array_equal(docs[i], docs[pool.dup_of[i]])
    # lengths lie in the mix's clip range
    assert all(32 <= len(d) <= 400 for d in docs)


def test_relabelled_pass_is_a_bijection_of_the_first():
    pool = generate.DocumentPool(_traffic(), VOCAB, 4)
    first, again = pool.batch(0), pool.batch(2)
    assert [len(d) for d in first] == [len(d) for d in again]
    a = np.concatenate(first)
    b = np.concatenate(again)
    assert not np.array_equal(a, b)
    # one token maps to one token, and never two tokens to one
    pairs = np.unique(np.stack([a, b], 1), axis=0)
    assert len(np.unique(pairs[:, 0])) == len(pairs)
    assert len(np.unique(pairs[:, 1])) == len(pairs)


@pytest.mark.parametrize("mutate_frac", [0.0, 0.02])
def test_second_pass_flags_only_its_own_duplicates(mutate_frac):
    from repro.data.dedup import DedupConfig
    from repro.data.service import DedupService, ServiceConfig
    pool = generate.DocumentPool(_traffic(mutate_frac=mutate_frac), VOCAB, 9)
    cfg = DedupConfig(ngram_n=5, n_signatures=112, lsh_bands=14,
                      threshold=0.0, vocab=VOCAB, impl="ref")
    with DedupService(cfg, ServiceConfig(n_workers=4, replication=2)) as svc:
        flags = [svc.add_batch(pool.batch(i)) for i in range(4)]
    first = np.concatenate(flags[:2])
    second = np.concatenate(flags[2:])
    planted = pool.dup_of >= 0
    assert not (second & ~planted).any()
    if mutate_frac == 0.0:
        # exact copies are always found: each pass flags exactly its own
        np.testing.assert_array_equal(first, planted)
        np.testing.assert_array_equal(second, planted)
