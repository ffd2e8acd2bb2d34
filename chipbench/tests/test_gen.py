"""The seeded generators at small sizes on the CPU."""
import numpy as np
import pytest

from chipbench import gen

BIG_SEED = 2**33 + 17           # seeds may need more than 32 bits


def test_key_keeps_all_64_bits():
    a, b = gen.key_from_seed(5), gen.key_from_seed(5 + (1 << 32))
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError):
        gen.key_from_seed(-1)


def _unordered(X, seed):
    """The dataset's rows in its own order, undoing the run's order."""
    return X[np.argsort(np.asarray(gen.feature_order(seed, X.shape[0])))]


def test_dense_unit_columns_labels_and_seed():
    X, y = gen.dense_glm(1, BIG_SEED, 40, 700, 0.8, block=300)
    assert X.shape == (40, 700) and X.dtype == np.float32
    np.testing.assert_allclose(np.linalg.norm(X, axis=0), 1.0, rtol=1e-5)
    assert set(np.unique(y)) == {-1.0, 1.0}
    # logistic labels carry signal: neither class is rare
    assert 0.2 < np.mean(y > 0) < 0.8
    X2, y2 = gen.dense_glm(1, BIG_SEED, 40, 700, 0.8, block=300)
    assert np.array_equal(X, X2) and np.array_equal(y, y2)
    # another seed: the same dataset, its features in another order
    X3, y3 = gen.dense_glm(1, BIG_SEED + 1, 40, 700, 0.8, block=300)
    assert not np.array_equal(X, X3) and np.array_equal(y, y3)
    assert np.array_equal(_unordered(X, BIG_SEED),
                          _unordered(X3, BIG_SEED + 1))
    # another dataset
    X4, _ = gen.dense_glm(2, BIG_SEED, 40, 700, 0.8, block=300)
    assert not np.array_equal(np.sort(X, axis=0), np.sort(X4, axis=0))


def test_dense_spectrum_decays():
    X, _ = gen.dense_glm(3, 0, 32, 4000, 0.8)
    s = np.linalg.svd(X.astype(np.float64), compute_uv=False) ** 2
    # covariance eigenvalues fall like k^-0.8: the 16th is ~16^-0.8 of the 1st
    assert 0.05 < s[15] / s[0] < 0.25


def test_doc_lengths_mean_and_bounds():
    k = gen.doc_lengths(72_309, 51.3, 0.8, 8, 256)
    assert k.min() >= 8 and k.max() == 256
    assert abs(k.mean() - 51.3) < 0.01
    assert np.all(np.diff(k) <= 0)                 # longest first
    assert np.array_equal(k, gen.doc_lengths(72_309, 51.3, 0.8, 8, 256))


def _sparse(seed, d=3000, n=2000, data_seed=1):
    return gen.sparse_glm(data_seed, seed, d, n, 20.0, 1.2, 0.8, 4, 64)


def test_sparse_nonzeros_per_sample_unit_columns_labels():
    d, n = 3000, 2000
    feat, samp, vals, y = _sparse(BIG_SEED, d, n)
    per = np.bincount(samp, minlength=n)
    # the seed decides which sample gets which length, never the lengths
    assert np.array_equal(np.sort(per)[::-1],
                          gen.doc_lengths(n, 20.0, 0.8, 4, 64))
    # ids are distinct within each sample
    assert len(np.unique(samp.astype(np.int64) * d + feat)) == len(feat)
    assert feat.min() >= 0 and feat.max() < d
    sq = np.bincount(samp, vals.astype(np.float64) ** 2, minlength=n)
    np.testing.assert_allclose(sq, 1.0, rtol=1e-5)
    assert set(np.unique(y)) == {-1.0, 1.0}


def test_sparse_zipf_head():
    d = 3000
    feat, *_ = _sparse(11, d, data_seed=11)
    freq = np.sort(np.bincount(feat, minlength=d))[::-1].astype(float)
    # the head is steep: the top id is in most samples; below the head,
    # where distinct draws no longer saturate, rank 1000 is seen about
    # 10^1.2 ~ 16x less often than rank 100
    assert freq[0] / 2000 > 0.5
    assert 10 < freq[99] / freq[999] < 25
    # the tail is long: most ids are seen
    assert np.mean(freq > 0) > 0.5


def test_sparse_same_seed_same_output():
    a, b, c = _sparse(7), _sparse(7), _sparse(8)
    for u, v in zip(a, b):
        assert np.array_equal(u, v)
    # another seed: the same samples, their features in another order
    assert not np.array_equal(a[0], c[0])
    for u, v in zip(a[1:], c[1:]):
        assert np.array_equal(u, v)
    back = lambda feat, seed: np.argsort(
        np.asarray(gen.feature_order(seed, 3000)))[feat]
    assert np.array_equal(back(a[0], 7), back(c[0], 8))
    assert not np.array_equal(_sparse(7, data_seed=2)[2], a[2])


def test_requests_distinct_ids_unit_norm_and_seed():
    w, reqs = gen.scoring_data(BIG_SEED, 50_000, 200, 39, 1.1)
    assert w.shape == (50_000,) and w.dtype == np.float32
    assert len(reqs) == 200
    for ids, vals in reqs:
        assert len(ids) == 39 == len(np.unique(ids))
        assert ids.min() >= 0 and ids.max() < 50_000
        assert np.sum(vals.astype(np.float64) ** 2) == pytest.approx(1.0)
    w2, reqs2 = gen.scoring_data(BIG_SEED, 50_000, 200, 39, 1.1)
    assert np.array_equal(w, w2)
    assert all(np.array_equal(a[0], b[0]) for a, b in zip(reqs, reqs2))
    ids = np.concatenate([i for i, _ in reqs])
    top = np.bincount(ids).max()
    assert top > 200 * 0.1                 # a hashed head id is frequent
