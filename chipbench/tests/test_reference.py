"""The plain references against the program, at small sizes on the CPU."""
import numpy as np
import pytest

from chipbench import gen, reference

LAM = 1e-3


@pytest.fixture(scope="module")
def dense():
    return gen.dense_glm(21, 5, 48, 1500, 0.8)


@pytest.fixture(scope="module")
def sparse():
    d, n = 400, 1200
    feat, samp, vals, y = gen.sparse_glm(22, 5, d, n, 12.0, 1.2, 0.8, 4, 32)
    return d, n, feat, samp, vals, y


def test_ops_match_dense_products(dense, sparse):
    X, _ = dense
    ops = reference.DenseOps(X, block=400)
    rng = np.random.default_rng(0)
    w, v = rng.standard_normal(X.shape[0]), rng.standard_normal(X.shape[1])
    X64 = X.astype(np.float64)
    np.testing.assert_allclose(ops.xt(w), X64.T @ w, rtol=1e-12)
    np.testing.assert_allclose(ops.x(v), X64 @ v, rtol=1e-12)
    d, n, feat, samp, vals, _ = sparse
    Xs = np.zeros((d, n))
    Xs[feat, samp] = vals
    cops = reference.CooOps(feat, samp, vals, d, n)
    w, v = rng.standard_normal(d), rng.standard_normal(n)
    np.testing.assert_allclose(cops.xt(w), Xs.T @ w, rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(cops.x(v), Xs @ v, rtol=1e-12, atol=1e-12)


def test_gradient_matches_finite_differences(dense):
    X, y = dense
    ops = reference.DenseOps(X)
    w = np.random.default_rng(1).standard_normal(X.shape[0]) * 0.1

    def f(w):
        a = ops.xt(w)
        return np.mean(np.logaddexp(0, -y * a)) + 0.5 * LAM * w @ w

    g = reference.logistic_grad(ops, y, w, LAM)
    e = np.zeros_like(w)
    e[3] = 1e-6
    assert g[3] == pytest.approx((f(w + e) - f(w - e)) / 2e-6, rel=1e-5)


def _newton64(ops, y, n):
    g0 = np.linalg.norm(reference.logistic_grad(ops, y, np.zeros(ops.d),
                                                LAM))
    return reference.newton(ops.xt, ops.x, np.asarray(y, np.float64), n,
                            LAM, 1e-10 * g0, 30, np, cg_rel=1e-8)


def test_newton_reference_agrees_with_disco_dense(dense):
    from repro.core import DiscoConfig, DiscoSolver
    X, y = dense
    ops = reference.DenseOps(X)
    w_ref, norms = _newton64(ops, y, X.shape[1])
    assert norms[-1] <= 1e-10 * norms[0]
    res = DiscoSolver(X, y, DiscoConfig(lam=LAM, partition="samples",
                                        grad_tol=1e-7, max_outer=30)).fit()
    assert res.converged
    rel = np.linalg.norm(res.w - w_ref) / np.linalg.norm(w_ref)
    assert rel < 1e-4
    assert reference.grad_rel(ops, y, [res.w], LAM)[0] < 1e-5


def test_newton_reference_agrees_with_disco_sparse(sparse):
    from repro.core import DiscoConfig, DiscoSolver
    from repro.data.sparse import CSRMatrix
    d, n, feat, samp, vals, y = sparse
    ops = reference.CooOps(feat, samp, vals, d, n)
    w_ref, _ = _newton64(ops, y, n)
    X = CSRMatrix.from_coo(feat, samp, vals, (d, n))
    res = DiscoSolver(X, y, DiscoConfig(lam=LAM, partition="features",
                                        grad_tol=1e-7, max_outer=30,
                                        ell_block_d=16,
                                        ell_block_n=16)).fit()
    rel = np.linalg.norm(res.w - w_ref) / np.linalg.norm(w_ref)
    assert rel < 1e-4
    # the reference reads the zero iterate and the solution apart
    assert reference.grad_rel(ops, y, [np.zeros(d)], LAM) == [1.0]
    assert reference.grad_rel(ops, y, [res.w], LAM)[0] < 1e-5


def test_margins_agree_with_scoring_engine():
    from repro.glm_serve import ScoreRequest, ScoringEngine
    w, reqs = gen.scoring_data(23, 3000, 70, 39, 1.1)
    eng = ScoringEngine(w, loss="logistic", batch=16, block_d=128)
    got = eng.score([ScoreRequest(indices=i, values=v) for i, v in reqs])
    want, scale = reference.margins(reqs, w)
    assert np.max(np.abs(got - want) / scale) < 1e-6
    assert np.all(scale > 0)
