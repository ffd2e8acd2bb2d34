"""Differential conformance suite for the HvpOperator registry.

The headline lockdown of the dispatch unification: every registered
(family, layout, partition, fusion, dtype) cell of
:func:`repro.core.hvp.operator_cells` is enumerated and either

* **supported** — the operator is built and checked against the f64
  NumPy oracle AND bit-compared (``np.array_equal``) to the frozen
  pre-refactor closures (``tests/oracles.py::legacy_local_hvp``), or
* **unsupported** — resolving it must raise
  :class:`UnsupportedHvpError` naming the cell (the latent-bug class
  where a flag used to be silently ignored).

A supported cell whose (family, layout) has no registered checker FAILS
the suite — coverage cannot silently rot as cells are added.

Also here: the satellite suites — hypothesis property tests (softmax
PSD / row-stochastic probabilities, Poisson & Huber finite-difference
consistency, random ELL geometry), the softmax-vs-NumPy-Newton
conformance (<= 1e-6 rel), λ-path warm == cold endpoints + X-pass
ledger, and the 4-device subprocess equivalence runs for multinomial
and λ-path solves.
"""
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from oracles import (ell_pair_case, fd_derivative, legacy_local_hvp,
                     local_hvp_multi_oracle, local_hvp_oracle,
                     slot_csr_case, slot_pair_case, softmax_newton_fit,
                     softmax_probs_oracle)
from repro.core.hvp import (SoftmaxHvpOperator, UnsupportedHvpError,
                            cell_id, make_local_operator, operator_cells,
                            render_support_matrix, resolve_cell,
                            validate_solver_cell)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

CELLS = operator_cells()
_TOL = {"float32": 1e-5, "bfloat16": 5e-2}
_JDT = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def _dense_case(rng, dtype, d=24, n=40):
    """Dense (d, n) problem in the cell's tile dtype + its f32 rounding
    for the oracle."""
    X = jnp.asarray(rng.standard_normal((d, n)), _JDT[dtype])
    Xf = np.asarray(X.astype(jnp.float32))
    c = jnp.asarray(rng.random(n), jnp.float32)
    u = jnp.asarray(rng.standard_normal(d), jnp.float32)
    U = jnp.asarray(rng.standard_normal((d, 3)), jnp.float32)
    return X, Xf, c, u, U


def _check_against_oracle(op, Xf, c, u, U, dtype):
    tol = _TOL[dtype]
    want = local_hvp_oracle(Xf, c, u)
    scale = max(np.abs(want).max(), 1.0)
    np.testing.assert_allclose(np.asarray(op.apply(u)), want,
                               atol=tol * scale, rtol=tol)
    want_m = local_hvp_multi_oracle(Xf, c, U)
    np.testing.assert_allclose(np.asarray(op.apply_multi(U)), want_m,
                               atol=tol * scale, rtol=tol)
    # split passes compose to the same product (the multi-shard DiSCO-F
    # contract: a psum goes between them)
    two = op.pass_b(op.pass_a(u))
    np.testing.assert_allclose(np.asarray(two), want, atol=tol * scale,
                               rtol=tol)
    two_m = op.pass_b_multi(op.pass_a_multi(U))
    np.testing.assert_allclose(np.asarray(two_m), want_m,
                               atol=tol * scale, rtol=tol)


def _check_binary_inmem(cell, rng, stream_env):
    use_kernel = cell.layout == "dense_kernel"
    if cell.layout == "ell":
        pair, Xp = ell_pair_case(rng, 24, 40, 0.3, 8, 8, width_pad=1,
                                 dtype=_JDT[cell.dtype])
        Xf = np.asarray(jnp.asarray(Xp, _JDT[cell.dtype])
                        .astype(jnp.float32))
        c = jnp.asarray(rng.random(Xp.shape[1]), jnp.float32)
        u = jnp.asarray(rng.standard_normal(Xp.shape[0]), jnp.float32)
        U = jnp.asarray(rng.standard_normal((Xp.shape[0], 3)), jnp.float32)
        X_loc = pair
    else:
        X_loc, Xf, c, u, U = _dense_case(rng, cell.dtype)
    op = make_local_operator(X_loc, c, use_kernel=use_kernel,
                             fused=cell.fused, partition=cell.partition)
    assert op.fused == cell.fused
    _check_against_oracle(op, Xf, c, u, U, cell.dtype)
    # bit-identity vs the frozen pre-refactor dispatch closures: same
    # kernels, same argument order => np.array_equal, not allclose
    leg, leg_m = legacy_local_hvp(X_loc, c, use_kernel=use_kernel,
                                  fused=cell.fused)
    assert np.array_equal(np.asarray(op.apply(u)), np.asarray(leg(u)))
    assert np.array_equal(np.asarray(op.apply_multi(U)),
                          np.asarray(leg_m(U)))


def _slot_case(rng, dtype):
    """A slot pair with a head slab and multi-chunk slots (24 features,
    2,000 samples, padded to 27 x 2,005) and its f32 dense twin."""
    X = slot_csr_case(rng, d=24, n=2000)
    return slot_pair_case(X, (27, 2005), _JDT[dtype]) + (X,)


def _check_binary_slots(cell, rng, stream_env):
    """Slots against the f64 oracle, and against the tile two-pass
    product of the same matrix (slots share no logic with the tiles, so
    there is no bit-identity target: f32 tolerance)."""
    from repro.data.sparse import EllPair, ell_pair_from_csr

    pair, Xf, X = _slot_case(rng, cell.dtype)
    c = jnp.asarray(rng.random(Xf.shape[1]), jnp.float32)
    u = jnp.asarray(rng.standard_normal(Xf.shape[0]), jnp.float32)
    U = jnp.asarray(rng.standard_normal((Xf.shape[0], 3)), jnp.float32)
    op = make_local_operator(pair, c, fused=cell.fused,
                             partition=cell.partition)
    assert op.fused == cell.fused and op.layout == "slots"
    _check_against_oracle(op, Xf, c, u, U, cell.dtype)
    fwd, tr = ell_pair_from_csr(X, 8, 8)
    rp, cp = fwd.n_row_blocks * 8, tr.n_row_blocks * 8
    ell = EllPair(jnp.asarray(fwd.data, _JDT[cell.dtype]),
                  jnp.asarray(fwd.cols),
                  jnp.asarray(tr.data, _JDT[cell.dtype]),
                  jnp.asarray(tr.cols))
    tiles = make_local_operator(ell, c[:cp],
                                partition=cell.partition)
    want = np.asarray(tiles.apply(jnp.pad(u[:24], (0, rp - 24))))[:24]
    tol = _TOL[cell.dtype]
    np.testing.assert_allclose(np.asarray(op.apply(u))[:24], want,
                               rtol=tol, atol=tol * np.abs(want).max())


def _softmax_local_oracle(Xf, P, wts, U):
    """f64 local softmax product X (w .* (P.*V - P.*rowsum(P.*V)))."""
    Xd = np.asarray(Xf, np.float64)
    V = Xd.T @ np.asarray(U, np.float64)
    PV = P * V
    S = PV - P * PV.sum(axis=1, keepdims=True)
    if wts is not None:
        S = wts[:, None] * S
    return Xd @ S


def _check_softmax_inmem(cell, rng, stream_env):
    use_kernel = cell.layout == "dense_kernel"
    K = 4
    W = rng.standard_normal((24, K)).astype(np.float32) * 0.3
    if cell.layout == "slots":
        pair, Xf, _ = _slot_case(rng, cell.dtype)
        wts = np.zeros(Xf.shape[1], np.float32)
        wts[:2000] = 1.0                    # mask the padding samples
        W = np.pad(W, ((0, Xf.shape[0] - 24), (0, 0)))
        base = make_local_operator(pair, None, fused=False,
                                   partition=cell.partition)
    elif cell.layout == "ell":
        pair, Xp = ell_pair_case(rng, 24, 40, 0.3, 8, 8, width_pad=1,
                                 dtype=_JDT[cell.dtype])
        Xf = np.asarray(jnp.asarray(Xp, _JDT[cell.dtype])
                        .astype(jnp.float32))
        wts = np.zeros(Xp.shape[1], np.float32)
        wts[:40] = 1.0                      # mask the ELL padding columns
        W = np.pad(W, ((0, Xp.shape[0] - 24), (0, 0)))
        X_loc = pair
        base = make_local_operator(X_loc, None, fused=False,
                                   partition=cell.partition)
    else:
        X = jnp.asarray(rng.standard_normal((24, 40)), _JDT[cell.dtype])
        Xf = np.asarray(X.astype(jnp.float32))
        wts = None
        base = make_local_operator(X, None, use_kernel=use_kernel,
                                   fused=False, partition=cell.partition)
    resolve_cell(cell.family, cell.layout, cell.partition, cell.fused,
                 cell.dtype)
    P = softmax_probs_oracle(Xf.T @ W).astype(np.float32)
    som = SoftmaxHvpOperator(base, jnp.asarray(P),
                             weights=(None if wts is None
                                      else jnp.asarray(wts)))
    d = Xf.shape[0]
    U = jnp.asarray(rng.standard_normal((d, K)), jnp.float32)
    want = _softmax_local_oracle(Xf, np.asarray(P, np.float64), wts, U)
    tol = _TOL[cell.dtype]
    scale = max(np.abs(want).max(), 1.0)
    np.testing.assert_allclose(np.asarray(som.apply(U)), want,
                               atol=tol * scale, rtol=tol)
    # (d, K, s) batched product == per-column apply (the s-step round
    # rides ONE multi-vector pass of width K*s)
    U3 = jnp.asarray(rng.standard_normal((d, K, 2)), jnp.float32)
    got3 = np.asarray(som.apply_batch(U3))
    for j in range(2):
        np.testing.assert_allclose(
            got3[:, :, j], np.asarray(som.apply(U3[:, :, j])),
            atol=1e-6 * scale, rtol=1e-6)


def _check_binary_streamed(cell, rng, stream_env):
    """End-to-end: a streaming solve in this cell lands on the in-memory
    two-pass f32 endpoint of the same partitioning."""
    import dataclasses

    from repro.core import DiscoConfig, DiscoSolver
    from repro.data.store import ShardStore

    base_cfg, stores, refs = stream_env
    cfg = dataclasses.replace(base_cfg, partition=cell.partition,
                              hvp_fused=cell.fused, hvp_dtype=cell.dtype)
    res = DiscoSolver.from_store(ShardStore(stores[cell.partition]),
                                 cfg).fit()
    ref = refs[cell.partition]
    tol = 1e-4 if cell.dtype == "float32" else 1e-2
    rel = np.linalg.norm(res.w - ref.w) / np.linalg.norm(ref.w)
    assert rel <= tol, (cell_id(*cell[:5]), rel)


CHECKERS = {
    ("binary", "dense"): _check_binary_inmem,
    ("binary", "dense_kernel"): _check_binary_inmem,
    ("binary", "ell"): _check_binary_inmem,
    ("binary", "slots"): _check_binary_slots,
    ("binary", "streamed"): _check_binary_streamed,
    ("softmax", "dense"): _check_softmax_inmem,
    ("softmax", "dense_kernel"): _check_softmax_inmem,
    ("softmax", "ell"): _check_softmax_inmem,
    ("softmax", "slots"): _check_softmax_inmem,
}


@pytest.fixture(scope="session")
def stream_env(tmp_path_factory):
    """Stores (both axes) + the in-memory two-pass f32 reference fits
    the streamed conformance cells compare against — built once."""
    from repro.core import DiscoConfig, DiscoSolver
    from repro.data.sparse import make_sparse_glm_data
    from repro.data.store import ShardStore

    X, y, _ = make_sparse_glm_data(d=48, n=96, density=0.25, seed=7)
    root = tmp_path_factory.mktemp("hvp_conformance_stores")
    base_cfg = DiscoConfig(loss="logistic", lam=1e-2, tau=16, max_outer=4,
                           grad_tol=1e-9, ell_block_d=8, ell_block_n=8,
                           partition_block=16, stream_chunk_size=16)
    stores, refs = {}, {}
    import dataclasses
    for axis in ("samples", "features"):
        p = str(root / axis)
        ShardStore.from_csr(X, y, p, axis=axis, chunk_size=16)
        stores[axis] = p
        refs[axis] = DiscoSolver(
            X, y, dataclasses.replace(base_cfg, partition=axis)).fit()
    return base_cfg, stores, refs


@pytest.mark.parametrize(
    "cell", CELLS,
    ids=[cell_id(c.family, c.layout, c.partition, c.fused, c.dtype)
         for c in CELLS])
def test_conformance_cell(cell, rng, stream_env):
    if not cell.supported:
        with pytest.raises(UnsupportedHvpError, match="unsupported"):
            resolve_cell(cell.family, cell.layout, cell.partition,
                         cell.fused, cell.dtype)
        return
    checker = CHECKERS.get((cell.family, cell.layout))
    if checker is None:
        pytest.fail(
            f"supported cell {cell_id(cell.family, cell.layout, cell.partition, cell.fused, cell.dtype)} "
            "has NO conformance checker — register one in CHECKERS")
    checker(cell, rng, stream_env)


def test_every_supported_cell_has_checker():
    """The coverage gate: a newly-registered supported (family, layout)
    must come with a checker before it ships."""
    missing = sorted({(c.family, c.layout) for c in CELLS if c.supported}
                     - set(CHECKERS))
    assert not missing, f"cells lacking conformance coverage: {missing}"


def test_registry_is_exhaustive_and_deterministic():
    assert len(CELLS) == 2 * 5 * 2 * 2 * 2
    assert CELLS == operator_cells()
    ids = [cell_id(c.family, c.layout, c.partition, c.fused, c.dtype)
           for c in CELLS]
    assert len(set(ids)) == len(ids)
    # the generated docs matrix has one row per (family, layout,
    # partition) triple
    matrix = render_support_matrix()
    assert matrix.count("\n") == 2 * 5 * 2 + 1


# ---------------------------------------------------------------------------
# latent dispatch-bug regressions: formerly-ignored flags now raise
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("partition", ["samples", "features"])
def test_dense_fused_raises_at_solver_setup(partition):
    """Pre-refactor, hvp_fused on the plain-jnp dense path was silently
    ignored; now the solver refuses the cell by name."""
    from repro.core import DiscoConfig, DiscoSolver

    X = np.eye(8, 12, dtype=np.float32)
    y = np.ones(12, np.float32)
    with pytest.raises(UnsupportedHvpError,
                       match=f"binary/dense/{partition}/fused"):
        DiscoSolver(X, y, DiscoConfig(partition=partition,
                                      hvp_fused=True))


def test_streamed_features_fused_raises(tmp_path):
    """Pre-refactor, streamed DiSCO-F ignored hvp_fused entirely (the
    closures were built from the two-pass scans regardless)."""
    from repro.core import DiscoConfig, DiscoSolver
    from repro.data.sparse import make_sparse_glm_data
    from repro.data.store import ShardStore

    X, y, _ = make_sparse_glm_data(d=16, n=32, density=0.3, seed=1)
    store = ShardStore.from_csr(X, y, str(tmp_path / "s"),
                                axis="features", chunk_size=8)
    with pytest.raises(UnsupportedHvpError,
                       match="binary/streamed/features/fused"):
        DiscoSolver.from_store(store, DiscoConfig(partition="features",
                                                  hvp_fused=True))


def test_softmax_fused_and_streamed_unsupported():
    from repro.core.softmax import SoftmaxConfig, SoftmaxSolver

    with pytest.raises(UnsupportedHvpError, match="softmax/.*fused"):
        resolve_cell("softmax", "dense_kernel", "samples", True)
    with pytest.raises(UnsupportedHvpError, match="softmax/streamed"):
        resolve_cell("softmax", "streamed", "samples", False)
    X = np.eye(4, 8, dtype=np.float32)
    y = np.arange(8) % 2
    with pytest.raises(UnsupportedHvpError, match="softmax/dense/.*fused"):
        SoftmaxSolver(X, y, SoftmaxConfig(hvp_fused=True))


def test_unknown_dtype_raises():
    with pytest.raises(UnsupportedHvpError, match="hvp_dtype"):
        validate_solver_cell(family="binary", partition="samples",
                             fused=False, dtype="float16")


def test_make_local_operator_dense_fused_raises(rng):
    X = jnp.asarray(rng.standard_normal((8, 12)), jnp.float32)
    c = jnp.asarray(rng.random(12), jnp.float32)
    with pytest.raises(UnsupportedHvpError, match="binary/dense/samples"):
        make_local_operator(X, c, fused=True, partition="samples")


# ---------------------------------------------------------------------------
# softmax solver vs f64 NumPy Newton (<= 1e-6 rel) + workload smoke
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("partition", ["samples", "features"])
@pytest.mark.parametrize("block_s", [1, 2])
def test_softmax_matches_numpy_newton(partition, block_s):
    rng = np.random.default_rng(11)
    d, n, K = 10, 80, 3
    X = rng.standard_normal((d, n)).astype(np.float32)
    y = rng.integers(0, K, size=n)
    lam = 0.1                       # rel floor ~ f32 grad floor / lam
    W_ref = softmax_newton_fit(X, y, lam, K=K)

    from repro.core.softmax import SoftmaxConfig, softmax_fit
    cfg = SoftmaxConfig(lam=lam, partition=partition, max_outer=30,
                        max_pcg=200, pcg_rel_tol=0.01, grad_tol=1e-10,
                        pcg_block_s=block_s, tau=24)
    res = softmax_fit(X, y, cfg)
    rel = np.linalg.norm(res.W - W_ref) / np.linalg.norm(W_ref)
    assert rel <= 1e-6, (partition, block_s, rel)


def test_softmax_use_kernel_matches_plain():
    rng = np.random.default_rng(12)
    d, n, K = 8, 48, 3
    X = rng.standard_normal((d, n)).astype(np.float32)
    y = rng.integers(0, K, size=n)

    from repro.core.softmax import SoftmaxConfig, softmax_fit
    kw = dict(lam=1e-2, max_outer=10, max_pcg=60, tau=16)
    r0 = softmax_fit(X, y, SoftmaxConfig(**kw))
    r1 = softmax_fit(X, y, SoftmaxConfig(use_kernel=True, **kw))
    np.testing.assert_allclose(r1.W, r0.W, atol=1e-5, rtol=1e-4)


@pytest.mark.parametrize("loss", ["poisson", "huber"])
def test_glm_losses_solve_end_to_end(loss):
    """Poisson / Huber ride the whole binary HVP stack unchanged (the
    loss enters only through d1/d2 coefficients)."""
    rng = np.random.default_rng(13)
    d, n = 12, 120
    X = (rng.standard_normal((d, n)) * 0.3).astype(np.float32)
    w_true = rng.standard_normal(d).astype(np.float32) * 0.2
    a = X.T @ w_true
    if loss == "poisson":
        y = rng.poisson(np.exp(a)).astype(np.float32)
    else:
        y = (a + 0.05 * rng.standard_normal(n)).astype(np.float32)

    from repro.core import DiscoConfig, disco_fit
    res = disco_fit(X, y, DiscoConfig(loss=loss, partition="samples",
                                      lam=1e-3, max_outer=25, max_pcg=100,
                                      grad_tol=1e-7, tau=32))
    assert res.history[-1]["grad_norm"] <= 1e-5
    # the solver's endpoint must be THE regularized optimum: f64 NumPy
    # Newton on the same objective
    Xd, yd, lam = np.asarray(X, np.float64), np.asarray(y, np.float64), 1e-3
    w = np.zeros(d)
    for _ in range(60):
        m = Xd.T @ w
        if loss == "poisson":
            d1, d2 = np.exp(m) - yd, np.exp(m)
        else:                                   # huber, delta = 1.0
            r_ = m - yd
            d1 = np.clip(r_, -1.0, 1.0)
            d2 = (np.abs(r_) <= 1.0).astype(np.float64)
        g = Xd @ d1 / n + lam * w
        H = Xd @ (d2[:, None] * Xd.T) / n + lam * np.eye(d)
        w = w - np.linalg.solve(H, g)
        if np.linalg.norm(g) < 1e-12:
            break
    rel = np.linalg.norm(res.w - w) / np.linalg.norm(w)
    assert rel <= 1e-4, (loss, rel)


# ---------------------------------------------------------------------------
# property suites (satellite 1)
#
# Each property is a plain helper checked two ways: always over a
# deterministic seeded grid (so the properties run even where hypothesis
# is not installed — this container ships without it), and additionally
# under hypothesis @given when the library is available.
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


def _prop_softmax_psd(d, n, K, dtype, seed):
    """P = softmax(X^T W) rows are a probability simplex, and the
    softmax Hessian (lam=0) is PSD: U . H U >= 0 for random U."""
    from repro.kernels import ops as kops

    r = np.random.default_rng(seed)
    X = jnp.asarray(r.standard_normal((d, n)), _JDT[dtype])
    W = jnp.asarray(r.standard_normal((d, K)), jnp.float32)
    P = np.asarray(jnp.asarray(
        softmax_probs_oracle(np.asarray(X.astype(jnp.float32)).T
                             @ np.asarray(W)), jnp.float32))
    assert (P >= 0).all()
    np.testing.assert_allclose(P.sum(axis=1), 1.0, atol=1e-5)
    U = jnp.asarray(r.standard_normal((d, K)), jnp.float32)
    HU = kops.softmax_hvp(X.astype(jnp.float32), jnp.asarray(P), U)
    quad = float(np.vdot(np.asarray(U), np.asarray(HU)))
    scale = float(np.vdot(np.asarray(U), np.asarray(U))) + 1e-9
    assert quad >= -1e-5 * scale


def _prop_poisson_fd(seed, scale):
    from repro.core.losses import POISSON

    r = np.random.default_rng(seed)
    a = jnp.asarray(r.standard_normal(17) * scale, jnp.float32)
    y = jnp.asarray(r.poisson(1.5, 17), jnp.float32)
    d1_fd = fd_derivative(lambda t: POISSON.value(t, y), a, eps=1e-3)
    np.testing.assert_allclose(np.asarray(POISSON.d1(a, y)), d1_fd,
                               atol=5e-3, rtol=5e-3)
    d2_fd = fd_derivative(lambda t: POISSON.d1(t, y), a, eps=1e-3)
    np.testing.assert_allclose(np.asarray(POISSON.d2(a, y)), d2_fd,
                               atol=5e-3, rtol=5e-3)
    assert (np.asarray(POISSON.d2(a, y)) > 0).all()   # strictly convex


def _prop_huber_fd(seed, delta):
    from repro.core.losses import make_huber

    loss = make_huber(delta)
    r = np.random.default_rng(seed)
    a = r.standard_normal(25).astype(np.float32) * 2.0
    y = r.standard_normal(25).astype(np.float32)
    # keep FD probes away from the |r| = delta seam
    keep = np.abs(np.abs(a - y) - delta) > 0.05
    a, y = jnp.asarray(a[keep]), jnp.asarray(y[keep])
    d1_fd = fd_derivative(lambda t: loss.value(t, y), a, eps=1e-3)
    np.testing.assert_allclose(np.asarray(loss.d1(a, y)), d1_fd,
                               atol=5e-3, rtol=5e-3)
    d2_fd = fd_derivative(lambda t: loss.d1(t, y), a, eps=1e-3)
    np.testing.assert_allclose(np.asarray(loss.d2(a, y)), d2_fd,
                               atol=5e-3, rtol=5e-3)
    d2 = np.asarray(loss.d2(a, y))
    assert set(np.unique(d2)).issubset({0.0, 1.0})
    assert np.abs(np.asarray(loss.d1(a, y))).max() <= delta + 1e-6


def _prop_ell_geometry(d, n, br, bc, fused, seed):
    """EllOperator == oracle over random shapes and ELL block sizes."""
    r = np.random.default_rng(seed)
    pair, Xp = ell_pair_case(r, d, n, 0.3, br, bc, width_pad=1)
    c = jnp.asarray(r.random(Xp.shape[1]), jnp.float32)
    u = jnp.asarray(r.standard_normal(Xp.shape[0]), jnp.float32)
    op = make_local_operator(pair, c, fused=fused, partition="samples")
    want = local_hvp_oracle(Xp, c, u)
    scale = max(np.abs(want).max(), 1.0)
    np.testing.assert_allclose(np.asarray(op.apply(u)), want,
                               atol=1e-4 * scale, rtol=1e-4)


@pytest.mark.parametrize("d,n,K,dtype,seed", [
    (2, 2, 2, "float32", 0), (7, 33, 3, "float32", 1),
    (40, 60, 5, "float32", 2), (13, 9, 4, "bfloat16", 3),
    (24, 48, 2, "bfloat16", 4), (3, 50, 5, "float32", 5),
])
def test_softmax_probs_row_stochastic_and_hvp_psd(d, n, K, dtype, seed):
    _prop_softmax_psd(d, n, K, dtype, seed)


@pytest.mark.parametrize("seed,scale", [(0, 0.1), (1, 0.7), (2, 1.3),
                                        (3, 2.0), (4, 1.0)])
def test_poisson_grad_hess_fd_consistency(seed, scale):
    _prop_poisson_fd(seed, scale)


@pytest.mark.parametrize("seed,delta", [(0, 0.3), (1, 0.7), (2, 1.0),
                                        (3, 1.6), (4, 2.0)])
def test_huber_grad_hess_fd_consistency(seed, delta):
    _prop_huber_fd(seed, delta)


@pytest.mark.parametrize("d,n,br,bc,fused,seed", [
    (4, 4, 2, 2, False, 0), (17, 23, 4, 8, False, 1),
    (48, 31, 8, 4, True, 2), (9, 48, 2, 4, True, 3),
    (33, 12, 8, 8, False, 4), (5, 47, 4, 2, True, 5),
])
def test_ell_operator_random_geometry(d, n, br, bc, fused, seed):
    _prop_ell_geometry(d, n, br, bc, fused, seed)


if HAVE_HYPOTHESIS:
    @given(d=st.integers(2, 40), n=st.integers(2, 60),
           K=st.integers(2, 5),
           dtype=st.sampled_from(["float32", "bfloat16"]),
           seed=st.integers(0, 99))
    @settings(max_examples=20, deadline=None)
    def test_softmax_psd_hypothesis(d, n, K, dtype, seed):
        _prop_softmax_psd(d, n, K, dtype, seed)

    @given(seed=st.integers(0, 199), scale=st.floats(0.1, 2.0))
    @settings(max_examples=25, deadline=None)
    def test_poisson_fd_hypothesis(seed, scale):
        _prop_poisson_fd(seed, scale)

    @given(seed=st.integers(0, 199), delta=st.floats(0.3, 2.0))
    @settings(max_examples=25, deadline=None)
    def test_huber_fd_hypothesis(seed, delta):
        _prop_huber_fd(seed, delta)

    @given(d=st.integers(4, 48), n=st.integers(4, 48),
           br=st.sampled_from([2, 4, 8]), bc=st.sampled_from([2, 4, 8]),
           fused=st.booleans(), seed=st.integers(0, 50))
    @settings(max_examples=15, deadline=None)
    def test_ell_geometry_hypothesis(d, n, br, bc, fused, seed):
        _prop_ell_geometry(d, n, br, bc, fused, seed)


# ---------------------------------------------------------------------------
# λ-path: warm == cold endpoints, ledger sane, layout shared
# ---------------------------------------------------------------------------


def _path_problem(seed=21, d=12, n=96):
    r = np.random.default_rng(seed)
    X = r.standard_normal((d, n)).astype(np.float32)
    w_true = r.standard_normal(d).astype(np.float32)
    y = np.sign(X.T @ w_true + 0.1 * r.standard_normal(n)) \
        .astype(np.float32)
    return X, y


def test_lambda_path_warm_matches_cold_endpoints():
    from repro.core import DiscoConfig
    from repro.core.lambda_path import lambda_path_fit

    X, y = _path_problem()
    lams = [0.3, 0.03, 0.003]
    cfg = DiscoConfig(partition="samples", max_outer=30, max_pcg=80,
                      tau=24, grad_tol=1e-7)
    warm = lambda_path_fit(X, y, lams, cfg, warm=True)
    cold = lambda_path_fit(X, y, lams, cfg, warm=False)
    assert warm.lambdas == sorted(lams, reverse=True)
    for lw, wr, cr in zip(warm.lambdas, warm.results, cold.results):
        scale = max(np.abs(cr.w).max(), 1e-6)
        np.testing.assert_allclose(wr.w, cr.w, atol=1e-4 * scale,
                                   rtol=1e-3, err_msg=f"lam={lw}")
    # warm-starting never pays MORE X passes than cold refits
    assert warm.total_x_passes <= cold.total_x_passes


def test_lambda_path_with_lam_shares_device_arrays():
    from repro.core import DiscoConfig, DiscoSolver

    X, y = _path_problem(seed=22)
    s0 = DiscoSolver(X, y, DiscoConfig(partition="samples", lam=0.1))
    s1 = s0.with_lam(0.01)
    assert s1.cfg.lam == 0.01 and s0.cfg.lam == 0.1
    assert s1.X is s0.X and s1.y is s0.y and s1.X_tau is s0.X_tau
    assert s1._step is not s0._step


def test_lambda_path_selects_by_validation_loss():
    from repro.core import DiscoConfig
    from repro.core.lambda_path import lambda_path_fit

    X, y = _path_problem(seed=23)
    Xv, yv = _path_problem(seed=24)
    res = lambda_path_fit(X, y, [1.0, 0.1, 0.01],
                          DiscoConfig(partition="samples", max_outer=20,
                                      max_pcg=60, tau=24),
                          X_val=Xv, y_val=yv)
    assert res.best_index is not None
    assert res.val_losses[res.best_index] == min(res.val_losses)
    assert res.best_lambda == res.lambdas[res.best_index]
    assert res.best_result is res.results[res.best_index]


def test_x_passes_ledger_arithmetic():
    from repro.core import DiscoConfig
    from repro.core.lambda_path import x_passes

    hist = [dict(pcg_iters=5), dict(pcg_iters=3)]
    # classic two-pass: 2 + 2*iters per outer
    assert x_passes(hist, DiscoConfig(pcg_block_s=1)) == (2 + 10) + (2 + 6)
    # fused halves the HVP passes
    assert x_passes(hist, DiscoConfig(pcg_block_s=1, hvp_fused=True)) \
        == (2 + 5) + (2 + 3)
    # s-step multi-shard DiSCO-S: basis ops are X-free, one batched
    # multi-vector HVP (2 passes two-pass) per round
    cfg_s = DiscoConfig(pcg_block_s=4, partition="samples")
    assert x_passes(hist, cfg_s, axis_size=4) == (2 + 5 * 2) + (2 + 3 * 2)
    # single-shard s-step: s-1 basis applications touch X per round
    per_round = 2 + 3 * 2
    assert x_passes(hist, cfg_s, axis_size=1) \
        == (2 + 5 * per_round) + (2 + 3 * per_round)


def test_refit_path_publishes_best_lambda(tmp_path):
    from repro.core import DiscoConfig
    from repro.data.sparse import make_sparse_glm_data
    from repro.data.store import ShardStore
    from repro.glm_serve.refit import RefitLoop
    from repro.glm_serve.registry import ModelRegistry

    X, y, _ = make_sparse_glm_data(d=24, n=96, density=0.3, seed=5)
    store = ShardStore.from_csr(X, y, str(tmp_path / "store"),
                                axis="samples", chunk_size=16)
    reg = ModelRegistry(str(tmp_path / "reg"))
    cfg = DiscoConfig(partition="samples", lam=1.0, max_outer=10,
                      max_pcg=60, tau=16, ell_block_d=8, ell_block_n=8,
                      partition_block=16)
    loop = RefitLoop(reg, store, cfg)
    Xv, yv, _ = make_sparse_glm_data(d=24, n=64, density=0.3, seed=6)
    version, path = loop.refit_path([1.0, 0.1, 0.01], X_val=Xv, y_val=yv)
    assert path.best_index is not None
    assert loop.cfg.lam == path.best_lambda
    assert reg.active_version() == version
    np.testing.assert_array_equal(reg.load().w, path.best_result.w)


# ---------------------------------------------------------------------------
# 4-device subprocess equivalence (satellite 2)
# ---------------------------------------------------------------------------

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["REPRO_KERNEL_MODE"] = "interpret"
    import numpy as np
    import jax
    assert len(jax.devices()) == 4
    from repro.launch.mesh import make_mesh

    from repro.core import DiscoConfig
    from repro.core.lambda_path import lambda_path_fit
    from repro.core.softmax import SoftmaxConfig, softmax_fit

    r = np.random.default_rng(3)
    d, n, K = 16, 96, 3
    X = r.standard_normal((d, n)).astype(np.float32)
    y = r.integers(0, K, size=n)

    for partition, axis in (("samples", "data"), ("features", "model")):
        mesh1 = make_mesh((1,), (axis,))
        mesh4 = make_mesh((4,), (axis,))
        for s in (1, 2):
            cfg = SoftmaxConfig(lam=1e-2, partition=partition,
                                max_outer=12, max_pcg=80, grad_tol=1e-7,
                                pcg_block_s=s, tau=24)
            W1 = softmax_fit(X, y, cfg, mesh=mesh1).W
            W4 = softmax_fit(X, y, cfg, mesh=mesh4).W
            np.testing.assert_allclose(W4, W1, atol=5e-4, rtol=1e-3)
            print("softmax", partition, "s=", s, "ok",
                  float(np.abs(W4 - W1).max()))

    yb = np.sign(r.standard_normal(n)).astype(np.float32)
    lams = [0.3, 0.03, 0.003]
    for partition, axis in (("samples", "data"), ("features", "model")):
        mesh1 = make_mesh((1,), (axis,))
        mesh4 = make_mesh((4,), (axis,))
        cfg = DiscoConfig(partition=partition, max_outer=15, max_pcg=80,
                          tau=24, grad_tol=1e-7, pcg_block_s=2)
        p1 = lambda_path_fit(X, yb, lams, cfg, mesh=mesh1)
        p4 = lambda_path_fit(X, yb, lams, cfg, mesh=mesh4)
        for lam, w1, w4 in zip(p1.lambdas, p1.results, p4.results):
            np.testing.assert_allclose(w4.w, w1.w, atol=5e-4, rtol=1e-3)
        print("lambda-path", partition, "ok")
    print("HVP_OPERATOR_MULTIDEVICE_PASS")
""")


@pytest.mark.slow
def test_softmax_and_lambda_path_4device_equivalence():
    """Multinomial softmax and warm λ-path solves agree between a
    single-device and a real 4-shard mesh under both partitionings and
    s-step PCG (same tolerance precedent as tests/test_multidevice.py)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "HVP_OPERATOR_MULTIDEVICE_PASS" in r.stdout
