"""Distributed PCG (Algorithms 2/3) against a dense numpy Newton solve."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from oracles import (make_glm_problem as _problem,
                     newton_direction_oracle as _dense_newton_direction)
from repro.core.glm import GLMProblem
from repro.core.losses import get_loss
from repro.core.pcg import PCGResult, pcg_features, pcg_samples
from repro.launch.mesh import make_mesh
from jax import shard_map


def _run_single_device(fn, in_specs, out_specs, axis, *args):
    mesh = make_mesh((1,), (axis,))
    return jax.jit(shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False))(*args)


@pytest.mark.parametrize("loss", ["quadratic", "logistic"])
@pytest.mark.parametrize("precond", ["woodbury", "none"])
def test_pcg_samples_solves_newton_system(rng, loss, precond):
    prob, w = _problem(rng, loss=loss)
    v_exact, g = _dense_newton_direction(prob, w)
    c = prob.hess_coeffs(w)
    tau = 32
    coeffs_tau = c[:tau]

    def body(X, cc, gg, Xt, ct):
        return pcg_samples(X, cc, prob.n, prob.lam, gg, 1e-7, 200,
                           X_tau=Xt, coeffs_tau=ct, mu=1e-2,
                           axis_name="data", precond=precond)

    res = _run_single_device(
        body, (P(None, "data"), P("data"), P(), P(), P()),
        PCGResult(P(), P(), P(), P()), "data",
        prob.X, c, jnp.asarray(g), prob.X[:, :tau], coeffs_tau)
    np.testing.assert_allclose(res.v, v_exact, atol=1e-3, rtol=1e-3)
    assert float(res.r_norm) <= 1e-6


@pytest.mark.parametrize("precond", ["woodbury", "none"])
def test_pcg_features_solves_newton_system(rng, precond):
    prob, w = _problem(rng)
    v_exact, g = _dense_newton_direction(prob, w)
    c = prob.hess_coeffs(w)
    tau = 32

    def body(X, cc, gg, ct):
        return pcg_features(X, cc, prob.n, prob.lam, gg, 1e-7, 200,
                            tau_idx=jnp.arange(tau), coeffs_tau=ct,
                            mu=1e-2, axis_name="model", precond=precond)

    res = _run_single_device(
        body, (P("model", None), P(), P("model"), P()),
        PCGResult(P("model"), P(), P(), P()), "model",
        prob.X, c, jnp.asarray(g), c[:tau])
    np.testing.assert_allclose(res.v, v_exact, atol=1e-3, rtol=1e-3)


def test_samples_and_features_agree(rng):
    """Algorithms 2 and 3 compute the SAME iterates (identical math,
    different partitioning) — core of the paper's 'same convergence,
    less communication' claim."""
    prob, w = _problem(rng)
    g = prob.grad(w)
    c = prob.hess_coeffs(w)
    tau = 16

    def body_s(X, cc, gg, Xt, ct):
        return pcg_samples(X, cc, prob.n, prob.lam, gg, 1e-6, 100,
                           X_tau=Xt, coeffs_tau=ct, mu=1e-2,
                           axis_name="data", precond="woodbury")

    def body_f(X, cc, gg, ct):
        return pcg_features(X, cc, prob.n, prob.lam, gg, 1e-6, 100,
                            tau_idx=jnp.arange(tau), coeffs_tau=ct,
                            mu=1e-2, axis_name="model", precond="woodbury")

    res_s = _run_single_device(
        body_s, (P(None, "data"), P("data"), P(), P(), P()),
        PCGResult(P(), P(), P(), P()), "data", prob.X, c, g, prob.X[:, :tau], c[:tau])
    res_f = _run_single_device(
        body_f, (P("model", None), P(), P("model"), P()),
        PCGResult(P("model"), P(), P(), P()), "model", prob.X, c, g, c[:tau])
    # on one device the block-diag preconditioner == full preconditioner,
    # so the iterates coincide exactly
    np.testing.assert_allclose(res_s.v, res_f.v, atol=1e-4, rtol=1e-4)
    assert int(res_s.iters) == int(res_f.iters)
    np.testing.assert_allclose(float(res_s.delta), float(res_f.delta),
                               atol=1e-4, rtol=1e-3)


def test_woodbury_preconditioning_reduces_iterations(rng):
    """Paper Fig 4 mechanism: better preconditioning => fewer PCG iters.

    Needs an ill-conditioned Hessian (cond ~ 7e4 here) — on easy problems
    plain CG already converges in ~10 steps and preconditioning is moot.
    """
    from repro.data.synthetic import make_glm_data
    X, y, _ = make_glm_data(d=100, n=500, cond_decay=2.0, seed=3)
    scal = (np.arange(1, 101) ** -1.0).astype(np.float32)
    X = (np.asarray(X).T * scal).T * 10          # power-law row scaling
    w = jnp.asarray(rng.standard_normal(100).astype(np.float32) * 0.1)
    prob = GLMProblem.create(X, np.asarray(y), loss="logistic", lam=1e-5)
    g = prob.grad(w)
    c = prob.hess_coeffs(w)
    iters = {}
    for precond, tau in (("none", 1), ("woodbury", 20), ("woodbury", 100),
                         ("woodbury", 300)):
        def body(X_, cc, gg, Xt, ct):
            return pcg_samples(X_, cc, prob.n, prob.lam, gg, 1e-7, 1000,
                               X_tau=Xt, coeffs_tau=ct, mu=1e-5,
                               axis_name="data", precond=precond)
        res = _run_single_device(
            body, (P(None, "data"), P("data"), P(), P(), P()),
            PCGResult(P(), P(), P(), P()), "data",
            prob.X, c, g, prob.X[:, :tau], c[:tau])
        iters[(precond, tau)] = int(res.iters)
    # monotone: more preconditioner samples -> fewer PCG iterations
    assert iters[("woodbury", 300)] < iters[("woodbury", 100)] \
        < iters[("woodbury", 20)] < iters[("none", 1)]
    # and the gain is large (paper: "very small tau already works")
    assert iters[("woodbury", 100)] * 3 < iters[("none", 1)]


def test_delta_is_newton_decrement(rng):
    """delta_k = sqrt(v^T H v) drives the damped step (Algorithm 1)."""
    prob, w = _problem(rng, loss="quadratic")
    g = prob.grad(w)
    c = prob.hess_coeffs(w)

    def body(X, cc, gg, Xt, ct):
        return pcg_samples(X, cc, prob.n, prob.lam, gg, 1e-8, 300,
                           X_tau=Xt, coeffs_tau=ct, mu=1e-2,
                           axis_name="data", precond="woodbury")

    res = _run_single_device(
        body, (P(None, "data"), P("data"), P(), P(), P()),
        PCGResult(P(), P(), P(), P()), "data", prob.X, c, g, prob.X[:, :16], c[:16])
    H = np.asarray(prob.hessian(w))
    v = np.asarray(res.v)
    np.testing.assert_allclose(float(res.delta),
                               float(np.sqrt(v @ H @ v)),
                               atol=1e-3, rtol=1e-2)
