"""s-step (communication-avoiding) PCG: solution equivalence with classic
PCG, multi-vector kernels vs jnp oracles, and the CommLedger round drop."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import DiscoConfig, disco_fit
from repro.core import comm
from repro.core.glm import GLMProblem
from repro.core.pcg import PCGResult, pcg_features, pcg_samples
from repro.launch.mesh import make_mesh
from jax import shard_map


def _problem(rng, d=40, n=200, loss="logistic", lam=1e-2):
    X = rng.standard_normal((d, n)).astype(np.float32)
    X /= np.linalg.norm(X, axis=0, keepdims=True)
    y = np.sign(rng.standard_normal(n)).astype(np.float32)
    w = rng.standard_normal(d).astype(np.float32) * 0.1
    prob = GLMProblem.create(X, y, loss=loss, lam=lam)
    return prob, jnp.asarray(w)


def _run_single_device(fn, in_specs, out_specs, axis, *args):
    mesh = make_mesh((1,), (axis,))
    return jax.jit(shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False))(*args)


# ---------------------------------------------------------------------------
# solver equivalence: pcg(block_s > 1) reaches the classic pcg(s=1) solution
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("precond", ["woodbury", "none"])
@pytest.mark.parametrize("s", [2, 4])
def test_sstep_samples_matches_classic(rng, precond, s):
    prob, w = _problem(rng)
    g = prob.grad(w)
    c = prob.hess_coeffs(w)
    tau = 32
    H = np.asarray(prob.hessian(w))
    v_exact = np.linalg.solve(H, np.asarray(g))

    def body(X, cc, gg, Xt, ct, bs):
        return pcg_samples(X, cc, prob.n, prob.lam, gg, 1e-6, 200,
                           X_tau=Xt, coeffs_tau=ct, mu=1e-2,
                           axis_name="data", precond=precond,
                           block_s=bs, axis_size=1)

    specs = (P(None, "data"), P("data"), P(), P(), P())
    out = PCGResult(P(), P(), P(), P())
    args = (prob.X, c, g, prob.X[:, :tau], c[:tau])
    r1 = _run_single_device(lambda *a: body(*a, 1), specs, out, "data", *args)
    rs = _run_single_device(lambda *a: body(*a, s), specs, out, "data", *args)
    # both solve H v = g to the same residual tolerance -> same solution
    np.testing.assert_allclose(rs.v, v_exact, atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(rs.v, r1.v, atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(float(rs.delta), float(r1.delta),
                               atol=1e-3, rtol=1e-2)
    assert float(rs.r_norm) <= 1e-6
    # each round advances ~s Krylov dimensions
    assert int(rs.iters) < int(r1.iters)


@pytest.mark.parametrize("precond", ["woodbury", "none"])
@pytest.mark.parametrize("s", [2, 4])
def test_sstep_features_matches_classic(rng, precond, s):
    prob, w = _problem(rng)
    g = prob.grad(w)
    c = prob.hess_coeffs(w)
    tau = 32
    H = np.asarray(prob.hessian(w))
    v_exact = np.linalg.solve(H, np.asarray(g))

    def body(X, cc, gg, ct, bs):
        return pcg_features(X, cc, prob.n, prob.lam, gg, 1e-6, 200,
                            tau_idx=jnp.arange(tau), coeffs_tau=ct,
                            mu=1e-2, axis_name="model", precond=precond,
                            block_s=bs)

    specs = (P("model", None), P(), P("model"), P())
    out = PCGResult(P("model"), P(), P(), P())
    args = (prob.X, c, g, c[:tau])
    r1 = _run_single_device(lambda *a: body(*a, 1), specs, out, "model", *args)
    rs = _run_single_device(lambda *a: body(*a, s), specs, out, "model", *args)
    np.testing.assert_allclose(rs.v, v_exact, atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(rs.v, r1.v, atol=1e-3, rtol=1e-3)
    assert float(rs.r_norm) <= 1e-6
    assert int(rs.iters) < int(r1.iters)


def test_sstep_round_count_near_optimal(rng):
    """With the exact (single-shard) basis operator and the carried
    previous-round direction, one round buys ~s classic iterations."""
    prob, w = _problem(rng)
    g = prob.grad(w)
    c = prob.hess_coeffs(w)
    tau = 32

    def body(X, cc, gg, Xt, ct, bs):
        return pcg_samples(X, cc, prob.n, prob.lam, gg, 1e-6, 200,
                           X_tau=Xt, coeffs_tau=ct, mu=1e-2,
                           axis_name="data", precond="woodbury",
                           block_s=bs, axis_size=1)

    specs = (P(None, "data"), P("data"), P(), P(), P())
    out = PCGResult(P(), P(), P(), P())
    args = (prob.X, c, g, prob.X[:, :tau], c[:tau])
    r1 = _run_single_device(lambda *a: body(*a, 1), specs, out, "data", *args)
    r4 = _run_single_device(lambda *a: body(*a, 4), specs, out, "data", *args)
    assert int(r4.iters) <= int(np.ceil(int(r1.iters) / 4)) + 1, \
        (int(r4.iters), int(r1.iters))


def test_sstep_use_kernel_matches_jnp_path(rng):
    """The multi-vector Pallas kernels (interpret mode) drive the s-step
    engine to the same result as the jnp path."""
    prob, w = _problem(rng)
    g = prob.grad(w)
    c = prob.hess_coeffs(w)
    tau = 32

    def body(X, cc, gg, Xt, ct, uk):
        return pcg_samples(X, cc, prob.n, prob.lam, gg, 1e-6, 200,
                           X_tau=Xt, coeffs_tau=ct, mu=1e-2,
                           axis_name="data", precond="woodbury",
                           block_s=4, axis_size=1, use_kernel=uk)

    specs = (P(None, "data"), P("data"), P(), P(), P())
    out = PCGResult(P(), P(), P(), P())
    args = (prob.X, c, g, prob.X[:, :tau], c[:tau])
    ra = _run_single_device(lambda *a: body(*a, False), specs, out, "data",
                            *args)
    rb = _run_single_device(lambda *a: body(*a, True), specs, out, "data",
                            *args)
    np.testing.assert_allclose(ra.v, rb.v, atol=2e-4, rtol=1e-3)


# ---------------------------------------------------------------------------
# multi-vector kernels vs jnp oracles (interpret mode, no hypothesis dep)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("d,n,s", [(64, 64, 1), (100, 237, 3), (130, 257, 5),
                                   (40, 200, 9), (1, 129, 2), (257, 130, 8)])
def test_xt_multi_matches_ref(rng, d, n, s):
    from repro.kernels import xt_multi
    from repro.kernels.ref import ref_xt_multi
    X = jnp.asarray(rng.standard_normal((d, n)), jnp.float32)
    U = jnp.asarray(rng.standard_normal((d, s)), jnp.float32)
    np.testing.assert_allclose(xt_multi(X, U, block_d=128, block_n=128),
                               ref_xt_multi(X, U), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("d,n,s", [(64, 64, 1), (100, 237, 3), (130, 257, 5),
                                   (40, 200, 9), (1, 129, 2), (257, 130, 8)])
def test_x_cz_multi_matches_ref(rng, d, n, s):
    from repro.kernels import x_cz_multi
    from repro.kernels.ref import ref_x_cz_multi
    X = jnp.asarray(rng.standard_normal((d, n)), jnp.float32)
    c = jnp.asarray(rng.random(n), jnp.float32)
    Z = jnp.asarray(rng.standard_normal((n, s)), jnp.float32)
    np.testing.assert_allclose(x_cz_multi(X, c, Z, block_d=128, block_n=128),
                               ref_x_cz_multi(X, c, Z), atol=1e-4, rtol=1e-4)


def test_glm_hvp_multi_columns_match_single(rng):
    """Each column of the batched HVP equals the single-vector HVP."""
    from repro.kernels import glm_hvp, glm_hvp_multi
    d, n, s = 96, 200, 4
    X = jnp.asarray(rng.standard_normal((d, n)), jnp.float32)
    c = jnp.asarray(rng.random(n), jnp.float32)
    U = jnp.asarray(rng.standard_normal((d, s)), jnp.float32)
    batched = glm_hvp_multi(X, c, U, 0.05, block_d=128, block_n=128)
    for j in range(s):
        single = glm_hvp(X, c, U[:, j], 0.05, block_d=128, block_n=128)
        np.testing.assert_allclose(batched[:, j], single,
                                   atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
# communication accounting
# ---------------------------------------------------------------------------

def test_comm_sstep_formulas():
    # DiSCO-S s-step round: broadcast + reduceAll of a (d, s+1) payload
    r, fl, spmd = comm.disco_s_sstep_cost(d=100, s=4, rounds=3)
    assert r == 6 and fl == 2 * 100 * 5 * 3 and spmd == 3
    # DiSCO-F s-step round: one (n, s) reduceAll (H p_prev carried free)
    # + the fused Gram reduce
    r, fl, spmd = comm.disco_f_sstep_cost(n=50, s=4, rounds=3)
    assert r == 3 and fl == (50 * 4 + 2 * 25 + 5) * 3 and spmd == 6


def test_sstep_ledger_rounds_drop(glm_data):
    """Acceptance: >= 2x fewer communication rounds at s=4 vs s=1, same
    final gradient norm (within PCG tolerance) on the synthetic logistic
    problem."""
    X, y, _ = glm_data
    kw = dict(loss="logistic", lam=1e-4, tau=16, max_outer=10,
              grad_tol=1e-8, pcg_rel_tol=0.02)
    for part in ("samples", "features"):
        base = disco_fit(X, y, DiscoConfig(partition=part, **kw))
        fast = disco_fit(X, y, DiscoConfig(partition=part, pcg_block_s=4,
                                           **kw))
        assert base.ledger.rounds >= 2 * fast.ledger.rounds, \
            (part, base.ledger.rounds, fast.ledger.rounds)
        # same Newton trajectory endpoint
        assert fast.grad_norms[-1] <= 1e-7, (part, fast.grad_norms[-1])
        np.testing.assert_allclose(fast.w, base.w, atol=5e-4, rtol=1e-3)
