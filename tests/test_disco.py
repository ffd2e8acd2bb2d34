"""DiSCO end-to-end (Algorithm 1): convergence, S/F equivalence, ledger."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DiscoConfig, DiscoSolver, disco_fit
from repro.core import comm
from repro.core.glm import GLMProblem
from repro.data.synthetic import make_glm_data


def _optimum(X, y, loss, lam):
    """High-accuracy reference optimum via many Newton steps."""
    res = disco_fit(X, y, DiscoConfig(loss=loss, lam=lam, partition="samples",
                                      precond="woodbury", tau=64,
                                      max_outer=50, grad_tol=1e-12,
                                      pcg_rel_tol=1e-3))
    return res.w


@pytest.mark.parametrize("loss", ["quadratic", "logistic", "squared_hinge"])
@pytest.mark.parametrize("partition", ["samples", "features"])
def test_disco_converges_all_losses(glm_data, loss, partition):
    X, y, _ = glm_data
    cfg = DiscoConfig(loss=loss, lam=1e-3, tau=32, partition=partition,
                      max_outer=25, grad_tol=1e-7)   # f32 floor ~1e-8
    res = disco_fit(X, y, cfg)
    assert res.converged, (loss, partition, res.grad_norms[-1])
    assert res.grad_norms[-1] <= 1e-7


def test_grad_norm_decreases_superlinearly(glm_data):
    """Newton-type behaviour: late-phase contraction is much faster than a
    fixed linear rate (vs e.g. plain GD)."""
    X, y, _ = glm_data
    res = disco_fit(X, y, DiscoConfig(loss="logistic", lam=1e-3, tau=32,
                                      max_outer=25, grad_tol=1e-7))
    g = res.grad_norms
    # contraction factor of the last step is tiny
    assert g[-1] / g[-2] < 0.05


def test_samples_features_same_trajectory(glm_data):
    """DiSCO-S and DiSCO-F produce the same Newton iterates on one device
    (the partitioning changes communication, not math)."""
    X, y, _ = glm_data
    kw = dict(loss="logistic", lam=1e-3, tau=16, max_outer=8,
              grad_tol=0.0)
    rs = disco_fit(X, y, DiscoConfig(partition="samples", **kw))
    rf = disco_fit(X, y, DiscoConfig(partition="features", **kw))
    gs = rs.grad_norms
    gf = rf.grad_norms
    # identical until the f32 floor (~1e-7) adds partition-order noise
    np.testing.assert_allclose(gs[:6], gf[:6], rtol=1e-3)
    np.testing.assert_allclose(rs.w, rf.w, atol=1e-4, rtol=1e-3)


def test_feature_partition_halves_comm_rounds(glm_data):
    """Paper §5.2/Fig 3: 'DiSCO-F uses only half of the rounds of
    communications compared with DiSCO-S' (same PCG iterations, but each
    costs one round instead of a broadcast+reduce pair)."""
    X, y, _ = glm_data
    kw = dict(loss="logistic", lam=1e-3, tau=16, max_outer=8, grad_tol=0.0)
    rs = disco_fit(X, y, DiscoConfig(partition="samples", **kw))
    rf = disco_fit(X, y, DiscoConfig(partition="features", **kw))
    ratio = rf.ledger.rounds / rs.ledger.rounds
    assert 0.4 <= ratio <= 0.65, ratio


def test_hessian_subsampling_still_converges(glm_data):
    """Paper §5.4: subsampled Hessian trades accuracy for time but the
    outer loop still drives the gradient down."""
    X, y, _ = glm_data
    res = disco_fit(X, y, DiscoConfig(loss="logistic", lam=1e-3, tau=32,
                                      hessian_subsample=0.25, max_outer=25))
    # inexact Hessian: no high-accuracy guarantee (paper: "give up the
    # guaranteed complexity") — but a 100x gradient reduction must hold
    assert res.grad_norms[-1] < 1e-2 * res.grad_norms[0]


def test_tau_zero_equals_identity_like(glm_data):
    """tau=1 (nearly no preconditioning) still converges, slower or equal."""
    X, y, _ = glm_data
    r_small = disco_fit(X, y, DiscoConfig(loss="logistic", lam=1e-3, tau=1,
                                          max_outer=30))
    r_big = disco_fit(X, y, DiscoConfig(loss="logistic", lam=1e-3, tau=100,
                                        max_outer=30))
    assert r_big.converged
    assert r_small.converged
    # bigger tau never needs more total PCG iterations
    it_small = sum(h["pcg_iters"] for h in r_small.history)
    it_big = sum(h["pcg_iters"] for h in r_big.history)
    assert it_big <= it_small


def test_solution_is_regularized_erm_optimum(glm_data):
    """The returned w satisfies the first-order condition of (P)."""
    X, y, _ = glm_data
    lam = 1e-3
    res = disco_fit(X, y, DiscoConfig(loss="logistic", lam=lam, tau=32,
                                      max_outer=30))
    prob = GLMProblem.create(X, y, loss="logistic", lam=lam)
    g = prob.grad(jnp.asarray(res.w))
    assert float(jnp.linalg.norm(g)) < 1e-6


def test_damped_step_monotone_descent(glm_data):
    """Self-concordant damping guarantees monotone objective decrease."""
    X, y, _ = glm_data
    res = disco_fit(X, y, DiscoConfig(loss="logistic", lam=1e-3, tau=32,
                                      max_outer=20, grad_tol=0.0))
    f = [h["f"] for h in res.history]
    assert all(b <= a + 1e-7 for a, b in zip(f, f[1:])), f


def test_comm_ledger_formulas():
    """Ledger accounting mirrors paper Table 4 / Algorithms 2-3."""
    # DiSCO-S PCG iteration: broadcast d + reduceAll d = 2 rounds, 2d floats
    r, fl, spmd = comm.disco_s_pcg_cost(d=100, iters=3)
    assert r == 6 and fl == 600
    # DiSCO-F PCG iteration: 1 reduceAll n-vector + 2 scalar reduceAlls
    r, fl, spmd = comm.disco_f_pcg_cost(n=50, iters=3)
    assert r == 3 and fl == 3 * (50 + 2)


_MASK_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    assert len(jax.devices()) == 4
    from repro.launch.mesh import make_mesh
    from repro.core.disco import _shard_subsample_mask
    from jax import shard_map

    mesh = make_mesh((4,), ("data",))

    def body(key):
        m = _shard_subsample_mask(key, 0.5, (64,), "data")
        return m.astype(jnp.float32)

    fn = jax.jit(shard_map(body, mesh=mesh, in_specs=(P(),),
                           out_specs=P("data"), check_vma=False))
    masks = np.asarray(fn(jax.random.PRNGKey(0))).reshape(4, 64)
    # regression (was: every shard drew the same mask): shards must differ
    for i in range(4):
        for j in range(i + 1, 4):
            assert not np.array_equal(masks[i], masks[j]), (i, j)
    # and each shard's draw is a plausible Bernoulli(0.5)
    assert 0.2 < masks.mean() < 0.8
    print("MASKS_DIFFER_PASS")
""")


@pytest.mark.slow
def test_hessian_subsample_masks_differ_per_shard():
    """Regression for the duplicated Bernoulli draw in the samples branch:
    the kept draw must fold the shard index into the key so shards drop
    *different* sample subsets."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _MASK_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "MASKS_DIFFER_PASS" in r.stdout


_SSTEP_4DEV_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import numpy as np
    import jax
    assert len(jax.devices()) == 4
    from repro.launch.mesh import make_mesh
    from repro.core import DiscoConfig, DiscoSolver
    from repro.data.synthetic import make_glm_data

    X, y, _ = make_glm_data(d=64, n=320, seed=0)
    kw = dict(loss="logistic", lam=1e-3, tau=64, max_outer=6, grad_tol=0.0)
    for partition, axis in (("features", "model"), ("samples", "data")):
        mesh4 = make_mesh((4,), (axis,))
        r1 = DiscoSolver(X, y, DiscoConfig(partition=partition, **kw),
                         mesh=mesh4).fit()
        rs = DiscoSolver(X, y, DiscoConfig(partition=partition,
                                           pcg_block_s=4, **kw),
                         mesh=mesh4).fit()
        # the 4-shard basis operator is approximate -> compare the Newton
        # trajectory endpoint, not the PCG path
        np.testing.assert_allclose(rs.w, r1.w, atol=5e-4, rtol=1e-3)
        if partition == "features":
            # block-diagonal basis operator carries real curvature: fewer
            # rounds even with the approximate 4-shard basis
            assert rs.ledger.rounds < r1.ledger.rounds, \
                (partition, r1.ledger.rounds, rs.ledger.rounds)
        else:
            # DiSCO-S + Woodbury: the tau-sample basis operator adds little
            # beyond the preconditioner, so s-step degrades gracefully to
            # ~locally-optimal CG — never meaningfully worse (DESIGN.md §2.5)
            assert rs.ledger.rounds <= 1.2 * r1.ledger.rounds, \
                (partition, r1.ledger.rounds, rs.ledger.rounds)
        print(partition, "OK", r1.ledger.rounds, rs.ledger.rounds)
    print("SSTEP_4DEV_PASS")
""")


@pytest.mark.slow
def test_sstep_4device_matches_classic():
    """s-step PCG on a real 4-shard mesh (approximate zero-comm basis
    operators) still reaches the classic trajectory's solution with fewer
    ledger rounds."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _SSTEP_4DEV_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=540)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "SSTEP_4DEV_PASS" in r.stdout


def test_pallas_kernel_path_matches_jnp(glm_data):
    """DiSCO with the Pallas glm_hvp kernel in the PCG hot path produces
    the same trajectory as the jnp path (interpret mode on CPU)."""
    X, y, _ = glm_data
    kw = dict(loss="logistic", lam=1e-3, tau=16, max_outer=6, grad_tol=0.0)
    for part in ("features", "samples"):
        a = disco_fit(X, y, DiscoConfig(partition=part, **kw))
        b = disco_fit(X, y, DiscoConfig(partition=part, use_kernel=True,
                                        **kw))
        np.testing.assert_allclose(a.w, b.w, atol=2e-5, rtol=1e-4)
        np.testing.assert_allclose(a.grad_norms[:4], b.grad_norms[:4],
                                   rtol=1e-3)
