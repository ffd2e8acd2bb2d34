"""CoCoA+ baseline (Jaggi et al. 2014; Ma et al. 2015 "adding" variant).

Maximizes the dual (D) with local SDCA on each node's own dual block and a
single d-vector reduceAll per outer iteration:

    w(alpha) = (1/(lam n)) X alpha
    each node: H SDCA coordinate steps on its local alpha block against
               v = w + (sigma'/(lam n)) X_j dalpha_j   (sigma' = m, gamma = 1)
    round    : w += sum_j (1/(lam n)) X_j dalpha_j     (reduceAll d)

Closed-form coordinate step for quadratic loss; safeguarded scalar Newton for
logistic (its conjugate has no closed-form maximizer).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import comm
from repro.launch.mesh import make_mesh
from repro.utils.padding import pad_to_multiple
from repro.core.losses import get_loss


@dataclasses.dataclass(frozen=True)
class CocoaConfig:
    loss: str = "logistic"        # 'logistic' | 'quadratic'
    lam: float = 1e-4
    max_outer: int = 100
    local_steps: int | None = None  # H; default = local sample count
    grad_tol: float = 1e-8
    seed: int = 0


def cocoa_fit(X, y, cfg: CocoaConfig | None = None, mesh: Mesh | None = None):
    cfg = cfg or CocoaConfig()
    loss = get_loss(cfg.loss)
    X = np.asarray(X)
    y = np.asarray(y)
    d, n = X.shape
    mesh = mesh if mesh is not None else make_mesh((jax.device_count(),),
                                                   ("data",))
    m = mesh.shape["data"]
    sigma_p = float(m)  # safe aggregation parameter for gamma = 1 (adding)

    Xp, npad = pad_to_multiple(X, 1, m)
    yp, _ = pad_to_multiple(y, 0, m)
    wts = np.pad(np.ones(n, X.dtype), (0, npad))
    n_loc = Xp.shape[1] // m
    H = cfg.local_steps or n_loc

    Xs = jax.device_put(jnp.asarray(Xp), NamedSharding(mesh, P(None, "data")))
    ys = jax.device_put(jnp.asarray(yp), NamedSharding(mesh, P("data")))
    ws = jax.device_put(jnp.asarray(wts), NamedSharding(mesh, P("data")))
    col_sq = jnp.sum(Xp * Xp, axis=0)
    cs = jax.device_put(col_sq, NamedSharding(mesh, P("data")))

    lam_n = cfg.lam * n

    def sdca_delta_quadratic(alpha_i, yi, xv, qi):
        # phi(a) = (a - y)^2  =>  phi*(u) = u^2/4 + u y
        denom = 0.5 + sigma_p * qi / lam_n
        return (yi - xv - 0.5 * alpha_i) / denom

    def sdca_delta_logistic(alpha_i, yi, xv, qi):
        # Maximize over delta with b = (alpha+delta) y in (0,1). Stationarity
        #   G(b) = -y log(b/(1-b)) - xv - kappa (b y - alpha) = 0,
        # G is strictly monotone in b (sign of -y) -> bisection is exact.
        kappa = sigma_p * qi / lam_n
        eps = 1e-7

        def G(b):
            return (-yi * (jnp.log(b) - jnp.log1p(-b)) - xv
                    - kappa * (b * yi - alpha_i))

        def body(_, carry):
            lo, hi = carry
            mid = 0.5 * (lo + hi)
            root_right = (G(mid) > 0) == (yi > 0)
            lo = jnp.where(root_right, mid, lo)
            hi = jnp.where(root_right, hi, mid)
            return lo, hi

        lo = lax.pcast(jnp.asarray(eps, xv.dtype), "data", to="varying")
        hi = lax.pcast(jnp.asarray(1.0 - eps, xv.dtype), "data", to="varying")
        lo, hi = lax.fori_loop(0, 40, body, (lo, hi))
        b = 0.5 * (lo + hi)
        return b * yi - alpha_i

    delta_fn = (sdca_delta_quadratic if cfg.loss == "quadratic"
                else sdca_delta_logistic)

    def step_local(X_loc, y_loc, wts_loc, q_loc, alpha_loc, w, key):
        key = jax.random.fold_in(key, lax.axis_index("data"))
        idx = jax.random.randint(key, (H,), 0, n_loc)

        def body(t, carry):
            alpha, dxa = carry  # dxa = X_j dalpha_j accumulated (d,)
            i = idx[t]
            xi = X_loc[:, i]
            v_dot = jnp.vdot(xi, w + (sigma_p / lam_n) * dxa)
            delta = delta_fn(alpha[i], y_loc[i], v_dot, q_loc[i]) * wts_loc[i]
            alpha = alpha.at[i].add(delta)
            dxa = dxa + delta * xi
            return alpha, dxa

        dxa0 = lax.pcast(jnp.zeros_like(w), "data", to="varying")
        alpha_loc, dxa = lax.fori_loop(0, H, body, (alpha_loc, dxa0))
        dw = lax.psum(dxa, "data") / lam_n        # the ONE d-vector reduceAll
        w_new = w + dw

        a = X_loc.T @ w_new
        g = lax.psum(X_loc @ (loss.d1(a, y_loc) * wts_loc), "data") / n \
            + cfg.lam * w_new
        gnorm = jnp.sqrt(jnp.vdot(g, g))
        fval = lax.psum(jnp.sum(loss.value(a, y_loc) * wts_loc), "data") / n \
            + 0.5 * cfg.lam * jnp.vdot(w_new, w_new)
        return alpha_loc, w_new, dict(grad_norm=gnorm, f=fval)

    fn = jax.jit(jax.shard_map(
        step_local, mesh=mesh,
        in_specs=(P(None, "data"), P("data"), P("data"), P("data"),
                  P("data"), P(), P()),
        out_specs=(P("data"), P(), P())))

    # feasible dual start: alpha*y in (0,1) for logistic; 0 fine for quadratic.
    # w must start dual-consistent: w0 = X alpha0 / (lam n).
    if cfg.loss == "logistic":
        alpha0 = 0.5 * yp * wts
    else:
        alpha0 = np.zeros_like(yp)
    alpha = jax.device_put(jnp.asarray(alpha0),
                           NamedSharding(mesh, P("data")))
    w = jnp.asarray((Xp @ alpha0) / lam_n, Xs.dtype)
    key = jax.random.PRNGKey(cfg.seed)

    history: list[dict[str, Any]] = []
    ledger = comm.CommLedger()
    for k in range(cfg.max_outer):
        key, sub = jax.random.split(key)
        alpha, w, stats = fn(Xs, ys, ws, cs, alpha, w, sub)
        stats = {s: float(v) for s, v in stats.items()}
        ledger.add(*comm.cocoa_iter_cost(d))
        stats.update(outer_iter=k, comm_rounds_cum=ledger.rounds)
        history.append(stats)
        if stats["grad_norm"] <= cfg.grad_tol:
            break
    return np.asarray(w), history, ledger
