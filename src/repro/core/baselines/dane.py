"""DANE baseline (Shamir, Srebro & Zhang 2013) — paper eq. (1).

Each iteration:
  round 1: reduceAll gradient  g = (1/m) sum_j grad f_j(w_k)
  local   : w_j = argmin_w f_j(w) - (grad f_j(w_k) - eta g)^T w
                                 + (mu/2)||w - w_k||^2
  round 2: reduceAll average   w_{k+1} = (1/m) sum_j w_j

The local subproblem is solved with a few damped-Newton-CG iterations on the
node's own samples (exact enough that DANE's behaviour — fast early progress,
stalling on ill-conditioned problems — is reproduced faithfully).
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
class DaneConfig:
    loss: str = "logistic"
    lam: float = 1e-4
    mu: float = 1e-2
    eta: float = 1.0
    max_outer: int = 50
    local_newton_iters: int = 8
    local_cg_iters: int = 32
    grad_tol: float = 1e-8


def _local_cg(hvp, b, iters):
    """Plain CG for the local Newton system (no communication)."""
    x = jnp.zeros_like(b)
    r = b
    p = r
    rs = jnp.vdot(r, r)

    def body(_, carry):
        x, r, p, rs = carry
        Hp = hvp(p)
        alpha = rs / jnp.maximum(jnp.vdot(p, Hp), 1e-30)
        x = x + alpha * p
        r = r - alpha * Hp
        rs_new = jnp.vdot(r, r)
        p = r + (rs_new / jnp.maximum(rs, 1e-30)) * p
        return x, r, p, rs_new

    x, *_ = lax.fori_loop(0, iters, body, (x, r, p, rs))
    return x


def dane_fit(X, y, cfg: DaneConfig | None = None, mesh: Mesh | None = None,
             w0: np.ndarray | None = None):
    """Returns (w, history, ledger). X is (d, n), sharded by samples."""
    cfg = cfg or DaneConfig()
    loss = get_loss(cfg.loss)
    X = np.asarray(X)
    y = np.asarray(y)
    d, n = X.shape
    mesh = mesh if mesh is not None else make_mesh((jax.device_count(),),
                                                   ("data",))
    m = mesh.shape["data"]

    Xp, npad = pad_to_multiple(X, 1, m)
    yp, _ = pad_to_multiple(y, 0, m)
    wts = np.pad(np.ones(n, X.dtype), (0, npad))
    xs = NamedSharding(mesh, P(None, "data"))
    ss = NamedSharding(mesh, P("data"))
    Xs = jax.device_put(jnp.asarray(Xp), xs)
    ys = jax.device_put(jnp.asarray(yp), ss)
    ws = jax.device_put(jnp.asarray(wts), ss)

    n_loc_eff = n / m  # effective local sample count (uniform partition)

    def step_local(X_loc, y_loc, wts_loc, w):
        def local_grad(wv):
            a = X_loc.T @ wv
            return X_loc @ (loss.d1(a, y_loc) * wts_loc) / n_loc_eff + cfg.lam * wv

        def local_hvp_at(wv):
            a = X_loc.T @ wv
            c = loss.d2(a, y_loc) * wts_loc
            def hvp(u):
                return (X_loc @ (c * (X_loc.T @ u)) / n_loc_eff
                        + (cfg.lam + cfg.mu) * u)
            return hvp

        gj = local_grad(w)
        g = lax.pmean(gj, "data")                       # round 1 (reduceAll d)
        gnorm = jnp.sqrt(jnp.vdot(g, g))
        a_vec = gj - cfg.eta * g

        # local damped Newton on h(v) = f_j(v) - a^T v + mu/2 ||v - w||^2
        def newton_body(_, v):
            grad_h = local_grad(v) - a_vec + cfg.mu * (v - w)
            step = _local_cg(local_hvp_at(v), grad_h, cfg.local_cg_iters)
            return v - step

        w_var = lax.pcast(w, "data", to="varying")  # carry becomes shard-local
        wj = lax.fori_loop(0, cfg.local_newton_iters, newton_body, w_var)
        w_new = lax.pmean(wj, "data")                   # round 2 (reduceAll d)

        a_full = X_loc.T @ w
        fval = lax.psum(jnp.sum(loss.value(a_full, y_loc) * wts_loc), "data") / n \
            + 0.5 * cfg.lam * jnp.vdot(w, w)
        return w_new, dict(grad_norm=gnorm, f=fval)

    fn = jax.jit(jax.shard_map(
        step_local, mesh=mesh,
        in_specs=(P(None, "data"), P("data"), P("data"), P()),
        out_specs=(P(), P())))

    w = jnp.zeros(d, Xs.dtype) if w0 is None else jnp.asarray(w0)
    history: list[dict[str, Any]] = []
    ledger = comm.CommLedger()
    for k in range(cfg.max_outer):
        w, stats = fn(Xs, ys, ws, w)
        stats = {s: float(v) for s, v in stats.items()}
        ledger.add(*comm.dane_iter_cost(d))
        stats.update(outer_iter=k, comm_rounds_cum=ledger.rounds)
        history.append(stats)
        if stats["grad_norm"] <= cfg.grad_tol:
            break
    return np.asarray(w), history, ledger
