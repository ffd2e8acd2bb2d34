"""Plain distributed gradient descent — sanity baseline.

One d-vector reduceAll per iteration; fixed 1/L step from a power-iteration
estimate of the top Hessian eigenvalue.
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
class GDConfig:
    loss: str = "logistic"
    lam: float = 1e-4
    max_outer: int = 500
    grad_tol: float = 1e-8
    step: float | None = None  # default: 1/L estimated by power iteration


def gd_fit(X, y, cfg: GDConfig | None = None, mesh: Mesh | None = None):
    cfg = cfg or GDConfig()
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
    Xs = jax.device_put(jnp.asarray(Xp), NamedSharding(mesh, P(None, "data")))
    ys = jax.device_put(jnp.asarray(yp), NamedSharding(mesh, P("data")))
    ws_w = jax.device_put(jnp.asarray(wts), NamedSharding(mesh, P("data")))

    if cfg.step is None:
        # L <= c_max/n * lambda_max(X X^T) + lam ; c_max <= 2 for our losses
        v = np.random.default_rng(0).standard_normal(d).astype(X.dtype)
        for _ in range(20):
            v = X @ (X.T @ v)
            v /= np.linalg.norm(v)
        lmax = float(v @ (X @ (X.T @ v)))
        step = 1.0 / (2.0 * lmax / n + cfg.lam)
    else:
        step = cfg.step

    def step_local(X_loc, y_loc, wts_loc, w):
        a = X_loc.T @ w
        g = lax.psum(X_loc @ (loss.d1(a, y_loc) * wts_loc), "data") / n \
            + cfg.lam * w
        gnorm = jnp.sqrt(jnp.vdot(g, g))
        fval = lax.psum(jnp.sum(loss.value(a, y_loc) * wts_loc), "data") / n \
            + 0.5 * cfg.lam * jnp.vdot(w, w)
        return w - step * g, dict(grad_norm=gnorm, f=fval)

    fn = jax.jit(jax.shard_map(
        step_local, mesh=mesh,
        in_specs=(P(None, "data"), P("data"), P("data"), P()),
        out_specs=(P(), P())))

    w = jnp.zeros(d, Xs.dtype)
    history: list[dict[str, Any]] = []
    ledger = comm.CommLedger()
    for k in range(cfg.max_outer):
        w, stats = fn(Xs, ys, ws_w, w)
        stats = {s: float(v) for s, v in stats.items()}
        ledger.add(1, d, 1)
        stats.update(outer_iter=k, comm_rounds_cum=ledger.rounds)
        history.append(stats)
        if stats["grad_norm"] <= cfg.grad_tol:
            break
    return np.asarray(w), history, ledger
