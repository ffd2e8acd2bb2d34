"""Multinomial softmax regression on the DiSCO skeleton.

The K-class extension of problem (P): weights ``W in R^{d x K}``, margins
``A = X^T W``, class probabilities ``P = softmax(A)`` and the cross-entropy
objective

    f(W) = -(1/n) sum_i log P[i, y_i] + (lam/2) ||W||_F^2.

Gradient and Hessian products stay GLM-shaped — ``grad = X (P - Y1)/n +
lam W`` and ``H U = X S / n + lam U`` with the class coupling ``S`` of
:class:`repro.core.hvp.SoftmaxHvpOperator` — so the whole distributed
machinery of :mod:`repro.core.disco` carries over: both partitionings,
the damped Newton outer loop, classic and s-step PCG. The payoff of the
multi-vector kernels: every Hessian application moves all K classes in a
single ``xt_multi``/``x_cz_multi`` (or ``ell_matmat``) pass, and one
s-step round batches all ``K * (s+1)`` basis columns into ONE kernel
pass — K-class curvature for the X traffic of a binary solve.

Softmax cells never fuse (the coupling sits between the passes) and the
streamed layout is not implemented; both are registry-unsupported cells
that raise :class:`repro.core.hvp.UnsupportedHvpError` at setup.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.hvp import (SoftmaxHvpOperator, make_local_operator,
                            validate_solver_cell)
from repro.core.pcg import (PCGResult, _krylov_columns, _mgs, _pcg_loop,
                            _sstep_loop)
from repro.data.sparse import hvp_tile_dtype, with_hvp_dtype
from repro.launch.mesh import make_mesh
from repro.utils.padding import pad_to_multiple


@dataclasses.dataclass(frozen=True)
class SoftmaxConfig:
    """Hyperparameters of one multinomial softmax solve.

    Mirrors :class:`repro.core.disco.DiscoConfig` where the fields mean
    the same thing; ``n_classes=0`` infers K from the labels. The
    preconditioner is the identity (plain CG) — the Woodbury closed form
    does not extend to the (dK x dK) coupled system.
    """

    n_classes: int = 0              # 0 = infer from labels
    lam: float = 1e-4
    partition: str = "samples"      # 'samples' (DiSCO-S) | 'features'
    max_outer: int = 30
    max_pcg: int = 200
    pcg_rel_tol: float = 0.05
    grad_tol: float = 1e-8
    pcg_block_s: int = 1            # s-step PCG rounds (DESIGN.md §2)
    tau: int = 100                  # s-step basis-estimate sample count
    use_kernel: bool = False        # Pallas multi-vector passes
    hvp_fused: bool = False         # always unsupported for softmax —
    #                                 kept so the registry can *name* the
    #                                 cell instead of silently ignoring it
    hvp_dtype: str = "float32"      # HVP tile storage: float32 | bfloat16


@dataclasses.dataclass
class SoftmaxResult:
    """Outcome of :meth:`SoftmaxSolver.fit`: ``W`` is (d, K) in original
    feature order; ``history`` carries per-outer-iteration stats like
    :class:`repro.core.disco.DiscoResult`."""

    W: np.ndarray
    history: list[dict[str, Any]]
    converged: bool

    @property
    def grad_norms(self) -> np.ndarray:
        """(outer_iters,) gradient norms, one per outer iteration."""
        return np.array([h["grad_norm"] for h in self.history])


class SoftmaxProblem:
    """Single-array softmax oracle (the K-class twin of
    :class:`repro.core.glm.GLMProblem`) — value/grad/HVP on one logical
    ``(d, n)`` matrix, used by tests and single-device callers."""

    def __init__(self, X, y, n_classes: int = 0, lam: float = 1e-4):
        self.X = jnp.asarray(X)
        y = np.asarray(y).astype(np.int32)
        K = int(n_classes) or int(y.max()) + 1
        self.n_classes = K
        self.Y1 = jnp.asarray(np.eye(K, dtype=np.float32)[y])
        self.lam = float(lam)
        self.d, self.n = self.X.shape

    def probs(self, W):
        """Row-stochastic class probabilities ``softmax(X^T W)``."""
        return jax.nn.softmax(self.X.T @ W, axis=-1)

    def value(self, W):
        """Regularized mean cross-entropy at ``W``."""
        A = self.X.T @ W
        ce = -jnp.sum(self.Y1 * jax.nn.log_softmax(A, axis=-1), axis=-1)
        return jnp.mean(ce) + 0.5 * self.lam * jnp.vdot(W, W)

    def grad(self, W):
        """Gradient ``X (P - Y1) / n + lam W`` (a (d, K) array)."""
        return self.X @ (self.probs(W) - self.Y1) / self.n \
            + self.lam * W

    def hvp(self, W, U):
        """K-class Hessian product ``H U`` via the class coupling (one
        multi-vector pass per direction)."""
        op = SoftmaxHvpOperator(make_local_operator(self.X, None),
                                self.probs(W))
        return op.apply(U) / self.n + self.lam * U

    def hessian(self, W):
        """Dense (dK, dK) Hessian — tests / tiny problems only."""
        P_ = self.probs(W)
        d, K = self.d, self.n_classes
        H = jnp.zeros((d * K, d * K))
        eye = jnp.eye(d * K)
        for j in range(d * K):
            col = self.hvp(W, eye[:, j].reshape(d, K))
            H = H.at[:, j].set(col.reshape(-1))
        del P_
        return H


class SoftmaxSolver:
    """Distributed damped-Newton multinomial softmax (dense data).

    Same outer loop and both partitionings as
    :class:`repro.core.disco.DiscoSolver`; every Hessian product is one
    multi-vector HVP through :class:`repro.core.hvp.SoftmaxHvpOperator`.

    Args:
        X: (d, n) dense feature-major data.
        y: (n,) integer class labels in ``[0, K)``.
        cfg: solver hyperparameters.
        mesh: optional 1-axis mesh (``data`` for samples partition,
            ``model`` for features); defaults to all local devices.
    """

    def __init__(self, X, y, cfg: SoftmaxConfig,
                 mesh: Mesh | None = None):
        X = np.asarray(X)
        y = np.asarray(y).astype(np.int32)
        assert X.ndim == 2 and y.shape == (X.shape[1],), \
            "X must be (d, n), y (n,) int labels"
        self.cfg = cfg
        validate_solver_cell(family="softmax", partition=cfg.partition,
                             fused=cfg.hvp_fused, dtype=cfg.hvp_dtype,
                             use_kernel=cfg.use_kernel)
        self.d, self.n = X.shape
        self.K = int(cfg.n_classes) or int(y.max()) + 1
        self.tau = min(cfg.tau, self.n)

        axis = "model" if cfg.partition == "features" else "data"
        self.axis = axis
        self.mesh = mesh if mesh is not None else make_mesh(
            (jax.device_count(),), (axis,))
        self.m = self.mesh.shape[axis]

        Y1 = np.eye(self.K, dtype=X.dtype)[y]               # (n, K)
        X_tau = X[:, : self.tau].copy()
        Y1_tau = Y1[: self.tau].copy()
        rep = NamedSharding(self.mesh, P())

        if cfg.partition == "features":
            Xp, _ = pad_to_multiple(X, 0, self.m)
            self.d_padded = Xp.shape[0]
            self.X = jax.device_put(jnp.asarray(Xp),
                                    NamedSharding(self.mesh, P(axis, None)))
            self.Y1 = jax.device_put(jnp.asarray(Y1), rep)
            self.wts = None
            self._w_sharding = NamedSharding(self.mesh, P(axis, None))
        elif cfg.partition == "samples":
            Xp, npad = pad_to_multiple(X, 1, self.m)
            Y1p = np.pad(Y1, ((0, npad), (0, 0)))
            wts = np.pad(np.ones(self.n, X.dtype), (0, npad))
            self.d_padded = self.d
            self.n_padded = Xp.shape[1]
            self.X = jax.device_put(jnp.asarray(Xp),
                                    NamedSharding(self.mesh, P(None, axis)))
            self.Y1 = jax.device_put(jnp.asarray(Y1p),
                                     NamedSharding(self.mesh, P(axis, None)))
            self.wts = jax.device_put(jnp.asarray(wts),
                                      NamedSharding(self.mesh, P(axis)))
            self._w_sharding = rep
        else:
            raise ValueError(f"unknown partition {cfg.partition!r}")
        self.X_tau = jax.device_put(jnp.asarray(X_tau), rep)
        self.Y1_tau = jax.device_put(jnp.asarray(Y1_tau), rep)
        self.X_hvp = with_hvp_dtype(self.X, hvp_tile_dtype(cfg.hvp_dtype))
        self._step = self._build_step()

    # ------------------------------------------------------------------
    def _pcg(self, hvp_flat, basis_parts, psum_dot, g_flat, eps, dtype):
        """Classic or s-step PCG over the flattened (d*K,) system."""
        cfg = self.cfg
        if cfg.pcg_block_s <= 1:
            return _pcg_loop(hvp_flat, lambda r: r, psum_dot, g_flat,
                             eps, cfg.max_pcg, dtype)
        s = int(cfg.pcg_block_s)
        build_basis, hvp_round, gram, update_scales = basis_parts
        return _sstep_loop(build_basis, hvp_round, gram, update_scales,
                           psum_dot, g_flat, eps, cfg.max_pcg, s)

    def _build_step(self):
        cfg, axis, K = self.cfg, self.axis, self.K
        n, tau, m = self.n, self.tau, self.m
        lam = cfg.lam
        s = int(cfg.pcg_block_s)

        if cfg.partition == "samples":
            dp = self.d_padded

            def step_local(X_loc, Xh_loc, Y1_loc, wts_loc, X_tau, Y1_tau,
                           W):
                A_loc = X_loc.T @ W                          # (n_loc, K)
                P_loc = jax.nn.softmax(A_loc, axis=-1)
                ce = -jnp.sum(Y1_loc * jax.nn.log_softmax(A_loc, axis=-1),
                              axis=-1) * wts_loc
                fval = lax.psum(jnp.sum(ce), axis) / n \
                    + 0.5 * lam * jnp.vdot(W, W)
                G1 = (P_loc - Y1_loc) * wts_loc[:, None]
                G = lax.psum(X_loc @ G1, axis) / n + lam * W
                gnorm = jnp.sqrt(jnp.vdot(G, G))

                base = make_local_operator(Xh_loc, None,
                                           use_kernel=cfg.use_kernel,
                                           partition="samples")
                som = SoftmaxHvpOperator(base, P_loc, weights=wts_loc)

                def hvp_flat(u):
                    U = u.reshape(dp, K)
                    HU = lax.psum(som.apply(U), axis) / n + lam * U
                    return HU.reshape(-1)

                psum_dot = lambda a, b: jnp.vdot(a, b)   # replicated

                # s-step wiring (DiSCO-S flavor: MGS basis, all s+1
                # columns through ONE batched K*(s+1)-wide kernel pass)
                if m == 1:
                    basis_flat = hvp_flat     # exact single-shard operator
                else:
                    A_tau = X_tau.T @ W
                    P_tau = jax.nn.softmax(A_tau, axis=-1)
                    som_tau = SoftmaxHvpOperator(
                        make_local_operator(X_tau, None), P_tau)
                    tau_f = jnp.asarray(tau, X_tau.dtype)

                    def basis_flat(u):
                        U = u.reshape(dp, K)
                        HU = som_tau.apply(U) / tau_f + lam * U
                        return HU.reshape(-1)

                def build_basis(r, p, scales):
                    del scales
                    cols = _krylov_columns(r, lambda x: x, basis_flat, s,
                                           jnp.ones((max(s - 1, 1),),
                                                    r.dtype))
                    cols.append(p)
                    return jnp.stack(_mgs(cols), axis=1)

                def hvp_round(U, Hp):
                    del Hp
                    U3 = U.reshape(dp, K, U.shape[1])
                    W3 = lax.psum(som.apply_batch(U3), axis) / n \
                        + lam * U3
                    return W3.reshape(dp * K, U.shape[1])

                def gram(U, Wm, r):
                    return U.T @ Wm, U.T @ U, U.T @ r

                res = self._pcg(
                    hvp_flat,
                    (build_basis, hvp_round, gram,
                     lambda scales, B: scales),
                    psum_dot, G.reshape(-1), cfg.pcg_rel_tol * gnorm,
                    X_loc.dtype)
                V = res.v.reshape(dp, K)
                W_new = W - V / (1.0 + res.delta)
                stats = dict(grad_norm=gnorm, f=fval,
                             pcg_iters=res.iters, delta=res.delta,
                             pcg_r_norm=res.r_norm)
                return W_new, stats

            fn = jax.jit(jax.shard_map(
                step_local, mesh=self.mesh,
                in_specs=(P(None, axis), P(None, axis), P(axis, None),
                          P(axis), P(), P(), P()),
                out_specs=(P(), P()),
                check_vma=False))

            def step(W):
                return fn(self.X, self.X_hvp, self.Y1, self.wts,
                          self.X_tau, self.Y1_tau, W)

        else:  # features
            dl = self.d_padded // m

            def step_local(X_loc, Xh_loc, Y1, W_loc):
                A = lax.psum(X_loc.T @ W_loc, axis)          # (n, K)
                Pm = jax.nn.softmax(A, axis=-1)
                ce = -jnp.sum(Y1 * jax.nn.log_softmax(A, axis=-1),
                              axis=-1)
                fval = jnp.sum(ce) / n + 0.5 * lam * lax.psum(
                    jnp.vdot(W_loc, W_loc), axis)
                G_loc = X_loc @ (Pm - Y1) / n + lam * W_loc
                gnorm = jnp.sqrt(lax.psum(jnp.vdot(G_loc, G_loc), axis))

                base = make_local_operator(Xh_loc, None,
                                           use_kernel=cfg.use_kernel,
                                           partition="features")
                som = SoftmaxHvpOperator(base, Pm)

                def hvp_flat(u):
                    # THE DiSCO-F communication, K columns wide: one
                    # (n, K) psum between pass A and pass B.
                    U = u.reshape(dl, K)
                    V = lax.psum(base.pass_a_multi(U), axis)
                    HU = base.pass_b_multi(som.coupling(V)) / n + lam * U
                    return HU.reshape(-1)

                psum_dot = lambda a, b: lax.psum(jnp.vdot(a, b), axis)

                def basis_flat(u):
                    # zero-communication block-diagonal local operator
                    U = u.reshape(dl, K)
                    HU = som.apply(U) / n + lam * U
                    return HU.reshape(-1)

                def build_basis(r, p, scales):
                    cols = _krylov_columns(r, lambda x: x, basis_flat, s,
                                           scales)
                    cols.append(p)
                    return jnp.stack(cols, axis=1)

                def hvp_round(U, Hp):
                    Uk = U[:, :s]
                    U3 = Uk.reshape(dl, K, s)
                    V = lax.psum(base.pass_a_multi(
                        U3.reshape(dl, K * s)), axis)
                    nn = V.shape[0]
                    S = som.coupling(V.reshape(nn, K, s))
                    W3 = base.pass_b_multi(
                        S.reshape(nn, K * s)).reshape(dl, K, s) / n \
                        + lam * U3
                    Wk = W3.reshape(dl * K, s)
                    return jnp.concatenate([Wk, Hp[:, None]], axis=1)

                def gram(U, Wm, r):
                    k = U.shape[1]
                    payload = jnp.concatenate(
                        [(U.T @ Wm).ravel(), (U.T @ U).ravel(), U.T @ r])
                    payload = lax.psum(payload, axis)
                    return (payload[: k * k].reshape(k, k),
                            payload[k * k: 2 * k * k].reshape(k, k),
                            payload[2 * k * k:])

                from repro.core.pcg import _feature_scales_update

                res = self._pcg(
                    hvp_flat,
                    (build_basis, hvp_round, gram,
                     lambda scales, B: _feature_scales_update(scales, B,
                                                              s)),
                    psum_dot, G_loc.reshape(-1),
                    cfg.pcg_rel_tol * gnorm, X_loc.dtype)
                V = res.v.reshape(dl, K)
                W_new = W_loc - V / (1.0 + res.delta)
                stats = dict(grad_norm=gnorm, f=fval,
                             pcg_iters=res.iters, delta=res.delta,
                             pcg_r_norm=res.r_norm)
                return W_new, stats

            fn = jax.jit(jax.shard_map(
                step_local, mesh=self.mesh,
                in_specs=(P(axis, None), P(axis, None), P(), P(axis, None)),
                out_specs=(P(axis, None), P()),
                check_vma=False))

            def step(W):
                return fn(self.X, self.X_hvp, self.Y1, W)

        # the device data enter the jitted program as arguments: an array
        # closed over by a jitted function is embedded as a constant
        return step

    # ------------------------------------------------------------------
    def fit(self, W0: np.ndarray | None = None) -> SoftmaxResult:
        """Damped Newton outer loop from ``W0`` (default zeros); ``W0``
        and the returned ``W`` are (d, K) in original feature order."""
        cfg = self.cfg
        dtype = self.X.dtype
        if W0 is None:
            W = jnp.zeros((self.d_padded, self.K), dtype)
        else:
            W0 = np.asarray(W0)
            W = jnp.asarray(np.pad(
                W0, ((0, self.d_padded - W0.shape[0]), (0, 0))
            ).astype(dtype))
        W = jax.device_put(W, self._w_sharding)

        history: list[dict[str, Any]] = []
        converged = False
        for k in range(cfg.max_outer):
            W, stats = self._step(W)
            stats = {s_: float(v) for s_, v in stats.items()}
            stats["outer_iter"] = k
            history.append(stats)
            if stats["grad_norm"] <= cfg.grad_tol:
                converged = True
                break
        return SoftmaxResult(W=np.asarray(W)[: self.d],
                             history=history, converged=converged)


def softmax_fit(X, y, cfg: SoftmaxConfig | None = None,
                mesh: Mesh | None = None,
                W0: np.ndarray | None = None) -> SoftmaxResult:
    """One-call convenience wrapper: build a :class:`SoftmaxSolver`, fit.

    Args:
        X: (d, n) dense feature-major data.
        y: (n,) integer class labels in ``[0, K)``.
        cfg: solver hyperparameters (defaults: :class:`SoftmaxConfig`).
        mesh: optional 1-axis mesh; defaults to all local devices.
        W0: optional (d, K) warm start.
    """
    cfg = cfg or SoftmaxConfig()
    return SoftmaxSolver(X, y, cfg, mesh=mesh).fit(W0)
