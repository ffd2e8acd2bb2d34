"""Plain references for ``correct``, independent of the program.

Nothing here imports ``repro``. The solver cells are judged by the
gradient of the L2-regularised logistic objective

    f(w) = (1/n) sum_i log(1 + exp(-y_i x_i^T w)) + (lam/2) ||w||^2

computed in float64 on the host at the ``w`` a timed solve returned,
relative to the gradient at ``w = 0``. The scoring cells are judged by
float64 margins ``<x, w>`` of each completed request. ``newton`` is a
plain damped Newton-CG solve: in float64 it is the reference the tests
compare the solver with; in bfloat16 on the device it is the control,
the lower-precision solve that a correct comparison has to fail.
"""
from __future__ import annotations

import numpy as np


class DenseOps:
    """``X^T w`` and ``X v`` of a dense ``(d, n)`` matrix, float64,
    in column blocks so that no float64 copy of ``X`` is made."""

    def __init__(self, X: np.ndarray, block: int = 25_000):
        self.X, self.block = X, block
        self.d, self.n = X.shape

    def _blocks(self):
        for lo in range(0, self.n, self.block):
            yield lo, self.X[:, lo:lo + self.block].astype(np.float64)

    def xt(self, w):
        w = np.asarray(w, np.float64)
        return np.concatenate([w @ b for _, b in self._blocks()])

    def x(self, v):
        v = np.asarray(v, np.float64)
        return sum(b @ v[lo:lo + b.shape[1]] for lo, b in self._blocks())

    def x_phi(self, w, phi):
        """``X phi(X^T w, slice)`` in one pass: each block's margins and
        its share of the product come from one float64 copy of it."""
        w = np.asarray(w, np.float64)
        return sum(b @ phi(w @ b, slice(lo, lo + b.shape[1]))
                   for lo, b in self._blocks())


class CooOps:
    """The same products for a sparse matrix given as COO triplets."""

    def __init__(self, feat, samp, vals, d: int, n: int):
        self.feat, self.samp = feat, samp
        self.vals = np.asarray(vals, np.float64)
        self.d, self.n = d, n

    def xt(self, w):
        return np.bincount(self.samp, self.vals * np.asarray(w)[self.feat],
                           minlength=self.n)

    def x(self, v):
        return np.bincount(self.feat, self.vals * np.asarray(v)[self.samp],
                           minlength=self.d)

    def x_phi(self, w, phi):
        return self.x(phi(self.xt(w), slice(None)))


def _sigmoid(z):
    return 0.5 * (1.0 + np.tanh(0.5 * z))


def logistic_grad(ops, y, w, lam: float) -> np.ndarray:
    """Float64 gradient of f at ``w``."""
    y = np.asarray(y, np.float64)
    w = np.asarray(w, np.float64)

    def d1(a, rows):
        return -y[rows] * _sigmoid(-y[rows] * a)
    return ops.x_phi(w, d1) / ops.n + lam * w


def grad_rel(ops, y, ws, lam: float) -> list[float]:
    """``||grad f(w)|| / ||grad f(0)||`` in float64, for each ``w``; the
    gradient at 0 is ``-X y / 2n``."""
    g0 = np.linalg.norm(ops.x(-0.5 * np.asarray(y, np.float64))) / ops.n
    return [float(np.linalg.norm(logistic_grad(ops, y, w, lam)) / g0)
            for w in ws]


def margins(reqs, w) -> tuple[np.ndarray, np.ndarray]:
    """Float64 margins of ``(ids, values)`` requests, and the float32
    rounding scale ``sum |x_i w_i|`` of each."""
    w = np.asarray(w, np.float64)
    out = np.empty(len(reqs))
    scale = np.empty(len(reqs))
    for i, (ids, vals) in enumerate(reqs):
        t = np.asarray(vals, np.float64) * w[ids]
        out[i], scale[i] = t.sum(), np.abs(t).sum()
    return out, scale


def newton(xt, x, y, n: int, lam: float, tol: float, max_outer: int,
           xp, cg_rel: float = 1e-3, max_cg: int = 500):
    """Damped Newton-CG on f with ``w_{k+1} = w_k - v / (1 + delta)``,
    ``delta = sqrt(v^T H v)``, stopping when ``||grad|| <= tol``.

    ``xt`` / ``x`` are ``X^T w`` and ``X v`` in the working precision,
    ``xp`` is ``numpy`` or ``jax.numpy``, ``y`` an ``xp`` array of +-1.
    Returns ``(w, gradient norms)``.
    """
    w = xp.zeros(x(xp.zeros_like(y)).shape, y.dtype)
    norms = []
    for _ in range(max_outer):
        a = xt(w)
        s = 0.5 * (1.0 + xp.tanh(0.5 * (-y * a)))
        g = x(-y * s) / n + lam * w
        gn = float(xp.linalg.norm(g))
        norms.append(gn)
        if gn <= tol:
            break
        c = s * (1.0 - s)

        def hvp(u):
            return x(c * xt(u)) / n + lam * u

        v = xp.zeros_like(g)
        r = g
        p = r
        rr = float(xp.vdot(r, r))
        for _ in range(max_cg):
            if rr ** 0.5 <= cg_rel * gn:
                break
            hp = hvp(p)
            alpha = rr / float(xp.vdot(p, hp))
            v = v + alpha * p
            r = r - alpha * hp
            rr_new = float(xp.vdot(r, r))
            p = r + (rr_new / rr) * p
            rr = rr_new
        delta = float(xp.vdot(v, hvp(v))) ** 0.5
        w = w - v / (1.0 + delta)
    return w, norms
