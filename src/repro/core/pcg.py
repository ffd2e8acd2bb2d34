"""Distributed Preconditioned Conjugate Gradient — paper Algorithms 2 and 3.

Both variants solve the Newton system  H v = g,  H = f''(w_k)  inexactly to
``||r|| <= eps`` and return (v, delta, iters) with delta = sqrt(v^T H v) for
the damped step of Algorithm 1.

* ``pcg_samples``  (Algorithm 2, DiSCO-S): data sharded by **samples** along
  the ``data`` mesh axis. PCG state vectors are replicated R^d; each H u
  costs one d-vector all-reduce (the paper's broadcast-u + reduceAll-Hu pair).
  The preconditioner uses tau samples held replicated (the paper's "first tau
  samples of the master", broadcast once).

* ``pcg_features`` (Algorithm 3, DiSCO-F): data sharded by **features** along
  the ``model`` mesh axis. Every PCG vector lives sharded as R^{d_j}; each
  H u costs one n-vector all-reduce plus two scalar all-reduces, and the
  Woodbury preconditioner is block-diagonal and fully local.

s-step (communication-avoiding) mode — ``block_s > 1`` (DESIGN.md §2):

Classic PCG pays its collectives once per Krylov dimension. The s-step
engine instead advances ``s`` dimensions per *round*: it builds an
(s+1)-column trial basis  U = [basis(K_s(M^{-1} H~, M^{-1} r)), p_prev]
from a **zero-communication basis operator** H~ (the exact local Hessian
block for DiSCO-F, the replicated tau-sample Hessian estimate for DiSCO-S;
both equal the true H on a single shard), applies the *true* H to all
columns with ONE batched multi-vector HVP (kernels/glm_hvp.py multi-vector
passes — one collective carrying an s+1-wide payload), assembles the small
Gram system with one fused psum payload, and takes the exact Galerkin step
over span(U) by solving the (s+1)x(s+1) system locally on every shard.

Because the Galerkin step uses the true H (residual update r <- r - (H U) a
is exact), every round is a monotone H-norm error reduction regardless of
basis quality; with the exact basis operator and the carried previous-round
direction p_prev, one round reproduces s classic PCG iterations. A
conditioning guard (whitened Gram solve + hard fallback) degrades to the
classic s=1 step when the monomial basis collapses.

These functions are written to run **inside shard_map** — all cross-device
traffic is explicit ``lax.psum``. Single-device meshes degenerate gracefully
(psum over an axis of size 1).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.hvp import make_local_operator
from repro.core.preconditioner import WoodburyPreconditioner, sag_solve
from repro.data.sparse import EllPair
from repro.obs import tracer as obs


class PCGResult(NamedTuple):
    v: jnp.ndarray        # inexact Newton direction (local shard in DiSCO-F)
    delta: jnp.ndarray    # sqrt(v^T H v)  (scalar, replicated)
    iters: jnp.ndarray    # PCG iterations (classic) or rounds (s-step)
    r_norm: jnp.ndarray   # final residual norm


def _pcg_loop(hvp, apply_precond, psum_dot, g, eps, max_iter, dtype):
    """Shared PCG skeleton.

    hvp(u) -> H u            (performs its own collectives)
    apply_precond(r) -> s    (local / replicated, zero comm by construction)
    psum_dot(a, b) -> scalar <a, b> globally (psum for sharded vectors,
                      plain vdot for replicated ones)
    """
    v0 = jnp.zeros_like(g)
    r0 = g
    s0 = apply_precond(r0)
    u0 = s0
    Hv0 = jnp.zeros_like(g)
    rs0 = psum_dot(r0, s0)

    def cond(state):
        t, _, r, _, _, _, _ = state
        rn = jnp.sqrt(psum_dot(r, r))
        return jnp.logical_and(t < max_iter, rn > eps)

    def body(state):
        t, v, r, s, u, Hv, rs = state
        Hu = hvp(u)
        alpha = rs / psum_dot(u, Hu)
        v = v + alpha * u
        Hv = Hv + alpha * Hu
        r_new = r - alpha * Hu
        s_new = apply_precond(r_new)
        rs_new = psum_dot(r_new, s_new)
        beta = rs_new / rs
        u_new = s_new + beta * u
        return (t + 1, v, r_new, s_new, u_new, Hv, rs_new)

    state = (jnp.zeros((), jnp.int32), v0, r0, s0, u0, Hv0, rs0)
    t, v, r, s, u, Hv, rs = lax.while_loop(cond, body, state)
    delta = jnp.sqrt(jnp.maximum(psum_dot(v, Hv), 0.0))
    r_norm = jnp.sqrt(psum_dot(r, r))
    return PCGResult(v=v, delta=delta, iters=t, r_norm=r_norm)


# ---------------------------------------------------------------------------
# s-step engine (communication-avoiding PCG)
# ---------------------------------------------------------------------------

def _solve_round(G, B, b, s, kappa_max=1e10):
    """Galerkin coefficients over the trial basis:  a ~= G^+ b.

    The solve is *whitened* with the basis Gram matrix B = U^T U — the
    algebraic equivalent of CholeskyQR-orthonormalizing U without ever
    communicating the orthonormal basis: eigendirections of B below a
    relative floor (degenerate/parallel basis columns, e.g. the zero
    p_prev on round one) are dropped, the rest are scaled to unit length,
    and the projected Hessian is (pseudo-)inverted on the retained,
    well-conditioned subspace. This is the graduated part of the
    monomial-basis conditioning guard.

    The hard fallback: if the monomial block B[:s,:s] is beyond salvage
    (cond > kappa_max) or the whitened solve produced non-finite values,
    fall back to the s=1 step over {q_1 = M^{-1} r, p_prev} — the
    locally-optimal two-term Galerkin step that is exactly one classic
    preconditioned CG iteration (steepest descent + conjugate momentum).
    """
    dtype = G.dtype
    tiny = jnp.asarray(1e-30, dtype)
    G = 0.5 * (G + G.T)
    B = 0.5 * (B + B.T)

    beig, Vb = jnp.linalg.eigh(B)
    bmax = jnp.maximum(jnp.max(jnp.abs(beig)), tiny)
    keep = beig > 5e-8 * bmax
    inv_sqrt = jnp.where(keep, lax.rsqrt(jnp.where(keep, beig, 1.0)), 0.0)
    T = Vb * inv_sqrt[None, :]                       # whitening transform

    Gt = T.T @ G @ T
    Gt = 0.5 * (Gt + Gt.T)
    geig, Vg = jnp.linalg.eigh(Gt)
    gmax = jnp.maximum(jnp.max(jnp.abs(geig)), tiny)
    gkeep = geig > 1e-6 * gmax
    ginv = jnp.where(gkeep, 1.0 / jnp.where(gkeep, geig, 1.0), 0.0)
    a = T @ (Vg @ (ginv * (Vg.T @ (T.T @ b))))

    beig_m = jnp.linalg.eigvalsh(B[:s, :s])          # monomial block only
    cond_m = jnp.max(beig_m) / jnp.maximum(jnp.min(beig_m), tiny)

    # 2x2 Galerkin over columns {q_1, p_prev} (indices 0 and s). Closed
    # form so overflowed middle-column Gram entries can't contaminate it;
    # degenerates to the pure q_1 step when p_prev = 0 (det = 0).
    g00, g01, g11 = G[0, 0], G[0, s], G[s, s]
    b0, b1 = b[0], b[s]
    det = g00 * g11 - g01 * g01
    safe_det = jnp.maximum(det, tiny)
    x0 = jnp.where(det > tiny * jnp.maximum(g00 * g11, tiny),
                   (g11 * b0 - g01 * b1) / safe_det,
                   b0 / jnp.maximum(g00, tiny))
    x1 = jnp.where(det > tiny * jnp.maximum(g00 * g11, tiny),
                   (g00 * b1 - g01 * b0) / safe_det, 0.0)
    a_fb = jnp.zeros_like(b).at[0].set(x0).at[s].set(x1)

    bad = jnp.logical_or(cond_m > kappa_max,
                         jnp.logical_not(jnp.all(jnp.isfinite(a))))
    return jnp.where(bad, a_fb, a)


def _sstep_loop(build_basis, hvp_round, gram, update_scales, psum_dot,
                g, eps, max_rounds, s):
    """Shared s-step round skeleton (both partitionings).

    build_basis(r, p_prev, scales) -> U (dim, s+1), zero communication
    hvp_round(U, Hp) -> H U  with the round's ONE batched-vector
                     collective. ``Hp = H p_prev`` is carried in the loop
                     state (it is last round's ``W a``): a variant whose
                     basis keeps the p_prev column verbatim (features) can
                     splice it in and batch only the s Krylov columns
    gram(U, W, r) -> (U^T W, U^T U, U^T r) globally (fused psum payload
                     for sharded vectors, plain local matmuls for
                     replicated ones)
    update_scales(scales, B) -> per-step basis scale estimates for the
                     next round (features); identity for samples (MGS
                     normalizes exactly for free)
    """
    v0 = jnp.zeros_like(g)
    r0 = g
    p0 = jnp.zeros_like(g)
    Hp0 = jnp.zeros_like(g)
    Hv0 = jnp.zeros_like(g)
    scales0 = jnp.ones((max(s - 1, 1),), g.dtype)

    def cond(state):
        t, _, r, _, _, _, _ = state
        rn = jnp.sqrt(psum_dot(r, r))
        return jnp.logical_and(t < max_rounds, rn > eps)

    def body(state):
        t, v, r, p, Hp, Hv, scales = state
        U = build_basis(r, p, scales)
        W = hvp_round(U, Hp)
        G, B, b = gram(U, W, r)
        a = _solve_round(G, B, b, s)
        dv = U @ a
        Hdv = W @ a
        return (t + 1, v + dv, r - Hdv, dv, Hdv, Hv + Hdv,
                update_scales(scales, B))

    state = (jnp.zeros((), jnp.int32), v0, r0, p0, Hp0, Hv0, scales0)
    t, v, r, p, Hp, Hv, _ = lax.while_loop(cond, body, state)
    delta = jnp.sqrt(jnp.maximum(psum_dot(v, Hv), 0.0))
    r_norm = jnp.sqrt(psum_dot(r, r))
    return PCGResult(v=v, delta=delta, iters=t, r_norm=r_norm)


def _krylov_columns(r, apply_precond, basis_op, s, scales):
    """[q_1, ..., q_s] with q_1 = M^{-1} r,  q_{i+1} = M^{-1} H~ q_i / scale_i.

    Monomial basis of the *preconditioned* zero-communication operator —
    spans K_s(M^{-1} H~, M^{-1} r), which with the exact basis operator is
    exactly the space s classic PCG iterations search.
    """
    cols = [apply_precond(r)]
    for i in range(s - 1):
        nxt = apply_precond(basis_op(cols[-1])) / scales[i]
        # f32 range guard: an overflowed column becomes a (poor but
        # harmless) trial direction instead of poisoning the Gram system —
        # the Galerkin step is exact for whatever columns U actually holds.
        cols.append(jnp.where(jnp.isfinite(nxt), nxt, 0.0))
    return cols


def _mgs(cols):
    """Modified Gram-Schmidt over a list of same-shape vectors.

    Only valid when the vectors are replicated (DiSCO-S): every dot is
    local, so the orthonormalization is communication-free. Columns that
    vanish under orthogonalization (exhausted Krylov space, zero p_prev)
    are returned as zeros and dropped later by the whitened Gram solve.
    """
    out = []
    for c in cols:
        w = c
        for o in out:
            w = w - jnp.vdot(o, w) * o
        nw = jnp.sqrt(jnp.vdot(w, w))
        out.append(jnp.where(nw > 1e-30, w / jnp.maximum(nw, 1e-30),
                             jnp.zeros_like(w)))
    return out


# ---------------------------------------------------------------------------
# preconditioner factories (shared by classic and s-step paths)
# ---------------------------------------------------------------------------

def _samples_precond(precond, X_tau, coeffs_tau, lam, mu, sag_epochs):
    if precond == "woodbury":
        P = WoodburyPreconditioner.build(X_tau, coeffs_tau, lam, mu)
        return P.apply_inv
    if precond == "sag":
        # original DiSCO: iterative inner solve, replicated on every device
        # (the master bottleneck, see DESIGN.md §2)
        return lambda r: sag_solve(X_tau, coeffs_tau, lam, mu, r,
                                   epochs=sag_epochs)
    if precond == "none":
        return lambda r: r
    raise ValueError(f"unknown precond {precond!r}")


def _features_precond(precond, X_loc, tau_idx, coeffs_tau, lam, mu,
                      X_tau_loc=None):
    if precond == "woodbury":
        # block-diagonal P^{[j]}: local feature rows of the tau samples,
        # zero communication (paper contribution 2). Sparse callers pass
        # the dense tau slab directly (tau ~ 100 columns; materialized
        # once per solve by DiscoSolver).
        if X_tau_loc is None:
            if isinstance(X_loc, EllPair):
                raise ValueError("sparse pcg_features needs the dense "
                                 "X_tau_loc slab for the Woodbury "
                                 "preconditioner (an EllPair cannot be "
                                 "column-sliced)")
            X_tau_loc = X_loc[:, tau_idx]
        P = WoodburyPreconditioner.build_blockdiag(X_tau_loc, coeffs_tau,
                                                   lam, mu)
        return P.apply_inv
    if precond == "none":
        return lambda r: r
    raise ValueError(f"unknown precond {precond!r}")


# ---------------------------------------------------------------------------
# Algorithm 2 — DiSCO-S (sample partitioning)
# ---------------------------------------------------------------------------

def pcg_samples(X_loc, coeffs_loc, n_global, lam, g, eps, max_iter,
                X_tau=None, coeffs_tau=None, mu=0.0, axis_name="data",
                precond="woodbury", sag_epochs=5, use_kernel=False,
                block_s=1, axis_size=None, hvp_fused=False):
    """Runs inside shard_map over ``axis_name``.

    X_loc       : (d, n_loc) local sample columns — f32, or the bf16
                  mixed-precision HVP copy (``DiscoConfig.hvp_dtype``;
                  all state vectors stay f32 either way)
    coeffs_loc  : (n_loc,) phi'' at w_k (already masked/scaled if the
                  Hessian is subsampled, paper §5.4)
    g           : (d,) replicated gradient
    X_tau       : (d, tau) replicated preconditioner samples ("master's"
                  first tau columns, broadcast once per outer iteration)
    precond     : 'woodbury' (DiSCO-S), 'sag' (original DiSCO), 'none' (CG)
    block_s     : >1 selects the s-step engine: ``block_s`` Krylov
                  dimensions per communication round (``iters`` then counts
                  rounds). ``max_iter`` caps rounds in that mode.
    axis_size   : static size of ``axis_name`` (pass 1 on a single-shard
                  mesh so the s-step basis operator is the exact Hessian)
    hvp_fused   : route every local HVP through the one-pass fused
                  kernels (docs/kernels.md): the sample-partitioned local
                  product X_loc (c .* X_loc^T u) completes both directions
                  before the psum, so the fused kernel applies to every
                  HVP here — X tiles stream from HBM once per application.
    """
    n_global = jnp.asarray(n_global, g.dtype)

    # ONE local (multi-)HVP operator per solve (core/hvp.py dispatches by
    # layout and validates the cell); every site below (classic hvp,
    # s-step basis operator, s-step round) frames it with its own
    # collective and scale. DiSCO-S products are local by construction
    # (the psum comes after), so ``hvp_fused`` swaps in the one-pass
    # kernels everywhere here.
    op = make_local_operator(X_loc, coeffs_loc, use_kernel=use_kernel,
                             fused=hvp_fused, partition="samples")
    local_hvp = op.apply
    local_hvp_multi = op.apply_multi

    def hvp(u):
        return lax.psum(local_hvp(u), axis_name) / n_global + lam * u

    apply_precond = _samples_precond(precond, X_tau, coeffs_tau, lam, mu,
                                     sag_epochs)

    # state vectors are replicated -> dots are local
    psum_dot = lambda a, b: jnp.vdot(a, b)

    if block_s <= 1:
        return _pcg_loop(hvp, apply_precond, psum_dot, g, eps, max_iter,
                         X_loc.dtype)

    s = int(block_s)
    if axis_size is None:
        raise ValueError("s-step pcg_samples needs the static mesh axis "
                         "size: pass axis_size (DiscoSolver passes its "
                         "shard count; single-device callers pass 1 to get "
                         "the exact basis operator)")

    # Zero-communication basis operator: the replicated tau-sample Hessian
    # estimate (exact on a single shard, where X_loc covers all samples).
    if axis_size == 1:
        def basis_op(u):
            return local_hvp(u) / n_global + lam * u
    else:
        if X_tau is None:
            raise ValueError("s-step pcg_samples on a multi-shard axis "
                             "needs replicated X_tau for the basis operator")
        tau = jnp.asarray(X_tau.shape[1], X_tau.dtype)

        def basis_op(u):
            return X_tau @ (coeffs_tau * (X_tau.T @ u)) / tau + lam * u

    def build_basis(r, p, scales):
        del scales  # MGS normalizes exactly; no scale estimates needed
        cols = _krylov_columns(r, apply_precond, basis_op, s,
                               jnp.ones((max(s - 1, 1),), r.dtype))
        cols.append(p)
        return jnp.stack(_mgs(cols), axis=1)

    # MGS mixes the carried direction into all columns, so the whole basis
    # goes through the batched HVP (Hp is not reusable here).
    def hvp_round(U, Hp):
        del Hp
        return lax.psum(local_hvp_multi(U), axis_name) / n_global + lam * U

    def gram(U, W, r):
        # replicated vectors: the whole Gram system is local, zero comm —
        # the batched HVP psum above is the round's ONLY collective.
        return U.T @ W, U.T @ U, U.T @ r

    update_scales = lambda scales, B: scales

    return _sstep_loop(build_basis, hvp_round, gram, update_scales,
                       psum_dot, g, eps, max_iter, s)


# ---------------------------------------------------------------------------
# Algorithm 3 — DiSCO-F (feature partitioning)
# ---------------------------------------------------------------------------

def pcg_features(X_loc, coeffs, n_global, lam, g_loc, eps, max_iter,
                 tau_idx=None, coeffs_tau=None, mu=0.0, axis_name="model",
                 precond="woodbury", use_kernel=False, block_s=1,
                 X_tau_loc=None, axis_size=None, hvp_fused=False):
    """Runs inside shard_map over ``axis_name``.

    X_loc      : (d_j, n) local feature rows (all samples) — a dense
                 array, or a :class:`repro.data.sparse.EllPair` or
                 :class:`repro.data.sparse.SlotPair` (then every vector
                 below carries the layout's padded lengths);
                 f32, or the bf16 mixed-precision HVP copy
                 (``DiscoConfig.hvp_dtype`` — state vectors stay f32)
    coeffs     : (n,) phi'' at w_k — *replicated* (derived from the globally
                 reduced margins, which every shard already holds)
    g_loc      : (d_j,) local gradient shard
    tau_idx    : (tau,) indices of the preconditioner samples, sliced
                 from a dense ``X_loc`` when ``X_tau_loc`` is not given
    X_tau_loc  : (d_j, tau) dense local rows of the preconditioner samples
                 — what ``DiscoSolver`` passes for every layout; required
                 for sparse ``X_loc`` (which cannot be column-sliced
                 in-kernel)
    block_s    : >1 selects the s-step engine (see pcg_samples)
    axis_size  : static size of ``axis_name``; with ``hvp_fused`` a size-1
                 axis lets the classic HVP fuse too (the z psum is the
                 identity there)
    hvp_fused  : one-pass fused kernels (docs/kernels.md) wherever no
                 collective separates the two HVP directions: always the
                 zero-communication s-step basis operator; the true HVP
                 only on a single-shard axis — the multi-shard DiSCO-F
                 HVP *must* psum the n-vector between its passes, so it
                 stays two-pass by construction.
    """
    n_global = jnp.asarray(n_global, g_loc.dtype)

    # ONE local operator per solve (core/hvp.py): the split passes (A
    # then B — the psum between them IS DiSCO-F's communication, so the
    # true multi-shard HVP can never fuse) and the collective-free local
    # product (one-pass fused when requested), which serves the s-step
    # basis operator at any shard count and the full HVP at m = 1.
    op = make_local_operator(X_loc, coeffs, use_kernel=use_kernel,
                             fused=hvp_fused, partition="features")
    passA, passB = op.pass_a, op.pass_b
    passA_multi, passB_multi = op.pass_a_multi, op.pass_b_multi
    local_hvp, local_hvp_multi = op.apply, op.apply_multi
    fuse_full = op.fused and axis_size == 1    # psum(z) == z on 1 shard

    if fuse_full:
        def hvp(u_loc):
            return local_hvp(u_loc) / n_global + lam * u_loc
    else:
        def hvp(u_loc):
            # THE communication of DiSCO-F: one reduceAll of an R^n
            # vector between pass A and pass B.
            z = lax.psum(passA(u_loc), axis_name)             # (n,)
            return passB(z) / n_global + lam * u_loc

    apply_precond = _features_precond(precond, X_loc, tau_idx, coeffs_tau,
                                      lam, mu, X_tau_loc=X_tau_loc)

    # state vectors are sharded -> dots need a scalar psum (cheap)
    psum_dot = lambda a, b: lax.psum(jnp.vdot(a, b), axis_name)

    if block_s <= 1:
        return _pcg_loop(hvp, apply_precond, psum_dot, g_loc, eps, max_iter,
                         X_loc.dtype)

    s = int(block_s)

    # Zero-communication basis operator: the block-diagonal local Hessian
    # X_j diag(c) X_j^T / n + lam I (exact on a single shard, where the
    # local rows are all rows). No collective separates its two passes —
    # deliberately NOT psum'd — so the fused one-pass kernel applies at
    # ANY shard count.
    def basis_op(u_loc):
        return local_hvp(u_loc) / n_global + lam * u_loc

    def build_basis(r_loc, p_loc, scales):
        # Sharded vectors: exact norms would cost a psum per basis step, so
        # columns are range-managed with the previous round's per-step
        # growth estimates (replicated scalars recycled from diag(B) of the
        # fused Gram payload); the whitened solve absorbs the remaining
        # column scaling exactly.
        cols = _krylov_columns(r_loc, apply_precond, basis_op, s, scales)
        cols.append(p_loc)
        return jnp.stack(cols, axis=1)

    # The basis keeps the p_prev column verbatim, and H p_prev is already
    # in hand from last round's W a (carried as Hp in the loop state) — so
    # only the s Krylov columns ride the batched HVP and the communicated
    # payload is (n, s), not (n, s+1).
    if fuse_full:
        def hvp_round(U, Hp):
            Uk = U[:, :s]
            Wk = local_hvp_multi(Uk) / n_global + lam * Uk
            return jnp.concatenate([Wk, Hp[:, None]], axis=1)
    else:
        def hvp_round(U, Hp):
            Uk = U[:, :s]
            Z = lax.psum(passA_multi(Uk), axis_name)           # (n, s)
            Wk = passB_multi(Z) / n_global + lam * Uk
            return jnp.concatenate([Wk, Hp[:, None]], axis=1)

    def gram(U, W, r_loc):
        # single fused all-reduce: U^T W, U^T U and U^T r concatenated into
        # one psum payload of (s+1)^2 * 2 + (s+1) floats (DESIGN.md §2.3) —
        # the s-step replacement for classic PCG's 2 scalar psums/iteration.
        k = U.shape[1]
        payload = jnp.concatenate([(U.T @ W).ravel(), (U.T @ U).ravel(),
                                   U.T @ r_loc])
        payload = lax.psum(payload, axis_name)
        G = payload[: k * k].reshape(k, k)
        B = payload[k * k: 2 * k * k].reshape(k, k)
        b = payload[2 * k * k:]
        return G, B, b

    def update_scales(scales, B):
        return _feature_scales_update(scales, B, s)

    return _sstep_loop(build_basis, hvp_round, gram, update_scales,
                       psum_dot, g_loc, eps, max_iter, s)


def _feature_scales_update(scales, B, s):
    """Next-round Krylov column scale estimates from diag(B) (DiSCO-F).

    s >= 2 here (block_s > 1), so there is always at least one ratio.
    Overflowed diag(B) entries give inf/inf = NaN, which clip would
    propagate forever — treat them as "no information" instead. Shared
    by the in-memory s-step loop and the host-driven streamed loop so
    both trajectories are identical.
    """
    dgn = jnp.sqrt(jnp.maximum(jnp.diagonal(B)[:s], 1e-30))
    ratios = dgn[1:] / jnp.maximum(dgn[:-1], 1e-30)
    ratios = jnp.where(jnp.isfinite(ratios), ratios, 1.0)
    return jnp.clip(scales * ratios, 1e-6, 1e6)


# ---------------------------------------------------------------------------
# host-driven streamed PCG (out-of-core data plane, docs/streaming.md)
# ---------------------------------------------------------------------------

def pcg_streamed(hvp, apply_precond, g, eps, max_iter, *, block_s=1,
                 hvp_multi=None, basis_op=None, variant="features",
                 between_rounds=None):
    """Host-driven PCG over a *streamed* Hessian operator.

    The in-memory loops (:func:`_pcg_loop` / :func:`_sstep_loop`) trace
    into one ``lax.while_loop`` with the data resident in device memory;
    an out-of-core solve applies ``H`` by scanning disk-backed chunk
    tiles (:mod:`repro.data.stream`), which cannot live inside a traced
    loop — so this twin runs the *identical recurrences* as a host loop
    around streaming callables:

    hvp(u)        -> H u        (streams the shard chunks internally)
    hvp_multi(U)  -> H U        (batched; one chunk read serves all
                   columns — the s-step x streaming synergy: ``s`` Krylov
                   dimensions per data pass instead of one)
    basis_op(u)   -> H~ u       zero-communication basis operator of the
                   s-step engine (the streamed block-diagonal local
                   Hessian for 'features', the resident tau-sample
                   estimate for 'samples')
    apply_precond, g: as in the in-memory twins, over *global* flat
                   vectors (the permuted padded axis), where every dot is
                   a plain ``jnp.vdot`` — the cross-shard reduction is
                   already folded into the chunk accumulation.

    ``variant`` mirrors the two in-memory s-step wirings: 'features'
    keeps unnormalized scale-managed Krylov columns and splices the
    carried ``H p_prev``; 'samples' MGS-orthonormalizes the replicated
    basis and batches all ``s + 1`` columns. Returns :class:`PCGResult`
    with the same fields/semantics as the in-memory paths.

    ``between_rounds``, when given, is called (no arguments) after each
    completed round before the next residual check — the elastic
    re-planning window (docs/robustness.md): the PCG state here is
    replicated and unpermuted in both variants (global flat vectors in
    the solve axis's canonical permuted layout), so a callback that
    swaps the underlying stream schedule — rewiring what ``hvp``/
    ``hvp_multi`` stream, not what they compute — leaves the recurrence
    exact.
    """
    eps = float(eps)
    v = jnp.zeros_like(g)
    r = g
    Hv = jnp.zeros_like(g)

    def rnorm(x):
        return float(jnp.sqrt(jnp.vdot(x, x)))

    # paper-style communication rounds per host iteration — matches
    # comm.disco_{s,f}_{pcg,sstep}_cost exactly (2/round for 'samples',
    # 1/round for 'features', classic and s-step alike), so the traced
    # tally can be cross-checked against CommLedger (bench_obs gate)
    rpi = 2 if variant == "samples" else 1

    def _emit_round():
        if obs.enabled():
            obs.count("comm.rounds", rpi)
            for _ in range(rpi):
                obs.instant("comm.allreduce", phase="pcg")

    if block_s <= 1:
        s_vec = apply_precond(r)
        u = s_vec
        rs = jnp.vdot(r, s_vec)
        t = 0
        rn = rnorm(r)
        while t < max_iter and rn > eps:
            with obs.span("pcg.round", t=t, variant=variant, block_s=1):
                Hu = hvp(u)
                alpha = rs / jnp.vdot(u, Hu)
                v = v + alpha * u
                Hv = Hv + alpha * Hu
                r = r - alpha * Hu
                s_new = apply_precond(r)
                rs_new = jnp.vdot(r, s_new)
                beta = rs_new / rs
                u = s_new + beta * u
                rs = rs_new
                # the residual check's host sync, pulled inside the
                # span so its duration covers the completed round
                rn = rnorm(r)
            t += 1
            _emit_round()
            if between_rounds is not None:
                between_rounds()
    else:
        if hvp_multi is None or basis_op is None:
            raise ValueError("streamed s-step PCG (block_s > 1) needs "
                             "both hvp_multi (the batched streamed HVP) "
                             "and basis_op (the zero-communication basis "
                             "operator)")
        s = int(block_s)
        p = jnp.zeros_like(g)
        Hp = jnp.zeros_like(g)
        scales = jnp.ones((max(s - 1, 1),), g.dtype)
        t = 0
        rn = rnorm(r)
        while t < max_iter and rn > eps:
            with obs.span("pcg.round", t=t, variant=variant,
                          block_s=s):
                if variant == "samples":
                    cols = _krylov_columns(r, apply_precond, basis_op, s,
                                           jnp.ones((max(s - 1, 1),),
                                                    r.dtype))
                    cols.append(p)
                    U = jnp.stack(_mgs(cols), axis=1)
                    W = hvp_multi(U)
                elif variant == "features":
                    cols = _krylov_columns(r, apply_precond, basis_op, s,
                                           scales)
                    cols.append(p)
                    U = jnp.stack(cols, axis=1)
                    Wk = hvp_multi(U[:, :s])
                    W = jnp.concatenate([Wk, Hp[:, None]], axis=1)
                else:
                    raise ValueError(
                        f"unknown streamed variant {variant!r}")
                G, B, b = U.T @ W, U.T @ U, U.T @ r
                a = _solve_round(G, B, b, s)
                dv = U @ a
                Hdv = W @ a
                v = v + dv
                r = r - Hdv
                p, Hp = dv, Hdv
                Hv = Hv + Hdv
                if variant == "features":
                    scales = _feature_scales_update(scales, B, s)
                rn = rnorm(r)
            t += 1
            _emit_round()
            if between_rounds is not None:
                between_rounds()

    delta = jnp.sqrt(jnp.maximum(jnp.vdot(v, Hv), 0.0))
    r_norm = jnp.sqrt(jnp.vdot(r, r))
    return PCGResult(v=v, delta=delta,
                     iters=jnp.asarray(t, jnp.int32), r_norm=r_norm)
