"""Shared NumPy oracles + legacy HVP closures for the test suite.

One home for the reference implementations that used to be duplicated
inline across tests/test_hvp_fused.py, tests/test_kernels.py and
tests/test_pcg.py, plus two things the HvpOperator conformance suite
(tests/test_hvp_operator.py) needs:

* ``legacy_local_hvp`` — a frozen, verbatim copy of the pre-refactor
  dispatch closures that ``core/pcg.py`` used to inline per backend.
  The refactored operators must reproduce these **bit-identically**
  (same kernel calls, same argument order), which is what locks the
  refactor down.
* problem builders (``sparse_case``, ``make_glm_problem``,
  ``softmax_problem``) producing matched (device data, NumPy oracle
  data) pairs.
"""
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# binary (margin GLM) oracles
# ---------------------------------------------------------------------------


def local_hvp_oracle(X, c, u):
    """The local curvature product  X (c .* (X^T u))  in f64 NumPy."""
    X = np.asarray(X, np.float64)
    return X @ (np.asarray(c, np.float64) * (X.T @ np.asarray(u, np.float64)))


def local_hvp_multi_oracle(X, c, U):
    """Batched local product  X (c[:, None] .* (X^T U))  in f64 NumPy."""
    X = np.asarray(X, np.float64)
    return X @ (np.asarray(c, np.float64)[:, None]
                * (X.T @ np.asarray(U, np.float64)))


def glm_hvp_oracle(X, c, u, lam, n_global=None):
    """Full GLM HVP  X diag(c) X^T u / n + lam u  in f64 NumPy."""
    n = X.shape[1] if n_global is None else n_global
    return local_hvp_oracle(X, c, u) / n + lam * np.asarray(u, np.float64)


def logistic_grad_oracle(X, y, w, lam):
    """Float64 gradient of the L2-regularised logistic objective
    ``(1/n) sum_i log(1 + exp(-y_i x_i^T w)) + (lam/2) ||w||^2`` at w."""
    X = np.asarray(X, np.float64)
    y = np.asarray(y, np.float64)
    w = np.asarray(w, np.float64)
    s = 0.5 * (1.0 + np.tanh(-0.5 * y * (X.T @ w)))   # sigmoid(-y x^T w)
    return X @ (-y * s) / X.shape[1] + lam * w


def newton_direction_oracle(prob, w):
    """Dense NumPy Newton direction ``H^{-1} g`` of a GLMProblem at w
    (the target every PCG variant must solve to its tolerance)."""
    H = np.asarray(prob.hessian(w))
    g = np.asarray(prob.grad(w))
    return np.linalg.solve(H, g), g


def make_glm_problem(rng, d=40, n=200, loss="logistic", lam=1e-2):
    """Column-normalized random GLM + a small random iterate (the
    standard PCG test problem, shared with tests/test_pcg.py)."""
    from repro.core.glm import GLMProblem

    X = rng.standard_normal((d, n)).astype(np.float32)
    X /= np.linalg.norm(X, axis=0, keepdims=True)
    y = np.sign(rng.standard_normal(n)).astype(np.float32)
    w = rng.standard_normal(d).astype(np.float32) * 0.1
    prob = GLMProblem.create(X, y, loss=loss, lam=lam)
    return prob, jnp.asarray(w)


# ---------------------------------------------------------------------------
# softmax (multinomial) oracles — all f64
# ---------------------------------------------------------------------------


def softmax_probs_oracle(A):
    """Row-stochastic softmax over the trailing axis (f64, max-shifted)."""
    A = np.asarray(A, np.float64)
    A = A - A.max(axis=-1, keepdims=True)
    E = np.exp(A)
    return E / E.sum(axis=-1, keepdims=True)


def softmax_hvp_oracle(X, W, U, lam, weights=None, n_global=None):
    """Multinomial softmax Hessian product  H U  in f64 NumPy.

    H U = X (P.*V - P.*rowsum(P.*V)) / n + lam U,  V = X^T U,
    P = softmax(X^T W). The oracle of ``ops.softmax_hvp`` and of
    ``SoftmaxHvpOperator`` (with the 1/n + ridge framing added here).
    """
    X = np.asarray(X, np.float64)
    n = X.shape[1] if n_global is None else n_global
    P = softmax_probs_oracle(X.T @ np.asarray(W, np.float64))
    V = X.T @ np.asarray(U, np.float64)
    PV = P * V
    S = PV - P * PV.sum(axis=1, keepdims=True)
    if weights is not None:
        S = np.asarray(weights, np.float64)[:, None] * S
    return X @ S / n + lam * np.asarray(U, np.float64)


def softmax_loss_grad_oracle(X, y, W, lam):
    """(cross-entropy objective, gradient) of multinomial softmax
    regression in f64 NumPy."""
    X = np.asarray(X, np.float64)
    W = np.asarray(W, np.float64)
    n = X.shape[1]
    K = W.shape[1]
    A = X.T @ W
    A = A - A.max(axis=1, keepdims=True)
    logZ = np.log(np.exp(A).sum(axis=1))
    f = float((logZ - A[np.arange(n), y]).mean()
              + 0.5 * lam * (W * W).sum())
    P = softmax_probs_oracle(X.T @ W)
    Y1 = np.eye(K)[np.asarray(y)]
    g = X @ (P - Y1) / n + lam * W
    return f, g


def softmax_newton_fit(X, y, lam, K=None, iters=50, tol=1e-12):
    """f64 NumPy Newton solve of multinomial softmax regression — the
    conformance target the JAX solver must match to <= 1e-6 rel."""
    X = np.asarray(X, np.float64)
    y = np.asarray(y)
    d, n = X.shape
    K = int(y.max()) + 1 if K is None else K
    W = np.zeros((d, K))
    for _ in range(iters):
        _, g = softmax_loss_grad_oracle(X, y, W, lam)
        # dense Hessian via dK column probes of the HVP oracle
        H = np.zeros((d * K, d * K))
        for j in range(d * K):
            e = np.zeros((d, K))
            e[j // K, j % K] = 1.0
            H[:, j] = softmax_hvp_oracle(X, W, e, lam).reshape(-1)
        W = W - np.linalg.solve(H, g.reshape(-1)).reshape(d, K)
        if np.linalg.norm(softmax_loss_grad_oracle(X, y, W, lam)[1]) < tol:
            break
    return W


# ---------------------------------------------------------------------------
# finite differences (gradient <-> Hessian consistency)
# ---------------------------------------------------------------------------


def fd_derivative(f, x, eps=1e-6):
    """Central finite difference of a scalar->array map, elementwise."""
    return (np.asarray(f(x + eps), np.float64)
            - np.asarray(f(x - eps), np.float64)) / (2 * eps)


# ---------------------------------------------------------------------------
# problem builders
# ---------------------------------------------------------------------------


def sparse_case(rng, d, n, density, br, bc, width_pad=0):
    """Random CSR + its (optionally width-padded) ELL pair + the padded
    dense equivalent for the NumPy oracle (shared with
    tests/test_hvp_fused.py)."""
    from repro.data.sparse import CSRMatrix, ell_pair_from_csr

    Xd = rng.standard_normal((d, n)) * (rng.random((d, n)) < density)
    csr = CSRMatrix.from_dense(Xd)
    fwd, tr = ell_pair_from_csr(csr, br, bc)
    if width_pad:
        fwd, tr = ell_pair_from_csr(csr, br, bc,
                                    width=fwd.width + width_pad,
                                    width_t=tr.width + width_pad)
    nrb, ncb = fwd.data.shape[0], tr.data.shape[0]
    Xp = np.zeros((nrb * br, ncb * bc), np.float32)
    Xp[:d, :n] = Xd
    return (jnp.asarray(fwd.data), jnp.asarray(fwd.cols),
            jnp.asarray(tr.data), jnp.asarray(tr.cols), Xp)


def ell_pair_case(rng, d, n, density, br, bc, width_pad=0, dtype=None):
    """Like :func:`sparse_case` but returns a ready
    :class:`repro.data.sparse.EllPair` (tiles optionally cast to
    ``dtype``) plus the matching padded dense X."""
    from repro.data.sparse import EllPair

    data, cols, dataT, colsT, Xp = sparse_case(rng, d, n, density, br, bc,
                                               width_pad)
    if dtype is not None:
        data, dataT = data.astype(dtype), dataT.astype(dtype)
    pair = EllPair(data=data, cols=cols, dataT=dataT, colsT=colsT)
    return pair, Xp


def slot_csr_case(rng, d=64, n=4000, full_row=True, heavy=True):
    """A CSR whose slot layout exercises every shape of the format: a
    feature present in every sample (``full_row``), heavy features that
    take the dense head slab (``heavy``), features longer than one chunk
    of slots and shorter ones, three hub samples in every light feature
    (sample-major chunks longer than one), empty features and empty
    samples."""
    from repro.data.sparse import CSRMatrix

    rows, cols = [], []
    for i in range(d):
        if i == 0 and full_row:
            k = n
        elif i < 6 and heavy:
            k = int(rng.integers(60, 200))
        elif i % 7 == 3:
            k = 0                                   # empty feature
        else:
            k = int(rng.integers(1, 14))            # 1 or 2 chunks
        if k == n:
            picked = np.arange(n)
        else:
            pool = n // 2                           # upper half empty
            picked = rng.choice(pool, size=k, replace=False)
            if i >= 6 and k:
                picked = np.union1d(picked, [0, 1, 2])[:max(k, 3)]
        rows.append(np.full(len(picked), i))
        cols.append(picked)
    rows, cols = np.concatenate(rows), np.concatenate(cols)
    vals = rng.standard_normal(len(rows)).astype(np.float32)
    return CSRMatrix.from_coo(rows, cols, vals, (d, n))


def slot_pair_case(csr, shape, dtype=None):
    """One shard's :class:`repro.data.sparse.SlotPair` of ``csr`` at the
    padded local ``shape`` (values optionally in ``dtype``) on the
    device, plus the padded dense X those values stand for, in f32."""
    import jax
    from repro.data.sparse import build_shard_slot_pairs

    pair = build_shard_slot_pairs([csr], shape)
    pair = jax.tree_util.tree_map(lambda a: jnp.asarray(a[0]), pair)
    if dtype is not None:
        pair = pair.with_values(dtype)
    Xp = np.zeros(shape, np.float32)
    Xp[: csr.shape[0], : csr.shape[1]] = csr.todense()
    if dtype is not None:
        Xp = np.asarray(jnp.asarray(Xp, dtype).astype(jnp.float32))
    return pair, Xp


# ---------------------------------------------------------------------------
# frozen pre-refactor dispatch (the bit-identity target)
# ---------------------------------------------------------------------------


def legacy_local_hvp(X_loc, coeffs, *, use_kernel=False, fused=False):
    """The local-HVP closures exactly as ``core/pcg.py`` inlined them
    before the HvpOperator refactor (verbatim copy of the old dispatch
    block). Returns ``(local_hvp, local_hvp_multi)``.

    The conformance suite runs these against the new operators with
    ``np.array_equal`` — same kernels, same argument order, same
    composition, so any behavioural drift in the refactor shows up as a
    bit difference.
    """
    from repro.data.sparse import EllPair

    sparse = isinstance(X_loc, EllPair)
    if sparse:
        from repro.kernels import ops as kops

        if fused:
            def local_hvp(u):
                return kops.ell_hvp(X_loc.dataT, X_loc.colsT, u,
                                    coeffs,
                                    fwd=(X_loc.data, X_loc.cols))

            def local_hvp_multi(U):
                return kops.ell_hvp_mm(X_loc.dataT, X_loc.colsT, U,
                                       coeffs,
                                       fwd=(X_loc.data, X_loc.cols))
        else:
            def local_hvp(u):
                z = kops.ell_matvec(X_loc.dataT, X_loc.colsT, u)
                return kops.ell_matvec(X_loc.data, X_loc.cols, z,
                                       coeffs)

            def local_hvp_multi(U):
                Z = kops.ell_matmat(X_loc.dataT, X_loc.colsT, U)
                return kops.ell_matmat(X_loc.data, X_loc.cols, Z,
                                       coeffs)
    elif use_kernel:
        from repro.kernels import ops as kops

        if fused:
            def local_hvp(u):
                return kops.x_c_xt_u(X_loc, coeffs, u)

            def local_hvp_multi(U):
                return kops.x_c_xt_multi(X_loc, coeffs, U)
        else:
            def local_hvp(u):
                z = kops.xt_u(X_loc, u)
                return kops.x_cz_local(X_loc, coeffs, z)

            def local_hvp_multi(U):
                Z = kops.xt_multi(X_loc, U)
                return kops.x_cz_multi(X_loc, coeffs, Z)
    else:
        if fused:
            raise ValueError("the legacy dense-jnp path silently ignored "
                             "fused — build it two-pass only")

        def local_hvp(u):
            return X_loc @ (coeffs * (X_loc.T @ u))

        def local_hvp_multi(U):
            return X_loc @ (coeffs[:, None] * (X_loc.T @ U))

    return local_hvp, local_hvp_multi
