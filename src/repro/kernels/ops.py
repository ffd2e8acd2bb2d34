"""jit'd public wrappers around the Pallas kernels.

Handles padding to block multiples, dtype plumbing, and backend dispatch:
on TPU the compiled kernels run natively; everywhere else they run in
``interpret=True`` (Python emulation — bit-faithful to the kernel body) or
fall back to the jnp reference for speed (``REPRO_KERNEL_MODE=ref``).

Set ``REPRO_KERNEL_MODE`` to one of:
  auto      (default) native on TPU, interpret elsewhere
  interpret force interpret mode (what the tests use)
  ref       skip Pallas, call the jnp oracle (fast CPU path for examples)
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.kernels import glm_hvp as _hvp
from repro.kernels import flash_attention as _fa
from repro.kernels import ref as _ref
from repro.kernels import sparse_hvp as _sparse
from repro.data.sparse import SLOT_BLOCK
from repro.obs import tracer as obs
from repro.utils.padding import pad_to_multiple as _pad_axis

# mode -> the tracer that already has its dispatch instant (dedup): a fresh
# tracer, such as one installed by ``obs.enable(reset=True)``, sees each
# mode once again
_seen_dispatch: dict[str, object] = {}


def ref_kernels_off_tpu() -> None:
    """Default ``REPRO_KERNEL_MODE`` to ``ref`` unless the backend is a TPU.

    For entry points that demo or time the solver: off the chip the jnp
    reference is the fast path (interpret mode emulates each kernel in
    Python), on the chip the kernels stay native. An explicit setting
    always wins.
    """
    if jax.default_backend() != "tpu":
        os.environ.setdefault("REPRO_KERNEL_MODE", "ref")


def _mode() -> str:
    m = os.environ.get("REPRO_KERNEL_MODE", "auto")
    resolved = m
    if m == "auto":
        resolved = ("native" if jax.default_backend() == "tpu"
                    else "interpret")
    tracer = obs.get_tracer()
    if obs.enabled() and _seen_dispatch.get(resolved) is not tracer:
        # once per distinct mode, not per call — the eager chunk ops
        # would otherwise flood the trace with identical instants
        _seen_dispatch[resolved] = tracer
        obs.instant("kernel.dispatch", mode=resolved, env=m)
    return resolved


# VMEM budget for the multi-vector dense and the ELL one-pass kernels
# (docs/kernels.md): the dense panel (d, block_n) — or the sparse tile
# row plus the resident u/y vectors — must fit alongside double
# buffering; past the budget the wrappers fall back to the two-pass
# kernels, which is always legal. The single-vector dense one-pass
# kernel plans its own panel (``glm_hvp.one_pass_block``).
_FUSED_VMEM_BYTES = int(os.environ.get("REPRO_FUSED_VMEM_BYTES", 4 << 20))


def _fused_panel_fits(d_padded: int, block_n: int, itemsize: int,
                      s_pad: int = 1) -> bool:
    # panel + the resident f32 u/y blocks (s_pad = LANE-padded probe
    # count for the multi-vector kernel — what is actually held in VMEM)
    panel = d_padded * block_n * itemsize
    vectors = 2 * d_padded * s_pad * 4
    return panel + vectors <= _FUSED_VMEM_BYTES


def ell_fused_fits(wt: int, bc: int, br: int, itemsize: int, u_len: int,
                   s: int = 1) -> bool:
    """Whether a fused one-pass ELL HVP's working set — one transposed
    tile row of ``wt`` (bc, br) tiles plus the resident u and y vectors
    over ``s`` probe columns — fits the fused VMEM budget.

    ``s`` is LANE-padded internally (the multi-vector kernel holds the
    *padded* (nrb, br, s) blocks resident). Callers that choose a
    *streaming plan* (disco's fused DiSCO-S chunk HVP) should check
    this up front with the plan's global tile geometry and fall back to
    the two-pass layout stream when it fails: past the budget the
    wrappers below need the forward layout, or they raise.
    """
    s_pad = 1 if s <= 1 else -(-s // LANE) * LANE
    tile_row = wt * bc * br * itemsize
    vectors = 2 * u_len * 4 * s_pad         # u + y accumulator, f32
    return tile_row + vectors <= _FUSED_VMEM_BYTES


def _fused_ell_fits(dataT, u_len: int, s: int = 1) -> bool:
    _, wt, bc, br = dataT.shape
    return ell_fused_fits(wt, bc, br, dataT.dtype.itemsize, u_len, s)


# ---------------------------------------------------------------------------
# GLM HVP
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("block_d", "block_n", "mode",
                                             "fused"))
def _glm_hvp_impl(X, c, u, lam, *, block_d, block_n, mode, fused):
    d, n = X.shape
    if fused:
        y = x_c_xt_u(X, c, u, block_d=block_d, mode=mode)
        return y / n + lam * u
    if mode == "ref":
        return _ref.ref_glm_hvp(X, c, u, lam)
    interp = mode == "interpret"
    Xp, _ = _pad_axis(X, 0, block_d)
    Xp, _ = _pad_axis(Xp, 1, block_n)
    cp, _ = _pad_axis(c, 0, block_n)
    up, _ = _pad_axis(u, 0, block_d)
    z = _hvp.xt_u(Xp, up, block_d=block_d, block_n=block_n,
                  interpret=interp)
    y = _hvp.x_cz(Xp, cp, z, block_d=block_d, block_n=block_n,
                  interpret=interp)
    return y[:d] / n + lam * u


def glm_hvp(X, c, u, lam, *, block_d=512, block_n=512, mode=None,
            fused=False):
    """H u = X diag(c) X^T u / n + lam u  via the Pallas HVP kernels.

    ``fused=True`` routes through the one-pass panel-resident kernel
    (:func:`x_c_xt_u`, at its own panel width) — X streams from HBM once
    instead of twice."""
    mode = mode or _mode()
    return _glm_hvp_impl(X, c, u, jnp.asarray(lam, jnp.float32),
                         block_d=block_d, block_n=block_n, mode=mode,
                         fused=fused)


def xt_u(X, u, *, block_d=512, block_n=512, mode=None):
    """z = X^T u (pass A only — what DiSCO-F all-reduces)."""
    mode = mode or _mode()
    if mode == "ref":
        return _ref.ref_xt_u(X, u)
    d, n = X.shape
    Xp, _ = _pad_axis(X, 0, block_d)
    Xp, _ = _pad_axis(Xp, 1, block_n)
    up, _ = _pad_axis(u, 0, block_d)
    z = _hvp.xt_u(Xp, up, block_d=block_d, block_n=block_n,
                  interpret=(mode == "interpret"))
    return z[:n]


def x_cz_local(X, c, z, *, block_d=512, block_n=512, mode=None):
    """y = X @ (c * z) (pass B only — the scale is fused in the kernel).

    Used by the distributed PCG: pass A's result is psum'd across shards
    (DiSCO-F's one n-vector round), then pass B runs on the local rows."""
    mode = mode or _mode()
    if mode == "ref":
        return _ref.ref_x_cz(X, c * z)
    d, n = X.shape
    Xp, _ = _pad_axis(X, 0, block_d)
    Xp, _ = _pad_axis(Xp, 1, block_n)
    cp, _ = _pad_axis(c, 0, block_n)
    zp, _ = _pad_axis(z, 0, block_n)
    y = _hvp.x_cz(Xp, cp, zp, block_d=block_d, block_n=block_n,
                  interpret=(mode == "interpret"))
    return y[:d]


# ---------------------------------------------------------------------------
# GLM HVP — multi-vector (s-step PCG)
# ---------------------------------------------------------------------------

LANE = 128  # TPU lane width; s-vector tiles are padded to this multiple


def xt_multi(X, U, *, block_d=512, block_n=512, mode=None):
    """Z = X^T U for a block of s probe vectors.  X: (d, n), U: (d, s).

    One X-tile read serves all s columns — the s-step basis HVP costs one
    streaming pass over X instead of s (see DESIGN.md §2)."""
    mode = mode or _mode()
    if mode == "ref":
        return _ref.ref_xt_multi(X, U)
    d, n = X.shape
    s = U.shape[1]
    Xp, _ = _pad_axis(X, 0, block_d)
    Xp, _ = _pad_axis(Xp, 1, block_n)
    Up, _ = _pad_axis(U, 0, block_d)
    Up, _ = _pad_axis(Up, 1, LANE)
    Z = _hvp.xt_multi(Xp, Up, block_d=block_d, block_n=block_n,
                      interpret=(mode == "interpret"))
    return Z[:n, :s]


def x_cz_multi(X, c, Z, *, block_d=512, block_n=512, mode=None):
    """Y = X @ (c .* Z) for a block of s vectors (c-scale fused in-kernel).

    Distributed use mirrors the single-vector pair: pass A's (n, s) result
    is psum'd across shards (the ONE vector round of an s-step DiSCO-F
    iteration block), then pass B runs on the local rows."""
    mode = mode or _mode()
    if mode == "ref":
        return _ref.ref_x_cz_multi(X, c, Z)
    d, n = X.shape
    s = Z.shape[1]
    Xp, _ = _pad_axis(X, 0, block_d)
    Xp, _ = _pad_axis(Xp, 1, block_n)
    cp, _ = _pad_axis(c, 0, block_n)
    Zp, _ = _pad_axis(Z, 0, block_n)
    Zp, _ = _pad_axis(Zp, 1, LANE)
    Y = _hvp.x_cz_multi(Xp, cp, Zp, block_d=block_d, block_n=block_n,
                        interpret=(mode == "interpret"))
    return Y[:d, :s]


@functools.partial(jax.jit, static_argnames=("block_d", "block_n", "mode",
                                             "fused"))
def _glm_hvp_multi_impl(X, c, U, lam, *, block_d, block_n, mode, fused):
    if fused:
        n = X.shape[1]
        Y = x_c_xt_multi(X, c, U, block_d=block_d, block_n=block_n,
                         mode=mode)
        return Y / n + lam * U
    if mode == "ref":
        return _ref.ref_glm_hvp_multi(X, c, U, lam)
    n = X.shape[1]
    Z = xt_multi(X, U, block_d=block_d, block_n=block_n, mode=mode)
    Y = x_cz_multi(X, c, Z, block_d=block_d, block_n=block_n, mode=mode)
    return Y / n + lam * U


def glm_hvp_multi(X, c, U, lam, *, block_d=512, block_n=512, mode=None,
                  fused=False):
    """Batched H U = X diag(c) X^T U / n + lam U over s probe vectors.

    ``fused=True`` uses the one-pass panel-resident kernel
    (:func:`x_c_xt_multi`), halving HBM reads of X per round."""
    mode = mode or _mode()
    return _glm_hvp_multi_impl(X, c, U, jnp.asarray(lam, jnp.float32),
                               block_d=block_d, block_n=block_n, mode=mode,
                               fused=fused)


# ---------------------------------------------------------------------------
# fused one-pass GLM HVP (panel-resident; docs/kernels.md)
# ---------------------------------------------------------------------------

def x_c_xt_u(X, c, u, *, block_d=512, block_n=None, mode=None,
             out_dtype=jnp.float32):
    """y = X (c .* (X^T u)) in ONE streaming pass over X.

    The local fused HVP core: both directions run from the same
    VMEM-resident full-height column panel, so X streams from HBM once
    per application instead of twice, and X is read where it lies (no
    padded copy). Legal wherever no collective separates the passes
    (DiSCO-S local products, single-shard DiSCO-F, the s-step
    zero-communication basis operators). ``block_n`` defaults to the
    kernel's own panel width (:func:`repro.kernels.glm_hvp.one_pass_block`);
    when no panel fits VMEM the two-pass kernels run instead, at
    ``block_d`` x 512 tiles. Accumulates f32, returns ``out_dtype``.
    """
    mode = mode or _mode()
    if mode == "ref":
        return _ref.ref_x_c_xt_u(X, c, u).astype(out_dtype)
    d, n = X.shape
    itemsize = X.dtype.itemsize
    bn = block_n or _hvp.one_pass_block(d, n, itemsize)
    if bn and _hvp.one_pass_vmem_bytes(d, bn, itemsize) \
            <= _hvp.ONE_PASS_VMEM_BYTES:
        return _hvp.x_c_xt_u(X, c, u, block_n=bn,
                             interpret=(mode == "interpret"),
                             out_dtype=out_dtype)
    z = xt_u(X, u, block_d=block_d, mode=mode)
    return x_cz_local(X, c, z, block_d=block_d, mode=mode).astype(out_dtype)


def dense_local_hvp(X, c, u):
    """The dense local product ``X (c .* (X^T u))`` as a solver runs it.

    Lowered for a TPU, and when the one-pass kernel's panel fits
    (:func:`repro.kernels.glm_hvp.one_pass_block`, decided from d and the
    itemsize), it is the one-pass kernel: one read of X per product, f32
    on the VPU. Lowered for any other platform, or when no panel fits,
    it is XLA's two passes, ``X @ (c * (X.T @ u))``. The platform is the
    one the program is lowered for (``lax.platform_dependent``), so a
    program compiled for a described chip takes the chip's branch.
    """
    def two_pass(X, c, u):
        return X @ (c * (X.T @ u))

    bn = _hvp.one_pass_block(*X.shape, X.dtype.itemsize)
    if not bn:
        return two_pass(X, c, u)

    def one_pass(X, c, u):
        return _hvp.x_c_xt_u(X, c, u, block_n=bn)

    return jax.lax.platform_dependent(X, c, u, tpu=one_pass,
                                      default=two_pass)


def x_c_xt_multi(X, c, U, *, block_d=512, block_n=512, mode=None,
                 out_dtype=jnp.float32):
    """Y = X (c .* (X^T U)) in ONE streaming pass over X (s vectors).

    Multi-vector fused HVP core for the s-step rounds: one resident
    panel read serves both directions of all s probe vectors (s padded
    to the TPU lane width and cropped back). Same fallback contract as
    :func:`x_c_xt_u`.
    """
    mode = mode or _mode()
    if mode == "ref":
        return _ref.ref_x_c_xt_multi(X, c, U).astype(out_dtype)
    interp = mode == "interpret"
    d, n = X.shape
    s = U.shape[1]
    if _fused_panel_fits(-(-d // LANE) * LANE, block_n,
                         X.dtype.itemsize, s_pad=-(-s // LANE) * LANE):
        Xp, _ = _pad_axis(X, 0, LANE)
        Xp, _ = _pad_axis(Xp, 1, block_n)
        cp, _ = _pad_axis(c, 0, block_n)
        Up, _ = _pad_axis(U, 0, LANE)
        Up, _ = _pad_axis(Up, 1, LANE)
        Y = _hvp.x_c_xt_multi(Xp, cp, Up, block_n=block_n,
                              interpret=interp, out_dtype=out_dtype)
        return Y[:d, :s]
    Z = xt_multi(X, U, block_d=block_d, block_n=block_n, mode=mode)
    return x_cz_multi(X, c, Z, block_d=block_d, block_n=block_n,
                      mode=mode).astype(out_dtype)


def softmax_coupling(probs, V, weights=None):
    """Softmax class coupling  S = P .* V - P .* rowsum(P .* V).

    The (n, K) mid-chain term of the multinomial Hessian product
    (docs/workloads.md): elementwise + one row reduction, so it needs no
    Pallas kernel of its own — it is exactly what sits *between* the
    multi-vector pass A and pass B, which is why no one-pass fused
    softmax kernel exists (see ``repro.core.hvp``). ``weights``
    optionally masks padded samples.
    """
    return _ref.ref_softmax_coupling(probs, V, weights)


def softmax_hvp(X, probs, U, *, lam=0.0, n_global=None, weights=None,
                block_d=512, block_n=512, mode=None):
    """Multinomial softmax Hessian product via the multi-vector kernels.

    H U = X S / n + lam U with S = :func:`softmax_coupling`(P, X^T U):
    all K classes of the direction ``U`` (d, K) ride ONE ``xt_multi``
    pass and ONE ``x_cz_multi`` pass — K-class curvature for the X
    traffic of a single two-pass binary HVP. Dispatches by
    ``REPRO_KERNEL_MODE`` like every op here.
    """
    n = X.shape[1] if n_global is None else n_global
    mode = mode or _mode()
    if mode == "ref":
        return _ref.ref_softmax_hvp(X, probs, U, lam, n_global=n,
                                    weights=weights)
    V = xt_multi(X, U, block_d=block_d, block_n=block_n, mode=mode)
    S = softmax_coupling(probs, V, weights)
    ones = jnp.ones((X.shape[1],), X.dtype)
    HU = x_cz_multi(X, ones, S, block_d=block_d, block_n=block_n,
                    mode=mode)
    return HU / n + lam * U


# ---------------------------------------------------------------------------
# Blocked-ELL sparse HVP passes (see data/sparse.py for the layout)
# ---------------------------------------------------------------------------

# Each public op resolves the kernel mode and the fused-fits decision per
# call (both read mutable state: the environment and the VMEM budget) and
# runs a jitted body with them static. Jitting matters where the streamed
# solver calls these eagerly, chunk by chunk: an un-jitted pallas_call
# re-traces its kernel, and so compiles again, on every call.

@functools.partial(jax.jit, static_argnames=("mode", "out_dtype"))
def _ell_matvec_impl(data, cols, v, c, *, mode, out_dtype):
    if mode == "ref":
        return _ref.ref_ell_mv(data, cols, v, c, out_dtype=out_dtype)
    return _sparse.ell_mv(data, cols, v, c,
                          interpret=(mode == "interpret"),
                          out_dtype=out_dtype)


def ell_matvec(data, cols, v, c=None, *, mode=None, out_dtype=jnp.float32):
    """y = A @ (c .* v) for a blocked-ELL operand (sparse HVP pass).

    data : (nb, W, br, bc) tiles; cols : (nb, W) int32 column-block ids
    v    : (ncb * bc,) padded input; c optional same-length fused scale
    returns (nb * br,) in ``out_dtype`` (default f32, the accumulator
    dtype — bf16 tile storage must not round intermediate results).
    Streaming the forward layout of a shard computes ``X_loc @ (c * z)``
    (pass B); streaming the transposed layout computes ``X_loc^T u``
    (pass A) — one kernel covers both HVP directions
    (docs/architecture.md#kernels).
    """
    return _ell_matvec_impl(data, cols, v, c, mode=mode or _mode(),
                            out_dtype=out_dtype)


@functools.partial(jax.jit, static_argnames=("mode", "out_dtype"))
def _ell_matmat_impl(data, cols, V, c, *, mode, out_dtype):
    if mode == "ref":
        return _ref.ref_ell_mm(data, cols, V, c, out_dtype=out_dtype)
    s = V.shape[1]
    Vp, _ = _pad_axis(V, 1, LANE)
    Y = _sparse.ell_mm(data, cols, Vp, c,
                       interpret=(mode == "interpret"),
                       out_dtype=out_dtype)
    return Y[:, :s]


def ell_matmat(data, cols, V, c=None, *, mode=None, out_dtype=jnp.float32):
    """Y = A @ (c[:, None] .* V) over s probe vectors (s-step rounds).

    V : (ncb * bc, s) -> (nb * br, s) in ``out_dtype``. The s axis is
    padded to the TPU lane width for the native kernel and cropped back,
    mirroring ``xt_multi``/``x_cz_multi``.
    """
    return _ell_matmat_impl(data, cols, V, c, mode=mode or _mode(),
                            out_dtype=out_dtype)


def _fused_or_fwd(dataT, fwd, u_len: int, s: int, mode: str) -> bool:
    """Whether a fused ELL HVP runs one-pass. Past the VMEM budget it
    falls back to the two-pass kernels over the forward layout ``fwd``;
    without ``fwd`` there is no kernel path, so raise (never the jnp
    reference: that would hide the device)."""
    if mode == "ref" or _fused_ell_fits(dataT, u_len, s):
        return True
    if fwd is None:
        _, wt, bc, br = dataT.shape
        raise ValueError(
            f"fused ELL HVP: a tile row of {wt} ({bc}, {br}) tiles plus "
            f"the resident vectors exceeds the {_FUSED_VMEM_BYTES}-byte "
            "VMEM budget; pass fwd=(data, cols) for the two-pass kernels")
    return False


@functools.partial(jax.jit, static_argnames=("mode", "fused", "out_dtype"))
def _ell_hvp_impl(dataT, colsT, u, c, fwd, *, mode, fused, out_dtype):
    if mode == "ref":
        if fwd is not None:
            z = _ref.ref_ell_mv(dataT, colsT, u)
            return _ref.ref_ell_mv(fwd[0], fwd[1], z, c,
                                   out_dtype=out_dtype)
        return _ref.ref_ell_hvp_t(dataT, colsT, u, c, out_dtype=out_dtype)
    interp = mode == "interpret"
    if fused:
        return _sparse.ell_hvp(dataT, colsT, u, c, interpret=interp,
                               out_dtype=out_dtype)
    z = _sparse.ell_mv(dataT, colsT, u, interpret=interp)
    return _sparse.ell_mv(fwd[0], fwd[1], z, c, interpret=interp,
                          out_dtype=out_dtype)


def ell_hvp(dataT, colsT, u, c=None, *, fwd=None, mode=None,
            out_dtype=jnp.float32):
    """One-pass blocked-ELL HVP: y = A (c .* (A^T u)).

    Streams only the *transposed* layout (``dataT``/``colsT``) — each
    resident tile row serves both HVP directions, so tile HBM traffic
    halves versus the two-pass ``ell_matvec`` pair (docs/kernels.md).
    ``u`` lives on A's padded row axis (nrb * br), ``c`` on its padded
    column axis. ``fwd=(data, cols)`` optionally supplies the forward
    layout: it enables the two-pass kernel fallback when the fused
    working set exceeds the VMEM budget (without it that case raises
    rather than leaving the kernels), and makes the 'ref'-mode dispatch
    take the exact two-oracle-pass path (bit-identical to the two-pass
    HVP in f32). Returns f32-accumulated ``out_dtype``.
    """
    mode = mode or _mode()
    fused = _fused_or_fwd(dataT, fwd, u.shape[0], 1, mode)
    return _ell_hvp_impl(dataT, colsT, u, c, fwd, mode=mode, fused=fused,
                         out_dtype=out_dtype)


@functools.partial(jax.jit, static_argnames=("mode", "fused", "out_dtype"))
def _ell_hvp_mm_impl(dataT, colsT, U, c, fwd, *, mode, fused, out_dtype):
    if mode == "ref":
        if fwd is not None:
            Z = _ref.ref_ell_mm(dataT, colsT, U)
            return _ref.ref_ell_mm(fwd[0], fwd[1], Z, c,
                                   out_dtype=out_dtype)
        return _ref.ref_ell_hvp_mm_t(dataT, colsT, U, c,
                                     out_dtype=out_dtype)
    interp = mode == "interpret"
    s = U.shape[1]
    Up, _ = _pad_axis(U, 1, LANE)
    if fused:
        Y = _sparse.ell_hvp_mm(dataT, colsT, Up, c, interpret=interp,
                               out_dtype=out_dtype)
        return Y[:, :s]
    Z = _sparse.ell_mm(dataT, colsT, Up, c=None, interpret=interp)[:, :s]
    return _ell_matmat_impl(fwd[0], fwd[1], Z, c, mode=mode,
                            out_dtype=out_dtype)


def ell_hvp_mm(dataT, colsT, U, c=None, *, fwd=None, mode=None,
               out_dtype=jnp.float32):
    """One-pass blocked-ELL multi-vector HVP: Y = A (c .* (A^T U)).

    U : (nrb * br, s) -> (nrb * br, s); the s axis is padded to the TPU
    lane width for the native kernel and cropped back. Same layout,
    fallback and ``fwd`` contract as :func:`ell_hvp` — one resident tile
    read serves both directions of all s probe vectors.
    """
    mode = mode or _mode()
    fused = _fused_or_fwd(dataT, fwd, U.shape[0], U.shape[1], mode)
    return _ell_hvp_mm_impl(dataT, colsT, U, c, fwd, mode=mode,
                            fused=fused, out_dtype=out_dtype)


# ---------------------------------------------------------------------------
# (id, value) slot products (see data/sparse.py for the layout)
# ---------------------------------------------------------------------------

# Vectors of at most this many 128-lane rows are gathered by a one-hot
# matmul, exact at HIGHEST precision: on a TPU v5e that takes about a
# quarter of the time of XLA's scalar gather (7 ns a slot) at the 164- and
# 283-row vectors of the real-sim benchmark cell, and its cost grows with
# the rows; longer vectors take the scalar gather.
_ONE_HOT_ROWS = 512


def _slot_gather(v, ids):
    """``v[ids]`` for a 1-D f32 ``v``."""
    rows = -(-v.shape[0] // LANE)
    if rows > _ONE_HOT_ROWS:
        return v[ids]
    table = jnp.pad(v, (0, rows * LANE - v.shape[0])).reshape(rows, LANE)
    hit = ((ids // LANE)[..., None] == jnp.arange(rows)).astype(jnp.float32)
    picked = jnp.einsum("clr,rk->clk", hit, table,
                        precision=jax.lax.Precision.HIGHEST)
    lane = (ids % LANE)[..., None] == jnp.arange(LANE)
    return jnp.sum(jnp.where(lane, picked, 0.0), axis=-1)


def _window_sum(part, owner, n_out):
    """Sorted segment sum of the chunk partials ``part`` into ``n_out``
    outputs. Each block of ``SLOT_BLOCK`` chunks spans at most 128
    consecutive owners (every output owns a chunk), so it reduces into a
    window of 2 x 128 outputs at a 128-aligned base, and the windows are
    added as whole 128-lane rows: no scalar scatter."""
    nb = part.shape[0] // SLOT_BLOCK
    blocks = owner.reshape(nb, SLOT_BLOCK)
    base = blocks[:, 0] // LANE
    rel = blocks - base[:, None] * LANE
    hit = rel[..., None] == jnp.arange(2 * LANE)
    win = jnp.sum(jnp.where(hit, part.reshape(nb, SLOT_BLOCK, 1), 0.0),
                  axis=1)
    n_rows = -(-n_out // LANE) + 1
    at = jnp.stack([base, base + 1], axis=1).reshape(-1)
    y = jnp.zeros((n_rows, LANE), jnp.float32).at[at].add(
        win.reshape(2 * nb, LANE), indices_are_sorted=True)
    return y.reshape(-1)[:n_out]


def _slot_mv(layout, v, n_out):
    ids, vals, owner = layout
    part = jnp.sum(vals.astype(jnp.float32) * _slot_gather(v, ids), axis=1)
    return _window_sum(part, owner, n_out)


def slot_matvec(layout, v, n_out):
    """``y = A @ v`` for one direction of a slot layout
    (:class:`repro.data.sparse.SlotLayout`): gather ``v`` at the slot ids,
    multiply by the slot values, reduce each chunk along its slots, then
    sum the chunk partials per owner. ``v`` is ``(len,)`` or ``(len, s)``
    (s probe vectors, one column at a time); f32 in and out. Plain jnp:
    no tile, no scatter over the nonzeros, the same on every backend."""
    v = v.astype(jnp.float32)
    if v.ndim == 2:
        return jax.vmap(lambda col: _slot_mv(layout, col, n_out),
                        in_axes=1, out_axes=1)(v)
    return _slot_mv(layout, v, n_out)


@jax.jit
def slot_xt(pair, u):
    """Pass A over a :class:`repro.data.sparse.SlotPair`: ``X^T u``, the
    head slab's rows by a dense product plus the sample-major slots.
    ``u`` is ``(rows,)`` or ``(rows, s)``; returns ``(cols[, s])`` f32."""
    u = u.astype(jnp.float32)
    head = jnp.tensordot(u[pair.head_rows], pair.head.astype(jnp.float32),
                         axes=(0, 0),
                         precision=jax.lax.Precision.HIGHEST)
    head = head if u.ndim == 1 else head.T
    return slot_matvec(pair.tr, u, pair.shape[1]) + head


@jax.jit
def slot_x(pair, z, c=None):
    """Pass B over a :class:`repro.data.sparse.SlotPair`: ``X (c .* z)``,
    the feature-major slots plus the head slab's rows by a dense
    product. ``z`` is ``(cols,)`` or ``(cols, s)``; returns
    ``(rows[, s])`` f32."""
    z = z.astype(jnp.float32)
    if c is not None:
        z = z * (c if z.ndim == 1 else c[:, None])
    head = jnp.dot(pair.head.astype(jnp.float32), z,
                   precision=jax.lax.Precision.HIGHEST)
    y = slot_matvec(pair.fwd, z, pair.shape[0])
    return y.at[pair.head_rows].add(head)


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "mode"))
def _flash_impl(q, k, v, *, causal, window, block_q, block_k, mode):
    if mode == "ref":
        return _ref.ref_attention(q, k, v, causal=causal, window=window)
    S, T = q.shape[2], k.shape[2]
    bq, bk = min(block_q, S), min(block_k, T)
    qp, _ = _pad_axis(q, 2, bq)
    kp, _ = _pad_axis(k, 2, bk)
    vp, _ = _pad_axis(v, 2, bk)
    out = _fa.flash_attention(qp, kp, vp, causal=causal, window=window,
                              block_q=bq, block_k=bk, kv_len=T,
                              interpret=(mode == "interpret"))
    return out[:, :, :S]


def flash_attention(q, k, v, *, causal=True, window=0,
                    block_q=512, block_k=512, mode=None):
    """Flash attention with GQA + causal/sliding-window masking."""
    mode = mode or _mode()
    return _flash_impl(q, k, v, causal=causal, window=window,
                       block_q=block_q, block_k=block_k, mode=mode)
