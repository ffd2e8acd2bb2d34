"""Unified Hessian-vector-product dispatch: one operator per loss x layout.

The PCG inner loops (:mod:`repro.core.pcg`) are generic in the *local*
curvature product ``u -> X_loc (c .* X_loc^T u)`` — everything else
(collectives, 1/n scaling, the ``+ lam u`` ridge term) is framing that the
solver adds per partitioning. Historically each (layout, fusion) combination
re-threaded its own closures through every call site; this module collapses
that combinatorics behind a single :class:`HvpOperator` interface selected
once at solver setup:

========================  =====================================================
operator                  backing
========================  =====================================================
:class:`DenseOperator`    plain ``jnp`` passes on a dense ``(d_loc, n)`` /
                          ``(d, n_loc)`` shard; the full product reads X
                          once by the one-pass kernel on a TPU
:class:`DenseKernelOperator`  Pallas GLM kernels (``kernels/glm_hvp.py``),
                          optionally one-pass fused
:class:`EllOperator`      blocked-ELL sparse kernels
                          (``kernels/sparse_hvp.py``), optionally fused
:class:`SlotOperator`     (id, value) slots plus a dense head slab
                          (``kernels/ops.py``, plain jnp; two-pass)
:class:`StreamedHvpOperator`  out-of-core chunk scans supplied by the
                          streaming solver (``data/stream.py``)
:class:`SoftmaxHvpOperator`   K-class softmax Hessian application composed
                          from any base operator's *multi-vector* passes
========================  =====================================================

Every operator exposes the same five methods — ``apply`` / ``apply_multi``
(the full local product; one-pass fused where legal) and ``pass_a`` /
``pass_b`` (+ ``_multi``) for callers that must place a collective between
the two directions (multi-shard DiSCO-F). The registry
(:func:`operator_cells`) enumerates every (family, layout, partition,
fusion, dtype) dispatch cell with an explicit supported/unsupported verdict,
:func:`resolve_cell` turns an unsupported combination into an
:class:`UnsupportedHvpError` naming the cell (no flag is ever silently
ignored again), and :func:`render_support_matrix` generates the
``docs/kernels.md`` fusion matrix from the same source of truth the
conformance suite (``tests/test_hvp_operator.py``) iterates.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax.numpy as jnp

from repro.data.sparse import EllPair, SlotPair
from repro.obs import tracer as obs

FAMILIES = ("binary", "softmax")
LAYOUTS = ("dense", "dense_kernel", "ell", "slots", "streamed")
PARTITIONS = ("samples", "features")
DTYPES = ("float32", "bfloat16")

_DTYPE_SHORT = {"float32": "f32", "bfloat16": "bf16"}


class UnsupportedHvpError(ValueError):
    """A (loss, layout, partition, fusion, dtype) dispatch cell that no
    registered operator implements. Raised at solver setup — never after a
    flag has been silently ignored."""


class OperatorCell(NamedTuple):
    """One dispatch cell of the HVP operator registry.

    ``supported`` is the verdict; ``reason`` explains an unsupported cell
    (empty for supported ones) and ``note`` qualifies a supported one
    (e.g. runtime VMEM fallbacks).
    """

    family: str      # 'binary' (margin GLM losses) | 'softmax' (K-class)
    layout: str      # 'dense' | 'dense_kernel' | 'ell' | 'slots' | 'streamed'
    partition: str   # 'samples' (DiSCO-S) | 'features' (DiSCO-F)
    fused: bool      # one-pass fused kernels requested
    dtype: str       # HVP tile storage dtype: 'float32' | 'bfloat16'
    supported: bool
    reason: str = ""
    note: str = ""


def cell_id(family: str, layout: str, partition: str, fused: bool,
            dtype: str) -> str:
    """Canonical short name of a dispatch cell, e.g.
    ``binary/ell/features/fused/bf16`` — the spelling error messages, the
    conformance suite and the coverage report all share."""
    return "/".join([family, layout, partition,
                     "fused" if fused else "two-pass",
                     _DTYPE_SHORT.get(dtype, dtype)])


def _cell_verdict(family: str, layout: str, partition: str, fused: bool,
                  dtype: str) -> tuple[bool, str, str]:
    """(supported, reason, note) for one cell — THE support rules."""
    if dtype not in DTYPES:
        return False, (f"unknown hvp_dtype {dtype!r}; supported: "
                       f"{'|'.join(DTYPES)}"), ""
    if family == "softmax" and layout == "streamed":
        return False, "streamed softmax is not implemented", ""
    if family == "softmax" and fused:
        return False, ("the softmax class coupling runs between pass A "
                       "and pass B, so no one-pass fused kernel exists"), ""
    if layout == "dense" and fused:
        return False, ("the default dense path picks its one-pass kernel "
                       "itself (on a TPU, when the panel fits); the flag "
                       "is use_kernel=True's"), ""
    if layout == "streamed" and partition == "features" and fused:
        return False, ("streamed DiSCO-F accumulates pass A chunk by "
                       "chunk, so no collective-free one-pass kernel can "
                       "cover the full HVP (this flag used to be silently "
                       "ignored here)"), ""
    note = ""
    if fused and layout == "slots":
        note = ("runs the two passes: no one-pass form exists across the "
                "sample-major and feature-major slot layouts")
    elif fused and layout == "streamed":
        note = ("VMEM-gated: oversized chunk panels fall back to the "
                "two-pass chunk stream")
    elif fused and partition == "features":
        note = ("fuses the s-step basis operator at any shard count; the "
                "full HVP fuses only on a 1-shard axis (the z psum "
                "separates the passes otherwise)")
    return True, "", note


def operator_cells() -> list[OperatorCell]:
    """Every registered dispatch cell, supported or not, in deterministic
    order — the iteration domain of the conformance suite and of the
    generated docs matrix."""
    cells = []
    for family in FAMILIES:
        for layout in LAYOUTS:
            for partition in PARTITIONS:
                for fused in (False, True):
                    for dtype in DTYPES:
                        ok, reason, note = _cell_verdict(
                            family, layout, partition, fused, dtype)
                        cells.append(OperatorCell(
                            family, layout, partition, fused, dtype,
                            ok, reason, note))
    return cells


def resolve_cell(family: str, layout: str, partition: str, fused: bool,
                 dtype: str = "float32") -> OperatorCell:
    """Look up one dispatch cell; raise :class:`UnsupportedHvpError`
    naming the cell if it is unsupported."""
    ok, reason, note = _cell_verdict(family, layout, partition, fused,
                                     dtype)
    cell = OperatorCell(family, layout, partition, fused, dtype, ok,
                        reason, note)
    if not ok:
        raise UnsupportedHvpError(
            f"HVP dispatch cell {cell_id(family, layout, partition, fused, dtype)} "
            f"is unsupported: {reason}")
    return cell


def validate_solver_cell(*, family: str, partition: str, fused: bool,
                         dtype: str, sparse: bool = False,
                         use_kernel: bool = False,
                         streaming: bool = False,
                         slots: bool = False) -> OperatorCell:
    """Solver-setup validation: map solver flags to the registry layout
    and resolve the cell (raising early, with the cell named, instead of
    letting an ignored flag surface as silent wrong dispatch deep in the
    PCG loop). ``slots`` marks a sparse solve that took the slot layout
    (:func:`repro.data.sparse.choose_hvp_layout`)."""
    if streaming:
        layout = "streamed"
    elif sparse:
        layout = "slots" if slots else "ell"
    elif use_kernel:
        layout = "dense_kernel"
    else:
        layout = "dense"
    cell = resolve_cell(family, layout, partition, fused, dtype)
    obs.instant("hvp.dispatch",
                cell=cell_id(family, layout, partition, fused, dtype))
    return cell


def render_support_matrix() -> str:
    """The ``docs/kernels.md`` fusion/support matrix, generated from the
    registry (``make test-matrix`` / ``tools/docs_check.py`` verify the
    docs carry exactly this block)."""
    lines = ["| family | layout | partition | two-pass | fused | dtypes |",
             "|---|---|---|---|---|---|"]
    for family in FAMILIES:
        for layout in LAYOUTS:
            for partition in PARTITIONS:
                row = [family, layout, partition]
                for fused in (False, True):
                    ok, reason, note = _cell_verdict(
                        family, layout, partition, fused, "float32")
                    if ok:
                        row.append("yes" + (f" ({note})" if note else ""))
                    else:
                        row.append(f"no — {reason}")
                row.append("f32, bf16")
                lines.append("| " + " | ".join(row) + " |")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# local operators (one class per layout)
# ---------------------------------------------------------------------------

class HvpOperator:
    """Interface of a *local* curvature product on one shard.

    ``apply(u) = X_loc (c .* X_loc^T u)`` with no collectives, no ``1/n``
    and no ridge term — the solver frames those per partitioning. The
    split passes exist so multi-shard DiSCO-F can psum the n-vector
    between them; ``apply``/``apply_multi`` run one-pass fused where the
    operator was built fused.
    """

    family = "binary"
    layout = "dense"
    fused = False

    def pass_a(self, u):
        """Pass A: ``z = X_loc^T u`` (an n-vector)."""
        raise NotImplementedError

    def pass_b(self, z):
        """Pass B: ``X_loc (c .* z)`` (back to the feature axis)."""
        raise NotImplementedError

    def pass_a_multi(self, U):
        """Batched pass A over column-stacked directions ``U``."""
        raise NotImplementedError

    def pass_b_multi(self, Z):
        """Batched pass B over column-stacked n-vectors ``Z``."""
        raise NotImplementedError

    def apply(self, u):
        """Full local product ``X_loc (c .* X_loc^T u)``."""
        return self.pass_b(self.pass_a(u))

    def apply_multi(self, U):
        """Batched full local product (one multi-vector kernel call)."""
        return self.pass_b_multi(self.pass_a_multi(U))


class DenseOperator(HvpOperator):
    """Dense layout, the default. The split passes are plain ``jnp``; the
    full product :meth:`apply` reads X once through the one-pass kernel
    where it is lowered for a TPU and the kernel's panel fits, and is the
    two ``jnp`` passes elsewhere (``kernels.ops.dense_local_hvp``)."""

    layout = "dense"

    def __init__(self, X, coeffs):
        from repro.kernels import ops as kops
        self._kops = kops
        self.X = X
        self.coeffs = coeffs
        self.fused = False

    def pass_a(self, u):
        """``X^T u`` via a dense matvec."""
        return self.X.T @ u

    def pass_b(self, z):
        """``X (c .* z)``; with no coefficients, plain ``X z``."""
        if self.coeffs is None:
            return self.X @ z
        return self.X @ (self.coeffs * z)

    def pass_a_multi(self, U):
        """``X^T U`` via one dense matmul."""
        return self.X.T @ U

    def pass_b_multi(self, Z):
        """``X (c[:, None] .* Z)`` via one dense matmul."""
        if self.coeffs is None:
            return self.X @ Z
        return self.X @ (self.coeffs[:, None] * Z)

    def apply(self, u):
        """Full local product ``X (c .* X^T u)``: one read of X on a TPU
        when the panel fits, else ``pass_b(pass_a(u))``."""
        if self.coeffs is None:
            return self.pass_b(self.pass_a(u))
        return self._kops.dense_local_hvp(self.X, self.coeffs, u)


class DenseKernelOperator(HvpOperator):
    """Dense layout through the Pallas GLM kernels
    (``kernels/glm_hvp.py``); ``fused=True`` selects the one-pass
    ``x_c_xt_u``/``x_c_xt_multi`` kernels for the full product."""

    layout = "dense_kernel"

    def __init__(self, X, coeffs, fused=False):
        from repro.kernels import ops as kops
        self._kops = kops
        self.X = X
        self.coeffs = (coeffs if coeffs is not None
                       else jnp.ones((X.shape[1],), X.dtype))
        self.fused = bool(fused)

    def pass_a(self, u):
        """``X^T u`` via the blocked Pallas reduction kernel."""
        return self._kops.xt_u(self.X, u)

    def pass_b(self, z):
        """``X (c .* z)`` via the blocked Pallas kernel."""
        return self._kops.x_cz_local(self.X, self.coeffs, z)

    def pass_a_multi(self, U):
        """Batched ``X^T U`` (one multi-vector kernel pass)."""
        return self._kops.xt_multi(self.X, U)

    def pass_b_multi(self, Z):
        """Batched ``X (c[:, None] .* Z)``."""
        return self._kops.x_cz_multi(self.X, self.coeffs, Z)

    def apply(self, u):
        """Full product; one-pass fused kernel when built fused."""
        if self.fused:
            return self._kops.x_c_xt_u(self.X, self.coeffs, u)
        return self.pass_b(self.pass_a(u))

    def apply_multi(self, U):
        """Batched full product; fused multi kernel when built fused."""
        if self.fused:
            return self._kops.x_c_xt_multi(self.X, self.coeffs, U)
        return self.pass_b_multi(self.pass_a_multi(U))


class EllOperator(HvpOperator):
    """Blocked-ELL sparse layout (``kernels/sparse_hvp.py``); the pair
    carries forward + transposed tilings, and ``fused=True`` completes
    both directions from the transposed layout alone."""

    layout = "ell"

    def __init__(self, ell: EllPair, coeffs, fused=False):
        from repro.kernels import ops as kops
        self._kops = kops
        self.ell = ell
        self.coeffs = coeffs
        self.fused = bool(fused)

    def pass_a(self, u):
        """``X^T u`` streaming the transposed ELL tiles."""
        return self._kops.ell_matvec(self.ell.dataT, self.ell.colsT, u)

    def pass_b(self, z):
        """``X (c .* z)`` streaming the forward ELL tiles."""
        return self._kops.ell_matvec(self.ell.data, self.ell.cols, z,
                                     self.coeffs)

    def pass_a_multi(self, U):
        """Batched ``X^T U`` over the transposed tiles."""
        return self._kops.ell_matmat(self.ell.dataT, self.ell.colsT, U)

    def pass_b_multi(self, Z):
        """Batched ``X (c[:, None] .* Z)`` over the forward tiles."""
        return self._kops.ell_matmat(self.ell.data, self.ell.cols, Z,
                                     self.coeffs)

    def apply(self, u):
        """Full product; the one-pass fused ELL kernel when built fused
        (with the forward layout as its VMEM-fallback twin)."""
        if self.fused:
            return self._kops.ell_hvp(self.ell.dataT, self.ell.colsT, u,
                                      self.coeffs,
                                      fwd=(self.ell.data, self.ell.cols))
        return self.pass_b(self.pass_a(u))

    def apply_multi(self, U):
        """Batched full product; fused multi ELL kernel when built fused."""
        if self.fused:
            return self._kops.ell_hvp_mm(self.ell.dataT, self.ell.colsT,
                                         U, self.coeffs,
                                         fwd=(self.ell.data,
                                              self.ell.cols))
        return self.pass_b_multi(self.pass_a_multi(U))


class SlotOperator(HvpOperator):
    """(id, value) slot layout (:class:`repro.data.sparse.SlotPair`): the
    densest rows as a dense head slab, every other nonzero a slot in the
    sample-major (pass A) and feature-major (pass B) layouts. Shares no
    logic with the tile kernels. ``fused=True`` runs the two passes:
    there is no one-pass form across two layouts."""

    layout = "slots"

    def __init__(self, pair: SlotPair, coeffs, fused=False):
        from repro.kernels import ops as kops
        self._kops = kops
        self.pair = pair
        self.coeffs = coeffs
        self.fused = bool(fused)

    def pass_a(self, u):
        """``X^T u`` from the head slab and the sample-major slots."""
        return self._kops.slot_xt(self.pair, u)

    def pass_b(self, z):
        """``X (c .* z)`` from the feature-major slots and the head slab."""
        return self._kops.slot_x(self.pair, z, self.coeffs)

    def pass_a_multi(self, U):
        """Batched ``X^T U``, one column at a time."""
        return self._kops.slot_xt(self.pair, U)

    def pass_b_multi(self, Z):
        """Batched ``X (c[:, None] .* Z)``, one column at a time."""
        return self._kops.slot_x(self.pair, Z, self.coeffs)


class StreamedHvpOperator(HvpOperator):
    """Out-of-core layout: the streaming solver supplies chunk-scan
    callables (each is one prefetched pass over the
    :class:`repro.data.store.ShardStore`), and this class gives them the
    common operator face. ``fused`` records whether the sample-partition
    scans run the one-pass chunk kernels (decided from the plan's global
    tile geometry via :meth:`repro.data.stream.StreamPlan.fused_hvp_fits`).
    """

    layout = "streamed"

    def __init__(self, apply: Callable, apply_multi: Callable,
                 pass_a: Callable | None = None,
                 pass_b: Callable | None = None,
                 pass_a_multi: Callable | None = None,
                 pass_b_multi: Callable | None = None,
                 fused: bool = False):
        self._apply = apply
        self._apply_multi = apply_multi
        self._pass_a = pass_a
        self._pass_b = pass_b
        self._pass_a_multi = pass_a_multi
        self._pass_b_multi = pass_b_multi
        self.fused = bool(fused)

    def _need(self, fn, name):
        if fn is None:
            raise UnsupportedHvpError(
                f"streamed operator was built without {name} (the "
                "sample-partition chunk scan completes both directions "
                "per chunk, so split passes do not exist there)")
        return fn

    def pass_a(self, u):
        """Pass A chunk scan (features partition streams)."""
        return self._need(self._pass_a, "pass_a")(u)

    def pass_b(self, z):
        """Pass B chunk scan (features partition streams)."""
        return self._need(self._pass_b, "pass_b")(z)

    def pass_a_multi(self, U):
        """Batched pass A chunk scan."""
        return self._need(self._pass_a_multi, "pass_a_multi")(U)

    def pass_b_multi(self, Z):
        """Batched pass B chunk scan."""
        return self._need(self._pass_b_multi, "pass_b_multi")(Z)

    def apply(self, u):
        """Full streamed product (one pass over the store)."""
        with obs.span("hvp.apply", multi=False, fused=self.fused):
            return self._apply(u)

    def apply_multi(self, U):
        """Batched full streamed product — one chunk read serves every
        column (the s-step x streaming synergy)."""
        with obs.span("hvp.apply", multi=True, fused=self.fused):
            return self._apply_multi(U)


class SoftmaxHvpOperator:
    """K-class softmax Hessian application as ONE multi-vector HVP.

    For multinomial softmax with weights ``W in R^{d x K}`` and
    probabilities ``P = softmax(X^T W)`` the local Hessian product on a
    direction ``U in R^{d x K}`` is

        ``H_loc U = X S,   S = P .* V - P .* rowsum(P .* V),  V = X^T U``

    — pass A and pass B are exactly the base operator's *multi-vector*
    passes (all K classes ride one kernel call each), with the class
    coupling ``S`` computed between them. Because the coupling sits
    between the passes, no one-pass fused kernel exists for softmax (the
    registry marks those cells unsupported).

    Args:
        base: any :class:`HvpOperator` over the local shard (built with
            ``coeffs=None`` — the coupling replaces the scalar d2
            coefficients).
        probs: ``(n_loc, K)`` class probabilities at the current iterate.
        weights: optional ``(n_loc,)`` sample mask/weights (padding).
    """

    family = "softmax"
    fused = False

    def __init__(self, base: HvpOperator, probs, weights=None):
        self.base = base
        self.layout = base.layout
        self.probs = probs
        self.weights = weights

    def coupling(self, V):
        """The softmax class coupling ``S = P.*V - P.*rowsum(P.*V)``
        (applied per trailing batch axis; sample weights folded in)."""
        P = self.probs
        if V.ndim == 3:
            P = P[:, :, None]
        PV = P * V
        S = PV - P * jnp.sum(PV, axis=1, keepdims=True)
        if self.weights is not None:
            wts = self.weights[:, None]
            if V.ndim == 3:
                wts = wts[:, :, None]
            S = wts * S
        return S

    def apply(self, U):
        """Local K-class Hessian product on one ``(d_loc, K)`` direction
        — one multi-vector pass per direction per HVP."""
        return self.base.pass_b_multi(self.coupling(
            self.base.pass_a_multi(U)))

    def apply_batch(self, U3):
        """Batched product on ``(d_loc, K, s)`` stacked directions: the
        s-step round's s directions x K classes all ride a single
        multi-vector kernel pass of width ``K*s``."""
        d, K, s = U3.shape
        V = self.base.pass_a_multi(U3.reshape(d, K * s))
        n = V.shape[0]
        S = self.coupling(V.reshape(n, K, s))
        return self.base.pass_b_multi(S.reshape(n, K * s)).reshape(d, K, s)


def make_local_operator(X_loc, coeffs, *, use_kernel: bool = False,
                        fused: bool = False,
                        partition: str = "samples") -> HvpOperator:
    """Build the local HVP operator for one shard — the ONE dispatch
    point the PCG loops use.

    Layout is inferred from the data: an :class:`repro.data.sparse.EllPair`
    selects :class:`EllOperator`, a :class:`repro.data.sparse.SlotPair`
    :class:`SlotOperator`; dense arrays select
    :class:`DenseKernelOperator` when ``use_kernel`` else
    :class:`DenseOperator`. Raises :class:`UnsupportedHvpError` (cell
    named) for combinations no operator implements — e.g. ``fused`` on
    the default dense path, which older revisions silently ignored.
    """
    if isinstance(X_loc, EllPair):
        resolve_cell("binary", "ell", partition, fused)
        return EllOperator(X_loc, coeffs, fused=fused)
    if isinstance(X_loc, SlotPair):
        resolve_cell("binary", "slots", partition, fused)
        return SlotOperator(X_loc, coeffs, fused=fused)
    if use_kernel:
        resolve_cell("binary", "dense_kernel", partition, fused)
        return DenseKernelOperator(X_loc, coeffs, fused=fused)
    resolve_cell("binary", "dense", partition, fused)
    return DenseOperator(X_loc, coeffs)
