"""Pallas TPU kernels for the blocked-ELL sparse GLM HVP.

The dense HVP kernels (glm_hvp.py) stream every tile of X; on the paper's
sparse datasets (rcv1, news20, splice-site) most tiles are empty — the
sparse path streams only the surviving tiles of the blocked-ELL layout
built by :mod:`repro.data.sparse`:

    data : (nb, W, br, bc)   dense tiles, per row-block a padded list
    cols : (nb, W) int32     column-block index of each tile

Kernel structure (the standard TPU block-sparse pattern): the grid is the
*static* ``(nb, W)`` tile list — ``data[i, k]`` is plain block indexing —
and only the **vector** block each tile multiplies is dynamic. ``cols``
rides in as a scalar-prefetch operand (``PrefetchScalarGridSpec``), so the
index maps of the vector operands can read ``cols[i, k]`` and the DMA for
the right ``(bc,)`` vector chunk is issued ahead of the compute, exactly
like a dense gather. Padding slots carry ``cols = 0`` with an all-zero
tile: they fetch (and discard) a real vector block, keeping the grid
rectangular with zero effect on the result.

Both generalized matvec directions run through the same kernel: ``X @ v``
streams the forward layout, ``X^T u`` streams the transposed layout
(tiles stored pre-transposed), so every pass accumulates into its output
row-block with the usual revisit-over-fastest-grid-axis reduction. The
optional per-input-element scale ``c`` fuses ``X @ (c .* v)`` — the
phi''-coefficient multiply of the HVP — into the tile pass, mirroring the
dense ``x_cz`` kernels.

Multi-vector variants (``*_mm``) amortize each tile read over ``s`` probe
vectors for the s-step PCG engine, identical to the dense
``xt_multi``/``x_cz_multi`` story (DESIGN.md §2).

Fused one-pass HVP (``ell_hvp`` / ``ell_hvp_mm``, docs/kernels.md): when
no collective separates the two HVP directions, the whole
``y = A (c .* (A^T u))`` runs from the transposed layout alone — the
grid walks its row-blocks, each program holds one block's entire padded
tile row in VMEM, computes that block's ``z`` slice, scales it, and
scatters the pass-B contributions from the *same resident tiles*. The
forward layout is never read: tile HBM traffic halves versus the
two-pass pair (and halves again under bf16 tile storage,
``DiscoConfig.hvp_dtype``). All kernels accumulate in f32 and return
``out_dtype`` (default f32) regardless of the tile dtype.

Cost model: one pass touches ``nb * W`` tiles — so the per-shard work is
proportional to the *padded* tile count. The LPT partitioner balances
per-shard nnz (the straggler time between barrier collectives); this
usually also lowers the shared padded width, except when one tile-dense
row-block saturates it for every assignment (docs/partitioning.md).

VMEM per program: one ``(br, bc)`` tile + ``(bc,)``/``(bc, s)`` vector
blocks + the ``(br,)``/``(br, s)`` accumulator — tiny; defaults
``br = bc = 128`` keep every operand lane-aligned (TPU wants the minor
two dims in multiples of (8, 128); interpret mode accepts any size).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _vec_block(size, block_of):
    """Block spec of one ``(1, size)`` chunk of a vector stored as
    ``(n_chunks, 1, size)``; ``block_of`` maps the grid indices (and the
    prefetched ``cols``) to the chunk index.

    The leading axis is squeezed out of the block, so the kernel sees a
    ``(1, size)`` ref, and the last two block dims equal the array's —
    the TPU tiling rule that a ``(1, size)`` block of an
    ``(n_chunks, size)`` array would break.
    """
    return pl.BlockSpec((None, 1, size),
                        lambda *idx: (block_of(*idx), 0, 0))


# ---------------------------------------------------------------------------
# generalized blocked-ELL matvec:  y = A (c .* v)
# ---------------------------------------------------------------------------

def _ell_mv_kernel(cols_ref, x_ref, c_ref, v_ref, y_ref):
    """Grid (nb, W), k fastest: y[i] += tile[i,k] @ (c*v)[cols[i,k]]."""
    del cols_ref  # consumed by the index maps (scalar prefetch)
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    x = x_ref[0, 0]                                   # (br, bc)
    cv = (c_ref[...] * v_ref[...]).astype(x.dtype)    # (1, bc)
    y_ref[...] += jnp.dot(x, cv.T,
                          preferred_element_type=jnp.float32).T


def ell_mv(data, cols, v, c=None, *, interpret=False,
           out_dtype=jnp.float32):
    """y = A @ (c .* v) for a blocked-ELL operand.

    data : (nb, W, br, bc) tiles;  cols : (nb, W) int32
    v    : (ncb * bc,) input vector (padded length)
    c    : optional (ncb * bc,) per-element scale (fused in-kernel)
    returns (nb * br,) in ``out_dtype`` (default f32 — the in-kernel
    accumulator dtype; casting to ``data.dtype`` would silently round it
    under bf16 tile storage)
    """
    nb, w, br, bc = data.shape
    assert v.shape[0] % bc == 0, (v.shape, bc)
    ncb = v.shape[0] // bc
    if c is None:
        c = jnp.ones(v.shape, jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb, w),
        in_specs=[
            pl.BlockSpec((1, 1, br, bc), lambda i, k, cols: (i, k, 0, 0)),
            _vec_block(bc, lambda i, k, cols: cols[i, k]),
            _vec_block(bc, lambda i, k, cols: cols[i, k]),
        ],
        out_specs=_vec_block(br, lambda i, k, cols: i),
    )
    out = pl.pallas_call(
        _ell_mv_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nb, 1, br), jnp.float32),
        interpret=interpret,
    )(cols, data, c.reshape(ncb, 1, bc), v.reshape(ncb, 1, bc))
    return out.reshape(nb * br).astype(out_dtype)


# ---------------------------------------------------------------------------
# multi-vector:  Y = A (c .* V)     (s probe vectors per tile read)
# ---------------------------------------------------------------------------

def _ell_mm_kernel(cols_ref, x_ref, c_ref, v_ref, y_ref):
    """Grid (nb, W), k fastest: Y[i] += tile[i,k] @ (c .* V)[cols[i,k]]."""
    del cols_ref
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    x = x_ref[0, 0]                                   # (br, bc)
    v = v_ref[...]                                    # (bc, s)
    cv = (c_ref[...].reshape(-1, 1) * v).astype(x.dtype)
    y_ref[0] += jnp.dot(x, cv, preferred_element_type=jnp.float32)


def ell_mm(data, cols, V, c=None, *, interpret=False,
           out_dtype=jnp.float32):
    """Y = A @ (c[:, None] .* V) for a blocked-ELL operand.

    V : (ncb * bc, s) probe block -> returns (nb * br, s) in
    ``out_dtype`` (default f32, the accumulator dtype). Each tile read
    from HBM is amortized over all ``s`` columns (the s-step engine's
    arithmetic-intensity win, same as the dense multi-vector kernels).
    """
    nb, w, br, bc = data.shape
    n_in, s = V.shape
    assert n_in % bc == 0, (V.shape, bc)
    ncb = n_in // bc
    if c is None:
        c = jnp.ones((n_in,), jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb, w),
        in_specs=[
            pl.BlockSpec((1, 1, br, bc), lambda i, k, cols: (i, k, 0, 0)),
            _vec_block(bc, lambda i, k, cols: cols[i, k]),
            pl.BlockSpec((bc, s), lambda i, k, cols: (cols[i, k], 0)),
        ],
        out_specs=pl.BlockSpec((1, br, s), lambda i, k, cols: (i, 0, 0)),
    )
    out = pl.pallas_call(
        _ell_mm_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nb, br, s), jnp.float32),
        interpret=interpret,
    )(cols, data, c.reshape(ncb, 1, bc), V)
    return out.reshape(nb * br, s).astype(out_dtype)


# ---------------------------------------------------------------------------
# fused one-pass HVP:  y = A (c .* (A^T u))  from the transposed layout
# ---------------------------------------------------------------------------

def _ell_hvp_kernel(cols_ref, xT_ref, c_ref, u_ref, y_ref):
    """Grid (ncb,): sample-block j's whole transposed tile row resident.

    Pass A runs a static loop over the row's WT tiles accumulating
    z = A^T u for this block (gathering u blocks by the prefetched
    column ids), the phi'' scale is applied, and pass B walks the SAME
    resident tiles scattering y[cols[j, k]] += cz @ tile — each tile is
    read from HBM exactly once for the whole HVP.
    """
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    wt, bc = xT_ref.shape[1], xT_ref.shape[2]
    z = jnp.zeros((1, bc), jnp.float32)
    for k in range(wt):
        t = xT_ref[0, k]                                  # (bc, br)
        ub = u_ref[pl.ds(cols_ref[j, k], 1), :]           # (1, br)
        z = z + jax.lax.dot_general(
            ub, t, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    cz = (c_ref[...] * z).astype(xT_ref.dtype)            # (1, bc)
    for k in range(wt):
        t = xT_ref[0, k]
        y_ref[pl.ds(cols_ref[j, k], 1), :] += jnp.dot(
            cz, t, preferred_element_type=jnp.float32)


def ell_hvp(dataT, colsT, u, c=None, *, interpret=False,
            out_dtype=jnp.float32):
    """One-pass blocked-ELL HVP: y = A (c .* (A^T u)).

    dataT/colsT : the *transposed* blocked-ELL layout of the local
    operand A (row-blocks = A's column blocks), shapes (ncb, WT, bc, br)
    / (ncb, WT). u : (nrb * br,) over A's padded row axis; c : optional
    (ncb * bc,) phi'' scale over A's padded column axis. Returns
    (nrb * br,) in ``out_dtype`` (f32 accumulation).

    The forward layout is never touched — tile HBM traffic halves
    versus the two-pass ``ell_mv`` pair. VMEM per program is the whole
    (WT, bc, br) tile row plus the full u and y vectors; the ops.py
    wrapper enforces the budget and falls back when it is exceeded.
    """
    ncb, wt, bc, br = dataT.shape
    assert u.shape[0] % br == 0, (u.shape, br)
    nrb = u.shape[0] // br
    if c is None:
        c = jnp.ones((ncb * bc,), jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(ncb,),
        in_specs=[
            pl.BlockSpec((1, wt, bc, br), lambda j, cols: (j, 0, 0, 0)),
            _vec_block(bc, lambda j, cols: j),
            pl.BlockSpec((nrb, br), lambda j, cols: (0, 0)),
        ],
        out_specs=pl.BlockSpec((nrb, br), lambda j, cols: (0, 0)),
    )
    out = pl.pallas_call(
        _ell_hvp_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nrb, br), jnp.float32),
        interpret=interpret,
    )(colsT, dataT, c.reshape(ncb, 1, bc),
      u.astype(dataT.dtype).reshape(nrb, br))
    return out.reshape(nrb * br).astype(out_dtype)


def _ell_hvp_mm_kernel(cols_ref, xT_ref, c_ref, u_ref, y_ref):
    """Multi-vector twin of :func:`_ell_hvp_kernel`: Z = A_j^T U from
    the resident tile row, then Y[cols[j, k]] += tile^T @ (c .* Z)."""
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    wt, bc = xT_ref.shape[1], xT_ref.shape[2]
    s = u_ref.shape[2]
    z = jnp.zeros((bc, s), jnp.float32)
    for k in range(wt):
        t = xT_ref[0, k]                                  # (bc, br)
        ub = u_ref[cols_ref[j, k]]                        # (br, s)
        z = z + jnp.dot(t, ub, preferred_element_type=jnp.float32)
    cz = (c_ref[...].reshape(-1, 1) * z).astype(xT_ref.dtype)
    for k in range(wt):
        t = xT_ref[0, k]
        y_ref[cols_ref[j, k]] += jax.lax.dot_general(
            t, cz, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def ell_hvp_mm(dataT, colsT, U, c=None, *, interpret=False,
               out_dtype=jnp.float32):
    """One-pass blocked-ELL multi-vector HVP: Y = A (c .* (A^T U)).

    U : (nrb * br, s) probe block -> (nrb * br, s) in ``out_dtype``.
    Same residency contract as :func:`ell_hvp`; each resident tile
    serves both directions of all ``s`` probe vectors — the s-step
    round's sparse HVP at half its two-pass tile traffic.
    """
    ncb, wt, bc, br = dataT.shape
    n_out, s = U.shape
    assert n_out % br == 0, (U.shape, br)
    nrb = n_out // br
    if c is None:
        c = jnp.ones((ncb * bc,), jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(ncb,),
        in_specs=[
            pl.BlockSpec((1, wt, bc, br), lambda j, cols: (j, 0, 0, 0)),
            _vec_block(bc, lambda j, cols: j),
            pl.BlockSpec((nrb, br, s), lambda j, cols: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((nrb, br, s), lambda j, cols: (0, 0, 0)),
    )
    out = pl.pallas_call(
        _ell_hvp_mm_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nrb, br, s), jnp.float32),
        interpret=interpret,
    )(colsT, dataT, c.reshape(ncb, 1, bc),
      U.astype(dataT.dtype).reshape(nrb, br, s))
    return out.reshape(nrb * br, s).astype(out_dtype)
