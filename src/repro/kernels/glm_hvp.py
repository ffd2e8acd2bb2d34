"""Pallas TPU kernels for the GLM Hessian-vector product (paper hot spot).

The DiSCO PCG inner loop is dominated by  H u = X diag(c) X^T u / n + lam u
(Algorithms 2/3, step 4). On TPU we split it into two MXU matvec passes over
the same X tiles:

  pass A  z = X^T u        (kernel ``xt_u``)    — DiSCO-F communicates this
  pass B  y = X (c * z)    (kernel ``x_cz``)    — the c-scale is fused into
                                                   the second pass

Tiling: X (d, n) is blocked (bd, bn) with bd/bn multiples of 128 so both the
matvec contraction and the lane dimension are MXU/VREG aligned. Probe vectors
are carried as 2-D (1, d)/(n, 1) tiles because TPU Pallas requires >=2-D
operands with a 128-lane minor dimension. Accumulation over the contraction
grid axis happens in the f32 output block (revisited across the fastest grid
dimension), the standard Pallas reduction pattern.

VMEM budget per program (defaults bd = bn = 512, f32):
  X block 512*512*4 = 1 MiB; vectors <= 4 KiB; acc 2 KiB  — well under 16 MiB,
  leaving room for double buffering of the X stream from HBM.

The HVP is memory-bound (reads X twice per PCG iteration; arithmetic
intensity ~= 2 flops/byte per pass), so block shape mainly controls DMA
efficiency, not MXU occupancy — see EXPERIMENTS.md §Perf.

Multi-vector variants (the s-step PCG engine, core/pcg.py):

  pass A  Z = X^T U        (kernel ``xt_multi``)   U: (d, s) -> Z: (n, s)
  pass B  Y = X (c .* Z)   (kernel ``x_cz_multi``) Z: (n, s) -> Y: (d, s)

Same (bd, bn) tiling over X, but each X tile read from HBM is amortized
across all s probe vectors — arithmetic intensity rises from matvec
(~2 flops/byte) towards matmul (~2s flops/byte), and the two passes feed the
single fused all-reduce of the s-step round. ``s`` is padded to a
lane-friendly multiple (128) by the ops.py wrappers so the (bd, s)/(bn, s)
vector tiles stay VREG/MXU aligned.

One-pass fused variants (``x_c_xt_u`` / ``x_c_xt_multi``, docs/kernels.md):

When no collective separates the two passes (DiSCO-S local products,
single-shard DiSCO-F, the s-step zero-communication basis operators) the
whole product  y = X (c .* (X^T u))  runs from **panel-resident** tiles:
the grid walks column panels X[:, j] of shape (d, bn); each program
computes the local z_j = X[:, j]^T u, applies the phi'' scale, and
immediately accumulates y += X[:, j] (c_j .* z_j) from the *same* VMEM
panel — X streams from HBM ONCE per HVP instead of twice, halving the
traffic of this memory-bound kernel. ``x_c_xt_u`` (one vector) runs on
the VPU in f32 over X as it lies: full-extent panels of its own width
(``one_pass_block``), the ragged last panel masked in the kernel.
``x_c_xt_multi`` (s vectors) runs MXU dots over a pre-padded X; the
ops.py wrapper falls back to the two-pass kernels when its panel does
not fit the VMEM budget.

All kernels accumulate in f32 and return f32 (``out_dtype``) regardless
of the tile dtype, so bf16 tile storage (DiscoConfig.hvp_dtype) halves
bytes moved without compounding rounding error across PCG iterations.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# ---------------------------------------------------------------------------
# pass A:  z = X^T u
# ---------------------------------------------------------------------------

def _xt_u_kernel(x_ref, u_ref, z_ref):
    """Grid (nj, di): z[1, bn] += u[1, bd] @ X[bd, bn]; di fastest."""
    di = pl.program_id(1)

    @pl.when(di == 0)
    def _init():
        z_ref[...] = jnp.zeros_like(z_ref)

    x = x_ref[...]
    u = u_ref[...]
    z_ref[...] += jnp.dot(u, x, preferred_element_type=jnp.float32)


def xt_u(X, u, *, block_d=512, block_n=512, interpret=False,
         out_dtype=jnp.float32):
    """z = X^T u.   X: (d, n), u: (d,) -> z: (n,).  Shapes pre-padded.

    Accumulates in f32 and returns ``out_dtype`` (default f32) — casting
    to ``X.dtype`` would silently round the accumulator under bf16 tile
    storage.
    """
    d, n = X.shape
    assert d % block_d == 0 and n % block_n == 0, (X.shape, block_d, block_n)
    grid = (n // block_n, d // block_d)
    out = pl.pallas_call(
        _xt_u_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_d, block_n), lambda nj, di: (di, nj)),
            pl.BlockSpec((1, block_d), lambda nj, di: (0, di)),
        ],
        out_specs=pl.BlockSpec((1, block_n), lambda nj, di: (0, nj)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
        interpret=interpret,
    )(X, u.astype(X.dtype).reshape(1, d))
    return out.reshape(n).astype(out_dtype)


# ---------------------------------------------------------------------------
# pass B:  y = X (c * z)    (c-scale fused)
# ---------------------------------------------------------------------------

def _x_cz_kernel(x_ref, c_ref, z_ref, y_ref):
    """Grid (di, nj): y[bd, 1] += X[bd, bn] @ (c*z)[bn, 1]; nj fastest."""
    nj = pl.program_id(1)

    @pl.when(nj == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    x = x_ref[...]
    cz = (c_ref[...] * z_ref[...]).astype(x.dtype)       # fused scale
    y_ref[...] += jnp.dot(x, cz.T,
                          preferred_element_type=jnp.float32)


def x_cz(X, c, z, *, block_d=512, block_n=512, interpret=False,
         out_dtype=jnp.float32):
    """y = X @ (c * z).   X: (d, n), c/z: (n,) -> y: (d,) in ``out_dtype``."""
    d, n = X.shape
    assert d % block_d == 0 and n % block_n == 0, (X.shape, block_d, block_n)
    grid = (d // block_d, n // block_n)
    out = pl.pallas_call(
        _x_cz_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_d, block_n), lambda di, nj: (di, nj)),
            pl.BlockSpec((1, block_n), lambda di, nj: (0, nj)),
            pl.BlockSpec((1, block_n), lambda di, nj: (0, nj)),
        ],
        out_specs=pl.BlockSpec((block_d, 1), lambda di, nj: (di, 0)),
        out_shape=jax.ShapeDtypeStruct((d, 1), jnp.float32),
        interpret=interpret,
    )(X, c.reshape(1, n), z.reshape(1, n))
    return out.reshape(d).astype(out_dtype)


# ---------------------------------------------------------------------------
# multi-vector pass A:  Z = X^T U     (s probe vectors per X tile read)
# ---------------------------------------------------------------------------

def _xt_multi_kernel(x_ref, u_ref, z_ref):
    """Grid (nj, di): Z[bn, s] += X[bd, bn]^T @ U[bd, s]; di fastest.

    The contraction is expressed as a dot_general over dim 0 of both
    operands so no transposed copy of the X tile is materialized in VMEM.
    """
    di = pl.program_id(1)

    @pl.when(di == 0)
    def _init():
        z_ref[...] = jnp.zeros_like(z_ref)

    x = x_ref[...]
    u = u_ref[...]
    z_ref[...] += jax.lax.dot_general(
        x, u, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def xt_multi(X, U, *, block_d=512, block_n=512, interpret=False,
             out_dtype=jnp.float32):
    """Z = X^T U.   X: (d, n), U: (d, s) -> Z: (n, s) in ``out_dtype``.
    Shapes pre-padded (d, n to block multiples; s to a lane multiple)."""
    d, n = X.shape
    s = U.shape[1]
    assert U.shape[0] == d, (X.shape, U.shape)
    assert d % block_d == 0 and n % block_n == 0, (X.shape, block_d, block_n)
    grid = (n // block_n, d // block_d)
    out = pl.pallas_call(
        _xt_multi_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_d, block_n), lambda nj, di: (di, nj)),
            pl.BlockSpec((block_d, s), lambda nj, di: (di, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, s), lambda nj, di: (nj, 0)),
        out_shape=jax.ShapeDtypeStruct((n, s), jnp.float32),
        interpret=interpret,
    )(X, U.astype(X.dtype))
    return out.astype(out_dtype)


# ---------------------------------------------------------------------------
# multi-vector pass B:  Y = X (c .* Z)    (c-scale fused, s vectors)
# ---------------------------------------------------------------------------

def _x_cz_multi_kernel(x_ref, c_ref, z_ref, y_ref):
    """Grid (di, nj): Y[bd, s] += X[bd, bn] @ (c .* Z)[bn, s]; nj fastest."""
    nj = pl.program_id(1)

    @pl.when(nj == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    x = x_ref[...]
    cz = (c_ref[...] * z_ref[...]).astype(x.dtype)       # fused scale
    y_ref[...] += jnp.dot(x, cz, preferred_element_type=jnp.float32)


def x_cz_multi(X, c, Z, *, block_d=512, block_n=512, interpret=False,
               out_dtype=jnp.float32):
    """Y = X @ (c[:, None] * Z).   X: (d, n), c: (n,), Z: (n, s) ->
    (d, s) in ``out_dtype``.

    c rides along as an (n, 1) column so the scale broadcasts against the
    (bn, s) Z tile inside the kernel — one multiply fused into pass B, same
    as the single-vector ``x_cz``."""
    d, n = X.shape
    s = Z.shape[1]
    assert Z.shape[0] == n and c.shape == (n,), (X.shape, c.shape, Z.shape)
    assert d % block_d == 0 and n % block_n == 0, (X.shape, block_d, block_n)
    grid = (d // block_d, n // block_n)
    out = pl.pallas_call(
        _x_cz_multi_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_d, block_n), lambda di, nj: (di, nj)),
            pl.BlockSpec((block_n, 1), lambda di, nj: (nj, 0)),
            pl.BlockSpec((block_n, s), lambda di, nj: (nj, 0)),
        ],
        out_specs=pl.BlockSpec((block_d, s), lambda di, nj: (di, 0)),
        out_shape=jax.ShapeDtypeStruct((d, s), jnp.float32),
        interpret=interpret,
    )(X, c.reshape(n, 1), Z)
    return out.astype(out_dtype)


# ---------------------------------------------------------------------------
# fused one-pass:  y = X (c .* (X^T u))     (panel-resident, single X read)
# ---------------------------------------------------------------------------

# The one-pass kernel's plan. Each grid step holds one full-height column
# panel X[:, j0:j0+bn] in VMEM and walks it in lane groups whose row slice
# is ONE_PASS_SLICE_VREGS f32 vregs: 2,048 lanes of 8 f32 rows, or 1,024
# of 16 bf16 rows. Narrower slices leave the loops' latency unhidden: in
# the v5e compiler's schedule at epsilon's shape, slices of 4 vregs take
# 7.5 bundles per vreg of X over both passes, as long as HBM takes to
# deliver that vreg (4 KiB at 819 GB/s and 1.5 GHz); slices of 16 take
# 3.3. Where a group outgrows the panel budget it halves, down to a
# quarter. The panel width comes from the byte target of one panel
# buffer; two buffers are in flight (double buffering), and the kernel's
# scoped VMEM limit is ONE_PASS_VMEM_BYTES, well inside the 128 MiB of a
# v5e core.
ONE_PASS_SLICE_VREGS = 16
ONE_PASS_PANEL_BYTES = 20 << 20
ONE_PASS_VMEM_BYTES = 64 << 20
_LANE, _SUBLANE = 128, 8


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _slice_rows(itemsize: int) -> int:
    """Rows of one slice: one (8, 128) f32 tile, or one packed (16, 128)
    bf16 tile."""
    return _SUBLANE * (4 // itemsize)


def _group_lanes(itemsize: int) -> int:
    """Lanes of a full lane group: ONE_PASS_SLICE_VREGS f32 vregs a
    slice."""
    return ONE_PASS_SLICE_VREGS * 1024 // _slice_rows(itemsize)


def one_pass_vmem_bytes(d: int, block_n: int, itemsize: int) -> int:
    """VMEM the one-pass kernel holds at panel width ``block_n``: two X
    panel buffers, two each of the (d, 1) u and (d, 128) accumulator
    blocks (a (d, 1) block pads to 128 lanes), and two (1, block_n) c
    blocks (padded to 8 sublanes)."""
    panel = _round_up(d, _slice_rows(itemsize)) * block_n * itemsize
    vectors = 2 * _round_up(d, _SUBLANE) * _LANE * 4
    return 2 * panel + 2 * vectors + 2 * _SUBLANE * block_n * 4


def one_pass_block(d: int, n: int, itemsize: int) -> int:
    """The one-pass kernel's panel width for a (d, n) X of ``itemsize``
    bytes: the most whole lane groups whose panel stays within
    ``ONE_PASS_PANEL_BYTES``, and no wider than n needs. 0 when not a
    quarter group fits, or the working set passes ``ONE_PASS_VMEM_BYTES``:
    then the product takes the two passes."""
    full = _group_lanes(itemsize)
    for group in (full, full // 2, full // 4):
        bn = ONE_PASS_PANEL_BYTES // (d * itemsize) // group * group
        if bn:
            bn = min(bn, _round_up(max(n, 1), group))
            break
    else:
        return 0
    if one_pass_vmem_bytes(d, bn, itemsize) > ONE_PASS_VMEM_BYTES:
        return 0
    return bn


def _x_c_xt_u_kernel(x_ref, c_ref, u_ref, y_ref, *, group, valid_last):
    """Grid (nj,): both HVP directions from the VMEM-resident column panel
    x_ref (d, bn), lane group by lane group, on the VPU in f32:

      pass A  z = sum over rows of X[:, g] * u        (an (r, group)
              accumulator over r-row slices, then one sublane reduce)
      scale   cz = c[g] * z
      pass B  y_ref (d, 128) += X[:, g] * cz, the group's 128-lane
              chunks added together; the lanes are summed once, by the
              caller, after the last panel.

    r is 8 rows for f32 panels and 16 for bf16 (one packed tile), which
    are upcast in VMEM. ``valid_last`` is the count of columns of the
    last panel that lie inside X (0 when n is a multiple of bn): there,
    lanes past it count as zero whatever the out-of-bounds block holds.
    """
    d, bn = x_ref.shape
    r = _slice_rows(x_ref.dtype.itemsize)
    d_main = d // r * r
    f32 = jnp.float32
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    def chunk_sum(p):
        acc = p[:, :_LANE]
        for k in range(1, group // _LANE):
            acc = acc + p[:, k * _LANE:(k + 1) * _LANE]
        return acc

    def panel(valid):
        def one_group(g, carry):
            g0 = pl.multiple_of(g * group, group)
            cols = pl.ds(g0, group)
            keep = None
            if valid < bn:
                lane = g0 + jax.lax.broadcasted_iota(jnp.int32, (1, group), 1)
                keep = lane < valid

            def rows_of(start, size):
                x = x_ref[pl.ds(start, size), cols].astype(f32)
                return x if keep is None else jnp.where(keep, x, 0.0)

            def a_step(i, acc):
                s = pl.multiple_of(i * r, r)
                return acc + rows_of(s, r) * u_ref[pl.ds(s, r), :]

            acc = jnp.zeros((r, group), f32)
            if d_main:
                acc = jax.lax.fori_loop(0, d_main // r, a_step, acc)
            z = jnp.sum(acc, axis=0, keepdims=True)
            if d > d_main:
                tail = rows_of(d_main, d - d_main) * u_ref[d_main:, :]
                z = z + jnp.sum(tail, axis=0, keepdims=True)
            cz = c_ref[:, cols].astype(f32) * z
            if keep is not None:
                cz = jnp.where(keep, cz, 0.0)
            czr = jnp.broadcast_to(cz, (r, group))

            def b_step(i, carry):
                s = pl.multiple_of(i * r, r)
                y_ref[pl.ds(s, r), :] += chunk_sum(rows_of(s, r) * czr)
                return carry

            if d_main:
                jax.lax.fori_loop(0, d_main // r, b_step, 0)
            if d > d_main:
                y_ref[d_main:, :] += chunk_sum(
                    rows_of(d_main, d - d_main) * czr[: d - d_main])
            return carry

        jax.lax.fori_loop(0, -(-valid // group), one_group, 0)

    if valid_last:
        last = pl.num_programs(0) - 1
        pl.when(j < last)(lambda: panel(bn))
        pl.when(j == last)(lambda: panel(valid_last))
    else:
        panel(bn)


def x_c_xt_u(X, c, u, *, block_n=None, interpret=False,
             out_dtype=jnp.float32):
    """y = X (c .* (X^T u)) in ONE streaming pass over X.

    X: (d, n) as it lies (f32, or bf16 upcast in VMEM), c: (n,), u: (d,).
    Nothing X-sized is padded, copied or transposed: a panel's first
    dimension is all of d, and the ragged last panel is masked in the
    kernel. ``block_n`` (a multiple of 128) defaults to
    :func:`one_pass_block`, which must not be 0. Products and sums stay
    in f32 on the VPU (no MXU pass rounds X or the vectors to bf16);
    returns ``out_dtype``.
    """
    d, n = X.shape
    bn = block_n or one_pass_block(d, n, X.dtype.itemsize)
    assert bn and bn % _LANE == 0, (X.shape, X.dtype, block_n)
    group = math.gcd(bn, _group_lanes(X.dtype.itemsize))
    kernel = functools.partial(_x_c_xt_u_kernel, group=group,
                               valid_last=n % bn)
    out = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(n, bn),),
        in_specs=[
            pl.BlockSpec((d, bn), lambda nj: (0, nj)),
            pl.BlockSpec((1, bn), lambda nj: (0, nj)),
            pl.BlockSpec((d, 1), lambda nj: (0, 0)),
        ],
        out_specs=pl.BlockSpec((d, _LANE), lambda nj: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((d, _LANE), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=ONE_PASS_VMEM_BYTES),
        interpret=interpret,
        name="dense_hvp_one_pass",
    )(X, c.astype(jnp.float32).reshape(1, n),
      u.astype(jnp.float32).reshape(d, 1))
    return jnp.sum(out, axis=1).astype(out_dtype)


def _x_c_xt_multi_kernel(x_ref, c_ref, u_ref, y_ref):
    """Grid (nj,): multi-vector twin — Z = X_j^T U from the resident
    panel, then Y(d, s) += X_j (c_j .* Z) from the same tiles."""
    nj = pl.program_id(0)

    @pl.when(nj == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    x = x_ref[...]                                       # (d, bn)
    z = jax.lax.dot_general(
        x, u_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)              # (bn, s)
    cz = (c_ref[...] * z).astype(x.dtype)                # c: (bn, 1)
    y_ref[...] += jnp.dot(x, cz, preferred_element_type=jnp.float32)


def x_c_xt_multi(X, c, U, *, block_n=512, interpret=False,
                 out_dtype=jnp.float32):
    """Y = X (c .* (X^T U)) in ONE streaming pass over X (s vectors).

    X: (d, n) with d a multiple of 128 and n a multiple of ``block_n``,
    c/U pre-padded to match; U: (d, s) with s padded to a lane multiple
    by the ops.py wrapper, which also checks that the panel fits VMEM.
    One panel read serves all s probe vectors of both passes — the
    s-step round's batched HVP at half its two-pass HBM traffic.
    """
    d, n = X.shape
    s = U.shape[1]
    assert U.shape[0] == d and c.shape == (n,), (X.shape, c.shape, U.shape)
    assert d % 128 == 0 and n % block_n == 0, (X.shape, block_n)
    out = pl.pallas_call(
        _x_c_xt_multi_kernel,
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((d, block_n), lambda nj: (0, nj)),
            pl.BlockSpec((block_n, 1), lambda nj: (nj, 0)),
            pl.BlockSpec((d, s), lambda nj: (0, 0)),
        ],
        out_specs=pl.BlockSpec((d, s), lambda nj: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((d, s), jnp.float32),
        interpret=interpret,
    )(X, c.reshape(n, 1), U.astype(X.dtype))
    return out.astype(out_dtype)
