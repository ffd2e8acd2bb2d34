"""Sparse data substrate: CSR + blocked-ELL containers, streaming libsvm.

The paper's headline datasets (rcv1, news20, the 273 GB splice-site set)
are *sparse*; the dense ``(d, n)`` arrays of :mod:`repro.data.libsvm` only
work for the laptop-scale reproductions. This module is the sparse
counterpart the partitioning/load-balancing subsystem runs on:

* :class:`CSRMatrix` — host-side CSR in the repo's **feature-major**
  convention (rows are features, columns are samples; see
  docs/architecture.md#shape-conventions), with the row/column nnz
  histograms the nnz-aware partitioner (:mod:`repro.data.partition`)
  balances on.
* :class:`BlockedEll` — a tile-granular blocked-ELL layout: the matrix is
  cut into ``(block_rows, block_cols)`` dense tiles, empty tiles are
  dropped, and each row-block keeps a fixed-width (padded) list of its
  surviving tiles. This is the layout the Pallas sparse HVP kernels
  (:mod:`repro.kernels.sparse_hvp`) stream: tile lookups are plain array
  indexing, so the kernel grid stays static and only the *vector* block
  picked per tile is dynamic (scalar-prefetched column index).
* :func:`load_libsvm_sparse` — a streaming, chunked libsvm reader with
  O(nnz + chunk) peak memory, replacing the all-in-RAM dense path for
  sparse datasets.
* :func:`make_sparse_glm_data` — synthetic power-law-sparsity GLM data
  (feature popularity ~ rank^-alpha, the regime where equal-width
  sharding straggles and LPT balancing pays; docs/partitioning.md).

Device-side, a shard's pair of blocked-ELL layouts (forward for
``X @ v``, transposed for ``X^T u``) travels through ``shard_map`` as the
:class:`EllPair` pytree of four arrays.
"""
from __future__ import annotations

import dataclasses
from typing import Iterator, NamedTuple

import jax
import numpy as np


# ---------------------------------------------------------------------------
# CSR container (host side, numpy)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CSRMatrix:
    """Compressed-sparse-row matrix in the feature-major ``(d, n)`` layout.

    Rows index **features**, columns index **samples** — the same
    convention as every dense ``X`` in the repo (see
    docs/architecture.md#shape-conventions). ``indptr`` has length
    ``d + 1``; ``indices[indptr[i]:indptr[i+1]]`` are the sample indices
    holding nonzeros of feature ``i``.
    """

    indptr: np.ndarray   # (d + 1,) int64
    indices: np.ndarray  # (nnz,) int32 column (sample) indices
    data: np.ndarray     # (nnz,) values
    shape: tuple[int, int]

    @property
    def nnz(self) -> int:
        """Number of stored nonzeros."""
        return int(self.data.shape[0])

    @property
    def dtype(self):
        return self.data.dtype

    # -- construction ------------------------------------------------------
    @classmethod
    def from_dense(cls, X: np.ndarray, dtype=np.float32) -> "CSRMatrix":
        """Build from a dense ``(d, n)`` array, dropping exact zeros."""
        X = np.asarray(X)
        d, n = X.shape
        mask = X != 0
        counts = mask.sum(axis=1)
        indptr = np.zeros(d + 1, np.int64)
        np.cumsum(counts, out=indptr[1:])
        rows, cols = np.nonzero(mask)
        order = np.lexsort((cols, rows))
        return cls(indptr=indptr,
                   indices=cols[order].astype(np.int32),
                   data=X[rows[order], cols[order]].astype(dtype),
                   shape=(d, n))

    @classmethod
    def from_coo(cls, rows, cols, vals, shape, dtype=np.float32
                 ) -> "CSRMatrix":
        """Build from COO triplets (duplicates must not occur)."""
        d, n = shape
        rows = np.asarray(rows, np.int64)
        cols = np.asarray(cols, np.int64)
        vals = np.asarray(vals)
        order = np.lexsort((cols, rows))
        rows, cols, vals = rows[order], cols[order], vals[order]
        counts = np.bincount(rows, minlength=d)
        indptr = np.zeros(d + 1, np.int64)
        np.cumsum(counts, out=indptr[1:])
        return cls(indptr=indptr, indices=cols.astype(np.int32),
                   data=vals.astype(dtype), shape=(d, n))

    # -- dense / histogram views ------------------------------------------
    def todense(self) -> np.ndarray:
        """Materialize the dense ``(d, n)`` array (tests / small data)."""
        d, n = self.shape
        X = np.zeros((d, n), self.data.dtype)
        rows = np.repeat(np.arange(d), np.diff(self.indptr))
        X[rows, self.indices] = self.data
        return X

    def nnz_per_row(self) -> np.ndarray:
        """(d,) nonzeros per feature — what DiSCO-F load-balances on."""
        return np.diff(self.indptr).astype(np.int64)

    def nnz_per_col(self) -> np.ndarray:
        """(n,) nonzeros per sample — what DiSCO-S load-balances on."""
        return np.bincount(self.indices, minlength=self.shape[1]
                           ).astype(np.int64)

    # -- structural ops ----------------------------------------------------
    def take_rows(self, idx: np.ndarray) -> "CSRMatrix":
        """New CSR holding rows ``idx`` in the given order (a row permute
        when ``idx`` is a permutation of ``range(d)``). Indices ``>= d``
        select synthetic *empty* rows — the padding slots a
        :class:`repro.data.partition.Partition` permutation may contain.
        """
        idx = np.asarray(idx, np.int64)
        d = self.shape[0]
        starts = np.where(idx < d, self.indptr[np.minimum(idx, d - 1)], 0)
        ends = np.where(idx < d, self.indptr[np.minimum(idx, d - 1) + 1], 0)
        counts = ends - starts
        indptr = np.zeros(len(idx) + 1, np.int64)
        np.cumsum(counts, out=indptr[1:])
        gather = np.concatenate(
            [np.arange(s, e) for s, e in zip(starts, ends)]
        ) if len(idx) else np.zeros(0, np.int64)
        gather = gather.astype(np.int64)
        return CSRMatrix(indptr=indptr, indices=self.indices[gather],
                         data=self.data[gather],
                         shape=(len(idx), self.shape[1]))

    def take_cols_dense(self, idx: np.ndarray) -> np.ndarray:
        """Dense ``(d, len(idx))`` slab of the selected sample columns —
        how the tau preconditioner samples are materialized for a sparse
        solve (tau ~ 100, so the slab is small). One O(nnz) mask pass;
        no transpose or sort."""
        idx = np.asarray(idx, np.int64)
        d, n = self.shape
        pos = np.full(n, -1, np.int64)
        pos[idx] = np.arange(len(idx))
        keep = pos[self.indices] >= 0
        rows = np.repeat(np.arange(d), np.diff(self.indptr))[keep]
        out = np.zeros((d, len(idx)), self.data.dtype)
        out[rows, pos[self.indices[keep]]] = self.data[keep]
        return out

    def transpose(self) -> "CSRMatrix":
        """CSR of X^T — an ``(n, d)`` matrix with rows = samples."""
        d, n = self.shape
        rows = np.repeat(np.arange(d), np.diff(self.indptr))
        return CSRMatrix.from_coo(self.indices, rows, self.data, (n, d),
                                  dtype=self.data.dtype)

    def xt_dot(self, w: np.ndarray) -> np.ndarray:
        """Host-side margins ``X^T w`` of a feature-major ``(d, n)`` CSR.

        One O(nnz) scatter-add pass, no transpose — the sparse half of
        :meth:`repro.core.glm.GLMProblem.decision_function` and the
        NumPy scoring oracle of :mod:`repro.glm_serve.scoring`.
        Accumulates in float64 and casts back to the value dtype.
        """
        w = np.asarray(w)
        d, n = self.shape
        rows = np.repeat(np.arange(d), np.diff(self.indptr))
        out = np.zeros(n, np.float64)
        np.add.at(out, self.indices,
                  self.data.astype(np.float64) * w.astype(np.float64)[rows])
        return out.astype(self.data.dtype)


# ---------------------------------------------------------------------------
# blocked-ELL tiles (host side) + the device-side pytree
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class BlockedEll:
    """Tile-granular blocked-ELL: per row-block, a padded list of tiles.

    ``data[i, k]`` is the dense ``(block_rows, block_cols)`` tile of the
    ``k``-th surviving column-block of row-block ``i``; ``cols[i, k]`` its
    column-block index. Padding slots carry ``cols = 0`` and an all-zero
    tile, so they contribute nothing to products. The padded logical shape
    is ``(n_row_blocks * block_rows, n_col_blocks * block_cols)``.

    ``width`` (the ELL fan-out, ``data.shape[1]``) is the padded-compute
    face of load imbalance: all shards pad to the *global* max width, so
    one nnz-heavy shard inflates every shard's tile stream. Balancing nnz
    usually shrinks it too, unless a single tile-dense row-block pins the
    maximum for any assignment (docs/partitioning.md).
    """

    data: np.ndarray   # (n_row_blocks, width, block_rows, block_cols)
    cols: np.ndarray   # (n_row_blocks, width) int32
    shape: tuple[int, int]          # logical (unpadded) shape
    block: tuple[int, int]          # (block_rows, block_cols)

    @property
    def width(self) -> int:
        return int(self.data.shape[1])

    @property
    def n_row_blocks(self) -> int:
        return int(self.data.shape[0])

    @property
    def n_col_blocks(self) -> int:
        return max(-(-self.shape[1] // self.block[1]), 1)

    def todense(self) -> np.ndarray:
        """Dense padded array, then cropped to the logical shape."""
        nb, w, br, bc = self.data.shape
        R, C = nb * br, self.n_col_blocks * bc
        X = np.zeros((R, C), self.data.dtype)
        for i in range(nb):
            for k in range(w):
                c = int(self.cols[i, k])
                X[i * br:(i + 1) * br, c * bc:(c + 1) * bc] += \
                    self.data[i, k]
        return X[: self.shape[0], : self.shape[1]]


def ell_from_csr(csr: CSRMatrix, block_rows: int, block_cols: int,
                 width: int | None = None) -> BlockedEll:
    """Cut ``csr`` into tiles and keep only the nonempty ones.

    ``width`` pads the per-row-block tile lists to a fixed fan-out (>= the
    natural max); shards of one mesh pass the global max so their stacked
    arrays are uniform. Zero-width matrices get ``width=1`` of zero tiles
    so downstream kernels always have a (no-op) tile to stream.
    """
    d, n = csr.shape
    br, bc = block_rows, block_cols
    nrb, ncb = -(-d // br), max(-(-n // bc), 1)
    rows = np.repeat(np.arange(d), np.diff(csr.indptr))
    rb, cb = rows // br, csr.indices // bc

    # per row-block: sorted unique column-blocks
    tile_ids = rb.astype(np.int64) * ncb + cb
    uniq = np.unique(tile_ids)
    urb, ucb = uniq // ncb, uniq % ncb
    per_block = np.bincount(urb, minlength=nrb)
    natural = int(per_block.max()) if len(uniq) else 0
    w = max(width or 0, natural, 1)
    if width is not None and width < natural:
        raise ValueError(f"width {width} < natural max width {natural}")

    data = np.zeros((nrb, w, br, bc), csr.data.dtype)
    cols = np.zeros((nrb, w), np.int32)
    # slot of each unique tile within its row-block (uniq is sorted, so
    # tiles of one row-block occupy a contiguous run starting at starts[r])
    starts = np.zeros(nrb + 1, np.int64)
    np.cumsum(per_block, out=starts[1:])
    cols[urb, np.arange(len(uniq)) - starts[urb]] = ucb.astype(np.int32)

    # scatter nonzeros into their tiles
    slot = np.searchsorted(uniq, tile_ids) - starts[rb]
    data[rb, slot, rows % br, csr.indices % bc] = csr.data
    return BlockedEll(data=data, cols=cols, shape=(d, n), block=(br, bc))


def ell_tile_widths(csr: CSRMatrix, block_rows: int, block_cols: int
                    ) -> tuple[int, int]:
    """Natural blocked-ELL widths of a matrix, forward and transposed.

    Returns ``(w_fwd, w_tr)`` — the max surviving tiles per row-block of
    ``ell_from_csr(csr, block_rows, block_cols)`` and of
    ``ell_from_csr(csr.T, block_cols, block_rows)`` — computed from the
    index structure alone (no tile data is built). The streaming planner
    (:mod:`repro.data.stream`) uses this to fix the global padded widths
    of every chunk before any chunk values are read; both results are at
    least 1 (the zero-tile floor ``ell_from_csr`` also applies).
    """
    nrb = -(-csr.shape[0] // block_rows)
    ncb = max(-(-csr.shape[1] // block_cols), 1)
    rows = np.repeat(np.arange(csr.shape[0]), np.diff(csr.indptr))
    rb = rows // block_rows
    cb = np.asarray(csr.indices, np.int64) // block_cols
    uniq = np.unique(rb.astype(np.int64) * ncb + cb)
    if not len(uniq):
        return 1, 1
    w_fwd = int(np.bincount(uniq // ncb, minlength=max(nrb, 1)).max())
    w_tr = int(np.bincount(uniq % ncb, minlength=ncb).max())
    return max(w_fwd, 1), max(w_tr, 1)


def pad_csr_rows(csr: CSRMatrix, n_rows: int) -> CSRMatrix:
    """Extend a CSR slab with trailing empty rows up to ``n_rows``.

    How a ragged final store chunk (:mod:`repro.data.store`) is brought
    to the uniform ``chunk_size`` width the streaming pipeline's static
    shapes require; a no-op when the slab is already full-width.
    """
    have = csr.shape[0]
    if have == n_rows:
        return csr
    if have > n_rows:
        raise ValueError(f"cannot pad {have} rows down to {n_rows}")
    indptr = np.concatenate(
        [np.asarray(csr.indptr, np.int64),
         np.full(n_rows - have, int(csr.indptr[-1]), np.int64)])
    return CSRMatrix(indptr=indptr, indices=np.asarray(csr.indices),
                     data=np.asarray(csr.data),
                     shape=(n_rows, csr.shape[1]))


def hvp_tile_dtype(name: str) -> np.dtype:
    """Resolve ``DiscoConfig.hvp_dtype`` to a numpy-compatible dtype.

    'float32' -> np.float32; 'bfloat16' -> the ml_dtypes bfloat16 (the
    numpy-registered dtype jax itself uses), so bf16 tile arrays can be
    built host-side in :func:`build_shard_ell_pairs` / the streaming
    planner and ``device_put`` at half the f32 byte volume. The mixed-
    precision contract (docs/kernels.md): only the *stored/streamed HVP
    tiles* carry this dtype — PCG state, coefficients, gradients and
    margins stay f32 at rest, and every kernel accumulates and returns
    f32. (Inside a kernel the probe-vector MXU operand is cast to the
    tile dtype for the dot itself, so bf16 rounds both dot operands;
    the f32 accumulator and outputs never round.)
    """
    if name in ("float32", "f32"):
        return np.dtype(np.float32)
    if name in ("bfloat16", "bf16"):
        import ml_dtypes  # jax dependency; numpy-registered bfloat16
        return np.dtype(ml_dtypes.bfloat16)
    raise ValueError(f"unknown hvp_dtype {name!r} "
                     "(expected 'float32' or 'bfloat16')")


class EllPair(NamedTuple):
    """Device-side sparse shard operand (a jax pytree of four arrays).

    ``data/cols`` hold the forward blocked-ELL layout of the local shard
    (row-blocks of ``X_loc`` — drives ``X @ v``); ``dataT/colsT`` hold the
    transposed layout (row-blocks of ``X_loc^T`` — drives ``X^T u``).
    Both layouts store the same nonzeros; the HVP reads X twice per
    application either way, so the 2x storage buys fully static kernel
    grids on both passes (DESIGN.md §4).

    Vector lengths are the *padded* dims: ``X @ v`` maps
    ``(ncb*bc,) -> (nrb*br,)`` and ``X^T u`` the reverse.
    """

    data: np.ndarray    # (nrb, W, br, bc)
    cols: np.ndarray    # (nrb, W) int32
    dataT: np.ndarray   # (ncb, WT, bc, br)
    colsT: np.ndarray   # (ncb, WT) int32

    @property
    def dtype(self):
        return self.data.dtype

    @property
    def padded_shape(self) -> tuple[int, int]:
        """(rows, cols) of the padded local operand."""
        nrb, _, br, _ = self.data.shape
        ncb, _, bc, _ = self.dataT.shape
        return nrb * br, ncb * bc

    @property
    def nbytes(self) -> int:
        """Bytes of every array of the pair."""
        return int(sum(a.nbytes for a in self))

    def with_values(self, dtype) -> "EllPair":
        """The same pair with its tile values in ``dtype``; the column
        arrays shared."""
        if self.dtype == dtype:
            return self
        return self._replace(data=self.data.astype(dtype),
                             dataT=self.dataT.astype(dtype))


def ell_pair_from_csr(csr: CSRMatrix, block_rows: int, block_cols: int,
                      width: int | None = None, width_t: int | None = None
                      ) -> tuple[BlockedEll, BlockedEll]:
    """Forward + transposed blocked-ELL layouts of one shard's matrix."""
    fwd = ell_from_csr(csr, block_rows, block_cols, width=width)
    tr = ell_from_csr(csr.transpose(), block_cols, block_rows,
                      width=width_t)
    return fwd, tr


def stack_shard_ells(ells: list[BlockedEll]
                     ) -> tuple[np.ndarray, np.ndarray]:
    """Stack per-shard ELLs into uniform ``(m, ...)`` arrays.

    Every shard is padded to the *global* max ELL width so the stacked
    arrays shard evenly along axis 0 under ``shard_map``. This padding is
    precisely the load-balancing cost surface: one nnz-heavy shard drags
    every shard's tile stream up to its width (docs/partitioning.md).
    """
    W = max(e.width for e in ells)
    data = np.stack([np.pad(e.data, ((0, 0), (0, W - e.width),
                                     (0, 0), (0, 0))) for e in ells])
    cols = np.stack([np.pad(e.cols, ((0, 0), (0, W - e.width)))
                     for e in ells])
    return data, cols


def shard_csrs_from_partition(X: CSRMatrix, part, axis: str
                              ) -> list[CSRMatrix]:
    """Split ``X`` into one local CSR per shard under a
    :class:`repro.data.partition.Partition` of the given axis
    ('features' | 'samples'). Every shard's matrix has identical shape
    (``part`` pads with empty indices). The single source of the
    shard-splitting convention — used by ``DiscoSolver._init_sparse``
    and ``benchmarks/bench_loadbalance.py`` alike, so what the benchmark
    measures is what the solver runs."""
    m, width = part.m, part.width
    if axis == "features":
        Xp = X.take_rows(part.perm)
        return [Xp.take_rows(np.arange(s * width, (s + 1) * width))
                for s in range(m)]
    if axis == "samples":
        XTp = X.transpose().take_rows(part.perm)
        return [XTp.take_rows(np.arange(s * width, (s + 1) * width))
                .transpose() for s in range(m)]
    raise ValueError(f"unknown partition axis {axis!r}")


def build_shard_ell_pairs(shard_csrs: list[CSRMatrix], block_rows: int,
                          block_cols: int, dtype=None
                          ) -> tuple[np.ndarray, np.ndarray,
                                     np.ndarray, np.ndarray]:
    """Per-shard forward + transposed ELLs, stacked for ``shard_map``.

    shard_csrs : each shard's local matrix, all with identical shape
    dtype      : optional tile-value dtype override — pass
                 ``hvp_tile_dtype('bfloat16')`` to build the half-width
                 mixed-precision HVP tile layouts (``cols`` stay int32)
    returns (data, cols, dataT, colsT) with leading shard axis ``m``;
    ``DiscoSolver`` device_puts these with ``P(axis, None, ...)``.
    """
    fwd = [ell_from_csr(c, block_rows, block_cols) for c in shard_csrs]
    tr = [ell_from_csr(c.transpose(), block_cols, block_rows)
          for c in shard_csrs]
    data, cols = stack_shard_ells(fwd)
    dataT, colsT = stack_shard_ells(tr)
    if dtype is not None:
        data = data.astype(dtype)
        dataT = dataT.astype(dtype)
    return data, cols, dataT, colsT


# ---------------------------------------------------------------------------
# (id, value) slots: the nonzeros themselves, grouped by output index
# ---------------------------------------------------------------------------

# Slots per chunk. A chunk belongs to one output index; a heavy output
# spans many chunks instead of widening every row.
SLOT_WIDTH = 8
# Chunks per reduction block: a block's partial sums land in a window of
# 2 x 128 outputs (kernels/ops.py), so every output owns at least one
# chunk and a layout holds a whole number of blocks.
SLOT_BLOCK = 128

# Device seconds per byte of each layout, measured on a TPU v5e at the
# shape of the benchmark's real-sim cell (d = 20,958, n = 36,155, 1.85M
# nonzeros): the Pallas tile kernel streams padded 128x128 tiles at about
# 200 GB/s; a dense slab row goes through ``jnp.dot`` at about 740 GB/s;
# a slot (8 B of id and value) costs about 1.5 ns, gathered by a one-hot
# matmul and reduced in 256-output windows (1.3 to 2.0 ns as the gathered
# vector grows from 164 to 283 rows of 128). So a slot byte costs about
# 37 tile bytes and 140 slab bytes.
TILE_S_PER_BYTE = 1 / 200e9
SLAB_S_PER_BYTE = 1 / 740e9
SLOT_S_PER_BYTE = 1.5e-9 / 8


class SlotLayout(NamedTuple):
    """One product direction as fixed-width chunks of (id, value) slots:
    ``y[owner[k]] += sum_l vals[k, l] * v[ids[k, l]]``.

    Chunks are grouped by output index, so ``owner`` is nondecreasing,
    and every output owns at least one chunk. Padding slots carry value
    0 and the valid id 0; padding chunks belong to the last output.
    """

    ids: np.ndarray     # (chunks, SLOT_WIDTH) int32 input indices
    vals: np.ndarray    # (chunks, SLOT_WIDTH) values
    owner: np.ndarray   # (chunks,) int32 output index


@dataclasses.dataclass(frozen=True)
class SlotPair:
    """Device-side slot operand of one shard, the twin of
    :class:`EllPair` (a jax pytree; ``shape`` is static).

    The ``head`` rows, the densest of ``X_loc``, are a dense
    ``(H, cols)`` slab at row indices ``head_rows``; every other nonzero
    is a slot, in ``fwd`` (feature-major: rows of ``X_loc``, drives
    ``X @ v``) and in ``tr`` (sample-major: rows of ``X_loc^T``, drives
    ``X^T u``). ``shape`` is the padded ``(rows, cols)`` of the local
    operand, the vector lengths of the two directions. Stacked shards
    carry a leading shard axis on every array.
    """

    fwd: SlotLayout
    tr: SlotLayout
    head: np.ndarray        # (H, cols) dense rows
    head_rows: np.ndarray   # (H,) int32
    shape: tuple[int, int]

    @property
    def dtype(self):
        return self.fwd.vals.dtype

    @property
    def nbytes(self) -> int:
        """Bytes of every array of the pair."""
        return int(sum(a.nbytes for a in jax.tree_util.tree_leaves(self)))

    def with_values(self, dtype) -> "SlotPair":
        """The same pair with its values (slot values and the head slab)
        in ``dtype``; ids, owners and head rows shared."""
        if self.dtype == dtype:
            return self
        return dataclasses.replace(
            self, fwd=self.fwd._replace(vals=self.fwd.vals.astype(dtype)),
            tr=self.tr._replace(vals=self.tr.vals.astype(dtype)),
            head=self.head.astype(dtype))


jax.tree_util.register_dataclass(
    SlotPair, data_fields=["fwd", "tr", "head", "head_rows"],
    meta_fields=["shape"])


def with_hvp_dtype(X, dtype):
    """The HVP twin of a solver's data (docs/kernels.md): a dense array,
    an :class:`EllPair` or a :class:`SlotPair` with its values in
    ``dtype`` and its index arrays shared; ``X`` itself when its values
    already are."""
    if isinstance(X, (EllPair, SlotPair)):
        return X.with_values(dtype)
    return X if X.dtype == dtype else X.astype(dtype)


def slot_chunks(nnz_per_output: np.ndarray) -> int:
    """Chunks a slot layout takes for these per-output nonzero counts:
    at least one per output, rounded up to whole blocks."""
    per = np.maximum(-(-np.asarray(nnz_per_output) // SLOT_WIDTH), 1)
    return -(-max(int(per.sum()), 1) // SLOT_BLOCK) * SLOT_BLOCK


def slot_layout_from_csr(csr: CSRMatrix, n_out: int,
                         n_chunks: int | None = None) -> SlotLayout:
    """Slots of ``csr`` with its rows as the output indices (padded with
    empty outputs to ``n_out``) and its column indices as the slot ids,
    padded to ``n_chunks`` chunks."""
    counts = np.zeros(n_out, np.int64)
    counts[: csr.shape[0]] = csr.nnz_per_row()
    per_row = np.maximum(-(-counts // SLOT_WIDTH), 1)
    natural = slot_chunks(counts)
    C = natural if n_chunks is None else n_chunks
    if C < natural:
        raise ValueError(f"n_chunks {C} < the {natural} chunks needed")
    first = np.zeros(n_out + 1, np.int64)
    np.cumsum(per_row, out=first[1:])
    owner = np.full(C, n_out - 1, np.int32)
    owner[: first[-1]] = np.repeat(np.arange(n_out), per_row)
    rows = np.repeat(np.arange(csr.shape[0]), csr.nnz_per_row())
    rank = np.arange(csr.nnz) - csr.indptr[rows]
    chunk = first[rows] + rank // SLOT_WIDTH
    ids = np.zeros((C, SLOT_WIDTH), np.int32)
    vals = np.zeros((C, SLOT_WIDTH), csr.data.dtype)
    ids[chunk, rank % SLOT_WIDTH] = csr.indices
    vals[chunk, rank % SLOT_WIDTH] = csr.data
    return SlotLayout(ids=ids, vals=vals, owner=owner)


def slot_head_rows(csr: CSRMatrix) -> np.ndarray:
    """Rows of ``csr`` that cost less as a dense slab row than as slots:
    those whose nonzeros' slot bytes, at ``SLOT_S_PER_BYTE``, take longer
    than a dense row of ``cols`` values at ``SLAB_S_PER_BYTE``. Densest
    first."""
    counts = csr.nnz_per_row()
    row_s = csr.shape[1] * csr.data.dtype.itemsize * SLAB_S_PER_BYTE
    heavy = np.nonzero(counts * 8 * SLOT_S_PER_BYTE > row_s)[0]
    return heavy[np.argsort(-counts[heavy], kind="stable")]


def _split_head(csr: CSRMatrix, head: np.ndarray
                ) -> tuple[np.ndarray, CSRMatrix]:
    """The dense ``(len(head), cols)`` slab of rows ``head`` and the CSR
    of every other row (head rows emptied)."""
    slab = np.zeros((len(head), csr.shape[1]), csr.data.dtype)
    counts = csr.nnz_per_row()
    rows = np.repeat(np.arange(csr.shape[0]), counts)
    in_head = np.zeros(csr.shape[0], bool)
    in_head[head] = True
    pos = np.zeros(csr.shape[0], np.int64)
    pos[head] = np.arange(len(head))
    hit = in_head[rows]
    slab[pos[rows[hit]], csr.indices[hit]] = csr.data[hit]
    keep = ~hit
    tail = CSRMatrix.from_coo(rows[keep], csr.indices[keep],
                              csr.data[keep], csr.shape,
                              dtype=csr.data.dtype)
    return slab, tail


def build_shard_slot_pairs(shard_csrs: list[CSRMatrix],
                           shape: tuple[int, int]) -> SlotPair:
    """Per-shard slot pairs stacked with a leading shard axis ``m``:
    each shard's head slab padded to the largest shard's head (zero rows
    at row 0), and its layouts to the largest shard's chunk counts (as
    :func:`build_shard_ell_pairs` stacks tile widths). ``shape`` is the
    padded local ``(rows, cols)``."""
    rows, cols = shape
    heads = [slot_head_rows(c) for c in shard_csrs]
    splits = [_split_head(c, h) for c, h in zip(shard_csrs, heads)]
    H = max(len(h) for h in heads)
    tails = [t for _, t in splits]
    tails_t = [t.transpose() for t in tails]
    C = max(slot_chunks(np.pad(t.nnz_per_row(), (0, rows - t.shape[0])))
            for t in tails)
    CT = max(slot_chunks(np.pad(t.nnz_per_row(), (0, cols - t.shape[0])))
             for t in tails_t)
    fwd = [slot_layout_from_csr(t, rows, C) for t in tails]
    tr = [slot_layout_from_csr(t, cols, CT) for t in tails_t]
    stack = lambda parts: SlotLayout(*(np.stack(f) for f in zip(*parts)))
    head = np.stack([np.pad(s, ((0, H - len(s)), (0, cols - s.shape[1])))
                     for s, _ in splits])
    head_rows = np.stack([np.pad(h, (0, H - len(h))).astype(np.int32)
                          for h in heads])
    return SlotPair(stack(fwd), stack(tr), head, head_rows, (rows, cols))


class LayoutChoice(NamedTuple):
    """Which layout a sparse in-memory solve takes, with the bytes and
    the estimated device seconds of one pass over each (every shard)."""

    layout: str         # 'slots' | 'ell'
    tile_bytes: int     # padded blocked-ELL tiles, both layouts
    slot_bytes: int     # slots, chunk owners and head slab, both layouts
    tile_s: float
    slot_s: float


def choose_hvp_layout(shard_csrs: list[CSRMatrix], block_rows: int,
                      block_cols: int) -> LayoutChoice:
    """Slots or tiles for these shards, from the index structure alone
    (no layout is built). Each is costed at its measured seconds per
    byte (``*_S_PER_BYTE``): tiles win only where they are dense enough,
    as on block-structured data at high tile fill."""
    m = len(shard_csrs)
    rows, cols = shard_csrs[0].shape
    itemsize = shard_csrs[0].data.dtype.itemsize
    widths = [ell_tile_widths(c, block_rows, block_cols)
              for c in shard_csrs]
    tiles = (-(-rows // block_rows) * max(w for w, _ in widths)
             + max(-(-cols // block_cols), 1) * max(w for _, w in widths))
    tile_bytes = m * tiles * block_rows * block_cols * itemsize
    slot_bytes = slab_bytes = 0
    for c in shard_csrs:
        head = slot_head_rows(c)
        keep = np.ones(rows, bool)
        keep[head] = False
        per_row = np.where(keep, c.nnz_per_row(), 0)
        per_col = np.bincount(
            c.indices[np.repeat(keep, c.nnz_per_row())], minlength=cols)
        chunks = slot_chunks(per_row) + slot_chunks(per_col)
        slot_bytes += chunks * (SLOT_WIDTH * (4 + itemsize) + 4)
        slab_bytes += len(head) * (cols * itemsize + 4)
    tile_s = tile_bytes * TILE_S_PER_BYTE
    slot_s = (slot_bytes * SLOT_S_PER_BYTE
              + 2 * slab_bytes * SLAB_S_PER_BYTE)
    layout = "slots" if slot_s < tile_s else "ell"
    return LayoutChoice(layout, int(tile_bytes), int(slot_bytes + slab_bytes),
                        tile_s, slot_s)


# ---------------------------------------------------------------------------
# streaming libsvm reader (bounded memory)
# ---------------------------------------------------------------------------

def truncate_features(fi: np.ndarray, si: np.ndarray, vs: np.ndarray,
                      n_features: int
                      ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Drop COO triplets whose 0-based feature index is ``>= n_features``.

    The single source of the explicit-``n_features`` *truncation*
    semantics every libsvm reader in the repo shares
    (:func:`repro.data.libsvm.load_libsvm`, :func:`load_libsvm_sparse`,
    :func:`iter_libsvm_chunks`): a requested feature dimension smaller
    than the max index seen drops the out-of-range features — the
    standard libsvm-reader convention — rather than writing out of the
    intended range. No-op (same arrays back) when nothing is out of
    range.
    """
    keep = fi < n_features
    if bool(keep.all()):
        return fi, si, vs
    return fi[keep], si[keep], vs[keep]


def iter_libsvm_chunks(path: str, chunk_samples: int = 8192,
                       dtype=np.float32, n_features: int | None = None
                       ) -> Iterator[tuple[np.ndarray, np.ndarray,
                                           np.ndarray, np.ndarray]]:
    """Yield ``(feat_idx, sample_idx, vals, labels)`` COO chunks.

    Feature indices are converted to 0-based. ``sample_idx`` is global
    (monotone across chunks). Peak memory is O(chunk nnz), independent of
    the file size — the building block of :func:`load_libsvm_sparse` and
    :class:`repro.data.store.ShardStore`.

    An explicit ``n_features`` applies the shared
    :func:`truncate_features` clamp to every chunk (features at index
    ``>= n_features`` are dropped), matching the
    ``load_libsvm`` / ``load_libsvm_sparse`` truncation semantics.
    """
    fi: list[int] = []
    si: list[int] = []
    vs: list[float] = []
    ys: list[float] = []
    base = 0

    def flush():
        f, s, v = (np.asarray(fi, np.int64), np.asarray(si, np.int64),
                   np.asarray(vs, dtype))
        if n_features is not None:
            f, s, v = truncate_features(f, s, v, n_features)
        return f, s, v, np.asarray(ys, dtype)

    n_in_chunk = 0
    with open(path) as f:
        for line in f:
            parts = line.split()
            if not parts:
                continue
            j = base + n_in_chunk
            ys.append(float(parts[0]))
            for tok in parts[1:]:
                idx, val = tok.split(":")
                fi.append(int(idx) - 1)   # libsvm indices are 1-based
                si.append(j)
                vs.append(float(val))
            n_in_chunk += 1
            if n_in_chunk >= chunk_samples:
                yield flush()
                base += n_in_chunk
                n_in_chunk = 0
                fi, si, vs, ys = [], [], [], []
    if n_in_chunk or base == 0:
        yield flush()


def load_libsvm_sparse(path: str, n_features: int | None = None,
                       dtype=np.float32, chunk_samples: int = 8192
                       ) -> tuple[CSRMatrix, np.ndarray]:
    """Streaming libsvm -> (CSRMatrix ``(d, n)``, labels ``(n,)``).

    Reads the file in ``chunk_samples``-sized chunks, accumulating COO
    triplets — peak memory O(nnz + chunk), never the dense ``d * n``.
    Matches :func:`repro.data.libsvm.load_libsvm` semantics via the
    shared :func:`truncate_features` clamp: an explicit ``n_features``
    smaller than the max seen index *truncates* (features beyond the
    range are dropped, per chunk), larger pads with empty features.
    """
    fparts, sparts, vparts, yparts = [], [], [], []
    max_feat = -1
    n = 0
    for fi, si, vs, ys in iter_libsvm_chunks(path, chunk_samples, dtype,
                                             n_features=n_features):
        if len(fi):
            max_feat = max(max_feat, int(fi.max()))
        fparts.append(fi)
        sparts.append(si)
        vparts.append(vs)
        yparts.append(ys)
        n += len(ys)
    fi = np.concatenate(fparts) if fparts else np.zeros(0, np.int64)
    si = np.concatenate(sparts) if sparts else np.zeros(0, np.int64)
    vs = np.concatenate(vparts) if vparts else np.zeros(0, dtype)
    y = np.concatenate(yparts) if yparts else np.zeros(0, dtype)
    d = n_features if n_features is not None else max_feat + 1
    return CSRMatrix.from_coo(fi, si, vs, (d, n), dtype=dtype), y


# ---------------------------------------------------------------------------
# synthetic power-law sparsity (the load-balancing stress regime)
# ---------------------------------------------------------------------------

def make_sparse_glm_data(d: int, n: int, density: float = 0.05,
                         alpha: float = 1.2, beta: float = 0.8,
                         task: str = "classification",
                         seed: int = 0, dtype=np.float32
                         ) -> tuple[CSRMatrix, np.ndarray, np.ndarray]:
    """Sparse GLM data with power-law feature *and* sample popularity.

    Feature ``i`` (0-based rank) appears with probability proportional to
    ``(i + 1)^-alpha``; sample ``j`` scales all of its probabilities by an
    activity ``(j + 1)^-beta`` (``beta = 0`` gives i.i.d. samples). Both
    axes normalized so the expected overall density is ``density`` — the
    scale-free structure of text datasets (rcv1/news20/splice) where a
    handful of head features (and long documents) carry most nonzeros.
    Equal-width sharding of such data concentrates nnz on the shard
    holding the head (docs/partitioning.md); this generator is the
    benchmark substrate for the ``>= 2x`` imbalance gate of
    ``benchmarks/bench_loadbalance.py``.

    Returns ``(X_csr (d, n), y (n,), w_true (d,))``.
    """
    rng = np.random.default_rng(seed)
    pop = (np.arange(1, d + 1, dtype=np.float64) ** (-alpha))
    p = pop * (density * d / pop.sum())                    # per-feature prob
    act = (np.arange(1, n + 1, dtype=np.float64) ** (-beta))
    act *= n / act.sum()                                   # mean-1 activity

    rows_l, cols_l = [], []
    for i in range(d):
        hit = np.nonzero(rng.random(n) < np.minimum(p[i] * act, 1.0))[0]
        rows_l.append(np.full(len(hit), i, np.int64))
        cols_l.append(hit.astype(np.int64))
    rows = np.concatenate(rows_l)
    cols = np.concatenate(cols_l)
    vals = rng.standard_normal(len(rows)).astype(dtype)
    X = CSRMatrix.from_coo(rows, cols, vals, (d, n), dtype=dtype)

    w_true = (rng.standard_normal(d) / np.sqrt(max(d, 1))).astype(dtype)
    Xd_w = np.zeros(n, np.float64)
    rr = np.repeat(np.arange(d), np.diff(X.indptr))
    np.add.at(Xd_w, X.indices, X.data.astype(np.float64) * w_true[rr])
    margins = Xd_w.astype(dtype)
    if task == "classification":
        scale = max(float(margins.std()), 1e-9)
        prob = 1.0 / (1.0 + np.exp(-margins / scale))
        y = np.where(rng.random(n) < prob, 1.0, -1.0).astype(dtype)
    elif task == "regression":
        y = (margins + 0.1 * rng.standard_normal(n)).astype(dtype)
    else:
        raise ValueError(f"unknown task {task!r}")
    return X, y, w_true
