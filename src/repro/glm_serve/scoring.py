"""Batched sparse scoring: feature-vector requests through the ELL kernels.

Inference for a fitted GLM is one sparse dot per request, ``margin =
<x, w>``. Serving millions of them efficiently is a *layout* problem:
the blocked-ELL Pallas path (:mod:`repro.kernels.sparse_hvp`) already
streams tile lists with a static grid, so a **batch** of requests packed
as the rows of a ``(B, d)`` sparse matrix scores with a single
``ell_matvec`` against the weight vector — one kernel dispatch for the
whole batch, the amortization the serving cost model
(:func:`repro.core.comm.glm_serving_throughput`) and the
``bench_serving`` throughput gate quantify.

Pieces:

* :class:`ScoreRequest` — one request: the (sparse) feature vector.
* :class:`RequestPacker` — requests -> fixed-shape blocked-ELL tiles.
  Every pack of the same packer has identical array shapes (short
  batches are padded with empty rows, tile lists to a fixed ELL width),
  so the jit'd scoring step compiles **once** — the shape-stable-tick
  property the micro-batching scheduler
  (:mod:`repro.glm_serve.scheduler`) is built on.
* :func:`oracle_margins` — the NumPy oracle the property tests and the
  ``bench_serving`` parity gate compare against.
* :class:`ScoringEngine` — weights (from a
  :class:`repro.glm_serve.registry.ModelRegistry` or given directly) +
  packer + jit'd step + loss link (predict / predict_proba via the
  :class:`repro.core.glm.GLMProblem` conventions), with between-tick
  hot swap of a newly published model version.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.losses import get_loss
from repro.data.sparse import CSRMatrix, ell_from_csr
from repro.kernels import ops as kops
from repro.obs import tracer as obs


@dataclasses.dataclass(frozen=True)
class ScoreRequest:
    """One scoring request: a sparse feature vector.

    ``indices`` are 0-based feature ids (unique, any order), ``values``
    the matching feature values. An empty request (no features) is
    valid and scores to margin 0.
    """

    indices: np.ndarray
    values: np.ndarray

    @classmethod
    def from_dense(cls, x: np.ndarray) -> "ScoreRequest":
        """Build from a dense (d,) feature vector, dropping zeros."""
        x = np.asarray(x)
        idx = np.nonzero(x)[0]
        return cls(indices=idx.astype(np.int64),
                   values=x[idx])

    @property
    def nnz(self) -> int:
        """Stored nonzeros of the request."""
        return int(len(self.values))


def oracle_margins(requests: Sequence[ScoreRequest], w: np.ndarray
                   ) -> np.ndarray:
    """NumPy reference margins ``<x_i, w>`` — the parity oracle.

    Computed per request as a float64 dot over its stored features, cast
    to ``w.dtype``; what the packer + ELL kernel path must reproduce to
    <= 1e-5 (``bench_serving`` gate, hypothesis property test).
    """
    w = np.asarray(w)
    w64 = w.astype(np.float64)
    out = np.zeros(len(requests), np.float64)
    for i, r in enumerate(requests):
        if r.nnz:
            out[i] = np.dot(np.asarray(r.values, np.float64),
                            w64[np.asarray(r.indices, np.int64)])
    return out.astype(w.dtype)


class RequestPacker:
    """Packs up to ``batch`` requests into fixed-shape ELL tiles.

    The batch matrix is ``R: (batch, d)`` with one request per row;
    margins are ``R @ w``, so the forward blocked-ELL layout of ``R``
    (row blocks of ``block_b`` requests, column blocks of ``block_d``
    features) drives :func:`repro.kernels.ops.ell_matvec` directly.

    Shapes are **static** across packs: rows pad to
    ``ceil(batch / block_b) * block_b`` (missing requests are empty
    rows), the tile fan-out pads to ``width`` (default: the number of
    feature blocks — always sufficient). A denser-than-``width`` pack
    raises, mirroring ``ell_from_csr``; all-padding tiles (an entirely
    empty batch) produce the zero-tile floor and score to zeros.
    """

    def __init__(self, d: int, batch: int, block_b: int = 8,
                 block_d: int = 128, width: int | None = None,
                 dtype=np.float32, tile_dtype=None):
        if d <= 0 or batch <= 0:
            raise ValueError(f"need d > 0 and batch > 0, got d={d}, "
                             f"batch={batch}")
        self.d = d
        self.batch = batch
        self.block_b = block_b
        self.block_d = block_d
        self.dtype = np.dtype(dtype)
        # tile_dtype: storage dtype of the packed ELL tiles (the bytes
        # each scoring dispatch stages) — e.g. bfloat16 for half-width
        # ticks; request values and weights stay ``dtype``, the kernel
        # accumulates f32 (docs/kernels.md mixed-precision contract)
        self.tile_dtype = self.dtype if tile_dtype is None \
            else np.dtype(tile_dtype)
        self.n_row_blocks = -(-batch // block_b)
        self.n_col_blocks = max(-(-d // block_d), 1)
        self.batch_padded = self.n_row_blocks * block_b
        self.d_padded = self.n_col_blocks * block_d
        self.width = width if width is not None else self.n_col_blocks
        if not 1 <= self.width <= self.n_col_blocks:
            raise ValueError(
                f"width must be in [1, {self.n_col_blocks}], got "
                f"{self.width}")

    def validate(self, r: ScoreRequest, label: str = "request"
                 ) -> np.ndarray:
        """Check one request's feature ids (in range, no duplicates).

        Returns the indices as int64. Duplicates must be rejected here:
        the ELL tile scatter is last-write-wins, so a duplicate id would
        silently mis-score instead of summing. Admission points (the
        scheduler's ``submit``) call this too, so a malformed request
        fails back to *its* submitter instead of poisoning a whole
        packed batch.
        """
        idx = np.asarray(r.indices, np.int64)
        if len(idx) and (idx.min() < 0 or idx.max() >= self.d):
            raise ValueError(
                f"{label} has feature ids outside [0, {self.d})")
        if len(idx) != len(np.unique(idx)):
            raise ValueError(f"{label} has duplicate feature ids")
        if len(idx) != len(np.asarray(r.values)):
            raise ValueError(
                f"{label} has {len(idx)} indices but "
                f"{len(np.asarray(r.values))} values")
        return idx

    def pack(self, requests: Sequence[ScoreRequest]
             ) -> tuple[np.ndarray, np.ndarray]:
        """ELL ``(data, cols)`` of a batch (shapes fixed per packer).

        data : (n_row_blocks, width, block_b, block_d)
        cols : (n_row_blocks, width) int32
        """
        if len(requests) > self.batch:
            raise ValueError(f"{len(requests)} requests > batch size "
                             f"{self.batch}")
        rows_l, cols_l, vals_l = [], [], []
        for i, r in enumerate(requests):
            idx = self.validate(r, label=f"request {i}")
            rows_l.append(np.full(len(idx), i, np.int64))
            cols_l.append(idx)
            vals_l.append(np.asarray(r.values, self.dtype))
        rows = np.concatenate(rows_l) if rows_l else np.zeros(0, np.int64)
        cols = np.concatenate(cols_l) if cols_l else np.zeros(0, np.int64)
        vals = (np.concatenate(vals_l) if vals_l
                else np.zeros(0, self.dtype))
        csr = CSRMatrix.from_coo(rows, cols, vals,
                                 (self.batch_padded, self.d),
                                 dtype=self.dtype)
        ell = ell_from_csr(csr, self.block_b, self.block_d,
                           width=self.width)
        data = ell.data if ell.data.dtype == self.tile_dtype \
            else ell.data.astype(self.tile_dtype)
        return data, ell.cols

    def pad_weights(self, w: np.ndarray) -> np.ndarray:
        """Zero-pad ``(d,)`` weights to the packed ``(d_padded,)``."""
        w = np.asarray(w, self.dtype)
        if w.shape != (self.d,):
            raise ValueError(f"weights shape {w.shape} != ({self.d},)")
        return np.pad(w, (0, self.d_padded - self.d))


class ScoringEngine:
    """Micro-batch scoring over a published model's weights.

    Args:
        model: a :class:`repro.glm_serve.registry.ModelRegistry` (the
            active version is loaded, and :meth:`maybe_reload` hot-swaps
            newly published versions between ticks) — or a plain
            ``(d,)`` weight array for registry-less use.
        loss: loss name for the prediction link; defaults to the
            registry model's ``cfg.loss`` (required for raw weights).
        batch: requests per scoring tick (the micro-batch width).
        block_b / block_d / width: packer tile geometry
            (:class:`RequestPacker`).
        hvp_dtype: tile storage dtype of the packed request batches,
            'float32' (default) or 'bfloat16' — the serving face of the
            solver's ``DiscoConfig.hvp_dtype``: the scoring dispatch
            stages half the tile bytes at bf16 while margins come back
            f32-accumulated (the kernels' out_dtype contract).
    """

    def __init__(self, model, loss: str | None = None, *,
                 batch: int = 64, block_b: int = 8, block_d: int = 128,
                 width: int | None = None, hvp_dtype: str = "float32"):
        from repro.data.sparse import hvp_tile_dtype
        from repro.glm_serve.registry import ModelRegistry

        self.registry = model if isinstance(model, ModelRegistry) else None
        if self.registry is not None:
            pub = self.registry.load()
            self.version: int | None = pub.version
            w = pub.w
            loss = loss or pub.cfg.loss
        else:
            self.version = None
            w = np.asarray(model)
            if loss is None:
                raise ValueError("loss is required when constructing "
                                 "from raw weights")
        self.loss = get_loss(loss)
        w = np.asarray(w)
        dtype = w.dtype if np.issubdtype(w.dtype, np.floating) \
            else np.float32
        self.hvp_dtype = hvp_dtype
        tile_dtype = hvp_tile_dtype(hvp_dtype)
        self.packer = RequestPacker(len(w), batch, block_b=block_b,
                                    block_d=block_d, width=width,
                                    dtype=dtype, tile_dtype=tile_dtype)
        self.w = w
        self._w_dev = jnp.asarray(self.packer.pad_weights(self.w))
        self._step = jax.jit(kops.ell_matvec)
        self.reloads = 0

    @property
    def batch(self) -> int:
        """Requests per tick (the packer's batch width)."""
        return self.packer.batch

    # -- hot swap ----------------------------------------------------------
    def maybe_reload(self) -> bool:
        """Swap in a newly activated registry version, if any.

        Same-dimension weights keep every compiled shape (no recompile,
        no pause); a dimension change rebuilds the packer. Returns True
        iff a swap happened. No-op for registry-less engines.
        """
        if self.registry is None:
            return False
        v = self.registry.active_version()
        if v is None or v == self.version:
            return False
        with obs.span("serve.hot_swap", version=int(v)):
            pub = self.registry.load(v)
            if len(pub.w) != self.packer.d:
                self.packer = RequestPacker(
                    len(pub.w), self.packer.batch,
                    block_b=self.packer.block_b,
                    block_d=self.packer.block_d,
                    dtype=self.packer.dtype,
                    tile_dtype=self.packer.tile_dtype)
            self.w = np.asarray(pub.w)
            self._w_dev = jnp.asarray(self.packer.pad_weights(self.w))
            self.version = v
            self.reloads += 1
        return True

    # -- scoring -----------------------------------------------------------
    def score(self, requests: Sequence[ScoreRequest]) -> np.ndarray:
        """Margins ``<x_i, w>`` for any number of requests.

        Requests are packed ``batch`` at a time; each pack is one jit'd
        ELL matvec (the shapes never change, so after the first call
        every tick reuses the same executable). Each piece of a pack
        ends in a wait: the kernel needs the tiles and the copy back
        waits for the kernel anyway, so the waits move the sync points
        without adding work, and each piece's span times its own work.
        """
        out = np.zeros(len(requests), self.packer.dtype)
        for lo in range(0, len(requests), self.packer.batch):
            part = requests[lo: lo + self.packer.batch]
            with obs.span("serve.pack"):
                data, cols = self.packer.pack(part)
            with obs.span("serve.copy_in"):
                data, cols = jax.block_until_ready(
                    (jnp.asarray(data), jnp.asarray(cols)))
            with obs.span("serve.kernel"):
                y = jax.block_until_ready(
                    self._step(data, cols, self._w_dev))
            with obs.span("serve.copy_out"):
                out[lo: lo + len(part)] = np.asarray(y)[: len(part)]
        return out

    def predict(self, requests: Sequence[ScoreRequest]) -> np.ndarray:
        """Predicted labels (±1 for classification losses, the margin
        for 'quadratic'), matching
        :meth:`repro.core.glm.GLMProblem.predict`."""
        a = self.score(requests)
        if self.loss.name == "quadratic":
            return a
        return np.where(a >= 0, 1.0, -1.0).astype(a.dtype)

    def predict_proba(self, requests: Sequence[ScoreRequest]
                      ) -> np.ndarray:
        """P(y = +1 | x) = sigmoid(margin); 'logistic' loss only."""
        if self.loss.name != "logistic":
            raise ValueError(
                f"predict_proba needs the 'logistic' loss, engine uses "
                f"{self.loss.name!r}")
        a = self.score(requests)
        p = 1.0 / (1.0 + np.exp(-a.astype(np.float64)))
        return p.astype(a.dtype)
