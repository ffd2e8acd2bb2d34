"""Batched sparse scoring: (id, value) slots gathered against resident weights.

Inference for a fitted GLM is one sparse dot per request, ``margin =
<x, w>``. A micro-batch of requests travels to the device as two small
``(batch, k)`` arrays, each request's feature ids and values, and scores
with one jitted gather-multiply-reduce against the weight vector, which
stays on the device between ticks. A pack costs ``batch * k * 8`` bytes
at float32 values, so one tick dispatch is amortized over the whole
batch (:func:`repro.core.comm.glm_serving_throughput`, the
``bench_serving`` throughput gate). Scoring does not use the solver's
blocked-ELL tiles: those are full by construction, while a batch of
independent requests would put about one nonzero in each tile.

Pieces:

* :class:`ScoreRequest` — one request: the (sparse) feature vector.
* :class:`RequestPacker` — requests -> fixed-shape (id, value) slots.
  ``k`` is the smallest power of two at least the batch's longest
  request, and short batches pad with rows of padding slots, so the
  shapes depend only on the request lengths: the jit'd step compiles
  once per ``k``, and traffic of one request length compiles once —
  the shape-stable tick the micro-batching scheduler
  (:mod:`repro.glm_serve.scheduler`) is built on.
* :func:`slot_margins` — the step: per row, the sum of each slot's value
  times the weight its id names.
* :func:`oracle_margins` — the NumPy oracle the property tests and the
  ``bench_serving`` parity gate compare against.
* :class:`ScoringEngine` — weights (from a
  :class:`repro.glm_serve.registry.ModelRegistry` or given directly) held
  on the device + packer + jit'd step + loss link (predict /
  predict_proba via the :class:`repro.core.glm.GLMProblem` conventions),
  with between-tick hot swap of a newly published model version.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.losses import get_loss
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
    to ``w.dtype``; what the packer + :func:`slot_margins` path must
    reproduce to <= 1e-5 (``bench_serving`` gate, hypothesis property
    test).
    """
    w = np.asarray(w)
    w64 = w.astype(np.float64)
    out = np.zeros(len(requests), np.float64)
    for i, r in enumerate(requests):
        if r.nnz:
            out[i] = np.dot(np.asarray(r.values, np.float64),
                            w64[np.asarray(r.indices, np.int64)])
    return out.astype(w.dtype)


def slot_margins(ids, vals, w):
    """Margins of one pack: ``sum_j vals[i, j] * w[ids[i, j]]`` per row.

    ``ids`` / ``vals`` are a pack of :class:`RequestPacker` and ``w`` the
    ``(d + 1,)`` weights of :meth:`RequestPacker.pad_weights`, whose last
    entry, the zero, is what every padding slot reads. Values stored
    narrower than ``w`` (bfloat16) are widened before the product, so the
    products and the sum are in ``w``'s precision.
    """
    return jnp.sum(vals.astype(w.dtype) * w[ids], axis=1)


class RequestPacker:
    """Packs up to ``batch`` requests into fixed-shape (id, value) slots.

    Row ``i`` of a pack holds request ``i``'s feature ids and values; the
    rest of the row, and every row of a short batch, are padding slots
    with id ``d`` and value 0. Rows are ``k`` slots wide, ``k`` the
    smallest power of two at least the batch's longest request (at least
    1, see :meth:`slots`), so a pack's shape depends only on the request
    lengths. An empty request (or batch) scores to zeros.

    ``value_dtype`` is the storage dtype of the packed values (e.g.
    bfloat16 for half-width packs); request values and weights stay
    ``dtype``.
    """

    def __init__(self, d: int, batch: int, dtype=np.float32,
                 value_dtype=None):
        if d <= 0 or batch <= 0:
            raise ValueError(f"need d > 0 and batch > 0, got d={d}, "
                             f"batch={batch}")
        self.d = d
        self.batch = batch
        self.dtype = np.dtype(dtype)
        self.value_dtype = self.dtype if value_dtype is None \
            else np.dtype(value_dtype)

    def validate(self, r: ScoreRequest, label: str = "request"
                 ) -> np.ndarray:
        """Check one request's feature ids (in range, no duplicates).

        Returns the indices as int64. An id out of range has no weight
        (the gather would read the padding zero, or clamp), and a
        duplicate id names one feature twice: both are malformed
        requests, not margins. Admission points (the scheduler's ``submit``) call this
        too, so a malformed request fails back to *its* submitter instead
        of poisoning a whole packed batch.
        """
        idx = np.asarray(r.indices, np.int64)
        srt = np.sort(idx)      # one sort: the extremes and the repeats
        if len(srt) and (srt[0] < 0 or srt[-1] >= self.d):
            raise ValueError(
                f"{label} has feature ids outside [0, {self.d})")
        if (srt[1:] == srt[:-1]).any():
            raise ValueError(f"{label} has duplicate feature ids")
        if len(idx) != len(np.asarray(r.values)):
            raise ValueError(
                f"{label} has {len(idx)} indices but "
                f"{len(np.asarray(r.values))} values")
        return idx

    @staticmethod
    def slots(requests: Sequence[ScoreRequest]) -> int:
        """``k`` of a batch: the smallest power of two at least its
        longest request, and at least 1."""
        longest = max((len(r.indices) for r in requests), default=0)
        return 1 << max(longest - 1, 0).bit_length()

    def pack(self, requests: Sequence[ScoreRequest]
             ) -> tuple[np.ndarray, np.ndarray]:
        """``(ids, vals)`` of a batch, each of shape ``(batch, k)``.

        ids  : int32, padding slots ``d``
        vals : ``value_dtype``, padding slots 0
        """
        if len(requests) > self.batch:
            raise ValueError(f"{len(requests)} requests > batch size "
                             f"{self.batch}")
        idx = [self.validate(r, label=f"request {i}")
               for i, r in enumerate(requests)]
        k = self.slots(requests)
        ids = np.full((self.batch, k), self.d, np.int32)
        vals = np.zeros((self.batch, k), self.value_dtype)
        for i, (r, ix) in enumerate(zip(requests, idx)):
            ids[i, :len(ix)] = ix
            vals[i, :len(ix)] = np.asarray(r.values, self.dtype)
        return ids, vals

    def pad_weights(self, w: np.ndarray) -> np.ndarray:
        """``(d,)`` weights with the zero appended that padding slots
        (id ``d``) read: ``(d + 1,)``."""
        w = np.asarray(w, self.dtype)
        if w.shape != (self.d,):
            raise ValueError(f"weights shape {w.shape} != ({self.d},)")
        return np.append(w, np.zeros(1, self.dtype))


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
        hvp_dtype: storage dtype of the packed request values,
            'float32' (default) or 'bfloat16' — the serving face of the
            solver's ``DiscoConfig.hvp_dtype``: a bf16 pack stages 6
            bytes a slot instead of 8, while the weights, the products
            and the margins stay f32.
    """

    def __init__(self, model, loss: str | None = None, *,
                 batch: int = 64, hvp_dtype: str = "float32"):
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
        self.packer = RequestPacker(len(w), batch, dtype=dtype,
                                    value_dtype=hvp_tile_dtype(hvp_dtype))
        self.w = w
        self._w_dev = jnp.asarray(self.packer.pad_weights(self.w))
        self._step = jax.jit(slot_margins)
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
                    dtype=self.packer.dtype,
                    value_dtype=self.packer.value_dtype)
            self.w = np.asarray(pub.w)
            self._w_dev = jnp.asarray(self.packer.pad_weights(self.w))
            self.version = v
            self.reloads += 1
        return True

    # -- scoring -----------------------------------------------------------
    def score(self, requests: Sequence[ScoreRequest]) -> np.ndarray:
        """Margins ``<x_i, w>`` for any number of requests.

        Requests are packed ``batch`` at a time; each pack is one call of
        the jit'd :func:`slot_margins` (one executable per ``k``, so
        after the first pack of each ``k`` every tick reuses one). Each
        piece of a pack ends in a wait: the step needs the slots and the
        copy back waits for the step anyway, so the waits move the sync
        points without adding work, and each piece's span times its own
        work. The ``serve.pack`` span carries ``k``, so a pack that
        widens ``k`` (and compiles) shows in a trace.
        """
        out = np.zeros(len(requests), self.packer.dtype)
        for lo in range(0, len(requests), self.packer.batch):
            part = requests[lo: lo + self.packer.batch]
            with obs.span("serve.pack", k=self.packer.slots(part)):
                ids, vals = self.packer.pack(part)
                obs.count("serve.pack_bytes", ids.nbytes + vals.nbytes)
            with obs.span("serve.copy_in"):
                ids, vals = jax.block_until_ready(
                    (jnp.asarray(ids), jnp.asarray(vals)))
            with obs.span("serve.kernel"):
                y = jax.block_until_ready(
                    self._step(ids, vals, self._w_dev))
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
