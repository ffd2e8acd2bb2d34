"""DiSCO: inexact damped Newton (paper Algorithm 1) with distributed PCG.

``DiscoSolver`` owns the sharded data, a compiled ``newton_step`` and the
outer Python loop. The whole step — gradient, PCG (Algorithm 2 or 3), damped
update — runs inside a single ``shard_map`` so every collective the algorithm
pays is explicit and visible in the lowered HLO.

Partitioning:
  * ``partition='samples'``  -> DiSCO-S, mesh axis ``data``  (Algorithm 2)
  * ``partition='features'`` -> DiSCO-F, mesh axis ``model`` (Algorithm 3)

The damped update is  w_{k+1} = w_k - v_k / (1 + delta_k),
delta_k = sqrt(v_k^T H v_k)  — the self-concordant damping that makes DiSCO
affine-invariant and globally convergent (Zhang & Xiao 2015).
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import comm
from repro.core.hvp import (StreamedHvpOperator, make_local_operator,
                             validate_solver_cell)
from repro.core.losses import get_loss
from repro.core.pcg import pcg_features, pcg_samples
from repro.obs import tracer as obs
from repro.data.partition import Partition, make_partition
from repro.kernels import ops as kops
from repro.kernels.glm_hvp import one_pass_block
from repro.launch.mesh import make_mesh
from repro.data.sparse import (CSRMatrix, EllPair, SlotPair,
                               build_shard_ell_pairs, build_shard_slot_pairs,
                               choose_hvp_layout, hvp_tile_dtype,
                               shard_csrs_from_partition, with_hvp_dtype)
from repro.robust.checkpoint import (CheckpointState, load_checkpoint,
                                     save_checkpoint)
from repro.robust.faults import FaultInjector, FaultPlan
from repro.robust.retry import RetryPolicy
from repro.robust.straggler import ChunkTimingLedger, ElasticReplanner
from repro.utils.padding import pad_to_multiple


@dataclasses.dataclass(frozen=True)
class DiscoConfig:
    """Hyperparameters of one DiSCO solve (paper Algorithms 1-3).

    Attributes:
        loss: loss name from :mod:`repro.core.losses`
            ('logistic' | 'quadratic' | 'squared_hinge').
        lam: L2 regularization weight of problem (P).
        mu: preconditioner damping added to lam (paper uses 1e-2).
        tau: preconditioner sample count — the "master's first tau
            samples" of the paper (~100); clamped to n.
        partition: 'features' (DiSCO-F, Algorithm 3, mesh axis ``model``)
            or 'samples' (DiSCO-S, Algorithm 2, mesh axis ``data``). See
            docs/partitioning.md for how to choose.
        precond: 'woodbury' (closed form, paper §4), 'sag' (original
            DiSCO's iterative master-side solve; samples partition only),
            or 'none' (plain CG).
        max_outer: Newton (outer) iteration cap.
        max_pcg: PCG iteration cap (s-step mode: *rounds* cap).
        pcg_rel_tol: inexactness eps_k = pcg_rel_tol * ||grad_k||.
        grad_tol: outer-loop stop when ||grad|| falls below this.
        hessian_subsample: fraction of samples entering each H u
            (paper §5.4); 1.0 disables subsampling.
        sag_epochs: inner epochs of the 'sag' preconditioner baseline.
        use_kernel: route dense HVPs through the Pallas kernels
            (kernels/glm_hvp.py). Ignored for sparse inputs — the
            blocked-ELL ops always dispatch by ``REPRO_KERNEL_MODE``.
        hvp_fused: one-pass fused HVP kernels (docs/kernels.md):
            wherever no collective separates the two HVP directions
            (every DiSCO-S local product, single-shard DiSCO-F, the
            s-step zero-communication basis operators) both passes run
            from the same resident tiles, halving HBM reads of X per
            application. f32 results are identical to the two-pass path
            (bit-identical under ``REPRO_KERNEL_MODE=ref``). Applies to
            the sparse/ELL and dense-``use_kernel`` paths.
        hvp_dtype: tile storage dtype of the HVP operands, 'float32'
            (default) or 'bfloat16'. bf16 halves the bytes the PCG inner
            loop streams; kernels accumulate in f32 and every
            first-order quantity (margins, gradient, PCG state, the
            preconditioner slab) stays f32, so the Newton iteration
            converges to the f32 optimum — the bf16 rounding perturbs
            only the curvature, like Hessian subsampling (paper §5.4).
        pcg_block_s: s-step (communication-avoiding) PCG: Krylov
            dimensions advanced per communication round (DESIGN.md §2);
            1 = classic PCG.
        partition_strategy: sparse inputs only — 'lpt' balances per-shard
            *nonzeros* with the capacity-constrained LPT greedy
            (docs/partitioning.md), 'width' is the naive equal-width
            baseline. Dense inputs always slice equal-width.
        partition_block: granularity (indices per block) of the nnz
            balancer for in-memory sparse inputs; 1 balances per index.
            Set to the store chunk size to reproduce a streaming solve's
            chunk-granular assignment exactly (docs/streaming.md).
        ell_block_d: blocked-ELL tile rows (feature axis) for sparse
            inputs; TPU-native kernels want multiples of 8 (128 ideal).
        ell_block_n: blocked-ELL tile columns (sample axis).
        stream_chunk_size: out-of-core solves — indices per on-disk
            chunk along the partition axis when :func:`disco_fit_streaming`
            converts in-memory data to a :class:`repro.data.store.ShardStore`
            (must be a multiple of the partition axis' ELL tile edge).
        prefetch_depth: out-of-core solves — chunk payloads the
            background prefetch thread keeps in flight ahead of the
            kernels; peak data-plane memory scales with
            ``stream_chunk_size * prefetch_depth`` (docs/streaming.md).
        elastic_replan: out-of-core solves — watch per-chunk measured
            load seconds and re-run the chunk-granular LPT on them when
            the observed shard imbalance exceeds ``replan_threshold``
            (docs/robustness.md). DiSCO-S re-plans between PCG rounds
            (the PCG state is replicated, so the swap is exact);
            DiSCO-F re-plans at outer-iteration boundaries (its PCG
            state and block-diagonal preconditioner are tied to the
            shard membership).
        replan_threshold: observed max/mean per-shard seconds that arms
            an elastic re-plan (1.0 is a perfect balance).
        io_retries: out-of-core solves — bounded retries per stream
            step on transient I/O errors (0 disables).
        io_backoff_s: first-retry backoff (seconds; doubles per retry).
        io_deadline_s: per-step wall-clock budget across all attempts
            (0 = no deadline); exceeding it raises
            :class:`repro.robust.retry.StepDeadlineExceeded`.
        trace: enable the process-global tracing/metrics plane
            (:mod:`repro.obs`, docs/observability.md) at solver
            construction — spans, counters and gauges from every layer.
            Global and sticky (equivalent to ``repro.obs.enable()``;
            ``REPRO_TRACE=1`` does the same from the environment).
            Excluded from the checkpoint config fingerprint, so a
            traced resume of an untraced solve (or vice versa) is fine.
        seed: PRNG seed (Hessian subsampling draws).
    """

    loss: str = "logistic"
    lam: float = 1e-4
    mu: float = 1e-2                # preconditioner damping (paper uses 1e-2)
    tau: int = 100                  # preconditioner sample count (paper: ~100)
    partition: str = "features"     # 'features' (DiSCO-F) | 'samples' (DiSCO-S)
    precond: str = "woodbury"       # 'woodbury' | 'sag' (orig. DiSCO) | 'none'
    max_outer: int = 30
    max_pcg: int = 256
    pcg_rel_tol: float = 0.05       # eps_k = pcg_rel_tol * ||grad||
    grad_tol: float = 1e-8
    hessian_subsample: float = 1.0  # paper §5.4; fraction of samples in H u
    sag_epochs: int = 5             # inner epochs for the 'sag' baseline
    use_kernel: bool = False        # Pallas glm_hvp in the PCG hot path
    hvp_fused: bool = False         # one-pass fused HVP (docs/kernels.md)
    hvp_dtype: str = "float32"      # HVP tile storage: float32 | bfloat16
    pcg_block_s: int = 1            # s-step PCG: Krylov vectors per comm round
    partition_strategy: str = "lpt"  # sparse: 'lpt' (nnz-balanced) | 'width'
    partition_block: int = 1        # nnz-balancer granularity (indices/block)
    ell_block_d: int = 128          # sparse tile rows (feature axis)
    ell_block_n: int = 128          # sparse tile cols (sample axis)
    stream_chunk_size: int = 4096   # out-of-core: indices per disk chunk
    prefetch_depth: int = 2         # out-of-core: chunks prefetched ahead
    elastic_replan: bool = False    # re-plan shards on measured chunk cost
    replan_threshold: float = 1.5   # observed max/mean seconds that arms it
    io_retries: int = 3             # stream-step retries on transient I/O
    io_backoff_s: float = 0.05      # first-retry backoff (doubles each try)
    io_deadline_s: float = 0.0      # per-step wall-clock budget (0 = none)
    trace: bool = False             # enable the repro.obs tracing plane
    seed: int = 0


@dataclasses.dataclass
class DiscoResult:
    """Outcome of :meth:`DiscoSolver.fit`.

    Attributes:
        w: (d,) solution in the *original* feature order (any internal
            load-balancing permutation and padding is undone).
        history: per-outer-iteration stats dicts (grad_norm, f,
            pcg_iters, delta, pcg_r_norm, ``iter_s`` measured
            wall-clock, comm_rounds_cum, ...).
        ledger: analytic communication totals (:class:`comm.CommLedger`).
        converged: True iff ||grad|| reached ``cfg.grad_tol``.
        partition_info: sparse solves only — the load-balance summary of
            :meth:`repro.data.partition.Partition.stats`, including the
            ``imbalance`` metric (max_shard_nnz / mean_shard_nnz) the
            paper's load-balancing contribution targets; None for dense.
        stream_stats: out-of-core solves only — the prefetch pipeline's
            byte ledger (``peak_bytes``, ``bytes_loaded``, ``passes``,
            ``max_step_bytes``; see
            :class:`repro.data.stream.PrefetchStats`); None otherwise.
        replan_events: elastic re-plans that fired during the solve
            (plain dicts of :class:`repro.robust.straggler.ReplanEvent`);
            empty unless ``cfg.elastic_replan`` triggered.
    """

    w: np.ndarray
    history: list[dict[str, Any]]
    ledger: comm.CommLedger
    converged: bool
    partition_info: dict[str, Any] | None = None
    stream_stats: dict[str, Any] | None = None
    replan_events: list[dict[str, Any]] = dataclasses.field(
        default_factory=list)

    @property
    def grad_norms(self) -> np.ndarray:
        """(outer_iters,) gradient norms, one per outer iteration."""
        return np.array([h["grad_norm"] for h in self.history])

    @property
    def comm_rounds(self) -> np.ndarray:
        """(outer_iters,) cumulative paper-style communication rounds."""
        return np.array([h["comm_rounds_cum"] for h in self.history])


def _chunk_mv(data, cols, v, c=None, *, mode):
    """One chunk's ``A (c .* v)``: matvec or, for a 2-D ``v``, matmat."""
    op = kops.ell_matmat if v.ndim == 2 else kops.ell_matvec
    return op(data, cols, v, c, mode=mode)


def _chunk_hvp(dataT, colsT, c, u, *, mode):
    """One chunk's whole HVP from its transposed layout (fused kernel)."""
    op = kops.ell_hvp_mm if u.ndim == 2 else kops.ell_hvp
    return op(dataT, colsT, u, c, mode=mode)


def _chunk_hvp_two_pass(dataT, colsT, data, cols, c, u, *, mode):
    """One chunk's HVP as two passes: ``X_t (c .* (X_t^T u))``."""
    return _chunk_mv(data, cols, _chunk_mv(dataT, colsT, u, mode=mode), c,
                     mode=mode)


@functools.partial(jax.jit, static_argnames=(
    "body", "mesh", "axis", "n_stacked", "n_sliced", "chunk", "reduce",
    "mode"))
def _on_shards(t, *arrays, body, mesh, axis, n_stacked, n_sliced, chunk,
               reduce, mode):
    """Step ``t`` of a streamed pass: ``body`` on every shard's device.

    A Pallas kernel cannot be partitioned by the compiler, so each shard's
    chunk products run inside a ``shard_map``. ``arrays`` are, in order,
    ``n_stacked`` arrays with a leading shard axis (the step's tiles, or
    per-shard inputs), ``n_sliced`` vectors on the permuted chunked axis
    (each shard reads the ``t``-th chunk of its own range) and whole,
    replicated operands; ``body`` takes them in that order. Returns the
    sum over shards (``reduce``) or their ``(m, ...)`` stack.
    """
    n_whole = len(arrays) - n_stacked - n_sliced

    def local(t, *arrs):
        stacked = [a[0] for a in arrs[:n_stacked]]
        sliced = [lax.dynamic_slice_in_dim(a, t * chunk, chunk)
                  for a in arrs[n_stacked:n_stacked + n_sliced]]
        out = body(*stacked, *sliced, *arrs[n_stacked + n_sliced:],
                   mode=mode)
        return lax.psum(out, axis) if reduce else out[None]

    in_specs = ((P(),) + (P(axis),) * (n_stacked + n_sliced)
                + (P(),) * n_whole)
    return shard_map(local, mesh=mesh, in_specs=in_specs,
                     out_specs=P() if reduce else P(axis),
                     check_vma=False)(t, *arrays)


def _shard_subsample_mask(key, frac, shape, axis_name):
    """Per-shard Bernoulli mask for Hessian subsampling (paper §5.4).

    The key is folded with the shard's axis index so every shard draws an
    *independent* subsample — with the raw key all shards would drop the
    same sample positions, biasing the subsampled Hessian.
    """
    key = jax.random.fold_in(key, lax.axis_index(axis_name))
    return jax.random.bernoulli(key, frac, shape)


class DiscoSolver:
    """Distributed inexact damped Newton for problem (P).

    Accepts the data matrix in the repo's feature-major ``(d, n)``
    convention (rows are features, columns are samples — see
    docs/architecture.md#shape-conventions) either **dense** (any array)
    or **sparse** (:class:`repro.data.sparse.CSRMatrix`). Sparse inputs
    additionally run the nnz-aware load-balanced partitioner
    (``cfg.partition_strategy``, docs/partitioning.md) and take the HVP
    layout their index structure favours, (id, value) slots or
    blocked-ELL tiles; the resulting shard-balance metrics are reported
    in ``DiscoResult.partition_info``.

    Whatever the layout, the placed data is one value, ``self.X`` (a
    dense array, or the stacked ``SlotPair`` or ``EllPair`` of the
    shards), with its HVP-dtype twin ``self.X_hvp``; one jitted step per
    partition takes every X product from that value's local operator.

    Args:
        X: (d, n) dense array or CSRMatrix.
        y: (n,) labels (+-1 for classification losses).
        cfg: solver hyperparameters.
        mesh: optional 1-axis jax mesh (axis ``model`` for DiSCO-F,
            ``data`` for DiSCO-S); defaults to all local devices.
    """

    def __init__(self, X, y, cfg: DiscoConfig, mesh: Mesh | None = None):
        self._streaming = False
        self._faults: FaultInjector | None = None
        self._replanner: ElasticReplanner | None = None
        self._replan_events: list[dict] = []
        self._outer_iter = 0
        self._sparse = isinstance(X, CSRMatrix)
        if not self._sparse:
            X = np.asarray(X)
            assert X.ndim == 2, "X must be (d, n)"
        y = np.asarray(y)
        assert y.shape == (X.shape[1],), "X must be (d, n), y (n,)"
        self.cfg = cfg
        self.loss = get_loss(cfg.loss)
        if cfg.trace:
            obs.enable()
        if not self._sparse:
            # a sparse solve validates once it has chosen its layout
            validate_solver_cell(family="binary", partition=cfg.partition,
                                 fused=cfg.hvp_fused, dtype=cfg.hvp_dtype,
                                 use_kernel=cfg.use_kernel)
        self.d, self.n = X.shape
        self.tau = min(cfg.tau, self.n)

        axis = "model" if cfg.partition == "features" else "data"
        self.axis = axis
        self.mesh = mesh if mesh is not None else make_mesh(
            (jax.device_count(),), (axis,))
        self.m = self.mesh.shape[axis]
        self._part: Partition | None = None

        if self._sparse:
            self._init_sparse(X, y)
        else:
            self._init_dense(X, y)
        self._w_sharding = NamedSharding(
            self.mesh, P(axis) if cfg.partition == "features" else P())
        self._w_shape = (self.d_padded,)
        obs.count("disco.hvp_one_pass_bytes", self._hvp_one_pass_bytes())
        self._step = self._build_step()

    def _hvp_one_pass_bytes(self) -> int:
        """Bytes of X the PCG's local products read once per HVP, all
        shards: the whole HVP copy when every shard's product takes the
        one-pass dense kernel, else 0. That is a dense X on TPU devices
        whose local panel fits (``one_pass_block``), in the classic PCG
        (``pcg_block_s`` 1), whose HVP is the full local product: always
        on DiSCO-S except over the two-pass kernels (``use_kernel``
        without ``hvp_fused``), and on DiSCO-F only fused on one shard."""
        cfg, X = self.cfg, self.X_hvp
        if (self._sparse or cfg.pcg_block_s > 1
                or self.mesh.devices.flat[0].platform != "tpu"):
            return 0
        if cfg.partition == "samples":
            full = not cfg.use_kernel or cfg.hvp_fused
            d_loc, n_loc = X.shape[0], X.shape[1] // self.m
        else:
            full = cfg.use_kernel and cfg.hvp_fused and self.m == 1
            d_loc, n_loc = X.shape[0] // self.m, X.shape[1]
        if full and one_pass_block(d_loc, n_loc, X.dtype.itemsize):
            return X.nbytes
        return 0

    def _init_dense(self, X, y):
        cfg, axis = self.cfg, self.axis
        # preconditioner samples: the first tau columns ("master's" samples)
        X_tau = X[:, : self.tau].copy()
        y_tau = y[: self.tau].copy()

        if cfg.partition == "features":
            Xp, _ = pad_to_multiple(X, 0, self.m)
            self.d_padded = Xp.shape[0]
            X_tau_p, _ = pad_to_multiple(X_tau, 0, self.m)
            self._place(X=(Xp, P(axis, None)), X_tau=(X_tau_p, P(axis, None)),
                        y=(y, P()), y_tau=(y_tau, P()))
        elif cfg.partition == "samples":
            Xp, npad = pad_to_multiple(X, 1, self.m)
            yp, _ = pad_to_multiple(y, 0, self.m)
            wts = np.ones(self.n, X.dtype)
            wts = np.pad(wts, (0, npad))
            self.d_padded = self.d
            self.n_padded = Xp.shape[1]
            self._place(X=(Xp, P(None, axis)), y=(yp, P(axis)),
                        weights=(wts, P(axis)), X_tau=(X_tau, P()),
                        y_tau=(y_tau, P()))
        else:
            raise ValueError(f"unknown partition {cfg.partition!r}")

        # mixed-precision HVP copy of X (docs/kernels.md): the PCG inner
        # loop streams this; margins/gradient/preconditioner stay on the
        # f32 original. Same object when hvp_dtype is the data dtype, so
        # the default costs nothing.
        self.X_hvp = with_hvp_dtype(self.X, hvp_tile_dtype(cfg.hvp_dtype))

    def _init_sparse(self, X: CSRMatrix, y):
        """Partition (load-balanced), lay out, and shard a sparse matrix.

        The chosen axis is permuted by the nnz-aware partitioner, and
        each shard's local matrix is laid out, as the index structure
        decides (:func:`repro.data.sparse.choose_hvp_layout`), either as
        (id, value) slots with a dense head slab (a ``SlotPair``) or as a
        forward + transposed blocked-ELL pair (an ``EllPair``). The
        shards' pairs, stacked on a leading shard axis, are ``self.X``.
        The tau preconditioner samples are materialized as a small dense
        slab (neither layout can be column-sliced on device).
        """
        cfg, axis, m = self.cfg, self.axis, self.m
        br, bc = cfg.ell_block_d, cfg.ell_block_n
        d, n = self.d, self.n
        dtype = X.dtype

        # preconditioner samples: the first tau *original* columns
        X_tau = X.take_cols_dense(np.arange(self.tau))          # (d, tau)
        y_tau = y[: self.tau].copy()

        if cfg.partition == "features":
            part = make_partition(X, "features", m,
                                  cfg.partition_strategy,
                                  block=cfg.partition_block,
                                  pad_multiple=br)
            shard_csrs = shard_csrs_from_partition(X, part, "features")
            self.d_padded = len(part.perm)
            self.n_padded = max(-(-n // bc), 1) * bc
            y_p = np.pad(y, (0, self.n_padded - n))
            smask = np.zeros(self.n_padded, dtype)
            smask[:n] = 1.0
            X_tau_p = np.zeros((self.d_padded, self.tau), dtype)
            valid = part.perm < d
            X_tau_p[valid] = X_tau[part.perm[valid]]

            placed = dict(X_tau=(X_tau_p, P(axis, None)), y=(y_p, P()),
                          smask=(smask, P()))
        elif cfg.partition == "samples":
            part = make_partition(X, "samples", m,
                                  cfg.partition_strategy,
                                  block=cfg.partition_block,
                                  pad_multiple=bc)
            shard_csrs = shard_csrs_from_partition(X, part, "samples")
            self.n_padded = len(part.perm)
            self.d_padded = -(-d // br) * br
            ext = lambda v: np.pad(v, (0, self.n_padded - n))
            y_p = ext(y)[part.perm]
            wts = ext(np.ones(n, dtype))[part.perm]
            X_tau_p = np.zeros((self.d_padded, self.tau), dtype)
            X_tau_p[:d] = X_tau

            placed = dict(y=(y_p, P(axis)), weights=(wts, P(axis)),
                          X_tau=(X_tau_p, P()))
        else:
            raise ValueError(f"unknown partition {cfg.partition!r}")
        self._part = part
        self.layout = choose_hvp_layout(shard_csrs, br, bc)
        slots = self.layout.layout == "slots"
        validate_solver_cell(family="binary", partition=cfg.partition,
                             fused=cfg.hvp_fused, dtype=cfg.hvp_dtype,
                             sparse=True, slots=slots)
        if slots:
            local = ((self.d_padded // m, self.n_padded)
                     if cfg.partition == "features"
                     else (self.d_padded, self.n_padded // m))
            pairs = build_shard_slot_pairs(shard_csrs, local)
        else:
            pairs = EllPair(*build_shard_ell_pairs(shard_csrs, br, bc))
        self._place(X=(pairs, P(axis)), y_tau=(y_tau, P()), **placed)
        # mixed-precision HVP copy (docs/kernels.md): the PCG loop
        # streams it; margins/gradient keep the f32 values, and ids,
        # owners and tile columns are shared. The same object at the
        # default hvp_dtype, so f32 costs nothing; otherwise each device
        # casts its own shard.
        self.X_hvp = with_hvp_dtype(self.X, hvp_tile_dtype(cfg.hvp_dtype))
        obs.count("disco.hvp_slot_bytes", pairs.nbytes if slots else 0)

    def _place(self, **arrays):
        """Put host arrays or pytrees of them, ``name=(tree, spec)``, on
        the mesh as ``self.<name>``: each device receives only the shard
        that ``spec`` gives each array, sliced on the host, so no device
        ever holds a whole sharded array on its way to the others. Waits
        for the copies, inside the ``disco.place`` span."""
        total = sum(a.size * jax.dtypes.canonicalize_dtype(a.dtype).itemsize
                    for t, _ in arrays.values()
                    for a in jax.tree_util.tree_leaves(t))
        with obs.span("disco.place", bytes=total, shards=self.m):
            placed = {name: jax.device_put(a, NamedSharding(self.mesh, spec))
                      for name, (a, spec) in arrays.items()}
            jax.block_until_ready(list(placed.values()))
        for name, a in placed.items():
            setattr(self, name, a)
        if obs.enabled():
            per_device = collections.Counter()
            for a in jax.tree_util.tree_leaves(list(placed.values())):
                for s in a.addressable_shards:
                    per_device[s.device] += s.data.nbytes
            obs.count("disco.place_bytes_max", max(per_device.values()))

    # ------------------------------------------------------------------
    def _build_step(self):
        """The jitted damped Newton step of an in-memory solve, one per
        partition (paper Algorithms 3 and 2), for every layout of
        ``self.X``. Every X product goes through the layout's local
        operator (``core/hvp.py``). A dense array is sliced on its
        partition dimension; a stacked pytree (slots or blocked-ELL
        tiles) is sharded on its leading shard axis, which each shard
        drops."""
        cfg, loss, axis = self.cfg, self.loss, self.axis
        n, tau = self.n, self.tau
        frac = cfg.hessian_subsample
        features = cfg.partition == "features"
        if isinstance(self.X, (EllPair, SlotPair)):
            x_spec = P(axis)
            local = lambda X: jax.tree_util.tree_map(lambda a: a[0], X)
        else:
            x_spec = P(axis, None) if features else P(None, axis)
            local = lambda X: X
        pcg_kw = dict(mu=cfg.mu, axis_name=axis, precond=cfg.precond,
                      use_kernel=cfg.use_kernel, block_s=cfg.pcg_block_s,
                      axis_size=self.m, hvp_fused=cfg.hvp_fused)

        def damped(w, res, gnorm, fval):
            w_new = w - res.v / (1.0 + res.delta)
            stats = dict(grad_norm=gnorm, f=fval, pcg_iters=res.iters,
                         delta=res.delta, pcg_r_norm=res.r_norm)
            return w_new, stats

        if features:
            # samples padded by the sparse layouts are masked out; dense
            # X has none, and its program takes no mask
            smask = self.smask if self._sparse else None

            def step_local(Xs, Xhs, X_tau_loc, y, y_tau, smask, w_loc, key):
                op = make_local_operator(local(Xs), None,
                                         partition="features")
                margins = lax.psum(op.pass_a(w_loc), axis)           # (n,)
                d1 = loss.d1(margins, y)
                c = loss.d2(margins, y)
                if smask is None:
                    f_loss = jnp.mean(loss.value(margins, y))
                else:
                    d1, c = d1 * smask, c * smask
                    f_loss = jnp.sum(loss.value(margins, y) * smask) / n
                g_loc = op.pass_b(d1) / n + cfg.lam * w_loc
                gnorm = jnp.sqrt(lax.psum(jnp.vdot(g_loc, g_loc), axis))
                fval = f_loss + 0.5 * cfg.lam * lax.psum(
                    jnp.vdot(w_loc, w_loc), axis)

                if frac < 1.0:  # Hessian subsampling, paper §5.4
                    mask = jax.random.bernoulli(key, frac, margins.shape)
                    c_eff = c * mask / frac
                else:
                    c_eff = c
                coeffs_tau = loss.d2(margins[:tau], y_tau)

                # the PCG loop streams the (possibly bf16) HVP copy; the
                # f32 tau slab feeds the preconditioner
                eps = cfg.pcg_rel_tol * gnorm
                res = pcg_features(
                    local(Xhs), c_eff, n, cfg.lam, g_loc, eps, cfg.max_pcg,
                    coeffs_tau=coeffs_tau, X_tau_loc=X_tau_loc, **pcg_kw)
                return damped(w_loc, res, gnorm, fval)

            fn = jax.jit(shard_map(
                step_local, mesh=self.mesh,
                in_specs=(x_spec, x_spec, P(axis, None), P(), P(), P(),
                          P(axis), P()),
                out_specs=(P(axis), P()),
                check_vma=False))  # pallas_call outputs carry no vma info

            step = functools.partial(fn, self.X, self.X_hvp, self.X_tau,
                                     self.y, self.y_tau, smask)

        else:  # samples
            def step_local(Xs, Xhs, y_loc, wts_loc, X_tau, y_tau, w, key):
                op = make_local_operator(local(Xs), None,
                                         partition="samples")
                margins = op.pass_a(w)                               # (n_loc,)
                d1 = loss.d1(margins, y_loc) * wts_loc
                c = loss.d2(margins, y_loc) * wts_loc
                g = lax.psum(op.pass_b(d1), axis) / n + cfg.lam * w
                gnorm = jnp.sqrt(jnp.vdot(g, g))
                fval = lax.psum(jnp.sum(loss.value(margins, y_loc) * wts_loc),
                                axis) / n + 0.5 * cfg.lam * jnp.vdot(w, w)

                if frac < 1.0:
                    mask = _shard_subsample_mask(key, frac, margins.shape,
                                                 axis)
                    c_eff = c * mask / frac
                else:
                    c_eff = c
                coeffs_tau = loss.d2(X_tau.T @ w, y_tau)

                eps = cfg.pcg_rel_tol * gnorm
                res = pcg_samples(
                    local(Xhs), c_eff, n, cfg.lam, g, eps, cfg.max_pcg,
                    X_tau=X_tau, coeffs_tau=coeffs_tau,
                    sag_epochs=cfg.sag_epochs, **pcg_kw)
                return damped(w, res, gnorm, fval)

            fn = jax.jit(shard_map(
                step_local, mesh=self.mesh,
                in_specs=(x_spec, x_spec, P(axis), P(axis), P(), P(), P(),
                          P()),
                out_specs=(P(), P()),
                check_vma=False))  # pallas_call outputs carry no vma info

            step = functools.partial(fn, self.X, self.X_hvp, self.y,
                                     self.weights, self.X_tau, self.y_tau)

        # the device data enter the jitted program as arguments, bound
        # ahead of (w, key): an array closed over by a jitted function is
        # embedded as a constant
        return step

    # ------------------------------------------------------------------
    # out-of-core streaming path (docs/streaming.md)
    # ------------------------------------------------------------------

    @classmethod
    def from_store(cls, store, cfg: DiscoConfig, mesh: Mesh | None = None,
                   fault_plan: FaultPlan | None = None) -> "DiscoSolver":
        """Build a solver that *streams* a :class:`repro.data.store.ShardStore`.

        The store's chunked axis must match ``cfg.partition``. Peak
        data-plane memory is bounded by ``m * chunk_size *
        (cfg.prefetch_depth + 2)`` tile payloads — never the dataset:
        every Hessian product is a scan over prefetched chunk tiles
        (:mod:`repro.data.stream`) reusing the blocked-ELL kernels, with
        the chunk-granular LPT balancer assigning chunks to shards from
        the store's nnz header alone. The outer loop, damped step,
        stopping rules and preconditioners are identical to the
        in-memory solver; :meth:`fit` works unchanged and additionally
        reports ``DiscoResult.stream_stats``.

        Robustness (docs/robustness.md): stream steps are retried per
        ``cfg.io_retries``/``io_backoff_s``/``io_deadline_s``; with
        ``cfg.elastic_replan`` the per-chunk timing ledger feeds an
        :class:`repro.robust.straggler.ElasticReplanner` that
        re-balances the chunk→shard schedule on *measured* seconds.
        ``fault_plan`` (tests/benchmarks only) threads a
        :class:`repro.robust.faults.FaultPlan` into the chunk read path
        and the outer loop.
        """
        from repro.data.stream import plan_streams

        if store.axis != cfg.partition:
            raise ValueError(
                f"store is chunked along {store.axis!r} but cfg.partition "
                f"is {cfg.partition!r}; rebuild the store along the "
                f"partition axis")
        self = cls.__new__(cls)
        self._streaming = True
        self._sparse = True
        self.cfg = cfg
        self.loss = get_loss(cfg.loss)
        if cfg.trace:
            obs.enable()
        validate_solver_cell(family="binary", partition=cfg.partition,
                             fused=cfg.hvp_fused, dtype=cfg.hvp_dtype,
                             streaming=True)
        self.d, self.n = store.shape
        self.tau = min(cfg.tau, self.n)
        axis = "model" if cfg.partition == "features" else "data"
        self.axis = axis
        self.mesh = mesh if mesh is not None else make_mesh(
            (jax.device_count(),), (axis,))
        self.m = self.mesh.shape[axis]
        self._replan_events = []
        self._outer_iter = 0

        def put(arrs):
            out = {}
            for k, a in arrs.items():
                spec = P(axis, *([None] * (a.ndim - 1)))
                out[k] = jax.device_put(
                    jnp.asarray(a), NamedSharding(self.mesh, spec))
            return out

        self._faults = (FaultInjector(fault_plan)
                        if fault_plan is not None else None)
        retry = (RetryPolicy(max_retries=cfg.io_retries,
                             backoff_s=cfg.io_backoff_s,
                             deadline_s=cfg.io_deadline_s)
                 if cfg.io_retries > 0 or cfg.io_deadline_s > 0 else None)
        ledger = ChunkTimingLedger(store.n_chunks)
        self._replanner = (
            ElasticReplanner(ledger, threshold=cfg.replan_threshold)
            if cfg.elastic_replan else None)
        self._plan = plan_streams(
            store, self.m, cfg.partition_strategy,
            block_rows=cfg.ell_block_d, block_cols=cfg.ell_block_n,
            prefetch_depth=cfg.prefetch_depth, device_put=put,
            hvp_dtype=hvp_tile_dtype(cfg.hvp_dtype),
            timing_ledger=ledger, fault_injector=self._faults,
            retry=retry)
        self._part = self._plan.partition
        self._init_streaming()
        self._step = self._build_step_streaming()
        return self

    def _init_streaming(self):
        """Resident (small) arrays of a streaming solve: labels, sample
        mask, and the dense tau-sample preconditioner slab — everything
        except the X chunks, which stay on disk."""
        cfg, plan = self.cfg, self._plan
        store = plan.store
        d, n, tau, m = self.d, self.n, self.tau, self.m
        chunk, T, width = plan.chunk_size, plan.n_steps, plan.width_local
        dtype = store.dtype
        y = np.asarray(store.labels())
        rep = NamedSharding(self.mesh, P())

        if cfg.partition == "features":
            self.d_padded = plan.axis_padded
            self.n_padded = plan.other_padded
            y_p = np.pad(y, (0, self.n_padded - n)).astype(dtype)
            smask = np.zeros(self.n_padded, dtype)
            smask[:n] = 1.0
            self._build_tau_features()
            self.y = jax.device_put(jnp.asarray(y_p), rep)
            self.smask = jax.device_put(jnp.asarray(smask), rep)
            self._w_sharding = NamedSharding(self.mesh, P(self.axis))
            self._w_shape = (self.d_padded,)
        else:  # samples
            self.n_padded = plan.axis_padded
            self.d_padded = plan.other_padded
            part = self._part
            ext = np.pad(y, (0, self.n_padded - n)).astype(dtype)
            self.y = jax.device_put(jnp.asarray(ext[part.perm]),
                                    NamedSharding(self.mesh, P(self.axis)))
            wts = np.pad(np.ones(n, dtype), (0, self.n_padded - n))
            self.weights = jax.device_put(
                jnp.asarray(wts[part.perm]),
                NamedSharding(self.mesh, P(self.axis)))
            # first tau *original* samples, read from the chunks that
            # cover them (sample chunks are in original file order)
            X_tau = np.zeros((self.d_padded, tau), dtype)
            pos = 0
            while pos < tau:
                cid = pos // store.chunk_size
                info = store.chunks[cid]
                cnt = min(tau, info.stop) - pos
                sub = store.chunk_csr(cid).take_rows(
                    np.arange(pos - info.start, pos - info.start + cnt))
                X_tau[:d, pos: pos + cnt] = sub.todense().T
                pos += cnt
            self.X_tau = jax.device_put(jnp.asarray(X_tau), rep)
            self._w_sharding = rep
            self._w_shape = (self.d_padded,)
        self.y_tau = jax.device_put(jnp.asarray(y[:tau].astype(dtype)),
                                    rep)

    def _build_tau_features(self):
        """(Re)build the DiSCO-F per-shard dense tau preconditioner slab
        from the CURRENT schedule — the permuted tau slab is assembled
        chunk by chunk (tau columns of each chunk's local feature rows —
        the only dense read), so an elastic re-plan rebuilds it to match
        the new chunk→shard membership."""
        plan, store, m, tau = self._plan, self._plan.store, self.m, self.tau
        chunk, T, width = plan.chunk_size, plan.n_steps, plan.width_local
        X_tau = np.zeros((m, width, tau), store.dtype)
        for s in range(m):
            for t in range(T):
                cid = int(plan.schedule[s, t])
                if cid < 0:
                    continue
                slab = store.chunk_csr(cid).take_cols_dense(
                    np.arange(tau))
                X_tau[s, t * chunk: t * chunk + slab.shape[0]] = slab
        self.X_tau = jax.device_put(
            jnp.asarray(X_tau),
            NamedSharding(self.mesh, P(self.axis, None, None)))

    # -- streamed X products (each is one prefetched pass over the store)
    def _shard_step(self, body, t, stacked=(), sliced=(), whole=(),
                    reduce=True):
        """One schedule step of a streamed pass, ``body`` run by every
        shard on its own device (see :func:`_on_shards`)."""
        return _on_shards(np.int32(t), *stacked, *sliced, *whole,
                          body=body, mesh=self.mesh, axis=self.axis,
                          n_stacked=len(stacked), n_sliced=len(sliced),
                          chunk=self._plan.chunk_size, reduce=reduce,
                          mode=kops._mode())

    def _stream_xt(self, u, local=False, hvp=False):
        """Pass A — ``z = X^T u`` over the permuted padded axis.

        features: streams the transposed chunk layouts and accumulates
        each chunk's ``(n_padded,)`` (or ``(n_padded, k)``) contribution;
        ``local=True`` keeps per-shard partial sums ``(m, n_padded)``
        (the zero-communication s-step basis operator). ``hvp=True``
        stages the tiles in ``cfg.hvp_dtype`` (the PCG loop's passes).
        """
        shape = (self.n_padded,) + u.shape[1:]
        if local:
            shape = (self.m,) + shape
        acc = jnp.zeros(shape, u.dtype)
        with self._plan.stream("tr", hvp=hvp) as pf:
            for t, payload in enumerate(pf):
                acc = acc + self._shard_step(
                    _chunk_mv, t, stacked=(payload["dataT"],
                                           payload["colsT"]),
                    sliced=(u,), reduce=not local)
        return acc

    def _stream_x(self, z, coeffs=None, local=False, hvp=False):
        """Pass B — ``y = X (c .* z)`` back onto the permuted padded axis.

        features: streams the forward chunk layouts; each chunk emits its
        own slab of the output, laid out in schedule order (exactly the
        permuted layout). ``local=True`` reads per-shard inputs
        ``z: (m, n_padded)`` (s-step basis operator pass B).
        """
        whole = () if coeffs is None else (coeffs,)
        parts = []
        with self._plan.stream("fwd", hvp=hvp) as pf:
            for t, payload in enumerate(pf):
                tiles = (payload["data"], payload["cols"])
                parts.append(self._shard_step(
                    _chunk_mv, t,
                    stacked=tiles + ((z,) if local else ()),
                    whole=(() if local else (z,)) + whole, reduce=False))
        return self._schedule_order(parts)

    def _schedule_order(self, parts):
        """Per-step ``(m, chunk, ...)`` outputs as one vector on the
        permuted padded axis: shard-major, then step."""
        out = jnp.stack(parts, axis=1)                 # (m, T, chunk, ...)
        return out.reshape((-1,) + out.shape[3:])

    def _stream_hvp_samples(self, u, coeffs):
        """DiSCO-S chunk-local pass: each sample chunk completes both HVP
        directions (``X_t (c_t .* (X_t^T u))``), so one pass over the
        store serves the whole product. With ``cfg.hvp_fused`` only the
        *transposed* layout is streamed and each chunk runs the one-pass
        fused kernel — half the staged tile bytes per HVP application
        (docs/kernels.md); tiles are staged in ``cfg.hvp_dtype`` either
        way. The fused-vs-two-pass choice is made HERE, from the plan's
        global tile geometry, so an oversized chunk row degrades to the
        two-pass kernel stream — never to the ops-level last-resort jnp
        path — and the whole stream takes one consistent shape."""
        plan = self._plan
        acc = jnp.zeros(u.shape, u.dtype)
        fused = self.cfg.hvp_fused and plan.fused_hvp_fits(
            self.d_padded, s=(u.shape[1] if u.ndim == 2 else 1))
        kind, keys, body = (("tr", ("dataT", "colsT"), _chunk_hvp) if fused
                            else ("both", ("dataT", "colsT", "data", "cols"),
                                  _chunk_hvp_two_pass))
        with plan.stream(kind, hvp=True) as pf:
            for t, payload in enumerate(pf):
                acc = acc + self._shard_step(
                    body, t, stacked=tuple(payload[k] for k in keys),
                    sliced=(coeffs,), whole=(u,))
        return acc

    def _stream_margins_samples(self, w):
        """DiSCO-S margins: one 'tr' pass, each chunk emitting its slab
        of the permuted ``(n_padded,)`` margin vector."""
        parts = []
        with self._plan.stream("tr") as pf:
            for t, payload in enumerate(pf):
                parts.append(self._shard_step(
                    _chunk_mv, t, stacked=(payload["dataT"],
                                           payload["colsT"]),
                    whole=(w,), reduce=False))
        return self._schedule_order(parts)

    def _stream_grad_samples(self, d1):
        """DiSCO-S gradient accumulation: one 'fwd' pass of
        ``sum_t X_t d1_t`` (the cross-shard reduce is the accumulation)."""
        acc = jnp.zeros((self.d_padded,), d1.dtype)
        with self._plan.stream("fwd") as pf:
            for t, payload in enumerate(pf):
                acc = acc + self._shard_step(
                    _chunk_mv, t, stacked=(payload["data"],
                                           payload["cols"]),
                    sliced=(d1,))
        return acc

    # -- elastic re-planning (docs/robustness.md) ----------------------
    def _replan_mapping(self, new_plan) -> np.ndarray:
        """Index map old-permuted-position -> new-permuted-position:
        ``vec_new = vec_old[mapping]`` re-permutes any vector living on
        the sharded (permuted, padded) axis to the new plan's layout."""
        return self._part.inv[new_plan.partition.perm]

    def _maybe_replan_samples(self, state: dict) -> None:
        """Between-PCG-rounds re-plan window of streaming DiSCO-S.

        The PCG state (v, r, u, Hv, ...) is replicated d-space and never
        permuted, so swapping the schedule mid-solve is *exact* — only
        the n-space resident vectors (labels, sample weights, and the
        in-flight Hessian coefficients in ``state``) live in the
        permuted layout and are re-permuted here.
        """
        if self._replanner is None:
            return
        out = self._replanner.maybe_replan(
            self._plan, outer_iter=self._outer_iter, trigger="pcg")
        if out is None:
            return
        new_plan, event = out
        mapping = self._replan_mapping(new_plan)
        ss = NamedSharding(self.mesh, P(self.axis))
        self.y = jax.device_put(self.y[mapping], ss)
        self.weights = jax.device_put(self.weights[mapping], ss)
        for k in state:
            state[k] = state[k][mapping]
        self._plan = new_plan
        self._part = new_plan.partition
        self._replan_events.append(event.to_dict())

    def _maybe_replan_features(self, w):
        """Outer-boundary re-plan window of streaming DiSCO-F.

        DiSCO-F's PCG state and block-diagonal Woodbury preconditioner
        live in the permuted *feature* layout and are tied to the shard
        membership, so the swap happens only between outer iterations:
        the iterate is re-permuted and the per-shard tau slab rebuilt
        for the new schedule (the design trade-off is documented in
        docs/robustness.md).
        """
        if self._replanner is None:
            return w
        out = self._replanner.maybe_replan(
            self._plan, outer_iter=self._outer_iter, trigger="outer")
        if out is None:
            return w
        new_plan, event = out
        mapping = self._replan_mapping(new_plan)
        self._plan = new_plan
        self._part = new_plan.partition
        self._build_tau_features()
        self._replan_events.append(event.to_dict())
        return jax.device_put(w[mapping], self._w_sharding)

    def _build_step_streaming(self):
        """Host-driven outer step: same math as the in-memory sparse
        step, with every X product replaced by a prefetched chunk scan
        and the PCG loop run by :func:`repro.core.pcg.pcg_streamed`."""
        from repro.core.pcg import pcg_streamed

        cfg, loss = self.cfg, self.loss
        n, tau, m = self.n, self.tau, self.m
        lam, frac = cfg.lam, cfg.hessian_subsample
        width = self._plan.width_local

        if cfg.partition == "features":
            def step(w, key):
                w = self._maybe_replan_features(w)
                margins = self._stream_xt(w)                  # (n_padded,)
                d1 = loss.d1(margins, self.y) * self.smask
                c = loss.d2(margins, self.y) * self.smask
                g = self._stream_x(d1) / n + lam * w
                gnorm = jnp.sqrt(jnp.vdot(g, g))
                if obs.enabled():
                    # host-driven path: count the outer margins/gradient
                    # rounds at their call site (disco_f_outer_cost)
                    r_outer = comm.disco_f_outer_cost(n, self.d, m)[0]
                    obs.count("comm.rounds", r_outer)
                    for _ in range(r_outer):
                        obs.instant("comm.allreduce", phase="outer")
                fval = jnp.sum(loss.value(margins, self.y)
                               * self.smask) / n \
                    + 0.5 * lam * jnp.vdot(w, w)
                if frac < 1.0:
                    mask = jax.random.bernoulli(key, frac, margins.shape)
                    c_eff = c * mask / frac
                else:
                    c_eff = c
                coeffs_tau = loss.d2(margins[:tau], self.y_tau)

                if cfg.precond == "woodbury":
                    from repro.core.preconditioner import \
                        WoodburyPreconditioner
                    blocks = [WoodburyPreconditioner.build_blockdiag(
                        self.X_tau[s], coeffs_tau, lam, cfg.mu)
                        for s in range(m)]

                    def apply_precond(r):
                        return jnp.concatenate(
                            [blocks[s].apply_inv(
                                r[s * width:(s + 1) * width])
                             for s in range(m)])
                elif cfg.precond == "none":
                    apply_precond = lambda r: r
                else:
                    raise ValueError(
                        f"unknown precond {cfg.precond!r} for streaming "
                        "DiSCO-F")

                # two-pass only: the pass-A accumulation over chunks IS
                # the cross-shard reduce, so the fused flag is rejected
                # at from_store (see core/hvp.py registry)
                op = StreamedHvpOperator(
                    apply=lambda u: self._stream_x(
                        self._stream_xt(u, hvp=True), coeffs=c_eff,
                        hvp=True),
                    apply_multi=lambda U: self._stream_x(
                        self._stream_xt(U, hvp=True), coeffs=c_eff,
                        hvp=True),
                    pass_a=lambda u: self._stream_xt(u, hvp=True),
                    pass_b=lambda z: self._stream_x(
                        z, coeffs=c_eff, hvp=True),
                    pass_a_multi=lambda U: self._stream_xt(U, hvp=True),
                    pass_b_multi=lambda Z: self._stream_x(
                        Z, coeffs=c_eff, hvp=True))

                def hvp(u):
                    return op.apply(u) / n + lam * u

                def hvp_multi(U):
                    return op.apply_multi(U) / n + lam * U

                def basis_op(u):
                    z_loc = self._stream_xt(u, local=True, hvp=True)
                    return self._stream_x(z_loc, coeffs=c_eff, local=True,
                                          hvp=True) / n + lam * u

                eps = cfg.pcg_rel_tol * gnorm
                res = pcg_streamed(hvp, apply_precond, g, eps,
                                   cfg.max_pcg, block_s=cfg.pcg_block_s,
                                   hvp_multi=hvp_multi, basis_op=basis_op,
                                   variant="features")
                w_new = w - res.v / (1.0 + res.delta)
                stats = dict(grad_norm=gnorm, f=fval, pcg_iters=res.iters,
                             delta=res.delta, pcg_r_norm=res.r_norm)
                return w_new, stats

        else:  # samples
            def step(w, key):
                margins = self._stream_margins_samples(w)    # permuted (n_p,)
                d1 = loss.d1(margins, self.y) * self.weights
                c = loss.d2(margins, self.y) * self.weights
                g = self._stream_grad_samples(d1) / n + lam * w
                gnorm = jnp.sqrt(jnp.vdot(g, g))
                if obs.enabled():
                    r_outer = comm.disco_s_outer_cost(self.d)[0]
                    obs.count("comm.rounds", r_outer)
                    for _ in range(r_outer):
                        obs.instant("comm.allreduce", phase="outer")
                fval = jnp.sum(loss.value(margins, self.y)
                               * self.weights) / n \
                    + 0.5 * lam * jnp.vdot(w, w)
                if frac < 1.0:
                    # identical per-shard draws as the in-memory
                    # _shard_subsample_mask (key folded with shard index)
                    mask = jnp.concatenate(
                        [jax.random.bernoulli(
                            jax.random.fold_in(key, s), frac, (width,))
                         for s in range(m)])
                    c_eff = c * mask / frac
                else:
                    c_eff = c
                coeffs_tau = loss.d2(self.X_tau.T @ w, self.y_tau)

                from repro.core.pcg import _samples_precond
                apply_precond = _samples_precond(
                    cfg.precond, self.X_tau, coeffs_tau, lam, cfg.mu,
                    cfg.sag_epochs)

                # mutable holder of the n-space (permuted) coefficients:
                # an elastic re-plan between PCG rounds re-permutes it
                # in place, so the hvp closures always stream the
                # layout the CURRENT schedule expects
                state = dict(c_eff=c_eff)

                op = StreamedHvpOperator(
                    apply=lambda u: self._stream_hvp_samples(
                        u, state["c_eff"]),
                    apply_multi=lambda U: self._stream_hvp_samples(
                        U, state["c_eff"]),
                    fused=cfg.hvp_fused)

                def hvp(u):
                    return op.apply(u) / n + lam * u

                def hvp_multi(U):
                    return op.apply_multi(U) / n + lam * U

                if m == 1:
                    basis_op = hvp            # exact single-shard operator
                else:
                    tau_f = jnp.asarray(tau, self.X_tau.dtype)

                    def basis_op(u):
                        return self.X_tau @ (coeffs_tau
                                             * (self.X_tau.T @ u)) \
                            / tau_f + lam * u

                between = (
                    (lambda: self._maybe_replan_samples(state))
                    if self._replanner is not None else None)
                eps = cfg.pcg_rel_tol * gnorm
                res = pcg_streamed(hvp, apply_precond, g, eps,
                                   cfg.max_pcg, block_s=cfg.pcg_block_s,
                                   hvp_multi=hvp_multi, basis_op=basis_op,
                                   variant="samples",
                                   between_rounds=between)
                w_new = w - res.v / (1.0 + res.delta)
                stats = dict(grad_norm=gnorm, f=fval, pcg_iters=res.iters,
                             delta=res.delta, pcg_r_norm=res.r_norm)
                return w_new, stats

        return step

    # ------------------------------------------------------------------
    def with_lam(self, lam: float) -> "DiscoSolver":
        """Cheap clone at a different regularization weight — the λ-path
        primitive (:mod:`repro.core.lambda_path`).

        Shares every sharded device array (X in its layout, its HVP-dtype
        twin, labels, the tau slab) with ``self`` and rebuilds only the
        compiled step, so sweeping a λ grid pays the data layout once.
        In-memory solvers only; streaming solves rebuild via
        :meth:`from_store` per λ.
        """
        if self._streaming:
            raise ValueError(
                "with_lam shares in-memory device arrays; a streaming "
                "solver must be rebuilt with DiscoSolver.from_store for "
                "each lam")
        import copy

        new = copy.copy(self)
        new.cfg = dataclasses.replace(self.cfg, lam=float(lam))
        new._replan_events = []
        new._outer_iter = 0
        new._step = new._build_step()
        return new

    # ------------------------------------------------------------------
    def _comm_costs(self, pcg_iters: int) -> tuple[int, int, int]:
        """``pcg_iters`` is PCG iterations for the classic path and *rounds*
        (each worth ``pcg_block_s`` iterations) for the s-step path."""
        s = self.cfg.pcg_block_s
        if self.cfg.partition == "features":
            r1, f1, s1 = comm.disco_f_outer_cost(self.n, self.d, self.m)
            if s > 1:
                r2, f2, s2 = comm.disco_f_sstep_cost(self.n, s, pcg_iters)
            else:
                r2, f2, s2 = comm.disco_f_pcg_cost(self.n, pcg_iters)
        else:
            r1, f1, s1 = comm.disco_s_outer_cost(self.d)
            if s > 1:
                r2, f2, s2 = comm.disco_s_sstep_cost(self.d, s, pcg_iters)
            else:
                r2, f2, s2 = comm.disco_s_pcg_cost(self.d, pcg_iters)
        return r1 + r2, f1 + f2, s1 + s2

    def _w_to_original(self, w) -> np.ndarray:
        """Iterate ``w`` back in the original feature order (padding
        slots dropped, any load-balancing permutation undone)."""
        if self._sparse and self.cfg.partition == "features":
            w_np = np.asarray(w)
            w_full = np.zeros(self.d, w_np.dtype)
            valid = self._part.perm < self.d
            w_full[self._part.perm[valid]] = w_np[valid]
            return w_full
        return np.asarray(w)[: self.d]

    def _cfg_fingerprint(self) -> dict:
        """JSON-canonical view of ``cfg`` (what checkpoints compare).

        ``trace`` is excluded: the observability toggle changes nothing
        about the solve, so a traced resume of an untraced checkpoint
        (or vice versa) must not be refused.
        """
        import json
        cfg_dict = dataclasses.asdict(self.cfg)
        cfg_dict.pop("trace", None)
        return json.loads(json.dumps(cfg_dict, default=float))

    def fit(self, w0: np.ndarray | None = None, *,
            checkpoint_dir: str | None = None, checkpoint_every: int = 1,
            resume: bool = False) -> DiscoResult:
        """Run the damped Newton outer loop from ``w0`` (default zeros).

        ``w0`` is given — and ``DiscoResult.w`` returned — in the
        original feature order; any internal padding and load-balancing
        permutation is applied/undone here.

        Checkpointing (docs/robustness.md): with ``checkpoint_dir`` the
        outer state (iterate, RNG key, history, communication ledger,
        re-plan events) is atomically persisted every
        ``checkpoint_every`` iterations via
        :mod:`repro.robust.checkpoint`. ``resume=True`` restarts from
        the newest snapshot there (a no-op when none exists) and
        continues the exact uninterrupted trajectory; the checkpoint's
        config must match ``cfg`` — mixing two solves raises
        ``ValueError``. The iterate is stored in original feature
        order, so a resume may land on a different mesh size or a
        re-planned schedule.
        """
        cfg = self.cfg
        dtype = self._plan.store.dtype if self._streaming else self.X.dtype

        history: list[dict[str, Any]] = []
        ledger = comm.CommLedger()
        key = jax.random.PRNGKey(cfg.seed)
        start_iter = 0
        if checkpoint_dir is not None and resume:
            state = load_checkpoint(checkpoint_dir)
            if state is not None:
                if state.cfg != self._cfg_fingerprint():
                    raise ValueError(
                        f"checkpoint at {checkpoint_dir!r} was written "
                        "by a solve with a different config; refusing "
                        "to resume (delete the checkpoint directory or "
                        "match the config)")
                w0 = state.w
                history = list(state.history)
                ledger = comm.CommLedger(**state.ledger)
                key = jnp.asarray(state.key)
                start_iter = state.next_iter
                self._replan_events = list(state.replan_events)

        if w0 is None:
            w = jnp.zeros(self._w_shape, dtype)
        else:
            w0 = np.pad(np.asarray(w0), (0, self._w_shape[0] - len(w0)))
            if self._sparse and cfg.partition == "features":
                w0 = w0[self._part.perm]  # into load-balanced order
            w = jnp.asarray(w0.astype(dtype))
        w = jax.device_put(w, self._w_sharding)

        converged = False
        for k in range(start_iter, cfg.max_outer):
            with obs.span("newton.outer", outer_iter=k,
                          streaming=bool(self._streaming)):
                self._outer_iter = k
                if self._faults is not None:
                    self._faults.on_outer_step(k)
                key, sub = jax.random.split(key)
                t_it = time.perf_counter()
                with obs.span("newton.step"):
                    # block on the step's outputs, so the span (and
                    # iter_s) covers the device work, not its dispatch
                    w, stats = jax.block_until_ready(self._step(w, sub))
                    stats = {name: float(v) for name, v in stats.items()}
                stats["iter_s"] = time.perf_counter() - t_it
                rounds, floats, spmd = self._comm_costs(
                    int(stats["pcg_iters"]))
                ledger.add(rounds, floats, spmd)
                obs.count("comm.floats", floats)
                obs.count("comm.spmd_collectives", spmd)
                if not self._streaming:
                    # in-memory PCG runs inside a jitted while_loop where
                    # per-round events are invisible; tally the analytic
                    # rounds instead. Streamed solves count at the actual
                    # call sites (step closures + pcg_streamed) — the
                    # independent tally bench_obs cross-checks.
                    obs.count("comm.rounds", rounds)
                stats.update(outer_iter=k, comm_rounds_cum=ledger.rounds,
                             comm_floats_cum=ledger.floats)
                history.append(stats)
                if checkpoint_dir is not None \
                        and (k + 1) % max(checkpoint_every, 1) == 0:
                    save_checkpoint(checkpoint_dir, CheckpointState(
                        next_iter=k + 1, w=self._w_to_original(w),
                        key=np.asarray(key), history=history,
                        ledger=dict(
                            rounds=ledger.rounds, floats=ledger.floats,
                            spmd_collectives=ledger.spmd_collectives),
                        replan_events=list(self._replan_events),
                        cfg=self._cfg_fingerprint()))
                if stats["grad_norm"] <= cfg.grad_tol:
                    converged = True
                    break

        w_full = self._w_to_original(w)
        stream_stats = None
        if self._streaming:
            st = self._plan.stats
            stream_stats = dict(passes=st.passes, steps=st.steps,
                                bytes_loaded=st.bytes_loaded,
                                peak_bytes=st.peak_bytes,
                                max_step_bytes=st.max_step_bytes)
        return DiscoResult(w=w_full, history=history, ledger=ledger,
                           converged=converged,
                           partition_info=(self._part.stats()
                                           if self._part else None),
                           stream_stats=stream_stats,
                           replan_events=list(self._replan_events))


def disco_fit(X, y, cfg: DiscoConfig | None = None, mesh: Mesh | None = None,
              w0: np.ndarray | None = None) -> DiscoResult:
    """One-call convenience wrapper: build a :class:`DiscoSolver`, fit.

    Args:
        X: (d, n) feature-major data — dense array or
            :class:`repro.data.sparse.CSRMatrix` (the latter engages the
            load-balanced sparse path, docs/partitioning.md).
        y: (n,) labels.
        cfg: solver hyperparameters (defaults: :class:`DiscoConfig`).
        mesh: optional 1-axis mesh; defaults to all local devices.
        w0: optional (d,) warm start in original feature order.

    Returns:
        :class:`DiscoResult` with the solution, per-iteration history,
        communication ledger, and (sparse only) partition_info.
    """
    cfg = cfg or DiscoConfig()
    return DiscoSolver(X, y, cfg, mesh=mesh).fit(w0)


def disco_fit_streaming(X, y, store_path: str,
                        cfg: DiscoConfig | None = None,
                        mesh: Mesh | None = None,
                        w0: np.ndarray | None = None) -> DiscoResult:
    """Out-of-core convenience wrapper: convert once, then stream.

    Converts ``(X, y)`` (a :class:`repro.data.sparse.CSRMatrix` +
    labels) into a :class:`repro.data.store.ShardStore` at
    ``store_path`` — chunked along ``cfg.partition`` with
    ``cfg.stream_chunk_size`` indices per chunk — and fits with
    :meth:`DiscoSolver.from_store`, whose peak data-plane memory is
    bounded by chunk size x ``cfg.prefetch_depth``, not dataset size
    (docs/streaming.md). Reuse an existing store directory directly via
    ``DiscoSolver.from_store(ShardStore(path), cfg)`` to skip the
    conversion.
    """
    from repro.data.store import ShardStore

    cfg = cfg or DiscoConfig()
    store = ShardStore.from_csr(X, y, store_path, axis=cfg.partition,
                                chunk_size=cfg.stream_chunk_size)
    return DiscoSolver.from_store(store, cfg, mesh=mesh).fit(w0)
