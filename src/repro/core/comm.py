"""Analytic communication accounting (paper Tables 2-4).

JAX/XLA emits the collectives; this module *counts* them the way the paper
does, so benchmarks can report "rounds of communication" and bytes moved
per algorithm. The counts below mirror the paper's Table 4 plus the per-outer
costs visible in Algorithms 2 and 3:

  DiSCO-S, per outer iteration : broadcast w_k (d) + reduceAll grad (d)
  DiSCO-S, per PCG iteration   : broadcast u_t (d) + reduceAll H u_t (d)
  DiSCO-F, per outer iteration : reduceAll margins (n) + final reduce v (d_j)
  DiSCO-F, per PCG iteration   : reduceAll (n) + 2 scalar reduceAlls

Under SPMD a broadcast+reduceAll pair of a replicated vector collapses into a
single all-reduce; we report both views (``paper_rounds`` — what an MPI
implementation pays — and ``spmd_collectives`` — what the lowered HLO
contains; the dry-run roofline cross-checks the latter).

DANE  : 2 reduceAll (d) per iteration (grad, then averaged local solution).
CoCoA+: 1 reduceAll (d) per outer iteration.
"""
from __future__ import annotations

import dataclasses

import numpy as np

BYTES_PER_FLOAT = 4  # f32 throughout


@dataclasses.dataclass
class CommLedger:
    rounds: int = 0          # paper-style rounds (MPI view)
    floats: int = 0          # total vector elements moved through collectives
    spmd_collectives: int = 0

    def add(self, rounds: int, floats: int, spmd: int | None = None):
        self.rounds += rounds
        self.floats += floats
        self.spmd_collectives += spmd if spmd is not None else rounds

    @property
    def bytes(self) -> int:
        return self.floats * BYTES_PER_FLOAT

    def merged(self, other: "CommLedger") -> "CommLedger":
        return CommLedger(self.rounds + other.rounds,
                          self.floats + other.floats,
                          self.spmd_collectives + other.spmd_collectives)


def disco_s_outer_cost(d: int) -> tuple[int, int, int]:
    """(rounds, floats, spmd) for one outer iteration excluding PCG."""
    return 2, 2 * d, 1


def disco_s_pcg_cost(d: int, iters: int) -> tuple[int, int, int]:
    """(rounds, floats, spmd) for ``iters`` classic DiSCO-S PCG
    iterations: per iteration one d-vector broadcast of the probe u_t
    plus one d-vector reduceAll of H u_t (a single SPMD all-reduce)."""
    return 2 * iters, 2 * d * iters, 1 * iters


def disco_f_outer_cost(n: int, d: int, m: int) -> tuple[int, int, int]:
    """(rounds, floats, spmd) for one DiSCO-F outer iteration excluding
    PCG: the margins reduceAll (n floats) + the final "Reduce an R^{d_j}
    vector" of Algorithm 3 line 12 (d floats total — the result stays
    sharded). Under SPMD only the margins psum materializes; v never
    leaves its shard (the d-float reduce is counted in ``floats`` for
    MPI fidelity)."""
    return 2, n + d, 1


def disco_f_pcg_cost(n: int, iters: int) -> tuple[int, int, int]:
    """(rounds, floats, spmd) for ``iters`` classic DiSCO-F PCG
    iterations: one n-vector reduceAll each, plus two scalar reduceAlls
    — the paper's "thin red arrows, a few scalars only" (Fig 2), counted
    in floats and SPMD collectives but not as vector *rounds*. This is
    the accounting under which "DiSCO-F uses half the rounds of DiSCO-S"
    (§5.2) holds."""
    return 1 * iters, (n + 2) * iters, 3 * iters


def disco_s_sstep_cost(d: int, s: int, rounds: int) -> tuple[int, int, int]:
    """s-step DiSCO-S (core/pcg.py, block_s > 1): per round the master
    broadcasts the (d, s+1) trial basis and reduceAlls the (d, s+1) batched
    HVP — the same broadcast+reduceAll pair as ONE classic iteration but
    carrying s+1 vectors, advancing s Krylov dimensions. The Gram system is
    replicated, so it costs nothing. Under SPMD the pair collapses into a
    single all-reduce (1 collective/round vs s for classic)."""
    k = s + 1
    return 2 * rounds, 2 * d * k * rounds, 1 * rounds


def disco_f_sstep_cost(n: int, s: int, rounds: int) -> tuple[int, int, int]:
    """s-step DiSCO-F: per round ONE (n, s) reduceAll (the batched pass-A
    payload — only the s Krylov columns; H p_prev is carried from the
    previous round's W a, costing nothing) plus one fused small reduceAll
    of the stacked Gram system (2(s+1)^2 + (s+1) floats — U^T W, U^T U,
    U^T r concatenated into a single psum payload). Consistent with
    ``disco_f_pcg_cost``, the small reduce is the s-step analogue of the
    classic path's "thin red arrow" scalar reduceAlls: counted in floats
    and SPMD collectives, not as a vector *round*."""
    k = s + 1
    return 1 * rounds, (n * s + 2 * k * k + k) * rounds, 2 * rounds


def dane_iter_cost(d: int) -> tuple[int, int, int]:
    """(rounds, floats, spmd) for one DANE iteration: two d-vector
    reduceAlls (gradient, then the averaged local solution)."""
    return 2, 2 * d, 2


def cocoa_iter_cost(d: int) -> tuple[int, int, int]:
    """(rounds, floats, spmd) for one CoCoA+ outer iteration: a single
    d-vector reduceAll of the aggregated local updates."""
    return 1, d, 1


# ---------------------------------------------------------------------------
# load-balance extension (paper title contribution; docs/partitioning.md)
#
# Every collective above is a *barrier*: the mesh advances at the pace of
# the slowest shard. With sparse data the per-shard work between barriers
# is proportional to that shard's nonzeros, so the compute term of any
# per-iteration time estimate must be gated by max_shard_nnz — not the
# mean. ``max/mean`` is exactly the imbalance metric the LPT partitioner
# minimizes (repro.data.partition).
# ---------------------------------------------------------------------------

def sparse_hvp_flops(nnz: int) -> int:
    """Flops of one sparse HVP application: two passes over the nonzeros
    (X^T u then X (c.*z)), one multiply-add each -> 4 flops/nnz."""
    return 4 * nnz


# ---------------------------------------------------------------------------
# HVP HBM-traffic model (docs/kernels.md; gate: benchmarks/bench_hvp_fused)
#
# The HVP is memory-bound (~2 flops/byte at f32), so the bytes the data
# tiles move through HBM — not the flops — bound the PCG inner loop. The
# two levers this model prices: the fused ONE-PASS kernels read the X
# tiles once per application instead of twice, and bf16 tile storage
# (DiscoConfig.hvp_dtype) halves the bytes per element again.
# ---------------------------------------------------------------------------

BYTES_BF16 = 2


def hvp_dtype_bytes(hvp_dtype: str) -> int:
    """Bytes per stored tile element for a ``DiscoConfig.hvp_dtype``.

    Resolved through :func:`repro.data.sparse.hvp_tile_dtype` (lazy
    import) so the cost model and the tile builders can never disagree
    on the accepted dtype spellings or widths.
    """
    from repro.data.sparse import hvp_tile_dtype
    return int(hvp_tile_dtype(hvp_dtype).itemsize)


def dense_hvp_bytes(d: int, n: int, s: int = 1, *, fused: bool = False,
                    dtype_bytes: int = BYTES_PER_FLOAT) -> int:
    """X-tile HBM bytes of ONE dense (multi-)HVP application.

    The two-pass kernels stream the full (d, n) tile set twice (pass A
    ``X^T u``, pass B ``X (c.*z)``); the fused one-pass kernel streams
    it once. The s probe vectors of a multi-HVP share the same tile
    stream either way (the s-step amortization), so ``s`` does not
    appear — it raises arithmetic intensity, not bytes.
    """
    del s  # tiles are shared across probe vectors; bytes are per pass
    passes = 1 if fused else 2
    return passes * d * n * dtype_bytes


def ell_hvp_bytes(tiles_fwd: int, tiles_tr: int, block_rows: int,
                  block_cols: int, *, fused: bool = False,
                  dtype_bytes: int = BYTES_PER_FLOAT) -> int:
    """Blocked-ELL tile HBM bytes of ONE sparse (multi-)HVP application.

    ``tiles_fwd``/``tiles_tr`` are the *padded* tile counts of the
    forward and transposed layouts (``n_row_blocks * width`` each). The
    two-pass pair reads both layouts once; the fused kernel reads only
    the transposed layout — the forward tiles are never touched.
    """
    tile = block_rows * block_cols * dtype_bytes
    return (tiles_tr if fused else tiles_fwd + tiles_tr) * tile


def straggler_factor(shard_nnz) -> float:
    """max_shard_nnz / mean_shard_nnz: the factor by which barrier
    collectives stretch the compute phase of a skewed partition (1.0 is a
    perfect balance). Identical to
    :func:`repro.data.partition.imbalance`; duplicated arithmetic here so
    the cost model has no data-layer dependency."""
    shard_nnz = np.asarray(shard_nnz, np.float64)
    mean = shard_nnz.mean()
    return float(shard_nnz.max() / mean) if mean > 0 else 1.0


def disco_sparse_iter_time(shard_nnz, pcg_iters: int, partition: str,
                           n: int, d: int, m: int, s: int = 1, *,
                           flops_per_sec: float = 5e11,
                           bytes_per_sec: float = 1e10,
                           latency_s: float = 5e-6,
                           hvp_fused: bool = False,
                           hvp_dtype_bytes: int = BYTES_PER_FLOAT,
                           hbm_bytes_per_sec: float = 8e11) -> dict:
    """Modeled seconds for ONE Newton iteration on a sparse partition.

    compute: (pcg_iters + 1) HVP applications (PCG loop + the margins/
    gradient pass), each the *heavier* of its MXU time
    (:func:`sparse_hvp_flops`) and its HBM time (the value bytes the
    tile stream moves: one pass over the nonzeros when ``hvp_fused``,
    two otherwise, at ``hvp_dtype_bytes`` per element) on the heaviest
    shard — the straggler gates every barrier, and the HVP is
    memory-bound, so the bytes term usually wins.
    comm: the paper-style (rounds, floats) of the matching cost function
    above, charged ``latency_s`` per round plus wire time.

    Returns a dict with ``compute_s``, ``hvp_bytes`` (per application),
    ``comm_s``, ``total_s`` and ``straggler`` so benchmarks can
    attribute the win of LPT balancing
    (``benchmarks/bench_loadbalance.py``) and of the fused/bf16 HVP
    (``benchmarks/bench_hvp_fused.py``).
    """
    shard_nnz = np.asarray(shard_nnz, np.float64)
    max_nnz = float(shard_nnz.max()) if len(shard_nnz) else 0.0

    if partition == "features":
        r1, f1, _ = disco_f_outer_cost(n, d, m)
        if s > 1:
            r2, f2, _ = disco_f_sstep_cost(n, s, pcg_iters)
        else:
            r2, f2, _ = disco_f_pcg_cost(n, pcg_iters)
    elif partition == "samples":
        r1, f1, _ = disco_s_outer_cost(d)
        if s > 1:
            r2, f2, _ = disco_s_sstep_cost(d, s, pcg_iters)
        else:
            r2, f2, _ = disco_s_pcg_cost(d, pcg_iters)
    else:
        raise ValueError(f"unknown partition {partition!r}")

    hvp_apps = pcg_iters * max(s, 1) + 1
    hvp_bytes = (1 if hvp_fused else 2) * max_nnz * hvp_dtype_bytes
    per_app = max(sparse_hvp_flops(int(max_nnz)) / flops_per_sec,
                  hvp_bytes / hbm_bytes_per_sec)
    compute_s = hvp_apps * per_app
    comm_s = (r1 + r2) * latency_s \
        + (f1 + f2) * BYTES_PER_FLOAT / bytes_per_sec
    return dict(compute_s=compute_s, hvp_bytes=hvp_bytes, comm_s=comm_s,
                total_s=compute_s + comm_s,
                straggler=straggler_factor(shard_nnz))


# ---------------------------------------------------------------------------
# out-of-core streaming extension (docs/streaming.md)
#
# When the data plane lives on disk (repro.data.store + repro.data.stream),
# every HVP re-reads the shard's chunks; the prefetch pipeline overlaps
# that I/O with kernel execution, so the per-iteration wall-clock pays
# max(io, compute), not their sum — plus a one-time pipeline fill of
# prefetch_depth chunks at the head of each pass.
# ---------------------------------------------------------------------------

STREAM_BYTES_PER_NNZ = 8  # stored CSR chunk payload: 4B value + 4B index


def streaming_data_passes(partition: str, pcg_iters: int, s: int = 1) -> int:
    """Full passes over the on-disk shard data for ONE Newton iteration.

    DiSCO-S sample-chunks complete both HVP directions per chunk (one
    pass per HVP application; the s-step basis operator is the resident
    tau-sample estimate, costing no I/O); DiSCO-F feature-chunks must
    finish pass A (the n-vector) before pass B starts (two passes per
    operator application, including each of the ``s - 1`` streamed
    zero-communication basis products of an s-step round). The margins +
    gradient of the outer step add 2 (features) / 2 (samples) passes.
    """
    if partition == "features":
        per_round = 2 * max(s, 1)            # 2(s-1) basis + 2 true HVP
        return 2 + pcg_iters * per_round
    if partition == "samples":
        return 2 + pcg_iters
    raise ValueError(f"unknown partition {partition!r}")


def disco_streaming_iter_time(shard_nnz, pcg_iters: int, partition: str,
                              n: int, d: int, m: int, s: int = 1, *,
                              chunk_nnz_max: int, prefetch_depth: int = 2,
                              flops_per_sec: float = 5e11,
                              bytes_per_sec: float = 1e10,
                              latency_s: float = 5e-6,
                              disk_bytes_per_sec: float = 2e9,
                              hvp_fused: bool = False,
                              hvp_dtype_bytes: int = BYTES_PER_FLOAT,
                              hbm_bytes_per_sec: float = 8e11) -> dict:
    """Modeled seconds for ONE Newton iteration of a *streaming* solve.

    Extends :func:`disco_sparse_iter_time` with the I/O plane: every data
    pass re-reads the heaviest shard's chunk bytes from disk
    (``STREAM_BYTES_PER_NNZ`` per nonzero), and the prefetch pipeline
    credits I/O–compute overlap: the streamed phase costs
    ``max(io_s, compute_s)`` plus a pipeline fill of ``prefetch_depth``
    chunks per pass, instead of ``io_s + compute_s``. The ``hvp_*``
    levers reach the compute/HBM term through the base model; disk
    bytes are unchanged (chunks are stored f32 CSR regardless — the
    fused/bf16 win is in the staged tile plane, not the disk format).

    Returns a dict with ``io_s``, ``compute_s``, ``comm_s``, ``fill_s``,
    the overlapped ``total_s``, the naive ``total_no_overlap_s``, and
    ``overlap_savings_s`` so benchmarks can attribute the pipeline win.
    """
    base = disco_sparse_iter_time(
        shard_nnz, pcg_iters, partition, n=n, d=d, m=m, s=s,
        flops_per_sec=flops_per_sec, bytes_per_sec=bytes_per_sec,
        latency_s=latency_s, hvp_fused=hvp_fused,
        hvp_dtype_bytes=hvp_dtype_bytes,
        hbm_bytes_per_sec=hbm_bytes_per_sec)
    shard_nnz = np.asarray(shard_nnz, np.float64)
    max_nnz = float(shard_nnz.max()) if len(shard_nnz) else 0.0
    passes = streaming_data_passes(partition, pcg_iters, s)
    io_s = passes * max_nnz * STREAM_BYTES_PER_NNZ / disk_bytes_per_sec
    fill_s = passes * prefetch_depth * chunk_nnz_max \
        * STREAM_BYTES_PER_NNZ / disk_bytes_per_sec
    compute_s, comm_s = base["compute_s"], base["comm_s"]
    total = comm_s + max(io_s, compute_s) + fill_s
    total_naive = comm_s + io_s + compute_s + fill_s
    return dict(io_s=io_s, compute_s=compute_s, comm_s=comm_s,
                fill_s=fill_s, data_passes=passes, total_s=total,
                total_no_overlap_s=total_naive,
                overlap_savings_s=total_naive - total,
                straggler=base["straggler"])


# ---------------------------------------------------------------------------
# online serving extension (docs/serving.md)
#
# The inference plane (repro.glm_serve) scores feature-vector requests
# as (id, value) slots gathered against device-resident weights. Its
# latency structure is the inverse of training's: per *tick* there is ONE
# step dispatch (jit call, host->device staging, launch) whose fixed cost
# dwarfs the per-request sparse dot product, so sequential
# single-request scoring is dispatch-bound and micro-batching B requests
# amortizes the dispatch over B — the ">= 4x at batch 64" gate of
# benchmarks/bench_serving.py is exactly this amortization.
# ---------------------------------------------------------------------------

#: bytes of one packed scoring slot: an int32 feature id and an f32 value
SLOT_BYTES = 4 + BYTES_PER_FLOAT


def scoring_flops(nnz: int) -> int:
    """Flops of scoring stored request nonzeros: one multiply-add per
    nonzero of the packed request batch (margins only — the loss link
    is O(batch) and negligible)."""
    return 2 * nnz


def glm_serving_tick_time(batch: int, nnz_per_req: float, *, slots: int,
                          dispatch_s: float = 2e-4,
                          flops_per_sec: float = 5e11,
                          bytes_per_sec: float = 1e10) -> dict:
    """Modeled seconds for ONE micro-batched scoring tick of ``batch``
    requests packed ``slots`` (id, value) slots wide (``k`` of
    :class:`repro.glm_serve.scoring.RequestPacker`).

    Three terms: the fixed per-tick ``dispatch_s`` (jit call + launch —
    paid once per tick regardless of batch); wire time for staging the
    pack, ``batch * slots`` slots of :data:`SLOT_BYTES` (padding slots
    cost bytes too); and compute time for the useful flops
    (:func:`scoring_flops` over ``batch * nnz_per_req`` nonzeros).

    Returns a dict with ``dispatch_s``, ``stage_s``, ``compute_s``,
    ``total_s`` and ``per_request_s``.
    """
    stage_s = max(batch, 1) * slots * SLOT_BYTES / bytes_per_sec
    compute_s = scoring_flops(int(batch * nnz_per_req)) / flops_per_sec
    total = dispatch_s + stage_s + compute_s
    return dict(dispatch_s=dispatch_s, stage_s=stage_s,
                compute_s=compute_s, total_s=total,
                per_request_s=total / max(batch, 1))


def glm_serving_throughput(batch: int, nnz_per_req: float, *, slots: int,
                           dispatch_s: float = 2e-4,
                           flops_per_sec: float = 5e11,
                           bytes_per_sec: float = 1e10) -> dict:
    """Modeled requests/second of micro-batched vs sequential scoring.

    ``batched_rps`` runs ticks of ``batch`` requests; ``sequential_rps``
    runs batch-1 ticks (one dispatch *per request* — the degenerate
    schedule the ``bench_serving`` gate compares against), both
    ``slots`` wide. Their ratio ``speedup`` approaches ``dispatch_s /
    per_request_work`` as requests shrink: the smaller the request, the
    more batching pays.
    """
    kw = dict(slots=slots, dispatch_s=dispatch_s,
              flops_per_sec=flops_per_sec, bytes_per_sec=bytes_per_sec)
    tick = glm_serving_tick_time(batch, nnz_per_req, **kw)
    single = glm_serving_tick_time(1, nnz_per_req, **kw)
    batched_rps = batch / tick["total_s"]
    sequential_rps = 1.0 / single["total_s"]
    return dict(batched_rps=batched_rps, sequential_rps=sequential_rps,
                speedup=batched_rps / sequential_rps,
                tick_s=tick["total_s"])


def elastic_replan_model(chunk_seconds, schedule_before, schedule_after,
                         passes_remaining: int,
                         replan_overhead_s: float = 0.0) -> dict:
    """Modeled wall-clock of finishing a solve with vs without a re-plan.

    The elastic re-planner (:mod:`repro.robust.straggler`) swaps the
    chunk->shard schedule when observed per-chunk seconds are imbalanced;
    this is the analytic twin of that decision, in the same barrier terms
    the rest of this module uses: one pass of a schedule costs
    ``sum_t max_s chunk_seconds`` (every collective waits for the
    slowest shard), so ``passes_remaining`` passes cost that much each,
    and the re-planned variant additionally pays ``replan_overhead_s``
    once (the LPT re-run plus re-permuting the resident vectors — no
    chunk data moves, chunks live in the store).

    Returns a dict with ``static_s`` (keep the old schedule),
    ``replanned_s`` (overhead + new-schedule passes), ``gain``
    (static / replanned; > 1 means the re-plan pays), and
    ``break_even_passes`` (passes after which it pays; ``inf`` when the
    new schedule is no faster).

    The ``bench_faults`` gate checks the *measured* counterpart of
    ``gain`` on an injected 4x straggler.
    """
    from repro.robust.straggler import barrier_seconds

    cs = np.asarray(chunk_seconds, np.float64)
    before = barrier_seconds(np.asarray(schedule_before), cs)
    after = barrier_seconds(np.asarray(schedule_after), cs)
    static_s = before * passes_remaining
    replanned_s = replan_overhead_s + after * passes_remaining
    per_pass_gain = before - after
    break_even = (replan_overhead_s / per_pass_gain
                  if per_pass_gain > 0 else float("inf"))
    return dict(static_s=float(static_s),
                replanned_s=float(replanned_s),
                gain=float(static_s / replanned_s) if replanned_s > 0
                else float("inf"),
                break_even_passes=float(break_even))
