"""Out-of-core streaming DiSCO: correctness + bounded-memory gate (ISSUE 3).

On a power-law sparse synthetic, for both partition axes:

  * convert the dataset once into an on-disk ShardStore (chunked along
    the partition axis at >= 8x dataset-to-chunk ratio), then solve with
    the async-prefetch streaming solver (``DiscoSolver.from_store``) and
    with the in-memory sparse solver at the *same* chunk-granular LPT
    partition (``DiscoConfig.partition_block``);
  * compare the converged solutions (the paper's regime: the data never
    fits, the answer must still match);
  * read the prefetch pipeline's byte ledger: peak resident data-plane
    bytes must be bounded by ``chunk payload x (prefetch_depth + 2)``
    and far below one full pass over the dataset — and must *scale* with
    the chunk size, which we verify by re-running with 2x chunks;
  * report the modeled streaming iteration time with and without
    I/O-compute overlap (``comm.disco_streaming_iter_time``).

Acceptance gate (ISSUE 3): streaming ``w_final`` matches in-memory to
<= 1e-5 relative error on BOTH partitions, and peak resident data-plane
bytes scale with ``chunk_size x prefetch_depth``, not total nnz.
"""
from __future__ import annotations

import os
import sys
import tempfile

import numpy as np

from benchmarks.common import Timer, save_json, smoke, table
from repro.core import DiscoConfig, DiscoSolver, comm
from repro.data.sparse import make_sparse_glm_data
from repro.data.store import ShardStore
from repro.kernels.ops import ref_kernels_off_tpu

if smoke():
    D, N, DENSITY = 128, 256, 0.05
    CHUNKS = 8                  # dataset-to-chunk ratio (>= 8x gate floor)
    MAX_OUTER, TAU = 8, 16
else:
    D, N, DENSITY = 512, 2048, 0.02
    CHUNKS = 16
    MAX_OUTER, TAU = 15, 32
GRAD_TOL = 2e-8                 # the f32 gradient noise floor
ALPHA, BETA = 1.2, 0.8
BLOCK = 8                       # ELL tile edge (small; CPU ref-mode bench)
DEPTH = 2


def _fit_pair(X, y, partition, chunk_size, depth=DEPTH):
    """(streaming result, in-memory result, streaming solver)."""
    cfg = DiscoConfig(partition=partition, loss="logistic", lam=1e-2,
                      tau=TAU, max_outer=MAX_OUTER, grad_tol=GRAD_TOL,
                      ell_block_d=BLOCK, ell_block_n=BLOCK,
                      partition_block=chunk_size,
                      stream_chunk_size=chunk_size, prefetch_depth=depth)
    with tempfile.TemporaryDirectory() as td:
        store = ShardStore.from_csr(X, y, os.path.join(td, "store"),
                                    axis=partition, chunk_size=chunk_size)
        solver = DiscoSolver.from_store(store, cfg)
        with Timer() as t_s:
            rs = solver.fit()
        dataset_bytes = store.data_bytes()
    with Timer() as t_m:
        rm = DiscoSolver(X, y, cfg).fit()
    return rs, rm, dataset_bytes, t_s.elapsed, t_m.elapsed


def run(quiet=False):
    ref_kernels_off_tpu()
    X, y, _ = make_sparse_glm_data(d=D, n=N, density=DENSITY, alpha=ALPHA,
                                   beta=BETA, seed=0)
    rows, gate = [], {}
    for partition in ("features", "samples"):
        axis_len = D if partition == "features" else N
        chunk = max(axis_len // CHUNKS, BLOCK)
        rs, rm, dataset_bytes, t_s, t_m = _fit_pair(X, y, partition, chunk)
        rel = float(np.linalg.norm(rs.w - rm.w)
                    / max(np.linalg.norm(rm.w), 1e-30))
        st = rs.stream_stats
        pass_bytes = st["bytes_loaded"] / max(st["passes"], 1)
        bound = (DEPTH + 2) * st["max_step_bytes"]
        # 2x chunks -> peak must track the chunk payload, not total nnz
        rs2, _, _, _, _ = _fit_pair(X, y, partition, 2 * chunk)
        st2 = rs2.stream_stats
        peak_ratio = st2["peak_bytes"] / max(st["peak_bytes"], 1)

        model = comm.disco_streaming_iter_time(
            np.asarray(rs.partition_info["shard_nnz"]),
            pcg_iters=int(rs.history[0]["pcg_iters"]), partition=partition,
            n=N, d=D, m=rs.partition_info["m"],
            chunk_nnz_max=int(max(np.asarray(
                rs.partition_info["shard_nnz"])) // CHUNKS + 1),
            prefetch_depth=DEPTH)

        rows.append(dict(
            partition=partition, chunk=chunk,
            rel_err=rel,
            peak_bytes=st["peak_bytes"],
            peak_bound_bytes=bound,
            pass_bytes=int(pass_bytes),
            dataset_bytes=dataset_bytes,
            peak_ratio_2x_chunk=round(peak_ratio, 2),
            stream_s=round(t_s, 2), inmem_s=round(t_m, 2),
            model_overlap_save_ms=round(
                model["overlap_savings_s"] * 1e3, 3)))
        gate[partition] = dict(
            rel_err=rel, rel_ok=rel <= 1e-5,
            peak_bounded=st["peak_bytes"] <= bound,
            # residency must be a (depth+2)/CHUNKS sliver of a full pass
            # — the "scales with chunk, not nnz" claim at this ratio
            peak_small=st["peak_bytes"]
            <= pass_bytes * (DEPTH + 3) / CHUNKS,
            peak_scales=1.2 <= peak_ratio <= 3.0,
            dataset_to_chunk=CHUNKS)

    ok = all(v["rel_ok"] and v["peak_bounded"] and v["peak_small"]
             and v["peak_scales"] for v in gate.values())
    out = table(rows, ["partition", "chunk", "rel_err", "peak_bytes",
                       "peak_bound_bytes", "pass_bytes", "dataset_bytes",
                       "peak_ratio_2x_chunk", "stream_s", "inmem_s",
                       "model_overlap_save_ms"],
                title=f"out-of-core streaming DiSCO (d={D} n={N}, "
                      f"{CHUNKS} chunks/axis, depth={DEPTH})")
    if not quiet:
        print(out)
        for part, v in gate.items():
            print(f"[gate] {part}: rel_err={v['rel_err']:.2e} "
                  f"(need <=1e-5) peak_bounded={v['peak_bounded']} "
                  f"peak_sliver_of_pass={v['peak_small']} "
                  f"peak_scales_with_chunk={v['peak_scales']}")
        print(f"[gate] {'PASS' if ok else 'FAIL'}: streaming matches "
              "in-memory on both partitions with chunk-bounded peak "
              "data-plane memory")
    save_json("streaming", {"rows": rows, "gate": gate, "pass": ok})
    return rows, ok


def main():
    return run()


if __name__ == "__main__":
    sys.exit(0 if main()[1] else 1)
