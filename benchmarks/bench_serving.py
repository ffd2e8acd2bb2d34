"""Online GLM serving: parity + throughput + warm-refit gate (ISSUE 4).

End-to-end exercise of the inference plane (docs/serving.md) on a
power-law sparse synthetic:

  * **fit → publish**: train with the streaming solver, publish to a
    :class:`repro.glm_serve.registry.ModelRegistry`, reload — the
    weight vector must round-trip **bit-identically**;
  * **scoring parity**: score held-out requests through the
    request packer's (id, value) slots and the engine's gather step, and
    compare against the dense NumPy oracle;
  * **micro-batched throughput**: the same request stream through the
    slot-based scheduler at batch 64 vs sequential single-request
    scoring (one step dispatch per request), p50/p99 latency and the
    modeled speedup (:func:`repro.core.comm.glm_serving_throughput`)
    alongside the measured one;
  * **warm-start refit**: append a fresh sample slice to the store
    (``ShardStore.append_chunks``), refit warm-started at the served
    weights vs cold from zeros — the self-concordant re-convergence
    claim, counted in Newton iterations.

Acceptance gate (ISSUE 4): parity <= 1e-5, batched throughput >= 4x
sequential at batch 64, warm refit >= 2x fewer Newton iterations than
cold, registry round-trip bit-identical.
"""
from __future__ import annotations

import os
import sys
import tempfile

import numpy as np

from benchmarks.common import Timer, save_json, smoke, table
from repro.core import DiscoConfig, DiscoSolver, comm
from repro.data.sparse import CSRMatrix, make_sparse_glm_data
from repro.data.store import ShardStore
from repro.glm_serve import (MicroBatchScheduler, ModelRegistry,
                             RefitLoop, RequestPacker, ScoreRequest,
                             ScoringEngine, oracle_margins)
from repro.kernels.ops import ref_kernels_off_tpu

if smoke():
    D, N, CHUNK = 64, 512, 64
    N_REQS = 128
else:
    D, N, CHUNK = 96, 1024, 128
    N_REQS = 256
DENSITY, ALPHA, BETA = 0.08, 1.2, 0.8
BATCH = 64                      # the micro-batch width the gate names
APPEND_FRAC = 16                # refit appends n/APPEND_FRAC new samples
# refit solver: tight forcing term so every Newton iteration is worth
# ~2 orders of magnitude — the regime where a warm start's head start
# translates directly into saved iterations (docs/serving.md)
LAM, PCG_RTOL, GRAD_TOL = 1e-4, 0.01, 5e-5
BLOCK = 8                       # ELL tile edge of the training solver


def _cfg():
    return DiscoConfig(partition="samples", loss="logistic", lam=LAM,
                       tau=32, max_outer=30, grad_tol=GRAD_TOL,
                       pcg_rel_tol=PCG_RTOL, ell_block_d=BLOCK,
                       ell_block_n=BLOCK, partition_block=CHUNK,
                       stream_chunk_size=CHUNK)


def _time_batched(engine, requests):
    """Seconds to drain ``requests`` through the micro-batch scheduler
    (one warmup tick excluded — jit compile is not serving time)."""
    engine.score(requests[:engine.batch])            # warmup / compile
    sched = MicroBatchScheduler(engine)
    for r in requests:
        sched.submit(r)
    with Timer() as t:
        sched.run_until_done()
    return t.elapsed, sched.stats


def _time_sequential(engine, requests):
    """Seconds to score ``requests`` one step dispatch at a time."""
    engine.score(requests[:1])                       # warmup / compile
    with Timer() as t:
        for r in requests:
            engine.score([r])
    return t.elapsed


def run(quiet=False):
    ref_kernels_off_tpu()
    X, y, _ = make_sparse_glm_data(d=D, n=N, density=DENSITY, alpha=ALPHA,
                                   beta=BETA, seed=0)
    Xd = X.todense()
    n0 = N - N // APPEND_FRAC
    X0, y0 = CSRMatrix.from_dense(Xd[:, :n0]), y[:n0]
    X1, y1 = CSRMatrix.from_dense(Xd[:, n0:]), y[n0:]
    cfg = _cfg()
    gate = {}

    with tempfile.TemporaryDirectory() as td:
        store = ShardStore.from_csr(X0, y0, os.path.join(td, "store"),
                                    axis="samples", chunk_size=CHUNK)
        with Timer() as t_fit:
            res = DiscoSolver.from_store(store, cfg).fit()
        reg = ModelRegistry(os.path.join(td, "registry"))
        v1 = reg.publish(res, cfg)
        pub = reg.load()
        bit_identical = pub.w.tobytes() == np.asarray(res.w).tobytes() \
            and pub.w.dtype == np.asarray(res.w).dtype
        gate["registry"] = dict(version=v1, bit_identical=bit_identical)

        # -- scoring parity vs the dense oracle ---------------------------
        rng = np.random.default_rng(1)
        cols = rng.choice(N, size=N_REQS, replace=False)
        requests = [ScoreRequest.from_dense(Xd[:, j]) for j in cols]
        engine = ScoringEngine(reg, batch=BATCH)
        got = engine.score(requests)
        want = oracle_margins(requests, pub.w)
        denom = max(float(np.abs(want).max()), 1e-30)
        parity = float(np.abs(got - want).max()) / denom
        gate["parity"] = dict(rel_err=parity, ok=parity <= 1e-5)

        # -- bf16 value scoring parity (mixed-precision serving path) -----
        engine_bf = ScoringEngine(reg, batch=BATCH, hvp_dtype="bfloat16")
        got_bf = engine_bf.score(requests)
        parity_bf = float(np.abs(got_bf - want).max()) / denom
        # bf16 mantissa is 8 bits: per-request dots should stay within
        # ~2^-8 of the oracle (the values round to bf16; the weights,
        # products and sum stay f32 — docs/kernels.md)
        gate["parity_bf16"] = dict(rel_err=parity_bf,
                                   ok=parity_bf <= 2e-2)

        # -- micro-batched vs sequential throughput -----------------------
        t_b, stats = _time_batched(engine, requests)
        seq_engine = ScoringEngine(reg, batch=1)
        t_s = _time_sequential(seq_engine, requests)
        speedup = t_s / max(t_b, 1e-12)
        nnz_per_req = float(np.mean([r.nnz for r in requests]))
        model = comm.glm_serving_throughput(
            BATCH, nnz_per_req, slots=RequestPacker.slots(requests))
        gate["throughput"] = dict(speedup=speedup, ok=speedup >= 4.0)

        # -- warm-start refit on appended data ----------------------------
        loop = RefitLoop(reg, store, cfg)
        loop.ingest(X1, y1)
        with Timer() as t_w:
            _, warm = loop.refit(warm=True)
        with Timer() as t_c:
            _, cold = loop.refit(warm=False)
        iters_w, iters_c = len(warm.history), len(cold.history)
        gate["refit"] = dict(
            warm_iters=iters_w, cold_iters=iters_c,
            converged=bool(warm.converged and cold.converged),
            ok=(warm.converged and cold.converged
                and iters_c >= 2 * iters_w))
        # scoring never paused: the engine hot-swaps the refit version
        swapped = engine.maybe_reload()

    rows = [dict(
        stage="serve", d=D, n=N, reqs=N_REQS, batch=BATCH,
        parity_rel_err=parity, parity_bf16_rel_err=parity_bf,
        batched_s=round(t_b, 4), sequential_s=round(t_s, 4),
        speedup=round(speedup, 2),
        model_speedup=round(model["speedup"], 1),
        p50_ms=round(stats.p50_s * 1e3, 3),
        p99_ms=round(stats.p99_s * 1e3, 3),
        rps=int(stats.throughput_rps(t_b)),
        warm_iters=iters_w, cold_iters=iters_c,
        warm_s=round(t_w.elapsed, 2), cold_s=round(t_c.elapsed, 2),
        fit_s=round(t_fit.elapsed, 2))]

    ok = (gate["registry"]["bit_identical"] and gate["parity"]["ok"]
          and gate["parity_bf16"]["ok"] and gate["throughput"]["ok"]
          and gate["refit"]["ok"] and swapped)
    out = table(rows, ["stage", "d", "n", "reqs", "batch",
                       "parity_rel_err", "parity_bf16_rel_err",
                       "batched_s", "sequential_s",
                       "speedup", "model_speedup", "p50_ms", "p99_ms",
                       "rps", "warm_iters", "cold_iters", "warm_s",
                       "cold_s", "fit_s"],
                title=f"online GLM serving (d={D} n={N}, batch={BATCH}, "
                      f"{N_REQS} requests)")
    if not quiet:
        print(out)
        print(f"[gate] registry round-trip bit-identical: "
              f"{gate['registry']['bit_identical']}")
        print(f"[gate] scoring parity rel_err={parity:.2e} (need <=1e-5)")
        print(f"[gate] bf16-value scoring parity rel_err={parity_bf:.2e} "
              f"(need <=2e-2)")
        print(f"[gate] micro-batched speedup {speedup:.1f}x "
              f"(need >=4x; model predicts "
              f"{model['speedup']:.0f}x)")
        print(f"[gate] warm refit {iters_w} vs cold {iters_c} Newton "
              f"iters (need cold >= 2x warm)")
        print(f"[gate] hot swap after refit: {swapped}")
        print(f"[gate] {'PASS' if ok else 'FAIL'}: registry + parity + "
              "batched throughput + warm-start refit")
    save_json("serving", {"rows": rows, "gate": gate, "pass": ok})
    return rows, ok


def main():
    return run()


if __name__ == "__main__":
    sys.exit(0 if main()[1] else 1)
