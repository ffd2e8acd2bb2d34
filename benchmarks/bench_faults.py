"""Fault tolerance + elastic re-planning under injected failures (ISSUE 6).

Two gates:

* **Straggler recovery** — chunk a power-law sparse dataset into a
  ShardStore, plan a static 4-shard LPT schedule, then straggle every
  chunk the static plan put on shard 0 (a degraded volume: ~4x the
  typical per-chunk cost, injected as real latency through the fault
  harness). One measured streaming pass feeds the per-chunk timing
  ledger; the elastic re-planner rebalances on the *measured* seconds
  and re-orders each shard's chunks by descending cost so stragglers
  align into the same barrier steps. A second measured pass under the
  new schedule confirms the estimates. Gate: modeled parallel wall-clock
  (``sum_t max_s`` — every collective waits for the slowest shard)
  recovers by **>= 1.5x** vs the static schedule, on re-measured times.
* **Retry-path accuracy** — a full streaming solve in which 50% of
  chunks fail their first read every pass (transient, seeded) must
  match the fault-free solve to **<= 1e-5** relative error: retries
  must be invisible to the numerics.

Also reports the analytic re-plan decision model
(``comm.elastic_replan_model``): static vs re-planned time-to-finish
and the break-even pass count for a nonzero re-plan overhead.
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
from repro.data.stream import plan_streams
from repro.kernels.ops import ref_kernels_off_tpu
from repro.robust.faults import FaultInjector, FaultPlan
from repro.robust.straggler import (ChunkTimingLedger, ElasticReplanner,
                                    barrier_seconds)

if smoke():
    D, N, DENSITY = 48, 1024, 0.1
    CHUNK, M = 64, 4
    MAX_OUTER, TAU = 4, 16
else:
    D, N, DENSITY = 96, 4096, 0.05
    CHUNK, M = 128, 4
    MAX_OUTER, TAU = 8, 32
STRAGGLE_X = 4.0                 # slow chunks cost ~4x the typical chunk
GATE_RECOVERY = 1.5              # required wall-clock recovery factor
GATE_REL = 1e-5                  # retry path must match fault-free


def _measure_pass(plan, ledger):
    """One real streaming pass; returns the ledger's measured seconds."""
    with plan.stream("fwd") as pf:
        for _ in pf:
            pass
    return ledger.chunk_seconds()


def _straggler_recovery(rows):
    X, y, _ = make_sparse_glm_data(d=D, n=N, density=DENSITY, alpha=1.2,
                                   beta=0.8, seed=0)
    with tempfile.TemporaryDirectory() as td:
        store = ShardStore.from_csr(X, y, os.path.join(td, "s"),
                                    axis="samples", chunk_size=CHUNK)
        # calibrate the injected delay to ~(STRAGGLE_X - 1)x the real
        # median chunk cost, with a floor well above timer noise
        cal_led = ChunkTimingLedger(store.n_chunks)
        cal = plan_streams(store, m=M, block_rows=16, block_cols=CHUNK,
                           timing_ledger=cal_led)
        base = float(np.median(_measure_pass(cal, cal_led)))
        delay = max((STRAGGLE_X - 1.0) * base, 0.01)

        static = plan_streams(store, m=M, block_rows=16, block_cols=CHUNK)
        slow = {int(c): delay for c in static.schedule[0] if c >= 0}
        injector = FaultInjector(FaultPlan(slow_chunks=slow))
        ledger = ChunkTimingLedger(store.n_chunks)
        plan = plan_streams(store, m=M, block_rows=16, block_cols=CHUNK,
                            timing_ledger=ledger,
                            fault_injector=injector)

        with Timer() as t_obs:
            cs_before = _measure_pass(plan, ledger)
        replanner = ElasticReplanner(ledger, threshold=1.3)
        out = replanner.maybe_replan(plan, trigger="bench")
        assert out is not None, "replanner did not fire on a 4x straggler"
        new_plan, event = out

        # re-measure under the new schedule: the latency follows the
        # chunks, so the recovery must hold on fresh observations too
        ledger.reset()
        cs_after = _measure_pass(new_plan, ledger)

    static_s = barrier_seconds(plan.schedule, cs_after)
    replanned_s = barrier_seconds(new_plan.schedule, cs_after)
    recovery = static_s / max(replanned_s, 1e-12)
    model = comm.elastic_replan_model(
        cs_before, plan.schedule, new_plan.schedule,
        passes_remaining=4 * MAX_OUTER, replan_overhead_s=t_obs.elapsed)

    rows.append(dict(
        case="straggler", n_chunks=int(plan.store.n_chunks),
        slow_chunks=len(slow), delay_ms=round(delay * 1e3, 2),
        observed_straggler=round(event.observed_straggler, 2),
        planned_straggler=round(event.planned_straggler, 2),
        moved_chunks=event.moved_chunks,
        static_pass_s=round(static_s, 4),
        replanned_pass_s=round(replanned_s, 4),
        recovery_x=round(recovery, 2),
        model_gain=round(model["gain"], 2),
        break_even_passes=round(model["break_even_passes"], 2)))
    return dict(recovery_x=recovery,
                recovery_ok=recovery >= GATE_RECOVERY,
                replan_fired=True, moved_chunks=event.moved_chunks)


def _retry_accuracy(rows):
    X, y, _ = make_sparse_glm_data(d=D, n=N, density=DENSITY, alpha=1.0,
                                   beta=0.6, seed=1)
    cfg = DiscoConfig(partition="samples", loss="logistic", lam=1e-2,
                      tau=TAU, max_outer=MAX_OUTER, grad_tol=1e-9,
                      ell_block_d=16, ell_block_n=CHUNK,
                      partition_block=CHUNK, io_backoff_s=0.0)
    with tempfile.TemporaryDirectory() as td:
        store = ShardStore.from_csr(X, y, os.path.join(td, "s"),
                                    axis="samples", chunk_size=CHUNK)
        with Timer() as t_ref:
            ref = DiscoSolver.from_store(store, cfg).fit()
        plan = FaultPlan(seed=7, read_error_rate=0.5,
                         read_error_attempts=1)
        solver = DiscoSolver.from_store(store, cfg, fault_plan=plan)
        with Timer() as t_flaky:
            res = solver.fit()
        faults = solver._faults.faults_injected
    rel = float(np.linalg.norm(res.w - ref.w)
                / max(np.linalg.norm(ref.w), 1e-30))
    rows.append(dict(
        case="retry", n_chunks=int(store.n_chunks),
        faults_injected=faults, rel_err=rel,
        fault_free_s=round(t_ref.elapsed, 2),
        flaky_s=round(t_flaky.elapsed, 2)))
    return dict(rel_err=rel, rel_ok=rel <= GATE_REL,
                faults_injected=faults, faults_ok=faults > 0)


def run(quiet=False):
    ref_kernels_off_tpu()
    rows = []
    gate = dict(straggler=_straggler_recovery(rows),
                retry=_retry_accuracy(rows))
    ok = (gate["straggler"]["recovery_ok"]
          and gate["retry"]["rel_ok"] and gate["retry"]["faults_ok"])
    out = table(rows, ["case", "n_chunks", "slow_chunks", "delay_ms",
                       "observed_straggler", "planned_straggler",
                       "moved_chunks", "static_pass_s", "replanned_pass_s",
                       "recovery_x", "model_gain", "break_even_passes",
                       "faults_injected", "rel_err"],
                title=f"fault tolerance (d={D} n={N}, chunk={CHUNK}, "
                      f"m={M}, {STRAGGLE_X:g}x straggler)")
    if not quiet:
        print(out)
        s, r = gate["straggler"], gate["retry"]
        print(f"[gate] straggler: recovery {s['recovery_x']:.2f}x "
              f"(need >={GATE_RECOVERY:g}x), replan moved "
              f"{s['moved_chunks']} chunks")
        print(f"[gate] retry: rel_err={r['rel_err']:.2e} "
              f"(need <={GATE_REL:g}) with {r['faults_injected']} "
              "injected transient read errors")
        print(f"[gate] {'PASS' if ok else 'FAIL'}: elastic re-plan "
              "recovers the injected straggler and the retry path is "
              "numerically invisible")
    save_json("faults", {"rows": rows, "gate": gate, "pass": ok})
    return rows, ok


def main():
    return run()


if __name__ == "__main__":
    sys.exit(0 if main()[1] else 1)
