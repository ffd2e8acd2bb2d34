"""Observability plane gates (ISSUE 9): overhead + rounds cross-check.

Two gates, both must PASS:

1. **Disabled overhead <= 2%** — the per-iteration instrumentation
   ``DiscoSolver.fit`` emits (a ``newton.outer`` span around a
   ``newton.step`` span + three counter increments) must, with tracing
   *disabled* (the no-op fast path everyone pays by default), add at
   most 2% to a tight precompiled solve loop's iteration time. The
   instrumentation delta is measured in isolation over a tight
   many-iteration loop — it is a couple of
   microseconds, far below the run-to-run jitter of the jitted step's
   dispatch, so a loop-minus-loop subtraction would gate on machine
   noise instead of on the code under test — and compared against the
   measured uninstrumented solve iteration. The traced (enabled) cost
   is reported the same way, for scale.

2. **Traced rounds == CommLedger.rounds, bit-equal** — a traced
   streamed DiSCO-S solve counts its communication rounds twice,
   independently of the analytic ledger: the ``comm.rounds`` counter
   and the ``comm.allreduce`` instant count, both emitted at the actual
   call sites (outer margins/gradient + each host PCG round). All three
   tallies must agree exactly, or the cost model and the implementation
   have diverged — the self-verifying half of the observability plane.
   Full mode runs the solve on a real 4-device mesh in a subprocess
   (device count must be forced before jax import); smoke mode runs
   in-process on one device.

Emits both ``results/obs.json`` and the schema-validated
``results/BENCH_obs.json`` via the shared ``write_bench_record`` path.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import textwrap
import time

import numpy as np

from benchmarks.common import (save_json, smoke, table,
                               write_bench_record)

if smoke():
    LOOP_N, REPS = 60, 5
    MAX_OUTER = 3
else:
    LOOP_N, REPS = 300, 9
    MAX_OUTER = 4
OVERHEAD_LIMIT_PCT = 2.0


# ---------------------------------------------------------------------------
# gate 1: disabled-mode overhead on a tight solve loop
# ---------------------------------------------------------------------------

def _overhead_case() -> dict:
    import jax
    import jax.numpy as jnp

    from repro import obs
    from repro.core.disco import DiscoConfig, DiscoSolver

    rng = np.random.default_rng(0)
    d, n = 32, 64
    X = rng.standard_normal((d, n)).astype(np.float32)
    y = np.sign(rng.standard_normal(n)).astype(np.float32)
    cfg = DiscoConfig(partition="samples", loss="logistic", lam=1e-2,
                      tau=16, max_outer=1, max_pcg=8)
    solver = DiscoSolver(X, y, cfg)
    step = solver._step
    key = jax.random.PRNGKey(0)
    w = jnp.zeros(solver._w_shape, np.float32)
    _, st = step(w, key)                      # compile outside the timing
    float(st["grad_norm"])

    def plain_loop():
        for _ in range(LOOP_N):
            _, st = step(w, key)
            float(st["grad_norm"])

    def instr_only(m: int):
        # the per-iteration instrumentation fit() actually emits, with
        # the solve step removed — isolates the cost under test
        for i in range(m):
            with obs.span("newton.outer", outer_iter=i,
                          streaming=False):
                with obs.span("newton.step"):
                    pass
            obs.count("comm.rounds", 10)
            obs.count("comm.floats", 1000)
            obs.count("comm.spmd_collectives", 5)

    def timed(fn, *a) -> float:
        t0 = time.perf_counter()
        fn(*a)
        return time.perf_counter() - t0

    # The jitted step's dispatch jitters by tens of microseconds
    # run-to-run on a shared host — an order of magnitude more than the
    # ~2us no-op instrumentation, so (instrumented loop) - (plain loop)
    # would gate on machine noise. Instead: time the instrumentation
    # delta in isolation over a tight many-iteration loop (stable to
    # tens of nanoseconds) and compare it against the measured solve
    # iteration. min-of-reps for all three quantities.
    obs.disable()
    instr_n = max(LOOP_N * 50, 10_000)
    plain_s = noop_s = span_s = float("inf")
    plain_loop(); instr_only(instr_n)          # warm both paths
    for _ in range(REPS):
        obs.disable()
        plain_s = min(plain_s, timed(plain_loop))
        noop_s = min(noop_s, timed(instr_only, instr_n))
        obs.enable(reset=True)
        span_s = min(span_s, timed(instr_only, instr_n))
    obs.disable()

    plain_us = plain_s * 1e6 / LOOP_N
    noop_us = noop_s * 1e6 / instr_n           # disabled fast path
    span_us = span_s * 1e6 / instr_n           # enabled (records events)
    disabled_pct = noop_us / plain_us * 100.0
    return dict(case="overhead", loop_n=LOOP_N,
                plain_us=round(plain_us, 3),
                disabled_us=round(plain_us + noop_us, 3),
                enabled_us=round(plain_us + span_us, 3),
                disabled_pct=round(disabled_pct, 3),
                enabled_span_us=round(span_us, 3))


# ---------------------------------------------------------------------------
# gate 2: traced rounds vs CommLedger, bit-equal (4-device in full mode)
# ---------------------------------------------------------------------------

def _traced_solve(mesh=None) -> dict:
    """One traced streamed DiSCO-S solve; returns the three tallies."""
    from repro import obs
    from repro.core.disco import DiscoConfig, DiscoSolver
    from repro.data.sparse import make_sparse_glm_data
    from repro.data.store import ShardStore

    X, y, _ = make_sparse_glm_data(d=96, n=320, density=0.15, alpha=1.0,
                                   beta=0.6, seed=2)
    cfg = DiscoConfig(partition="samples", loss="logistic", lam=1e-2,
                      tau=16, max_outer=MAX_OUTER, grad_tol=1e-10,
                      ell_block_d=8, ell_block_n=8, partition_block=16,
                      stream_chunk_size=16, trace=True)
    tracer = obs.enable(reset=True)
    with tempfile.TemporaryDirectory() as td:
        store = ShardStore.from_csr(X, y, os.path.join(td, "store"),
                                    axis="samples", chunk_size=16)
        res = DiscoSolver.from_store(store, cfg, mesh=mesh).fit()
    events, counters, _ = tracer.snapshot()
    # the Chrome export must round-trip through json (Perfetto-loadable)
    json.dumps(obs.export.chrome_trace(tracer))
    obs.disable()
    import jax
    return dict(platform=jax.devices()[0].platform,
                devices=len(jax.devices()),
                outer_iters=len(res.history),
                ledger_rounds=int(res.ledger.rounds),
                counter_rounds=int(counters.get("comm.rounds", 0)),
                allreduce_spans=sum(1 for e in events
                                    if e.kind == "comm.allreduce"),
                span_kinds=len({e.kind for e in events}),
                replans=len(res.replan_events))


_SUBPROCESS_SCRIPT = textwrap.dedent("""
    import json, os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    from repro.kernels.ops import ref_kernels_off_tpu
    from repro.launch.mesh import make_mesh
    ref_kernels_off_tpu()
    assert len(jax.devices()) == 4
    mesh = make_mesh((4,), ("data",))
    from benchmarks import bench_obs
    print("OBS_RESULT " + json.dumps(bench_obs._traced_solve(mesh=mesh)))
""")


def _rounds_case() -> dict:
    if smoke():
        out = _traced_solve()
    else:
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            [repo, os.path.join(repo, "src")]
            + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
        env.pop("XLA_FLAGS", None)
        # the forced 4-device mesh exists only on the CPU backend, and a
        # child must never compete with this process for an accelerator
        env["JAX_PLATFORMS"] = "cpu"
        r = subprocess.run([sys.executable, "-c", _SUBPROCESS_SCRIPT],
                           env=env, capture_output=True, text=True,
                           timeout=540)
        if r.returncode != 0:
            raise RuntimeError(f"4-device traced solve failed:\n"
                               f"{r.stdout}\n{r.stderr}")
        line = [ln for ln in r.stdout.splitlines()
                if ln.startswith("OBS_RESULT ")][-1]
        out = json.loads(line[len("OBS_RESULT "):])
    out["case"] = f"trace-{out['devices']}dev-{out['platform']}"
    out["rounds_match"] = (
        out["counter_rounds"] == out["ledger_rounds"]
        == out["allreduce_spans"])
    return out


def run(quiet=False):
    from repro.kernels.ops import ref_kernels_off_tpu

    ref_kernels_off_tpu()
    overhead = _overhead_case()
    rounds = _rounds_case()
    rows = [overhead, rounds]
    gate = dict(
        disabled_pct=overhead["disabled_pct"],
        overhead_ok=overhead["disabled_pct"] <= OVERHEAD_LIMIT_PCT,
        rounds_match=bool(rounds["rounds_match"]),
        devices=rounds["devices"])
    ok = gate["overhead_ok"] and gate["rounds_match"]
    out = table(rows, ["case", "loop_n", "plain_us", "disabled_us",
                       "enabled_us", "disabled_pct", "devices",
                       "outer_iters", "ledger_rounds", "counter_rounds",
                       "allreduce_spans", "span_kinds", "rounds_match"],
                title=f"observability plane (loop_n={LOOP_N}, "
                      f"max_outer={MAX_OUTER})")
    if not quiet:
        print(out)
        print(f"[gate] disabled-mode overhead "
              f"{overhead['disabled_pct']:+.2f}% "
              f"(need <= {OVERHEAD_LIMIT_PCT:.0f}%): "
              f"{'ok' if gate['overhead_ok'] else 'FAIL'}")
        print(f"[gate] traced rounds on {rounds['devices']}-device "
              f"DiSCO-S: counter={rounds['counter_rounds']} "
              f"allreduce_spans={rounds['allreduce_spans']} "
              f"ledger={rounds['ledger_rounds']} -> "
              f"{'bit-equal' if gate['rounds_match'] else 'MISMATCH'}")
        print(f"[gate] {'PASS' if ok else 'FAIL'}: no-op fast path is "
              "free and the trace agrees with the analytic comm model")
    payload = {"bench": "obs", "rows": rows, "gate": gate, "pass": ok}
    save_json("obs", payload)
    write_bench_record("obs", payload)
    return rows, ok


def main():
    return run()


if __name__ == "__main__":
    main()
