"""Observability plane: tracer semantics, exporters, cross-layer
instrumentation, and the traced-rounds-vs-ledger invariant.

The rounds tests re-check bench_obs's gate at test granularity: the
``comm.rounds`` counter and the ``comm.allreduce`` instants are emitted
at the *actual call sites* of the streamed path, independently of the
analytic ``CommLedger`` — all three must agree exactly. The checkpoint
test covers the per-iteration ``iter_s`` wall-clock satellite: history
(including timings) and ledger must round-trip through a checkpoint and
a resumed solve must continue the exact trajectory.
"""
import json
import os
import re
import threading

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.fixture(autouse=True)
def _obs_clean():
    """Tests toggle the process-global tracer; always leave it off."""
    from repro import obs
    obs.disable()
    yield
    obs.disable()


@pytest.fixture()
def ref_mode(monkeypatch):
    # solver-driving tests: interpret-mode kernel emulation is needlessly
    # slow for these shapes
    monkeypatch.setenv("REPRO_KERNEL_MODE", "ref")


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------

def test_span_nesting_and_thread_attribution():
    from repro import obs

    tracer = obs.enable(reset=True)
    with obs.span("newton.outer", outer_iter=0) as sp:
        with obs.span("pcg.round", t=0):
            pass
        sp.set(extra=1)
    obs.instant("comm.allreduce", phase="pcg")

    def worker():
        with obs.span("stream.chunk_load", cid=3, shard=1, layouts="fwd"):
            pass

    th = threading.Thread(target=worker, name="prefetch-test")
    th.start()
    th.join()

    events, _, _ = tracer.snapshot()
    kinds = [e.kind for e in events]
    # exit order: inner span records before the outer one
    assert kinds == ["pcg.round", "newton.outer", "comm.allreduce",
                     "stream.chunk_load"]
    outer = events[1]
    assert outer.ph == "X" and outer.dur_ns >= 0
    assert outer.args == {"outer_iter": 0, "extra": 1}   # set() merged
    inner = events[0]
    assert inner.t0_ns >= outer.t0_ns                    # nested inside
    assert events[2].ph == "i" and events[2].dur_ns == 0
    assert events[3].thread == "prefetch-test"
    assert events[3].tid != outer.tid


def test_kernel_dispatch_instant_once_per_tracer(ref_mode):
    """Each fresh tracer records the resolved kernel mode once, however
    many kernels dispatch and whatever tracers came before it."""
    import gc

    from repro import obs
    from repro.kernels import ops

    for _ in range(3):
        tracer = obs.enable(reset=True)
        ops._mode()
        ops._mode()
        assert tracer.span_count("kernel.dispatch") == 1
        obs.disable()
        del tracer
        gc.collect()        # a new tracer may reuse the old one's address


def test_noop_fast_path_identity():
    from repro import obs
    from repro.obs.tracer import _NOOP_SPAN

    obs.disable()
    assert not obs.enabled()
    # the disabled span is one cached singleton — no allocation per site
    s1 = obs.span("newton.outer", outer_iter=0)
    s2 = obs.span("pcg.round")
    assert s1 is s2 is _NOOP_SPAN
    with s1 as sp:
        sp.set(anything=1)
    # disabled emission drops silently, even for unregistered names
    obs.instant("comm.allreduce")
    obs.count("comm.rounds", 5)
    obs.gauge("serve.queue_depth", 1)
    tracer = obs.enable(reset=True)
    assert tracer.snapshot() == ([], {}, {})


def test_unknown_kinds_raise():
    from repro import obs

    obs.enable(reset=True)
    with pytest.raises(ValueError, match="SPAN_KINDS"):
        obs.span("no.such.kind")
    with pytest.raises(ValueError, match="SPAN_KINDS"):
        obs.instant("no.such.kind")
    with pytest.raises(ValueError, match="SPAN_KINDS"):
        obs.complete("no.such.kind", 0)
    with pytest.raises(ValueError, match="COUNTER_KINDS"):
        obs.count("no.such.counter")
    with pytest.raises(ValueError, match="GAUGE_KINDS"):
        obs.gauge("no.such.gauge", 1.0)


def test_counters_gauges_and_span_count():
    from repro import obs

    tracer = obs.enable(reset=True)
    obs.count("comm.rounds", 3)
    obs.count("comm.rounds")
    obs.count("io.retries")
    obs.gauge("serve.queue_depth", 7)
    obs.gauge("serve.queue_depth", 2)        # last value wins
    obs.instant("comm.allreduce")
    obs.instant("comm.allreduce")
    _, counters, gauges = tracer.snapshot()
    assert counters == {"comm.rounds": 4, "io.retries": 1}
    assert gauges == {"serve.queue_depth": 2}
    assert tracer.span_count("comm.allreduce") == 2


def test_disabled_obs_imports_no_jax():
    """The disabled path is stdlib only: importing and calling obs with
    tracing off loads no jax (the enabled tracer imports jax.profiler)."""
    import subprocess
    import sys

    code = ("import sys\n"
            "from repro import obs\n"
            "with obs.span('newton.outer', outer_iter=0):\n"
            "    obs.count('serve.queue_wait_s', 1.0)\n"
            "assert not obs.enabled()\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n")
    env = {k: v for k, v in os.environ.items() if k != "REPRO_TRACE"}
    env["PYTHONPATH"] = SRC
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr


def test_enabled_span_sits_on_the_profilers_host_plane(tmp_path):
    """An enabled span is also a TraceAnnotation: a CPU capture shows it
    by name on the host plane inside an outer annotation, with its
    opening args as event stats and the in-memory event's duration."""
    import glob
    import time

    import jax
    from repro import obs

    tracer = obs.enable(reset=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("outer"):
            with obs.span("serve.tick", tick=3) as sp:
                time.sleep(0.02)
                sp.set(scored=1)
    finally:
        jax.profiler.stop_trace()
    (ev,), _, _ = tracer.snapshot()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    host = {e.name: e for plane in pd.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events
            if e.name in ("outer", "serve.tick")}
    tick, outer = host["serve.tick"], host["outer"]
    assert outer.start_ns <= tick.start_ns
    assert tick.start_ns + tick.duration_ns \
        <= outer.start_ns + outer.duration_ns
    assert dict(tick.stats) == {"tick": 3}
    assert ev.args == {"tick": 3, "scored": 1}
    assert abs(tick.duration_ns - ev.dur_ns) < 1e6


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

def test_chrome_trace_structure(tmp_path):
    from repro import obs

    tracer = obs.enable(reset=True)
    with obs.span("newton.outer", outer_iter=0):
        obs.instant("comm.allreduce", phase="outer")
    obs.count("comm.rounds", 2)
    obs.gauge("serve.queue_depth", 1)

    events = obs.export.chrome_trace(tracer)
    json.dumps(events)                       # Perfetto-loadable
    phases = [e["ph"] for e in events]
    assert phases.count("X") == 1 and phases.count("i") == 1
    x = next(e for e in events if e["ph"] == "X")
    assert x["name"] == "newton.outer" and x["dur"] >= 0 and x["ts"] >= 0
    i = next(e for e in events if e["ph"] == "i")
    assert i["s"] == "t"
    metas = [e for e in events if e["ph"] == "M"]
    assert any(m["name"] == "thread_name" for m in metas)
    labels = [m for m in metas if m["name"] == "process_labels"]
    assert labels and "comm.rounds" in str(labels[-1]["args"])

    path = tmp_path / "trace.json"
    obs.export.write_chrome_trace(tracer, str(path))
    assert json.loads(path.read_text()) == json.loads(json.dumps(events))


# ---------------------------------------------------------------------------
# registry drift: every emission site in the tree names a registered kind
# ---------------------------------------------------------------------------

def test_emitted_kinds_are_registered():
    """Grep the source tree for obs emission literals; each must be in
    the registry (and each registered kind must be emitted somewhere) —
    the docs tables can then never drift from what the code can emit."""
    from repro.obs.tracer import COUNTER_KINDS, GAUGE_KINDS, SPAN_KINDS

    pat = re.compile(
        r"obs\.(span|instant|complete|count|gauge)\(\s*\n?\s*\"([^\"]+)\"")
    emitted: dict[str, set] = {"span": set(), "count": set(),
                               "gauge": set()}
    root = os.path.join(SRC, "repro")
    for dirpath, _, files in os.walk(root):
        for fname in files:
            if not fname.endswith(".py") or "obs" in dirpath:
                continue
            with open(os.path.join(dirpath, fname)) as f:
                for fn, kind in pat.findall(f.read()):
                    group = {"instant": "span", "complete": "span"}.get(
                        fn, fn)
                    emitted[group].add(kind)
    assert emitted["span"], "no instrumentation sites found at all?"
    assert emitted["span"] <= set(SPAN_KINDS)
    assert emitted["count"] <= set(COUNTER_KINDS)
    assert emitted["gauge"] <= set(GAUGE_KINDS)
    # the registry carries no dead vocabulary either
    assert set(SPAN_KINDS) <= emitted["span"]
    assert set(COUNTER_KINDS) <= emitted["count"]
    assert set(GAUGE_KINDS) <= emitted["gauge"]


def test_render_span_kinds_covers_registry():
    from repro import obs
    from repro.obs.tracer import COUNTER_KINDS, GAUGE_KINDS, SPAN_KINDS

    text = obs.render_span_kinds()
    for name in list(SPAN_KINDS) + list(COUNTER_KINDS) + list(GAUGE_KINDS):
        assert f"`{name}`" in text


# ---------------------------------------------------------------------------
# traced solves: rounds invariant + iter_s
# ---------------------------------------------------------------------------

def _sparse_problem(seed=1):
    from repro.data.sparse import make_sparse_glm_data
    return make_sparse_glm_data(d=96, n=160, density=0.2, alpha=1.0,
                                beta=0.5, seed=seed)


def _stream_cfg(partition, **kw):
    from repro.core import DiscoConfig
    base = dict(partition=partition, loss="logistic", lam=1e-2, tau=16,
                max_outer=3, grad_tol=1e-10, ell_block_d=8, ell_block_n=8,
                partition_block=16, stream_chunk_size=16, trace=True)
    base.update(kw)
    return DiscoConfig(**base)


@pytest.mark.parametrize("partition,block_s", [("features", 1),
                                               ("samples", 1),
                                               ("samples", 2)])
def test_streamed_rounds_match_ledger(tmp_path, ref_mode, partition,
                                      block_s):
    """Streamed solves count rounds at the call sites; the independent
    tally must equal the analytic CommLedger and the allreduce marks."""
    from repro import obs
    from repro.core import DiscoSolver
    from repro.data.store import ShardStore

    X, y, _ = _sparse_problem()
    store = ShardStore.from_csr(X, y, str(tmp_path / "s"), axis=partition,
                                chunk_size=16)
    tracer = obs.enable(reset=True)
    cfg = _stream_cfg(partition, pcg_block_s=block_s)
    res = DiscoSolver.from_store(store, cfg).fit()
    events, counters, _ = tracer.snapshot()
    assert res.ledger.rounds > 0
    assert counters["comm.rounds"] == res.ledger.rounds
    assert tracer.span_count("comm.allreduce") == res.ledger.rounds
    assert counters["comm.floats"] == res.ledger.floats
    assert counters["comm.spmd_collectives"] == res.ledger.spmd_collectives
    # per-round spans exist on the streamed path (host-driven PCG);
    # pcg_iters already counts rounds — an s-step round advances the
    # Krylov space by block_s but is one while iteration
    assert tracer.span_count("pcg.round") == sum(int(h["pcg_iters"])
                                                 for h in res.history)


def test_inmemory_counter_matches_ledger_and_iter_s(ref_mode, glm_data):
    from repro import obs
    from repro.core import DiscoConfig, DiscoSolver

    X, y, _ = glm_data
    cfg = DiscoConfig(partition="samples", loss="logistic", lam=1e-2,
                      tau=16, max_outer=3, grad_tol=1e-10, trace=True)
    tracer = obs.enable(reset=True)
    res = DiscoSolver(X, y, cfg).fit()
    _, counters, _ = tracer.snapshot()
    assert counters["comm.rounds"] == res.ledger.rounds > 0
    assert tracer.span_count("newton.outer") == len(res.history)
    for h in res.history:
        assert h["iter_s"] > 0.0             # per-iteration wall-clock


@pytest.mark.parametrize("layout", ["dense", "sparse"])
def test_solver_placement_spans_once_per_construction(ref_mode, glm_data,
                                                      layout):
    """Each ``DiscoSolver`` construction opens one ``disco.place`` span
    and adds its most-loaded device's placed bytes to
    ``disco.place_bytes_max``; both kinds are registered."""
    from repro import obs
    from repro.core import DiscoConfig, DiscoSolver
    from repro.obs.tracer import COUNTER_KINDS, SPAN_KINDS

    assert SPAN_KINDS["disco.place"][1] == "span"
    assert "disco.place_bytes_max" in COUNTER_KINDS
    X, y, _ = glm_data if layout == "dense" else _sparse_problem()
    cfg = DiscoConfig(partition="samples", loss="logistic", lam=1e-2,
                      tau=16, ell_block_d=8, ell_block_n=8)
    tracer = obs.enable(reset=True)
    solvers = [DiscoSolver(X, y, cfg) for _ in range(2)]
    events, counters, _ = tracer.snapshot()
    place = [e for e in events if e.kind == "disco.place"]
    assert len(place) == 2 and all(e.ph == "X" for e in place)
    assert {e.args["shards"] for e in place} == {1}
    placed = (["X", "y", "weights", "X_tau", "y_tau"] if layout == "dense"
              else ["ell_data", "ell_cols", "ell_dataT", "ell_colsT", "y",
                    "weights", "X_tau", "y_tau"])
    one = sum(getattr(solvers[0], k).nbytes for k in placed)
    # one device holds everything: the span's bytes and the counter agree
    assert {e.args["bytes"] for e in place} == {one}
    assert counters["disco.place_bytes_max"] == 2 * one


def test_newton_step_nests_in_each_outer_and_times_iter_s(ref_mode,
                                                         glm_data):
    from repro import obs
    from repro.core import DiscoConfig, DiscoSolver

    X, y, _ = glm_data
    cfg = DiscoConfig(partition="samples", loss="logistic", lam=1e-2,
                      tau=16, max_outer=3, grad_tol=1e-10)
    tracer = obs.enable(reset=True)
    res = DiscoSolver(X, y, cfg).fit()
    events, _, _ = tracer.snapshot()
    outers = [e for e in events if e.kind == "newton.outer"]
    steps = [e for e in events if e.kind == "newton.step"]
    assert len(outers) == len(steps) == len(res.history) == 3
    for outer, step, h in zip(outers, steps, res.history):
        assert outer.args["outer_iter"] == h["outer_iter"]
        assert outer.t0_ns <= step.t0_ns
        assert step.t0_ns + step.dur_ns <= outer.t0_ns + outer.dur_ns
        assert abs(h["iter_s"] - step.dur_ns * 1e-9) < 1e-3


def test_measured_vs_predicted_rows(ref_mode, glm_data):
    from repro import obs
    from repro.core import DiscoConfig, DiscoSolver

    X, y, _ = glm_data
    cfg = DiscoConfig(partition="samples", loss="logistic", lam=1e-2,
                      tau=16, max_outer=3, grad_tol=1e-10)
    res = DiscoSolver(X, y, cfg).fit()
    rows = obs.report.measured_vs_predicted(
        res.history, [int(np.count_nonzero(X))], "samples",
        n=X.shape[1], d=X.shape[0], m=1)
    assert len(rows) == len(res.history)
    assert rows[0]["compile"] and not any(r["compile"] for r in rows[1:])
    for r in rows:
        assert r["measured_s"] > 0 and r["predicted_s"] > 0
        assert r["ratio"] == pytest.approx(r["measured_s"]
                                           / r["predicted_s"])


# ---------------------------------------------------------------------------
# satellite: checkpoint round-trips history (iter_s) + ledger; resume
# continues the exact trajectory
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrips_history_and_resume_matches(tmp_path,
                                                          ref_mode):
    from repro.core import DiscoSolver
    from repro.data.store import ShardStore
    from repro.robust.checkpoint import load_checkpoint
    from repro.robust.faults import FaultInjector, FaultPlan, SimulatedKill

    X, y, _ = _sparse_problem(seed=4)
    store = ShardStore.from_csr(X, y, str(tmp_path / "s"), axis="samples",
                                chunk_size=16)
    cfg = _stream_cfg("samples", max_outer=6, trace=False)
    ckpt = str(tmp_path / "ckpt")
    ref = DiscoSolver.from_store(store, cfg).fit()
    assert all("iter_s" in h for h in ref.history)

    plan = FaultPlan(kill_at_step=3)
    with pytest.raises(SimulatedKill):
        DiscoSolver.from_store(store, cfg, fault_plan=plan).fit(
            checkpoint_dir=ckpt, checkpoint_every=1)

    # the snapshot round-trips the full history — including the iter_s
    # wall-clocks — and the exact ledger totals
    state = load_checkpoint(ckpt)
    assert state.next_iter == 3 and len(state.history) == 3
    for h in state.history:
        assert h["iter_s"] > 0.0
    for got, want in zip(state.history, ref.history):
        assert set(got) == set(want)
        for k in ("outer_iter", "pcg_iters", "comm_rounds_cum",
                  "comm_floats_cum"):
            assert got[k] == want[k], k
    mid = ref.ledger
    assert state.ledger["rounds"] + state.ledger["floats"] > 0

    # resume-then-fit lands on the uninterrupted endpoint with the
    # uninterrupted ledger and per-iteration stats (timings excluded —
    # wall-clocks are machine facts, not trajectory facts)
    res = DiscoSolver.from_store(store, cfg).fit(checkpoint_dir=ckpt,
                                                 resume=True)
    assert len(res.history) == len(ref.history)
    np.testing.assert_allclose(res.w, ref.w, atol=1e-7, rtol=1e-6)
    assert res.ledger.rounds == mid.rounds
    assert res.ledger.floats == mid.floats
    assert res.ledger.spmd_collectives == mid.spmd_collectives
    for got, want in zip(res.history, ref.history):
        for k in ("outer_iter", "pcg_iters", "comm_rounds_cum",
                  "comm_floats_cum"):
            assert got[k] == want[k], k
        assert got["iter_s"] > 0.0


# ---------------------------------------------------------------------------
# serving plane: tick spans + queue gauges
# ---------------------------------------------------------------------------

def test_scheduler_ticks_emit_spans_and_gauges():
    from repro import obs
    from repro.glm_serve import (MicroBatchScheduler, ScoreRequest,
                                 ScoringEngine)

    rng = np.random.default_rng(0)
    w = rng.standard_normal(24).astype(np.float32)
    eng = ScoringEngine(w, loss="logistic", batch=4)
    sched = MicroBatchScheduler(eng)
    tracer = obs.enable(reset=True)
    for _ in range(9):
        sched.submit(ScoreRequest(np.array([0, 5]),
                                  np.array([1.0, -1.0], np.float32)))
    sched.run_until_done()
    events, counters, gauges = tracer.snapshot()
    ticks = [e for e in events if e.kind == "serve.tick"]
    assert len(ticks) == sched.stats.ticks == 3      # ceil(9 / 4)
    # scored counts ride on the span args (set() after scoring)
    assert [t.args["scored"] for t in ticks] == [4, 4, 1]
    assert counters["serve.scored"] == sched.stats.completed == 9
    assert set(gauges) == {"serve.queue_depth"}
    assert gauges["serve.queue_depth"] == 1          # depth before last tick


def test_tick_pieces_nest_in_each_scored_tick_and_queue_wait_counts():
    """Each tick that scores shows pack, copy in, kernel and copy out
    once, in order, inside its serve.tick; an empty tick shows none. On
    a fake clock the queue-wait counter is the hand-computed sum."""
    from repro import obs
    from repro.glm_serve import (MicroBatchScheduler, ScoreRequest,
                                 ScoringEngine)

    pieces = ("serve.pack", "serve.copy_in", "serve.kernel",
              "serve.copy_out")
    rng = np.random.default_rng(0)
    w = rng.standard_normal(24).astype(np.float32)
    eng = ScoringEngine(w, loss="logistic", batch=4)
    now = [0.0]
    sched = MicroBatchScheduler(eng, clock=lambda: now[0])
    tracer = obs.enable(reset=True)
    submits = [0.0, 0.5, 1.0, 1.5, 2.0, 2.5]
    for t in submits:
        now[0] = t
        sched.submit(ScoreRequest(np.array([0, 5]),
                                  np.array([1.0, -1.0], np.float32)))
    for t in (3.0, 4.0, 4.5):            # 4 scored, 2 scored, none
        now[0] = t
        sched.tick()
    events, counters, _ = tracer.snapshot()
    ticks = [e for e in events if e.kind == "serve.tick"]
    assert [t.args["scored"] for t in ticks] == [4, 2, 0]
    for tick in ticks:
        inside = [e for e in events if e.kind in pieces
                  and tick.t0_ns <= e.t0_ns
                  and e.t0_ns + e.dur_ns <= tick.t0_ns + tick.dur_ns]
        if not tick.args["scored"]:
            assert inside == []
            continue
        assert tuple(e.kind for e in inside) == pieces
        for a, b in zip(inside, inside[1:]):
            assert a.t0_ns + a.dur_ns <= b.t0_ns
    assert sum(e.kind in pieces for e in events) == 2 * len(pieces)
    want = sum(3.0 - t for t in submits[:4]) + sum(4.0 - t
                                                  for t in submits[4:])
    assert counters["serve.queue_wait_s"] == pytest.approx(want)
    assert counters["serve.scored"] == 6
