"""One run of one cell: set-up, the measured window, the check, the line.

A cell names a configuration (``configs/<config>.json``) and a traffic
mix (``traffic/<mix>.json``); its per-layer metrics are the files
``metrics/<metric>.py`` that ``BENCHMARK.json`` lists for it. The
configuration's ``system`` picks what is driven:

* ``solver``: ``repro.core.DiscoSolver.fit`` on data drawn from the seed;
  checked by the float64 gradient at every distinct ``w`` the window's
  solves returned (a seeded sample of at most ``MAX_CHECKED``);
* ``scoring``: ``repro.glm_serve.MicroBatchScheduler`` over a
  ``ScoringEngine`` with weights and requests drawn from the seed;
  checked by the float64 margin of every request due in the window.

A configuration's ``program_options`` (keyword arguments of
``DiscoConfig`` or ``ScoringEngine``) are empty in every cell, which
runs the library's defaults; ``control.py`` sets them to switch on the
program's own lower-precision path.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import sys
import tempfile
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
BENCHMARK = os.path.join(ROOT, "BENCHMARK.json")
MAX_CHECKED = 3          # distinct solutions checked against the reference
TAILS = (50, 90, 95, 99, 99.9)   # latency percentiles of an open loop


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, benchmark: str = BENCHMARK):
    """``(cell, config, mix, per-layer metric specs)`` of a cell, by name."""
    spec = _json(benchmark)
    cells = {c["name"]: c for c in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    config = _json(os.path.join(HERE, "configs", cell["config"] + ".json"))
    mix = _json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    layer = [m for m in spec["per_layer"]
             if workload in m.get("workloads", [workload])]
    return cell, config, mix, e2e, layer


def load_metric(name: str):
    """The reader module ``metrics/<name>.py``, or else the family's
    ``metrics/<family>.py`` for a name ``<family>.<suffix>``: one reader
    serves ``device_idle.solve`` and ``device_idle.closed`` alike."""
    path = os.path.join(HERE, "metrics", name + ".py")
    if not os.path.exists(path):
        path = os.path.join(HERE, "metrics", name.split(".")[0] + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def require_chip(chips: int) -> None:
    """Exit non-zero, printing nothing on stdout, without a TPU or with
    fewer chips than the cell asks for."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chipbench: needs a TPU, but JAX found platform "
                         f"{devices[0].platform!r} "
                         f"({devices[0].device_kind!r}, {len(devices)} "
                         "devices)")
    if len(devices) < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chips, JAX "
                         f"found {len(devices)}")


class CompileClock:
    """Backend compiles, from JAX's own monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.compiles, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.compiles += 1
            self.seconds += duration


# ---------------------------------------------------------------------------
# systems under test
# ---------------------------------------------------------------------------

def solver_data(config: dict, seed: int):
    """``(data, y)`` of a solver cell: a dense ``X`` or sparse COO
    triplets ``(feat, samp, vals)``, from the configuration's
    ``data_seed`` with the features in the order of ``seed``."""
    from chipbench import gen
    c = config
    if c["layout"] == "dense":
        return gen.dense_glm(c["data_seed"], seed, c["d"], c["n"],
                             c["cond_decay"])
    feat, samp, vals, y = gen.sparse_glm(
        c["data_seed"], seed, c["d"], c["n"], c["nnz_per_sample"],
        c["alpha"], c["beta"], c["k_min"], c["k_max"])
    return (feat, samp, vals), y


class SolveSystem:
    """A ``DiscoSolver`` on the configuration's data, warmed by one solve."""

    def __init__(self, config: dict, seed: int):
        import jax
        import jax.numpy as jnp
        from repro.core import DiscoConfig, DiscoSolver
        from repro.data.sparse import CSRMatrix

        c = config
        if c["loss"] != "logistic":
            raise ValueError("the solver cells check the logistic loss")
        d, n = c["d"], c["n"]
        data, y = solver_data(c, seed)
        self._data = data
        if c["layout"] == "dense":
            g0 = float(np.linalg.norm(data @ y)) / (2 * n)
            self.nnz = None
        else:
            feat, samp, vals = data
            g0 = float(np.linalg.norm(np.bincount(
                feat, vals.astype(np.float64) * y[samp], minlength=d))) \
                / (2 * n)
            self.nnz = len(vals)
            data = CSRMatrix.from_coo(feat, samp, vals, (d, n))
        self.y = y
        self.config = c
        # the gradient at w = 0 is -X y / 2n; the target is relative to it
        self.cfg = DiscoConfig(loss=c["loss"], lam=c["lam"],
                               partition=c["partition"],
                               max_outer=c["max_outer"],
                               grad_tol=c["grad_rel_target"] * g0,
                               **c.get("program_options", {}))
        self.solver = DiscoSolver(data, y, self.cfg)
        # the configuration states float32 data: count the floating arrays
        # the solver holds on the device below that, HVP operands included
        self.narrow = sum(
            isinstance(a, jax.Array) and jnp.issubdtype(a.dtype, jnp.floating)
            and a.dtype.itemsize < 4
            for a in jax.tree_util.tree_leaves(vars(self.solver)))
        self.solve()                   # compiles, or loads from the cache

    def solve(self) -> dict:
        res = self.solver.fit()
        return dict(w=res.w, history=res.history, converged=res.converged)

    def window(self, mix: dict, seconds: float, seed: int) -> dict:
        from chipbench import drive
        return drive.solve_loop(self.solve, seconds)

    def free(self) -> None:
        del self.solver
        gc.collect()                   # the jitted step refers back to it

    def check(self, out: dict, seed: int) -> tuple[int, int, dict]:
        return solve_compared(self.config, self._data, self.y,
                              out["solves"], seed, self.narrow)

    def end_to_end(self, out: dict) -> dict:
        return dict(solve_s=out["elapsed_s"] / len(out["solves"]))

    def record(self, out: dict) -> dict:
        from chipbench import roofline
        c = self.config
        return dict(
            histories=[s["history"] for s in out["solves"]],
            pcg_block_s=self.cfg.pcg_block_s,
            pass_bytes=roofline.pass_bytes(c["layout"], c["d"], c["n"],
                                           self.nnz))


def solve_compared(config: dict, data, y, solves: list, seed: int,
                   narrow: int) -> tuple[int, int, dict]:
    """``(attempted, failed, compared)`` of a solver cell's solves
    (``{"w", "converged"}`` each): the float64 relative gradient at every
    distinct ``w`` (a seeded sample of at most ``MAX_CHECKED``), the
    solves that stopped short of the target, and ``narrow``, the floating
    arrays held below float32."""
    from chipbench import reference
    unconverged = sum(not s["converged"] for s in solves)
    distinct = {}
    for s in solves:
        w = np.asarray(s["w"])
        distinct.setdefault(w.tobytes(), w)
    ws = list(distinct.values())
    if len(ws) > MAX_CHECKED:
        pick = np.random.default_rng(seed).choice(
            len(ws), MAX_CHECKED, replace=False)
        ws = [ws[i] for i in sorted(pick)]
    rel = max(reference.grad_rel(reference_ops(config, data), y, ws,
                                 config["lam"]))
    compared = dict(grad_rel=[rel, config["limits"]["grad_rel"]],
                    unconverged=[unconverged, 0],
                    narrow_arrays=[int(narrow), 0])
    return len(solves), unconverged, compared


def reference_ops(config: dict, data):
    """The float64 products of the reference over a cell's data."""
    from chipbench import reference
    if config["layout"] == "dense":
        return reference.DenseOps(data)
    return reference.CooOps(*data, config["d"], config["n"])


class ScoreSystem:
    """A ``MicroBatchScheduler`` over a ``ScoringEngine`` at the library's
    default geometry, warmed by full ticks."""

    def __init__(self, config: dict, seed: int):
        from repro.glm_serve import (MicroBatchScheduler, ScoreRequest,
                                     ScoringEngine)
        from chipbench import gen

        c = config
        self.config = c
        self.w, self.reqs = gen.scoring_data(
            seed, c["d"], c["request_pool"], c["nnz_per_request"],
            c["alpha"])
        self.objs = [ScoreRequest(indices=i, values=v) for i, v in self.reqs]
        self.engine = ScoringEngine(self.w, loss=c["loss"],
                                    **c.get("program_options", {}))
        self.sched = MicroBatchScheduler(self.engine)
        for t in range(c["warmup_ticks"]):
            for i in range(self.engine.batch):
                self.sched.submit(self.request(t * self.engine.batch + i))
            self.sched.tick()
        self.sched.take_finished()

    def request(self, i: int):
        return self.objs[i % len(self.objs)]

    def window(self, mix: dict, seconds: float, seed: int) -> dict:
        from chipbench import drive
        st = self.sched.stats
        before = (st.busy_s, st.ticks, st.completed)
        if mix["loop"] == "open":
            due = drive.arrivals(mix["rate"], seconds, seed)
            out = drive.open_loop(self.sched, self.request, due)
            out["due"] = due
            out["attempted"] = len(due)
        else:
            out = drive.closed_loop(self.sched, self.request,
                                    int(mix["clients"]), seconds)
            out["attempted"] = out["sent"]
        out["serve"] = dict(busy_s=st.busy_s - before[0],
                            ticks=st.ticks - before[1],
                            completed=st.completed - before[2],
                            batch=self.engine.batch)
        return out

    def free(self) -> None:
        del self.sched, self.engine
        gc.collect()

    def check(self, out: dict, seed: int) -> tuple[int, int, dict]:
        return score_compared(self.config, self.reqs, self.w, out["done"],
                              out["attempted"])

    def end_to_end(self, out: dict) -> dict:
        if "due" in out:
            # latency from when each request was due; one never answered
            # counts as infinitely late
            lat = np.full(out["attempted"], np.inf)
            for i, t, _ in out["done"]:
                lat[i] = t - out["due"][i]
            return {f"score_p{q:g}_ms": 1e3 * float(
                np.percentile(lat, q, method="higher")) for q in TAILS}
        return dict(score_rps=out["in_window"] / out["window_s"])

    def record(self, out: dict) -> dict:
        return dict(serve=out["serve"])


def score_compared(config: dict, reqs: list, w, done: list,
                   attempted: int) -> tuple[int, int, dict]:
    """``(attempted, failed, compared)`` of a scoring cell: the answers
    ``(i, done_s, margin)`` of requests ``0 .. attempted - 1`` (request
    ``i`` is ``reqs[i % len(reqs)]``) against float64 margins, over the
    float32 rounding scale; requests never answered, or answered twice."""
    from chipbench import reference
    answered: dict[int, float] = {}
    repeated = 0
    for i, _, margin in done:
        repeated += i in answered
        answered[i] = margin
    unanswered = attempted - len(answered)
    idx = sorted(answered)
    want, scale = reference.margins([reqs[i % len(reqs)] for i in idx], w)
    got = np.array([answered[i] for i in idx], np.float64)
    err = float(np.max(np.abs(got - want) / np.maximum(scale, 1e-30))) \
        if idx else float("inf")
    compared = dict(margin_err=[err, config["limits"]["margin_err"]],
                    unanswered=[unanswered, 0], repeated=[repeated, 0])
    return attempted, unanswered + repeated, compared


def is_correct(compared: dict) -> bool:
    return all(v <= lim for v, lim in compared.values())


SYSTEMS = dict(solver=SolveSystem, scoring=ScoreSystem)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def device_info() -> dict:
    import jax
    devices = jax.devices()
    stats = [d.memory_stats() or {} for d in devices]
    return dict(platform=devices[0].platform, kind=devices[0].device_kind,
                count=len(devices),
                memory_peak_bytes=max(s.get("peak_bytes_in_use", 0)
                                      for s in stats))


def run(config: dict, mix: dict, e2e: list, layer: list, *, seed: int,
        seconds: float, trace: bool, t_start: float, peaks: dict | None,
        log=None, plane_prefix=None, line_prefix=None) -> dict:
    """Set up, measure, check; return the result line as a dict.

    ``peaks`` are the chip's (``roofline.peaks``); without them no
    roofline share is read. ``log`` receives the lines for standard
    error. ``plane_prefix`` and ``line_prefix`` say where the trace's
    device operations are (the TPU's by default).
    """
    import jax
    from chipbench import drive, trace as tr

    log = log or (lambda s: print(s, file=sys.stderr, flush=True))
    drive.validate(mix)
    clock = CompileClock()
    system = SYSTEMS[config["system"]](config, seed)
    # what set-up made lives on: no collection in the window walks it
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    log(f"chipbench: set-up {setup_s:.3f} s, {clock.compiles} compiles "
        f"({clock.seconds:.3f} s)")

    compiles = clock.compiles
    reduced = None
    with tempfile.TemporaryDirectory() as tdir:
        if trace:
            jax.profiler.start_trace(tdir)
        with jax.profiler.TraceAnnotation(tr.WINDOW):
            out = system.window(mix, seconds, seed)
        if trace:
            jax.profiler.stop_trace()
            pd = tr.load(tr.find_xplane(tdir))
            kw = {k: v for k, v in (("plane_prefix", plane_prefix),
                                    ("line_prefix", line_prefix)) if v}
            reduced = tr.reduce(pd, **kw)
    gc.unfreeze()
    window_compiles = clock.compiles - compiles
    device = device_info()
    ends = system.end_to_end(out)
    rec = system.record(out)
    system.free()

    attempted, failed, compared = system.check(out, seed)
    correct = is_correct(compared)
    if "lateness_s" in out:
        late = out["lateness_s"][np.isfinite(out["lateness_s"])]
        print(json.dumps(dict(generator_lateness_ms=dict(
            p50=1e3 * float(np.median(late)),
            p99=1e3 * float(np.percentile(late, 99)),
            max=1e3 * float(late.max())))), flush=True)
    log(f"chipbench: {window_compiles} compiles inside the window")
    for h in rec.get("histories", [])[:1]:
        log(f"chipbench: a solve takes {len(h)} outer iterations and "
            f"{sum(x['pcg_iters'] for x in h):.0f} PCG iterations")

    metrics = {}
    if trace:
        device.update(busy_s=reduced["busy_s"],
                      window_s=reduced["window_s"])
        rec.update(trace=reduced, peaks=peaks)
        for m in layer:
            value = load_metric(m["name"]).read(rec)
            if value is not None:
                metrics[m["name"]] = dict(value=value, unit=m["unit"])
    else:
        ends["setup_s"] = setup_s
        for m in e2e:
            metrics[m["name"]] = dict(value=ends[m["name"]], unit=m["unit"])
    result = dict(correct=correct, attempted=attempted, failed=failed,
                  metrics=metrics, device=device)
    if trace:
        result["breakdown"] = dict(device_ops=reduced["device_ops"],
                                   idle_gaps=reduced["idle_gaps"])
    result["compared"] = {k: dict(value=v, limit=lim)
                          for k, (v, lim) in compared.items()}
    for k, (v, lim) in compared.items():
        log(f"compared {k} {v!r} limit {lim!r}")
    return result

