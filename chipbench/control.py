#!/usr/bin/env python3
"""Readings that a cell's limits are set from: the program's and the
controls', on many seeds, in one process.

    python3 chipbench/control.py --workload epsilon.solve \\
        --program-seeds 1 2 3 --control-seeds 1 2 3 --seconds 3

The program's reading of a seed is a whole run of the cell (set-up, a
short window at the cell's own load, the check). Each control is judged
by the same check (``harness.solve_compared`` or
``harness.score_compared``) and has to come out not ``correct``:

* ``program_bf16``: the program with its own lower-precision path switched
  on (``PROGRAM_CONTROL``: bfloat16 HVP tiles in the solver, bfloat16
  request tiles in the scoring engine), as a whole run of the cell;
* ``reference_bf16``: the plain reference put in the program's place and
  computed one precision below the configuration's float32: bfloat16
  storage of the data (or of the weights and request values) with float32
  accumulation.

One JSON line per reading, with ``correct`` and every number compared
beside its limit. The benchmark's own runs never run a control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the program's own lower-precision path, per system
PROGRAM_CONTROL = dict(solver=dict(hvp_dtype="bfloat16"),
                       scoring=dict(hvp_dtype="bfloat16"))


def solver_control(config: dict, seed: int) -> dict:
    """bf16 damped Newton-CG on the device in place of ``DiscoSolver``,
    judged as the cell judges the solver's solves."""
    import jax
    import jax.numpy as jnp
    from chipbench import harness, reference

    c = config
    d, n, lam = c["d"], c["n"], c["lam"]
    bf16, f32 = jnp.bfloat16, jnp.float32
    data, y = harness.solver_data(c, seed)
    ops = harness.reference_ops(c, data)
    if c["layout"] == "dense":
        Xd = jax.device_put(data).astype(bf16)
        held = [Xd]
        xt_ = jax.jit(lambda A, w: jnp.dot(w.astype(bf16), A,
                                           preferred_element_type=f32))
        x_ = jax.jit(lambda A, v: jnp.dot(A, v.astype(bf16),
                                          preferred_element_type=f32))

        def xt(w):
            return xt_(Xd, w)

        def x(v):
            return x_(Xd, v)
    else:
        feat, samp, vals = data
        fd, sd = jnp.asarray(feat), jnp.asarray(samp)
        vb = jnp.asarray(vals).astype(bf16)
        held = [vb]
        vd = vb.astype(f32)

        def rnd(v):
            return v.astype(bf16).astype(f32)

        xt = jax.jit(lambda w: jax.ops.segment_sum(
            vd * rnd(w)[fd], sd, num_segments=n))
        x = jax.jit(lambda v: jax.ops.segment_sum(
            vd * rnd(v)[sd], fd, num_segments=d))
    g0 = float(np.linalg.norm(
        reference.logistic_grad(ops, y, np.zeros(d), lam)))
    tol = c["grad_rel_target"] * g0
    t0 = time.perf_counter()
    w, norms = reference.newton(
        xt, x, jnp.asarray(y, f32), n, lam, tol, c["max_outer"], jnp,
        max_cg=100)
    solve_s = time.perf_counter() - t0
    solves = [dict(w=np.asarray(w), converged=norms[-1] <= tol)]
    _, _, compared = harness.solve_compared(c, data, y, solves, seed,
                                            len(held))
    return dict(compared=compared, outer=len(norms), control_solve_s=solve_s)


def scoring_control(config: dict, seed: int) -> dict:
    """bf16 weights and values, float32 accumulation, for every request
    of the pool, judged as the cell judges the scheduler's answers."""
    import ml_dtypes
    from chipbench import gen, harness

    c = config
    w, reqs = gen.scoring_data(seed, c["d"], c["request_pool"],
                               c["nnz_per_request"], c["alpha"])
    bf = ml_dtypes.bfloat16
    wl = w.astype(bf).astype(np.float32)
    done = [(k, 0.0, float(np.sum(v.astype(bf).astype(np.float32) * wl[i],
                                  dtype=np.float32)))
            for k, (i, v) in enumerate(reqs)]
    _, _, compared = harness.score_compared(c, reqs, w, done, len(reqs))
    return dict(compared=compared)


CONTROLS = dict(solver=solver_control, scoring=scoring_control)


def _line(reading: str, seed: int, compared: dict, **extra) -> str:
    from chipbench import harness
    return json.dumps(dict(
        reading=reading, seed=seed, correct=harness.is_correct(compared),
        **extra, compared={k: dict(value=v, limit=lim)
                           for k, (v, lim) in compared.items()}))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--program-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from chipbench import harness, roofline

    cell, config, mix, e2e, layer = harness.load_cell(args.workload)
    harness.require_chip(cell["chips"])
    import jax
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    peaks = roofline.peaks(jax.devices()[0].device_kind)

    def whole_run(cfg, seed):
        res = harness.run(cfg, mix, e2e, [], seed=seed, seconds=args.seconds,
                          trace=False, t_start=time.perf_counter(),
                          peaks=peaks, log=lambda s: None)
        compared = {k: (v["value"], v["limit"])
                    for k, v in res["compared"].items()}
        return compared, res["metrics"]

    for seed in args.program_seeds:
        compared, metrics = whole_run(config, seed)
        print(_line("program", seed, compared, metrics=metrics), flush=True)
    lower = dict(config, program_options=PROGRAM_CONTROL[config["system"]])
    for seed in args.control_seeds:
        compared, metrics = whole_run(lower, seed)
        print(_line("program_bf16", seed, compared, metrics=metrics),
              flush=True)
    for seed in args.control_seeds:
        out = CONTROLS[config["system"]](config, seed)
        compared = out.pop("compared")
        print(_line("reference_bf16", seed, compared, **out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
