"""Load-balanced sparse partitioning: LPT vs equal-width (ISSUE 2 gate).

On a synthetic power-law-sparsity dataset (feature popularity ~ rank^-1.2,
sample activity ~ rank^-0.8 — the scale-free regime of the paper's text
datasets) this benchmark compares, for both partition axes:

  * the imbalance metric  max_shard_nnz / mean_shard_nnz  of equal-width
    vs nnz-aware LPT partitioning (repro.data.partition),
  * the padded blocked-ELL tile stream each strategy produces (all shards
    pad to the global max ELL width, so one overloaded shard inflates
    every shard's tile count — the *local compute* cost of skew),
  * the modeled distributed per-Newton-iteration wall-clock
    (comm.disco_sparse_iter_time: compute gated by the heaviest shard),
  * measured end-to-end wall-clock per Newton iteration of the full
    sparse DiscoSolver on a forced 8-device CPU mesh (a subprocess held
    to the CPU backend, same idiom as tests/test_multidevice.py; these
    are CPU timings on any host), unless ``--no-e2e`` is given.

Acceptance gate (ISSUE 2): LPT improves the imbalance metric >= 2x over
equal-width for BOTH ``partition='features'`` and ``partition='samples'``.

See docs/partitioning.md for why max/mean is the right metric (every
collective is a barrier; the straggler gates the mesh).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import numpy as np

from benchmarks.common import save_json, smoke, table
from repro.core import comm
from repro.data.partition import make_partition
from repro.data.sparse import (ell_from_csr, make_sparse_glm_data,
                               shard_csrs_from_partition)

D, N = 2048, 4096
DENSITY, ALPHA, BETA = 0.005, 1.2, 0.8
M = 8                 # modeled shard count
BLOCK = 16            # blocked-ELL tile edge (small enough that the tail
                      # of the power-law leaves tiles empty; TPU-native
                      # deployments use 128 with proportionally larger d)
PCG_ITERS = 32        # typical inner-loop depth for the modeled time

_E2E_SCRIPT = textwrap.dedent("""
    import json, os, sys, time
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax
    from repro.core import DiscoConfig, DiscoSolver
    from repro.data.sparse import make_sparse_glm_data
    from repro.kernels.ops import ref_kernels_off_tpu
    from repro.launch.mesh import make_mesh
    ref_kernels_off_tpu()

    X, y, _ = make_sparse_glm_data(d=%d, n=%d, density=%f, alpha=%f,
                                   beta=%f, seed=0)
    out = {"platform": jax.devices()[0].platform}
    for part, axis in (("features", "model"), ("samples", "data")):
        mesh = make_mesh((8,), (axis,))
        for strat in ("width", "lpt"):
            cfg = DiscoConfig(partition=part, partition_strategy=strat,
                              loss="logistic", lam=1e-4, tau=32,
                              max_outer=3, grad_tol=0.0,
                              ell_block_d=%d, ell_block_n=%d)
            solver = DiscoSolver(X, y, cfg, mesh=mesh)
            solver.fit()                       # warm-up: compile
            t0 = time.perf_counter()
            res = solver.fit()
            dt = (time.perf_counter() - t0) / len(res.history)
            out[f"{part}/{strat}"] = dict(
                s_per_newton_iter=dt,
                imbalance=res.partition_info["imbalance"])
    print(json.dumps(out))
""")


def _shard_tile_stream(X, part, axis, block):
    """Total padded tiles all shards stream per full HVP (both passes):
    m * (nrb_fwd * Wmax_fwd + nrb_tr * Wmax_tr). All shards pad to the
    global max ELL width of each layout, so the heaviest shard sets
    everyone's tile count — the local-compute face of imbalance."""
    m = part.m
    shards = shard_csrs_from_partition(X, part, axis)
    fwd = [ell_from_csr(c, block, block) for c in shards]
    tr = [ell_from_csr(c.transpose(), block, block) for c in shards]
    wmax_f = max(e.width for e in fwd)
    wmax_t = max(e.width for e in tr)
    tiles = m * (fwd[0].n_row_blocks * wmax_f
                 + tr[0].n_row_blocks * wmax_t)
    return tiles, wmax_f


def _run_e2e():
    """The 8-device sweep in a child held to the CPU backend: the forced
    device count only exists there, and a child must never compete with
    this process for an accelerator. A failed child fails the run."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    extra = env.get("PYTHONPATH")
    env["PYTHONPATH"] = src + (os.pathsep + extra if extra else "")
    script = _E2E_SCRIPT % (D // 2, N // 2, DENSITY, ALPHA, BETA,
                            BLOCK, BLOCK)
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=900)
    if proc.returncode != 0:
        raise RuntimeError("8-device CPU e2e sweep failed:\n"
                           + proc.stderr[-2000:])
    return json.loads(proc.stdout.strip().splitlines()[-1])


def run(quiet=False, e2e=True):
    d, n, m = (D // 4, N // 4, 4) if smoke() else (D, N, M)
    if smoke():
        e2e = False                 # no subprocess sweep in the CI smoke
    X, y, _ = make_sparse_glm_data(d=d, n=n, density=DENSITY, alpha=ALPHA,
                                   beta=BETA, seed=0)
    rows, gate = [], {}
    for axis in ("features", "samples"):
        per = {}
        for strat in ("width", "lpt"):
            part = make_partition(X, axis, m, strat, pad_multiple=BLOCK)
            tiles, wmax = _shard_tile_stream(X, part, axis, BLOCK)
            model = comm.disco_sparse_iter_time(
                part.shard_nnz, PCG_ITERS, axis, n=n, d=d, m=m)
            per[strat] = dict(imbalance=part.imbalance, tiles=tiles)
            rows.append(dict(
                partition=axis, strategy=strat,
                imbalance=round(part.imbalance, 3),
                max_shard_nnz=int(part.shard_nnz.max()),
                mean_shard_nnz=int(part.shard_nnz.mean()),
                ell_tiles_per_pass=tiles, ell_width_max=wmax,
                model_iter_ms=round(model["total_s"] * 1e3, 3),
                model_compute_ms=round(model["compute_s"] * 1e3, 3)))
        gate[axis] = dict(
            width=per["width"]["imbalance"], lpt=per["lpt"]["imbalance"],
            ratio=per["width"]["imbalance"] / per["lpt"]["imbalance"],
            tile_ratio=per["width"]["tiles"] / max(per["lpt"]["tiles"], 1))

    out = table(rows, ["partition", "strategy", "imbalance",
                       "max_shard_nnz", "mean_shard_nnz",
                       "ell_tiles_per_pass", "ell_width_max",
                       "model_iter_ms", "model_compute_ms"],
                title=f"nnz load-balancing — LPT vs equal-width "
                      f"(m={m}, power-law d={d} n={n})")
    ok = all(v["ratio"] >= 2.0 for v in gate.values())

    e2e_res = _run_e2e() if e2e else None
    if not quiet:
        print(out)
        for axis, v in gate.items():
            print(f"[gate] {axis}: imbalance width/lpt = "
                  f"{v['width']:.2f}/{v['lpt']:.2f} = {v['ratio']:.2f}x "
                  f"(need >= 2.0); padded tile stream {v['tile_ratio']:.2f}x"
                  f" smaller under LPT")
        print(f"[gate] {'PASS' if ok else 'FAIL'}: >=2x better "
              "max/mean shard-nnz imbalance under LPT, both partitions")
        if e2e_res:
            for part in ("features", "samples"):
                w = e2e_res[f"{part}/width"]["s_per_newton_iter"]
                l = e2e_res[f"{part}/lpt"]["s_per_newton_iter"]
                print(f"[e2e]  {part}: s/Newton-iter width={w:.3f} "
                      f"lpt={l:.3f} ({w / l:.2f}x) on a forced 8-device "
                      f"{e2e_res['platform']} mesh")
    save_json("loadbalance", {"rows": rows, "gate": gate,
                              "e2e": e2e_res, "pass": ok})
    return rows, ok


def main():
    e2e = "--no-e2e" not in sys.argv
    return run(e2e=e2e)


if __name__ == "__main__":
    main()
