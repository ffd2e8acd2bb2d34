import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every jax import (see dryrun.py).

DOC = """GLM dry-run: the PAPER'S OWN workload lowered at pod scale.

Lowers one full DiSCO Newton step (gradient + PCG + damped update, i.e.
Algorithm 1 with Algorithm 2 or 3 inside) over a splice-site-scale dense
GLM on 256 / 512 chips, and reads the communication pattern back out of
the compiled HLO. This turns the paper's Table 4 into a machine-checked
property of the XLA partitioning:

  DiSCO-F: per PCG iteration ONE all-reduce of an n-vector (+ scalars)
  DiSCO-S: per PCG iteration one all-reduce of a  d-vector (the SPMD view
           collapses the paper's broadcast+reduce pair into one collective)

Problem scale (dense stand-in for the 273 GB sparse splice-site.test):
d = 1,048,576 features, n = 262,144 samples -> X is 1 TiB f32, 4 GiB per
chip on the 16x16 mesh — genuinely impossible on one host, the paper's
motivating regime.

Usage:
  python -m repro.launch.dryrun_glm [--partition features|samples|both]
                                    [--mesh pod|multipod|both] [--json out]
"""

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.losses import get_loss
from repro.core.pcg import PCGResult, pcg_features, pcg_samples
from repro.launch.dryrun import collective_stats
from repro.launch.mesh import make_mesh

D_GLOBAL = 1 << 20          # 1,048,576 features
N_GLOBAL = 1 << 18          # 262,144 samples
TAU = 128
PCG_ITERS = 16              # fixed trip count so the HLO while-loop is bounded


def _flat_mesh(n_dev: int, axis: str) -> Mesh:
    assert jax.device_count() >= n_dev
    return make_mesh((n_dev,), (axis,))


def build_step(partition: str, mesh: Mesh, loss_name="logistic",
               lam=1e-6, mu=1e-2):
    """One Newton step of Algorithm 1 as a shard_map'd jit fn + arg specs."""
    loss = get_loss(loss_name)
    axis = mesh.axis_names[0]
    m = mesh.shape[axis]

    if partition == "features":
        d_loc = D_GLOBAL // m

        def step(X_loc, w_loc, y, y_tau):
            margins = jax.lax.psum(X_loc.T @ w_loc, axis)
            d1 = loss.d1(margins, y)
            c = loss.d2(margins, y)
            g_loc = X_loc @ d1 / N_GLOBAL + lam * w_loc
            coeffs_tau = loss.d2(margins[:TAU], y_tau)
            res = pcg_features(X_loc, c, N_GLOBAL, lam, g_loc, 0.0,
                               PCG_ITERS, tau_idx=jnp.arange(TAU),
                               coeffs_tau=coeffs_tau, mu=mu,
                               axis_name=axis, precond="woodbury")
            return w_loc - res.v / (1.0 + res.delta)

        fn = jax.shard_map(
            step, mesh=mesh,
            in_specs=(P(axis, None), P(axis), P(), P()),
            out_specs=P(axis), check_vma=False)
        args = (jax.ShapeDtypeStruct((D_GLOBAL, N_GLOBAL), jnp.float32),
                jax.ShapeDtypeStruct((D_GLOBAL,), jnp.float32),
                jax.ShapeDtypeStruct((N_GLOBAL,), jnp.float32),
                jax.ShapeDtypeStruct((TAU,), jnp.float32))
        in_sh = (NamedSharding(mesh, P(axis, None)),
                 NamedSharding(mesh, P(axis)),
                 NamedSharding(mesh, P()), NamedSharding(mesh, P()))
        out_sh = NamedSharding(mesh, P(axis))
    elif partition == "samples":
        def step(X_loc, y_loc, X_tau, y_tau, w):
            margins = X_loc.T @ w
            d1 = loss.d1(margins, y_loc)
            c = loss.d2(margins, y_loc)
            g = jax.lax.psum(X_loc @ d1, axis) / N_GLOBAL + lam * w
            coeffs_tau = loss.d2(X_tau.T @ w, y_tau)
            res = pcg_samples(X_loc, c, N_GLOBAL, lam, g, 0.0, PCG_ITERS,
                              X_tau=X_tau, coeffs_tau=coeffs_tau, mu=mu,
                              axis_name=axis, precond="woodbury")
            return w - res.v / (1.0 + res.delta)

        fn = jax.shard_map(
            step, mesh=mesh,
            in_specs=(P(None, axis), P(axis), P(), P(), P()),
            out_specs=P(), check_vma=False)
        args = (jax.ShapeDtypeStruct((D_GLOBAL, N_GLOBAL), jnp.float32),
                jax.ShapeDtypeStruct((N_GLOBAL,), jnp.float32),
                jax.ShapeDtypeStruct((D_GLOBAL, TAU), jnp.float32),
                jax.ShapeDtypeStruct((TAU,), jnp.float32),
                jax.ShapeDtypeStruct((D_GLOBAL,), jnp.float32))
        in_sh = (NamedSharding(mesh, P(None, axis)),
                 NamedSharding(mesh, P(axis)),
                 NamedSharding(mesh, P()), NamedSharding(mesh, P()),
                 NamedSharding(mesh, P()))
        out_sh = NamedSharding(mesh, P())
    else:
        raise ValueError(partition)

    return jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh), args


def run(partition: str, n_dev: int) -> dict:
    mesh = _flat_mesh(n_dev, "model" if partition == "features" else "data")
    t0 = time.perf_counter()
    fn, args = build_step(partition, mesh)
    compiled = fn.lower(*args).compile()
    dt = time.perf_counter() - t0
    colls = collective_stats(compiled.as_text())
    mem = compiled.memory_analysis()

    # paper Table 4 expectation, per-device bytes inside the PCG while body
    # (the body is counted once; PCG_ITERS multiplies analytically):
    if partition == "features":
        expect = N_GLOBAL * 4            # one n-vector all-reduce / iter
    else:
        expect = D_GLOBAL * 4            # one d-vector all-reduce / iter
    rec = {
        "partition": partition, "devices": n_dev,
        "d": D_GLOBAL, "n": N_GLOBAL, "tau": TAU,
        "pcg_iters": PCG_ITERS,
        "X_bytes_per_device": int(D_GLOBAL) * N_GLOBAL * 4 // n_dev,
        "collectives": colls,
        "expected_pcg_vector_bytes": expect,
        "arg_gib": round(mem.argument_size_in_bytes / 2**30, 2),
        "temp_gib": round(mem.temp_size_in_bytes / 2**30, 2),
        "compile_s": round(dt, 1),
    }
    print(f"[glm-dryrun] {partition} x {n_dev} chips: "
          f"X {rec['X_bytes_per_device']/2**30:.1f} GiB/chip, "
          f"args {rec['arg_gib']} GiB, temp {rec['temp_gib']} GiB, "
          f"colls { {k: v for k, v in colls.items() if isinstance(v, dict) and v['count']} } "
          f"(compile {rec['compile_s']}s)", flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--partition", default="both",
                    choices=["features", "samples", "both"])
    ap.add_argument("--mesh", default="pod",
                    choices=["pod", "multipod", "both"])
    ap.add_argument("--json", default=None)
    args = ap.parse_args()
    parts = ["features", "samples"] if args.partition == "both" \
        else [args.partition]
    sizes = {"pod": [256], "multipod": [512], "both": [256, 512]}[args.mesh]
    recs = []
    for p in parts:
        for n in sizes:
            recs.append(run(p, n))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(recs, f, indent=1)
    # machine-check Table 4: F's in-loop vector collective is n-sized,
    # S's is d-sized
    by = {r["partition"]: r for r in recs}
    if "features" in by and "samples" in by:
        f_ar = by["features"]["collectives"]["all-reduce"]["bytes"]
        s_ar = by["samples"]["collectives"]["all-reduce"]["bytes"]
        print(f"[claim/Table4-HLO] all-reduce bytes in one Newton step "
              f"(PCG body counted once): F={f_ar:,} vs S={s_ar:,} "
              f"(n={N_GLOBAL:,} floats vs d={D_GLOBAL:,} floats per iter)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
