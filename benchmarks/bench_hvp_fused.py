"""Fused one-pass HVP + mixed-precision tile storage gate (ISSUE 5).

Roofline-style audit of the PCG inner loop's dominant cost — the HBM
bytes the Hessian-vector product streams (docs/kernels.md):

  * **byte ratio**: fused one-pass vs two-pass HBM tile traffic, dense
    (analytic ``comm.dense_hvp_bytes``) and blocked-ELL (measured from
    the tile arrays each path actually touches), at f32 and bf16 tile
    storage;
  * **numeric parity**: the fused f32 HVP must match the two-pass path
    to <= 1e-6 relative error (kernel level), and a full ``hvp_fused``
    DiSCO solve must match the two-pass solve bit-identically in ref
    mode, classic and s-step, both partitionings;
  * **bf16 end-to-end**: a ``hvp_dtype='bfloat16'`` solve (bf16
    curvature, f32 first-order terms) must land within 1e-4 relative
    error of the f32 solver;
  * **wall-clock**: jit'd fused vs two-pass HVP timings — gated (>= 1.5x)
    only where the kernels time the memory system they model, i.e. on a
    TPU backend; on CPU hosts the modeled speedup (byte ratio) is
    reported instead.

Acceptance gate (ISSUE 5): fused moves <= 0.6x the two-pass HBM bytes;
bf16 end-to-end rel err <= 1e-4; fused == two-pass <= 1e-6; a
well-formed ``BENCH_hvp.json`` perf-trajectory record is emitted via the
shared ``benchmarks/common.py`` writer.
"""
from __future__ import annotations

import os
import sys
import time

import numpy as np

from benchmarks.common import (Timer, load_bench_record, save_json, smoke,
                               table, write_bench_record)

if smoke():
    D, N = 128, 512
    DS, NS = 64, 256            # solver problem
    REPS = 3
else:
    D, N = 512, 4096
    DS, NS = 96, 320
    REPS = 10
DENSITY, ALPHA, BETA = 0.15, 1.0, 0.6
BLOCK = 8                       # ELL tile edge of the solver problems
LAM, GRAD_TOL, MAX_OUTER = 1e-2, 1e-9, 12


def _time_hvp(fn, u, reps=REPS):
    import jax

    fn(u).block_until_ready()                  # compile / warm cache
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(u)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def _dense_section(rows, gate):
    import jax
    import jax.numpy as jnp

    from repro.core import comm
    from repro.kernels import ops as kops

    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.standard_normal((D, N)), jnp.float32)
    c = jnp.asarray(rng.random(N), jnp.float32)
    u = jnp.asarray(rng.standard_normal(D), jnp.float32)

    two = jax.jit(lambda v: kops.x_cz_local(X, c, kops.xt_u(X, v)))
    fused = jax.jit(lambda v: kops.x_c_xt_u(X, c, v))
    y2, y1 = np.asarray(two(u)), np.asarray(fused(u))
    rel = float(np.abs(y1 - y2).max() / max(np.abs(y2).max(), 1e-30))
    gate["dense_parity"] = dict(rel_err=rel, ok=rel <= 1e-6)

    # the wall-clock gate is only meaningful when the native Pallas
    # kernels actually run (TPU backend, mode not overridden to ref)
    timeable = jax.default_backend() == "tpu" and kops._mode() == "native"
    t_two = _time_hvp(two, u)
    t_fused = _time_hvp(fused, u)
    speedup = t_two / max(t_fused, 1e-12)

    for dt, db in (("float32", comm.BYTES_PER_FLOAT),
                   ("bfloat16", comm.BYTES_BF16)):
        b_two = comm.dense_hvp_bytes(D, N, dtype_bytes=comm.BYTES_PER_FLOAT)
        b_fused = comm.dense_hvp_bytes(D, N, fused=True, dtype_bytes=db)
        ratio = b_fused / b_two
        rows.append(dict(
            path="dense", dtype=dt, d=D, n=N,
            bytes_twopass=b_two, bytes_fused=b_fused,
            byte_ratio=round(ratio, 4),
            speedup_modeled=round(b_two / b_fused, 2),
            speedup_measured=(round(speedup, 2)
                              if timeable and dt == "float32" else None),
            gbps_fused=(round(b_fused / max(t_fused, 1e-12) / 1e9, 2)
                        if dt == "float32" else None)))
    gate["dense_bytes"] = dict(
        ratio_f32=rows[-2]["byte_ratio"], ratio_bf16=rows[-1]["byte_ratio"],
        ok=rows[-2]["byte_ratio"] <= 0.6 and rows[-1]["byte_ratio"] <= 0.6)
    gate["wallclock"] = dict(
        timeable=timeable, speedup=round(speedup, 2),
        ok=(speedup >= 1.5) if timeable else True)
    return timeable, speedup


def _ell_section(rows, gate):
    import jax
    import jax.numpy as jnp

    from repro.core import comm
    from repro.data.sparse import (ell_pair_from_csr, hvp_tile_dtype,
                                   make_sparse_glm_data)
    from repro.kernels import ops as kops

    X, _, _ = make_sparse_glm_data(d=D, n=N, density=DENSITY, alpha=ALPHA,
                                   beta=BETA, seed=1)
    fwd, tr = ell_pair_from_csr(X, BLOCK, BLOCK)
    data, cols = jnp.asarray(fwd.data), jnp.asarray(fwd.cols)
    dataT, colsT = jnp.asarray(tr.data), jnp.asarray(tr.cols)
    rng = np.random.default_rng(2)
    nrb, ncb = data.shape[0], dataT.shape[0]
    u = jnp.asarray(rng.standard_normal(nrb * BLOCK), jnp.float32)
    c = jnp.asarray(rng.random(ncb * BLOCK), jnp.float32)

    two = jax.jit(lambda v: kops.ell_matvec(
        data, cols, kops.ell_matvec(dataT, colsT, v), c))
    fused = jax.jit(lambda v: kops.ell_hvp(dataT, colsT, v, c,
                                           fwd=(data, cols)))
    y2, y1 = np.asarray(two(u)), np.asarray(fused(u))
    rel = float(np.abs(y1 - y2).max() / max(np.abs(y2).max(), 1e-30))
    gate["ell_parity"] = dict(rel_err=rel, ok=rel <= 1e-6)

    # measured tile bytes: exactly the arrays each path streams
    tiles_fwd = int(np.prod(data.shape[:2]))
    tiles_tr = int(np.prod(dataT.shape[:2]))
    b_two = comm.ell_hvp_bytes(tiles_fwd, tiles_tr, BLOCK, BLOCK)
    assert b_two == data.nbytes + dataT.nbytes      # model == measured
    for dt in ("float32", "bfloat16"):
        db = comm.hvp_dtype_bytes(dt)
        b_fused = comm.ell_hvp_bytes(tiles_fwd, tiles_tr, BLOCK, BLOCK,
                                     fused=True, dtype_bytes=db)
        if dt == "bfloat16":
            hdt = hvp_tile_dtype(dt)
            assert b_fused == dataT.astype(hdt).nbytes
        ratio = b_fused / b_two
        rows.append(dict(
            path="ell", dtype=dt, d=D, n=N,
            tiles_fwd=tiles_fwd, tiles_tr=tiles_tr,
            bytes_twopass=b_two, bytes_fused=b_fused,
            byte_ratio=round(ratio, 4),
            speedup_modeled=round(b_two / b_fused, 2),
            speedup_measured=None, gbps_fused=None))
    gate["ell_bytes"] = dict(
        ratio_f32=rows[-2]["byte_ratio"], ratio_bf16=rows[-1]["byte_ratio"],
        ok=rows[-2]["byte_ratio"] <= 0.6 and rows[-1]["byte_ratio"] <= 0.6)


def _solver_section(rows, gate):
    from repro.core import DiscoConfig, disco_fit
    from repro.data.sparse import make_sparse_glm_data
    from repro.kernels import ops as kops

    X, y, _ = make_sparse_glm_data(d=DS, n=NS, density=0.2, alpha=1.0,
                                   beta=0.5, seed=3)
    base = dict(loss="logistic", lam=LAM, tau=16, max_outer=MAX_OUTER,
                grad_tol=GRAD_TOL, ell_block_d=BLOCK, ell_block_n=BLOCK,
                partition_block=16)
    # bit-identity is a ref-mode dispatch property (same jaxpr); native/
    # interpret kernels reorder the pass-B accumulation, so the ISSUE's
    # "identical or <= 1e-6 rel err" criterion applies there
    exact = kops._mode() == "ref"
    ident_ok, bf16_ok = True, True
    for partition in ("features", "samples"):
        for s in (1, 2):
            cfg = DiscoConfig(partition=partition, pcg_block_s=s, **base)
            r0 = disco_fit(X, y, cfg)
            r1 = disco_fit(X, y, DiscoConfig(partition=partition,
                                             pcg_block_s=s,
                                             hvp_fused=True, **base))
            rel_f = float(np.linalg.norm(r1.w - r0.w)
                          / max(np.linalg.norm(r0.w), 1e-30))
            ident = bool(np.array_equal(r0.w, r1.w)) if exact \
                else rel_f <= 1e-6
            rb = disco_fit(X, y, DiscoConfig(partition=partition,
                                             pcg_block_s=s, hvp_fused=True,
                                             hvp_dtype="bfloat16", **base))
            rel_bf = float(np.linalg.norm(rb.w - r0.w)
                           / max(np.linalg.norm(r0.w), 1e-30))
            ident_ok &= ident
            bf16_ok &= rel_bf <= 1e-4
            rows.append(dict(
                path="solve", dtype="bfloat16", partition=partition,
                block_s=s, fused_bitident=ident, fused_rel_err=rel_f,
                bf16_rel_err=rel_bf,
                outer_f32=len(r0.history), outer_bf16=len(rb.history)))
    gate["solver_fused_identical"] = dict(ok=ident_ok, exact_mode=exact)
    gate["solver_bf16"] = dict(
        max_rel_err=max(r["bf16_rel_err"] for r in rows
                        if r["path"] == "solve"),
        ok=bf16_ok)


def run(quiet=False):
    from repro.kernels.ops import ref_kernels_off_tpu

    # the gate audits byte *ratios* and solver parity; the fast jnp
    # reference path keeps CPU runs honest and quick. On a TPU backend
    # the mode is left alone so the native kernels run and the
    # wall-clock gate times the memory system it models.
    ref_kernels_off_tpu()
    rows: list[dict] = []
    gate: dict = {}

    timeable, speedup = _dense_section(rows, gate)
    _ell_section(rows, gate)
    _solver_section(rows, gate)

    ok = all(g.get("ok", True) for g in gate.values())

    record = dict(bench="hvp_fused", smoke=smoke(),
                  backend=("tpu" if timeable else "cpu"), rows=rows)
    path = write_bench_record("hvp", record)
    loaded = load_bench_record("hvp")        # smoke asserts well-formed
    assert loaded["bench"] == "hvp_fused" and len(loaded["rows"]) == len(rows)

    if not quiet:
        print(table([r for r in rows if r["path"] != "solve"],
                    ["path", "dtype", "bytes_twopass", "bytes_fused",
                     "byte_ratio", "speedup_modeled", "speedup_measured",
                     "gbps_fused"],
                    title=f"fused one-pass HVP vs two-pass (d={D}, n={N})"))
        print()
        print(table([r for r in rows if r["path"] == "solve"],
                    ["partition", "block_s", "fused_bitident",
                     "bf16_rel_err", "outer_f32", "outer_bf16"],
                    title=f"end-to-end DiSCO solves (d={DS}, n={NS})"))
        print(f"[gate] dense byte ratio f32/bf16: "
              f"{gate['dense_bytes']['ratio_f32']:.2f}/"
              f"{gate['dense_bytes']['ratio_bf16']:.2f} (need <=0.6)")
        print(f"[gate] ELL byte ratio f32/bf16: "
              f"{gate['ell_bytes']['ratio_f32']:.2f}/"
              f"{gate['ell_bytes']['ratio_bf16']:.2f} (need <=0.6)")
        print(f"[gate] fused==two-pass rel err: dense "
              f"{gate['dense_parity']['rel_err']:.1e}, ell "
              f"{gate['ell_parity']['rel_err']:.1e} (need <=1e-6)")
        print(f"[gate] solver fused bit-identical (ref mode): "
              f"{gate['solver_fused_identical']['ok']}")
        print(f"[gate] bf16 end-to-end rel err "
              f"{gate['solver_bf16']['max_rel_err']:.1e} (need <=1e-4)")
        if timeable:
            print(f"[gate] wall-clock fused speedup {speedup:.2f}x "
                  "(need >=1.5x)")
        else:
            print(f"[gate] wall-clock: not timeable on this backend "
                  f"(cpu ref path; modeled speedup "
                  f"{1 / gate['dense_bytes']['ratio_f32']:.1f}x) — "
                  "gated on TPU only")
        print(f"[gate] BENCH_hvp.json written + validated: {path}")
        print(f"[gate] {'PASS' if ok else 'FAIL'}: fused bytes + parity "
              "+ bf16 end-to-end + perf record")
    save_json("hvp_fused", {"rows": rows, "gate": gate, "pass": ok})
    return rows, ok


def main():
    return run()


if __name__ == "__main__":
    sys.exit(0 if main()[1] else 1)
