import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every jax import (see dryrun.py).

DOC = """Structural cost probe for the roofline analysis.

XLA's ``cost_analysis()`` counts a ``lax.scan`` body ONCE, independent of the
trip count — so the full-config dry-run under-reports FLOPs / bytes /
collective bytes of an L-layer network by ~L x (verified: the undercount
factor equals the layer count). This probe recovers exact totals
structurally:

  1. lower the SAME step with the layer stack UNROLLED (python loop) at
     k=1 and k=2 layer units (unit = shared_attn_period for hybrids,
     1 otherwise; whisper scales encoder and decoder together);
  2. marginal per-unit cost = c(2) - c(1); per-step total for the real
     depth L:   cost(L) = c(1) + (L/unit - 1) * marginal.

Linearity holds because every assigned stack is homogeneous in its unit —
the only depth-dependent ops are the per-layer blocks themselves. Non-layer
cost (embedding, unembed, CE, optimizer scatter) lives in c(1) - marginal
and is extrapolated exactly.

Inner sequential loops are likewise normalized: SSM probes set
ssm_chunk = seq_len, making the chunked selective-scan a single chunk
(nc = 1) so its associative scan is fully counted.

Usage:
  python -m repro.launch.costprobe --all --mesh both --json costprobe.json
"""

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, INPUT_SHAPES, get_config
from repro.configs.shapes import input_specs, is_applicable
from repro.launch.dryrun import (CFG_OVERRIDES, MICROBATCHES,
                                 collective_stats)
from repro.launch.mesh import make_production_mesh
from repro.models import decode_step, forward
from repro.models import policy as actpolicy
from repro.train.losses import lm_loss
from repro.train.sharding import (batch_pspec_for, cache_pspecs,
                                  param_pspecs)


def probe_cfg(cfg, k: int, shape_kind: str):
    """Reduced-depth unrolled variant: k layer-units deep."""
    unit = cfg.shared_attn_period if cfg.arch_type == "hybrid" else 1
    kw = {"num_layers": k * unit}
    if cfg.is_encoder_decoder:
        kw["encoder_layers"] = k
    if cfg.ssm != "none" and shape_kind in ("train", "prefill"):
        kw["ssm_chunk"] = INPUT_SHAPES_SEQ[shape_kind]
    return cfg.replace(**kw), unit


INPUT_SHAPES_SEQ = {}  # filled per-shape below


def build_probe(cfg, shape_name: str, mesh):
    """Like dryrun.build_lowerable but with unroll=True step bodies."""
    shape = INPUT_SHAPES[shape_name]
    specs = input_specs(cfg, shape_name)
    pspec = param_pspecs(cfg, mesh)
    shard = lambda tree: jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda x: isinstance(x, P))
    from repro.models import init_params
    params_sds = jax.eval_shape(lambda k: init_params(cfg, k),
                                jax.random.PRNGKey(0))

    if shape.kind == "train":
        from repro.optim import (AdamWConfig, AdamWState, adamw_init,
                                 adamw_update)
        acfg = AdamWConfig()
        opt_sds = jax.eval_shape(adamw_init, params_sds)
        mb = MICROBATCHES.get((cfg.name, "train"), 1)

        def train_step(params, opt_state, batch):
            # gradient accumulation over mb microbatches (activation memory
            # scales 1/mb; the python loop keeps cost_analysis exact)
            B = batch["tokens"].shape[0]
            step = B // mb
            loss = 0.0
            grads = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            for i in range(mb):
                sl = jax.tree.map(
                    lambda a: jax.lax.dynamic_slice_in_dim(
                        a, i * step, step, 0), batch)
                (li, _), gi = jax.value_and_grad(
                    lambda p, b: lm_loss(cfg, p, b, remat=True, unroll=True),
                    has_aux=True)(params, sl)
                grads = jax.tree.map(
                    lambda g, x: g + x.astype(jnp.float32) / mb, grads, gi)
                loss = loss + li / mb
            params, opt_state, _ = adamw_update(acfg, grads, opt_state,
                                                params)
            return params, opt_state, loss

        batch = specs["batch"]
        mom_pspec = param_pspecs(cfg, mesh, for_optimizer=True)
        opt_pspec = AdamWState(step=P(), mu=mom_pspec, nu=mom_pspec)
        in_sh = (shard(pspec), shard(opt_pspec),
                 shard(batch_pspec_for(batch, mesh)))
        out_sh = (shard(pspec), shard(opt_pspec), NamedSharding(mesh, P()))
        fn = jax.jit(train_step, in_shardings=in_sh, out_shardings=out_sh)
        return fn, (params_sds, opt_sds, batch)

    if shape.kind == "prefill":
        mb_p = MICROBATCHES.get((cfg.name, "prefill"), 1)

        def prefill_step(params, batch):
            # chunked serving: heavy prefills process batch slices
            # sequentially (mb_p=1 -> single forward)
            B = batch["tokens"].shape[0]
            step = B // mb_p
            outs = []
            for i in range(mb_p):
                sl = jax.tree.map(
                    lambda a: jax.lax.dynamic_slice_in_dim(
                        a, i * step, step, 0), batch)
                logits, _ = forward(cfg, params, sl, last_only=True,
                                    unroll=True)
                outs.append(logits)
            return jnp.concatenate(outs, 0) if mb_p > 1 else outs[0]

        batch = specs["batch"]
        in_sh = (shard(pspec), shard(batch_pspec_for(batch, mesh)))
        fn = jax.jit(prefill_step, in_shardings=in_sh,
                     out_shardings=NamedSharding(mesh, P()))
        return fn, (params_sds, batch)

    tokens, cache = specs["tokens"], specs["cache"]

    def serve_step(params, tokens, cache):
        logits, cache = decode_step(cfg, params, tokens, cache, unroll=True)
        return logits, cache

    cspec = cache_pspecs(cfg, cache, mesh)
    in_sh = (shard(pspec), NamedSharding(mesh, P()), shard(cspec))
    out_sh = (NamedSharding(mesh, P()), shard(cspec))
    fn = jax.jit(serve_step, in_shardings=in_sh, out_shardings=out_sh)
    return fn, (params_sds, tokens, cache)


def _costs(cfg, shape_name, mesh) -> dict:
    with actpolicy.use_mesh(mesh):
        fn, args = build_probe(cfg, shape_name, mesh)
        lowered = fn.lower(*args)
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    colls = collective_stats(compiled.as_text())
    return {"flops": cost.get("flops", 0.0),
            "bytes": cost.get("bytes accessed", 0.0),
            "coll_bytes": float(colls["total_bytes"]),
            "colls": colls}


def run_combo(arch: str, shape_name: str, multi_pod: bool) -> dict:
    cfg = get_config(arch)
    ok, reason = is_applicable(cfg, shape_name)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "skipped", "reason": reason}
    shape = INPUT_SHAPES[shape_name]
    INPUT_SHAPES_SEQ[shape.kind] = shape.seq_len
    cfg = cfg.replace(**CFG_OVERRIDES.get((cfg.name, shape.kind), {}))

    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.perf_counter()
    cfg1, unit = probe_cfg(cfg, 1, shape.kind)
    cfg2, _ = probe_cfg(cfg, 2, shape.kind)
    c1 = _costs(cfg1, shape_name, mesh)
    c2 = _costs(cfg2, shape_name, mesh)
    n_units = cfg.num_layers // unit

    def extrap(key):
        marginal = c2[key] - c1[key]
        return c1[key] + (n_units - 1) * marginal

    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "status": "ok", "devices": mesh.size,
        "unit": unit, "n_units": n_units,
        "probe_1": {k: c1[k] for k in ("flops", "bytes", "coll_bytes")},
        "probe_2": {k: c2[k] for k in ("flops", "bytes", "coll_bytes")},
        "flops_per_device": extrap("flops"),
        "bytes_per_device": extrap("bytes"),
        "collective_bytes_per_device": extrap("coll_bytes"),
        "probe_s": round(time.perf_counter() - t0, 1),
    }
    print(f"  flops/dev {rec['flops_per_device']:.3e}  "
          f"bytes/dev {rec['bytes_per_device']:.3e}  "
          f"coll/dev {rec['collective_bytes_per_device']:.3e}  "
          f"({rec['probe_s']}s)", flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="pod",
                    choices=["pod", "multipod", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--json", default=None)
    args = ap.parse_args()

    archs = ARCHS if (args.all or not args.arch) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or not args.shape) \
        else [args.shape]
    meshes = {"pod": [False], "multipod": [True],
              "both": [False, True]}[args.mesh]

    records, failures = [], 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch} x {shape} x {'2x16x16' if mp else '16x16'}"
                print(f"[costprobe] {tag}", flush=True)
                try:
                    rec = run_combo(arch, shape, mp)
                except Exception as e:  # noqa: BLE001
                    failures += 1
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "2x16x16" if mp else "16x16",
                           "status": "failed",
                           "error": f"{type(e).__name__}: {e}"}
                    print(f"  FAILED: {rec['error'][:300]}", flush=True)
                records.append(rec)

    if args.json:
        with open(args.json, "w") as f:
            json.dump(records, f, indent=1)
        print(f"wrote {len(records)} records -> {args.json}")
    n_ok = sum(r["status"] == "ok" for r in records)
    print(f"costprobe: {n_ok} ok, "
          f"{sum(r['status'] == 'skipped' for r in records)} skipped, "
          f"{failures} failed")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
