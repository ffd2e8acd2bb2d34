import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every jax import: jax locks the device count on first init.

DOC = """Multi-pod dry-run: lower + compile every (arch x input-shape x mesh)
combination against the production mesh with ShapeDtypeStruct stand-ins —
no allocation, no data. Failures here (sharding mismatch, OOM at compile,
unsupported collective) are bugs in the framework.

Per combo it records:
  * memory_analysis()  — per-device bytes (proves the config fits HBM)
  * cost_analysis()    — HLO FLOPs / bytes for the roofline terms
  * collective bytes   — parsed from the post-SPMD HLO text, per op kind

Usage:
  python -m repro.launch.dryrun --arch olmo-1b --shape train_4k
  python -m repro.launch.dryrun --all --mesh both --json out.json
"""



import argparse
import json
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, INPUT_SHAPES, get_config
from repro.configs.shapes import input_specs, is_applicable
from repro.launch.mesh import make_production_mesh
from repro.models import decode_step, forward
from repro.models import policy as actpolicy
from repro.train.losses import lm_loss
from repro.train.sharding import (batch_pspec_for, cache_pspecs,
                                  param_pspecs)

# ---------------------------------------------------------------------------
# HLO collective parsing
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(type_str: str) -> int:
    """Sum bytes over every array in an HLO result type (handles tuples)."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_stats(hlo_text: str) -> dict:
    """Per-kind {count, bytes} of collective ops in (post-SPMD) HLO text."""
    stats = {k: {"count": 0, "bytes": 0} for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        # result-typed op line:  %name = <type> <opcode>(...)
        m = re.match(r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\(?[^)=]*?\)?)\s+"
                     r"([\w\-]+)\(", s)
        if not m:
            continue
        type_str, opcode = m.groups()
        for kind in _COLLECTIVES:
            if opcode == kind or opcode.startswith(kind + "-"):
                # exclude -start/-done double counting: count only starts
                if opcode.endswith("-done"):
                    break
                stats[kind]["count"] += 1
                stats[kind]["bytes"] += _shape_bytes(type_str)
                break
    stats["total_bytes"] = sum(v["bytes"] for k, v in stats.items()
                               if isinstance(v, dict))
    return stats


# ---------------------------------------------------------------------------
# step builders (what each input-shape kind lowers)
# ---------------------------------------------------------------------------

# memory-bound combos process the batch in slices: train = gradient
# accumulation, prefill = sequential request slices (chunked serving).
# Chosen from the measured dry-run HBM overruns (EXPERIMENTS.md §Perf it.5).
# per-combo config overrides for memory (chunked-scan buffer is
# (B, ssm_chunk, d_inner, N) f32 — 8.6 GiB at chunk=256 on falcon train)
CFG_OVERRIDES = {
    ("falcon-mamba-7b", "train"): {"ssm_chunk": 32},
    ("zamba2-2.7b", "train"): {"ssm_chunk": 64},
}

MICROBATCHES = {
    ("falcon-mamba-7b", "train"): 2,
    ("qwen3-moe-30b-a3b", "train"): 4,
    ("mixtral-8x7b", "train"): 2,
    ("qwen3-moe-30b-a3b", "prefill"): 2,
    ("mixtral-8x7b", "prefill"): 2,
}


def build_lowerable(cfg, shape_name: str, mesh):
    """Returns (fn, kwargs_of_ShapeDtypeStructs, in_shardings_kwargs)."""
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
        from repro.optim import AdamWConfig, adamw_init, adamw_update
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
                    lambda p, b: lm_loss(cfg, p, b, remat=True),
                    has_aux=True)(params, sl)
                grads = jax.tree.map(
                    lambda g, x: g + x.astype(jnp.float32) / mb, grads, gi)
                loss = loss + li / mb
            params, opt_state, _ = adamw_update(acfg, grads, opt_state,
                                                params)
            return params, opt_state, loss

        batch = specs["batch"]
        # optimizer moments inherit the param sharding (2-D FSDP x TP)
        from repro.optim import AdamWState
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
                logits, _ = forward(cfg, params, sl, last_only=True)
                outs.append(logits)
            return jnp.concatenate(outs, 0) if mb_p > 1 else outs[0]

        batch = specs["batch"]
        in_sh = (shard(pspec), shard(batch_pspec_for(batch, mesh)))
        fn = jax.jit(prefill_step, in_shardings=in_sh,
                     out_shardings=NamedSharding(mesh, P()))
        return fn, (params_sds, batch)

    # decode
    tokens, cache = specs["tokens"], specs["cache"]

    def serve_step(params, tokens, cache):
        logits, cache = decode_step(cfg, params, tokens, cache)
        return logits, cache

    cspec = cache_pspecs(cfg, cache, mesh)
    in_sh = (shard(pspec), NamedSharding(mesh, P()), shard(cspec))
    out_sh = (NamedSharding(mesh, P()), shard(cspec))
    fn = jax.jit(serve_step, in_shardings=in_sh, out_shardings=out_sh)
    return fn, (params_sds, tokens, cache)


# ---------------------------------------------------------------------------
# one combo
# ---------------------------------------------------------------------------

def run_combo(arch: str, shape_name: str, multi_pod: bool,
              verbose: bool = True) -> dict:
    cfg = get_config(arch)
    ok, reason = is_applicable(cfg, shape_name)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "2x16x16" if multi_pod else "16x16",
                "status": "skipped", "reason": reason}

    shape = INPUT_SHAPES[shape_name]
    cfg = cfg.replace(**CFG_OVERRIDES.get((cfg.name, shape.kind), {}))
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.perf_counter()
    with actpolicy.use_mesh(mesh):
        fn, arg_specs = build_lowerable(cfg, shape_name, mesh)
        lowered = fn.lower(*arg_specs)
    t_lower = time.perf_counter() - t0

    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    colls = collective_stats(compiled.as_text())

    n_dev = mesh.size
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "status": "ok",
        "devices": n_dev,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "flops_per_device": cost.get("flops", 0.0),
        "bytes_per_device": cost.get("bytes accessed", 0.0),
        "collectives": colls,
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes",
                                  getattr(mem, "temp_size_in_bytes", 0)),
        },
    }
    if verbose:
        gb = 1 << 30
        m = rec["memory"]
        print(f"  args {m['argument_bytes']/gb:.2f} GiB  "
              f"temp {m['temp_bytes']/gb:.2f} GiB  "
              f"flops/dev {rec['flops_per_device']:.3e}  "
              f"coll {colls['total_bytes']/gb:.3f} GiB  "
              f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=list(INPUT_SHAPES) + [None])
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod",
                                                      "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--json", default=None, help="write records here")
    args = ap.parse_args()

    archs = ARCHS if (args.all or not args.arch) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or not args.shape) \
        else [args.shape]
    meshes = {"pod": [False], "multipod": [True],
              "both": [False, True]}[args.mesh]

    records = []
    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch} x {shape} x {'2x16x16' if mp else '16x16'}"
                print(f"[dryrun] {tag}", flush=True)
                try:
                    rec = run_combo(arch, shape, mp)
                except Exception as e:  # noqa: BLE001 — report, keep going
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
    n_skip = sum(r["status"] == "skipped" for r in records)
    print(f"dryrun: {n_ok} ok, {n_skip} skipped, {failures} failed")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
