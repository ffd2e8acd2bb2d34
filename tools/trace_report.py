#!/usr/bin/env python
"""Trace report CLI: critical-path table + measured-vs-analytic diff.

Runs two small traced DiSCO solves — one in-memory sparse, one streamed
out-of-core — and for each prints

1. the per-(shard, kind) span aggregation of
   :func:`repro.obs.report.span_rows`, with the ``critical`` column
   flagging the straggler shard whose total gates each phase's barrier;
2. the per-outer-iteration measured-vs-predicted table of
   :func:`repro.obs.report.measured_vs_predicted`, diffing the
   ``iter_s`` wall-clock recorded in ``DiscoResult.history`` against
   the analytic iteration-time model (``comm.disco_sparse_iter_time``
   in-memory, ``comm.disco_streaming_iter_time`` streamed). The first
   row includes jit compilation and is flagged ``compile`` — its ratio
   is expected to be large.

``--chrome-out PREFIX`` additionally writes ``PREFIX.inmemory.json``
and ``PREFIX.streamed.json`` Chrome trace-event files loadable in
Perfetto / ``chrome://tracing`` (docs/observability.md).

Usage::

    PYTHONPATH=src python tools/trace_report.py [--chrome-out /tmp/tr]
"""
from __future__ import annotations

import argparse
import os
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "src"))
from repro.kernels.ops import ref_kernels_off_tpu  # noqa: E402

ref_kernels_off_tpu()   # the fast jnp path off the chip

# workload: small enough for CI, large enough that every span kind fires
D, N, DENSITY = 96, 320, 0.15
MAX_OUTER = 4
CHUNK = 16


def _fmt(v) -> str:
    if isinstance(v, bool):
        return "*" if v else ""
    if isinstance(v, float):
        return f"{v:.4g}"
    return str(v)


def _table(rows: list[dict], cols: list[str], title: str) -> str:
    grid = [cols] + [[_fmt(r.get(c, "")) for c in cols] for r in rows]
    widths = [max(len(row[i]) for row in grid) for i in range(len(cols))]
    lines = [f"== {title} ==",
             "  ".join(c.ljust(w) for c, w in zip(grid[0], widths)),
             "  ".join("-" * w for w in widths)]
    lines += ["  ".join(v.ljust(w) for v, w in zip(row, widths))
              for row in grid[1:]]
    return "\n".join(lines)


def _config(streaming: bool):
    from repro.core.disco import DiscoConfig
    return DiscoConfig(partition="samples", loss="logistic", lam=1e-2,
                       tau=16, max_outer=MAX_OUTER, grad_tol=1e-10,
                       ell_block_d=8, ell_block_n=8, partition_block=16,
                       stream_chunk_size=CHUNK, trace=True)


def _report(label: str, res, cfg, streaming: bool,
            chrome_out: str | None) -> None:
    from repro import obs

    tracer = obs.get_tracer()
    print()
    print(_table(obs.report.span_rows(tracer),
                 ["shard", "kind", "events", "total_s", "mean_ms",
                  "max_ms", "critical"],
                 f"{label}: spans per (shard, kind)  [* = critical path]"))

    info = res.partition_info
    shard_nnz = info["shard_nnz"]
    chunks = max(1, (info["n_items"] + CHUNK - 1) // CHUNK)
    mvp = obs.report.measured_vs_predicted(
        res.history, shard_nnz, cfg.partition, n=N, d=D, m=info["m"],
        s=cfg.pcg_block_s, hvp_fused=cfg.hvp_fused,
        hvp_dtype=cfg.hvp_dtype, streaming=streaming,
        chunk_nnz_max=int(max(shard_nnz) // chunks + 1),
        prefetch_depth=cfg.prefetch_depth)
    for r in mvp:
        r["measured_ms"] = r.pop("measured_s") * 1e3
        r["predicted_ms"] = r.pop("predicted_s") * 1e3
    print()
    print(_table(mvp,
                 ["outer_iter", "pcg_iters", "measured_ms",
                  "predicted_ms", "ratio", "compile"],
                 f"{label}: measured vs analytic iteration time "
                 "[* = includes jit compile]"))

    if chrome_out:
        path = f"{chrome_out}.{label.replace('-', '')}.json"
        obs.export.write_chrome_trace(tracer, path)
        print(f"[chrome trace] {path}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chrome-out", default=None, metavar="PREFIX",
                    help="write PREFIX.{inmemory,streamed}.json "
                         "Perfetto-loadable trace files")
    args = ap.parse_args(argv)

    from repro import obs
    from repro.core.disco import DiscoSolver
    from repro.data.sparse import make_sparse_glm_data
    from repro.data.store import ShardStore

    X, y, _ = make_sparse_glm_data(d=D, n=N, density=DENSITY, alpha=1.0,
                                   beta=0.6, seed=2)

    obs.enable(reset=True)
    cfg = _config(streaming=False)
    res = DiscoSolver(X, y, cfg).fit()
    _report("in-memory", res, cfg, streaming=False,
            chrome_out=args.chrome_out)

    obs.enable(reset=True)
    cfg = _config(streaming=True)
    with tempfile.TemporaryDirectory() as td:
        store = ShardStore.from_csr(X, y, os.path.join(td, "store"),
                                    axis="samples", chunk_size=CHUNK)
        res = DiscoSolver.from_store(store, cfg).fit()
    _report("streamed", res, cfg, streaming=True,
            chrome_out=args.chrome_out)
    obs.disable()
    return 0


if __name__ == "__main__":
    sys.exit(main())
