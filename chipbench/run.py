#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, on the chip this process finds.

    python3 chipbench/run.py --workload epsilon.solve --seed 7 \\
        --seconds 10 --trace 0

Set-up (data and weights from ``--seed``, the system's construction,
compiling or loading from the persistent cache in ``<checkout>/.jax_cache``,
and a warm-up) counts as ``setup_s``; then the cell's traffic runs for
``--seconds``; then the results are checked against a plain reference.
The last line of standard output is the result as one JSON object: the
cell's end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``. The numbers compared, each with its limit, close standard
error. Without a TPU, or with fewer chips than the cell asks for, the run
exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        raise SystemExit(f"chipbench: the system under test is missing: no "
                         f"src/repro under {ROOT}")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from chipbench import harness, roofline

    cell, config, mix, e2e, layer = harness.load_cell(args.workload)
    harness.require_chip(cell["chips"])

    import jax
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    peaks = roofline.peaks(jax.devices()[0].device_kind)

    result = harness.run(config, mix, e2e, layer, seed=args.seed,
                         seconds=args.seconds, trace=bool(args.trace),
                         t_start=T_START, peaks=peaks)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
