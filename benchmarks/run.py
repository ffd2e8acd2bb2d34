"""Benchmark harness entry point: ``python -m benchmarks.run``.

Runs every paper-table/figure benchmark (fig3, fig4, fig5, table4,
woodbury), the gated engine benches (sstep, loadbalance, streaming,
serving), the amdahl decomposition, and — if a dry-run results file
exists — the roofline analysis. ``--quick`` skips the expensive sweeps; ``--smoke``
(the ``make bench-smoke`` CI gate) runs *everything* at tiny shapes.
"""
from __future__ import annotations

import argparse
import os
import sys
import time


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fig4/fig5/table4/woodbury only (no fig3 sweep)")
    ap.add_argument("--smoke", action="store_true",
                    help="every benchmark at tiny shapes (the "
                         "`make bench-smoke` CI gate; sets "
                         "REPRO_BENCH_SMOKE=1)")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset: fig3,fig4,fig5,table4,"
                         "sstep,loadbalance,streaming,serving,hvp_fused,"
                         "faults,lambda_path,obs,woodbury,amdahl,"
                         "roofline")
    args = ap.parse_args(argv)

    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.smoke:
        os.environ["REPRO_BENCH_SMOKE"] = "1"
        from repro.kernels.ops import ref_kernels_off_tpu
        ref_kernels_off_tpu()

    selected = set(args.only.split(",")) if args.only else None

    def want(name):
        if selected is not None:
            return name in selected
        if args.quick and not args.smoke:
            # these run many full fits (or a forced-8-device subprocess)
            return name not in ("fig3", "sstep", "loadbalance",
                                "streaming", "serving", "hvp_fused",
                                "faults", "lambda_path", "obs")
        return True

    t0 = time.perf_counter()
    print("=" * 72)
    print("repro benchmark suite — DiSCO-S/F (Ma & Takac 2016) in JAX")
    print("=" * 72)

    if want("table4"):
        from benchmarks import bench_table4_comm
        bench_table4_comm.main()
        print()
    if want("sstep"):
        from benchmarks import bench_sstep
        bench_sstep.main()
        print()
    if want("loadbalance"):
        from benchmarks import bench_loadbalance
        bench_loadbalance.main()
        print()
    if want("streaming"):
        from benchmarks import bench_streaming
        bench_streaming.run()
        print()
    if want("serving"):
        from benchmarks import bench_serving
        bench_serving.run()
        print()
    if want("hvp_fused"):
        from benchmarks import bench_hvp_fused
        bench_hvp_fused.run()
        print()
    if want("faults"):
        from benchmarks import bench_faults
        bench_faults.run()
        print()
    if want("lambda_path"):
        from benchmarks import bench_lambda_path
        bench_lambda_path.run()
        print()
    if want("obs"):
        from benchmarks import bench_obs
        bench_obs.run()
        print()
    if want("woodbury"):
        from benchmarks import bench_woodbury
        bench_woodbury.main()
        print()
    if want("amdahl"):
        from benchmarks import bench_amdahl
        bench_amdahl.main()
        print()
    if want("fig4"):
        from benchmarks import bench_fig4_tau
        bench_fig4_tau.main()
        print()
    if want("fig5"):
        from benchmarks import bench_fig5_subsample
        bench_fig5_subsample.main()
        print()
    if want("fig3"):
        from benchmarks import bench_fig3_algorithms
        bench_fig3_algorithms.main()
        print()
    if want("roofline"):
        from benchmarks import roofline
        if os.path.exists(roofline.DEFAULT_RESULTS):
            roofline.main(["--mesh", "16x16"])
            print()
            roofline.main(["--mesh", "2x16x16"])
        else:
            print("[roofline] skipped: no dryrun_results.json — run "
                  "PYTHONPATH=src python -m repro.launch.dryrun --all "
                  "--mesh both --json dryrun_results.json")

    print(f"\nbenchmark suite done in {time.perf_counter() - t0:.1f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
