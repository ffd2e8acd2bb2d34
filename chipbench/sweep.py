#!/usr/bin/env python3
"""Find the knee of an open-loop scoring cell: one engine, many rates.

    python3 chipbench/sweep.py --workload ctr.score.open --seed 3 \\
        --seconds 8 --rates 200 300 400 500

For each rate the cell's open loop runs for ``--seconds`` on the same
warmed engine and prints one JSON line: the p50 and p99 latency from due
time, the requests still waiting when the last one fell due (a backlog
that grows through the window), and the mean tick. The knee is the
highest rate whose backlog stays within a batch; the cell's traffic file
fixes its rate at about four fifths of it. The benchmark's own runs do
not call this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from chipbench import drive, harness

    cell, config, _, _, _ = harness.load_cell(args.workload)
    harness.require_chip(cell["chips"])
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    system = harness.ScoreSystem(config, args.seed)
    sched = system.sched
    for rate in args.rates:
        due = drive.arrivals(rate, args.seconds, args.seed)
        st = sched.stats
        busy, ticks = st.busy_s, st.ticks
        waiting_at_last_due = []
        tick = sched.tick

        def watched():
            out = tick()
            waiting_at_last_due.append(len(sched.waiting))
            return out
        sched.tick = watched
        out = drive.open_loop(sched, system.request, due)
        del sched.tick
        lat = np.full(len(due), np.inf)
        for i, t, _ in out["done"]:
            lat[i] = t - due[i]
        n_ticks = st.ticks - ticks
        print(json.dumps(dict(
            rate=rate, requests=len(due),
            p50_ms=1e3 * float(np.percentile(lat, 50, method="higher")),
            p99_ms=1e3 * float(np.percentile(lat, 99, method="higher")),
            last_due_latency_ms=1e3 * float(lat[-1]),
            max_waiting=max(waiting_at_last_due, default=0),
            tick_ms=1e3 * (st.busy_s - busy) / max(n_ticks, 1),
            fill=float(np.isfinite(lat).sum()) / max(n_ticks, 1)
            / system.engine.batch)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
