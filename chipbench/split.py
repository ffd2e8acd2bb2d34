#!/usr/bin/env python3
"""The per-layer split of a cell's window, from the program's own spans.

    python3 chipbench/split.py --workload ctr.score.open --seed 7 \\
        --seconds 10 --modes off obs off obs

One warmed system runs the cell's traffic for ``--seconds`` once per
mode, each window with the same seed:

* ``off``: no profiler and ``repro.obs`` off, as in the benchmark's
  ``--trace 0`` runs;
* ``profile``: the ``jax.profiler`` on and ``repro.obs`` off, as in the
  benchmark's ``--trace 1`` runs;
* ``obs``: the profiler and ``repro.obs`` on, so the program's spans sit
  in the trace beside the device operations (chipbench/spans.py).

Each window prints one JSON line: the cell's end-to-end metrics, every
reader in ``metrics/`` that finds something to read, and in ``obs`` mode
each span kind's mean, self and idle milliseconds. ``off`` against
``obs`` windows is what tracing costs. The benchmark's own runs do not
call this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODES = ("off", "profile", "obs")


def readers() -> list[str]:
    """The metric families that ``metrics/`` has a reader for."""
    return sorted(f[:-3] for f in os.listdir(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "metrics"))
        if f.endswith(".py"))


def measure(system, mix: dict, seconds: float, seed: int, mode: str,
            peaks: dict | None = None, **planes) -> dict:
    """One window of ``system`` in ``mode``; the line as a dict.
    ``planes`` (``plane_prefix``, ``line_prefix``) say where the trace's
    device operations are (the TPU's by default)."""
    import jax
    from chipbench import harness, spans, trace as tr
    from repro import obs

    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    tracer = None
    with tempfile.TemporaryDirectory() as tdir:
        if mode != "off":
            jax.profiler.start_trace(tdir)
        if mode == "obs":
            tracer = obs.enable(reset=True)
        try:
            with jax.profiler.TraceAnnotation(tr.WINDOW):
                out = system.window(mix, seconds, seed)
        finally:
            obs.disable()
            if mode != "off":
                jax.profiler.stop_trace()
        rec = system.record(out)
        if mode != "off":
            pd = tr.load(tr.find_xplane(tdir))
            rec.update(trace=tr.reduce(pd, **planes), peaks=peaks,
                       spans=spans.reduce(pd, **planes))
    if tracer is not None:
        rec["counters"] = tracer.snapshot()[1]
    line = dict(mode=mode, **system.end_to_end(out))
    for name in readers():
        value = harness.load_metric(name).read(rec)
        if value is not None:
            line[name] = value
    line["spans"] = {
        kind: {f: spans.mean_ms(rec, kind, f + "_s")
               for f in ("total", "self", "idle")} | dict(count=s["count"])
        for kind, s in sorted(rec.get("spans", {}).items())}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--modes", nargs="+", choices=MODES,
                    default=["off", "obs"])
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from chipbench import drive, harness, roofline

    cell, config, mix, _, _ = harness.load_cell(args.workload)
    harness.require_chip(cell["chips"])
    import jax
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    peaks = roofline.peaks(jax.devices()[0].device_kind)
    drive.validate(mix)
    system = harness.SYSTEMS[config["system"]](config, args.seed)
    gc.collect()
    gc.freeze()
    for mode in args.modes:
        line = measure(system, mix, args.seconds, args.seed, mode, peaks)
        print(json.dumps(dict(workload=args.workload, seed=args.seed,
                              **line)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
