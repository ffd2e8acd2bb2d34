"""Exporter for the in-process tracer (docs/observability.md).

:func:`chrome_trace` — Chrome trace-event JSON ("trace event format",
the JSON-array flavour). Load the written file straight into
https://ui.perfetto.dev (or chrome://tracing) to see the span timeline,
one track per thread — the chunk-prefetch producer thread shows up as
its own lane next to the solver's main thread. The same spans also sit
in any ``jax.profiler`` capture taken while tracing is on, beside the
device operations.
"""
from __future__ import annotations

import json

from repro.obs.tracer import Tracer


def chrome_trace(tracer: Tracer) -> list[dict]:
    """Convert a tracer's events to Chrome trace-event dicts.

    Emits one ``M`` (metadata) event naming each thread, then one
    ``X`` (complete, with ``dur``) or ``i`` (instant, thread-scoped)
    event per recorded span/instant. Timestamps are microseconds
    relative to the tracer's epoch, as the format requires.
    """
    events, counters, gauges = tracer.snapshot()
    out: list[dict] = []
    named: set[int] = set()
    for ev in events:
        if ev.tid not in named:
            named.add(ev.tid)
            out.append({"ph": "M", "name": "thread_name", "pid": 1,
                        "tid": ev.tid, "args": {"name": ev.thread}})
        rec = {"name": ev.kind, "ph": ev.ph, "pid": 1, "tid": ev.tid,
               "ts": (ev.t0_ns - tracer.epoch_ns) / 1e3,
               "args": ev.args}
        if ev.ph == "X":
            rec["dur"] = ev.dur_ns / 1e3
        else:
            rec["s"] = "t"              # thread-scoped instant
        out.append(rec)
    if counters or gauges:
        out.append({"ph": "M", "name": "process_labels", "pid": 1,
                    "tid": 0,
                    "args": {"labels": json.dumps(
                        {"counters": counters, "gauges": gauges})}})
    return out


def write_chrome_trace(tracer: Tracer, path: str) -> str:
    """Write :func:`chrome_trace` output as a Perfetto-loadable JSON
    file; returns ``path``."""
    with open(path, "w") as f:
        json.dump(chrome_trace(tracer), f)
    return path

