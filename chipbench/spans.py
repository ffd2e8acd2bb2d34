"""Reduction of the program's own spans in a ``jax.profiler`` trace.

While ``repro.obs`` tracing is on, each of the program's spans is also a
``TraceAnnotation`` named by its kind on the host plane (one line per
thread), on the clock of the device operations. For each program span
kind found inside the benchmark's ``window`` span this module gives:

* ``count`` and ``total_s``;
* ``self_s``: the spans less the program spans nested directly inside
  them on the same thread (``newton.outer`` less ``newton.step``);
* ``idle_s``: the spans less the device's busy time inside them, busy
  being the union of the device's operation intervals clipped to the
  window (averaged over the device planes, as ``trace.reduce`` does).

A trace of a program without such spans gives ``{}``: the readers of the
metrics built on it then find nothing to read.
"""
from __future__ import annotations

import bisect
from collections import defaultdict

from chipbench import trace as tr


def program_kinds() -> set[str]:
    """The span kinds of the program under test (``repro.obs``)."""
    from repro.obs import SPAN_KINDS
    return {k for k, (_, event, _) in SPAN_KINDS.items() if event == "span"}


def _thread_spans(pd, kinds, host_plane: str, lo: float, hi: float):
    """``[[(start_ns, end_ns, kind), ...] per host line]`` of the spans of
    ``kinds`` that lie inside ``[lo, hi]``."""
    out = []
    for plane in pd.planes:
        if plane.name != host_plane:
            continue
        for line in plane.lines:
            evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                   for e in line.events if e.name in kinds
                   and e.start_ns >= lo and e.start_ns + e.duration_ns <= hi]
            if evs:
                out.append(evs)
    return out


class _Busy:
    """Device busy time inside any interval, from merged intervals."""

    def __init__(self, merged):
        self.starts = [s for s, _ in merged]
        self.ends = [e for _, e in merged]
        self.before = [0.0]
        for s, e in merged:
            self.before.append(self.before[-1] + e - s)

    def _upto(self, t: float) -> float:
        j = bisect.bisect_right(self.starts, t) - 1
        if j < 0:
            return 0.0
        return self.before[j] + min(t, self.ends[j]) - self.starts[j]

    def inside(self, s: float, e: float) -> float:
        return self._upto(e) - self._upto(s)


def reduce(pd, plane_prefix: str = tr.TPU_PLANES,
           line_prefix: str = tr.TPU_OPS,
           host_plane: str = tr.HOST_PLANE) -> dict:
    """``{kind: {count, total_s, self_s, idle_s}}`` of the program spans
    inside the trace's one ``window`` span."""
    windows = tr.host_spans(pd, (tr.WINDOW,), host_plane)
    if len(windows) != 1:
        raise ValueError(f"expected one {tr.WINDOW!r} span, found "
                         f"{len(windows)}")
    lo, hi, _ = windows[0]
    planes = tr.op_intervals(pd, plane_prefix, line_prefix)
    if not planes:
        raise ValueError(f"no operations on {plane_prefix}* / "
                         f"{line_prefix}* lines")
    busy = [_Busy(tr.union(evs, lo, hi)) for evs in planes.values()]
    agg: dict[str, dict] = defaultdict(
        lambda: dict(count=0, total_s=0.0, self_s=0.0, idle_s=0.0))
    for evs in _thread_spans(pd, program_kinds(), host_plane, lo, hi):
        for (s, e, kind), (_, own) in zip(
                sorted(evs, key=lambda iv: (iv[0], -iv[1])),
                tr.self_times(evs)):
            a = agg[kind]
            a["count"] += 1
            a["total_s"] += (e - s) * 1e-9
            a["self_s"] += own * 1e-9
            a["idle_s"] += (e - s - sum(b.inside(s, e) for b in busy)
                            / len(busy)) * 1e-9
    return dict(agg)


def mean_ms(rec: dict, kind: str, field: str = "total_s"):
    """Mean ``field`` of a span kind in ``rec["spans"]``, in ms; ``None``
    where the record holds no such span."""
    span = (rec.get("spans") or {}).get(kind)
    if not span or not span["count"]:
        return None
    return 1e3 * span[field] / span["count"]
