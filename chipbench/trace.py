"""Reduction of a ``jax.profiler`` trace to device busy and idle time.

The benchmark wraps its measured window in a host ``TraceAnnotation``
named ``window`` and each call into a layer in one named after it
(``solve``, ``tick``, ``submit``). From the ``.xplane.pb`` this module
takes:

* ``busy_s``: the union of the device's operation intervals inside the
  window, averaged over the device planes;
* ``window_s``: the window span's length;
* ``device_ops``: the operations with the most device self time (less
  the operations nested inside them, as a ``while`` holds its body),
  summed by name, each named by its HLO instruction (``fusion.12``);
* ``idle_gaps``: the longest gaps between device operations inside the
  window, each named by the innermost host span around its middle
  (``host`` where no span is open).

On a TPU the operations are the ``XLA Ops`` lines of the
``/device:TPU:<i>`` planes. The tests read a CPU capture, whose
operations sit on the host plane, by passing other plane and line names.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

TPU_PLANES = "/device:TPU:"
TPU_OPS = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW = "window"
SPANS = ("solve", "tick", "submit")
TOP = 10


def find_xplane(log_dir: str) -> str:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` log directory."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def op_intervals(pd, plane_prefix: str = TPU_PLANES,
                 line_prefix: str = TPU_OPS) -> dict[str, list]:
    """``{plane: [(start_ns, end_ns, name), ...]}`` of the operations on
    the matching lines of each matching plane (instants left out)."""
    out: dict[str, list] = {}
    for plane in pd.planes:
        if not plane.name.startswith(plane_prefix):
            continue
        evs = [(e.start_ns, e.start_ns + e.duration_ns, short_name(e.name))
               for line in plane.lines if line.name.startswith(line_prefix)
               for e in line.events if e.duration_ns > 0]
        if evs:
            out[plane.name] = evs
    return out


def short_name(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%") if " = " in name else name


def self_times(intervals) -> list[tuple[str, float]]:
    """``(name, self time)`` of each interval: its length less that of
    the intervals nested directly inside it."""
    evs = sorted(intervals, key=lambda iv: (iv[0], -iv[1]))
    own = [e - s for s, e, _ in evs]
    stack: list[int] = []
    for i, (s, e, _) in enumerate(evs):
        while stack and evs[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= evs[stack[-1]][1]:
            own[stack[-1]] -= e - s
        stack.append(i)
    return [(name, t) for (_, _, name), t in zip(evs, own)]


def host_spans(pd, names, plane_name: str = HOST_PLANE) -> list:
    """``[(start_ns, end_ns, name)]`` of host events named in ``names``."""
    names = set(names)
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for plane in pd.planes if plane.name == plane_name
            for line in plane.lines for e in line.events
            if e.name in names]


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged ``(start, end)`` intervals, clipped to ``[lo, hi]``."""
    merged: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e, *_ in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _innermost(spans, t: float) -> str:
    best = None
    for s, e, name in spans:
        if s <= t < e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "host"


def reduce(pd, plane_prefix: str = TPU_PLANES, line_prefix: str = TPU_OPS,
           host_plane: str = HOST_PLANE) -> dict:
    """Busy and window seconds and the breakdown of one traced window."""
    windows = host_spans(pd, (WINDOW,), host_plane)
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW!r} span, found "
                         f"{len(windows)}")
    lo, hi, _ = windows[0]
    planes = op_intervals(pd, plane_prefix, line_prefix)
    if not planes:
        raise ValueError(f"no operations on {plane_prefix}* / "
                         f"{line_prefix}* lines")
    spans = host_spans(pd, SPANS, host_plane)
    busy, gaps = [], []
    per_op: dict[str, float] = defaultdict(float)
    for evs in planes.values():
        merged = union(evs, lo, hi)
        busy.append(sum(e - s for s, e in merged))
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, 0.5 * (s + e)))
        clipped = [(max(s, lo), min(e, hi), name) for s, e, name in evs]
        for name, t in self_times([c for c in clipped if c[1] > c[0]]):
            per_op[name] += t
    gaps.sort(key=lambda g: -g[0])
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])
    return dict(
        busy_s=sum(busy) / len(busy) * 1e-9,
        window_s=(hi - lo) * 1e-9,
        device_ops=[[name, ns * 1e-9] for name, ns in ops[:TOP]],
        idle_gaps=[[_innermost(spans, mid), ns * 1e-9]
                   for ns, mid in gaps[:TOP]])
