"""The program-span reduction on a recorded CPU trace, its readers, and
the split of a window on the CPU.

``data/cpu_spans.xplane.pb`` was written by ``record_spans.py``: the
program with ``repro.obs`` on, one scheduler tick before the ``window``
span, then inside it a solve of three outer iterations and four ticks,
three of which score. On the CPU the operations sit on the host plane's
``tf_XLAPjRtCpuClient`` lines.
"""
import os

import numpy as np
import pytest

from chipbench import harness, spans, split, trace as tr

DATA = os.path.join(os.path.dirname(__file__), "data")
CPU = dict(plane_prefix="/host:CPU", line_prefix="tf_XLAPjRtCpuClient")
PIECES = ("serve.pack", "serve.copy_in", "serve.kernel", "serve.copy_out")
SEED = 2**33 + 7


@pytest.fixture(scope="module")
def pd():
    return tr.load(os.path.join(DATA, "cpu_spans.xplane.pb"))


@pytest.fixture(scope="module")
def red(pd):
    return spans.reduce(pd, **CPU)


def _events(pd, kind):
    (lo, hi, _), = tr.host_spans(pd, (tr.WINDOW,))
    return [(s, e) for s, e, _ in tr.host_spans(pd, (kind,))
            if lo <= s and e <= hi]


def _covered_ns(intervals, lo, hi):
    """Union length inside ``[lo, hi]`` by elementary segments."""
    pts = np.unique(np.clip([t for s, e, *_ in intervals for t in (s, e)]
                            + [lo, hi], lo, hi))
    mids = 0.5 * (pts[:-1] + pts[1:])
    cov = np.zeros(len(mids), bool)
    for s, e, *_ in intervals:
        cov |= (mids >= s) & (mids < e)
    return float(np.sum(np.diff(pts)[cov]))


def test_counts_are_the_spans_inside_the_window(red):
    assert red["newton.outer"]["count"] == red["newton.step"]["count"] == 3
    # the tick before the window is left out; the empty tick counts
    assert red["serve.tick"]["count"] == 4
    for kind in PIECES:
        assert red[kind]["count"] == 3
    assert set(red) <= spans.program_kinds()


def test_self_time_is_the_span_less_its_nested_spans(pd, red):
    for parent, children in (("newton.outer", ("newton.step",)),
                             ("serve.tick", PIECES)):
        total = sum(e - s for s, e in _events(pd, parent))
        nested = sum(e - s for kind in children for s, e in
                     _events(pd, kind))
        assert red[parent]["total_s"] == pytest.approx(total * 1e-9)
        assert red[parent]["self_s"] == pytest.approx((total - nested)
                                                      * 1e-9)
        assert 0 < red[parent]["self_s"] < red[parent]["total_s"]
    for kind in ("newton.step",) + PIECES:
        assert red[kind]["self_s"] == pytest.approx(red[kind]["total_s"])


def test_idle_is_the_span_less_the_device_busy_inside_it(pd, red):
    (lo, hi, _), = tr.host_spans(pd, (tr.WINDOW,))
    ops = next(iter(tr.op_intervals(pd, **CPU).values()))
    for kind in ("newton.outer", "newton.step", "serve.tick") + PIECES:
        want = sum((e - s) - _covered_ns(ops, s, e)
                   for s, e in _events(pd, kind))
        assert red[kind]["idle_s"] == pytest.approx(want * 1e-9, abs=1e-12)
        assert 0 <= red[kind]["idle_s"] <= red[kind]["total_s"]
    # the step ran operations on the device, so it was not idle throughout
    assert red["newton.step"]["idle_s"] < red["newton.step"]["total_s"]


def test_a_trace_without_program_spans_reduces_to_nothing():
    pd = tr.load(os.path.join(DATA, "cpu_loop.xplane.pb"))
    red = spans.reduce(pd, **CPU)
    assert red == {}
    for name in ("tick_pack_ms", "step_idle_ms", "outer_host_ms"):
        assert harness.load_metric(name).read(dict(spans=red)) is None


def test_reduce_refuses_a_trace_without_device_operations(pd):
    with pytest.raises(ValueError, match="no operations"):
        spans.reduce(pd)                   # no TPU planes in a CPU trace


def test_busy_inside_by_hand():
    busy = spans._Busy([(0, 2), (5, 6), (9, 10)])
    assert busy.inside(-3, 20) == 4
    assert busy.inside(1, 5.5) == 1.5
    assert busy.inside(2, 5) == 0
    assert busy.inside(9.5, 9.75) == 0.25


# ---------------------------------------------------------------------------
# readers, on synthetic records
# ---------------------------------------------------------------------------

SPAN_READERS = {
    "tick_pack_ms": ("serve.pack", "total_s"),
    "tick_copy_in_ms": ("serve.copy_in", "total_s"),
    "tick_kernel_ms": ("serve.kernel", "total_s"),
    "outer_host_ms": ("newton.outer", "self_s"),
    "step_idle_ms": ("newton.step", "idle_s"),
}


@pytest.mark.parametrize("name", sorted(SPAN_READERS))
def test_span_readers_read_their_span_and_nothing_else(name):
    kind, field = SPAN_READERS[name]
    read = harness.load_metric(name).read
    span = dict(count=4, total_s=0.8, self_s=0.2, idle_s=0.1)
    assert read({}) is None
    assert read(dict(spans={})) is None
    assert read(dict(spans={"other.kind": span})) is None
    assert read(dict(spans={kind: dict(span, count=0)})) is None
    assert read(dict(spans={kind: span})) == pytest.approx(
        1e3 * span[field] / 4)


def test_queue_wait_reader():
    read = harness.load_metric("queue_wait_ms.open").read
    assert read({}) is None
    assert read(dict(counters={})) is None
    assert read(dict(counters={"serve.scored": 10})) is None
    assert read(dict(counters={"serve.queue_wait_s": 1.0})) is None
    assert read(dict(counters={"serve.queue_wait_s": 0.5,
                               "serve.scored": 10})) == pytest.approx(50.0)


# ---------------------------------------------------------------------------
# the split of one window, on the CPU at a tiny size
# ---------------------------------------------------------------------------

TINY = {
    "ctr.score.open": dict(d=4096, request_pool=300),
    "epsilon.solve": dict(d=48, n=3000),
}
TINY_MIX = {"ctr.score.open": dict(rate=150)}


def _system(name):
    _, config, mix, _, _ = harness.load_cell(name)
    config = dict(config, **TINY[name])
    mix = dict(mix, **TINY_MIX.get(name, {}))
    return harness.SYSTEMS[config["system"]](config, SEED), mix


def test_split_reads_the_tick_pieces_and_queue_wait():
    system, mix = _system("ctr.score.open")
    off = split.measure(system, mix, 1.0, SEED, "off", **CPU)
    line = split.measure(system, mix, 1.0, SEED, "obs", **CPU)
    assert off["spans"] == {} and "tick_pack_ms" not in off
    assert off["tick_ms"] > 0 and "score_p90_ms" in off
    for name in ("tick_pack_ms", "tick_copy_in_ms", "tick_kernel_ms",
                 "queue_wait_ms", "tick_ms", "device_idle",
                 "batch_fill"):
        assert line[name] >= 0, name
    pieces = sum(line["spans"][k]["total"] for k in PIECES)
    # the pieces are the whole of ScoringEngine.score's time
    assert pieces == pytest.approx(line["tick_ms"], rel=0.05)
    assert line["spans"]["serve.tick"]["count"] \
        >= line["spans"]["serve.pack"]["count"] > 0


def test_split_reads_the_outer_step():
    system, mix = _system("epsilon.solve")
    line = split.measure(system, mix, 0.5, SEED, "obs", **CPU)
    for name in ("outer_host_ms", "step_idle_ms", "newton_iter_ms"):
        assert line[name] >= 0, name
    outer = line["spans"]["newton.outer"]
    step = line["spans"]["newton.step"]
    assert outer["count"] == step["count"] > 0
    assert step["total"] == pytest.approx(line["newton_iter_ms"], rel=0.05)
    assert outer["self"] == pytest.approx(outer["total"] - step["total"])


def test_split_refuses_an_unknown_mode():
    with pytest.raises(ValueError, match="mode"):
        split.measure(None, {}, 1.0, SEED, "fast")
