"""The trace reduction on a recorded CPU trace, and the byte counts.

``data/cpu_loop.xplane.pb`` was written by ``record_trace.py``: a tiny
jitted loop inside a ``window`` span, with an 80 ms host sleep outside
any span and a 40 ms sleep inside a ``tick`` span. On the CPU the
operations sit on the host plane's ``tf_XLAPjRtCpuClient`` lines.
"""
import os

import numpy as np
import pytest

from chipbench import roofline, trace as tr

DATA = os.path.join(os.path.dirname(__file__), "data", "cpu_loop.xplane.pb")
CPU = dict(plane_prefix="/host:CPU", line_prefix="tf_XLAPjRtCpuClient")


@pytest.fixture(scope="module")
def pd():
    return tr.load(DATA)


def _covered_ns(intervals, lo, hi):
    """Union length by elementary segments: a segment counts when any
    interval covers its midpoint."""
    pts = np.unique(np.clip([t for s, e, _ in intervals for t in (s, e)]
                            + [lo, hi], lo, hi))
    mids = 0.5 * (pts[:-1] + pts[1:])
    cov = np.zeros(len(mids), bool)
    for s, e, _ in intervals:
        cov |= (mids >= s) & (mids < e)
    return float(np.sum(np.diff(pts)[cov]))


def test_union_merges_overlaps_and_clips():
    ivs = [(0, 2, "a"), (1, 3, "b"), (5, 6, "c"), (5.5, 5.7, "d"),
           (9, 12, "e")]
    assert tr.union(ivs, 0, 10) == [(0, 3), (5, 6), (9, 10)]
    assert tr.union(ivs, 2, 5.6) == [(2, 3), (5, 5.6)]


def test_self_times_subtract_nested_operations():
    ivs = [(0, 10, "while"), (1, 3, "fusion.1"), (4, 6, "fusion.2"),
           (4.5, 5, "copy"), (12, 13, "fusion.1")]
    got = dict()
    for name, t in tr.self_times(ivs):
        got[name] = got.get(name, 0) + t
    assert got == {"while": 6, "fusion.1": 3, "fusion.2": 1.5, "copy": 0.5}
    assert tr.short_name("%while.1 = (s32[]) while(%t), body=%b") \
        == "while.1"
    assert tr.short_name("dot_general.2") == "dot_general.2"


def test_busy_is_the_union_of_operation_intervals(pd):
    red = tr.reduce(pd, **CPU)
    (lo, hi, _), = tr.host_spans(pd, (tr.WINDOW,))
    ops = tr.op_intervals(pd, **CPU)
    assert len(ops) == 1
    evs = next(iter(ops.values()))
    want = _covered_ns(evs, lo, hi) * 1e-9
    assert red["busy_s"] == pytest.approx(want, rel=1e-9)
    assert red["window_s"] == pytest.approx((hi - lo) * 1e-9)
    # overlapping events: the plain sum over-counts, the union does not
    assert red["busy_s"] < sum(min(e, hi) - max(s, lo)
                               for s, e, _ in evs) * 1e-9
    assert 0 < red["busy_s"] < red["window_s"]


def test_idle_gaps_are_named_by_their_enclosing_span(pd):
    red = tr.reduce(pd, **CPU)
    (name0, gap0), (name1, gap1) = red["idle_gaps"][:2]
    assert (name0, name1) == ("host", "tick")
    assert 0.080 <= gap0 < 0.2 and 0.040 <= gap1 < 0.080
    gaps = [g for _, g in red["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True) and len(gaps) <= tr.TOP
    # the gaps and the busy time fill the window
    (lo, hi, _), = tr.host_spans(pd, (tr.WINDOW,))
    merged = tr.union(next(iter(tr.op_intervals(pd, **CPU).values())),
                      lo, hi)
    all_gaps = (hi - lo) - sum(e - s for s, e in merged)
    assert red["window_s"] - red["busy_s"] == pytest.approx(
        all_gaps * 1e-9)


def test_top_ops_sum_device_self_time_by_name(pd):
    red = tr.reduce(pd, **CPU)
    (lo, hi, _), = tr.host_spans(pd, (tr.WINDOW,))
    evs = next(iter(tr.op_intervals(pd, **CPU).values()))
    inside = [(max(s, lo), min(e, hi), n) for s, e, n in evs if e > lo
              and s < hi]
    by_name = {}
    for name, t in tr.self_times(inside):
        by_name[name] = by_name.get(name, 0) + t
    # self times add up to the summed lengths less the nested ones
    assert sum(by_name.values()) <= sum(e - s for s, e, _ in inside)
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:tr.TOP]
    assert [n for n, _ in red["device_ops"]] == [n for n, _ in top]
    for (_, got), (_, want) in zip(red["device_ops"], top):
        assert got == pytest.approx(want * 1e-9)
    assert red["device_ops"][0][0].startswith("dot_general")


def test_reduce_refuses_a_trace_without_device_operations(pd):
    with pytest.raises(ValueError, match="no operations"):
        tr.reduce(pd)                      # no TPU planes in a CPU trace


def test_pass_bytes_by_hand():
    # epsilon: 2,000 x 400,000 f32 values
    assert roofline.pass_bytes("dense", 2_000, 400_000) == 3_200_000_000
    # real-sim: 3,709,452 nonzeros, a 4-byte value and a 4-byte index each
    assert roofline.pass_bytes("sparse", 20_958, 72_309,
                               nnz=3_709_452) == 29_675_616
    with pytest.raises(ValueError):
        roofline.pass_bytes("sparse", 10, 10)


def test_solve_passes_and_roofline_share_by_hand():
    hist = [[dict(pcg_iters=10), dict(pcg_iters=5)], [dict(pcg_iters=7)]]
    assert roofline.solve_passes(hist) == (10 + 1) + (5 + 1) + (7 + 1)
    assert roofline.solve_passes(hist, block_s=4) == 41 + 21 + 29
    # 24 passes of 819 MB at 819 GB/s take 24 ms; busy 48 ms -> 50%
    assert roofline.hbm_roofline_pct(24, 819_000_000, 0.048,
                                     819e9) == pytest.approx(50.0)


def test_peaks_know_the_v5e_and_refuse_others():
    p = roofline.peaks("TPU v5 lite")
    assert p["hbm_byte_per_s"] == 819e9 and p["bf16_flop_per_s"] == 197e12
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
