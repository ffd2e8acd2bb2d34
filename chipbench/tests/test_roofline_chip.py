"""The per-chip HBM roofline share of a solve on a mesh of chips."""
import json

import jax
import pytest

from chipbench import harness

REC = dict(histories=[[dict(pcg_iters=10), dict(pcg_iters=5)]],
           pcg_block_s=1, pass_bytes=4 * 819_000_000,
           trace=dict(busy_s=0.1, window_s=0.12),
           peaks=dict(hbm_byte_per_s=819e9))


@pytest.mark.parametrize("chips", [1, 4])
def test_each_chip_reads_its_share_of_a_pass(monkeypatch, chips):
    whole = harness.load_metric("hvp_hbm_roofline").read(REC)
    monkeypatch.setattr(jax, "device_count", lambda: chips)
    share = harness.load_metric("hvp_hbm_roofline_chip").read(REC)
    # 17 passes of 3.276 GB: 68 ms of one chip's HBM time, over 100 ms busy
    assert whole == pytest.approx(68.0)
    assert share == pytest.approx(whole / chips)


def test_without_a_trace_it_reads_nothing():
    reader = harness.load_metric("hvp_hbm_roofline_chip")
    assert reader.read({k: v for k, v in REC.items() if k != "trace"}) \
        is None
    assert reader.read(dict(REC, trace=dict(busy_s=0.0, window_s=1.0))) \
        is None


def test_only_the_four_chip_cell_reads_the_per_chip_share():
    with open(harness.BENCHMARK) as f:
        spec = json.load(f)
    layer = {m["name"]: m for m in spec["per_layer"]}
    assert "ocr.solve.4chip" not in layer["hvp_hbm_roofline"]["workloads"]
    assert layer["hvp_hbm_roofline_chip"]["workloads"] == ["ocr.solve.4chip"]
    cells = {c["name"]: c for c in spec["workloads"]}
    assert cells["ocr.solve.4chip"]["chips"] == 4
