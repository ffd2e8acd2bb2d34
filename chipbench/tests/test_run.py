"""The entry point's refusals, and the layout it finds by name."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from chipbench import harness

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
ARGS = ["--workload", "epsilon.solve", "--seed", str(2**33 + 1),
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chipbench/run.py", *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_without_a_tpu_it_exits_nonzero_naming_the_platform():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "needs a TPU" in p.stderr and "'cpu'" in p.stderr


def test_without_the_program_it_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench")
    p = _run(tmp_path)
    assert p.returncode != 0 and p.stdout == ""
    assert "src/repro" in p.stderr


def test_every_cell_finds_its_files_by_name():
    with open(harness.BENCHMARK) as f:
        spec = json.load(f)
    layers = {m["name"]: m for m in spec["per_layer"]}
    for cell in spec["workloads"]:
        _, config, mix, e2e, layer = harness.load_cell(cell["name"])
        assert config["system"] in harness.SYSTEMS
        assert {m["name"] for m in e2e} >= {"setup_s"} and len(e2e) >= 2
        assert layer
        for m in layer:
            reader = harness.load_metric(m["name"])
            assert (reader.LAYER, reader.SOURCE, reader.UNIT) == (
                m["layer"], m["source"], m["unit"])
            assert m["moves"] in {e["name"] for e in e2e}
    for m in layers.values():
        family = m["name"].split(".")[0]
        assert {m["name"] + ".py", family + ".py"} & set(
            os.listdir(os.path.join(ROOT, "chipbench", "metrics")))


def test_a_metric_family_shares_one_reader():
    a = harness.load_metric("device_idle.solve")
    b = harness.load_metric("device_idle.closed")
    assert a.__file__ == b.__file__
    assert a.read(dict(trace=dict(busy_s=3.0, window_s=4.0))) == 25.0
    assert a.read(dict()) is None


def test_an_unknown_workload_is_refused():
    with pytest.raises(SystemExit, match="unknown workload"):
        harness.load_cell("no.such.cell")


def test_arrivals_offer_the_same_gaps_in_another_order():
    from chipbench import drive
    a = drive.arrivals(400, 10, seed=1)
    b = drive.arrivals(400, 10, seed=2)
    assert len(a) == len(b) == 4000 and a[0] == b[0] == 0
    assert np.all(np.diff(a) > 0)
    # both draw their gaps from the exponential's 4,000 quantiles
    gaps = -np.log1p(-(np.arange(4000) + 0.5) / 4000) / 400
    for t in (a, b):
        got = np.sort(np.diff(t))
        i = np.clip(np.searchsorted(gaps, got), 1, len(gaps) - 1)
        near = np.minimum(abs(gaps[i] - got), abs(gaps[i - 1] - got))
        assert near.max() < 1e-9
    assert not np.array_equal(a, b)
    assert abs(a[-1] - 10) < 0.1                   # the mean rate holds

