"""chip_smoke.py on the CPU: every phase at tiny sizes with interpret-mode
kernels (conftest), and the script's refusal to run without a chip."""
import importlib.util
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture(scope="module")
def cs():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    module = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = module     # dataclasses look it up there
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def tiny(cs):
    return cs.Sizes(dense_d=48, dense_n=640, dense_outer=6, sparse_d=256,
                    sparse_n=512, sparse_density=0.05, sparse_outer=8,
                    stream_d=64, stream_n=512, stream_density=0.1,
                    stream_chunk=128, stream_outer=12, requests=24, batch=8,
                    tile=16)


def test_dense_phase_tiny(cs, tiny):
    rec, w = cs.phase_dense(0, tiny)
    assert rec["w_rel_err_vs_jnp"] <= cs.W_REL_TOL
    assert rec["grad_abs_err"] <= rec["grad_err_bound"]
    assert rec["steady_compiles"] == 0
    assert w.shape == (tiny.dense_d,)


def test_sparse_phase_tiny(cs, tiny):
    rec = cs.phase_sparse(0, tiny)
    assert rec["w_rel_err_vs_dense"] <= cs.SPARSE_REL_TOL
    assert rec["layout"] in ("slots", "ell")
    assert rec["hvp_layout_bytes"] > 0


def test_stream_and_score_phases_tiny(cs, tiny, tmp_path):
    data = cs.stream_problem(0, tiny)
    rec, res = cs.phase_stream(0, tiny, str(tmp_path), data=data)
    assert rec["outer_iters"] >= 2 and rec["passes"] > 0
    assert rec["w_rel_err_vs_inmemory"] <= cs.SPARSE_REL_TOL
    score = cs.phase_score(tiny, data[0], res, cs.stream_config(0, tiny),
                           str(tmp_path))
    assert score["margin_rel_err"] <= cs.SCORE_REL_TOL


FOUR = """
import sys, tempfile
sys.path.insert(0, sys.argv[1])
import chip_smoke as cs
tiny = cs.Sizes(dense_d=48, dense_n=640, sparse_d=256, sparse_n=512,
                stream_d=64, stream_n=512, stream_density=0.1,
                stream_chunk=128, requests=24, batch=8, tile=16)
with tempfile.TemporaryDirectory() as wd:
    cs.run_four_chips(0, tiny, wd)
"""


def test_four_chip_path_on_four_cpu_devices():
    """``--chips 4``'s comparisons on a forced 4-device CPU host."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", REPRO_KERNEL_MODE="interpret",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", FOUR, ROOT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    runs = [ln for ln in proc.stdout.splitlines() if "four_vs_one" in ln]
    assert len(runs) == 3
    assert '"shard_devices": [0, 1, 2, 3]' in proc.stdout


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_script_refuses_to_run_without_a_chip(tmp_path, where):
    script = os.path.join(ROOT, "chip_smoke.py")
    if where == "alone":   # no repro package next to the script
        script = shutil.copy(script, tmp_path / "chip_smoke.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, str(script)], env=env,
                          cwd=tmp_path, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    if where == "checkout":
        assert "'cpu'" in proc.stderr
