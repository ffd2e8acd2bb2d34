"""Streaming (out-of-core) DiSCO vs the in-memory solver: identical
partition plan, matching Newton trajectory, bounded data-plane memory.

The 4-device variant runs in a subprocess (device count must be forced
before jax initializes), same idiom as tests/test_multidevice.py.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.fixture()
def ref_mode(monkeypatch):
    # the streaming path applies kernels eagerly per chunk; interpret-mode
    # python emulation is needlessly slow for these shapes
    monkeypatch.setenv("REPRO_KERNEL_MODE", "ref")


def _problem(seed=1):
    from repro.data.sparse import make_sparse_glm_data
    return make_sparse_glm_data(d=96, n=160, density=0.2, alpha=1.0,
                                beta=0.5, seed=seed)


@pytest.mark.parametrize("partition", ["features", "samples"])
def test_streaming_matches_inmemory_1device(tmp_path, ref_mode, partition):
    """Converged streaming solve == converged in-memory solve (same
    chunk-granular partition) to tight tolerance, with the prefetch
    ledger bounded by chunk x depth, not dataset size."""
    from repro.core import DiscoConfig, DiscoSolver
    from repro.data.store import ShardStore

    X, y, _ = _problem()
    store = ShardStore.from_csr(X, y, str(tmp_path / "s"), axis=partition,
                                chunk_size=16)
    cfg = DiscoConfig(partition=partition, loss="logistic", lam=1e-2,
                      tau=16, max_outer=15, grad_tol=2e-8, ell_block_d=8,
                      ell_block_n=8, partition_block=16,
                      stream_chunk_size=16)
    rs = DiscoSolver.from_store(store, cfg).fit()
    rm = DiscoSolver(X, y, cfg).fit()
    assert rs.converged and rm.converged
    np.testing.assert_allclose(rs.w, rm.w, atol=1e-6, rtol=1e-4)
    assert rs.partition_info == rm.partition_info
    st = rs.stream_stats
    assert st is not None and st["passes"] > 0
    # data-plane residency: chunk-sized payloads, never the whole stream
    assert st["peak_bytes"] <= (cfg.prefetch_depth + 2) \
        * st["max_step_bytes"]
    assert st["peak_bytes"] < st["bytes_loaded"] / 4


def test_streaming_sstep_and_subsample_1device(tmp_path, ref_mode):
    """s-step rounds + Hessian subsampling through the streamed path
    reach the in-memory endpoint (same per-shard subsample draws)."""
    from repro.core import DiscoConfig, DiscoSolver
    from repro.data.store import ShardStore

    X, y, _ = _problem(seed=3)
    store = ShardStore.from_csr(X, y, str(tmp_path / "s"), axis="samples",
                                chunk_size=16)
    cfg = DiscoConfig(partition="samples", loss="logistic", lam=1e-2,
                      tau=16, max_outer=8, grad_tol=1e-9, ell_block_d=8,
                      ell_block_n=8, partition_block=16, pcg_block_s=2,
                      hessian_subsample=0.5, seed=7)
    rs = DiscoSolver.from_store(store, cfg).fit()
    rm = DiscoSolver(X, y, cfg).fit()
    np.testing.assert_allclose(rs.w, rm.w, atol=1e-5, rtol=1e-3)
    its_s = [int(h["pcg_iters"]) for h in rs.history]
    its_m = [int(h["pcg_iters"]) for h in rm.history]
    assert len(its_s) == len(its_m)
    assert all(abs(a - b) <= 1 for a, b in zip(its_s, its_m))


def test_disco_fit_streaming_wrapper(tmp_path, ref_mode):
    from repro.core import DiscoConfig, disco_fit, disco_fit_streaming

    X, y, _ = _problem(seed=5)
    cfg = DiscoConfig(partition="features", loss="logistic", lam=1e-2,
                      tau=16, max_outer=8, grad_tol=1e-9, ell_block_d=8,
                      ell_block_n=8, partition_block=16,
                      stream_chunk_size=16)
    rs = disco_fit_streaming(X, y, str(tmp_path / "s"), cfg)
    rm = disco_fit(X, y, cfg)
    np.testing.assert_allclose(rs.w, rm.w, atol=1e-6, rtol=1e-4)


def test_from_store_axis_mismatch(tmp_path, ref_mode):
    from repro.core import DiscoConfig, DiscoSolver
    from repro.data.store import ShardStore

    X, y, _ = _problem(seed=6)
    store = ShardStore.from_csr(X, y, str(tmp_path / "s"), axis="samples",
                                chunk_size=16)
    with pytest.raises(ValueError, match="chunked along"):
        DiscoSolver.from_store(store, DiscoConfig(partition="features"))


# ---------------------------------------------------------------------------
# 4-device subprocess test (the ISSUE 3 satellite gate)
# ---------------------------------------------------------------------------

SCRIPT = textwrap.dedent("""
    import os, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["REPRO_KERNEL_MODE"] = "ref"
    import numpy as np
    import jax
    assert len(jax.devices()) == 4
    from repro.launch.mesh import make_mesh
    from repro.core import DiscoConfig, DiscoSolver
    from repro.data.sparse import make_sparse_glm_data
    from repro.data.store import ShardStore

    X, y, _ = make_sparse_glm_data(d=128, n=320, density=0.15, alpha=1.0,
                                   beta=0.6, seed=2)
    kw = dict(loss="logistic", lam=1e-2, tau=16, max_outer=8,
              grad_tol=1e-9, ell_block_d=8, ell_block_n=8,
              partition_block=16)

    for partition, axis in (("features", "model"), ("samples", "data")):
        mesh = make_mesh((4,), (axis,))
        for s in (1, 2):
            cfg = DiscoConfig(partition=partition, pcg_block_s=s, **kw)
            with tempfile.TemporaryDirectory() as td:
                store = ShardStore.from_csr(X, y, td + "/s",
                                            axis=partition, chunk_size=16)
                rs = DiscoSolver.from_store(store, cfg, mesh=mesh).fit()
            rm = DiscoSolver(X, y, cfg, mesh=mesh).fit()
            # same chunk-granular plan -> identical partition stats
            assert rs.partition_info == rm.partition_info, partition
            # same trajectory: equal outer count, per-outer PCG counts
            # equal up to eps-boundary FP noise, same endpoint
            assert len(rs.history) == len(rm.history), (partition, s)
            its_s = [int(h["pcg_iters"]) for h in rs.history]
            its_m = [int(h["pcg_iters"]) for h in rm.history]
            assert all(abs(a - b) <= 1 for a, b in zip(its_s, its_m)), (
                partition, s, its_s, its_m)
            np.testing.assert_allclose(rs.w, rm.w, atol=1e-6, rtol=1e-4)
            print(partition, "s=", s, "OK", its_s, its_m)
    print("STREAMING_MULTIDEVICE_PASS")
""")


@pytest.mark.slow
def test_streaming_disco_4device_matches_inmemory():
    """Streaming DiSCO on a real 4-shard mesh reproduces the in-memory
    solver — w_final, iteration counts, partition_info — for both
    partitions, classic and s-step PCG."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=540)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "STREAMING_MULTIDEVICE_PASS" in r.stdout
