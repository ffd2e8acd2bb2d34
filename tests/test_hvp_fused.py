"""Fused one-pass HVP kernels + mixed-precision tile storage (ISSUE 5).

Three layers of coverage:

* kernel level — fused == two-pass == NumPy oracle across non-square
  blocks, padded ELL widths, s-step multi-vector shapes and both tile
  dtypes (interpret mode: the kernel bodies execute on CPU exactly as
  they would on TPU), plus the out_dtype regression (bf16 tiles must
  NOT round the f32 accumulator) and the VMEM-budget fallback; the
  dense one-pass kernel against a float64 oracle at the dense cells'
  widths, and its dispatch rule;
* solver level — ``hvp_fused=True`` reproduces the two-pass
  ``DiscoSolver`` bit-identically in ref mode, and ``hvp_dtype=
  'bfloat16'`` converges to the f32 optimum;
* 4-device subprocess — the bit-identity holds on a real 4-shard mesh,
  classic and s-step, both partitionings (same idiom as
  tests/test_streaming.py).
"""
import importlib
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from oracles import sparse_case as _sparse_case  # shared NumPy oracles

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


# ---------------------------------------------------------------------------
# kernel level: dense fused
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("d,n", [(40, 70), (130, 257), (1, 5), (257, 33)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dense_fused_matches_twopass_and_oracle(rng, d, n, dtype):
    from repro.kernels import ops as kops

    X = jnp.asarray(rng.standard_normal((d, n)), dtype)
    c = jnp.asarray(rng.random(n), jnp.float32)
    u = jnp.asarray(rng.standard_normal(d), jnp.float32)
    got = kops.x_c_xt_u(X, c, u, block_n=128)
    two = kops.x_cz_local(X, c, kops.xt_u(X, u, block_d=128, block_n=128),
                          block_d=128, block_n=128)
    Xf = np.asarray(X, np.float32)
    want = Xf @ (np.asarray(c) * (Xf.T @ np.asarray(u)))
    assert got.dtype == jnp.float32          # f32 out regardless of tiles
    # the one-pass kernel multiplies the stored values by the f32 vectors
    # in f32, bf16 tiles included, so both dtypes meet the f32 tolerance;
    # the two-pass kernels round u and c*z to the tile dtype for the MXU,
    # so the two paths agree to 1e-6 only in f32
    scale = max(np.abs(want).max(), 1.0)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5 * scale,
                               rtol=1e-5)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(two), want, atol=tol * scale,
                               rtol=tol)
    if dtype == jnp.float32:
        np.testing.assert_allclose(np.asarray(got), np.asarray(two),
                                   atol=1e-6 * scale, rtol=1e-6)


@pytest.mark.parametrize("s", [1, 2, 5])
def test_dense_fused_multi_matches_oracle(rng, s):
    from repro.kernels import ops as kops

    d, n = 96, 150
    X = jnp.asarray(rng.standard_normal((d, n)), jnp.float32)
    c = jnp.asarray(rng.random(n), jnp.float32)
    U = jnp.asarray(rng.standard_normal((d, s)), jnp.float32)
    got = kops.x_c_xt_multi(X, c, U, block_n=128)
    Xf = np.asarray(X)
    want = Xf @ (np.asarray(c)[:, None] * (Xf.T @ np.asarray(U)))
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-4, rtol=1e-4)
    # column k of the batched fused HVP == the single-vector fused HVP
    one = kops.x_c_xt_u(X, c, U[:, 0], block_n=128)
    # Both kernels accumulate the same d*n products in f32, in different
    # orders, so each may be off by about eps32 * sum|terms|: bound the
    # gap by that per element, not by a fixed 1e-5 (values reach ~1e2).
    Xa = np.abs(Xf)
    scale = Xa @ (np.asarray(c) * (Xa.T @ np.abs(np.asarray(U[:, 0]))))
    gap = np.abs(np.asarray(got[:, 0]) - np.asarray(one))
    assert np.all(gap <= np.finfo(np.float32).eps * scale), gap.max()


def test_dense_fused_vmem_fallback(rng, monkeypatch):
    """Past the panel budget the wrapper must fall back to the two-pass
    kernels and still match."""
    from repro.kernels import ops as kops

    dense = importlib.import_module("repro.kernels.glm_hvp")
    monkeypatch.setattr(dense, "ONE_PASS_VMEM_BYTES", 1024)  # force fallback
    d, n = 64, 100
    X = jnp.asarray(rng.standard_normal((d, n)), jnp.float32)
    c = jnp.asarray(rng.random(n), jnp.float32)
    u = jnp.asarray(rng.standard_normal(d), jnp.float32)
    got = kops.x_c_xt_u(X, c, u, block_d=128, block_n=128)
    Xf = np.asarray(X)
    want = Xf @ (np.asarray(c) * (Xf.T @ np.asarray(u)))
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-4, rtol=1e-4)


# the one-pass kernel at the dense cells' widths: (d, dtype, columns past
# the last whole panel), so the last panel is ragged
ONE_PASS_CASES = [
    (2000, jnp.float32, 300),      # epsilon's d
    (1156, jnp.float32, 77),       # ocr's d: not a multiple of 8 rows
    (130, jnp.float32, 5),
    (1156, jnp.bfloat16, 77),      # the bf16 twin: 16-row packed tiles
    (130, jnp.bfloat16, 129),
]


@pytest.mark.parametrize("d,dtype,extra", ONE_PASS_CASES)
def test_dense_one_pass_matches_f64_oracle(rng, d, dtype, extra):
    """The one-pass kernel at its own panel width against a float64
    oracle over the stored values: max |y - y64| / max |y64| <= 1e-6.

    Its products and sums are f32, so the error is a few f32 roundings
    (about 3e-7 here). A kernel that rounded X, u or c*z to bf16, as an
    MXU pass at default precision does, misses by about 1e-3 and fails.
    The coefficients hold zeros (the Hessian-subsampling mask), and the
    interpreter fills the out-of-bounds part of the ragged last panel
    with NaN, so only the in-kernel mask keeps those columns out.
    """
    from jax.experimental.pallas import tpu as pltpu

    dense = importlib.import_module("repro.kernels.glm_hvp")
    bn = dense.one_pass_block(d, 1 << 30, jnp.dtype(dtype).itemsize)
    n = bn + extra
    assert dense.one_pass_block(d, n, jnp.dtype(dtype).itemsize) == bn
    X = jnp.asarray(rng.standard_normal((d, n)), dtype)
    c = rng.random(n) * (rng.random(n) < 0.7)
    u = rng.standard_normal(d)
    nan_oob = pltpu.InterpretParams(out_of_bounds_reads="uninitialized",
                                    uninitialized_memory="nan")
    got = np.asarray(dense.x_c_xt_u(X, jnp.asarray(c, jnp.float32),
                                    jnp.asarray(u, jnp.float32),
                                    interpret=nan_oob))
    X64 = np.asarray(X.astype(jnp.float32), np.float64)
    want = X64 @ (np.asarray(jnp.asarray(c, jnp.float32), np.float64)
                  * (X64.T @ np.asarray(jnp.asarray(u, jnp.float32),
                                        np.float64)))
    assert got.dtype == np.float32 and np.isfinite(got).all()
    assert np.abs(got - want).max() <= 1e-6 * np.abs(want).max()


def test_dense_one_pass_dispatch_rule(rng):
    """The panel plan engages at the dense cells' widths, in f32 and in
    bf16, and not where one lane group of X outgrows the panel budget;
    off a TPU the default dense operator is XLA's two passes, the same
    program bit for bit, with no kernel in it."""
    import jax
    from repro.core.hvp import DenseOperator

    dense = importlib.import_module("repro.kernels.glm_hvp")
    for d, n_loc in ((2000, 400_000), (1156, 875_000)):
        for itemsize in (4, 2):
            bn = dense.one_pass_block(d, n_loc, itemsize)
            assert bn >= 2048 and bn % 1024 == 0, (d, itemsize, bn)
            assert d * bn * itemsize <= dense.ONE_PASS_PANEL_BYTES
            assert dense.one_pass_vmem_bytes(d, bn, itemsize) \
                <= dense.ONE_PASS_VMEM_BYTES
    assert dense.one_pass_block(70_000, 400_000, 4) == 0
    assert 0 < dense.one_pass_block(8000, 400_000, 4) < 2048
    assert dense.one_pass_block(2000, 100, 4) <= 2048    # no wider than n

    X = jnp.asarray(rng.standard_normal((200, 700)), jnp.float32)
    c = jnp.asarray(rng.random(700), jnp.float32)
    u = jnp.asarray(rng.standard_normal(200), jnp.float32)
    op = DenseOperator(X, c)
    text = jax.jit(op.apply).lower(u).as_text()
    assert "dense_hvp_one_pass" not in text and "custom_call" not in text
    two = jax.jit(lambda u: X @ (c * (X.T @ u)))(u)
    assert np.array_equal(np.asarray(jax.jit(op.apply)(u)),
                          np.asarray(two))


# ---------------------------------------------------------------------------
# kernel level: blocked-ELL fused
# ---------------------------------------------------------------------------

ELL_CASES = [
    # d, n, density, br, bc, width_pad
    (24, 40, 0.3, 8, 8, 0),
    (30, 50, 0.25, 3, 5, 2),      # non-square blocks + padded width
    (16, 64, 0.4, 8, 16, 1),
    (40, 24, 0.2, 16, 8, 0),
]


@pytest.mark.parametrize("d,n,density,br,bc,wpad", ELL_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ell_fused_matches_twopass_and_oracle(rng, d, n, density, br, bc,
                                              wpad, dtype):
    from repro.kernels import ops as kops

    data, cols, dataT, colsT, Xp = _sparse_case(rng, d, n, density, br, bc,
                                                wpad)
    data, dataT = data.astype(dtype), dataT.astype(dtype)
    u = jnp.asarray(rng.standard_normal(Xp.shape[0]), jnp.float32)
    c = jnp.asarray(rng.random(Xp.shape[1]), jnp.float32)
    got = kops.ell_hvp(dataT, colsT, u, c, fwd=(data, cols))
    bare = kops.ell_hvp(dataT, colsT, u, c)       # no fwd layout at all
    two = kops.ell_matvec(data, cols, kops.ell_matvec(dataT, colsT, u), c)
    Xf = np.asarray(jnp.asarray(Xp, dtype), np.float32)  # stored rounding
    want = Xf @ (np.asarray(c) * (Xf.T @ np.asarray(u)))
    assert got.dtype == jnp.float32
    scale = max(np.abs(want).max(), 1.0)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got), want, atol=tol * scale,
                               rtol=tol)
    np.testing.assert_allclose(np.asarray(bare), want, atol=tol * scale,
                               rtol=tol)
    np.testing.assert_allclose(np.asarray(got), np.asarray(two),
                               atol=1e-6 * scale, rtol=1e-6)


@pytest.mark.parametrize("s", [1, 2, 3])
def test_ell_fused_multi_matches_oracle(rng, s):
    from repro.kernels import ops as kops

    data, cols, dataT, colsT, Xp = _sparse_case(rng, 32, 48, 0.3, 8, 8, 1)
    U = jnp.asarray(rng.standard_normal((Xp.shape[0], s)), jnp.float32)
    c = jnp.asarray(rng.random(Xp.shape[1]), jnp.float32)
    got = kops.ell_hvp_mm(dataT, colsT, U, c, fwd=(data, cols))
    bare = kops.ell_hvp_mm(dataT, colsT, U, c)
    want = Xp @ (np.asarray(c)[:, None] * (Xp.T @ np.asarray(U)))
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(bare), want, atol=1e-4,
                               rtol=1e-4)
    two = kops.ell_matmat(data, cols, kops.ell_matmat(dataT, colsT, U), c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(two),
                               atol=1e-5, rtol=1e-5)


def test_ell_fused_vmem_fallback(rng, monkeypatch):
    from repro.kernels import ops as kops

    data, cols, dataT, colsT, Xp = _sparse_case(rng, 24, 40, 0.3, 8, 8, 0)
    u = jnp.asarray(rng.standard_normal(Xp.shape[0]), jnp.float32)
    c = jnp.asarray(rng.random(Xp.shape[1]), jnp.float32)
    want = np.asarray(kops.ell_hvp(dataT, colsT, u, c, fwd=(data, cols)))
    monkeypatch.setattr(kops, "_FUSED_VMEM_BYTES", 64)    # force fallback
    with_fwd = kops.ell_hvp(dataT, colsT, u, c, fwd=(data, cols))
    np.testing.assert_allclose(np.asarray(with_fwd), want, atol=1e-5,
                               rtol=1e-5)
    # without the forward layout there is no kernel fallback: it raises
    # instead of leaving the kernels for the jnp reference
    with pytest.raises(ValueError, match="VMEM budget"):
        kops.ell_hvp(dataT, colsT, u, c)
    with pytest.raises(ValueError, match="VMEM budget"):
        kops.ell_hvp_mm(dataT, colsT, u[:, None], c)


# ---------------------------------------------------------------------------
# out_dtype regression: bf16 tiles must not round the f32 accumulator
# ---------------------------------------------------------------------------

def test_out_dtype_default_f32_under_bf16_tiles(rng):
    """The pre-fix kernels ended with .astype(data.dtype): under bf16
    tile storage that silently rounded the f32 accumulator to bf16.
    Default out_dtype must be f32 and match the f32-accumulated oracle
    strictly better than a bf16-rounded output could."""
    from repro.kernels import ops as kops

    data, cols, dataT, colsT, Xp = _sparse_case(rng, 32, 48, 0.5, 8, 8, 0)
    v = jnp.asarray(rng.standard_normal(Xp.shape[1]), jnp.float32)
    data_bf = data.astype(jnp.bfloat16)
    y = kops.ell_matvec(data_bf, cols, v)
    assert y.dtype == jnp.float32
    # f32-accumulation oracle over the bf16-stored operands (the kernel
    # casts the vector to the tile dtype for the MXU): the output must
    # match to f32 accuracy — a bf16-rounded output would miss by
    # ~2^-8 relative
    want = np.asarray(jnp.asarray(Xp, jnp.bfloat16), np.float32) \
        @ np.asarray(jnp.asarray(v, jnp.bfloat16), np.float32)
    err = np.abs(np.asarray(y) - want).max()
    rounded_err = np.abs(
        np.asarray(jnp.asarray(y, jnp.bfloat16), np.float32) - want).max()
    scale = max(np.abs(want).max(), 1e-30)
    assert err / scale < 1e-5
    assert err <= rounded_err    # strictly no worse than the old cast
    # explicit out_dtype still available
    assert kops.ell_matvec(data_bf, cols, v,
                           out_dtype=jnp.bfloat16).dtype == jnp.bfloat16

    Y = kops.ell_matmat(data_bf, cols,
                        jnp.stack([v, v], axis=1))
    assert Y.dtype == jnp.float32

    X = jnp.asarray(rng.standard_normal((40, 60)), jnp.bfloat16)
    u = jnp.asarray(rng.standard_normal(40), jnp.float32)
    assert kops.xt_u(X, u, block_d=128, block_n=128).dtype == jnp.float32


# ---------------------------------------------------------------------------
# hypothesis property sweep (optional dep, mirrors tests/test_kernels.py)
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                   # pragma: no cover
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    @given(d=st.integers(1, 200), n=st.integers(1, 200),
           seed=st.integers(0, 99))
    @settings(max_examples=15, deadline=None)
    def test_dense_fused_property_random_shapes(d, n, seed):
        from repro.kernels import ops as kops

        r = np.random.default_rng(seed)
        X = jnp.asarray(r.standard_normal((d, n)), jnp.float32)
        c = jnp.asarray(r.random(n), jnp.float32)
        u = jnp.asarray(r.standard_normal(d), jnp.float32)
        got = kops.x_c_xt_u(X, c, u, block_n=128)
        Xf = np.asarray(X)
        want = Xf @ (np.asarray(c) * (Xf.T @ np.asarray(u)))
        np.testing.assert_allclose(np.asarray(got), want,
                                   atol=1e-4 * max(np.abs(want).max(), 1),
                                   rtol=1e-4)

    @given(d=st.integers(2, 60), n=st.integers(2, 60),
           br=st.sampled_from([2, 3, 8]), bc=st.sampled_from([2, 5, 8]),
           wpad=st.integers(0, 2), s=st.integers(1, 3),
           seed=st.integers(0, 50))
    @settings(max_examples=15, deadline=None)
    def test_ell_fused_property(d, n, br, bc, wpad, s, seed):
        from repro.kernels import ops as kops

        r = np.random.default_rng(seed)
        data, cols, dataT, colsT, Xp = _sparse_case(r, d, n, 0.3, br, bc,
                                                    wpad)
        c = jnp.asarray(r.random(Xp.shape[1]), jnp.float32)
        U = jnp.asarray(r.standard_normal((Xp.shape[0], s)), jnp.float32)
        got = kops.ell_hvp_mm(dataT, colsT, U, c, fwd=(data, cols))
        want = Xp @ (np.asarray(c)[:, None] * (Xp.T @ np.asarray(U)))
        np.testing.assert_allclose(np.asarray(got), want,
                                   atol=1e-4 * max(np.abs(want).max(), 1),
                                   rtol=1e-4)


# ---------------------------------------------------------------------------
# solver level (1 device, ref mode for exact dispatch parity)
# ---------------------------------------------------------------------------

@pytest.fixture()
def ref_mode(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_MODE", "ref")


def _solver_problem(seed=1):
    from repro.data.sparse import make_sparse_glm_data
    return make_sparse_glm_data(d=96, n=160, density=0.2, alpha=1.0,
                                beta=0.5, seed=seed)


@pytest.mark.parametrize("partition", ["features", "samples"])
def test_solver_fused_bit_identical_1device(ref_mode, partition):
    from repro.core import DiscoConfig, disco_fit

    X, y, _ = _solver_problem()
    kw = dict(partition=partition, loss="logistic", lam=1e-2, tau=16,
              max_outer=8, grad_tol=1e-9, ell_block_d=8, ell_block_n=8,
              partition_block=16)
    for s in (1, 2):
        r0 = disco_fit(X, y, DiscoConfig(pcg_block_s=s, **kw))
        r1 = disco_fit(X, y, DiscoConfig(pcg_block_s=s, hvp_fused=True,
                                         **kw))
        assert np.array_equal(r0.w, r1.w), (partition, s)
        assert len(r0.history) == len(r1.history)


def test_solver_bf16_converges_to_f32_optimum(ref_mode):
    """bf16 curvature + f32 first-order terms: the damped Newton loop
    must land within 1e-4 of the f32 solve (the mixed-precision
    accuracy contract, docs/kernels.md)."""
    from repro.core import DiscoConfig, disco_fit

    X, y, _ = _solver_problem(seed=4)
    kw = dict(loss="logistic", lam=1e-2, tau=16, max_outer=12,
              grad_tol=1e-9, ell_block_d=8, ell_block_n=8,
              partition_block=16)
    for partition in ("features", "samples"):
        r0 = disco_fit(X, y, DiscoConfig(partition=partition, **kw))
        rb = disco_fit(X, y, DiscoConfig(partition=partition,
                                         hvp_fused=True,
                                         hvp_dtype="bfloat16", **kw))
        rel = np.linalg.norm(rb.w - r0.w) / np.linalg.norm(r0.w)
        assert rel <= 1e-4, (partition, rel)


def _bf16_twin_problem(layout):
    """Data on which each in-memory layout is the one the solver takes,
    with the config keys it needs."""
    from repro.data.sparse import make_sparse_glm_data
    from repro.data.synthetic import make_glm_data

    if layout == "dense":
        X, y, _ = make_glm_data(d=60, n=300, seed=0)
        return np.asarray(X), np.asarray(y), {}
    if layout == "ell":                           # 20% dense: tiles win
        X, y, _ = _solver_problem(seed=5)
        return X, y, dict(ell_block_d=8, ell_block_n=8)
    X, y, _ = make_sparse_glm_data(1500, 2500, density=0.004, alpha=1.0,
                                   seed=3)
    return X, y, {}


@pytest.mark.parametrize("partition", ["features", "samples"])
@pytest.mark.parametrize("layout", ["dense", "ell", "slots"])
def test_solver_bf16_twin_actually_engaged(ref_mode, layout, partition):
    """Under ``hvp_dtype='bfloat16'`` every floating leaf of the HVP twin
    ``X_hvp`` is bf16 while ``X``, the first-order plane, stays f32, and
    index arrays are shared; at f32 the twin is ``X`` itself."""
    import jax
    from repro.core import DiscoConfig, DiscoSolver

    X, y, kw = _bf16_twin_problem(layout)
    kw = dict(partition=partition, loss="logistic", lam=1e-2, tau=16, **kw)
    s = DiscoSolver(X, y, DiscoConfig(hvp_dtype="bfloat16", **kw))
    if layout != "dense":
        assert s.layout.layout == layout
    leaves = zip(jax.tree_util.tree_leaves(s.X_hvp),
                 jax.tree_util.tree_leaves(s.X))
    floating = 0
    for h, f in leaves:
        if jnp.issubdtype(f.dtype, jnp.floating):
            assert str(h.dtype) == "bfloat16"
            assert str(f.dtype) == "float32"      # first-order plane f32
            floating += 1
        else:
            assert h is f                         # ids, columns shared
    assert floating > 0
    s32 = DiscoSolver(X, y, DiscoConfig(**kw))
    assert s32.X_hvp is s32.X


def test_hvp_dtype_validation():
    from repro.data.sparse import hvp_tile_dtype

    assert hvp_tile_dtype("float32") == np.float32
    assert hvp_tile_dtype("bfloat16").itemsize == 2
    with pytest.raises(ValueError, match="hvp_dtype"):
        hvp_tile_dtype("float16")


# ---------------------------------------------------------------------------
# streaming: fused + bf16 staging reach the same endpoint, fewer bytes
# ---------------------------------------------------------------------------

def test_streaming_fused_bf16_matches_inmemory(tmp_path, ref_mode):
    import dataclasses

    from repro.core import DiscoConfig, DiscoSolver
    from repro.data.store import ShardStore

    X, y, _ = _solver_problem(seed=6)
    store = ShardStore.from_csr(X, y, str(tmp_path / "s"), axis="samples",
                                chunk_size=16)
    cfg = DiscoConfig(partition="samples", loss="logistic", lam=1e-2,
                      tau=16, max_outer=8, grad_tol=1e-9, ell_block_d=8,
                      ell_block_n=8, partition_block=16,
                      stream_chunk_size=16)
    rm = DiscoSolver(X, y, cfg).fit()
    r_plain = DiscoSolver.from_store(store, cfg).fit()
    # fused f32 streamed PCG: <= 1e-6 rel err of the two-pass streamed
    # solve (chunk accumulation order differs, so not bit-identical)
    r_f32 = DiscoSolver.from_store(
        ShardStore(str(tmp_path / "s")),
        dataclasses.replace(cfg, hvp_fused=True)).fit()
    scale = np.abs(r_plain.w).max()
    np.testing.assert_allclose(r_f32.w, r_plain.w, atol=1e-6 * scale,
                               rtol=1e-6)
    cfg_f = dataclasses.replace(cfg, hvp_fused=True,
                                hvp_dtype="bfloat16")
    r_fused = DiscoSolver.from_store(ShardStore(str(tmp_path / "s")),
                                     cfg_f).fit()
    np.testing.assert_allclose(r_plain.w, rm.w, atol=1e-6, rtol=1e-4)
    np.testing.assert_allclose(r_fused.w, rm.w, atol=1e-3, rtol=1e-3)
    # fused streams ONE layout for HVP passes, bf16 halves its values:
    # the data plane must shrink
    assert r_fused.stream_stats["bytes_loaded"] \
        < 0.75 * r_plain.stream_stats["bytes_loaded"]


# ---------------------------------------------------------------------------
# 4-device subprocess: fused == two-pass bit-identically on a real mesh
# ---------------------------------------------------------------------------

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["REPRO_KERNEL_MODE"] = "ref"
    import numpy as np
    import jax
    assert len(jax.devices()) == 4
    from repro.launch.mesh import make_mesh
    from repro.core import DiscoConfig, DiscoSolver
    from repro.data.sparse import make_sparse_glm_data

    X, y, _ = make_sparse_glm_data(d=128, n=320, density=0.15, alpha=1.0,
                                   beta=0.6, seed=2)
    kw = dict(loss="logistic", lam=1e-2, tau=16, max_outer=6,
              grad_tol=1e-9, ell_block_d=8, ell_block_n=8,
              partition_block=16)

    for partition, axis in (("features", "model"), ("samples", "data")):
        mesh = make_mesh((4,), (axis,))
        for s in (1, 2):
            cfg0 = DiscoConfig(partition=partition, pcg_block_s=s, **kw)
            cfg1 = DiscoConfig(partition=partition, pcg_block_s=s,
                               hvp_fused=True, **kw)
            r0 = DiscoSolver(X, y, cfg0, mesh=mesh).fit()
            r1 = DiscoSolver(X, y, cfg1, mesh=mesh).fit()
            assert len(r0.history) == len(r1.history), (partition, s)
            assert np.array_equal(r0.w, r1.w), (
                partition, s, np.abs(r0.w - r1.w).max())
            rb = DiscoSolver(X, y, DiscoConfig(
                partition=partition, pcg_block_s=s, hvp_fused=True,
                hvp_dtype="bfloat16", **kw), mesh=mesh).fit()
            rel = np.linalg.norm(rb.w - r0.w) / np.linalg.norm(r0.w)
            assert rel <= 1e-4, (partition, s, rel)
            print(partition, "s=", s, "bit-identical, bf16 rel", rel)
    print("HVP_FUSED_MULTIDEVICE_PASS")
""")


@pytest.mark.slow
def test_fused_disco_4device_bit_identical():
    """On a real 4-shard mesh, hvp_fused=True reproduces the two-pass
    solver bit-identically (ref mode) for classic + s-step PCG under
    both partitionings, and the bf16 mixed-precision solve stays within
    1e-4 of the f32 endpoint."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=540)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "HVP_FUSED_MULTIDEVICE_PASS" in r.stdout
