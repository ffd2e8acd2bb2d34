"""Sparse substrate: CSR/blocked-ELL containers, ELL kernels vs the dense
reference (the ISSUE 2 fp32-tolerance gate), streaming libsvm reader, and
sparse DiscoSolver equivalence with the dense solver."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import DiscoConfig, disco_fit
from repro.data.libsvm import load_libsvm, save_libsvm
from repro.data.sparse import (CSRMatrix, EllPair, SlotPair, ell_from_csr,
                               ell_pair_from_csr, load_libsvm_sparse,
                               make_sparse_glm_data, stack_shard_ells)
from repro.kernels import ops as kops
from repro.kernels import ref as kref


def _random_sparse(rng, d=37, n=53, density=0.15):
    Xd = (rng.random((d, n)) * (rng.random((d, n)) < density)
          ).astype(np.float32)
    return Xd, CSRMatrix.from_dense(Xd)


# ---------------------------------------------------------------------------
# containers
# ---------------------------------------------------------------------------

def test_csr_roundtrip_and_histograms(rng):
    Xd, X = _random_sparse(rng)
    np.testing.assert_allclose(X.todense(), Xd)
    assert X.nnz == int((Xd != 0).sum())
    np.testing.assert_array_equal(X.nnz_per_row(), (Xd != 0).sum(1))
    np.testing.assert_array_equal(X.nnz_per_col(), (Xd != 0).sum(0))
    np.testing.assert_allclose(X.transpose().todense(), Xd.T)


def test_csr_take_rows_with_padding(rng):
    Xd, X = _random_sparse(rng)
    idx = np.array([5, 2, 40, 0])       # 40 >= d selects an empty pad row
    out = X.take_rows(idx).todense()
    exp = np.zeros((4, Xd.shape[1]), np.float32)
    exp[0], exp[1], exp[3] = Xd[5], Xd[2], Xd[0]
    np.testing.assert_allclose(out, exp)


def test_csr_take_cols_dense(rng):
    Xd, X = _random_sparse(rng)
    np.testing.assert_allclose(X.take_cols_dense(np.arange(7)), Xd[:, :7])


@pytest.mark.parametrize("br,bc", [(8, 16), (16, 8), (64, 64), (5, 7)])
def test_blocked_ell_roundtrip(rng, br, bc):
    Xd, X = _random_sparse(rng)
    ell = ell_from_csr(X, br, bc)
    np.testing.assert_allclose(ell.todense(), Xd)
    fwd, tr = ell_pair_from_csr(X, br, bc)
    np.testing.assert_allclose(tr.todense(), Xd.T)


def test_stack_shard_ells_pads_to_global_width(rng):
    _, X1 = _random_sparse(rng, density=0.4)
    _, X2 = _random_sparse(rng, density=0.02)
    e1, e2 = ell_from_csr(X1, 8, 8), ell_from_csr(X2, 8, 8)
    data, cols = stack_shard_ells([e1, e2])
    assert data.shape[0] == 2 and data.shape[2] == max(e1.width, e2.width)
    assert cols.shape == data.shape[:3]


# ---------------------------------------------------------------------------
# ELL kernels vs dense reference (fp32-tolerance gate)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["ref", "interpret"])
def test_ell_matvec_matches_dense(rng, mode):
    Xd, X = _random_sparse(rng)
    ell = ell_from_csr(X, 8, 16)
    n_p = ell.n_col_blocks * 16
    d_p = ell.n_row_blocks * 8
    Xp = np.zeros((d_p, n_p), np.float32)
    Xp[:37, :53] = Xd
    v = rng.standard_normal(n_p).astype(np.float32)
    c = rng.random(n_p).astype(np.float32)

    y = kops.ell_matvec(jnp.asarray(ell.data), jnp.asarray(ell.cols),
                        jnp.asarray(v), jnp.asarray(c), mode=mode)
    np.testing.assert_allclose(np.asarray(y), Xp @ (c * v),
                               rtol=2e-5, atol=2e-5)
    y2 = kops.ell_matvec(jnp.asarray(ell.data), jnp.asarray(ell.cols),
                         jnp.asarray(v), mode=mode)
    np.testing.assert_allclose(np.asarray(y2), Xp @ v, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("mode", ["ref", "interpret"])
def test_ell_matmat_matches_dense(rng, mode):
    Xd, X = _random_sparse(rng)
    ell = ell_from_csr(X, 8, 16)
    n_p = ell.n_col_blocks * 16
    d_p = ell.n_row_blocks * 8
    Xp = np.zeros((d_p, n_p), np.float32)
    Xp[:37, :53] = Xd
    V = rng.standard_normal((n_p, 5)).astype(np.float32)
    c = rng.random(n_p).astype(np.float32)

    Y = kops.ell_matmat(jnp.asarray(ell.data), jnp.asarray(ell.cols),
                        jnp.asarray(V), jnp.asarray(c), mode=mode)
    np.testing.assert_allclose(np.asarray(Y), Xp @ (c[:, None] * V),
                               rtol=2e-5, atol=2e-5)


def test_ell_sparse_hvp_matches_dense_reference(rng):
    """Full HVP chain H u = X diag(c) X^T u / n + lam u on the ELL pair
    vs the dense jnp oracle — the ISSUE 2 fp32 acceptance check."""
    Xd, X = _random_sparse(rng, d=48, n=80, density=0.2)
    fwd, tr = ell_pair_from_csr(X, 8, 16)
    n_p = fwd.n_col_blocks * 16
    d_p = fwd.n_row_blocks * 8
    u = rng.standard_normal(d_p).astype(np.float32)
    c = rng.random(n_p).astype(np.float32)
    lam = 1e-3
    Xp = np.zeros((d_p, n_p), np.float32)
    Xp[:48, :80] = Xd

    for mode in ("ref", "interpret"):
        z = kops.ell_matvec(jnp.asarray(tr.data), jnp.asarray(tr.cols),
                            jnp.asarray(u), mode=mode)
        hv = kops.ell_matvec(jnp.asarray(fwd.data), jnp.asarray(fwd.cols),
                             z, jnp.asarray(c), mode=mode)
        hv = np.asarray(hv) / 80 + lam * u
        want = np.asarray(kref.ref_glm_hvp(jnp.asarray(Xp), jnp.asarray(c),
                                           jnp.asarray(u), lam,
                                           n_global=80))
        np.testing.assert_allclose(hv, want, rtol=5e-5, atol=5e-5)


# ---------------------------------------------------------------------------
# streaming libsvm reader
# ---------------------------------------------------------------------------

def test_streaming_reader_matches_dense_loader(rng, tmp_path):
    Xd, X = _random_sparse(rng, d=12, n=40)
    y = np.sign(rng.standard_normal(40)).astype(np.float32)
    p = str(tmp_path / "toy.svm")
    save_libsvm(p, Xd, y)
    for chunk in (3, 7, 1000):   # chunk boundaries must not matter
        Xs, ys = load_libsvm_sparse(p, n_features=12, chunk_samples=chunk)
        np.testing.assert_allclose(Xs.todense(), Xd, atol=1e-6)
        np.testing.assert_array_equal(ys, y)


def test_streaming_reader_truncates_explicit_n_features(tmp_path):
    p = str(tmp_path / "trunc.svm")
    with open(p, "w") as f:
        f.write("1 1:1.5 7:2.5\n-1 2:3.5\n")
    Xs, y = load_libsvm_sparse(p, n_features=3)
    assert Xs.shape == (3, 2)
    dense = Xs.todense()
    assert dense[0, 0] == pytest.approx(1.5)
    assert dense[1, 1] == pytest.approx(3.5)
    assert Xs.nnz == 2            # feature 7 dropped
    # and identical semantics to the dense loader
    Xd, yd = load_libsvm(p, n_features=3)
    np.testing.assert_allclose(dense, Xd)
    np.testing.assert_array_equal(y, yd)


# ---------------------------------------------------------------------------
# synthetic power-law generator
# ---------------------------------------------------------------------------

def test_make_sparse_glm_data_shapes_and_skew():
    X, y, w = make_sparse_glm_data(d=256, n=512, density=0.05, alpha=1.2,
                                   beta=0.8, seed=0)
    assert X.shape == (256, 512) and y.shape == (512,) and w.shape == (256,)
    assert set(np.unique(y)) <= {-1.0, 1.0}
    # power-law head: the top 10% of features carry a clear majority of nnz
    per_feat = np.sort(X.nnz_per_row())[::-1]
    head = per_feat[: 26].sum()
    assert head > 0.4 * X.nnz, (head, X.nnz)
    # sample axis is skewed too (beta > 0)
    per_sample = X.nnz_per_col()
    assert per_sample[:51].mean() > 2 * per_sample.mean()


# ---------------------------------------------------------------------------
# end-to-end: sparse solver == dense solver
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("partition", ["features", "samples"])
@pytest.mark.parametrize("strategy", ["width", "lpt"])
def test_sparse_solver_matches_dense(partition, strategy):
    X, y, _ = make_sparse_glm_data(d=96, n=200, density=0.2, alpha=0.8,
                                   beta=0.5, seed=1)
    kw = dict(loss="logistic", lam=1e-3, tau=16, max_outer=4, grad_tol=0.0,
              ell_block_d=16, ell_block_n=16)
    rd = disco_fit(X.todense(), y, DiscoConfig(partition=partition, **kw))
    rs = disco_fit(X, y, DiscoConfig(partition=partition,
                                     partition_strategy=strategy, **kw))
    # interpret-mode kernels accumulate f32 in a different order than the
    # dense path; after 4 Newton iterations the trajectories agree to
    # realistic end-to-end fp32 tolerance
    np.testing.assert_allclose(rs.w, rd.w, rtol=2e-2, atol=1e-2)
    info = rs.partition_info
    assert info is not None and info["strategy"] == strategy
    assert info["imbalance"] >= 1.0


@pytest.mark.parametrize("partition", ["features", "samples"])
def test_sparse_solver_sstep_matches_classic(partition):
    X, y, _ = make_sparse_glm_data(d=96, n=200, density=0.2, alpha=0.8,
                                   beta=0.5, seed=1)
    kw = dict(loss="logistic", lam=1e-3, tau=16, max_outer=4, grad_tol=0.0,
              ell_block_d=16, ell_block_n=16)
    r1 = disco_fit(X, y, DiscoConfig(partition=partition, **kw))
    r4 = disco_fit(X, y, DiscoConfig(partition=partition, pcg_block_s=4,
                                     **kw))
    # both solve each Newton system to the same PCG tolerance; the
    # endpoints agree to end-to-end fp32 tolerance
    np.testing.assert_allclose(r4.w, r1.w, rtol=3e-2, atol=2e-2)


def test_sparse_solver_warm_start_roundtrip():
    """w0 goes in (and w comes out) in original feature order even when
    LPT permutes features internally."""
    X, y, _ = make_sparse_glm_data(d=64, n=150, density=0.25, alpha=1.0,
                                   seed=3)
    cfg = DiscoConfig(loss="logistic", lam=1e-2, tau=16, max_outer=2,
                      grad_tol=0.0, partition="features",
                      partition_strategy="lpt",
                      ell_block_d=8, ell_block_n=8)
    r1 = disco_fit(X, y, cfg)
    r2 = disco_fit(X, y, cfg, w0=r1.w)    # continue from the solution
    # restarting from the solution must not blow up the trajectory
    assert r2.grad_norms[-1] <= 5 * r1.grad_norms[-1] + 1e-6


# ---------------------------------------------------------------------------
# (id, value) slots: layout, products, layout choice, solver
# ---------------------------------------------------------------------------

SLOT_CASES = {"full_row": dict(full_row=True, heavy=True),
              "heavy_only": dict(full_row=False, heavy=True),
              "no_head": dict(full_row=False, heavy=False)}


@pytest.mark.parametrize("case", sorted(SLOT_CASES))
def test_slot_layout_holds_every_nonzero_once(rng, case):
    """Owners sorted, every output owns a chunk, whole blocks of chunks,
    and the slots plus the head slab rebuild the matrix exactly."""
    import jax

    from oracles import slot_csr_case
    from repro.data.sparse import (SLOT_BLOCK, SLOT_WIDTH,
                                   build_shard_slot_pairs)

    X = slot_csr_case(rng, **SLOT_CASES[case])
    shape = (X.shape[0] + 3, X.shape[1] + 5)
    pair = jax.tree_util.tree_map(lambda a: a[0],
                                  build_shard_slot_pairs([X], shape))
    for lay, n_out, transpose in ((pair.fwd, shape[0], False),
                                  (pair.tr, shape[1], True)):
        assert lay.ids.shape[1] == SLOT_WIDTH
        assert lay.ids.shape[0] % SLOT_BLOCK == 0
        assert np.all(np.diff(lay.owner) >= 0)
        assert set(np.unique(lay.owner)) == set(range(n_out))
        got = np.zeros((n_out, shape[1 - transpose]), np.float64)
        np.add.at(got, (np.repeat(lay.owner, SLOT_WIDTH), lay.ids.ravel()),
                  lay.vals.ravel())
        want = np.zeros(shape)
        want[: X.shape[0], : X.shape[1]] = X.todense()
        want[pair.head_rows] = 0.0
        np.testing.assert_array_equal(got, want.T if transpose else want)
    heads = X.nnz_per_row()[pair.head_rows]
    assert len(pair.head_rows) == 0 or heads.min() > 14
    dense = np.zeros(shape)
    dense[: X.shape[0], : X.shape[1]] = X.todense()
    np.testing.assert_array_equal(pair.head, dense[pair.head_rows])
    if case == "no_head":
        assert pair.head.shape == (0, shape[1])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(SLOT_CASES))
def test_slot_products_match_oracle_and_tiles(rng, case, dtype):
    """Both passes, one vector and three, against the f64 oracle of the
    values the slots hold and against the tile two-pass products."""
    from oracles import slot_csr_case, slot_pair_case
    from repro.core.hvp import EllOperator, SlotOperator
    from repro.data.sparse import EllPair

    X = slot_csr_case(rng, **SLOT_CASES[case])
    shape = (X.shape[0] + 3, X.shape[1] + 5)
    jdt = jnp.bfloat16 if dtype == "bfloat16" else None
    pair, Xp = slot_pair_case(X, shape, jdt)
    c = jnp.asarray(rng.random(shape[1]), jnp.float32)
    u = jnp.asarray(rng.standard_normal(shape[0]), jnp.float32)
    U = jnp.asarray(rng.standard_normal((shape[0], 3)), jnp.float32)
    op = SlotOperator(pair, c)
    Xd = Xp.astype(np.float64)
    z, Z = op.pass_a(u), op.pass_a_multi(U)
    y, Y = op.pass_b(z), op.pass_b_multi(Z)
    for got, want in ((z, Xd.T @ np.asarray(u, np.float64)),
                      (Z, Xd.T @ np.asarray(U, np.float64))):
        assert got.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                                   atol=1e-5 * np.abs(want).max())
    cz = np.asarray(c, np.float64)[:, None] * np.asarray(Z, np.float64)
    np.testing.assert_allclose(
        np.asarray(Y), Xd @ cz, rtol=1e-5, atol=1e-5 * np.abs(Xd @ cz).max())
    want = Xd @ (np.asarray(c, np.float64) * np.asarray(z, np.float64))
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-5,
                               atol=1e-5 * np.abs(want).max())
    # the tile two-pass products of the same matrix (8 x 8 tiles; tiles
    # in bf16 round the probe vector too, so bf16 compares loosely)
    fwd, tr = ell_pair_from_csr(X, 8, 8)
    ell = EllPair(*(jnp.asarray(a, jdt) if a.dtype == np.float32 and jdt
                    else jnp.asarray(a)
                    for a in (fwd.data, fwd.cols, tr.data, tr.cols)))
    rp, cp = fwd.n_row_blocks * 8, tr.n_row_blocks * 8
    pad = lambda v, k: jnp.pad(v[: min(len(v), k)],
                               (0, max(k - len(v), 0)))
    tiles = EllOperator(ell, pad(c, cp))
    zt = tiles.pass_a(pad(u, rp))
    yt = tiles.pass_b(pad(z, cp))
    tol = 1e-5 if dtype == "float32" else 2e-2
    d, n = X.shape
    np.testing.assert_allclose(np.asarray(z)[:n], np.asarray(zt)[:n],
                               rtol=tol, atol=tol * np.abs(zt).max())
    np.testing.assert_allclose(np.asarray(y)[:d], np.asarray(yt)[:d],
                               rtol=tol, atol=tol * np.abs(yt).max())


@pytest.mark.parametrize("m", [1, 2])
@pytest.mark.parametrize("partition", ["features", "samples"])
def test_slot_shards_match_their_dense_blocks(rng, partition, m):
    """Stacked shards (one or two, either partition) each hold exactly
    their own block of the permuted matrix."""
    import jax

    from oracles import slot_csr_case
    from repro.core.hvp import SlotOperator
    from repro.data.partition import make_partition
    from repro.data.sparse import (build_shard_slot_pairs,
                                   shard_csrs_from_partition)

    X = slot_csr_case(rng, d=64, n=4000)
    part = make_partition(X, partition, m, "lpt", pad_multiple=8)
    shards = shard_csrs_from_partition(X, part, partition)
    shape = shards[0].shape
    pairs = build_shard_slot_pairs(shards, shape)
    for s in range(m):
        op = SlotOperator(jax.tree_util.tree_map(
            lambda a: jnp.asarray(a[s]), pairs), None)
        Xd = shards[s].todense().astype(np.float64)
        u = rng.standard_normal(shape[0]).astype(np.float32)
        z = rng.standard_normal(shape[1]).astype(np.float32)
        np.testing.assert_allclose(np.asarray(op.pass_a(jnp.asarray(u))),
                                   Xd.T @ u, rtol=1e-5, atol=1e-4)
        np.testing.assert_allclose(np.asarray(op.pass_b(jnp.asarray(z))),
                                   Xd @ z, rtol=1e-5, atol=1e-4)


def _block_dense(rng, blocks=3, b=128):
    """Dense 128 x 128 blocks on the diagonal: tiles at full fill."""
    Xd = np.zeros((blocks * b, blocks * b), np.float32)
    for k in range(blocks):
        Xd[k * b:(k + 1) * b, k * b:(k + 1) * b] = rng.standard_normal(
            (b, b))
    return CSRMatrix.from_dense(Xd)


@pytest.mark.parametrize("data", ["realsim_like", "block_dense"])
def test_layout_choice_follows_tile_fill(rng, data):
    """Slots where tiles are mostly padding (Zipf text at 0.4% density
    under 128 x 128 tiles), tiles where they are full."""
    from repro.data.sparse import choose_hvp_layout

    if data == "realsim_like":
        X, _, _ = make_sparse_glm_data(1500, 2500, density=0.004,
                                       alpha=1.0, seed=3)
        want = "slots"
    else:
        X, want = _block_dense(rng), "ell"
    choice = choose_hvp_layout([X], 128, 128)
    assert choice.layout == want, choice
    if want == "slots":
        assert choice.tile_bytes > 5 * choice.slot_bytes


@pytest.mark.parametrize("partition", ["features", "samples"])
def test_slot_solver_reaches_grad_target(partition):
    """A sparse solve through slots reaches grad_rel <= 1e-5 in float64,
    and its counter records the slot bytes it placed."""
    from oracles import logistic_grad_oracle
    from repro import obs
    from repro.core import DiscoSolver

    X, y, _ = make_sparse_glm_data(1500, 2500, density=0.004, alpha=1.0,
                                   seed=3)
    lam = 1e-3
    Xd = X.todense().astype(np.float64)
    g0 = np.linalg.norm(logistic_grad_oracle(Xd, y, np.zeros(X.shape[0]),
                                             lam))
    tracer = obs.enable(reset=True)
    try:
        solver = DiscoSolver(X, y, DiscoConfig(
            partition=partition, loss="logistic", lam=lam, tau=16,
            max_outer=20, grad_tol=1e-7 * g0))
        _, counters, _ = tracer.snapshot()
    finally:
        obs.disable()
    assert solver.layout.layout == "slots"
    assert isinstance(solver.X, SlotPair)
    assert counters["disco.hvp_slot_bytes"] == solver.X.nbytes > 0
    res = solver.fit()
    g = logistic_grad_oracle(Xd, y, res.w.astype(np.float64), lam)
    assert res.converged
    assert np.linalg.norm(g) / g0 <= 1e-5


def test_tile_solver_counts_no_slot_bytes(rng):
    from repro import obs
    from repro.core import DiscoSolver

    X = _block_dense(rng, blocks=2)
    y = np.where(rng.random(X.shape[1]) < 0.5, -1.0, 1.0).astype(np.float32)
    tracer = obs.enable(reset=True)
    try:
        solver = DiscoSolver(X, y, DiscoConfig(partition="features",
                                               tau=16))
        _, counters, _ = tracer.snapshot()
    finally:
        obs.disable()
    assert solver.layout.layout == "ell"
    assert counters["disco.hvp_slot_bytes"] == 0


@pytest.mark.parametrize("layout", ["array", "ell", "slots"])
def test_with_hvp_dtype(rng, layout):
    """The HVP twin of each layout: values cast to the HVP dtype, index
    arrays shared with the original, and the original itself back when
    its values already have that dtype."""
    import jax

    from oracles import slot_csr_case
    from repro.data.sparse import (build_shard_ell_pairs,
                                   build_shard_slot_pairs, hvp_tile_dtype,
                                   with_hvp_dtype)

    X = slot_csr_case(rng)
    host = {"array": lambda: X.todense(),
            "ell": lambda: EllPair(*build_shard_ell_pairs([X], 8, 8)),
            "slots": lambda: build_shard_slot_pairs([X], X.shape)}[layout]()
    dev = jax.tree_util.tree_map(jnp.asarray, host)
    bf16 = hvp_tile_dtype("bfloat16")
    h = with_hvp_dtype(dev, bf16)
    assert type(h) is type(dev)
    if layout == "array":
        values, shared = [(h, dev)], []
    elif layout == "ell":
        values = [(h.data, dev.data), (h.dataT, dev.dataT)]
        shared = [(h.cols, dev.cols), (h.colsT, dev.colsT)]
    else:
        values = [(h.fwd.vals, dev.fwd.vals), (h.tr.vals, dev.tr.vals),
                  (h.head, dev.head)]
        shared = [(h.fwd.ids, dev.fwd.ids), (h.tr.ids, dev.tr.ids),
                  (h.fwd.owner, dev.fwd.owner), (h.tr.owner, dev.tr.owner),
                  (h.head_rows, dev.head_rows)]
    for a, f in values:
        assert a.dtype == bf16 and f.dtype == np.float32
        np.testing.assert_array_equal(np.asarray(a),
                                      np.asarray(f).astype(bf16))
    for a, f in shared:
        assert a is f
    assert with_hvp_dtype(dev, np.dtype(np.float32)) is dev
    assert with_hvp_dtype(h, bf16) is h
