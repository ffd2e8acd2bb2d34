"""GLM serving subsystem (repro.glm_serve): registry round-trips,
request packing vs the NumPy oracle, micro-batch scheduling, warm-start
refits; plus the GLMProblem inference API parity tests."""
import dataclasses
import os

import jax
import numpy as np
import pytest

from repro.core import DiscoConfig, DiscoSolver, GLMProblem, disco_fit
from repro.core.comm import CommLedger
from repro.core.disco import DiscoResult
from repro.data.sparse import CSRMatrix, make_sparse_glm_data
from repro.data.store import ShardStore
from repro.glm_serve import (MicroBatchScheduler, ModelRegistry,
                             RequestPacker, ScoreRequest, ScoringEngine,
                             oracle_margins, RefitLoop)
from repro.glm_serve.scoring import slot_margins


@pytest.fixture()
def ref_mode(monkeypatch):
    # the refit's solver kernels in interpret-mode python emulation are
    # needlessly slow for these shapes
    monkeypatch.setenv("REPRO_KERNEL_MODE", "ref")


def _sparse_problem(d=48, n=160, seed=0):
    return make_sparse_glm_data(d=d, n=n, density=0.15, alpha=1.0,
                                beta=0.5, seed=seed)


def _requests_from_cols(Xd, cols):
    return [ScoreRequest.from_dense(Xd[:, j]) for j in cols]


# ---------------------------------------------------------------------------
# GLMProblem inference API (satellite): dense vs sparse parity
# ---------------------------------------------------------------------------

class TestGLMPredict:
    def _fit(self, loss="logistic"):
        X, y, _ = _sparse_problem()
        Xd = X.todense()
        yy = y if loss != "quadratic" else Xd.T @ np.ones(Xd.shape[0])
        prob = GLMProblem.create(Xd, yy, loss=loss, lam=1e-2)
        w = np.linalg.lstsq(Xd.T, yy, rcond=None)[0].astype(np.float32)
        return prob, X, Xd, w

    def test_decision_function_dense_sparse_parity(self):
        prob, X, Xd, w = self._fit()
        a_dense = prob.decision_function(w)            # training X
        a_dense2 = prob.decision_function(w, Xd)       # explicit dense
        a_sparse = prob.decision_function(w, X)        # CSR stays sparse
        np.testing.assert_allclose(a_dense, a_dense2, rtol=1e-6,
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(a_dense), a_sparse,
                                   rtol=1e-5, atol=1e-5)

    def test_predict_signs_and_proba(self):
        prob, X, Xd, w = self._fit()
        a = prob.decision_function(w, X)
        pred = prob.predict(w, X)
        assert set(np.unique(pred)).issubset({-1.0, 1.0})
        np.testing.assert_array_equal(pred, np.where(a >= 0, 1.0, -1.0))
        p = prob.predict_proba(w, X)
        assert np.all((p >= 0) & (p <= 1))
        np.testing.assert_allclose(
            p, 1.0 / (1.0 + np.exp(-a.astype(np.float64))), rtol=1e-5,
            atol=1e-6)
        # proba agrees with predict through the 0.5 threshold
        np.testing.assert_array_equal(np.where(p >= 0.5, 1.0, -1.0), pred)

    def test_quadratic_predicts_margin_and_proba_raises(self):
        prob, X, Xd, w = self._fit(loss="quadratic")
        np.testing.assert_allclose(prob.predict(w, X),
                                   prob.decision_function(w, X))
        with pytest.raises(ValueError, match="logistic"):
            prob.predict_proba(w, X)

    def test_csr_xt_dot_matches_dense(self, rng):
        Xd = np.where(rng.random((13, 9)) < 0.4,
                      rng.standard_normal((13, 9)), 0.0).astype(np.float32)
        X = CSRMatrix.from_dense(Xd)
        w = rng.standard_normal(13).astype(np.float32)
        np.testing.assert_allclose(X.xt_dot(w), Xd.T @ w, rtol=1e-5,
                                   atol=1e-6)


# ---------------------------------------------------------------------------
# model registry
# ---------------------------------------------------------------------------

def _fake_result(d=16, seed=0):
    rng = np.random.default_rng(seed)
    return DiscoResult(
        w=rng.standard_normal(d).astype(np.float32),
        history=[dict(grad_norm=0.5, f=1.0, pcg_iters=3.0, delta=0.1,
                      pcg_r_norm=1e-3, outer_iter=0, comm_rounds_cum=8,
                      comm_floats_cum=128.0)],
        ledger=CommLedger(rounds=8, floats=128, spmd_collectives=4),
        converged=True,
        partition_info=dict(strategy="lpt", m=2, imbalance=1.25),
        stream_stats=None)


class TestRegistry:
    def test_publish_load_roundtrip_exact(self, tmp_path):
        reg = ModelRegistry(str(tmp_path / "reg"))
        cfg = DiscoConfig(partition="samples", lam=3e-3, pcg_block_s=2)
        res = _fake_result()
        v = reg.publish(res, cfg)
        assert v == 1 and reg.active_version() == 1
        pub = reg.load()
        # w must round-trip bit for bit
        assert pub.w.tobytes() == res.w.tobytes()
        assert pub.w.dtype == res.w.dtype
        assert pub.cfg == cfg
        assert pub.result.converged == res.converged
        assert pub.result.history == res.history
        assert dataclasses.asdict(pub.result.ledger) \
            == dataclasses.asdict(res.ledger)
        assert pub.result.partition_info == res.partition_info
        assert pub.result.stream_stats is None

    def test_versions_monotone_and_activate(self, tmp_path):
        reg = ModelRegistry(str(tmp_path / "reg"))
        cfg = DiscoConfig()
        v1 = reg.publish(_fake_result(seed=1), cfg)
        v2 = reg.publish(_fake_result(seed=2), cfg)
        v3 = reg.publish(_fake_result(seed=3), cfg, activate=False)
        assert (v1, v2, v3) == (1, 2, 3)
        assert reg.versions() == [1, 2, 3]
        assert reg.active_version() == 2       # v3 published, not active
        reg.activate(3)
        assert reg.active_version() == 3
        # every version stays loadable and distinct
        assert not np.array_equal(reg.load(1).w, reg.load(3).w)
        with pytest.raises(ValueError, match="no published version"):
            reg.activate(99)

    def test_load_empty_registry_raises(self, tmp_path):
        reg = ModelRegistry(str(tmp_path / "reg"))
        assert reg.active_version() is None
        with pytest.raises(ValueError, match="no active version"):
            reg.load()

    def test_format_version_check(self, tmp_path):
        import json
        reg = ModelRegistry(str(tmp_path / "reg"))
        reg.publish(_fake_result(), DiscoConfig())
        mpath = os.path.join(str(tmp_path / "reg"), "versions",
                             "v000001", "model.json")
        with open(mpath) as f:
            header = json.load(f)
        header["format_version"] = 999
        with open(mpath, "w") as f:
            json.dump(header, f)
        with pytest.raises(ValueError, match="format"):
            reg.load(1)

    def test_no_stale_staging_dirs(self, tmp_path):
        reg = ModelRegistry(str(tmp_path / "reg"))
        reg.publish(_fake_result(), DiscoConfig())
        names = os.listdir(os.path.join(str(tmp_path / "reg"), "versions"))
        assert all(not n.startswith(".tmp") for n in names)


# ---------------------------------------------------------------------------
# request packer vs the NumPy oracle
# ---------------------------------------------------------------------------

def _packed_margins(packer, requests, w):
    """Margins of one pack through the engine's jitted step."""
    ids, vals = packer.pack(requests)
    y = jax.jit(slot_margins)(ids, vals, packer.pad_weights(w))
    return np.asarray(y)[: len(requests)]


class TestPacker:
    def test_shapes_static_across_packs(self):
        """One shape per k: empty, one-request, empty-feature and full
        batches of the same k pack alike, and the shape follows only the
        longest request."""
        p = RequestPacker(d=40, batch=6)
        one = ScoreRequest(np.array([0]), np.array([1.0]))
        empty = ScoreRequest(np.array([], np.int64),
                             np.array([], np.float32))
        shapes = {}
        batches = [
            [],                                           # empty batch
            [one],
            [empty] * 6,                                  # empty features
            [one] * 6,                                    # full batch
            [ScoreRequest(np.arange(3), np.ones(3, np.float32))] * 3,
            [ScoreRequest(np.arange(40), np.ones(40, np.float32)), one],
        ]
        for reqs in batches:
            ids, vals = p.pack(reqs)
            assert ids.dtype == np.int32 and vals.dtype == np.float32
            assert ids.shape == vals.shape
            shapes.setdefault(p.slots(reqs), set()).add(ids.shape)
        assert shapes == {1: {(6, 1)}, 4: {(6, 4)}, 64: {(6, 64)}}

    def test_all_padding_tiles_score_zero(self):
        p = RequestPacker(d=32, batch=4)
        w = np.linspace(1, 2, 32).astype(np.float32)
        out = _packed_margins(p, [], w)
        assert out.shape == (0,)
        empty = [ScoreRequest(np.array([], np.int64),
                              np.array([], np.float32))] * 3
        ids, vals = p.pack(empty)
        assert np.all(ids == 32) and np.all(vals == 0)
        np.testing.assert_array_equal(_packed_margins(p, empty, w),
                                      np.zeros(3, np.float32))

    def test_single_request_batch(self):
        p = RequestPacker(d=20, batch=8)
        w = np.arange(20, dtype=np.float32)
        r = ScoreRequest(np.array([3, 17]), np.array([2.0, -1.0],
                                                     np.float32))
        np.testing.assert_allclose(_packed_margins(p, [r], w),
                                   oracle_margins([r], w), rtol=1e-6)

    def test_rejects_bad_requests(self):
        p = RequestPacker(d=16, batch=2)
        with pytest.raises(ValueError, match="outside"):
            p.pack([ScoreRequest(np.array([16]), np.array([1.0]))])
        with pytest.raises(ValueError, match="outside"):
            p.pack([ScoreRequest(np.array([-1]), np.array([1.0]))])
        with pytest.raises(ValueError, match="batch size"):
            p.pack([ScoreRequest(np.array([0]), np.array([1.0]))] * 3)
        with pytest.raises(ValueError, match="d > 0"):
            RequestPacker(d=0, batch=2)
        # a sparse vector names each feature once
        with pytest.raises(ValueError, match="duplicate"):
            p.pack([ScoreRequest(np.array([3, 3]),
                                 np.array([1.0, 2.0], np.float32))])
        with pytest.raises(ValueError, match="values"):
            p.pack([ScoreRequest(np.array([1, 2]),
                                 np.array([1.0], np.float32))])

    def test_narrow_width_overflow_raises(self):
        """A request longer than the last pack's k does not overflow: its
        pack widens to the next power of two, and every pack still
        matches the oracle."""
        rng = np.random.default_rng(5)
        w = rng.standard_normal(16).astype(np.float32)
        eng = ScoringEngine(w, loss="logistic", batch=2)
        narrow = ScoreRequest(np.array([0, 15]), np.ones(2, np.float32))
        wide = ScoreRequest(np.arange(1, 12),
                            rng.standard_normal(11).astype(np.float32))
        assert eng.packer.pack([narrow])[0].shape == (2, 2)
        assert eng.packer.pack([narrow, wide])[0].shape == (2, 16)
        for reqs in ([narrow], [narrow, wide], [narrow]):
            np.testing.assert_allclose(eng.score(reqs),
                                       oracle_margins(reqs, w),
                                       rtol=1e-5, atol=1e-6)

    def test_property_packer_matches_oracle(self):
        """Property test: packed-slot scoring == NumPy oracle across
        request sparsity (incl. empty-feature requests), batch fill
        levels (single request, exactly-full), slot widths, and
        duplicate-free random feature subsets."""
        pytest.importorskip("hypothesis")
        from hypothesis import given, settings, strategies as st

        @settings(max_examples=40, deadline=None)
        @given(
            d=st.integers(1, 40),
            batch=st.integers(1, 9),
            n_reqs=st.integers(0, 9),
            density=st.floats(0.0, 1.0),   # 0.0 -> empty-feature requests
            seed=st.integers(0, 2 ** 16),
        )
        def check(d, batch, n_reqs, density, seed):
            n_reqs = min(n_reqs, batch)
            rng = np.random.default_rng(seed)
            reqs = []
            for _ in range(n_reqs):
                k = rng.binomial(d, density)
                idx = rng.choice(d, size=k, replace=False)
                reqs.append(ScoreRequest(
                    indices=idx.astype(np.int64),
                    values=rng.standard_normal(k).astype(np.float32)))
            w = rng.standard_normal(d).astype(np.float32)
            p = RequestPacker(d=d, batch=batch)
            got = _packed_margins(p, reqs, w)
            np.testing.assert_allclose(got, oracle_margins(reqs, w),
                                       rtol=1e-4, atol=1e-5)

        check()

    def test_padding_slots_add_exactly_zero(self):
        """Padding slots read the zero kept after the weights, never
        ``w[0]``: an infinite or huge ``w[0]`` leaves every margin
        exact."""
        d = 24
        rng = np.random.default_rng(2)
        for w0 in (np.inf, np.finfo(np.float32).max):
            w = rng.standard_normal(d).astype(np.float32)
            w[0] = w0
            p = RequestPacker(d=d, batch=4)
            reqs = [ScoreRequest(np.array([3, 9, 20]),
                                 np.array([1.0, -2.0, 0.5], np.float32)),
                    ScoreRequest(np.array([5]), np.array([4.0],
                                                         np.float32))]
            ids, vals = p.pack(reqs)
            assert ids.shape == (4, 4) and np.all(ids[2:] == d)
            y = np.asarray(jax.jit(slot_margins)(ids, vals,
                                                 p.pad_weights(w)))
            assert np.all(np.isfinite(y))
            np.testing.assert_array_equal(y[2:], np.zeros(2, np.float32))
            np.testing.assert_allclose(y[:2], oracle_margins(reqs, w),
                                       rtol=1e-6)


# ---------------------------------------------------------------------------
# scoring engine
# ---------------------------------------------------------------------------

class TestScoringEngine:
    def test_parity_and_chunking(self):
        X, y, _ = _sparse_problem()
        Xd = X.todense()
        rng = np.random.default_rng(1)
        w = rng.standard_normal(X.shape[0]).astype(np.float32)
        eng = ScoringEngine(w, loss="logistic", batch=8)
        reqs = _requests_from_cols(Xd, range(19))   # 2 full packs + tail
        np.testing.assert_allclose(eng.score(reqs),
                                   oracle_margins(reqs, w), rtol=1e-4,
                                   atol=1e-5)
        pred = eng.predict(reqs)
        assert set(np.unique(pred)).issubset({-1.0, 1.0})
        p = eng.predict_proba(reqs)
        assert np.all((p >= 0) & (p <= 1))

    def test_raw_weights_require_loss(self):
        with pytest.raises(ValueError, match="loss"):
            ScoringEngine(np.ones(4, np.float32))

    def test_registry_hot_swap(self, tmp_path):
        reg = ModelRegistry(str(tmp_path / "reg"))
        cfg = DiscoConfig(loss="logistic")
        res1 = _fake_result(d=24, seed=1)
        reg.publish(res1, cfg)
        eng = ScoringEngine(reg, batch=4)
        assert eng.version == 1
        r = ScoreRequest(np.array([0, 5]), np.array([1.0, 2.0],
                                                    np.float32))
        m1 = eng.score([r])[0]
        assert not eng.maybe_reload()           # nothing new
        res2 = _fake_result(d=24, seed=2)
        reg.publish(res2, cfg)
        assert eng.maybe_reload()               # picks up v2
        assert eng.version == 2 and eng.reloads == 1
        m2 = eng.score([r])[0]
        assert m1 != m2
        np.testing.assert_allclose(m2, oracle_margins([r], res2.w)[0],
                                   rtol=1e-5)

    def test_bf16_values_keep_f32_weights_and_margins(self):
        """``hvp_dtype='bfloat16'`` stores the packed values at bf16 only:
        the device weights and the margins stay f32, and the margins are
        the f32 dots of the bf16-rounded values."""
        import ml_dtypes
        rng = np.random.default_rng(4)
        w = rng.standard_normal(32).astype(np.float32)
        eng = ScoringEngine(w, loss="logistic", batch=4,
                            hvp_dtype="bfloat16")
        reqs = [ScoreRequest(np.array([1, 7, 30]),
                             rng.standard_normal(3).astype(np.float32)),
                ScoreRequest(np.array([4]), np.array([1 / 3], np.float32))]
        ids, vals = eng.packer.pack(reqs)
        assert vals.dtype == ml_dtypes.bfloat16 and ids.dtype == np.int32
        assert eng._w_dev.dtype == np.float32
        got = eng.score(reqs)
        assert got.dtype == np.float32
        rounded = [ScoreRequest(r.indices, np.asarray(
            np.asarray(r.values, ml_dtypes.bfloat16), np.float32))
            for r in reqs]
        np.testing.assert_allclose(got, oracle_margins(rounded, w),
                                   rtol=1e-6)
        assert np.max(np.abs(got - oracle_margins(reqs, w))) > 0

    def test_pack_bytes_counts_the_staged_slots(self):
        """``serve.pack_bytes`` adds ``batch * k * 8`` per f32 pack, and
        each ``serve.pack`` span carries its ``k``."""
        from repro import obs
        w = np.arange(100, dtype=np.float32)
        eng = ScoringEngine(w, loss="logistic", batch=8)
        reqs = [ScoreRequest(np.arange(i, i + 39),
                             np.ones(39, np.float32)) for i in range(8)]
        reqs += [ScoreRequest(np.array([2, 3]), np.ones(2, np.float32))]
        tracer = obs.enable(reset=True)
        try:
            eng.score(reqs)                 # one pack of k=64, one of k=2
            events, counters, _ = tracer.snapshot()
        finally:
            obs.disable()
        assert counters["serve.pack_bytes"] == 8 * 64 * 8 + 8 * 2 * 8
        assert [e.args["k"] for e in events
                if e.kind == "serve.pack"] == [64, 2]


# ---------------------------------------------------------------------------
# micro-batching scheduler
# ---------------------------------------------------------------------------

class TestScheduler:
    def _engine(self, d=24, seed=0, batch=4):
        rng = np.random.default_rng(seed)
        w = rng.standard_normal(d).astype(np.float32)
        return w, ScoringEngine(w, loss="logistic", batch=batch)

    def test_drains_queue_and_matches_oracle(self):
        w, eng = self._engine()
        rng = np.random.default_rng(3)
        reqs = [ScoreRequest.from_dense(
            np.where(rng.random(24) < 0.3, rng.standard_normal(24), 0.0)
            .astype(np.float32)) for _ in range(11)]
        sched = MicroBatchScheduler(eng)
        rids = [sched.submit(r) for r in reqs]
        fin = sched.run_until_done()
        assert sched.stats.completed == 11 and sched.stats.rejected == 0
        assert sched.stats.ticks == 3           # ceil(11 / 4)
        got = np.array([fin[rid].margin for rid in rids], np.float32)
        np.testing.assert_allclose(got, oracle_margins(reqs, w),
                                   rtol=1e-4, atol=1e-5)
        assert len(sched.stats.latencies_s) == 11
        assert sched.stats.p50_s <= sched.stats.p99_s
        assert sched.stats.throughput_rps(1.0) == 11

    def test_deadline_rejection(self):
        _, eng = self._engine()
        t = [0.0]
        sched = MicroBatchScheduler(eng, clock=lambda: t[0])
        r = ScoreRequest(np.array([0]), np.array([1.0], np.float32))
        rid_ok = sched.submit(r, deadline_s=10.0)
        rid_late = sched.submit(r, deadline_s=0.5)
        rid_none = sched.submit(r)              # no deadline: never drops
        t[0] = 1.0                              # past rid_late's deadline
        sched.tick()
        assert sched.finished[rid_late].rejected
        assert sched.finished[rid_late].margin is None
        assert not sched.finished[rid_ok].rejected
        assert not sched.finished[rid_none].rejected
        assert sched.stats.rejected == 1 and sched.stats.completed == 2

    def test_malformed_submit_fails_fast_not_the_batch(self):
        """A bad request raises at submit() — it never enters the queue,
        so a later tick cannot lose the innocent requests batched with
        it."""
        w, eng = self._engine(d=8)
        sched = MicroBatchScheduler(eng)
        good = ScoreRequest(np.array([0]), np.array([1.0], np.float32))
        rid = sched.submit(good)
        with pytest.raises(ValueError, match="outside"):
            sched.submit(ScoreRequest(np.array([99]),
                                      np.array([1.0], np.float32)))
        with pytest.raises(ValueError, match="duplicate"):
            sched.submit(ScoreRequest(np.array([1, 1]),
                                      np.array([1.0, 1.0], np.float32)))
        sched.run_until_done()
        assert sched.stats.completed == 1
        assert not sched.finished[rid].rejected
        got = sched.take_finished()
        assert list(got) == [rid] and sched.finished == {}

    def test_hot_swap_between_ticks(self, tmp_path):
        reg = ModelRegistry(str(tmp_path / "reg"))
        cfg = DiscoConfig(loss="logistic")
        reg.publish(_fake_result(d=24, seed=1), cfg)
        eng = ScoringEngine(reg, batch=2)
        sched = MicroBatchScheduler(eng)
        r = ScoreRequest(np.array([1]), np.array([1.0], np.float32))
        a = sched.submit(r)
        sched.tick()
        res2 = _fake_result(d=24, seed=2)
        reg.publish(res2, cfg)                  # refit lands mid-traffic
        b = sched.submit(r)
        sched.tick()                            # swap happens HERE
        assert eng.version == 2
        np.testing.assert_allclose(sched.finished[b].margin,
                                   oracle_margins([r], res2.w)[0],
                                   rtol=1e-5)
        assert sched.finished[a].margin != sched.finished[b].margin


# ---------------------------------------------------------------------------
# warm-start refit loop
# ---------------------------------------------------------------------------

def test_refit_loop_end_to_end(tmp_path, ref_mode):
    """fit -> publish -> ingest -> warm refit: the store grows, the new
    version lands and activates, warm start takes no more Newton
    iterations than cold (the >= 2x claim is the bench_serving gate;
    here we assert the mechanism)."""
    X, y, _ = _sparse_problem(d=32, n=128, seed=4)
    Xd = X.todense()
    n0 = 112
    X0, y0 = CSRMatrix.from_dense(Xd[:, :n0]), y[:n0]
    X1, y1 = CSRMatrix.from_dense(Xd[:, n0:]), y[n0:]
    cfg = DiscoConfig(partition="samples", loss="logistic", lam=1e-3,
                      tau=16, max_outer=20, grad_tol=1e-5,
                      pcg_rel_tol=0.01, ell_block_d=8, ell_block_n=8,
                      partition_block=16, stream_chunk_size=16)
    store = ShardStore.from_csr(X0, y0, str(tmp_path / "s"),
                                axis="samples", chunk_size=16)
    reg = ModelRegistry(str(tmp_path / "reg"))
    res0 = DiscoSolver.from_store(store, cfg).fit()
    reg.publish(res0, cfg)

    loop = RefitLoop(reg, store, cfg)
    assert loop.ingest(X1, y1) == 128
    assert store.shape == (32, 128)
    v_warm, warm = loop.refit(warm=True)
    assert reg.active_version() == v_warm
    assert warm.converged
    v_cold, cold = loop.refit(warm=False)
    assert cold.converged
    assert loop.newton_iters(warm) <= loop.newton_iters(cold)
    # both refits fit the SAME grown dataset: solutions agree
    np.testing.assert_allclose(warm.w, cold.w, atol=1e-4, rtol=1e-3)
    # and match an in-memory fit of the concatenated data
    rm = disco_fit(CSRMatrix.from_dense(Xd), y, cfg)
    np.testing.assert_allclose(warm.w, rm.w, atol=1e-4, rtol=1e-3)


# ---------------------------------------------------------------------------
# ServeStats percentiles: the p50/p99 the serving bench reports must be
# numpy.percentile, including the degenerate cases
# ---------------------------------------------------------------------------

class TestServeStatsPercentiles:
    def test_percentiles_match_numpy_property(self):
        pytest.importorskip("hypothesis")
        from hypothesis import given, settings, strategies as st

        from repro.glm_serve import ServeStats

        @settings(max_examples=60, deadline=None)
        @given(lat=st.lists(st.floats(min_value=0.0, max_value=10.0,
                                      allow_nan=False,
                                      allow_infinity=False),
                            min_size=1, max_size=200),
               q=st.sampled_from([0.0, 50.0, 90.0, 99.0, 100.0]))
        def check(lat, q):
            s = ServeStats()
            s.latencies_s.extend(lat)
            want = float(np.percentile(np.asarray(lat), q))
            assert s.percentile(q) == pytest.approx(want, rel=1e-12)
            assert s.p50_s == pytest.approx(
                float(np.percentile(np.asarray(lat), 50.0)))
            assert s.p99_s == pytest.approx(
                float(np.percentile(np.asarray(lat), 99.0)))

        check()

    def test_single_sample_every_quantile(self):
        from repro.glm_serve import ServeStats
        s = ServeStats()
        s.latencies_s.append(0.25)
        for q in (0.0, 50.0, 99.0, 100.0):
            assert s.percentile(q) == 0.25
        assert s.p50_s == s.p99_s == 0.25

    def test_tied_samples(self):
        from repro.glm_serve import ServeStats
        s = ServeStats()
        s.latencies_s.extend([1.5] * 10)
        for q in (0.0, 50.0, 99.0, 100.0):
            assert s.percentile(q) == 1.5

    def test_empty_is_zero(self):
        from repro.glm_serve import ServeStats
        assert ServeStats().p50_s == 0.0
        assert ServeStats().p99_s == 0.0
        assert ServeStats().percentile(100.0) == 0.0
