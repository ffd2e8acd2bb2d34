"""``correct`` at small sizes on the CPU: sound runs pass, the control
and the planted faults fail.

Each run skips the harness's look for a chip and drives the rest of a
run (set-up, window, check) through ``harness.run``, at the limits in
the configuration files. The controls (``control.py``) are the
program's own bfloat16 path and the plain reference computed in
bfloat16 in the program's place. The faults break the
timed path underneath: a solve that returns its state unchanged, half
of the samples or requests left out, an answer altered where it is
produced. Every cell is on one chip, so there is no exchange between
chips to leave out.
"""
import time

import numpy as np
import pytest

from chipbench import control, harness

SEED = 2**33 + 5
TINY = {
    "epsilon.solve": dict(d=48, n=3000),
    "realsim.solve": dict(d=512, n=2048, nnz_per_sample=12, k_min=4,
                          k_max=32),
    "ctr.score.open": dict(d=4096, request_pool=300),
    "ctr.score.closed": dict(d=4096, request_pool=300),
}
# an open loop that the CPU's interpreted kernels keep up with
TINY_MIX = {"ctr.score.open": dict(rate=150)}


def cell(name):
    _, config, mix, e2e, layer = harness.load_cell(name)
    return (dict(config, **TINY[name]), dict(mix, **TINY_MIX.get(name, {})),
            e2e, layer)


def run(name, seed=SEED):
    config, mix, e2e, layer = cell(name)
    return harness.run(config, mix, e2e, layer, seed=seed, seconds=1.0,
                       trace=False, t_start=time.perf_counter(),
                       peaks=None, log=lambda s: None)


@pytest.mark.parametrize("name", list(TINY))
def test_sound_run_is_correct(name):
    res = run(name)
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "compared"


@pytest.mark.parametrize("name", list(TINY))
def test_reference_control_is_not_correct(name):
    config, *_ = cell(name)
    compared = control.CONTROLS[config["system"]](config, SEED)["compared"]
    assert not harness.is_correct(compared)
    # the numeric comparison alone separates it
    number = "grad_rel" if config["system"] == "solver" else "margin_err"
    value, limit = compared[number]
    assert value > limit


@pytest.mark.parametrize("name", list(TINY))
def test_program_lower_precision_path_is_not_correct(name):
    config, mix, e2e, layer = cell(name)
    lower = dict(config,
                 program_options=control.PROGRAM_CONTROL[config["system"]])
    res = harness.run(lower, mix, e2e, layer, seed=SEED, seconds=1.0,
                      trace=False, t_start=time.perf_counter(), peaks=None,
                      log=lambda s: None)
    assert not res["correct"], res["compared"]


# -- faults in the solver -----------------------------------------------------

def _unchanged_state(monkeypatch):
    from repro.core import DiscoSolver
    fit = DiscoSolver.fit

    def stale(self, *a, **k):
        res = fit(self, *a, **k)
        res.w = np.zeros_like(res.w)
        return res
    monkeypatch.setattr(DiscoSolver, "fit", stale)


def _half_the_samples(monkeypatch):
    from repro.core import DiscoSolver
    from repro.data.sparse import CSRMatrix
    init = DiscoSolver.__init__

    def half(self, X, y, cfg, mesh=None):
        n = len(y) // 2
        if isinstance(X, CSRMatrix):
            keep = X.transpose().take_rows(np.arange(n)).transpose()
        else:
            keep = X[:, :n]
        init(self, keep, y[:n], cfg, mesh)
    monkeypatch.setattr(DiscoSolver, "__init__", half)


@pytest.mark.parametrize("fault", [_unchanged_state, _half_the_samples])
@pytest.mark.parametrize("name", ["epsilon.solve", "realsim.solve"])
def test_solver_fault_is_not_correct(monkeypatch, name, fault):
    fault(monkeypatch)
    assert not run(name)["correct"]


# -- faults in scoring --------------------------------------------------------

def _half_the_batch(monkeypatch):
    from repro.glm_serve import ScoringEngine
    score = ScoringEngine.score

    def half(self, requests):
        out = score(self, requests)
        out[len(out) // 2:] = 0.0
        return out
    monkeypatch.setattr(ScoringEngine, "score", half)


def _altered_answer(monkeypatch):
    from repro.glm_serve import ScoringEngine
    score = ScoringEngine.score

    def altered(self, requests):
        out = score(self, requests)
        out[0] += 1e-3 * (1.0 + abs(out[0]))
        return out
    monkeypatch.setattr(ScoringEngine, "score", altered)


def _dropped_request(monkeypatch):
    from repro.glm_serve import MicroBatchScheduler
    tick = MicroBatchScheduler._tick

    def drop(self):
        if len(self.waiting) > 1:
            self.waiting.pop()              # never answered
        return tick(self)
    monkeypatch.setattr(MicroBatchScheduler, "_tick", drop)


@pytest.mark.parametrize("fault", [_half_the_batch, _altered_answer,
                                   _dropped_request])
@pytest.mark.parametrize("name", ["ctr.score.open", "ctr.score.closed"])
def test_scoring_fault_is_not_correct(monkeypatch, name, fault):
    fault(monkeypatch)
    assert not run(name)["correct"]
