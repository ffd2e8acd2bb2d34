"""Record ``data/cpu_spans.xplane.pb``, the trace that test_spans.py reads.

    JAX_PLATFORMS=cpu REPRO_KERNEL_MODE=ref PYTHONPATH=src \\
        python chipbench/tests/record_spans.py

The program with ``repro.obs`` on, on the CPU: one scheduler tick before
the ``window`` span, then inside it a small dense solve of three outer
iterations and four scheduler ticks (three that score, then one on an
empty queue), with a 30 ms host sleep between the solve and the ticks.
"""
import os
import shutil
import sys
import tempfile
import time

import jax
import numpy as np

sys.path[:0] = [os.path.join(os.path.dirname(__file__), "..", ".."),
                os.path.join(os.path.dirname(__file__), "..", "..", "src")]
from chipbench import trace as tr  # noqa: E402
from repro import obs  # noqa: E402
from repro.core import DiscoConfig, DiscoSolver  # noqa: E402
from repro.glm_serve import (MicroBatchScheduler, ScoreRequest,  # noqa: E402
                             ScoringEngine)

OUT = os.path.join(os.path.dirname(__file__), "data", "cpu_spans.xplane.pb")
OUTER_ITERS = 3
SCORED_TICKS = 3


def main():
    rng = np.random.default_rng(0)
    X = rng.standard_normal((16, 64)).astype(np.float32)
    y = np.sign(rng.standard_normal(64)).astype(np.float32)
    solver = DiscoSolver(X, y, DiscoConfig(
        partition="samples", loss="logistic", lam=1e-2, tau=8,
        max_outer=OUTER_ITERS, grad_tol=0.0))
    solver.fit()
    eng = ScoringEngine(rng.standard_normal(64).astype(np.float32),
                        loss="logistic", batch=4, block_b=2, block_d=8)
    sched = MicroBatchScheduler(eng)
    req = ScoreRequest(np.array([1, 9, 40]),
                       np.array([1.0, -0.5, 2.0], np.float32))

    def ticks(n):
        for _ in range(4 * n):
            sched.submit(req)
        for _ in range(n):
            sched.tick()

    ticks(1)                                  # compiles
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        obs.enable(reset=True)
        ticks(1)                              # outside the window
        with jax.profiler.TraceAnnotation(tr.WINDOW):
            solver.fit()
            time.sleep(0.03)
            ticks(SCORED_TICKS)
            sched.tick()                      # an empty queue
        obs.disable()
        jax.profiler.stop_trace()
        shutil.copy(tr.find_xplane(d), OUT)


if __name__ == "__main__":
    main()
