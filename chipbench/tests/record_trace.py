"""Record ``data/cpu_loop.xplane.pb``, the trace that test_trace.py reads.

    JAX_PLATFORMS=cpu python chipbench/tests/record_trace.py

A tiny jitted loop on the CPU inside a ``window`` span: three ``solve``
spans, a 40 ms host sleep inside a ``tick`` span between two calls, and
an 80 ms sleep outside any span.
"""
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))
from chipbench import trace as tr  # noqa: E402

OUT = os.path.join(os.path.dirname(__file__), "data", "cpu_loop.xplane.pb")


def main():
    step = jax.jit(lambda x: jnp.tanh(x @ x.T) @ x)
    x = jnp.ones((384, 384), jnp.float32) / 384
    step(x).block_until_ready()
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        with jax.profiler.TraceAnnotation(tr.WINDOW):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("solve"):
                    x = step(x).block_until_ready()
            with jax.profiler.TraceAnnotation("tick"):
                x = step(x).block_until_ready()
                time.sleep(0.04)
                x = step(x).block_until_ready()
            time.sleep(0.08)
            with jax.profiler.TraceAnnotation("solve"):
                x = step(x).block_until_ready()
        jax.profiler.stop_trace()
        shutil.copy(tr.find_xplane(d), OUT)


if __name__ == "__main__":
    main()
