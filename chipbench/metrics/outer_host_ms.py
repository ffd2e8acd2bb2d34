"""Milliseconds of host work per Newton outer iteration, outside the step.

Layer: the outer step's host side (``DiscoSolver.fit``, core/disco.py):
the mean self time of ``newton.outer`` (the loop body less its
``newton.step``): fault hook, key split, stats, ledger, checkpoint and
convergence test (chipbench/spans.py).
"""
from chipbench import spans

LAYER = "outer step: host"
SOURCE = "program_span"
UNIT = "ms"


def read(rec):
    return spans.mean_ms(rec, "newton.outer", "self_s")
