"""Milliseconds per Newton outer iteration in the window's solves.

Layer: the outer step, ``DiscoSolver.fit`` (core/disco.py). Each
iteration's ``iter_s`` is the solver's own host-clock span around
``block_until_ready`` of the jitted step; the mean is over every outer
iteration of every solve in the window.
"""
LAYER = "outer step"
SOURCE = "program_span"
UNIT = "ms"


def read(rec):
    its = [h["iter_s"] for hist in rec.get("histories", ()) for h in hist]
    return 1e3 * sum(its) / len(its) if its else None
