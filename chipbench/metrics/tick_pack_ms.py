"""Milliseconds per scored batch spent packing requests into tiles.

Layer: the scoring engine's pack (``RequestPacker.pack``,
glm_serve/scoring.py). The mean ``serve.pack`` span of the window, from
the program's spans on the profiler's clock (chipbench/spans.py). The
reader of ``tick_pack_ms.<suffix>`` for every scoring cell family.
"""
from chipbench import spans

LAYER = "scoring engine: pack"
SOURCE = "program_span"
UNIT = "ms"


def read(rec):
    return spans.mean_ms(rec, "serve.pack")
