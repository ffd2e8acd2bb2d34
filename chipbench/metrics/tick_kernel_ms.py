"""Milliseconds per scored batch in the scoring kernel.

Layer: the scoring engine's kernel (the jitted ``ell_matvec``,
glm_serve/scoring.py). The mean ``serve.kernel`` span of the window,
dispatch to ``block_until_ready`` (chipbench/spans.py). The reader of
``tick_kernel_ms.<suffix>`` for every scoring cell family.
"""
from chipbench import spans

LAYER = "scoring engine: kernel"
SOURCE = "program_span"
UNIT = "ms"


def read(rec):
    return spans.mean_ms(rec, "serve.kernel")
