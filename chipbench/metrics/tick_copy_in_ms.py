"""Milliseconds per scored batch copying its tiles to the device.

Layer: the scoring engine's host-to-device copy (glm_serve/scoring.py).
The mean ``serve.copy_in`` span of the window, which ends when both
arrays are ready on the device (chipbench/spans.py). The reader of
``tick_copy_in_ms.<suffix>`` for every scoring cell family.
"""
from chipbench import spans

LAYER = "scoring engine: host-to-device"
SOURCE = "program_span"
UNIT = "ms"


def read(rec):
    return spans.mean_ms(rec, "serve.copy_in")
