"""Milliseconds per Newton outer step in which the device runs nothing.

Layer: the outer step's device wait (core/disco.py): the mean time
inside ``newton.step`` (dispatch to ``block_until_ready`` and the stats'
conversion) not covered by any device operation, from the program's
spans and the device planes of one trace (chipbench/spans.py).
"""
from chipbench import spans

LAYER = "outer step: device wait"
SOURCE = "device_trace"
UNIT = "ms"


def read(rec):
    return spans.mean_ms(rec, "newton.step", "idle_s")
