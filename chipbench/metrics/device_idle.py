"""Share of the traced window in which no operation ran on the device.

Layer: the device. 1 - busy / window from the device planes of the
profiler trace (chipbench/trace.py). One reader for every cell family:
``device_idle.solve``, ``device_idle.closed`` and any other
``device_idle.<suffix>`` that BENCHMARK.json lists, with its own
``moves``.
"""
LAYER = "device"
SOURCE = "device_trace"
UNIT = "%"


def read(rec):
    trace = rec.get("trace")
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
