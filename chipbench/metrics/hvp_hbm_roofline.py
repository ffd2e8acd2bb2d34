"""The window's least HBM time over the device's busy time, in %.

Layer: the HVP operator and its kernels (core/hvp.py, kernels/). Each
HVP and each outer iteration's gradient needs one read of the data at
its stored f32 width (``roofline.pass_bytes``: d*n*4 dense, nnz*8
sparse); their bytes over the chip's HBM bandwidth (``peaks.json``) is
the least time the window's work could take, and the device busy time
comes from the trace. A second read per pass, padding and all other
device work lower the share; it passes 100% only if work is skipped.
"""
from chipbench import roofline

LAYER = "HVP operator and kernels"
SOURCE = "device_trace"
UNIT = "%"


def read(rec):
    trace, peaks = rec.get("trace"), rec.get("peaks")
    if not rec.get("histories") or not trace or not peaks \
            or trace["busy_s"] <= 0:
        return None
    passes = roofline.solve_passes(rec["histories"], rec["pcg_block_s"])
    return roofline.hbm_roofline_pct(passes, rec["pass_bytes"],
                                     trace["busy_s"],
                                     peaks["hbm_byte_per_s"])
