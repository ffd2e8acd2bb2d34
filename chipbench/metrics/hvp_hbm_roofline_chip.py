"""One chip's least HBM time for the window's passes over its busy time,
in %.

Layer: the HVP operator and its kernels (core/hvp.py, kernels/), on a
mesh that shares the data. ``hvp_hbm_roofline`` charges every pass over
the whole data to one chip's HBM bandwidth; when the solver's mesh splits
the data over several chips, each chip reads only its shard of a pass.
Here each pass's bytes (``roofline.pass_bytes``) are divided by the
chips the mesh spans, ``jax.device_count()``, the mesh ``DiscoSolver``
builds by default; the busy time is the trace's mean over the chips. On
one chip it reads what ``hvp_hbm_roofline`` reads.
"""
from chipbench import roofline

LAYER = "HVP operator and kernels"
SOURCE = "device_trace"
UNIT = "%"


def read(rec):
    import jax
    trace, peaks = rec.get("trace"), rec.get("peaks")
    if not rec.get("histories") or not trace or not peaks \
            or trace["busy_s"] <= 0:
        return None
    passes = roofline.solve_passes(rec["histories"], rec["pcg_block_s"])
    return roofline.hbm_roofline_pct(
        passes, rec["pass_bytes"] / jax.device_count(), trace["busy_s"],
        peaks["hbm_byte_per_s"])
