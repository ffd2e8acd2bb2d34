"""Peaks by device kind, and the least bytes a solve's passes must read."""
from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; an unknown kind is
    an error, never a default."""
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS}; known: {sorted(table)}")
    return table[device_kind]


def pass_bytes(layout: str, d: int, n: int, nnz: int | None = None,
               value_bytes: int = 4, index_bytes: int = 4) -> int:
    """Bytes of one read of the data at its stored width: every value of
    a dense ``(d, n)`` matrix, or every nonzero's value and index of a
    sparse one. Padding, tiles and a second layout are not counted: this
    is what any implementation of a pass has to read."""
    if layout == "dense":
        return d * n * value_bytes
    if layout == "sparse":
        if nnz is None:
            raise ValueError("a sparse layout needs nnz")
        return nnz * (value_bytes + index_bytes)
    raise ValueError(f"unknown layout {layout!r}")


def solve_passes(histories, block_s: int = 1) -> int:
    """Passes over the data that solves with these ``DiscoResult``
    histories needed: one per HVP (``pcg_iters`` counts rounds of
    ``block_s`` HVPs) and one gradient pass per outer iteration."""
    return sum(block_s * int(h["pcg_iters"]) + 1
               for hist in histories for h in hist)


def hbm_roofline_pct(passes: int, bytes_per_pass: int, busy_s: float,
                     hbm_byte_per_s: float) -> float:
    """Least HBM time of the passes over the device's busy time, in %."""
    return 100.0 * passes * bytes_per_pass / hbm_byte_per_s / busy_s
