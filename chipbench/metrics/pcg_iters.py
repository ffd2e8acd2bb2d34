"""Hessian-vector products per solve.

Layer: PCG (core/pcg.py). ``DiscoResult.history[*].pcg_iters`` counts
PCG iterations, or rounds of ``pcg_block_s`` products in s-step mode, so
it is multiplied by ``pcg_block_s``; summed over a solve's outer
iterations and averaged over the window's solves.
"""
LAYER = "PCG"
SOURCE = "program_counter"
UNIT = "HVP/solve"


def read(rec):
    hists = rec.get("histories")
    if not hists:
        return None
    s = rec["pcg_block_s"]
    return sum(s * h["pcg_iters"] for hist in hists for h in hist) \
        / len(hists)
