"""Milliseconds per scheduler tick spent in ``ScoringEngine.score``.

Layer: the scoring engine (glm_serve/scoring.py: pack, copy, kernel,
copy back). ``ServeStats.busy_s / ticks`` over the window's ticks, the
scheduler's own host-clock span. The reader of ``tick_ms.<suffix>`` for
every scoring cell family.
"""
LAYER = "scoring engine"
SOURCE = "program_span"
UNIT = "ms"


def read(rec):
    serve = rec.get("serve")
    if not serve or not serve["ticks"]:
        return None
    return 1e3 * serve["busy_s"] / serve["ticks"]
