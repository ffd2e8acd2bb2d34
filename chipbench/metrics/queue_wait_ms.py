"""Milliseconds a scored request waited in the scheduler's queue.

Layer: the scheduler (``MicroBatchScheduler``, glm_serve/scheduler.py).
The program's counters over the window: ``serve.queue_wait_s``, submit
to admission on the scheduler's clock, over ``serve.scored``. A closed
loop of two callers per slot waits one tick by construction, so it is
read for open loops.
"""
LAYER = "scheduler"
SOURCE = "program_counter"
UNIT = "ms"


def read(rec):
    counters = rec.get("counters") or {}
    if "serve.queue_wait_s" not in counters \
            or not counters.get("serve.scored"):
        return None
    return 1e3 * counters["serve.queue_wait_s"] / counters["serve.scored"]
