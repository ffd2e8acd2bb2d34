"""Share of the scheduler's tick slots that carried a request, in %.

Layer: the scheduler, ``MicroBatchScheduler`` (glm_serve/scheduler.py).
Requests scored over ticks times ``batch``, from ``ServeStats``'
counters over the window's ticks. The reader of ``batch_fill.<suffix>``
for every scoring cell family; a closed loop fills every tick, so it is
listed for open loops only.
"""
LAYER = "scheduler"
SOURCE = "program_counter"
UNIT = "%"


def read(rec):
    serve = rec.get("serve")
    if not serve or not serve["ticks"]:
        return None
    return 100.0 * serve["completed"] / (serve["ticks"] * serve["batch"])
