"""Share of the coded subtasks that reached the device whose results the
decode used: 100 x subtasks used (delta per round) / subtasks started,
from the program's OverlapStats (latency cells).  The rest is device work
the fastest-delta collect threw away."""


def read(rec):
    o = rec["overlap"]
    started = getattr(o, "subtasks_started", None)
    if not started:
        return None
    return 100.0 * o.subtasks_used / started
