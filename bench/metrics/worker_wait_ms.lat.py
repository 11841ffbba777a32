"""Milliseconds per collected round from the submit of its subtasks to the
delta-th worker finish (on the device pool, the reaper's first sight of
the delta-th result), from the program's OverlapStats (latency cells)."""


def read(rec):
    o = rec["overlap"]
    ready_s = getattr(o, "delta_ready_s", None)
    if ready_s is None or not o.rounds:
        return None
    return ready_s / o.rounds * 1e3
