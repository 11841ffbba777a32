"""Host milliseconds per collected round that the workers spent slicing
their coded shares (and, on the device pool, placing them) before their
program ran, from the program's OverlapStats (latency cells).  Every
subtask that started counts once, a straggler's in a later round."""


def read(rec):
    o = rec["overlap"]
    prep_s = getattr(o, "prep_s", None)
    if prep_s is None or not o.rounds:
        return None
    return prep_s / o.rounds * 1e3
