"""Milliseconds of the longest single span of the engine thread in the
whole window (admit, idle, encode, submit, reap wait, gather, inverse,
decode or transition, complete), from the program's OverlapStats (latency
cells).  A stall of the engine alone shows here; the span's name goes to
the log."""
from harness.cell import log


def read(rec):
    o = rec["overlap"]
    longest_s = getattr(o, "longest_phase_s", None)
    if not longest_s:
        return None
    log(f"bench: longest engine span {o.longest_phase} "
        f"{longest_s * 1e3:.3f} ms")
    return longest_s * 1e3
