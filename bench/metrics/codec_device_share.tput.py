"""Share of device-busy time spent in the encoder, decoder and transition
programs, from the trace."""
from harness.trace import DECODE, ENCODE, TRANSITION

CODEC = (ENCODE, DECODE, TRANSITION)


def read(rec):
    t = rec["trace"]
    if not t or t["busy_s"] <= 0:
        return None
    progs = t["programs"]
    if not any(p in progs for p in CODEC):
        return None
    return 100.0 * sum(progs.get(p, 0.0) for p in CODEC) / t["busy_s"]
