"""Host milliseconds per collected worker round in the engine's dispatch
(encode + submit) and collect (reap + gather) phases, from the program's
OverlapStats (latency cells)."""


def read(rec):
    o = rec["overlap"]
    if o is None or not o.rounds:
        return None
    return (o.dispatch_s + o.collect_s) / o.rounds * 1e3
