"""Print the structure of a profiler trace: planes, lines, the most common
event names with their total seconds, and the stats of a few events.

    python bench/dump_trace.py bench/out/trace/<workload>

Look at one trace this way before changing ``harness/trace.py``.
"""
import collections
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import trace  # noqa: E402


def main(path: str, top: int = 15) -> None:
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = trace.xplane_path(path)
    for p in ProfileData.from_file(path).planes:
        print(f"PLANE {p.name!r} stats={dict(p.stats)}")
        for ln in p.lines:
            evs = list(ln.events)
            total = collections.Counter()
            count = collections.Counter()
            for e in evs:
                total[e.name] += e.duration_ns * 1e-9
                count[e.name] += 1
            print(f"  LINE {ln.name!r}: {len(evs)} events, "
                  f"{len(count)} names")
            for name, secs in total.most_common(top):
                print(f"    {count[name]:7d} x {secs:10.6f} s  {name[:120]}")
            for e in evs[:3]:
                print(f"    e.g. {e.name[:80]!r} start {e.start_ns} dur "
                      f"{e.duration_ns} stats {dict(e.stats)}"[:600])


if __name__ == "__main__":
    main(sys.argv[1])
