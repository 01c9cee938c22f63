"""Print what a profiler trace holds, to read it by hand: each plane and
line with its event count, the commonest event names, and the stats of a
few events.

    python bench/tools/dump_trace.py <trace dir>
"""
import collections
import sys

from jax.profiler import ProfileData

sys.path.insert(0, __file__.rsplit("/bench/", 1)[0])
from bench import trace_reduce  # noqa: E402


def main(path):
    pd = ProfileData.from_file(trace_reduce.find_xplane(path))
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            names = collections.Counter(e.name for e in evs)
            print(f"  LINE {line.name!r}: {len(evs)} events; top {names.most_common(12)}")
            seen = set()
            for e in evs:
                key = e.name.split(".")[0]
                if key in seen or len(seen) >= 6:
                    continue
                seen.add(key)
                print(f"     {e.name!r} start {e.start_ns:.0f} dur {e.duration_ns:.0f} stats {dict(e.stats)}")


if __name__ == "__main__":
    main(sys.argv[1])
