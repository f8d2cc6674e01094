"""Print what a profiler capture holds: planes, their lines, how many
events each has and a few of them with their stats. For looking at one
trace by hand before trusting ``trace_reduce.py`` on a new device or JAX.

    python3 benchmarks/dump_xplane.py <profile dir or .xplane.pb> [samples] [regex]

With a regex, only events whose name matches it are shown.
"""

import glob
import os
import re
import sys


def main(argv) -> int:
    from jax.profiler import ProfileData

    path = argv[1]
    samples = int(argv[2]) if len(argv) > 2 else 12
    only = re.compile(argv[3]) if len(argv) > 3 else None
    if os.path.isdir(path):
        path = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                recursive=True))[-1]
    print(f"{path}: {os.path.getsize(path)} bytes")
    for plane in ProfileData.from_file(path).planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            seen = set()
            for ev in events:
                if ev.name in seen or (only and not only.search(ev.name)):
                    continue
                seen.add(ev.name)
                if len(seen) > samples:
                    break
                print(f"    {ev.name!r} start_ns={ev.start_ns:.0f} "
                      f"dur_ns={ev.duration_ns:.0f} stats={dict(ev.stats)}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
