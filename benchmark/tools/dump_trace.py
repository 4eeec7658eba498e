"""Look at a trace by hand: planes, lines, and a few events of each with
their statistics.

    python benchmark/tools/dump_trace.py <dir or .xplane.pb> [events]
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> int:
    from jax.profiler import ProfileData

    from benchmark.reduce import xplane

    path = sys.argv[1]
    n = int(sys.argv[2]) if len(sys.argv) > 2 else 6
    if os.path.isdir(path):
        path = xplane.newest_xplane(path)
    print(path, os.path.getsize(path), "bytes")
    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            for ev in events[:n]:
                print(f"    {ev.name!r} start={ev.start_ns} "
                      f"dur={ev.duration_ns} {dict(ev.stats)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
