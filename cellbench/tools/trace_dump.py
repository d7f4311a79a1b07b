#!/usr/bin/env python3
"""Look at one trace by hand: planes, lines, and the events that took most
time in each line.   python3 cellbench/tools/trace_dump.py <file.xplane.pb>"""

import collections
import sys

from jax.profiler import ProfileData


def main() -> None:
    data = ProfileData.from_file(sys.argv[1])
    top = int(sys.argv[2]) if len(sys.argv) > 2 else 12
    for plane in data.planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            if not evs:
                continue
            tot = collections.Counter()
            cnt = collections.Counter()
            for ev in evs:
                tot[ev.name] += ev.duration_ns
                cnt[ev.name] += 1
            lo = min(e.start_ns for e in evs)
            hi = max(e.start_ns + e.duration_ns for e in evs)
            print(f"  LINE {line.name!r}: {len(evs)} events, span {(hi - lo) / 1e9:.4f} s, "
                  f"{len(tot)} names")
            for name, ns in tot.most_common(top):
                print(f"      {ns / 1e6:10.3f} ms  x{cnt[name]:<6} {name[:150]}")


if __name__ == "__main__":
    main()
