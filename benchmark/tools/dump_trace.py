#!/usr/bin/env python3
"""Look at a trace by hand: planes, lines, the commonest event names, and
the stats of a few events.  ``python3 benchmark/tools/dump_trace.py <xplane.pb>``"""

import collections
import sys

from jax.profiler import ProfileData


def main(path: str, grep: str = ""):
    data = ProfileData.from_file(path)
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            if not events:
                continue
            total = sum(e.duration_ns for e in events) * 1e-9
            print(f"  LINE {line.name!r}: {len(events)} events, "
                  f"{total:.4f} s summed, first start "
                  f"{events[0].start_ns * 1e-9:.6f} s")
            names = collections.Counter()
            secs = collections.Counter()
            for e in events:
                names[e.name] += 1
                secs[e.name] += e.duration_ns * 1e-9
            for name, s in secs.most_common(25):
                print(f"      {s:10.6f} s  x{names[name]:<6} {name[:140]}")
            shown = 0
            for e in events:
                if (grep and grep in e.name) or (not grep and shown < 2):
                    stats = {k: (str(v)[:200]) for k, v in e.stats}
                    print(f"      EVENT {e.name[:100]!r} dur "
                          f"{e.duration_ns} ns stats {stats}")
                    shown += 1
                    if shown >= 4:
                        break


if __name__ == "__main__":
    main(*sys.argv[1:3])
