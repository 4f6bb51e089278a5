#!/usr/bin/env python3
"""Look at one trace by hand: the planes, their lines, the events that
took most time on each, and a small JSON copy of the trace that
``trace_reduce`` reads (what the recorded trace under ``tests/data/``
was made with).

    python3 benchmark/trace_dump.py <trace dir> [<out.json.gz> [<max events per line>]]
"""

import glob
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv):
    import jax

    from benchmark import trace_reduce

    path = max(glob.glob(os.path.join(argv[0], "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    data = jax.profiler.ProfileData.from_file(path)
    print("trace", path, os.path.getsize(path), "bytes")
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            total, count = {}, 0
            for ev in line.events:
                count += 1
                total[ev.name] = total.get(ev.name, 0.0) + ev.duration_ns
            print(f"  LINE {line.name!r}: {count} events")
            for name, ns in sorted(total.items(),
                                   key=lambda kv: -kv[1])[:12]:
                print(f"      {ns / 1e6:12.3f} ms  {name[:110]}")
    trace = trace_reduce.Trace.from_xplane(path)
    busy, window = trace.busy_and_window()
    print("busy_s", busy, "window_s", window)
    print("modules", trace.modules())
    print("breakdown", trace.breakdown())
    if len(argv) > 1:
        trace.to_json(argv[1], int(argv[2]) if len(argv) > 2 else None)


if __name__ == "__main__":
    main(sys.argv[1:])
