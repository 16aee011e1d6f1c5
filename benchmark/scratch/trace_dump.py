#!/usr/bin/env python3
"""Prints the structure of a profiler trace: planes, lines, the commonest
event names with one example of their stats. For looking at a trace by hand
before writing a reader against it.

    python3 benchmark/scratch/trace_dump.py <dir or .xplane.pb>
"""
import collections
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> int:
    from jax.profiler import ProfileData

    from benchmark.lib.trace import find_xplane

    path = sys.argv[1]
    if os.path.isdir(path):
        path = find_xplane(path)
    print("file", path, os.path.getsize(path), "bytes")
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            ev = list(line.events)
            if not ev:
                continue
            names = collections.Counter(e.name for e in ev)
            dur = collections.Counter()
            for e in ev:
                dur[e.name] += e.duration_ns
            print(f"  LINE {line.name!r}: {len(ev)} events, "
                  f"{len(names)} names")
            if not plane.name.startswith("/device:"):
                continue
            for name, ns in dur.most_common(12):
                ex = next(e for e in ev if e.name == name)
                stats = {k: (v if not isinstance(v, str) else v[:120])
                         for k, v in ex.stats}
                print(f"    {ns / 1e6:10.3f} ms x{names[name]:<5} {name[:90]}"
                      f"  {stats}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
