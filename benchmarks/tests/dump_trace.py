"""Print what a ``*.xplane.pb`` holds — planes, lines, event counts,
the first events of each line — to look at a trace by hand before
writing code against it.  ``python benchmarks/tests/dump_trace.py
<file> [events per line]``."""

import sys

import jax


def main(path, n=6):
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            ev = list(line.events)
            if not ev:
                continue
            t0 = min(e.start_ns for e in ev)
            t1 = max(e.start_ns + e.duration_ns for e in ev)
            print(f"  LINE {line.name!r}: {len(ev)} events, "
                  f"{t0} .. {t1} ns ({(t1 - t0) / 1e9:.3f} s)")
            for e in ev[:n]:
                print(f"    {e.name[:90]!r} start {e.start_ns} "
                      f"dur {e.duration_ns}")


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 6)
