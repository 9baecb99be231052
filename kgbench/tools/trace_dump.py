"""Print the structure of a profiler trace: planes, lines, event counts,
the names that took most time and the stats of a few events per line.

``python3 kgbench/tools/trace_dump.py [trace dir]`` (default: the last
traced run's ``.kgbench_trace``). Look at a trace by hand with it before
writing a reader against it.
"""
import collections
import glob
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def main(path: str) -> None:
    import jax
    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    for f in files:
        print("file", f, os.path.getsize(f))
        data = jax.profiler.ProfileData.from_file(f)
        for plane in data.planes:
            lines = list(plane.lines)
            print(f"PLANE {plane.name!r} lines={len(lines)}")
            for line in lines:
                evs = list(line.events)
                tot = collections.Counter()
                for e in evs:
                    tot[e.name] += e.duration_ns
                print(f"  LINE {line.name!r} events={len(evs)}"
                      + (f" span=[{evs[0].start_ns:.0f}, "
                         f"{evs[-1].start_ns + evs[-1].duration_ns:.0f}]"
                         if evs else ""))
                for name, ns in tot.most_common(12):
                    print(f"    {ns / 1e6:10.3f} ms  {name[:110]}")
                for e in evs[:2] + [e for e in evs if "kernel" in e.name
                                    or "custom" in e.name][:2]:
                    stats = {k: (str(v)[:300]) for k, v in e.stats}
                    print(f"    sample {e.name[:80]!r} start={e.start_ns:.0f} "
                          f"dur={e.duration_ns:.0f} stats={stats}")


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        ROOT, ".kgbench_trace"))
