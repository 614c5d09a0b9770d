"""Look at a trace by hand before trusting a reduction of it:

    python3 -m benchmark.tools.trace_dump <dir or .xplane.pb> [substring]

Prints every plane and line with its event count, the names that take
most time on each device line, and the stats of the first event whose
name holds ``substring`` (default ``custom-call``).
"""
import collections
import sys

from benchmark import trace_reduce


def main(argv):
    from jax.profiler import ProfileData
    path = argv[1]
    needle = argv[2] if len(argv) > 2 else "custom-call"
    if not path.endswith(".pb"):
        path = trace_reduce.find_xplane(path)
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"plane {plane.name!r}")
        shown = False
        for line in plane.lines:
            events = list(line.events)
            print(f"  line {line.name!r}: {len(events)} events")
            if not plane.name.startswith("/device:") or not events:
                continue
            total = collections.Counter()
            count = collections.Counter()
            for ev in events:
                total[ev.name] += ev.duration_ns
                count[ev.name] += 1
            for name, ns in total.most_common(25):
                print(f"    {ns / 1e6:12.3f} ms  x{count[name]:<6d} {name}")
            if not shown:
                for ev in events:
                    if needle in ev.name:
                        print(f"    stats of {ev.name!r}: "
                              f"{[(k, str(v)[:120]) for k, v in ev.stats]}")
                        shown = True
                        break
    s = trace_reduce.summarize(trace_reduce.load(path))
    print("summary:", None if s is None else
          {k: v for k, v in vars(s).items() if k != "by_name_s"})


if __name__ == "__main__":
    main(sys.argv)
