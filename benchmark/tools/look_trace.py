"""Look at one trace by hand: planes, lines, event counts, the names that
take most time, and the statistics of the first few events of each line.

    python3 benchmark/tools/look_trace.py <trace dir or .xplane.pb>
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(path):
    from jax.profiler import ProfileData

    from benchmark.harness import trace_reduce
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    profile = ProfileData.from_file(path)
    for plane in profile.planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            total = {}
            for e in events:
                total[e.name] = total.get(e.name, 0.0) + e.duration_ns / 1e9
            print(f"  LINE {line.name!r}: {len(events)} events, "
                  f"{len(total)} names")
            for name, sec in sorted(total.items(),
                                    key=lambda kv: -kv[1])[:25]:
                print(f"      {sec:10.6f} s  {name[:140]}")
            for e in events[:2]:
                stats = [(k, str(v)[:120]) for k, v in e.stats]
                print(f"      first event {e.name[:80]!r}: {stats[:12]}")
    summary = trace_reduce.summarize(profile)
    if summary:
        summary.pop("op_s")
        print("SUMMARY", summary)


if __name__ == "__main__":
    main(sys.argv[1])
