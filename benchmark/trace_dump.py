"""The by-hand look at a trace: planes, lines, and the events that
took most time on each line. ``python3 benchmark/trace_dump.py <dir>``"""

from __future__ import annotations

import collections
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import trace_reduce  # noqa: E402


def main(trace_dir: str, top: int = 25) -> None:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(trace_reduce.find_xplane(trace_dir))
    for plane in data.planes:
        print(f"== plane {plane.name}")
        for line in plane.lines:
            total = collections.Counter()
            count = collections.Counter()
            lo, hi = None, None
            for ev in line.events:
                total[ev.name] += ev.duration_ns
                count[ev.name] += 1
                lo = ev.start_ns if lo is None else min(lo, ev.start_ns)
                hi = ev.start_ns + ev.duration_ns if hi is None else max(hi, ev.start_ns + ev.duration_ns)
            n = sum(count.values())
            if not n:
                continue
            print(f"  -- line {line.name!r}: {n} events, span {(hi - lo) / 1e6:.3f} ms, starts at {lo}")
            for name, ns in total.most_common(top):
                print(f"       {ns / 1e6:10.3f} ms  x{count[name]:<6d} {name[:110]}")


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 25)
