"""Device idle under named spans of the serve thread (ISSUE 36).

``host_spans`` gives every instant of device idle to the innermost span
open on the serve thread; ``HostIdle.idle_ns`` keeps it by that span's
name. A reader here sums the names it is about, over the traced window
(``TraceSummary.window_s``, the base of ``device_idle``), so its share
and the other names of the ``# host_idle`` line add up to that metric.

A run whose serve thread never opened one of the names (the parent
commit, a plain run, a slice with no such work) reads ``None``, not 0:
the spans' names are looked up on the line itself, so a span that was
there and saw no idle still reads 0.0.
"""

from __future__ import annotations

import functools
import os

from benchmark import host_spans, spec, trace_reduce


@functools.lru_cache(maxsize=2)
def _names_on_the_line(path: str, mtime_ns: int) -> frozenset:
    from jax.profiler import ProfileData

    spans, _ = host_spans.host_events(list(ProfileData.from_file(path).planes))
    return frozenset(name for _, _, name in spans)


def idle_share_pct(run, names) -> float | None:
    """Device idle while the serve thread's innermost span is one of
    ``names`` (``span/...``), as % of the traced window."""
    found = host_spans.for_run(run)
    if found is None or run.trace.window_s <= 0:
        return None
    hit = [found.idle_ns[n] for n in names if n in found.idle_ns]
    if not hit:
        trace_dir = os.path.join(spec.ROOT, ".bench_out", "trace", run.cell.name)
        path = trace_reduce.find_xplane(trace_dir)
        if not set(names) & _names_on_the_line(path, os.stat(path).st_mtime_ns):
            return None
    return 100.0 * sum(hit) / (run.trace.window_s * 1e9)
