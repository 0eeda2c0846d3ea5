"""Frontend and batcher: the serve thread's time inside span/serve_prefill* (single-shot prefills and chunks) as % of the traced window."""

import os

from benchmark import host_spans, spec, trace_reduce


def read(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    trace_dir = os.path.join(spec.ROOT, ".bench_out", "trace", run.cell.name)
    try:
        path = trace_reduce.find_xplane(trace_dir)
    except FileNotFoundError:
        return None
    from jax.profiler import ProfileData

    spans, _ = host_spans.host_events(list(ProfileData.from_file(path).planes))
    inside = [e - s for s, e, name in spans if name.startswith("span/serve_prefill")]
    if not spans:
        return None
    return 100.0 * sum(inside) / 1e9 / run.trace.window_s
