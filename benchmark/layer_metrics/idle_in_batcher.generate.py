"""Frontend and batcher: device idle while the serve thread's innermost span is a span/serve_* (admission, commit, the self time of serve_decode_step and serve_prefill*), as % of the traced window."""

from benchmark import host_spans


def read(run):
    return host_spans.idle_share_pct(run, "batcher")
