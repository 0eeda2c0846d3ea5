"""Frontend and batcher: device idle while the serve thread's innermost span is span/serve_admit (a request's admission, its self time: what its claim, prefill or extend and publish leave), as % of the traced window."""

from benchmark import span_idle


def read(run):
    return span_idle.idle_share_pct(run, ("span/serve_admit",))
