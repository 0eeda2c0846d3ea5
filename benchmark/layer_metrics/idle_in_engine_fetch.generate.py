"""Engine: device idle while the serve thread's innermost span is span/engine_*_fetch (the device has finished, the tokens are not yet in hand), as % of the traced window."""

from benchmark import host_spans


def read(run):
    return host_spans.idle_share_pct(run, "engine_fetch")
