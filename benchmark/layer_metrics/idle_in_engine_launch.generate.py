"""Engine: device idle while the serve thread's innermost span is span/engine_*_build, _upload or _dispatch (the next step not yet on the device), as % of the traced window."""

from benchmark import host_spans


def read(run):
    return host_spans.idle_share_pct(run, "engine_launch")
