"""Device: device idle with no program span open on the serve thread, plus the traced window's idle lead and tail, as % of the traced window."""

from benchmark import host_spans


def read(run):
    return host_spans.idle_share_pct(run, host_spans.UNATTRIBUTED)
