"""95th percentile, over every request sent, of due instant -> first token."""

from benchmark import readers


def read(run):
    return readers.latency_percentile(run, readers.ttft_ms, 95)
