"""95th percentile, over requests, of the mean gap between a request's output tokens."""

from benchmark import readers


def read(run):
    return readers.latency_percentile(run, readers.tpot_ms, 95)
