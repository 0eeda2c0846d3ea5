"""Output tokens of completed requests per second of the window, under a backlog."""

from benchmark import readers


def read(run):
    return readers.output_tokens_per_s(run)
