"""Batcher: decode_tokens / decode_steps / max_slots over the window."""

from benchmark import readers


def read(run):
    return readers.batch_occupancy_pct(run)
