"""Batcher: 95th percentile of the replies' queue_wait_s (submit -> admitted to a slot)."""

from benchmark import readers


def read(run):
    return readers.reply_field_percentile_ms(run, "queue_wait_s", 95)
