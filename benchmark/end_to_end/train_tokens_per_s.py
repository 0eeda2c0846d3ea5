"""Tokens through Trainer.fit per second: all N steps of the timed fit over all of its time, device sync to device sync."""

from benchmark import readers


def read(run):
    return readers.train_tokens_per_s(run)
