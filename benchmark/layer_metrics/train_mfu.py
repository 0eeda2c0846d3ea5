"""Trainer: 6ND x steps/s over the bf16 peak (benchmark/peaks.py)."""

from benchmark import readers


def read(run):
    return readers.train_mfu_pct(run)
