"""Trainer: mean device duration of one execution of the jitted train step (its XLA module on the device plane)."""

from benchmark import readers


def read(run):
    return readers.module_mean_ms(run, r"train_step")
