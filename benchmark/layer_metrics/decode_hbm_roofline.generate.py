"""Kernels: bytes a decode step must read over 819 GB/s, over the decode program's mean device time. Memory-bound."""

from benchmark import readers


def read(run):
    return readers.decode_hbm_roofline_pct(run, r"decode_impl")
