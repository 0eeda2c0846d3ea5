"""Kernels: bytes a decode step must read (weights outside the routed experts, the head over the vocabulary slice, the routed experts that got a pair, the rows each kind's tables reach at each layer's own row width) over 819 GB/s, over the decode programs' mean device time; the steps are those of the traced slice, counted by the program inside it."""

import statistics

from benchmark import roofline_mimo_v2 as ops


def read(run):
    piece = run.model.get("slice")
    if run.trace is None or run.peaks is None or not piece or not piece.get("decode_steps"):
        return None
    durations = run.trace.module_durations(r"decode_impl")
    if not durations:
        return None
    steps = piece["decode_steps"]
    bytes_ = ops.decode_step_bytes(
        run.model["sizes"], itemsize=run.model["param_itemsize"],
        experts_hit=piece["moe_decode_experts_hit"] / steps,
        reach_bytes=piece["kv_sampled_reach_bytes"] / steps,
    )
    return 100.0 * bytes_ / run.peaks.hbm_bytes_per_s / statistics.fmean(durations)
