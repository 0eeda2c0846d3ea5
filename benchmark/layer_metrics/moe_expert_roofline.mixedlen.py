"""Kernels: the grouped expert product (the megablox gmm kernels) against the nearer bound of each call, operations or weight bytes, over its device time in the trace, at this model's widths (K 4,096 x N 2,048, 16 experts held of 256 scored)."""

import re

from benchmark import roofline_mimo_v2 as ops

# The device's op line of one grouped product (layer_metrics/moe_expert_roofline.longdoc.py
# has where the name comes from), output [rows, width].
GMM_OP = re.compile(r"^%gmm[\w.]* = \w+\[(\d+),(\d+)\]\S* custom-call\(")


def read(run):
    if run.trace is None or run.peaks is None or "sizes" not in run.model:
        return None
    s, item = run.model["sizes"], run.model["param_itemsize"]
    # the traced slice's own pairs and steps, as the program counted them inside it
    piece = run.model.get("slice") or {}
    held, steps = piece.get("moe_pairs_held"), piece.get("decode_steps")
    if not held or not steps or not run.model.get("expert_layers"):
        return None
    calls = [(int(m.group(1)), count, secs) for plane in run.trace.planes
             for name, (count, secs) in plane.ops.items() if (m := GMM_OP.match(name))]
    rows_run = sum(rows * count for rows, count, _ in calls)
    if not rows_run:
        return None
    # Rows routed to absent experts, of padding and of parked slots are computed nowhere:
    # of the rows the products were launched over (three products a layer), the share the
    # program counted as pairs of the experts held here.
    real = 3.0 * held / rows_run
    hit_decode = piece["moe_decode_experts_hit"] / steps / run.model["expert_layers"]
    decode_rows = run.model["max_slots"] * s["top_k"]
    least = seconds = 0.0
    for rows, count, secs in calls:
        # A decode step's product has max_slots x top_k rows and hits what the program
        # counted; a chunk's has more and hits every held expert.
        hit = hit_decode if rows == decode_rows else float(s["held"])
        flops, bytes_ = ops.grouped_product_cost(
            s, rows=rows * real, experts_hit=hit, matrices=1, itemsize=item)
        least += count * max(flops / run.peaks.bf16_flops_per_s,
                             bytes_ / run.peaks.hbm_bytes_per_s)
        seconds += secs
    return 100.0 * least / seconds if seconds else None
