"""Kernels: the grouped expert product (the megablox gmm kernels) against the nearer bound of each call, operations or weight bytes, over its device time in the trace."""

import re

from benchmark import roofline_cohere2_moe as ops

# The device's op line of one grouped product: the Pallas call is named after its
# kernel function, gmm (the trace's family "gmm custom-call", my chip run, PR 28;
# the named scope around it does not reach the op's name), output [rows, width].
GMM_OP = re.compile(r"^%gmm[\w.]* = \w+\[(\d+),(\d+)\]\S* custom-call\(")


def read(run):
    if run.trace is None or run.peaks is None or "sizes" not in run.model:
        return None
    s, item = run.model["sizes"], run.model["param_itemsize"]
    # the traced slice's own pairs and steps, as the program counted them inside it
    piece = run.model.get("slice") or {}
    routed, steps = piece.get("moe_pairs_routed"), piece.get("decode_steps")
    if not routed or not steps:
        return None
    held_share = piece["moe_pairs_held"] / routed
    hit_decode = piece["moe_decode_experts_hit"] / steps / run.model["layers"]
    decode_rows = run.model["max_slots"] * s["top_k"]
    least = seconds = 0.0
    for plane in run.trace.planes:
        for name, (count, secs) in plane.ops.items():
            m = GMM_OP.match(name)
            if not m:
                continue
            rows = int(m.group(1))
            # A decode step's product has max_slots x top_k rows and hits what the
            # program counted; a prefill chunk's has more and hits every held expert.
            hit = hit_decode if rows == decode_rows else float(s["held"])
            flops, bytes_ = ops.grouped_product_cost(
                s, rows=rows * held_share, experts_hit=hit, matrices=1, itemsize=item)
            least += count * max(flops / run.peaks.bf16_flops_per_s,
                                 bytes_ / run.peaks.hbm_bytes_per_s)
            seconds += secs
    return 100.0 * least / seconds if seconds else None
