"""Engine: model operations of every prompt and output token of the window's requests (causal attention inside each kind's reach at each kind's widths, no padding; routed pairs as the program counted them) over the window and the chip's bf16 peak: the share of the whole step."""

from benchmark import roofline_mimo_v2 as ops


def read(run):
    if not run.requests or run.peaks is None or run.window_s <= 0 or "sizes" not in run.model:
        return None
    s = run.model["sizes"]
    ok = [r for r in run.requests if r["ok"]]
    pairs = run.counters.get("serving/moe_pairs_held")
    if not ok or pairs is None:
        return None
    flops = sum(ops.request_flops(s, r["prompt_len"], r["n_tokens"]) for r in ok)
    flops += pairs * ops.pair_flops(s)
    return 100.0 * flops / run.window_s / run.peaks.bf16_flops_per_s
