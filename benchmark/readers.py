"""Arithmetic the metric readers share. A reader is a file of its own
(``benchmark/end_to_end/<metric>.py``, ``benchmark/layer_metrics/
<metric>.py``) with ``read(run) -> float | None``; it returns ``None``
where the run holds nothing to read, and the harness leaves the metric
out of the line."""

from __future__ import annotations

import math
import statistics

from benchmark import peaks as peaks_mod
from benchmark import roofline
from benchmark.record import percentile


def _latencies(run, per_request) -> list[float] | None:
    """One latency per request sent; a failed, shed or refused request
    misses every latency (it counts as infinite)."""
    if not run.requests:
        return None
    out = []
    for r in run.requests:
        v = per_request(r) if r["ok"] else math.inf
        if v is not None:
            out.append(v)
    return out


def _finite(v):
    return None if v is None or not math.isfinite(v) else v


def ttft_ms(r) -> float:
    """Due instant -> first token: how late the generator sent it (the
    benchmark's clock) plus submit -> first token (``ttft_s`` of the
    reply; the frontend hands back whole replies, so the benchmark
    cannot see the first token itself)."""
    return 1e3 * (r["late_s"] + r["ttft_s"])


def tpot_ms(r) -> float | None:
    """Mean gap between a request's output tokens: (due -> reply in
    hand) less (due -> first token), over the tokens after the first."""
    if r["n_tokens"] < 2:
        return None
    return (1e3 * r["client_s"] - ttft_ms(r)) / (r["n_tokens"] - 1)


def latency_percentile(run, per_request, q: float) -> float | None:
    values = _latencies(run, per_request)
    return _finite(percentile(values, q)) if values else None


def output_tokens_per_s(run) -> float | None:
    if not run.requests or run.window_s <= 0:
        return None
    return sum(r["n_tokens"] for r in run.requests if r["ok"]) / run.window_s


def train_tokens_per_s(run) -> float | None:
    if not run.train or run.window_s <= 0:
        return None
    return run.train["steps"] * run.train["tokens_per_step"] / run.window_s


def hist_percentile_ms(run, name: str, q: float) -> float | None:
    samples = run.hists.get(name) or []
    v = percentile(samples, q)
    return None if v is None else 1e3 * v


def reply_field_percentile_ms(run, field: str, q: float) -> float | None:
    values = [r[field] for r in run.requests if r["ok"] and r.get(field) is not None]
    v = percentile(values, q)
    return None if v is None else 1e3 * v


def batch_occupancy_pct(run) -> float | None:
    steps = run.counters.get("serving/decode_steps", 0)
    if not steps:
        return None
    return 100.0 * run.counters["serving/decode_tokens"] / steps / run.model["max_slots"]


def device_idle_pct(run) -> float | None:
    if run.trace is None or run.trace.idle_share is None or run.trace.busy_s <= 0:
        return None
    return 100.0 * run.trace.idle_share


def module_mean_ms(run, pattern: str) -> float | None:
    if run.trace is None:
        return None
    durations = run.trace.module_durations(pattern)
    return 1e3 * statistics.fmean(durations) if durations else None


def train_mfu_pct(run) -> float | None:
    """6*N*D x steps/s over the chips' bf16 peak. 6ND leaves out
    attention's own operations: an end-to-end utilisation that reads a
    little low, not a roofline share."""
    if not run.train or run.peaks is None or run.window_s <= 0:
        return None
    flops = peaks_mod.train_step_flops(run.model["n_params"], run.train["tokens_per_step"])
    steps_per_s = run.train["steps"] / run.window_s
    return 100.0 * peaks_mod.mfu(
        flops, steps_per_s, run.peaks.bf16_flops_per_s * run.model["chips"]
    )


def decode_hbm_roofline_pct(run, pattern: str) -> float | None:
    """Bytes one decode step must read (every weight once, the keys and
    values of the live tokens once) over the HBM peak, over the mean
    device time of one execution of the decode program in the trace.
    The bound is memory: a decode step does 2 operations a weight byte
    read at batch 1, far under the chip's 240 operations a byte."""
    if run.trace is None or run.peaks is None or not run.requests:
        return None
    durations = run.trace.module_durations(pattern)
    steps = run.counters.get("serving/decode_steps", 0)
    if not durations or not steps:
        return None
    live_requests = run.counters["serving/decode_tokens"] / steps
    ok = [r for r in run.requests if r["ok"]]
    if not ok:
        return None
    tokens_each = statistics.fmean(r["prompt_len"] + r["n_tokens"] / 2 for r in ok)
    bytes_ = roofline.decode_step_bytes(
        n_params=run.model["n_params"], param_itemsize=run.model["param_itemsize"],
        live_kv_tokens=live_requests * tokens_each,
        kv_bytes_token=run.model["kv_bytes_token"],
    )
    least = bytes_ / run.peaks.hbm_bytes_per_s
    return 100.0 * least / statistics.fmean(durations)


def flash_fwd_roofline_pct(run, pattern: str, *, itemsize: int = 2) -> float | None:
    """The roofline's least time of one forward flash-attention call at
    the cell's shapes over that kernel's mean device time in the trace."""
    if run.trace is None or run.peaks is None or not run.train:
        return None
    count, seconds = run.trace.op_seconds(pattern)
    if not count:
        return None
    flops, bytes_ = roofline.flash_fwd_cost(
        batch=run.train["batch"] // run.model["chips"], heads=run.model["heads"],
        seq=run.train["seq_len"], head_dim=run.model["head_dim"], itemsize=itemsize,
    )
    least, _ = roofline.least_seconds(flops, bytes_, run.peaks)
    return 100.0 * least / (seconds / count)
