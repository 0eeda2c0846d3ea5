"""Derived accounting: throughput, model-FLOPs MFU, and goodput.

The numbers the paper's tuning loop actually optimizes (ISSUE 2
tentpole (d); arXiv:1909.09756 reports exactly these for the TPU-v3 pod
runs):

* **examples/sec, tokens/sec** — window throughput, computed by the
  loop from wall time and ``global_batch_size``.
* **MFU** — model FLOPs utilization: achieved model FLOPs/sec over the
  accelerator's peak. Model FLOPs use the standard ``6 * N * D``
  estimate (2ND forward + 4ND backward for N params over D processed
  examples·tokens — the PaLM appendix-B convention), NOT the XLA cost
  analysis: MFU is meant to be comparable across implementations, so
  rematerialization or a fused kernel must not change the numerator.
* **goodput** — productive steps over total stepped work: steps whose
  update survived into the final params, vs. work burned by bad-step
  skips and rollback replays (fed by the PR 1 guard counters).

Peak FLOPs come from a device-kind table (bf16 peak per chip) or the
``--telemetry_peak_tflops`` override. A kind that is not in the table —
the CPU, a new TPU generation — has NO peak and therefore no MFU:
``mfu`` is None on its lines, never a figure against an invented peak.
"""

from __future__ import annotations

from typing import Mapping

# bf16 peak FLOPs/sec per chip by PJRT device_kind substring (first
# match wins — order matters for "v5"/"v5 lite"). Source: Google Cloud
# TPU documentation, per-generation system architecture pages ("TPU
# v5e": 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s per chip).
PEAK_FLOPS_BY_DEVICE_KIND: tuple[tuple[str, float], ...] = (
    ("v6e", 918e12),
    ("v5 lite", 197e12),  # v5e reports "TPU v5 lite"
    ("v5e", 197e12),
    ("v5p", 459e12),
    ("v5", 459e12),
    ("v4 lite", 138e12),  # v4i
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)


def peak_flops_per_device(device_kind: str = "") -> float | None:
    """Peak bf16 FLOPs/sec of one device of this PJRT kind; None for a
    kind the table does not list."""
    kind = (device_kind or "").lower()
    for sub, peak in PEAK_FLOPS_BY_DEVICE_KIND:
        if sub in kind:
            return peak
    return None


def train_step_flops(
    n_params: int, examples_per_step: int, tokens_per_example: int = 1
) -> float:
    """Model FLOPs for ONE optimizer step: 6 * N * (examples * tokens).

    ``tokens_per_example`` is 1 for per-example workloads (image
    classification) and the sequence length for token workloads (LM,
    BERT) — the D in 6ND is *processed tokens*.
    """
    return 6.0 * float(n_params) * float(examples_per_step) * float(
        max(tokens_per_example, 1)
    )


def mfu(
    flops_per_step: float, steps_per_sec: float, peak_flops_total: float
) -> float | None:
    """Achieved model FLOPs/sec over total peak; None if peak unknown."""
    if peak_flops_total <= 0 or flops_per_step <= 0 or steps_per_sec <= 0:
        return None
    return flops_per_step * steps_per_sec / peak_flops_total


def goodput(counters: Mapping[str, int]) -> float | None:
    """Productive fraction of stepped work.

    ``train/steps_total`` counts every device step the loop ran —
    including skipped bad steps, executions a rollback later discarded,
    and their replays; ``resilience/bad_steps`` is work whose update
    was dropped on device; ``resilience/steps_lost`` is the
    rollback-discarded work NET of those bad steps (the two loss terms
    are disjoint by construction, see BadStepGuard.note_rollback).
    Productive = total - bad - lost.
    """
    total = counters.get("train/steps_total", 0)
    if total <= 0:
        return None
    lost = counters.get("resilience/bad_steps", 0) + counters.get(
        "resilience/steps_lost", 0
    )
    return max(total - lost, 0) / total
