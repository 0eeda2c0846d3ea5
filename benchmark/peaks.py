"""Published peaks of one chip, keyed by the device kind JAX reports.

Copied from ``tensorflow_examples_tpu/telemetry/accounting.py`` (the
yardstick may not move with the program) and extended with memory
bandwidth and size. Source: Google Cloud TPU documentation, "TPU v5e"
system architecture page: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of
HBM at 819 GB/s per chip. A kind that is not listed is an error, not a
default.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops_per_s: float
    hbm_bytes_per_s: float
    hbm_bytes: int
    source: str


# First match wins, on a lower-cased substring of ``device_kind``.
PEAKS_BY_DEVICE_KIND: tuple[tuple[str, Peaks], ...] = (
    ("v5 lite", Peaks(197e12, 819e9, 16 * 2**30, "Google Cloud TPU docs, TPU v5e")),
    ("v5e", Peaks(197e12, 819e9, 16 * 2**30, "Google Cloud TPU docs, TPU v5e")),
)


def peaks_for(device_kind: str) -> Peaks:
    kind = (device_kind or "").lower()
    for sub, peaks in PEAKS_BY_DEVICE_KIND:
        if sub in kind:
            return peaks
    raise KeyError(
        f"device kind {device_kind!r} is not in benchmark/peaks.py: add its "
        "published peaks with their source before reporting a share of them"
    )


def peaks_of_this_device() -> Peaks | None:
    """Peaks of the device JAX runs on; ``None`` on the CPU (the tests),
    where no share of a peak is ever computed."""
    import jax

    dev = jax.devices()[0]
    return None if dev.platform == "cpu" else peaks_for(dev.device_kind)


def train_step_flops(n_params: int, tokens_per_step: int) -> float:
    """Model operations of ONE optimizer step by 6*N*D (2ND forward,
    4ND backward; PaLM appendix B). Leaves out attention's own
    operations (the QK^T and PV products), so it reads low for long
    sequences; recomputed operations never count. An end-to-end
    utilisation, not a kernel's roofline share."""
    return 6.0 * float(n_params) * float(tokens_per_step)


def mfu(flops_per_step: float, steps_per_s: float, peak_flops_total: float) -> float:
    return flops_per_step * steps_per_s / peak_flops_total
