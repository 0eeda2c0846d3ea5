"""Operations and bytes a kernel or a step must do, from its shapes.

Kept with the benchmark so that no PR that claims a gain can change
what a roofline share is a share of. The least time the chip could take
is the larger of operations over peak FLOP/s and bytes over peak
bytes/s; the share is that over the time measured in the device trace.
"""

from __future__ import annotations

from benchmark.peaks import Peaks


def gpt2_param_count(*, n_layer: int, n_embd: int, vocab_size: int,
                     n_positions: int, n_inner: int | None = None) -> int:
    """Parameters of a GPT-2 with a tied head (embeddings counted once)."""
    d, ff = n_embd, (n_inner or 4 * n_embd)
    block = (
        2 * d              # ln_1
        + d * 3 * d + 3 * d  # qkv
        + d * d + d        # attention projection
        + 2 * d            # ln_2
        + d * ff + ff      # mlp_fc
        + ff * d + d       # mlp_proj
    )
    return vocab_size * d + n_positions * d + n_layer * block + 2 * d


def kv_bytes_per_token(*, n_layer: int, n_embd: int, cache_itemsize: int) -> int:
    return 2 * n_layer * n_embd * cache_itemsize


def decode_step_bytes(*, n_params: int, param_itemsize: int, live_kv_tokens: float,
                      kv_bytes_token: int) -> float:
    """Bytes ONE decode step must read: every weight once (the batch
    shares them) and the keys and values of every live token once.
    Activations, the written row and the logits are left out (small
    beside the weights), so the share reads slightly low."""
    return n_params * param_itemsize + live_kv_tokens * kv_bytes_token


def flash_fwd_cost(*, batch: int, heads: int, seq: int, head_dim: int,
                   itemsize: int, causal: bool = True) -> tuple[float, float]:
    """(operations, bytes) of one forward flash-attention call: the
    QK^T and PV products, halved under a causal mask; q, k, v read and
    o written once."""
    flops = 4.0 * batch * heads * seq * seq * head_dim * (0.5 if causal else 1.0)
    bytes_ = 4.0 * batch * heads * seq * head_dim * itemsize
    return flops, bytes_


def least_seconds(flops: float, bytes_: float, peaks: Peaks) -> tuple[float, str]:
    """The roofline's least time and which bound sets it."""
    t_c, t_m = flops / peaks.bf16_flops_per_s, bytes_ / peaks.hbm_bytes_per_s
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
