"""Cohere2-MoE (Command A+) serving workload: one chip's share of a
layer, built from the published sizes, with seeded parameters.

The surface the serving benchmark's runner uses, as ``workloads/gpt2``
has it: a config dataclass (built by ``benchmark/spec.program_config``
from a configuration file's keys), ``model_config(cfg)`` and
``make_task(cfg).init_fn``. There is no training path: at 16 bytes a
parameter the same cut needs 75 GB.

The cut is the chip's share of a layer that several chips share:
attention, the shared experts and the router replicated; the routed
experts spread over the chips, this one holding ``held_experts``;
``vocab_size`` rows of the vocabulary; the first ``num_hidden_layers``
of ``layer_types``, the rest on further stages
(``models/cohere2_moe.py`` has the layer).
"""

from __future__ import annotations

import dataclasses

from tensorflow_examples_tpu.models import cohere2_moe


@dataclasses.dataclass
class Cohere2MoeServeConfig:
    # Published widths (command-a-plus-05-2026's config.json keys).
    hidden_size: int = 4096
    num_attention_heads: int = 128
    num_key_value_heads: int = 8
    head_dim: int = 128
    intermediate_size: int = 4096
    num_experts_per_tok: int = 8
    num_shared_experts: int = 4
    sliding_window: int = 4096
    rope_theta: float = 50000.0
    layer_norm_eps: float = 1e-5
    logit_scale: float = 1.0
    # The router's width: every published expert, held here or not.
    router_experts: int = 128
    # The published pattern (any length) and the cut: the layers
    # served are its first ``num_hidden_layers``.
    layer_types: tuple = (
        "sliding_attention", "sliding_attention", "sliding_attention",
        "full_attention",
    )
    num_hidden_layers: int = 4
    held_experts: tuple = tuple(range(16))  # ids of the experts on this chip
    vocab_size: int = 32768
    seq_len: int = 16384
    param_dtype: str = "bfloat16"


def model_config(cfg: Cohere2MoeServeConfig) -> cohere2_moe.Cohere2MoeConfig:
    kinds = {"sliding_attention": int(cfg.sliding_window), "full_attention": None}
    served = tuple(cfg.layer_types)[: int(cfg.num_hidden_layers)]
    unknown = [t for t in served if t not in kinds]
    if unknown or len(served) < cfg.num_hidden_layers:
        raise ValueError(
            f"layer_types {list(cfg.layer_types)} does not name "
            f"{cfg.num_hidden_layers} layers of {sorted(kinds)}"
        )
    return cohere2_moe.Cohere2MoeConfig(
        vocab_size=int(cfg.vocab_size),
        max_len=int(cfg.seq_len),
        d_model=int(cfg.hidden_size),
        num_heads=int(cfg.num_attention_heads),
        num_kv_heads=int(cfg.num_key_value_heads),
        head_dim=int(cfg.head_dim),
        layer_windows=tuple(kinds[t] for t in served),
        rope_theta=float(cfg.rope_theta),
        layer_norm_eps=float(cfg.layer_norm_eps),
        logit_scale=float(cfg.logit_scale),
        ffn_dim=int(cfg.intermediate_size),
        num_experts=int(cfg.router_experts),
        top_k=int(cfg.num_experts_per_tok),
        num_shared=int(cfg.num_shared_experts),
        held_experts=tuple(int(e) for e in cfg.held_experts),
        param_dtype=cfg.param_dtype,
    )


@dataclasses.dataclass
class ServeTask:
    """What a serving runner needs of a workload: the parameters."""

    model_cfg: cohere2_moe.Cohere2MoeConfig

    def init_fn(self, rng) -> dict:
        """``{"params": ...}`` drawn on the device from ``rng`` in the
        config's dtype, leaf by leaf (4.7 B parameters drawn in float32
        and cast would need 19 GB)."""
        return {"params": cohere2_moe.init_params(self.model_cfg, rng)}


def make_task(cfg: Cohere2MoeServeConfig, mesh=None) -> ServeTask:
    if mesh is not None:
        raise NotImplementedError(
            "the cohere2_moe workload serves one chip's share of a "
            "layer on one chip; it has no sharded placement"
        )
    return ServeTask(model_config(cfg))
