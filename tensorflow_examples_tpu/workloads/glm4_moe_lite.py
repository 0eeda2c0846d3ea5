"""GLM-4.7-Flash (``glm4_moe_lite``) serving workload: the first layers
of the model, each whole on one chip, built from the published sizes,
with seeded parameters.

The surface the serving benchmark's runner uses, as ``workloads/gpt2``
and ``workloads/cohere2_moe`` have it: a config dataclass (built by
``benchmark/spec.program_config`` from a configuration file's keys),
``model_config(cfg)`` and ``make_task(cfg).init_fn``. There is no
training path (the trainer runs the flax ``Transformer`` alone).

The cut is depth and served context: every head, every routed expert
(``held_experts`` defaults to all of them and is honoured when fewer:
the chip's share of a layer that several chips share, as
``workloads/cohere2_moe`` has it) and the whole vocabulary are here;
the layers served are the model's first ``num_hidden_layers``, the rest
on further stages; the multi-token-prediction layer is not served
(``models/glm4_moe_lite.py`` has the layer).
"""

from __future__ import annotations

import dataclasses

from tensorflow_examples_tpu.models import glm4_moe_lite


@dataclasses.dataclass
class Glm4MoeLiteServeConfig:
    # Published widths (GLM-4.7-Flash's config.json keys).
    hidden_size: int = 2048
    num_attention_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    intermediate_size: int = 10240
    moe_intermediate_size: int = 1536
    n_routed_experts: int = 64
    num_experts_per_tok: int = 4
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.8
    first_k_dense_replace: int = 1
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-5
    vocab_size: int = 154880
    # The cut: the layers served are the model's first ones.
    num_hidden_layers: int = 7
    seq_len: int = 32768
    # ids of the experts on this chip; None = every routed expert.
    held_experts: tuple | None = None
    param_dtype: str = "bfloat16"


def model_config(cfg: Glm4MoeLiteServeConfig) -> glm4_moe_lite.Glm4MoeLiteConfig:
    held = range(int(cfg.n_routed_experts)) if cfg.held_experts is None \
        else cfg.held_experts
    layers = int(cfg.num_hidden_layers)
    return glm4_moe_lite.Glm4MoeLiteConfig(
        vocab_size=int(cfg.vocab_size),
        max_len=int(cfg.seq_len),
        d_model=int(cfg.hidden_size),
        num_layers=layers,
        first_dense=min(int(cfg.first_k_dense_replace), layers),
        num_heads=int(cfg.num_attention_heads),
        q_lora_rank=int(cfg.q_lora_rank),
        kv_lora_rank=int(cfg.kv_lora_rank),
        qk_nope_head_dim=int(cfg.qk_nope_head_dim),
        qk_rope_head_dim=int(cfg.qk_rope_head_dim),
        v_head_dim=int(cfg.v_head_dim),
        rope_theta=float(cfg.rope_theta),
        rms_norm_eps=float(cfg.rms_norm_eps),
        dense_ffn_dim=int(cfg.intermediate_size),
        ffn_dim=int(cfg.moe_intermediate_size),
        num_experts=int(cfg.n_routed_experts),
        top_k=int(cfg.num_experts_per_tok),
        num_shared=int(cfg.n_shared_experts),
        routed_scale=float(cfg.routed_scaling_factor),
        held_experts=tuple(int(e) for e in held),
        param_dtype=cfg.param_dtype,
    )


@dataclasses.dataclass
class ServeTask:
    """What a serving runner needs of a workload: the parameters."""

    model_cfg: glm4_moe_lite.Glm4MoeLiteConfig

    def init_fn(self, rng) -> dict:
        """``{"params": ...}`` drawn on the device from ``rng`` in the
        config's dtype, leaf by leaf."""
        return {"params": glm4_moe_lite.init_params(self.model_cfg, rng)}


def make_task(cfg: Glm4MoeLiteServeConfig, mesh=None) -> ServeTask:
    if mesh is not None:
        raise NotImplementedError(
            "the glm4_moe_lite workload serves whole layers on one chip; "
            "it has no sharded placement"
        )
    return ServeTask(model_config(cfg))
