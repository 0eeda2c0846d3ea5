"""MiMo-V2.5 (``mimo_v2``) serving workload: one chip's share of the
first layers of the model, built from the published sizes, with seeded
parameters.

The surface the serving benchmark's runner uses, as the other serving
workloads have it: a config dataclass (built by
``benchmark/spec.program_config`` from a configuration file's keys),
``model_config(cfg)`` and ``make_task(cfg).init_fn``. There is no
training path: at 16 bytes a parameter the same cut needs 36 GB.

The cut is the chip's share of a layer that several chips share:
attention, the router and the norms replicated; the routed experts
spread over the chips, this one holding ``held_experts`` (the router
stays ``router_experts`` wide); ``vocab_size`` rows of the vocabulary,
embedding and head; the first ``num_hidden_layers`` entries of
``hybrid_layer_pattern`` (0 a full layer, 1 a window layer) and of
``moe_layer_freq`` (0 a dense FFN, 1 an expert layer), the rest on
further stages (``models/mimo_v2.py`` has the layer). The
multi-token-prediction layers and the vision and audio towers are not
served.
"""

from __future__ import annotations

import dataclasses

from tensorflow_examples_tpu.models import mimo_v2


@dataclasses.dataclass
class MimoV2ServeConfig:
    # Published widths (MiMo-V2.5's config.json keys).
    hidden_size: int = 4096
    num_attention_heads: int = 64
    num_key_value_heads: int = 4
    head_dim: int = 192
    v_head_dim: int = 128
    rope_theta: float = 1e7
    add_full_attention_sink_bias: bool = False
    swa_num_attention_heads: int = 64
    swa_num_key_value_heads: int = 8
    swa_head_dim: int = 192
    swa_v_head_dim: int = 128
    swa_rope_theta: float = 1e4
    add_swa_attention_sink_bias: bool = True
    sliding_window: int = 128
    partial_rotary_factor: float = 0.334
    attention_value_scale: float = 0.707
    layernorm_epsilon: float = 1e-5
    intermediate_size: int = 16384
    moe_intermediate_size: int = 2048
    num_experts_per_tok: int = 8
    # The router's width: every published expert, held here or not.
    router_experts: int = 256
    # The published patterns (any length) and the cut: the layers
    # served are their first ``num_hidden_layers``.
    hybrid_layer_pattern: tuple = (0, 1, 1, 1, 1, 0, 1)
    moe_layer_freq: tuple = (0, 1, 1, 1, 1, 1, 1)
    num_hidden_layers: int = 7
    held_experts: tuple = tuple(range(16))  # ids of the experts on this chip
    vocab_size: int = 19072
    seq_len: int = 32768
    param_dtype: str = "bfloat16"


def model_config(cfg: MimoV2ServeConfig) -> mimo_v2.MimoV2Config:
    layers = int(cfg.num_hidden_layers)
    pattern = tuple(cfg.hybrid_layer_pattern)[:layers]
    moe = tuple(cfg.moe_layer_freq)[:layers]
    if min(len(pattern), len(moe)) < layers or set(pattern) - {0, 1}:
        raise ValueError(
            f"hybrid_layer_pattern {list(cfg.hybrid_layer_pattern)} and "
            f"moe_layer_freq {list(cfg.moe_layer_freq)} do not name "
            f"{layers} layers of 0 (full; dense) and 1 (window; experts)"
        )
    rotary = int(int(cfg.head_dim) * float(cfg.partial_rotary_factor))
    return mimo_v2.MimoV2Config(
        vocab_size=int(cfg.vocab_size),
        max_len=int(cfg.seq_len),
        d_model=int(cfg.hidden_size),
        full=mimo_v2.AttentionKind(
            num_heads=int(cfg.num_attention_heads),
            num_kv_heads=int(cfg.num_key_value_heads),
            head_dim=int(cfg.head_dim),
            v_head_dim=int(cfg.v_head_dim),
            rope_theta=float(cfg.rope_theta),
            sink=bool(cfg.add_full_attention_sink_bias),
        ),
        window=mimo_v2.AttentionKind(
            num_heads=int(cfg.swa_num_attention_heads),
            num_kv_heads=int(cfg.swa_num_key_value_heads),
            head_dim=int(cfg.swa_head_dim),
            v_head_dim=int(cfg.swa_v_head_dim),
            rope_theta=float(cfg.swa_rope_theta),
            sink=bool(cfg.add_swa_attention_sink_bias),
        ),
        layer_windows=tuple(
            int(cfg.sliding_window) if kind else None for kind in pattern
        ),
        moe_layers=tuple(bool(m) for m in moe),
        rotary_dim=rotary - rotary % 2,
        value_scale=float(cfg.attention_value_scale),
        rms_norm_eps=float(cfg.layernorm_epsilon),
        dense_ffn_dim=int(cfg.intermediate_size),
        ffn_dim=int(cfg.moe_intermediate_size),
        num_experts=int(cfg.router_experts),
        top_k=int(cfg.num_experts_per_tok),
        held_experts=tuple(int(e) for e in cfg.held_experts),
        param_dtype=cfg.param_dtype,
    )


@dataclasses.dataclass
class ServeTask:
    """What a serving runner needs of a workload: the parameters."""

    model_cfg: mimo_v2.MimoV2Config

    def init_fn(self, rng) -> dict:
        """``{"params": ...}`` drawn on the device from ``rng`` in the
        config's dtype, leaf by leaf."""
        return {"params": mimo_v2.init_params(self.model_cfg, rng)}


def make_task(cfg: MimoV2ServeConfig, mesh=None) -> ServeTask:
    if mesh is not None:
        raise NotImplementedError(
            "the mimo_v2 workload serves one chip's share of a layer on "
            "one chip; it has no sharded placement"
        )
    return ServeTask(model_config(cfg))
