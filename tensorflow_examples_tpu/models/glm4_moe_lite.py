"""GLM-4.7-Flash (``glm4_moe_lite``) as the serving engine runs it: the
config of the layers served on one chip, and its seeded parameters.

The layer, for input ``x`` (sequential and pre-norm):

* ``x = x + Attn(RMSNorm(x))``, then ``x = x + FFN(RMSNorm(x))``;
  ``RMSNorm``: ``x / sqrt(mean(x^2) + eps) * scale``, no bias.
* attention (latent, DeepSeek-V2-style MLA), ``num_heads`` heads:
  ``c_q = RMSNorm(h W_qa)`` (``q_lora_rank``), ``q = c_q W_qb`` per head
  ``[q_nope | q_pe]`` (``qk_nope_head_dim`` + ``qk_rope_head_dim``),
  ``q_pe`` rotated in interleaved pairs (``rope_theta``);
  ``[c_kv | k_pe] = h W_kva`` (``kv_lora_rank`` + ``qk_rope_head_dim``),
  ``c_kv = RMSNorm(c_kv)``, ``k_pe`` rotated — ONE key head shared by
  every query head; ``[k_nope | v] = c_kv W_kvb`` per head. Scores
  ``(q_nope . k_nope + q_pe . k_pe) * (nope + rope) ** -0.5``, causal;
  ``o = P v``; out ``o W_o``. **A token's cache row is ``[c_kv | k_pe]``
  after the norm and the rotation: ``kv_lora_rank + qk_rope_head_dim``
  values, no heads, no V.**
* FFN of the first ``first_dense`` layers: SwiGLU of width
  ``dense_ffn_dim``. Of the others: ``s = sigmoid(h W_r)`` over
  ``num_experts`` in float32; the ``top_k`` largest of ``s +
  router bias`` are chosen, weighed by their ``s`` (no bias) over the
  chosen's sum, times ``routed_scale``; SwiGLU experts of width
  ``ffn_dim``, of which this chip holds ``held_experts``
  (``parallel/moe.moe_ffn_held``); plus one shared SwiGLU expert of
  width ``ffn_dim * num_shared``, unweighted.
* after the last layer ``RMSNorm``, then an untied head ``[d, vocab]``.

No flax module: the engine's block (``serving/blocks.Glm4MoeLiteBlock``)
is a pure function of this param tree, and there is no training path.
The multi-token-prediction layer of the published model is not part of
this tree (it drafts for speculative decoding; the base forward is
complete without it).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from tensorflow_examples_tpu.models.cohere2_moe import _draw  # one leaf, one expert at a time


@dataclasses.dataclass(frozen=True)
class Glm4MoeLiteConfig:
    vocab_size: int = 154880
    max_len: int = 32768             # served context
    d_model: int = 2048
    num_layers: int = 7              # the first layers of the model
    first_dense: int = 1             # leading layers with a dense FFN
    num_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-5
    dense_ffn_dim: int = 10240
    ffn_dim: int = 1536              # one routed expert's width
    num_experts: int = 64            # the router's width, as published
    top_k: int = 4
    num_shared: int = 1
    routed_scale: float = 1.8
    held_experts: tuple = tuple(range(64))  # ids of the experts on this chip
    param_dtype: str = "bfloat16"

    def __post_init__(self):
        if self.qk_rope_head_dim % 2:
            raise ValueError(
                f"qk_rope_head_dim={self.qk_rope_head_dim} must be even "
                "(rotary pairs)"
            )
        if not 0 <= self.first_dense <= self.num_layers:
            raise ValueError(
                f"first_dense={self.first_dense} must lie in "
                f"[0, num_layers={self.num_layers}]"
            )
        held = tuple(self.held_experts)
        if len(set(held)) != len(held) or not all(
            0 <= e < self.num_experts for e in held
        ):
            raise ValueError(
                f"held_experts={held} must be distinct ids below "
                f"num_experts={self.num_experts}"
            )

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        """Values of one token's cache row: ``c_kv`` then ``k_pe``."""
        return self.kv_lora_rank + self.qk_rope_head_dim


def param_shapes(cfg: Glm4MoeLiteConfig) -> dict:
    """{path: shape} of every leaf, in the tree's own nesting."""
    d, h, f = cfg.d_model, cfg.num_heads, cfg.ffn_dim
    n_held, fs = len(cfg.held_experts), cfg.ffn_dim * cfg.num_shared
    attn = {
        "q_a": (d, cfg.q_lora_rank), "q_ln": {"scale": (cfg.q_lora_rank,)},
        "q_b": (cfg.q_lora_rank, h, cfg.qk_head_dim),
        "kv_a": (d, cfg.latent_dim), "kv_ln": {"scale": (cfg.kv_lora_rank,)},
        "kv_b": (cfg.kv_lora_rank, h, cfg.qk_nope_head_dim + cfg.v_head_dim),
        "o": (h, cfg.v_head_dim, d),
    }
    norms = {"ln_1": {"scale": (d,)}, "ln_2": {"scale": (d,)}}

    def swiglu(width):
        return {"w_gate": (d, width), "w_up": (d, width), "w_down": (width, d)}

    dense = {**norms, "attn": attn, "mlp": swiglu(cfg.dense_ffn_dim)}
    sparse = {
        **norms, "attn": attn,
        "moe": {
            "router": (d, cfg.num_experts), "bias": (cfg.num_experts,),
            "w_gate": (n_held, d, f), "w_up": (n_held, d, f),
            "w_down": (n_held, f, d),
        },
        "shared": swiglu(fs),
    }
    tree = {
        "wte": {"embedding": (cfg.vocab_size, d)},
        "ln_f": {"scale": (d,)},
        "lm_head": {"kernel": (d, cfg.vocab_size)},
    }
    for i in range(cfg.num_layers):
        tree[f"h_{i}"] = dense if i < cfg.first_dense else sparse
    return tree


def init_params(cfg: Glm4MoeLiteConfig, key, *, std: float = 0.02) -> dict:
    """Seeded parameters, leaf by leaf: normal ``std`` in
    ``cfg.param_dtype`` for every matrix, ones for the RMSNorm scales,
    and the router's selection bias normal ``std`` in float32 (a
    published checkpoint carries a trained one; zeros would leave the
    biased choice untested)."""
    dtype = jnp.dtype(cfg.param_dtype)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)
    )
    keys = jax.random.split(key, len(leaves))

    def leaf(k, path, shape):
        if path[-1].key == "bias":
            return jax.random.normal(k, shape, jnp.float32) * std
        if len(shape) == 1:
            return jnp.ones(shape, dtype)
        stacked = len(shape) == 3 and path[-1].key.startswith("w_")
        return _draw(k, shape, dtype, std, stacked=stacked)

    out = [leaf(k, path, shape) for k, (path, shape) in zip(keys, leaves)]
    return jax.tree.unflatten(treedef, out)
