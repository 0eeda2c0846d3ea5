"""Cohere2-MoE (Command A+) as the serving engine runs it: the config
of ONE chip's share of a layer, and its seeded parameters.

The layer, for input ``x`` (a parallel block: attention and experts
both read ``h = LN(x)`` and both add to ``x``):

* ``LN``: mean-subtracting LayerNorm with a scale and no bias.
* attention: ``num_heads`` query heads over ``num_kv_heads`` key/value
  heads (query head ``i`` reads KV head ``i // (H / Hkv)``), no biases.
  A **window** layer (``layer_windows[i] = W``) rotates q and k over the
  whole head dim in interleaved pairs (``rope_theta``) and lets query
  ``i`` see keys ``j`` with ``0 <= i - j < W``; a **full** layer
  (``None``) applies no positions at all and is causal.
* experts: sigmoid router over ``num_experts``, top ``top_k``,
  weights normalised over the chosen; SwiGLU experts of width
  ``ffn_dim``; the chip holds ``held_experts`` and adds only their part
  of the routed sum (``parallel/moe.moe_ffn_held``); ``num_shared``
  shared experts, their mean added by every chip.
* after the last layer ``LN``, then ``logit_scale * x @ wte.T`` over
  the rows of the vocabulary held here (tied embedding).

No flax module: the engine's block (``serving/blocks.Cohere2MoeBlock``)
is a pure function of this param tree, and there is no training path.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Cohere2MoeConfig:
    vocab_size: int = 32768          # rows of the vocabulary held here
    max_len: int = 16384             # served context
    d_model: int = 4096
    num_heads: int = 128
    num_kv_heads: int = 8
    head_dim: int = 128
    # One entry a layer: None = full (causal, no positions), W = window.
    layer_windows: tuple = (4096, 4096, 4096, None)
    rope_theta: float = 50000.0
    layer_norm_eps: float = 1e-5
    logit_scale: float = 1.0
    ffn_dim: int = 4096
    num_experts: int = 128           # the router's width, as published
    top_k: int = 8
    num_shared: int = 4
    held_experts: tuple = tuple(range(16))  # ids of the experts on this chip
    param_dtype: str = "bfloat16"

    def __post_init__(self):
        if self.num_heads % self.num_kv_heads:
            raise ValueError(
                f"num_heads={self.num_heads} is not a multiple of "
                f"num_kv_heads={self.num_kv_heads}"
            )
        if self.head_dim % 2:
            raise ValueError(f"head_dim={self.head_dim} must be even (rotary pairs)")
        held = tuple(self.held_experts)
        if len(set(held)) != len(held) or not all(
            0 <= e < self.num_experts for e in held
        ):
            raise ValueError(
                f"held_experts={held} must be distinct ids below "
                f"num_experts={self.num_experts}"
            )

    @property
    def num_layers(self) -> int:
        return len(self.layer_windows)


def param_shapes(cfg: Cohere2MoeConfig) -> dict:
    """{path: shape} of every leaf, in the tree's own nesting."""
    d, h, g, c, f = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.ffn_dim
    n_held, n_sh = len(cfg.held_experts), cfg.num_shared
    layer = {
        "ln": {"scale": (d,)},
        "attn": {"q": (d, h, c), "k": (d, g, c), "v": (d, g, c), "o": (h, c, d)},
        "moe": {
            "router": (d, cfg.num_experts),
            "w_gate": (n_held, d, f), "w_up": (n_held, d, f), "w_down": (n_held, f, d),
        },
        "shared": {"w_gate": (n_sh, d, f), "w_up": (n_sh, d, f), "w_down": (n_sh, f, d)},
    }
    tree = {"wte": {"embedding": (cfg.vocab_size, d)}, "ln_f": {"scale": (d,)}}
    for i in range(cfg.num_layers):
        tree[f"h_{i}"] = layer
    return tree


def _draw(key, shape, dtype, std, *, stacked):
    """One leaf: float32 normals cast to ``dtype``; the stacked expert
    leaves one expert at a time, so that no float32 copy larger than
    one expert matrix (or the embedding) ever exists."""
    if stacked:
        return jax.lax.map(
            lambda k: (jax.random.normal(k, shape[1:], jnp.float32) * std).astype(dtype),
            jax.random.split(key, shape[0]),
        )
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def init_params(cfg: Cohere2MoeConfig, key, *, std: float = 0.02) -> dict:
    """Seeded parameters in ``cfg.param_dtype``, leaf by leaf: normal
    ``std`` for every matrix (the family's initializer_range), ones for
    the LayerNorm scales."""
    dtype = jnp.dtype(cfg.param_dtype)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)
    )
    keys = jax.random.split(key, len(leaves))
    out = [
        jnp.ones(shape, dtype) if len(shape) == 1
        else _draw(k, shape, dtype, std, stacked=path[-1].key.startswith("w_"))
        for k, (path, shape) in zip(keys, leaves)
    ]
    return jax.tree.unflatten(treedef, out)
