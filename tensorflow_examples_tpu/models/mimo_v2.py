"""MiMo-V2.5 (``mimo_v2``) as the serving engine runs it: the config of
ONE chip's share of the layers served, and its seeded parameters.

The layer, for input ``x`` (sequential and pre-norm), of kind ``full``
or ``window`` (``layer_windows[i]`` is ``None`` or ``W``); the two kinds
have their own :class:`AttentionKind` (KV heads, head widths, rotary
base, sink):

* ``x = x + Attn(RMSNorm(x))``, then ``x = x + FFN(RMSNorm(x))``;
  ``RMSNorm``: ``x / sqrt(mean(x^2) + eps) * scale``, no bias.
* attention: ``num_heads`` query heads of ``head_dim`` over
  ``num_kv_heads`` key heads of ``head_dim`` and value heads of
  ``v_head_dim`` (narrower: 192 beside 128), query head ``i`` reading KV
  head ``i // (H / G)``; ``v`` is scaled by ``value_scale`` after its
  projection; the FIRST ``rotary_dim`` dimensions of q and k turn in
  rotate-half pairs (dimension ``i`` with ``i + rotary_dim / 2``,
  ``rope_theta`` of the kind), the others carry no position; scores
  ``q . k / sqrt(head_dim)``, causal, and in a window layer only for
  ``0 <= i - j < W``. A kind with ``sink`` has one learned logit a head
  that joins the softmax's denominator and carries no value:
  ``p_ij = exp(s_ij) / (exp(b_n) + sum_j' exp(s_ij'))``.
* FFN of a layer with ``moe_layers[i]`` false: SwiGLU of width
  ``dense_ffn_dim``. Of the others: ``s = sigmoid(h W_r)`` over
  ``num_experts`` in float32; the ``top_k`` largest of ``s + router
  bias`` are chosen (``noaux_tc``, one group), weighed by their ``s``
  (no bias) over the chosen's sum, no scaling factor; SwiGLU experts of
  width ``ffn_dim``, of which this chip holds ``held_experts``
  (``parallel/moe.moe_ffn_held``). No shared expert.
* after the last layer ``RMSNorm``, then an untied head ``[d, vocab]``
  over the rows of the vocabulary held here.

No flax module: the engine's block (``serving/blocks.MimoV2Block``) is a
pure function of this param tree, and there is no training path. The
published model's multi-token-prediction layers and its vision and
audio towers are not part of this tree (the text path alone).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from tensorflow_examples_tpu.models.cohere2_moe import _draw  # one leaf, one expert at a time


@dataclasses.dataclass(frozen=True)
class AttentionKind:
    """The attention of one kind of layer."""

    num_heads: int = 64
    num_kv_heads: int = 4
    head_dim: int = 192       # a query's and a key's head
    v_head_dim: int = 128     # a value's head
    rope_theta: float = 1e7
    sink: bool = False        # a learned logit a head in the softmax's denominator

    def __post_init__(self):
        if self.num_heads % self.num_kv_heads:
            raise ValueError(
                f"num_heads={self.num_heads} is not a multiple of "
                f"num_kv_heads={self.num_kv_heads}"
            )


@dataclasses.dataclass(frozen=True)
class MimoV2Config:
    vocab_size: int = 19072          # rows of the vocabulary held here
    max_len: int = 32768             # served context
    d_model: int = 4096
    full: AttentionKind = AttentionKind()
    window: AttentionKind = AttentionKind(
        num_kv_heads=8, rope_theta=1e4, sink=True
    )
    # One entry a layer: None = full, W = window (the kind's attention).
    layer_windows: tuple = (None, 128, 128, 128, 128, None, 128)
    # One entry a layer: an expert layer, or a dense FFN.
    moe_layers: tuple = (False, True, True, True, True, True, True)
    rotary_dim: int = 64             # int(head_dim x partial_rotary_factor)
    value_scale: float = 0.707
    rms_norm_eps: float = 1e-5
    dense_ffn_dim: int = 16384
    ffn_dim: int = 2048              # one routed expert's width
    num_experts: int = 256           # the router's width, as published
    top_k: int = 8
    held_experts: tuple = tuple(range(16))  # ids of the experts on this chip
    param_dtype: str = "bfloat16"

    def __post_init__(self):
        if len(self.moe_layers) != len(self.layer_windows):
            raise ValueError(
                f"moe_layers has {len(self.moe_layers)} entries for "
                f"{len(self.layer_windows)} layers"
            )
        heads = min(self.full.head_dim, self.window.head_dim)
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= heads:
            raise ValueError(
                f"rotary_dim={self.rotary_dim} must be even and within a "
                f"head of {heads} (rotate-half pairs)"
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
    def num_layers(self) -> int:
        return len(self.layer_windows)

    def attention(self, layer: int) -> AttentionKind:
        """The attention of ``layer``'s kind."""
        return self.full if self.layer_windows[layer] is None else self.window


def param_shapes(cfg: MimoV2Config) -> dict:
    """{path: shape} of every leaf, in the tree's own nesting."""
    d, f = cfg.d_model, cfg.ffn_dim
    n_held = len(cfg.held_experts)

    def attn(a: AttentionKind):
        tree = {
            "q": (d, a.num_heads, a.head_dim),
            "k": (d, a.num_kv_heads, a.head_dim),
            "v": (d, a.num_kv_heads, a.v_head_dim),
            "o": (a.num_heads, a.v_head_dim, d),
        }
        if a.sink:
            tree["sinks"] = (a.num_heads,)
        return tree

    def swiglu(width):
        return {"w_gate": (d, width), "w_up": (d, width), "w_down": (width, d)}

    moe = {
        "router": (d, cfg.num_experts), "bias": (cfg.num_experts,),
        "w_gate": (n_held, d, f), "w_up": (n_held, d, f),
        "w_down": (n_held, f, d),
    }
    tree = {
        "wte": {"embedding": (cfg.vocab_size, d)},
        "ln_f": {"scale": (d,)},
        "lm_head": {"kernel": (d, cfg.vocab_size)},
    }
    for i in range(cfg.num_layers):
        tree[f"h_{i}"] = {
            "ln_1": {"scale": (d,)}, "ln_2": {"scale": (d,)},
            "attn": attn(cfg.attention(i)),
            **({"moe": moe} if cfg.moe_layers[i]
               else {"mlp": swiglu(cfg.dense_ffn_dim)}),
        }
    return tree


def init_params(cfg: MimoV2Config, key, *, std: float = 0.02) -> dict:
    """Seeded parameters, leaf by leaf: normal ``std`` in
    ``cfg.param_dtype`` for every matrix, ones for the RMSNorm scales;
    in float32 the router's selection bias normal ``std`` and the sink
    logits normal 1 (a published checkpoint carries trained ones; zeros
    would leave the biased choice untested and give every head the same
    sink)."""
    dtype = jnp.dtype(cfg.param_dtype)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)
    )
    keys = jax.random.split(key, len(leaves))

    def leaf(k, path, shape):
        name = path[-1].key
        if name in ("bias", "sinks"):
            return jax.random.normal(k, shape, jnp.float32) * (
                std if name == "bias" else 1.0
            )
        if len(shape) == 1:
            return jnp.ones(shape, dtype)
        stacked = len(shape) == 3 and name.startswith("w_")
        return _draw(k, shape, dtype, std, stacked=stacked)

    out = [leaf(k, path, shape) for k, (path, shape) in zip(keys, leaves)]
    return jax.tree.unflatten(treedef, out)
