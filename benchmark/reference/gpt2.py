"""GPT-2 in plain ``jax.numpy`` and float32: forward pass and loss.

Follows the published description (Radford et al. 2019; the Hugging
Face ``GPT2LMHeadModel``): learned token and position embeddings,
pre-LayerNorm blocks (eps 1e-5) of causal multi-head attention and a
4x MLP with the tanh-approximated GELU ("gelu_new"), a final LayerNorm
and a head tied to the token embedding. No kernels, no cache, no
batching tricks, no dropout. Matrix products run under
``jax.default_matmul_precision("highest")``: on a TPU a float32 product
otherwise runs in bf16 passes, and the reference is what the program is
held to.

Departure from the published layout, noted: the parameter tree is the
one this repo's checkpoints hold (``models/transformer.py``): the fused
``c_attn`` kernel is stored ``[d, 3, heads, head_dim]`` and ``c_proj``
``[heads, head_dim, d]`` — the same numbers, reshaped.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _layer_norm(x, p):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + 1e-5) * p["scale"] + p["bias"]


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654 * (x + 0.044715 * x ** 3)))


def forward(params, tokens, *, n_layer: int):
    """tokens [B, L] int32 -> logits [B, L, V] float32."""
    with jax.default_matmul_precision("highest"):
        f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
        wte = f32(params["wte"]["embedding"])
        length = tokens.shape[1]
        x = wte[tokens] + f32(params["wpe"]["embedding"])[:length][None]
        causal = jnp.tril(jnp.ones((length, length), bool))
        for i in range(n_layer):
            p = f32(params[f"h_{i}"])
            a = p["attn"]
            d, _, heads, hd = a["qkv"]["kernel"].shape
            y = _layer_norm(x, p["ln_1"])
            qkv = y @ a["qkv"]["kernel"].reshape(d, 3 * heads * hd)
            qkv = qkv.reshape(*y.shape[:2], 3, heads, hd) + a["qkv"]["bias"]
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]       # [B, L, H, hd]
            scores = jnp.einsum("bqhc,bkhc->bhqk", q, k) / jnp.sqrt(jnp.float32(hd))
            scores = jnp.where(causal[None, None], scores, -jnp.inf)
            att = jnp.einsum("bhqk,bkhc->bqhc", jax.nn.softmax(scores, axis=-1), v)
            x = x + att.reshape(*y.shape[:2], heads * hd) @ a["proj"]["kernel"].reshape(
                heads * hd, d
            ) + a["proj"]["bias"]
            y = _layer_norm(x, p["ln_2"])
            h = _gelu_new(y @ p["mlp_fc"]["kernel"] + p["mlp_fc"]["bias"])
            x = x + h @ p["mlp_proj"]["kernel"] + p["mlp_proj"]["bias"]
        return _layer_norm(x, f32(params["ln_f"])) @ wte.T


def loss(params, tokens, *, n_layer: int):
    """Mean next-token negative log-likelihood of tokens [B, L+1]."""
    logits = forward(params, tokens[:, :-1], n_layer=n_layer)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return -picked.mean()
