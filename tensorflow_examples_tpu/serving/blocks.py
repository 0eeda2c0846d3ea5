"""The blocks the serving engine can run, behind one interface.

The engine's forwards (``serving/engine.py``: prefill, extend, decode,
and GPT-2's verify) are each ONE loop

    x = model.embed(params, tokens, positions)
    for layer: x, stats = model.block(params, x, layer, positions, attend, valid)
    logits = model.head(params, x)

and differ only in where ``attend(q, k, v)`` finds K and V (the fresh
prompt, the cached context plus the tail, the slots' block tables). A
model is one of the classes below, chosen by the type of its config
(:func:`block_for`), never by a ``ServeConfig`` field:

* :class:`Gpt2Block` — ``models/transformer.py``'s param tree: learned
  positions, LayerNorm with bias, ``H`` equal heads, a dense GELU MLP.
* :class:`Cohere2MoeBlock` — ``models/cohere2_moe.py``'s: a parallel
  block, grouped-query heads, window layers with rotary positions and
  full layers with none, an expert layer that holds some of the
  experts (``parallel/moe.moe_ffn_held``) beside shared experts.

What a model tells the engine beside its three functions: ``num_layers``,
``num_heads`` / ``num_kv_heads`` / ``head_dim`` (a cache row is
``num_kv_heads * head_dim`` values), ``layer_windows`` (one entry a
layer: ``None`` = full, ``W`` = window; the paged pool keeps one
block-id space per kind), ``stats_len`` (the int32 counts a block adds
to a step's fetched output, 0 for none; a model that has any books them
itself, ``count_stats(registry, stats, decode=)`` — the engine only
forwards what it fetched) and ``name`` (what a refusal says).

Every matmul weight of GPT-2 is read through ``core/precision.
materialize`` (``_w``) and its embedding tables through ``take_rows``
(``_rows``): under a PrecisionConfig the leaf is a QuantizedWeight
dequantized inside the jitted step. LayerNorm/softmax math mirrors the
flax defaults (eps 1e-5, gelu approximate).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from tensorflow_examples_tpu.core import precision as precision_mod
from tensorflow_examples_tpu.core.precision import materialize as _w
from tensorflow_examples_tpu.core.precision import take_rows as _rows
from tensorflow_examples_tpu.models.cohere2_moe import Cohere2MoeConfig
from tensorflow_examples_tpu.models.transformer import TransformerConfig
from tensorflow_examples_tpu.parallel.moe import moe_ffn_held


def _normalise(x, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps)


def _layer_norm(x, p, eps=1e-5):
    return _normalise(x, eps) * p["scale"] + p["bias"]


def _block_mlp(x, p):
    h = jnp.dot(x, _w(p["mlp_fc"]["kernel"])) + p["mlp_fc"]["bias"]
    h = jax.nn.gelu(h, approximate=True)
    return jnp.dot(h, _w(p["mlp_proj"]["kernel"])) + p["mlp_proj"]["bias"]


def _qkv(x, p):
    """[..., d] -> q, k, v each [..., H, hd]."""
    y = jnp.einsum("...d,dthc->...thc", x, _w(p["qkv"]["kernel"]))
    y = y + p["qkv"]["bias"]
    return y[..., 0, :, :], y[..., 1, :, :], y[..., 2, :, :]


def _attn_out(att, p):
    """[..., H, hd] attention output -> [..., d] residual contribution."""
    return jnp.einsum("...hc,hcd->...d", att, _w(p["proj"]["kernel"])) + p[
        "proj"
    ]["bias"]


class Gpt2Block:
    """GPT-2's block over the ``models/transformer.py`` param tree
    (same names: wte/wpe/h_i/ln_f)."""

    name = "gpt2"
    stats_len = 0

    def __init__(self, cfg: TransformerConfig):
        self.cfg = cfg
        self.num_layers = cfg.num_layers
        self.num_heads = self.num_kv_heads = cfg.num_heads
        self.head_dim = cfg.head_dim
        self.max_len = cfg.max_len
        self.layer_windows = (None,) * cfg.num_layers

    def param_dtype(self, params):
        wte = params["wte"]["embedding"]
        if isinstance(wte, precision_mod.QuantizedWeight):
            return jnp.float32
        return wte.dtype

    def embed(self, params, tokens, positions):
        """``positions`` broadcast against ``tokens`` from the right
        ([L] under [B, L], [S] beside [S], [S, T] beside [S, T])."""
        return _rows(params["wte"]["embedding"], tokens) + _rows(
            params["wpe"]["embedding"], positions
        )

    def block(self, params, x, layer, positions, attend, valid=None):
        del positions, valid  # learned positions were added by embed
        p = params[f"h_{layer}"]
        y = _layer_norm(x, p["ln_1"])
        q, k, v = _qkv(y, p["attn"])
        x = x + _attn_out(attend(q, k, v), p["attn"])
        x = x + _block_mlp(_layer_norm(x, p["ln_2"]), p)
        return x, None

    def head(self, params, x):
        x = _layer_norm(x, params["ln_f"])
        return jnp.dot(x, _w(params["wte"]["embedding"]).T)

    def last_logits(self, params, x, index):
        """Logits of row ``index`` of ``x`` [T, d] (every row's are
        computed, as the prefill programs always have)."""
        return jax.lax.dynamic_index_in_dim(
            self.head(params, x), index, keepdims=False
        )


def _scale_norm(x, scale, eps):
    """Mean-subtracting LayerNorm with a scale and no bias, float32."""
    return _normalise(x.astype(jnp.float32), eps) * scale.astype(jnp.float32)


def rope_interleaved(x, positions, theta: float):
    """Rotate ``x`` [..., heads, D] over all of D in interleaved pairs
    (``rope_gptj``): pair ``(x[2i], x[2i+1])`` turns by ``positions *
    theta ** (-2i / D)``. ``positions`` carries x's leading axes (or
    broadcasts against them from the right). Float32 inside."""
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[..., None, None] * inv_freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], d // 2, 2)
    x0, x1 = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


class Cohere2MoeBlock:
    """Cohere2-MoE's parallel block over ``models/cohere2_moe.py``'s
    param tree. The residual stream, the LayerNorms and the router are
    float32; the projections and the experts run in the parameters'
    dtype with float32 accumulation.

    ``block`` also returns the step's expert counts, ``stats_len`` int32
    values: the (token, expert) pairs each held expert computed, then
    the pairs the router sent to all ``num_experts``, then the held
    experts that got at least one pair (each summed over the layers by
    the forward)."""

    name = "cohere2_moe"

    def __init__(self, cfg: Cohere2MoeConfig):
        self.cfg = cfg
        self.num_layers = cfg.num_layers
        self.num_heads = cfg.num_heads
        self.num_kv_heads = cfg.num_kv_heads
        self.head_dim = cfg.head_dim
        self.max_len = cfg.max_len
        self.layer_windows = tuple(cfg.layer_windows)
        self.stats_len = len(cfg.held_experts) + 2

    def count_stats(self, registry, stats, *, decode: bool) -> None:
        """Book one step's fetched ``stats`` (``block``'s, summed over
        the layers) into the registry's expert counters."""
        held = self.cfg.held_experts
        n = len(held)
        pairs_held = int(stats[:n].sum())
        registry.counter("serving/moe_pairs_held").inc(pairs_held)
        registry.counter("serving/moe_pairs_routed").inc(int(stats[n]))
        for expert, pairs in zip(held, stats[:n]):
            registry.counter(
                f"serving/moe_pairs_expert_{expert}"
            ).inc(int(pairs))
        if decode:
            registry.counter("serving/moe_decode_pairs_held").inc(pairs_held)
            registry.counter("serving/moe_decode_experts_hit").inc(
                int(stats[n + 1])
            )

    def param_dtype(self, params):
        return params["wte"]["embedding"].dtype

    def embed(self, params, tokens, positions):
        del positions  # window layers rotate q and k; full layers have none
        return params["wte"]["embedding"][tokens].astype(jnp.float32)

    def block(self, params, x, layer, positions, attend, valid=None):
        cfg, p = self.cfg, params[f"h_{layer}"]
        dtype = p["attn"]["q"].dtype
        window = self.layer_windows[layer]
        h = _scale_norm(x, p["ln"]["scale"], cfg.layer_norm_eps)
        hb = h.astype(dtype)
        q, k, v = (
            jnp.einsum("...d,dhc->...hc", hb, p["attn"][n]) for n in "qkv"
        )
        if window is not None:
            q = rope_interleaved(q, positions, cfg.rope_theta)
            k = rope_interleaved(k, positions, cfg.rope_theta)
        with jax.named_scope("attn_full" if window is None else "attn_window"):
            att = attend(q, k, v)
        a = jnp.einsum(
            "...hc,hcd->...d", att.astype(dtype), p["attn"]["o"],
            preferred_element_type=jnp.float32,
        )
        flat, flat_b = h.reshape(-1, h.shape[-1]), hb.reshape(-1, h.shape[-1])
        rows = None if valid is None else jnp.broadcast_to(
            valid, x.shape[:-1]
        ).reshape(-1)
        moe = p["moe"]
        part, pairs = moe_ffn_held(
            moe["router"], moe["w_gate"], moe["w_up"], moe["w_down"], flat,
            held=tuple(cfg.held_experts), top_k=cfg.top_k, valid=rows,
        )
        sh = p["shared"]
        with jax.named_scope("moe_shared"):
            g = jnp.einsum("nd,sdf->snf", flat_b, sh["w_gate"],
                           preferred_element_type=jnp.float32)
            u = jnp.einsum("nd,sdf->snf", flat_b, sh["w_up"],
                           preferred_element_type=jnp.float32)
            shared = jnp.einsum(
                "snf,sfd->nd", (jax.nn.silu(g) * u).astype(dtype),
                sh["w_down"], preferred_element_type=jnp.float32,
            ) / cfg.num_shared
        n_real = flat.shape[0] if rows is None else jnp.sum(rows)
        stats = jnp.concatenate([
            pairs,
            jnp.stack([n_real * cfg.top_k, jnp.sum(pairs > 0)]).astype(jnp.int32),
        ])
        return x + a + (part + shared).reshape(x.shape), stats

    def head(self, params, x):
        cfg = self.cfg
        wte = params["wte"]["embedding"]
        x = _scale_norm(x, params["ln_f"]["scale"], cfg.layer_norm_eps)
        return cfg.logit_scale * jnp.dot(
            x.astype(wte.dtype), wte.T, preferred_element_type=jnp.float32
        )

    def last_logits(self, params, x, index):
        """Logits of row ``index`` of ``x`` [T, d]: only that row meets
        the vocabulary."""
        return self.head(
            params, jax.lax.dynamic_index_in_dim(x, index, keepdims=False)
        )


def block_for(model_cfg):
    """The block that serves ``model_cfg``, by the config's type."""
    if isinstance(model_cfg, Cohere2MoeConfig):
        return Cohere2MoeBlock(model_cfg)
    if isinstance(model_cfg, TransformerConfig):
        return Gpt2Block(model_cfg)
    raise TypeError(
        f"no serving block for a {type(model_cfg).__name__}: the engine "
        "serves TransformerConfig (GPT-2) and Cohere2MoeConfig"
    )
