"""The blocks the serving engine can run, behind one interface.

The engine's forwards (``serving/engine.py``: prefill, extend, decode,
and GPT-2's verify) are each ONE loop

    x = model.embed(params, tokens, positions)
    for layer: x, stats = model.block(params, x, layer, positions, attend, valid)
    logits = model.head(params, x)

and differ only in where ``attend(q, *row)`` finds the context (the
fresh prompt, the cached context plus the tail, the slots' block
tables); ``row`` is what the block caches of a token, and the forward
writes exactly that. A model is one of the classes below, chosen by the
type of its config (:func:`block_for`), never by a ``ServeConfig``
field:

* :class:`Gpt2Block` — ``models/transformer.py``'s param tree: learned
  positions, LayerNorm with bias, ``H`` equal heads, a dense GELU MLP.
* :class:`Cohere2MoeBlock` — ``models/cohere2_moe.py``'s: a parallel
  block, grouped-query heads, window layers with rotary positions and
  full layers with none, an expert layer that holds some of the
  experts (``parallel/moe.moe_ffn_held``) beside shared experts.
* :class:`Glm4MoeLiteBlock` — ``models/glm4_moe_lite.py``'s: a
  sequential pre-norm block with RMS norms, latent attention (one
  latent row a token in the cache, no heads, no V), a dense first
  layer, then expert layers whose router chooses by a biased score, a
  shared expert, an untied head.
* :class:`MimoV2Block` — ``models/mimo_v2.py``'s: a sequential pre-norm
  block whose full and window layers differ in their KV heads (4 and
  8), keys of 192 beside values of 128, rotary on the first 64
  dimensions with a base a kind, a learned sink logit in the window
  layers' softmax, a dense first layer, then expert layers that hold
  some of the experts under a biased choice, no shared expert.

What a model tells the engine beside its three functions, **per layer**
wherever layers may differ (one tuple entry a layer): ``num_layers``;
**``cache_rows``** (per layer, the arrays the layer keeps of a token,
``(heads, width)`` each: K and V of the layer's own KV heads and its
key and value widths for the first two and the fourth — 4 x 192 and
4 x 128 in MiMo's full layers, 8 x 192 and 8 x 128 in its window layers
— one ``1 x (dc + dr)`` latent row for the third, zero-padded to a
whole number of 128-lane tiles — ``kv_cache.lane_dense``: 640 columns
for 576 values; a toy row under one tile stays as it is — because the
TPU re-lays a whole pool array of any other width around every write;
the pool allocates each layer's arrays by it, the layers of one kind
alike, and the forwards scatter what ``block`` hands ``attend`` after
``q``); **``row_values``** (per layer; any block but GPT-2's, which
books no ``kv_sampled_*``: the values of a token's row in the model's
mathematics, pad columns left out — what a step must read of it,
``serving/kv_sampled_reach_bytes``); **``own_attention``** (true:
attention over the cache is the block's own mathematics,
``chunk_attention`` for a prompt chunk and ``decode_attention`` for a
decode step; false: the engine's generic ``softmax(q k) v`` over
gathered heads serves it, and the block says what that needs of each
layer in **``layer_attention``**, one :class:`LayerAttention` a layer:
query and KV heads, key and value widths, the softmax scale, and
whether the layer has a sink — then ``sinks(params, layer)`` hands the
``[H]`` float32 logits); ``refused`` (any block but GPT-2's: why each
mechanism the engine refuses it cannot serve it); ``layer_windows``
(one entry a layer: ``None`` = full, ``W`` = window; the paged pool
keeps one block-id space per kind, a kind being a window and a row
shape); ``stats_len`` (the int32 counts a block adds to a step's
fetched output, 0 for none; a model that has any books them itself,
``count_stats(registry, stats, decode=)`` — the engine only forwards
what it fetched) and ``name`` (what a refusal says).

Every matmul weight of GPT-2 is read through ``core/precision.
materialize`` (``_w``) and its embedding tables through ``take_rows``
(``_rows``): under a PrecisionConfig the leaf is a QuantizedWeight
dequantized inside the jitted step. LayerNorm/softmax math mirrors the
flax defaults (eps 1e-5, gelu approximate).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from tensorflow_examples_tpu.core import precision as precision_mod
from tensorflow_examples_tpu.core.precision import materialize as _w
from tensorflow_examples_tpu.core.precision import take_rows as _rows
from tensorflow_examples_tpu.models.cohere2_moe import Cohere2MoeConfig
from tensorflow_examples_tpu.models.glm4_moe_lite import Glm4MoeLiteConfig
from tensorflow_examples_tpu.models.mimo_v2 import MimoV2Config
from tensorflow_examples_tpu.models.transformer import TransformerConfig
from tensorflow_examples_tpu.parallel.moe import moe_ffn_held
from tensorflow_examples_tpu.serving import kv_cache
from tensorflow_examples_tpu.telemetry import schema
from tensorflow_examples_tpu.telemetry.spans import span


class LayerAttention(NamedTuple):
    """What the engine's generic attention needs of ONE layer of a
    block without ``own_attention``."""

    heads: int        # query heads
    kv_heads: int     # key/value heads: query head i reads i // (heads / kv_heads)
    key_dim: int      # a query's and a key's head width
    value_dim: int    # a value's head width (the attention's output)
    sm_scale: float   # on q . k
    sink: bool = False  # model.sinks(params, layer): [heads] f32 logits

    @property
    def rows(self) -> tuple:
        """The layer's cache row: K and V as ``(heads, width)``."""
        return (self.kv_heads, self.key_dim), (self.kv_heads, self.value_dim)


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
    own_attention = False

    def __init__(self, cfg: TransformerConfig):
        self.cfg = cfg
        self.num_layers = cfg.num_layers
        attn = LayerAttention(
            cfg.num_heads, cfg.num_heads, cfg.head_dim, cfg.head_dim,
            cfg.head_dim ** -0.5,
        )
        self.layer_attention = (attn,) * cfg.num_layers
        self.cache_rows = (attn.rows,) * cfg.num_layers  # K, V
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


def _count_expert_stats(registry, held, stats, decode: bool) -> None:
    """The expert counts a block with held experts puts first in its
    stats — the pairs each held expert computed, the pairs routed to
    all experts, the held experts hit — into the registry's counters."""
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
    own_attention = False
    # Why each mechanism the engine refuses this block cannot serve it.
    refused = {
        "verify": "its verify forward is GPT-2's",
        "pages": "a page payload has one block-id space and equal heads",
        "kv_dtype": "the grouped-query gather does not dequantize",
        "weights": "the block reads its weights as stored",
        "paged_flash": "the kernel reads rows of equal heads through one "
                       "table",
        "flash": "no grouped-query or window mask",
        "sharding": "the placement rules are GPT-2's",
    }

    def __init__(self, cfg: Cohere2MoeConfig):
        self.cfg = cfg
        self.num_layers = cfg.num_layers
        attn = LayerAttention(
            cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.head_dim,
            cfg.head_dim ** -0.5,
        )
        self.layer_attention = (attn,) * cfg.num_layers
        self.cache_rows = (attn.rows,) * cfg.num_layers  # K, V
        self.row_values = (2 * cfg.num_kv_heads * cfg.head_dim,) * cfg.num_layers
        self.max_len = cfg.max_len
        self.layer_windows = tuple(cfg.layer_windows)
        self.stats_len = len(cfg.held_experts) + 2

    def count_stats(self, registry, stats, *, decode: bool) -> None:
        """Book one step's fetched ``stats`` (``block``'s, summed over
        the layers) into the registry's expert counters."""
        _count_expert_stats(registry, self.cfg.held_experts, stats, decode)

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


def _rms_norm(x, scale, eps):
    """``x / sqrt(mean(x^2) + eps) * scale``, float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps
    ) * scale.astype(jnp.float32)


def _untied_head(params, x, eps):
    """The final RMS norm, then the untied head ``[d, vocab]``."""
    kernel = params["lm_head"]["kernel"]
    x = _rms_norm(x, params["ln_f"]["scale"], eps)
    return jnp.dot(
        x.astype(kernel.dtype), kernel, preferred_element_type=jnp.float32
    )


def _swiglu(x, p):
    """One SwiGLU FFN on ``x`` [n, d] in the weights' dtype; float32 out."""
    f32 = dict(preferred_element_type=jnp.float32)
    g = jnp.dot(x, p["w_gate"], **f32)
    u = jnp.dot(x, p["w_up"], **f32)
    return jnp.dot(
        (jax.nn.silu(g) * u).astype(p["w_down"].dtype), p["w_down"], **f32
    )


@functools.lru_cache(maxsize=None)
def _record_mla_plan(family, queries, context, dtype, head_group, rows):
    """One ``span/mla_plan`` per traced shape, so a run's record says
    which attention form each program family ran, how the heads were
    grouped and how wide the row's arrays are as stored (at trace time,
    never inside a step)."""
    with span(
        schema.MLA_PLAN_SPAN, family=family, queries=queries, context=context,
        dtype=dtype, form="absorbed", head_group=head_group,
        rows=list(rows),
    ):
        pass


class Glm4MoeLiteBlock:
    """GLM-4.7-Flash's block over ``models/glm4_moe_lite.py``'s param
    tree. The residual stream, the RMS norms and the router are
    float32; the projections, the attention products and the experts
    run in the parameters' dtype with float32 accumulation.

    **The cache row is the block's**: ``[c_kv | k_pe]`` after the norm
    and the rotation, ``kv_lora_rank + qk_rope_head_dim`` values a
    token a layer (``row_values``), no heads, no V, stored in ONE array
    zero-padded to whole 128-lane tiles (``cache_rows``: 576 values in
    640 columns; ``kv_cache`` says why). ``block`` hands it to
    ``attend`` behind the queries, the forward writes it, and the
    attention over rows is this class's: ``chunk_attention`` (a prompt
    chunk over itself and the cached context) and ``decode_attention``
    (one query a slot through a block table), both absorbed
    (``kv_cache``: every head reads the row as it lies).

    ``block`` also returns the step's counts, ``stats_len`` int32
    values summed over the layers by the forward: the expert counts of
    :class:`Cohere2MoeBlock` (the dense layers add none), then the query
    tokens that went through the latent attention."""

    name = "glm4_moe_lite"
    own_attention = True
    # Why each mechanism the engine refuses this block cannot serve it.
    refused = {
        "verify": "its verify forward is GPT-2's: K and V rows of equal "
                  "heads, not a latent row",
        "pages": "a page payload carries K and V with heads, not a latent "
                 "row",
        "kv_dtype": "a latent row has no heads to keep a scale for",
        "weights": "the block reads its weights as stored",
        "paged_flash": "the kernel reads K and V rows of equal heads, not a "
                       "latent row shared by every head",
        "flash": "the kernel takes K and V with heads; a latent row is "
                 "attended by the block itself",
        "sharding": "the placement rules are GPT-2's, and a latent row has "
                    "no heads axis to shard",
    }
    def __init__(self, cfg: Glm4MoeLiteConfig):
        self.cfg = cfg
        self.num_layers = cfg.num_layers
        self.num_heads = cfg.num_heads
        # One latent row a token, shared by every head, in every layer.
        self.row_values = (cfg.latent_dim,) * cfg.num_layers
        self.stored_width = kv_cache.lane_dense(cfg.latent_dim)
        self.cache_rows = (((1, self.stored_width),),) * cfg.num_layers
        self.max_len = cfg.max_len
        self.layer_windows = (None,) * cfg.num_layers
        self.stats_len = len(cfg.held_experts) + 3
        self.sm_scale = cfg.qk_head_dim ** -0.5

    def count_stats(self, registry, stats, *, decode: bool) -> None:
        """Book one step's fetched ``stats`` into the registry: the
        expert counters, and the query tokens through the latent
        attention (every layer counted them: one layer's share is
        booked)."""
        held = self.cfg.held_experts
        _count_expert_stats(registry, held, stats, decode)
        registry.counter(schema.LATENT_ATTENTION_TOKENS).inc(
            int(stats[len(held) + 2]) // self.num_layers
        )

    def param_dtype(self, params):
        return params["wte"]["embedding"].dtype

    def embed(self, params, tokens, positions):
        del positions  # rotary: q_pe and k_pe turn inside the block
        return params["wte"]["embedding"][tokens].astype(jnp.float32)

    # ------------------------------------------------ attention over rows

    def _up(self, params, layer):
        """``W_kvb`` of one layer as ``(W_uk [dc, H, dn], W_uv [dc, H,
        dv])``."""
        kv_b = params[f"h_{layer}"]["attn"]["kv_b"]
        dn = self.cfg.qk_nope_head_dim
        return kv_b[..., :dn], kv_b[..., dn:]

    def _split(self, q):
        dn = self.cfg.qk_nope_head_dim
        return q[..., :dn], q[..., dn:]

    def chunk_attention(self, params, layer, q, row, ctx_rows=None,
                        ctx_len=0):
        """A prompt chunk: q [T, H, dn + dr], its own rows ``row`` [T,
        1, W] as stored, the cached context ``ctx_rows`` [C, 1, W]
        (first ``ctx_len`` populated) or None. Returns [T, H, dv]."""
        t_n = q.shape[0]
        cols = t_n + (0 if ctx_rows is None else ctx_rows.shape[0])
        _record_mla_plan(
            "extend" if ctx_rows is not None else "prefill", t_n, cols,
            str(q.dtype),
            kv_cache.latent_head_group(self.num_heads, t_n, cols),
            row.shape[-1:],
        )
        with jax.named_scope("attn_latent_absorbed"):
            return kv_cache.latent_chunk_attention(
                *self._split(q), row[:, 0], *self._up(params, layer),
                None if ctx_rows is None else ctx_rows[:, 0],
                ctx_len=ctx_len, sm_scale=self.sm_scale,
            )

    def decode_attention(self, params, layer, q, blocks, positions, table):
        """A decode step: q [S, H, dn + dr] at ``positions`` over one
        layer's pool ``blocks`` [NB, BS, W] through ``table`` [S, nb].
        Returns [S, H, dv]."""
        _record_mla_plan(
            "decode", q.shape[0], table.shape[1] * blocks.shape[1],
            str(q.dtype), self.num_heads, blocks.shape[-1:],
        )
        with jax.named_scope("attn_latent_absorbed"):
            return kv_cache.latent_decode_attention(
                *self._split(q), blocks, positions, table,
                *self._up(params, layer), sm_scale=self.sm_scale,
            )

    # -------------------------------------------------------------- block

    def block(self, params, x, layer, positions, attend, valid=None):
        cfg, p = self.cfg, params[f"h_{layer}"]
        a, eps, dc = p["attn"], cfg.rms_norm_eps, cfg.kv_lora_rank
        dtype = a["q_a"].dtype
        f32 = dict(preferred_element_type=jnp.float32)
        hb = _rms_norm(x, p["ln_1"]["scale"], eps).astype(dtype)
        c_q = _rms_norm(
            jnp.dot(hb, a["q_a"], **f32), a["q_ln"]["scale"], eps
        ).astype(dtype)
        q_nope, q_pe = self._split(
            jnp.einsum("...r,rhc->...hc", c_q, a["q_b"], **f32)
        )
        q = jnp.concatenate(
            [q_nope, rope_interleaved(q_pe, positions, cfg.rope_theta)],
            axis=-1,
        ).astype(dtype)
        kv = jnp.dot(hb, a["kv_a"], **f32)
        c_kv = _rms_norm(kv[..., :dc], a["kv_ln"]["scale"], eps)
        k_pe = rope_interleaved(
            kv[..., None, dc:], positions, cfg.rope_theta
        )
        # What the token leaves in the cache, and nothing else: the pad
        # columns are written as zeros.
        row = kv_cache.pad_columns(
            jnp.concatenate([c_kv[..., None, :], k_pe], axis=-1),
            self.stored_width,
        ).astype(dtype)
        att = attend(q, row)
        x = x + jnp.einsum(
            "...hc,hcd->...d", att.astype(dtype), a["o"], **f32
        )

        h = _rms_norm(x, p["ln_2"]["scale"], eps)
        flat = h.reshape(-1, h.shape[-1])
        rows = None if valid is None else jnp.broadcast_to(
            valid, x.shape[:-1]
        ).reshape(-1)
        n_real = flat.shape[0] if rows is None else jnp.sum(rows)
        n_held = len(cfg.held_experts)
        if "mlp" in p:
            with jax.named_scope("ffn_dense"):
                y = _swiglu(flat.astype(dtype), p["mlp"])
            pairs = jnp.zeros((n_held,), jnp.int32)
            routed = 0
        else:
            moe = p["moe"]
            part, pairs = moe_ffn_held(
                moe["router"], moe["w_gate"], moe["w_up"], moe["w_down"],
                flat, held=tuple(cfg.held_experts), top_k=cfg.top_k,
                valid=rows, select_bias=moe["bias"],
                scale=cfg.routed_scale,
            )
            with jax.named_scope("moe_shared"):
                y = part + _swiglu(flat.astype(dtype), p["shared"])
            routed = n_real * cfg.top_k
        stats = jnp.concatenate([
            pairs,
            jnp.stack([routed, jnp.sum(pairs > 0), n_real]).astype(jnp.int32),
        ])
        return x + y.reshape(x.shape), stats

    def head(self, params, x):
        return _untied_head(params, x, self.cfg.rms_norm_eps)

    def last_logits(self, params, x, index):
        """Logits of row ``index`` of ``x`` [T, d]: only that row meets
        the vocabulary."""
        return self.head(
            params, jax.lax.dynamic_index_in_dim(x, index, keepdims=False)
        )


def rope_half(x, positions, theta: float, rotary_dim: int):
    """Rotate the FIRST ``rotary_dim`` dimensions of ``x`` [..., heads,
    D] in rotate-half pairs: ``(x[i], x[i + rotary_dim / 2])`` turns by
    ``positions * theta ** (-2i / rotary_dim)``; the other dimensions
    carry no position. ``positions`` carries x's leading axes (or
    broadcasts against them from the right). Float32 out."""
    half = rotary_dim // 2
    inv_freq = theta ** (
        -jnp.arange(0, rotary_dim, 2, dtype=jnp.float32) / rotary_dim
    )
    ang = positions.astype(jnp.float32)[..., None, None] * inv_freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x = x.astype(jnp.float32)
    x0, x1 = x[..., :half], x[..., half:rotary_dim]
    return jnp.concatenate(
        [x0 * cos - x1 * sin, x1 * cos + x0 * sin, x[..., rotary_dim:]],
        axis=-1,
    )


class MimoV2Block:
    """MiMo-V2.5's block over ``models/mimo_v2.py``'s param tree. The
    residual stream, the RMS norms, the rotation and the router are
    float32; the projections and the experts run in the parameters'
    dtype with float32 accumulation.

    A full and a window layer keep rows of different shapes (K and V of
    the kind's own KV heads, a key head wider than a value head:
    ``cache_rows`` / ``layer_attention`` per layer), rotate the first
    ``rotary_dim`` dimensions of q and k with the kind's base, and the
    window layers hand the engine a sink logit a head (``sinks``). The
    values are scaled before they are cached: what a row holds is what
    attention reads.

    ``block`` also returns the step's expert counts, as
    :class:`Cohere2MoeBlock` does (a dense layer adds none)."""

    name = "mimo_v2"
    own_attention = False
    # Why each mechanism the engine refuses this block cannot serve it.
    refused = {
        "verify": "its verify forward is GPT-2's",
        "pages": "a page payload has one block-id space, one row shape "
                 "and equal heads",
        "kv_dtype": "the grouped-query gather does not dequantize, and a "
                    "scale a head has no one head count to go by",
        "weights": "the block reads its weights as stored",
        "paged_flash": "the kernel reads K and V rows of equal heads and "
                       "one width through one table, and knows no sink",
        "flash": "no grouped-query or window mask, no sink, one head "
                 "width for keys and values",
        "sharding": "the placement rules are GPT-2's",
    }

    def __init__(self, cfg: MimoV2Config):
        self.cfg = cfg
        self.num_layers = cfg.num_layers
        self.max_len = cfg.max_len
        self.layer_windows = tuple(cfg.layer_windows)
        self.layer_attention = tuple(
            LayerAttention(
                a.num_heads, a.num_kv_heads, a.head_dim, a.v_head_dim,
                a.head_dim ** -0.5, a.sink,
            ) for a in map(cfg.attention, range(cfg.num_layers))
        )
        self.cache_rows = tuple(a.rows for a in self.layer_attention)
        self.row_values = tuple(
            a.kv_heads * (a.key_dim + a.value_dim)
            for a in self.layer_attention
        )
        self.stats_len = len(cfg.held_experts) + 2

    def count_stats(self, registry, stats, *, decode: bool) -> None:
        """Book one step's fetched ``stats`` (``block``'s, summed over
        the layers) into the registry's expert counters."""
        _count_expert_stats(registry, self.cfg.held_experts, stats, decode)

    def param_dtype(self, params):
        return params["wte"]["embedding"].dtype

    def embed(self, params, tokens, positions):
        del positions  # rotary: q and k turn inside the block
        return params["wte"]["embedding"][tokens].astype(jnp.float32)

    def sinks(self, params, layer):
        """The sink logits [H] float32 of a layer whose
        ``layer_attention`` says it has them."""
        return params[f"h_{layer}"]["attn"]["sinks"]

    def block(self, params, x, layer, positions, attend, valid=None):
        cfg, p = self.cfg, params[f"h_{layer}"]
        a, kind, eps = p["attn"], cfg.attention(layer), cfg.rms_norm_eps
        dtype = a["q"].dtype
        f32 = dict(preferred_element_type=jnp.float32)
        hb = _rms_norm(x, p["ln_1"]["scale"], eps).astype(dtype)
        q, k, v = (
            jnp.einsum("...d,dhc->...hc", hb, a[n], **f32) for n in "qkv"
        )
        q, k = (
            rope_half(t, positions, kind.rope_theta, cfg.rotary_dim)
            .astype(dtype) for t in (q, k)
        )
        v = (cfg.value_scale * v).astype(dtype)
        window = self.layer_windows[layer]
        with jax.named_scope("attn_full" if window is None else "attn_window"):
            att = attend(q, k, v)
        x = x + jnp.einsum(
            "...hc,hcd->...d", att.astype(dtype), a["o"], **f32
        )

        h = _rms_norm(x, p["ln_2"]["scale"], eps)
        flat = h.reshape(-1, h.shape[-1])
        n_held = len(cfg.held_experts)
        if "mlp" in p:
            with jax.named_scope("ffn_dense"):
                y = _swiglu(flat.astype(dtype), p["mlp"])
            pairs = jnp.zeros((n_held,), jnp.int32)
            routed = 0
        else:
            rows = None if valid is None else jnp.broadcast_to(
                valid, x.shape[:-1]
            ).reshape(-1)
            moe = p["moe"]
            y, pairs = moe_ffn_held(
                moe["router"], moe["w_gate"], moe["w_up"], moe["w_down"],
                flat, held=tuple(cfg.held_experts), top_k=cfg.top_k,
                valid=rows, select_bias=moe["bias"],
            )
            n_real = flat.shape[0] if rows is None else jnp.sum(rows)
            routed = n_real * cfg.top_k
        stats = jnp.concatenate([
            pairs,
            jnp.stack([routed, jnp.sum(pairs > 0)]).astype(jnp.int32),
        ])
        return x + y.reshape(x.shape), stats

    def head(self, params, x):
        return _untied_head(params, x, self.cfg.rms_norm_eps)

    def last_logits(self, params, x, index):
        """Logits of row ``index`` of ``x`` [T, d]: only that row meets
        the vocabulary."""
        return self.head(
            params, jax.lax.dynamic_index_in_dim(x, index, keepdims=False)
        )


def block_for(model_cfg):
    """The block that serves ``model_cfg``, by the config's type."""
    if isinstance(model_cfg, MimoV2Config):
        return MimoV2Block(model_cfg)
    if isinstance(model_cfg, Glm4MoeLiteConfig):
        return Glm4MoeLiteBlock(model_cfg)
    if isinstance(model_cfg, Cohere2MoeConfig):
        return Cohere2MoeBlock(model_cfg)
    if isinstance(model_cfg, TransformerConfig):
        return Gpt2Block(model_cfg)
    raise TypeError(
        f"no serving block for a {type(model_cfg).__name__}: the engine "
        "serves TransformerConfig (GPT-2), Cohere2MoeConfig, "
        "Glm4MoeLiteConfig and MimoV2Config"
    )
