"""Plain reference of GLM-4.7-Flash (``glm4_moe_lite``): the model's first
layers, in ``jax.numpy``, float32, matrix products at ``highest``
precision (on a TPU a float32 product otherwise runs as one bfloat16
pass). The EXPANDED attention only: keys and values of every head made
from the latent, no cache, no absorption, no kernels, no batching.
Written from the published ``config.json`` keys (the configuration
file's), independent of ``tensorflow_examples_tpu/serving`` and
``parallel/moe.py``; only the parameter tree's names are the program's
(``models/glm4_moe_lite.py``).

For layer input ``x`` (sequential, pre-norm; RMS(x) = x / sqrt(mean(x^2) + rms_norm_eps) * scale):

    h   = RMS(x)
    c_q = RMS(h Wqa)                       q_lora_rank
    q   = c_q Wqb  -> H heads of [q_nope (qk_nope_head_dim) | q_pe (qk_rope_head_dim)],  q_pe rotated
    [c_kv | k_pe] = h Wkva                 kv_lora_rank + qk_rope_head_dim
    c_kv = RMS(c_kv);  k_pe rotated, ONE head shared by all H
    [k_nope | v] = c_kv Wkvb               per head: qk_nope_head_dim + v_head_dim
    a   = concat_h softmax_j((q_nope_h . k_nope_hj + q_pe_h . k_pe_j) / sqrt(nope + rope)) v_hj  Wo     causal
    x   = x + a
    h   = RMS(x)
    layer < first_k_dense_replace:   m = Wdown (silu(Wgate h) * (Wup h))            width intermediate_size
    else:  s = sigmoid(h Wr) over n_routed_experts (float32)
           chosen = the num_experts_per_tok largest of s + e_score_correction_bias   (noaux_tc, n_group 1)
           w_e = s_e / (sum of the chosen s + 1e-20) * routed_scaling_factor         (the bias is NOT in the weight)
           m = sum_{e chosen AND held here} w_e E_e(h)  +  S(h)                       one shared expert, unweighted
    x'  = x + m
    logits = RMS(x_last) Whead             untied head over the whole vocabulary

Departures from the published description, each the configuration
file's (``assumed`` / ``reduced`` / ``deployment``):

* the layers are the model's first ``num_hidden_layers``; the
  multi-token-prediction layer is not computed;
* rotary pairs are interleaved (``(x[2i], x[2i+1])``): the published
  code's half-split is a fixed permutation of columns of random
  ``Wqb`` / ``Wkva``;
* only the experts in ``held_experts`` (all of them, in the benchmark's
  configuration) add to the routed sum; the router is at full width.

It runs beside a serving engine that fills most of the chip, so it
never holds scores for more than one block of queries and one head at a
time. ``forward`` also reports, per row asked for, how close the router
came to choosing otherwise for an expert held here (``route_gap``: the
distance of the nearest held expert's BIASED score from the line
between chosen and unchosen, smallest over the layers). A
lower-precision run may put such an expert on the other side, the row's
hidden state then differs by a whole expert's output, and the
comparison that reads this must know.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

NEG = -1e30
HEAD_SLICES = 8


def sizes(config: dict) -> dict:
    """The sizes the reference needs, from a configuration file's keys."""
    held = config.get("held_experts")
    return dict(
        dc=int(config["kv_lora_rank"]),
        dn=int(config["qk_nope_head_dim"]), dr=int(config["qk_rope_head_dim"]),
        eps=float(config["rms_norm_eps"]), theta=float(config["rope_theta"]),
        top_k=int(config["num_experts_per_tok"]), scale=float(config["routed_scaling_factor"]),
        layers=int(config["num_hidden_layers"]),
        held=tuple(range(int(config["n_routed_experts"])) if held is None
                   else (int(e) for e in held)),
    )


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _int8_round(w):
    """The nearest precision below bfloat16 that serving stacks use for
    weights: int8 levels with one scale per output channel (the last
    axis). Only to set ``correct``'s limits: such a run must fail."""
    s = jnp.max(jnp.abs(w), axis=tuple(range(w.ndim - 1)), keepdims=True) / 127.0
    return jnp.round(w / jnp.maximum(s, 1e-30)) * s


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * _f32(scale)


def _rope(x, positions, theta):
    """[T, heads, D], interleaved pairs: (x[2i], x[2i+1]) turned by
    positions * theta ** (-2i / D)."""
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None, None] * inv_freq
    x0, x1 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x0 * jnp.cos(ang) - x1 * jnp.sin(ang),
                     x1 * jnp.cos(ang) + x0 * jnp.sin(ang)], axis=-1)
    return out.reshape(x.shape)


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


@functools.partial(jax.jit, static_argnames=("sz", "q_block", "weights"))
def _layer(x, p, *, sz, q_block, weights):
    """One layer on ``x`` [T, d] float32 (T a multiple of ``q_block``).
    Returns ``(x', route_gap [T])``."""
    sz = dict(sz)
    w = (lambda a: _int8_round(_f32(a))) if weights == "int8" else _f32
    t_n = x.shape[0]
    dc, dn = sz["dc"], sz["dn"]
    pos = jnp.arange(t_n)
    a = p["attn"]
    h = _rms(x, p["ln_1"]["scale"], sz["eps"])

    kv = h @ w(a["kv_a"])
    c_kv = _rms(kv[:, :dc], a["kv_ln"]["scale"], sz["eps"])
    k_pe = _rope(kv[:, None, dc:], pos, sz["theta"])[:, 0]          # [T, dr]: one head
    kv_b = w(a["kv_b"])
    k_nope = jnp.einsum("tc,chn->htn", c_kv, kv_b[..., :dn])          # expanded, per head
    v = jnp.einsum("tc,chv->htv", c_kv, kv_b[..., dn:])
    wqa, wqb, wo = w(a["q_a"]), w(a["q_b"]), w(a["o"])
    sm_scale = 1.0 / np.sqrt(dn + sz["dr"])

    def query_block(start):
        hb = jax.lax.dynamic_slice_in_dim(h, start, q_block)
        qpos = start + jnp.arange(q_block)
        q = jnp.einsum("tr,rhc->thc", _rms(hb @ wqa, a["q_ln"]["scale"], sz["eps"]), wqb)
        q_nope, q_pe = q[..., :dn], _rope(q[..., dn:], qpos, sz["theta"])
        seen = pos[None, :] <= qpos[:, None]

        def head(args):
            qn, qp, kn, vh = args  # [qb, dn], [qb, dr], [T, dn], [T, dv]
            s = (qn @ kn.T + qp @ k_pe.T) * sm_scale
            return jax.nn.softmax(jnp.where(seen, s, NEG), axis=-1) @ vh

        att = jax.lax.map(head, (jnp.moveaxis(q_nope, 1, 0), jnp.moveaxis(q_pe, 1, 0),
                                 k_nope, v))  # [H, qb, dv]
        return jnp.einsum("htv,hvd->td", att, wo)

    x = x + jax.lax.map(query_block, jnp.arange(0, t_n, q_block)).reshape(t_n, -1)
    h = _rms(x, p["ln_2"]["scale"], sz["eps"])
    if "mlp" in p:
        # a block of rows at a time: [T, intermediate_size] float32 is 1.2 GB at 30k rows
        m = tuple(w(p["mlp"][n]) for n in ("w_gate", "w_up", "w_down"))
        dense = jax.lax.map(lambda hb: _swiglu(hb, *m), h.reshape(-1, q_block, h.shape[-1]))
        return x + dense.reshape(x.shape), jnp.full((t_n,), jnp.inf)

    # Router over every published expert: chosen by the biased score, weighed by the plain one.
    moe = p["moe"]
    score = jax.nn.sigmoid(h @ _f32(moe["router"]))
    biased = score + _f32(moe["bias"])
    top_b, top_e = jax.lax.top_k(biased, sz["top_k"] + 1)
    chosen_e = top_e[:, : sz["top_k"]]
    chosen_s = jnp.take_along_axis(score, chosen_e, axis=-1)
    weight = chosen_s / (jnp.sum(chosen_s, axis=-1, keepdims=True) + 1e-20) * sz["scale"]
    held = jnp.asarray(sz["held"], jnp.int32)
    # How far the nearest HELD expert's biased score lies from the line between chosen
    # and unchosen (midway between the last chosen and the first unchosen).
    edge = (top_b[:, -2] + top_b[:, -1])[:, None] / 2
    route_gap = jnp.min(jnp.abs(biased[:, held] - edge), axis=-1) if len(sz["held"]) \
        else jnp.full((t_n,), jnp.inf)

    def held_expert(args):
        expert, wg, wu, wd = args
        mine = jnp.sum(jnp.where(chosen_e == expert, weight, 0.0), axis=-1)
        return mine[:, None] * _swiglu(h, w(wg), w(wu), w(wd))

    routed = jnp.zeros_like(x)
    if len(sz["held"]):
        routed = jax.lax.scan(
            lambda acc, args: (acc + held_expert(args), None), routed,
            (held, moe["w_gate"], moe["w_up"], moe["w_down"]),
        )[0]
    sh = p["shared"]
    shared = _swiglu(h, w(sh["w_gate"]), w(sh["w_up"]), w(sh["w_down"]))
    return x + routed + shared, route_gap


def forward(params, tokens, config: dict, *, rows, pad_to: int | None = None,
            q_block: int = 512, weights: str | None = None):
    """Logits of ``tokens`` (a list of ids) at the positions ``rows``.

    Returns ``(logits [len(rows), V] float64 numpy, route_gap
    [len(rows)])``: per row, the smallest distance over the layers of a
    held expert's biased router score from the line between chosen and
    unchosen (``inf`` where nothing is held). ``pad_to`` pads the
    sequence (causal: padding behind is inert) so that several lengths
    share one compiled shape; ``weights="int8"`` rounds every matrix to
    int8 levels first."""
    sz = sizes(config)
    n = len(tokens)
    t_n = -(-max(pad_to or n, n) // q_block) * q_block
    ids = np.zeros((t_n,), np.int32)
    ids[:n] = tokens
    frozen = tuple(sorted(sz.items()))
    with jax.default_matmul_precision("highest"):
        x = _f32(params["wte"]["embedding"][jnp.asarray(ids)])
        gap = jnp.full((t_n,), jnp.inf)
        for layer in range(sz["layers"]):
            x, g = _layer(x, params[f"h_{layer}"], sz=frozen, q_block=q_block, weights=weights)
            gap = jnp.minimum(gap, g)
        rows = jnp.asarray(list(rows), jnp.int32)
        last = _rms(x[rows], params["ln_f"]["scale"], sz["eps"])
        # The head a slice of the vocabulary at a time: whole, its float32
        # copy is 1.3 GB beside an engine that fills most of the chip.
        kernel = params["lm_head"]["kernel"]
        w = (lambda a: _int8_round(_f32(a))) if weights == "int8" else _f32
        step = -(-kernel.shape[1] // HEAD_SLICES)
        logits = np.concatenate([
            np.asarray(last @ w(kernel[:, i:i + step])).astype(np.float64)
            for i in range(0, kernel.shape[1], step)
        ], axis=-1)
    return logits, np.asarray(gap[rows])


def layer_parts(params, tokens, config: dict, layer: int, *, q_block: int = 8):
    """For the test that the shares add up: ``(x, x')`` of one layer on
    the embedded ``tokens``, as this share computes it."""
    sz = sizes(config)
    with jax.default_matmul_precision("highest"):
        x = _f32(params["wte"]["embedding"][jnp.asarray(tokens)])
        out, _ = _layer(x, params[f"h_{layer}"], sz=tuple(sorted(sz.items())),
                        q_block=q_block, weights=None)
    return np.asarray(x, np.float64), np.asarray(out, np.float64)
