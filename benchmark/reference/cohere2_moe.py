"""Plain reference of Cohere2-MoE (Command A+, ``cohere2_moe``): one
chip's share of the model, in ``jax.numpy``, float32, matrix products
at ``highest`` precision (on a TPU a float32 product otherwise runs as
one bfloat16 pass). No cache, no kernels, no batching. Written from the
published ``config.json`` keys (the configuration file's), independent
of ``tensorflow_examples_tpu/serving`` and ``parallel/moe.py``; only the
parameter tree's names are the program's (``models/cohere2_moe.py``).

For layer input ``x`` (``use_parallel_block``):

    h  = LN(x)                     mean-subtracting, scale, no bias, eps layer_norm_eps
    q  = h Wq  (H heads of D);  k = h Wk, v = h Wv  (G heads of D);  query head i reads KV head i // (H/G)
    a  = concat_i softmax_j(q_i . k_j / sqrt(D)) v_j  Wo
         sliding layer: q, k rotated over all D in interleaved pairs (rope_gptj, rope_theta),
                        query i sees keys j with 0 <= i - j < sliding_window
         full layer:    no positions, causal
    s  = sigmoid(h Wr)  over num_experts;  the num_experts_per_tok largest;  w_e = s_e / sum of the chosen
    E(h) = Wdown (silu(Wgate h) * (Wup h))
    m  = sum_{e chosen AND held here} w_e E_e(h)  +  mean_s S_s(h)   over the shared experts
    x' = x + a + m
    logits = logit_scale * LN(x_last) Wemb^T        over the rows of the vocabulary held here

Departures from the published description, each the configuration
file's (``assumed`` / ``reduced`` / ``deployment``):

* only the experts in ``held`` add to the routed sum (the chip's share
  of a layer that several chips share); the router is at full width;
* the vocabulary is the slice held here;
* the expert width is ``intermediate_size``; "average" is the mean of
  the shared experts' outputs, added to the routed sum; full layers
  carry no positions; the window counts the query's own position.

It runs beside a serving engine that fills most of the chip, so it
works in pieces: one layer at a time under ``jit``, the experts cast to
float32 one at a time (``lax.map``), queries in blocks and KV heads one
group at a time. ``forward`` also reports, per row asked for, how close
the router came to choosing otherwise for an expert held here
(``route_gap``: the distance of the nearest held expert's router logit
from the line between chosen and unchosen). A lower-precision run may
put such an expert on the other side, the row's hidden state then
differs by a whole expert's output and later layers' routing with it,
and the comparison that reads this must know.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

NEG = -1e30


def sizes(config: dict) -> dict:
    """The sizes the reference needs, from a configuration file's keys
    (the published ones, the experts ``held`` here, ``vocab_size`` as
    held here)."""
    kinds = {"sliding_attention": int(config["sliding_window"]), "full_attention": None}
    return dict(
        heads=int(config["num_attention_heads"]), kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]), eps=float(config["layer_norm_eps"]),
        theta=float(config["rope_theta"]), logit_scale=float(config["logit_scale"]),
        top_k=int(config["num_experts_per_tok"]), shared=int(config["num_shared_experts"]),
        windows=tuple(kinds[t] for t in config["layer_types"][: int(config["num_hidden_layers"])]),
        held=tuple(int(e) for e in config["held_experts"]),
    )


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _int8_round(w):
    """The nearest precision below bfloat16 that serving stacks use for
    weights: int8 levels with one scale per output channel (the last
    axis). Only to set ``correct``'s limits: such a run must fail."""
    s = jnp.max(jnp.abs(w), axis=tuple(range(w.ndim - 1)), keepdims=True) / 127.0
    return jnp.round(w / jnp.maximum(s, 1e-30)) * s


def _norm(x, scale, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale


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


@functools.partial(jax.jit, static_argnames=("window", "sz", "q_block", "weights"))
def _layer(x, p, *, window, sz, q_block, weights):
    """One layer on ``x`` [T, d] float32 (T a multiple of ``q_block``).
    Returns ``(x', route_gap [T])``."""
    sz = dict(sz)
    w = (lambda a: _int8_round(_f32(a))) if weights == "int8" else _f32
    t_n = x.shape[0]
    h_n, g_n, d_h = sz["heads"], sz["kv_heads"], sz["head_dim"]
    pos = jnp.arange(t_n)
    h = _norm(x, _f32(p["ln"]["scale"]), sz["eps"])

    k = jnp.einsum("td,dgc->tgc", h, w(p["attn"]["k"]))
    v = jnp.einsum("td,dgc->tgc", h, w(p["attn"]["v"]))
    if window is not None:
        k = _rope(k, pos, sz["theta"])
    wq, wo = w(p["attn"]["q"]), w(p["attn"]["o"])

    def query_block(start):
        hb = jax.lax.dynamic_slice_in_dim(h, start, q_block)
        qpos = start + jnp.arange(q_block)
        q = jnp.einsum("td,dhc->thc", hb, wq)
        if window is not None:
            q = _rope(q, qpos, sz["theta"])
        seen = pos[None, :] <= qpos[:, None]
        if window is not None:
            seen &= qpos[:, None] - pos[None, :] < window

        def group(args):
            qg, kg, vg = args  # [R, qb, D], [T, D], [T, D]
            s = jnp.einsum("rtd,kd->rtk", qg, kg) / np.sqrt(d_h)
            prob = jax.nn.softmax(jnp.where(seen[None], s, NEG), axis=-1)
            return jnp.einsum("rtk,kd->rtd", prob, vg)

        out = jax.lax.map(group, (
            jnp.moveaxis(q.reshape(q_block, g_n, h_n // g_n, d_h), (1, 2), (0, 1)),
            jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0),
        ))  # [G, R, qb, D]
        att = jnp.moveaxis(out, 2, 0).reshape(q_block, h_n, d_h)
        return jnp.einsum("thc,hcd->td", att, wo)

    a = jax.lax.map(query_block, jnp.arange(0, t_n, q_block)).reshape(t_n, -1)

    # Router over every published expert; the chosen and their weights.
    logit = h @ _f32(p["moe"]["router"])
    top_l, top_e = jax.lax.top_k(logit, sz["top_k"] + 1)
    chosen_e = top_e[:, : sz["top_k"]]
    chosen_s = jax.nn.sigmoid(top_l[:, : sz["top_k"]])
    weight = chosen_s / jnp.sum(chosen_s, axis=-1, keepdims=True)
    held = jnp.asarray(sz["held"], jnp.int32)
    # How far the nearest HELD expert's logit lies from the line between chosen
    # and unchosen (midway between the last chosen and the first unchosen logit).
    edge = (top_l[:, -2] + top_l[:, -1])[:, None] / 2
    route_gap = jnp.min(jnp.abs(logit[:, held] - edge), axis=-1) if len(sz["held"]) \
        else jnp.full((t_n,), jnp.inf)

    def held_expert(args):
        expert, wg, wu, wd = args
        mine = jnp.sum(jnp.where(chosen_e == expert, weight, 0.0), axis=-1)
        return mine[:, None] * _swiglu(h, w(wg), w(wu), w(wd))

    moe = p["moe"]
    routed = jnp.zeros_like(x)
    if len(sz["held"]):
        routed = jax.lax.scan(
            lambda acc, args: (acc + held_expert(args), None), routed,
            (held, moe["w_gate"], moe["w_up"], moe["w_down"]),
        )[0]
    sh = p["shared"]
    shared = jax.lax.scan(
        lambda acc, ws: (acc + _swiglu(h, *map(w, ws)), None), jnp.zeros_like(x),
        (sh["w_gate"], sh["w_up"], sh["w_down"]),
    )[0] / sz["shared"]
    return x + a + routed + shared, route_gap


def forward(params, tokens, config: dict, *, rows, pad_to: int | None = None,
            q_block: int = 512, weights: str | None = None):
    """Logits of ``tokens`` (a list of ids) at the positions ``rows``.

    Returns ``(logits [len(rows), V] float64 numpy, route_gap
    [len(rows)])``: per row, the smallest distance over the layers of a
    held expert's router logit from the line between chosen and
    unchosen (``inf`` where nothing is held). ``pad_to`` pads the sequence (causal: padding behind is
    inert) so that several lengths share one compiled shape;
    ``weights="int8"`` rounds every matrix to int8 levels first."""
    sz = sizes(config)
    n = len(tokens)
    t_n = -(-max(pad_to or n, n) // q_block) * q_block
    ids = np.zeros((t_n,), np.int32)
    ids[:n] = tokens
    frozen = tuple(sorted(sz.items()))
    with jax.default_matmul_precision("highest"):
        x = _f32(params["wte"]["embedding"][jnp.asarray(ids)])
        gap = jnp.full((t_n,), jnp.inf)
        for layer, window in enumerate(sz["windows"]):
            x, g = _layer(x, params[f"h_{layer}"], window=window, sz=frozen,
                          q_block=q_block, weights=weights)
            gap = jnp.minimum(gap, g)
        rows = jnp.asarray(list(rows), jnp.int32)
        last = _norm(x[rows], _f32(params["ln_f"]["scale"]), sz["eps"])
        wte = _f32(params["wte"]["embedding"])
        if weights == "int8":
            wte = _int8_round(wte.T).T
        logits = sz["logit_scale"] * (last @ wte.T)
    return np.asarray(logits).astype(np.float64), np.asarray(gap[rows])


def layer_parts(params, tokens, config: dict, layer: int, *, q_block: int = 8):
    """For the test that the shares add up: ``(x, x')`` of one layer on
    the embedded ``tokens``, as this share computes it."""
    sz = sizes(config)
    with jax.default_matmul_precision("highest"):
        x = _f32(params["wte"]["embedding"][jnp.asarray(tokens)])
        out, _ = _layer(x, params[f"h_{layer}"], window=sz["windows"][layer],
                        sz=tuple(sorted(sz.items())), q_block=q_block, weights=None)
    return np.asarray(x, np.float64), np.asarray(out, np.float64)
