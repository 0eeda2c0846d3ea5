"""Plain reference of MiMo-V2.5 (``mimo_v2``): one chip's share of the
first layers of the model, in ``jax.numpy``, float32, matrix products
at ``highest`` precision (on a TPU a float32 product otherwise runs as
one bfloat16 pass). No cache, no kernels, no batching. Written from the
published ``config.json`` keys (the configuration file's), independent
of ``tensorflow_examples_tpu/serving`` and ``parallel/moe.py``; only the
parameter tree's names are the program's (``models/mimo_v2.py``).

For layer ``l`` of kind ``c`` (``hybrid_layer_pattern[l]``: 0 full, 1
window), with ``H, G_c, D, Dv`` its query heads, KV heads, key width and
value width (the ``swa_*`` keys for a window layer), on input ``x``:

    h  = rms_norm(x, w1, layernorm_epsilon)
    q  = h Wq  [H, D];   k = h Wk  [G_c, D];   v = attention_value_scale * (h Wv)  [G_c, Dv]
    q, k: the FIRST int(D * partial_rotary_factor) dimensions turn in rotate-half pairs
          (dimension i with i + 32), base rope_theta (full) or swa_rope_theta (window); the rest untouched
    s_ij = q_i . k_j / sqrt(D)   for j <= i, and in a window layer only for i - j < sliding_window
          (the query's own position counts); query head n reads KV head n // (H / G_c)
    full:   p = softmax_j(s)
    window (add_swa_attention_sink_bias), head n with learned b_n:
            p_ij = exp(s_ij) / (exp(b_n) + sum_j' exp(s_ij'))    the sink takes mass and gives no value
    x  = x + (sum_j p_ij v_j) Wo                                   Wo: [H * Dv, d]
    h2 = rms_norm(x, w2, layernorm_epsilon)
    moe_layer_freq[l] == 0:  x = x + (silu(h2 Wg) * (h2 Wu)) Wd    intermediate_size wide
    else:  sg = sigmoid(h2 Wr) over every published expert;  chosen = top num_experts_per_tok of sg + bias;
           w_e = sg_e / sum over the chosen;  no scaling factor;  no shared expert
           x = x + sum_{e chosen AND held here} w_e E_e(h2),   E = SwiGLU of width moe_intermediate_size
    logits = rms_norm(x_last, wf) W_head        over the rows of the vocabulary held here (untied)

Departures from the published description, each the configuration
file's (``assumed`` / ``reduced`` / ``deployment``): only the experts in
``held_experts`` add to the routed sum (the chip's share of a layer
that sixteen chips share; the router at full width); the vocabulary is
the slice held here; the first ``num_hidden_layers`` layers; the text
path alone (no towers, no multi-token-prediction layers).

It runs beside a serving engine that fills most of the chip, so it
works in pieces: one layer at a time under ``jit``, the experts cast to
float32 one at a time, queries (and the dense layer's rows) in blocks
and KV heads one group at a time. ``forward`` also reports, per row
asked for, how close the router came to choosing otherwise for an
expert held here (``route_gap``: the distance of the nearest held
expert's biased score from the line between chosen and unchosen). A
lower-precision run may put such an expert on the other side, the row's
hidden state then differs by a whole expert's output and later layers'
routing with it, and the comparison that reads this must know.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

NEG = -1e30


def sizes(config: dict) -> dict:
    """The sizes the reference needs, from a configuration file's keys
    (the published ones, the experts ``held`` here, the cut in depth)."""
    n = int(config["num_hidden_layers"])
    pattern = [int(k) for k in config["hybrid_layer_pattern"][:n]]

    def kind(prefix, theta, sink):
        heads, d = int(config[prefix + "num_attention_heads"]), int(config[prefix + "head_dim"])
        return (heads, int(config[prefix + "num_key_value_heads"]), d,
                int(config[prefix + "v_head_dim"]), float(config[theta]), bool(config[sink]))

    return dict(
        # per kind: (H, G, D, Dv, rotary base, sink)
        full=kind("", "rope_theta", "add_full_attention_sink_bias"),
        window=kind("swa_", "swa_rope_theta", "add_swa_attention_sink_bias"),
        windows=tuple(int(config["sliding_window"]) if k else None for k in pattern),
        rotary=int(int(config["head_dim"]) * float(config["partial_rotary_factor"])) // 2 * 2,
        value_scale=float(config["attention_value_scale"]),
        eps=float(config["layernorm_epsilon"]), top_k=int(config["num_experts_per_tok"]),
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


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * _f32(scale)


def _rope(x, positions, theta, rotary):
    """[T, heads, D]: of the first ``rotary`` dimensions, (x[i], x[i +
    rotary/2]) turned by positions * theta ** (-2i / rotary)."""
    half = rotary // 2
    inv_freq = theta ** (-jnp.arange(0, rotary, 2, dtype=jnp.float32) / rotary)
    ang = positions.astype(jnp.float32)[:, None, None] * inv_freq
    x0, x1 = x[..., :half], x[..., half:rotary]
    return jnp.concatenate([x0 * jnp.cos(ang) - x1 * jnp.sin(ang),
                            x1 * jnp.cos(ang) + x0 * jnp.sin(ang), x[..., rotary:]], axis=-1)


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


@functools.partial(jax.jit, static_argnames=("window", "sz", "q_block", "weights"))
def _layer(x, p, *, window, sz, q_block, weights):
    """One layer on ``x`` [T, d] float32 (T a multiple of ``q_block``).
    Returns ``(x', route_gap [T])``."""
    sz = dict(sz)
    w = (lambda a: _int8_round(_f32(a))) if weights == "int8" else _f32
    t_n = x.shape[0]
    h_n, g_n, d_h, _, theta, sink = sz["full" if window is None else "window"]
    pos = jnp.arange(t_n)
    a = p["attn"]
    h = _rms(x, p["ln_1"]["scale"], sz["eps"])

    k = _rope(jnp.einsum("td,dgc->tgc", h, w(a["k"])), pos, theta, sz["rotary"])
    v = sz["value_scale"] * jnp.einsum("td,dgc->tgc", h, w(a["v"]))
    wq, wo = w(a["q"]), w(a["o"])
    # one more logit a head in the denominator; none where the kind has no sink
    sinks = _f32(a["sinks"]).reshape(g_n, h_n // g_n) if sink else None

    def query_block(start):
        hb = jax.lax.dynamic_slice_in_dim(h, start, q_block)
        qpos = start + jnp.arange(q_block)
        q = _rope(jnp.einsum("td,dhc->thc", hb, wq), qpos, theta, sz["rotary"])
        seen = pos[None, :] <= qpos[:, None]
        if window is not None:
            seen &= qpos[:, None] - pos[None, :] < window

        def group(args):
            qg, kg, vg, bg = args  # [R, qb, D], [T, D], [T, Dv], [R]
            s = jnp.where(seen[None], jnp.einsum("rtd,kd->rtk", qg, kg) / np.sqrt(d_h), NEG)
            if bg is None:
                prob = jax.nn.softmax(s, axis=-1)
            else:  # the sink as one more column of the softmax, dropped after it
                b = jnp.broadcast_to(bg[:, None, None], (*s.shape[:-1], 1))
                prob = jax.nn.softmax(jnp.concatenate([s, b], axis=-1), axis=-1)[..., :-1]
            return jnp.einsum("rtk,kd->rtd", prob, vg)

        out = jax.lax.map(group, (
            jnp.moveaxis(q.reshape(q_block, g_n, h_n // g_n, d_h), (1, 2), (0, 1)),
            jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0), sinks,
        ))  # [G, R, qb, Dv]
        att = jnp.moveaxis(out, 2, 0).reshape(q_block, h_n, -1)
        return jnp.einsum("thc,hcd->td", att, wo)

    x = x + jax.lax.map(query_block, jnp.arange(0, t_n, q_block)).reshape(t_n, -1)
    h = _rms(x, p["ln_2"]["scale"], sz["eps"])
    if "mlp" in p:
        # a block of rows at a time: [T, intermediate_size] float32 is 1.3 GB at 20k rows
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
    weight = chosen_s / jnp.sum(chosen_s, axis=-1, keepdims=True)
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
    return x + routed, route_gap


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
        for layer, window in enumerate(sz["windows"]):
            x, g = _layer(x, params[f"h_{layer}"], window=window, sz=frozen,
                          q_block=q_block, weights=weights)
            gap = jnp.minimum(gap, g)
        rows = jnp.asarray(list(rows), jnp.int32)
        last = _rms(x[rows], params["ln_f"]["scale"], sz["eps"])
        kernel = _f32(params["lm_head"]["kernel"])
        if weights == "int8":
            kernel = _int8_round(kernel)
        logits = last @ kernel
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
