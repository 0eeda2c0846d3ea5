"""Operations and bytes of one chip's share of MiMo-V2.5's first layers,
from its shapes: what the mixed-length cell's utilisation and roofline
shares are shares of. Beside ``roofline.py`` and the other models'
files (kept with the benchmark so that no PR that claims a gain can
change a yardstick); every function takes the sizes as
``sizes(config)`` gives them and counts what MUST be done — causal
attention inside each kind's reach, no padding, the experts actually
hit, each layer's cache row at its own width.
"""

from __future__ import annotations


def sizes(config: dict) -> dict:
    """From a configuration file (benchmark/configs/<name>.json). Per
    layer ``kinds[l]`` is ``"full"`` or ``"window"`` and ``moe[l]``
    says whether its FFN is an expert layer; per kind ``(H, G, D, Dv)``."""
    n = int(config["num_hidden_layers"])
    kinds = tuple("window" if k else "full" for k in config["hybrid_layer_pattern"][:n])

    def attn(prefix):
        return tuple(int(config[prefix + key]) for key in (
            "num_attention_heads", "num_key_value_heads", "head_dim", "v_head_dim"))

    return dict(
        d=int(config["hidden_size"]), full=attn(""), window=attn("swa_"),
        sinks=dict(full=bool(config["add_full_attention_sink_bias"]),
                   window=bool(config["add_swa_attention_sink_bias"])),
        kinds=kinds, moe=tuple(bool(m) for m in config["moe_layer_freq"][:n]),
        reach=int(config["sliding_window"]),
        ff_dense=int(config["intermediate_size"]), ff=int(config["moe_intermediate_size"]),
        router=int(config["n_routed_experts_published"]),
        top_k=int(config["num_experts_per_tok"]), held=len(config["held_experts"]),
        vocab=int(config["vocab_size"]),
    )


def expert_layers(s: dict) -> int:
    return sum(s["moe"])


def attention_params(s: dict, kind: str) -> int:
    """q, k, v and o of one layer of ``kind``, and its sink logits."""
    h, g, d, dv = s[kind]
    return s["d"] * (h * d + g * d + g * dv) + h * dv * s["d"] + (h if s["sinks"][kind] else 0)


def expert_params(s: dict) -> int:
    """One routed SwiGLU expert: gate, up, down."""
    return 3 * s["d"] * s["ff"]


def layer_params_outside_routed(s: dict, layer: int) -> int:
    """Everything a token meets in one layer but the routed experts:
    attention, the two norms, and the dense FFN or the router and its bias."""
    ffn = s["d"] * s["router"] + s["router"] if s["moe"][layer] else 3 * s["d"] * s["ff_dense"]
    return attention_params(s, s["kinds"][layer]) + 2 * s["d"] + ffn


def param_count(s: dict) -> int:
    """Parameters held on this chip: the layers with the held experts,
    the embedding and the untied head over the vocabulary slice, the
    final norm."""
    layers = sum(
        layer_params_outside_routed(s, layer) + (s["held"] * expert_params(s) if moe else 0)
        for layer, moe in enumerate(s["moe"])
    )
    return layers + 2 * s["vocab"] * s["d"] + s["d"]


def kv_row_values(s: dict, kind: str) -> int:
    """K and V of one token in one layer of ``kind``."""
    _, g, d, dv = s[kind]
    return g * (d + dv)


def resident_token_bytes(s: dict, itemsize: int) -> int:
    """What a token holds for as long as it is resident: its rows in the
    full layers (the window layers' are released 128 positions on)."""
    return sum(kv_row_values(s, k) for k in s["kinds"] if k == "full") * itemsize


def window_token_bytes(s: dict, itemsize: int) -> int:
    """A token's rows in the window layers, held for ``reach`` positions."""
    return sum(kv_row_values(s, k) for k in s["kinds"] if k == "window") * itemsize


def one_shape_token_bytes(s: dict, itemsize: int) -> int:
    """What a pool with ONE row shape for every layer would hold of a
    token (the widest kind's row in all of them, nothing released)."""
    return len(s["kinds"]) * max(kv_row_values(s, k) for k in set(s["kinds"])) * itemsize


def token_flops_outside_attention(s: dict) -> float:
    """Operations one token costs in every layer whatever its context,
    WITHOUT the routed experts (counted from the program's own count of
    pairs): the projections, the dense FFN, the routers."""
    total = 0
    for layer, kind in enumerate(s["kinds"]):
        matrices = layer_params_outside_routed(s, layer) - 2 * s["d"] \
            - (s["router"] if s["moe"][layer] else 0) - (s[kind][0] if s["sinks"][kind] else 0)
        total += 2.0 * matrices
    return total


def pair_flops(s: dict) -> float:
    """One (token, expert) pair through one SwiGLU expert."""
    return 2.0 * expert_params(s)


def head_flops(s: dict) -> float:
    """One row against the vocabulary held here."""
    return 2.0 * s["d"] * s["vocab"]


def attention_flops(s: dict, first: int, last: int) -> float:
    """QK^T and PV of the queries at positions ``[first, last)``, each
    over the keys it may see: ``i + 1`` in a full layer, ``min(i + 1,
    reach)`` in a window layer; a key costs a head ``2 D`` for the score
    and ``2 Dv`` for the value."""
    def keys(upto: int, cap: int | None) -> float:  # sum_{i < upto} min(i + 1, cap)
        if cap is None or upto <= cap:
            return upto * (upto + 1) / 2.0
        return cap * (cap + 1) / 2.0 + (upto - cap) * float(cap)

    total = 0.0
    for kind in s["kinds"]:
        h, _, d, dv = s[kind]
        cap = None if kind == "full" else s["reach"]
        total += 2.0 * h * (d + dv) * (keys(last, cap) - keys(first, cap))
    return total


def request_flops(s: dict, prompt_len: int, n_out: int) -> float:
    """A served request without its routed pairs: every prompt token
    and every output token but the last goes through the layers; the
    head sees one row per output token."""
    n = prompt_len + max(n_out - 1, 0)
    return n * token_flops_outside_attention(s) + attention_flops(s, 0, n) + n_out * head_flops(s)


def cache_reach_bytes(s: dict, *, itemsize: int, contexts: list[float]) -> float:
    """Bytes of the live requests' caches one decode step must read: of
    each context what each layer's kind can reach, at that layer's row."""
    return sum(
        kv_row_values(s, kind) * itemsize * (c if kind == "full" else min(c, s["reach"]))
        for kind in s["kinds"] for c in contexts
    )


def decode_step_bytes(s: dict, *, itemsize: int, experts_hit: float, reach_bytes: float) -> float:
    """Bytes ONE decode step must read: every weight outside the routed
    experts once, the head over the vocabulary slice (the embedding
    gives one row a slot: left out), the routed experts that got a pair
    (``experts_hit``, summed over the layers), and ``reach_bytes`` of the
    live requests' caches (the program's own count of what each kind's
    tables reach, or :func:`cache_reach_bytes`)."""
    weights = sum(layer_params_outside_routed(s, layer) for layer in range(len(s["kinds"])))
    weights += s["vocab"] * s["d"] + s["d"] + experts_hit * expert_params(s)
    return weights * itemsize + reach_bytes


def grouped_product_cost(s: dict, *, rows: float, experts_hit: float, matrices: int,
                         itemsize: int) -> tuple[float, float]:
    """(operations, bytes) of grouped products over ``rows`` (token,
    expert) pairs that hit ``experts_hit`` experts, ``matrices`` of an
    expert's three [d, ff] matrices each: the pairs' products, and each
    hit expert's matrix read once (rows in and out are small beside)."""
    flops = 2.0 * rows * s["d"] * s["ff"] * matrices
    bytes_ = experts_hit * s["d"] * s["ff"] * matrices * itemsize
    return flops, bytes_
