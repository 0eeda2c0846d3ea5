"""Operations and bytes of one chip's share of Cohere2-MoE, from its
shapes: what the new cell's utilisation and roofline shares are shares
of. Beside ``roofline.py`` (kept with the benchmark so that no PR that
claims a gain can change a yardstick); every function takes the sizes as
``sizes(config)`` gives them and counts what MUST be done — causal
attention only, no padding, the experts actually hit.
"""

from __future__ import annotations


def sizes(config: dict) -> dict:
    """From a configuration file (benchmark/configs/<name>.json)."""
    types = config["layer_types"][: int(config["num_hidden_layers"])]
    return dict(
        d=int(config["hidden_size"]), heads=int(config["num_attention_heads"]),
        kv_heads=int(config["num_key_value_heads"]), head_dim=int(config["head_dim"]),
        ff=int(config["intermediate_size"]), router=int(config["num_experts_published"]),
        top_k=int(config["num_experts_per_tok"]), shared=int(config["num_shared_experts"]),
        held=len(config["held_experts"]), vocab=int(config["vocab_size"]),
        window=int(config["sliding_window"]),
        window_layers=sum(t == "sliding_attention" for t in types),
        full_layers=sum(t == "full_attention" for t in types),
    )


def expert_params(s: dict) -> int:
    """One SwiGLU expert: gate, up, down."""
    return 3 * s["d"] * s["ff"]


def layer_params_outside_routed(s: dict) -> int:
    attn = 2 * s["d"] * s["heads"] * s["head_dim"] + 2 * s["d"] * s["kv_heads"] * s["head_dim"]
    return attn + s["shared"] * expert_params(s) + s["d"] * s["router"] + s["d"]


def param_count(s: dict) -> int:
    """Parameters held on this chip (tied embedding counted once)."""
    layers = s["window_layers"] + s["full_layers"]
    per_layer = layer_params_outside_routed(s) + s["held"] * expert_params(s)
    return layers * per_layer + s["vocab"] * s["d"] + s["d"]


def kv_row_bytes(s: dict, itemsize: int) -> int:
    """K and V of one token in one layer."""
    return 2 * s["kv_heads"] * s["head_dim"] * itemsize


def token_flops_outside_attention(s: dict) -> float:
    """Operations one token costs in every layer whatever its context,
    WITHOUT the routed experts (they are counted from the program's own
    count of pairs): projections, shared experts, router."""
    layers = s["window_layers"] + s["full_layers"]
    return 2.0 * layers * (layer_params_outside_routed(s) - s["d"])


def pair_flops(s: dict) -> float:
    """One (token, expert) pair through one SwiGLU expert."""
    return 2.0 * expert_params(s)


def head_flops(s: dict) -> float:
    """One row against the vocabulary held here."""
    return 2.0 * s["d"] * s["vocab"]


def attention_flops(s: dict, first: int, last: int) -> float:
    """QK^T and PV of the queries at positions ``[first, last)``, each
    over the keys it may see: ``i + 1`` in a full layer, ``min(i + 1,
    W)`` in a window layer."""
    def keys(upto: int, cap: int | None) -> float:  # sum_{i < upto} min(i + 1, cap)
        if cap is None or upto <= cap:
            return upto * (upto + 1) / 2.0
        return cap * (cap + 1) / 2.0 + (upto - cap) * float(cap)

    per_key = 4.0 * s["heads"] * s["head_dim"]
    full = keys(last, None) - keys(first, None)
    window = keys(last, s["window"]) - keys(first, s["window"])
    return per_key * (s["full_layers"] * full + s["window_layers"] * window)


def request_flops(s: dict, prompt_len: int, n_out: int) -> float:
    """A served request without its routed pairs: every prompt token
    and every output token but the last goes through the layers; the
    head sees one row per output token."""
    n = prompt_len + max(n_out - 1, 0)
    return n * token_flops_outside_attention(s) + attention_flops(s, 0, n) + n_out * head_flops(s)


def cache_read_bytes(s: dict, *, itemsize: int, contexts: list[float]) -> float:
    """Bytes of the live requests' caches one decode step must read:
    of each context what each kind's layers can reach."""
    row = kv_row_bytes(s, itemsize)
    return sum(
        row * (s["full_layers"] * c + s["window_layers"] * min(c, s["window"]))
        for c in contexts
    )


def decode_step_bytes(s: dict, *, itemsize: int, experts_hit: float,
                      contexts: list[float]) -> float:
    """Bytes ONE decode step must read: every weight outside the routed
    experts once (the embedding is the head), the routed experts that
    got a pair (``experts_hit``, summed over the layers), and the live
    requests' caches (``cache_read_bytes``)."""
    layers = s["window_layers"] + s["full_layers"]
    weights = layers * layer_params_outside_routed(s) + s["vocab"] * s["d"] + s["d"]
    weights += experts_hit * expert_params(s)
    return weights * itemsize + cache_read_bytes(s, itemsize=itemsize, contexts=contexts)


def grouped_product_cost(s: dict, *, rows: float, experts_hit: float, matrices: int,
                         itemsize: int) -> tuple[float, float]:
    """(operations, bytes) of grouped products over ``rows`` (token,
    expert) pairs that hit ``experts_hit`` experts, ``matrices`` of an
    expert's three [d, ff] matrices each: the pairs' products, and each
    hit expert's matrix read once (rows in and out are small beside)."""
    flops = 2.0 * rows * s["d"] * s["ff"] * matrices
    bytes_ = experts_hit * s["d"] * s["ff"] * matrices * itemsize
    return flops, bytes_
