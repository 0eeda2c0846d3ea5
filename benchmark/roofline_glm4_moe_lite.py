"""Operations and bytes of GLM-4.7-Flash's first layers on one chip,
from its shapes: what the new cell's utilisation and roofline shares are
shares of. Beside ``roofline.py`` and ``roofline_cohere2_moe.py`` (kept
with the benchmark so that no PR that claims a gain can change a
yardstick); every function takes the sizes as ``sizes(config)`` gives
them and counts what MUST be done — causal attention only, no padding,
the tokens actually computed (a reused prefix costs nothing), the
experts actually hit.

Attention is counted in the form that is the model's mathematics —
EXPANDED: every head's keys ``qk_nope + qk_rope`` wide, its values
``v_head_dim`` wide, K and V made from the latent once a token —
whatever form the program runs, so a share does not move when the form
does.
"""

from __future__ import annotations


def sizes(config: dict) -> dict:
    """From a configuration file (benchmark/configs/<name>.json)."""
    layers = int(config["num_hidden_layers"])
    dense = min(int(config["first_k_dense_replace"]), layers)
    held = config.get("held_experts")
    return dict(
        d=int(config["hidden_size"]), heads=int(config["num_attention_heads"]),
        q_rank=int(config["q_lora_rank"]), dc=int(config["kv_lora_rank"]),
        dn=int(config["qk_nope_head_dim"]), dr=int(config["qk_rope_head_dim"]),
        dv=int(config["v_head_dim"]), ff_dense=int(config["intermediate_size"]),
        ff=int(config["moe_intermediate_size"]), router=int(config["n_routed_experts"]),
        top_k=int(config["num_experts_per_tok"]), shared=int(config["n_shared_experts"]),
        held=int(config["n_routed_experts"]) if held is None else len(held),
        vocab=int(config["vocab_size"]), dense_layers=dense, sparse_layers=layers - dense,
    )


def attention_params(s: dict) -> int:
    """The projections of one latent-attention layer (no norm scales)."""
    return (s["d"] * s["q_rank"] + s["q_rank"] * s["heads"] * (s["dn"] + s["dr"])
            + s["d"] * (s["dc"] + s["dr"]) + s["dc"] * s["heads"] * (s["dn"] + s["dv"])
            + s["heads"] * s["dv"] * s["d"])


def expert_params(s: dict) -> int:
    """One routed SwiGLU expert: gate, up, down."""
    return 3 * s["d"] * s["ff"]


def layer_matrices_outside_routed(s: dict, *, dense: bool) -> int:
    """Matrix parameters every token meets in one layer, without the
    routed experts: attention, and the dense FFN or shared expert + router."""
    ffn = 3 * s["d"] * s["ff_dense"] if dense else \
        s["shared"] * expert_params(s) + s["d"] * s["router"]
    return attention_params(s) + ffn


def layer_params_outside_routed(s: dict, *, dense: bool) -> int:
    """The same with the norm scales and the router's bias: bytes read."""
    small = 2 * s["d"] + s["q_rank"] + s["dc"] + (0 if dense else s["router"])
    return layer_matrices_outside_routed(s, dense=dense) + small


def param_count(s: dict) -> int:
    """Parameters held on this chip (embedding and head are two tables)."""
    return (s["dense_layers"] * layer_params_outside_routed(s, dense=True)
            + s["sparse_layers"] * (layer_params_outside_routed(s, dense=False)
                                    + s["held"] * expert_params(s))
            + 2 * s["vocab"] * s["d"] + s["d"])


def latent_row_bytes(s: dict, itemsize: int) -> int:
    """What one token leaves in one layer's cache: c_kv and k_pe, no V."""
    return (s["dc"] + s["dr"]) * itemsize


def kv_bytes_token(s: dict, itemsize: int) -> int:
    """One token's cache over the layers served."""
    return (s["dense_layers"] + s["sparse_layers"]) * latent_row_bytes(s, itemsize)


def token_flops_outside_attention(s: dict) -> float:
    """Operations one computed token costs in every layer whatever its
    context, WITHOUT the routed experts (counted from the program's own
    count of pairs): projections (K and V expanded from the latent once
    a token), the dense FFN, the shared expert, the router."""
    return 2.0 * (s["dense_layers"] * layer_matrices_outside_routed(s, dense=True)
                  + s["sparse_layers"] * layer_matrices_outside_routed(s, dense=False))


def pair_flops(s: dict) -> float:
    """One (token, expert) pair through one SwiGLU expert."""
    return 2.0 * expert_params(s)


def head_flops(s: dict) -> float:
    """One row against the vocabulary."""
    return 2.0 * s["d"] * s["vocab"]


def attention_flops(s: dict, first: int, last: int) -> float:
    """QK^T and PV, expanded, of the queries at positions ``[first,
    last)``, each over the ``i + 1`` keys it may see, in every layer."""
    keys = (last * (last + 1) - first * (first + 1)) / 2.0
    per_key = 2.0 * s["heads"] * (s["dn"] + s["dr"] + s["dv"])
    return (s["dense_layers"] + s["sparse_layers"]) * per_key * keys


def request_flops(s: dict, prompt_len: int, n_out: int, reused: int = 0) -> float:
    """A served request without its routed pairs: the prompt tokens past
    the ``reused`` prefix and every output token but the last go through
    the layers; the head sees one row per output token."""
    n = prompt_len + max(n_out - 1, 0)
    return ((n - reused) * token_flops_outside_attention(s)
            + attention_flops(s, reused, n) + n_out * head_flops(s))


def decode_step_bytes(s: dict, *, itemsize: int, experts_hit: float,
                      reach_bytes: float) -> float:
    """Bytes ONE decode step must read: every weight outside the routed
    experts once, the head (the embedding's few rows are not counted),
    the routed experts that got a pair (``experts_hit``, summed over the
    layers), and the latent rows every live slot's table reaches
    (``reach_bytes``, as the program sampled it)."""
    weights = (s["dense_layers"] * layer_params_outside_routed(s, dense=True)
               + s["sparse_layers"] * layer_params_outside_routed(s, dense=False)
               + s["vocab"] * s["d"] + s["d"] + experts_hit * expert_params(s))
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
