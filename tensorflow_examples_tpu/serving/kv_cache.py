"""Bucket ladders, block gathers and the cache attentions.

The training-side decode path (``models/transformer.py`` flax ``cache``
collection) keys the whole batch off ONE scalar index — fine for
sampling a fixed batch in lockstep, useless for continuous batching
where every concurrent request sits at a different position. The
serving-side replacement is the block pool of ``paged_kv.py``; this
module holds what the compiled steps do with it:

* ``bucket_ladder`` / ``pick_bucket`` — the power-of-two rungs every
  compiled program comes from.
* ``gather_block_kv`` / ``gather_layer_kv`` — a slot's contiguous view
  out of one layer's ``[NB, BS, H*D]`` blocks, by its block table
  (dequantizing where the pool is int8/fp8).
* ``varlen_decode_attention`` is the per-slot generalization of
  ``ops/decode.flash_decode_attention``'s contract: each slot's query
  attends over exactly its own populated prefix (``lengths`` rides in
  as a vector, not a scalar). The bucket discipline lives in the
  caller (``engine.py``): the block tables are cut to the smallest
  power-of-two KV bucket covering the longest active request before
  this runs, so a step over mostly-short requests gathers O(bucket)
  cache bytes, not O(max_len) — the same populated-prefix economics as
  the flash-decode bucket ladder, expressed through an XLA gather
  instead of a Pallas grid (scalar-prefetch index maps cannot see a
  per-slot length vector; the single-length case — prefill — reuses
  the Pallas kernel directly, see ``engine._prefill_attend``).
  ``varlen_verify_attention`` is its T-rows-a-slot form, and the
  ``grouped_*`` attentions serve grouped-query and window layers.

Everything here is functionally pure: the pool's arrays are replaced
wholesale by the jitted steps that update them, so the engine composes
with donation on backends that support it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from tensorflow_examples_tpu.ops.attention import NEG_INF


def bucket_ladder(floor: int, max_len: int) -> list[int]:
    """Power-of-two padding buckets: ``floor, 2*floor, ...`` capped at
    (and always including) ``max_len``. One compiled program per rung;
    the smallest sufficient rung serves each request."""
    if floor < 1 or max_len < 1:
        raise ValueError(f"floor={floor} and max_len={max_len} must be >= 1")
    ladder: list[int] = []
    b = min(floor, max_len)
    while b < max_len:
        ladder.append(b)
        b *= 2
    ladder.append(max_len)
    return ladder


def pick_bucket(ladder: list[int], needed: int) -> int:
    """Smallest rung >= needed (ladder is ascending; last rung = max)."""
    for b in ladder:
        if b >= needed:
            return b
    raise ValueError(
        f"needed={needed} exceeds the largest bucket {ladder[-1]}"
    )


def gather_block_kv(blocks: jax.Array, block_tables: jax.Array,
                    num_heads: int, *, scales: jax.Array | None = None,
                    dtype=None) -> jax.Array:
    """Gather contiguous cache views out of ONE layer's paged block pool.

    blocks: [NB, BS, H*D] — NB physical blocks of BS token rows, a row
    being the token's H*D values (``paged_kv.PagedKVPool``). block_tables:
    [..., nb] int32 — logical-block -> physical-block maps for the active
    KV bucket (nb = bucket // BS; entries past an allocation point at
    the reserved null block 0, whose rows length-masking never lets
    through). Returns [..., nb*BS, H, D]: only the gathered blocks are
    ever re-viewed, never the pool.

    ``num_heads`` is the heads a row holds: the key/value heads of a
    grouped-query model. ``scales`` ([NB, BS, H], the int8/fp8 pools' per-row scales) selects
    the dequantizing gather, to ``dtype``.
    """
    bs = blocks.shape[1]
    view = (*block_tables.shape[:-1], block_tables.shape[-1] * bs, num_heads)
    g = blocks[block_tables].reshape(*view, -1)
    if scales is not None:
        from tensorflow_examples_tpu.core.precision import dequantize_rows

        g = dequantize_rows(g, scales[block_tables].reshape(view), dtype)
    return g


def gather_layer_kv(k_blocks, v_blocks, block_tables, num_heads, dtype,
                    *, k_scale=None, v_scale=None):
    """:func:`gather_block_kv` of one layer's K and V pools (and their
    scales, when the pool is quantized) by the same tables."""
    return tuple(
        gather_block_kv(blocks, block_tables, num_heads, scales=scales,
                        dtype=dtype)
        for blocks, scales in ((k_blocks, k_scale), (v_blocks, v_scale))
    )


def varlen_decode_attention(
    q: jax.Array,
    k_blocks: jax.Array,
    v_blocks: jax.Array,
    lengths: jax.Array,
    *,
    block_tables: jax.Array,
    sm_scale: float | None = None,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Single-token attention over per-slot populated cache prefixes.

    q: [S, H, D] — one new query per slot, sitting at global position
    ``lengths[s] - 1`` (its own K/V already written to the cache).
    k_blocks / v_blocks: one layer's block pool ([NB, BS, H*D], with
    ``k_scale``/``v_scale`` [NB, BS, H] when it is quantized).
    block_tables: [S, nb] int32, cut to the active KV bucket — each
    slot's view is gathered by its table first
    (:func:`gather_block_kv`, [S, Kb, H, D]); rows >= the slot's length
    are garbage and masked.
    lengths: [S] int32 populated lengths INCLUDING the new token.

    Returns [S, H, D]. Numerics mirror
    ``ops/decode.decode_attention_reference`` (f32 scores/softmax,
    output cast back to q.dtype) with the scalar length promoted to a
    vector — slot s sees columns < lengths[s], nothing else.
    """
    k_cache, v_cache = gather_layer_kv(
        k_blocks, v_blocks, block_tables, q.shape[-2], q.dtype,
        k_scale=k_scale, v_scale=v_scale,
    )
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    s = jnp.einsum(
        "shd,skhd->shk", q, k_cache, preferred_element_type=jnp.float32
    ) * sm_scale
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    s = jnp.where(col < lengths[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    return jnp.einsum(
        "shk,skhd->shd", p, v_cache, preferred_element_type=jnp.float32
    ).astype(q.dtype)


def varlen_verify_attention(
    q: jax.Array,
    k_blocks: jax.Array,
    v_blocks: jax.Array,
    positions: jax.Array,
    *,
    block_tables: jax.Array,
    sm_scale: float | None = None,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Multi-token generalization of :func:`varlen_decode_attention`
    for the speculative ``verify_k`` step (ISSUE 11).

    q: [S, T, H, D] — T new queries per slot (the launch token plus
    T-1 draft tokens), occupying global positions
    ``positions[s] .. positions[s] + T - 1``; their K/V rows are
    already written to the cache. Row t of slot s attends columns
    ``<= positions[s] + t`` — its own populated prefix INCLUDING
    itself, the verify-time mirror of continuous decode's per-slot
    length vector (T=1 reduces to exactly
    ``varlen_decode_attention(..., lengths=positions + 1)``).

    k_blocks / v_blocks: one layer's block pool ([NB, BS, H*D],
    scales [NB, BS, H]) behind ``block_tables`` — same gather contract
    as the decode path. Returns [S, T, H, D]; numerics mirror the
    decode path (f32 scores/softmax, probabilities cast to the value
    dtype, f32 accumulation) so a verify step's sampled tokens match what T
    single-token steps would have drawn — the property every
    token-identical golden with speculation on rests on.
    """
    k_cache, v_cache = gather_layer_kv(
        k_blocks, v_blocks, block_tables, q.shape[-2], q.dtype,
        k_scale=k_scale, v_scale=v_scale,
    )
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    s = jnp.einsum(
        "sthd,skhd->shtk", q, k_cache,
        preferred_element_type=jnp.float32,
    ) * sm_scale
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 3)
    row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    limit = positions[:, None, None, None] + row
    s = jnp.where(col <= limit, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    return jnp.einsum(
        "shtk,skhd->sthd", p, v_cache,
        preferred_element_type=jnp.float32,
    ).astype(q.dtype)


def _window_ok(query_pos, key_pos, window):
    """Causal (and, with a window, ``0 <= i - j < W``) visibility of
    key positions to query positions, broadcast together."""
    ok = key_pos <= query_pos
    if window is not None:
        ok &= query_pos - key_pos < window
    return ok


def window_base(position, window, block_size: int):
    """Absolute position of column 0 of a window kind's gathered view:
    the start of the oldest logical block a query at ``position`` reads
    (the block tables the engine uploads for a window kind begin
    there); 0 for a full kind."""
    if window is None:
        return 0
    return (jnp.maximum(position - window + 1, 0) // block_size) * block_size


def grouped_decode_attention(
    q: jax.Array,
    k_blocks: jax.Array,
    v_blocks: jax.Array,
    positions: jax.Array,
    block_tables: jax.Array,
    *,
    num_kv_heads: int,
    window: int | None = None,
    sm_scale: float | None = None,
) -> jax.Array:
    """:func:`varlen_decode_attention` over a paged layer for
    grouped-query heads and, optionally, a window.

    q: [S, H, D], slot s's query at ``positions[s]`` (its own K/V
    already written). k_blocks / v_blocks: one layer's pool ``[NB, BS,
    G*D]`` with ``G = num_kv_heads``; query head ``i`` reads KV head
    ``i // (H / G)``. block_tables: [S, nb] — for a full layer the
    slot's logical blocks from 0, for a window layer those from the
    oldest block the query reads (:func:`window_base`), ``nb <= W / BS
    + 1`` whatever the context. Slot s sees key positions ``j`` with
    ``0 <= positions[s] - j`` (``< W`` under a window). Numerics as the
    plain path: f32 scores and softmax, probabilities in the value
    dtype, f32 accumulation. Returns [S, H, D]."""
    s_n, h, d = q.shape
    g = num_kv_heads
    k, v = gather_layer_kv(k_blocks, v_blocks, block_tables, g, q.dtype)
    if sm_scale is None:
        sm_scale = d ** -0.5
    scores = jnp.einsum(
        "sgrd,skgd->sgrk", q.reshape(s_n, g, h // g, d), k,
        preferred_element_type=jnp.float32,
    ) * sm_scale
    base = window_base(positions, window, k_blocks.shape[1])
    key_pos = jnp.reshape(base, (-1, 1)) + jnp.arange(k.shape[1])[None, :]
    ok = _window_ok(positions[:, None], key_pos, window)
    scores = jnp.where(ok[:, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum(
        "sgrk,skgd->sgrd", p, v, preferred_element_type=jnp.float32
    ).astype(q.dtype).reshape(s_n, h, d)


def grouped_chunk_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    k_ctx: jax.Array | None = None,
    v_ctx: jax.Array | None = None,
    *,
    ctx_len=0,
    ctx_base=0,
    window: int | None = None,
    sm_scale: float | None = None,
) -> jax.Array:
    """Attention of one prompt chunk for grouped-query heads and,
    optionally, a window: a prefill (no context) or one extend step.

    q: [T, H, D], the chunk's queries at positions ``ctx_len + t``; k,
    v: [T, G, D], the chunk's own keys and values (seen causally).
    k_ctx, v_ctx: [C, G, D] — the cached context as gathered, column c
    at position ``ctx_base + c``, of which only positions below
    ``ctx_len`` are populated. One KV group at a time (``lax.map``), so
    the scores that exist at once are ``[H / G, T, C + T]`` f32, not
    all H heads'. Returns [T, H, D]."""
    t_n, h, d = q.shape
    g = k.shape[1]
    if sm_scale is None:
        sm_scale = d ** -0.5
    q_pos = ctx_len + jnp.arange(t_n)
    ok_tail = _window_ok(q_pos[:, None], q_pos[None, :], window)
    if k_ctx is not None:
        c_pos = ctx_base + jnp.arange(k_ctx.shape[0])
        ok_ctx = _window_ok(q_pos[:, None], c_pos[None, :], window) & (
            c_pos < ctx_len
        )[None, :]

    def one_group(args):
        qg, kg, vg, kcg, vcg = args  # [R,T,D] [T,D] [T,D] [C,D] [C,D]
        # The context's columns (where there is one) before the chunk's.
        pieces = [(kg, vg, ok_tail)]
        if kcg is not None:
            pieces.insert(0, (kcg, vcg, ok_ctx))
        prob = jax.nn.softmax(jnp.concatenate([
            jnp.where(ok[None], jnp.einsum(
                "rtd,kd->rtk", qg, kx, preferred_element_type=jnp.float32
            ) * sm_scale, NEG_INF)
            for kx, _, ok in pieces
        ], axis=-1), axis=-1)
        out, col = None, 0
        for _, vx, _ in pieces:
            part = jnp.einsum(
                "rtk,kd->rtd",
                prob[..., col:col + vx.shape[0]].astype(vx.dtype), vx,
                preferred_element_type=jnp.float32,
            )
            out = part if out is None else out + part
            col += vx.shape[0]
        return out

    by_group = lambda x: None if x is None else jnp.moveaxis(x, 1, 0)
    out = jax.lax.map(one_group, (
        jnp.moveaxis(q.reshape(t_n, g, h // g, d), (1, 2), (0, 1)),
        by_group(k), by_group(v), by_group(k_ctx), by_group(v_ctx),
    ))  # [G, R, T, D]
    return jnp.moveaxis(out, 2, 0).reshape(t_n, h, d).astype(q.dtype)
