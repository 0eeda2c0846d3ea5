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
* ``latent_chunk_attention`` / ``latent_decode_attention`` — attention
  over LATENT rows (``[c_kv | k_pe]``, one a token, no heads, no V):
  a prompt chunk over itself and the cached context, a decode step
  through the block tables, both ABSORBED (the queries moved into the
  latent space, every head reading the same rows).

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


def _softmax_with_sink(scores, sink):
    """``softmax(scores)`` over the last axis, with ``sink`` (broadcast
    against ``scores[..., :1]``, or None) as one more logit of the
    denominator that has no column of its own: ``exp(s_j) / (exp(sink)
    + sum_j' exp(s_j'))`` — the sink takes mass and gives no value."""
    if sink is None:
        return jax.nn.softmax(scores, axis=-1)
    top = jnp.maximum(jnp.max(scores, axis=-1, keepdims=True), sink)
    e = jnp.exp(scores - top)
    return e / (jnp.sum(e, axis=-1, keepdims=True) + jnp.exp(sink - top))


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
    sinks: jax.Array | None = None,
) -> jax.Array:
    """:func:`varlen_decode_attention` over a paged layer for
    grouped-query heads and, optionally, a window and a sink.

    q: [S, H, D], slot s's query at ``positions[s]`` (its own K/V
    already written). k_blocks / v_blocks: one layer's pool ``[NB, BS,
    G*D]`` and ``[NB, BS, G*Dv]`` with ``G = num_kv_heads`` (a value
    head may be narrower than a key head); query head ``i`` reads KV
    head ``i // (H / G)``. ``sinks``: [H] float32, one logit a head in
    the softmax's denominator (:func:`_softmax_with_sink`), or None.
    block_tables: [S, nb] — for a full layer the
    slot's logical blocks from 0, for a window layer those from the
    oldest block the query reads (:func:`window_base`), ``nb <= W / BS
    + 1`` whatever the context. Slot s sees key positions ``j`` with
    ``0 <= positions[s] - j`` (``< W`` under a window). Numerics as the
    plain path: f32 scores and softmax, probabilities in the value
    dtype, f32 accumulation. Returns [S, H, Dv]."""
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
    sink = None if sinks is None else sinks.reshape(1, g, h // g, 1)
    p = _softmax_with_sink(scores, sink).astype(v.dtype)
    return jnp.einsum(
        "sgrk,skgd->sgrd", p, v, preferred_element_type=jnp.float32
    ).astype(q.dtype).reshape(s_n, h, v.shape[-1])


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
    sinks: jax.Array | None = None,
) -> jax.Array:
    """Attention of one prompt chunk for grouped-query heads and,
    optionally, a window and a sink: a prefill (no context) or one
    extend step.

    q: [T, H, D], the chunk's queries at positions ``ctx_len + t``; k:
    [T, G, D], v: [T, G, Dv], the chunk's own keys and values (seen
    causally; a value head may be narrower than a key head). k_ctx:
    [C, G, D], v_ctx: [C, G, Dv] — the cached context as gathered,
    column c at position ``ctx_base + c``, of which only positions
    below ``ctx_len`` are populated. ``sinks``: [H] float32, one logit
    a head in the softmax's denominator (:func:`_softmax_with_sink`),
    or None. One KV group at a time (``lax.map``), so the scores that
    exist at once are ``[H / G, T, C + T]`` f32, not all H heads'.
    Returns [T, H, Dv]."""
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
        # [R,T,D] [T,D] [T,Dv] [C,D] [C,Dv] [R]
        qg, kg, vg, kcg, vcg, sink = args
        # The context's columns (where there is one) before the chunk's.
        pieces = [(kg, vg, ok_tail)]
        if kcg is not None:
            pieces.insert(0, (kcg, vcg, ok_ctx))
        prob = _softmax_with_sink(jnp.concatenate([
            jnp.where(ok[None], jnp.einsum(
                "rtd,kd->rtk", qg, kx, preferred_element_type=jnp.float32
            ) * sm_scale, NEG_INF)
            for kx, _, ok in pieces
        ], axis=-1), None if sink is None else sink[:, None, None])
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
        None if sinks is None else sinks.reshape(g, h // g),
    ))  # [G, R, T, Dv]
    return jnp.moveaxis(out, 2, 0).reshape(
        t_n, h, v.shape[-1]
    ).astype(q.dtype)


# ------------------------------------------------------ latent attention
#
# A latent layer caches ONE row a token, ``[c_kv | k_pe]``: the
# compressed key/value vector (``dc`` values) and the rotary key all
# heads share (``dr`` values). Head ``h``'s key is ``[c_kv W_uk[h] |
# k_pe]`` and its value ``c_kv W_uv[h]``. Both functions below attend
# ABSORBED: ``W_uk`` moves to the query (``q_lat = q_nope W_uk^T``) and
# ``W_uv`` behind the softmax, so the scores are ``dc + dr`` wide
# straight against the rows and the values ``dc`` wide, every head
# reading the same rows once (multi-query attention over the latent).
# The EXPANDED form (K and V of every head made from the rows, then
# ordinary attention: ``2 * dc * (dn + dv)`` more operations a cached
# row a head) is the model's mathematics as published and what the
# plain reference computes; on the chip it was slower at every shape
# the engine runs (PERF.md §6, PR 32; ``tools/mla_forms_bench.py``
# still times it).
#
# The row is STORED lane-dense (:func:`lane_dense`: 576 values in 640
# columns, the pad zero). XLA:TPU keeps an array whose minor dimension
# is not a whole number of 128-lane tiles with ``NB`` minor-most and
# re-lays the WHOLE pool around every row scatter (two pool-sized
# copies a layer in every program: 10 ms of a 61 ms decode step,
# PERF.md §6, PR 33). Both functions take rows of the stored width and
# pad the absorbed query with zeros to meet them, so a pad column adds
# nothing to a score; the values are the first ``dc`` columns as
# before. Of the three forms timed (this; ``c_kv`` and ``k_pe`` as two
# arrays, ``k_pe`` padded to 128 or left at 64) this was the fastest
# by 4-5 ms a decode step: a second array is a second gather and a
# second score product (``tools/mla_forms_bench.py --only pool``).

LANES = 128

# The f32 scores that may exist at once, in elements: a chunk attends
# in groups of heads no larger than this allows.
LATENT_SCORE_ELEMENTS = 1 << 26
# The bytes of cached rows a decode step gathers at once. Measured on
# the chip (PERF.md §6, PR 32): 32 slots' rows of one layer gathered
# whole (1.2 GB at 32k rows a slot) are read at 118 GB/s, groups of up
# to ~75 MB at 175-200 GB/s.
LATENT_GATHER_BYTES = 80 << 20


def lane_dense(width: int) -> int:
    """``width`` rounded up to a whole number of 128-lane tiles: the
    stored width of a cache row array the TPU does not re-lay. A width
    under one tile is left as it is: a toy size, whose pool is small
    whatever its layout, and which padding would multiply."""
    return width if width < LANES else -(-width // LANES) * LANES


def pad_columns(x: jax.Array, width: int) -> jax.Array:
    """``x`` with zero columns appended up to ``width``."""
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])])


def _largest_divisor(n: int, limit: int) -> int:
    """The largest divisor of ``n`` no larger than ``limit`` (at least 1)."""
    group = max(1, min(n, limit))
    while n % group:
        group -= 1
    return group


def latent_head_group(heads: int, rows: int, cols: int) -> int:
    """Heads a chunk's attention takes at once: the largest divisor of
    ``heads`` whose ``[group, rows, cols]`` float32 scores stay within
    ``LATENT_SCORE_ELEMENTS`` (at least one head)."""
    return _largest_divisor(heads, LATENT_SCORE_ELEMENTS // max(rows * cols, 1))


def latent_chunk_attention(
    q_nope: jax.Array,
    q_pe: jax.Array,
    rows: jax.Array,
    w_uk: jax.Array,
    w_uv: jax.Array,
    ctx_rows: jax.Array | None = None,
    *,
    ctx_len=0,
    sm_scale: float,
) -> jax.Array:
    """Attention of one prompt chunk over latent rows, absorbed: a
    prefill (no context) or one extend step.

    q_nope: [T, H, dn], q_pe: [T, H, dr] (rotated), the chunk's queries
    at positions ``ctx_len + t``; rows: [T, W], the chunk's own latent
    rows as stored (``[c_kv | k_pe | 0]``, ``W >= dc + dr``; seen
    causally); ctx_rows: [C, W], the cached context as gathered, of
    which only the first ``ctx_len`` rows are populated. w_uk: [dc, H,
    dn], w_uv: [dc, H, dv].

    :func:`latent_head_group` heads at a time (``lax.map``), so the
    scores that exist at once are ``[group, T, C + T]`` float32, never
    all heads'. Numerics as the other cache attentions: f32 scores and
    softmax, probabilities in the rows' dtype, f32 accumulation.
    Returns [T, H, dv]."""
    t_n, h, _ = q_nope.shape
    dc = w_uk.shape[0]
    dtype = q_nope.dtype
    q_pos = ctx_len + jnp.arange(t_n)
    pieces = [(rows, q_pos[:, None] >= q_pos[None, :])]
    if ctx_rows is not None:
        seen = jnp.arange(ctx_rows.shape[0]) < ctx_len
        pieces.insert(0, (ctx_rows, jnp.broadcast_to(
            seen[None, :], (t_n, ctx_rows.shape[0])
        )))
    cols = sum(r.shape[0] for r, _ in pieces)
    g = latent_head_group(h, t_n, cols)
    f32 = dict(preferred_element_type=jnp.float32)

    def one_group(args):
        qn, qp, uk, uv = args  # [G,T,dn] [G,T,dr] [G,dc,dn] [G,dc,dv]
        q_lat = jnp.einsum("gtn,gcn->gtc", qn, uk, **f32).astype(dtype)
        q_all = pad_columns(  # [G,T,W]
            jnp.concatenate([q_lat, qp], axis=-1), rows.shape[-1]
        )
        prob = jax.nn.softmax(jnp.concatenate([
            jnp.where(
                ok[None],
                jnp.einsum("gtr,kr->gtk", q_all, kx, **f32) * sm_scale,
                NEG_INF,
            ) for kx, ok in pieces
        ], axis=-1), axis=-1)
        out, col = None, 0
        for kx, _ in pieces:
            n = kx.shape[0]
            part = jnp.einsum(
                "gtk,kc->gtc", prob[..., col:col + n].astype(kx.dtype),
                kx[:, :dc], **f32,
            )
            out = part if out is None else out + part
            col += n
        return jnp.einsum(  # [G,T,dv] f32
            "gtc,gcv->gtv", out.astype(dtype), uv, **f32
        )

    by_group = lambda x, axis: jnp.moveaxis(x, axis, 0).reshape(  # noqa: E731
        h // g, g, *x.shape[:axis], *x.shape[axis + 1:]
    )
    out = jax.lax.map(one_group, (
        by_group(q_nope, 1), by_group(q_pe, 1),
        by_group(w_uk, 1), by_group(w_uv, 1),
    ))  # [H/G, G, T, dv]
    return jnp.moveaxis(out.reshape(h, t_n, -1), 0, 1).astype(dtype)


def latent_slot_group(slots: int, cols: int, row_bytes: int) -> int:
    """Slots a decode step's attention gathers at once: the largest
    divisor of ``slots`` whose gathered rows ``[group, cols, row]`` stay
    within ``LATENT_GATHER_BYTES`` (at least one slot)."""
    return _largest_divisor(
        slots, LATENT_GATHER_BYTES // max(cols * row_bytes, 1)
    )


def latent_decode_attention(
    q_nope: jax.Array,
    q_pe: jax.Array,
    blocks: jax.Array,
    positions: jax.Array,
    block_tables: jax.Array,
    w_uk: jax.Array,
    w_uv: jax.Array,
    *,
    sm_scale: float,
) -> jax.Array:
    """One decode step's attention over a paged latent layer, ABSORBED:
    every head reads the slot's gathered rows once.

    q_nope: [S, H, dn], q_pe: [S, H, dr] (rotated): slot s's query at
    ``positions[s]`` (its own row already written). blocks: one layer's
    pool ``[NB, BS, W]``, rows as stored (``[c_kv | k_pe | 0]``, ``W >=
    dc + dr``); block_tables: [S, nb], the slot's logical blocks from
    0. Slot s sees rows at positions ``<= positions[s]``.
    :func:`latent_slot_group` slots at a time, so the rows gathered at
    once are ``[group, nb * BS, W]``, never all slots' (1.3 GB a layer
    at 32 slots of 32k rows). The values are the rows' first ``dc``
    columns: the product runs over whole rows and the rotary and pad
    columns of the result are dropped, which costs a quarter more
    operations and saves a copy of every gathered row. f32 scores and
    softmax, probabilities in the rows' dtype, f32 accumulation.
    Returns [S, H, dv]."""
    s_n, nb = block_tables.shape
    dc = w_uk.shape[0]
    dtype = q_nope.dtype
    f32 = dict(preferred_element_type=jnp.float32)
    q_lat = jnp.einsum("shn,chn->shc", q_nope, w_uk, **f32).astype(dtype)
    q_all = pad_columns(                                   # [S,H,W]
        jnp.concatenate([q_lat, q_pe], axis=-1), blocks.shape[2]
    )
    g = latent_slot_group(
        s_n, nb * blocks.shape[1], blocks.shape[2] * blocks.dtype.itemsize
    )

    def one_group(args):
        q, table, pos = args  # [G,H,R] [G,nb] [G]
        rows = gather_block_kv(blocks, table, 1)[..., 0, :]    # [G,K,W]
        s = jnp.einsum("ghr,gkr->ghk", q, rows, **f32) * sm_scale
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(col <= pos[:, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(rows.dtype)
        return jnp.einsum("ghk,gkr->ghr", p, rows, **f32)[..., :dc]

    # Unrolled, not ``lax.map``: a loop that reads the pool's array makes
    # XLA copy the whole array into the loop's state, a layer's pool a layer.
    o_lat = jnp.concatenate([
        one_group((q_all[i:i + g], block_tables[i:i + g], positions[i:i + g]))
        for i in range(0, s_n, g)
    ])
    return jnp.einsum(
        "shc,chv->shv", o_lat.astype(dtype), w_uv, **f32
    ).astype(dtype)
