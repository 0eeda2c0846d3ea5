"""Slot-granular KV cache pool + variable-length decode attention.

The training-side decode path (``models/transformer.py`` flax ``cache``
collection) keys the whole batch off ONE scalar index — fine for
sampling a fixed batch in lockstep, useless for continuous batching
where every concurrent request sits at a different position. This
module owns the serving-side replacement:

* ``KVCachePool`` preallocates the worst-case cache ONCE —
  ``[layers, slots, heads, max_len, head_dim]`` for K and V — and hands
  out *slots* (one per in-flight request) with host-side alloc/free and
  per-slot populated-length tracking. Slot state is published as
  ``serving/kv_occupancy`` / ``serving/kv_tokens`` gauges on every
  transition, so a scrape always sees live cache pressure.
* ``varlen_decode_attention`` is the per-slot generalization of
  ``ops/decode.flash_decode_attention``'s contract: each slot's query
  attends over exactly its own populated prefix (``lengths`` rides in
  as a vector, not a scalar). The bucket discipline lives in the
  caller (``engine.py``): the cache is sliced to the smallest
  power-of-two KV bucket covering the longest active request before
  this runs, so a step over mostly-short requests reads O(bucket)
  cache bytes, not O(max_len) — the same populated-prefix economics as
  the flash-decode bucket ladder, expressed through XLA slicing
  instead of a Pallas grid (scalar-prefetch index maps cannot see a
  per-slot length vector; the single-length case — prefill — reuses
  the Pallas kernel directly, see ``engine._prefill_attend``).

Everything here is functionally pure on the device side: the pool's
arrays are replaced wholesale by the jitted steps that update them, so
the engine composes with donation on backends that support it.
"""

from __future__ import annotations

import threading

import jax
import jax.numpy as jnp
import numpy as np

from tensorflow_examples_tpu.ops.attention import NEG_INF
from tensorflow_examples_tpu.telemetry import registry as registry_mod


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
    k_cache: jax.Array,
    v_cache: jax.Array,
    lengths: jax.Array,
    *,
    sm_scale: float | None = None,
    block_tables: jax.Array | None = None,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Single-token attention over per-slot populated cache prefixes.

    q: [S, H, D] — one new query per slot, sitting at global position
    ``lengths[s] - 1`` (its own K/V already written to the cache).
    k_cache / v_cache: [S, H, Kb, D] — the cache sliced to the active
    KV bucket; slots' rows >= their length are garbage and masked.
    lengths: [S] int32 populated lengths INCLUDING the new token.

    With ``block_tables`` ([S, nb] int32, ISSUE 8), k_cache/v_cache
    are instead one layer's paged block pool ([NB, BS, H*D], with
    ``k_scale``/``v_scale`` [NB, BS, H] when it is quantized) and each
    slot's view is gathered by its block table first
    (:func:`gather_block_kv`, [S, Kb, H, D]) — the paged mirror of the
    dense slice, same masking contract downstream.

    Returns [S, H, D]. Numerics mirror
    ``ops/decode.decode_attention_reference`` (f32 scores/softmax,
    output cast back to q.dtype) with the scalar length promoted to a
    vector — slot s sees columns < lengths[s], nothing else.
    """
    kv = "shkd"
    if block_tables is not None:
        k_cache, v_cache = gather_layer_kv(
            k_cache, v_cache, block_tables, q.shape[-2], q.dtype,
            k_scale=k_scale, v_scale=v_scale,
        )
        kv = "skhd"
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    s = jnp.einsum(
        f"shd,{kv}->shk", q, k_cache, preferred_element_type=jnp.float32
    ) * sm_scale
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    s = jnp.where(col < lengths[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    return jnp.einsum(
        f"shk,{kv}->shd", p, v_cache, preferred_element_type=jnp.float32
    ).astype(q.dtype)


def varlen_verify_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    positions: jax.Array,
    *,
    sm_scale: float | None = None,
    block_tables: jax.Array | None = None,
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

    k_cache / v_cache: [S, H, Kb, D] bucket-sliced caches, or one
    layer's paged block pool ([NB, BS, H*D], scales [NB, BS, H]) when
    ``block_tables`` is given — same gather contract as the decode
    path. Returns [S, T, H, D]; numerics mirror the decode path (f32
    scores/softmax, probabilities cast to the value dtype, f32
    accumulation) so a verify step's sampled tokens match what T
    single-token steps would have drawn — the property every
    token-identical golden with speculation on rests on.
    """
    kv = "shkd"
    if block_tables is not None:
        k_cache, v_cache = gather_layer_kv(
            k_cache, v_cache, block_tables, q.shape[-2], q.dtype,
            k_scale=k_scale, v_scale=v_scale,
        )
        kv = "skhd"
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    s = jnp.einsum(
        f"sthd,{kv}->shtk", q, k_cache,
        preferred_element_type=jnp.float32,
    ) * sm_scale
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 3)
    row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    limit = positions[:, None, None, None] + row
    s = jnp.where(col <= limit, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    return jnp.einsum(
        f"shtk,{kv}->sthd", p, v_cache,
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


class KVCachePool:
    """Preallocated per-request KV slots with host-side bookkeeping.

    Device state: ``k``/``v`` [L, S, H, max_len, D], replaced wholesale
    by the engine's jitted steps. Host state: a free-slot list and the
    per-slot populated lengths (the numpy mirror the engine feeds back
    into every decode step). Thread-safe: the batcher loop allocates
    and frees while frontend threads read occupancy.
    """

    def __init__(
        self,
        *,
        num_layers: int,
        num_slots: int,
        num_heads: int,
        max_len: int,
        head_dim: int,
        dtype=jnp.float32,
        registry=None,
        sharding=None,
    ):
        if num_slots < 1:
            raise ValueError(f"num_slots={num_slots} must be >= 1")
        self.num_layers = num_layers
        self.num_slots = num_slots
        self.num_heads = num_heads
        self.max_len = max_len
        self.head_dim = head_dim
        self.dtype = dtype
        self._registry = registry
        # Optional NamedSharding for the [L, S, H, max_len, D] device
        # arrays (ISSUE 7): the engine derives it from its
        # ShardingConfig — heads over `model` is the tensor-parallel
        # layout — so the cache is born (and reallocated) in the same
        # placement the compiled steps consume. None = single-device
        # default placement, today's behavior.
        self._sharding = sharding
        self.k = self._zeros()
        self.v = self._zeros()
        self.lengths = np.zeros((num_slots,), np.int32)
        self._free = list(range(num_slots - 1, -1, -1))  # pop() -> slot 0 first
        self._lock = threading.Lock()
        self._publish()

    # ------------------------------------------------------------- slots

    def _reg(self):
        return (
            self._registry
            if self._registry is not None
            else registry_mod.default_registry()
        )

    def _publish(self) -> None:
        reg = self._reg()
        active = self.num_slots - len(self._free)
        # Dense pool: a claimed slot IS max_len of committed cache, so
        # slot occupancy and capacity occupancy are the same number.
        # The paged pool (paged_kv.py) splits them — kv_occupancy
        # becomes used-block fraction there — and publishes both.
        reg.gauge("serving/kv_occupancy").set(active / self.num_slots)
        reg.gauge("serving/kv_slot_occupancy").set(active / self.num_slots)
        reg.gauge("serving/kv_slots_active").set(active)
        reg.gauge("serving/kv_tokens").set(int(self.lengths.sum()))

    def alloc(self) -> int | None:
        """Claim a free slot (None when the pool is full). The slot's
        length starts at 0; the engine's prefill sets it."""
        with self._lock:
            if not self._free:
                return None
            slot = self._free.pop()
            self.lengths[slot] = 0
            self._publish()
            return slot

    def free(self, slot: int) -> None:
        with self._lock:
            if slot in self._free:  # double-free is a caller bug
                raise ValueError(f"slot {slot} is already free")
            self.lengths[slot] = 0
            self._free.append(slot)
            self._publish()

    def _zeros(self):
        shape = (self.num_layers, self.num_slots, self.num_heads,
                 self.max_len, self.head_dim)
        if self._sharding is None:
            return jnp.zeros(shape, self.dtype)
        # Born sharded: zeros are created per-shard in place — the full
        # pool never materializes on one device (it may only fit split).
        return jnp.zeros(shape, self.dtype, device=self._sharding)

    def reallocate(self) -> None:
        """Replace ``k``/``v`` with fresh zeroed device arrays (in the
        pool's sharding). The engine calls this when a donated compiled
        step fails at runtime: donation consumed the old buffers, so
        without replacement every later step would hit 'Array has been
        deleted'. Slot bookkeeping is untouched — the batcher fails and
        frees the whole in-flight set (its KV is gone) right after."""
        self.k = self._zeros()
        self.v = self._zeros()

    def reset(self) -> None:
        """Release every slot and zero the length mirror (the device
        arrays keep whatever garbage they hold — unpopulated rows are
        never read). Used after engine warmup."""
        with self._lock:
            self.lengths[:] = 0
            self._free = list(range(self.num_slots - 1, -1, -1))
            self._publish()

    @property
    def active_slots(self) -> int:
        with self._lock:
            return self.num_slots - len(self._free)

    @property
    def occupancy(self) -> float:
        return self.active_slots / self.num_slots

    def max_active_length(self) -> int:
        """Longest populated prefix over all slots (0 when idle) — the
        engine picks the decode KV bucket from this."""
        with self._lock:
            return int(self.lengths.max(initial=0))

    # -------------------------------------------------- byte accounting

    @property
    def kv_bits(self) -> int:
        """Storage bits per cache element (uniform with the paged
        pool's quantization-aware figure)."""
        return jnp.dtype(self.dtype).itemsize * 8

    def bytes_per_slot(self) -> int:
        """K+V device bytes one claimed slot commits (the dense pool
        commits the full ``max_len`` extent per slot, used or not —
        the economics the paged pool exists to beat)."""
        return int(
            2 * self.num_layers * self.num_heads * self.max_len
            * self.head_dim * jnp.dtype(self.dtype).itemsize
        )

    def used_bytes(self) -> int:
        """Cache bytes committed to the currently active request set
        (tier-1 asserts the paged pool's figure for a mixed-length set
        is <= 1/2 of this one at equal concurrency)."""
        return self.active_slots * self.bytes_per_slot()
