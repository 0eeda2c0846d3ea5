"""Block-paged KV cache pool with prefix reuse + int8 KV (ISSUE 8).

A cache that commits ``max_len`` rows per slot the moment the slot is
claimed lets a 12-token request hold as much as a 1024-token one, and
caps concurrency by the worst case, not the workload. This module is
the engine's KV pool: slots (one per in-flight request, with host-side
alloc/free and per-slot populated lengths) over block-granular storage:

* **Paged blocks** — the device arrays are, per layer, ``[NB, BS, row]``
  pools of ``NB`` physical blocks of ``BS`` (power-of-two) token rows
  each. What a row holds is the serving block's to say (``rows``, one
  width per array a layer keeps): K and V of ``H*D`` values each for
  attention with heads (the default, lane-dense), ONE latent row,
  zero-padded to whole 128-lane tiles (576 values in 640 columns), and
  no V for latent attention (``serving/blocks.py``). The pool stores
  and counts the widths it is given (``bytes_per_block``: the pad is
  paid for).
  A slot holds a *block table* (logical block index -> physical block
  id); capacity scales with the tokens a request has actually used,
  so a mixed short/long request set commits a fraction of what
  ``slots x max_len`` rows would (tier-1 asserts <= 1/2 via
  ``used_bytes()``).
  Physical block 0 is reserved as the **null block**: pad entries of
  every table point at it, parked decode slots write their discarded
  rows into it, and length masking guarantees its garbage is never
  read into a real request's attention.
* **Free-list allocator** — blocks are claimed from a free list and
  refcounted (prefix sharing means a block can back several slots).
  Exhaustion is LOUD: :class:`BlockExhausted` (after evicting
  reusable-but-unreferenced prefix blocks, LRU first) — admission
  rejects the request (HTTP 503) instead of anything silently
  stalling, and a mid-decode exhaustion fails only the requests that
  needed new blocks while the engine keeps serving the rest
  (tests pin both, mirroring the PR 5 ``EngineStepError`` contract).
* **Prefix cache** — immutable FULL blocks of a request's prompt are
  published for reuse, keyed by an exact chained key
  ``(parent physical block id, the BS token ids in this block)`` — a
  walk from the root reproduces the whole token prefix, so a hit can
  never serve another prompt's cache (no hash collisions by
  construction). A later request whose prompt starts with the same
  full blocks maps them into its table (refcount++) and prefills only
  the tail (``engine._extend_impl``): shared system prompts prefill
  once. The partial tail is copy-on-write by construction — cached
  blocks cover only ``[0, c)`` with ``c`` block-aligned and strictly
  below the prompt length, and every write a request ever makes lands
  at positions ``>= prompt_len > c``, i.e. in its own private blocks;
  a shared block is never written again while published.
* **int8 KV** (``kv_dtype="int8"``) — blocks store int8 with per-row
  f32 scales kept blockwise (``[NB, BS, H]`` per layer,
  ``core/precision.quantize_int8_rows``): rows append one decode step
  at a time without requantizing the block. fp32/bf16 paged serving
  stays token-identical to the cacheless reference; int8 is a measured
  bounded-divergence mode (tests pin both).

* **Layer kinds** (ISSUE 28) — a layer is ``full`` (every token of the
  context stays readable) or ``window(W)`` (query ``i`` reads keys
  ``j`` with ``0 <= i - j < W``). Each kind is a block-id SPACE of its
  own: its layers' arrays have the kind's ``NB``, and the pool keeps
  one free list and one table per slot per kind. A window kind's table
  is indexed by logical block like the other, but a block all of whose
  positions have left the window goes back to the kind's free list
  (``ensure_position`` / ``ensure_span``), so a slot never holds more
  than ``(W + span) / BS + 1`` of them whatever its context, ``span``
  being the most positions one step writes (the prefill chunk).
  ``num_blocks`` counts the full kind's blocks; a window kind's ``NB``
  follows from the slots, ``W``, ``span`` and ``BS``. A model whose
  layers are all ``full`` (GPT-2) has one kind and nothing changes.
  A kind is a window AND a row shape (ISSUE 34): ``rows`` may be given
  per layer, the layers of one kind keep rows of one shape (full layers
  K and V of 4 heads, window layers of 8; keys of 192 beside values of
  128), each layer's arrays are as wide as its own row, and the bytes
  (``bytes_per_block``, ``used_bytes``, ``used_bytes_by_kind``) count
  each layer by it.
  With a window kind present, prompt blocks are NOT shared across
  requests (a hit would find released blocks): nothing is published or
  looked up, the prefix cache stays empty.

Occupancy telemetry keeps two things apart (ISSUE 8 satellite):
``serving/kv_occupancy`` is the **used-block fraction** (the capacity
signal the router tier load-balances on), while
``serving/kv_slot_occupancy`` tracks claimed slots — a pool with every
slot busy on short prompts does not read as full.
"""

from __future__ import annotations

import logging
import threading
from collections import OrderedDict

import jax.numpy as jnp
import numpy as np

from tensorflow_examples_tpu.serving import scheduler
from tensorflow_examples_tpu.telemetry import registry as registry_mod

log = logging.getLogger(__name__)

NULL_BLOCK = 0  # physical block 0: pad/garbage target, never allocated


class BlockExhausted(RuntimeError):
    """The block free list is empty (even after evicting unreferenced
    prefix-cache blocks). At admission this rejects the request (503);
    mid-decode it names the slots that could not grow (``slots``) so
    the batcher fails exactly those and keeps serving the rest."""

    def __init__(self, msg: str, *, slots: tuple[int, ...] = ()):
        super().__init__(msg)
        self.slots = tuple(slots)


class _WindowSpace:
    """One window kind's block-id space: ``num_blocks`` physical blocks
    (0 the null block), a free list, and one table per slot indexed by
    LOGICAL block, ``NULL_BLOCK`` where a block was released or never
    claimed. A slot's live blocks are the logical range ``[first[slot],
    end[slot])``. No refcounts: a window kind's blocks are never
    shared. The pool's lock guards every call."""

    def __init__(self, window: int, num_slots: int, max_blocks: int,
                 block_size: int, span: int):
        if window < 1 or window % block_size:
            raise ValueError(
                f"window={window} must be a positive multiple of "
                f"block_size={block_size}"
            )
        self.window = window
        self.block_size = block_size
        # Positions start-W+1 .. end-1 with end - start <= span: the
        # most logical blocks one slot can hold live.
        self.per_slot = min(max_blocks, (window + span) // block_size + 1)
        self.num_blocks = num_slots * self.per_slot + 1
        self.tables = np.full((num_slots, max_blocks), NULL_BLOCK, np.int32)
        self.first = np.zeros((num_slots,), np.int32)
        self.end = np.zeros((num_slots,), np.int32)
        self.released_total = 0
        self.reset()

    def reset(self) -> None:
        self.tables[:, :] = NULL_BLOCK
        self.first[:] = 0
        self.end[:] = 0
        self.free = list(range(self.num_blocks - 1, 0, -1))

    @property
    def used(self) -> int:
        return self.num_blocks - 1 - len(self.free)

    def first_block(self, position: int) -> int:
        """The oldest logical block a query at ``position`` reads."""
        return max(position - self.window + 1, 0) // self.block_size

    def cover(self, slot: int, start: int, end: int) -> int:
        """Make the slot's table hold what queries at positions
        ``[start, end)`` read and write: release every block wholly
        older than ``start - W + 1``, claim the blocks up to the one
        that holds ``end - 1``. Returns the number released."""
        lo = self.first_block(start)
        hi = (end - 1) // self.block_size + 1
        first, last = int(self.first[slot]), int(self.end[slot])
        released = 0
        for i in range(first, min(lo, last)):
            self.free.append(int(self.tables[slot, i]))
            self.tables[slot, i] = NULL_BLOCK
            released += 1
        begin = max(last, lo)
        if hi - begin > len(self.free):
            # Cannot happen while per_slot is honoured; never silently.
            raise BlockExhausted(
                f"window({self.window}) block space exhausted: "
                f"{self.num_blocks - 1} blocks all in use"
            )
        for i in range(begin, hi):
            self.tables[slot, i] = self.free.pop()
        self.first[slot] = max(first, lo)
        self.end[slot] = max(last, hi)
        self.released_total += released
        return released

    def release_slot(self, slot: int) -> None:
        for i in range(int(self.first[slot]), int(self.end[slot])):
            self.free.append(int(self.tables[slot, i]))
        self.tables[slot, :] = NULL_BLOCK
        self.first[slot] = 0
        self.end[slot] = 0


class PagedKVPool:
    """The KV pool: a slot interface (``alloc``/``free``/``reset``/
    ``reallocate``/``lengths``/``max_active_length``/``occupancy``)
    over block-granular storage.

    Host bookkeeping (all under one lock; the batcher loop is the only
    writer, frontend threads read occupancy):

    * ``block_tables`` — int32 ``[num_slots, max_len // BS]``, physical
      block ids, ``NULL_BLOCK`` where unallocated.
    * ``_refcount``   — per physical block; prefix sharing makes this
      > 1. A block at refcount 0 returns to the free list unless it is
      published in the prefix cache, in which case it parks in the
      LRU evictable set (still hittable, reclaimed on pressure).
    * prefix cache    — chained exact-token map, see module docstring.
    """

    def __init__(
        self,
        *,
        num_layers: int,
        num_slots: int,
        num_heads: int,
        max_len: int,
        head_dim: int,
        block_size: int = 16,
        num_blocks: int = 0,
        dtype=jnp.float32,
        kv_dtype: str = "",
        prefix_cache: bool = True,
        registry=None,
        sharding=None,
        layer_windows=None,
        window_span: int = 0,
        rows: tuple | None = None,
    ):
        """``num_heads`` is the heads a cache row holds (the key/value
        heads of a grouped-query model). ``rows``: the width of each
        array a layer keeps per token, as the serving block describes
        its cache row — one tuple of widths for every layer, or one such
        tuple a layer (the layers of one kind alike); omitted, K and V
        of ``num_heads * head_dim`` each. ``layer_windows``: one entry a
        layer, ``None`` for a full layer or the window ``W``; omitted,
        every layer is full. ``window_span``: the most positions one
        step writes into a slot (the prefill chunk; 0 = ``max_len``),
        which with ``W`` sizes a window kind's block space."""
        if num_slots < 1:
            raise ValueError(f"num_slots={num_slots} must be >= 1")
        if block_size < 1 or block_size & (block_size - 1):
            raise ValueError(
                f"block_size={block_size} must be a power of two"
            )
        if max_len % block_size:
            raise ValueError(
                f"block_size={block_size} must divide max_len={max_len}"
            )
        self.num_layers = num_layers
        self.num_slots = num_slots
        self.num_heads = num_heads
        self.max_len = max_len
        self.head_dim = head_dim
        self.block_size = block_size
        self.max_blocks_per_slot = max_len // block_size
        # Default capacity is the worst case (every slot at max_len),
        # so no admitted request can exhaust it; operators shrink it
        # (ServeConfig.kv_blocks) to bank the memory the paging exists
        # to save. +1 for the null block.
        self.num_blocks = (
            int(num_blocks) if num_blocks
            else num_slots * self.max_blocks_per_slot + 1
        )
        if self.num_blocks < 2:
            raise ValueError("num_blocks must leave at least one "
                             "allocatable block beyond the null block")
        self.dtype = dtype
        self.kv_dtype = kv_dtype or ""
        if self.kv_dtype not in ("", "int8", "fp8"):
            raise ValueError(
                f"kv_dtype={kv_dtype!r} not in ('', 'int8', 'fp8')"
            )
        # fp8 KV (ISSUE 15): same blockwise per-row scales, the payload
        # stored as float8_e4m3fn — the precision registry's row
        # quantization is dtype-generic, so the whole int8 path (write,
        # gather-dequant, wire pages) serves fp8 unchanged. Gated
        # loudly on builds without a working fp8.
        if self.kv_dtype == "fp8":
            from tensorflow_examples_tpu.core import precision

            if not precision.fp8_supported():
                raise ValueError(
                    "kv_dtype='fp8' requested but this jax "
                    "build/backend has no working float8_e4m3fn — "
                    "use kv_dtype='int8'"
                )
        self.quantized = self.kv_dtype in ("int8", "fp8")
        if rows is None:
            rows = (num_heads * head_dim,) * 2
        if not isinstance(rows[0], (tuple, list)):
            rows = (rows,) * num_layers
        # Per layer, the widths of the arrays it keeps of a token.
        self.layer_rows = tuple(tuple(int(r) for r in row) for row in rows)
        if len(self.layer_rows) != num_layers or len(
            {len(row) for row in self.layer_rows}
        ) != 1:
            raise ValueError(
                f"rows names {len(self.layer_rows)} layers' arrays "
                f"({self.layer_rows}) for {num_layers} layers of as many "
                "arrays each"
            )
        # The one row shape of a pool whose layers all keep the same
        # (every model but one with row shapes by kind); None otherwise.
        self.rows = self.layer_rows[0] \
            if len(set(self.layer_rows)) == 1 else None
        if self.quantized and self.rows != (num_heads * head_dim,) * 2:
            raise ValueError(
                f"kv_dtype={kv_dtype!r} keeps one scale a head of K and "
                f"of V; cache rows of widths {self.layer_rows} have no "
                "such heads"
            )
        # Kinds: index 0 is the full kind (this object's own tables,
        # free list and refcounts below), then one _WindowSpace per
        # distinct window, ascending.
        windows = tuple(layer_windows) if layer_windows is not None \
            else (None,) * num_layers
        if len(windows) != num_layers:
            raise ValueError(
                f"layer_windows has {len(windows)} entries for "
                f"{num_layers} layers"
            )
        self.kinds = (None, *sorted({w for w in windows if w is not None}))
        self.layer_kind = tuple(self.kinds.index(w) for w in windows)
        # A kind's row shape: its layers' own, all alike (None for a
        # full kind without layers).
        shapes = [
            {r for r, k in zip(self.layer_rows, self.layer_kind) if k == kind}
            for kind in range(len(self.kinds))
        ]
        if any(len(of_kind) > 1 for of_kind in shapes):
            raise ValueError(
                f"cache rows {self.layer_rows} differ between layers of one "
                f"kind (layer_windows {windows}): a kind is a window and "
                "ONE row shape"
            )
        self.kind_rows = tuple(next(iter(s), None) for s in shapes)
        # (The list never changes; each space's tables and free list
        # are read and written under self._lock, like the full kind's.)
        self._windows = [
            _WindowSpace(w, num_slots, self.max_blocks_per_slot,
                         block_size, int(window_span) or max_len)
            for w in self.kinds[1:]
        ]
        # Sharing prompt blocks across requests needs every block of
        # the prefix to be there still: not with a window kind.
        self.prefix_cache_enabled = bool(prefix_cache) and not self._windows
        self._registry = registry
        self._sharding = sharding
        self._alloc_arrays()
        # Slot/block bookkeeping below is written by the batcher loop
        # and read by frontend threads (occupancy, paged_stats, the
        # /health digest) — all under self._lock; graftlint's lock pass
        # checks the annotations (ISSUE 14). ``lengths``/
        # ``block_tables`` are also READ by the engine from the loop
        # thread (same thread as every writer), which per-file analysis
        # does not see — documented in docs/static_analysis.md.
        self.lengths = np.zeros((num_slots,), np.int32)  # guard: self._lock
        self.block_tables = np.full(  # guard: self._lock
            (num_slots, self.max_blocks_per_slot), NULL_BLOCK, np.int32
        )
        self._slot_blocks = np.zeros((num_slots,), np.int32)  # guard: self._lock
        self._free_slots = list(range(num_slots - 1, -1, -1))  # guard: self._lock
        self._free_blocks = list(range(self.num_blocks - 1, 0, -1))  # guard: self._lock
        self._refcount = np.zeros((self.num_blocks,), np.int32)  # guard: self._lock
        # Prefix cache: (parent physical id | -1, tokens tuple) -> id;
        # reverse map for eviction; per block, how many published
        # blocks are chained under it; LRU order over refcount-0 cached
        # blocks ("evictable": published but unreferenced).
        self._cache: dict[tuple, int] = {}  # guard: self._lock
        self._cache_key: dict[int, tuple] = {}  # guard: self._lock
        self._children: dict[int, int] = {}  # guard: self._lock
        # Content chain digests (ISSUE 12): per published block, the
        # replica- and restart-stable scheduler.chain_key of its whole
        # token prefix (+ its chain depth). The /health prefix digest
        # and the router's affinity score are built from these — never
        # from physical ids, which are meaningless across replicas.
        self._chain_hash: dict[int, str] = {}  # guard: self._lock
        self._chain_depth: dict[int, int] = {}  # guard: self._lock
        # Bloom-digest cache (ISSUE 15): generation counter bumped on
        # every published-chain change; the encoded filter is built
        # OUTSIDE the lock from a snapshot and reused until the
        # generation moves, so a /health probe never holds the
        # allocation lock for a full blake2b sweep of a huge cache.
        self._digest_gen = 0  # guard: self._lock
        self._bloom_cache: tuple | None = None  # guard: self._lock
        self._evictable: OrderedDict[int, None] = OrderedDict()  # guard: self._lock
        self.prefix_hits = 0  # guard: self._lock
        self.prefix_misses = 0  # guard: self._lock
        self._lock = threading.Lock()
        self._publish_locked()  # pre-sharing: no reader exists yet

    # ------------------------------------------------------ device state

    def _alloc_arrays(self) -> None:
        # One array per layer and per entry of ``rows``, a token's row
        # its values (H*D of K, of V; or one latent row, padded): the
        # minor dimension is lane-dense (768 = 6 x 128 for GPT-2, 640
        # for a latent row of 576), so the TPU's default layout is the
        # one the row scatters and block gathers want. A trailing [...,
        # BS, D=64] made XLA put NB minor-most and convert the WHOLE
        # pool on the way in and out of every program (PERF.md, PR
        # 26), and so did a row of 576 (PR 33): the engine refuses or
        # names what is not (``InferenceEngine.__init__``).
        if self.kv_dtype == "fp8":
            from tensorflow_examples_tpu.core import precision

            store = precision.fp8_dtype()
        elif self.quantized:
            store = jnp.int8
        else:
            store = self.dtype
        kw = {} if self._sharding is None else {"device": self._sharding}

        def per_layer(make, minors, dtype):
            # Each layer's array has the NB of the layer's kind and the
            # width of the layer's own row.
            return tuple(
                make((self.kind_blocks(kind), self.block_size, minor),
                     dtype, **kw)
                for kind, minor in zip(self.layer_kind, minors)
            )

        self._payload = tuple(
            per_layer(jnp.zeros, widths, store)
            for widths in zip(*self.layer_rows)
        )
        if self.quantized:
            heads = (self.num_heads,) * self.num_layers
            self.k_scale = per_layer(jnp.ones, heads, jnp.float32)
            self.v_scale = per_layer(jnp.ones, heads, jnp.float32)
        else:
            self.k_scale = self.v_scale = None

    @property
    def k(self) -> tuple:
        """The first array a layer keeps (K; the latent rows)."""
        return self._payload[0]

    @property
    def v(self) -> tuple:
        """The second (V); a pool of one array a layer has none."""
        return self._payload[1]

    def kind_blocks(self, kind: int) -> int:
        """Physical blocks (the null block included) of one kind."""
        return self.num_blocks if kind == 0 \
            else self._windows[kind - 1].num_blocks

    def window_tables(self, kind: int) -> np.ndarray:
        """A window kind's ``[num_slots, max_len // BS]`` table, by
        logical block (read by the engine on the loop thread, like
        ``block_tables``)."""
        return self._windows[kind - 1].tables

    def kv_state(self) -> tuple:
        """The device state the engine's compiled steps donate and
        return (``set_kv_state`` reassigns from the outputs): one entry
        per array of ``rows`` — ``(k, v)``, or ``(latent,)`` — and,
        quantized, ``(k, v, k_scale, v_scale)``, each a tuple of
        ``num_layers`` arrays — ``[NB, BS, row]`` payloads, ``[NB, BS,
        H]`` scales, ``NB`` that of the layer's kind."""
        if self.quantized:
            return (*self._payload, self.k_scale, self.v_scale)
        return self._payload

    def set_kv_state(self, state: tuple) -> None:
        n = len(self.layer_rows[0])
        self._payload = tuple(state[:n])
        if self.quantized:
            self.k_scale, self.v_scale = state[n:]

    def reallocate(self) -> None:
        """Fresh zeroed device arrays after a failed donated step (the
        ``EngineStepError`` path — the old buffers were consumed).
        Every cached prefix lived in those buffers, so the prefix
        cache is invalidated wholesale; slot bookkeeping is untouched
        because the batcher fails and frees the whole in-flight set
        right after."""
        self._alloc_arrays()
        with self._lock:
            self._drop_cache_locked()
            self._publish_locked()

    def _drop_cache_locked(self) -> None:
        for bid in list(self._evictable):
            self._free_blocks.append(bid)
        self._evictable.clear()
        self._cache.clear()
        self._cache_key.clear()
        self._children.clear()
        self._chain_hash.clear()
        self._chain_depth.clear()
        self._digest_gen += 1
        self._bloom_cache = None

    # ------------------------------------------------------------- slots

    def _reg(self):
        return (
            self._registry
            if self._registry is not None
            else registry_mod.default_registry()
        )

    def _publish_locked(self) -> None:
        reg = self._reg()
        active = self.num_slots - len(self._free_slots)
        usable = self.num_blocks - 1
        used = int((self._refcount > 0).sum())
        reg.gauge("serving/kv_occupancy").set(used / usable)
        reg.gauge("serving/kv_slot_occupancy").set(active / self.num_slots)
        reg.gauge("serving/kv_slots_active").set(active)
        reg.gauge("serving/kv_blocks_used").set(used)
        reg.gauge("serving/kv_blocks_total").set(usable)
        reg.gauge("serving/kv_tokens").set(int(self.lengths.sum()))
        reg.gauge("serving/prefix_cache_blocks").set(len(self._cache))
        if self._windows:
            reg.gauge("serving/kv_blocks_in_use_full").set(used)
            reg.gauge("serving/kv_blocks_in_use_window").set(
                sum(w.used for w in self._windows)
            )

    def alloc(self) -> int | None:
        """Claim a free slot (None when every slot is taken). No blocks
        are committed yet — the engine's prefill allocates exactly what
        the prompt needs."""
        with self._lock:
            if not self._free_slots:
                return None
            slot = self._free_slots.pop()
            self.lengths[slot] = 0
            self.block_tables[slot, :] = NULL_BLOCK
            self._slot_blocks[slot] = 0
            self._publish_locked()
            return slot

    def free(self, slot: int) -> None:
        with self._lock:
            if slot in self._free_slots:  # double-free is a caller bug
                raise ValueError(f"slot {slot} is already free")
            # Last block first: a chain parks its leaves before its
            # head, the order eviction takes them in.
            for i in reversed(range(int(self._slot_blocks[slot]))):
                self._release_block_locked(int(self.block_tables[slot, i]))
            self.block_tables[slot, :] = NULL_BLOCK
            self._slot_blocks[slot] = 0
            for w in self._windows:
                w.release_slot(slot)
            self.lengths[slot] = 0
            self._free_slots.append(slot)
            self._publish_locked()

    def reset(self) -> None:
        """Release every slot and every block (post-warmup; the device
        arrays keep their garbage — unpopulated rows are never read)."""
        with self._lock:
            self.lengths[:] = 0
            self.block_tables[:, :] = NULL_BLOCK
            self._slot_blocks[:] = 0
            self._free_slots = list(range(self.num_slots - 1, -1, -1))
            # Cache drop FIRST (it returns parked evictable blocks to
            # the free list), then the wholesale rebuild — the other
            # order would append those ids on top of a full list and
            # hand the same physical block out twice.
            self._drop_cache_locked()
            self._free_blocks = list(range(self.num_blocks - 1, 0, -1))
            self._refcount[:] = 0
            for w in self._windows:
                w.reset()
            self.prefix_hits = 0
            self.prefix_misses = 0
            self._publish_locked()

    @property
    def active_slots(self) -> int:
        with self._lock:
            return self.num_slots - len(self._free_slots)

    @property
    def occupancy(self) -> float:
        """Used-block fraction — what ``/health`` reports and the
        router load-balances on (the fullest kind's, where there are
        several). A full-slots pool of short prompts is
        NOT full (that is the satellite fix: slot occupancy is
        published separately as ``serving/kv_slot_occupancy``)."""
        with self._lock:
            return max([
                float((self._refcount > 0).sum()) / (self.num_blocks - 1),
                *(w.used / (w.num_blocks - 1) for w in self._windows),
            ])

    def max_active_length(self) -> int:
        with self._lock:
            return int(self.lengths.max(initial=0))

    # ------------------------------------------------------------ blocks

    def _alloc_block_locked(self) -> int:
        if self._free_blocks:
            return self._free_blocks.pop()
        if self._evictable:
            # Reclaim an unreferenced prefix block — cache reuse is an
            # optimization, never a reason to refuse admission: the
            # least recently released that no published block is
            # chained under. A chain goes from its LEAVES, so what is
            # left of it still hits, and no child stays keyed by a
            # parent id that can be published again under other tokens.
            # Slots release last block first, so the oldest parked
            # block is such a leaf — unless a racing twin published its
            # tail under a chain it never held (``insert_prefix``) and
            # still holds it: then the oldest goes with what hangs
            # under it.
            bid = next(
                (b for b in self._evictable if not self._children.get(b)),
                next(iter(self._evictable)),
            )
            self._unpublish_locked(bid)
            del self._evictable[bid]
            return bid
        self._reg().counter("serving/kv_exhausted_total").inc()
        log.warning(
            "KV block pool exhausted (%d/%d blocks referenced by "
            "active requests) — shedding",
            int((self._refcount > 0).sum()), self.num_blocks - 1,
        )
        raise BlockExhausted(
            f"KV block pool exhausted: {self.num_blocks - 1} blocks "
            f"({self.block_size} tokens each) all referenced by active "
            "requests — admission must shed load"
        )

    def _unpublish_locked(self, bid: int) -> None:
        """Take ``bid`` out of the prefix cache, with every block
        published under it (a parked one goes back to the free list, a
        referenced one when its slots release it)."""
        doomed = [bid]
        if self._children.get(bid):
            under: dict[int, list[int]] = {}
            for (parent, _), child in self._cache.items():
                under.setdefault(parent, []).append(child)
            for b in doomed:  # grows as it is walked
                doomed.extend(under.get(b, ()))
        parent = self._cache_key[bid][0]
        if parent in self._children:
            self._children[parent] -= 1
        for b in doomed:
            del self._cache[self._cache_key.pop(b)]
            self._children.pop(b, None)
            self._chain_hash.pop(b, None)
            self._chain_depth.pop(b, None)
            if b != bid and self._evictable.pop(b, 0) is None:
                self._free_blocks.append(b)
        self._digest_gen += 1

    def _release_block_locked(self, bid: int) -> None:
        if bid == NULL_BLOCK:
            return
        self._refcount[bid] -= 1
        if self._refcount[bid] > 0:
            return
        if bid in self._cache_key:
            self._evictable[bid] = None  # published: park, reclaimable
        else:
            self._free_blocks.append(bid)

    def alloc_blocks(self, n: int) -> list[int]:
        """Claim ``n`` fresh private blocks (refcount 1 each) or raise
        :class:`BlockExhausted` having claimed none (all-or-nothing, so
        a rejected admission leaks nothing)."""
        with self._lock:
            got: list[int] = []
            try:
                for _ in range(n):
                    got.append(self._alloc_block_locked())
            except BlockExhausted:
                for bid in got:
                    self._free_blocks.append(bid)
                raise
            for bid in got:
                self._refcount[bid] = 1
            self._publish_locked()
            return got

    def assign(self, slot: int, blocks: list[int]) -> None:
        """Install a slot's block table (reused prefix blocks first,
        then its private blocks — refcounts were already taken by
        ``prefix_lookup``/``alloc_blocks``)."""
        with self._lock:
            if len(blocks) > self.max_blocks_per_slot:
                raise ValueError(
                    f"{len(blocks)} blocks exceed the per-slot table "
                    f"({self.max_blocks_per_slot})"
                )
            self.block_tables[slot, :] = NULL_BLOCK
            self.block_tables[slot, :len(blocks)] = blocks
            self._slot_blocks[slot] = len(blocks)
            self._publish_locked()

    def ensure_position(self, slot: int, position: int) -> None:
        """Grow the slot's table to cover ``position`` (one block per
        step in plain decode; a speculative verify step may need
        several — the spec window can cross block boundaries). Growth
        is all-or-nothing: on :class:`BlockExhausted` nothing was
        claimed and the caller fails THAT request."""
        need = position // self.block_size + 1
        with self._lock:
            have = int(self._slot_blocks[slot])
            if need <= have:
                self._cover_windows_locked(slot, position, position + 1)
                return
            if need > self.max_blocks_per_slot:
                raise ValueError(
                    f"position {position} exceeds max_len {self.max_len}"
                )
            got: list[int] = []
            try:
                for _ in range(need - have):
                    got.append(self._alloc_block_locked())
            except BlockExhausted:
                for bid in got:
                    self._free_blocks.append(bid)
                raise
            for i, bid in enumerate(got):
                self._refcount[bid] = 1
                self.block_tables[slot, have + i] = bid
            self._slot_blocks[slot] = need
            self._cover_windows_locked(slot, position, position + 1)
            self._publish_locked()

    def _cover_windows_locked(self, slot: int, start: int, end: int) -> None:
        released = sum(w.cover(slot, start, end) for w in self._windows)
        if released:
            self._reg().counter(
                "serving/kv_window_blocks_released_total"
            ).inc(released)

    def ensure_span(self, slot: int, start: int, end: int) -> None:
        """Before a prefill or one of its chunks writes positions
        ``[start, end)``: every window kind claims the blocks of the
        span and releases what no query from ``start`` on can read. The
        full kind's blocks were claimed whole at admission
        (``claim_prompt_blocks``); a pool of one kind does nothing."""
        if not self._windows:
            return
        with self._lock:
            self._cover_windows_locked(slot, start, end)
            self._publish_locked()

    def covered_positions(self, slot: int) -> int:
        """Token rows the slot's allocated blocks can hold — the cap on
        how many verify rows may COMMIT when a speculative window could
        not be fully backed (rows past it land in the null block and
        their tokens must not ship)."""
        with self._lock:
            return int(self._slot_blocks[slot]) * self.block_size

    # ------------------------------------------------------ prefix cache

    def prefix_lookup(self, prompt) -> tuple[list[int], int]:
        """Longest reusable cached prefix of ``prompt``: (physical
        block ids with refcounts ALREADY taken, covered token count
        ``c``). ``c`` is block-aligned and capped strictly below
        ``len(prompt)`` — at least one tail token always prefills, so
        the extend step has a real query row to sample the first token
        from."""
        if not self.prefix_cache_enabled:
            return [], 0
        bs = self.block_size
        max_full = (len(prompt) - 1) // bs  # cap: tail keeps >= 1 token
        with self._lock:
            blocks: list[int] = []
            parent = -1
            for i in range(max_full):
                block = tuple(int(t) for t in prompt[i * bs:(i + 1) * bs])
                bid = self._cache.get((parent, block))
                if bid is None:
                    break
                blocks.append(bid)
                parent = bid
            if blocks:
                for bid in blocks:
                    if self._refcount[bid] == 0:
                        self._evictable.pop(bid, None)
                    self._refcount[bid] += 1
                self.prefix_hits += 1
                self._reg().counter("serving/prefix_hits").inc()
            else:
                self.prefix_misses += 1
                self._reg().counter("serving/prefix_misses").inc()
            self._publish_locked()
            return blocks, len(blocks) * bs

    def release_prefix(self, blocks: list[int]) -> None:
        """Undo a ``prefix_lookup``'s refcounts (the admission that
        followed it failed before ``assign``)."""
        with self._lock:
            for bid in reversed(blocks):
                self._release_block_locked(bid)
            self._publish_locked()

    def claim_prompt_blocks(self, slot: int, prompt) -> tuple[int, list]:
        """Claim and install ``slot``'s whole prompt table — longest
        reusable cached prefix first (refcounts taken), fresh private
        blocks for the rest — all-or-nothing: on :class:`BlockExhausted`
        the reused refcounts are released and nothing is claimed.
        Returns ``(ctx, fresh)``: the cached token count and the fresh
        block ids (the table rows from ``ctx // block_size`` on). The
        ONE home of the claim discipline — the prefill, chunked-prefill,
        and page-import paths all route through it."""
        total = -(-len(prompt) // self.block_size)
        reused, ctx = self.prefix_lookup(prompt)
        try:
            fresh = self.alloc_blocks(total - len(reused))
        except BlockExhausted:
            self.release_prefix(reused)
            raise
        self.assign(slot, reused + fresh)
        return ctx, fresh

    def insert_prefix(self, slot: int, prompt) -> None:
        """Publish the slot's FULL prompt blocks for reuse. Idempotent
        per chain link; a block already published under a different
        physical id (a racing identical prompt) is left alone — first
        writer wins, both copies serve."""
        if not self.prefix_cache_enabled:
            return
        bs = self.block_size
        with self._lock:
            parent = -1
            parent_hash = ""
            for i in range(len(prompt) // bs):
                block = tuple(int(t) for t in prompt[i * bs:(i + 1) * bs])
                key = (parent, block)
                # The content chain digest walks alongside the physical
                # chain: same tokens -> same hash on every replica and
                # across resets (the /health digest contract).
                parent_hash = scheduler.chain_key(parent_hash, block)
                existing = self._cache.get(key)
                if existing is not None:
                    parent = existing
                    continue
                bid = int(self.block_tables[slot, i])
                if bid == NULL_BLOCK:
                    break
                self._cache[key] = bid
                self._cache_key[bid] = key
                if parent != -1:
                    self._children[parent] = self._children.get(parent, 0) + 1
                self._chain_hash[bid] = parent_hash
                self._chain_depth[bid] = i + 1
                self._digest_gen += 1
                parent = bid
            self._publish_locked()

    def _chains_locked(self) -> int:
        """Distinct chain HEADS — root blocks (parent -1) of the
        published chains, i.e. how many distinct prompts' first blocks
        this cache holds (caller holds the lock)."""
        return sum(1 for key in self._cache if key[0] == -1)

    def prefix_digest(self, max_keys: int = scheduler.DIGEST_MAX_KEYS
                      ) -> dict:
        """The replica's published prefix summary (ISSUE 12): the
        content chain keys of every cached block (shallowest first,
        capped at ``max_keys`` — shared system prompts are the
        shallowest links, so the cap sheds the least-routable tails
        first), plus ``blocks`` (published block count) and ``chains``
        (distinct chain heads). Keys are pure functions of token
        content, so the digest is stable across ``reset()`` and replica
        restarts — the property the router's affinity match relies on
        (test-pinned). ``truncated`` says the cap actually bit (ISSUE
        13 satellite): on a very large cache the shed tail keys can
        never win an affinity match, so the flag makes those misses
        diagnosable on ``/health`` instead of invisible."""
        with self._lock:
            items = sorted(
                self._chain_hash.items(),
                key=lambda kv: (self._chain_depth[kv[0]], kv[1]),
            )
            truncated = len(items) > max_keys
            out = {
                "keys": [h for _, h in items[:max_keys]],
                "blocks": len(self._cache),
                "chains": self._chains_locked(),
                "truncated": truncated,
            }
            gen = self._digest_gen
            cached = self._bloom_cache
        if truncated:
            # ISSUE 15 satellite: past the cap, ALSO publish a bloom
            # filter over the ENTIRE chain-key set, so affinity
            # routing keeps working on very large caches (false
            # positives only overstate a load-guarded preference).
            # Built OUTSIDE the lock from the snapshot and cached per
            # generation — a probe of an unchanged huge cache reuses
            # the encoded filter instead of re-hashing every key, and
            # never stalls allocation while hashing.
            if cached is not None and cached[0] == gen:
                out["bloom"] = cached[1]
            else:
                bloom = scheduler.encode_bloom(h for _, h in items)
                with self._lock:
                    # Store only while still current: a slow build
                    # racing a fresher probe must not clobber the
                    # newer cached filter with an older-generation one
                    # (which would force a full re-hash per probe).
                    if self._digest_gen == gen:
                        self._bloom_cache = (gen, bloom)
                out["bloom"] = bloom
        return out

    # -------------------------------------------------- byte accounting

    def bytes_per_block(self, kind: int | None = None) -> int:
        """Device bytes one physical block commits — every array of
        the row as stored (K and V; or the one latent row with its pad
        columns), int8 payload + its
        blockwise f32 row scales when quantized — over the layers of
        its ``kind``: over every layer when the pool has one kind."""
        itemsize = 1 if self.quantized else jnp.dtype(self.dtype).itemsize
        per = sum(
            sum(row) * itemsize
            + (len(row) * self.num_heads * 4 if self.quantized else 0)
            for row, k in zip(self.layer_rows, self.layer_kind)
            if kind is None or k == kind
        )
        return int(self.block_size * per)

    def used_bytes(self) -> int:
        """Cache bytes committed to the active request set — blocks
        actually referenced, not slots claimed. The number the tier-1
        memory-claim test compares against ``slots x max_len`` rows."""
        return sum(self.used_bytes_by_kind())

    def used_bytes_by_kind(self) -> list[int]:
        """:meth:`used_bytes` kind by kind (the full kind first): each
        kind's blocks in use times the bytes its layers' rows make of a
        block."""
        with self._lock:
            used = [int((self._refcount > 0).sum()),
                    *(w.used for w in self._windows)]
        return [n * self.bytes_per_block(kind) for kind, n in enumerate(used)]

    def kind_name(self, kind: int) -> str:
        """``full`` or ``window<W>``: a kind in a counter's name."""
        w = self.kinds[kind]
        return "full" if w is None else f"window{w}"

    # ------------------------------------------------------------- stats

    @property
    def kv_bits(self) -> int:
        return 8 if self.quantized else jnp.dtype(self.dtype).itemsize * 8

    def paged_stats(self) -> dict:
        """Numeric paged-pool fields for the schema-v6 serving stats
        line (serving/batcher.stats_line) and the bench record."""
        with self._lock:
            used = int((self._refcount > 0).sum())
            usable = self.num_blocks - 1
            hits, misses = self.prefix_hits, self.prefix_misses
            chains = self._chains_locked()
            published = len(self._cache)
        looked = hits + misses
        return {
            "block_size": self.block_size,
            "blocks_total": usable,
            "blocks_used": used,
            "kv_block_occupancy": used / usable,
            "kv_slot_occupancy": (
                self.active_slots / self.num_slots
            ),
            "prefix_hits": hits,
            "prefix_misses": misses,
            "prefix_hit_rate": (hits / looked) if looked else 0.0,
            "kv_bits": self.kv_bits,
            # Schema v9 (ISSUE 12): the affinity digest's size — what
            # the router's /replicas summary aggregates fleet-wide.
            "prefix_blocks": published,
            "prefix_chains": chains,
            # Schema v10 (ISSUE 13 satellite): 1 when the published
            # /health digest is capped below the cached chain set —
            # affinity misses on the shed tails are expected, not a
            # routing bug.
            "digest_truncated": int(
                published > scheduler.DIGEST_MAX_KEYS
            ),
        }
