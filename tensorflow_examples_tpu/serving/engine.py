"""The compiled serving step: bucketed prefill + fixed-shape decode.

Shape discipline is the whole design (SURVEY.md's "as fast as the
hardware allows" applied to inference): XLA recompiles on any new
abstract shape, and a serving process that compiles mid-traffic turns
a p50 of milliseconds into a p95 of seconds. So every program the
engine runs comes from a FINITE, warmed-up ladder:

* **Prefill** pads each prompt to the smallest power-of-two length
  bucket (``ServeConfig.prefill_bucket_floor`` up to the model's
  ``max_len``) and runs batch-1: one compiled program per rung.
  Causal masking makes the pad rows inert — the true prompt length
  rides in as a traced scalar that only picks the logits row. The
  prompt's cache rows — K and V, or whatever the block says it caches
  of a token (``blocks.py`` ``cache_rows``) — are scattered into the
  slot's blocks of the ONE KV pool (``paged_kv.PagedKVPool``: per layer
  ``[NB, BS, row]`` blocks behind per-slot block tables).
* **Extend** runs only a prompt's TAIL over context already in the
  pool — a prefix-cache hit, or one chunk of a chunked prefill — one
  program per tail bucket of the same ladder.
* **Decode** always runs the full ``[max_slots]`` batch — continuous
  batching means the batch composition changes every step, so the
  batch *shape* must not. Per-slot state (token, position, sampling
  seed/temperature/top-k) rides in as traced vectors; the block tables
  are cut to the smallest power-of-two bucket covering the longest
  active request (``kv_bucket_floor`` ladder), so short-context steps
  gather O(bucket) cache bytes — the serving-side mirror of
  ``ops/decode.flash_decode_attention``'s populated-prefix ladder,
  which the prefill path reuses directly under ``attention="flash"``
  (its scalar-length contract matches prefill exactly; the per-slot
  length *vector* of continuous decode is what
  ``kv_cache.varlen_decode_attention`` generalizes).
* **Verify** (``spec_decode_k > 0``) is decode over T = k+1 rows a
  slot, on the decode ladder.

``warmup()`` compiles the entire ladder ahead of traffic (the
AOT-compiled serving path: every program exists before the first
request) and every compiled variant is wrapped in the PR-3
``CompilationSentinel`` — a post-warmup recompile is a WARNING naming
the exact shape delta, and ``post_warmup_recompiles()`` is the number
CI asserts to be zero (tools/serve_bench.py banks it in the bench
record).

The forward math operates directly on the ``models/transformer.py``
param tree (same names: wte/wpe/h_i/ln_f) rather than through flax
``Transformer.apply``: the flax decode path keys the whole batch off
one scalar cache index, which continuous batching cannot use. Parity
with the flax model is pinned by tests/test_serving.py (engine vs
``transformer.generate`` greedy decode, token-identical).

Sampling reuses ``models.transformer.sample_tokens``'s exact math with
per-request keys (``fold_in(PRNGKey(seed), absolute_position)``), so a
request's tokens are a pure function of (params, prompt, seed) — the
batch it happened to be coalesced into cannot change its output, which
is what makes the continuous-batching golden test meaningful.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from tensorflow_examples_tpu.core import precision as precision_mod
from tensorflow_examples_tpu.models.transformer import TransformerConfig
from tensorflow_examples_tpu.ops.attention import NEG_INF, attention_reference
from tensorflow_examples_tpu.serving import kv_cache as kv_mod
from tensorflow_examples_tpu.serving import launch_block
from tensorflow_examples_tpu.serving import paged_kv
from tensorflow_examples_tpu.serving.blocks import Gpt2Block, block_for
from tensorflow_examples_tpu.telemetry import registry as registry_mod
from tensorflow_examples_tpu.telemetry import schema
from tensorflow_examples_tpu.telemetry.compilation import CompilationSentinel
from tensorflow_examples_tpu.telemetry.spans import span as host_span
from tensorflow_examples_tpu.utils import faults as faults_mod

log = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Engine + batcher knobs (one object configures the whole stack)."""

    max_slots: int = 8           # concurrent requests = decode batch shape
    prefill_bucket_floor: int = 16
    kv_bucket_floor: int = 64
    attention: str = "xla"       # xla | flash (Pallas prefill attend) |
    #                              paged_flash (fused Pallas paged-decode
    #                              kernel, ops/paged_decode.py)
    cache_dtype: str = ""        # "" -> follow the params dtype
    # ---- weight quantization (core/precision.py registry; ISSUE 15) ----
    weight_dtype: str = ""       # "" (serve the tree as restored) |
    #                              "int8" | "fp8": weight-only
    #                              quantization at LOAD time via
    #                              PrecisionConfig.weight_only —
    #                              kernels/embeddings stored at
    #                              1 byte/elt with per-row f32 scales,
    #                              dequantized inside the compiled
    #                              matmuls. Bounded-divergence mode
    #                              (first token exact in practice,
    #                              streams may diverge within the
    #                              serve_quant gate); fp8 requires
    #                              backend float8_e4m3fn support.
    compile_warmup: int = 1      # expected compiles per sentinel-wrapped fn
    # ---- speculative decoding (serving/speculative.py; ISSUE 11) ----
    spec_decode_k: int = 0       # drafts verified per decode step; 0 off.
    #                              Output streams stay token-identical
    #                              (acceptance is seed-deterministic);
    #                              k buys TPOT, never changes tokens.
    draft: str = "ngram"         # draft source; "ngram" = self-
    #                              speculative (no second model)
    draft_ngram: int = 3         # longest n-gram the drafter matches
    # ---- paged KV (serving/paged_kv.py; ISSUE 8) ----
    kv_block_size: int = 16      # token rows per KV block: a power of
    #                              two dividing both bucket floors and
    #                              max_len
    kv_blocks: int = 0           # physical blocks; 0 -> the worst case
    #                              (slots * max_len / block: every slot
    #                              at max_len at once)
    kv_dtype: str = ""           # "" -> cache_dtype | "int8" (per-block
    #                              scales, bounded-divergence mode)
    prefix_cache: bool = True    # reuse immutable full prompt blocks
    # ---- cache-aware fleet scheduling (serving/scheduler.py; ISSUE 12) ----
    role: str = "mixed"          # mixed | prefill | decode — the fleet
    #                              scheduling role the replica publishes
    #                              on /health. "mixed" (default) keeps
    #                              every pre-ISSUE-12 behavior; prefill
    #                              replicas run prompts to completion-of-
    #                              prefill and export the KV pages,
    #                              decode replicas import them and
    #                              continue the stream. Advisory: any
    #                              role still serves a full /generate
    #                              (that is what makes role failover a
    #                              plain in-flight failover).
    prefill_chunk_tokens: int = 0  # >0: admission splits any cold
    #                              prompt tail longer than this into
    #                              block-aligned chunks run one per
    #                              decode-loop iteration through the
    #                              extend rungs, so a long prefill
    #                              interleaves with decode steps
    #                              instead of monopolizing them.
    #                              Requires prefix_cache=True; must
    #                              be a multiple of kv_block_size.
    # ---- continuous batcher (serving/batcher.py) ----
    max_batch: int = 0           # admission cap; 0 -> max_slots
    max_queue: int = 64          # bounded queue PER SLO CLASS: beyond
    #                              this, load-shed
    max_delay_s: float = 0.002   # idle coalescing window before first prefill
    watchdog_secs: float = 0.0   # 0 disables the serve-loop watchdog
    # ---- brownout overload controller (serving/overload.py; ISSUE 13) ----
    brownout: bool = False       # enable the degradation ladder: shed
    #                              batch -> cap max_new_tokens -> skip
    #                              speculation -> shed interactive,
    #                              stepped with hysteresis as pressure
    #                              builds/clears
    brownout_queue_hi: int = 0   # queue-depth high watermark; 0 ->
    #                              2 * max_slots
    brownout_kv_hi: float = 0.92  # KV-occupancy high watermark
    brownout_ttft_hi_s: float = 0.0  # recent-window TTFT p95 high
    #                              watermark; 0 disables the signal
    brownout_clear_frac: float = 0.5  # clear watermark = frac * hi
    brownout_hold_s: float = 0.5  # hysteresis: min dwell per rung (up),
    #                              sustained-clear time per rung (down)
    brownout_max_new_tokens: int = 8  # the level-2 generation cap
    # ---- frontend ----
    request_timeout_s: float = 120.0

    def __post_init__(self):
        if self.kv_block_size <= 0:
            raise ValueError(
                f"kv_block_size={self.kv_block_size}: the dense "
                "(un-paged) KV pool that 0 used to select is gone; the "
                "engine serves from the paged pool alone (kv_block_size "
                "is a power of two, 16 by default)"
            )


# --------------------------------------------------------------- forward
#
# Every forward below is ONE loop over a block interface
# (serving/blocks.py): ``model.embed``, ``model.block(params, x, layer,
# positions, attend, valid)`` per layer, ``model.head``. They differ only
# in where ``attend(q, *row)`` finds the context — the fresh prompt, the
# pool through a block table — and in where they write ``row``: what the
# block caches of a token (K and V with heads; one latent row), scattered
# as the block handed it over. A block with ``own_attention`` attends
# from its rows itself (``chunk_attention`` / ``decode_attention``); the
# others get the generic attention over gathered heads. The verify
# forward serves GPT-2 alone (the engine refuses it for any other block
# at construction); prefill, decode and extend serve every block.


def _prefill_attend(q, k, v, *, impl: str):
    """Causal self-attention for prefill, [B, L, H, hd] layout.

    ``impl="flash"`` reuses ``ops/decode.flash_decode_attention`` with
    its exact contract: the freshly-computed K/V ARE the populated
    cache and the static bucket length is the scalar ``length`` — a
    prefill is precisely the single-length case of cache attention.
    """
    swap = lambda t: t.transpose(0, 2, 1, 3)  # [B,L,H,D] -> [B,H,L,D]
    if impl == "flash":
        from tensorflow_examples_tpu.ops.decode import flash_decode_attention

        out = flash_decode_attention(swap(q), swap(k), swap(v), q.shape[1])
    else:
        out = attention_reference(swap(q), swap(k), swap(v), causal=True)
    return swap(out)


def _run_blocks(model, params, x, positions, attend_for, valid=None):
    """The loop every forward shares: ``attend_for(layer)`` is the
    layer's ``attend(q, k, v)``. Returns the final hidden state and the
    blocks' stats summed over the layers (None for a model without)."""
    stats = None
    for layer in range(model.num_layers):
        x, s = model.block(
            params, x, layer, positions, attend_for(layer), valid
        )
        if s is not None:
            stats = s if stats is None else stats + s
    return x, stats


def _plain(model, layer) -> bool:
    """A layer the GPT-2 attention paths serve as they are: as many
    key/value heads as query heads, values as wide as keys, no window,
    no sink."""
    a = model.layer_attention[layer]
    return (
        a.kv_heads == a.heads and a.value_dim == a.key_dim and not a.sink
        and model.layer_windows[layer] is None
    )


def _grouped(model, params, layer) -> dict:
    """What the generic grouped attentions take of one layer beside its
    tensors (``layer_attention``, ``layer_windows``, ``sinks``)."""
    a = model.layer_attention[layer]
    return dict(
        window=model.layer_windows[layer], sm_scale=a.sm_scale,
        sinks=model.sinks(params, layer) if a.sink else None,
    )


@functools.lru_cache(maxsize=None)
def _record_kind_plan(family, rung, kinds):
    """One ``span/kind_plan`` per traced program of a pool with several
    kinds (at trace time, never inside a step): ``kinds`` is, per kind,
    ``schema.KIND_PLAN_KIND_KEYS`` — what the kind's layers keep of a
    token and attend with, the kind's physical blocks, and the columns
    of its table in this program."""
    with host_span(
        schema.KIND_PLAN_SPAN, family=family, rung=rung,
        kinds=[dict(zip(schema.KIND_PLAN_KIND_KEYS, k)) for k in kinds],
    ):
        pass


def _per_kind(tables) -> tuple:
    """A program's block-table argument by kind: the bare array of a
    one-kind pool (GPT-2's programs keep their signature), a tuple of
    one array per kind otherwise."""
    return tables if isinstance(tables, tuple) else (tables,)


def forward_full(cfg: TransformerConfig, params, tokens):
    """Full causal forward of ``tokens`` [B, L]: logits [B, L, V]. The
    engine's cacheless reference path (which recomputes attention over
    the whole prefix per emitted token). GPT-2 only."""
    model = Gpt2Block(cfg)
    positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)
    x, _ = _run_blocks(
        model, params, model.embed(params, tokens, positions), positions,
        lambda layer: functools.partial(_prefill_attend, impl="xla"),
    )
    return model.head(params, x)


# ------------------------------------------------------- the pool's forwards
#
# Cache rows land in per-layer [NB, BS, row] block pools addressed through
# per-slot block tables (ISSUE 8; paged_kv.py). ``kv`` is the pool's
# device state — one entry per array of the block's ``cache_rows``: (k,
# v) with rows of Hkv*D, (latent,) for a latent row — or, quantized, (k,
# v, k_scale, v_scale), each
# a tuple of one array per layer, with per-row scales stored blockwise
# ([NB, BS, H]; core/precision.quantize_rows). No
# program reads or writes more of a layer's array than the blocks its
# tables name: writes are in-place scatters of token rows, reads gather
# blocks and re-view only what they gathered. A layer's blocks are
# those of its KIND (``layer_kind[layer]``: 0 the full kind, then one
# per window; paged_kv.py): a pool of several kinds hands every program
# one table per kind where a one-kind pool hands it one.


def _paged_write_rows(kv, layer, index, *row):
    """Write a token's cache ``row`` — one ``[..., heads, width]`` array
    per array the pool keeps: K and V, or a latent row of one "head" —
    into layer ``layer``'s arrays at ``index`` (a tuple indexing their
    leading axes, block then row): values flattened to the pool's
    ``heads*width`` rows, K and V quantized with their
    per-row scales when the pool is (the store dtype, int8 or fp8,
    rides on the pool arrays themselves — one write path serves both).
    A decode or verify step writes ``(write_blocks, offsets)``, one row
    per slot (and draft); parked slots write into the null block (their
    table entry is 0) — discarded by masking."""
    from tensorflow_examples_tpu.core.precision import quantize_rows

    def rows(x):
        return x.reshape(*x.shape[:-2], -1)

    if len(kv) == 4:
        (qk, sk), (qv, sv) = (
            quantize_rows(x, kv[0][layer].dtype) for x in row
        )
        new = (rows(qk), rows(qv), sk, sv)
    else:
        new = tuple(rows(x) for x in row)
    return tuple(
        (*arrs[:layer],
         arrs[layer].at[index].set(x.astype(arrs[layer].dtype)),
         *arrs[layer + 1:])
        for arrs, x in zip(kv, new)
    )


def _paged_write_prompt(kv, written, block_ids, layer_kind, *, block_size):
    """Scatter a prefill's freshly computed cache rows (``written``: per
    layer, the row's arrays, ``[bucket, heads, width]`` each) into the
    blocks named by ``block_ids`` (one ``[bucket //
    BS]`` array per kind; pad entries point at the null block; their
    garbage is never read). ``[bucket, H, hd] -> [nb, BS, H, hd]`` is a
    pure reshape."""
    ids = _per_kind(block_ids)
    for layer, row in enumerate(written):
        row = (x.reshape(-1, block_size, *x.shape[1:]) for x in row)
        kv = _paged_write_rows(kv, layer, (ids[layer_kind[layer]],), *row)
    return kv


def _layer_scales(kv, layer) -> dict:
    """A quantized pool's per-row scales of one layer, as the keyword
    arguments every paged attention takes ({} for an fp pool)."""
    if len(kv) == 4:
        return dict(k_scale=kv[2][layer], v_scale=kv[3][layer])
    return {}


def _forward_prefill(model, params, kv, block_ids, tokens, length,
                     layer_kind, *, block_size: int, impl: str):
    """A whole prompt, ``tokens`` [1, bucket] right-padded: causal
    self-attention over the fresh rows, which are then scattered into
    the slot's blocks. Returns the pool state, the final hidden state
    [1, bucket, d] and the blocks' stats."""
    written = []

    def attend_for(layer):
        def attend(q, *row):  # each [1, bucket, heads, width]
            written.append(tuple(x[0] for x in row))
            if model.own_attention:
                return model.chunk_attention(
                    params, layer, q[0], row[0][0]
                )[None]
            k, v = row
            if _plain(model, layer):
                return _prefill_attend(q, k, v, impl=impl)
            return kv_mod.grouped_chunk_attention(
                q[0], k[0], v[0], **_grouped(model, params, layer)
            )[None]
        return attend

    positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)
    x, stats = _run_blocks(
        model, params, model.embed(params, tokens, positions), positions,
        attend_for, positions < length,
    )
    kv = _paged_write_prompt(
        kv, written, block_ids, layer_kind, block_size=block_size
    )
    return kv, x, stats


def _forward_decode(model, params, kv, tokens, positions, tables,
                    layer_kind, *, block_size: int,
                    attention: str = "xla"):
    """One continuous-decode step over every slot of the paged pool:
    writes route through the block table, attention gathers by it (the
    ``varlen_decode_attention`` block-table path). Under
    ``attention="paged_flash"`` the gather + masked attention fuse into
    the ``ops/paged_decode`` Pallas kernel — one launch reading K/V
    straight through the table (int8 pools dequantize in-kernel); the
    XLA gather path stays as the selectable reference oracle.

    ``tables``: per kind, [S, nb]. The full kind's holds each slot's
    logical blocks from 0; a window kind's those from the oldest block
    the slot's query reads (``kv_cache.window_base``), ``nb <= W / BS +
    1`` whatever the context. Slots not decoding have all-null tables
    (and position 0): their rows land in the null block, their pairs
    are routed nowhere, their output is discarded."""
    tabs = _per_kind(tables)
    lengths = positions + 1
    write_blocks = []
    for kind, table in enumerate(tabs):
        column = positions // block_size
        if kind:  # a window kind's table starts at the oldest block read
            window = model.layer_windows[layer_kind.index(kind)]
            column -= kv_mod.window_base(
                positions, window, block_size
            ) // block_size
        write_blocks.append(
            jnp.take_along_axis(table, column[:, None], axis=1)[:, 0]
        )
    offsets = positions % block_size
    plain_attend = kv_mod.varlen_decode_attention
    if attention == "paged_flash":
        from tensorflow_examples_tpu.ops.paged_decode import (
            paged_decode_attention as plain_attend,
        )
    state = [kv]

    def attend_for(layer):
        kind = layer_kind[layer]

        def attend(q, *row):  # each [S, heads, width]
            state[0] = kv_ = _paged_write_rows(
                state[0], layer, (write_blocks[kind], offsets), *row
            )
            if model.own_attention:
                return model.decode_attention(
                    params, layer, q, kv_[0][layer], positions, tabs[kind]
                )
            if _plain(model, layer):
                return plain_attend(
                    q, kv_[0][layer], kv_[1][layer], lengths,
                    block_tables=tabs[kind], **_layer_scales(kv_, layer),
                )
            return kv_mod.grouped_decode_attention(
                q, kv_[0][layer], kv_[1][layer], positions, tabs[kind],
                num_kv_heads=model.layer_attention[layer].kv_heads,
                **_grouped(model, params, layer),
            )
        return attend

    # A live slot always holds logical block 0 of the full kind (a model
    # whose blocks count nothing never reads this).
    valid = tabs[0][:, 0] != 0
    x, stats = _run_blocks(
        model, params, model.embed(params, tokens, positions), positions,
        attend_for, valid,
    )
    return state[0], model.head(params, x), stats


def _forward_verify(cfg: TransformerConfig, params, kv, tokens,
                    positions, tables, *, block_size: int):
    """The speculative ``verify_k`` step (ISSUE 11; GPT-2): score
    T = k+1 tokens per slot in ONE forward. ``tokens`` [S, T] holds each
    slot's launch token followed by its k draft tokens; row t lands at
    position ``positions[s] + t``, scattered through the block table
    (the spec window may cross block boundaries), and attends its own
    populated prefix over the slot's gathered view
    (``kv_cache.varlen_verify_attention``). Returns the pool state and
    logits [S, T, V]; T=1 is numerically the plain decode step. Rows
    beyond a slot's allocated blocks — draft padding the pool could not
    or need not back — resolve to the null block, whose garbage
    acceptance (host side) never commits."""
    model = Gpt2Block(cfg)
    s_n, t_n = tokens.shape
    nb = tables.shape[1]
    pos_grid = positions[:, None] + jnp.arange(t_n, dtype=jnp.int32)
    blk = jnp.minimum(pos_grid // block_size, nb - 1)
    write_blocks = jnp.where(
        pos_grid < nb * block_size,
        jnp.take_along_axis(tables, blk, axis=1),
        0,
    )
    offsets = pos_grid % block_size
    state = [kv]

    def attend_for(layer):
        def attend(q, k, v):  # [S, T, H, hd]
            state[0] = kv_ = _paged_write_rows(
                state[0], layer, (write_blocks, offsets), k, v
            )
            return kv_mod.varlen_verify_attention(
                q, kv_[0][layer], kv_[1][layer], positions,
                block_tables=tables, **_layer_scales(kv_, layer),
            )
        return attend

    x = model.embed(params, tokens, jnp.minimum(pos_grid, cfg.max_len - 1))
    x, _ = _run_blocks(model, params, x, pos_grid, attend_for)
    return state[0], model.head(params, x)


def _plain_extend_attention(q, k, v, kc, vc, ctx_len, sm_scale):
    """One extend step's attention for equal heads and no window: each
    tail row over (a) the cached context ``kc``/``vc`` [ctx_cols, H,
    hd], masked to ``ctx_len`` columns, and (b) the tail itself,
    causally. Numerics mirror ``varlen_decode_attention`` (f32
    scores/softmax, probabilities cast to the value dtype, f32
    accumulation) so hits stay token-identical at fp32 (test-pinned)."""
    tb, ctx_cols = q.shape[1], kc.shape[0]
    colc = jax.lax.broadcasted_iota(jnp.int32, (1, 1, tb, ctx_cols), 3)
    rowt = jax.lax.broadcasted_iota(jnp.int32, (1, 1, tb, tb), 2)
    colt = jax.lax.broadcasted_iota(jnp.int32, (1, 1, tb, tb), 3)
    s_ctx = jnp.einsum(
        "bthd,khd->bhtk", q, kc, preferred_element_type=jnp.float32
    ) * sm_scale
    s_ctx = jnp.where(colc < ctx_len, s_ctx, NEG_INF)
    s_tail = jnp.einsum(
        "bthd,bkhd->bhtk", q, k, preferred_element_type=jnp.float32
    ) * sm_scale
    s_tail = jnp.where(rowt >= colt, s_tail, NEG_INF)
    prob = jax.nn.softmax(
        jnp.concatenate([s_ctx, s_tail], axis=-1), axis=-1
    )
    p_ctx, p_tail = prob[..., :ctx_cols], prob[..., ctx_cols:]
    out = jnp.einsum(
        "bhtk,khd->bthd", p_ctx.astype(vc.dtype), vc,
        preferred_element_type=jnp.float32,
    ) + jnp.einsum(
        "bhtk,bkhd->bthd", p_tail.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.astype(q.dtype)


def _forward_extend(model, params, kv, ctx_table, tail_ids, tokens,
                    ctx_len, tail_len, layer_kind, *, block_size: int):
    """Chunked prefill on top of a cached context: run only the prompt
    TAIL (``tokens`` [1, tb], absolute positions ``ctx_len + i``), with
    each tail row attending over (a) the cached context gathered by
    ``ctx_table``, masked to ``ctx_len`` columns, and (b) the tail
    itself, causally. This is what makes a prefix-cache hit a compute
    saving, not just a memory one (the shared prefix's layers are never
    re-run), and what runs one chunk of a chunked prefill. Tail K/V is
    scattered into ``tail_ids`` [tb // BS].

    ``ctx_table`` and ``tail_ids``: per kind. The full kind's context
    table is the first blocks of the slot's table, as many as the
    program's context rung holds (the whole table on the top rung);
    a window kind's holds
    the logical blocks from the oldest one the chunk's first query
    reads (``kv_cache.window_base(ctx_len, ...)``), at most ``W / BS +
    1``."""
    tb = tokens.shape[1]
    ctx_tables = _per_kind(ctx_table)
    positions = ctx_len + jnp.arange(tb, dtype=jnp.int32)
    written = []

    def attend_for(layer):
        kind = layer_kind[layer]
        window = model.layer_windows[layer]

        def attend(q, *row):  # each [1, tb, heads, width]
            written.append(tuple(x[0] for x in row))
            if model.own_attention:
                # The cached rows as [ctx_cols, heads, width], as they lie.
                ctx = kv_mod.gather_block_kv(
                    kv[0][layer], ctx_tables[kind], row[0].shape[-2]
                )
                return model.chunk_attention(
                    params, layer, q[0], row[0][0], ctx, ctx_len
                )[None]
            k, v = row
            attn = model.layer_attention[layer]
            # The cached context as [ctx_cols, Hkv, width], from this
            # layer's blocks of the table alone.
            kc, vc = (
                x.astype(q.dtype) for x in kv_mod.gather_layer_kv(
                    kv[0][layer], kv[1][layer], ctx_tables[kind],
                    attn.kv_heads, q.dtype, **_layer_scales(kv, layer),
                )
            )
            if _plain(model, layer):
                return _plain_extend_attention(
                    q, k, v, kc, vc, ctx_len, attn.sm_scale
                )
            return kv_mod.grouped_chunk_attention(
                q[0], k[0], v[0], kc, vc, ctx_len=ctx_len,
                ctx_base=kv_mod.window_base(ctx_len, window, block_size),
                **_grouped(model, params, layer),
            )[None]
        return attend

    # Pad rows past the true tail may index past max_len; clip — they
    # are causally downstream of every real row and discarded.
    x = model.embed(
        params, tokens, jnp.minimum(positions, model.max_len - 1)
    )
    x, stats = _run_blocks(
        model, params, x, positions, attend_for,
        jnp.arange(tb) < tail_len,
    )
    kv = _paged_write_prompt(
        kv, written, tail_ids, layer_kind, block_size=block_size
    )
    return kv, x, stats


# The pool's device state in order (``PagedKVPool.kv_state``), by the
# names its arrays have in a KV page payload.
_PAGE_NAMES = ("k", "v", "k_scale", "v_scale")


# -------------------------------------------------------------- sampling


def _sample_row(key, logits, temp, top_k):
    """Traced-knob clone of ``models.transformer.sample_tokens`` for ONE
    row: temperature/top_k arrive as arrays (a batch mixes settings), so
    the static ``if``s become selects — same math, same keys, identical
    tokens (tests pin it)."""
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.where(temp > 0, temp, 1.0)
    kth = jax.lax.dynamic_index_in_dim(
        jnp.sort(scaled, stable=False),
        jnp.maximum(scaled.shape[0] - top_k, 0),
        keepdims=False,
    )
    filtered = jnp.where(
        (top_k > 0) & (scaled < kth), NEG_INF, scaled
    )
    sampled = jax.random.categorical(key, filtered).astype(jnp.int32)
    return jnp.where(temp == 0.0, greedy, sampled)


_sample_batch = jax.vmap(_sample_row)


def _token_logprobs(logits, tokens):
    """The model's own log-probability (temperature 1, no top-k: what
    ``classify`` reports) of each row's ``tokens`` entry, float32
    log-softmax — carried to the host as its int32 bit pattern so that
    it rides in the step's one fetch behind the tokens."""
    logits = logits.astype(jnp.float32)
    chosen = jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0]
    return jax.lax.bitcast_convert_type(
        chosen - jax.nn.logsumexp(logits, axis=-1), jnp.int32
    )


def request_key(seed: int, position: int) -> jax.Array:
    """The per-token sampling key: a pure function of (request seed,
    absolute position), so batched serving and the unbatched reference
    replay draw identical samples."""
    return jax.random.fold_in(jax.random.PRNGKey(seed), position)


# Every program derives its keys INSIDE the trace, from the (seed,
# position) its launch block carries — decode vmapped over the per-slot
# vectors, a prefill, an extend or a chunk from its two scalars
# (``InferenceEngine._operands``). An eager ``request_key`` on the
# batcher loop thread is two tiny device programs with operands of
# their own, sitting between consecutive compiled steps, exactly where
# TPOT is won or lost; only the plain reference calls it eagerly. Seeds
# are int32 (the frontend caps them at 2**31 - 1) so the traced
# PRNGKey seeding matches the eager replay's.
_request_key_batch = jax.vmap(request_key)


def _sample_verify(seeds, positions, logits, temps, top_ks):
    """Sample every verify row with its request's own per-POSITION key:
    row t of slot s draws with ``fold_in(seed_s, positions[s] + t + 1)``
    — exactly the key a plain decode step would consume at that
    absolute position. That per-position (not per-step) key discipline
    is what keeps sampled streams token-identical with speculation on:
    acceptance changes which rows ship, never what any position draws.
    """
    s_n, t_n, _ = logits.shape
    pos = positions[:, None] + jnp.arange(t_n, dtype=jnp.int32) + 1
    keys = jax.vmap(_request_key_batch)(
        jnp.broadcast_to(seeds[:, None], (s_n, t_n)), pos
    )
    flat = _sample_batch(
        keys.reshape((s_n * t_n,) + keys.shape[2:]),
        logits.reshape(s_n * t_n, -1),
        jnp.repeat(temps, t_n),
        jnp.repeat(top_ks, t_n),
    )
    return flat.reshape(s_n, t_n)


# ---------------------------------------------------------------- engine


class EngineStepError(RuntimeError):
    """A compiled prefill/decode step failed at runtime. The KV caches
    were donated to the failed call (consumed on donation-honoring
    backends), so the engine has already reallocated them — every
    in-flight request's cache state is gone and the batcher must fail
    the whole active set, not just the request being stepped."""


class ChunkedPrefill:
    """In-progress chunked prefill (ISSUE 12): the slot's blocks are
    already allocated (prefix reuse applied); ``spans`` are the
    block-aligned chunk plan and ``idx`` the next chunk to run. The
    batcher holds one of these per mid-prefill request and calls
    ``engine.prefill_step`` once per decode-loop iteration."""

    __slots__ = ("slot", "prompt", "spans", "idx", "seed",
                 "temperature", "top_k", "pending")

    def __init__(self, slot, prompt, spans, seed, temperature, top_k):
        self.slot = slot
        self.prompt = prompt
        self.spans = spans
        self.idx = 0
        self.seed = seed
        self.temperature = temperature
        self.top_k = top_k
        # Outputs of the chunks before the last, still on the device:
        # read with the final chunk's (the blocks' stats ride in them).
        self.pending = []


def _named(step, rung: str):
    """Give ``step`` — a ``functools.partial`` of an ``*_impl`` step
    function over its rung — the step function's name and the rung's
    (``K512``, ``T512_C2048``) as its ``__name__``
    (``paged_decode_impl_K512``). JAX names the
    compiled program after it in the lowering, the compile events and
    the profiler's trace; a nameless partial is ``jit__unknown``
    everywhere, and no decode execution can be told from a prefill.
    The sentinel's names (``serve_decode_K512``) are the operator's and
    stay."""
    step.__name__ = f"{step.func.__name__.lstrip('_')}_{rung}"
    return step


def _pack(tables: list):
    """A program's table argument: the bare array of a one-kind pool,
    a tuple of one array per kind otherwise (``_per_kind`` undoes it)."""
    return tables[0] if len(tables) == 1 else tuple(tables)


class InferenceEngine:
    """Loads params once, owns the KV pool, runs the compiled steps.

    Device-facing methods (``prefill`` / ``decode`` / ``warmup``) are
    single-threaded by contract — the continuous batcher's loop thread
    is the only caller. ``submit``-side concurrency lives in
    serving/batcher.py.
    """

    def __init__(
        self,
        model_cfg: TransformerConfig,
        params,
        *,
        cfg: ServeConfig | None = None,
        registry=None,
        sharding=None,
        precision=None,
    ):
        # The block this engine runs, chosen by the config's type
        # (serving/blocks.py): GPT-2's, Cohere2-MoE's or GLM-4.7-Flash's.
        self.model = block_for(model_cfg)
        self.gpt2 = isinstance(self.model, Gpt2Block)
        if self.gpt2 and model_cfg.moe_experts:
            raise NotImplementedError(
                "the GPT-2 serving block is dense: a TransformerConfig "
                "with moe_experts is not served (the expert layer the "
                "engine runs is Cohere2MoeConfig's)"
            )
        if self.gpt2 and model_cfg.attention not in ("xla", "flash"):
            # ring/ulysses are training-side context-parallel impls.
            raise ValueError(
                f"model attention={model_cfg.attention!r}; the serving "
                "forward supports 'xla' or 'flash'"
            )
        self.model_cfg = model_cfg
        self.cfg = cfg or ServeConfig()
        if not self.gpt2:
            self._refuse_for_block(sharding, precision)
        # Fleet identity (ISSUE 10): which replica this engine is in a
        # multi-replica process (serve_bench --router / the chaos
        # harness). The serve-side fault engine keys on it; 0 for a
        # standalone server.
        self.replica_id = 0
        if self.cfg.attention not in ("xla", "flash", "paged_flash"):
            raise ValueError(
                f"ServeConfig.attention={self.cfg.attention!r} not in "
                "('xla', 'flash', 'paged_flash')"
            )
        # Prefill always runs the full-prompt causal forward; the
        # paged-decode kernel only exists for the per-slot decode step.
        self._prefill_attn = (
            "flash" if self.cfg.attention == "flash" else "xla"
        )
        # Weight quantization at LOAD time (ISSUE 15): the precision
        # registry rewrites the host tree BEFORE any device placement
        # — quantized leaves are (q, scale) children under the
        # weight's own path, so the sharding rules below place them
        # like the weight they came from (scales by rank-clipped
        # spec). ``precision=`` takes a full PrecisionConfig; the
        # ``weight_dtype`` knob is sugar for the standard weight-only
        # registry. The registry's kv_dtype unifies the cache side:
        # ServeConfig.kv_dtype wins when both are set.
        self.precision = precision
        if self.precision is None and self.cfg.weight_dtype:
            self.precision = precision_mod.PrecisionConfig.weight_only(
                self.cfg.weight_dtype, kv_dtype=self.cfg.kv_dtype
            )
        self.kv_dtype = self.cfg.kv_dtype or (
            self.precision.kv_dtype if self.precision is not None else ""
        )
        if self.precision is not None:
            # Cast-only registries (bf16/f32 rules, no int8/fp8) apply
            # too; quantize_tree is the identity for an empty config.
            params = precision_mod.quantize_tree(params, self.precision)
        # Sharded serving (ISSUE 7): the SAME ShardingConfig training
        # persisted to workdir/sharding.json places the param tree by
        # its rules (instead of replicating) and the KV pool with heads
        # over `model`; GSPMD inserts the TP collectives into the
        # already-compiled prefill/decode ladder, so the zero-recompile
        # contract is untouched — the ladder is warmed with the
        # sharded placements it will serve with. sharding=None keeps
        # today's single-device placement exactly.
        self.sharding = sharding
        self.mesh = None
        self.param_sharding_digest = None
        if sharding is None:
            # COMMITTED to device 0, like the KV pool (_kv_sharding):
            # jit's cache key includes which arguments are committed,
            # so a ladder warmed with a fresh (uncommitted) pool and
            # then served with the committed arrays its own steps
            # returned recompiles the first rung on the first request —
            # on any host with more than one device, and invisibly to
            # the signature sentinel.
            params = jax.device_put(params, jax.devices()[0])
        else:
            # No asarray pre-pass: shard_params device_puts the host
            # tree straight into the mesh layout — a model that only
            # fits sharded must never materialize on device 0 first.
            from tensorflow_examples_tpu.core.sharding import shard_params
            from tensorflow_examples_tpu.models.transformer import (
                GPT2_RULES,
            )
            from tensorflow_examples_tpu.sharding import resolve_params

            self.mesh = sharding.build_mesh()
            rules = sharding.sharding_rules(default=GPT2_RULES)
            params = shard_params(params, self.mesh, rules)
            self.param_sharding_digest = resolve_params(
                params, self.mesh, rules
            ).digest()
        self.params = params
        self.registry = (
            registry if registry is not None
            else registry_mod.default_registry()
        )
        self.sentinel = CompilationSentinel(
            warmup=self.cfg.compile_warmup, registry=self.registry
        )
        # precision/* instruments (ISSUE 15): the serving tier's own
        # record of what precision it is actually running — weight
        # payload bits, stored-vs-f32 param bytes, quantized leaf
        # count. Scraped via /metrics, stamped (when quantized) as the
        # schema-v11 serving keys.
        self._precision_stats = precision_mod.tree_precision_stats(
            self.params
        )
        self.quantized_weights = (
            self._precision_stats["quantized_params"] > 0
        )
        reg = self.registry
        reg.gauge("precision/weight_bits").set(
            self._precision_stats["weight_bits"]
        )
        reg.gauge("precision/param_bytes").set(
            self._precision_stats["param_bytes"]
        )
        reg.gauge("precision/param_bytes_f32").set(
            self._precision_stats["param_bytes_f32"]
        )
        reg.gauge("precision/quantized_params").set(
            self._precision_stats["quantized_params"]
        )
        param_dtype = self.model.param_dtype(self.params)
        cache_dtype = (
            jnp.dtype(self.cfg.cache_dtype)
            if self.cfg.cache_dtype
            else param_dtype
        )
        if self.cfg.attention == "paged_flash" and self.kv_dtype == "fp8":
            raise ValueError(
                "attention='paged_flash' dequantizes int8 in-kernel; "
                "fp8 KV serves through the XLA gather path "
                "(attention='xla')"
            )
        if self.cfg.role not in ("mixed", "prefill", "decode"):
            raise ValueError(
                f"role={self.cfg.role!r} not in ('mixed', 'prefill', "
                "'decode')"
            )
        if self.cfg.prefill_chunk_tokens < 0:
            raise ValueError(
                f"prefill_chunk_tokens={self.cfg.prefill_chunk_tokens} "
                "must be >= 0"
            )
        if self.cfg.prefill_chunk_tokens:
            if not self.cfg.prefix_cache:
                raise ValueError(
                    "prefill_chunk_tokens requires prefix_cache=True "
                    "(the chunk program IS the per-tail-bucket extend "
                    "rung)"
                )
            if self.cfg.prefill_chunk_tokens % self.cfg.kv_block_size:
                raise ValueError(
                    f"prefill_chunk_tokens="
                    f"{self.cfg.prefill_chunk_tokens} must be a "
                    f"multiple of kv_block_size={self.cfg.kv_block_size}"
                    " (chunk boundaries scatter whole blocks)"
                )
        if self.cfg.spec_decode_k < 0:
            raise ValueError(
                f"spec_decode_k={self.cfg.spec_decode_k} must be >= 0"
            )
        if self.cfg.spec_decode_k + 1 > self.cfg.prefill_bucket_floor:
            # Parked slots write their discarded verify rows at
            # positions [0, k+1); any later prefill overwrites at least
            # the smallest bucket, which must cover them.
            raise ValueError(
                f"spec_decode_k={self.cfg.spec_decode_k} + 1 must not "
                f"exceed prefill_bucket_floor="
                f"{self.cfg.prefill_bucket_floor}"
            )
        bs = self.cfg.kv_block_size
        for name, val in (
            ("prefill_bucket_floor", self.cfg.prefill_bucket_floor),
            ("kv_bucket_floor", self.cfg.kv_bucket_floor),
            ("max_len", model_cfg.max_len),
        ):
            if val % bs:
                raise ValueError(
                    f"kv_block_size={bs} must divide {name}={val} "
                    "(every compiled bucket is a whole number of "
                    "blocks)"
                )
        # The TPU keeps a pool array whose rows are not a whole number
        # of 128-lane tiles with NB minor-most and re-lays ALL of it
        # around every row write, in every program (PERF.md §6, PRs 26
        # and 33). A block that makes its own row pads it
        # (``kv_cache.lane_dense``) or is refused; K and V with heads
        # are as wide as the model is, so that is said and served.
        # Rows under one tile are toy sizes and pass.
        for heads, width in (  # each row shape's arrays, once
            array for row in dict.fromkeys(self.model.cache_rows)
            for array in row
        ):
            values = heads * width
            if values % kv_mod.LANES == 0 or values < kv_mod.LANES:
                continue
            if self.model.own_attention:
                raise ValueError(
                    f"{self.model.name}: a cache row array of {values} "
                    f"values is not a whole number of {kv_mod.LANES}-lane "
                    "tiles; pad it in cache_rows (kv_cache.lane_dense) — "
                    "the TPU would re-lay the whole pool around every "
                    "write"
                )
            log.warning(
                "%s: cache rows of %d values (%d heads x %d) are not a "
                "whole number of %d-lane tiles: on a TPU every paged "
                "program re-lays each layer's whole pool",
                self.model.name, values, heads, width, kv_mod.LANES,
            )
        # A cache row is what the block says it is, layer by layer
        # (``cache_rows``: K and V of the layer's KEY/VALUE heads, fewer
        # than the query heads under grouped-query attention, a value
        # head as wide as the model makes it; one latent row, padded to
        # whole tiles); kv_blocks counts the full kind's blocks, a
        # window kind's follow from the slots, its W, the chunk and the
        # block size (``num_heads``/``head_dim`` are the first array's:
        # what a quantized pool keeps scales by).
        self.pool = paged_kv.PagedKVPool(
            num_layers=self.model.num_layers,
            num_slots=self.cfg.max_slots,
            num_heads=self.model.cache_rows[0][0][0],
            max_len=model_cfg.max_len,
            head_dim=self.model.cache_rows[0][0][1],
            block_size=bs,
            num_blocks=self.cfg.kv_blocks,
            dtype=cache_dtype,
            kv_dtype=self.kv_dtype,
            prefix_cache=self.cfg.prefix_cache,
            registry=self.registry,
            sharding=self._kv_sharding(),
            layer_windows=self.model.layer_windows,
            window_span=self.cfg.prefill_chunk_tokens,
            rows=tuple(
                tuple(h * w for h, w in row) for row in self.model.cache_rows
            ),
        )
        self._layer_kind = self.pool.layer_kind
        self._kinds = len(self.pool.kinds)
        # With chunked prefill on, no prefill or extend call is ever
        # longer than one chunk (a longer prompt is split, prefill_open):
        # the rungs above the chunk's are unreachable, and for a model
        # whose pool has a window kind they must not exist — the kind's
        # block space holds W plus ONE chunk a slot — nor for one that
        # attends from its own rows, whose chunk attention is sized for
        # a chunk of queries (a 32k-token rung's scores are not).
        longest = model_cfg.max_len
        if self.cfg.prefill_chunk_tokens and (
            self._kinds > 1 or self.model.own_attention
        ):
            longest = min(longest, self.cfg.prefill_chunk_tokens)
        self.prefill_ladder = kv_mod.bucket_ladder(
            min(self.cfg.prefill_bucket_floor, longest), longest
        )
        self.kv_ladder = kv_mod.bucket_ladder(
            self.cfg.kv_bucket_floor, model_cfg.max_len
        )
        # The pool's whole device-state tuple is donated (arg 1 after
        # partial binds the rung): every step returns the updated state
        # and the pool unconditionally reassigns from the outputs, so
        # XLA can alias in place instead of copying the pool per
        # generated token. Backends without donation support just
        # ignore the hint.
        self._prefill_fns = {
            lb: self.sentinel.wrap(
                jax.jit(
                    _named(functools.partial(
                        self._paged_prefill_impl, lb), f"L{lb}"),
                    donate_argnums=(1,),
                ),
                f"serve_prefill_L{lb}",
            )
            for lb in self.prefill_ladder
        }
        self._decode_fns = {
            kb: self.sentinel.wrap(
                jax.jit(
                    _named(functools.partial(
                        self._paged_decode_impl, kb), f"K{kb}"),
                    donate_argnums=(1,),
                ),
                f"serve_decode_K{kb}",
            )
            for kb in self.kv_ladder
        }
        # The extend family: a program per TAIL bucket over the slot's
        # WHOLE table (``_extend_fns[tb]``, ``extend_impl_T<tb>``, as it
        # always was: the cached context masked to its true length) and,
        # for the LONGEST tail bucket — the one that writes a prompt
        # chunk by chunk, a launch every ``prefill_ladder[-1]`` prompt
        # tokens where a shorter tail is one launch a request — a
        # program per CONTEXT rung under the whole table
        # (``_extend_fns[tb, cb]``, ``extend_impl_T<tb>_C<cb>``): the
        # context rides in as the table's first ``cb / BS`` blocks, and
        # a launch takes the smallest rung that holds its context as a
        # decode step takes its K (``_extend_launch``). The context
        # rungs are the kv ladder's from the longest tail up: a program
        # scores T x (C + T) columns, so a rung under the longest tail
        # saves less than the program it costs (each costs ~2 s of
        # every start-up) — and a model whose tails run to ``max_len``
        # (GPT-2: no chunk cap on its prefill ladder) has no rung under
        # the whole table.
        self.extend_ladder = [
            cb for cb in self.kv_ladder if cb >= self.prefill_ladder[-1]
        ]
        self._extend_fns = {
            rung: self.sentinel.wrap(
                jax.jit(
                    _named(functools.partial(
                        self._extend_impl, rung), name),
                    donate_argnums=(1,),
                ),
                f"serve_extend_{name}",
            )
            for rung, name in (
                *((tb, f"T{tb}") for tb in self.prefill_ladder),
                *(((self.prefill_ladder[-1], cb),
                   f"T{self.prefill_ladder[-1]}_C{cb}")
                  for cb in self.extend_ladder[:-1]),
            )
        } if self.cfg.prefix_cache else {}
        self._verify_fns = {
            kb: self.sentinel.wrap(
                jax.jit(
                    _named(functools.partial(
                        self._paged_verify_impl, kb), f"K{kb}"),
                    donate_argnums=(1,),
                ),
                f"serve_verify_K{kb}",
            )
            for kb in self.kv_ladder
        } if self.cfg.spec_decode_k > 0 else {}
        # The launch protocol: a program's per-launch operands travel as
        # ONE packed int32 block (launch_block), laid out per (kind,
        # rung) by ``_launch_spec`` and moved by ``_put`` — one
        # transfer per launch, counted beside the launches themselves.
        self._specs = {
            (kind, rung): self._launch_spec(kind, rung)
            for kind, fns in (
                ("prefill", self._prefill_fns), ("decode", self._decode_fns),
                ("extend", self._extend_fns), ("verify", self._verify_fns),
            )
            for rung in fns
        }
        # Where a block lives: the whole of it wherever params are —
        # device 0, committed like the params and the pool, or every
        # device of the mesh.
        self._block_home = jax.devices()[0] if self.mesh is None else (
            jax.sharding.NamedSharding(
                self.mesh, jax.sharding.PartitionSpec()
            )
        )
        self._transfers = reg.counter("serving/launch_transfers_total")
        self._launches = reg.counter("serving/launches_total")
        self.warmed = False
        # The last decode step's log-probability of each slot's token.
        self.last_logprobs = np.zeros((cfg.max_slots,), np.float32)
        self._ref_fwd = None

    def _refuse_for_block(self, sharding, precision) -> None:
        """A block other than GPT-2's runs on the pool's XLA path —
        prefill, extend (chunked prefill, prefix hits) and decode — and
        on nothing else. Every other mechanism keeps serving GPT-2 as it
        is and REFUSES this block here, by name, at construction: no
        silent fallback. The reason is the block's (``refused``): what
        its cache row is decides what cannot read it."""
        cfg, name = self.cfg, self.model.name
        refused = [
            ("verify", "speculative verify (spec_decode_k)",
             cfg.spec_decode_k > 0),
            ("pages", "KV page export/import (role='prefill'/'decode')",
             cfg.role != "mixed"),
            ("kv_dtype", "quantized KV (kv_dtype int8/fp8)",
             bool(cfg.kv_dtype or (precision and precision.kv_dtype))),
            ("weights", "weight quantization (weight_dtype / precision=)",
             bool(cfg.weight_dtype or precision)),
            ("paged_flash",
             "the fused paged_flash decode kernel (attention='paged_flash')",
             cfg.attention == "paged_flash"),
            ("flash", "the Pallas flash prefill (attention='flash')",
             cfg.attention == "flash"),
            ("sharding", "sharded serving (sharding=)", sharding is not None),
        ]
        for key, mechanism, on in refused:
            if on:
                raise NotImplementedError(
                    f"{mechanism} does not serve the {name} block "
                    f"({self.model.refused[key]}); it serves GPT-2 only"
                )

    def _kv_sharding(self):
        """KV-pool NamedSharding from the ShardingConfig: heads shard
        over ``model`` — the last dim of a layer's [NB, BS, H*D] (and of
        its [NB, BS, H] scales), whose shards hold whole heads — the layout
        that keeps per-slot attention local to the head shard the qkv
        projection already produced. A head count the model axis
        doesn't divide replicates instead (placement is an
        optimization, never a shape contract). Without a config: device
        0, explicitly — the same committed placement as the params."""
        if self.mesh is None:
            return jax.sharding.SingleDeviceSharding(jax.devices()[0])
        from jax.sharding import NamedSharding, PartitionSpec as P

        from tensorflow_examples_tpu.core.mesh import AxisNames

        m = int(self.mesh.shape[AxisNames.MODEL])
        heads = (
            AxisNames.MODEL
            if m > 1 and self.model.cache_rows[0][0][0] % m == 0
            else None
        )
        return NamedSharding(self.mesh, P(None, None, heads))

    # ------------------------------------------------- launch operands

    def _launch_spec(self, kind: str, rung: int) -> tuple:
        """The layout of one program's launch block (launch_block): its
        operands in the order its step function reads them, a static
        function of the program's kind, its rung, ``max_slots`` and the
        pool's kinds. A table is one array per kind. The three
        single-request programs end in (seed, position, temperature,
        top_k): the two the sampling key is made of, in the trace."""
        Field = launch_block.Field
        s = self.cfg.max_slots
        bs = self.cfg.kv_block_size
        if kind in ("decode", "verify"):
            wide = (s, self.cfg.spec_decode_k + 1) if kind == "verify" else (s,)
            return (
                Field("tokens", wide), Field("positions", (s,)),
                Field("tables", [
                    (s, nb) for nb in self._kind_blocks(rung // bs)
                ]),
                Field("seeds", (s,)), Field("temps", (s,), "float32"),
                Field("top_ks", (s,)),
            )
        if kind == "extend":
            tb, nb_full = self._extend_shape(rung)
            where = (
                Field("ctx_table", [
                    (nb,) for nb in self._kind_blocks(nb_full)
                ]),
                Field("tail_ids", [(tb // bs,)] * self._kinds),
                Field("tokens", (1, tb)),
                Field("ctx_len", ()), Field("tail_len", ()),
            )
        else:
            where = (
                Field("block_ids", [(rung // bs,)] * self._kinds),
                Field("tokens", (1, rung)), Field("length", ()),
            )
        return (
            *where, Field("seed", ()), Field("position", ()),
            Field("temperature", (), "float32"), Field("top_k", ()),
        )

    def _operands(self, kind: str, rung: int, operands: tuple):
        """A program's operands as its step function reads them. The
        engine launches every program with ONE packed block: taken
        apart here (static slices and bitcasts), and the sampling key
        of a prefill, an extend or a chunk made here, in the trace,
        from the block's (seed, position). The long form — every
        operand an argument of its own, the key a ``uint32[2]`` — is
        passed through: ``benchmark/sizing.py`` and ``sizing_kinds.py``
        lower the programs that way (PERF.md §7)."""
        if len(operands) > 1:
            return operands
        vals = [
            _pack(v) if isinstance(v, list) else v
            for v in launch_block.unpack(self._specs[kind, rung], operands[0])
        ]
        if kind in ("prefill", "extend"):
            *where, seed, position, temp, top_k = vals
            vals = [*where, request_key(seed, position), temp, top_k]
        return vals

    def _put(self, spec, values):
        """A launch's operands to the device: ONE transfer."""
        self._transfers.inc()
        return jax.device_put(
            launch_block.pack(spec, values), self._block_home
        )

    # ----------------------------------------------------- compiled fns

    def _with_stats(self, tokens, stats):
        """What a step hands back for the host's ONE fetch: the sampled
        token(s) (a decode step's with their log-probabilities'
        bits), and behind them the blocks' int32 stats where the model
        has any (no second transfer)."""
        if stats is None:
            return tokens
        return jnp.concatenate([jnp.reshape(tokens, (-1,)), stats])

    def _kind_plan(self, family: str, rung: int, table_blocks) -> None:
        """``span/kind_plan`` of one program of a pool with several
        kinds, as it is traced: what each kind's layers keep of a token
        and attend with, its physical blocks, and the columns of its
        table here (``table_blocks``)."""
        if self._kinds < 2:
            return
        pool, model = self.pool, self.model
        kinds = []
        for kind in sorted(set(pool.layer_kind)):  # the kinds that have layers
            layer = pool.layer_kind.index(kind)
            k_row, v_row = pool.kind_rows[kind]
            kinds.append((
                pool.kinds[kind], model.cache_rows[layer][0][0], k_row, v_row,
                model.layer_attention[layer].sink,
                pool.kind_blocks(kind), int(table_blocks[kind]),
            ))
        _record_kind_plan(family, rung, tuple(kinds))

    def _paged_prefill_impl(self, bucket, params, kv, *operands):
        """tokens [1, bucket] (right-padded), length = true prompt len.
        Scatters the prompt's K/V into the slot's blocks (pad rows land
        in the null block or carry garbage that per-slot length masking
        never reads), samples the first generated token from the logits
        at row length-1."""
        block_ids, tokens, length, key, temp, top_k = self._operands(
            "prefill", bucket, operands
        )
        self._kind_plan(
            "prefill", bucket,
            [bucket // self.cfg.kv_block_size] * self._kinds,
        )
        kv, x, stats = _forward_prefill(
            self.model, params, kv, block_ids, tokens, length,
            self._layer_kind, block_size=self.cfg.kv_block_size,
            impl=self._prefill_attn,
        )
        last = self.model.last_logits(params, x[0], length - 1)
        tok = _sample_row(key, last, temp, top_k)
        return kv, self._with_stats(tok, stats), last

    def _paged_decode_impl(self, bucket, params, kv, *operands):
        tokens, positions, tables, seeds, temps, top_ks = self._operands(
            "decode", bucket, operands
        )
        self._kind_plan(
            "decode", bucket,
            self._kind_blocks(bucket // self.cfg.kv_block_size),
        )
        kv, logits, stats = _forward_decode(
            self.model, params, kv, tokens, positions, tables,
            self._layer_kind, block_size=self.cfg.kv_block_size,
            attention=self.cfg.attention,
        )
        keys = _request_key_batch(seeds, positions + 1)
        toks = _sample_batch(keys, logits, temps, top_ks)
        return kv, self._with_stats(
            jnp.concatenate([toks, _token_logprobs(logits, toks)]), stats
        )

    def _paged_verify_impl(self, bucket, params, kv, *operands):
        """Speculative verify (ISSUE 11): tokens [S, T] = launch token
        + k drafts per slot, one forward, per-position sampling keys.
        Returns the pool state and the sampled stream [S, T] the host's
        acceptance walks. (The verify attention keeps the gather path —
        its cost amortizes over T tokens.)"""
        tokens, positions, tables, seeds, temps, top_ks = self._operands(
            "verify", bucket, operands
        )
        kv, logits = _forward_verify(
            self.model_cfg, params, kv, tokens, positions, tables,
            block_size=self.cfg.kv_block_size,
        )
        return kv, _sample_verify(seeds, positions, logits, temps, top_ks)

    def _extend_impl(self, rung, params, kv, *operands):
        """Prefix-cache hit path and chunked prefill: prefill only the
        prompt tail over the cached context (see ``_forward_extend``);
        samples the first token from the tail's last true row. ``rung``
        is the program's key in ``_extend_fns``."""
        (ctx_table, tail_ids, tokens, ctx_len, tail_len, key, temp,
         top_k) = self._operands("extend", rung, operands)
        tail_bucket, nb_full = self._extend_shape(rung)
        self._kind_plan("extend", tail_bucket, self._kind_blocks(nb_full))
        kv, x, stats = _forward_extend(
            self.model, params, kv, ctx_table, tail_ids, tokens,
            ctx_len, tail_len, self._layer_kind,
            block_size=self.cfg.kv_block_size,
        )
        last = self.model.last_logits(params, x[0], tail_len - 1)
        tok = _sample_row(key, last, temp, top_k)
        return kv, self._with_stats(tok, stats), last

    # ------------------------------------------------- tables, by kind

    def _kind_blocks(self, nb_full: int) -> list[int]:
        """Columns of each kind's table in a program whose full kind
        gets ``nb_full``: a window kind never needs more than the
        ``W / BS + 1`` logical blocks one query's window touches."""
        bs = self.cfg.kv_block_size
        return [
            nb_full if w is None else min(nb_full, w // bs + 1)
            for w in self.pool.kinds
        ]

    def _extend_shape(self, rung) -> tuple[int, int]:
        """(tail bucket, columns of the full kind's context table) of
        the extend program ``rung``: ``tb`` takes the slot's whole
        table, ``(tb, cb)`` its first ``cb / BS`` blocks."""
        if isinstance(rung, tuple):
            return rung[0], rung[1] // self.cfg.kv_block_size
        return rung, self.pool.max_blocks_per_slot

    def _extend_launch(self, slot: int, ctx: int, tail: int):
        """The extend program for ``tail`` new tokens over ``ctx``
        cached ones — the smallest tail bucket that holds the tail and,
        where the family has them for that bucket, the smallest context
        rung that holds the context — as (its key in ``_extend_fns``,
        its tail bucket, the slot's context tables for it); books what
        the launch gathers beside what it reads."""
        tb = kv_mod.pick_bucket(self.prefill_ladder, tail)
        cb = kv_mod.pick_bucket(self.extend_ladder, max(ctx, 1))
        rung = (tb, cb) if (tb, cb) in self._extend_fns else tb
        nb_full = self._extend_shape(rung)[1]
        reg = self.registry
        reg.counter(schema.EXTEND_GATHERED_TOKENS).inc(
            nb_full * self.cfg.kv_block_size
        )
        reg.counter(schema.EXTEND_CONTEXT_TOKENS).inc(ctx)
        return rung, tb, self._kind_tables(ctx, nb_full, slot)

    def _kind_tables(self, position, nb_full: int, slot=None,
                     live=None) -> list:
        """Each kind's table as the programs read it, for one ``slot``
        ([nb]) or for all ([S, nb], ``position`` a vector): the full
        kind's logical blocks from 0, a window kind's from the oldest
        block a query at ``position`` reads. ``live`` (with all slots)
        names the slots the step serves: every other row is null, so
        that a slot which holds blocks but is not stepping — one in the
        middle of a chunked prefill — is written into the null block
        like an empty one, and not into its own position 0."""
        pool, bs = self.pool, self.cfg.kv_block_size
        rows = slice(None) if slot is None else slice(slot, slot + 1)
        out = [np.ascontiguousarray(pool.block_tables[rows, :nb_full])]
        for kind, (w, nb) in enumerate(
            zip(pool.kinds, self._kind_blocks(nb_full))
        ):
            if kind == 0:
                continue
            first = np.maximum(np.asarray(position) - w + 1, 0) // bs
            cols = np.minimum(
                np.reshape(first, (-1, 1)) + np.arange(nb),
                pool.max_blocks_per_slot - 1,
            )
            out.append(np.take_along_axis(
                pool.window_tables(kind)[rows], cols, axis=1
            ).astype(np.int32))
        if live is not None:
            parked = np.ones((out[0].shape[0],), bool)
            parked[list(live)] = False
            out[0] = out[0].copy()  # it may be the pool's own array
            for table in out:
                table[parked] = 0
        return out if slot is None else [t[0] for t in out]

    def _span_ids(self, slot: int, first_block: int, last_block: int,
                  width: int) -> list:
        """Per kind, the physical ids of the slot's logical blocks
        ``[first_block, last_block)`` padded with the null block to
        ``width``: where a prefill or a chunk scatters its K/V."""
        out = []
        for kind in range(self._kinds):
            table = self.pool.block_tables if kind == 0 \
                else self.pool.window_tables(kind)
            ids = np.zeros((width,), np.int32)
            ids[:last_block - first_block] = table[
                slot, first_block:last_block
            ]
            out.append(ids)
        return out

    # --------------------------------------------------------- lifecycle

    def warmup(self) -> dict[str, int]:
        """Compile the full bucket ladder ahead of traffic (the AOT
        pass). Returns per-fn compile counts; after this, any further
        compile is a sentinel-warned recompile and
        ``post_warmup_recompiles()`` counts it."""
        # Every program is compiled as it is served: one packed block
        # of zeros (all-null tables: the writes land in the null block)
        # through the launch path's own transfer.
        bs = self.cfg.kv_block_size
        for kind, fns, named in (
            ("prefill", self._prefill_fns, dict(length=1)),
            ("decode", self._decode_fns, {}),
            ("extend", self._extend_fns, dict(ctx_len=bs, tail_len=1)),
            ("verify", self._verify_fns, {}),
        ):
            for rung, fn in fns.items():
                spec = self._specs[kind, rung]
                block = self._put(spec, launch_block.zeros(spec, **named))
                self._call(fn, block)[0].block_until_ready()
        self.pool.reset()
        self.warmed = True
        counts = self.sentinel.compile_counts()
        log.info(
            "serving engine warm: %d compiled programs (%s)",
            sum(counts.values()),
            ", ".join(sorted(counts)),
        )
        return counts

    def expected_compiles(self) -> int:
        return (
            len(self.prefill_ladder) + len(self.kv_ladder)
            + len(self._extend_fns) + len(self._verify_fns)
        )

    def post_warmup_recompiles(self) -> int:
        """Total compiles beyond each variant's warmup allowance — the
        number that must be 0 in steady state (CI asserts it)."""
        return self.sentinel.post_warmup_recompiles()

    # ------------------------------------------------ precision accounting

    def precision_stats(self) -> dict | None:
        """The schema-v11 serving keys (``weight_bits`` /
        ``param_bytes`` / ``param_bytes_f32`` / ``quantized_params``)
        when this engine serves quantized weights; None on an
        unquantized tree — a pre-quant serving line carries none of
        them, the same optional-on-write rule as every schema bump."""
        if not self.quantized_weights:
            return None
        return dict(self._precision_stats)

    def byte_breakdown(self, *, per_device: bool = False) -> dict:
        """Serving-side HBM accounting (what ``serve_bench
        --weight-dtype`` banks as ``hbm_bytes_per_replica`` and the
        quantized×sharded test states its ≤0.35× claim in):
        ``params_bytes`` as stored (quantized leaves at 1 byte/elt
        plus their f32 row scales), ``params_bytes_f32`` (the same
        logical tree at 4 bytes/elt), and the KV pool's committed
        bytes. ``per_device=True`` counts each leaf's bytes on ONE
        device — sharded leaves at 1/N (``telemetry/memory.tree_bytes``
        semantics) — and then reports ONLY the per-device-meaningful
        ``params_bytes``/``weight_bits``: the f32 baseline and the
        pool's used-block accounting are global numbers, and mixing
        units in one dict would make the natural ratios silently
        wrong (compare two engines' per-device ``params_bytes``
        instead, which is what the quantized×sharded test does)."""
        from tensorflow_examples_tpu.telemetry.memory import tree_bytes

        out = {
            "params_bytes": tree_bytes(
                self.params, per_device=per_device
            ),
            "weight_bits": self._precision_stats["weight_bits"],
        }
        if not per_device:
            out["params_bytes_f32"] = self._precision_stats[
                "param_bytes_f32"
            ]
            out["kv_cache_bytes"] = int(self.pool.used_bytes())
        return out

    # ------------------------------------------------------ request ops

    def _run_compiled(self, kind: str, fn, block):
        """Run one donated compiled step. On ANY runtime failure the
        donated KV buffers were consumed, so the pool is reallocated
        and :class:`EngineStepError` surfaces — the one place the
        donation-recovery contract lives (prefill/extend, decode, and
        verify all route through it; the batcher fails the whole
        in-flight set on the error).

        Every dispatch runs inside a host-side span
        (``span/engine_{kind}_dispatch``, ISSUE 18): the compiled call
        returns un-synced device arrays, so the span measures DISPATCH
        wall only — tracing adds no device sync and no new compiled
        programs (the zero-recompile sentinel stays golden-pinned).
        Its callers bracket the rest of a step the same way (ISSUE 25):
        ``engine_{kind}_build`` (numpy inputs, block tables),
        ``engine_{kind}_upload`` (``_put``: the operands packed into
        one block and its one ``device_put``) and
        ``engine_{kind}_fetch`` (the one ``np.asarray`` that waits for
        the device), so the serve thread has no unnamed stretch between
        a step's first line and its tokens in hand."""
        try:
            with host_span(f"engine_{kind}_dispatch"):
                return self._call(fn, block)
        except Exception as e:
            self.pool.reallocate()
            raise EngineStepError(
                f"compiled {kind} step failed (KV caches reallocated): "
                f"{type(e).__name__}: {e}"
            ) from e

    def _call(self, fn, block):
        """One compiled launch, counted (``serving/launches_total``
        beside ``serving/launch_transfers_total`` is the launch
        protocol's engagement: 1 transfer a launch): params, the
        pool's device state — donated — and the operand block in; the
        state kept from what comes back, the rest returned."""
        self._launches.inc()
        kv, *out = fn(self.params, self.pool.kv_state(), block)
        self.pool.set_kv_state(kv)
        return out

    def _prefill_fault_tick(self, slot: int) -> None:
        """Serve-side fault hook for PREFILL-role replicas (ISSUE 12):
        a dedicated prefill replica's unit of work is the prefill, not
        a decode step, so its fault schedule counts prefills — which is
        what lets the chaos tier kill one deterministically
        mid-handoff. Mixed/decode replicas keep the decode-step
        counting every existing golden pins."""
        if self.cfg.role != "prefill":
            return
        feng = faults_mod.serve_active()
        if feng is not None:
            feng.decode_step(self.replica_id, [slot])

    def prefill(self, slot: int, prompt: Sequence[int], *, seed: int = 0,
                temperature: float = 0.0, top_k: int = 0):
        """Run a prompt into ``slot``; returns (first generated token,
        last-position logits as numpy — the classify payload).

        Allocates exactly the blocks the prompt needs
        (``paged_kv.BlockExhausted`` propagates BEFORE any device call
        — no donation happened, so only THIS request fails) and, on a
        prefix-cache hit, maps the shared blocks into the slot's table
        and prefills only the tail (``_extend_impl``)."""
        n = len(prompt)
        if n < 1:
            raise ValueError("empty prompt")
        if n > self.model_cfg.max_len:
            raise ValueError(
                f"prompt length {n} exceeds max_len {self.model_cfg.max_len}"
            )
        self._prefill_fault_tick(slot)
        bs = self.cfg.kv_block_size
        # A hit is only possible when the extend rungs exist to serve
        # it: the pool's prefix cache and the engine's extend ladder
        # are both keyed off cfg.prefix_cache, so claim_prompt_blocks
        # returns ctx=0 exactly when there is no rung to run a tail on.
        with host_span("engine_prefill_build"):
            ctx, _ = self.pool.claim_prompt_blocks(slot, prompt)
            self.pool.ensure_span(slot, ctx, n)
            total_blocks = -(-n // bs)
            if ctx == 0:
                bucket = kv_mod.pick_bucket(self.prefill_ladder, n)
                ids = self._span_ids(slot, 0, total_blocks, bucket // bs)
                tokens = np.zeros((1, bucket), np.int32)
                tokens[0, :n] = prompt
                kind, fns, host = "prefill", self._prefill_fns, (
                    ids, tokens, n,
                )
            else:
                tail = n - ctx
                bucket, tb, ctx_tables = self._extend_launch(slot, ctx, tail)
                tail_ids = self._span_ids(
                    slot, ctx // bs, total_blocks, tb // bs
                )
                tokens = np.zeros((1, tb), np.int32)
                tokens[0, :tail] = prompt[ctx:]
                kind, fns, host = "extend", self._extend_fns, (
                    ctx_tables, tail_ids, tokens, ctx, tail,
                )
        # The first token is drawn with the key of (seed, n), made in
        # the program.
        with host_span("engine_prefill_upload"):
            block = self._put(self._specs[kind, bucket], (
                *host, seed, n, temperature, top_k,
            ))
        tok, last = self._run_compiled("prefill", fns[bucket], block)
        if ctx:
            self.registry.counter(
                "serving/prefix_reused_tokens"
            ).inc(ctx)
        self.pool.insert_prefix(slot, prompt)
        self.pool.lengths[slot] = n
        self.registry.counter("serving/prefill_tokens").inc(n)
        return self._fetch_prefill(tok, last)

    def _fetch_prefill(self, tok, last, pending=()):
        """The prefill's device->host sync: first token and last-row
        logits, under ``span/engine_prefill_fetch``. Where the model's
        blocks report stats they ride behind the token, and in the
        ``pending`` outputs of a chunked prefill's earlier chunks
        (finished long since: reading them waits for nothing)."""
        with host_span("engine_prefill_fetch"):
            tok, last = np.asarray(tok), np.asarray(last)
            if self.model.stats_len:
                for out in (*map(np.asarray, pending), tok):
                    self.model.count_stats(
                        self.registry, out[1:], decode=False
                    )
            return int(tok.reshape(-1)[0]), last

    # --------------------------------- chunked prefill (ISSUE 12 (b))

    def prefill_open(self, slot: int, prompt: Sequence[int], *,
                     seed: int = 0, temperature: float = 0.0,
                     top_k: int = 0):
        """Open a CHUNKED prefill when admission should split this
        prompt (``prefill_chunk_tokens > 0`` and the cold portion
        exceeds it); returns the :class:`ChunkedPrefill` state
        ``prefill_step`` consumes, or None when the prompt needs no
        chunking (the caller uses plain :meth:`prefill`). The slot's
        blocks — reused prefix blocks first — are allocated here
        all-or-nothing, so a ``BlockExhausted`` rejects the request
        before any device work."""
        chunk = self.cfg.prefill_chunk_tokens
        n = len(prompt)
        if chunk <= 0 or not self._extend_fns or n <= chunk:
            return None
        if n > self.model_cfg.max_len:
            raise ValueError(
                f"prompt length {n} exceeds max_len "
                f"{self.model_cfg.max_len}"
            )
        self._prefill_fault_tick(slot)
        from tensorflow_examples_tpu.serving import scheduler

        bs = self.cfg.kv_block_size
        ctx, _ = self.pool.claim_prompt_blocks(slot, prompt)
        if ctx:
            self.registry.counter("serving/prefix_reused_tokens").inc(ctx)
        spans = scheduler.plan_chunks(n, ctx, chunk, bs)
        if len(spans) > 1:
            # Single-span plans (a mostly-cached prompt whose cold tail
            # fits one chunk) are NOT chunked admissions — the batcher
            # runs them inline, exactly like the plain prefix-hit path.
            self.registry.counter("serving/chunked_prefills").inc()
        return ChunkedPrefill(
            slot, [int(t) for t in prompt], spans,
            seed, temperature, top_k,
        )

    def prefill_step(self, state: ChunkedPrefill):
        """Run ONE chunk of an open chunked prefill through the extend
        rung (the chunk attends the already-written context blocks,
        masked to the true covered length, plus itself causally).
        Returns ``(done, first_token, last_logits)`` — the token/logits
        are None until the final chunk, whose sampling key is
        ``request_key(seed, n)``, exactly the unchunked prefill's, so
        the chunked stream is token-identical to the single-shot one
        (test-pinned)."""
        bs = self.cfg.kv_block_size
        slot, prompt = state.slot, state.prompt
        with host_span("engine_prefill_build"):
            start, end = state.spans[state.idx]
            tail = end - start
            # Window kinds claim this chunk's blocks and let go of what
            # no query from ``start`` on can read.
            self.pool.ensure_span(slot, start, end)
            rung, tb, ctx_tables = self._extend_launch(slot, start, tail)
            tail_ids = self._span_ids(
                slot, start // bs, -(-end // bs), tb // bs
            )
            tokens = np.zeros((1, tb), np.int32)
            tokens[0, :tail] = prompt[start:end]
        with host_span("engine_prefill_upload"):
            block = self._put(self._specs["extend", rung], (
                ctx_tables, tail_ids, tokens, start, tail,
                state.seed, end, state.temperature, state.top_k,
            ))
        tok, last = self._run_compiled(
            "prefill", self._extend_fns[rung], block
        )
        state.idx += 1
        self.registry.counter("serving/prefill_chunks").inc()
        if state.idx < len(state.spans):
            if self.model.stats_len:
                state.pending.append(tok)
            return False, None, None
        n = len(prompt)
        self.pool.lengths[slot] = n
        self.pool.insert_prefix(slot, prompt)
        self.registry.counter("serving/prefill_tokens").inc(n)
        return (True, *self._fetch_prefill(tok, last, state.pending))

    # ----------------------------------- KV page handoff (ISSUE 12 (c))

    def _pages_are_gpt2s(self, what: str) -> None:
        if not self.gpt2:
            raise NotImplementedError(
                f"KV page {what} does not serve the {self.model.name} "
                "block (a page payload has one block-id space and K and "
                "V rows of equal heads); it serves GPT-2 only"
            )

    def export_kv_pages(self, slot: int, prompt: Sequence[int], *,
                        skip_tokens: int = 0) -> dict:
        """Serialize the slot's finished prompt KV blocks as the
        prefill->decode handoff payload (``scheduler.encode_pages``
        wire format, quantization scales included). The prefill-role
        half of disaggregated serving: the importer's decode continues
        with numerically identical cache state, so the handed-off
        stream is token-identical to a mixed replica serving the whole
        request.

        ``skip_tokens`` is the streaming DELTA handoff (ISSUE 15
        satellite): the router's digest exchange says the importer
        already caches that many leading prompt tokens, so the leading
        full blocks they cover stay OFF the wire (``start_block``
        meta). Floored to this replica's block multiple and capped so
        at least the final (partial) block always ships."""
        self._pages_are_gpt2s("export")
        if skip_tokens < 0:
            raise ValueError(f"skip_tokens={skip_tokens} must be >= 0")
        from tensorflow_examples_tpu.serving import scheduler

        n = len(prompt)
        bs = self.cfg.kv_block_size
        nb = -(-n // bs)
        # Only FULL blocks strictly before the tail are skippable —
        # the same cap prefix_lookup applies to reusable blocks.
        skip = min(int(skip_tokens) // bs, (n - 1) // bs)
        idx = jnp.asarray(
            [int(b) for b in self.pool.block_tables[slot, skip:nb]]
        )
        # The wire keeps its [L, pages, H, BS, D] order (scales [L,
        # pages, H, BS]); the few pages moved are re-ordered on the
        # host copy.
        def to_wire(name, layers):
            # [L, pages, BS, H*D] (scales: [L, pages, BS, H]).
            arr = np.stack(jax.device_get([a[idx] for a in layers]))
            if not name.endswith("_scale"):
                arr = arr.reshape(
                    *arr.shape[:-1], self.model_cfg.num_heads, -1
                )
            return np.ascontiguousarray(np.moveaxis(arr, 3, 2))

        arrays = {
            name: to_wire(name, layers)
            for name, layers in zip(_PAGE_NAMES, self.pool.kv_state())
        }
        meta = dict(
            block_size=bs,
            num_layers=self.model_cfg.num_layers,
            num_heads=self.model_cfg.num_heads,
            head_dim=self.model_cfg.head_dim,
            length=n,
            kv_bits=self.pool.kv_bits,
            start_block=skip,
        )
        self.registry.counter("serving/kv_pages_exported").inc(nb - skip)
        if skip:
            self.registry.counter(
                "serving/kv_pages_delta_skipped"
            ).inc(skip)
        return scheduler.encode_pages(meta, arrays)

    def import_kv_pages(self, slot: int, payload,
                        prompt: Sequence[int]) -> None:
        """Map a handed-off page payload into ``slot``: validate the
        geometry against this replica's pool (mismatch is a loud
        ValueError -> 400, never a silently wrong cache), claim the
        blocks, scatter the host arrays in, set the slot's length, and
        publish the prompt into the local prefix cache so later
        shared-prefix traffic gains affinity here too. A
        ``BlockExhausted`` propagates before any write (503 upstream)."""
        self._pages_are_gpt2s("import")
        from tensorflow_examples_tpu.serving import scheduler

        meta, arrays = scheduler.decode_pages(payload)
        expect = dict(
            block_size=self.cfg.kv_block_size,
            num_layers=self.model_cfg.num_layers,
            num_heads=self.model_cfg.num_heads,
            head_dim=self.model_cfg.head_dim,
            kv_bits=self.pool.kv_bits,
        )
        for key, want in expect.items():
            if meta[key] != want:
                raise ValueError(
                    f"pages geometry mismatch: {key}={meta[key]} but "
                    f"this replica serves {key}={want}"
                )
        n = meta["length"]
        if n != len(prompt):
            raise ValueError(
                f"pages cover {n} tokens but the prompt has "
                f"{len(prompt)}"
            )
        if n > self.model_cfg.max_len:
            raise ValueError(
                f"pages length {n} exceeds max_len "
                f"{self.model_cfg.max_len}"
            )
        bs = self.cfg.kv_block_size
        nb = -(-n // bs)
        # Delta handoff (ISSUE 15 satellite): the payload may start at
        # start_block > 0 — the exporter left off leading blocks the
        # router's digest exchange says this replica already caches
        # (absent on pre-delta payloads: a full export).
        start = meta.get("start_block", 0)
        if start >= nb:
            raise ValueError(
                f"pages start_block={start} but the prompt spans only "
                f"{nb} blocks"
            )
        nb_pages = nb - start
        shapes = {
            "k": (meta["num_layers"], nb_pages, meta["num_heads"], bs,
                  meta["head_dim"]),
            "v": (meta["num_layers"], nb_pages, meta["num_heads"], bs,
                  meta["head_dim"]),
        }
        if self.pool.quantized:
            shapes["k_scale"] = shapes["k"][:-1]
            shapes["v_scale"] = shapes["v"][:-1]
        for name, want_shape in shapes.items():
            arr = arrays.get(name)
            if arr is None:
                raise ValueError(f"pages payload is missing {name!r}")
            if tuple(arr.shape) != want_shape:
                raise ValueError(
                    f"pages array {name!r} has shape "
                    f"{tuple(arr.shape)}, expected {want_shape}"
                )
        state = self.pool.kv_state()
        for name, layers in zip(_PAGE_NAMES, state):
            # The payload arrays carry the DONOR's cache dtype; a
            # same-width mismatch (f16 pages into a bf16 pool) would
            # value-cast every KV entry — exactly the silently-wrong
            # cache the wire format promises cannot happen. kv_bits
            # catches width; this catches kind.
            want = jnp.dtype(layers[0].dtype)
            got = jnp.dtype(arrays[name].dtype)
            if got != want:
                raise ValueError(
                    f"pages dtype mismatch: {name!r} is {got} but "
                    f"this replica's cache stores {want}"
                )
        # Leading blocks this pool ALREADY caches (a previous handoff
        # or local prefill of the same prefix) are mapped, not
        # re-scattered: chained exact-token keys guarantee identical
        # content, so repeated handoffs of a shared system prompt hold
        # one copy and pay the device write only for the cold tail.
        ctx, fresh = self.pool.claim_prompt_blocks(slot, prompt)
        if ctx < start * bs:
            # The delta payload assumes this replica caches the first
            # ``start`` blocks, but the local prefix cache covers only
            # ``ctx`` tokens (evicted since the router's probe, or a
            # stale/bloom-false-positive digest). Loud 400 — the
            # router falls back to the full path, never a torn cache.
            raise ValueError(
                f"delta pages start at block {start} but this "
                f"replica's prefix cache covers only {ctx} of "
                f"{start * bs} skipped tokens — re-send full pages"
            )
        if fresh:
            col = nb - len(fresh) - start  # payload column of fresh[0]
            idx = jnp.asarray(fresh)
            new = []
            for name, layers in zip(_PAGE_NAMES, state):
                # Wire order [L, pages, H, BS(, D)] -> the pool's rows
                # [L, pages, BS, H(*D)], on the host copy.
                pages = np.moveaxis(arrays[name][:, col:], 2, 3)
                pages = pages.reshape(*pages.shape[:3], -1)
                new.append(tuple(
                    a.at[idx].set(jnp.asarray(pages[layer]))
                    for layer, a in enumerate(layers)
                ))
            self.pool.set_kv_state(tuple(new))
        self.pool.lengths[slot] = n
        self.pool.insert_prefix(slot, prompt)
        self.registry.counter("serving/kv_pages_imported").inc(
            len(fresh)
        )
        if ctx:
            self.registry.counter(
                "serving/prefix_reused_tokens"
            ).inc(ctx)

    # graftlint: hot-path — one bulk np.asarray per step is the budget;
    # any additional host sync lands straight in TPOT (ISSUE 14).
    def decode(self, entries: Sequence[tuple[int, int, int, float, int]]):
        """One continuous-decode step. ``entries`` is the active set:
        (slot, input_token, seed, temperature, top_k) per request —
        every entry's input token sits at cache row
        ``pool.lengths[slot]``. Returns {slot: generated token}; the
        model's log-probability of each is ``last_logprobs[slot]``
        until the next step."""
        if not entries:
            return {}
        feng = faults_mod.serve_active()
        if feng is not None:
            # Serve-side fault hook (ISSUE 10): may sleep (slowrep),
            # raise a forced BlockExhausted (kvexhaust) or kill this
            # replica's transport and raise InjectedCrash (crash) —
            # all BEFORE any device call, so no donated state is lost
            # to an injected fault.
            feng.decode_step(self.replica_id, [e[0] for e in entries])
        bucket = kv_mod.pick_bucket(
            self.kv_ladder,
            max(int(self.pool.lengths[e[0]]) for e in entries) + 1,
        )
        with host_span("engine_decode_build", K=bucket):
            s = self.cfg.max_slots
            tokens = np.zeros((s,), np.int32)
            positions = np.zeros((s,), np.int32)
            temps = np.zeros((s,), np.float32)
            top_ks = np.zeros((s,), np.int32)
            seeds = np.zeros((s,), np.int32)
            slots = []
            for slot, token, seed, temp, tk in entries:
                pos = int(self.pool.lengths[slot])
                tokens[slot] = token
                positions[slot] = pos
                temps[slot] = temp
                top_ks[slot] = tk
                seeds[slot] = seed
                slots.append(slot)
            # Grow block tables BEFORE the device step: an exhaustion
            # here has consumed nothing (no donation yet), so only the
            # requests that could not grow fail — the engine keeps
            # serving the rest (the batcher handles the partition).
            exhausted = []
            for slot in slots:
                try:
                    self.pool.ensure_position(
                        slot, int(positions[slot])
                    )
                except paged_kv.BlockExhausted:
                    exhausted.append(slot)
            if exhausted:
                raise paged_kv.BlockExhausted(
                    "KV block pool exhausted mid-decode for slot(s) "
                    f"{exhausted}; pool is serving at capacity",
                    slots=tuple(exhausted),
                )
            bs = self.cfg.kv_block_size
            tables = self._kind_tables(
                positions, bucket // bs, live=slots
            )
            if not self.gpt2:
                # What the pool holds for what is resident (a block
                # several slots share counted once), and what of it
                # this step's tables reach (every slot's own reach: a
                # shared block once per reader), sampled once a step
                # (kv_bytes_per_resident_token; the decode roofline's
                # cache bytes: the row's VALUES, what the mathematics
                # reads, whatever pad columns the pool stores).
                # A pool of several kinds also books its bytes kind by
                # kind, and every step the token rows its full-kind
                # gather touches: the rung, for every live slot.
                reg, pool = self.registry, self.pool
                ctx = positions[slots].astype(np.int64) + 1
                by_kind = pool.used_bytes_by_kind()
                reg.counter("serving/kv_sampled_bytes").inc(sum(by_kind))
                if len(by_kind) > 1:
                    for kind, used in enumerate(by_kind):
                        reg.counter(
                            schema.KV_KIND_BYTES_COUNTER_PREFIX
                            + pool.kind_name(kind)
                        ).inc(used)
                reg.counter("serving/kv_sampled_tokens").inc(
                    int(ctx.sum())
                )
                reg.counter(schema.DECODE_GATHERED_TOKENS).inc(
                    bucket * len(slots)
                )
                itemsize = jnp.dtype(pool.dtype).itemsize
                reach = [
                    int((ctx if w is None else np.minimum(ctx, w)).sum())
                    for w in pool.kinds
                ]
                reg.counter("serving/kv_sampled_reach_bytes").inc(sum(
                    values * itemsize * reach[kind] for values, kind in
                    zip(self.model.row_values, pool.layer_kind)
                ))
        with host_span("engine_decode_upload"):
            block = self._put(self._specs["decode", bucket], (
                tokens, positions, tables, seeds, temps, top_ks,
            ))
        (out,) = self._run_compiled(
            "decode", self._decode_fns[bucket], block
        )
        with host_span("engine_decode_fetch"):
            out = np.asarray(out)
        # [S] tokens, [S] float32 log-probabilities as bits, the stats.
        self.last_logprobs = out[s:2 * s].view(np.float32)
        if self.model.stats_len:
            self.model.count_stats(self.registry, out[2 * s:], decode=True)
        for slot in slots:
            self.pool.lengths[slot] += 1
        self.registry.counter("serving/decode_steps").inc()
        self.registry.counter("serving/decode_tokens").inc(len(slots))
        return {slot: int(out[slot]) for slot in slots}

    # graftlint: hot-path — same budget as decode(): the one bulk
    # np.asarray(out) below is the step's accepted device->host sync.
    def verify(self, entries):
        """One SPECULATIVE decode step (ISSUE 11): score each active
        request's launch token plus its draft tokens in one compiled
        ``verify_k`` forward and commit the longest agreeing prefix.

        ``entries``: (slot, input_token, draft_tokens, seed,
        temperature, top_k) per request — the input token sits at cache
        row ``pool.lengths[slot]``, drafts at the rows after it.
        Returns {slot: committed token list} — ALWAYS at least one
        token per entry (the verify-sampled next token; a plain decode
        step would have produced exactly it), plus one more per
        accepted draft (``speculative.accept_drafts``). ``lengths``
        advance by the committed count, so rejected draft rows are
        overwritten by the next step's writes and never attended.
        """
        if not entries:
            return {}
        if not self._verify_fns:
            raise RuntimeError(
                "verify() requires spec_decode_k > 0 (no verify rungs "
                "were compiled)"
            )
        from tensorflow_examples_tpu.serving.speculative import (
            accept_drafts,
        )

        feng = faults_mod.serve_active()
        if feng is not None:
            # Same serve-side fault hook as decode(): a chaos schedule
            # counts speculative steps exactly like plain ones, BEFORE
            # any device call (no donated state lost to a fault).
            feng.decode_step(self.replica_id, [e[0] for e in entries])
        t_n = self.cfg.spec_decode_k + 1
        max_len = self.model_cfg.max_len
        bucket = kv_mod.pick_bucket(
            self.kv_ladder,
            min(max(int(self.pool.lengths[e[0]]) for e in entries) + t_n,
                max_len),
        )
        with host_span("engine_verify_build", K=bucket):
            s = self.cfg.max_slots
            tokens = np.zeros((s, t_n), np.int32)
            positions = np.zeros((s,), np.int32)
            temps = np.zeros((s,), np.float32)
            top_ks = np.zeros((s,), np.int32)
            seeds = np.zeros((s,), np.int32)
            slots: list[int] = []
            drafts_by_slot: dict[int, list[int]] = {}
            limits: dict[int, int] = {}
            for slot, token, drafts, seed, temp, tk in entries:
                pos = int(self.pool.lengths[slot])
                drafts = [int(d) for d in drafts][: self.cfg.spec_decode_k]
                tokens[slot, 0] = token
                tokens[slot, 1:1 + len(drafts)] = drafts
                positions[slot] = pos
                temps[slot] = temp
                top_ks[slot] = tk
                seeds[slot] = seed
                slots.append(slot)
                drafts_by_slot[slot] = drafts
            exhausted = []
            for slot in slots:
                pos = int(positions[slot])
                try:
                    self.pool.ensure_position(
                        slot, min(pos + t_n - 1, max_len - 1)
                    )
                except paged_kv.BlockExhausted:
                    # Shrink the spec window before shedding anything:
                    # the NON-speculative requirement is one row.
                    try:
                        self.pool.ensure_position(slot, pos)
                    except paged_kv.BlockExhausted:
                        exhausted.append(slot)
                        continue
                # Committed rows must have landed in the cache: in the
                # blocks the slot holds (at most max_len rows).
                limits[slot] = self.pool.covered_positions(slot) - pos
            if exhausted:
                raise paged_kv.BlockExhausted(
                    "KV block pool exhausted mid-decode for slot(s) "
                    f"{exhausted}; pool is serving at capacity",
                    slots=tuple(exhausted),
                )
            bs = self.cfg.kv_block_size
            tables = self._kind_tables(
                positions, bucket // bs, live=slots
            )
        with host_span("engine_verify_upload"):
            block = self._put(self._specs["verify", bucket], (
                tokens, positions, tables, seeds, temps, top_ks,
            ))
        (out,) = self._run_compiled(
            "verify", self._verify_fns[bucket], block
        )
        with host_span("engine_verify_fetch"):
            out = np.asarray(out)
        committed: dict[int, list[int]] = {}
        total = drafted = accepted = 0
        for slot in slots:
            toks = accept_drafts(
                drafts_by_slot[slot], out[slot], limit=limits[slot]
            )
            committed[slot] = toks
            self.pool.lengths[slot] += len(toks)
            total += len(toks)
            drafted += len(drafts_by_slot[slot])
            accepted += len(toks) - 1
        reg = self.registry
        reg.counter("serving/decode_steps").inc()
        reg.counter("serving/decode_tokens").inc(total)
        reg.counter("serving/spec_steps").inc()
        reg.counter("serving/spec_request_steps").inc(len(slots))
        reg.counter("serving/spec_drafted_total").inc(drafted)
        reg.counter("serving/spec_accepted_total").inc(accepted)
        return committed

    # ------------------------------------------------------- references

    def _reference_step(self):
        """One jitted (params, tokens[1, max_len], length, key, temp,
        top_k) -> (sampled token, last-row logits) step for the
        reference replay. Always the full ``max_len`` shape — rows past
        ``length`` hold zeros that causal masking makes inert, so ONE
        compile covers every prefix length and the replay is not
        eager-dispatch-bound. Deliberately NOT sentinel-wrapped: the
        reference is test/verify machinery, never the serving path, and
        must not count against the zero-recompile budget."""
        if self._ref_fwd is None:
            def step(params, tokens, length, key, temp, top_k):
                logits = forward_full(self.model_cfg, params, tokens)
                last = jax.lax.dynamic_index_in_dim(
                    logits[0], length - 1, keepdims=False
                )
                return _sample_row(key, last, temp, top_k), last

            self._ref_fwd = jax.jit(step)
        return self._ref_fwd

    def _reference_last(self, toks: list[int], *, seed: int,
                        temperature: float, top_k: int):
        padded = np.zeros((1, self.model_cfg.max_len), np.int32)
        padded[0, :len(toks)] = toks
        return self._reference_step()(
            self.params, jnp.asarray(padded), jnp.int32(len(toks)),
            request_key(seed, len(toks)), jnp.float32(temperature),
            jnp.int32(top_k),
        )

    def reference_generate(self, prompt: Sequence[int], *, max_new: int,
                           seed: int = 0, temperature: float = 0.0,
                           top_k: int = 0, eos_id: int | None = None):
        """The unbatched, cacheless replay of one request: a full
        forward of the whole prefix per emitted token, sampling with
        the same (seed, position) keys. O(n^2) on purpose — it shares
        no batching, bucketing, or KV-cache machinery with the serving
        path, which is what makes the continuous-batching golden
        comparison meaningful."""
        toks = [int(t) for t in prompt]
        out: list[int] = []
        for _ in range(max_new):
            tok, _ = self._reference_last(
                toks, seed=seed, temperature=temperature, top_k=top_k
            )
            nxt = int(tok)
            out.append(nxt)
            toks.append(nxt)
            if eos_id is not None and nxt == eos_id:
                break
        return out

    def reference_classify(self, prompt: Sequence[int], *, top_n: int = 5):
        _, last = self._reference_last(
            [int(t) for t in prompt], seed=0, temperature=0.0, top_k=0
        )
        return top_logprobs(np.asarray(last), top_n)


def _log_softmax(logits: np.ndarray) -> np.ndarray:
    x = logits.astype(np.float64)
    return x - (np.log(np.sum(np.exp(x - x.max()))) + x.max())


def top_logprobs(logits: np.ndarray, top_n: int) -> list[dict]:
    """Next-token distribution head: top-n (token, logprob) pairs."""
    logp = _log_softmax(logits)
    order = np.argsort(logp)[::-1][:top_n]
    return [{"token": int(t), "logprob": float(logp[t])} for t in order]


def token_logprob(logits: np.ndarray, token: int) -> float:
    """The log-probability of ``token`` under ``logits`` (a prefill's
    last row: what the first generated token was sampled from)."""
    return float(_log_softmax(logits)[token])
