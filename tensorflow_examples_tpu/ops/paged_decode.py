"""Fused Pallas paged-decode attention: block-table gather + varlen
masked attention in ONE kernel (ISSUE 11 tentpole).

The paged serving decode step (``serving/engine._forward_decode``)
previously ran two XLA programs per layer: a gather that materializes
each slot's contiguous cache view out of the block pool
(``kv_cache.gather_block_kv`` — O(bucket) HBM *writes* per step for
bytes that are read exactly once), then the masked attention over the
gathered copy. This kernel folds both: the KV BlockSpec index map reads
each slot's *block table* directly (scalar prefetch), so the Pallas
pipeline DMAs physical cache blocks straight from the pool into VMEM —
no materialized per-slot copy, half the HBM traffic, one kernel launch.

Contract (the per-slot generalization of
``ops/decode.flash_decode_attention``, which covers the scalar-length
prefill case):

* ``q`` [S, H, D] — one new query per slot, its own K/V already
  written through the block table.
* ``k_blocks`` / ``v_blocks`` [NB, BS, H*D] — ONE layer's physical
  block pools (``serving/paged_kv.PagedKVPool`` layout: a token's row
  is its H*D values).
* ``lengths`` [S] int32 — populated lengths INCLUDING the new token;
  slot s attends columns ``< lengths[s]``, nothing else.
* ``block_tables`` [S, nb] int32 — logical->physical block map for the
  active KV bucket (``nb = bucket // BS``); entries past a slot's
  allocation point at the null block, whose rows the length mask never
  admits.
* ``k_scale`` / ``v_scale`` [NB, BS, H] f32 (optional) — the int8
  pools' blockwise per-row scales (``core/precision``): passing them
  selects the **dequant-in-kernel** path, so a quantized cache is read
  at 1 byte/element from HBM and widened to f32 only in VMEM — the
  whole point of int8 KV on a bandwidth-bound step.

Grid is (slot, kv-block); one step handles EVERY head of one physical
block, with the familiar online-softmax scratch carry
(``ops/attention.py``) kept per head. A step's K/V block is the pool's
own ``(1, BS, H*D)`` — Mosaic constrains the last two dims of a block
to (8k, 128k) or the full array dim, and these are the full dims, as
are ``(1, H, D)`` of q and ``(1, BS, H)`` of the scales. The block is
widened to f32 once and the heads are a static unrolled loop over its
``D``-wide lane slices, each the same 2-D ``[1, D]·[BS, D]ᵀ`` product
``ops/decode.py`` runs. Unpopulated trailing blocks are clamped to the
last populated index in the index map — a repeated index is a no-op
for the Pallas pipeline, so **no HBM traffic is issued for blocks past
a slot's length** — and ``pl.when`` skips their compute.

int8 scales are per (block, row, head): a ``[BS, 1]`` column of the
scale block multiplies the head's ``[BS, D]`` tile along lanes —
``core/precision.dequantize_rows`` in VMEM.

The XLA gather path (``kv_cache.varlen_decode_attention`` with
``block_tables=``) stays in-tree as the reference oracle:
tests/test_kernels.py pins this kernel against it element-wise in
interpret mode (tier-1, CPU) across slot-length/block-table edge cases
and cross-lowers it for the TPU, tests_tpu/ runs it compiled on the
chip, and the engine keeps the oracle selectable
(``ServeConfig.attention="xla"``). No backward: decode is
inference-only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tensorflow_examples_tpu.core.device import pallas_interpret
from tensorflow_examples_tpu.ops.attention import NEG_INF


def _paged_decode_kernel(
    len_ref, tbl_ref, q_ref, k_ref, v_ref, *rest, sm_scale, block_size,
    num_heads, quantized,
):
    if quantized:
        ksc_ref, vsc_ref, o_ref, m_s, l_s, acc_s = rest
    else:
        o_ref, m_s, l_s, acc_s = rest
    s, j = pl.program_id(0), pl.program_id(1)
    length = len_ref[s]
    col0 = j * block_size

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    # Blocks at or past the slot's length contribute nothing; their
    # fetch was already clamped to the last populated block in the
    # index map (no DMA), and this guard skips their MXU work.
    def _attend():
        live = col0 + lax.broadcasted_iota(
            jnp.int32, (1, block_size), 1
        ) < length
        head_dim = q_ref.shape[-1]
        kb = k_ref[0].astype(jnp.float32)                  # [BS, H*D]
        vb = v_ref[0].astype(jnp.float32)
        if quantized:
            ksc, vsc = ksc_ref[0], vsc_ref[0]              # [BS, H]
        for h in range(num_heads):
            row = pl.ds(h, 1)
            lanes = slice(h * head_dim, (h + 1) * head_dim)
            q = q_ref[0, row, :] * sm_scale                # [1, D] f32
            k, v = kb[:, lanes], vb[:, lanes]              # [BS, D]
            if quantized:
                k = k * ksc[:, h:h + 1]
                v = v * vsc[:, h:h + 1]
            scores = lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [1, BS]
            scores = jnp.where(live, scores, NEG_INF)
            m = m_s[row, :]
            m_new = jnp.maximum(m, jnp.max(scores, axis=1, keepdims=True))
            p = jnp.exp(scores - m_new)
            alpha = jnp.exp(m - m_new)
            m_s[row, :] = m_new
            l_s[row, :] = l_s[row, :] * alpha + jnp.sum(
                p, axis=1, keepdims=True
            )
            acc_s[row, :] = acc_s[row, :] * alpha + jnp.dot(
                p, v, preferred_element_type=jnp.float32
            )

    pl.when(col0 < length)(_attend)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        # An empty slot (length 0, every block skipped) divides by the
        # epsilon and writes ~0 — discarded garbage, never NaN.
        l = jnp.maximum(l_s[...], 1e-30)
        o_ref[0] = acc_s[...] / l


@functools.lru_cache(maxsize=None)
def _make_paged_decode(num_slots, num_heads, nb, block_size, head_dim,
                       quantized, interpret):
    """One compiled variant per (slots, heads, table width, block
    geometry, quantization, interpret) — the engine's KV bucket ladder
    keys the table width."""

    def kv_index(s, j, len_ref, tbl_ref):
        # Clamp unpopulated blocks to the last populated one: the
        # pipeline sees an unchanged physical index and skips the copy.
        last = jnp.maximum((len_ref[s] - 1) // block_size, 0)
        return (tbl_ref[s, jnp.minimum(j, last)], 0, 0)

    qo_spec = pl.BlockSpec(
        (1, num_heads, head_dim), lambda s, j, ln, tb: (s, 0, 0)
    )
    kv_spec = pl.BlockSpec(
        (1, block_size, num_heads * head_dim), kv_index
    )
    in_specs = [qo_spec, kv_spec, kv_spec]
    if quantized:
        sc_spec = pl.BlockSpec((1, block_size, num_heads), kv_index)
        in_specs += [sc_spec, sc_spec]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(num_slots, nb),
        in_specs=in_specs,
        out_specs=qo_spec,
        scratch_shapes=[
            pltpu.VMEM((num_heads, 1), jnp.float32),
            pltpu.VMEM((num_heads, 1), jnp.float32),
            pltpu.VMEM((num_heads, head_dim), jnp.float32),
        ],
    )

    def call(q, k_blocks, v_blocks, lengths, tables, scales, sm_scale):
        kernel = functools.partial(
            _paged_decode_kernel,
            sm_scale=sm_scale,
            block_size=block_size,
            num_heads=num_heads,
            quantized=quantized,
        )
        # q and the output cross the kernel boundary in f32 whatever
        # the serving dtype: [S, H, D] is tiny, and f32 rows slice at
        # any static sublane offset where packed bf16 rows do not.
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(q.shape, jnp.float32),
            interpret=interpret,
        )(lengths, tables, q.astype(jnp.float32), k_blocks, v_blocks,
          *scales)
        return out.astype(q.dtype)

    return call


def paged_decode_attention(
    q: jax.Array,
    k_blocks: jax.Array,
    v_blocks: jax.Array,
    lengths: jax.Array,
    block_tables: jax.Array,
    *,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    sm_scale: float | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Single-token per-slot attention straight through the block
    table; see the module docstring for the full contract. Returns
    [S, H, D] in ``q.dtype``."""
    if interpret is None:
        interpret = pallas_interpret("paged_decode_attention")
    num_slots, num_heads, head_dim = q.shape
    block_size = k_blocks.shape[1]
    nb = block_tables.shape[1]
    if (k_scale is None) != (v_scale is None):
        raise ValueError("pass both k_scale and v_scale, or neither")
    quantized = k_scale is not None
    if sm_scale is None:
        sm_scale = head_dim ** -0.5
    call = _make_paged_decode(
        num_slots, num_heads, nb, block_size, head_dim, quantized,
        bool(interpret),
    )
    scales = (k_scale, v_scale) if quantized else ()
    return call(
        q, k_blocks, v_blocks,
        jnp.asarray(lengths, jnp.int32),
        jnp.asarray(block_tables, jnp.int32),
        scales, float(sm_scale),
    )


def paged_decode_reference(
    q: jax.Array,
    k_blocks: jax.Array,
    v_blocks: jax.Array,
    lengths: jax.Array,
    block_tables: jax.Array,
    *,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    sm_scale: float | None = None,
) -> jax.Array:
    """The XLA gather-path oracle the kernel is pinned against: exactly
    what the engine runs under ``attention="xla"`` — gather (and, int8,
    dequantize) by table, then the masked attention."""
    from tensorflow_examples_tpu.serving.kv_cache import (
        varlen_decode_attention,
    )

    return varlen_decode_attention(
        q, k_blocks, v_blocks, lengths, sm_scale=sm_scale,
        block_tables=block_tables, k_scale=k_scale, v_scale=v_scale,
    )
