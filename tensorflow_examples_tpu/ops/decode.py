"""Flash-decode: Pallas KV-cache attention for autoregressive sampling.

TPU-native replacement for the naive decode path (SURVEY.md §2c kernels
layer, §5g long-context): the previous ``_decode_attend`` materialized a
``[q_len, max_len]`` score matrix against the FULL static cache every
step — quadratic HBM reads once training-scale contexts (4k–16k) meet a
static cache sized for them. This kernel reads only the cache blocks
that are actually populated:

- Grid is (batch·head, q-block, kv-block) like the training flash kernel
  (``ops/attention.py``), with the same online-softmax scratch carry.
  The KV extent of the grid is picked from a power-of-two bucket ladder
  by the populated length (``lax.switch`` over per-bucket compilations),
  so a single-token step through a huge cache SEQUENCES O(context)
  programs, not O(max_len).
- The *valid cache length* rides in as a scalar-prefetch operand
  (``pltpu.PrefetchScalarGridSpec``), so the KV BlockSpec index_map can
  see it: blocks past the last populated one (bucket overshoot) are
  clamped to the last valid index. Re-requesting the same block is a
  no-op for the Pallas pipeline — **no HBM traffic is issued for
  unpopulated cache blocks**, and ``pl.when`` guards skip their MXU
  work. A decode step at context length n reads O(n) cache bytes.
- Causality inside the populated region falls out of global positions:
  query row r sits at position length - q_len + r and sees cache slots
  ≤ its position; the final (partial) block is masked with iota.
- bf16 cache tiles upcast to f32 on the MXU (``preferred_element_type``)
  — same numerics policy as the training kernel.

No backward: decode is inference-only. Parity vs the XLA reference is
asserted in tests/test_kernels.py (interpret mode) and
tests_tpu/test_tpu_kernels.py (compiled, on the live chip).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tensorflow_examples_tpu.core.device import pallas_interpret
from tensorflow_examples_tpu.ops.attention import NEG_INF, _fit_block


def decode_attention_reference(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    length: jax.Array | int,
    *,
    sm_scale: float | None = None,
) -> jax.Array:
    """Plain-XLA masked cache attention; numerics reference for the kernel.

    q: [B, H, q_len, D] — the newly appended queries, occupying global
    positions ``length - q_len … length - 1``.
    k_cache / v_cache: [B, H, max_len, D]; slots ≥ ``length`` are garbage.
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    q_len, max_len = q.shape[2], k_cache.shape[2]
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k_cache, preferred_element_type=jnp.float32
    ) * sm_scale
    pos = (length - q_len) + lax.broadcasted_iota(
        jnp.int32, (q_len, max_len), 0
    )
    col = lax.broadcasted_iota(jnp.int32, (q_len, max_len), 1)
    s = jnp.where(col <= pos, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    return jnp.einsum(
        "bhqk,bhkd->bhqd", p, v_cache, preferred_element_type=jnp.float32
    ).astype(q.dtype)


def _decode_kernel(
    len_ref, q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s, *, sm_scale, q_len
):
    block_q, block_kv = q_ref.shape[1], k_ref.shape[1]
    i, j = pl.program_id(1), pl.program_id(2)
    length = len_ref[0]
    # Global position of this q block's first row (cache slot it occupies).
    q_pos = (length - q_len) + i * block_q
    kv_offset = j * block_kv

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    # KV blocks entirely after this q block's last row position contribute
    # nothing (that also covers every unpopulated block: slot p < length
    # for all rows). The BlockSpec index_map has already clamped their
    # fetches, so skipped iterations issue neither DMA nor MXU work.
    def _attend():
        q = q_ref[0].astype(jnp.float32) * sm_scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [block_q, block_kv]
        row = q_pos + lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
        col = kv_offset + lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 1
        )
        s = jnp.where(col <= row, s, NEG_INF)
        m = m_s[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        m_s[...] = m_new
        l_s[...] = l_s[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_s[...] = acc_s[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )

    pl.when(kv_offset <= q_pos + block_q - 1)(_attend)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        l = jnp.maximum(l_s[...], 1e-30)
        o_ref[0] = (acc_s[...] / l).astype(o_ref.dtype)


@functools.lru_cache(maxsize=None)
def _make_decode(q_len, block_q, block_kv, interpret, kv_blocks):
    """One kernel variant iterating exactly ``kv_blocks`` KV programs.

    The public entry compiles a power-of-two LADDER of these (see
    ``flash_decode_attention``) and lax.switches on the populated block
    count, so per-step grid-sequencer work is bounded by ~2× the
    populated context rather than by ``max_len`` (the clamp already suppressed DMA + MXU for unpopulated blocks, but
    a 32k-slot cache still sequenced cdiv(32k, block) programs per
    single-token step). The kernel body is bucket-agnostic — finalize
    keys off ``pl.num_programs`` and the index clamp covers buckets
    that overshoot the populated length."""

    def call(q, k, v, length, sm_scale):
        bh, _, head_dim = q.shape
        # Partial trailing blocks are safe HERE (unlike the training
        # kernel): padded KV columns carry global indices ≥ max_len and
        # every real row's position is < max_len, so the causal mask
        # kills them; padded query rows are clipped on write-back.
        grid = (bh, pl.cdiv(q_len, block_q), kv_blocks)

        def kv_index(b, i, j, len_ref):
            # Clamp unpopulated blocks to the last populated one: the
            # pipeline sees an unchanged index and skips the copy.
            # (Index_maps receive scalar-prefetch refs AFTER the grid
            # indices — the kernel body receives them first.)
            last = (len_ref[0] - 1) // block_kv
            return (b, jnp.minimum(j, last), 0)

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec(
                    (1, block_q, head_dim), lambda b, i, j, s: (b, i, 0)
                ),
                pl.BlockSpec((1, block_kv, head_dim), kv_index),
                pl.BlockSpec((1, block_kv, head_dim), kv_index),
            ],
            out_specs=pl.BlockSpec(
                (1, block_q, head_dim), lambda b, i, j, s: (b, i, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, head_dim), jnp.float32),
            ],
        )
        return pl.pallas_call(
            functools.partial(
                _decode_kernel, sm_scale=sm_scale, q_len=q_len
            ),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            interpret=interpret,
        )(jnp.reshape(length, (1,)).astype(jnp.int32), q, k, v)

    return call


def flash_decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    length: jax.Array | int,
    *,
    sm_scale: float | None = None,
    block_q: int | None = None,
    block_kv: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Attend ``q`` [B, H, q_len, D] over a static KV cache, reading only
    populated blocks.

    ``length`` (traced scalar ok) is the total populated cache length
    INCLUDING the q_len tokens just written; queries occupy global
    positions ``length - q_len … length - 1`` and each sees cache slots
    ≤ its own position. Works for both prefill (q_len = prompt length)
    and stepping (q_len = 1) — each distinct q_len compiles once, same
    contract as the caller's cache update.
    """
    if interpret is None:
        interpret = pallas_interpret("flash_decode_attention")
    b, h, q_len, head_dim = q.shape
    max_len = k_cache.shape[2]
    if sm_scale is None:
        sm_scale = head_dim**-0.5
    # Prefer an exact divisor (zero padded work); arbitrary lengths fall
    # back to a 256 block with a partial tail — legal here, see kernel.
    try:
        block_q = block_q or _fit_block(256, q_len)
    except ValueError:
        block_q = 256
    try:
        block_kv = block_kv or _fit_block(256, max_len)
    except ValueError:
        block_kv = 256
    fold = lambda x: x.reshape(b * h, x.shape[2], head_dim)
    qf, kf, vf = fold(q), fold(k_cache), fold(v_cache)
    sm_scale = float(sm_scale)

    # Power-of-two bucket ladder over KV block counts: 1, 2, 4, …,
    # cdiv(max_len, block_kv). Each bucket is its own compiled kernel;
    # the populated block count picks the smallest sufficient bucket,
    # so a short-context step through a huge cache sequences O(context)
    # programs, not O(max_len).
    total = pl.cdiv(max_len, block_kv)
    counts = []
    c = 1
    while c < total:
        counts.append(c)
        c *= 2
    counts.append(total)

    if isinstance(length, int):  # static length: exact bucket, no switch
        needed = -(-length // block_kv)
        # Clamp to the full-cache bucket for length > max_len, matching
        # the traced path (searchsorted clamps the same overrun); a
        # bare next() would raise an opaque StopIteration here.
        nkv = next((c for c in counts if c >= needed), total)
        call = _make_decode(q_len, block_q, block_kv, bool(interpret), nkv)
        out = call(qf, kf, vf, length, sm_scale)
        return out.reshape(b, h, q_len, head_dim)

    if len(counts) == 1:
        call = _make_decode(
            q_len, block_q, block_kv, bool(interpret), counts[0]
        )
        out = call(qf, kf, vf, length, sm_scale)
        return out.reshape(b, h, q_len, head_dim)

    needed = lax.div(
        jnp.asarray(length, jnp.int32) + (block_kv - 1), block_kv
    )
    idx = jnp.searchsorted(
        jnp.asarray(counts, jnp.int32), needed, side="left"
    )
    branches = [
        (lambda f: lambda a, kk, vv, ln: f(a, kk, vv, ln, sm_scale))(
            _make_decode(q_len, block_q, block_kv, bool(interpret), nkv)
        )
        for nkv in counts
    ]
    out = lax.switch(idx, branches, qf, kf, vf, length)
    return out.reshape(b, h, q_len, head_dim)
