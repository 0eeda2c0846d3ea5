"""Flash (blockwise) attention as a Pallas TPU kernel.

TPU-native replacement for the reference's CUDA ``tf.custom_op`` kernels
(BASELINE.json:north_star — "rewrite any tf.custom_op / CUDA kernels ...
as Pallas or XLA custom-calls"; SURVEY.md §2c, §5g). The kernel is the
single-device base for ring attention (``parallel/ring.py``): it computes
attention over KV *blocks* with an online softmax and can return the
per-row logsumexp, so ring hops merge kernel outputs exactly.

Design (TPU-first, not a CUDA translation):
- The grid is (batch·head, q-block, kv-block) with the KV dimension
  innermost: only ONE [block_kv, head_dim] K/V tile is VMEM-resident at
  a time, so sequence length is bounded by HBM, not VMEM — 16k–32k+
  tokens run with the same kernel. The online-softmax running
  (max, sum, acc) live in VMEM scratch carried across the inner KV grid
  steps; outputs are written on the last step.
- All matmuls run on the MXU in f32 accumulation
  (``preferred_element_type``), inputs may be bf16.
- Causal masking skips whole KV blocks above the diagonal (``pl.when``
  guards: no MXU work issued) and masks inside the diagonal block with
  ``broadcasted_iota``.
- Backward is the standard two-kernel split (dkv by KV block, dq by Q
  block) using the saved logsumexp, so the [seq, seq] score matrix is
  never materialized. When the forward exposed the logsumexp, its
  cotangent is exact: d(lse_i)/d(s_ij) = p_ij folds into
  ``ds = p · (dp − delta + dlse)``.

On non-TPU backends the same kernels run in Pallas interpret mode (used
by the CPU test suite) and an XLA reference implementation is provided
for numerics comparison.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tensorflow_examples_tpu.core.device import pallas_interpret

NEG_INF = -1e30


def attention_reference(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    sm_scale: float | None = None,
    key_bias: jax.Array | None = None,
) -> jax.Array:
    """Plain-XLA attention; the numerics reference for the Pallas kernel.

    q, k, v: [batch, heads, seq, head_dim]. Softmax in f32.
    ``key_bias``: optional [batch, seq_kv] additive score bias (f32),
    broadcast over heads and query rows — the padding-mask shape.
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * sm_scale
    if key_bias is not None:
        s = s + key_bias[:, None, None, :].astype(jnp.float32)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        row = lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        col = lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        s = jnp.where(row + (sk - sq) >= col, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum(
        "bhqk,bhkd->bhqd", p.astype(v.dtype), v, preferred_element_type=jnp.float32
    ).astype(q.dtype)


# --------------------------------------------------------------- forward


def _fwd_kernel(
    q_ref, k_ref, v_ref, *rest, sm_scale, causal, has_bias=False
):
    if has_bias:
        kb_ref, o_ref, lse_ref, m_s, l_s, acc_s = rest
    else:
        kb_ref = None
        o_ref, lse_ref, m_s, l_s, acc_s = rest
    block_q, head_dim = q_ref.shape[1], q_ref.shape[2]
    block_kv = k_ref.shape[1]
    qi, kj = pl.program_id(1), pl.program_id(2)
    num_kv = pl.num_programs(2)
    # Bottom-right-aligned causal diagonal: query i attends keys
    # <= i + (seq_kv - seq_q), matching attention_reference.
    offset = num_kv * block_kv - pl.num_programs(1) * block_q
    q_offset = qi * block_q
    kv_offset = kj * block_kv

    @pl.when(kj == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    # Causal: KV blocks entirely above the diagonal contribute nothing —
    # issue no MXU work for them.
    def _attend():
        q = q_ref[0].astype(jnp.float32) * sm_scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [block_q, block_kv]
        if has_bias:
            s = s + kb_ref[0]  # [1, block_kv] broadcasts over rows
        if causal:
            row = q_offset + lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0
            )
            col = kv_offset + lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1
            )
            s = jnp.where(row + offset >= col, s, NEG_INF)
        m = m_s[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        m_s[...] = m_new
        l_s[...] = l_s[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_s[...] = acc_s[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )

    if causal:
        pl.when(q_offset + block_q - 1 + offset >= kv_offset)(_attend)
    else:
        _attend()

    @pl.when(kj == num_kv - 1)
    def _finalize():
        l = jnp.maximum(l_s[...], 1e-30)
        o_ref[0] = (acc_s[...] / l).astype(o_ref.dtype)
        lse_ref[0] = (m_s[...] + jnp.log(l)).astype(jnp.float32)


def _flash_fwd(
    q, k, v, sm_scale, causal, block_q, block_kv, interpret, kb=None, heads=1
):
    bh, seq_q, head_dim = q.shape
    seq_kv = k.shape[1]
    grid = (bh, seq_q // block_q, seq_kv // block_kv)
    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, has_bias=kb is not None
    )
    in_specs = [
        pl.BlockSpec((1, block_q, head_dim), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_kv, head_dim), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, block_kv, head_dim), lambda b, i, j: (b, j, 0)),
    ]
    args = (q, k, v)
    if kb is not None:
        # Carried as [batch, 1, seq_kv]: Mosaic constrains the LAST TWO
        # dims of a block to (8k, 128k) or the full array dim, so a
        # rank-2 [batch, seq_kv] bias with a (1, block_kv) block is
        # unlowerable whenever batch > 1 (compiled-TPU-only failure;
        # interpret mode never enforces it). Rank-3 puts batch outside
        # the constrained dims. Grid dim 0 is batch·heads, so the batch
        # row is program_id(0) // heads (static closure).
        in_specs.append(
            pl.BlockSpec((1, 1, block_kv), lambda b, i, j: (b // heads, 0, j))
        )
        args = args + (kb[:, None, :],)
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, head_dim), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq_q, head_dim), q.dtype),
            jax.ShapeDtypeStruct((bh, seq_q, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, head_dim), jnp.float32),
        ],
        interpret=interpret,
    )(*args)
    return o, lse


# -------------------------------------------------------------- backward


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dlse_ref, *rest,
    sm_scale, causal, has_bias=False,
):
    if has_bias:
        kb_ref, dk_ref, dv_ref, dk_s, dv_s = rest
    else:
        kb_ref = None
        dk_ref, dv_ref, dk_s, dv_s = rest
    block_kv, head_dim = k_ref.shape[1], k_ref.shape[2]
    block_q = q_ref.shape[1]
    ki, qj = pl.program_id(1), pl.program_id(2)
    num_q = pl.num_programs(2)
    offset = pl.num_programs(1) * block_kv - num_q * block_q
    kv_offset = ki * block_kv
    q_offset = qj * block_q

    @pl.when(qj == 0)
    def _init():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    # Q blocks strictly above this KV block's diagonal see none of it.
    def _accumulate():
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        q = q_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]  # [block_q, 1]
        delta = delta_ref[0]
        dlse = dlse_ref[0]
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # [block_q, block_kv]
        if has_bias:
            s = s + kb_ref[0]
        if causal:
            row = q_offset + lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0
            )
            col = kv_offset + lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1
            )
            s = jnp.where(row + offset >= col, s, NEG_INF)
        p = jnp.exp(s - lse)  # [block_q, block_kv]
        # dv += p^T do
        dv_s[...] += lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        # dp = do v^T ; ds = p * (dp - delta + dlse)
        dp = lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta + dlse)
        # dk += ds^T q * scale
        dk_s[...] += sm_scale * lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    if causal:
        pl.when(q_offset + block_q - 1 + offset >= kv_offset)(_accumulate)
    else:
        _accumulate()

    @pl.when(qj == num_q - 1)
    def _finalize():
        dk_ref[0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dlse_ref, *rest,
    sm_scale, causal, has_bias=False,
):
    if has_bias:
        kb_ref, dq_ref, dq_s = rest
    else:
        kb_ref = None
        dq_ref, dq_s = rest
    block_q, head_dim = q_ref.shape[1], q_ref.shape[2]
    block_kv = k_ref.shape[1]
    qi, kj = pl.program_id(1), pl.program_id(2)
    num_kv = pl.num_programs(2)
    offset = num_kv * block_kv - pl.num_programs(1) * block_q
    q_offset = qi * block_q
    kv_offset = kj * block_kv

    @pl.when(kj == 0)
    def _init():
        dq_s[...] = jnp.zeros_like(dq_s)

    def _accumulate():
        q = q_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]
        delta = delta_ref[0]
        dlse = dlse_ref[0]
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale
        if has_bias:
            s = s + kb_ref[0]
        if causal:
            row = q_offset + lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0
            )
            col = kv_offset + lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1
            )
            s = jnp.where(row + offset >= col, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta + dlse)
        dq_s[...] += sm_scale * jnp.dot(
            ds, k, preferred_element_type=jnp.float32
        )

    if causal:
        pl.when(q_offset + block_q - 1 + offset >= kv_offset)(_accumulate)
    else:
        _accumulate()

    @pl.when(kj == num_kv - 1)
    def _finalize():
        dq_ref[0] = dq_s[...].astype(dq_ref.dtype)


def _flash_bwd(
    sm_scale, causal, block_q, block_kv, interpret, residuals, do, dlse,
    kb=None, heads=1,
):
    q, k, v, o, lse = residuals
    bh, seq_q, head_dim = q.shape
    seq_kv = k.shape[1]
    has_bias = kb is not None
    # delta_i = rowsum(do_i * o_i) — cheap, let XLA fuse it.
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1, keepdims=True
    )
    if dlse is None:
        dlse = jnp.zeros_like(lse)
    dlse = dlse.astype(jnp.float32).reshape(lse.shape)

    q_blk = pl.BlockSpec((1, block_q, head_dim), lambda b, i, j: (b, j, 0))
    kv_blk = pl.BlockSpec((1, block_kv, head_dim), lambda b, i, j: (b, i, 0))
    vec_blk = pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, j, 0))
    # Bias rides as [batch, 1, seq_kv] — see _flash_fwd's spec note on
    # Mosaic's last-two-dims block constraint. In the dkv grid the KV
    # block index is grid dim 1 (i).
    kb3 = kb[:, None, :] if has_bias else None
    kb_blk = pl.BlockSpec((1, 1, block_kv), lambda b, i, j: (b // heads, 0, i))
    in_specs = [q_blk, kv_blk, kv_blk, q_blk, vec_blk, vec_blk, vec_blk]
    args = (q, k, v, do, lse, delta, dlse)
    if has_bias:
        in_specs.append(kb_blk)
        args = args + (kb3,)

    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
            has_bias=has_bias,
        ),
        grid=(bh, seq_kv // block_kv, seq_q // block_q),
        in_specs=in_specs,
        out_specs=[kv_blk, kv_blk],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_kv, head_dim), jnp.float32),
            pltpu.VMEM((block_kv, head_dim), jnp.float32),
        ],
        interpret=interpret,
    )(*args)

    q_blk = pl.BlockSpec((1, block_q, head_dim), lambda b, i, j: (b, i, 0))
    kv_blk = pl.BlockSpec((1, block_kv, head_dim), lambda b, i, j: (b, j, 0))
    vec_blk = pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0))
    kb_blk = pl.BlockSpec((1, 1, block_kv), lambda b, i, j: (b // heads, 0, j))
    in_specs = [q_blk, kv_blk, kv_blk, q_blk, vec_blk, vec_blk, vec_blk]
    args = (q, k, v, do, lse, delta, dlse)
    if has_bias:
        in_specs.append(kb_blk)
        args = args + (kb3,)

    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
            has_bias=has_bias,
        ),
        grid=(bh, seq_q // block_q, seq_kv // block_kv),
        in_specs=in_specs,
        out_specs=q_blk,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, head_dim), jnp.float32)],
        interpret=interpret,
    )(*args)
    return dq, dk, dv


# ------------------------------------------------------------ public api


@functools.lru_cache(maxsize=None)
def _make_flash(causal, block_q, block_kv, interpret):
    # sm_scale stays out of the cache key (a swept/per-layer scale must
    # not leak a closure per value) — it rides through as a nondiff arg.
    @functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
    def flash(q, k, v, sm_scale):
        o, _ = _flash_fwd(q, k, v, sm_scale, causal, block_q, block_kv, interpret)
        return o

    def fwd(q, k, v, sm_scale):
        o, lse = _flash_fwd(q, k, v, sm_scale, causal, block_q, block_kv, interpret)
        return o, (q, k, v, o, lse)

    def bwd(sm_scale, residuals, g):
        return _flash_bwd(
            sm_scale, causal, block_q, block_kv, interpret, residuals, g, None
        )

    flash.defvjp(fwd, bwd)
    return flash


@functools.lru_cache(maxsize=None)
def _make_flash_lse(causal, block_q, block_kv, interpret):
    """Variant returning (o, lse) with the exact lse cotangent in bwd —
    the building block ring attention merges across hops."""

    @functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
    def flash(q, k, v, sm_scale):
        o, lse = _flash_fwd(q, k, v, sm_scale, causal, block_q, block_kv, interpret)
        return o, lse

    def fwd(q, k, v, sm_scale):
        o, lse = _flash_fwd(q, k, v, sm_scale, causal, block_q, block_kv, interpret)
        return (o, lse), (q, k, v, o, lse)

    def bwd(sm_scale, residuals, g):
        do, dlse = g
        return _flash_bwd(
            sm_scale, causal, block_q, block_kv, interpret, residuals, do, dlse
        )

    flash.defvjp(fwd, bwd)
    return flash


@functools.lru_cache(maxsize=None)
def _make_flash_bias(causal, block_q, block_kv, interpret, heads):
    """Variant with a [batch, seq_kv] additive key bias (padding masks).

    The bias is treated as NON-differentiable data — it comes from an
    attention mask, and a ±NEG_INF bias has no meaningful gradient — so
    its cotangent is zeros; the bwd kernels still ADD it when
    recomputing the scores (p must match the forward's softmax).
    """

    @functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
    def flash(q, k, v, kb, sm_scale):
        o, _ = _flash_fwd(
            q, k, v, sm_scale, causal, block_q, block_kv, interpret,
            kb=kb, heads=heads,
        )
        return o

    def fwd(q, k, v, kb, sm_scale):
        o, lse = _flash_fwd(
            q, k, v, sm_scale, causal, block_q, block_kv, interpret,
            kb=kb, heads=heads,
        )
        return o, (q, k, v, o, lse, kb)

    def bwd(sm_scale, residuals, g):
        *res, kb = residuals
        dq, dk, dv = _flash_bwd(
            sm_scale, causal, block_q, block_kv, interpret, tuple(res), g,
            None, kb=kb, heads=heads,
        )
        return dq, dk, dv, jnp.zeros_like(kb)

    flash.defvjp(fwd, bwd)
    return flash


_DEFAULT_BLOCK = 256  # one guess; the on-chip sweep is ROADMAP queue 1 item 9


def _fit_block(target: int, seq: int) -> int:
    """Auto block size: the largest divisor of ``seq`` ≤ ``target`` that
    is a multiple of 128 (TPU lane width), else of 8 (sublane), else —
    no exact tiling exists — a clear error. When ``seq <= target`` the
    full sequence rides as one block (Pallas pads it internally); longer
    sequences with no multiple-of-8 divisor ≤ target (e.g. 4·odd
    lengths) are rejected rather than tiled with a partial tail, because
    these kernels' in-block masks index from block offsets and would
    read garbage KV columns past ``seq``. (The decode kernel in
    ops/decode.py masks by *global position* instead, so it accepts
    arbitrary lengths.)"""
    b = min(target, seq)
    if seq % b == 0:
        return b
    for cand in range(b - b % 128, 0, -128):
        if seq % cand == 0:
            return cand
    for cand in range(b - b % 8, 0, -8):
        if seq % cand == 0:
            return cand
    raise ValueError(
        f"sequence length {seq} has no multiple-of-8 block divisor "
        f"<= {target}; pad the sequence to a multiple of 8"
    )


@functools.lru_cache(maxsize=1)
def _tuned_block_table() -> dict:
    """Per-sequence block defaults from an on-chip sweep
    (tools/flash_tune.py → tools/flash_table_from_sweep.py →
    docs/tpu_sweeps/flash_block_table.json). Maps str(seq) →
    {"block_q": B, "block_kv": B} from the fwd+bwd-optimal cell —
    training is the default consumer. No sweep has been run on a v5e,
    so the file does not exist and the table is empty (the 256
    fallback); a file that exists but cannot be read is an error."""
    import json
    import os

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)
        ))),
        "docs", "tpu_sweeps", "flash_block_table.json",
    )
    try:
        with open(path) as f:
            return json.load(f).get("by_seq", {})
    except FileNotFoundError:
        return {}


def _resolve_block(block: int | None, seq: int, which: str = "block_q") -> int:
    """Explicit block sizes are honored exactly (divisibility enforced,
    never silently overridden); None selects the swept per-seq default
    (falling back to the 256 target fit)."""
    if block is None:
        tuned = _tuned_block_table().get(str(seq))
        if tuned and tuned.get(which):
            return _fit_block(int(tuned[which]), seq)
        return _fit_block(_DEFAULT_BLOCK, seq)
    b = min(block, seq)
    if seq % b:
        raise ValueError(
            f"sequence length {seq} is not divisible by block size {b}; "
            "pass block sizes that divide it, or None for auto"
        )
    return b


def _prepare(q, k, v, causal, sm_scale, block_q, block_kv, interpret):
    if interpret is None:
        interpret = pallas_interpret("flash_attention")
    b, h, seq_q, head_dim = q.shape
    seq_kv = k.shape[2]
    block_q = _resolve_block(block_q, seq_q, "block_q")
    block_kv = _resolve_block(block_kv, seq_kv, "block_kv")
    if causal and seq_q > seq_kv:
        # Rows with zero visible keys are degenerate (the reference
        # softmaxes an all-masked row into uniform weights; the kernel
        # would return 0) — reject rather than silently diverge.
        raise ValueError(
            f"causal attention requires seq_q ({seq_q}) <= seq_kv ({seq_kv})"
        )
    if sm_scale is None:
        sm_scale = head_dim**-0.5
    return float(sm_scale), block_q, block_kv, interpret


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    sm_scale: float | None = None,
    block_q: int | None = None,
    block_kv: int | None = None,
    interpret: bool | None = None,
    key_bias: jax.Array | None = None,
) -> jax.Array:
    """Blockwise attention, differentiable; q/k/v: [batch, heads, seq, dim].

    Runs the Pallas kernel compiled by Mosaic on ``tpu`` and in
    interpret mode on ``cpu`` (the tests); any other platform needs an
    explicit ``interpret=`` (``core/device.pallas_interpret``).
    block_q/block_kv None = auto: 256-targeted (not measured on a v5e —
    PERF.md), fitted down to a hardware-legal divisor of
    the sequence; explicit sizes are enforced exactly.

    ``key_bias``: optional [batch, seq_kv] additive score bias (f32),
    broadcast over heads and query rows — the padding-mask shape BERT
    needs. Non-differentiable (zero cotangent; it is mask data).
    """
    sm_scale, block_q, block_kv, interpret = _prepare(
        q, k, v, causal, sm_scale, block_q, block_kv, interpret
    )
    b, h, seq_q, head_dim = q.shape
    fold = lambda x: x.reshape(b * h, x.shape[2], head_dim)
    if key_bias is not None:
        if key_bias.shape != (b, k.shape[2]):
            raise ValueError(
                f"key_bias shape {key_bias.shape} != (batch, seq_kv) "
                f"({b}, {k.shape[2]})"
            )
        flash = _make_flash_bias(bool(causal), block_q, block_kv, interpret, h)
        out = flash(
            fold(q), fold(k), fold(v),
            key_bias.astype(jnp.float32), sm_scale,
        )
        return out.reshape(b, h, seq_q, head_dim)
    flash = _make_flash(bool(causal), block_q, block_kv, interpret)
    out = flash(fold(q), fold(k), fold(v), sm_scale)
    return out.reshape(b, h, seq_q, head_dim)


def flash_attention_with_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    sm_scale: float | None = None,
    block_q: int | None = None,
    block_kv: int | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Like ``flash_attention`` but also returns the row logsumexp
    [batch, heads, seq] (f32), differentiable in both outputs. Partial
    attention results merge exactly via their lse — the primitive ring
    attention builds on."""
    sm_scale, block_q, block_kv, interpret = _prepare(
        q, k, v, causal, sm_scale, block_q, block_kv, interpret
    )
    b, h, seq_q, head_dim = q.shape
    flash = _make_flash_lse(bool(causal), block_q, block_kv, interpret)
    fold = lambda x: x.reshape(b * h, x.shape[2], head_dim)
    o, lse = flash(fold(q), fold(k), fold(v), sm_scale)
    return o.reshape(b, h, seq_q, head_dim), lse.reshape(b, h, seq_q)


def dot_product_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    sm_scale: float | None = None,
    use_flash: bool = True,
) -> jax.Array:
    """Dispatcher: Pallas flash kernel when enabled, XLA reference otherwise."""
    if use_flash:
        return flash_attention(q, k, v, causal=causal, sm_scale=sm_scale)
    return attention_reference(q, k, v, causal=causal, sm_scale=sm_scale)
