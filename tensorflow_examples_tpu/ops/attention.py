"""Flash (blockwise) attention as a Pallas TPU kernel.

TPU-native replacement for the reference's CUDA ``tf.custom_op`` kernels
(BASELINE.json:north_star — "rewrite any tf.custom_op / CUDA kernels ...
as Pallas or XLA custom-calls"; SURVEY.md §2c, §5g). The kernel is the
single-device base for ring attention (``parallel/ring.py``): it computes
attention over KV *blocks* with an online softmax and can return the
per-row logsumexp, so ring hops merge kernel outputs exactly.

Design (TPU-first, not a CUDA translation):
- One grid step meets a GROUP of rows (queries in the forward and dq
  kernels, keys in the dk/dv kernel) with a CHUNK of the other
  sequence, each up to ``_SPAN`` rows and whole in VMEM (GPT-2's 1,024
  keys of 64 are 128 KB): few, fat grid steps. Longer sequences walk
  several chunks on the innermost grid axis with the running state in
  VMEM scratch, so sequence length stays bounded by HBM, not VMEM.
- Where the causal diagonal crosses such a rectangle is one of a few
  offsets known when the kernel is traced, so each gets a static body:
  every tile of ``block`` rows takes what it sees of the chunk in ONE
  shot — the slices wholly visible as one wide product, the slices the
  diagonal crosses under a constant mask, the hidden ones not at all.
  No loop over small tiles, no mask but on the diagonal, and with one
  chunk no running softmax state either. A rectangle above the diagonal
  costs neither a fetch nor arithmetic.
- Every product takes its operands in the dtype the caller passed and
  accumulates in f32 (``preferred_element_type``): bf16 inputs feed the
  MXU bf16, f32 inputs f32. Scores, the softmax statistics, ``exp`` and
  the accumulators are f32 either way; ``p`` and ``ds`` are rounded to
  the operand dtype before their products, as ``attention_reference``
  rounds ``p``.
- Backward is the standard two-kernel split (dkv by KV tile, dq by Q
  tile) using the saved logsumexp, so the [seq, seq] score matrix is
  never materialized. The dk/dv kernel works on transposed tiles
  (``k·qᵀ``: keys on sublanes, queries on lanes), so its four products
  need no transpose and the per-query vectors ``lse`` and ``delta`` are
  lane-dense rows; the forward's ``lse`` output keeps its
  ``[batch·heads, seq, 1]`` shape and is re-laid outside the kernels.
  When the forward exposed the logsumexp, its cotangent is exact:
  d(lse_i)/d(s_ij) = p_ij folds into ``ds = p · (dp − (delta − dlse))``,
  one subtraction outside the kernels.
- The tiles of each kernel are a pure function of the shapes and the
  dtype (``flash_blocks``), from a sweep on the chip (PERF.md §6).

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
from tensorflow_examples_tpu.telemetry.spans import span

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


# ----------------------------------------------------------- tile algebra
#
# One grid step meets a GROUP of rows (queries in the forward and dq
# kernels, keys in dk/dv) with a CHUNK of the other sequence, both up to
# ``_SPAN`` long and whole in VMEM. Where the causal diagonal crosses
# that rectangle is one of a few offsets known when the kernel is
# traced (``_crossings``), so the body for each is static: every tile
# of ``block`` rows takes the visible part of the chunk in one shot —
# the slices wholly visible as one wide product, the slices the
# diagonal crosses under a constant mask, the hidden ones not at all.

_NN = (((1,), (0,)), ((), ()))  # a · b
_NT = (((1,), (1,)), ((), ()))  # a · bᵀ

_SPAN = 1024  # rows of a group, and of a chunk, at most
_SCOPED_VMEM_DEFAULT = 16 << 20  # Mosaic's own limit on a v5e
_TILE_TEMPS = 8  # f32 copies of a tile x chunk score block kept alive


def _dot(a, b, dims=_NN):
    """One MXU product: operands as they are, the sum in f32."""
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _span(seq: int, block: int) -> int:
    """Rows of one group or chunk: the whole sequence up to ``_SPAN``,
    else the most ``block``-row tiles under that which divide it."""
    tiles = seq // block
    fit = max(1, _SPAN // block)
    return block * next(
        t for t in range(min(tiles, fit), 0, -1) if tiles % t == 0
    )


def _crossings(groups, group, chunks, chunk, shift):
    """Every place the diagonal can stand in a group x chunk rectangle
    of the grid, as ``d`` = the last column of the chunk that the
    group's first row sees (columns are keys for the forward and dq,
    query rows — counted from the chunk's end — for dk/dv). All wholly
    visible rectangles are the one case ``d = chunk - 1``; a rectangle
    with ``d + group - 1 < 0`` is wholly hidden and not listed."""
    found = {
        min(i * group + shift - c * chunk, chunk - 1)
        for i in range(groups) for c in range(chunks)
    }
    return tuple(sorted(d for d in found if d > -group))


def _visible(d, row, block, step, chunk):
    """For the tile of ``block`` rows from ``row`` on, in a rectangle
    with the diagonal at ``d``: how many ``step``-column slices of the
    chunk all its rows see, and how many any row sees.
    Bottom-right-aligned causal diagonal: row i sees keys
    <= i + (seq_kv - seq_q), matching attention_reference."""
    clip = lambda x: min(max(x, 0), chunk)
    return clip(d + row + 1) // step, clip(d + row + block - 1 + step) // step


def _ahead(shape, columns_axis):
    """Column index minus row index inside a tile of ``shape``."""
    cols = lax.broadcasted_iota(jnp.int32, shape, columns_axis)
    return cols - lax.broadcasted_iota(jnp.int32, shape, 1 - columns_axis)


def _by_crossing(body, d, crossings, chunk):
    """Run the one static ``body(d)`` this grid step's rectangle needs:
    the listed place where its diagonal stands, nothing if it is wholly
    hidden; not causal, everything is visible everywhere."""
    if crossings is None:
        return body(chunk - 1)
    d = jnp.minimum(d, chunk - 1)
    for at in crossings:
        pl.when(d == at)(functools.partial(body, at))


# --------------------------------------------------------------- forward


def _fwd_kernel(
    q_ref, k_ref, v_ref, *rest,
    sm_scale, offset, block_q, block_kv, crossings, has_bias=False,
):
    kb_ref = rest[0] if has_bias else None
    o_ref, lse_ref, *state = rest[has_bias:]
    group, chunk = q_ref.shape[1], k_ref.shape[1]
    gi, c = pl.program_id(1), pl.program_id(2)

    def scores(q, a, b):
        # The f32 scores are scaled, not q: a bf16 x bf16 product is
        # exact in f32, a pre-scaled bf16 q only for power-of-two scales.
        s = _dot(q, k_ref[0, a:b, :], _NT) * sm_scale  # [block_q, b - a]
        if has_bias:
            s = s + kb_ref[0, 0, :, a:b]  # [1, b - a] broadcasts over rows
        return s

    def finish(rows, m, l, acc):
        l = jnp.maximum(l, 1e-30)
        o_ref[0, rows, :] = (acc / l).astype(o_ref.dtype)
        lse_ref[0, rows, :] = m + jnp.log(l)

    def attend(d):
        for row in range(0, group, block_q):
            rows = slice(row, row + block_q)
            full, some = _visible(d, row, block_q, block_kv, chunk)
            if not some:
                continue
            q = q_ref[0, rows, :]
            split, end = full * block_kv, some * block_kv
            parts = []
            if split:
                parts.append((0, split, scores(q, 0, split)))
            if end > split:
                seen = _ahead((block_q, end - split), 1) <= d + row - split
                parts.append((
                    split, end,
                    jnp.where(seen, scores(q, split, end), NEG_INF),
                ))
            m = functools.reduce(
                jnp.maximum,
                [jnp.max(s, axis=1, keepdims=True) for _, _, s in parts],
            )
            if state:  # merge with what earlier chunks left
                m_s, l_s, acc_s = state
                m_old, m = m_s[rows, :], jnp.maximum(m_s[rows, :], m)
                alpha = jnp.exp(m_old - m)
                l, acc = l_s[rows, :] * alpha, acc_s[rows, :] * alpha
            else:
                l = acc = 0.0
            for a, b, s in parts:
                p = jnp.exp(s - m)
                l = l + jnp.sum(p, axis=1, keepdims=True)
                v = v_ref[0, a:b, :]
                acc = acc + _dot(p.astype(v.dtype), v)
            if state:
                m_s[rows, :], l_s[rows, :], acc_s[rows, :] = m, l, acc
            else:
                finish(rows, m, l, acc)

    if state:
        m_s, l_s, acc_s = state

        @pl.when(c == 0)
        def _init():
            m_s[...] = jnp.full_like(m_s, NEG_INF)
            l_s[...] = jnp.zeros_like(l_s)
            acc_s[...] = jnp.zeros_like(acc_s)

    _by_crossing(attend, gi * group + offset - c * chunk, crossings, chunk)

    if state:
        @pl.when(c == pl.num_programs(2) - 1)
        def _finalize():
            finish(slice(None), m_s[...], l_s[...], acc_s[...])


def _compiler_params(rows: int, block_elems: int):
    """Mosaic's scoped-VMEM limit from the tile: ``rows`` operand,
    output and scratch rows, each a 128-lane f32 row at most and held
    twice (the pipeline's two buffers), plus ``_TILE_TEMPS`` f32 copies
    of the tile x chunk score block for what a kernel body keeps alive
    (scores, p, dp, ds, the mask, the operand-dtype casts). Never under
    the compiler's own default, which the measured tiles stay inside."""
    need = 2 * rows * 128 * 4 + _TILE_TEMPS * 4 * block_elems
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=max(need, _SCOPED_VMEM_DEFAULT),
    )


def _rows(x, block):
    """[n, seq] -> [n, seq // block, 1, block]: a per-position f32
    vector (lse, delta, the key bias), lane-dense, ``block`` of it a
    row. A block's last two dims are then the array's own, which Mosaic
    always accepts (a rank-2 [batch, seq_kv] bias with a (1, block_kv)
    block is unlowerable whenever batch > 1 — compiled-TPU-only,
    interpret mode never enforces it)."""
    return x.reshape(x.shape[0], x.shape[1] // block, 1, block)


def _rectangles(rows, cols, row_block, col_block, shift, causal):
    """The grid of group x chunk rectangles one kernel walks: (groups,
    chunks), their sizes, the crossings to trace bodies for, and the
    chunk a grid step fetches — a chunk wholly hidden from the group
    re-names the last one that is not: same block index, so the
    pipeline fetches nothing."""
    group, chunk = _span(rows, row_block), _span(cols, col_block)
    groups, chunks = rows // group, cols // chunk
    if not causal:
        return (groups, chunks), group, chunk, None, lambda i, c: c
    crossings = _crossings(groups, group, chunks, chunk, shift)
    visit = lambda i, c: jnp.minimum(
        c, (i * group + group - 1 + shift) // chunk
    )
    return (groups, chunks), group, chunk, crossings, visit


def _traced_once(call):
    """Every layer of a model makes the same kernel call at the same
    shapes, and tracing and lowering a kernel's body in Python is most
    of what such a call costs at start-up (GPT-2 124M's train step:
    4.1 s of it for 36 calls, 1.4 s for three, on the sandbox's CPU).
    So ``call(*arrays, **static)`` — ``static`` hashable, an array may
    be None — is traced once per operand types and ``static``, and its
    jaxpr replayed for every other call; the lowering of equal
    equations is shared the same way. The least recently used traces
    go, so a swept ``sm_scale`` leaks nothing."""

    @functools.lru_cache(maxsize=64)
    def trace(types, static):
        def placed(*given):
            given = iter(given)
            return call(
                *(t if t is None else next(given) for t in types),
                **dict(static),
            )

        return jax.make_jaxpr(placed, return_shape=True)(
            *(t for t in types if t is not None)
        )

    @functools.wraps(call)
    def replay(*arrays, **static):
        types = tuple(a if a is None else jax.typeof(a) for a in arrays)
        closed, shape = trace(types, tuple(sorted(static.items())))
        out = jax.core.eval_jaxpr(
            closed.jaxpr, closed.consts, *(a for a in arrays if a is not None)
        )
        return jax.tree.unflatten(jax.tree.structure(shape), out)

    return replay


@_traced_once
def _flash_fwd(
    q, k, v, kb=None, *, sm_scale, causal, blocks, interpret, heads=1
):
    bh, seq_q, head_dim = q.shape
    seq_kv = k.shape[1]
    block_q, block_kv = blocks
    offset = seq_kv - seq_q
    grid, group, chunk, crossings, visit = _rectangles(
        seq_q, seq_kv, block_q, block_kv, offset, causal
    )
    row_blk = pl.BlockSpec((1, group, head_dim), lambda b, i, c: (b, i, 0))
    kv_blk = pl.BlockSpec(
        (1, chunk, head_dim), lambda b, i, c: (b, visit(i, c), 0)
    )
    in_specs = [row_blk, kv_blk, kv_blk]
    args = [q, k, v]
    if kb is not None:
        # Grid dim 0 is batch·heads, so the bias row is
        # program_id(0) // heads (static closure).
        in_specs.append(pl.BlockSpec(
            (1, 1, 1, chunk), lambda b, i, c: (b // heads, visit(i, c), 0, 0)
        ))
        args.append(_rows(kb, chunk))
    # One chunk holds every key: nothing to merge, no running state.
    state = [] if grid[1] == 1 else [
        pltpu.VMEM((group, 1), jnp.float32),
        pltpu.VMEM((group, 1), jnp.float32),
        pltpu.VMEM((group, head_dim), jnp.float32),
    ]
    return pl.pallas_call(
        functools.partial(
            _fwd_kernel, sm_scale=sm_scale, offset=offset, block_q=block_q,
            block_kv=block_kv, crossings=crossings, has_bias=kb is not None,
        ),
        grid=(bh, *grid),
        in_specs=in_specs,
        out_specs=[
            row_blk,
            pl.BlockSpec((1, group, 1), lambda b, i, c: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq_q, head_dim), q.dtype),
            jax.ShapeDtypeStruct((bh, seq_q, 1), jnp.float32),
        ],
        scratch_shapes=state,
        compiler_params=_compiler_params(
            6 * group + 2 * chunk, block_q * chunk
        ),
        interpret=interpret,
    )(*args)


# -------------------------------------------------------------- backward


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
    sm_scale, offset, block_q, block_kv, crossings, has_bias=False,
):
    kb_ref = rest[0] if has_bias else None
    dq_ref, *state = rest[has_bias:]
    group, chunk = q_ref.shape[1], k_ref.shape[1]
    gi, c = pl.program_id(1), pl.program_id(2)

    def accumulate(d):
        for row in range(0, group, block_q):
            rows = slice(row, row + block_q)
            full, some = _visible(d, row, block_q, block_kv, chunk)
            if not some:
                continue
            q, do = q_ref[0, rows, :], do_ref[0, rows, :]
            # The per-row vectors arrive as lane-dense rows; laid along
            # the sublanes once a tile, to broadcast against its rows.
            lse = lse_ref[0, 0, :, rows].T  # [block_q, 1]
            delta = delta_ref[0, 0, :, rows].T

            def part(a, b, seen=None):
                k, v = k_ref[0, a:b, :], v_ref[0, a:b, :]
                s = _dot(q, k, _NT) * sm_scale  # [block_q, b - a]
                if has_bias:
                    s = s + kb_ref[0, 0, :, a:b]
                if seen is not None:
                    s = jnp.where(seen, s, NEG_INF)
                p = jnp.exp(s - lse)
                # dp = do v^T; ds = p (dp - delta), delta less any dlse
                ds = p * (_dot(do, v, _NT) - delta)
                return _dot(ds.astype(k.dtype), k)

            split, end = full * block_kv, some * block_kv
            dq = part(0, split) if split else 0.0
            if end > split:
                seen = _ahead((block_q, end - split), 1) <= d + row - split
                dq = dq + part(split, end, seen)
            if state:
                state[0][rows, :] += dq
            else:
                dq_ref[0, rows, :] = (dq * sm_scale).astype(dq_ref.dtype)

    if state:
        dq_s, = state

        @pl.when(c == 0)
        def _init():
            dq_s[...] = jnp.zeros_like(dq_s)

    _by_crossing(accumulate, gi * group + offset - c * chunk, crossings, chunk)

    if state:
        @pl.when(c == pl.num_programs(2) - 1)
        def _finalize():
            dq_ref[0] = (dq_s[...] * sm_scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
    sm_scale, block_q, block_kv, crossings, has_bias=False,
):
    """Transposed tiles: keys on sublanes, queries on lanes. ``k·qᵀ``,
    ``v·doᵀ``, ``pᵀ·do`` and ``dsᵀ·q`` are then plain products, and
    ``lse`` / ``delta`` broadcast as the lane-dense rows they arrive as.

    Counted from the far corner — keys from the group's last, query
    rows from the chunk's last — a group of keys against a chunk of rows
    is the forward's rectangle with the diagonal top-left aligned: key
    κ' is seen by rows ρ' <= κ' + d. So the same ``_visible`` says, for
    each tile of keys, how many slices of rows (from the chunk's end)
    all of it is seen by, and how many any of it."""
    kb_ref = rest[0] if has_bias else None
    dk_ref, dv_ref, *state = rest[has_bias:]
    group, chunk = k_ref.shape[1], q_ref.shape[1]
    gi, c = pl.program_id(1), pl.program_id(2)
    far_group = pl.num_programs(1) - 1 - gi
    far_chunk = pl.num_programs(2) - 1 - c

    def accumulate(d):
        for far in range(0, group, block_kv):
            keys = slice(group - far - block_kv, group - far)
            full, some = _visible(d, far, block_kv, block_q, chunk)
            if not some:
                continue
            k, v = k_ref[0, keys, :], v_ref[0, keys, :]
            if has_bias:
                bias = kb_ref[0, 0, :, keys].T  # [block_kv, 1]

            def part(a, b, seen=None):
                q, do = q_ref[0, a:b, :], do_ref[0, a:b, :]
                st = _dot(k, q, _NT) * sm_scale  # [block_kv, b - a]
                if has_bias:
                    st = st + bias
                if seen is not None:
                    st = jnp.where(seen, st, NEG_INF)
                pt = jnp.exp(st - lse_ref[0, 0, :, a:b])  # [1, b - a] a key
                dv = _dot(pt.astype(do.dtype), do)
                dst = pt * (_dot(v, do, _NT) - delta_ref[0, 0, :, a:b])
                return _dot(dst.astype(q.dtype), q), dv

            start, split = chunk - some * block_q, chunk - full * block_q
            dk = dv = 0.0
            if split > start:
                # key - row here, against the same bound in far terms
                seen = _ahead((block_kv, split - start), 0) <= (
                    d + far + block_kv - some * block_q
                )
                dk, dv = part(start, split, seen)
            if full:
                dk_p, dv_p = part(split, chunk)
                dk, dv = dk + dk_p, dv + dv_p
            if state:
                state[0][keys, :] += dk
                state[1][keys, :] += dv
            else:
                dk_ref[0, keys, :] = (dk * sm_scale).astype(dk_ref.dtype)
                dv_ref[0, keys, :] = dv.astype(dv_ref.dtype)

    if state:
        dk_s, dv_s = state

        @pl.when(c == 0)
        def _init():
            dk_s[...] = jnp.zeros_like(dk_s)
            dv_s[...] = jnp.zeros_like(dv_s)

    _by_crossing(
        accumulate, far_group * group - far_chunk * chunk, crossings, chunk
    )

    if state:
        @pl.when(c == pl.num_programs(2) - 1)
        def _finalize():
            dk_ref[0] = (dk_s[...] * sm_scale).astype(dk_ref.dtype)
            dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


@_traced_once
def _flash_dq(
    q, k, v, do, lse, delta, kb=None, *,
    sm_scale, causal, blocks, interpret, heads=1,
):
    """dq by query tile; ``lse`` and ``delta`` are [bh, seq_q] f32."""
    bh, seq_q, head_dim = q.shape
    seq_kv = k.shape[1]
    block_q, block_kv = blocks
    offset = seq_kv - seq_q
    grid, group, chunk, crossings, visit = _rectangles(
        seq_q, seq_kv, block_q, block_kv, offset, causal
    )
    row_blk = pl.BlockSpec((1, group, head_dim), lambda b, i, c: (b, i, 0))
    kv_blk = pl.BlockSpec(
        (1, chunk, head_dim), lambda b, i, c: (b, visit(i, c), 0)
    )
    vec_blk = pl.BlockSpec((1, 1, 1, group), lambda b, i, c: (b, i, 0, 0))
    in_specs = [row_blk, kv_blk, kv_blk, row_blk, vec_blk, vec_blk]
    args = [q, k, v, do, _rows(lse, group), _rows(delta, group)]
    if kb is not None:
        in_specs.append(pl.BlockSpec(
            (1, 1, 1, chunk), lambda b, i, c: (b // heads, visit(i, c), 0, 0)
        ))
        args.append(_rows(kb, chunk))
    state = [] if grid[1] == 1 else [
        pltpu.VMEM((group, head_dim), jnp.float32)
    ]
    return pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, sm_scale=sm_scale, offset=offset, block_q=block_q,
            block_kv=block_kv, crossings=crossings, has_bias=kb is not None,
        ),
        grid=(bh, *grid),
        in_specs=in_specs,
        out_specs=row_blk,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=state,
        compiler_params=_compiler_params(
            4 * group + 2 * chunk + group // 8, block_q * chunk
        ),
        interpret=interpret,
    )(*args)


@_traced_once
def _flash_dkv(
    q, k, v, do, lse, delta, kb=None, *,
    sm_scale, causal, blocks, interpret, heads=1,
):
    """dk and dv by key tile; ``lse`` and ``delta`` are [bh, seq_q] f32."""
    bh, seq_q, head_dim = q.shape
    seq_kv = k.shape[1]
    block_q, block_kv = blocks
    # The far-corner view (see the kernel): the diagonal is top-left
    # aligned there, so no shift, and the grid's indices count back.
    grid, group, chunk, crossings, far_visit = _rectangles(
        seq_kv, seq_q, block_kv, block_q, 0, causal
    )
    groups, chunks = grid
    visit = lambda i, c: chunks - 1 - far_visit(groups - 1 - i, chunks - 1 - c)
    kv_blk = pl.BlockSpec((1, group, head_dim), lambda b, i, c: (b, i, 0))
    row_blk = pl.BlockSpec(
        (1, chunk, head_dim), lambda b, i, c: (b, visit(i, c), 0)
    )
    vec_blk = pl.BlockSpec(
        (1, 1, 1, chunk), lambda b, i, c: (b, visit(i, c), 0, 0)
    )
    in_specs = [row_blk, kv_blk, kv_blk, row_blk, vec_blk, vec_blk]
    args = [q, k, v, do, _rows(lse, chunk), _rows(delta, chunk)]
    if kb is not None:
        in_specs.append(pl.BlockSpec(
            (1, 1, 1, group), lambda b, i, c: (b // heads, i, 0, 0)
        ))
        args.append(_rows(kb, group))
    state = [] if chunks == 1 else [
        pltpu.VMEM((group, head_dim), jnp.float32),
        pltpu.VMEM((group, head_dim), jnp.float32),
    ]
    return pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, sm_scale=sm_scale, block_q=block_q,
            block_kv=block_kv, crossings=crossings, has_bias=kb is not None,
        ),
        grid=(bh, *grid),
        in_specs=in_specs,
        out_specs=[kv_blk, kv_blk],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=state,
        compiler_params=_compiler_params(
            6 * group + 2 * chunk + chunk // 8, block_kv * chunk
        ),
        interpret=interpret,
    )(*args)


def _flash_bwd(
    sm_scale, causal, blocks, interpret, residuals, do, dlse,
    kb=None, heads=1,
):
    q, k, v, o, lse = residuals
    # delta_i = rowsum(do_i * o_i) — cheap, let XLA fuse it; an lse
    # cotangent only ever appears as (delta - dlse), so it is folded in
    # here and the plain path makes no zeros to stand in for it.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32).reshape(delta.shape)
    lse = lse.reshape(delta.shape)  # the forward's [bh, seq, 1], lane-dense
    static = dict(
        sm_scale=sm_scale, causal=causal, interpret=interpret, heads=heads
    )
    grads = (q, k, v, do, lse, delta, kb)
    dq = _flash_dq(*grads, blocks=blocks[1], **static)
    dk, dv = _flash_dkv(*grads, blocks=blocks[2], **static)
    return dq, dk, dv


# ------------------------------------------------------------ public api


@functools.lru_cache(maxsize=None)
def _make_flash(causal, blocks, interpret):
    # sm_scale stays out of this cache's key (a swept/per-layer scale
    # must not leak a closure per value) — it rides through as a
    # nondiff arg, into _traced_once's bounded one.
    forward = functools.partial(
        _flash_fwd, causal=causal, blocks=blocks[0], interpret=interpret
    )

    @functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
    def flash(q, k, v, sm_scale):
        o, _ = forward(q, k, v, sm_scale=sm_scale)
        return o

    def fwd(q, k, v, sm_scale):
        o, lse = forward(q, k, v, sm_scale=sm_scale)
        return o, (q, k, v, o, lse)

    def bwd(sm_scale, residuals, g):
        return _flash_bwd(sm_scale, causal, blocks, interpret, residuals, g, None)

    flash.defvjp(fwd, bwd)
    return flash


@functools.lru_cache(maxsize=None)
def _make_flash_lse(causal, blocks, interpret):
    """Variant returning (o, lse) with the exact lse cotangent in bwd —
    the building block ring attention merges across hops."""
    forward = functools.partial(
        _flash_fwd, causal=causal, blocks=blocks[0], interpret=interpret
    )

    @functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
    def flash(q, k, v, sm_scale):
        return tuple(forward(q, k, v, sm_scale=sm_scale))

    def fwd(q, k, v, sm_scale):
        o, lse = forward(q, k, v, sm_scale=sm_scale)
        return (o, lse), (q, k, v, o, lse)

    def bwd(sm_scale, residuals, g):
        do, dlse = g
        return _flash_bwd(
            sm_scale, causal, blocks, interpret, residuals, do, dlse
        )

    flash.defvjp(fwd, bwd)
    return flash


@functools.lru_cache(maxsize=None)
def _make_flash_bias(causal, blocks, interpret, heads):
    """Variant with a [batch, seq_kv] additive key bias (padding masks).

    The bias is treated as NON-differentiable data — it comes from an
    attention mask, and a ±NEG_INF bias has no meaningful gradient — so
    its cotangent is zeros; the bwd kernels still ADD it when
    recomputing the scores (p must match the forward's softmax).
    """
    forward = functools.partial(
        _flash_fwd, causal=causal, blocks=blocks[0], interpret=interpret,
        heads=heads,
    )

    @functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
    def flash(q, k, v, kb, sm_scale):
        o, _ = forward(q, k, v, kb, sm_scale=sm_scale)
        return o

    def fwd(q, k, v, kb, sm_scale):
        o, lse = forward(q, k, v, kb, sm_scale=sm_scale)
        return o, (q, k, v, o, lse, kb)

    def bwd(sm_scale, residuals, g):
        *res, kb = residuals
        dq, dk, dv = _flash_bwd(
            sm_scale, causal, blocks, interpret, tuple(res), g, None,
            kb=kb, heads=heads,
        )
        return dq, dk, dv, jnp.zeros_like(kb)

    flash.defvjp(fwd, bwd)
    return flash


KERNELS = ("fwd", "dq", "dkv")

# (block_q, block_kv) each kernel aims for: the fastest of {128, 256,
# 512}² on a v5e at GPT-2 124M's training shape — batch·heads 192, seq
# 1,024, head_dim 64, bf16, causal (PERF.md §6 has the table; f32
# operands and BERT's non-causal 512 are within 2% of their own best
# there, 4,096 causal prefers a 512-row forward tile by 9%).
_BLOCK_TARGETS = {"fwd": (256, 256), "dq": (256, 256), "dkv": (128, 128)}


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


def flash_blocks(
    seq_q: int, seq_kv: int, head_dim: int, dtype, causal: bool, kernel: str
) -> tuple[int, int]:
    """The (block_q, block_kv) tile of one of ``KERNELS`` for a call of
    these shapes: the swept targets, fitted down to hardware-legal
    divisors of the two lengths (``_fit_block``). ``head_dim``,
    ``dtype`` and ``causal`` are what a further sweep would be looked
    up by; the one there is gave them no row of their own."""
    block_q, block_kv = _BLOCK_TARGETS[kernel]
    return _fit_block(block_q, seq_q), _fit_block(block_kv, seq_kv)


def _resolve_block(block: int, seq: int) -> int:
    """An explicit block size is honored exactly (divisibility enforced,
    never silently overridden)."""
    b = min(block, seq)
    if seq % b:
        raise ValueError(
            f"sequence length {seq} is not divisible by block size {b}; "
            "pass block sizes that divide it, or None for auto"
        )
    return b


@functools.lru_cache(maxsize=None)
def _record_plan(shape, seq_kv, dtype, causal, blocks):
    """One ``span/flash_plan`` per traced shape, so a run's record says
    which tiles it ran (at trace time, never inside a step)."""
    with span(
        "flash_plan", q=str(shape), seq_kv=seq_kv, dtype=dtype,
        causal=causal, **{n: str(b) for n, b in zip(KERNELS, blocks)},
    ):
        pass


def _prepare(q, k, v, causal, sm_scale, block_q, block_kv, interpret):
    if interpret is None:
        interpret = pallas_interpret("flash_attention")
    b, h, seq_q, head_dim = q.shape
    seq_kv = k.shape[2]
    if causal and seq_q > seq_kv:
        # Rows with zero visible keys are degenerate (the reference
        # softmaxes an all-masked row into uniform weights; the kernel
        # would return 0) — reject rather than silently diverge.
        raise ValueError(
            f"causal attention requires seq_q ({seq_q}) <= seq_kv ({seq_kv})"
        )
    plan = []
    for kernel in KERNELS:
        auto = flash_blocks(seq_q, seq_kv, head_dim, q.dtype, causal, kernel)
        plan.append((
            auto[0] if block_q is None else _resolve_block(block_q, seq_q),
            auto[1] if block_kv is None else _resolve_block(block_kv, seq_kv),
        ))
    blocks = tuple(plan)
    _record_plan(q.shape, seq_kv, jnp.dtype(q.dtype).name, bool(causal), blocks)
    if sm_scale is None:
        sm_scale = head_dim**-0.5
    return float(sm_scale), blocks, interpret


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
    block_q/block_kv None = auto: each kernel's own tile
    (``flash_blocks``: measured on a v5e — PERF.md §6), fitted down to a
    hardware-legal divisor of the sequence; explicit sizes set every
    kernel's tile and are enforced exactly.

    ``key_bias``: optional [batch, seq_kv] additive score bias (f32),
    broadcast over heads and query rows — the padding-mask shape BERT
    needs. Non-differentiable (zero cotangent; it is mask data).
    """
    sm_scale, blocks, interpret = _prepare(
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
        flash = _make_flash_bias(bool(causal), blocks, interpret, h)
        out = flash(
            fold(q), fold(k), fold(v),
            key_bias.astype(jnp.float32), sm_scale,
        )
        return out.reshape(b, h, seq_q, head_dim)
    flash = _make_flash(bool(causal), blocks, interpret)
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
    sm_scale, blocks, interpret = _prepare(
        q, k, v, causal, sm_scale, block_q, block_kv, interpret
    )
    b, h, seq_q, head_dim = q.shape
    flash = _make_flash_lse(bool(causal), blocks, interpret)
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
