"""Fused softmax cross-entropy as a Pallas TPU kernel.

The reference computed sparse categorical cross-entropy via stock ops,
which materializes a full [tokens, vocab] log-softmax in HBM — at GPT-2
scale (vocab 50257) that is the single largest activation in the model.
This kernel is HBM-bandwidth shaped instead: the vocab axis is consumed
in VMEM-sized chunks with an online logsumexp; only per-row (nll, lse)
ever leave the chip's VMEM in forward, and backward recomputes the
softmax chunk-by-chunk from the saved lse (SURVEY.md §2c obligation —
"fused cross-entropy" in the kernels layer).

Grid layout: (row blocks, vocab chunks). The TPU grid is sequential with
the last dimension fastest, so VMEM scratch carries the running
(max, sumexp, label-logit) across vocab chunks of one row block — the
same accumulation pattern as a blocked matmul's K loop.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from tensorflow_examples_tpu.core import collectives as coll
from tensorflow_examples_tpu.core.collectives import shard_map as _shard_map
from tensorflow_examples_tpu.core.device import pallas_interpret
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def cross_entropy_reference(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Per-example NLL in f32 via plain XLA. logits [N, V], labels [N].

    Label selection uses the gather-free mask+reduce (ops.losses
    .select_label) so this path partitions cleanly under SPMD too."""
    from tensorflow_examples_tpu.ops.losses import select_label

    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    return lse - select_label(logits, labels)


# --------------------------------------------------------------- kernels


def _ce_fwd_kernel(
    logits_ref, labels_ref, nll_ref, lse_ref, m_acc, l_acc, t_acc, *, vocab
):
    j = pl.program_id(1)
    block_n, block_v = logits_ref.shape

    @pl.when(j == 0)
    def _():
        m_acc[...] = jnp.full_like(m_acc, NEG_INF)
        l_acc[...] = jnp.zeros_like(l_acc)
        t_acc[...] = jnp.zeros_like(t_acc)

    col = j * block_v + lax.broadcasted_iota(jnp.int32, (block_n, block_v), 1)
    s = jnp.where(col < vocab, logits_ref[...].astype(jnp.float32), NEG_INF)
    labels = labels_ref[...]  # [block_n, 1]

    m_prev, l_prev = m_acc[...], l_acc[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + jnp.sum(jnp.exp(s - m_new), axis=1, keepdims=True)
    m_acc[...] = m_new
    l_acc[...] = l_new
    # The label's logit lands in exactly one vocab chunk; accumulate it.
    t_acc[...] += jnp.sum(
        jnp.where(col == labels, s, 0.0), axis=1, keepdims=True
    )

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        lse = m_acc[...] + jnp.log(jnp.maximum(l_acc[...], 1e-30))
        lse_ref[...] = lse
        nll_ref[...] = lse - t_acc[...]


def _ce_bwd_kernel(logits_ref, labels_ref, lse_ref, g_ref, dlogits_ref, *, vocab):
    j = pl.program_id(1)
    block_n, block_v = logits_ref.shape
    col = j * block_v + lax.broadcasted_iota(jnp.int32, (block_n, block_v), 1)
    logits = logits_ref[...].astype(jnp.float32)
    p = jnp.exp(logits - lse_ref[...])  # softmax chunk from saved lse
    onehot = (col == labels_ref[...]).astype(jnp.float32)
    d = g_ref[...] * (p - onehot)
    dlogits_ref[...] = jnp.where(col < vocab, d, 0.0).astype(dlogits_ref.dtype)


def _fwd_call(logits, labels2d, block_n, block_v, interpret):
    n, vocab = logits.shape
    grid = (pl.cdiv(n, block_n), pl.cdiv(vocab, block_v))
    row_spec = pl.BlockSpec((block_n, 1), lambda i, j: (i, 0))
    nll, lse = pl.pallas_call(
        functools.partial(_ce_fwd_kernel, vocab=vocab),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, block_v), lambda i, j: (i, j)),
            row_spec,
        ],
        out_specs=[row_spec, row_spec],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_n, 1), jnp.float32),
            pltpu.VMEM((block_n, 1), jnp.float32),
            pltpu.VMEM((block_n, 1), jnp.float32),
        ],
        interpret=interpret,
    )(logits, labels2d)
    return nll, lse


@functools.lru_cache(maxsize=None)
def _make_fused(block_n, block_v, interpret):
    @jax.custom_vjp
    def fused(logits, labels2d):
        nll, _ = _fwd_call(logits, labels2d, block_n, block_v, interpret)
        return nll

    def fwd(logits, labels2d):
        nll, lse = _fwd_call(logits, labels2d, block_n, block_v, interpret)
        return nll, (logits, labels2d, lse)

    def bwd(residuals, g):
        logits, labels2d, lse = residuals
        n, vocab = logits.shape
        row_spec = pl.BlockSpec((block_n, 1), lambda i, j: (i, 0))
        dlogits = pl.pallas_call(
            functools.partial(_ce_bwd_kernel, vocab=vocab),
            grid=(pl.cdiv(n, block_n), pl.cdiv(vocab, block_v)),
            in_specs=[
                pl.BlockSpec((block_n, block_v), lambda i, j: (i, j)),
                row_spec, row_spec, row_spec,
            ],
            out_specs=pl.BlockSpec((block_n, block_v), lambda i, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct(logits.shape, logits.dtype),
            interpret=interpret,
        )(logits, labels2d, lse, g.astype(jnp.float32))
        return dlogits, None

    fused.defvjp(fwd, bwd)
    return fused


# ------------------------------------------------------------ public api


def cross_entropy_per_example(
    logits: jax.Array,
    labels: jax.Array,
    *,
    block_n: int = 256,
    block_v: int = 4096,
    fused: bool | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Per-example NLL [N] (f32) from logits [N, V] and int labels [N].

    Default blocks 256×4096: ~4% faster fwd and grad than 128×2048 at
    the GPT-2 shape (8192 tokens × 50257 vocab, bf16, single v5e,
    within-run sweep); 512×4096 exceeds the compiler's VMEM budget.
    Blocks clamp to the actual (n, vocab) for small shapes."""
    if fused is None:
        fused = True
    if not fused:
        return cross_entropy_reference(logits, labels)
    if interpret is None:
        interpret = pallas_interpret("fused_cross_entropy")
    n, vocab = logits.shape
    block_n = min(block_n, n)
    block_v = min(block_v, vocab)
    fn = _make_fused(block_n, block_v, interpret)
    return fn(logits, labels.astype(jnp.int32)[:, None])[:, 0]


def mesh_cross_entropy_per_example(
    logits: jax.Array,  # [B, S, V]
    labels: jax.Array,  # [B, S] int
    *,
    mesh,
    fused: bool | None = None,
) -> jax.Array:
    """Token-sharded per-example NLL [B, S] for meshed training steps.

    The fused Pallas kernel is OPAQUE to the SPMD partitioner: called on
    data-sharded logits it triggers the partitioner's while-loop gather
    fallback, which all-gathers the full ``[tokens, vocab]`` logits
    across the data axes every step (measured: five data-axis
    ``[1024, 512]`` all-gathers in the dp2×model4 census,
    ``tools/ep_census.py``, round 4). CE is per-token independent, so a
    ``shard_map`` over the token axes makes the kernel local per shard
    with zero collectives. The ``model`` axis joins the seq-dim
    sharding when it divides: CE is replicated work under TP otherwise,
    and feeding logits in model-replicated would cost a [tokens, vocab]
    dlogits psum over ``model`` in the backward (measured before this
    split landed); with the split, sharding propagation pushes the seq
    partition up into the LM-head matmul itself. Axes that don't divide
    the corresponding dim are dropped (tokens replicate there — same policy as
    ``parallel/moe.py``); on a 1-device mesh this degenerates to the
    plain call.
    """
    from jax.sharding import PartitionSpec as P

    from tensorflow_examples_tpu.core.mesh import token_partition_axes

    def _plain(lg, lb):
        v = lg.shape[-1]
        return cross_entropy_per_example(
            lg.reshape(-1, v), lb.reshape(-1), fused=fused
        ).reshape(lb.shape)

    if mesh is None:
        return _plain(logits, labels)
    batch_axes, seq_axes = token_partition_axes(
        mesh, labels.shape[0], labels.shape[1], include_model=True
    )
    if not batch_axes and not seq_axes:
        return _plain(logits, labels)
    lg_spec = P(
        batch_axes if batch_axes else None,
        seq_axes if seq_axes else None,
        None,
    )
    lb_spec = P(
        batch_axes if batch_axes else None, seq_axes if seq_axes else None
    )
    return _shard_map(
        _plain,
        mesh=mesh,
        in_specs=(lg_spec, lb_spec),
        out_specs=lb_spec,
        check_vma=False,
    )(logits, labels)


def cross_entropy_loss(
    logits: jax.Array,
    labels: jax.Array,
    weights: jax.Array | None = None,
    *,
    fused: bool | None = None,
) -> jax.Array:
    """Weighted-mean token cross-entropy for LM heads.

    logits [..., V], labels [...]; weights [...] masks padding. Leading
    dims are flattened so the kernel sees one [tokens, vocab] problem.
    """
    from tensorflow_examples_tpu.ops.losses import weighted_mean

    vocab = logits.shape[-1]
    flat_logits = logits.reshape(-1, vocab)
    flat_labels = labels.reshape(-1)
    nll = cross_entropy_per_example(flat_logits, flat_labels, fused=fused)
    return weighted_mean(
        nll, None if weights is None else weights.reshape(-1)
    )


# ------------------------------------------------- vocab-parallel (TP) CE


def tp_cross_entropy_from_hidden(
    hidden: jax.Array,   # [N, d] final hidden states (post ln_f)
    wte: jax.Array,      # [V, d] tied embedding / LM head table
    labels: jax.Array,   # [N] int
    *,
    mesh,
    axis_name: str = "model",
    block_v: int = 2048,
) -> jax.Array:
    """Per-example NLL with the vocab axis sharded over ``axis_name``.

    The Megatron-style parallel LM head: each device holds a [V/m, d]
    slice of the embedding table, computes its local logits on the MXU,
    and only the online-softmax partials (max, sumexp, label-logit) cross
    ICI via pmax/psum — the full [N, V] logits never exist anywhere, and
    each device's HBM sees at most [N, V/m]. Degenerates to the fused
    Pallas kernel when the axis is trivial.

    Inside, the local [N, V/m] problem is consumed in ``block_v`` chunks
    by a lax.scan (the XLA analogue of the Pallas kernel's vocab loop) so
    peak memory is [N, block_v] regardless of shard width.
    """
    from jax.sharding import PartitionSpec as P

    from tensorflow_examples_tpu.core.mesh import AxisNames

    if mesh is None or mesh.shape[axis_name] == 1:
        logits = jnp.einsum(
            "nd,vd->nv", hidden, wte, preferred_element_type=jnp.float32
        )
        return cross_entropy_per_example(logits, labels)

    n_shards = mesh.shape[axis_name]
    vocab = wte.shape[0]
    batch = tuple(a for a in AxisNames.BATCH_AXES if mesh.shape[a] > 1)
    bspec = P(batch if batch else None)

    # Pad the vocab axis only to the shard count: when vocab % n_shards
    # == 0 this is a no-op and the shard_map split lines up EXACTLY with
    # the P(model, None) table sharding (no resharding collective). The
    # inner chunking pads per-shard, locally.
    v_local = pl.cdiv(vocab, n_shards)
    wte_pad = jnp.pad(wte, ((0, v_local * n_shards - vocab), (0, 0)))
    block = min(block_v, v_local)
    num_blocks = pl.cdiv(v_local, block)

    def local(hidden, wte_local, labels):
        shard = lax.axis_index(axis_name)
        base = shard * v_local
        n = hidden.shape[0]
        # Local pad so every dynamic_slice chunk is full-size; padded
        # rows have global col >= vocab only when base + local idx maps
        # past this shard's true rows — mask on the LOCAL index as well
        # as the global vocab bound.
        local_pad = num_blocks * block - v_local
        wte_loc = jnp.pad(wte_local, ((0, local_pad), (0, 0)))

        def chunk(carry, i):
            m, l, t = carry
            w = lax.dynamic_slice(
                wte_loc, (i * block, 0), (block, wte_loc.shape[1])
            )
            s = jnp.einsum(
                "nd,vd->nv", hidden, w, preferred_element_type=jnp.float32
            )
            local_idx = i * block + lax.broadcasted_iota(
                jnp.int32, (n, block), 1
            )
            col = base + local_idx
            s = jnp.where((local_idx < v_local) & (col < vocab), s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=1))
            l_new = l * jnp.exp(m - m_new) + jnp.sum(
                jnp.exp(s - m_new[:, None]), axis=1
            )
            t_new = t + jnp.sum(
                jnp.where(col == labels[:, None], s, 0.0), axis=1
            )
            return (m_new, l_new, t_new), None

        # Initial carries derived from hidden so they inherit its
        # varying-axes type under shard_map (cf. parallel/ring.py).
        zero = 0.0 * hidden[:, 0].astype(jnp.float32)
        (m, l, t), _ = lax.scan(
            chunk,
            (zero + NEG_INF, zero, zero),
            jnp.arange(num_blocks),
        )
        # Merge shards: global max, rescaled sumexp, label logit (the
        # label lands in exactly one shard; others contribute 0). The max
        # is a pure stabilizer — stop_gradient keeps the exact softmax
        # gradient and sidesteps pmax's missing differentiation rule.
        gm = coll.pmax(lax.stop_gradient(m), axis_name)
        gl = coll.psum(l * jnp.exp(m - gm), axis_name)
        gt = coll.psum(t, axis_name)
        return gm + jnp.log(jnp.maximum(gl, 1e-30)) - gt

    return _shard_map(
        local,
        mesh=mesh,
        in_specs=(bspec, P(axis_name, None), bspec),
        out_specs=bspec,
        check_vma=False,
    )(hidden, wte_pad, labels.astype(jnp.int32))
