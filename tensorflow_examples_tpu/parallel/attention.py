"""Mesh-aware attention dispatch: the jit ↔ shard_map bridge.

XLA auto-partitions dense math from sharding annotations, but a Pallas
kernel is opaque to the SPMD partitioner — calling it under jit with
sharded operands would force an all-gather. ``mesh_attention`` closes the
gap: it wraps the flash kernel (or the ring/Ulysses collectives when the
``context`` axis is real) in ``shard_map`` with the framework's canonical
specs, so batch rides (data, fsdp), heads ride ``model``, and sequence
rides ``context`` — each device runs the kernel on exactly its shard.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from tensorflow_examples_tpu.core.collectives import shard_map as _shard_map
from tensorflow_examples_tpu.core.mesh import AxisNames
from tensorflow_examples_tpu.ops.attention import dot_product_attention
from tensorflow_examples_tpu.parallel.ring import ring_attention, ulysses_attention


def attention_spec(mesh: Mesh) -> P:
    """PartitionSpec for [batch, heads, seq, head_dim] on the mesh."""
    batch = tuple(a for a in AxisNames.BATCH_AXES if mesh.shape[a] > 1)
    model = AxisNames.MODEL if mesh.shape[AxisNames.MODEL] > 1 else None
    ctx = AxisNames.CONTEXT if mesh.shape[AxisNames.CONTEXT] > 1 else None
    return P(batch if batch else None, model, ctx, None)


def decode_spec(mesh: Mesh, batch: int, heads: int) -> P:
    """PartitionSpec for decode-time [batch, heads, seq, head_dim]
    operands: batch over the batch axes, heads over ``model`` — the TP
    layout the projections already produce — with each dimension
    replicated instead when its size doesn't divide the mesh axes.
    No ``context`` entry: the KV cache is positionally complete on every
    device; context parallelism is a training-time concept."""
    batch_axes = tuple(a for a in AxisNames.BATCH_AXES if mesh.shape[a] > 1)
    nb = math.prod(mesh.shape[a] for a in batch_axes) if batch_axes else 1
    if batch % nb:
        batch_axes = ()
    m = mesh.shape[AxisNames.MODEL]
    model = AxisNames.MODEL if m > 1 and heads % m == 0 else None
    return P(batch_axes if batch_axes else None, model, None, None)


def mesh_decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    length: jax.Array,
    *,
    mesh: Mesh | None,
    sm_scale: float | None = None,
) -> jax.Array:
    """KV-cache flash-decode on a mesh: the Pallas kernel is opaque to
    the SPMD partitioner (calling it with sharded operands would force
    an all-gather of the cache — the exact O(max_len) read the kernel
    exists to avoid), so it runs under ``shard_map`` with batch/heads
    sharding. Single-device meshes fall through to the plain kernel."""
    from tensorflow_examples_tpu.ops.decode import flash_decode_attention

    if mesh is None or all(mesh.shape[a] == 1 for a in AxisNames.ALL):
        return flash_decode_attention(q, k_cache, v_cache, length, sm_scale=sm_scale)
    spec = decode_spec(mesh, q.shape[0], q.shape[1])
    local = functools.partial(flash_decode_attention, sm_scale=sm_scale)
    return _shard_map(
        local,
        mesh=mesh,
        in_specs=(spec, spec, spec, P()),
        out_specs=spec,
        check_vma=False,
    )(q, k_cache, v_cache, length)


def _stage_tp_axis(heads: int):
    """Detect the PP×TP stage situation: we are INSIDE a manual
    (shard_map) region — a pipeline stage — whose ``model`` axis is
    still AUTO and nontrivial, and the head count divides it. Returns
    the axis name to nest a model-only shard_map over, else None.

    Without this, a flash call inside a pipe-manual stage is opaque to
    the partitioner, which all-gathers the model-sharded heads around
    the Pallas kernel (the round-3 reason PP×TP stages had to use
    ``attention="xla"``)."""
    get_am = getattr(jax.sharding, "get_abstract_mesh", None)
    if get_am is None:
        # jax builds without the abstract-mesh API can't express the
        # pipe-manual nesting either — there is no stage context.
        return None
    am = get_am()
    manual = getattr(am, "manual_axes", ()) if am is not None else ()
    if not manual or AxisNames.MODEL in manual:
        return None
    m = dict(am.shape).get(AxisNames.MODEL, 1)
    if m > 1 and heads % m == 0:
        return AxisNames.MODEL
    return None


def mesh_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mesh: Mesh | None,
    causal: bool = True,
    sm_scale: float | None = None,
    impl: str = "flash",  # flash | xla | ring | ulysses
    key_bias: jax.Array | None = None,
) -> jax.Array:
    """Attention on [B, H, S, D] operands laid out on ``mesh``.

    With no mesh (or a trivial one) this is the plain single-device
    dispatcher; otherwise a shard_map over the canonical spec. ``ring`` /
    ``ulysses`` select the context-parallel algorithm when
    mesh.context > 1 (``flash`` defaults to ring in that case).

    ``key_bias`` ([B, S_kv] additive score bias — padding masks, the
    BERT path) routes through the flash kernel's bias variant under the
    decode-style spec: batch over the batch axes, heads over ``model``
    (each with replication fallback when the dim doesn't divide), seq
    replicated — so TP meshes shard heads WITHOUT gathering around the
    opaque Pallas call (ADVICE r3), and any mesh that doesn't fit
    simply replicates that dim (the ring/ulysses context algorithms
    carry no bias plumbing). Not supported with ``impl="xla"``.
    """
    from tensorflow_examples_tpu.ops.attention import flash_attention

    if key_bias is not None and impl == "xla":
        raise ValueError("key_bias requires the flash path (impl != 'xla')")
    if impl == "xla":
        return dot_product_attention(
            q, k, v, causal=causal, sm_scale=sm_scale, use_flash=False
        )
    if mesh is None or all(mesh.shape[a] == 1 for a in AxisNames.ALL):
        tp = _stage_tp_axis(q.shape[1])
        if tp is not None:
            # PP×TP stage: nest a model-only shard_map (the context
            # mesh already has `pipe` manual) so heads stay sharded
            # around the Pallas call. Proven exact fwd+bwd. No stage
            # caller passes key_bias today (only BERT does, and BERT
            # has no pipeline path) — keep that explicit rather than
            # shipping an unexercised bias-cotangent path.
            if key_bias is not None:
                raise NotImplementedError(
                    "key_bias inside a pipeline stage is unexercised; "
                    "add a test with the bias grad psum before enabling"
                )
            spec = P(None, tp, None, None)
            return _shard_map(
                lambda ql, kl, vl: flash_attention(
                    ql, kl, vl, causal=causal, sm_scale=sm_scale
                ),
                in_specs=(spec, spec, spec),
                out_specs=spec,
                axis_names={tp},
                check_vma=False,
            )(q, k, v)
        if key_bias is not None:
            return flash_attention(
                q, k, v, causal=causal, sm_scale=sm_scale, key_bias=key_bias
            )
        return dot_product_attention(q, k, v, causal=causal, sm_scale=sm_scale)

    has_context = mesh.shape[AxisNames.CONTEXT] > 1
    if key_bias is not None:
        # Divisibility-safe spec (replication fallback per dim), same
        # as the decode path — a non-dividing head count must not turn
        # a previously-working flash config into a trace error.
        spec = decode_spec(mesh, q.shape[0], q.shape[1])
        bias_spec = P(spec[0], None)
        out = _shard_map(
            lambda ql, kl, vl, bl: flash_attention(
                ql, kl, vl, causal=causal, sm_scale=sm_scale, key_bias=bl
            ),
            mesh=mesh,
            in_specs=(spec, spec, spec, bias_spec),
            out_specs=spec,
            check_vma=False,
        )(q, k, v, key_bias)
        return out
    if has_context and impl == "ulysses":
        local = functools.partial(
            ulysses_attention,
            axis_name=AxisNames.CONTEXT, causal=causal, sm_scale=sm_scale,
        )
    elif has_context:
        local = functools.partial(
            ring_attention,
            axis_name=AxisNames.CONTEXT, causal=causal, sm_scale=sm_scale,
        )
    else:
        local = functools.partial(
            dot_product_attention, causal=causal, sm_scale=sm_scale
        )
    # Causal context-parallel padding (the zigzag
    # odd-shard corner): pad the GLOBAL sequence so every shard is even
    # (zigzag always eligible, perfectly balanced) and every half-chunk
    # kernel-tileable. Tail pads sit at the causal future of every real
    # query — no real row ever attends a pad key, pad rows' outputs are
    # sliced off, and their grads are dropped by the slice transpose.
    # Only valid for causal attention (non-causal would softmax over
    # the pad keys), which is exactly where zigzag applies.
    seq = q.shape[2]
    pad = 0
    if has_context and causal and impl != "ulysses":
        c = mesh.shape[AxisNames.CONTEXT]
        target = -(-seq // (2 * c)) * (2 * c)  # next multiple of 2c
        # Kernel tileability: the zigzag path attends both single
        # half-chunks (length hc) and concatenated pairs (2·hc), so
        # each must either ride one block (≤ 256) or tile by 8
        # (2·hc % 8 == 0 ⟺ hc % 4 == 0).
        hc = target // (2 * c)
        while (hc > 256 and hc % 8) or (2 * hc > 256 and hc % 4):
            target += 2 * c
            hc = target // (2 * c)
        pad = target - seq
    if pad:
        widths = [(0, 0), (0, 0), (0, pad), (0, 0)]
        q, k, v = (jnp.pad(a, widths) for a in (q, k, v))
    spec = attention_spec(mesh)
    # check_vma=False: the Pallas kernel's out_shape carries no
    # varying-axes type, which the vma checker (jax 0.9) rejects.
    out = _shard_map(
        local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)
    return out[:, :, :seq] if pad else out
