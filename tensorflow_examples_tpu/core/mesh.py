"""Device mesh construction and axis conventions.

The reference's distribution layer was per-example ``tf.distribute``
strategies (MirroredStrategy for single-host DP, MultiWorkerMirroredStrategy
for BERT's multi-host DP; BASELINE.json:north_star). TPU-native, all of that
collapses into ONE concept: a ``jax.sharding.Mesh`` with named axes. Data
parallelism is "shard the batch over the ``data`` axis"; tensor parallelism
is "shard weight matrices over ``model``"; sequence/context parallelism is
"shard the sequence over ``context``". XLA emits psum/all-gather/ppermute
over ICI for whatever sharding is requested — there is no user-space NCCL
equivalent to manage.

Axis conventions (used by every model and sharding rule in the framework):

- ``data``    — pure data parallelism (batch dim). Gradients are all-reduced
                over this axis by XLA when params are replicated across it.
- ``fsdp``    — batch AND parameter sharding (ZeRO-3 style). Params are
                sharded over this axis and all-gathered just-in-time.
- ``model``   — tensor parallelism (hidden/heads dims).
- ``context`` — sequence/context parallelism (ring attention).
- ``pipe``    — pipeline parallelism (layer stages, GPipe microbatching).

A single-chip run is simply a 1×1×1×1 mesh; code written against the mesh
runs unchanged from 1 chip to a multi-host slice.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import jax
from jax.experimental import mesh_utils
from jax.sharding import Mesh


class AxisNames:
    """Canonical mesh axis names."""

    DATA = "data"
    FSDP = "fsdp"
    MODEL = "model"
    CONTEXT = "context"
    PIPE = "pipe"

    ALL = (DATA, FSDP, MODEL, CONTEXT, PIPE)

    # The batch dimension of activations is sharded over every
    # batch-like axis.
    BATCH_AXES = (DATA, FSDP)


def token_partition_axes(
    mesh,
    batch_dim: int,
    seq_dim: int | None = None,
    *,
    include_model: bool = False,
) -> tuple[tuple, tuple]:
    """Shared axis-dropping policy for token-parallel shard_maps.

    Returns ``(batch_axes, seq_axes)`` for partitioning a ``[B, S, ...]``
    activation over the mesh: every nontrivial batch-like axis shards
    the batch dim (ALL dropped if their product doesn't divide it —
    jit in_specs must divide exactly, and decode-time batch=1 is the
    common non-dividing case), ``context`` shards the seq dim when it
    divides, and — when ``include_model`` — ``model`` joins the seq
    sharding if it also divides (token-independent ops like CE are
    replicated work under TP otherwise). Consumers: ``parallel/moe.py``
    (batch policy), ``ops/cross_entropy.py`` (batch + seq + model).
    Axes dropped here mean the tokens REPLICATE over that axis, which
    is always correct, just less parallel.
    """

    batch_axes = tuple(a for a in AxisNames.BATCH_AXES if mesh.shape[a] > 1)
    nb = math.prod(mesh.shape[a] for a in batch_axes) if batch_axes else 1
    if batch_dim % nb:
        batch_axes = ()
    seq_axes: tuple = ()
    if seq_dim is not None:
        c = mesh.shape[AxisNames.CONTEXT]
        if c > 1 and seq_dim % c == 0:
            seq_axes += (AxisNames.CONTEXT,)
        if include_model:
            m = mesh.shape[AxisNames.MODEL]
            denom = (c if seq_axes else 1) * m
            if m > 1 and seq_dim % denom == 0:
                seq_axes += (AxisNames.MODEL,)
    return batch_axes, seq_axes


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Logical mesh shape. -1 for ``data`` means "all remaining devices"."""

    data: int = -1
    fsdp: int = 1
    model: int = 1
    context: int = 1
    pipe: int = 1

    def resolve(self, n_devices: int) -> tuple[int, int, int, int, int]:
        fixed = self.fsdp * self.model * self.context * self.pipe
        data = self.data
        if data == -1:
            if n_devices % fixed != 0:
                raise ValueError(
                    f"{n_devices} devices not divisible by "
                    f"fsdp*model*context*pipe={fixed}"
                )
            data = n_devices // fixed
        total = data * fixed
        if total != n_devices:
            raise ValueError(
                f"mesh {data}x{self.fsdp}x{self.model}x{self.context}"
                f"x{self.pipe}={total} != available devices {n_devices}"
            )
        return (data, self.fsdp, self.model, self.context, self.pipe)


def create_mesh(
    config: MeshConfig | None = None,
    *,
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """Build the framework-standard 5-axis mesh.

    ``mesh_utils.create_device_mesh`` lays the mesh out along the
    physical ICI topology (keeps the fastest-varying logical axis on the
    torus); on CPU / single chip it is a plain reshape. A shape it
    cannot place on the real chips is an error — never a quiet,
    topology-blind reshape.
    """
    if devices is None:
        devices = jax.devices()
    config = config or MeshConfig()
    shape = config.resolve(len(devices))
    device_array = mesh_utils.create_device_mesh(shape, devices=list(devices))
    return Mesh(device_array, axis_names=AxisNames.ALL)


def local_batch_size(global_batch_size: int, mesh: Mesh) -> int:
    """Per-host batch size for input pipelines (tf.data ``shard()`` analogue).

    The reference sharded input per worker via
    ``dataset.shard(num_workers, index)`` inside
    MultiWorkerMirroredStrategy (SURVEY.md §3(5)); here each host feeds the
    slice of the global batch that lands on its addressable devices.
    """
    n_batch = math.prod(mesh.shape[a] for a in AxisNames.BATCH_AXES)
    if global_batch_size % n_batch:
        raise ValueError(
            f"global batch {global_batch_size} not divisible by batch mesh size {n_batch}"
        )
    per_shard = global_batch_size // n_batch
    local_shards = max(1, n_batch // jax.process_count())
    return per_shard * local_shards
