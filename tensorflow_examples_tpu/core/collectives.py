"""Thin collective façade for ``shard_map`` code (SURVEY.md §5h).

The reference stack's communication backend was NCCL under
``tf.distribute`` cross-device ops; on TPU there is no user-space
transport to write — collectives are XLA HLO ops routed over ICI within
a slice and DCN across slices by the compiler. This module is the
framework's single naming point for them: ``shard_map`` code imports
from here, so grepping call sites answers "what does this program put on
the interconnect", and the bandwidth microbenchmark (``bench.py
--bench=collectives``, the NCCL-perf-test replacement) measures exactly
these ops.

All functions are ``jax.lax`` passthroughs with the framework's axis
conventions documented; they are valid only inside ``shard_map`` (or
``pmap``) over a mesh axis.
"""

from __future__ import annotations

from typing import Any, Callable, Sequence

import jax
from jax import lax

AxisName = str | Sequence[str]


def shard_map(
    f: Callable,
    *,
    mesh=None,
    in_specs,
    out_specs,
    axis_names: set | None = None,
    check_vma: bool = True,
):
    """``jax.shard_map`` with the framework's defaults (its single
    spelling): ``mesh``/``axis_names`` passed only when given, so the
    meshless form resolves its manual axes from the enclosing
    shard_map. ``check_vma`` defaults to True to match ``jax.shard_map``
    — callers that need it off (every Pallas-opaque site today) say so.
    """
    kw: dict = {"check_vma": check_vma}
    if mesh is not None:
        kw["mesh"] = mesh
    if axis_names is not None:
        kw["axis_names"] = set(axis_names)
    return jax.shard_map(f, in_specs=in_specs, out_specs=out_specs, **kw)


def psum(x: Any, axis: AxisName) -> Any:
    """All-reduce sum over a mesh axis (the DP gradient reduction;
    bidirectional-ring bandwidth 2(n-1)/n · payload over ICI)."""
    return lax.psum(x, axis)


def pmean(x: Any, axis: AxisName) -> Any:
    """All-reduce mean — metric aggregation across data shards."""
    return lax.pmean(x, axis)


def pmax(x: Any, axis: AxisName) -> Any:
    """All-reduce max — e.g. the global row max in vocab-parallel CE."""
    return lax.pmax(x, axis)


def all_gather(x: Any, axis: AxisName, *, axis_index_groups=None, tiled=True):
    """Gather shards along the axis ((n-1)/n · result bytes on the wire).
    ``tiled=True`` concatenates along dim 0 (the FSDP parameter
    un-shard); ``tiled=False`` stacks a new leading dim."""
    return lax.all_gather(
        x, axis, axis_index_groups=axis_index_groups, tiled=tiled
    )


def reduce_scatter(x: Any, axis: AxisName, *, scatter_dimension=0):
    """Sum-reduce then scatter shards — the ZeRO gradient primitive;
    half an all-reduce's traffic when each rank only needs its shard."""
    return lax.psum_scatter(
        x, axis, scatter_dimension=scatter_dimension, tiled=True
    )


def ppermute(x: Any, axis: AxisName, perm: Sequence[tuple[int, int]]):
    """Point-to-point permutation. With ``ring_perm`` this is the
    nearest-neighbor ICI hop ring attention and GPipe are built on."""
    return lax.ppermute(x, axis, perm)


def ring_perm(axis_size: int) -> list[tuple[int, int]]:
    """The (i → i+1 mod n) permutation: one ring hop."""
    return [(i, (i + 1) % axis_size) for i in range(axis_size)]


def all_to_all(
    x: Any, axis: AxisName, *, split_axis: int, concat_axis: int, tiled=True
):
    """Transpose shards across the axis — resharding one array dimension
    for another (Ulysses sequence↔heads, MoE token↔expert exchanges)."""
    return lax.all_to_all(
        x, axis, split_axis=split_axis, concat_axis=concat_axis, tiled=tiled
    )


def axis_index(axis: AxisName) -> jax.Array:
    """This device's coordinate along the mesh axis."""
    return lax.axis_index(axis)


def axis_size(axis: AxisName) -> int:
    """Number of shards along the mesh axis. ``lax.axis_size`` where
    the jax build has it; ``psum(1, axis)`` — which jax constant-folds
    to the static size — on older builds."""
    if hasattr(lax, "axis_size"):
        return lax.axis_size(axis)
    return lax.psum(1, axis)
