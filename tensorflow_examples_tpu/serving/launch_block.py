"""The one operand block of an engine launch.

Every host->device transfer costs the serve thread a fixed slice of a
millisecond in the runtime, whatever its size (0.27 ms each on the
v5e, PERF.md), and a launch's small operands — tokens, positions,
block tables, seeds, temperatures — used to be six to ten of them. So
a launch packs them into ONE ``int32`` vector on the host, moves it
with ONE ``jax.device_put``, and the compiled program takes it apart
again: static slices, reshapes and bitcasts, free on the device.

A spec drives both sides. It is a sequence of :class:`Field` — name,
shape, 4-byte dtype — in the order the program's step function reads
its operands; a field whose ``shape`` is a LIST of shapes is one array
per kind of the paged pool (a block table per block-id space) and
travels as a list. The engine derives each program's spec from what it
can observe (``InferenceEngine._launch_spec``: the program's kind, its
rung, ``max_slots``, the pool's kinds), nothing a user sets.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import numpy as np

# A field is carried as its 32 bits: float32 is viewed as int32 on the
# host and bitcast back in the trace, never converted.
_DTYPES = {"int32": np.int32, "float32": np.float32}


class Field(NamedTuple):
    name: str
    shape: tuple | list  # a list: one shape per kind of the pool
    dtype: str = "int32"


def _parts(field: Field, value=None):
    """(shape, value) of each array the field holds."""
    if isinstance(field.shape, list):
        values = [None] * len(field.shape) if value is None else value
        return zip(field.shape, values, strict=True)
    return ((field.shape, value),)


def size(spec) -> int:
    """Elements of the block a spec packs into."""
    return sum(
        math.prod(shape) for field in spec for shape, _ in _parts(field)
    )


def zeros(spec, **named) -> list:
    """Values for ``pack``: zeros of every field's shape (the warm-up
    launches' operands), with the scalar fields in ``named`` set."""
    def zero(field):
        made = [np.zeros(s, _DTYPES[field.dtype]) for s, _ in _parts(field)]
        return made if isinstance(field.shape, list) else made[0]

    return [named[f.name] if f.name in named else zero(f) for f in spec]


def pack(spec, values) -> np.ndarray:
    """``values`` (one per field, in the spec's order) as one fresh
    ``int32`` vector. Fresh matters: ``device_put`` may alias host
    memory on the CPU backend, so a block is never written again."""
    flat = []
    for field, value in zip(spec, values, strict=True):
        for shape, part in _parts(field, value):
            arr = np.asarray(part, _DTYPES[field.dtype])
            if arr.shape != tuple(shape):
                raise ValueError(
                    f"launch operand {field.name!r} has shape {arr.shape}; "
                    f"its program reads {tuple(shape)}"
                )
            flat.append(arr.reshape(-1).view(np.int32))
    return np.concatenate(flat)


def unpack(spec, block) -> list:
    """The fields of ``block`` inside a trace, in the spec's order: a
    per-kind field as a list of arrays."""
    n_block = size(spec)
    if block.shape != (n_block,) or block.dtype != np.int32:
        raise ValueError(
            f"launch block is {block.dtype}{list(block.shape)}; the spec "
            f"packs int32[{n_block}]"
        )
    at = 0

    def take(shape, dtype):
        nonlocal at
        n = math.prod(shape)
        x = block[at:at + n].reshape(shape)
        at += n
        if dtype == "int32":
            return x
        return jax.lax.bitcast_convert_type(x, _DTYPES[dtype])

    return [
        [take(s, f.dtype) for s in f.shape] if isinstance(f.shape, list)
        else take(f.shape, f.dtype)
        for f in spec
    ]
