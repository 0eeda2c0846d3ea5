"""Recompilation sentinel (ISSUE 3 tentpole (1)).

A silent recompilation is the classic "mysteriously slow run": a shape
or dtype that drifts mid-run (ragged final batch, a resumed run with a
different bundle size, a config knob that changes an aval) makes XLA
retrace + recompile the step — seconds to minutes of dead time that
shows up nowhere except a step-time spike. The repo's own history
(round-4 sub-floor bench readings, diagnosed only by the out-of-band
``tools/hlo_fingerprint.py``) is the motivating incident.

``CompilationSentinel`` wraps each jitted step function the trainer
builds (train step, bundled train step per K, eval step) and tracks the
**abstract input signature** — the ``(path, shape, dtype)`` tuple of
every array leaf — of each call:

* a call with a NEW signature is a compilation: its host wall time is
  bracketed by a ``compile`` span (Chrome trace + ``span/compile``
  histogram) and counted in ``compile/count``;
* after a configurable warmup (``TrainConfig.compile_warmup`` expected
  compilations per wrapped function — 1 covers the normal one-compile
  life of a step), any further compile is a **recompile**: counted in
  ``compile/recompiles``, logged at WARNING with the exact shape/dtype
  delta vs. the previous signature (down to the changed axis), and —
  when a ``Telemetry`` object is bound — emitted as a
  ``kind="compile_warning"`` schema-v2 JSONL line so the run record
  carries the evidence.

The wrapper forwards attribute access to the underlying jitted
callable, so AOT consumers (``trainer._train_step.lower(...)`` in
bench.py and the diagnostics tools) are unaffected.

Signature tracking is host-side bookkeeping only (one pytree flatten of
the already-on-host arg structure per *launch*, amortized by
``steps_per_launch``); it cannot see cache evictions or persistent-
cache hits, but every aval-driven retrace — the failure mode that
matters — is exactly a new signature.
"""

from __future__ import annotations

import logging
import time
from typing import Any, Callable

from tensorflow_examples_tpu.telemetry import registry as registry_mod
from tensorflow_examples_tpu.telemetry import spans as spans_mod

log = logging.getLogger(__name__)

# Cap the delta text: a giant param tree diff must not balloon the JSONL
# line (the first few entries name the culprit; the rest repeat it).
_MAX_DELTA_CHARS = 600
_MAX_DELTA_LEAVES = 8


def fast_signature(args: tuple, kwargs: dict) -> tuple:
    """The cheap per-launch aval fingerprint: (treedef, ((shape, dtype),
    ...)). No per-leaf string formatting — this runs on EVERY launch,
    including inside bench.py's timed loops, so it must stay a plain
    flatten plus tuple build. PyTreeDefs are hashable, and a differing
    tuple is exactly the condition under which jit retraces (modulo
    weak types, which step inputs don't carry)."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    return (
        treedef,
        tuple(
            (getattr(leaf, "shape", ()), getattr(leaf, "dtype", None))
            for leaf in leaves
        ),
    )


def abstract_signature(args: tuple, kwargs: dict) -> tuple:
    """The path-annotated aval signature: (path, shape, dtype) per leaf.
    Costs a keystr per leaf, so it is computed only when a NEW
    ``fast_signature`` appears and a human-readable delta is needed."""
    import jax
    import numpy as np

    leaves = jax.tree_util.tree_flatten_with_path((args, kwargs))[0]
    out = []
    for path, leaf in leaves:
        shape = getattr(leaf, "shape", None)
        if shape is None:
            shape = np.shape(leaf)
        dtype = getattr(leaf, "dtype", None)
        out.append(
            (
                jax.tree_util.keystr(path),
                tuple(int(d) for d in shape),
                str(dtype) if dtype is not None else type(leaf).__name__,
            )
        )
    return tuple(out)


def describe_delta(old: tuple | None, new: tuple) -> str:
    """Human-readable shape/dtype diff between two signatures, naming
    the changed axis — the line an operator reads to find the ragged
    batch."""
    if old is None:
        return "first compilation"
    old_map = {p: (s, d) for p, s, d in old}
    new_map = {p: (s, d) for p, s, d in new}
    parts: list[str] = []
    for path, (shape, dtype) in new_map.items():
        prev = old_map.get(path)
        if prev is None:
            parts.append(f"{path}: new input {shape} {dtype}")
            continue
        pshape, pdtype = prev
        if shape != pshape:
            if len(shape) == len(pshape):
                axes = ", ".join(
                    f"axis {i}: {pshape[i]}->{shape[i]}"
                    for i in range(len(shape))
                    if shape[i] != pshape[i]
                )
            else:
                axes = f"rank {len(pshape)}->{len(shape)}"
            parts.append(f"{path}: shape {pshape}->{shape} ({axes})")
        if dtype != pdtype:
            parts.append(f"{path}: dtype {pdtype}->{dtype}")
    for path in old_map.keys() - new_map.keys():
        parts.append(f"{path}: input removed")
    if not parts:
        # Same avals but a new tuple can only mean structure-level drift
        # (ordering); name it rather than emitting an empty delta.
        return "input tree structure changed (identical leaf avals)"
    shown = parts[:_MAX_DELTA_LEAVES]
    if len(parts) > len(shown):
        shown.append(f"... and {len(parts) - len(shown)} more leaves")
    return "; ".join(shown)[:_MAX_DELTA_CHARS]


class _FnRecord:
    __slots__ = ("name", "seen", "last_sig", "compiles")

    def __init__(self, name: str):
        self.name = name
        self.seen: set = set()
        self.last_sig: tuple | None = None
        self.compiles = 0


class SentinelWrapped:
    """A jitted callable under sentinel observation. Transparent:
    ``__getattr__`` forwards ``lower`` / ``trace`` / anything else to
    the wrapped function."""

    def __init__(self, sentinel: "CompilationSentinel", fn: Callable,
                 name: str):
        self._sentinel = sentinel
        self._fn = fn
        self._name = name

    def __call__(self, *args, **kwargs):
        return self._sentinel._observed_call(
            self._fn, self._name, args, kwargs
        )

    def __getattr__(self, item):
        return getattr(self._fn, item)

    def __repr__(self):  # pragma: no cover - debugging nicety
        return f"SentinelWrapped({self._name}, {self._fn!r})"


class CompilationSentinel:
    """Per-Trainer compile observer; ``bind`` a per-fit Telemetry to
    turn post-warmup recompiles into JSONL warning lines."""

    def __init__(self, *, warmup: int = 1, registry=None, tracer=None):
        self.warmup = max(int(warmup), 0)
        self._registry = registry
        self._tracer = tracer
        self._fns: dict[str, _FnRecord] = {}
        self.events: list[dict] = []  # every compile event, introspectable
        self.step: int = 0  # maintained by the loop: labels warning lines
        self.on_recompile: Callable[[dict], None] | None = None

    @classmethod
    def from_config(cls, cfg) -> "CompilationSentinel":
        return cls(warmup=int(getattr(cfg, "compile_warmup", 1) or 0))

    # ------------------------------------------------------------ wiring

    def wrap(self, fn: Callable | None, name: str):
        """Wrap a jitted callable; None passes through (eval-less tasks)."""
        if fn is None:
            return None
        self._fns.setdefault(name, _FnRecord(name))
        return SentinelWrapped(self, fn, name)

    def bind(self, telemetry) -> None:
        """Route post-warmup recompile events into a fit's Telemetry
        (which emits the ``compile_warning`` JSONL line)."""
        self.on_recompile = telemetry.compile_warning

    def unbind(self) -> None:
        self.on_recompile = None

    # ----------------------------------------------------------- observe

    def _reg(self):
        return (
            self._registry
            if self._registry is not None
            else registry_mod.default_registry()
        )

    def _span(self, name: str, **args):
        tracer = (
            self._tracer
            if self._tracer is not None
            else spans_mod.default_tracer()
        )
        return tracer.span(name, **args)

    def _observed_call(self, fn, name, args, kwargs):
        rec = self._fns.setdefault(name, _FnRecord(name))
        sig = fast_signature(args, kwargs)
        if sig in rec.seen:
            return fn(*args, **kwargs)
        # New signature: this call pays trace + compile. Host wall time
        # around the (synchronous-until-compiled) dispatch is the
        # compile cost an operator experiences. The path-annotated
        # signature (keystr per leaf) is only computed here, off the
        # per-launch hot path.
        path_sig = abstract_signature(args, kwargs)
        t0 = time.perf_counter()
        with self._span("compile", fn=name):
            out = fn(*args, **kwargs)
        wall = time.perf_counter() - t0
        delta = describe_delta(rec.last_sig, path_sig)
        rec.seen.add(sig)
        rec.last_sig = path_sig
        rec.compiles += 1
        reg = self._reg()
        reg.counter("compile/count").inc()
        reg.gauge("compile/last_wall_secs").set(wall)
        event = {
            "fn": name,
            "count": rec.compiles,
            "wall_secs": round(wall, 6),
            "delta": delta,
        }
        self.events.append(event)
        if rec.compiles > self.warmup:
            reg.counter("compile/recompiles").inc()
            log.warning(
                "RECOMPILATION of %s at step %d (compile #%d for this fn, "
                "%.2fs): %s",
                name, self.step, rec.compiles, wall, delta,
            )
            if self.on_recompile is not None:
                try:
                    self.on_recompile(dict(event, step=self.step))
                except Exception:  # pragma: no cover - telemetry best effort
                    log.exception("recompile warning emission failed")
        else:
            log.info(
                "compiled %s (#%d, %.2fs): %s", name, rec.compiles, wall,
                delta,
            )
        return out

    # ----------------------------------------------------------- inspect

    def compile_counts(self) -> dict[str, int]:
        return {name: r.compiles for name, r in self._fns.items()}

    def post_warmup_recompiles(self) -> int:
        """Total compiles beyond each wrapped fn's warmup allowance —
        the number CI asserts to be 0 in steady state (the serving
        engine's zero-recompile contract, and the sharded-training
        smoke in tests/test_sharding.py)."""
        return sum(
            max(0, r.compiles - self.warmup) for r in self._fns.values()
        )
