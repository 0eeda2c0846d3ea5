"""What a runner hands back, and what the metric readers read."""

from __future__ import annotations

import dataclasses
import time
from typing import Any

COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


@dataclasses.dataclass
class Context:
    """One invocation: the cell and the command line."""

    cell: Any                 # spec.Cell
    seed: int
    seconds: float
    trace: bool
    t_start: float            # perf_counter at process start
    trace_dir: str
    compiles: "CompileLog"
    rates: list | None = None  # --rates: sweep an open-loop cell for its knee


@dataclasses.dataclass
class Run:
    """One measured window. Readers take what they need and return
    ``None`` for what is not there."""

    cell: Any
    setup_s: float = 0.0
    warmup_s: float = 0.0
    window_s: float = 0.0
    attempted: int = 0
    failed: int = 0
    correct: bool = False
    correct_detail: dict = dataclasses.field(default_factory=dict)
    requests: list = dataclasses.field(default_factory=list)     # traffic_gen records
    counters: dict = dataclasses.field(default_factory=dict)     # deltas over the window
    hists: dict = dataclasses.field(default_factory=dict)        # name -> window samples, s
    train: dict = dataclasses.field(default_factory=dict)        # steps, tokens_per_step, ...
    model: dict = dataclasses.field(default_factory=dict)        # n_params, itemsizes, slots
    trace: Any = None                                            # trace_reduce.TraceSummary
    peaks: Any = None                                            # peaks.Peaks
    compiles_in_window: list = dataclasses.field(default_factory=list)
    notes: dict = dataclasses.field(default_factory=dict)        # printed on an earlier line


class CompileLog:
    """Every trace-and-lower JAX makes, by function name, on JAX's own
    monitoring hook: a real count of compilations, persistent-cache hit
    or not (``CompilationSentinel`` counts shape signatures and missed
    PR 21's placement recompile). JAX's listener list is process-wide
    and has no public way out of it, so there is one log a process:
    ``CompileLog.get()``."""

    _instance: "CompileLog | None" = None

    def __init__(self):
        self.events: list[tuple[float, str]] = []

    @classmethod
    def get(cls) -> "CompileLog":
        if cls._instance is None:
            import jax.monitoring

            cls._instance = cls()
            jax.monitoring.register_event_duration_secs_listener(cls._instance._on_event)
        return cls._instance

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if event == COMPILE_EVENT:
            self.events.append((time.perf_counter(), str(kw.get("fun_name", "?"))))

    def between(self, t_a: float, t_b: float) -> list[str]:
        return [name for t, name in self.events if t_a <= t <= t_b]


def percentile(values: list[float], q: float) -> float | None:
    """Nearest-rank percentile, q in [0, 100]."""
    if not values:
        return None
    ordered = sorted(values)
    rank = max(1, -(-len(ordered) * q // 100))
    return ordered[int(rank) - 1]
