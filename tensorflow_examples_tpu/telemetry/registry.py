"""Process-local metrics registry: counters, gauges, time-histograms.

The one place every runtime layer publishes its numbers into (ISSUE 2
tentpole (a)): the prefetch pipeline counts skipped poisoned batches,
``retry_io`` counts IO retries, the bad-step guard counts skipped/rolled
back steps, the checkpoint manager counts saves — and the ``Telemetry``
window writer (telemetry/hub.py) snapshots everything into each JSONL
line, so the PR 1 resilience events stop being write-only log text.

Design constraints, in order:

* **Cheap on the happy path.** An increment is a dict lookup (cached at
  the call site via the returned instrument handle) + one locked int
  add. No per-element work, no allocation.
* **Thread-safe.** Instruments are hit from the training loop, the
  prefetch generator, and the watchdog thread.
* **Cumulative.** Counters are monotonic for the life of the process.
  The ``Telemetry`` hub (hub.py) snapshots them at fit start and emits
  per-fit DELTAS, so each emitted session is self-contained; within a
  session consumers diff windows for rates, and a torn/partial final
  window is harmless — the previous line still carries a consistent
  prefix of the run.

A module-level default registry mirrors ``logging``'s root-logger
pattern: library code (data/prefetch.py, utils/faults.py, …) publishes
into ``default_registry()`` without plumbing a handle through every
call; the trainer's ``Telemetry`` drains the same instance. Tests use
``reset_default_registry()`` for isolation.
"""

from __future__ import annotations

import collections
import math
import threading
from typing import Mapping


class Counter:
    """Monotonic cumulative counter."""

    __slots__ = ("name", "_value", "_lock")

    def __init__(self, name: str):
        self.name = name
        self._value = 0  # guard: self._lock
        self._lock = threading.Lock()

    def inc(self, n: int = 1) -> None:
        if n < 0:
            raise ValueError(f"counter {self.name}: inc({n}) must be >= 0")
        with self._lock:
            self._value += n

    @property
    def value(self) -> int:
        return self._value  # graftlint: ignore — atomic int load; a
        # snapshot read concurrent with inc() sees either value, both
        # consistent (monotonic counter)


class Gauge:
    """Last-write-wins instantaneous value. Lockless BY DESIGN: a
    gauge store is a single reference assignment (atomic under the
    GIL) and concurrent setters racing is the semantics, not a bug —
    the graftlint ignores below record that decision where the
    ``_value`` annotation on Counter/TimeHistogram would otherwise
    flag these same-named accesses."""

    __slots__ = ("name", "_value")

    def __init__(self, name: str):
        self.name = name
        self._value: float | None = None  # graftlint: ignore — lockless by design

    def set(self, v: float) -> None:
        self._value = float(v)  # graftlint: ignore — atomic ref store

    @property
    def value(self) -> float | None:
        return self._value  # graftlint: ignore — atomic ref load


def _nearest_rank(sorted_samples: list[float], q: float) -> float | None:
    """Nearest-rank percentile (q in [0, 100]) over pre-sorted samples."""
    if not sorted_samples:
        return None
    rank = max(int(math.ceil(q / 100.0 * len(sorted_samples))) - 1, 0)
    return sorted_samples[min(rank, len(sorted_samples) - 1)]


class TimeHistogram:
    """Duration distribution: running count/sum/min/max plus a bounded
    sample window for percentiles.

    Exact aggregates are kept for the whole run; percentiles are
    computed over the most recent ``max_samples`` observations (a
    training run's step-time distribution is what you want *recently*,
    and an unbounded sample list would grow without limit on a
    multi-week run).
    """

    __slots__ = ("name", "count", "total", "min", "max", "_samples", "_lock")

    def __init__(self, name: str, *, max_samples: int = 8192):
        self.name = name
        self.count = 0     # guard: self._lock
        self.total = 0.0   # guard: self._lock
        self.min = math.inf   # guard: self._lock
        self.max = -math.inf  # guard: self._lock
        self._samples: collections.deque = collections.deque(  # guard: self._lock
            maxlen=max_samples
        )
        self._lock = threading.Lock()

    def record(self, seconds: float) -> None:
        s = float(seconds)
        with self._lock:
            self.count += 1
            self.total += s
            self.min = min(self.min, s)
            self.max = max(self.max, s)
            self._samples.append(s)

    def samples_since(self, mark: int) -> list[float]:
        """The samples recorded since ``count`` read ``mark``, oldest
        first — a measurement window reads ``count`` when it opens and
        this when it closes. What the bounded sample window has already
        dropped is not returned (size the histogram for the window:
        ``max_samples``)."""
        with self._lock:
            fresh = self.count - int(mark)
            if fresh < 0:
                raise ValueError(
                    f"mark {mark} is ahead of count {self.count} "
                    f"of histogram {self.name!r}"
                )
            kept = list(self._samples)
        return kept[len(kept) - min(fresh, len(kept)):]

    def percentile(self, q: float) -> float | None:
        """Nearest-rank percentile (q in [0, 100]) over the sample window."""
        with self._lock:
            samples = sorted(self._samples)
        return _nearest_rank(samples, q)

    def percentiles(self, *qs: float) -> tuple[float | None, ...]:
        """Several percentiles in ONE lock acquisition + sort (the fleet
        vector and the /metrics endpoint read p50+p95 together every
        window — don't pay the sort twice)."""
        with self._lock:
            samples = sorted(self._samples)
        return tuple(_nearest_rank(samples, q) for q in qs)

    def summary(self) -> dict:
        with self._lock:
            n, total = self.count, self.total
            lo = self.min if n else None
            hi = self.max if n else None
            samples = sorted(self._samples)
        return {
            "count": n,
            "total": total,
            "mean": (total / n) if n else None,
            "min": lo,
            "max": hi,
            "p50": _nearest_rank(samples, 50),
            "p95": _nearest_rank(samples, 95),
            "p99": _nearest_rank(samples, 99),
        }


class MetricsRegistry:
    """Namespace of instruments; get-or-create by name, snapshot as dicts."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: dict[str, Counter] = {}  # guard: self._lock
        self._gauges: dict[str, Gauge] = {}      # guard: self._lock
        self._histograms: dict[str, TimeHistogram] = {}  # guard: self._lock

    def counter(self, name: str) -> Counter:
        with self._lock:
            c = self._counters.get(name)
            if c is None:
                c = self._counters[name] = Counter(name)
            return c

    def gauge(self, name: str) -> Gauge:
        with self._lock:
            g = self._gauges.get(name)
            if g is None:
                g = self._gauges[name] = Gauge(name)
            return g

    def histogram(self, name: str, **kw) -> TimeHistogram:
        with self._lock:
            h = self._histograms.get(name)
            if h is None:
                h = self._histograms[name] = TimeHistogram(name, **kw)
            return h

    # ----------------------------------------------------------- snapshots

    def counter_values(self) -> dict[str, int]:
        with self._lock:
            counters = list(self._counters.values())
        return {c.name: c.value for c in counters}

    def gauge_values(self) -> dict[str, float]:
        with self._lock:
            gauges = list(self._gauges.values())
        return {g.name: g.value for g in gauges if g.value is not None}

    def histogram_summaries(self) -> dict[str, dict]:
        with self._lock:
            hists = list(self._histograms.values())
        return {h.name: h.summary() for h in hists}

    def snapshot(self) -> dict:
        return {
            "counters": self.counter_values(),
            "gauges": self.gauge_values(),
            "histograms": self.histogram_summaries(),
        }

    def merge_counter_values(self, values: Mapping[str, int]) -> None:
        """Fold an external counter snapshot into this registry —
        offline aggregation (e.g. combining per-session or per-host
        snapshots in analysis code). The in-loop cross-host reduction
        (Telemetry._reduced_counters) is collective-based and does not
        go through here."""
        for name, v in values.items():
            self.counter(name).inc(int(v))


_default: MetricsRegistry = MetricsRegistry()


def default_registry() -> MetricsRegistry:
    """The process-wide registry library code publishes into."""
    return _default


def reset_default_registry() -> MetricsRegistry:
    """Fresh default registry (test isolation); returns the new one."""
    global _default
    _default = MetricsRegistry()
    return _default
