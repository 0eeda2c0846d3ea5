"""Span tracer: a per-step host-side timeline, exportable as Chrome trace.

``with span("data_fetch"): ...`` brackets each training-loop phase (host
batch fetch, device step dispatch, metric flush, eval, checkpoint
save/restore — wired in train/loop.py and train/checkpoint.py). Each
completed span becomes

* a **trace event** in a bounded in-memory buffer, exported as
  Chrome-trace/Perfetto JSON (``chrome://tracing`` / ui.perfetto.dev
  "complete" events, phase ``"X"``) by ``Telemetry.close()``;
* a **duration sample** in the registry time-histogram
  ``span/<name>`` — which is where the run report's per-phase time
  breakdown and the step-time percentiles come from; and
* while a ``jax.profiler`` session is open, an event ``span/<name>``
  (with the span's ``args``) on the calling thread's line of the
  profiler's own trace — the same clock as the device's events, so a
  reduction can lay the host's spans over the device's idle gaps
  (``benchmark/host_spans.py``). With no session open the annotation
  costs a flag read.

The open-span bookkeeping is keyed by thread id and readable from OTHER
threads: the watchdog's hang dump (utils/diagnostics.py) calls
``active_span_names()`` so a stall report says "stuck inside
``data_fetch``", not just the loop's coarse phase marker.

Host-side only by design: device-internal timing belongs to the XLA
profiler (``cfg.profile``); these spans answer the cheaper, always-on
question "where did the *host* loop's wall time go".
"""

from __future__ import annotations

import contextlib
import functools
import json
import threading
import time
from typing import Callable

# Chrome-trace buffer bound: ~100k events ≈ a few MB of JSON — plenty
# for any smoke/diagnostic run; a multi-day run keeps the FIRST N events
# (startup + steady state onset, the diagnostically interesting part)
# and counts the rest as dropped.
MAX_EVENTS = 100_000


@functools.cache
def _trace_annotation():
    """``jax.profiler.TraceAnnotation``, imported at the first span: the
    telemetry package itself imports without JAX (the report tools read
    run records with it)."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation


class Tracer:
    def __init__(
        self,
        registry=None,
        *,
        max_events: int = MAX_EVENTS,
        now_ns: Callable[[], int] | None = None,
    ):
        # None = resolve default_registry() per record, so a tracer made
        # before reset_default_registry() still lands in the live one.
        self._registry = registry
        self._now_ns = now_ns if now_ns is not None else time.perf_counter_ns
        self._epoch_ns = self._now_ns()
        self._max_events = max_events
        self._lock = threading.Lock()
        self._events: list[dict] = []
        self.dropped = 0
        # thread id -> stack of open span names (read cross-thread by the
        # watchdog; mutated only by the owning thread, under the lock).
        self._open: dict[int, list[str]] = {}

    # ------------------------------------------------------------- record

    @contextlib.contextmanager
    def span(self, name: str, **args):
        """Yields a dict: what the body puts there joins ``args`` when
        the span closes (a number known only at the end, such as the
        blocks a claim reused)."""
        tid = threading.get_ident()
        label = f"span/{name}"
        late: dict = {}
        # The annotation brackets the bookkeeping below too, so back-to-back
        # spans leave no hole between them on the profiler's timeline.
        with _trace_annotation()(label, **args) as annotation:
            t0 = self._now_ns()
            with self._lock:
                self._open.setdefault(tid, []).append(name)
            try:
                yield late
            finally:
                t1 = self._now_ns()
                if late:
                    annotation.set_metadata(**late)
                    args = {**args, **late}
                with self._lock:
                    stack = self._open.get(tid)
                    if stack and stack[-1] == name:
                        stack.pop()
                    if len(self._events) < self._max_events:
                        ev = {
                            "name": name,
                            "ph": "X",
                            "ts": (t0 - self._epoch_ns) / 1e3,  # µs
                            "dur": (t1 - t0) / 1e3,
                            "pid": 0,
                            "tid": tid,
                        }
                        if args:
                            ev["args"] = args
                        self._events.append(ev)
                    else:
                        self.dropped += 1
                reg = self._registry
                if reg is None:
                    from tensorflow_examples_tpu.telemetry import (
                        registry as _reg,
                    )

                    reg = _reg.default_registry()
                reg.histogram(label).record((t1 - t0) / 1e9)

    # ------------------------------------------------------------ inspect

    def active_span_names(self) -> list[str]:
        """Innermost open span of every thread that has one (the watchdog
        reads this from its own thread while the loop thread is stuck)."""
        with self._lock:
            return [stack[-1] for stack in self._open.values() if stack]

    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    # ------------------------------------------------------------- export

    def chrome_trace(self) -> dict:
        """The Chrome-trace JSON object (load in chrome://tracing or
        ui.perfetto.dev). ``displayTimeUnit`` and per-event fields follow
        the Trace Event Format spec's "complete event" shape."""
        with self._lock:
            events = list(self._events)
            dropped = self.dropped
        trace = {"traceEvents": events, "displayTimeUnit": "ms"}
        if dropped:
            trace["droppedEventCount"] = dropped
        return trace

    def write_chrome_trace(self, path: str) -> None:
        import os

        # The jsonl sink usually creates workdir/telemetry/ first, but
        # the trace must not depend on which sinks are enabled.
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
            f.write("\n")


_default: Tracer = Tracer()


def default_tracer() -> Tracer:
    return _default


def reset_default_tracer(**kw) -> Tracer:
    """Fresh default tracer (test isolation / new run); returns it."""
    global _default
    _default = Tracer(**kw)
    return _default


def span(name: str, **args):
    """Convenience: a span on the default tracer (library call sites)."""
    return _default.span(name, **args)


def active_span_names() -> list[str]:
    return _default.active_span_names()
