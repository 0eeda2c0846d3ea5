"""The Telemetry object the trainer owns (ISSUE 2 tentpole).

One instance per ``Trainer.fit`` drives everything observable about the
run: it snapshots the process-local registry (counters/gauges/
histograms) into a schema-versioned line per log window, derives the
accounting numbers (throughput, step-time percentiles, MFU, goodput),
fans the line out to the configured sinks, and exports the span
timeline as Chrome-trace JSON on close.

Abnormal-exit contract (satellite): the JSONL sink flushes per line, so
completed windows are always durable; ``final_window`` additionally
emits the partial in-flight window with an ``exit_reason`` on
preemption/abort, and ``emergency_flush`` is the watchdog-fatal hook —
called from the watchdog thread right before ``os._exit(87)`` — that
pushes sinks and the trace to disk while the main thread is wedged.

Cross-host: most counters are incremented by every process for the SAME
global event (the loop is SPMD — steps, checkpoint saves, bad steps are
replicated), so their local value already IS the global truth and
summing them would inflate by process_count. Only the counters in
``HOST_LOCAL_COUNTERS`` — events each host observes independently — are
summed over processes (a fixed name set, so the collective has
identical shape on every host); every host then computes the identical
line and process 0's JSONL is the run record.

Fleet layer (ISSUE 4): every line carries a ``host`` field (schema v3),
every cadenced window the attached ``FleetMonitor`` allgathers the
per-host health vector and the summary lands as a ``kind="fleet"`` line
right after the window line, and an attached ``MetricsServer`` exposes
/metrics, /health, and /window live (the hub keeps ``last_line`` for
it). The emergency path additionally snapshots the fleet state
(collective-free) and closes the server before exit 87.
"""

from __future__ import annotations

import logging
import time
from typing import Mapping

from tensorflow_examples_tpu.telemetry import accounting
from tensorflow_examples_tpu.telemetry import registry as registry_mod
from tensorflow_examples_tpu.telemetry import schema
from tensorflow_examples_tpu.telemetry import sinks as sinks_mod
from tensorflow_examples_tpu.telemetry import spans as spans_mod

log = logging.getLogger(__name__)

# Counters summed across hosts at each cadenced window: ONLY events each
# host observes independently (its own flaky reads, its own poisoned
# local batches). Everything else (train/steps_total, checkpoint/saves,
# resilience/*) is SPMD-replicated — each host's value already equals
# the global truth, so those pass through unreduced. Fixed set: the
# collective must have identical shape on every process.
HOST_LOCAL_COUNTERS = (
    "io/retries",
    "data/batches_skipped",
)


class Telemetry:
    def __init__(
        self,
        sinks: list,
        *,
        registry=None,
        tracer=None,
        flops_per_step: float = 0.0,
        peak_flops_total: float = 0.0,
        tokens_per_example: int = 1,
        trace_file: str | None = None,
        flush_every: int = 1,
        memory=None,
        fleet=None,
        host: int | None = None,
    ):
        self.sinks = sinks
        self.registry = (
            registry
            if registry is not None
            else registry_mod.default_registry()
        )
        self.tracer = (
            tracer if tracer is not None else spans_mod.default_tracer()
        )
        self.flops_per_step = float(flops_per_step)
        self.peak_flops_total = float(peak_flops_total)
        self.tokens_per_example = max(int(tokens_per_example), 1)
        self.trace_file = trace_file
        self.flush_every = max(int(flush_every), 1)
        # Device-side observability (ISSUE 3): the per-fit memory
        # monitor (None = no memory fields on lines) and the profiler-
        # window cross-link carried on the final line.
        self.memory = memory
        # Fleet observability (ISSUE 4): the per-host skew monitor (None
        # = no fleet lines), the host index stamped on every line, the
        # latest emitted line (for the /window endpoint), and the
        # optional live-metrics server closed on the emergency path.
        self.fleet = fleet
        if host is None:
            try:
                import jax

                host = jax.process_index()
            except Exception:  # pragma: no cover - pre-init edge
                host = 0
        self.host = int(host)
        # last_line carries the latest NON-fleet line (the /window
        # endpoint's payload — a fleet line right after every window
        # would otherwise hide the metrics a watcher wants); the fleet
        # stream gets its own slot for /fleet.
        self.last_line: dict | None = None
        self.last_fleet_line: dict | None = None
        self.server = None  # MetricsServer, attached by the trainer
        self.profile_info: dict | None = None
        # Placement provenance (ISSUE 7, schema v5): set by the trainer
        # ({"mesh_shape", "param_sharding_digest", "zero1"}); rides the
        # kind="final" line so a run record names the layout it ran on.
        self.sharding_info: dict | None = None
        self._emergency = False  # watchdog-fatal: cached-only sampling
        self._windows_since_flush = 0
        self._last_step = 0  # most recent log_window step (fatal marker)
        self._closed = False
        # Counters are process-global and a process may run several
        # fits; every line this Telemetry emits carries DELTAS from the
        # fit-start snapshot, so each fit is a self-contained session
        # and offline aggregation can simply sum sessions.
        self._counter_base = dict(self.registry.counter_values())
        self._session_start = time.time()  # session id in every line
        if self.flops_per_step > 0:
            self.registry.gauge("telemetry/flops_per_step").set(
                self.flops_per_step
            )
        if self.peak_flops_total > 0:
            self.registry.gauge("telemetry/peak_flops_total").set(
                self.peak_flops_total
            )

    @classmethod
    def from_config(cls, cfg, *, n_params: int = 0) -> "Telemetry":
        """Build from TrainConfig knobs (sink spec, trace toggle, flush
        cadence, peak override) + the workload's size numbers."""
        import jax

        sinks = sinks_mod.make_sinks(
            getattr(cfg, "telemetry_sinks", "console"), cfg.workdir
        )
        # Processed tokens per example: seq_len for token workloads
        # (GPT-2 feeds tokens[:, :-1] — seq_len positions; BERT pads to
        # seq_len), 1 for per-example workloads (images).
        tokens = int(getattr(cfg, "seq_len", 0) or 0) or 1
        flops = accounting.train_step_flops(
            n_params, cfg.global_batch_size, tokens
        )
        peak_tflops = float(getattr(cfg, "telemetry_peak_tflops", 0.0) or 0.0)
        # No peak (a device kind the table does not list, and no
        # override) means no MFU: accounting.mfu returns None for 0.
        peak = peak_tflops * 1e12 or accounting.peak_flops_per_device(
            jax.devices()[0].device_kind
        ) or 0.0
        trace_file = (
            sinks_mod.trace_path(cfg.workdir)
            if cfg.workdir
            and getattr(cfg, "telemetry_trace", True)
            and jax.process_index() == 0
            else None
        )
        from tensorflow_examples_tpu.telemetry import fleet as fleet_mod
        from tensorflow_examples_tpu.telemetry import memory as memory_mod

        return cls(
            sinks,
            flops_per_step=flops,
            peak_flops_total=peak * jax.device_count(),
            tokens_per_example=tokens,
            trace_file=trace_file,
            flush_every=getattr(cfg, "telemetry_flush_every", 1),
            memory=memory_mod.MemoryMonitor(),
            fleet=fleet_mod.FleetMonitor.from_config(cfg),
        )

    # ------------------------------------------------------------ intake

    def span(self, name: str, **args):
        return self.tracer.span(name, **args)

    def note_steps(self, n: int) -> None:
        """Count completed device steps (INCLUDING skipped bad steps and
        rollback replays — goodput's denominator is total stepped work)."""
        self.registry.counter("train/steps_total").inc(n)

    def record_step_time(self, seconds: float, k: int = 1) -> None:
        """One loop-iteration wall time; bundles amortize over k steps."""
        self.registry.histogram("step_time").record(seconds / max(k, 1))

    # ----------------------------------------------------------- windows

    def _fit_counters(self) -> dict[str, int]:
        """This fit's counters: deltas from the fit-start snapshot."""
        base = self._counter_base
        return {
            k: max(v - base.get(k, 0), 0)
            for k, v in self.registry.counter_values().items()
        }

    def _reduced_counters(self, values=None) -> dict[str, int]:
        values = (
            dict(values) if values is not None else self._fit_counters()
        )
        import jax

        if jax.process_count() == 1:
            return values
        import numpy as np
        from jax.experimental import multihost_utils

        vec = np.asarray(
            [values.get(n, 0) for n in HOST_LOCAL_COUNTERS], np.int64
        )
        summed = multihost_utils.process_allgather(vec).sum(axis=0)
        values.update(
            {n: int(v) for n, v in zip(HOST_LOCAL_COUNTERS, summed)}
        )
        return values

    def _derived(
        self, window_metrics: Mapping[str, float], counters: Mapping[str, int]
    ) -> dict:
        steps_per_sec = window_metrics.get("steps_per_sec")
        examples_per_sec = window_metrics.get("examples_per_sec")
        # One summary() pass: a single lock acquisition + sort of the
        # sample window, instead of one per percentile.
        step_summary = self.registry.histogram("step_time").summary()
        derived = {
            "examples_per_sec": examples_per_sec,
            "tokens_per_sec": (
                examples_per_sec * self.tokens_per_example
                if examples_per_sec is not None
                and self.tokens_per_example > 1
                else None
            ),
            "step_time_p50": step_summary["p50"],
            "step_time_p95": step_summary["p95"],
            "goodput": accounting.goodput(counters),
            # Analytic 6ND MFU: an end-to-end utilisation, not a
            # measurement of the device (the benchmark reduces traces).
            "mfu": (
                accounting.mfu(
                    self.flops_per_step, steps_per_sec,
                    self.peak_flops_total,
                )
                if steps_per_sec is not None
                else None
            ),
        }
        return derived

    def log_window(
        self,
        step: int,
        metrics: Mapping[str, float],
        *,
        prefix: str = "train",
        kind: str = "window",
        exit_reason: str | None = None,
        reduce: bool = True,
        extra: Mapping | None = None,
    ) -> dict:
        """Emit one window line to every sink; returns the line.

        ``reduce=False`` skips the cross-host counter reduction — REQUIRED
        on abnormal-exit paths (preemption, abort), where peer processes
        may never reach the matching collective and the reduction would
        deadlock the dying process.

        ``extra`` merges additional schema-v2 objects into the line
        (the ``"compile"`` payload of a compile_warning, the
        ``"memory"`` breakdown of a memory snapshot line).
        """
        # Local fit-delta counters are captured BEFORE the cross-host
        # reduction: the fleet vector must carry each host's OWN
        # io/batch-skip numbers (the reduction replaces them with fleet
        # sums — identical on every host, useless for localization).
        local_counters = self._fit_counters()
        counters = (
            self._reduced_counters(local_counters)
            if reduce
            else local_counters
        )
        line = {
            "schema_version": schema.SCHEMA_VERSION,
            "kind": kind,
            "host": self.host,
            "step": int(step),
            "time_unix": time.time(),
            "session_start_unix": self._session_start,
            "metrics": {
                (f"{prefix}/{k}" if prefix else k): (
                    float(v) if v is not None else None
                )
                for k, v in metrics.items()
            },
            "counters": counters,
            "gauges": self.registry.gauge_values(),
            "derived": self._derived(metrics, counters),
        }
        if kind == "final":
            line["exit_reason"] = exit_reason or "complete"
            if self.profile_info is not None:
                line["profile"] = dict(self.profile_info)
            if self.sharding_info is not None:
                line["sharding"] = dict(self.sharding_info)
        # Memory watermark fields ride every cadenced/final line (the
        # kind="memory" init snapshot carries its own via ``extra``).
        # On the watchdog-fatal path only CACHED values are used: a
        # fresh live-array/PJRT poll from the watchdog thread could
        # block behind the wedged main thread.
        if self.memory is not None and kind in ("window", "final"):
            try:
                if not self._emergency:
                    self.memory.sample()
                line["memory"] = self.memory.window_fields()
            except Exception:  # pragma: no cover - accounting best effort
                log.exception("memory sampling failed (continuing)")
        if extra:
            line.update(extra)
        self._last_step = int(step)
        for sink in self.sinks:
            try:
                sink.write(line)
            except Exception:
                log.exception(
                    "telemetry sink %s failed to write (continuing)",
                    type(sink).__name__,
                )
        if kind == "fleet":
            self.last_fleet_line = line
        elif kind in ("window", "eval", "final"):
            # /window's contract: the latest SCALAR line. Memory and
            # compile_warning snapshots are JSONL-record material and
            # must not displace the window a watcher reads loss from.
            self.last_line = line
        # Fleet summary rides every cadenced window (ISSUE 4): the
        # gather is a collective, so it runs ONLY on the reduce=True
        # window path — the same place the counter reduction already
        # synchronizes every host. LOCAL counters: the vector's
        # io/skip entries are per-host evidence, not the fleet sums.
        if kind == "window" and reduce and self.fleet is not None:
            self._emit_fleet(step, local_counters)
        # Flush accounting AFTER the fleet emission, and never for the
        # fleet line itself: it rides every window, so counting it
        # would silently halve a configured telemetry_flush_every —
        # instead the window's own flush (below) covers both lines.
        if kind != "fleet":
            self._windows_since_flush += 1
            if self._windows_since_flush >= self.flush_every:
                self.flush()
        return line

    def _emit_fleet(self, step: int, counters: Mapping[str, int]) -> None:
        try:
            payload = self.fleet.gather(counters)
        except Exception:  # pragma: no cover - collective teardown races
            log.exception("fleet gather failed (continuing)")
            return
        self.log_window(
            step, {}, kind="fleet", reduce=False, extra={"fleet": payload}
        )

    def last_window_age(self) -> float | None:
        """Seconds since the last emitted line (the /health signal)."""
        if self.last_line is None:
            return None
        return max(time.time() - self.last_line["time_unix"], 0.0)

    def final_window(
        self,
        step: int,
        metrics: Mapping[str, float],
        *,
        prefix: str = "train",
        exit_reason: str,
    ) -> dict:
        """The partial in-flight window on an exit path (no collective:
        peers may already be gone)."""
        return self.log_window(
            step, metrics, prefix=prefix, kind="final",
            exit_reason=exit_reason, reduce=False,
        )

    # ------------------------------------- device-side lines (ISSUE 3)

    def note_memory_init(self, state, step: int = 0) -> dict | None:
        """The fit-start memory snapshot: params/opt/model-state/other
        breakdown as a ``kind="memory"`` line (telemetry/memory.py)."""
        if self.memory is None:
            return None
        try:
            breakdown = self.memory.init_breakdown(state)
        except Exception:  # pragma: no cover - accounting best effort
            log.exception("memory init snapshot failed (continuing)")
            return None
        return self.log_window(
            step, {}, kind="memory", reduce=False,
            extra={"memory": breakdown},
        )

    def compile_warning(self, event: Mapping) -> dict:
        """A post-warmup recompilation (telemetry/compilation.py):
        lands as a ``kind="compile_warning"`` line naming the shape/
        dtype delta. No collective — every SPMD process sees the same
        recompile, and a mid-step collective outside the program is a
        deadlock risk."""
        event = dict(event)
        step = int(event.pop("step", self._last_step))
        return self.log_window(
            step, {}, kind="compile_warning", reduce=False,
            extra={"compile": event},
        )

    def note_profile(self, info: Mapping) -> None:
        """Cross-link a completed profiler window from the final line."""
        self.profile_info = dict(info)

    # ------------------------------------------------------------- flush

    def flush(self) -> None:
        self._windows_since_flush = 0
        for sink in self.sinks:
            try:
                sink.flush()
            except Exception:  # pragma: no cover - sink teardown races
                log.exception("telemetry sink flush failed (continuing)")

    def write_trace(self) -> None:
        if self.trace_file:
            try:
                self.tracer.write_chrome_trace(self.trace_file)
            except Exception:  # pragma: no cover - disk-full etc.
                log.exception("chrome trace export failed (continuing)")

    def emergency_flush(self) -> None:
        """Watchdog-fatal path: called from the WATCHDOG thread right
        before ``os._exit(87)`` while the main thread is wedged. Lands a
        fleet snapshot (cached — NO collective: peers may be past their
        own matching point) and a final marker line (local counters
        only, no loop state: the partial window lives on the wedged
        thread), then closes the metrics server and pushes the trace
        and sinks to disk. Must never block on the main thread."""
        self._emergency = True  # memory fields come from cache only
        if self.fleet is not None:
            # The hung run's last known fleet state (ISSUE 4 satellite):
            # which host was straggling when everything stopped is
            # exactly the forensics the postmortem needs.
            try:
                self.log_window(
                    self._last_step, {}, kind="fleet", reduce=False,
                    extra={
                        "fleet": self.fleet.snapshot(self._fit_counters())
                    },
                )
            except Exception:  # pragma: no cover - dying anyway
                log.exception("watchdog-fatal fleet snapshot failed")
        try:
            self.final_window(
                self._last_step, {}, exit_reason="watchdog_fatal"
            )
        except Exception:  # pragma: no cover - dying anyway; best effort
            log.exception("watchdog-fatal final line failed")
        self.close_server()
        self.write_trace()
        self.flush()

    def close_server(self) -> None:
        """Shut the /metrics endpoint down (idempotent; all exit paths —
        a dead run must not keep answering scrapes as if live)."""
        server, self.server = self.server, None
        if server is not None:
            try:
                server.close()
            except Exception:  # pragma: no cover - socket teardown races
                log.exception("metrics server close failed (continuing)")

    def close(self) -> None:
        """Flush everything and write the trace; idempotent (the loop's
        ``finally`` calls this after any earlier abnormal-exit flush)."""
        if self._closed:
            return
        self._closed = True
        self.close_server()
        self.write_trace()
        for sink in self.sinks:
            try:
                sink.close()
            except Exception:  # pragma: no cover - sink teardown races
                log.exception("telemetry sink close failed (continuing)")
