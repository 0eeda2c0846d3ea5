"""Failure detection + crash diagnostics (SURVEY.md §5c).

The reference had nothing beyond checkpoint-restart; TPU-native failure
handling here is three layers:

1. **Crash handlers** (``install_crash_handlers``): faulthandler tracebacks
   for hard faults (SIGSEGV/SIGABRT — e.g. a dying PJRT plugin) written to
   ``workdir/debugging/``, plus ``cloud_tpu_diagnostics`` integration when
   that package is importable (TPU-side stack traces on Cloud TPU VMs).
2. **Hang watchdog** (``Watchdog``): a daemon thread the training loop
   pings every step. If no progress for ``timeout_s`` (device hang, stuck
   collective, lost host↔TPU link), it dumps every Python thread's
   stack — turning a silent hang into a diagnosable event. The loop also
   marks which *phase* it is in (``enter("input_fetch")`` /
   ``enter("device_step")``), so the dump says whether the host input
   pipeline or the device step stalled. By default detection-only; with
   ``fatal_timeout_s > 0`` the watchdog FAILS FAST once the stall
   exceeds that bound — dump, then ``on_fatal`` (default:
   ``os._exit(HUNG_EXIT_CODE)``) — because at pod scale a silently hung
   host wedges the whole slice (ISSUE 1 / arXiv:1909.09756).
3. **Recovery** is checkpoint-resume, which the shared loop already does
   (orbax latest-checkpoint restore + stateless-resumable input order),
   plus the preemption/bad-step machinery in train/resilience.py.
"""

from __future__ import annotations

import faulthandler
import logging
import os
import sys
import threading
import time
from typing import Callable

log = logging.getLogger(__name__)


_fault_file = None  # singleton: faulthandler holds exactly one target


def install_crash_handlers(workdir: str = "") -> None:
    """Route hard-fault (SIGSEGV/SIGABRT/…) tracebacks somewhere durable.

    With ``workdir``: to ``workdir/debugging/faults_<pid>.log`` (the path
    is logged so operators know where to look — faulthandler writes to a
    single target, so the file supersedes stderr). Without: to stderr.
    Idempotent; repeated calls reuse the open file.
    """
    global _fault_file
    if workdir:
        debug_dir = os.path.join(workdir, "debugging")
        os.makedirs(debug_dir, exist_ok=True)
        path = os.path.join(debug_dir, f"faults_{os.getpid()}.log")
        if _fault_file is None or _fault_file.name != path:
            if _fault_file is not None:
                _fault_file.close()
            _fault_file = open(path, "w")  # noqa: SIM115 - outlives the call
        faulthandler.enable(file=_fault_file)
        log.info("hard-fault tracebacks -> %s", path)
    else:
        faulthandler.enable()
    try:  # TPU-side stack traces on Cloud TPU VMs (optional dependency)
        import cloud_tpu_diagnostics  # noqa: F401

        log.info("cloud_tpu_diagnostics available for TPU-side traces")
    except ImportError:
        pass


# Exit code for a watchdog-terminated (fail-fast) run: distinguishable
# from clean exits (0), python errors (1), and signal deaths (128+N).
HUNG_EXIT_CODE = 87


class Watchdog:
    """Detects training-loop hangs; dumps all thread stacks once per hang.

    >>> wd = Watchdog(timeout_s=600); wd.start()
    >>> for step ...: wd.ping(step)
    >>> wd.stop()

    ``enter(phase)`` marks loop phases ("input_fetch", "device_step", …)
    and counts as a heartbeat — a phase transition IS progress — so the
    hang report can name the stalled phase and how long it sat there.
    With ``fatal_timeout_s > 0``, a stall that long triggers fail-fast:
    diagnostic dump, then ``on_fatal(step, stalled_s)`` (default
    ``os._exit(HUNG_EXIT_CODE)`` — a deliberate hard exit: the main
    thread is by definition wedged, possibly inside a C call that a
    Python-level exception could never interrupt).
    """

    def __init__(
        self,
        timeout_s: float,
        *,
        fatal_timeout_s: float = 0.0,
        on_hang: Callable[[int, float], None] | None = None,
        on_fatal: Callable[[int, float], None] | None = None,
        flush_fn: Callable[[], None] | None = None,
        poll_s: float | None = None,
    ):
        self.timeout_s = timeout_s
        self.fatal_timeout_s = fatal_timeout_s
        self._on_hang = on_hang
        self._on_fatal = on_fatal
        # Best-effort pre-exit flush: runs on the fatal path BEFORE
        # on_fatal/os._exit, from the watchdog thread, so the run's
        # metrics survive the hard exit (ISSUE 2 abnormal-exit
        # satellite). The trainer passes Telemetry.emergency_flush,
        # which also snapshots the last fleet state and closes the
        # /metrics server (ISSUE 4) — a hung run's last per-host skew
        # picture is never lost, and the port stops answering scrapes
        # as if the run were live.
        self._flush_fn = flush_fn
        self._poll_s = poll_s if poll_s is not None else min(timeout_s / 4, 30.0)
        if fatal_timeout_s > 0:
            self._poll_s = min(self._poll_s, max(fatal_timeout_s / 4, 0.05))
        self._last_ping = time.monotonic()
        self._last_step = -1
        self._phase = "startup"
        self._phase_since = time.monotonic()
        self._paused = False
        self._fired_for = -2  # last step a hang was reported for
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    def start(self) -> "Watchdog":
        self._thread = threading.Thread(
            target=self._run, name="train-watchdog", daemon=True
        )
        self._thread.start()
        return self

    def ping(self, step: int) -> None:
        self._last_ping = time.monotonic()
        self._last_step = step

    def enter(self, phase: str) -> None:
        """Mark a loop phase ("input_fetch", "device_step", "restore", …).

        A phase transition is progress, so this refreshes the heartbeat
        (but not the step counter)."""
        now = time.monotonic()
        self._phase = phase
        self._phase_since = now
        self._last_ping = now

    def status(self) -> dict:
        """Live state for the /health endpoint (telemetry/serve.py):
        current phase + how long it has been the phase, the stall age,
        and the configured timeouts. Readable from any thread — every
        field is a single attribute read of values the loop thread
        writes atomically."""
        now = time.monotonic()
        return {
            "phase": self._phase,
            "phase_age_secs": now - self._phase_since,
            "stalled_secs": now - self._last_ping,
            "last_step": self._last_step,
            "paused": self._paused,
            "timeout_secs": self.timeout_s,
            "fatal_timeout_secs": self.fatal_timeout_s,
        }

    def pause(self) -> None:
        """Suspend hang detection (long known-slow phase: eval, ckpt,
        first-step compile). Timer restarts on the next ping/resume."""
        self._paused = True

    def resume(self) -> None:
        # Refresh the ping BEFORE unpausing: the watcher thread must
        # never see unpaused state with a stale timestamp.
        self._last_ping = time.monotonic()
        self._paused = False

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)

    def _dump(self, stalled: float, *, fatal: bool) -> None:
        # Name the innermost open telemetry span(s), not just the coarse
        # phase marker: "phase 'input_fetch', open spans ['data_fetch']"
        # tells you which instrumented region actually wedged.
        try:
            from tensorflow_examples_tpu.telemetry.spans import (
                active_span_names,
            )

            open_spans = active_span_names()
        except Exception:  # pragma: no cover - telemetry unavailable
            open_spans = []
        log.error(
            "WATCHDOG%s: no training progress for %.1fs (last step %d, "
            "phase %r for %.1fs, open spans %s) — dumping all thread "
            "stacks",
            " FATAL" if fatal else "",
            stalled,
            self._last_step,
            self._phase,
            time.monotonic() - self._phase_since,
            open_spans,
        )
        faulthandler.dump_traceback(file=sys.stderr)
        if _fault_file is not None:
            # Also into the durable fault log (stderr may not be
            # captured on managed VMs — the motivating scenario).
            faulthandler.dump_traceback(file=_fault_file)
            _fault_file.flush()

    def _run(self) -> None:
        fatal_fired = False
        while not self._stop.wait(self._poll_s):
            if self._paused:
                continue
            stalled = time.monotonic() - self._last_ping
            fatal_now = (
                self.fatal_timeout_s > 0
                and stalled >= self.fatal_timeout_s
                and not fatal_fired
            )
            if (
                not fatal_now  # one dump when both fire in the same pass
                and stalled >= self.timeout_s
                and self._fired_for != self._last_step
            ):
                self._fired_for = self._last_step
                self._dump(stalled, fatal=False)
                if self._on_hang is not None:
                    self._on_hang(self._last_step, stalled)
            if fatal_now:
                fatal_fired = True
                self._dump(stalled, fatal=True)
                if self._flush_fn is not None:
                    try:
                        self._flush_fn()
                    except Exception:  # pragma: no cover - best effort
                        log.exception("pre-exit telemetry flush failed")
                if self._on_fatal is not None:
                    self._on_fatal(self._last_step, stalled)
                else:
                    log.critical(
                        "WATCHDOG: failing fast with exit code %d rather "
                        "than hanging the slice",
                        HUNG_EXIT_CODE,
                    )
                    if _fault_file is not None:
                        _fault_file.flush()
                    os._exit(HUNG_EXIT_CODE)
