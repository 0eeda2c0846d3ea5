"""In-loop ``jax.profiler`` windows (ISSUE 3 tentpole (3)).

The host span timeline (telemetry/spans.py) answers "where did the host
loop's time go"; the *device*-internal breakdown belongs to the XLA
profiler. Before this module the loop had one hardcoded one-shot window
(``--profile`` → steps 10..20) and the measurement tooling
(tools/profile_trace.py) re-implemented its own capture loop.

``ProfilerWindow`` is the single programmable capture path:

* ``TrainConfig.profile_start_step`` / ``profile_num_steps`` /
  ``profile_dir`` describe a window in run-relative steps; any run can
  capture a device trace without code changes. The legacy ``--profile``
  flag is sugar for ``start=10, num=10``.
* The window is **one-shot** (a re-arm would sync + restart the
  profiler every subsequent step — pinned by
  tests/test_bundled_steps.py) and bracketed by a ``profile`` span in
  the host timeline.
* On stop, the window's facts land in gauges (``profile/steps``,
  ``profile/wall_secs``) and are cross-linked from the run's final
  JSONL line as the ``"profile"`` object (dir, start, steps, wall) —
  so the record of *where the trace lives* survives with the run.

Reducing the captured ``.xplane.pb`` to numbers (device busy and idle
time, program and kernel times, the host's spans over the idle gaps) is
the benchmark's: ``benchmark/trace_reduce.py`` and
``benchmark/host_spans.py`` read it with ``jax.profiler.ProfileData``
alone.
"""

from __future__ import annotations

import logging
import os
import time

log = logging.getLogger(__name__)


class ProfilerWindow:
    """One-shot windowed device trace, driven by the training loop.

    ``maybe_start(rel_step)`` before a chunk (run-relative step index),
    ``maybe_stop(rel_steps_done, block_on=...)`` after it; ``finish``
    closes an in-flight window on any exit path.
    """

    def __init__(
        self,
        start_step: int,
        num_steps: int,
        out_dir: str,
        telemetry=None,
    ):
        self.start_step = max(int(start_step), 0)
        self.num_steps = max(int(num_steps), 1)
        self.out_dir = out_dir
        self._telemetry = telemetry
        self._state = "pending"  # pending -> active -> done
        self._span_cm = None
        self._t0 = 0.0
        self._first_rel = 0
        self._last_rel = 0  # latest rel_steps_done seen while active
        self.info: dict | None = None

    @classmethod
    def from_config(cls, cfg, telemetry=None) -> "ProfilerWindow | None":
        """None when no window is configured. ``--profile`` (legacy) maps
        to the historical steps-10..20 one-shot."""
        num = int(getattr(cfg, "profile_num_steps", 0) or 0)
        start = int(getattr(cfg, "profile_start_step", 0) or 0)
        if num <= 0:
            if not getattr(cfg, "profile", False):
                return None
            start, num = (start or 10), 10
        out_dir = (
            getattr(cfg, "profile_dir", "") or
            (os.path.join(cfg.workdir, "profile") if cfg.workdir
             else "/tmp/tpu_profile")
        )
        return cls(start, num, out_dir, telemetry)

    # -------------------------------------------------------------- drive

    @property
    def active(self) -> bool:
        return self._state == "active"

    def maybe_start(self, rel_step: int) -> None:
        if self._state != "pending" or rel_step < self.start_step:
            return
        import jax

        jax.profiler.start_trace(self.out_dir)
        self._state = "active"
        self._first_rel = rel_step
        self._last_rel = rel_step
        self._t0 = time.perf_counter()
        if self._telemetry is not None:
            self._span_cm = self._telemetry.span(
                "profile", dir=self.out_dir
            )
            self._span_cm.__enter__()
        log.info(
            "profiler window open: run-relative step %d, %d step(s) -> %s",
            rel_step, self.num_steps, self.out_dir,
        )

    def maybe_stop(self, rel_steps_done: int, block_on=None) -> None:
        if self._state != "active":
            return
        self._last_rel = rel_steps_done
        if rel_steps_done - self._first_rel >= self.num_steps:
            self._stop(rel_steps_done, block_on)

    def finish(self, block_on=None) -> None:
        """Close an in-flight window (exit paths: preempt, abort, loop
        end before the window filled). Steps already traced — the
        latest ``maybe_stop`` progress mark — are recorded, not lost."""
        if self._state == "active":
            self._stop(self._last_rel, block_on)

    # ------------------------------------------------------------ internal

    def _stop(self, rel_steps_done: int, block_on) -> None:
        import jax

        if block_on is not None:
            # The traced steps must actually retire inside the window.
            jax.block_until_ready(block_on)
        wall = time.perf_counter() - self._t0
        jax.profiler.stop_trace()
        self._state = "done"
        if self._span_cm is not None:
            self._span_cm.__exit__(None, None, None)
            self._span_cm = None
        steps = max(rel_steps_done - self._first_rel, 0)
        self.info = {
            "dir": self.out_dir,
            "start_step": self._first_rel,
            "num_steps": steps,
            "wall_secs": round(wall, 6),
        }
        if self._telemetry is not None:
            reg = self._telemetry.registry
            reg.gauge("profile/steps").set(steps)
            reg.gauge("profile/wall_secs").set(wall)
            self._telemetry.note_profile(self.info)
        log.info(
            "profiler window closed: %d step(s) in %.3fs -> %s",
            steps, wall, self.out_dir,
        )
