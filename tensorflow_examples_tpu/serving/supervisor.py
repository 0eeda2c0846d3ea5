"""Replica supervision: detect a dead/stuck replica, restart it,
re-admit it only after /health goes green (ISSUE 10 tentpole (1)).

PR 8's router already *stops dispatching* to a replica that dies (probe
failures rotate it out; the ISSUE 10 circuit breaker ejects it on
dispatch failures) — but nothing brought it back: a crashed replica
left a hole in the fleet until an operator noticed. This module is the
missing loop, the serving mirror of the trainer's PreemptionGuard
discipline: **failure is a normal input**.

The supervisor owns a set of :class:`ReplicaHandle`-shaped objects —
anything with ``url``, ``alive()`` and a blocking ``restart()`` — and a
background thread that, per sweep:

1. **Detects** a dead or stuck replica: ``alive()`` false (process
   exit / in-proc kill), or its ``/health`` not answering green for
   longer than ``health_stall_s`` (a wedged process that still holds
   its socket — the serving version of the training watchdog's hung
   step).
2. **Quarantines** it in the router (``Router.quarantine`` — no
   dispatch no matter what the probe/breaker state says) so the
   restart window cannot eat requests.
3. **Restarts** it via the handle — for the in-proc chaos replicas
   (serving/chaos.py) that means a fresh engine + **full AOT warmup**
   of the bucket ladder; for :class:`ProcessReplica` a respawned
   process whose own startup warms.
4. **Re-admits** it (``Router.readmit``) only once ``/health`` answers
   200 with ``ok: true`` — never a cold or half-warm replica; bumps
   ``router/restarts_total`` (the schema-v7 ``router_restarts``
   counter).

A handle that keeps dying is retried up to ``max_restarts`` times with
``restart_backoff_s`` between attempts, then left quarantined with an
ERROR — a crash-looping build must page an operator, not flap the
fleet forever. ``tools/serve_fleet.py --spawn`` wires this over real
processes; the chaos tier (tests/test_chaos.py, ``serve_bench
--chaos``) drives it in-proc.

ISSUE 13 adds the :class:`Autoscaler` — the loop that *decides* fleet
size. The supervisor keeps replicas ALIVE; the autoscaler keeps the
fleet SIZED to its SLO, scaling up (spawn -> AOT warm -> /health green
-> join router + supervisor) when the probe-fed signals run hot and
scaling down drain-first when they stay idle, with a crash-loop guard
so the two loops never fight over the same replica.
"""

from __future__ import annotations

import logging
import shlex
import subprocess
import sys
import threading
import time

from tensorflow_examples_tpu.serving.router import Router, _get_json

log = logging.getLogger(__name__)


class ProcessReplica:
    """A replica that is a real child process (``serve_fleet --spawn``).

    ``cmd`` is the spawn command (string, ``shlex``-split; a ``{port}``
    placeholder receives ``port``). The process is expected to serve
    the PR 5 frontend surface on ``http://127.0.0.1:{port}``.

    No device assignment happens here: the child takes whatever its
    own command line and environment give it. A chip belongs to one
    process, so several TPU replicas on one host do not work this way
    today (docs/serving.md; ROADMAP queue 2 item 4).
    """

    def __init__(self, cmd: str, *, port: int,
                 host: str = "127.0.0.1",
                 stop_timeout_s: float = 10.0):
        self.cmd = cmd
        self.port = int(port)
        self.url = f"http://{host}:{self.port}"
        self.stop_timeout_s = stop_timeout_s
        self._proc: subprocess.Popen | None = None

    def start(self) -> "ProcessReplica":
        argv = shlex.split(self.cmd.format(port=self.port))
        log.info("spawning replica %s: %s", self.url, argv)
        self._proc = subprocess.Popen(argv)
        return self

    def alive(self) -> bool:
        return self._proc is not None and self._proc.poll() is None

    @property
    def exit_code(self) -> int | None:
        """The child's exit code; None while it runs (or never ran)."""
        return None if self._proc is None else self._proc.poll()

    def terminate(self) -> None:
        """SIGTERM (the replica's own drain path), escalate to SIGKILL
        after ``stop_timeout_s``."""
        proc = self._proc
        if proc is None or proc.poll() is not None:
            return
        proc.terminate()
        try:
            proc.wait(timeout=self.stop_timeout_s)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=self.stop_timeout_s)

    def restart(self) -> None:
        self.terminate()
        self.start()

    def close(self) -> None:
        self.terminate()


class Supervisor:
    """Watch replicas, restart the dead/stuck ones, re-admit on green.

    ``handles`` maps replica URL -> handle; every URL must already be a
    replica of ``router``. Restarts run serially on the supervisor
    thread (one failure at a time is the design point; a correlated
    fleet-wide outage needs an operator anyway).
    """

    def __init__(
        self,
        router: Router,
        handles,
        *,
        poll_s: float = 0.25,
        health_stall_s: float = 5.0,
        health_timeout_s: float = 2.0,
        warm_timeout_s: float = 300.0,
        max_restarts: int = 5,
        restart_backoff_s: float = 0.5,
    ):
        self.router = router
        self.handles = {h.url.rstrip("/"): h for h in handles}
        for url in self.handles:
            if router._find(url) is None:
                raise ValueError(
                    f"supervised url {url} is not a router replica"
                )
        self.poll_s = poll_s
        self.health_stall_s = health_stall_s
        self.health_timeout_s = health_timeout_s
        self.warm_timeout_s = warm_timeout_s
        self.max_restarts = max_restarts
        self.restart_backoff_s = restart_backoff_s
        # True while an incident is being handled (detect -> restart ->
        # readmit/give-up). The autoscaler's crash-loop guard reads it:
        # no scaling decision while the supervisor is spending its
        # restart budget (ISSUE 13).
        self._busy = False
        # Completed restart cycles (reporting: serve_bench --chaos sums
        # this into router_restarts).
        self.restarts: dict[str, int] = {u: 0 for u in self.handles}
        # Failed attempts within the CURRENT incident — reset on every
        # successful readmit, so max_restarts bounds one crash-loop,
        # not the replica's whole lifetime (a replica independently
        # recovered N times must not be abandoned on failure N+1).
        self._attempts: dict[str, int] = {u: 0 for u in self.handles}
        self.given_up: set[str] = set()
        # Last role each replica's /health reported (ISSUE 12):
        # heterogeneous prefill/decode fleets are first-class, so an
        # incident log must say WHICH role went down — a dead prefill
        # replica stalls handoffs fleet-wide, not 1/N of traffic.
        self.roles: dict[str, str] = {u: "mixed" for u in self.handles}
        # (url, event) rows: "detected" / "restarted" / "readmitted" /
        # "gave_up" — the chaos tier asserts the transition sequence.
        self.events: list[tuple[str, str]] = []
        self._last_ok = {u: time.monotonic() for u in self.handles}
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    # ----------------------------------------------- elastic fleet (ISSUE 13)

    def busy(self) -> bool:
        """An incident is in flight (quarantine -> restart -> readmit).
        The autoscaler holds all scaling while this is true so it never
        fights the restart budget."""
        return self._busy

    def adopt_router(self, router: "Router") -> None:
        """Re-point supervision at a new router (ISSUE 16: warm-standby
        takeover). The standby rebuilt its replica view from /health
        sweeps before promoting, so every supervised URL is expected to
        exist there; any that don't are added so quarantine/readmit
        keep working across the switch."""
        for url in self.handles:
            if router._find(url) is None:
                router.add_replica(url)
        self.router = router

    def add_handle(self, handle) -> None:
        """Supervise one more replica at runtime (the autoscaler's
        scale-up registers its freshly-green spawn here)."""
        url = handle.url.rstrip("/")
        self.handles[url] = handle
        self.restarts.setdefault(url, 0)
        self._attempts.setdefault(url, 0)
        self.roles.setdefault(url, "mixed")
        self._last_ok[url] = time.monotonic()
        self.given_up.discard(url)

    def remove_handle(self, url: str) -> None:
        """Stop supervising a replica (scale-down, after drain +
        router removal). The handle itself is the caller's to close."""
        url = url.rstrip("/")
        self.handles.pop(url, None)
        self.restarts.pop(url, None)
        self._attempts.pop(url, None)
        self.roles.pop(url, None)
        self._last_ok.pop(url, None)
        self.given_up.discard(url)

    # ------------------------------------------------------------ sweep

    def _healthy(self, url: str) -> bool:
        status, body = _get_json(
            url + "/health", self.health_timeout_s
        )
        if status == 0:
            return False
        if isinstance(body.get("role"), str):
            self.roles[url] = body["role"]
        # Any well-formed HTTP answer means the process is responsive;
        # a 503 that is an orderly drain is NOT a stall (the replica is
        # finishing its work on purpose).
        return status == 200 or bool(body.get("draining"))

    def check_once(self) -> None:
        """One synchronous sweep (the loop body; tests call it
        directly for determinism)."""
        now = time.monotonic()
        # Snapshot: the autoscaler may add/remove handles mid-sweep.
        for url, handle in list(self.handles.items()):
            if url in self.given_up or url not in self.handles:
                continue
            if handle.alive() and self._healthy(url):
                self._last_ok[url] = time.monotonic()
                continue
            stalled = now - self._last_ok.get(url, now)
            if handle.alive() and stalled < self.health_stall_s:
                continue  # transient blip: give /health time to recover
            reason = (
                "process dead" if not handle.alive()
                else f"/health stalled {stalled:.1f}s"
            )
            log.warning(
                "SUPERVISOR: %s replica %s down (%s) — quarantining "
                "and restarting", self.roles.get(url, "mixed"), url,
                reason,
            )
            self.events.append((url, "detected"))
            self.router.quarantine(url)
            self._busy = True
            try:
                self._restart(url, handle)
            finally:
                self._busy = False

    def _restart(self, url: str, handle) -> None:
        while self._attempts[url] < self.max_restarts:
            self._attempts[url] += 1
            try:
                handle.restart()  # blocking: respawn + re-warm the AOT
                #                   ladder before anything is re-admitted
            except Exception:  # noqa: BLE001 — a failed restart must
                # not kill the supervisor loop
                log.exception(
                    "SUPERVISOR: restart of %s failed (attempt %d/%d)",
                    url, self._attempts[url], self.max_restarts,
                )
                time.sleep(self.restart_backoff_s)
                continue
            self.events.append((url, "restarted"))
            if self._await_green(url):
                self._last_ok[url] = time.monotonic()
                self._attempts[url] = 0  # incident over: fresh budget
                self.restarts[url] += 1
                self.router.readmit(url)
                self.router.registry.counter(
                    "router/restarts_total"
                ).inc()
                self.events.append((url, "readmitted"))
                log.info(
                    "SUPERVISOR: replica %s restarted and re-admitted "
                    "(/health green)", url,
                )
                return
            log.warning(
                "SUPERVISOR: restarted %s never went green within "
                "%.1fs (attempt %d/%d)", url, self.warm_timeout_s,
                self._attempts[url], self.max_restarts,
            )
            time.sleep(self.restart_backoff_s)
        self.given_up.add(url)
        self.events.append((url, "gave_up"))
        log.error(
            "SUPERVISOR: giving up on %s after %d restart attempts — "
            "left quarantined; operator action required", url,
            self.max_restarts,
        )

    def _await_green(self, url: str) -> bool:
        deadline = time.monotonic() + self.warm_timeout_s
        while time.monotonic() < deadline and not self._stop.is_set():
            status, body = _get_json(
                url + "/health", self.health_timeout_s
            )
            if status == 200 and body.get("ok"):
                return True
            time.sleep(min(0.05, self.poll_s))
        return False

    # -------------------------------------------------------- lifecycle

    def _loop(self) -> None:
        while not self._stop.is_set():
            try:
                self.check_once()
            except Exception:  # noqa: BLE001 — the watcher must survive
                log.exception("supervisor sweep failed")
            self._stop.wait(self.poll_s)

    def start(self) -> "Supervisor":
        self._thread = threading.Thread(
            target=self._loop, name="replica-supervisor", daemon=True
        )
        self._thread.start()
        return self

    def close(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=max(10.0, self.warm_timeout_s))


# --------------------------------------------------------------------------
# Telemetry-driven autoscaler (ISSUE 13 tentpole (3)): the loop that
# DECIDES fleet size.


def scrape_ttft_p95(url: str, timeout_s: float = 2.0) -> float | None:
    """One replica's recent ``serving_ttft_seconds{quantile="0.95"}``
    from its Prometheus ``/metrics`` endpoint (None when unreachable or
    no TTFT sample yet). The autoscaler's latency signal comes from the
    replica's real scrape surface, not a private API."""
    import urllib.error
    import urllib.request

    try:
        with urllib.request.urlopen(
            url.rstrip("/") + "/metrics", timeout=timeout_s
        ) as resp:
            text = resp.read().decode("utf-8", "replace")
    except (OSError, ValueError):
        return None
    for line in text.splitlines():
        if line.startswith("serving_ttft_seconds{") \
                and 'quantile="0.95"' in line:
            try:
                return float(line.rsplit(" ", 1)[1])
            except (IndexError, ValueError):
                return None
    return None


class AutoscalerConfig:
    """Scaling policy knobs (plain attributes so callers override a la
    carte)."""

    def __init__(
        self,
        *,
        min_replicas: int = 1,
        max_replicas: int = 4,
        target_queue_depth: float = 4.0,   # mean queued per eligible
        #                                    replica above this -> up
        target_kv_occupancy: float = 0.85,  # mean KV pressure -> up
        target_ttft_p95_s: float = 0.0,    # worst replica TTFT p95
        #                                    above this -> up (0 off)
        scale_down_frac: float = 0.25,     # idle watermark = frac of
        #                                    each up-target
        hold_s: float = 2.0,               # min wall between actions
        scale_down_idle_s: float = 3.0,    # sustained idle before a
        #                                    drain starts
        drain_timeout_s: float = 60.0,
        warm_timeout_s: float = 300.0,     # green gate for a spawn
        evaluate_every_s: float = 0.5,
    ):
        self.min_replicas = int(min_replicas)
        self.max_replicas = int(max_replicas)
        self.target_queue_depth = float(target_queue_depth)
        self.target_kv_occupancy = float(target_kv_occupancy)
        self.target_ttft_p95_s = float(target_ttft_p95_s)
        self.scale_down_frac = float(scale_down_frac)
        self.hold_s = float(hold_s)
        self.scale_down_idle_s = float(scale_down_idle_s)
        self.drain_timeout_s = float(drain_timeout_s)
        self.warm_timeout_s = float(warm_timeout_s)
        self.evaluate_every_s = float(evaluate_every_s)


class Autoscaler:
    """Resize the fleet against an SLO target (ISSUE 13).

    Reads the router's probe-fed replica view (the ``/replicas``
    numbers: queue depth, KV occupancy, brownout level) plus each
    replica's real ``/metrics`` TTFT p95, and walks the fleet between
    ``min_replicas`` and ``max_replicas``:

    * **Scale-up** — ``spawn(index)`` builds a new replica handle
      (blocking through its full AOT warmup, so cold-start compilation
      happens BEFORE the replica sees traffic), the green gate waits
      for ``/health`` 200 ok (the PR 9 readmit discipline), and only
      then does the replica join the router and the supervisor.
      ``scale_up_latencies`` records decision -> serving wall per
      event (the ``scale_up_latency_s`` the traffic record stamps).
    * **Scale-down** — always drain-first: ``router.drain`` stops new
      dispatch, the loop waits for the replica to go idle
      (active == 0, queue empty via ``/health``), then removes it from
      router + supervisor and closes the handle (``stop()`` when the
      handle has one — the graceful path — else ``close()``). A drain
      that cannot complete within ``drain_timeout_s`` is ABORTED
      (undrain, keep the replica): scaling down may be delayed,
      never lossy.
    * **Crash-loop guard** — no action while ``supervisor.busy()`` (an
      incident is spending the restart budget), quarantined replicas
      are never drain targets, and once the supervisor has GIVEN UP on
      a crash-looping replica the autoscaler refuses to scale up at
      all (spawning more of a crash-looping build fights the budget
      the supervisor just exhausted; ``autoscaler/blocked_total``
      counts both guards).

    One action per evaluation, serially, with ``hold_s`` between
    actions — the same one-failure-at-a-time design point as the
    supervisor. Tests drive :meth:`evaluate_once` directly."""

    def __init__(
        self,
        router: Router,
        supervisor: Supervisor,
        spawn,
        *,
        cfg: AutoscalerConfig | None = None,
        registry=None,
        health_timeout_s: float = 2.0,
        alerts=None,
    ):
        self.router = router
        self.supervisor = supervisor
        self.spawn = spawn
        self.cfg = cfg or AutoscalerConfig()
        # Advisory alert signal (ISSUE 19): anything with the
        # AlertEngine ``stats()`` shape. A firing SLO alert marks the
        # fleet hot (scale up even before queue depth shows it) and
        # vetoes scale-down — the brownout ladder's cousin, fed by the
        # canary prober and organic burn rates instead of queue state.
        self.alerts = alerts
        self.registry = (
            registry if registry is not None else router.registry
        )
        self.health_timeout_s = health_timeout_s
        # Handles this autoscaler manages (it may scale down replicas
        # it did not spawn, as long as the supervisor holds a handle).
        self._spawn_index = len(supervisor.handles)
        self.events: list[tuple[float, str, str]] = []  # (unix, verb, url)
        self.scale_up_latencies: list[float] = []
        self._last_action = 0.0
        self._idle_since: float | None = None
        self._acting = False
        self._soft_stop = False
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    def acting(self) -> bool:
        """A scale action (spawn/warm/drain) is in flight right now."""
        return self._acting

    # ---------------------------------------------------------- signals

    def fleet_signals(self) -> dict:
        """The decision inputs, from the fleet's own scrape surfaces:
        the router's probe-fed replica states and each eligible
        replica's ``/metrics`` TTFT p95."""
        cfg = self.router.cfg
        eligible = [
            r for r in self.router.replicas
            if r.eligible(cfg.unhealthy_after)
        ]
        n = len(eligible)
        ttft = None
        if self.cfg.target_ttft_p95_s > 0:
            vals = [
                v for v in (
                    scrape_ttft_p95(r.url, self.health_timeout_s)
                    for r in eligible
                ) if v is not None
            ]
            ttft = max(vals) if vals else None
        return {
            "replicas": len(self.router.replicas),
            "eligible": n,
            "queue_depth_mean": (
                sum(r.queue_depth for r in eligible) / n if n else 0.0
            ),
            "kv_occupancy_mean": (
                sum(r.kv_occupancy for r in eligible) / n if n else 0.0
            ),
            "brownout_max": max(
                (r.brownout_level for r in eligible), default=0
            ),
            "ttft_p95_s": ttft,
            "alerts_firing": (
                int(self.alerts.stats()["alerts_firing"])
                if self.alerts is not None else 0
            ),
        }

    # --------------------------------------------------------- decision

    def evaluate_once(self) -> str:
        """One control-loop tick; returns the action taken
        ("scale_up" / "scale_down" / "hold" / "blocked")."""
        reg = self.registry
        reg.counter("autoscaler/evaluations_total").inc()
        cfg = self.cfg
        if self.supervisor.busy():
            # Crash-loop guard (1): an incident is mid-restart — the
            # fleet picture is churning and the budget is spoken for.
            reg.counter("autoscaler/blocked_total").inc()
            return "blocked"
        sig = self.fleet_signals()
        reg.gauge("autoscaler/replicas").set(sig["replicas"])
        now = time.monotonic()
        if sig["alerts_firing"] > 0:
            reg.counter("autoscaler/alert_advisory_total").inc()
        hot = (
            sig["queue_depth_mean"] >= cfg.target_queue_depth
            or sig["kv_occupancy_mean"] >= cfg.target_kv_occupancy
            or sig["brownout_max"] > 0
            or sig["alerts_firing"] > 0
            or (
                cfg.target_ttft_p95_s > 0
                and sig["ttft_p95_s"] is not None
                and sig["ttft_p95_s"] >= cfg.target_ttft_p95_s
            )
            or sig["eligible"] == 0
        )
        idle = (
            sig["queue_depth_mean"]
            <= cfg.scale_down_frac * cfg.target_queue_depth
            and sig["kv_occupancy_mean"]
            <= cfg.scale_down_frac * cfg.target_kv_occupancy
            and sig["brownout_max"] == 0
            and sig["alerts_firing"] == 0
            and (
                cfg.target_ttft_p95_s <= 0
                or sig["ttft_p95_s"] is None
                or sig["ttft_p95_s"]
                <= cfg.scale_down_frac * cfg.target_ttft_p95_s
            )
        )
        if hot:
            self._idle_since = None
            if sig["replicas"] >= cfg.max_replicas:
                reg.counter("autoscaler/at_max_total").inc()
                return "hold"
            if self.supervisor.given_up:
                # Crash-loop guard (2): the supervisor just exhausted a
                # restart budget on this build — spawning more of it
                # would crash-loop too. Page an operator instead.
                reg.counter("autoscaler/blocked_total").inc()
                log.error(
                    "AUTOSCALER: scale-up refused — supervisor gave up "
                    "on %s; operator action required",
                    sorted(self.supervisor.given_up),
                )
                return "blocked"
            if now - self._last_action < cfg.hold_s:
                return "hold"
            self._acting = True
            try:
                return self._scale_up()
            finally:
                self._acting = False
        if idle and sig["replicas"] > cfg.min_replicas:
            if self._idle_since is None:
                self._idle_since = now
                return "hold"
            if (
                now - self._idle_since >= cfg.scale_down_idle_s
                and now - self._last_action >= cfg.hold_s
            ):
                self._acting = True
                try:
                    return self._scale_down()
                finally:
                    self._acting = False
            return "hold"
        self._idle_since = None
        return "hold"

    # ---------------------------------------------------------- actions

    def _await_green(self, url: str, timeout_s: float) -> bool:
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline and not self._stop.is_set():
            status, body = _get_json(
                url + "/health", self.health_timeout_s
            )
            if status == 200 and body.get("ok"):
                return True
            time.sleep(0.05)
        return False

    def _scale_up(self) -> str:
        reg = self.registry
        t0 = time.monotonic()
        idx = self._spawn_index
        self._spawn_index += 1
        log.info("AUTOSCALER: scaling up (spawn %d)", idx)
        try:
            handle = self.spawn(idx)  # blocking: build + AOT warmup
        except Exception:  # noqa: BLE001 — a failed spawn must not
            # kill the control loop
            log.exception("AUTOSCALER: spawn %d failed", idx)
            reg.counter("autoscaler/spawn_failures_total").inc()
            self._last_action = time.monotonic()
            return "hold"
        url = handle.url.rstrip("/")
        if not self._await_green(url, self.cfg.warm_timeout_s):
            # Green gate (PR 9 discipline): never admit a cold or
            # half-warm replica. A spawn that cannot go green is torn
            # down, not routed to.
            log.error(
                "AUTOSCALER: spawned %s never went green; discarding",
                url,
            )
            reg.counter("autoscaler/spawn_failures_total").inc()
            handle.close()
            self._last_action = time.monotonic()
            return "hold"
        self.router.add_replica(url)
        self.router.probe_once()
        self.supervisor.add_handle(handle)
        latency = time.monotonic() - t0
        self.scale_up_latencies.append(latency)
        self._last_action = time.monotonic()
        reg.counter("autoscaler/scale_ups_total").inc()
        reg.histogram("autoscaler/scale_up_latency").record(latency)
        self.events.append((time.time(), "scale_up", url))
        log.info(
            "AUTOSCALER: %s serving after %.1fs (decision -> green -> "
            "routed)", url, latency,
        )
        return "scale_up"

    def _pick_drain_target(self):
        cfg = self.router.cfg
        candidates = [
            r for r in self.router.replicas
            if r.url in self.supervisor.handles
            and not r.quarantined
            and not r.drained
            and r.eligible(cfg.unhealthy_after)
        ]
        if len(candidates) <= self.cfg.min_replicas:
            return None
        # Least-loaded goes first: fewest in-flight requests to wait
        # out, and the fleet loses the least capacity.
        return min(
            candidates,
            key=lambda r: (r.load_score(), -self.router.replicas.index(r)),
        )

    def _scale_down(self) -> str:
        reg = self.registry
        target = self._pick_drain_target()
        if target is None:
            return "hold"
        url = target.url
        log.info("AUTOSCALER: scaling down %s (drain first)", url)
        self.router.drain(url)
        deadline = time.monotonic() + self.cfg.drain_timeout_s
        drained = False
        while time.monotonic() < deadline and not self._stop.is_set():
            status, body = _get_json(
                url + "/health", self.health_timeout_s
            )
            if status in (200, 503) and isinstance(body, dict) and (
                body.get("active_requests") == 0
                and body.get("queue_depth") == 0
            ):
                drained = True
                break
            time.sleep(0.05)
        if not drained:
            # Never lossy: a drain that cannot complete aborts the
            # scale-down and the replica keeps serving.
            log.warning(
                "AUTOSCALER: drain of %s did not complete in %.0fs — "
                "aborting scale-down", url, self.cfg.drain_timeout_s,
            )
            self.router.undrain(url)
            reg.counter("autoscaler/drain_aborted_total").inc()
            self._last_action = time.monotonic()
            return "hold"
        handle = self.supervisor.handles.get(url)
        self.router.remove_replica(url)
        self.supervisor.remove_handle(url)
        if handle is not None:
            stop = getattr(handle, "stop", None)
            (stop if callable(stop) else handle.close)()
        self._idle_since = None
        self._last_action = time.monotonic()
        reg.counter("autoscaler/scale_downs_total").inc()
        self.events.append((time.time(), "scale_down", url))
        log.info("AUTOSCALER: %s drained and removed", url)
        return "scale_down"

    # -------------------------------------------------------- lifecycle

    def _loop(self) -> None:
        while not self._stop.is_set() and not self._soft_stop:
            try:
                self.evaluate_once()
            except Exception:  # noqa: BLE001 — the control loop must
                # survive any single evaluation
                log.exception("autoscaler evaluation failed")
            self._stop.wait(self.cfg.evaluate_every_s)

    def start(self) -> "Autoscaler":
        self._thread = threading.Thread(
            target=self._loop, name="fleet-autoscaler", daemon=True
        )
        self._thread.start()
        return self

    def close(self) -> None:
        """Graceful first: stop scheduling NEW evaluations and let an
        in-flight action (a spawn mid-warmup, a drain mid-wait) finish
        — aborting a half-done scale action would discard a warmed
        replica or strand a drained one. Hard-stop only if the join
        times out."""
        self._soft_stop = True
        if self._thread is not None:
            self._thread.join(timeout=max(
                30.0,
                self.cfg.drain_timeout_s + 5.0,
                self.cfg.warm_timeout_s + 5.0,
            ))
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10.0)


def main_check(urls, timeout_s: float = 2.0) -> int:  # pragma: no cover
    """Tiny CLI helper: print each replica's health verdict (used by
    operators, not tests)."""
    rc = 0
    for url in urls:
        status, body = _get_json(
            url.rstrip("/") + "/health", timeout_s
        )
        ok = status == 200 and bool(body.get("ok"))
        print(f"{url}: {'OK' if ok else f'DOWN (status {status})'}")
        rc = rc or (0 if ok else 1)
    return rc


if __name__ == "__main__":  # pragma: no cover
    sys.exit(main_check(sys.argv[1:]))
