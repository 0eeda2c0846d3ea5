"""Multi-replica router tier: one endpoint over N serving replicas.

PR 5 ended with one engine process per endpoint; the ROADMAP's
millions-of-users traffic needs N replicas behind one address with the
operational verbs a fleet actually uses (ISSUE 8). This module is that
tier, deliberately stdlib-only like every HTTP surface in the repo:

* **Load-aware dispatch** — a background thread probes each replica's
  ``/health`` (the PR 5 frontend already publishes queue depth, KV
  occupancy, active requests, drain state); requests go to the
  eligible replica with the lowest load score
  ``queue_depth + kv_occupancy`` (queue pressure dominates; the paged
  pool's ``kv_occupancy`` is used-block fraction, so short-prompt
  replicas correctly read as roomy — the ISSUE 8 gauge-semantics fix
  is what makes this signal honest), ties broken by fewest dispatches.
* **Prefix-affinity dispatch** (ISSUE 12, ``prefix_affinity`` on by
  default) — paged replicas also publish a prefix digest (content
  chain keys of their cached blocks, ``serving/scheduler.py``); the
  router hashes the prompt's block-aligned prefix chain and prefers
  the replica already holding the longest cached chain
  (``router/affinity_hits_total``), load-guarded by
  ``affinity_load_gap`` so affinity never starves a hot replica.
* **Disaggregated roles** (ISSUE 12) — replicas publish a ``role``
  (``mixed`` | ``prefill`` | ``decode``); when the fleet has both
  specialist roles, generate traffic routes prefill-leg ->
  KV-page handoff -> decode-leg (``/prefill`` -> ``/resume``,
  ``router/handoffs_total``), falling back to the full path on any
  leg failure (``router/handoff_fallbacks_total``) — roles are
  advisory, every replica still serves a full ``/generate``, so a
  dead role-holder is an ordinary in-flight failover.
* **Drain-aware rollout** — ``drain(url)`` (or ``POST /drain``) stops
  NEW dispatch to a replica while its in-flight requests finish on the
  replica itself; a replica that starts draining on its own (SIGTERM —
  its ``/health`` flips 503 with ``draining: true``) is detected by
  the probe and likewise rotated out without failing anything. Roll a
  fleet by draining one replica, restarting it, undraining, repeating.
* **Bounded retry with backoff** (ISSUE 10, replacing PR 8's
  retry-once) — a dispatch answered 503 (shed/draining) or a transport
  failure is retried up to ``max_retries`` times on different replicas
  of the same set with exponential backoff, all within a per-request
  wall budget (``retry_budget_s``); anything else a replica *answers*
  (400/404/504/500) passes through untouched — the router never
  re-runs a request a replica actually executed. A transport failure
  after dispatch is **in-flight failover**: the replica may have died
  mid-decode, and the re-dispatch replays the request from the prompt
  on another replica (``router/failovers_total``). Replay is safe and
  token-identical by construction — generation is a pure function of
  (params, prompt, seed) via the engine's per-request ``fold_in``
  seeding, so the failed-over stream matches what the dead replica
  would have produced, and the survivors' prefix cache makes the
  re-prefill cheap.
* **Per-replica circuit breaker** (ISSUE 10) — ``eject_after``
  consecutive dispatch failures eject the replica
  (``router/ejections_total``; breaker *open*, no dispatch); after
  ``eject_cooldown_s`` the breaker goes *half-open* and admits exactly
  one trial (a successful ``/health`` probe or one live request);
  success readmits (``router/readmits_total``, breaker closed),
  failure re-ejects for another cooldown.
* **Hedged dispatch** (ISSUE 10, opt-in ``hedge_after_s > 0``) — a
  request still unanswered after the hedge deadline is sent a second
  time to another replica; the first 200 wins and the loser is
  abandoned (``router/hedges_total`` / ``hedge_wins_total`` /
  ``hedge_cancelled_total``). Requests are idempotent-by-seeding, so
  hedging can never produce divergent streams — it only caps p99.
* **Fleet-down fast-fail** (ISSUE 13 satellite) — when not one replica
  is eligible and at least one is hard-down (every breaker open, probes
  failing, quarantined), requests shed immediately with their own
  counter (``router/fleet_down_total``) instead of burning
  ``retry_budget_s`` each rediscovering the same dead fleet; a
  fully-drained fleet (operator rollout, no failure) still gets the
  plain no-replica 503.
* **Elastic fleet verbs** (ISSUE 13) — ``add_replica(url)`` /
  ``remove_replica(url)`` let the autoscaler
  (``serving/supervisor.py``) resize the fleet at runtime; the probe
  also learns each replica's ``brownout_level`` from ``/health``, so
  the router's ``/health``/``/replicas``/stats line carry the fleet
  overload view (worst level, summed transitions).
* **Supervision hooks** — ``quarantine(url)`` / ``readmit(url)`` let
  ``serving/supervisor.py`` rotate a dead replica out while it is
  restarted and re-warmed, and re-admit it only after its ``/health``
  has gone green (``router/restarts_total`` counts completed
  restart cycles).
* **Canary compare** — replicas are grouped into sets (``base`` and
  ``canary``); a configured fraction of traffic goes to the canary
  set and per-set latency/throughput records
  (:meth:`Router.canary_records`) feed ``tools/run_diff.py``, whose
  serving-aware GATE_KEYS rank TTFT/TPOT/prefix-hit regressions first.

The router publishes its own observability surface
(:class:`RouterFrontend`): ``/metrics`` (Prometheus), ``/health``,
``/replicas``, ``/window`` (a schema-v6 ``kind="serving"`` line whose
serving object carries the v6 router fields), and the admin verbs
``POST /drain`` / ``POST /undrain``. ``tools/serve_fleet.py`` is the
CLI wrapper; ``tools/serve_bench.py --router`` measures the whole tier
and banks the ``serve_router`` record ``bench_gate`` accepts.
"""

from __future__ import annotations

import dataclasses
import http.client
import http.server
import json
import logging
import queue
import socket
import threading
import time
import urllib.error
import urllib.request
import uuid

from tensorflow_examples_tpu.telemetry import registry as registry_mod
from tensorflow_examples_tpu.telemetry import schema
from tensorflow_examples_tpu.telemetry import slo as slo_mod
from tensorflow_examples_tpu.telemetry import timeseries as timeseries_mod
from tensorflow_examples_tpu.telemetry import tracing as tracing_mod
from tensorflow_examples_tpu.telemetry.serve import (
    json_safe,
    render_prometheus,
)
from tensorflow_examples_tpu.utils import faults as faults_mod

log = logging.getLogger(__name__)

_MAX_BODY = 1 << 20
_MAX_SAMPLES = 8192  # per-set latency samples kept for canary records


@dataclasses.dataclass(frozen=True)
class RouterConfig:
    probe_interval_s: float = 0.5   # /health poll cadence per replica
    probe_timeout_s: float = 2.0
    request_timeout_s: float = 120.0
    retry_budget_s: float = 10.0    # wall budget for ALL retry attempts
    max_retries: int = 2            # bounded retry (ISSUE 10): total
    #                                 re-dispatches after the first try
    retry_backoff_s: float = 0.05   # base backoff, doubled per retry
    eject_after: int = 3            # consecutive DISPATCH failures ->
    #                                 circuit breaker opens (ejected)
    eject_cooldown_s: float = 3.0   # open -> half-open (one trial)
    hedge_after_s: float = 0.0      # >0: hedged dispatch for p99 — a
    #                                 request unanswered this long is
    #                                 sent again elsewhere, first 200
    #                                 wins, loser abandoned
    unhealthy_after: int = 3        # consecutive probe failures
    canary_fraction: float = 0.25   # traffic share when a canary set
    #                                 is configured
    prefix_affinity: bool = True    # ISSUE 12: prefer the replica
    #                                 already holding the longest cached
    #                                 chain of this prompt's blocks
    #                                 (probe-published prefix digests)
    affinity_load_gap: float = 2.0  # affinity never starves a hot
    #                                 replica: a cached-chain holder is
    #                                 only preferred while its load
    #                                 score is within this gap of the
    #                                 least-loaded eligible replica
    trace_sample_fraction: float = 0.01  # ISSUE 18 tail sampler: the
    #                                 seeded deterministic share of
    #                                 NORMAL traffic kept (slow/error/
    #                                 retried/failed-over/hedged/
    #                                 preempted/deduped/resumed/
    #                                 brownout traces are ALWAYS kept)
    trace_seed: int = 0             # the seeded fraction's hash salt


def _as_object(status: int, body) -> tuple[int, dict]:
    """Coerce a parsed reply to the (status, dict) contract. A replica
    answering valid-but-non-object JSON (a bare list/string/number) is
    as malformed as a torn body: status 0, so probes mark it unhealthy
    and dispatches treat it as retryable — never an AttributeError
    inside the probe loop (ISSUE 10 satellite)."""
    if isinstance(body, dict):
        return status, body
    return 0, {"error": f"non-object JSON reply: {type(body).__name__}"}


def _get_json(url: str, timeout: float) -> tuple[int, dict]:
    try:
        with urllib.request.urlopen(url, timeout=timeout) as resp:
            return _as_object(resp.status, json.loads(resp.read()))
    except urllib.error.HTTPError as e:
        try:
            return _as_object(e.code, json.loads(e.read() or b"{}"))
        except (ValueError, OSError, http.client.HTTPException):
            return e.code, {}
    except (OSError, ValueError, http.client.HTTPException) as e:
        return 0, {"error": f"{type(e).__name__}: {e}"}


def post_json(url: str, body: dict, timeout: float) -> tuple[int, dict]:
    """POST a JSON body, always returning ``(status, reply_dict)`` —
    status 0 on transport failure (reset, timeout, refused, torn
    body). The one JSON-over-HTTP client in the serving stack: the
    dispatcher, the probe loop's writes, and tools/serve_bench.py all
    route through it, so the status-0 contract cannot drift."""
    data = json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"}
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return _as_object(resp.status, json.loads(resp.read()))
    except urllib.error.HTTPError as e:
        try:
            return _as_object(e.code, json.loads(e.read() or b"{}"))
        except (ValueError, OSError, http.client.HTTPException):
            return e.code, {}
    except (OSError, ValueError, http.client.HTTPException) as e:
        # Transport failure: status 0 — the dispatcher treats it like a
        # 503 (retryable on another replica) and the probe loop will
        # notice a dead replica on its own. A peer that dies between
        # the headers and the body is an ``IncompleteRead``, which is
        # an HTTPException and no OSError.
        return 0, {"error": f"{type(e).__name__}: {e}"}


class _TraceState:
    """Per-request trace bookkeeping threaded through the dispatch
    path (ISSUE 18): the trace id, the router's root ``request`` span
    id, the incoming parent span (when the CLIENT originated the
    context), the SLO class, and the forced-keep flags the dispatch
    loop accumulates (retried / failover / hedged)."""

    __slots__ = ("trace_id", "root_id", "parent_id", "slo", "flags")

    def __init__(self, trace_id: str, root_id: str,
                 parent_id: str | None, slo: str):
        self.trace_id = trace_id
        self.root_id = root_id
        self.parent_id = parent_id
        self.slo = slo
        self.flags: set = set()


class ReplicaState:
    """One replica as the router sees it: probe-sourced load numbers +
    router-side rollout state."""

    # Mutable fields are written by the probe loop, the dispatcher, and
    # the rollout/supervision verbs — three thread families — so every
    # write (and every multi-field read that must not tear) happens
    # under the owning Router's lock. The `# guard:` annotations below
    # cover the state-machine/bookkeeping fields and make that contract
    # machine-checked (graftlint lock pass, ISSUE 14); the accepted
    # lock-free reads inside eligible() (called from the autoscaler/
    # chaos threads, where one stale decision is harmless) live in the
    # committed baseline. The probe-sourced load numbers (queue_depth,
    # kv_occupancy, active_requests, role, prefix digest, ...) are
    # deliberately UNANNOTATED: they are last-write-wins snapshots the
    # probe rewrites every sweep — the lint does not check them, and
    # cross-thread readers (load_score() from the supervisor tier)
    # accept staleness by design.
    def __init__(self, url: str, set_name: str = "base"):
        self.url = url.rstrip("/")
        self.set_name = set_name
        self.drained = False          # guard: Router._lock (operator rollout)
        self.draining_remote = False  # guard: Router._lock (replica SIGTERM)
        self.quarantined = False      # guard: Router._lock (being restarted)
        self.failures = 0             # guard: Router._lock (consecutive probe failures)
        self.probed = False           # guard: Router._lock
        self.last_probe_unix = 0.0
        self.queue_depth = 0.0
        self.kv_occupancy = 0.0
        self.active_requests = 0.0
        self.slots = 0
        self.post_warmup_recompiles = 0
        self.dispatched = 0           # guard: Router._lock
        self.completed = 0            # guard: Router._lock
        self.errors = 0               # guard: Router._lock
        # Cache-aware scheduling state (ISSUE 12), probe-sourced: the
        # replica's role (mixed serves everything — the pre-ISSUE-12
        # behavior), its prefix-cache block size, and the content chain
        # keys of the blocks it currently caches (the affinity digest).
        self.role = "mixed"
        self.block_size = 0
        self.prefix_digest: frozenset = frozenset()
        self.prefix_blocks = 0
        self.prefix_chains = 0
        # Overload state (ISSUE 13), probe-sourced: the replica's
        # brownout ladder level, its transition count, and its
        # digest-truncation flag.
        self.brownout_level = 0
        self.brownout_transitions = 0
        self.digest_truncated = False
        # Circuit breaker (ISSUE 10). States: "closed" (normal),
        # "open" (ejected — no dispatch until the cooldown expires),
        # "half_open" (cooldown expired — exactly ONE trial in flight
        # at a time; success readmits, failure re-ejects). Transitions
        # happen under the Router's lock.
        self.breaker = "closed"       # guard: Router._lock
        self.consec_errors = 0        # guard: Router._lock (consecutive dispatch failures)
        self.open_until = 0.0         # guard: Router._lock (monotonic: open -> half_open)
        self.half_open_trial = False  # guard: Router._lock (trial in flight)

    def breaker_poll_locked(self, now: float) -> None:
        """Open -> half-open once the cooldown expires (caller holds
        the router lock — the ``_locked`` suffix is the repo's
        caller-holds-the-lock convention, checked by graftlint)."""
        if self.breaker == "open" and now >= self.open_until:
            self.breaker = "half_open"
            self.half_open_trial = False

    def eligible(self, unhealthy_after: int,
                 now: float | None = None) -> bool:
        if (
            self.drained
            or self.draining_remote
            or self.quarantined
            or self.failures >= unhealthy_after
        ):
            return False
        if self.breaker == "closed":
            return True
        if now is None:
            now = time.monotonic()
        if self.breaker == "open":
            return now >= self.open_until  # pick() flips to half_open
        return not self.half_open_trial    # half_open: one trial only

    def load_score(self) -> float:
        """Least-loaded dispatch key: queued requests dominate, KV
        pressure (used-block fraction under paging) breaks near-ties."""
        return float(self.queue_depth) + float(self.kv_occupancy)

    def serves(self, role: str | None) -> bool:
        """Role capability filter: a ``mixed`` replica serves every
        leg; ``prefill``/``decode`` replicas serve their own leg.
        ``role=None`` (a full /generate) matches everyone — roles are
        a dispatch preference, not a capability wall, which is what
        makes killing a role-holder an ordinary failover."""
        return role is None or self.role in (role, "mixed")

    def snapshot_locked(self) -> dict:
        # Caller holds Router._lock (graftlint lock-pass convention).
        return {
            "url": self.url,
            "set": self.set_name,
            "role": self.role,
            "prefix_blocks": self.prefix_blocks,
            "prefix_chains": self.prefix_chains,
            "brownout_level": self.brownout_level,
            "digest_truncated": self.digest_truncated,
            "drained": self.drained,
            "draining_remote": self.draining_remote,
            "quarantined": self.quarantined,
            "breaker": self.breaker,
            "consec_errors": self.consec_errors,
            "probe_failures": self.failures,
            "queue_depth": self.queue_depth,
            "kv_occupancy": self.kv_occupancy,
            "active_requests": self.active_requests,
            "slots": self.slots,
            "post_warmup_recompiles": self.post_warmup_recompiles,
            "dispatched": self.dispatched,
            "completed": self.completed,
            "errors": self.errors,
        }


class _SetStats:
    """Per-replica-set client-side latency aggregates (the canary
    compare's raw material). Replies already carry the replica-measured
    ttft_s/total_s; tokens give TPOT."""

    def __init__(self):
        self.lock = threading.Lock()
        self.requests = 0             # guard: self.lock
        self.completed = 0            # guard: self.lock
        self.errors = 0               # guard: self.lock
        self.ttft: list[float] = []   # guard: self.lock
        self.tpot: list[float] = []   # guard: self.lock
        self.e2e: list[float] = []    # guard: self.lock
        self.tokens = 0               # guard: self.lock
        self.t0 = time.monotonic()

    def record(self, status: int, reply: dict) -> None:
        with self.lock:
            self.requests += 1
            if status != 200:
                self.errors += 1
                return
            self.completed += 1
            toks = len(reply.get("tokens") or ())
            self.tokens += toks
            ttft = reply.get("ttft_s")
            total = reply.get("total_s")
            if isinstance(ttft, (int, float)):
                self.ttft.append(float(ttft))
                if isinstance(total, (int, float)) and toks > 1:
                    self.tpot.append(
                        (float(total) - float(ttft)) / (toks - 1)
                    )
            if isinstance(total, (int, float)):
                self.e2e.append(float(total))
            for samples in (self.ttft, self.tpot, self.e2e):
                if len(samples) > _MAX_SAMPLES:
                    del samples[: len(samples) - _MAX_SAMPLES]

    @staticmethod
    def _pct(samples: list[float], q: float) -> float | None:
        if not samples:
            return None
        s = sorted(samples)
        idx = max(0, min(len(s) - 1, round(q / 100 * len(s) + 0.5) - 1))
        return round(s[int(idx)] * 1e3, 3)

    def record_doc(self, set_name: str) -> dict:
        with self.lock:
            wall = max(time.monotonic() - self.t0, 1e-9)
            return {
                "bench": "serve_router_set",
                "set": set_name,
                "requests": self.requests,
                "completed": self.completed,
                "errors": self.errors,
                "generated_tokens": self.tokens,
                "req_per_s": round(self.completed / wall, 3),
                "tok_per_s": round(self.tokens / wall, 3),
                "ttft_p50_ms": self._pct(self.ttft, 50),
                "ttft_p95_ms": self._pct(self.ttft, 95),
                "tpot_p50_ms": self._pct(self.tpot, 50),
                "tpot_p95_ms": self._pct(self.tpot, 95),
                "e2e_p95_ms": self._pct(self.e2e, 95),
            }


class Router:
    """Dispatcher + probe loop over replica sets (no sockets of its
    own — :class:`RouterFrontend` is the HTTP surface; tests drive
    ``handle()`` directly too)."""

    def __init__(
        self,
        replicas: list[str],
        *,
        canary: list[str] | None = None,
        cfg: RouterConfig | None = None,
        registry=None,
        journal=None,
        lease=None,
        fencing_token: int = 0,
        recorder=None,
        trace_path: str | None = None,
        slo_cfg=None,
        alert_path: str | None = None,
    ):
        if not replicas:
            raise ValueError("router needs at least one replica URL")
        self.cfg = cfg or RouterConfig()
        self.registry = (
            registry if registry is not None
            else registry_mod.MetricsRegistry()
        )
        self.replicas = [ReplicaState(u, "base") for u in replicas]
        self.replicas += [
            ReplicaState(u, "canary") for u in (canary or [])
        ]
        self.has_canary = any(
            r.set_name == "canary" for r in self.replicas
        )
        self._set_stats = {"base": _SetStats(), "canary": _SetStats()}
        self._lock = threading.Lock()
        self._req_counter = 0
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._start_unix = time.time()
        # Control-plane durability (ISSUE 16): the request journal and
        # the active-router lease. Both optional — a journal-less
        # router still strips the client's request_id/resume_from
        # control fields (replicas reject unknown fields) and serves
        # resume by replay-and-skip; it just cannot dedupe or replay
        # across its own death.
        self.journal = journal
        if journal is not None and journal.registry is None:
            journal.registry = self.registry
        self._lease = lease
        self._fencing_token = int(fencing_token)
        # Per-request tracing (ISSUE 18): the recorder mints/accepts
        # trace contexts in handle(), assembles each request's span
        # tree from the router's own dispatch/leg spans plus the
        # replica-returned ones, and tail-samples at finish. Inject a
        # SHARED recorder (chaos.RouterPair does) so a takeover's
        # successor stitches onto the primary's traces in place.
        self._owns_recorder = recorder is None
        self.recorder = (
            recorder if recorder is not None
            else tracing_mod.TraceRecorder(
                registry=self.registry, path=trace_path,
                sample_fraction=self.cfg.trace_sample_fraction,
                seed=self.cfg.trace_seed,
            )
        )
        # SLO alerting (ISSUE 19): always on — a default SLOConfig is
        # deliberately generous, so the engine is silent until traffic
        # actually breaches an objective. Every finished ORGANIC
        # request feeds it (probe-tagged requests feed it through
        # serving/prober.py instead); firing/resolve transitions land
        # in ``alert_path`` as v14 ``kind="alert"`` lines and the
        # summary rides the stats line (the v14 keys).
        self.alerts = slo_mod.AlertEngine(
            slo_cfg, registry=self.registry, path=alert_path,
        )
        # In-process time-series store (ISSUE 19): sampled once per
        # stats_line() call — the existing stats cadence — and served
        # as GET /series by the frontend.
        self.series = timeseries_mod.TimeSeriesStore(self.registry)

    def attach_lease(self, lease, token: int) -> None:
        """(Re)bind this router to the active-router lease at fencing
        ``token``. Dispatch refuses once the lease holds a NEWER token
        (a promoted standby fenced this router out); the probe loop
        heartbeats the lease while the token is still the newest."""
        self._lease = lease
        self._fencing_token = int(token)

    # ------------------------------------------------------------ probes

    def probe_once(self) -> None:
        """One synchronous sweep (the background loop's body; tests
        call it directly for determinism)."""
        for r in self.replicas:
            status, body = _get_json(
                r.url + "/health", self.cfg.probe_timeout_s
            )
            r.last_probe_unix = time.time()
            if status == 0 or not isinstance(body, dict):
                # Transport failure OR a malformed/non-JSON body
                # (_get_json coerces the latter to status 0): the
                # replica is marked unhealthy and the sweep moves on to
                # the next one — garbage can fail a replica, never the
                # probe loop (ISSUE 10 satellite).
                with self._lock:
                    r.failures += 1
                    failures = r.failures
                if failures == self.cfg.unhealthy_after:
                    log.warning(
                        "replica %s unreachable or malformed after %d "
                        "probes — rotating out", r.url, failures,
                    )
                continue
            # Any HTTP answer means the process is alive; a 503 with
            # draining=true is the replica's own drain, not a failure.
            with self._lock:
                r.failures = 0
                r.probed = True
                r.draining_remote = bool(body.get("draining"))
                for field in ("queue_depth", "kv_occupancy",
                              "active_requests"):
                    v = body.get(field)
                    if isinstance(v, (int, float)):
                        setattr(r, field, float(v))
                for field in ("slots", "post_warmup_recompiles",
                              "prefix_blocks", "prefix_chains",
                              "brownout_level",
                              "brownout_transitions"):
                    v = body.get(field)
                    if isinstance(v, (int, float)):
                        setattr(r, field, int(v))
                r.digest_truncated = bool(body.get("digest_truncated"))
                # Cache-aware scheduling fields (ISSUE 12) — absent on
                # pre-ISSUE-12 replicas, which simply never win an
                # affinity preference.
                role = body.get("role")
                if isinstance(role, str) and role in (
                    "mixed", "prefill", "decode"
                ):
                    r.role = role
                bs = body.get("prefix_block_size")
                if isinstance(bs, (int, float)) and int(bs) > 0:
                    r.block_size = int(bs)
                digest = body.get("prefix_digest")
                if isinstance(digest, list):
                    r.prefix_digest = frozenset(
                        k for k in digest if isinstance(k, str)
                    )
                bloom = body.get("prefix_bloom")
                if isinstance(bloom, dict):
                    # ISSUE 15 satellite: a truncated replica ALSO
                    # publishes a bloom filter over its whole chain-key
                    # set — prefer it (the key list is capped; the
                    # filter is not). Malformed payloads fail THIS
                    # field only, never the sweep.
                    from tensorflow_examples_tpu.serving import (
                        scheduler,
                    )

                    try:
                        r.prefix_digest = scheduler.decode_bloom(bloom)
                    except ValueError:
                        pass  # keep the (truncated) key list
                # Half-open probe -> readmit (ISSUE 10): once the
                # breaker's cooldown has expired, a green /health is
                # the trial — the replica rejoins dispatch without
                # risking a live request on it.
                r.breaker_poll_locked(time.monotonic())
                if (
                    status == 200
                    and r.breaker == "half_open"
                    and not r.half_open_trial
                ):
                    r.breaker = "closed"
                    r.consec_errors = 0
                    self.registry.counter("router/readmits_total").inc()
                    log.info(
                        "replica %s readmitted (half-open /health probe "
                        "green)", r.url,
                    )
        with self._lock:
            eligible = sum(
                r.eligible(self.cfg.unhealthy_after)
                for r in self.replicas
            )
        self.registry.gauge("router/replicas_eligible").set(eligible)

    def _probe_loop(self) -> None:
        while not self._stop.is_set():
            try:
                self.probe_once()
                self._heartbeat()
            except Exception:  # noqa: BLE001 — the probe must survive
                log.exception("replica probe sweep failed")
            self._stop.wait(self.cfg.probe_interval_s)

    def _heartbeat(self) -> None:
        """Refresh the active-router lease (ISSUE 16). Rides the probe
        cadence: a router whose probe loop stalls (or whose process
        dies) stops heartbeating, which is precisely the signal the
        warm standby promotes on. A fenced heartbeat is a no-op write-
        wise (the lease refuses it), so a stalled-then-revived primary
        can never clobber its successor's lease."""
        if self._lease is not None and self._fencing_token > 0:
            self._lease.heartbeat(self._fencing_token)

    def fenced(self) -> bool:
        """True when the lease holds a NEWER fencing token than ours:
        a standby promoted itself over this router, and every dispatch
        here must be refused (split-brain pin — no request is ever
        served by two routers). Also true for a never-promoted standby
        (token 0 vs any granted lease): passivity and fencing are the
        same check."""
        if self._lease is None:
            return False
        return self._lease.fenced(self._fencing_token)

    def start(self) -> "Router":
        self.probe_once()  # synchronous first sweep: never dispatch blind
        self._heartbeat()
        self._thread = threading.Thread(
            target=self._probe_loop, name="router-probe", daemon=True
        )
        self._thread.start()
        return self

    def close(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
        if self._owns_recorder:
            # An injected (shared) recorder outlives this router — the
            # RouterPair's successor is still finishing traces into it.
            self.recorder.close()
        self.alerts.close()

    # ------------------------------------------------ elastic fleet (ISSUE 13)

    def add_replica(self, url: str,
                    set_name: str = "base") -> ReplicaState:
        """Register a replica at runtime (the autoscaler's scale-up
        verb). Idempotent per URL. The replica list is replaced
        copy-on-write, so the probe sweep and pick() iterate a stable
        snapshot without holding the lock."""
        url = url.rstrip("/")
        with self._lock:
            for r in self.replicas:
                if r.url == url:
                    return r
            r = ReplicaState(url, set_name)
            self.replicas = self.replicas + [r]
            self.has_canary = any(
                rep.set_name == "canary" for rep in self.replicas
            )
        self.registry.counter("router/replicas_added_total").inc()
        log.info("replica %s added (fleet now %d)", url,
                 len(self.replicas))
        return r

    def remove_replica(self, url: str) -> bool:
        """Deregister a replica at runtime (the autoscaler's
        scale-down verb — callers drain first; removal itself never
        cancels anything)."""
        url = url.rstrip("/")
        with self._lock:
            keep = [r for r in self.replicas if r.url != url]
            if len(keep) == len(self.replicas):
                return False
            self.replicas = keep
            self.has_canary = any(
                r.set_name == "canary" for r in self.replicas
            )
        self.registry.counter("router/replicas_removed_total").inc()
        log.info("replica %s removed (fleet now %d)", url,
                 len(self.replicas))
        return True

    # ---------------------------------------------------------- rollout

    def _find(self, url: str) -> ReplicaState | None:
        url = url.rstrip("/")
        for r in self.replicas:
            if r.url == url:
                return r
        return None

    def drain(self, url: str) -> bool:
        """Stop dispatching to ``url`` (in-flight requests finish on
        the replica; nothing is cancelled). The rollout verb. The flag
        flips under the lock (ISSUE 14 lock-pass finding: an unlocked
        write here raced pick()'s locked eligibility read — quarantine/
        readmit always locked, drain/undrain had drifted)."""
        r = self._find(url)
        if r is None:
            return False
        with self._lock:
            r.drained = True
        log.info("replica %s drained (router-side)", r.url)
        return True

    def undrain(self, url: str) -> bool:
        r = self._find(url)
        if r is None:
            return False
        with self._lock:
            r.drained = False
            r.failures = 0
        return True

    # ------------------------------------------------------ supervision

    def quarantine(self, url: str) -> bool:
        """Rotate a replica out while the supervisor restarts it: no
        dispatch, no matter what its breaker or probe state says, until
        :meth:`readmit`."""
        r = self._find(url)
        if r is None:
            return False
        with self._lock:
            r.quarantined = True
        log.warning("replica %s quarantined (supervisor)", r.url)
        return True

    def readmit(self, url: str) -> bool:
        """Re-admit a restarted replica with a clean slate (the
        supervisor calls this only after its /health has gone green)."""
        r = self._find(url)
        if r is None:
            return False
        with self._lock:
            r.quarantined = False
            r.draining_remote = False
            r.failures = 0
            r.consec_errors = 0
            r.breaker = "closed"
            r.half_open_trial = False
        self.registry.counter("router/readmits_total").inc()
        log.info("replica %s readmitted (supervisor)", r.url)
        return True

    # --------------------------------------------------------- dispatch

    def pick(self, *, set_name: str | None = None,
             exclude: tuple = (), prompt=None,
             role: str | None = None,
             key_cache: dict | None = None) -> ReplicaState | None:
        """Least-loaded eligible replica (of ``set_name`` when the
        canary split is routing), ties broken by fewest dispatches. A
        half-open replica may be picked for exactly one trial request
        at a time (the dispatch outcome closes or re-opens its
        breaker).

        ISSUE 12: with ``prompt`` (token ids) and ``prefix_affinity``
        on, the replica already holding the longest cached chain of the
        prompt's block-aligned prefix wins — but only while its load
        score stays within ``affinity_load_gap`` of the least-loaded
        candidate, so affinity can never starve a hot replica.
        ``role`` narrows the pool to replicas serving that leg
        (mixed always qualifies)."""
        with self._lock:
            now = time.monotonic()
            pool = []
            for r in self.replicas:
                r.breaker_poll_locked(now)
                if (
                    r.eligible(self.cfg.unhealthy_after, now)
                    and r not in exclude
                    and (set_name is None or r.set_name == set_name)
                    and r.serves(role)
                ):
                    pool.append(r)
            if not pool:
                return None
            best = self._pick_locked(pool, prompt, key_cache)
            best.dispatched += 1
            if best.breaker == "half_open":
                best.half_open_trial = True
            return best

    def _pick_locked(self, pool: list, prompt,
                     key_cache: dict | None = None) -> ReplicaState:
        """Affinity-then-load choice over an eligible pool (caller
        holds the lock). ``key_cache`` ({block_size: chain keys},
        request-scoped when handle() passes one) keeps the prompt
        hashed at most once per block size per REQUEST — not per pick,
        retry, leg, and fallback."""
        least = min(pool, key=lambda r: (r.load_score(), r.dispatched))
        if not self.cfg.prefix_affinity or not prompt:
            return least
        from tensorflow_examples_tpu.serving import scheduler

        keys_by_bs = key_cache if key_cache is not None else {}
        best, best_aff = least, 0
        cap = least.load_score() + self.cfg.affinity_load_gap
        for r in pool:
            if not r.prefix_digest or r.block_size < 1:
                continue
            if r.load_score() > cap:
                continue  # affinity must not starve a hot replica
            keys = keys_by_bs.get(r.block_size)
            if keys is None:
                keys = scheduler.prompt_chain_keys(prompt, r.block_size)
                keys_by_bs[r.block_size] = keys
            aff = scheduler.affinity_blocks(keys, r.prefix_digest)
            if aff > best_aff or (
                aff == best_aff and aff > 0
                and (r.load_score(), r.dispatched)
                < (best.load_score(), best.dispatched)
            ):
                best, best_aff = r, aff
        if best_aff > 0:
            self.registry.counter("router/affinity_hits_total").inc()
        return best

    def fleet_down(self) -> bool:
        """True when NOT ONE replica is eligible AND at least one is
        hard-down — breaker open, probe-failed, or quarantined (ISSUE
        13 satellite). The fast-fail check: a total outage must shed
        each request in milliseconds, not burn ``retry_budget_s`` per
        queued request rediscovering the same dead fleet. A fleet
        that is merely drained everywhere (an operator rollout, no
        failure anywhere) is NOT an outage — that stays the plain
        no-replica 503."""
        now = time.monotonic()
        hard_down = False
        with self._lock:
            for r in self.replicas:
                r.breaker_poll_locked(now)
                if r.eligible(self.cfg.unhealthy_after, now):
                    return False
                if (
                    r.quarantined
                    or r.failures >= self.cfg.unhealthy_after
                    or r.breaker == "open"
                ):
                    hard_down = True
        return hard_down

    def _route_set(self) -> str | None:
        """Which set this request goes to (None = no split): the canary
        set receives ``canary_fraction`` of traffic, interleaved
        deterministically rather than sampled."""
        if not self.has_canary:
            return None
        with self._lock:
            n = self._req_counter
            self._req_counter += 1
        f = min(max(self.cfg.canary_fraction, 0.0), 1.0)
        return "canary" if int((n + 1) * f) != int(n * f) else "base"

    # -------------------------------------------- dispatch bookkeeping

    def _note_success(self, r: ReplicaState) -> None:
        with self._lock:
            r.completed += 1
            r.consec_errors = 0
            if r.breaker != "closed":
                r.breaker = "closed"
                r.half_open_trial = False
                self.registry.counter("router/readmits_total").inc()
                log.info(
                    "replica %s readmitted (half-open trial request "
                    "succeeded)", r.url,
                )

    def _note_failure(self, r: ReplicaState, *, transport: bool,
                      draining: bool, breaker: bool = True,
                      shed: bool = False) -> None:
        """Book one dispatch failure. ``transport`` also bumps the
        probe-failure count (the replica may be gone); ``draining``
        marks the replica's own drain instead of tripping the breaker
        (an orderly drain is not a fault); ``shed`` marks a POLICY 503
        (queue full / brownout — the replica answered, it is alive and
        healthy, just overloaded: under a flash crowd the breaker
        tripping on sheds would eject the whole fleet and turn correct
        batch-class shedding into an interactive outage, ISSUE 13);
        ``breaker=False`` for 4xx replies (the request's fault, not the
        replica's)."""
        now = time.monotonic()
        with self._lock:
            r.errors += 1
            if transport:
                r.failures += 1
            if draining:
                r.draining_remote = True
                r.half_open_trial = False
                return
            if shed:
                # An answered shed is PROOF of life: reset the breaker
                # streak (a replica alternating sheds and transport
                # errors is flapping, not dispatch-failing) and release
                # any half-open trial so the probe path can readmit.
                r.consec_errors = 0
                r.half_open_trial = False
                self.registry.counter(
                    "router/replica_sheds_total"
                ).inc()
                return
            if not breaker:
                return
            r.consec_errors += 1
            if r.breaker == "half_open":
                r.breaker = "open"
                r.open_until = now + self.cfg.eject_cooldown_s
                r.half_open_trial = False
                self.registry.counter("router/ejections_total").inc()
                log.warning(
                    "replica %s re-ejected (half-open trial failed); "
                    "next probe in %.1fs", r.url,
                    self.cfg.eject_cooldown_s,
                )
            elif (
                r.breaker == "closed"
                and r.consec_errors >= self.cfg.eject_after
            ):
                r.breaker = "open"
                r.open_until = now + self.cfg.eject_cooldown_s
                self.registry.counter("router/ejections_total").inc()
                log.warning(
                    "replica %s EJECTED after %d consecutive dispatch "
                    "failures (circuit breaker open, half-open probe "
                    "in %.1fs)", r.url, r.consec_errors,
                    self.cfg.eject_cooldown_s,
                )

    def _send_to(self, r: ReplicaState, body: dict,
                 kind: str) -> tuple[int, dict]:
        """One real dispatch to one replica, with breaker bookkeeping."""
        status, reply = post_json(
            r.url + "/" + kind, body, self.cfg.request_timeout_s
        )
        if status == 200:
            self._note_success(r)
        elif status in (0, 503):
            self._note_failure(
                r, transport=(status == 0),
                draining=bool(reply.get("draining")),
                # A policy shed (queue/brownout) is breaker-exempt; a
                # KV-exhaustion shed is NOT — a wedged-full pool sheds
                # forever and must still be ejectable.
                shed=(
                    status == 503
                    and bool(reply.get("shed"))
                    and not reply.get("exhausted")
                ),
            )
        else:
            # The replica ANSWERED (400/404/500/504): never re-run the
            # request elsewhere. 5xx still counts against the breaker —
            # a replica answering 500s is failing; a 4xx is the
            # request's own fault.
            self._note_failure(
                r, transport=False, draining=False,
                breaker=(status >= 500),
            )
        return status, reply

    def _dispatch(self, primary: ReplicaState, body: dict, kind: str,
                  set_name: str | None, tried: list, tr=None,
                  parent_span_id: str | None = None
                  ) -> tuple[int, dict]:
        """One dispatch attempt — hedged when ``hedge_after_s`` is set:
        if the primary has not answered by the hedge deadline, the
        request is sent again to another replica; the first 200 wins
        and the loser is abandoned (its eventual reply is discarded;
        idempotent-by-seeding makes the duplicate execution harmless).
        Any hedge replica used is appended to ``tried``."""
        if self.cfg.hedge_after_s <= 0:
            return self._send_to(primary, body, kind)
        results: queue.Queue = queue.Queue()

        def run(rep):
            results.put((rep, *self._send_to(rep, body, kind)))

        threading.Thread(
            target=run, args=(primary,), name="router-dispatch",
            daemon=True,
        ).start()
        try:
            _, status, reply = results.get(
                timeout=self.cfg.hedge_after_s
            )
            return status, reply  # answered before the hedge deadline
        except queue.Empty:
            pass
        hedge = self.pick(set_name=set_name, exclude=tuple(tried))
        if hedge is None and set_name is not None:
            hedge = self.pick(exclude=tuple(tried))
        if hedge is None:
            _, status, reply = results.get()  # nothing to hedge with
            return status, reply
        tried.append(hedge)
        self.registry.counter("router/hedges_total").inc()
        self.registry.counter("router/dispatched_total").inc()
        t_hedge = time.monotonic()
        if tr is not None:
            tr.flags.add("hedged")
        threading.Thread(
            target=run, args=(hedge,), name="router-hedge", daemon=True,
        ).start()

        def hedge_span(won: bool):
            # The hedge leg is router-side bookkeeping: its span hangs
            # off the ATTEMPT that spawned it, tagged with whether the
            # hedge's reply was the one that answered the client.
            if tr is not None:
                self.recorder.add_span(
                    tr.trace_id, tracing_mod.close_span(
                        "hedge", t_hedge, parent_id=parent_span_id,
                        tags={"replica": hedge.url, "won": won},
                    )
                )

        first_failure = None
        for arrival in range(2):
            rep, status, reply = results.get()
            if status == 200:
                if arrival == 0:
                    # The slower dispatch is still in flight: abandon
                    # it — its reply is discarded on arrival (only
                    # breaker bookkeeping runs).
                    self.registry.counter(
                        "router/hedge_cancelled_total"
                    ).inc()
                if rep is hedge:
                    self.registry.counter(
                        "router/hedge_wins_total"
                    ).inc()
                hedge_span(won=rep is hedge)
                return status, reply
            if first_failure is None:
                first_failure = (status, reply)
        hedge_span(won=False)
        return first_failure

    # ------------------------------------- disaggregated roles (ISSUE 12)

    @staticmethod
    def _clean_prompt(body: dict):
        """The request's token ids when hashable for affinity/handoff
        (a 'text' body has no ids until a replica tokenizes it)."""
        prompt = body.get("prompt")
        if (
            isinstance(prompt, list) and prompt
            and all(isinstance(t, int) and not isinstance(t, bool)
                    for t in prompt)
        ):
            return prompt
        return None

    def _disagg_ready(self) -> bool:
        """True when the fleet has BOTH an eligible prefill-role and an
        eligible decode-role replica — the topology the handoff path
        exists for. A dead prefill replica flips this off, and generate
        traffic falls back to the full path on whoever is left."""
        now = time.monotonic()
        with self._lock:
            roles = {
                r.role for r in self.replicas
                if r.eligible(self.cfg.unhealthy_after, now)
            }
        return "prefill" in roles and "decode" in roles

    def _leg(self, body: dict, kind: str, role: str | None,
             prompt, key_cache: dict | None = None,
             tr=None) -> dict | None:
        """One handoff leg with the same bounded-retry discipline as
        the full path (different replica per attempt, leg-scoped wall
        budget); None when the leg cannot complete — the caller falls
        back to a full /generate, which is always safe because
        generation is a pure function of (params, prompt, seed).

        Deliberately SIMPLER than handle()'s loop: no cross-set
        fallback and no wait-out-and-rescan on an empty pool — a leg
        that cannot find a role-holder right now should not burn the
        request's budget waiting for one, because the full path IS the
        retry continuation and every replica can serve it."""
        reg = self.registry
        t0 = time.monotonic()
        tried: list[ReplicaState] = []
        attempts = 0
        while True:
            within = time.monotonic() - t0 < self.cfg.retry_budget_s
            r = self.pick(
                prompt=prompt, role=role, exclude=tuple(tried),
                key_cache=key_cache,
            )
            if r is None:
                return None
            tried.append(r)
            reg.counter("router/dispatched_total").inc()
            send = body
            span_id = None
            t_att = time.monotonic()
            if tr is not None:
                # Same per-attempt discipline as the full path: each
                # leg attempt gets its own span and hands the replica
                # a context parented under it, so a handoff trace
                # shows prefill and resume legs side by side with
                # their replica-side segments nested inside.
                span_id = tracing_mod.new_span_id()
                send = dict(body)
                send["trace"] = {
                    "trace_id": tr.trace_id,
                    "parent_span_id": span_id,
                    "sampled": True,
                }
            status, reply = self._send_to(r, send, kind)
            if tr is not None:
                rspans = reply.pop("trace_spans", None) \
                    if isinstance(reply, dict) else None
                if rspans:
                    self.recorder.ingest(
                        tr.trace_id, rspans, parent_id=span_id
                    )
                self.recorder.add_span(
                    tr.trace_id, tracing_mod.close_span(
                        f"{kind}_leg", t_att, parent_id=tr.root_id,
                        span_id=span_id, tags={
                            "replica": r.url,
                            "role": role or "any",
                            "attempt": attempts + 1,
                            "status": int(status),
                        },
                    )
                )
            if status == 200:
                return reply
            if (
                status in (0, 503)
                and attempts < self.cfg.max_retries
                and within
            ):
                attempts += 1
                reg.counter("router/retries_total").inc()
                if tr is not None:
                    tr.flags.add("retried")
                if status == 0:
                    # The role-holder died mid-leg: in-flight failover,
                    # same accounting as the full path.
                    reg.counter("router/failovers_total").inc()
                    if tr is not None:
                        tr.flags.add("failover")
                backoff = self.cfg.retry_backoff_s * (2 ** (attempts - 1))
                remaining = self.cfg.retry_budget_s - (
                    time.monotonic() - t0
                )
                if backoff > 0 and remaining > 0:
                    time.sleep(min(backoff, remaining))
                continue
            return None

    def _decode_cached_tokens(self, prompt, key_cache: dict) -> int:
        """Digest exchange for the streaming delta handoff (ISSUE 15
        satellite): how many leading prompt tokens EVERY eligible
        resume-side replica already caches (per its last probe) — the
        skip that is safe whichever replica the affinity-routed resume
        leg lands on. Conservative by construction (the minimum over
        the tier); the importer still validates its cache actually
        covers the skip (probe staleness, bloom false positives) and a
        mismatch 400 falls back to the full path, never a torn cache."""
        from tensorflow_examples_tpu.serving import scheduler

        now = time.monotonic()
        with self._lock:
            candidates = [
                r for r in self.replicas
                if r.eligible(self.cfg.unhealthy_after, now)
                and r.serves("decode")
            ]
            best: int | None = None
            for r in candidates:
                if r.block_size < 1 or not r.prefix_digest:
                    return 0
                keys = key_cache.get(r.block_size)
                if keys is None:
                    keys = scheduler.prompt_chain_keys(
                        prompt, r.block_size
                    )
                    key_cache[r.block_size] = keys
                tokens = scheduler.affinity_blocks(
                    keys, r.prefix_digest
                ) * r.block_size
                best = tokens if best is None else min(best, tokens)
        return best or 0

    def _handle_disagg(self, body: dict, prompt,
                       key_cache: dict | None = None,
                       tr=None) -> tuple[int, dict] | None:
        """Prefill/decode handoff: run the prompt on a prefill-role
        replica (affinity applies — that is where the prefix caches
        live), ship the returned KV pages to a decode-role replica's
        /resume, and reply its stream. Replica-measured ttft_s/total_s
        both gain the prefill leg's wall so client-facing TPOT
        ((total - ttft) / (n - 1)) stays a pure decode number. None on
        any failure — the caller replays the request through the full
        path (token-identical by seeding), so a dead role-holder costs
        a failover, never a request."""
        # Streaming delta (ISSUE 15): tell the prefill leg how many
        # leading tokens the decode tier already caches — those pages
        # never enter the wire. The resume body stays untouched (the
        # skip is encoded in the pages' own start_block meta).
        pbody = body
        skip = self._decode_cached_tokens(
            prompt, key_cache if key_cache is not None else {}
        )
        if skip:
            pbody = dict(body)
            pbody["skip_tokens"] = skip
        preply = self._leg(pbody, "prefill", "prefill", prompt,
                           key_cache, tr)
        if (
            not isinstance(preply, dict)
            or not isinstance(preply.get("pages"), dict)
            or not isinstance(preply.get("first_token"), int)
        ):
            return None
        res_body = dict(body)
        res_body["pages"] = preply["pages"]
        res_body["first_token"] = preply["first_token"]
        # The resume leg is affinity-routed too: importers publish the
        # prompt into their own prefix cache, so repeated handoffs of a
        # shared prompt park on the decode replica already holding it
        # (one copy, cold-tail-only scatter) instead of spreading N
        # copies across the decode tier.
        dreply = self._leg(res_body, "resume", "decode", prompt,
                           key_cache, tr)
        if not isinstance(dreply, dict):
            return None
        self.registry.counter("router/handoffs_total").inc()
        if skip:
            # Counted only on a COMPLETED handoff: a fallback after a
            # stale-digest 400 saved nothing, and the "tokens kept off
            # the wire" metric must not overstate itself.
            self.registry.counter(
                "router/handoff_delta_tokens_total"
            ).inc(skip)
        pre_total = preply.get("total_s")
        if isinstance(pre_total, (int, float)):
            for key in ("ttft_s", "total_s"):
                if isinstance(dreply.get(key), (int, float)):
                    dreply[key] = dreply[key] + float(pre_total)
        return 200, dreply

    # ------------------------------------------------------ entry point

    def handle(self, body: dict, *, kind: str) -> tuple[int, dict]:
        """Dispatch one generate/classify request: least-loaded pick
        with prefix affinity, bounded retry with backoff on
        503/transport failure (different replica of the same set,
        within the per-request wall budget). A transport failure
        mid-request is an in-flight failover: the re-dispatch replays
        the request from the prompt on another replica,
        token-identical by the per-request seeding. On a fleet with
        disaggregated roles, generate requests route through the
        prefill->decode handoff first (canary split and hedging apply
        to the full path only), falling back to the full path whenever
        a leg cannot complete.

        ISSUE 16 control plane: generate bodies may carry the client
        fields ``request_id`` (idempotency key) and ``resume_from`` (a
        committed-token offset) — both stripped before dispatch
        (replica frontends reject unknown fields). With a journal
        attached, a duplicated ``request_id`` inside the dedupe window
        returns the ORIGINAL tokens (``router/dedup_hits_total``, no
        second generation); every accepted token-id request appends an
        intent record before dispatch and a progress+done record on
        completion; ``resume_from > 0`` answers with the remainder of
        the SAME stream (journal dedupe hit, or replay-and-skip — the
        re-dispatch is token-identical by seeding, so slicing off the
        committed prefix IS the original stream's tail). A router
        whose lease is fenced (a promoted standby holds a newer token)
        refuses every dispatch with a retryable 503.

        ISSUE 19 probes: a body carrying ``"probe": true`` (the
        synthetic canary prober's tag, stripped before dispatch) rides
        the NORMAL dispatch path — same compiled replica code, same
        retry machinery — but is excluded from the organic request
        accounting: it never touches the journal (no dedupe-window
        entry, no tenant intent record), never counts in
        ``router/requests_total``, and never feeds the AlertEngine's
        organic rules (the prober reports its own results through
        ``observe_probe``). Probe traffic counts only under the
        ``probe/`` instruments."""
        reg = self.registry
        is_probe = False
        if kind == "generate" and "probe" in body:
            body = dict(body)  # never mutate the caller's dict
            is_probe = bool(body.pop("probe"))
        if is_probe:
            reg.counter("probe/router_requests_total").inc()
        else:
            reg.counter("router/requests_total").inc()
        t0 = time.monotonic()
        request_id: str | None = None
        resume_from = 0
        if kind == "generate" and (
            "request_id" in body or "resume_from" in body
        ):
            body = dict(body)  # never mutate the caller's dict
            request_id = body.pop("request_id", None)
            resume_from = body.pop("resume_from", 0)
            if request_id is not None and (
                not isinstance(request_id, str) or not request_id
            ):
                return 400, {
                    "error": "'request_id' must be a non-empty string"
                }
            if (
                isinstance(resume_from, bool)
                or not isinstance(resume_from, int)
                or resume_from < 0
            ):
                return 400, {
                    "error": "'resume_from' must be a non-negative "
                             "committed-token offset"
                }
        # Per-request tracing (ISSUE 18): accept the client's wire
        # context or mint one; the "trace" body field is the router's
        # to own from here (each dispatch attempt re-issues it with
        # that attempt's span as the parent).
        tr: _TraceState | None = None
        if kind == "generate":
            wire = body.get("trace")
            if "trace" in body:
                body = dict(body)
                body.pop("trace")
            if not isinstance(wire, dict):
                wire = None
            ctx = self.recorder.new_context(wire)
            parent = (wire or {}).get("parent_span_id")
            tr = _TraceState(
                ctx.trace_id,
                tracing_mod.new_span_id(),
                parent if isinstance(parent, str) and parent else None,
                body.get("slo")
                if body.get("slo") in ("interactive", "batch")
                else "interactive",
            )
        if self.fenced():
            # Split-brain pin (ISSUE 16): a stalled-then-revived
            # primary must never dispatch against the fleet a promoted
            # standby now owns. Retryable — the client's next attempt
            # lands on the active router.
            reg.counter("router/fenced_dispatch_total").inc()
            reply = {
                "error": "router fenced: a newer lease token is "
                         "active (standby takeover)",
                "fenced": True, "retry": True, "shed": True,
            }
            reg.histogram("router/e2e").record(time.monotonic() - t0)
            self._trace_finish(tr, 503, reply, t0, probe=is_probe)
            return 503, reply
        # Probe exclusion (ISSUE 19): a canary probe must never enter
        # the dedupe window or leave tenant intent records — a fleet
        # restart would otherwise replay synthetic traffic.
        journal = (
            self.journal if kind == "generate" and not is_probe
            else None
        )
        if journal is not None and request_id is not None:
            hit = journal.lookup(request_id)
            if hit is not None:
                # Idempotency-key dedupe: the original stream answers
                # the retry — no second generation burned.
                reg.counter("router/dedup_hits_total").inc()
                tokens = list(hit["tokens"])
                reply = {
                    "tokens": tokens[resume_from:],
                    "request_id": request_id,
                    "dedup": True,
                }
                if resume_from:
                    reg.counter("router/resumed_streams_total").inc()
                    reply["resumed"] = True
                    reply["resume_from"] = resume_from
                reg.histogram("router/e2e").record(
                    time.monotonic() - t0
                )
                if tr is not None:
                    # The stitch (ISSUE 18): the journal's done record
                    # carries the ORIGINAL request's trace_id — adopt
                    # it, so the dedupe fast path's spans JOIN that
                    # trace (across routers too: a takeover successor
                    # shares the journal) instead of forking a new one.
                    self.recorder.add_span(
                        tr.trace_id, tracing_mod.close_span(
                            "dedupe_hit", t0, parent_id=tr.root_id,
                            tags={"request_id": request_id},
                        )
                    )
                    orig_tid = hit.get("trace_id")
                    if isinstance(orig_tid, str) and orig_tid:
                        self.recorder.adopt(tr.trace_id, orig_tid)
                        tr.trace_id = orig_tid
                self._trace_finish(tr, 200, reply, t0, probe=is_probe)
                return 200, reply
        if self.fleet_down():
            # Fast-fail (ISSUE 13 satellite): a fleet-wide outage
            # sheds NOW — no per-request retry-budget burn, no backoff
            # loop rediscovering the same dead fleet. Its own counter
            # so an operator can tell "total outage" from "one replica
            # briefly unpickable".
            reg.counter("router/fleet_down_total").inc()
            reply = {
                "error": "no healthy replica (fleet-wide outage)",
                "retry": True, "shed": True, "fleet_down": True,
            }
            self._set_stats["base"].record(503, reply)
            reg.histogram("router/e2e").record(time.monotonic() - t0)
            self._trace_finish(tr, 503, reply, t0, probe=is_probe)
            return 503, reply
        prompt = self._clean_prompt(body)
        if journal is not None and prompt is None:
            # A 'text' body has no token ids until a replica tokenizes
            # it — not replayable, so not journaled (dedupe above still
            # applied if the client keyed it).
            journal = None
        if journal is not None:
            if request_id is None:
                request_id = f"auto-{uuid.uuid4().hex[:12]}"
            if not journal.has_intent(request_id):
                # Accepted = journaled, BEFORE dispatch: if this router
                # dies mid-request, the successor's replay finds the
                # intent and finishes the stream — and the stamped
                # trace_id (ISSUE 18) makes that replay continue THIS
                # trace rather than start one of its own.
                journal.append_intent(
                    request_id, body,
                    trace_id=tr.trace_id if tr is not None else None,
                )
        # killrouter@T counts GENERATE dispatches only (the fault
        # grammar's spec): classify/score traffic must not advance T.
        feng = faults_mod.serve_active() if kind == "generate" else None
        if feng is not None and feng.router_dispatch():
            # killrouter@T just hard-aborted THIS router (ISSUE 16
            # satellite): the client's connection is already reset —
            # leave the intent incomplete for the successor's journal
            # replay instead of racing a dispatch against takeover.
            reply = {
                "error": "router killed (injected fault)", "retry": True,
            }
            self._trace_finish(tr, 503, reply, t0, probe=is_probe)
            return 503, reply
        status, reply = self._handle_dispatch(body, kind, t0, prompt, tr)
        if status == 200 and journal is not None and isinstance(
            reply.get("tokens"), list
        ):
            # Completion records — skipped once fenced: the successor
            # owns the journal now, and it will (re)complete the
            # intent itself. Duplicate done records for the same id
            # would be harmless (identical by seeding) but one writer
            # is one writer.
            if not self.fenced():
                journal.append_progress(
                    request_id, len(reply["tokens"])
                )
                journal.append_done(
                    request_id, reply["tokens"], status,
                    trace_id=tr.trace_id if tr is not None else None,
                )
        if status == 200 and isinstance(reply.get("tokens"), list):
            if resume_from:
                # Replay-and-skip (reusing the PR 9 failover
                # machinery): the re-dispatched stream is
                # token-identical by seeding, so the reconnecting
                # client gets the remainder of the SAME stream.
                reg.counter("router/resumed_streams_total").inc()
                reply["tokens"] = reply["tokens"][resume_from:]
                reply["resumed"] = True
                reply["resume_from"] = resume_from
            if request_id is not None:
                reply.setdefault("request_id", request_id)
        self._trace_finish(tr, status, reply, t0, probe=is_probe)
        return status, reply

    def _trace_finish(self, tr, status: int, reply: dict,
                      t0: float, *, probe: bool = False) -> None:
        """Close the request's root span and hand the trace to the
        tail sampler (ISSUE 18). Every handle() exit path for a traced
        request funnels through here exactly once — including the
        dedupe fast path, where finish() MERGES into the original
        request's stored trace instead of forking a new one."""
        if tr is None:
            return
        e2e = time.monotonic() - t0
        self.recorder.add_span(
            tr.trace_id, tracing_mod.close_span(
                "request", t0, span_id=tr.root_id,
                parent_id=tr.parent_id, tags={"status": int(status)},
            )
        )
        if reply.get("dedup"):
            tr.flags.add("deduped")
        if reply.get("resumed"):
            tr.flags.add("resumed")
        self.recorder.finish(
            tr.trace_id, slo=tr.slo, status=int(status), e2e_s=e2e,
            flags=tr.flags,
        )
        self.recorder.exemplars.record("router/e2e", e2e, tr.trace_id)
        reply.setdefault("trace_id", tr.trace_id)
        if not probe:
            # Feed the SLO engine (ISSUE 19): every organic request's
            # end-to-end latency and error outcome consumes (or
            # doesn't) its class's error budget; the trace_id rides
            # along so a firing alert can name its worst offender.
            # Engine lock is a leaf — no router lock is held here.
            self.alerts.observe(
                tr.slo, e2e_s=e2e, error=status >= 500,
                trace_id=tr.trace_id,
            )

    def _handle_dispatch(self, body: dict, kind: str, t0: float,
                         prompt, tr=None) -> tuple[int, dict]:
        """The dispatch core handle() wraps: disagg handoff first,
        then the canary-aware bounded-retry loop."""
        reg = self.registry
        key_cache: dict = {}  # prompt chain keys, hashed once per request
        if kind == "generate" and prompt is not None \
                and self._disagg_ready():
            out = self._handle_disagg(body, prompt, key_cache, tr)
            if out is not None:
                status, reply = out
                self._set_stats["base"].record(status, reply)
                self.registry.histogram("router/e2e").record(
                    time.monotonic() - t0
                )
                return status, reply
            reg.counter("router/handoff_fallbacks_total").inc()
        # The canary interleave slot is claimed only by requests that
        # actually reach the full path — a completed handoff records
        # under "base" without consuming one, so the canary set still
        # receives its exact fraction of full-path traffic.
        set_name = self._route_set()
        tried: list[ReplicaState] = []
        attempts = 0
        while True:
            within_budget = (
                time.monotonic() - t0 < self.cfg.retry_budget_s
            )
            r = self.pick(
                set_name=set_name, exclude=tuple(tried), prompt=prompt,
                key_cache=key_cache,
            )
            if r is None and tried and set_name is not None:
                # The preferred set has no further replica: the retry
                # may cross sets rather than fail the request (the
                # canary compare just loses one sample).
                r = self.pick(exclude=tuple(tried), prompt=prompt,
                              key_cache=key_cache)
            if r is None:
                if self.fleet_down():
                    # Mid-retry total outage (e.g. the last survivor's
                    # breaker just opened): shed immediately — the
                    # wait-and-rescan below exists for TRANSIENT
                    # ineligibility, not a dead fleet.
                    reg.counter("router/fleet_down_total").inc()
                    status, reply = 503, {
                        "error": "no healthy replica (fleet-wide "
                                 "outage)",
                        "retry": True, "shed": True, "fleet_down": True,
                    }
                    break
                if (
                    tried
                    and attempts <= self.cfg.max_retries
                    and within_budget
                ):
                    # Mid-failover with every replica momentarily
                    # ineligible (e.g. the supervisor is restarting
                    # one and the rest are shedding): wait out a slice
                    # of the budget and rescan the whole pool instead
                    # of failing a request we already accepted.
                    time.sleep(
                        min(0.05, self.cfg.retry_budget_s / 20)
                    )
                    tried = []
                    continue
                reg.counter("router/no_replica_total").inc()
                status, reply = 503, {
                    "error": "no live replica available", "retry": True,
                    "shed": True,
                }
                break
            tried.append(r)
            reg.counter("router/dispatched_total").inc()
            send = body
            span_id = None
            t_att = time.monotonic()
            if tr is not None:
                # Each attempt gets its OWN span and re-issues the
                # wire context with that span as the parent, so the
                # replica's spans nest under the attempt that actually
                # carried them — a failover trace shows both the dead
                # dispatch and the one that answered.
                span_id = tracing_mod.new_span_id()
                send = dict(body)
                send["trace"] = {
                    "trace_id": tr.trace_id,
                    "parent_span_id": span_id,
                    "sampled": True,
                }
            status, reply = self._dispatch(
                r, send, kind, set_name, tried, tr=tr,
                parent_span_id=span_id,
            )
            if tr is not None:
                rspans = reply.pop("trace_spans", None) \
                    if isinstance(reply, dict) else None
                if rspans:
                    self.recorder.ingest(
                        tr.trace_id, rspans, parent_id=span_id
                    )
                outcome = "ok" if status == 200 else (
                    "transport" if status == 0 else str(status)
                )
                self.recorder.add_span(
                    tr.trace_id, tracing_mod.close_span(
                        "dispatch", t_att, parent_id=tr.root_id,
                        span_id=span_id, tags={
                            "replica": r.url,
                            "set": r.set_name or "base",
                            "attempt": attempts + 1,
                            "status": int(status),
                            "outcome": outcome,
                        },
                    )
                )
            if status == 200:
                break
            if status in (0, 503):
                attempts += 1
                within_budget = (
                    time.monotonic() - t0 < self.cfg.retry_budget_s
                )
                if attempts <= self.cfg.max_retries and within_budget:
                    reg.counter("router/retries_total").inc()
                    if tr is not None:
                        tr.flags.add("retried")
                    if status == 0:
                        # The replica died with the request possibly
                        # mid-decode: replay it from the prompt
                        # elsewhere.
                        reg.counter("router/failovers_total").inc()
                        if tr is not None:
                            tr.flags.add("failover")
                    backoff = self.cfg.retry_backoff_s * (
                        2 ** (attempts - 1)
                    )
                    remaining = self.cfg.retry_budget_s - (
                        time.monotonic() - t0
                    )
                    if backoff > 0 and remaining > 0:
                        time.sleep(min(backoff, remaining))
                    continue
                status = 503
                break
            # 400/404/500/504: the replica processed (or rejected) the
            # request — never re-run it elsewhere.
            break
        stats = self._set_stats[
            (tried[-1].set_name if tried else None) or set_name or "base"
        ]
        stats.record(status, reply)
        self.registry.histogram("router/e2e").record(
            time.monotonic() - t0
        )
        return status, reply

    # -------------------------------------------- journal replay (ISSUE 16)

    def replay_incomplete(self) -> int:
        """Drain the journal's accepted-but-unfinished intents through
        the fleet (the restart/takeover verb): each incomplete intent
        re-dispatches as an ordinary generate — token-identical to
        what the dead router would have served, because generation is
        a pure function of (params, prompt, seed) — and its done
        record closes the intent. Returns the number replayed."""
        if self.journal is None:
            return 0
        replayed = 0
        for intent in self.journal.incomplete():
            body = {
                "prompt": intent["prompt"],
                "max_new_tokens": intent["max_new_tokens"],
                "temperature": intent["temperature"],
                "top_k": intent["top_k"],
                "seed": intent["seed"],
                "slo": intent["slo"],
                "request_id": intent["request_id"],
            }
            if intent.get("trace_id"):
                # Continue the dead router's trace (ISSUE 18): the
                # replay's spans MERGE into the original trace_id the
                # intent carries, so a takeover-survived request reads
                # as one tree across both routers.
                body["trace"] = {
                    "trace_id": intent["trace_id"], "sampled": True,
                }
            status, _ = self.handle(body, kind="generate")
            if status == 200:
                replayed += 1
                self.registry.counter(
                    "router/journal_replayed_total"
                ).inc()
            else:
                log.warning(
                    "journal replay of %s failed with status %d",
                    intent["request_id"], status,
                )
        return replayed

    # ------------------------------------------------------------ stats

    def canary_records(self) -> tuple[dict, dict]:
        """(base record, canary record) — two ``serve_router_set``
        docs ``tools/run_diff.py`` compares directly (its load_record
        accepts bench records; the serving GATE_KEYS rank TTFT/TPOT/
        prefix-hit regressions first)."""
        return (
            self._set_stats["base"].record_doc("base"),
            self._set_stats["canary"].record_doc("canary"),
        )

    def stats_line(self) -> dict:
        """A schema-v6 ``kind="serving"`` line for the router process:
        fleet-aggregated serving object plus the v6 router fields."""
        counters = {
            k: v for k, v in self.registry.counter_values().items()
            if k.startswith("router/")
        }
        gauges = {
            k: v for k, v in self.registry.gauge_values().items()
            if k.startswith("router/")
        }
        # Taken OUTSIDE self._lock: the recorder has its own lock and
        # nesting the two would order them router->recorder here while
        # the dispatch path orders recorder-only — keep them disjoint.
        tstats = self.recorder.stats()
        # Same discipline for the SLO engine (ISSUE 19): evaluate on
        # the stats cadence (the prober also evaluates on its own
        # tick), then read the v14 summary — engine lock is a leaf,
        # never nested inside self._lock. The time-series store
        # samples here too: one stats tick = one ring sample.
        self.alerts.evaluate()
        astats = self.alerts.stats()
        self.series.sample()
        with self._lock:
            # One consistent fleet snapshot: the probe loop rewrites
            # these fields mid-sweep, and a line aggregated across a
            # torn sweep would pair one replica's new occupancy with
            # another's stale brownout level (ISSUE 14 lock pass).
            probed = [r for r in self.replicas if r.probed]
            occ = [r.kv_occupancy for r in probed]
            serving = {
                "active_requests": int(
                    sum(r.active_requests for r in probed)
                ),
                "queue_depth": int(sum(r.queue_depth for r in probed)),
                "slots": int(sum(r.slots for r in probed)),
                "kv_occupancy": (sum(occ) / len(occ)) if occ else 0.0,
                "post_warmup_recompiles": int(
                    sum(r.post_warmup_recompiles for r in probed)
                ),
                "draining": 0,
                "replicas": len(self.replicas),
                "router_dispatched": int(
                    counters.get("router/dispatched_total", 0)
                ),
                "router_retries": int(
                    counters.get("router/retries_total", 0)
                ),
                "router_no_replica": int(
                    counters.get("router/no_replica_total", 0)
                ),
                # --- v7 (ISSUE 10): fault-tolerance counters ---
                "router_ejections": int(
                    counters.get("router/ejections_total", 0)
                ),
                "router_readmits": int(
                    counters.get("router/readmits_total", 0)
                ),
                "router_hedges": int(
                    counters.get("router/hedges_total", 0)
                ),
                "router_failovers": int(
                    counters.get("router/failovers_total", 0)
                ),
                "router_restarts": int(
                    counters.get("router/restarts_total", 0)
                ),
                # --- v9 (ISSUE 12): fleet-summed prefix-cache summary ---
                "prefix_blocks": int(
                    sum(r.prefix_blocks for r in probed)
                ),
                "prefix_chains": int(
                    sum(r.prefix_chains for r in probed)
                ),
                # --- v10 (ISSUE 13): fleet overload view — the WORST
                # replica's brownout level (one browning-out replica is an
                # incident, not an average), summed transitions, and
                # whether any affinity digest is capped.
                "brownout_level": int(
                    max((r.brownout_level for r in probed), default=0)
                ),
                "brownout_transitions": int(
                    sum(r.brownout_transitions for r in probed)
                ),
                "digest_truncated": int(
                    any(r.digest_truncated for r in probed)
                ),
                # --- v12 (ISSUE 16): control-plane durability — the
                # journal's append count, warm-standby takeovers and the
                # last takeover's detection-to-serving wall, resumed
                # client streams, and idempotency-key dedupe hits.
                "journal_appends": int(
                    counters.get("router/journal_appends_total", 0)
                ),
                "takeover_total": int(
                    counters.get("router/takeover_total", 0)
                ),
                "resumed_streams": int(
                    counters.get("router/resumed_streams_total", 0)
                ),
                "dedup_hits": int(
                    counters.get("router/dedup_hits_total", 0)
                ),
                "takeover_latency_s": float(
                    gauges.get("router/takeover_latency_s", 0.0)
                ),
                # --- v13 (ISSUE 18): tail-sampled tracing — kept vs
                # dropped trace counts, the resulting coverage
                # fraction, and how many kept traces were kept for
                # being SLOW (the p99-attribution feedstock).
                "traces_kept": tstats["traces_kept"],
                "traces_dropped": tstats["traces_dropped"],
                "trace_coverage": tstats["trace_coverage"],
                "slow_trace_count": tstats["slow_trace_count"],
                # --- v14 (ISSUE 19): the SLO engine's alerting
                # summary — rules currently firing, the worst rule's
                # error budget remaining, the canary prober's rolling
                # success rate, and cumulative firing transitions.
                "alerts_firing": astats["alerts_firing"],
                "error_budget_remaining": astats[
                    "error_budget_remaining"
                ],
                "probe_success_rate": astats["probe_success_rate"],
                "alert_count": astats["alert_count"],
            }
        return {
            "schema_version": schema.SERVING_SCHEMA_VERSION,
            "kind": "serving",
            "step": serving["router_dispatched"],
            "time_unix": time.time(),
            "session_start_unix": self._start_unix,
            "host": 0,
            "metrics": {},
            "counters": counters,
            "gauges": gauges,
            "derived": {},
            "serving": serving,
        }

    def replica_snapshots(self) -> list[dict]:
        """Per-replica state docs for ``/replicas`` — each snapshot
        taken under the lock so the probe loop cannot tear it
        mid-render (ISSUE 14 lock pass)."""
        with self._lock:
            return [r.snapshot_locked() for r in self.replicas]

    def health_payload(self) -> tuple[int, dict]:
        with self._lock:
            eligible = [
                r for r in self.replicas
                if r.eligible(self.cfg.unhealthy_after)
            ]
            body = {
                "ok": bool(eligible),
                "role": "router",
                "replicas": len(self.replicas),
                "eligible": len(eligible),
                "sets": sorted({r.set_name for r in self.replicas}),
                # Fleet overload view (ISSUE 13): worst replica's
                # brownout level + fleet-summed transition count, and
                # the fast-fail outage counter — the operator's "is the
                # fleet browning out or down" one-liner.
                "brownout_max": int(max(
                    (r.brownout_level for r in self.replicas), default=0
                )),
                "brownout_transitions": int(sum(
                    r.brownout_transitions for r in self.replicas
                )),
                "digest_truncated": bool(any(
                    r.digest_truncated for r in self.replicas
                )),
            }
        body["fleet_down_total"] = int(
            self.registry.counter_values().get(
                "router/fleet_down_total", 0
            )
        )
        return (200 if body["ok"] else 503), body


class _RouterHTTPServer(http.server.ThreadingHTTPServer):
    # The fleet's front door: a flash crowd's connection burst must
    # reach the dispatcher (which sheds by POLICY), not bounce off the
    # stdlib's 5-entry accept backlog as transport failures (ISSUE 13).
    request_queue_size = 128

    # In-flight client connections, tracked so RouterFrontend.abort()
    # can RESET them (the killrouter fault's PR-9 semantics: the
    # router dies like a SIGKILLed process, clients observe transport
    # failures — never a polite 503). Normal shutdown never touches
    # this.
    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.conn_lock = threading.Lock()
        self.live_connections: set = set()

    def process_request(self, request, client_address):
        with self.conn_lock:
            self.live_connections.add(request)
        super().process_request(request, client_address)

    def shutdown_request(self, request):
        with self.conn_lock:
            self.live_connections.discard(request)
        super().shutdown_request(request)


class RouterFrontend:
    """The router's HTTP surface: proxied POST /generate //classify,
    GET /metrics //health //replicas //window //trace/{id} (+ /canary
    with a canary set), admin POST /drain //undrain
    {"replica": url}."""

    def __init__(self, router: Router, *, port: int = 0,
                 bind_host: str = ""):
        self.router = router
        self.requested_port = int(port)
        self.bind_host = bind_host
        self.port: int | None = None
        self._httpd: http.server.ThreadingHTTPServer | None = None
        self._thread: threading.Thread | None = None
        self._lock = threading.Lock()

    def start(self) -> "RouterFrontend":
        router = self.router

        class Handler(http.server.BaseHTTPRequestHandler):
            def _send(self, status, content_type, payload: bytes):
                self.send_response(status)
                self.send_header("Content-Type", content_type)
                self.send_header("Content-Length", str(len(payload)))
                self.end_headers()
                self.wfile.write(payload)

            def _send_json(self, status, obj):
                self._send(
                    status,
                    "application/json",
                    (json.dumps(json_safe(obj)) + "\n").encode(),
                )

            def _body(self):
                try:
                    n = int(self.headers.get("Content-Length", 0))
                except ValueError:
                    return None
                if n < 0 or n > _MAX_BODY:
                    return None
                try:
                    return json.loads(self.rfile.read(n) or b"{}")
                except json.JSONDecodeError:
                    return None

            def do_POST(self):  # noqa: N802 - http.server contract
                path = self.path.split("?", 1)[0].rstrip("/")
                try:
                    body = self._body()
                    if body is None or not isinstance(body, dict):
                        self._send_json(
                            400, {"error": "malformed JSON body"}
                        )
                        return
                    if path in ("/generate", "/classify"):
                        status, reply = router.handle(
                            body, kind=path[1:]
                        )
                        self._send_json(status, reply)
                    elif path in ("/drain", "/undrain"):
                        url = body.get("replica", "")
                        op = (
                            router.drain if path == "/drain"
                            else router.undrain
                        )
                        if not isinstance(url, str) or not op(url):
                            self._send_json(
                                404,
                                {"error": f"unknown replica {url!r}"},
                            )
                        else:
                            self._send_json(
                                200, {"ok": True, "replica": url}
                            )
                    else:
                        self._send_json(
                            404,
                            {"error": "POST: /generate /classify "
                                      "/drain /undrain"},
                        )
                except ConnectionError:
                    pass

            def do_GET(self):  # noqa: N802 - http.server contract
                path = self.path.split("?", 1)[0].rstrip("/") or "/"
                try:
                    if path == "/metrics":
                        self._send(
                            200,
                            "text/plain; version=0.0.4; charset=utf-8",
                            render_prometheus(
                                router.registry,
                                exemplars=router.recorder.exemplars,
                            ).encode(),
                        )
                    elif path.startswith("/trace/"):
                        # Live trace lookup (ISSUE 18): the recorder
                        # keeps EVERY finished trace in its bounded
                        # ring (sampling only gates sink writes), so
                        # the operator can pull any recent request's
                        # span tree by the trace_id its reply carried.
                        tid = path[len("/trace/"):]
                        doc = router.recorder.get(tid)
                        if doc is None:
                            self._send_json(
                                404,
                                {"error": f"unknown trace {tid!r}"},
                            )
                        else:
                            self._send_json(200, doc)
                    elif path == "/health":
                        self._send_json(*router.health_payload())
                    elif path == "/replicas":
                        self._send_json(
                            200,
                            {"replicas": router.replica_snapshots()},
                        )
                    elif path == "/window":
                        self._send_json(200, router.stats_line())
                    elif path == "/canary":
                        base, canary = router.canary_records()
                        self._send_json(
                            200, {"base": base, "canary": canary}
                        )
                    elif path == "/alerts":
                        # Live alert state (ISSUE 19): every rule's
                        # burn rates and state machine position, plus
                        # the firing subset with exemplar trace ids —
                        # what tools/slo_watch.py polls.
                        self._send_json(200, router.alerts.payload())
                    elif path == "/series":
                        # The in-process time-series store (ISSUE 19):
                        # ring-buffered history of every router
                        # instrument, sampled on the stats cadence.
                        self._send_json(
                            200, router.series.to_payload()
                        )
                    else:
                        self._send(
                            404,
                            "text/plain; charset=utf-8",
                            b"GET: /metrics /health /replicas /window "
                            b"/canary /alerts /series /trace/{id}   "
                            b"POST: /generate /classify /drain "
                            b"/undrain\n",
                        )
                except ConnectionError:
                    pass

            def log_message(self, fmt, *args):  # quiet under load
                log.debug("router frontend: " + fmt, *args)

        self._httpd = _RouterHTTPServer(
            (self.bind_host, self.requested_port), Handler
        )
        self._httpd.daemon_threads = True
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(
            target=self._httpd.serve_forever,
            name="router-frontend",
            daemon=True,
        )
        self._thread.start()
        log.info(
            "router live on port %d over %d replica(s)",
            self.port, len(self.router.replicas),
        )
        return self

    def url(self, path: str = "/generate") -> str:
        host = self.bind_host or "127.0.0.1"
        return f"http://{host}:{self.port}{path}"

    def close(self) -> None:
        with self._lock:
            httpd, self._httpd = self._httpd, None
            thread, self._thread = self._thread, None
        if httpd is None:
            return
        httpd.shutdown()
        httpd.server_close()
        if thread is not None and thread is not threading.current_thread():
            thread.join(timeout=5)

    def abort(self) -> None:
        """Die like a killed router process (the ``killrouter@T``
        fault's verb, ISSUE 16 — same semantics as
        ``ServingFrontend.abort``): stop listening AND reset every
        in-flight client connection, so clients observe transport
        failures, never a drained 503. Handler threads hit the dead
        sockets on their own (ConnectionError, already swallowed);
        nothing is joined — safe from any thread, including a handler
        mid-dispatch."""
        with self._lock:
            httpd, self._httpd = self._httpd, None
            self._thread = None
        if httpd is None:
            return
        try:
            httpd.shutdown()
            httpd.server_close()
        except OSError:  # pragma: no cover - best-effort teardown
            pass
        with httpd.conn_lock:
            conns = list(httpd.live_connections)
        for conn in conns:
            try:
                conn.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass  # already gone
