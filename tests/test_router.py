"""Router tier (ISSUE 8): load-aware dispatch, drain-aware rollout,
retry-once-on-503, canary compare via tools/run_diff.py.

The load-bearing test is
:class:`TestDrainMidLoad::test_drain_one_replica_zero_failed_requests`
— the acceptance contract: 2 replicas under concurrent load, one
drained mid-stream, every request completes 200 and the drained
replica takes no new dispatch.

Replicas here are device-free fake engines behind REAL HTTP frontends:
the router only ever speaks HTTP, so this is end-to-end for everything
the router tier owns while staying O(ms) per request. The real-engine
tier (2 warmed paged replicas behind the router over HTTP) is covered
by ``serve_bench --smoke --router`` in tests/test_tools.py.
"""

import json
import os
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from conftest import slot_pool
from tensorflow_examples_tpu.serving.batcher import (
    ContinuousBatcher,
    Request,
)
from tensorflow_examples_tpu.serving.engine import ServeConfig
from tensorflow_examples_tpu.serving.frontend import ServingFrontend
from tensorflow_examples_tpu.serving.router import (
    ReplicaState,
    Router,
    RouterConfig,
    RouterFrontend,
)
from tensorflow_examples_tpu.telemetry import schema
from tensorflow_examples_tpu.telemetry.registry import MetricsRegistry

pytestmark = pytest.mark.serving

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))


class _FakeEngine:
    """Deterministic device-free engine (mirrors test_serving's): token
    stream is prompt[-1]+1, +2, ... — so any replica serves identical
    output and the router's routing cannot change results."""

    def __init__(self, *, max_slots=4, max_queue=32, max_len=64,
                 step_delay=0.0):
        self.cfg = ServeConfig(
            max_slots=max_slots, max_queue=max_queue, max_delay_s=0.0,
            request_timeout_s=30.0,
        )
        import serve_bench

        from tensorflow_examples_tpu.models import transformer

        base = dict(serve_bench.SMOKE_MODEL)
        base["max_len"] = max_len
        self.model_cfg = transformer.TransformerConfig(**base)
        self.registry = MetricsRegistry()
        self.pool = slot_pool(max_slots, max_len, self.registry)
        self.step_delay = step_delay
        self.warmed = True

    def post_warmup_recompiles(self):
        return 0

    def prefill(self, slot, prompt, *, seed=0, temperature=0.0, top_k=0):
        self.pool.lengths[slot] = len(prompt)
        last = np.zeros((self.model_cfg.vocab_size,), np.float32)
        return (prompt[-1] + 1) % self.model_cfg.vocab_size, last

    def decode(self, entries):
        if self.step_delay:
            time.sleep(self.step_delay)
        out = {}
        for slot, token, _seed, _temp, _tk in entries:
            self.pool.lengths[slot] += 1
            out[slot] = (token + 1) % self.model_cfg.vocab_size
        return out


def _replica(**kw):
    eng = _FakeEngine(**kw)
    batcher = ContinuousBatcher(eng).start()
    frontend = ServingFrontend(batcher, port=0).start()
    return eng, batcher, frontend


def _close(replicas):
    for _, batcher, frontend in replicas:
        batcher.close(drain=True)
        frontend.close()


def _post(url, body, timeout=30):
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


class TestPick:
    """Dispatch policy units — no sockets, states set by hand."""

    def _router(self):
        r = Router(["http://a:1", "http://b:2"])
        for rep in r.replicas:
            rep.probed = True
        return r

    def test_least_loaded_by_queue_then_occupancy(self):
        r = self._router()
        a, b = r.replicas
        a.queue_depth, b.queue_depth = 3.0, 0.0
        assert r.pick() is b
        a.queue_depth = b.queue_depth = 0.0
        a.kv_occupancy, b.kv_occupancy = 0.9, 0.1
        assert r.pick() is b

    def test_tie_breaks_to_fewest_dispatched(self):
        r = self._router()
        a, b = r.replicas
        picked = {r.pick().url for _ in range(2)}
        assert picked == {a.url, b.url}  # alternates on the tiebreak

    def test_drained_and_unhealthy_excluded(self):
        r = self._router()
        a, b = r.replicas
        a.drained = True
        assert r.pick() is b
        b.failures = r.cfg.unhealthy_after
        assert r.pick() is None
        assert r.undrain(a.url) and r.pick() is a

    def test_remote_draining_excluded(self):
        r = self._router()
        a, b = r.replicas
        a.draining_remote = True
        for _ in range(3):
            assert r.pick() is b

    def test_replica_state_snapshot_shape(self):
        s = ReplicaState("http://x:9/").snapshot_locked()
        assert s["url"] == "http://x:9" and s["set"] == "base"


class TestRouterE2E:
    @pytest.mark.timeout(120)
    def test_dispatch_spreads_and_proxies(self):
        replicas = [_replica(), _replica()]
        urls = [f"http://127.0.0.1:{fe.port}" for _, _, fe in replicas]
        router = Router(
            urls, cfg=RouterConfig(probe_interval_s=0.05)
        ).start()
        rfront = RouterFrontend(router, port=0).start()
        try:
            for i in range(8):
                status, reply = _post(
                    rfront.url("/generate"),
                    {"prompt": [10 + i], "max_new_tokens": 3},
                )
                assert status == 200
                assert reply["tokens"] == [
                    (10 + i + k + 1) % 211 for k in range(3)
                ]
            # Both replicas took work (least-loaded ties alternate).
            assert all(r.dispatched > 0 for r in router.replicas)
            # Observability surface.
            line = router.stats_line()
            assert schema.validate_line(json.loads(json.dumps(line))) == []
            assert line["serving"]["replicas"] == 2
            assert line["serving"]["router_dispatched"] == 8
            with urllib.request.urlopen(
                rfront.url("/replicas"), timeout=10
            ) as resp:
                snap = json.loads(resp.read())
            assert len(snap["replicas"]) == 2
            with urllib.request.urlopen(
                rfront.url("/health"), timeout=10
            ) as resp:
                health = json.loads(resp.read())
            assert health["ok"] and health["eligible"] == 2
        finally:
            rfront.close()
            router.close()
            _close(replicas)

    @pytest.mark.timeout(120)
    def test_retry_once_on_503_lands_on_other_replica(self):
        """Replica A is draining (its frontend answers 503) but the
        router has not probed since: the dispatch hits A, gets the
        503, and retries ONCE onto B — the client sees 200."""
        replicas = [_replica(), _replica()]
        urls = [f"http://127.0.0.1:{fe.port}" for _, _, fe in replicas]
        # No probe thread (start() not called): the router's view is
        # frozen at one manual sweep, so it provably dispatches to the
        # already-draining replica first.
        router = Router(urls, cfg=RouterConfig())
        router.probe_once()
        try:
            a, b = router.replicas
            replicas[0][1].close(drain=True)  # A drains itself
            # Force the first pick onto A (fewest dispatched).
            b.dispatched = 5
            status, reply = router.handle(
                {"prompt": [7], "max_new_tokens": 2}, kind="generate"
            )
            assert status == 200 and reply["tokens"] == [8, 9]
            assert a.errors == 1
            counters = router.registry.counter_values()
            assert counters["router/retries_total"] == 1
        finally:
            router.close()
            _close(replicas[1:])
            replicas[0][2].close()

    @pytest.mark.timeout(120)
    def test_no_replica_is_503_not_hang(self):
        replicas = [_replica()]
        urls = [f"http://127.0.0.1:{fe.port}" for _, _, fe in replicas]
        router = Router(
            urls, cfg=RouterConfig(probe_interval_s=60.0)
        ).start()
        try:
            router.drain(urls[0])
            status, reply = router.handle(
                {"prompt": [1]}, kind="generate"
            )
            assert status == 503 and reply.get("retry")
            assert (
                router.registry.counter_values()[
                    "router/no_replica_total"
                ] == 1
            )
        finally:
            router.close()
            _close(replicas)


class TestDrainMidLoad:
    @pytest.mark.timeout(180)
    def test_drain_one_replica_zero_failed_requests(self):
        """Acceptance: 2 replicas, concurrent load, one drained via the
        admin endpoint mid-stream -> every request completes, zero
        failures, and the drained replica takes no dispatch after the
        drain settles."""
        replicas = [
            _replica(step_delay=0.01), _replica(step_delay=0.01)
        ]
        urls = [f"http://127.0.0.1:{fe.port}" for _, _, fe in replicas]
        router = Router(
            urls, cfg=RouterConfig(probe_interval_s=0.05)
        ).start()
        rfront = RouterFrontend(router, port=0).start()
        n, statuses = 24, [None] * 24
        drained_at_dispatch: list[int] = []
        next_i = [0]
        lock = threading.Lock()

        def worker():
            while True:
                with lock:
                    i = next_i[0]
                    if i >= n:
                        return
                    next_i[0] += 1
                if i == 8:
                    # Mid-load rollout drain via the admin verb.
                    status, reply = _post(
                        rfront.url("/drain"), {"replica": urls[0]}
                    )
                    assert status == 200 and reply["ok"]
                    drained_at_dispatch.append(
                        router.replicas[0].dispatched
                    )
                s, _ = _post(
                    rfront.url("/generate"),
                    {"prompt": [i % 200], "max_new_tokens": 4},
                )
                statuses[i] = s

        threads = [
            threading.Thread(target=worker, daemon=True)
            for _ in range(4)
        ]
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert statuses.count(200) == n, statuses  # ZERO failures
            # Post-drain, replica 0 took at most the requests already
            # being picked concurrently with the drain call.
            assert router.replicas[0].dispatched <= (
                drained_at_dispatch[0] + 4
            )
            # ...and the survivor carried the rest.
            assert router.replicas[1].dispatched >= n // 2
        finally:
            rfront.close()
            router.close()
            _close(replicas)


class TestCanary:
    @pytest.mark.timeout(120)
    def test_canary_split_and_run_diff_record(self, tmp_path):
        """Acceptance: canary compare produces a run_diff doc — two
        per-set records through tools/run_diff.py with the serving
        keys ranked."""
        import run_diff

        replicas = [_replica(), _replica(step_delay=0.01)]
        urls = [f"http://127.0.0.1:{fe.port}" for _, _, fe in replicas]
        router = Router(
            [urls[0]], canary=[urls[1]],
            cfg=RouterConfig(
                probe_interval_s=0.05, canary_fraction=0.5
            ),
        ).start()
        rfront = RouterFrontend(router, port=0).start()
        try:
            for i in range(10):
                status, _ = _post(
                    rfront.url("/generate"),
                    {"prompt": [i + 1], "max_new_tokens": 3},
                )
                assert status == 200
            base, canary = router.canary_records()
            assert base["completed"] == 5 and canary["completed"] == 5
            assert base["set"] == "base" and canary["set"] == "canary"
            # /canary serves the same records.
            with urllib.request.urlopen(
                rfront.url("/canary"), timeout=10
            ) as resp:
                doc = json.loads(resp.read())
            assert doc["base"]["completed"] == 5
        finally:
            rfront.close()
            router.close()
            _close(replicas)
        a_path, b_path = tmp_path / "base.json", tmp_path / "canary.json"
        a_path.write_text(json.dumps(base))
        b_path.write_text(json.dumps(canary))
        out = tmp_path / "diff.json"
        rc = run_diff.main([str(a_path), str(b_path), "--json", str(out)])
        assert rc == 0
        with open(out) as f:
            diff = json.load(f)
        ranked = {d["metric"] for d in diff["ranked"]}
        assert "ttft_p95_ms" in ranked and "tok_per_s" in ranked
        # The canary's gateable serving figures are flattened on top
        # (bench_gate --record consumes this doc directly).
        assert diff["ttft_p95_ms"] == canary["ttft_p95_ms"]


class TestCircuitBreaker:
    """ISSUE 10 breaker state machine: closed -> open (ejected) ->
    half-open (one trial) -> closed on success / re-open on failure.
    Driven through the router's own bookkeeping, no sockets."""

    def _router(self, **cfg_kw):
        kw = dict(eject_after=3, eject_cooldown_s=0.2)
        kw.update(cfg_kw)
        r = Router(["http://a:1", "http://b:2"], cfg=RouterConfig(**kw))
        for rep in r.replicas:
            rep.probed = True
        return r

    def test_consecutive_failures_eject(self):
        r = self._router()
        a = r.replicas[0]
        for i in range(r.cfg.eject_after - 1):
            r._note_failure(a, transport=True, draining=False)
            assert a.breaker == "closed", i
        r._note_failure(a, transport=True, draining=False)
        assert a.breaker == "open"
        assert not a.eligible(r.cfg.unhealthy_after)
        assert (
            r.registry.counter_values()["router/ejections_total"] == 1
        )

    def test_success_resets_consecutive_count(self):
        r = self._router()
        a = r.replicas[0]
        for _ in range(r.cfg.eject_after - 1):
            r._note_failure(a, transport=False, draining=False)
        r._note_success(a)
        assert a.consec_errors == 0
        r._note_failure(a, transport=False, draining=False)
        assert a.breaker == "closed"  # the streak was broken

    def test_draining_503_is_not_a_breaker_failure(self):
        r = self._router(eject_after=1)
        a = r.replicas[0]
        r._note_failure(a, transport=False, draining=True)
        assert a.breaker == "closed" and a.draining_remote

    def test_half_open_single_trial_then_readmit(self):
        r = self._router(eject_after=1)
        a, b = r.replicas
        b.drained = True  # force every pick onto a
        r._note_failure(a, transport=True, draining=False)
        assert a.breaker == "open"
        assert r.pick() is None  # ejected: nothing eligible
        time.sleep(r.cfg.eject_cooldown_s + 0.05)
        trial = r.pick()  # cooldown expired -> half-open, ONE trial
        assert trial is a and a.breaker == "half_open"
        assert r.pick() is None  # trial in flight: no second dispatch
        r._note_success(a)
        assert a.breaker == "closed"
        assert (
            r.registry.counter_values()["router/readmits_total"] == 1
        )
        assert r.pick() is a  # back in rotation

    def test_half_open_failure_reopens(self):
        r = self._router(eject_after=1)
        a, b = r.replicas
        b.drained = True
        r._note_failure(a, transport=True, draining=False)
        time.sleep(r.cfg.eject_cooldown_s + 0.05)
        assert r.pick() is a and a.breaker == "half_open"
        r._note_failure(a, transport=True, draining=False)
        assert a.breaker == "open"  # re-ejected for another cooldown
        assert r.pick() is None
        assert (
            r.registry.counter_values()["router/ejections_total"] == 2
        )

    def test_probe_green_readmits_half_open(self):
        """The /health-probe path of the half-open trial: a green probe
        readmits without risking a live request."""
        replicas = [_replica()]
        urls = [f"http://127.0.0.1:{fe.port}" for _, _, fe in replicas]
        router = Router(
            urls, cfg=RouterConfig(eject_after=1, eject_cooldown_s=0.05)
        )
        try:
            router.probe_once()
            a = router.replicas[0]
            router._note_failure(a, transport=False, draining=False)
            assert a.breaker == "open"
            time.sleep(0.1)
            router.probe_once()
            assert a.breaker == "closed"
            assert (
                router.registry.counter_values()[
                    "router/readmits_total"
                ] == 1
            )
        finally:
            router.close()
            _close(replicas)


class TestBoundedRetryAndFailover:
    @pytest.mark.timeout(120)
    def test_transport_failure_fails_over_and_counts(self):
        """A replica that died mid-request (transport failure, status
        0) triggers in-flight failover: the request replays on the
        other replica and router/failovers_total counts it."""
        replicas = [_replica()]
        live_url = f"http://127.0.0.1:{replicas[0][2].port}"
        # A dead URL: bind-then-close guarantees connection refused.
        import socket

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            dead_url = f"http://127.0.0.1:{s.getsockname()[1]}"
        router = Router(
            [dead_url, live_url],
            cfg=RouterConfig(retry_backoff_s=0.01, eject_after=1),
        )
        router.probe_once()
        try:
            # Force the first pick onto the dead replica.
            router.replicas[1].dispatched = 5
            status, reply = router.handle(
                {"prompt": [7], "max_new_tokens": 2}, kind="generate"
            )
            assert status == 200 and reply["tokens"] == [8, 9]
            counters = router.registry.counter_values()
            assert counters["router/failovers_total"] == 1
            assert counters["router/retries_total"] == 1
            assert counters["router/ejections_total"] == 1
            assert router.replicas[0].breaker == "open"
        finally:
            router.close()
            _close(replicas)

    @pytest.mark.timeout(120)
    def test_retries_bounded_by_max_retries(self):
        """Every replica down -> the request fails 503 after at most
        max_retries re-dispatches, never an unbounded loop."""
        import socket

        urls = []
        for _ in range(2):
            with socket.socket() as s:
                s.bind(("127.0.0.1", 0))
                urls.append(f"http://127.0.0.1:{s.getsockname()[1]}")
        router = Router(
            urls,
            cfg=RouterConfig(
                max_retries=2, retry_backoff_s=0.01,
                retry_budget_s=5.0, eject_after=10,
            ),
        )
        try:
            status, reply = router.handle(
                {"prompt": [1]}, kind="generate"
            )
            assert status == 503
            counters = router.registry.counter_values()
            assert counters["router/retries_total"] == 2
        finally:
            router.close()


class TestHedgedDispatch:
    @pytest.mark.timeout(120)
    def test_hedge_wins_and_loser_is_discarded(self):
        """A slow primary past the hedge deadline triggers a second
        dispatch; the fast hedge's response wins, the slow loser is
        abandoned (counted, its reply discarded on arrival)."""
        slow = _replica(step_delay=0.25)
        fast = _replica()
        urls = [
            f"http://127.0.0.1:{slow[2].port}",
            f"http://127.0.0.1:{fast[2].port}",
        ]
        router = Router(
            urls, cfg=RouterConfig(hedge_after_s=0.05)
        )
        router.probe_once()
        try:
            # Force the primary pick onto the slow replica.
            router.replicas[1].dispatched = 5
            status, reply = router.handle(
                {"prompt": [7], "max_new_tokens": 4}, kind="generate"
            )
            assert status == 200
            # Determinism across replicas: same tokens either way.
            assert reply["tokens"] == [8, 9, 10, 11]
            counters = router.registry.counter_values()
            assert counters["router/hedges_total"] == 1
            assert counters["router/hedge_wins_total"] == 1
            assert counters["router/hedge_cancelled_total"] == 1
            # The winner was the fast replica; the slow loser's reply
            # lands later and is discarded (bookkeeping only).
            assert router.replicas[1].completed == 1
        finally:
            router.close()
            _close([slow, fast])

    def test_hedge_disabled_by_default(self):
        assert RouterConfig().hedge_after_s == 0.0


class TestProbeGarbage:
    """ISSUE 10 satellite: malformed /health bodies mark the replica
    unhealthy instead of risking the probe loop."""

    # A peer that dies between the headers and the body: more bytes
    # declared than sent (http.client.IncompleteRead, no OSError).
    TORN = b'{"ok": tr'

    def _garbage_server(self, payload: bytes):
        import http.server
        import threading

        declared = len(payload) + (230 if payload == self.TORN else 0)

        class H(http.server.BaseHTTPRequestHandler):
            def do_GET(self):
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(declared))
                self.end_headers()
                self.wfile.write(payload)

            def do_POST(self):
                self.rfile.read(int(self.headers["Content-Length"]))
                self.do_GET()

            def log_message(self, *a):
                pass

        httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0), H)
        threading.Thread(
            target=httpd.serve_forever, daemon=True
        ).start()
        return httpd

    @pytest.mark.timeout(120)
    @pytest.mark.parametrize(
        "payload", [b"<<<not json", b"[1, 2, 3]", b'"just a string"', TORN],
        ids=["non-json", "json-array", "json-string", "torn-body"],
    )
    def test_garbage_health_body_marks_unhealthy(self, payload):
        from tensorflow_examples_tpu.serving.router import post_json

        garbage = self._garbage_server(payload)
        replicas = [_replica()]
        urls = [
            f"http://127.0.0.1:{garbage.server_address[1]}",
            f"http://127.0.0.1:{replicas[0][2].port}",
        ]
        router = Router(urls, cfg=RouterConfig())
        try:
            # The one JSON-over-HTTP client keeps its contract against
            # the same peer: status 0, never an exception.
            assert post_json(urls[0] + "/generate", {}, 5.0)[0] == 0
            for _ in range(router.cfg.unhealthy_after):
                router.probe_once()  # must never raise
            bad, good = router.replicas
            assert bad.failures >= router.cfg.unhealthy_after
            assert not bad.eligible(router.cfg.unhealthy_after)
            # The sweep survived the garbage and still probed the
            # well-behaved replica.
            assert good.probed and good.failures == 0
            status, _ = router.handle(
                {"prompt": [5], "max_new_tokens": 2}, kind="generate"
            )
            assert status == 200
        finally:
            router.close()
            _close(replicas)
            garbage.shutdown()
            garbage.server_close()


class TestRouterSchema:
    def test_v6_serving_keys_flagged_on_older_versions(self):
        r = Router(["http://a:1"])
        line = json.loads(json.dumps(r.stats_line()))
        assert schema.validate_line(line) == []
        v5 = dict(line, schema_version=5)
        assert any(
            "v6 serving key" in p for p in schema.validate_line(v5)
        )
        v4 = dict(line, schema_version=4)
        assert any(
            "v6 serving key" in p for p in schema.validate_line(v4)
        )

    def test_v7_serving_keys_flagged_on_older_versions(self):
        """ISSUE 10: the fault-tolerance counters are v7-only — a 'v6'
        line carrying router_failovers is a mislabeled v7 line."""
        r = Router(["http://a:1"])
        line = json.loads(json.dumps(r.stats_line()))
        assert line["schema_version"] == schema.SERVING_SCHEMA_VERSION
        assert schema.validate_line(line) == []
        for key in schema.SERVING_KEYS_V7:
            assert key in line["serving"], key
        v6 = dict(line, schema_version=6)
        assert any(
            "v7 serving key" in p for p in schema.validate_line(v6)
        )

    def test_v9_serving_keys_flagged_on_older_versions(self):
        """ISSUE 12: the router's fleet-summed prefix summary is
        v9-only — a 'v8' line carrying prefix_blocks is a mislabeled
        v9 line, same rule as every earlier bump."""
        r = Router(["http://a:1"])
        rep = r.replicas[0]
        rep.probed = True
        rep.prefix_blocks, rep.prefix_chains = 5, 2
        line = json.loads(json.dumps(r.stats_line()))
        assert schema.validate_line(line) == []
        assert line["serving"]["prefix_blocks"] == 5
        assert line["serving"]["prefix_chains"] == 2
        v8 = dict(line, schema_version=8)
        assert any(
            "v9 serving key" in p for p in schema.validate_line(v8)
        )


class TestRouterAffinityProbe:
    @pytest.mark.timeout(120)
    def test_probe_learns_role_and_digest_fields(self):
        """The probe sweep parses the ISSUE 12 /health fields even from
        a dense-pool replica (role only) and the /replicas snapshot
        carries them."""
        replicas = [_replica()]
        urls = [f"http://127.0.0.1:{fe.port}" for _, _, fe in replicas]
        router = Router(urls, cfg=RouterConfig(probe_interval_s=60.0))
        try:
            router.probe_once()
            rep = router.replicas[0]
            assert rep.role == "mixed"  # ServeConfig default
            assert rep.prefix_digest == frozenset()
            snap = rep.snapshot_locked()
            assert snap["role"] == "mixed"
            assert snap["prefix_blocks"] == 0
        finally:
            router.close()
            _close(replicas)


class TestFleetLockDiscipline:
    """Regression tests for the ISSUE 14 graftlint lock-pass findings:
    ``drain``/``undrain`` mutated ``ReplicaState.drained``/``failures``
    WITHOUT the router lock (while ``quarantine``/``readmit`` and
    ``pick()`` took it — a drain racing a pick could dispatch to a
    just-drained replica), and ``health_payload``/``stats_line``
    aggregated the fleet view with no lock at all, so a probe sweep
    mid-render could tear it (one replica's fresh occupancy summed
    with another's stale brownout level). Both now serialize on
    ``Router._lock`` — pinned here by holding the lock from another
    thread and asserting the verb blocks until release."""

    def _assert_serializes(self, router, call):
        locked = threading.Event()
        release = threading.Event()
        holder_done = threading.Event()

        def hold():
            with router._lock:
                locked.set()
                release.wait(5)
            holder_done.set()

        done = threading.Event()

        def run():
            call()
            done.set()

        t1 = threading.Thread(target=hold, daemon=True)
        t1.start()
        assert locked.wait(2)
        t2 = threading.Thread(target=run, daemon=True)
        t2.start()
        # The verb must be waiting on the fleet lock, not mutating
        # lock-free past it (the pre-fix behavior).
        time.sleep(0.1)
        assert not done.is_set(), (
            f"{call.__name__} completed while Router._lock was held — "
            "it is not serializing with pick()/the probe sweep"
        )
        release.set()
        assert done.wait(2), f"{call.__name__} never finished post-release"
        t1.join(2)
        t2.join(2)

    def test_drain_takes_the_fleet_lock(self):
        router = Router(["http://127.0.0.1:9/"])
        self._assert_serializes(
            router, lambda: router.drain("http://127.0.0.1:9/")
        )
        assert router.replicas[0].drained

    def test_undrain_takes_the_fleet_lock(self):
        router = Router(["http://127.0.0.1:9/"])
        router.drain("http://127.0.0.1:9/")
        self._assert_serializes(
            router, lambda: router.undrain("http://127.0.0.1:9/")
        )
        assert not router.replicas[0].drained

    def test_fleet_views_take_the_fleet_lock(self):
        router = Router(["http://127.0.0.1:9/"])
        self._assert_serializes(router, lambda: router.health_payload())
        self._assert_serializes(router, lambda: router.stats_line())
        self._assert_serializes(
            router, lambda: router.replica_snapshots()
        )

    def test_drained_replica_never_picked_after_drain_returns(self):
        """Functional shape of the race: once drain() returns, no
        concurrent pick() may return the drained replica — hammered
        from several threads while the drain flips."""
        urls = ["http://127.0.0.1:9/", "http://127.0.0.1:10/"]
        router = Router(urls)
        for r in router.replicas:
            r.probed = True
        stop = threading.Event()
        drained_at = []
        bad = []

        def picker():
            while not stop.is_set():
                t_start = time.monotonic()
                r = router.pick()
                # Only a pick that STARTED after drain() returned is a
                # violation — the lock serializes it behind the drain,
                # so it must see drained=True.
                if (
                    r is not None and drained_at
                    and t_start > drained_at[0]
                    and r.url == urls[0].rstrip("/")
                ):
                    bad.append(r.url)

        threads = [
            threading.Thread(target=picker, daemon=True)
            for _ in range(4)
        ]
        for t in threads:
            t.start()
        time.sleep(0.05)
        router.drain(urls[0])
        drained_at.append(time.monotonic())
        time.sleep(0.1)
        stop.set()
        for t in threads:
            t.join(2)
        assert not bad, f"picked drained replica after drain(): {bad}"


class TestTracing:
    """ISSUE 18: per-request trace trees over fake replicas — the wire
    contract (reply ``trace_id``, ``GET /trace/{id}``), per-attempt
    dispatch spans under failover, client context adoption, the v13
    stats keys, /metrics exemplars, and the journal dedupe stitch."""

    @pytest.mark.timeout(120)
    def test_reply_trace_id_and_trace_endpoint(self):
        replicas = [_replica()]
        urls = [f"http://127.0.0.1:{replicas[0][2].port}"]
        router = Router(urls, cfg=RouterConfig(probe_interval_s=0.05))
        router.probe_once()
        rfront = RouterFrontend(router, port=0).start()
        try:
            status, reply = _post(
                rfront.url("/generate"),
                {"prompt": [7], "max_new_tokens": 3},
            )
            assert status == 200 and reply["tokens"] == [8, 9, 10]
            tid = reply["trace_id"]
            assert isinstance(tid, str) and tid
            with urllib.request.urlopen(
                rfront.url(f"/trace/{tid}"), timeout=10
            ) as resp:
                doc = json.loads(resp.read())
            assert doc["trace_id"] == tid
            names = [s["name"] for s in doc["spans"]]
            # Router-side spans plus the replica's own, stitched via
            # the reply's trace_spans — one tree, no shared memory.
            assert "request" in names and "dispatch" in names
            assert "queue_wait" in names, names
            # The replica spans nest under the dispatch attempt.
            by_id = {s["span_id"]: s for s in doc["spans"]}
            disp = next(s for s in doc["spans"] if s["name"] == "dispatch")
            qw = next(s for s in doc["spans"] if s["name"] == "queue_wait")
            assert qw["parent_id"] == disp["span_id"]
            assert by_id[disp["parent_id"]]["name"] == "request"
            # Unknown id -> 404, not a crash.
            try:
                with urllib.request.urlopen(
                    rfront.url("/trace/nope"), timeout=10
                ) as resp:
                    assert False, "expected 404"
            except urllib.error.HTTPError as e:
                assert e.code == 404
                assert "unknown trace" in json.loads(e.read())["error"]
        finally:
            rfront.close()
            router.close()
            _close(replicas)

    @pytest.mark.timeout(120)
    def test_failover_trace_shows_both_dispatch_attempts(self):
        """A transport-failure failover leaves BOTH attempts in the
        tree: the dead replica's dispatch span (outcome=transport) and
        the survivor's (outcome=ok), each with its own span_id — plus
        the failover/retried flags that force the tail sampler to
        keep the trace."""
        replicas = [_replica()]
        live_url = f"http://127.0.0.1:{replicas[0][2].port}"
        import socket

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            dead_url = f"http://127.0.0.1:{s.getsockname()[1]}"
        router = Router(
            [dead_url, live_url],
            cfg=RouterConfig(retry_backoff_s=0.01, eject_after=1),
        )
        router.probe_once()
        try:
            router.replicas[1].dispatched = 5  # force the dead pick
            status, reply = router.handle(
                {"prompt": [7], "max_new_tokens": 2}, kind="generate"
            )
            assert status == 200 and reply["tokens"] == [8, 9]
            doc = router.recorder.get(reply["trace_id"])
            assert doc is not None and not doc.get("open")
            assert "failover" in doc["flags"]
            assert "retried" in doc["flags"]
            assert doc["kept"] is True  # forced keep, not seeded luck
            dispatches = [
                s for s in doc["spans"] if s["name"] == "dispatch"
            ]
            assert len(dispatches) == 2
            outcomes = {
                s["tags"]["replica"]: s["tags"]["outcome"]
                for s in dispatches
            }
            assert outcomes[dead_url] == "transport"
            assert outcomes[live_url] == "ok"
            assert (
                dispatches[0]["span_id"] != dispatches[1]["span_id"]
            )
            # Replica spans hang off the attempt that answered, never
            # the dead one.
            qw = [s for s in doc["spans"] if s["name"] == "queue_wait"]
            live_span = next(
                s for s in dispatches if s["tags"]["replica"] == live_url
            )
            assert qw and all(
                s["parent_id"] == live_span["span_id"] for s in qw
            )
        finally:
            router.close()
            _close(replicas)

    @pytest.mark.timeout(120)
    def test_client_wire_context_is_adopted(self):
        """A client-minted traceparent wins: the reply carries the
        client's trace_id and the root request span parents under the
        client's span — the client can stitch the router's tree into
        its own."""
        replicas = [_replica()]
        urls = [f"http://127.0.0.1:{replicas[0][2].port}"]
        router = Router(urls)
        router.probe_once()
        try:
            status, reply = router.handle(
                {
                    "prompt": [3], "max_new_tokens": 2,
                    "trace": {
                        "trace_id": "cafe" * 4,
                        "parent_span_id": "feed0123",
                        "sampled": True,
                    },
                },
                kind="generate",
            )
            assert status == 200
            assert reply["trace_id"] == "cafe" * 4
            doc = router.recorder.get("cafe" * 4)
            root = next(
                s for s in doc["spans"] if s["name"] == "request"
            )
            assert root["parent_id"] == "feed0123"
        finally:
            router.close()
            _close(replicas)

    @pytest.mark.timeout(120)
    def test_stats_line_carries_v13_keys_and_validates(self):
        replicas = [_replica()]
        urls = [f"http://127.0.0.1:{replicas[0][2].port}"]
        # sample_fraction=1.0: this test is about the keys, not the
        # sampler's coin.
        router = Router(
            urls, cfg=RouterConfig(trace_sample_fraction=1.0)
        )
        router.probe_once()
        try:
            status, _ = router.handle(
                {"prompt": [2], "max_new_tokens": 2}, kind="generate"
            )
            assert status == 200
            line = json.loads(json.dumps(router.stats_line()))
            assert schema.validate_line(line) == []
            serving = line["serving"]
            for key in schema.SERVING_KEYS_V13:
                assert key in serving, key
            assert serving["traces_kept"] == 1
            assert serving["traces_dropped"] == 0
            assert serving["trace_coverage"] == 1.0
            # v13 keys on an older version label must flag.
            v12 = dict(line, schema_version=12)
            assert any(
                "v13 serving key" in p for p in schema.validate_line(v12)
            )
        finally:
            router.close()
            _close(replicas)

    @pytest.mark.timeout(120)
    def test_metrics_exposes_e2e_exemplar_with_trace_id(self):
        replicas = [_replica()]
        urls = [f"http://127.0.0.1:{replicas[0][2].port}"]
        router = Router(urls)
        router.probe_once()
        rfront = RouterFrontend(router, port=0).start()
        try:
            status, reply = _post(
                rfront.url("/generate"),
                {"prompt": [5], "max_new_tokens": 2},
            )
            assert status == 200
            with urllib.request.urlopen(
                rfront.url("/metrics"), timeout=10
            ) as resp:
                text = resp.read().decode()
            line = next(
                ln for ln in text.splitlines()
                if ln.startswith("router_e2e_seconds_worst{")
            )
            # The exemplar names the trace that explains the worst
            # observation — here the only one there is.
            assert f'trace_id="{reply["trace_id"]}"' in line
        finally:
            rfront.close()
            router.close()
            _close(replicas)

    @pytest.mark.timeout(120)
    def test_journal_dedupe_stitches_into_original_trace(self, tmp_path):
        """A duplicated request_id answers from the journal — and its
        spans JOIN the original trace (journal-stamped trace_id +
        recorder merge), instead of forking a second tree."""
        from tensorflow_examples_tpu.serving.journal import (
            RequestJournal,
        )

        replicas = [_replica()]
        urls = [f"http://127.0.0.1:{replicas[0][2].port}"]
        journal = RequestJournal(str(tmp_path / "j.jsonl"))
        router = Router(urls, journal=journal)
        router.probe_once()
        try:
            body = {
                "prompt": [9], "max_new_tokens": 2,
                "request_id": "rid-1",
            }
            status, first = router.handle(body, kind="generate")
            assert status == 200 and not first.get("dedup")
            tid = first["trace_id"]
            assert journal.lookup("rid-1")["trace_id"] == tid
            status, second = router.handle(body, kind="generate")
            assert status == 200 and second["dedup"] is True
            assert second["tokens"] == first["tokens"]
            # The stitch: the duplicate's reply names the ORIGINAL
            # trace, and the merged doc holds both passes' spans.
            assert second["trace_id"] == tid
            doc = router.recorder.get(tid)
            names = [s["name"] for s in doc["spans"]]
            assert "dispatch" in names  # original pass
            assert "dedupe_hit" in names  # duplicate's fast path
            assert names.count("request") == 2  # one root per pass
            assert "deduped" in doc["flags"]
            assert doc["kept"] is True
        finally:
            router.close()
            _close(replicas)
