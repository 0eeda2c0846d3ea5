"""Serving chaos tier (ISSUE 10): replica death is a normal input.

The load-bearing test is
:class:`TestChaosGolden::test_kill_one_of_three_zero_failed_requests`
— the acceptance contract: 3 REAL in-proc paged replicas (warmed AOT
ladders) under concurrent load, one killed mid-decode by a
deterministic ``crash@R:N`` fault. Every request completes 200 (the
router's in-flight failover replays the victims from the prompt on a
survivor), every stream — failed-over ones included — is
token-identical to the engine's unbatched reference, the supervisor
restores the fleet to 3 green replicas without operator action, and
the survivors take ZERO post-warmup recompiles.

Everything else here is deterministic harness coverage that doesn't
need a device: fault-spec parsing, forced BlockExhausted / transport /
poisoned-health faults against device-free fake engines, supervisor
transitions over a real child process (:class:`ProcessReplica`).
"""

import json
import os
import sys
import time

import numpy as np
import pytest

from conftest import slot_pool
from tensorflow_examples_tpu.serving.chaos import ChaosFleet, RouterPair
from tensorflow_examples_tpu.serving.engine import ServeConfig
from tensorflow_examples_tpu.serving.router import (
    Router,
    RouterConfig,
    RouterFrontend,
)
from tensorflow_examples_tpu.serving.supervisor import (
    ProcessReplica,
    Supervisor,
)
from tensorflow_examples_tpu.telemetry import schema
from tensorflow_examples_tpu.telemetry.registry import MetricsRegistry
from tensorflow_examples_tpu.utils import faults as faults_mod

pytestmark = [pytest.mark.serving, pytest.mark.chaos]

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))


# ------------------------------------------------------------ fault specs


class TestServeFaultSpec:
    def test_parse_all_kinds(self):
        plan = faults_mod.parse_serve_spec(
            "crash@1:4,slowrep@0:0.25,transport@2:3,kvexhaust@0:7,"
            "badhealth@1:2"
        )
        assert plan.crash_at == {1: 4}
        assert plan.slow_replica == {0: 0.25}
        assert plan.transport_drop == {2: 3}
        assert plan.kvexhaust_at == {0: 7}
        assert plan.bad_health == {1: 2}

    def test_unknown_kind_and_malformed_args_raise(self):
        with pytest.raises(ValueError, match="unknown serve fault"):
            faults_mod.parse_serve_spec("explode@0:1")
        with pytest.raises(ValueError, match="needs '@<replica>:<arg>'"):
            faults_mod.parse_serve_spec("crash@3")
        with pytest.raises(ValueError, match="malformed"):
            faults_mod.parse_serve_spec("crash@a:b")

    def test_faults_fire_once_and_are_recorded(self, serve_faults):
        eng = serve_faults("transport@0:2,badhealth@1:1")
        assert eng.transport_fault(0) and eng.transport_fault(0)
        assert not eng.transport_fault(0)  # budget spent
        assert not eng.transport_fault(1)  # other replica untouched
        assert eng.health_fault(1) and not eng.health_fault(1)
        kinds = [k for k, _, _ in eng.fired]
        assert kinds.count("transport") == 2
        assert kinds.count("badhealth") == 1


# --------------------------------------------------- device-free harness


class _FakeEngine:
    """Deterministic device-free engine (test_router's, plus the ISSUE
    10 serve-fault hook and the warmup the chaos replica expects):
    token stream is prompt[-1]+1, +2, ... so every replica serves
    identical output and failover cannot change results."""

    def __init__(self, *, max_slots=4, max_queue=32, max_len=64,
                 step_delay=0.0, replica_id=0):
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
        self.replica_id = replica_id
        self.warmed = False

    def warmup(self):
        self.warmed = True
        return {}

    def post_warmup_recompiles(self):
        return 0

    def prefill(self, slot, prompt, *, seed=0, temperature=0.0, top_k=0):
        self.pool.lengths[slot] = len(prompt)
        last = np.zeros((self.model_cfg.vocab_size,), np.float32)
        return (prompt[-1] + 1) % self.model_cfg.vocab_size, last

    def decode(self, entries):
        feng = faults_mod.serve_active()
        if feng is not None:
            # Mirror InferenceEngine.decode's hook site so the harness
            # tests exercise the same fault semantics device-free.
            feng.decode_step(self.replica_id, [e[0] for e in entries])
        if self.step_delay:
            time.sleep(self.step_delay)
        out = {}
        for slot, token, _seed, _temp, _tk in entries:
            self.pool.lengths[slot] += 1
            out[slot] = (token + 1) % self.model_cfg.vocab_size
        return out


def _fake_fleet(n=2, *, step_delay=0.0, router_cfg=None,
                supervisor_kw=None):
    def make_factory(k):
        return lambda: _FakeEngine(step_delay=step_delay, replica_id=k)

    fleet = ChaosFleet(
        [make_factory(k) for k in range(n)],
        router_cfg=router_cfg or RouterConfig(
            probe_interval_s=0.05, retry_budget_s=20.0, max_retries=4,
            eject_after=1, eject_cooldown_s=0.5,
        ),
        supervisor_kw=dict(
            poll_s=0.05, health_stall_s=2.0, warm_timeout_s=30.0,
        ) | (supervisor_kw or {}),
    )
    fleet.start()
    return fleet


def _post(url, body, timeout=30):
    import serve_bench

    return serve_bench._post_json(url, body, timeout)


class TestChaosHarnessFake:
    """Fault kinds + breaker/supervisor transitions, device-free."""

    @pytest.mark.timeout(120)
    def test_kill_eject_restart_readmit_transitions(self, serve_faults):
        """The chaos state machine end-to-end on fake engines: crash
        mid-decode -> transport failure -> breaker EJECTS (eject_after
        =1) -> supervisor detects, restarts, READMITS -> the restarted
        replica serves again."""
        serve_faults("crash@0:2")
        fleet = _fake_fleet(2, step_delay=0.005)
        rfront = RouterFrontend(fleet.router, port=0).start()
        try:
            url = rfront.url("/generate")
            statuses = [
                _post(url, {"prompt": [i + 1], "max_new_tokens": 4})[0]
                for i in range(10)
            ]
            assert statuses.count(200) == 10, statuses
            counters = fleet.router.registry.counter_values()
            assert counters.get("router/failovers_total", 0) >= 1
            assert counters.get("router/ejections_total", 0) >= 1
            assert fleet.await_fleet_green(2, timeout_s=30)
            events = [
                e for u, e in fleet.supervisor.events
                if u == fleet.replicas[0].url
            ]
            assert events[:3] == ["detected", "restarted", "readmitted"]
            assert sum(fleet.supervisor.restarts.values()) == 1
            counters = fleet.router.registry.counter_values()
            assert counters.get("router/restarts_total", 0) == 1
            assert counters.get("router/readmits_total", 0) >= 1
            # The restarted replica takes traffic again.
            fleet.router.probe_once()
            status, reply = _post(
                url, {"prompt": [42], "max_new_tokens": 2}
            )
            assert status == 200 and reply["tokens"] == [43, 44]
        finally:
            rfront.close()
            fleet.close()

    @pytest.mark.timeout(120)
    def test_forced_block_exhaustion_fails_over(self, serve_faults):
        """kvexhaust@R:N: the paged pool's loud capacity path — the
        victim requests get 503 retry:true from the replica and the
        router re-runs them elsewhere; nothing fails."""
        serve_faults("kvexhaust@0:1")
        fleet = _fake_fleet(2, step_delay=0.005)
        rfront = RouterFrontend(fleet.router, port=0).start()
        try:
            url = rfront.url("/generate")
            statuses = [
                _post(url, {"prompt": [i + 1], "max_new_tokens": 4})[0]
                for i in range(8)
            ]
            assert statuses.count(200) == 8, statuses
            counters = fleet.router.registry.counter_values()
            assert counters.get("router/retries_total", 0) >= 1
            # Forced exhaustion is NOT a crash: the replica stays up.
            assert all(r.alive() for r in fleet.replicas)
        finally:
            rfront.close()
            fleet.close()

    @pytest.mark.timeout(120)
    def test_transport_fault_fails_over(self, serve_faults):
        serve_faults("transport@0:1")
        fleet = _fake_fleet(2)
        rfront = RouterFrontend(fleet.router, port=0).start()
        try:
            url = rfront.url("/generate")
            statuses = [
                _post(url, {"prompt": [i + 1], "max_new_tokens": 2})[0]
                for i in range(6)
            ]
            assert statuses.count(200) == 6, statuses
            counters = fleet.router.registry.counter_values()
            assert counters.get("router/failovers_total", 0) >= 1
        finally:
            rfront.close()
            fleet.close()

    @pytest.mark.timeout(120)
    def test_poisoned_health_marks_unhealthy_not_crash(
        self, serve_faults
    ):
        """badhealth@R:K: garbage /health bodies mark the replica
        unhealthy; the probe sweep survives and keeps probing the
        OTHER replicas (ISSUE 10 satellite regression)."""
        serve_faults(f"badhealth@0:{10}")
        fleet = _fake_fleet(
            2,
            router_cfg=RouterConfig(
                probe_interval_s=60.0, eject_after=1,
            ),
            supervisor_kw=dict(health_stall_s=3600.0),
        )
        rfront = RouterFrontend(fleet.router, port=0).start()
        try:
            router = fleet.router
            for _ in range(router.cfg.unhealthy_after):
                router.probe_once()
            a, b = router.replicas
            assert a.failures >= router.cfg.unhealthy_after
            assert not a.eligible(router.cfg.unhealthy_after)
            # The sweep did NOT stop at the garbage replica.
            assert b.probed and b.failures == 0
            status, _ = _post(
                rfront.url("/generate"),
                {"prompt": [5], "max_new_tokens": 2},
            )
            assert status == 200
        finally:
            rfront.close()
            fleet.close()


# ------------------------------------------------- process supervision


CHILD_SERVER = """\
import http.server, json, sys

class H(http.server.BaseHTTPRequestHandler):
    def do_GET(self):
        body = json.dumps(
            {"ok": True, "queue_depth": 0, "kv_occupancy": 0.0}
        ).encode()
        self.send_response(200)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, *a):
        pass

http.server.ThreadingHTTPServer(
    ("127.0.0.1", int(sys.argv[1])), H
).serve_forever()
"""


class TestProcessSupervision:
    @pytest.mark.timeout(120)
    def test_dead_process_restarted_and_readmitted(self, tmp_path):
        """ProcessReplica + Supervisor over a real child process: kill
        -9 the replica, one supervisor sweep respawns it and re-admits
        it only after /health is green again."""
        import socket

        script = tmp_path / "stub_replica.py"
        script.write_text(CHILD_SERVER)
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        rep = ProcessReplica(
            f"{sys.executable} {script} {{port}}", port=port
        ).start()
        router = None
        sup = None
        try:
            deadline = time.monotonic() + 30
            from tensorflow_examples_tpu.serving.router import _get_json

            while time.monotonic() < deadline:
                if _get_json(rep.url + "/health", 1.0)[0] == 200:
                    break
                time.sleep(0.05)
            else:
                pytest.fail("stub replica never came up")
            router = Router(
                [rep.url], cfg=RouterConfig(probe_interval_s=60.0)
            )
            router.probe_once()
            sup = Supervisor(
                router, [rep], poll_s=0.05, health_stall_s=2.0,
                warm_timeout_s=30.0,
            )
            rep._proc.kill()  # SIGKILL: no drain, no goodbye
            rep._proc.wait(timeout=10)
            assert not rep.alive()
            sup.check_once()  # detect -> quarantine -> respawn -> green
            assert rep.alive()
            assert [e for _, e in sup.events] == [
                "detected", "restarted", "readmitted"
            ]
            assert not router.replicas[0].quarantined
            assert (
                router.registry.counter_values()[
                    "router/restarts_total"
                ] == 1
            )
            assert _get_json(rep.url + "/health", 2.0)[0] == 200
        finally:
            if sup is not None:
                sup.close()
            if router is not None:
                router.close()
            rep.close()


# ------------------------------------------- crash-loop abandonment


class TestCrashLoopAbandonment:
    """ISSUE 13 satellite: a replica that exhausts ``max_restarts``
    while traffic is in flight stays quarantined — the router never
    re-dispatches to it, and its in-flight requests fail over
    token-identically."""

    @pytest.mark.timeout(120)
    def test_crash_looping_replica_abandoned_under_load(
        self, serve_faults
    ):
        serve_faults("crash@0:2")
        builds = [0]

        def flaky_factory():
            # First build (fleet start) succeeds; every supervisor
            # restart of this replica fails — a crash-looping build.
            builds[0] += 1
            if builds[0] > 1:
                raise RuntimeError("crash-looping build")
            return _FakeEngine(step_delay=0.005, replica_id=0)

        fleet = ChaosFleet(
            [flaky_factory,
             lambda: _FakeEngine(step_delay=0.005, replica_id=1)],
            router_cfg=RouterConfig(
                probe_interval_s=0.05, retry_budget_s=20.0,
                max_retries=4, eject_after=1, eject_cooldown_s=0.5,
            ),
            supervisor_kw=dict(
                poll_s=0.05, health_stall_s=2.0, warm_timeout_s=30.0,
                max_restarts=2, restart_backoff_s=0.01,
            ),
        )
        fleet.start()
        rfront = RouterFrontend(fleet.router, port=0).start()
        try:
            import serve_bench

            url = rfront.url("/generate")
            n, max_new = 10, 4
            prompts = [[3 * i + 1] for i in range(n)]
            # Concurrent load across the kill: replica 0 dies
            # mid-decode (crash@0:2) and every restart attempt fails.
            out = serve_bench.drive(
                None, prompts, concurrency=4, max_new=max_new,
                temperature=0.0, top_k=0, http_url=url, timeout=30.0,
            )
            vocab = fleet.replicas[1].engine.model_cfg.vocab_size
            for prompt, reply in zip(prompts, out["replies"]):
                assert reply is not None and reply[0] == 200, reply
                # Token-identical failover: the fake stream is a pure
                # function of the prompt, so a replayed victim matches.
                assert reply[1]["tokens"] == [
                    (prompt[-1] + 1 + j) % vocab for j in range(max_new)
                ]
            counters = fleet.router.registry.counter_values()
            assert counters.get("router/failovers_total", 0) >= 1
            # The supervisor exhausts max_restarts and gives up.
            url0 = fleet.replicas[0].url
            deadline = time.monotonic() + 30
            while (
                url0 not in fleet.supervisor.given_up
                and time.monotonic() < deadline
            ):
                time.sleep(0.02)
            assert url0 in fleet.supervisor.given_up
            events = [
                e for u, e in fleet.supervisor.events if u == url0
            ]
            assert events[0] == "detected"
            assert events[-1] == "gave_up"
            assert "readmitted" not in events
            # Abandoned = quarantined, ineligible, never restarted.
            state0 = fleet.router._find(url0)
            assert state0.quarantined
            assert not state0.eligible(fleet.router.cfg.unhealthy_after)
            assert fleet.supervisor.restarts[url0] == 0
            assert counters.get("router/restarts_total", 0) == 0
            # The router never re-dispatches to the abandoned replica:
            # follow-up traffic serves 200 off the survivor alone.
            dispatched_before = state0.dispatched
            for i in range(4):
                status, reply = _post(
                    url, {"prompt": [50 + i], "max_new_tokens": 2}
                )
                assert status == 200
                assert reply["tokens"] == [
                    (50 + i + 1 + j) % vocab for j in range(2)
                ]
            assert state0.dispatched == dispatched_before
        finally:
            rfront.close()
            fleet.close()


# --------------------------------------------------- THE chaos golden


CHAOS_MODEL = dict(
    vocab_size=211,
    max_len=32,
    num_layers=1,
    num_heads=2,
    d_model=16,
    dropout=0.0,
    attention="xla",
)


def _real_engine_factory(spec_decode_k: int = 0, role: str = "mixed"):
    """Tiny REAL paged engine for the golden: small enough that three
    warmups + one supervisor re-warm stay tier-1 friendly, real enough
    that the token-identity and zero-recompile claims mean something.
    ``spec_decode_k`` arms speculative decoding (ISSUE 11) — the chaos
    contract must hold with the verify path on the hot loop too.
    ``role`` builds the heterogeneous prefill/decode fleets of the
    ISSUE 12 golden."""
    import jax
    import jax.numpy as jnp

    from tensorflow_examples_tpu.models import transformer
    from tensorflow_examples_tpu.serving.engine import InferenceEngine

    cfg = transformer.TransformerConfig(**CHAOS_MODEL)
    model = transformer.Transformer(cfg)
    tokens = jnp.zeros((1, 8), jnp.int32)
    params = model.init(
        {"params": jax.random.PRNGKey(0)}, tokens
    )["params"]
    return InferenceEngine(
        cfg,
        params,
        cfg=ServeConfig(
            max_slots=4, prefill_bucket_floor=16, kv_bucket_floor=16,
            kv_block_size=8, max_delay_s=0.0, request_timeout_s=60.0,
            spec_decode_k=spec_decode_k, role=role,
        ),
        registry=MetricsRegistry(),
    )


def _spec_engine_factory():
    return _real_engine_factory(spec_decode_k=2)


def _prefill_engine_factory():
    return _real_engine_factory(role="prefill")


def _decode_engine_factory():
    return _real_engine_factory(role="decode")


class TestChaosGolden:
    @pytest.mark.timeout(480)
    def test_kill_one_of_three_zero_failed_requests(self, serve_faults):
        """ISSUE 10 acceptance: 3 in-proc paged replicas under
        concurrent load; killing one mid-decode yields ZERO failed
        requests, every replayed stream token-identical to the
        unbatched reference, the supervisor restores the fleet to 3
        healthy replicas, and the survivors take zero post-warmup
        recompiles."""
        import serve_bench

        fault_engine = serve_faults("crash@1:3")
        fleet = ChaosFleet(
            [_real_engine_factory] * 3,
            router_cfg=RouterConfig(
                probe_interval_s=0.1, retry_budget_s=30.0,
                max_retries=4, eject_after=1, eject_cooldown_s=1.0,
            ),
            supervisor_kw=dict(
                poll_s=0.05, health_stall_s=3.0, warm_timeout_s=240.0,
            ),
        )
        fleet.start()
        rfront = RouterFrontend(fleet.router, port=0).start()
        try:
            n, max_new = 12, 6
            prompts = serve_bench.make_prompts(
                n, vocab=CHAOS_MODEL["vocab_size"],
                max_len=CHAOS_MODEL["max_len"], max_new=max_new,
                seed=7, shared_prefix_every=4,
            )
            out = serve_bench.drive(
                None, prompts, concurrency=4, max_new=max_new,
                temperature=0.7, top_k=0,
                http_url=rfront.url("/generate"), timeout=60.0,
            )
            statuses = [
                r[0] if r is not None else None for r in out["replies"]
            ]
            # ZERO failed requests across the replica kill.
            assert statuses.count(200) == n, statuses
            # The kill actually happened, mid-decode, and victims were
            # failed over (replayed from the prompt elsewhere).
            assert ("crash", 1, 3) in fault_engine.fired
            counters = fleet.router.registry.counter_values()
            assert counters.get("router/failovers_total", 0) >= 1
            assert counters.get("router/ejections_total", 0) >= 1
            # Every stream — failed-over ones included — is
            # token-identical to the unbatched reference (the
            # per-request fold_in seeding makes replay invisible).
            ref_engine = fleet.replicas[0].engine
            for i, prompt in enumerate(prompts):
                expect = ref_engine.reference_generate(
                    prompt, max_new=max_new, seed=i,
                    temperature=0.7, top_k=0,
                )
                got = out["replies"][i][1]["tokens"]
                assert got == expect, (
                    f"request {i} diverged after failover: "
                    f"{got} != {expect}"
                )
            # The supervisor restores the fleet: restart -> re-warm ->
            # /health green -> readmit, no operator action.
            assert fleet.await_fleet_green(3, timeout_s=240)
            events = [
                e for u, e in fleet.supervisor.events
                if u == fleet.replicas[1].url
            ]
            assert events[:3] == ["detected", "restarted", "readmitted"]
            counters = fleet.router.registry.counter_values()
            assert counters.get("router/restarts_total", 0) == 1
            # Zero post-warmup recompiles on the survivors (and on the
            # freshly re-warmed replica).
            for rep in fleet.replicas:
                assert rep.engine.post_warmup_recompiles() == 0
            # The fleet serves after restoration — including the
            # restarted replica's slot in the rotation.
            for i in range(4):
                status, reply = _post(
                    rfront.url("/generate"),
                    {"prompt": [3 + i], "max_new_tokens": 2,
                     "seed": 99 + i},
                )
                assert status == 200
            # Schema v7: the router's stats line carries the
            # fault-tolerance counters and validates.
            line = json.loads(json.dumps(fleet.router.stats_line()))
            assert schema.validate_line(line) == []
            assert line["schema_version"] == schema.SERVING_SCHEMA_VERSION
            assert line["serving"]["router_failovers"] >= 1
            assert line["serving"]["router_ejections"] >= 1
            assert line["serving"]["router_restarts"] == 1
        finally:
            rfront.close()
            fleet.close()

    @pytest.mark.timeout(480)
    def test_kill_one_of_three_with_speculation_on(self, serve_faults):
        """ISSUE 11 acceptance: the kill-one-of-three chaos contract
        holds with SPECULATIVE decoding enabled (spec_decode_k=2) —
        zero failed requests, and every failover replay token-identical
        to the unbatched reference. Speculation is seed-deterministic
        per position, so a victim replayed from the prompt on a
        survivor commits exactly the same stream no matter how its
        draft windows land."""
        import serve_bench

        fault_engine = serve_faults("crash@1:3")
        fleet = ChaosFleet(
            [_spec_engine_factory] * 3,
            router_cfg=RouterConfig(
                probe_interval_s=0.1, retry_budget_s=30.0,
                max_retries=4, eject_after=1, eject_cooldown_s=1.0,
            ),
            supervisor_kw=dict(
                poll_s=0.05, health_stall_s=3.0, warm_timeout_s=240.0,
            ),
        )
        fleet.start()
        rfront = RouterFrontend(fleet.router, port=0).start()
        try:
            n, max_new = 10, 5
            prompts = serve_bench.make_prompts(
                n, vocab=CHAOS_MODEL["vocab_size"],
                max_len=CHAOS_MODEL["max_len"], max_new=max_new,
                seed=17, shared_prefix_every=4,
            )
            out = serve_bench.drive(
                None, prompts, concurrency=3, max_new=max_new,
                temperature=0.7, top_k=0,
                http_url=rfront.url("/generate"), timeout=60.0,
            )
            statuses = [
                r[0] if r is not None else None for r in out["replies"]
            ]
            assert statuses.count(200) == n, statuses
            assert ("crash", 1, 3) in fault_engine.fired
            counters = fleet.router.registry.counter_values()
            assert counters.get("router/failovers_total", 0) >= 1
            ref_engine = fleet.replicas[0].engine
            for i, prompt in enumerate(prompts):
                expect = ref_engine.reference_generate(
                    prompt, max_new=max_new, seed=i,
                    temperature=0.7, top_k=0,
                )
                got = out["replies"][i][1]["tokens"]
                assert got == expect, (
                    f"speculative request {i} diverged after failover: "
                    f"{got} != {expect}"
                )
            assert fleet.await_fleet_green(3, timeout_s=240)
            for rep in fleet.replicas:
                assert rep.engine.post_warmup_recompiles() == 0
        finally:
            rfront.close()
            fleet.close()

    @pytest.mark.timeout(480)
    def test_kill_prefill_replica_mid_handoff(self, serve_faults):
        """ISSUE 12 acceptance: a HETEROGENEOUS fleet (1 prefill + 2
        decode replicas) serves through the prefill->decode KV-page
        handoff; killing the prefill replica mid-handoff (its fault
        schedule counts prefills — the prefill-role unit of work)
        yields ZERO failed requests: the router falls back to full
        /generate on the decode replicas (roles are advisory, so the
        failover is ordinary), every stream stays token-identical to
        the unbatched reference, and the supervisor restores the
        prefill replica — role preserved — without operator action."""
        import serve_bench

        fault_engine = serve_faults("crash@0:2")
        fleet = ChaosFleet(
            [_prefill_engine_factory, _decode_engine_factory,
             _decode_engine_factory],
            router_cfg=RouterConfig(
                probe_interval_s=0.1, retry_budget_s=30.0,
                max_retries=4, eject_after=1, eject_cooldown_s=1.0,
            ),
            supervisor_kw=dict(
                poll_s=0.05, health_stall_s=3.0, warm_timeout_s=240.0,
            ),
        )
        fleet.start()
        assert fleet.role_census() == {"prefill": 1, "decode": 2}
        rfront = RouterFrontend(fleet.router, port=0).start()
        try:
            # The probe sweep must learn the role topology before the
            # first dispatch exercises the handoff path.
            deadline = time.monotonic() + 30
            while (
                not fleet.router._disagg_ready()
                and time.monotonic() < deadline
            ):
                time.sleep(0.02)
            assert fleet.router._disagg_ready()
            n, max_new = 10, 5
            prompts = serve_bench.make_prompts(
                n, vocab=CHAOS_MODEL["vocab_size"],
                max_len=CHAOS_MODEL["max_len"], max_new=max_new,
                seed=29, shared_prefix_every=4,
            )
            out = serve_bench.drive(
                None, prompts, concurrency=3, max_new=max_new,
                temperature=0.7, top_k=0,
                http_url=rfront.url("/generate"), timeout=60.0,
            )
            statuses = [
                r[0] if r is not None else None for r in out["replies"]
            ]
            # ZERO failed requests across the prefill-replica kill.
            assert statuses.count(200) == n, statuses
            # The kill actually happened, mid-prefill on the prefill
            # replica, and the router failed over.
            assert ("crash", 0, 2) in fault_engine.fired
            counters = fleet.router.registry.counter_values()
            assert counters.get("router/failovers_total", 0) >= 1
            # Handoffs completed before the kill (the topology was
            # exercised, not just built).
            assert counters.get("router/handoffs_total", 0) >= 1
            # Token-identical — handed-off, failed-over, and fallback
            # full-path streams alike (pure function of params/prompt/
            # seed).
            ref_engine = fleet.replicas[1].engine
            for i, prompt in enumerate(prompts):
                expect = ref_engine.reference_generate(
                    prompt, max_new=max_new, seed=i,
                    temperature=0.7, top_k=0,
                )
                got = out["replies"][i][1]["tokens"]
                assert got == expect, (
                    f"request {i} diverged across the handoff kill: "
                    f"{got} != {expect}"
                )
            # The supervisor restores the fleet — the restarted
            # replica comes back with its PREFILL role.
            assert fleet.await_fleet_green(3, timeout_s=240)
            events = [
                e for u, e in fleet.supervisor.events
                if u == fleet.replicas[0].url
            ]
            assert events[:3] == ["detected", "restarted", "readmitted"]
            assert fleet.role_census() == {"prefill": 1, "decode": 2}
            for rep in fleet.replicas:
                assert rep.engine.post_warmup_recompiles() == 0
            # Post-restore, the handoff path serves again.
            fleet.router.probe_once()
            handoffs_before = counters.get("router/handoffs_total", 0)
            status, reply = _post(
                rfront.url("/generate"),
                {"prompt": [11, 12, 13], "max_new_tokens": 3,
                 "seed": 77},
            )
            assert status == 200
            assert reply["tokens"] == ref_engine.reference_generate(
                [11, 12, 13], max_new=3, seed=77
            )
            counters = fleet.router.registry.counter_values()
            assert counters.get(
                "router/handoffs_total", 0
            ) > handoffs_before
        finally:
            rfront.close()
            fleet.close()

    @pytest.mark.timeout(480)
    def test_decode_crash_yields_one_stitched_trace(self, serve_faults):
        """ISSUE 18 acceptance: a disaggregated fleet (1 prefill + 2
        decode) under chaos — a decode replica crashes mid-decode —
        leaves ONE stitched trace for the failed-over request: the
        dead attempt's leg span (transport status 0) and the answering
        one side by side under the same root, the replica-side
        queue/prefill/decode segments nested under the attempt that
        carried them, root wall ≈ the client-measured e2e, zero
        post-warmup recompiles, and tools/trace_report.py's critical
        path walking into the leg that ANSWERED, not the dead one."""
        import serve_bench
        import trace_report

        fault_engine = serve_faults("crash@1:3")
        fleet = ChaosFleet(
            [_prefill_engine_factory, _decode_engine_factory,
             _decode_engine_factory],
            router_cfg=RouterConfig(
                probe_interval_s=0.1, retry_budget_s=30.0,
                max_retries=4, eject_after=1, eject_cooldown_s=1.0,
                # A chaos golden inspects EVERY trace — no sampler coin.
                trace_sample_fraction=1.0,
            ),
            supervisor_kw=dict(
                poll_s=0.05, health_stall_s=3.0, warm_timeout_s=240.0,
            ),
        )
        fleet.start()
        assert fleet.role_census() == {"prefill": 1, "decode": 2}
        rfront = RouterFrontend(fleet.router, port=0).start()
        try:
            deadline = time.monotonic() + 30
            while (
                not fleet.router._disagg_ready()
                and time.monotonic() < deadline
            ):
                time.sleep(0.02)
            assert fleet.router._disagg_ready()
            n, max_new = 10, 5
            prompts = serve_bench.make_prompts(
                n, vocab=CHAOS_MODEL["vocab_size"],
                max_len=CHAOS_MODEL["max_len"], max_new=max_new,
                seed=37, shared_prefix_every=4,
            )
            out = serve_bench.drive(
                None, prompts, concurrency=3, max_new=max_new,
                temperature=0.7, top_k=0,
                http_url=rfront.url("/generate"), timeout=60.0,
            )
            statuses = [
                r[0] if r is not None else None for r in out["replies"]
            ]
            assert statuses.count(200) == n, statuses
            # The decode replica died mid-decode and the router failed
            # the victims over.
            assert ("crash", 1, 3) in fault_engine.fired
            counters = fleet.router.registry.counter_values()
            assert counters.get("router/failovers_total", 0) >= 1
            assert counters.get("router/handoffs_total", 0) >= 1
            # Every reply names its trace, and every trace finished.
            docs = []
            for i, (status, reply) in enumerate(out["replies"]):
                doc = fleet.router.recorder.get(reply["trace_id"])
                assert doc is not None and not doc.get("open"), i
                docs.append(doc)
            failed_over = [
                d for d in docs if "failover" in d["flags"]
            ]
            assert failed_over, [d["flags"] for d in docs]
            doc = failed_over[0]
            idx = docs.index(doc)
            names = [s["name"] for s in doc["spans"]]
            # ONE tree: a single root covering the whole request.
            assert names.count("request") == 1
            root = next(
                s for s in doc["spans"] if s["name"] == "request"
            )
            # Both attempts of the interrupted hop are in the tree —
            # the dead one (transport, status 0) AND the one that
            # answered — whether the router retried the leg or fell
            # back to the full path.
            attempts = [
                s for s in doc["spans"]
                if s["name"] in ("prefill_leg", "resume_leg", "dispatch")
            ]
            assert len(attempts) >= 2, names
            att_statuses = [s["tags"]["status"] for s in attempts]
            assert 0 in att_statuses, att_statuses
            assert 200 in att_statuses, att_statuses
            # Replica-side segments crossed the wire and nest under an
            # attempt span (never float at the root).
            attempt_ids = {s["span_id"] for s in attempts}
            segs = [
                s for s in doc["spans"]
                if s["name"] in ("queue_wait", "prefill",
                                 "prefill_chunk", "decode_segment",
                                 "resume_import")
            ]
            assert any(s["name"] == "queue_wait" for s in segs), names
            assert any(
                s["name"] == "decode_segment" for s in segs
            ), names
            assert all(
                s["parent_id"] in attempt_ids for s in segs
            ), names
            # The span tree accounts for the client's wall: the root
            # covers (almost all of) the measured e2e — transport
            # overhead is the only slack.
            client = out["client_s"][idx]
            assert root["dur_s"] <= client + 0.05
            assert root["dur_s"] >= 0.5 * client, (
                root["dur_s"], client
            )
            # The attribution tool walks the path that ANSWERED: the
            # dead attempt ended early, so the critical path (latest
            # finisher chain) goes through the 200 leg.
            path = trace_report.critical_path(doc)
            assert path and path[0]["name"] == "request"
            leg_row = next(
                r for r in path
                if r["name"] in ("prefill_leg", "resume_leg", "dispatch")
            )
            assert leg_row["tags"]["status"] == 200, path
            # Forced keep: a failed-over trace is never sampled away.
            assert doc["kept"] is True
            assert doc["keep_reason"] in ("failover", "retried", "slow")
            # Fleet restored; zero post-warmup recompiles everywhere.
            assert fleet.await_fleet_green(3, timeout_s=240)
            for rep in fleet.replicas:
                assert rep.engine.post_warmup_recompiles() == 0
            # The v13 stats line tells the same story and validates.
            line = json.loads(json.dumps(fleet.router.stats_line()))
            assert schema.validate_line(line) == []
            serving = line["serving"]
            assert serving["traces_kept"] >= n
            assert serving["trace_coverage"] == 1.0
        finally:
            rfront.close()
            fleet.close()


# ------------------------------------- ISSUE 16: the control plane dies


class TestRouterPairFake:
    """Takeover mechanics over device-free fake replicas: the full
    RouterPair choreography (journal, lease, killrouter, promotion,
    client failover, dedupe, split-brain fence) at O(ms) per request.
    The real-engine version with token-identity is TestTakeoverGolden."""

    @pytest.mark.timeout(120)
    def test_killrouter_takeover_zero_lost_requests(
        self, serve_faults, tmp_path
    ):
        import serve_bench

        fault_engine = serve_faults("killrouter@3")
        fleet = _fake_fleet(2)
        pair = RouterPair(
            fleet.urls,
            journal_path=str(tmp_path / "journal.jsonl"),
            lease_path=str(tmp_path / "lease.json"),
            router_cfg=fleet.router_cfg,
            standby_interval_s=0.05,
            miss_budget_s=0.3,
        )
        pair.supervisor = fleet.supervisor
        pair.start()
        try:
            n, max_new = 8, 4
            prompts = serve_bench.make_prompts(
                n, vocab=211, max_len=64, max_new=max_new, seed=11,
            )
            out = serve_bench._drive_takeover(
                pair.endpoints(), prompts, concurrency=3,
                max_new=max_new, temperature=0.0, top_k=0,
                timeout=30.0,
            )
            statuses = [
                r[0] if r is not None else None for r in out["replies"]
            ]
            # ZERO lost accepted requests across the router kill: the
            # client's two-endpoint retry loop plus the journal absorb
            # it.
            assert statuses.count(200) == n, statuses
            assert any(k == "killrouter" for k, _, _ in fault_engine.fired)
            # The standby serves as soon as it holds the lease — replay
            # may still be in flight when the drive returns, so wait
            # for promote() to finish rather than sampling the event.
            assert pair.monitor.promoted.wait(10.0)
            assert pair.monitor.takeover_latency_s is not None
            # The dispatch the kill interrupted was left incomplete in
            # the journal and replayed by the promoted standby.
            assert pair.monitor.replayed >= 1
            # The supervisor now reports restarts to the NEW active
            # router (adopt_router on promotion).
            assert fleet.supervisor.router is pair.standby
            # Nothing is left on the replay worklist.
            assert pair.journal.incomplete() == []
            # Explicit idempotent retry against the active endpoint:
            # original tokens, dedup-flagged, no second generation.
            orig = out["replies"][0][1]["tokens"]
            status, dup = _post(pair.endpoints()[1], {
                "prompt": prompts[0], "max_new_tokens": max_new,
                "seed": 0, "request_id": "tko-0",
            })
            assert status == 200 and dup.get("dedup") is True
            assert dup["tokens"] == orig
            counters = pair.registry.counter_values()
            assert counters.get("router/dedup_hits_total", 0) >= 1
            assert counters.get("router/takeover_total", 0) == 1
            # Resume: the remainder of the SAME stream from an offset.
            status, res = _post(pair.endpoints()[1], {
                "prompt": prompts[0], "max_new_tokens": max_new,
                "seed": 0, "request_id": "tko-0", "resume_from": 2,
            })
            assert status == 200 and res["tokens"] == orig[2:]
            assert res.get("resumed") is True
        finally:
            pair.close()
            fleet.close()

    @pytest.mark.timeout(120)
    def test_split_brain_fenced_dispatch_refused(self, tmp_path):
        """The split-brain pin: a primary that STALLS (misses its
        heartbeats without dying) is fenced by the promoted standby's
        newer token — its own dispatch path refuses to serve, so no
        request is ever handled by two routers."""
        fleet = _fake_fleet(2)
        pair = RouterPair(
            fleet.urls,
            journal_path=str(tmp_path / "journal.jsonl"),
            lease_path=str(tmp_path / "lease.json"),
            router_cfg=fleet.router_cfg,
            standby_interval_s=0.05,
            miss_budget_s=0.2,
        )
        pair.start()
        try:
            # The live primary serves.
            status, reply = _post(pair.endpoints()[0], {
                "prompt": [7], "max_new_tokens": 2,
            })
            assert status == 200 and reply["tokens"] == [8, 9]
            # Simulate the stall: stop the primary's loops (heartbeats
            # cease) WITHOUT closing its HTTP frontend — the process is
            # alive, just not heartbeating (GC pause, CPU starvation).
            pair.primary.close()
            deadline = time.monotonic() + 30
            while (
                not pair.monitor.promoted.is_set()
                and time.monotonic() < deadline
            ):
                time.sleep(0.02)
            assert pair.monitor.promoted.is_set()
            # The revived primary's dispatch is REFUSED: retryable
            # fenced 503, counter stamped — the same check that kept
            # the standby passive before promotion.
            assert pair.primary.fenced()
            status, body = _post(pair.endpoints()[0], {
                "prompt": [7], "max_new_tokens": 2,
            })
            assert status == 503 and body.get("fenced") is True
            assert body.get("retry") is True
            counters = pair.registry.counter_values()
            assert counters.get("router/fenced_dispatch_total", 0) >= 1
            # Its stale heartbeat can never clobber the new lease.
            assert pair.lease.heartbeat(1) is False
            assert pair.lease.read()["token"] == 2
            # The promoted standby serves the same request correctly.
            status, reply = _post(pair.endpoints()[1], {
                "prompt": [7], "max_new_tokens": 2,
            })
            assert status == 200 and reply["tokens"] == [8, 9]
        finally:
            pair.close()
            fleet.close()


class TestTakeoverGolden:
    @pytest.mark.timeout(480)
    def test_killrouter_mid_stream_zero_lost_token_identical(
        self, serve_faults, tmp_path
    ):
        """ISSUE 16 acceptance: a 2-replica REAL fleet with a
        primary/standby router pair under concurrent sampled load;
        ``killrouter`` fires mid-stream. The standby promotes within
        the heartbeat budget, ZERO accepted requests are lost, every
        stream — died-in-flight, journal-replayed, client-retried —
        is token-identical to the unbatched reference, a duplicated
        request_id retry returns the ORIGINAL tokens as a dedupe hit
        (no second generation), the fleet takes zero post-warmup
        recompiles, and the v12 stats line validates."""
        import serve_bench

        fault_engine = serve_faults("killrouter@3")
        fleet = ChaosFleet(
            [_real_engine_factory] * 2,
            router_cfg=RouterConfig(
                probe_interval_s=0.1, retry_budget_s=30.0,
                max_retries=4, eject_after=2, eject_cooldown_s=1.0,
            ),
            supervisor_kw=dict(
                poll_s=0.05, health_stall_s=3.0, warm_timeout_s=240.0,
            ),
        )
        fleet.start()
        miss_budget_s = 1.0
        pair = RouterPair(
            fleet.urls,
            journal_path=str(tmp_path / "journal.jsonl"),
            lease_path=str(tmp_path / "lease.json"),
            router_cfg=fleet.router_cfg,
            standby_interval_s=0.1,
            miss_budget_s=miss_budget_s,
        )
        pair.supervisor = fleet.supervisor
        pair.start()
        try:
            n, max_new = 10, 6
            prompts = serve_bench.make_prompts(
                n, vocab=CHAOS_MODEL["vocab_size"],
                max_len=CHAOS_MODEL["max_len"], max_new=max_new,
                seed=23, shared_prefix_every=4,
            )
            out = serve_bench._drive_takeover(
                pair.endpoints(), prompts, concurrency=4,
                max_new=max_new, temperature=0.7, top_k=0,
                timeout=60.0,
            )
            statuses = [
                r[0] if r is not None else None for r in out["replies"]
            ]
            # ZERO lost accepted requests across the router kill.
            assert statuses.count(200) == n, statuses
            assert any(
                k == "killrouter" for k, _, _ in fault_engine.fired
            )
            # The standby promoted, within the heartbeat budget (the
            # promotion verb itself: acquire + sweep + replay). Clients
            # can drain against the lease-holding standby before replay
            # completes, so wait for the event instead of sampling it.
            assert pair.monitor.promoted.wait(10.0)
            latency = pair.monitor.takeover_latency_s
            assert latency is not None and latency <= miss_budget_s * 10
            # The interrupted dispatch replayed from the journal.
            assert pair.monitor.replayed >= 1
            assert pair.journal.incomplete() == []
            # Every stream is token-identical to the unbatched
            # reference — takeover, replay, and client retries are
            # invisible in the tokens (pure function of params/prompt/
            # seed).
            ref_engine = fleet.replicas[0].engine
            for i, prompt in enumerate(prompts):
                expect = ref_engine.reference_generate(
                    prompt, max_new=max_new, seed=i,
                    temperature=0.7, top_k=0,
                )
                got = out["replies"][i][1]["tokens"]
                assert got == expect, (
                    f"request {i} diverged across takeover: "
                    f"{got} != {expect}"
                )
            # Idempotency: duplicate request_id returns the ORIGINAL
            # stream as a dedupe hit — no second generation burned.
            dispatched_before = pair.registry.counter_values().get(
                "router/dispatched_total", 0
            )
            orig = out["replies"][0][1]["tokens"]
            status, dup = _post(pair.endpoints()[1], {
                "prompt": prompts[0], "max_new_tokens": max_new,
                "temperature": 0.7, "top_k": 0, "seed": 0,
                "request_id": "tko-0",
            })
            assert status == 200 and dup.get("dedup") is True
            assert dup["tokens"] == orig
            counters = pair.registry.counter_values()
            assert counters.get("router/dedup_hits_total", 0) >= 1
            assert counters.get(
                "router/dispatched_total", 0
            ) == dispatched_before
            # Stitched ACROSS routers (ISSUE 18): the journal's done
            # record carries the original request's trace_id; the
            # promoted router's dedupe fast path adopts it, so the
            # duplicate's reply names the ORIGINAL trace and the
            # pair-shared recorder holds ONE merged tree — the
            # original pass's spans plus the dedupe hit.
            orig_tid = pair.journal.lookup("tko-0")["trace_id"]
            assert isinstance(orig_tid, str) and orig_tid
            assert dup["trace_id"] == orig_tid
            tdoc = pair.recorder.get(orig_tid)
            assert tdoc is not None and not tdoc.get("open")
            tnames = [s["name"] for s in tdoc["spans"]]
            assert "dedupe_hit" in tnames
            assert tnames.count("request") >= 2  # both passes' roots
            assert "deduped" in tdoc["flags"]
            assert tdoc["kept"] is True
            # Zero post-warmup recompiles fleet-wide.
            for rep in fleet.replicas:
                assert rep.engine.post_warmup_recompiles() == 0
            # The promoted router's stats line is schema-v12 and tells
            # the whole story (shared registry survives the switch).
            line = json.loads(json.dumps(pair.standby.stats_line()))
            assert schema.validate_line(line) == []
            assert line["schema_version"] == 14
            serving = line["serving"]
            assert serving["takeover_total"] == 1
            assert serving["journal_appends"] >= 2 * n
            assert serving["dedup_hits"] >= 1
            assert serving["takeover_latency_s"] == pytest.approx(
                latency
            )
            # Split-brain coda: the dead primary's fencing token is
            # stale — were it revived, its dispatch path refuses.
            assert pair.primary.fenced()
            status, body = pair.primary.handle(
                {"prompt": [5], "max_new_tokens": 2}, kind="generate"
            )
            assert status == 503 and body.get("fenced") is True
        finally:
            pair.close()
            fleet.close()


class TestAlertGolden:
    """ISSUE 19's chaos acceptance golden: inject a latency fault into
    one replica of a healthy fleet -> the SLO engine walks pending ->
    firing with an alert that names the SLO class and carries a
    resolvable worst-offender exemplar whose trace names the sick
    replica -> clear the fault -> the alert resolves after sustained
    health. The whole episode lands in the v14 alert sink."""

    @pytest.mark.timeout(300)
    def test_latency_fault_fires_then_resolves(
        self, serve_faults, tmp_path
    ):
        from tensorflow_examples_tpu.telemetry.slo import (
            AlertEngine,
            SLOConfig,
            SLOObjective,
        )

        # Replica 0 sleeps 0.25 s at EVERY decode step: ~0.75 s per
        # 3-token request against a 0.2 s e2e ceiling.
        serve_faults("slowrep@0:0.25")
        fleet = _fake_fleet(2, router_cfg=RouterConfig(
            probe_interval_s=0.05, retry_budget_s=20.0, max_retries=4,
            eject_after=4, eject_cooldown_s=0.5,
            trace_sample_fraction=1.0,
        ))
        path = str(tmp_path / "alerts.jsonl")
        # Chaos-tier windows: seconds, not minutes, and no dwell on the
        # firing edge (two evaluate ticks suffice).
        fleet.router.alerts = AlertEngine(
            SLOConfig(
                objectives=(SLOObjective(slo="interactive",
                                         e2e_p95_s=0.2,
                                         error_budget=0.1),),
                windows_s=(0.5, 2.0), burn_thresholds=(2.0, 1.0),
                pending_for_s=0.0, resolve_after_s=0.2,
            ),
            registry=fleet.router.registry, path=path,
        )
        rfront = RouterFrontend(fleet.router, port=0).start()
        try:
            url = rfront.url("/generate")
            deadline = time.time() + 90
            fired = None
            while fired is None and time.time() < deadline:
                for i in range(4):
                    status, _ = _post(
                        url, {"prompt": [i + 2], "max_new_tokens": 3}
                    )
                    assert status == 200
                for a in fleet.router.alerts.evaluate():
                    if (a["name"] == "e2e_interactive"
                            and a["state"] == "firing"):
                        fired = a
            assert fired is not None, "alert never fired under fault"
            # The alert names the SLO class and carries the exemplar.
            assert fired["slo"] == "interactive"
            assert fired["severity"] in ("page", "ticket")
            assert fired["burn_rate"] >= 2.0
            assert fired["value"] > 0.2  # the worst offender's e2e
            tid = fired.get("trace_id")
            assert isinstance(tid, str) and tid
            # The exemplar RESOLVES: the recorder holds the trace, and
            # its dispatch leg names the sick replica — alert ->
            # trace_report --trace-id is one copy-paste.
            tdoc = fleet.router.recorder.get(tid)
            assert tdoc is not None and not tdoc.get("open")
            legs = [
                s for s in tdoc["spans"]
                if (s.get("tags") or {}).get("replica")
            ]
            assert legs, tdoc["spans"]
            assert legs[-1]["tags"]["replica"] == fleet.replicas[0].url
            # Clear the fault: organic traffic goes healthy, the burn
            # drains out of the fast window, and the rule resolves.
            faults_mod.serve_clear()
            resolved = None
            deadline = time.time() + 90
            while resolved is None and time.time() < deadline:
                for i in range(4):
                    _post(url, {"prompt": [i + 2],
                                "max_new_tokens": 3})
                time.sleep(0.1)
                for a in fleet.router.alerts.evaluate():
                    if (a["name"] == "e2e_interactive"
                            and a["state"] == "resolved"):
                        resolved = a
            assert resolved is not None, "alert never resolved"
            stats = fleet.router.alerts.stats()
            assert stats["alerts_firing"] == 0
            assert stats["alert_count"] >= 1
            # The episode is durable: firing AND resolved transitions
            # in the sink, every line schema-v14 valid.
            with open(path) as f:
                lines = [json.loads(ln) for ln in f if ln.strip()]
            states = [ln["alert"]["state"] for ln in lines]
            assert "firing" in states and "resolved" in states
            for ln in lines:
                assert ln["schema_version"] == 14
                assert schema.validate_line(ln) == [], ln
            # Zero post-warmup recompiles fleet-wide (the standing
            # serving acceptance bar).
            for rep in fleet.replicas:
                assert rep.engine.post_warmup_recompiles() == 0
        finally:
            rfront.close()
            fleet.close()
