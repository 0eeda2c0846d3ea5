"""Overload tier (ISSUE 13): load is a normal input.

The load-bearing tests:

* :class:`TestFlashCrowdGolden` — a seeded 3x flash crowd through a
  2-replica fleet (device-free engines, real batcher/frontend/router/
  HTTP): ALL shedding lands on the batch class, every interactive
  request completes with a token-identical stream, the brownout ladder
  engages and fully clears within the run.
* :class:`TestSloAdmission` / :class:`TestPreemption` — interactive is
  admitted first and PREEMPTS batch for decode slots, with the
  preempted batch request replayed token-identically.
* :class:`TestOverloadController` — the brownout ladder's state
  machine under a fake clock: one rung per hold on the way up,
  sustained-clear hysteresis on the way down, per-level enforcement.
* :class:`TestSchemaV10` — the schema bump pins: per-class p95s, shed
  counters, brownout level/transitions, digest_truncated — forbidden
  on v4-v9 serving lines like every earlier bump.
"""

import json
import os
import sys
import threading
import time

import numpy as np
import pytest

from conftest import slot_pool
from tensorflow_examples_tpu.serving.batcher import (
    ContinuousBatcher,
    QueueFull,
    Request,
)
from tensorflow_examples_tpu.serving.engine import ServeConfig
from tensorflow_examples_tpu.serving.frontend import ServingFrontend
from tensorflow_examples_tpu.serving.overload import (
    LEVEL_CAP_TOKENS,
    LEVEL_NO_SPEC,
    LEVEL_SHED_BATCH,
    LEVEL_SHED_INTERACTIVE,
    MAX_LEVEL,
    OverloadController,
)
from tensorflow_examples_tpu.serving.router import (
    Router,
    RouterConfig,
    RouterFrontend,
)
from tensorflow_examples_tpu.telemetry import schema
from tensorflow_examples_tpu.telemetry.registry import MetricsRegistry

pytestmark = pytest.mark.serving

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))


class _Clock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


class _FakeEngine:
    """Deterministic device-free engine (test_router's, plus
    ServeConfig passthrough so tests can turn the brownout knobs):
    token stream is prompt[-1]+1, +2, ... so replay after preemption
    or failover cannot change results."""

    def __init__(self, *, max_slots=4, max_queue=32, max_len=64,
                 step_delay=0.0, **cfg_kw):
        self.cfg = ServeConfig(
            max_slots=max_slots, max_queue=max_queue, max_delay_s=0.0,
            request_timeout_s=30.0, **cfg_kw,
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

    def warmup(self):
        return {}

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


def _reference(prompt, n, vocab=211):
    return [(prompt[-1] + 1 + i) % vocab for i in range(n)]


# ------------------------------------------------------------ controller


class TestOverloadController:
    def _ctl(self, **kw):
        clock = _Clock()
        base = dict(
            registry=MetricsRegistry(), queue_hi=4, kv_hi=0.9,
            clear_frac=0.5, hold_s=1.0, max_new_tokens_cap=4,
            clock=clock,
        )
        base.update(kw)
        return OverloadController(**base), clock

    def test_escalates_one_rung_per_hold(self):
        ctl, clock = self._ctl()
        assert ctl.update(queue_depth=10, kv_occupancy=0.0) == 1
        # Still hot immediately after: the hold gates the next rung.
        assert ctl.update(queue_depth=10, kv_occupancy=0.0) == 1
        clock.advance(1.1)
        assert ctl.update(queue_depth=10, kv_occupancy=0.0) == 2
        for _ in range(5):
            clock.advance(1.1)
            ctl.update(queue_depth=10, kv_occupancy=0.0)
        assert ctl.level == MAX_LEVEL  # capped at the top rung

    def test_kv_signal_alone_escalates(self):
        ctl, _ = self._ctl()
        assert ctl.update(queue_depth=0, kv_occupancy=0.95) == 1

    def test_clears_one_rung_per_sustained_hold(self):
        ctl, clock = self._ctl()
        ctl.update(queue_depth=10, kv_occupancy=0.0)
        clock.advance(1.1)
        ctl.update(queue_depth=10, kv_occupancy=0.0)
        assert ctl.level == 2
        # Below the clear watermark, but not yet for a full hold.
        ctl.update(queue_depth=0, kv_occupancy=0.0)
        assert ctl.level == 2
        clock.advance(1.1)
        assert ctl.update(queue_depth=0, kv_occupancy=0.0) == 1
        # The NEXT rung down needs its own full hold.
        assert ctl.update(queue_depth=0, kv_occupancy=0.0) == 1
        clock.advance(1.1)
        assert ctl.update(queue_depth=0, kv_occupancy=0.0) == 0

    def test_between_watermarks_holds_level(self):
        """Hysteresis band: above clear (2 = 0.5*4) but below hi (4)
        neither escalates nor clears."""
        ctl, clock = self._ctl()
        ctl.update(queue_depth=10, kv_occupancy=0.0)
        assert ctl.level == 1
        for _ in range(5):
            clock.advance(1.1)
            ctl.update(queue_depth=3, kv_occupancy=0.0)
        assert ctl.level == 1

    def test_enforcement_by_level(self):
        ctl, _ = self._ctl()
        assert not ctl.sheds("batch") and not ctl.sheds("interactive")
        assert ctl.max_new_cap() is None and not ctl.spec_disabled()
        ctl.level = LEVEL_SHED_BATCH
        assert ctl.sheds("batch") and not ctl.sheds("interactive")
        ctl.level = LEVEL_CAP_TOKENS
        assert ctl.max_new_cap() == 4 and not ctl.spec_disabled()
        ctl.level = LEVEL_NO_SPEC
        assert ctl.spec_disabled() and not ctl.sheds("interactive")
        ctl.level = LEVEL_SHED_INTERACTIVE
        assert ctl.sheds("interactive") and ctl.sheds("batch")

    def test_ttft_signal_uses_recent_window_only(self):
        ctl, clock = self._ctl(ttft_hi_s=0.5)
        ctl.note_ttft(2.0)  # way over the watermark
        assert ctl.update(queue_depth=0, kv_occupancy=0.0) == 1
        # The sample ages out of the window: pressure reads clear.
        clock.advance(10.0)
        assert ctl.ttft_p95() is None
        clock.advance(1.1)
        ctl.update(queue_depth=0, kv_occupancy=0.0)
        clock.advance(1.1)
        assert ctl.update(queue_depth=0, kv_occupancy=0.0) == 0

    def test_disabled_controller_never_moves(self):
        ctl, _ = self._ctl(enabled=False)
        assert ctl.update(queue_depth=1000, kv_occupancy=1.0) == 0
        assert not ctl.sheds("batch") and ctl.max_new_cap() is None

    def test_transitions_counted_logged_and_evented(self):
        ctl, clock = self._ctl()
        ctl.update(queue_depth=10, kv_occupancy=0.0)
        clock.advance(1.1)
        ctl.update(queue_depth=10, kv_occupancy=0.0)
        counters = ctl.registry.counter_values()
        assert counters["serving/brownout_transitions_total"] == 2
        assert counters["serving/brownout_escalations_total"] == 2
        assert ctl.registry.gauge_values()[
            "serving/brownout_level"
        ] == 2.0
        assert [(f, t) for _, f, t, _ in ctl.events] == [(0, 1), (1, 2)]
        assert "queue_depth" in ctl.events[0][3]


# ------------------------------------------------------- SLO admission


class TestSloAdmission:
    def test_interactive_admitted_before_batch(self):
        """Both classes queued before the loop starts, ONE slot: the
        interactive request must be served to completion first even
        though batch was submitted earlier."""
        eng = _FakeEngine(max_slots=1, step_delay=0.002)
        b = ContinuousBatcher(eng)
        order = []
        fut_b = b.submit(Request(prompt=[5], max_new_tokens=3,
                                 slo="batch"))
        fut_i = b.submit(Request(prompt=[9], max_new_tokens=3))
        fut_b.add_done_callback(lambda f: order.append("batch"))
        fut_i.add_done_callback(lambda f: order.append("interactive"))
        b.start()
        try:
            assert fut_i.result(timeout=10).tokens == _reference([9], 3)
            assert fut_b.result(timeout=10).tokens == _reference([5], 3)
        finally:
            b.close(drain=True)
        assert order == ["interactive", "batch"]

    def test_unknown_slo_rejected(self):
        eng = _FakeEngine()
        b = ContinuousBatcher(eng)
        fut = b.submit(Request(prompt=[1], slo="bulk"))
        with pytest.raises(ValueError, match="slo class"):
            fut.result(timeout=5)
        assert b.registry.counter_values()[
            "serving/rejected_total"
        ] == 1
        b.close(drain=False)

    def test_frontend_validates_slo_field(self):
        eng = _FakeEngine()
        b = ContinuousBatcher(eng).start()
        fe = ServingFrontend(b, port=0)
        try:
            status, reply = fe.handle_request(
                {"prompt": [1], "slo": "bulk"}, kind="generate"
            )
            assert status == 400 and "slo" in reply["error"]
            status, reply = fe.handle_request(
                {"prompt": [1], "max_new_tokens": 2, "slo": "batch"},
                kind="generate",
            )
            assert status == 200
            assert reply["tokens"] == _reference([1], 2)
        finally:
            b.close(drain=True)

    def test_per_class_histograms_and_shed_counters(self):
        eng = _FakeEngine(max_slots=4)
        b = ContinuousBatcher(eng).start()
        try:
            futs = [
                b.submit(Request(prompt=[3], max_new_tokens=2,
                                 slo=slo))
                for slo in ("interactive", "batch")
            ]
            for f in futs:
                f.result(timeout=10)
        finally:
            b.close(drain=True)
        hists = b.registry.histogram_summaries()
        for cls in ("interactive", "batch"):
            for name in ("queue_wait", "ttft", "tpot", "e2e"):
                h = hists.get(f"serving/{name}_{cls}")
                assert h and h["count"] >= 1, (name, cls)

    def test_batch_queue_full_sheds_with_class_counter(self):
        """Per-class bounds: the batch queue overflowing sheds BATCH
        (with its class counter) while the interactive queue still
        accepts — batch absorbs the shedding first, structurally."""
        eng = _FakeEngine(max_slots=1, max_queue=1)
        b = ContinuousBatcher(eng)  # not started: pure queue behavior
        first = b.submit(Request(prompt=[1], max_new_tokens=2,
                                 slo="batch"))
        with pytest.raises(QueueFull):
            b.submit(Request(prompt=[3], max_new_tokens=1,
                             slo="batch"))
        counters = b.registry.counter_values()
        assert counters["serving/shed_batch_total"] == 1
        assert counters["serving/shed_total"] == 1
        # The interactive queue is NOT full: its class still flows.
        fut = b.submit(Request(prompt=[4], max_new_tokens=1))
        b.start()
        try:
            assert fut.result(timeout=10).tokens == _reference([4], 1)
            assert first.result(timeout=20).tokens == \
                _reference([1], 2)
        finally:
            b.close(drain=True)


class TestPreemption:
    @pytest.mark.timeout(60)
    def test_interactive_preempts_batch_and_replays_identically(self):
        """One slot held by a long batch request; an interactive
        arrival preempts it (slot freed, batch re-queued), completes
        first, and the batch request then REPLAYS from the prompt with
        a token-identical stream."""
        eng = _FakeEngine(max_slots=1, step_delay=0.01)
        b = ContinuousBatcher(eng).start()
        try:
            fut_b = b.submit(Request(prompt=[7], max_new_tokens=12,
                                     slo="batch"))
            deadline = time.monotonic() + 5
            while not b._active and time.monotonic() < deadline:
                time.sleep(0.005)
            assert b._active, "batch request never started"
            fut_i = b.submit(Request(prompt=[40], max_new_tokens=2))
            res_i = fut_i.result(timeout=15)
            assert res_i.tokens == _reference([40], 2)
            assert not fut_b.done(), (
                "batch should still be re-running after preemption"
            )
            res_b = fut_b.result(timeout=30)
            assert res_b.tokens == _reference([7], 12)
            assert res_b.truncated is None
            assert b.registry.counter_values()[
                "serving/preempted_total"
            ] >= 1
        finally:
            b.close(drain=True)

    def test_interactive_never_preempts_interactive(self):
        eng = _FakeEngine(max_slots=1, step_delay=0.01)
        b = ContinuousBatcher(eng).start()
        try:
            fut_a = b.submit(Request(prompt=[7], max_new_tokens=6))
            deadline = time.monotonic() + 5
            while not b._active and time.monotonic() < deadline:
                time.sleep(0.005)
            fut_b = b.submit(Request(prompt=[9], max_new_tokens=2))
            assert fut_a.result(timeout=15).tokens == _reference([7], 6)
            assert fut_b.result(timeout=15).tokens == _reference([9], 2)
            assert b.registry.counter_values().get(
                "serving/preempted_total", 0
            ) == 0
        finally:
            b.close(drain=True)


# --------------------------------------------------- brownout integration


class TestBrownoutIntegration:
    def test_level1_sheds_batch_submits_only(self):
        eng = _FakeEngine(brownout=True)
        b = ContinuousBatcher(eng).start()
        try:
            b._overload.level = 1
            with pytest.raises(QueueFull, match="brownout"):
                b.submit(Request(prompt=[1], slo="batch"))
            counters = b.registry.counter_values()
            assert counters["serving/shed_batch_total"] == 1
            assert counters["serving/brownout_shed_total"] == 1
            fut = b.submit(Request(prompt=[2], max_new_tokens=1))
            assert fut.result(timeout=10).tokens == _reference([2], 1)
        finally:
            b.close(drain=True)

    def test_level2_caps_generation_as_prefix(self):
        eng = _FakeEngine(brownout=True, brownout_max_new_tokens=3)
        b = ContinuousBatcher(eng).start()
        try:
            b._overload.level = 2
            fut = b.submit(Request(prompt=[5], max_new_tokens=10))
            res = fut.result(timeout=10)
            assert res.truncated == "brownout"
            # A PREFIX of the uncapped stream, exactly cap tokens long.
            assert res.tokens == _reference([5], 10)[:3]
            assert b.registry.counter_values()[
                "serving/brownout_truncated_total"
            ] == 1
        finally:
            b.close(drain=True)

    def test_level4_sheds_interactive_too(self):
        eng = _FakeEngine(brownout=True)
        b = ContinuousBatcher(eng).start()
        try:
            b._overload.level = 4
            with pytest.raises(QueueFull, match="brownout"):
                b.submit(Request(prompt=[1]))
            assert b.registry.counter_values()[
                "serving/shed_interactive_total"
            ] == 1
        finally:
            b.close(drain=True)

    @pytest.mark.timeout(60)
    def test_ladder_engages_under_load_and_clears_idle(self):
        """End-to-end: a slow engine + a queue flood walks the ladder
        up (real transitions, counted), then the idle loop walks it
        fully back to 0 — the hysteresis story, wired."""
        eng = _FakeEngine(
            max_slots=1, max_queue=32, step_delay=0.01,
            brownout=True, brownout_queue_hi=2,
            brownout_hold_s=0.05,
        )
        b = ContinuousBatcher(eng).start()
        try:
            futs = [
                b.submit(Request(prompt=[3], max_new_tokens=4))
                for _ in range(12)
            ]
            deadline = time.monotonic() + 20
            while b.brownout_level == 0 \
                    and time.monotonic() < deadline:
                time.sleep(0.005)
            assert b.brownout_level >= 1, "ladder never engaged"
            for f in futs:
                try:
                    f.result(timeout=30)
                except QueueFull:
                    pass  # the ladder's own sheds are expected
            deadline = time.monotonic() + 20
            while b.brownout_level > 0 \
                    and time.monotonic() < deadline:
                time.sleep(0.01)
            assert b.brownout_level == 0, "ladder never cleared"
            assert b.registry.counter_values()[
                "serving/brownout_transitions_total"
            ] >= 2  # at least one up AND one down
        finally:
            b.close(drain=True)

    def test_health_exposes_brownout_state(self):
        eng = _FakeEngine(brownout=True)
        b = ContinuousBatcher(eng)
        fe = ServingFrontend(b, port=0)
        b._overload.level = 2
        b._overload.events.append((time.time(), 1, 2, "test"))
        status, body = fe.health_payload()
        assert status == 200
        assert body["brownout_level"] == 2
        assert body["brownout_transitions"] == 1
        b.close(drain=False)


# ------------------------------------------------- flash-crowd golden


class TestFlashCrowdGolden:
    @pytest.mark.timeout(180)
    def test_flash_crowd_sheds_batch_only_interactive_survives(self):
        """THE overload acceptance (ISSUE 13): a seeded 3x flash crowd
        against a 2-replica fleet (real batcher/frontend/router over
        HTTP, deterministic engines). All shedding lands on the batch
        class, every interactive request completes 200 with a stream
        token-identical to the reference (prefix under a brownout
        cap), the ladder engages and fully clears, and interactive
        flash-window TTFT p95 stays within the declared budget of the
        steady window's."""
        import serve_bench

        engines = [
            _FakeEngine(
                max_slots=4, max_queue=64, step_delay=0.004,
                brownout=True, brownout_queue_hi=6,
                brownout_hold_s=0.25, brownout_max_new_tokens=4,
            )
            for _ in range(2)
        ]
        stacks = []
        for eng in engines:
            b = ContinuousBatcher(eng).start()
            fe = ServingFrontend(b, port=0).start()
            stacks.append((b, fe))
        router = Router(
            [f"http://127.0.0.1:{fe.port}" for _, fe in stacks],
            cfg=RouterConfig(
                probe_interval_s=0.05, request_timeout_s=30.0,
            ),
        ).start()
        rfront = RouterFrontend(router, port=0).start()
        try:
            schedule = serve_bench.make_traffic_schedule(
                "flash", 150, rate=120.0, vocab=211, max_len=64,
                max_new=8, batch_fraction=0.5, flash_factor=3.0,
                seed=7,
            )
            outcome = serve_bench.drive_open_loop(
                None, schedule, http_url=rfront.url("/generate"),
                timeout=30.0,
            )
            # Settle: the ladder must walk fully back down.
            deadline = time.monotonic() + 30
            while any(b.brownout_level for b, _ in stacks) \
                    and time.monotonic() < deadline:
                time.sleep(0.05)

            shed_interactive = shed_batch = 0
            for reply, ev in zip(outcome["replies"], schedule):
                assert reply is not None, "request never resolved"
                status, body = reply
                assert status in (200, 503), (status, body)
                if status == 503:
                    if ev["slo"] == "interactive":
                        shed_interactive += 1
                    else:
                        shed_batch += 1
                    continue
                ref = _reference(ev["prompt"], ev["max_new"])
                toks = body["tokens"]
                if body.get("truncated") == "brownout":
                    assert toks == ref[:len(toks)] and toks, (
                        "brownout cap must deliver a stream prefix"
                    )
                else:
                    assert toks == ref, "stream not token-identical"
            # The whole point: batch absorbs the flash crowd.
            assert shed_interactive == 0, (
                f"{shed_interactive} interactive requests shed"
            )
            transitions = sum(
                len(b._overload.events) for b, _ in stacks
            )
            assert transitions >= 2, "brownout ladder never engaged"
            assert all(b.brownout_level == 0 for b, _ in stacks), (
                "brownout ladder never cleared"
            )
            # Interactive latency: flash p95 within budget of steady.
            def p95(phases):
                vals = sorted(
                    r[1]["ttft_s"]
                    for r, ev in zip(outcome["replies"], schedule)
                    if r[0] == 200 and ev["slo"] == "interactive"
                    and ev["phase"] in phases
                )
                return vals[int(0.95 * (len(vals) - 1))] if vals \
                    else None

            steady, flash = p95(("steady",)), p95(("flash",))
            assert steady is not None and flash is not None
            assert flash <= serve_bench.FLASH_TTFT_BUDGET * max(
                steady, 0.05
            ), f"flash p95 {flash:.3f}s vs steady {steady:.3f}s"
        finally:
            rfront.close()
            router.close()
            for b, fe in stacks:
                b.close(drain=True)
                fe.close()


# ------------------------------------------------------------ schema v10


def _build_paged_engine(**kw):
    import serve_bench

    cfg = ServeConfig(
        max_slots=4, prefill_bucket_floor=16, kv_bucket_floor=32,
        kv_block_size=16, **kw,
    )
    return serve_bench.build_smoke_engine(cfg)


class TestSchemaV10:
    def test_stats_line_is_v10_and_validates(self):
        eng = _FakeEngine(brownout=True)
        b = ContinuousBatcher(eng)
        line = json.loads(json.dumps(b.stats_line()))
        assert line["schema_version"] == \
            schema.SERVING_SCHEMA_VERSION == 14
        assert schema.validate_line(line) == []
        assert line["serving"]["brownout_level"] == 0
        assert line["serving"]["shed_interactive"] == 0
        assert line["serving"]["shed_batch"] == 0
        assert line["serving"]["preempted_batch"] == 0

    def test_v10_keys_flagged_on_older_versions(self):
        base = {
            "schema_version": 10, "kind": "serving", "step": 1,
            "time_unix": 1.0, "session_start_unix": 1.0, "host": 0,
            "metrics": {}, "counters": {}, "gauges": {}, "derived": {},
            "serving": {
                "active_requests": 0, "queue_depth": 0, "slots": 4,
                "kv_occupancy": 0.0, "post_warmup_recompiles": 0,
                "draining": 0, "brownout_level": 1,
                "brownout_transitions": 2, "shed_interactive": 0,
                "shed_batch": 3, "preempted_batch": 1,
                "ttft_p95_interactive": 0.01, "ttft_p95_batch": 0.2,
                "queue_wait_p95_interactive": 0.001,
                "queue_wait_p95_batch": 0.1,
                "tpot_p95_interactive": 0.002,
                "tpot_p95_batch": 0.002, "digest_truncated": 0,
            },
        }
        assert schema.validate_line(base) == []
        for version in (4, 5, 6, 7, 8, 9):
            stale = dict(base, schema_version=version)
            problems = schema.validate_line(stale)
            for key in schema.SERVING_KEYS_V10:
                assert any(
                    f"v10 serving key '{key}'" in p for p in problems
                ), (version, key, problems)

    def test_per_class_p95s_on_line_after_traffic(self):
        eng = _FakeEngine()
        b = ContinuousBatcher(eng).start()
        try:
            for slo in ("interactive", "batch"):
                b.submit(Request(
                    prompt=[3], max_new_tokens=2, slo=slo
                )).result(timeout=10)
            line = json.loads(json.dumps(b.stats_line()))
        finally:
            b.close(drain=True)
        assert schema.validate_line(line) == []
        for key in ("ttft_p95_interactive", "ttft_p95_batch",
                    "queue_wait_p95_interactive",
                    "queue_wait_p95_batch"):
            assert isinstance(line["serving"][key], float), key

    def test_router_line_carries_fleet_brownout_view(self):
        r = Router(["http://a:1", "http://b:2"])
        for i, rep in enumerate(r.replicas):
            rep.probed = True
            rep.brownout_level = i * 2   # 0, 2
            rep.brownout_transitions = 3
            rep.digest_truncated = (i == 1)
        line = json.loads(json.dumps(r.stats_line()))
        assert line["schema_version"] == schema.SERVING_SCHEMA_VERSION
        assert schema.validate_line(line) == []
        assert line["serving"]["brownout_level"] == 2  # fleet MAX
        assert line["serving"]["brownout_transitions"] == 6
        assert line["serving"]["digest_truncated"] == 1
        status, health = r.health_payload()
        assert health["brownout_max"] == 2
        assert health["digest_truncated"] is True


class TestDigestTruncation:
    """ISSUE 13 satellite: prefix_digest caps loudly, not silently."""

    @pytest.mark.timeout(300)
    def test_digest_reports_truncation_and_health_exposes_it(self):
        eng = _build_paged_engine()
        pool = eng.pool
        # Publish 3 chained blocks, then cap the digest below that.
        slot = pool.alloc()
        prompt = list(range(48))
        pool.claim_prompt_blocks(slot, prompt)
        pool.insert_prefix(slot, prompt)
        full = pool.prefix_digest()
        assert full["truncated"] is False and len(full["keys"]) == 3
        capped = pool.prefix_digest(max_keys=2)
        assert capped["truncated"] is True
        assert len(capped["keys"]) == 2
        assert capped["blocks"] == 3  # the COUNT stays honest
        # paged_stats carries the numeric flag (0 here: the real cap
        # is DIGEST_MAX_KEYS, far above 3 blocks).
        assert pool.paged_stats()["digest_truncated"] == 0
        b = ContinuousBatcher(eng)
        fe = ServingFrontend(b, port=0)
        _, body = fe.health_payload()
        assert body["digest_truncated"] is False
        line = json.loads(json.dumps(b.stats_line()))
        assert schema.validate_line(line) == []
        assert line["serving"]["digest_truncated"] == 0
        b.close(drain=False)
