"""SLO engine, time-series store, canary prober (ISSUE 19).

The load-bearing contracts:

* :class:`TestAlertEngine` — the burn-rate unit matrix on an
  injectable clock: multi-window gating (a spike that burns only the
  fast window cannot fire), pending -> firing hysteresis, flap
  suppression, sustained-health resolve, the worst-offender exemplar,
  and the fsynced ``kind="alert"`` sink round-trip.
* :class:`TestProbeExclusion` — the probe tag's exclusion contract on
  a REAL router + journal: probe traffic leaves the journal dedupe
  window, the tenant intent log, ``router/requests_total`` and the
  organic AlertEngine feed untouched.
* :class:`TestSchemaV14Ritual` — the versioning ritual for the v14
  additions (the alert kind and the serving summary keys are forbidden
  on every line that predates them).

Replicas here are device-free fake engines behind real HTTP frontends
(the test_router idiom); the real-fleet tier is ``serve_bench --smoke
--slo`` in tests/test_tools.py and the chaos alert golden in
tests/test_chaos.py.
"""

import json
import os
import sys
import threading
import time

import numpy as np
import pytest

from conftest import slot_pool
from tensorflow_examples_tpu.serving.batcher import ContinuousBatcher
from tensorflow_examples_tpu.serving.engine import ServeConfig
from tensorflow_examples_tpu.serving.frontend import ServingFrontend
from tensorflow_examples_tpu.serving.prober import (
    CanaryProber,
    fleet_targets,
)
from tensorflow_examples_tpu.serving.router import (
    Router,
    RouterConfig,
    RouterFrontend,
)
from tensorflow_examples_tpu.telemetry import schema, slo
from tensorflow_examples_tpu.telemetry.registry import MetricsRegistry
from tensorflow_examples_tpu.telemetry.slo import (
    AlertEngine,
    SLOConfig,
    SLOObjective,
)
from tensorflow_examples_tpu.telemetry.timeseries import TimeSeriesStore

pytestmark = pytest.mark.serving

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))


class _FakeEngine:
    """Deterministic device-free engine (the test_router idiom): token
    stream is prompt[-1]+1, +2, ... — every replica serves identical
    output, so known-answer probes agree across the fleet."""

    def __init__(self, *, max_slots=4, max_queue=32, max_len=64):
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
        self.warmed = True

    def post_warmup_recompiles(self):
        return 0

    def prefill(self, slot, prompt, *, seed=0, temperature=0.0, top_k=0):
        self.pool.lengths[slot] = len(prompt)
        last = np.zeros((self.model_cfg.vocab_size,), np.float32)
        return (prompt[-1] + 1) % self.model_cfg.vocab_size, last

    def decode(self, entries):
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


def _cfg(**over):
    """A strict config the unit matrix can breach deterministically:
    one class, e2e ceiling 0.1s, 10% budget, fast/slow = 10s/30s."""
    kw = dict(
        objectives=(
            SLOObjective(slo="interactive", ttft_p95_s=0.1,
                         e2e_p95_s=0.1, error_budget=0.1,
                         availability=0.9),
        ),
        windows_s=(10.0, 30.0),
        burn_thresholds=(5.0, 2.0),
        pending_for_s=2.0,
        resolve_after_s=5.0,
    )
    kw.update(over)
    return SLOConfig(**kw)


class _Clock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


# ---------------------------------------------------------------- config


class TestSLOConfig:
    def test_defaults_are_generous_and_valid(self):
        cfg = SLOConfig()
        assert cfg.objective("interactive").ttft_p95_s >= 5.0
        assert cfg.objective("batch") is not None
        assert cfg.objective("nope") is None
        assert cfg.windows_s[0] < cfg.windows_s[1]

    def test_save_load_round_trip(self, tmp_path):
        path = str(tmp_path / "slo.json")
        cfg = _cfg()
        cfg.save(path)
        with open(path) as f:
            doc = json.load(f)
        assert doc["version"] == slo.SLO_JSON_VERSION
        loaded = SLOConfig.load(path)
        assert loaded == cfg

    def test_bare_object_loads_without_wrapper(self, tmp_path):
        path = str(tmp_path / "bare.json")
        with open(path, "w") as f:
            json.dump({"objectives": [{"slo": "interactive",
                                       "e2e_p95_s": 1.0}]}, f)
        cfg = SLOConfig.load(path)
        assert cfg.objective("interactive").e2e_p95_s == 1.0

    def test_wrong_version_rejected(self, tmp_path):
        path = str(tmp_path / "v9.json")
        with open(path, "w") as f:
            json.dump({"version": 9, "config": {}}, f)
        with pytest.raises(ValueError, match="version"):
            SLOConfig.load(path)

    def test_duplicate_class_rejected(self):
        with pytest.raises(ValueError, match="duplicate"):
            SLOConfig(objectives=(
                SLOObjective(slo="a"), SLOObjective(slo="a"),
            ))

    def test_unknown_keys_rejected(self):
        with pytest.raises(ValueError, match="unknown"):
            SLOObjective.from_json_dict({"slo": "x", "nope": 1})
        with pytest.raises(ValueError, match="unknown"):
            SLOConfig.from_json_dict({"bogus": 1})

    def test_bad_windows_rejected(self):
        with pytest.raises(ValueError, match="windows"):
            _cfg(windows_s=(30.0, 10.0))
        with pytest.raises(ValueError, match="budget"):
            SLOObjective(slo="x", error_budget=0.0)


# ----------------------------------------------------------- time series


class TestTimeSeriesStore:
    def test_ring_trims_to_capacity(self):
        ts = TimeSeriesStore(capacity=4)
        for i in range(6):
            ts.record("x", float(i), now=float(i))
        pts = ts.series("x")
        assert len(pts) == 4
        assert [v for _t, v in pts] == [2.0, 3.0, 4.0, 5.0]

    def test_sample_walks_counters_gauges_histograms(self):
        reg = MetricsRegistry()
        reg.counter("serving/requests_total").inc(3)
        reg.gauge("serving/queue_depth").set(7.0)
        for v in range(1, 101):
            reg.histogram("serving/ttft").record(v / 100.0)
        ts = TimeSeriesStore(reg, capacity=8)
        n = ts.sample(now=1.0)
        assert n >= 5  # counter + gauge + three percentile series
        assert ts.series("serving/requests_total") == [(1.0, 3.0)]
        assert ts.series("serving/queue_depth") == [(1.0, 7.0)]
        names = ts.names()
        for suffix in (".p50", ".p95", ".p99"):
            assert "serving/ttft" + suffix in names, names
        p95 = ts.series("serving/ttft.p95")[0][1]
        assert 0.90 <= p95 <= 1.0

    def test_sample_without_registry_is_noop(self):
        ts = TimeSeriesStore()
        assert ts.sample() == 0
        assert ts.names() == []

    def test_rollup_percentiles(self):
        ts = TimeSeriesStore(capacity=200)
        for i in range(1, 101):
            ts.record("lat", float(i), now=float(i))
        r = ts.rollup("lat")
        assert r["count"] == 100
        assert r["min"] == 1.0 and r["max"] == 100.0
        assert r["last"] == 100.0
        assert r["p50"] == 50.0
        assert r["p95"] == 95.0
        assert r["p99"] == 99.0
        assert ts.rollup("unknown")["count"] == 0

    def test_to_payload_is_json_ready(self):
        reg = MetricsRegistry()
        reg.counter("c").inc()
        ts = TimeSeriesStore(reg, capacity=8)
        ts.sample(now=1.0)
        ts.sample(now=2.0)
        payload = json.loads(json.dumps(ts.to_payload()))
        assert payload["capacity"] == 8
        assert payload["samples_taken"] == 2
        assert payload["series"]["c"] == [[1.0, 1.0], [2.0, 1.0]]
        assert payload["rollups"]["c"]["count"] == 2
        assert payload["rollups"]["c"]["last"] == 1.0

    @pytest.mark.timeout(120)
    def test_concurrent_record_sample_scrape(self):
        """The lock-order tier's concurrency pin: writers (record +
        registry-fed sample) race scrapers (to_payload/rollup) with no
        exception, no deadlock, and a consistent final payload."""
        reg = MetricsRegistry()
        ts = TimeSeriesStore(reg, capacity=64)
        stop = threading.Event()
        errors = []

        def writer():
            i = 0
            while not stop.is_set():
                reg.counter("w/count").inc()
                ts.record("w/direct", float(i))
                ts.sample()
                i += 1

        def scraper():
            while not stop.is_set():
                payload = ts.to_payload(last=16)
                for pts in payload["series"].values():
                    assert all(len(p) == 2 for p in pts)
                ts.rollup("w/direct")

        threads = [threading.Thread(target=writer),
                   threading.Thread(target=writer),
                   threading.Thread(target=scraper),
                   threading.Thread(target=scraper)]

        def run(t):
            try:
                t.run_orig()
            except Exception as e:  # pragma: no cover - failure path
                errors.append(e)

        for t in threads:
            t.run_orig, t.run = t.run, lambda t=t: run(t)
            t.start()
        time.sleep(0.5)
        stop.set()
        for t in threads:
            t.join(timeout=10)
        assert not errors
        payload = ts.to_payload()
        assert payload["samples_taken"] > 0
        assert len(payload["series"]["w/direct"]) <= 64


# --------------------------------------------------------------- engine


class TestAlertEngine:
    def _bad(self, eng, clock, n=20, *, trace_id=None, value=1.0):
        for _ in range(n):
            eng.observe("interactive", e2e_s=value, trace_id=trace_id,
                        now=clock.t)

    def _good(self, eng, clock, n=20):
        for _ in range(n):
            eng.observe("interactive", e2e_s=0.01, now=clock.t)

    def test_healthy_traffic_never_fires(self):
        clock = _Clock()
        eng = AlertEngine(_cfg(), registry=MetricsRegistry(),
                          now=clock)
        for _ in range(10):
            self._good(eng, clock, 5)
            clock.t += 1.0
            assert eng.evaluate() == []
        s = eng.stats()
        assert s["alerts_firing"] == 0 and s["alert_count"] == 0
        assert s["error_budget_remaining"] == 1.0

    def test_unknown_slo_class_ignored(self):
        clock = _Clock()
        eng = AlertEngine(_cfg(), registry=MetricsRegistry(),
                          now=clock)
        eng.observe("mystery", e2e_s=99.0, error=True)
        assert eng.evaluate() == []

    def test_sustained_breach_walks_pending_then_firing(self):
        clock = _Clock()
        eng = AlertEngine(_cfg(), registry=MetricsRegistry(),
                          now=clock)
        self._bad(eng, clock)
        assert eng.evaluate() == []  # ok -> pending, nothing emitted
        rules = eng.payload()["rules"]
        assert rules["e2e_interactive"]["state"] == "pending"
        clock.t += 1.0  # still inside pending_for_s=2.0
        self._bad(eng, clock, 5)
        assert eng.evaluate() == []
        clock.t += 1.5  # dwell satisfied
        self._bad(eng, clock, 5)
        fired = eng.evaluate()
        assert any(
            a["name"] == "e2e_interactive" and a["state"] == "firing"
            for a in fired
        )
        s = eng.stats()
        assert s["alerts_firing"] >= 1 and s["alert_count"] >= 1
        assert s["error_budget_remaining"] == 0.0

    def test_brief_flap_is_suppressed(self):
        """A breach shorter than pending_for_s never fires."""
        clock = _Clock()
        eng = AlertEngine(_cfg(), registry=MetricsRegistry(),
                          now=clock)
        self._bad(eng, clock, 3)
        assert eng.evaluate() == []  # pending
        # Health returns before the dwell elapses: back to ok.
        clock.t += 1.0
        self._good(eng, clock, 60)
        assert eng.evaluate() == []
        assert eng.payload()["rules"]["e2e_interactive"]["state"] == "ok"
        clock.t += 5.0
        assert eng.evaluate() == []
        assert eng.stats()["alert_count"] == 0

    def test_slow_window_gates_a_single_spike(self):
        """The multi-window method's reason to exist: a short spike
        saturates the fast window but not the slow one — no alert."""
        clock = _Clock()
        eng = AlertEngine(_cfg(), registry=MetricsRegistry(),
                          now=clock)
        self._good(eng, clock, 95)  # a healthy half-minute of history
        clock.t += 25.0  # good events now outside the fast window
        self._bad(eng, clock, 3)  # the spike
        assert eng.evaluate() == []
        rules = eng.payload()["rules"]["e2e_interactive"]
        assert rules["burn_rate_fast"] >= 5.0  # fast window IS burning
        assert rules["burn_rate_slow"] < 2.0  # slow window absorbs it
        assert rules["state"] == "ok"

    def test_firing_resolves_after_sustained_health(self, tmp_path):
        path = str(tmp_path / "alerts.jsonl")
        clock = _Clock()
        eng = AlertEngine(_cfg(), registry=MetricsRegistry(),
                          now=clock, path=path)
        self._bad(eng, clock, 20, trace_id="t-worst")
        eng.evaluate()
        clock.t += 2.5
        self._bad(eng, clock, 5, trace_id="t-worst")
        fired = eng.evaluate()
        assert [a["state"] for a in fired] == ["firing"]
        # Health returns; bad events age past the slow window.
        clock.t += 61.0
        self._good(eng, clock, 10)
        assert eng.evaluate() == []  # healthy_since starts
        clock.t += 6.0  # > resolve_after_s
        self._good(eng, clock, 5)
        resolved = eng.evaluate()
        assert [a["state"] for a in resolved] == ["resolved"]
        assert eng.stats()["alerts_firing"] == 0
        # The sink round-trip: one line per transition, all valid v14.
        with open(path) as f:
            lines = [json.loads(ln) for ln in f if ln.strip()]
        assert [
            (ln["alert"]["name"], ln["alert"]["state"]) for ln in lines
        ] == [("e2e_interactive", "firing"),
              ("e2e_interactive", "resolved")]
        for ln in lines:
            assert ln["schema_version"] == 14
            assert schema.validate_line(ln) == [], ln
        alerts = slo.read_alerts(path)
        assert len(alerts) == 2
        assert alerts[0]["trace_id"] == "t-worst"
        eng.close()

    def test_read_alerts_tolerates_torn_tail(self, tmp_path):
        path = str(tmp_path / "alerts.jsonl")
        clock = _Clock()
        eng = AlertEngine(_cfg(), registry=MetricsRegistry(),
                          now=clock, path=path)
        self._bad(eng, clock)
        eng.evaluate()
        clock.t += 2.5
        self._bad(eng, clock, 5)
        eng.evaluate()
        eng.close()
        with open(path, "a") as f:
            f.write('{"kind": "alert", "alert": {"name"')  # the tear
        alerts = slo.read_alerts(path)
        assert len(alerts) == 1 and alerts[0]["state"] == "firing"
        assert slo.read_alerts(str(tmp_path / "missing.jsonl")) == []

    def test_worst_offender_exemplar_wins(self):
        """The firing alert embeds the trace_id of the WORST bad event
        in the window, not the first or last."""
        clock = _Clock()
        eng = AlertEngine(_cfg(), registry=MetricsRegistry(),
                          now=clock)
        self._bad(eng, clock, 10, trace_id="t-mild", value=0.5)
        self._bad(eng, clock, 1, trace_id="t-worst", value=9.0)
        self._bad(eng, clock, 10, trace_id="t-mild2", value=0.5)
        eng.evaluate()
        clock.t += 2.5
        self._bad(eng, clock, 2, trace_id="t-mild3", value=0.5)
        fired = [a for a in eng.evaluate()
                 if a["name"] == "e2e_interactive"]
        assert fired and fired[0]["trace_id"] == "t-worst"
        assert fired[0]["value"] == 9.0
        assert fired[0]["slo"] == "interactive"

    def test_severity_page_vs_ticket(self):
        clock = _Clock()
        eng = AlertEngine(_cfg(), registry=MetricsRegistry(),
                          now=clock)
        # All-bad: burn = 1/0.1 = 10 = 2x the fast threshold -> page.
        self._bad(eng, clock, 20)
        eng.evaluate()
        clock.t += 2.5
        self._bad(eng, clock, 2)
        fired = [a for a in eng.evaluate()
                 if a["name"] == "e2e_interactive"]
        assert fired[0]["severity"] == "page"
        # 60% bad: burn 6 — over the threshold but under 2x -> ticket.
        eng2 = AlertEngine(_cfg(), registry=MetricsRegistry(),
                           now=clock)
        self._bad(eng2, clock, 12)
        self._good(eng2, clock, 8)
        eng2.evaluate()
        clock.t += 2.5
        self._bad(eng2, clock, 3)
        self._good(eng2, clock, 2)
        fired = [a for a in eng2.evaluate()
                 if a["name"] == "e2e_interactive"]
        assert fired and fired[0]["severity"] == "ticket"

    def test_probe_failures_burn_availability(self):
        clock = _Clock()
        eng = AlertEngine(_cfg(), registry=MetricsRegistry(),
                          now=clock)
        for _ in range(3):
            eng.observe_probe(slo="interactive", ok=True,
                              replica="r0", ttft_s=0.01)
        eng.observe_probe(slo="interactive", ok=False, replica="r1")
        s = eng.stats()
        assert s["probe_success_rate"] == 0.75
        # budget 1-availability = 0.1; 25% bad -> burn 2.5 < fast 5.
        assert eng.evaluate() == []
        for _ in range(10):
            eng.observe_probe(slo="interactive", ok=False,
                              replica="r1")
        eng.evaluate()
        clock.t += 2.5
        eng.observe_probe(slo="interactive", ok=False, replica="r1")
        fired = [a for a in eng.evaluate()
                 if a["name"] == "probe_interactive"]
        assert fired and fired[0]["state"] == "firing"
        assert fired[0]["replica"] == "r1"
        assert eng.stats()["probe_success_rate"] < 0.5

    def test_stats_keys_are_exactly_the_v14_serving_keys(self):
        eng = AlertEngine(registry=MetricsRegistry())
        assert set(eng.stats()) == set(schema.SERVING_KEYS_V14)

    def test_payload_shape(self):
        clock = _Clock()
        eng = AlertEngine(_cfg(), registry=MetricsRegistry(),
                          now=clock)
        payload = json.loads(json.dumps(eng.payload()))
        assert payload["firing"] == []
        assert set(payload["rules"]) == {
            "ttft_interactive", "e2e_interactive",
            "errors_interactive", "probe_interactive",
        }
        assert payload["config"]["windows_s"] == [10.0, 30.0]
        for key in schema.SERVING_KEYS_V14:
            assert key in payload


# -------------------------------------------------------------- prober


class TestCanaryProber:
    def _prober(self, replies, **kw):
        """A prober whose transport is a scripted list of (status,
        reply) tuples (popped per probe) — no sockets."""
        from tensorflow_examples_tpu.serving import prober as pmod

        p = CanaryProber({"r0": "http://fake:1"},
                         registry=MetricsRegistry(), **kw)
        calls = []

        def fake_post(url, body, timeout):
            calls.append((url, body))
            return replies.pop(0)

        return p, calls, fake_post

    def test_probe_body_carries_the_tag(self):
        p = CanaryProber({"r0": "http://fake:1"},
                         registry=MetricsRegistry())
        body = p.probe_body()
        assert body["probe"] is True
        assert body["temperature"] == 0.0
        assert body["max_new_tokens"] > 0

    def test_known_answer_banks_then_catches_mismatch(self, monkeypatch):
        from tensorflow_examples_tpu.serving import prober as pmod

        replies = [
            (200, {"tokens": [3, 4, 5], "ttft_s": 0.01}),
            (200, {"tokens": [3, 4, 5], "ttft_s": 0.01}),
            (200, {"tokens": [3, 4, 6], "ttft_s": 0.01}),  # corrupted
        ]
        p, calls, fake_post = self._prober(replies)
        monkeypatch.setattr(pmod, "post_json", fake_post)
        r1 = p.probe_one("r0", "http://fake:1")
        assert r1["ok"] is True and r1["mismatch"] is False
        r2 = p.probe_one("r0", "http://fake:1")
        assert r2["ok"] is True
        r3 = p.probe_one("r0", "http://fake:1")
        # A 200 with the wrong tokens is a FAILED probe.
        assert r3["ok"] is False and r3["mismatch"] is True
        counters = p.registry.counter_values()
        assert counters["probe/sent_total"] == 3
        assert counters["probe/mismatch_total"] == 1
        assert counters["probe/failed_total"] == 1
        assert calls[0][1]["probe"] is True

    def test_transport_failure_feeds_engine_and_fires(self, monkeypatch):
        from tensorflow_examples_tpu.serving import prober as pmod

        clock = _Clock()
        eng = AlertEngine(
            _cfg(pending_for_s=0.0), registry=MetricsRegistry(),
            now=clock,
        )
        replies = [(0, {})] * 40
        p, _calls, fake_post = self._prober(replies, alerts=eng)
        monkeypatch.setattr(pmod, "post_json", fake_post)
        p.probe_once()  # sweep + evaluate: ok -> pending
        clock.t += 0.5
        p.probe_once()  # pending dwell (0) satisfied -> firing
        assert p.advisory() is True
        assert eng.stats()["alerts_firing"] >= 1
        assert eng.stats()["probe_success_rate"] == 0.0
        assert p.registry.counter_values()["probe/failed_total"] == 2

    def test_fleet_targets_shape(self):
        targets = fleet_targets(
            "http://127.0.0.1:9000",
            ["http://a:1/", "http://b:2"],
        )
        assert targets == {
            "router": "http://127.0.0.1:9000",
            "http://a:1": "http://a:1/",
            "http://b:2": "http://b:2",
        }
        assert fleet_targets(None, ["http://a:1"]) == {
            "http://a:1": "http://a:1"
        }
        with pytest.raises(ValueError):
            CanaryProber({})

    @pytest.mark.timeout(120)
    def test_probes_real_replica_end_to_end(self):
        """One real sweep: fake engine behind a real HTTP frontend;
        the probe rides the ordinary /generate path and the replica
        tolerates (ignores) the tag."""
        replicas = [_replica()]
        url = f"http://127.0.0.1:{replicas[0][2].port}"
        try:
            p = CanaryProber({"rep": url}, registry=MetricsRegistry(),
                             timeout_s=30.0)
            first = p.probe_once()
            second = p.probe_once()
        finally:
            _close(replicas)
        assert [r["ok"] for r in first + second] == [True, True]
        assert second[0]["mismatch"] is False  # deterministic answer
        assert p.registry.counter_values()["probe/sent_total"] == 2


# ----------------------------------------------------- router exclusion


class TestProbeExclusion:
    """The exclusion contract, pinned on a real router: synthetic
    probes never enter the journal dedupe window, the tenant intent
    log, ``router/requests_total``, or the organic AlertEngine feed."""

    @pytest.mark.timeout(120)
    def test_probe_tag_excluded_from_journal_and_counters(
        self, tmp_path
    ):
        from tensorflow_examples_tpu.serving.journal import (
            RequestJournal,
        )

        replicas = [_replica()]
        urls = [f"http://127.0.0.1:{replicas[0][2].port}"]
        journal = RequestJournal(str(tmp_path / "journal.jsonl"))
        router = Router(urls, journal=journal)
        router.probe_once()
        try:
            # One ORGANIC request establishes the baseline.
            status, _ = router.handle(
                {"prompt": [2], "max_new_tokens": 2,
                 "request_id": "org-1"},
                kind="generate",
            )
            assert status == 200
            base = journal.stats()
            assert base["appends"] >= 1
            assert journal.lookup("org-1") is not None
            organic_events = len(
                router.alerts._rules["errors_interactive"].events
            )
            assert organic_events == 1
            # Probe traffic: same request_id on purpose — probes must
            # not dedupe, journal, or feed the organic engine.
            body = {"prompt": [2], "max_new_tokens": 2,
                    "request_id": "probe-1", "probe": True}
            for _ in range(3):
                status, reply = router.handle(dict(body),
                                              kind="generate")
                assert status == 200 and reply["tokens"]
            assert journal.stats() == base
            assert journal.lookup("probe-1") is None
            counters = router.registry.counter_values()
            assert counters["router/requests_total"] == 1
            assert counters["probe/router_requests_total"] == 3
            assert len(
                router.alerts._rules["errors_interactive"].events
            ) == organic_events
        finally:
            router.close()
            journal.close()
            _close(replicas)

    @pytest.mark.timeout(120)
    def test_probe_tag_does_not_mutate_caller_body(self):
        replicas = [_replica()]
        urls = [f"http://127.0.0.1:{replicas[0][2].port}"]
        router = Router(urls)
        router.probe_once()
        body = {"prompt": [2], "max_new_tokens": 2, "probe": True}
        try:
            status, _ = router.handle(body, kind="generate")
            assert status == 200
            assert body["probe"] is True  # the copy was popped, not us
        finally:
            router.close()
            _close(replicas)


# --------------------------------------------------- router stats + HTTP


class TestRouterSurfaces:
    @pytest.mark.timeout(120)
    def test_stats_line_carries_v14_keys_and_validates(self):
        replicas = [_replica()]
        urls = [f"http://127.0.0.1:{replicas[0][2].port}"]
        router = Router(urls)
        router.probe_once()
        try:
            status, _ = router.handle(
                {"prompt": [2], "max_new_tokens": 2}, kind="generate"
            )
            assert status == 200
            line = json.loads(json.dumps(router.stats_line()))
            assert schema.validate_line(line) == []
            serving = line["serving"]
            for key in schema.SERVING_KEYS_V14:
                assert key in serving, key
            assert serving["alerts_firing"] == 0
            assert serving["alert_count"] == 0
            assert serving["error_budget_remaining"] == 1.0
            assert serving["probe_success_rate"] == 1.0
            # v14 keys on an older version label must flag.
            v13 = dict(line, schema_version=13)
            assert any(
                "v14 serving key" in p
                for p in schema.validate_line(v13)
            )
            # The stats tick also sampled the time-series ring.
            assert router.series.samples_taken == 1
            assert "router/requests_total" in router.series.names()
        finally:
            router.close()
            _close(replicas)

    @pytest.mark.timeout(120)
    def test_frontends_serve_alerts_and_series(self):
        import urllib.request

        replicas = [_replica()]
        urls = [f"http://127.0.0.1:{replicas[0][2].port}"]
        router = Router(urls)
        router.probe_once()
        rfront = RouterFrontend(router, port=0).start()

        def get(url):
            with urllib.request.urlopen(url, timeout=30) as resp:
                return resp.status, json.loads(resp.read())

        try:
            router.stats_line()  # one tick feeds the ring
            status, alerts = get(rfront.url("/alerts"))
            assert status == 200
            assert alerts["alerts_firing"] == 0
            assert "rules" in alerts and "config" in alerts
            status, series = get(rfront.url("/series"))
            assert status == 200
            assert series["samples_taken"] >= 1
            assert "router/replicas_eligible" in series["series"]
            # The REPLICA frontend serves /series too (fed by the
            # serve.py stats loop; here we tick it by hand).
            replicas[0][2].series.sample()
            rurl = f"http://127.0.0.1:{replicas[0][2].port}"
            status, rseries = get(rurl + "/series")
            assert status == 200
            assert rseries["samples_taken"] >= 1
        finally:
            rfront.close()
            router.close()
            _close(replicas)

    @pytest.mark.timeout(120)
    def test_autoscaler_treats_firing_alert_as_advisory_hot(self):
        """The PR-12 hook: a firing alert marks the fleet hot (scale
        up) and blocks scale-down idleness, via any object with the
        AlertEngine stats() shape."""
        from tensorflow_examples_tpu.serving.supervisor import (
            Autoscaler,
            AutoscalerConfig,
        )

        class _Alerts:
            def __init__(self):
                self.firing = 0

            def stats(self):
                return {"alerts_firing": self.firing,
                        "error_budget_remaining": 1.0,
                        "probe_success_rate": 1.0, "alert_count": 0}

        class _Supervisor:
            handles = []

            def busy(self):
                return False

        replicas = [_replica()]
        urls = [f"http://127.0.0.1:{replicas[0][2].port}"]
        router = Router(urls)
        router.probe_once()
        alerts = _Alerts()
        scaler = Autoscaler(
            router, _Supervisor(), lambda idx: None, alerts=alerts,
            cfg=AutoscalerConfig(min_replicas=1, max_replicas=1),
        )
        try:
            sig = scaler.fleet_signals()
            assert sig["alerts_firing"] == 0
            alerts.firing = 1
            sig = scaler.fleet_signals()
            assert sig["alerts_firing"] == 1
            # max_replicas=1 means the hot verdict cannot act — the pin
            # is the advisory counter, not the scale action.
            decision = scaler.evaluate_once()
            assert isinstance(decision, str)
            counters = router.registry.counter_values()
            assert counters.get(
                "autoscaler/alert_advisory_total", 0
            ) >= 1
        finally:
            scaler.close()
            router.close()
            _close(replicas)


# ------------------------------------------------------- schema ritual


class TestSchemaV14Ritual:
    """The versioning ritual for v14: the additions exist, and both
    the alert kind and the serving summary keys are forbidden on every
    line that predates them."""

    def test_v14_pins(self):
        assert schema.SERVING_SCHEMA_VERSION == 14
        assert schema.SERVING_KEYS_V14 == (
            "alerts_firing", "error_budget_remaining",
            "probe_success_rate", "alert_count",
        )
        assert schema.KINDS == schema.KINDS_V13 + ("alert",)
        assert schema.ALERT_STATES == ("firing", "resolved")
        assert "alert/" in schema.INSTRUMENT_PREFIXES
        assert "probe/" in schema.INSTRUMENT_PREFIXES

    def _alert_line(self, **over):
        line = {
            "schema_version": 14, "kind": "alert", "step": 0,
            "time_unix": 2.0, "session_start_unix": 1.0, "host": 0,
            "metrics": {}, "counters": {}, "gauges": {}, "derived": {},
            "alert": {
                "name": "e2e_interactive", "slo": "interactive",
                "state": "firing", "severity": "page",
                "burn_rate": 12.5, "budget_remaining": 0.1,
                "since_unix": 1.5, "window_s": 60.0,
                "value": 2.5, "threshold": 0.5,
                "trace_id": "t" * 16, "replica": "http://a:1",
            },
        }
        line.update(over)
        return line

    def test_valid_alert_line_passes(self):
        assert schema.validate_line(self._alert_line()) == []

    def test_alert_kind_forbidden_before_v14(self):
        for version in (4, 5, 6, 7, 8, 9, 10, 11, 12, 13):
            problems = schema.validate_line(
                self._alert_line(schema_version=version))
            assert any("kind 'alert'" in p for p in problems), (
                version, problems)

    def test_v14_serving_keys_forbidden_before_v14(self):
        base = {
            "schema_version": 14, "kind": "serving", "step": 1,
            "time_unix": 1.0, "session_start_unix": 1.0, "host": 0,
            "metrics": {}, "counters": {}, "gauges": {}, "derived": {},
            "serving": {
                "active_requests": 0, "queue_depth": 0, "slots": 4,
                "kv_occupancy": 0.0, "post_warmup_recompiles": 0,
                "draining": 0, "alerts_firing": 0,
                "error_budget_remaining": 1.0,
                "probe_success_rate": 1.0, "alert_count": 0,
            },
        }
        assert schema.validate_line(base) == []
        for version in (4, 5, 6, 7, 8, 9, 10, 11, 12, 13):
            stale = dict(base, schema_version=version)
            problems = schema.validate_line(stale)
            for key in schema.SERVING_KEYS_V14:
                assert any(
                    f"v14 serving key '{key}'" in p for p in problems
                ), (version, key, problems)

    def test_alert_object_forbidden_on_non_alert_lines(self):
        line = self._alert_line(kind="window")
        line["metrics"] = {"loss": 1.0}
        problems = schema.validate_line(line)
        assert any("alert object on a non-alert line" in p
                   for p in problems)

    def test_missing_alert_object_flagged(self):
        line = self._alert_line()
        del line["alert"]
        problems = schema.validate_line(line)
        assert any("missing the alert object" in p for p in problems)

    def test_alert_field_types_enforced(self):
        line = self._alert_line()
        line["alert"]["state"] = "screaming"
        problems = schema.validate_line(line)
        assert any("alert['state']" in p for p in problems)
        line = self._alert_line()
        line["alert"]["burn_rate"] = "hot"
        problems = schema.validate_line(line)
        assert any("'burn_rate'" in p for p in problems)
        line = self._alert_line()
        del line["alert"]["name"]
        problems = schema.validate_line(line)
        assert any("missing required key 'name'" in p for p in problems)
        line = self._alert_line()
        line["alert"]["trace_id"] = 7
        problems = schema.validate_line(line)
        assert any("'trace_id'" in p for p in problems)

    def test_v1_line_rejects_v14_field(self):
        line = {
            "schema_version": 1, "kind": "window", "step": 1,
            "time_unix": 1.0, "session_start_unix": 1.0, "host": 0,
            "metrics": {"loss": 1.0}, "counters": {}, "gauges": {},
            "derived": {}, "alert": {"name": "x"},
        }
        problems = schema.validate_line(line)
        assert any("v14 field 'alert'" in p for p in problems)
