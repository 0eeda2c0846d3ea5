"""Telemetry layer tests (ISSUE 2).

Covers the core contracts — registry counter/gauge/histogram semantics,
span nesting + Chrome-trace export (golden file under a fake clock),
throughput/MFU/goodput math for the MNIST and GPT-2 shapes, the JSONL
line schema — and the wired behavior: a CPU MNIST smoke run producing a
schema-valid JSONL + a multi-span Chrome trace (the ISSUE 2 acceptance
criterion), final-window flushes on the preemption and bad-step abort
exit paths, the explicit null-writer fallback for the TensorBoard sink,
and the watchdog naming the open span in its hang dump.

Marked ``telemetry`` (and deliberately not ``slow``) so the tier-1
command always validates the observability layer it relies on.
"""

import json
import logging
import os
import re

import jax
import numpy as np
import pytest

from tensorflow_examples_tpu.data.memory import eval_batches, train_iterator
from tensorflow_examples_tpu.data.sources import synthetic_images
from tensorflow_examples_tpu.telemetry import accounting, schema
from tensorflow_examples_tpu.telemetry import registry as registry_mod
from tensorflow_examples_tpu.telemetry import sinks as sinks_mod
from tensorflow_examples_tpu.telemetry import spans as spans_mod
from tensorflow_examples_tpu.train import resilience
from tensorflow_examples_tpu.train.loop import Trainer
from tensorflow_examples_tpu.utils import faults as faults_mod
from tensorflow_examples_tpu.workloads import mnist

pytestmark = pytest.mark.telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "golden")


@pytest.fixture
def fresh_telemetry():
    """Isolated default registry + tracer for counting assertions."""
    reg = registry_mod.reset_default_registry()
    tracer = spans_mod.reset_default_tracer()
    yield reg, tracer
    registry_mod.reset_default_registry()
    spans_mod.reset_default_tracer()


def tiny_cfg(**kw):
    defaults = dict(
        device="cpu",
        global_batch_size=64,
        train_steps=12,
        log_every=4,
        learning_rate=1e-2,
        hidden=16,
        num_layers=1,
        dropout=0.0,
        precision="f32",
        checkpoint_every=6,
        watchdog_secs=0,
    )
    defaults.update(kw)
    return mnist.MnistConfig(**defaults)


def _data(n=256):
    return synthetic_images(n=n, shape=(28, 28, 1), num_classes=10, seed=0)


# ------------------------------------------------------------- registry


class TestRegistry:
    def test_counter_semantics(self):
        reg = registry_mod.MetricsRegistry()
        c = reg.counter("x")
        assert c is reg.counter("x")  # get-or-create returns the instance
        c.inc()
        c.inc(4)
        assert c.value == 5
        with pytest.raises(ValueError, match="must be >= 0"):
            c.inc(-1)
        assert reg.counter_values() == {"x": 5}

    def test_gauge_semantics(self):
        reg = registry_mod.MetricsRegistry()
        g = reg.gauge("g")
        assert g.value is None
        assert reg.gauge_values() == {}  # unset gauges don't emit
        g.set(2)
        g.set(3.5)
        assert reg.gauge_values() == {"g": 3.5}

    def test_histogram_semantics(self):
        reg = registry_mod.MetricsRegistry()
        h = reg.histogram("t")
        assert h.percentile(50) is None
        assert h.summary()["count"] == 0
        for v in [0.1, 0.2, 0.3, 0.4, 1.0]:
            h.record(v)
        s = h.summary()
        assert s["count"] == 5
        assert s["min"] == pytest.approx(0.1)
        assert s["max"] == pytest.approx(1.0)
        assert s["mean"] == pytest.approx(0.4)
        assert h.percentile(50) == pytest.approx(0.3)  # nearest-rank
        assert h.percentile(95) == pytest.approx(1.0)

    def test_histogram_sample_window_bounded(self):
        h = registry_mod.TimeHistogram("t", max_samples=4)
        for v in [10.0, 10.0, 1.0, 2.0, 3.0, 4.0]:
            h.record(v)
        assert h.count == 6  # aggregates cover the whole run...
        assert h.max == 10.0
        assert h.percentile(95) == 4.0  # ...percentiles the recent window

    def test_snapshot_and_merge(self):
        reg = registry_mod.MetricsRegistry()
        reg.counter("a").inc(2)
        reg.gauge("b").set(1.0)
        reg.histogram("c").record(0.5)
        snap = reg.snapshot()
        assert snap["counters"] == {"a": 2}
        assert snap["gauges"] == {"b": 1.0}
        assert snap["histograms"]["c"]["count"] == 1
        reg.merge_counter_values({"a": 3, "new": 7})
        assert reg.counter_values() == {"a": 5, "new": 7}


# ---------------------------------------------------------------- spans


class TestSpans:
    def test_nesting_feeds_histogram_and_active_names(self, fresh_telemetry):
        reg, tracer = fresh_telemetry
        seen_inside = []
        with tracer.span("outer"):
            with tracer.span("inner"):
                seen_inside.append(tracer.active_span_names())
        assert seen_inside == [["inner"]]  # innermost open span
        assert tracer.active_span_names() == []
        names = [e["name"] for e in tracer.events()]
        assert names == ["inner", "outer"]  # completion order
        assert reg.histogram("span/outer").count == 1
        assert reg.histogram("span/inner").count == 1

    def test_nesting_timestamps_contained(self):
        tracer = spans_mod.Tracer(registry_mod.MetricsRegistry())
        with tracer.span("outer"):
            with tracer.span("inner"):
                pass
        inner, outer = tracer.events()
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3

    def test_event_buffer_bounded(self):
        tracer = spans_mod.Tracer(
            registry_mod.MetricsRegistry(), max_events=2
        )
        for i in range(5):
            with tracer.span(f"s{i}"):
                pass
        assert len(tracer.events()) == 2  # first events kept
        assert tracer.dropped == 3
        assert tracer.chrome_trace()["droppedEventCount"] == 3

    def test_chrome_trace_golden(self):
        """Pin the export format byte-for-byte under a fake clock (thread
        id normalized — the one legitimately nondeterministic field)."""
        clock = iter(range(0, 100_000, 1000))  # 1µs ticks
        tracer = spans_mod.Tracer(
            registry_mod.MetricsRegistry(), now_ns=lambda: next(clock)
        )
        with tracer.span("step", step=3):
            with tracer.span("fetch"):
                pass
        trace = tracer.chrome_trace()
        for ev in trace["traceEvents"]:
            ev["tid"] = 0
        got = json.dumps(trace, indent=2, sort_keys=True) + "\n"
        golden_path = os.path.join(GOLDEN, "chrome_trace.json")
        with open(golden_path) as f:
            assert got == f.read(), (
                "chrome trace format drifted; if intentional, regenerate "
                f"{golden_path} with this test's `got` value"
            )


# ----------------------------------------------------------- accounting


class TestAccounting:
    def test_train_step_flops_mnist_shape(self):
        # Per-example workload: 6 * N * B (tokens_per_example = 1).
        assert accounting.train_step_flops(12_730, 256) == pytest.approx(
            6.0 * 12_730 * 256
        )

    def test_train_step_flops_gpt2_shape(self):
        # Token workload: 6 * N * B * S — GPT-2 124M at B=16, S=1024.
        n = 124_000_000
        assert accounting.train_step_flops(n, 16, 1024) == pytest.approx(
            6.0 * n * 16 * 1024
        )

    def test_mfu(self):
        # 100 GFLOP steps at 10/s on a 10 TFLOP/s chip = 10% MFU.
        assert accounting.mfu(100e9, 10.0, 10e12) == pytest.approx(0.1)
        assert accounting.mfu(0.0, 10.0, 10e12) is None
        assert accounting.mfu(100e9, 10.0, 0.0) is None

    def test_peak_table(self):
        assert accounting.peak_flops_per_device("TPU v4") == 275e12
        assert accounting.peak_flops_per_device("TPU v5 lite") == 197e12
        # Not in the table: no peak, hence no MFU — never a default.
        assert accounting.peak_flops_per_device("cpu") is None
        from tensorflow_examples_tpu.telemetry import Telemetry

        tel = Telemetry.from_config(
            tiny_cfg(telemetry_sinks="", telemetry_trace=False), n_params=10
        )
        assert tel.peak_flops_total == 0.0
        assert accounting.mfu(tel.flops_per_step, 1.0, 0.0) is None

    def test_goodput(self):
        assert accounting.goodput({}) is None  # nothing stepped yet
        assert accounting.goodput({"train/steps_total": 100}) == 1.0
        assert accounting.goodput(
            {
                "train/steps_total": 100,
                "resilience/bad_steps": 3,
                "resilience/steps_lost": 7,
            }
        ) == pytest.approx(0.90)


# ---------------------------------------------------------------- schema


class TestSchema:
    def _line(self, **over):
        line = {
            "schema_version": schema.SCHEMA_VERSION,
            "kind": "window",
            "host": 0,
            "step": 10,
            "time_unix": 1_700_000_000.0,
            "session_start_unix": 1_699_999_000.0,
            "metrics": {"train/loss": 1.5},
            "counters": {"train/steps_total": 10},
            "gauges": {},
            "derived": {"mfu": None, "goodput": 1.0},
        }
        line.update(over)
        return line

    def test_valid_line(self):
        assert schema.validate_line(self._line()) == []
        schema.validate(self._line())  # and the raising form passes

    def test_golden_v1_line_still_parses(self):
        """Pre-ISSUE-3 run dirs must keep validating: a frozen v1 line
        (no memory/compile/profile fields, v1 kinds only)."""
        v1 = {
            "schema_version": 1,
            "kind": "final",
            "step": 400,
            "time_unix": 1_760_000_000.0,
            "session_start_unix": 1_759_999_000.0,
            "metrics": {"train/loss": 2.31},
            "counters": {"train/steps_total": 400, "io/retries": 1},
            "gauges": {"telemetry/flops_per_step": 1.2e15},
            "derived": {"examples_per_sec": 51234.0, "mfu": 0.42,
                        "goodput": 1.0},
            "exit_reason": "complete",
        }
        assert schema.validate_line(v1) == []

    def test_v2_fields_rejected_on_v1_lines(self):
        assert any(
            "v2 field" in p
            for p in schema.validate_line(
                self._line(schema_version=1, memory={"live_bytes": 1})
            )
        )
        assert schema.validate_line(self._line(kind="memory",
                                               schema_version=1))

    def test_memory_kind_and_fields(self):
        # memory object optional on windows, required on memory lines.
        assert schema.validate_line(
            self._line(memory={"live_bytes": 100, "peak_live_bytes": 200})
        ) == []
        assert any(
            "missing the memory object" in p
            for p in schema.validate_line(self._line(kind="memory"))
        )
        assert schema.validate_line(
            self._line(kind="memory", memory={"params_bytes": 10})
        ) == []
        assert schema.validate_line(self._line(memory={"x": "big"}))

    def test_compile_warning_contract(self):
        good = self._line(
            kind="compile_warning",
            compile={"fn": "train_step", "delta": "axis 0: 64->32",
                     "count": 2, "wall_secs": 0.5},
        )
        assert schema.validate_line(good) == []
        assert any(
            "missing the compile object" in p
            for p in schema.validate_line(self._line(kind="compile_warning"))
        )
        assert schema.validate_line(
            self._line(kind="compile_warning", compile={"fn": "x"})
        )  # delta required
        # and the compile object is exclusive to compile_warning lines
        assert schema.validate_line(
            self._line(compile={"fn": "x", "delta": "y"})
        )

    def test_profile_object_final_only(self):
        prof = {"dir": "/tmp/p", "start_step": 10, "num_steps": 10,
                "wall_secs": 1.0}
        assert schema.validate_line(
            self._line(kind="final", exit_reason="complete", profile=prof)
        ) == []
        assert schema.validate_line(self._line(profile=prof))
        assert schema.validate_line(
            self._line(kind="final", exit_reason="complete",
                       profile={"dir": 3})
        )

    def test_v3_host_field_contract(self):
        """ISSUE 4: every v3 line carries the writing host's index; v1/
        v2 lines must not (a 'v2' line with one is mislabeled v3)."""
        assert schema.validate_line(self._line()) == []
        line = self._line()
        del line["host"]
        assert any("host" in p for p in schema.validate_line(line))
        assert schema.validate_line(self._line(host=-1))
        assert schema.validate_line(self._line(host=True))
        v2 = self._line(schema_version=2)
        assert any(
            "v3 field 'host'" in p for p in schema.validate_line(v2)
        )
        del v2["host"]
        assert schema.validate_line(v2) == []  # v2 without host: fine
        assert any(
            "v3 field 'fleet'" in p
            for p in schema.validate_line(dict(v2, fleet={"hosts": []}))
        )

    def _fleet(self, **over):
        fleet = {
            "hosts": [
                {"host": 0, "step_time_p50": 0.01, "step_time_p95": 0.011,
                 "data_fetch_p95": 0.001, "steps_lost": 0,
                 "peak_live_bytes": 1024, "io_retries": 0,
                 "batches_skipped": 0},
                {"host": 1, "step_time_p50": 0.01, "step_time_p95": 0.05,
                 "data_fetch_p95": 0.04, "steps_lost": 0,
                 "peak_live_bytes": 1024, "io_retries": 3,
                 "batches_skipped": 0},
            ],
            "slowest_host": 1,
            "skew": 4.5,
            "side": "input",
            "straggler": True,
        }
        fleet.update(over)
        return fleet

    def test_fleet_line_contract(self):
        good = self._line(kind="fleet", fleet=self._fleet())
        assert schema.validate_line(good) == []
        # nulls where a host had no data yet are fine
        assert schema.validate_line(
            self._line(kind="fleet", fleet=self._fleet(
                slowest_host=None, skew=None, side=None, straggler=False,
            ))
        ) == []
        # the fleet object is required on (and exclusive to) fleet lines
        assert any(
            "missing the fleet object" in p
            for p in schema.validate_line(self._line(kind="fleet"))
        )
        assert any(
            "non-fleet line" in p
            for p in schema.validate_line(self._line(fleet=self._fleet()))
        )
        # hosts must be a non-empty list of host-indexed objects
        assert schema.validate_line(
            self._line(kind="fleet", fleet=self._fleet(hosts=[]))
        )
        assert schema.validate_line(
            self._line(kind="fleet",
                       fleet=self._fleet(hosts=[{"step_time_p50": 1.0}]))
        )
        # every FLEET_HOST_KEYS entry is required (writer and validator
        # share the tuple — fleet.VECTOR_KEYS aliases the schema's
        # vector, whose required prefix is FLEET_HOST_KEYS; the
        # data_work_p95 extension is additive/optional so pre-ISSUE-6
        # lines keep validating)
        from tensorflow_examples_tpu.telemetry import fleet as fleet_mod

        assert fleet_mod.VECTOR_KEYS is schema.FLEET_VECTOR_KEYS
        assert schema.FLEET_VECTOR_KEYS[: len(schema.FLEET_HOST_KEYS)] == (
            schema.FLEET_HOST_KEYS
        )
        incomplete = dict(self._fleet()["hosts"][0])
        del incomplete["data_fetch_p95"]
        assert any(
            "missing 'data_fetch_p95'" in p
            for p in schema.validate_line(
                self._line(kind="fleet",
                           fleet=self._fleet(hosts=[incomplete]))
            )
        )
        assert schema.validate_line(
            self._line(kind="fleet", fleet=self._fleet(side="network"))
        )
        assert schema.validate_line(
            self._line(kind="fleet", fleet=self._fleet(skew="big"))
        )
        assert schema.validate_line(
            self._line(kind="fleet", fleet=self._fleet(straggler="yes"))
        )
        # v2 lines don't know the fleet kind at all
        assert schema.validate_line(
            {**self._line(kind="fleet", fleet=self._fleet()),
             "schema_version": 2}
        )

    def test_violations_detected(self):
        assert schema.validate_line("not a dict")
        assert any(
            "missing" in p
            for p in schema.validate_line({"schema_version": 1})
        )
        assert schema.validate_line(self._line(schema_version=99))
        assert schema.validate_line(self._line(kind="bogus"))
        assert schema.validate_line(self._line(step=-1))
        assert schema.validate_line(self._line(session_start_unix="soon"))
        assert schema.validate_line(self._line(counters={"c": -2}))
        assert schema.validate_line(self._line(counters={"c": 1.5}))
        assert schema.validate_line(self._line(metrics={"m": "oops"}))
        # exit_reason is required on final lines and forbidden elsewhere.
        assert schema.validate_line(self._line(kind="final"))
        assert not schema.validate_line(
            self._line(kind="final", exit_reason="complete")
        )
        assert schema.validate_line(self._line(exit_reason="complete"))
        with pytest.raises(ValueError, match="violates schema"):
            schema.validate(self._line(kind="bogus"))


# ------------------------------------------------- wired smoke run


@pytest.fixture(scope="class")
def smoke_run(tmp_path_factory):
    """One tiny MNIST fit with every telemetry surface on (acceptance
    criterion run): JSONL + trace + eval + checkpoints."""
    registry_mod.reset_default_registry()
    spans_mod.reset_default_tracer()
    wd = str(tmp_path_factory.mktemp("telemetry_smoke"))
    # The CPU is not in the peaks table: the MFU these tests read needs
    # an explicit peak.
    cfg = tiny_cfg(workdir=wd, eval_every=6, telemetry_peak_tflops=1.0)
    ds = _data()
    trainer = Trainer(mnist.make_task(cfg), cfg)
    metrics = trainer.fit(
        lambda start: train_iterator(ds, 64, seed=7, start_step=start),
        eval_iter_fn=lambda: eval_batches(_data(n=128), 64),
    )
    yield wd, cfg, trainer, metrics
    registry_mod.reset_default_registry()
    spans_mod.reset_default_tracer()


@pytest.mark.timeout(300)
class TestSmokeRun:
    def _lines(self, wd):
        with open(sinks_mod.metrics_path(wd)) as f:
            return [json.loads(line) for line in f]

    def test_every_jsonl_line_validates(self, smoke_run):
        wd, _, _, _ = smoke_run
        lines = self._lines(wd)
        assert lines, "no telemetry lines written"
        for line in lines:
            assert schema.validate_line(line) == [], line

    def test_window_cadence_and_final_marker(self, smoke_run):
        wd, cfg, _, _ = smoke_run
        lines = self._lines(wd)
        kinds = [(l["kind"], l["step"]) for l in lines]
        assert ("window", 4) in kinds and ("window", 12) in kinds
        assert lines[-1]["kind"] == "final"
        assert lines[-1]["exit_reason"] == "complete"
        assert lines[-1]["step"] == cfg.train_steps

    def test_counters_cover_wired_layers(self, smoke_run):
        wd, cfg, _, _ = smoke_run
        c = self._lines(wd)[-1]["counters"]
        assert c["train/steps_total"] == cfg.train_steps
        assert c["data/batches_fetched"] >= cfg.train_steps
        assert c["checkpoint/saves"] >= 2  # cadence + final
        assert c.get("data/batches_skipped", 0) == 0

    def test_derived_accounting_present(self, smoke_run):
        """The acceptance numbers: examples/sec, step-time p50/p95, MFU,
        goodput all non-null on window lines."""
        wd, _, _, _ = smoke_run
        windows = [l for l in self._lines(wd) if l["kind"] == "window"]
        for key in (
            "examples_per_sec",
            "step_time_p50",
            "step_time_p95",
            "mfu",
            "goodput",
        ):
            assert windows[-1]["derived"][key] is not None, key
        assert windows[-1]["derived"]["goodput"] == 1.0

    def test_trace_has_core_span_names(self, smoke_run):
        wd, _, _, _ = smoke_run
        with open(sinks_mod.trace_path(wd)) as f:
            trace = json.load(f)
        names = {e["name"] for e in trace["traceEvents"]}
        assert {
            "data_fetch",
            "device_step",
            "metric_flush",
            "checkpoint_save",
            "eval",
        } <= names, names

    def test_eval_line_emitted(self, smoke_run):
        wd, _, _, _ = smoke_run
        evals = [l for l in self._lines(wd) if l["kind"] == "eval"]
        assert evals and any(
            k.startswith("eval/") for k in evals[-1]["metrics"]
        )

    def test_schema_v3_memory_watermark(self, smoke_run):
        """ISSUE 3 acceptance (schema bumped to v3 by ISSUE 4): the run
        emits current-version lines with a nonzero peak-memory
        watermark, plus the fit-start breakdown snapshot attributing
        bytes to params vs. optimizer."""
        wd, _, _, _ = smoke_run
        lines = self._lines(wd)
        assert all(
            l["schema_version"] == schema.SCHEMA_VERSION for l in lines
        )
        mems = [l for l in lines if l["kind"] == "memory"]
        assert len(mems) == 1  # the fit-start snapshot
        bd = mems[0]["memory"]
        assert bd["params_bytes"] > 0
        assert bd["opt_bytes"] > 0  # adam moments embed the param tree
        assert bd["live_bytes"] >= bd["params_bytes"] + bd["opt_bytes"]
        windows = [l for l in lines if l["kind"] == "window"]
        assert windows[-1]["memory"]["peak_live_bytes"] > 0
        assert (
            lines[-1]["memory"]["peak_live_bytes"]
            >= lines[-1]["memory"]["live_bytes"]
        )

    def test_compile_counters_and_no_recompiles(self, smoke_run):
        """Fixed-shape training compiles each step fn exactly once
        (train + eval): the sentinel counts them, and no recompile
        warning fires."""
        wd, _, _, _ = smoke_run
        lines = self._lines(wd)
        c = lines[-1]["counters"]
        assert c["compile/count"] >= 2  # train_step + eval_step
        assert c.get("compile/recompiles", 0) == 0
        assert not [l for l in lines if l["kind"] == "compile_warning"]
        with open(sinks_mod.trace_path(wd)) as f:
            names = {e["name"] for e in json.load(f)["traceEvents"]}
        assert "compile" in names  # compile wall time is span-traced

    def test_fleet_lines_on_single_host(self, smoke_run):
        """ISSUE 4: even a one-host run emits a kind="fleet" line per
        cadenced window (one-host fleet, no straggler), every line
        carries the writing host index, and a window line precedes each
        fleet line at the same step."""
        wd, _, _, _ = smoke_run
        lines = self._lines(wd)
        assert all(l["host"] == 0 for l in lines)
        fleets = [l for l in lines if l["kind"] == "fleet"]
        windows = [l for l in lines if l["kind"] == "window"]
        assert len(fleets) == len(windows) >= 2
        assert [f["step"] for f in fleets] == [w["step"] for w in windows]
        fl = fleets[-1]["fleet"]
        assert [h["host"] for h in fl["hosts"]] == [0]
        assert fl["hosts"][0]["step_time_p95"] > 0
        assert fl["hosts"][0]["peak_live_bytes"] > 0
        assert fl["slowest_host"] == 0
        assert fl["skew"] == pytest.approx(1.0)
        assert fl["straggler"] is False

    def test_report_cli_on_real_run(self, smoke_run, capsys):
        """The full acceptance loop: the run dir feeds the report CLI,
        which must surface examples/sec, step-time p50/p95, the MFU
        estimate, and goodput. In-process main() — the subprocess-level
        contract is pinned in tests/test_tools.py."""
        import sys

        sys.path.insert(0, os.path.join(REPO, "tools"))
        import telemetry_report

        wd, _, _, _ = smoke_run
        rc = telemetry_report.main(
            [wd, "--json", os.path.join(wd, "report.json")]
        )
        out = capsys.readouterr().out
        assert rc == 0, out
        for needle in ("examples/sec", "p50", "p95", "mfu estimate",
                       "goodput", "ended: complete"):
            assert needle in out, (needle, out)
        rec = json.load(open(os.path.join(wd, "report.json")))
        for key in ("examples_per_sec_mean", "step_time_p50",
                    "step_time_p95", "mfu", "goodput"):
            assert rec[key] is not None, key
        assert rec["trace_phases"]["device_step"]["count"] > 0


# ------------------------------------------- abnormal-exit flushes


@pytest.mark.timeout(300)
class TestAbnormalExitFlush:
    """One Trainer (one jit compile) exercises both abnormal exit paths:
    the guard stays compiled-in ("skip" and "abort" share guard_on), and
    each fit rebinds workdir/policy via ``config.replace`` — fit() reads
    sinks, guard, and cadences from the live config at call time."""

    @pytest.fixture(scope="class")
    def exit_trainer(self):
        registry_mod.reset_default_registry()
        spans_mod.reset_default_tracer()
        cfg = tiny_cfg(
            train_steps=12, log_every=50, bad_step_policy="skip"
        )
        yield Trainer(mnist.make_task(cfg), cfg)
        registry_mod.reset_default_registry()
        spans_mod.reset_default_tracer()

    def test_sigterm_final_window_in_jsonl(
        self, faults, tmp_path, devices, exit_trainer, fresh_telemetry
    ):
        """Preemption satellite: the partial in-flight window must land
        in the JSONL before the clean exit — log_every is sized so NO
        cadenced window fires before the SIGTERM."""
        wd = str(tmp_path)
        trainer = exit_trainer
        trainer.config = trainer.config.replace(workdir=wd)
        ds = _data()
        faults("sigterm@4")
        with pytest.raises(resilience.Preempted):
            trainer.fit(
                lambda start: train_iterator(ds, 64, seed=7, start_step=start)
            )
        with open(sinks_mod.metrics_path(wd)) as f:
            lines = [json.loads(line) for line in f]
        assert lines, "preempt exit wrote no telemetry"
        final = lines[-1]
        assert schema.validate_line(final) == []
        assert final["kind"] == "final"
        assert final["exit_reason"] == "preempt"
        # The partial window's metrics made it out (steps 0..4 ran un-
        # logged), and the preemption itself is counted.
        assert any(k == "train/loss" for k in final["metrics"])
        assert final["counters"]["resilience/preemptions"] == 1
        assert final["counters"]["train/steps_total"] == final["step"]

    def test_bad_step_abort_writes_final_line(
        self, faults, tmp_path, devices, exit_trainer, fresh_telemetry
    ):
        wd = str(tmp_path)
        trainer = exit_trainer
        trainer.config = trainer.config.replace(
            workdir=wd, bad_step_policy="abort"
        )
        # The shared trainer resumed at step 5 (post-preemption state);
        # inject within the live step range.
        faults("nan@7")
        with pytest.raises(resilience.BadStepError):
            trainer.fit(train_iterator(_data(), 64, seed=0))
        with open(sinks_mod.metrics_path(wd)) as f:
            lines = [json.loads(line) for line in f]
        final = lines[-1]
        assert final["kind"] == "final"
        assert final["exit_reason"] == "error:BadStepError"
        assert final["counters"]["resilience/bad_steps"] >= 1
        assert accounting.goodput(final["counters"]) < 1.0


def test_emergency_flush_lands_fatal_marker(tmp_path, fresh_telemetry):
    """The watchdog-fatal hook (exit 87) must leave a final JSONL line
    and the trace on disk even when no window was ever emitted."""
    from tensorflow_examples_tpu.telemetry.hub import Telemetry

    reg, tracer = fresh_telemetry
    jsonl = str(tmp_path / "metrics.jsonl")
    trace = str(tmp_path / "trace.json")
    tel = Telemetry(
        [sinks_mod.JsonlSink(jsonl)], registry=reg, tracer=tracer,
        trace_file=trace,
    )
    # Counted AFTER creation: lines carry fit-start deltas.
    reg.counter("train/steps_total").inc(3)
    with tracer.span("device_step"):
        pass
    tel.emergency_flush()
    lines = [json.loads(l) for l in open(jsonl)]
    assert len(lines) == 1
    assert schema.validate_line(lines[0]) == []
    assert lines[0]["kind"] == "final"
    assert lines[0]["exit_reason"] == "watchdog_fatal"
    assert lines[0]["counters"]["train/steps_total"] == 3
    assert {e["name"] for e in json.load(open(trace))["traceEvents"]} == {
        "device_step"
    }


# ------------------------------------------------------ fleet monitor


class TestFleetMonitor:
    """telemetry/fleet.py unit layer: the mocked-allgather path (the
    real 2-process collective is pinned in tests/test_distributed.py)."""

    def _monitor(self, reg, allgather, *, skew_factor=2.0, count=2):
        from tensorflow_examples_tpu.telemetry import fleet as fleet_mod

        return fleet_mod.FleetMonitor(
            skew_factor=skew_factor, registry=reg, allgather=allgather,
            process_index=0, process_count=count,
        )

    def _feed(self, reg, *, step=0.01, fetch=0.001, n=10):
        for _ in range(n):
            reg.histogram("step_time").record(step)
            reg.histogram("span/data_fetch").record(fetch)
        reg.gauge("memory/peak_live_bytes").set(4096)

    def test_input_side_straggler_named(self, fresh_telemetry, caplog):
        """A host whose data-fetch excess explains its step-time excess
        is an INPUT-side straggler; the warning names host and side."""
        reg, _ = fresh_telemetry
        self._feed(reg)

        def allgather(vec):
            slow = vec.copy()
            slow[1] *= 5.0  # step_time_p95
            slow[2] += slow[1]  # the fetch IS the stall
            return np.stack([vec, slow])

        mon = self._monitor(reg, allgather)
        with caplog.at_level(
            logging.WARNING, logger="tensorflow_examples_tpu"
        ):
            summary = mon.gather({"resilience/steps_lost": 0})
        assert summary["slowest_host"] == 1
        assert summary["skew"] == pytest.approx(5.0, rel=1e-3)
        assert summary["side"] == "input"
        assert summary["straggler"] is True
        warned = [
            r.getMessage()
            for r in caplog.records
            if "FLEET STRAGGLER" in r.getMessage()
        ]
        assert len(warned) == 1
        assert "host 1" in warned[0] and "input-side" in warned[0]
        # one warning per straggling host per fit — a second window with
        # the same straggler stays quiet
        caplog.clear()
        with caplog.at_level(
            logging.WARNING, logger="tensorflow_examples_tpu"
        ):
            mon.gather({"resilience/steps_lost": 0})
        assert not [
            r for r in caplog.records
            if "FLEET STRAGGLER" in r.getMessage()
        ]

    def test_device_blocked_host_not_misreported_as_input_side(
        self, fresh_telemetry
    ):
        """ISSUE 6 satellite: input-side verdicts read data_work (host
        time PRODUCING batches), not data_fetch. A host whose fetch
        time is queue back-pressure wait — big data_fetch, small
        data_work — is compute-side; only real production time flips
        the verdict to input."""
        from tensorflow_examples_tpu.telemetry import fleet as fleet_mod

        reg, _ = fresh_telemetry
        self._feed(reg)
        for _ in range(10):
            reg.histogram("span/data_work").record(0.0005)
        work_i = fleet_mod.VECTOR_KEYS.index("data_work_p95")

        def blocked_on_device(vec):
            slow = vec.copy()
            slow[1] *= 5.0  # step time skewed...
            slow[2] += slow[1]  # ...and the FETCH span shows the wait
            # ...but data_work stays flat: the host wasn't producing.
            return np.stack([vec, slow])

        summary = self._monitor(reg, blocked_on_device).gather({})
        assert summary["slowest_host"] == 1
        assert summary["straggler"] is True
        assert summary["side"] == "compute"  # pre-fix: "input"

        def genuinely_input_bound(vec):
            slow = vec.copy()
            slow[1] *= 5.0
            slow[2] += slow[1]
            slow[work_i] += slow[1]  # the host really was producing
            return np.stack([vec, slow])

        summary = self._monitor(reg, genuinely_input_bound).gather({})
        assert summary["side"] == "input"
        # hosts entries carry the new key (numeric), schema-valid
        assert summary["hosts"][0]["data_work_p95"] is not None

    def test_compute_side_straggler(self, fresh_telemetry):
        """Skewed step time with flat data-fetch time = the device side
        (slow chip, thermal, busy host) is to blame."""
        reg, _ = fresh_telemetry
        self._feed(reg)

        def allgather(vec):
            slow = vec.copy()
            slow[1] *= 4.0  # step time skewed, fetch untouched
            return np.stack([vec, slow])

        summary = self._monitor(reg, allgather).gather({})
        assert summary["slowest_host"] == 1
        assert summary["side"] == "compute"
        assert summary["straggler"] is True

    def test_balanced_fleet_not_flagged(self, fresh_telemetry):
        reg, _ = fresh_telemetry
        self._feed(reg)

        def allgather(vec):
            other = vec.copy()
            other[1] *= 1.1  # 10% wobble is not a straggler
            return np.stack([vec, other])

        summary = self._monitor(reg, allgather).gather({})
        assert summary["straggler"] is False
        assert summary["skew"] == pytest.approx(1.1, rel=1e-3)

    def test_single_host_and_empty_registry(self, fresh_telemetry):
        reg, _ = fresh_telemetry
        mon = self._monitor(reg, None, count=1)
        # No samples at all: a valid summary with null attribution.
        empty = mon.gather({})
        assert empty["slowest_host"] is None
        assert empty["straggler"] is False
        self._feed(reg)
        summary = mon.gather({"resilience/steps_lost": 3})
        assert summary["hosts"][0]["steps_lost"] == 3
        assert summary["skew"] == pytest.approx(1.0)
        assert summary["straggler"] is False  # 1-host fleet never flags

    def test_emergency_snapshot_is_collective_free(self, fresh_telemetry):
        """The watchdog-fatal path must never enter a collective: the
        snapshot replays the cached summary (marked emergency), and
        works even before any gather happened."""
        reg, _ = fresh_telemetry
        self._feed(reg)
        calls = []

        def allgather(vec):
            calls.append(1)
            slow = vec.copy()
            slow[1] *= 5.0
            return np.stack([vec, slow])

        mon = self._monitor(reg, allgather)
        mon.gather({})
        assert len(calls) == 1
        snap = mon.snapshot()
        assert len(calls) == 1  # NO new collective
        assert snap["emergency"] is True
        assert snap["slowest_host"] == 1
        # Never gathered: local-only snapshot, still collective-free.
        cold = self._monitor(reg, allgather)
        snap = cold.snapshot()
        assert len(calls) == 1
        assert snap["emergency"] is True
        assert [h["host"] for h in snap["hosts"]] == [0]


@pytest.mark.timeout(300)
def test_fleet_line_names_fault_injected_straggler(
    tmp_path, faults, monkeypatch, fresh_telemetry, caplog
):
    """ISSUE 4 acceptance on CPU (mocked allgather): a run whose input
    pipeline is stalled by the ``slow`` fault spec must emit a fleet
    line naming THIS host as an input-side straggler, and log the
    warning naming host and side.

    Two fits: a healthy one whose measured health vector becomes the
    synthetic peer (host 1), then the fault-injected one as host 0 —
    the allgather mock stacks [this host, healthy peer], so the skew
    and side attribution come entirely from REAL measurements and the
    REAL injected fault, not from hand-written numbers.
    """
    from tensorflow_examples_tpu.telemetry import fleet as fleet_mod

    cfg = tiny_cfg(
        workdir=str(tmp_path), train_steps=8, log_every=4,
        checkpoint_every=0, straggler_skew_factor=2.0,
    )
    ds = _data()

    # ---- fit 1: healthy run; its vector is the synthetic fast peer ----
    trainer = Trainer(mnist.make_task(cfg), cfg)
    trainer.fit(lambda start: train_iterator(ds, 64, seed=7, start_step=start))
    healthy_vec = fleet_mod.FleetMonitor().local_vector({})
    assert np.isfinite(healthy_vec[:3]).all()

    # ---- fit 2: same trainer, slow-host fault armed, mocked fleet ----
    registry_mod.reset_default_registry()
    spans_mod.reset_default_tracer()

    def mock_allgather(vec):
        return np.stack([vec, healthy_vec])

    def from_config(cfg_):
        return fleet_mod.FleetMonitor(
            skew_factor=float(cfg_.straggler_skew_factor),
            allgather=mock_allgather,
            process_index=0,
            process_count=2,
        )

    monkeypatch.setattr(
        fleet_mod.FleetMonitor, "from_config", staticmethod(from_config)
    )
    faults("slow@5:1.0,slow@6:1.0")  # the injected slow host: host 0
    wd2 = str(tmp_path / "faulted")
    trainer.config = cfg.replace(workdir=wd2)
    with caplog.at_level(logging.WARNING, logger="tensorflow_examples_tpu"):
        # Fit 1 left the (checkpoint-less) state at step 8: continue to
        # 16 so this fit really steps; fetch indices restart at 0.
        trainer.fit(
            lambda start: train_iterator(ds, 64, seed=7, start_step=start),
            num_steps=16,
        )

    with open(sinks_mod.metrics_path(wd2)) as f:
        lines = [json.loads(line) for line in f]
    for line in lines:
        assert schema.validate_line(line) == [], line
    fleets = [l for l in lines if l["kind"] == "fleet"]
    assert fleets, [l["kind"] for l in lines]
    fl = fleets[-1]["fleet"]
    assert [h["host"] for h in fl["hosts"]] == [0, 1]
    assert fl["slowest_host"] == 0  # the fault-injected host, by name
    assert fl["straggler"] is True
    assert fl["side"] == "input"  # the stall sat in the data fetch
    assert fl["skew"] >= 2.0
    assert fl["hosts"][0]["data_fetch_p95"] >= 0.9  # the 1s stalls
    warned = [
        r.getMessage()
        for r in caplog.records
        if "FLEET STRAGGLER" in r.getMessage()
    ]
    assert warned and "host 0" in warned[0] and "input-side" in warned[0]


# ------------------------------------------------------ metrics server


def _get(url: str):
    import urllib.error
    import urllib.request

    try:
        with urllib.request.urlopen(url, timeout=5) as resp:
            return resp.status, resp.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


_PROM_SAMPLE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? "
    r"(-?\d+(\.\d+)?([eE][+-]?\d+)?|NaN|[+-]Inf)$"
)


def _assert_valid_prometheus(text: str) -> list[str]:
    """Every line is a comment or a well-formed sample; returns the
    sample metric names."""
    names = []
    for line in text.strip().splitlines():
        if line.startswith("#"):
            assert line.startswith(("# TYPE ", "# HELP ")), line
            continue
        assert _PROM_SAMPLE.match(line), f"invalid prometheus line: {line}"
        names.append(line.split("{")[0].split(" ")[0])
    return names


class TestMetricsServer:
    def test_endpoints_serve_registry_and_window(self, fresh_telemetry):
        import json as json_mod

        from tensorflow_examples_tpu.telemetry import fleet as fleet_mod
        from tensorflow_examples_tpu.telemetry import serve as serve_mod
        from tensorflow_examples_tpu.telemetry.hub import Telemetry

        reg, tracer = fresh_telemetry
        reg.counter("train/steps_total").inc(7)
        reg.gauge("memory/peak_live_bytes").set(2048)
        reg.histogram("step_time").record(0.01)
        tel = Telemetry(
            [], registry=reg, tracer=tracer, host=0,
            fleet=fleet_mod.FleetMonitor(
                registry=reg, process_index=0, process_count=1
            ),
        )
        srv = serve_mod.MetricsServer(reg, port=0, telemetry=tel).start()
        try:
            # /window and /fleet 404 before any line exists
            status, _ = _get(srv.url("/window"))
            assert status == 404
            status, _ = _get(srv.url("/fleet"))
            assert status == 404
            # the fit-start memory snapshot must NOT satisfy /window —
            # its contract is the latest window/eval/final line
            tel.log_window(
                0, {}, kind="memory", reduce=False,
                extra={"memory": {"live_bytes": 1, "params_bytes": 1}},
            )
            status, _ = _get(srv.url("/window"))
            assert status == 404
            tel.log_window(7, {"loss": 1.25})
            status, text = _get(srv.url("/metrics"))
            assert status == 200
            names = _assert_valid_prometheus(text)
            assert "train_steps_total" in names
            assert "memory_peak_live_bytes" in names
            assert "step_time_seconds_count" in names
            assert 'host="0"' in text
            status, body = _get(srv.url("/health"))
            assert status == 200
            health = json_mod.loads(body)
            assert health["ok"] is True
            assert health["last_step"] == 7
            assert health["last_window_age_secs"] < 60
            # /window serves the WINDOW line (metrics intact), even
            # though the fleet line was emitted after it; /fleet serves
            # the fleet summary.
            status, body = _get(srv.url("/window"))
            assert status == 200
            line = json_mod.loads(body)
            assert line["kind"] == "window"
            assert line["step"] == 7
            assert line["metrics"]["train/loss"] == 1.25
            status, body = _get(srv.url("/fleet"))
            assert status == 200
            fleet_line = json_mod.loads(body)
            assert fleet_line["kind"] == "fleet"
            assert fleet_line["fleet"]["hosts"][0]["host"] == 0
            status, _ = _get(srv.url("/bogus"))
            assert status == 404
        finally:
            srv.close()
        srv.close()  # idempotent

    def test_health_503_on_watchdog_stall(self, fresh_telemetry):
        import json as json_mod
        import time as time_mod

        from tensorflow_examples_tpu.telemetry import serve as serve_mod
        from tensorflow_examples_tpu.utils.diagnostics import Watchdog

        reg, _ = fresh_telemetry
        wd = Watchdog(0.05, poll_s=10.0)  # not started: no dump thread
        wd.enter("device_step")
        srv = serve_mod.MetricsServer(reg, port=0, watchdog=wd).start()
        try:
            time_mod.sleep(0.1)  # stall past the timeout
            status, body = _get(srv.url("/health"))
            assert status == 503
            health = json_mod.loads(body)
            assert health["ok"] is False
            assert health["phase"] == "device_step"
            assert health["stalled_secs"] >= 0.05
            wd.pause()  # paused phases (eval, ckpt) are not stalls
            status, _ = _get(srv.url("/health"))
            assert status == 200
        finally:
            srv.close()

    def test_from_config_gating(self, fresh_telemetry):
        from tensorflow_examples_tpu.telemetry import serve as serve_mod

        assert serve_mod.MetricsServer.from_config(tiny_cfg()) is None
        srv = serve_mod.MetricsServer.from_config(
            tiny_cfg(metrics_port=18347)
        )
        assert srv is not None and srv.requested_port == 18347

    def test_sanitize_and_render(self, fresh_telemetry):
        from tensorflow_examples_tpu.telemetry import serve as serve_mod

        assert serve_mod.sanitize_metric_name("a/b-c.d") == "a_b_c_d"
        assert serve_mod.sanitize_metric_name("0weird") == "_0weird"
        reg, _ = fresh_telemetry
        reg.counter("io/retries").inc(2)
        text = serve_mod.render_prometheus(reg, host=3)
        assert "# TYPE io_retries counter" in text
        assert 'io_retries{host="3"} 2.0' in text


@pytest.mark.timeout(300)
def test_metrics_served_during_live_fit(tmp_path, fresh_telemetry):
    """ISSUE 4 acceptance: with metrics_port set, /metrics serves valid
    Prometheus text and /health answers WHILE the run is live (queried
    from inside the input pipeline, mid-fit), and the port is closed on
    the fit exit path."""
    import socket
    import urllib.error
    import urllib.request

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    cfg = tiny_cfg(
        workdir=str(tmp_path), metrics_port=port, train_steps=8,
        log_every=4, checkpoint_every=0, watchdog_secs=30,
    )
    ds = _data()
    captured = {}

    def data(start):
        for i, batch in enumerate(
            train_iterator(ds, 64, seed=7, start_step=start)
        ):
            if i == 6 and not captured:  # after the step-4 window landed
                captured["metrics"] = _get(f"http://127.0.0.1:{port}/metrics")
                captured["health"] = _get(f"http://127.0.0.1:{port}/health")
                captured["window"] = _get(f"http://127.0.0.1:{port}/window")
            yield batch

    trainer = Trainer(mnist.make_task(cfg), cfg)
    trainer.fit(data)
    assert captured, "input pipeline never reached the probe batch"
    status, text = captured["metrics"]
    assert status == 200
    names = _assert_valid_prometheus(text)
    assert "train_steps_total" in names
    status, body = captured["health"]
    assert status == 200
    health = json.loads(body)
    assert health["ok"] is True and health["phase"] is not None
    status, body = captured["window"]
    assert status == 200
    assert json.loads(body)["step"] == 4
    # Exit path closed the server: the port no longer answers.
    assert trainer._telemetry.server is None
    with pytest.raises(urllib.error.URLError):
        urllib.request.urlopen(f"http://127.0.0.1:{port}/health", timeout=2)


def test_emergency_flush_fleet_snapshot_and_server_close(
    tmp_path, fresh_telemetry
):
    """ISSUE 4 satellite: the watchdog-fatal hook lands the cached
    fleet state as an emergency kind="fleet" line and closes the
    metrics server — before the final marker hits the disk is fine,
    before exit 87 is the contract."""
    import urllib.error
    import urllib.request

    from tensorflow_examples_tpu.telemetry import fleet as fleet_mod
    from tensorflow_examples_tpu.telemetry import serve as serve_mod
    from tensorflow_examples_tpu.telemetry.hub import Telemetry

    reg, tracer = fresh_telemetry
    reg.histogram("step_time").record(0.01)
    jsonl = str(tmp_path / "metrics.jsonl")
    mon = fleet_mod.FleetMonitor(
        skew_factor=2.0, registry=reg, process_index=0, process_count=1
    )
    tel = Telemetry(
        [sinks_mod.JsonlSink(jsonl)], registry=reg, tracer=tracer,
        fleet=mon, host=0,
    )
    srv = serve_mod.MetricsServer(reg, port=0, telemetry=tel).start()
    tel.server = srv
    port = srv.port
    tel.emergency_flush()
    lines = [json.loads(l) for l in open(jsonl)]
    # window-less run: [fleet snapshot, final marker], both schema-valid
    assert [l["kind"] for l in lines[-2:]] == ["fleet", "final"]
    for line in lines:
        assert schema.validate_line(line) == [], line
    assert lines[-2]["fleet"]["emergency"] is True
    assert lines[-1]["exit_reason"] == "watchdog_fatal"
    assert tel.server is None
    with pytest.raises(urllib.error.URLError):
        urllib.request.urlopen(f"http://127.0.0.1:{port}/health", timeout=2)


class TestTensorBoardSinkFallback:
    def test_null_writer_warns_once_naming_cause(
        self, tmp_path, caplog, monkeypatch
    ):
        """_make_writer satellite: the old silent `except: return None`
        becomes an explicit null writer + ONE warning naming the import
        failure."""
        import sys

        monkeypatch.setitem(sys.modules, "clu", None)  # import -> error
        monkeypatch.setattr(sinks_mod, "_tb_warned", False)
        with caplog.at_level(
            logging.WARNING, logger="tensorflow_examples_tpu"
        ):
            sink = sinks_mod.TensorBoardSink(str(tmp_path))
        warned = [
            r
            for r in caplog.records
            if "TensorBoard sink unavailable" in r.getMessage()
        ]
        assert len(warned) == 1
        # Names the failure class and its message (ModuleNotFoundError
        # here, via the sys.modules[...] = None import block).
        assert "Error" in warned[0].getMessage()
        assert "clu" in warned[0].getMessage()
        # Null behavior: writes are inert, never raising.
        sink.write(
            {"step": 1, "metrics": {"train/loss": 1.0}, "derived": {}}
        )
        sink.flush()
        caplog.clear()
        with caplog.at_level(
            logging.WARNING, logger="tensorflow_examples_tpu"
        ):
            sinks_mod.TensorBoardSink(str(tmp_path))  # second: quiet
        assert not [
            r
            for r in caplog.records
            if "TensorBoard sink unavailable" in r.getMessage()
        ]

    def test_unknown_sink_name_rejected(self):
        with pytest.raises(ValueError, match="unknown telemetry sink"):
            sinks_mod.make_sinks("jsonl,frobnicator", "")


# ------------------------------------------------------- watchdog span


def test_watchdog_dump_names_open_span(caplog, fresh_telemetry):
    import time

    from tensorflow_examples_tpu.utils.diagnostics import Watchdog

    hangs = []
    wd = Watchdog(
        0.15, on_hang=lambda step, stalled: hangs.append(step), poll_s=0.03
    ).start()
    try:
        wd.ping(3)
        with caplog.at_level(
            logging.ERROR, logger="tensorflow_examples_tpu"
        ):
            with spans_mod.span("data_fetch"):
                time.sleep(0.4)
    finally:
        wd.stop()
    dumps = [
        r.getMessage() for r in caplog.records if "WATCHDOG" in r.getMessage()
    ]
    assert dumps and "data_fetch" in dumps[0]


# ----------------------------------------- recompilation sentinel


class TestCompilationSentinel:
    def test_signature_and_delta_name_changed_axis(self):
        from tensorflow_examples_tpu.telemetry import compilation

        a = compilation.abstract_signature(
            ({"x": np.zeros((64, 28), np.float32)},), {}
        )
        b = compilation.abstract_signature(
            ({"x": np.zeros((32, 28), np.float32)},), {}
        )
        assert a != b
        delta = compilation.describe_delta(a, b)
        assert "axis 0: 64->32" in delta and "'x'" in delta
        # dtype changes are named too
        c = compilation.abstract_signature(
            ({"x": np.zeros((32, 28), np.float16)},), {}
        )
        assert "dtype float32->float16" in compilation.describe_delta(b, c)
        assert compilation.describe_delta(None, a) == "first compilation"

    def test_wrapper_counts_and_warns_after_warmup(self, fresh_telemetry):
        from tensorflow_examples_tpu.telemetry import compilation

        reg, _ = fresh_telemetry
        sentinel = compilation.CompilationSentinel(warmup=1)
        calls = []
        wrapped = sentinel.wrap(lambda x: calls.append(1) or x, "f")
        events = []
        sentinel.on_recompile = events.append
        x64, x32 = np.zeros((64,)), np.zeros((32,))
        wrapped(x64)
        wrapped(x64)  # cached signature: no new compile
        assert reg.counter("compile/count").value == 1
        assert not events
        sentinel.step = 7
        wrapped(x32)  # post-warmup recompile
        assert reg.counter("compile/count").value == 2
        assert reg.counter("compile/recompiles").value == 1
        assert len(events) == 1
        assert events[0]["step"] == 7 and events[0]["fn"] == "f"
        assert "axis 0: 64->32" in events[0]["delta"]
        wrapped(x32)  # now-known signature: quiet
        assert len(events) == 1
        assert len(calls) == 4  # every call reached the wrapped fn

    def test_wrapper_forwards_attributes(self):
        from tensorflow_examples_tpu.telemetry import compilation

        sentinel = compilation.CompilationSentinel()
        jitted = jax.jit(lambda x: x * 2)
        wrapped = sentinel.wrap(jitted, "g")
        # The AOT surface bench.py and the diag tools rely on:
        lowered = wrapped.lower(np.ones((4,), np.float32))
        assert lowered.compile() is not None
        assert sentinel.wrap(None, "absent") is None

    @pytest.mark.timeout(300)
    def test_post_warmup_shape_change_emits_one_warning_line(
        self, tmp_path, devices, fresh_telemetry
    ):
        """ISSUE 3 acceptance, one fit covering both device-side paths:
        a post-warmup batch-shape change triggers EXACTLY ONE
        compile_warning JSONL line naming the changed axis (the
        repeated new shape is then a known signature), while an in-loop
        profiler window ([2, 5)) captures a real device trace
        cross-linked from the final line."""
        import glob

        wd = str(tmp_path)
        cfg = tiny_cfg(
            workdir=wd, train_steps=8, log_every=4, checkpoint_every=0,
            eval_every=0, profile_start_step=2, profile_num_steps=3,
        )
        ds = _data()

        def data(start):
            base = train_iterator(ds, 64, seed=7, start_step=start)
            for i, batch in enumerate(base):
                if i + start >= 5:  # ragged from step 5 on
                    batch = {k: v[:32] for k, v in batch.items()}
                yield batch

        trainer = Trainer(mnist.make_task(cfg), cfg)
        trainer.fit(data)
        with open(sinks_mod.metrics_path(wd)) as f:
            lines = [json.loads(line) for line in f]
        warnings = [l for l in lines if l["kind"] == "compile_warning"]
        assert len(warnings) == 1, [l["kind"] for l in lines]
        line = warnings[0]
        assert schema.validate_line(line) == []
        comp = line["compile"]
        assert comp["fn"] == "train_step"
        assert "axis 0: 64->32" in comp["delta"]
        assert "'image'" in comp["delta"]
        final = lines[-1]
        assert final["counters"]["compile/count"] == 2
        assert final["counters"]["compile/recompiles"] == 1

        # ---- the profiler window, from the same run ----
        assert schema.validate_line(final) == []
        prof = final["profile"]
        assert prof["start_step"] == 2
        assert prof["num_steps"] == 3
        assert prof["dir"] == os.path.join(wd, "profile")
        assert prof["wall_secs"] > 0
        assert final["gauges"]["profile/steps"] == 3
        assert glob.glob(
            os.path.join(wd, "profile", "**", "*.xplane.pb"),
            recursive=True,
        ), "profiler window captured no device trace"
        with open(sinks_mod.trace_path(wd)) as f:
            names = {e["name"] for e in json.load(f)["traceEvents"]}
        assert "profile" in names  # the bracketing span


# ------------------------------------------------ memory accounting


class TestMemoryAccounting:
    def test_tree_bytes_concrete_and_abstract(self):
        import jax.numpy as jnp

        from tensorflow_examples_tpu.telemetry import memory as memory_mod

        tree = {
            "a": jnp.ones((4, 4), jnp.float32),
            "b": jnp.ones((2,), jnp.int32),
        }
        assert memory_mod.tree_bytes(tree) == 64 + 8
        abstract = jax.eval_shape(lambda: tree)
        assert memory_mod.tree_bytes(abstract) == 64 + 8

    def test_state_byte_breakdown(self):
        import jax.numpy as jnp
        import optax

        from tensorflow_examples_tpu.train.state import TrainState

        state = TrainState.create(
            apply_fn=None,
            params={"w": jnp.ones((10,), jnp.float32)},
            tx=optax.adam(1e-3),
        )
        sizes = state.byte_breakdown()
        assert sizes["params"] == 40
        assert sizes["opt_state"] >= 80  # adam mu + nu embed the params
        assert sizes["model_state"] == 0

    def test_is_oom_classification(self):
        from tensorflow_examples_tpu.telemetry import memory as memory_mod

        assert memory_mod.is_oom(
            RuntimeError("RESOURCE_EXHAUSTED: Out of memory allocating "
                         "1073741824 bytes")
        )
        assert memory_mod.is_oom(ValueError("allocation failure"))
        assert memory_mod.is_oom(RuntimeError("OOM when allocating"))
        assert not memory_mod.is_oom(ValueError("shape mismatch"))
        assert not memory_mod.is_oom(RuntimeError("in the classroom"))

    def test_monitor_watermark_and_forensics(self, fresh_telemetry):
        import jax.numpy as jnp

        from tensorflow_examples_tpu.telemetry import memory as memory_mod

        reg, _ = fresh_telemetry
        mon = memory_mod.MemoryMonitor(registry=reg)
        big = jnp.ones((256, 256), jnp.float32)  # 256 KiB resident
        live = mon.sample()
        assert live >= big.nbytes
        assert reg.gauge("memory/peak_live_bytes").value == live
        fields = mon.window_fields()
        assert fields["peak_live_bytes"] >= fields["live_bytes"] - 1
        report = mon.oom_report(top=3)
        assert "live arrays" in report and "MiB" in report
        assert "(256, 256)" in report  # the big array is named
        del big

    def test_oom_teardown_hook_logs_report(self, caplog, fresh_telemetry):
        from tensorflow_examples_tpu.telemetry import memory as memory_mod

        mon = memory_mod.MemoryMonitor()
        with caplog.at_level(
            logging.ERROR, logger="tensorflow_examples_tpu"
        ):
            assert memory_mod.maybe_log_oom_report(
                RuntimeError("RESOURCE_EXHAUSTED: out of memory"), mon
            )
            assert not memory_mod.maybe_log_oom_report(
                ValueError("unrelated"), mon
            )
            assert not memory_mod.maybe_log_oom_report(None, mon)
        dumps = [
            r.getMessage()
            for r in caplog.records
            if "OOM allocation forensics" in r.getMessage()
        ]
        assert len(dumps) == 1


# ------------------------------------------------- profiler windows


class TestProfilerWindow:
    def test_from_config_mappings(self):
        from tensorflow_examples_tpu.telemetry import profiling

        assert profiling.ProfilerWindow.from_config(tiny_cfg()) is None
        legacy = profiling.ProfilerWindow.from_config(
            tiny_cfg(profile=True)
        )
        assert (legacy.start_step, legacy.num_steps) == (10, 10)
        explicit = profiling.ProfilerWindow.from_config(
            tiny_cfg(profile_start_step=3, profile_num_steps=5,
                     workdir="/w")
        )
        assert (explicit.start_step, explicit.num_steps) == (3, 5)
        assert explicit.out_dir == os.path.join("/w", "profile")
        override = profiling.ProfilerWindow.from_config(
            tiny_cfg(profile_num_steps=2, profile_dir="/elsewhere")
        )
        assert override.out_dir == "/elsewhere"
        # The wired capture (real trace + final-line cross-link) is
        # asserted on the sentinel acceptance fit above — one shared
        # training run keeps the tier-1 budget flat.


if __name__ == "__main__":
    import sys

    sys.exit(pytest.main([__file__, "-q"]))
