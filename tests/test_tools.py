"""Tests for the harvest/stamp tooling (tools/*.py).

These scripts guard the round's on-chip evidence — a parsing or merge
bug silently loses or mislabels TPU records — so their contracts are
pinned here at the same level as the framework code (SURVEY.md §4
test strategy: every layer that can corrupt results gets direct unit
coverage).
"""

import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

from last_json_line import last_json_line  # noqa: E402


def _rec(bench, backend="tpu", value=1.0, **kw):
    r = {
        "metric": f"{bench}_metric", "bench": bench, "value": value,
        "unit": "u", "backend": backend, "window_values": [value],
        "fingerprint_tflops_pre": 100.0, "fingerprint_tflops_post": 110.0,
    }
    r.update(kw)
    return r


class TestLastJsonLine:
    def test_picks_last_parseable(self, tmp_path):
        p = tmp_path / "log"
        p.write_text(
            "noise\n"
            + json.dumps({"a": 1}) + "\n"
            + "{broken json\n"
            + json.dumps({"a": 2}) + "\n"
            + "trailing noise\n"
        )
        assert last_json_line(str(p)) == {"a": 2}

    def test_no_json_and_missing_file(self, tmp_path):
        p = tmp_path / "log"
        p.write_text("nothing here\n")
        assert last_json_line(str(p)) is None
        assert last_json_line(str(tmp_path / "absent")) is None

    def test_cli_requirements(self, tmp_path):
        log = tmp_path / "log"
        out = tmp_path / "out.json"
        log.write_text(json.dumps({"backend": "tpu", "v": 3}) + "\n")
        ok = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "last_json_line.py"),
             str(log), str(out), "backend=tpu"],
            capture_output=True,
        )
        assert ok.returncode == 0
        assert json.load(open(out))["v"] == 3
        bad = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "last_json_line.py"),
             str(log), str(out), "backend=cpu"],
            capture_output=True,
        )
        assert bad.returncode == 1


class TestHarvestMerge:
    def _merge(self, tmp_path, recs, selftest=None):
        d = tmp_path / "results"
        d.mkdir()
        for r in recs:
            (d / f"{r['bench']}.json").write_text(json.dumps(r))
        if selftest is not None:
            (d / "selftest.json").write_text(json.dumps(
                {"metric": "selftest", "selftest": selftest}
            ))
        p = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "harvest_merge.py"),
             str(d)],
            capture_output=True, text=True,
        )
        assert p.returncode == 0, p.stderr
        return json.loads(p.stdout), p.stderr

    def test_resnet50_heads_and_extras_ordered(self, tmp_path):
        out, _ = self._merge(
            tmp_path, [_rec("mnist"), _rec("resnet50"), _rec("gpt2")]
        )
        assert out["bench"] == "resnet50"
        assert [e["bench"] for e in out["extras"]] == ["gpt2", "mnist"]
        assert "resnet50" in out["harvested"]

    def test_minority_backend_dropped_loudly(self, tmp_path):
        out, err = self._merge(
            tmp_path,
            [_rec("resnet50"), _rec("gpt2"), _rec("mnist", backend="cpu")],
        )
        assert out["backend"] == "tpu"
        assert all(e["bench"] != "mnist" for e in out["extras"])
        assert "DROPPING mnist" in err

    def test_tpu_preferred_even_as_minority(self, tmp_path):
        out, _ = self._merge(
            tmp_path,
            [_rec("resnet50", backend="cpu"), _rec("gpt2", backend="cpu"),
             _rec("mnist", backend="tpu")],
        )
        assert out["backend"] == "tpu"
        assert out["bench"] == "mnist"

    def test_head_keeps_own_fingerprints_spread_is_window_wide(
        self, tmp_path
    ):
        recs = [
            _rec("resnet50", fingerprint_tflops_pre=500.0,
                 fingerprint_tflops_post=600.0),
            # A wedged post-probe: must reach the spread, not the head.
            _rec("moe", fingerprint_tflops_pre=450.0,
                 fingerprint_tflops_post=78.0),
        ]
        out, _ = self._merge(tmp_path, recs)
        assert out["fingerprint_tflops_pre"] == 500.0
        assert out["fingerprint_tflops_post"] == 600.0
        assert out["fingerprint_spread"] == [78.0, 600.0]

    def test_truncated_lists_missing_and_selftest_carried(self, tmp_path):
        st = {"ok": True, "summary": "9/9"}
        out, _ = self._merge(tmp_path, [_rec("resnet50")], selftest=st)
        assert out["selftest"] == st
        assert "gpt2" in out["truncated"]

    def test_nested_sweep_keys_stripped(self, tmp_path):
        out, _ = self._merge(
            tmp_path,
            [_rec("resnet50", tpu_harvest={"old": 1}, extras=[{"x": 1}],
                  harvested=["resnet50"])],
        )
        assert "tpu_harvest" not in out
        assert out["extras"] == []
        assert out["harvested"] == ["resnet50"]


class TestStampFloors:
    def _stamp(self, tmp_path, record):
        p = tmp_path / "merged.json"
        p.write_text(json.dumps(record))
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "stamp_floors.py"),
             str(p)],
            capture_output=True, text=True,
        )
        assert r.returncode == 0, r.stderr
        return r.stdout

    def test_per_record_fingerprints_and_unfloored_exclusion(self, tmp_path):
        head = _rec("resnet50", fingerprint_tflops_pre=500.0)
        head["rel_mfu"] = 0.08
        diag = _rec("decode_grid", fingerprint_tflops_pre=470.0)
        diag["metric"] = "decode_grid_step_time_ratio"
        other = _rec("gpt2", fingerprint_tflops_pre=480.0)
        head["extras"] = [other, diag]
        out = self._stamp(tmp_path, head)
        assert '"resnet50_metric": (1.0, 500.0),' in out
        assert '"gpt2_metric": (1.0, 480.0),' in out
        # The diagnostic must appear only as a comment, never a floor.
        assert '"decode_grid_step_time_ratio": (' not in out
        assert "deliberately unfloored" in out
        assert '"resnet50_metric": 0.08,' in out  # rel_mfu section

    def test_errored_metrics_flagged_not_stamped(self, tmp_path):
        head = _rec("resnet50", fingerprint_tflops_pre=500.0)
        head["extras"] = [{"metric": "bert_metric", "bench": "bert",
                           "error": "boom", "backend": "tpu"}]
        out = self._stamp(tmp_path, head)
        assert "ERRORED" in out
        assert "'bert'" in out or "bert" in out
        assert '"bert_metric": (' not in out


class TestStepFlops:
    """The bundled-FLOPs fallback (round 5): where a backend's
    lowering-only cost_analysis returns None, bundled benches must fall
    back to analysing the compiled bundled program at flops/K —
    otherwise the record silently loses rel_mfu."""

    @pytest.fixture()
    def trainer_and_stack(self):
        import bench
        from tensorflow_examples_tpu.data.memory import train_iterator
        from tensorflow_examples_tpu.data.sources import synthetic_images
        from tensorflow_examples_tpu.train.loop import Trainer
        from tensorflow_examples_tpu.workloads import mnist

        bench.BACKEND = "cpu"
        cfg = mnist.MnistConfig(
            global_batch_size=8, log_every=10**9, checkpoint_every=0,
            eval_every=0, train_steps=10**6, watchdog_secs=0,
        )
        tr = Trainer(mnist.make_task(cfg), cfg, mesh=bench._chip_mesh())
        ds = synthetic_images(n=64, shape=(28, 28, 1), num_classes=10, seed=0)
        it = train_iterator(ds, 8, seed=0)
        yield bench, tr, bench._bundle_prep(tr, it, 1, 4)[0]
        # last_mode is flops PROVENANCE for the bench record; a test
        # that exercised the fallback must not bank "compiled-bundled/k"
        # for whatever measures flops next in this process.
        bench._step_flops.last_mode = None

    def test_bundle_uses_lowering_when_available(self, trainer_and_stack):
        bench, tr, stack = trainer_and_stack
        f = bench._step_flops(tr, stack, bundle=4)
        assert f and f > 0
        assert bench._step_flops.last_mode == "lowered"

    def test_bundle_falls_back_to_compiled_bundled(self, trainer_and_stack):
        bench, tr, stack = trainer_and_stack

        class _NoCostLowered:  # a lowering with no cost model
            def cost_analysis(self):
                return None

        tr.__dict__["_train_step"] = type(
            "Stub", (), {"lower": lambda self, *a: _NoCostLowered()}
        )()
        f = bench._step_flops(tr, stack, bundle=4)
        assert f and f > 0
        assert bench._step_flops.last_mode == "compiled-bundled/k"
        # flops are PER STEP (the bundled program's total / k): one
        # bundled analysis must not report k-fold FLOPs.
        total = tr._build_bundled_step(4).lower(
            tr.state, stack
        ).compile().cost_analysis()
        total = total[0] if isinstance(total, (list, tuple)) else total
        assert abs(f * 4 - float(total.get("flops", 0.0))) / (f * 4) < 1e-6


class TestDiagCommon:
    def test_parse_budget(self):
        from diag_common import parse_budget

        assert parse_budget(["--budget=42.5"]) == 42.5
        assert parse_budget(["--other"], default=9.0) == 9.0

    def test_make_emit_last_line_wins(self, tmp_path, capsys):
        from diag_common import make_emit

        out = {"a": 1}
        emit = make_emit(out)
        emit(True)  # watchdog snapshot
        out["b"] = 2
        emit()  # main's full record
        lines = [
            json.loads(l) for l in capsys.readouterr().out.splitlines()
        ]
        assert lines[0] == {"a": 1, "truncated": True}
        assert lines[-1] == {"a": 1, "b": 2}
        # and the consumer contract picks the full record:
        p = tmp_path / "log"
        p.write_text("\n".join(json.dumps(l) for l in lines) + "\n")
        assert last_json_line(str(p)) == {"a": 1, "b": 2}

    def test_watchdog_emits_then_cancel_suppresses(self, capsys):
        import time as _time

        from diag_common import make_emit, start_watchdog

        t = start_watchdog(5.0, make_emit({"x": 1}))  # floor: fires at 5s...
        t.cancel()  # ...unless cancelled first
        _time.sleep(0.1)
        assert capsys.readouterr().out == ""


class TestFlashTuneSweep:
    def test_sweep_shape_interpret_cells_and_best(self):
        """Sweep mechanics on a tiny interpret-mode shape: legal cells
        only, best_* selected by min, deadline truncation honored."""
        import time as _time

        import flash_tune

        rec = flash_tune._sweep_shape(
            "tiny", 1, 1, 128, 8, True, 1, _time.monotonic() + 600
        )
        # seq 128 admits only the (128, 128) cell out of BLOCKS^2.
        assert [c["block_q"] for c in rec["cells"]] == [128]
        assert rec["best_fwd"] == rec["cells"][0]
        assert rec["best_fwdbwd"] == rec["cells"][0]
        assert "truncated" not in rec

    def test_sweep_shape_deadline_truncates(self):
        import time as _time

        import flash_tune

        rec = flash_tune._sweep_shape(
            "tiny", 1, 1, 128, 8, True, 1, _time.monotonic() - 1.0
        )
        assert rec["truncated"] is True
        assert rec["cells"] == []
        assert "best_fwd" not in rec


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-q"]))


class TestApplyFloors:
    """tools/apply_floors.py: mechanical floor restamps must be
    line-scoped (comments and unstamped metrics byte-identical) and
    refuse partial/no-op stamps unless told otherwise."""

    SRC = (
        "FLOORS = {\n"
        '    "tpu": {\n'
        "        # provenance comment stays\n"
        '        "m_a": (1.0, 10.0),  # inline note stays\n'
        '        "m_b": (2.0, 20.0),\n'
        "    },\n"
        '    "cpu": {\n'
        '        "m_a": (9.0, 0.1),\n'
        "    },\n"
        "}\n"
        "REL_MFU_FLOORS: dict = {\n"
        '    "tpu": {\n'
        '        "m_a": 0.5,\n'
        "    },\n"
        '    "cpu": {},\n'
        "}\n"
    )

    def _mod(self):
        import apply_floors

        return apply_floors

    def test_line_scoped_rewrite_preserves_comments(self):
        af = self._mod()
        out = af._rewrite(self.SRC, "FLOORS", "tpu", {"m_a": "(3.0, 30.0)"})
        assert '"m_a": (3.0, 30.0),  # inline note stays' in out
        assert '"m_b": (2.0, 20.0),' in out  # untouched
        assert '"m_a": (9.0, 0.1),' in out  # cpu block untouched
        assert "# provenance comment stays" in out

    def test_new_metric_appended_to_backend_block(self):
        af = self._mod()
        out = af._rewrite(self.SRC, "FLOORS", "tpu", {"m_new": "(7.0, 70.0)"})
        tpu_block = out.split('"cpu": {')[0]
        assert '"m_new": (7.0, 70.0),  # first floor' in tpu_block

    def test_missing_backend_refused(self):
        af = self._mod()
        with pytest.raises(SystemExit):
            af._rewrite(self.SRC, "FLOORS", "gpu", {"m_a": "(3.0, 30.0)"})

    def test_wrapped_entry_refused_not_duplicated(self):
        # A formatter-wrapped entry no longer matches the one-line
        # regex; appending would leave a duplicate dict key (ADVICE
        # r4) — the rewrite must refuse instead.
        af = self._mod()
        src = self.SRC.replace(
            '"m_b": (2.0, 20.0),',
            '"m_b": (\n            2.0, 20.0),',
        )
        with pytest.raises(SystemExit, match="m_b"):
            af._rewrite(src, "FLOORS", "tpu", {"m_b": "(5.0, 50.0)"})

    def test_bundle_protocol_stamped_with_floor(
        self, tmp_path, monkeypatch, capsys
    ):
        """A restamp carries the record's launch protocol into
        FLOOR_BUNDLES (dry-run against the real bench.py — the floors
        policy says protocol moves WITH the floor)."""
        af = self._mod()
        # bundle=4 differs from bench.py's current stamp (8) on purpose:
        # the assertion needs the rewrite to CHANGE the line, or it
        # cannot appear in the dry-run diff at all.
        rec = {
            "backend": "tpu",
            "metric": "bert_base_examples_per_sec_per_chip",
            "bench": "bert", "value": 25000.0,
            "fingerprint_tflops_pre": 50000.0, "bundle": 4,
        }
        p = tmp_path / "r.json"
        p.write_text(json.dumps(rec))
        monkeypatch.setattr(
            sys, "argv", ["apply_floors.py", str(p), "--dry-run"]
        )
        monkeypatch.chdir(REPO)
        assert af.main() == 0
        diff = capsys.readouterr().out
        assert '"bert_base_examples_per_sec_per_chip": (25000.0, 50000.0),' in diff
        assert '"bert_base_examples_per_sec_per_chip": 4,' in diff

    def test_truncated_record_needs_partial_flag(self, tmp_path, monkeypatch, capsys):
        af = self._mod()
        rec = {"backend": "tpu", "metric": "m_a", "value": 3.0,
               "fingerprint_tflops_pre": 30.0, "truncated": ["m_b"]}
        p = tmp_path / "r.json"
        p.write_text(json.dumps(rec))
        monkeypatch.setattr(sys, "argv", ["apply_floors.py", str(p)])
        monkeypatch.chdir(REPO)
        assert af.main() == 1
        assert "pass --partial" in capsys.readouterr().out


class TestKernelSourceHash:
    def test_changes_with_ops_content_and_layout(self, tmp_path):
        from kernel_source_hash import kernel_source_hash

        root = tmp_path / "repo"
        ops = root / "tensorflow_examples_tpu" / "ops"
        tt = root / "tests_tpu"
        ops.mkdir(parents=True)
        tt.mkdir()
        (ops / "k.py").write_text("a = 1\n")
        (tt / "t.py").write_text("b = 2\n")
        h0 = kernel_source_hash(str(root))
        assert h0 == kernel_source_hash(str(root))  # deterministic
        (ops / "k.py").write_text("a = 3\n")
        h1 = kernel_source_hash(str(root))
        assert h1 != h0  # content edit
        (ops / "k.py").rename(ops / "k2.py")
        assert kernel_source_hash(str(root)) != h1  # rename counts too

    def test_repo_hash_is_stable_here(self):
        from kernel_source_hash import kernel_source_hash

        assert kernel_source_hash() == kernel_source_hash()


class TestTelemetryReport:
    """tools/telemetry_report.py smoke (ISSUE 2 satellite): a run dir's
    JSONL + trace turn into the human summary and the machine record."""

    def _run_dir(self, tmp_path):
        """Handcraft a schema-valid run dir (no training needed)."""
        tdir = tmp_path / "telemetry"
        tdir.mkdir()
        base = {
            "schema_version": 1, "session_start_unix": 99.0, "gauges": {
                "telemetry/flops_per_step": 1e9,
                "telemetry/peak_flops_total": 1e12,
            },
        }
        lines = [
            dict(base, kind="window", step=10, time_unix=100.0,
                 metrics={"train/loss": 2.0},
                 counters={"train/steps_total": 10,
                           "data/batches_fetched": 10},
                 derived={"examples_per_sec": 640.0,
                          "tokens_per_sec": None,
                          "step_time_p50": 0.010, "step_time_p95": 0.020,
                          "mfu": 0.01, "goodput": 1.0}),
            dict(base, kind="window", step=20, time_unix=101.0,
                 metrics={"train/loss": 1.0},
                 counters={"train/steps_total": 20,
                           "data/batches_fetched": 20,
                           "resilience/bad_steps": 2},
                 derived={"examples_per_sec": 660.0,
                          "tokens_per_sec": None,
                          "step_time_p50": 0.011, "step_time_p95": 0.021,
                          "mfu": 0.011, "goodput": 0.9}),
            dict(base, kind="final", step=20, time_unix=101.5, metrics={},
                 counters={"train/steps_total": 20,
                           "data/batches_fetched": 20,
                           "resilience/bad_steps": 2,
                           "checkpoint/saves": 1},
                 derived={"examples_per_sec": None, "tokens_per_sec": None,
                          "step_time_p50": 0.011, "step_time_p95": 0.021,
                          "mfu": None, "goodput": 0.9},
                 exit_reason="complete"),
        ]
        with open(tdir / "metrics.jsonl", "w") as f:
            f.write("\n".join(json.dumps(l) for l in lines) + "\n")
            f.write("{torn tail never valid json\n")  # must be skipped
        with open(tdir / "trace.json", "w") as f:
            json.dump({"traceEvents": [
                {"name": "device_step", "ph": "X", "ts": 0.0, "dur": 9000.0,
                 "pid": 0, "tid": 0},
                {"name": "data_fetch", "ph": "X", "ts": 0.0, "dur": 1000.0,
                 "pid": 0, "tid": 0},
            ]}, f)
        return tmp_path

    def test_summary_and_json_record(self, tmp_path, capsys):
        import telemetry_report

        wd = self._run_dir(tmp_path)
        out_json = tmp_path / "report.json"
        rc = telemetry_report.main([str(wd), "--json", str(out_json)])
        stdout = capsys.readouterr().out
        assert rc == 0, stdout
        # The acceptance quartet, human-readable:
        assert "examples/sec" in stdout
        assert "p50" in stdout and "p95" in stdout
        assert "mfu estimate" in stdout
        assert "goodput: 90.00%" in stdout
        assert "ended: complete" in stdout
        assert "skipped 1 line" in stdout  # torn tail counted loudly
        assert "device_step" in stdout  # trace phase breakdown
        rec = json.load(open(out_json))
        assert rec["examples_per_sec_last"] == 660.0
        assert rec["examples_per_sec_mean"] == 650.0
        assert rec["step_time_p50"] == 0.011
        assert rec["mfu"] == 0.011
        assert rec["goodput"] == 0.9
        assert rec["exit_reason"] == "complete"
        assert rec["trace_phases"]["device_step"]["total_ms"] == 9.0

    def test_missing_run_dir_exits_1(self, tmp_path, capsys):
        import telemetry_report

        assert telemetry_report.main([str(tmp_path / "nope")]) == 1
        assert "no telemetry found" in capsys.readouterr().err

    def test_preempt_resume_sessions_aggregated(self, tmp_path, capsys):
        """Counters are cumulative PER PROCESS: a preempted-then-resumed
        run's report must sum the sessions, not read only the last
        line (which would hide session 1's preemption entirely)."""
        import telemetry_report

        tdir = tmp_path / "telemetry"
        tdir.mkdir()
        base = {"schema_version": 1, "gauges": {}, "metrics": {},
                "derived": {"examples_per_sec": None,
                            "tokens_per_sec": None, "step_time_p50": 0.01,
                            "step_time_p95": 0.02, "mfu": None,
                            "goodput": None}}
        lines = [
            # session 1: preempted at step 50, 2 bad steps
            dict(base, kind="final", step=50, time_unix=100.0,
                 session_start_unix=90.0,
                 counters={"train/steps_total": 50,
                           "resilience/bad_steps": 2,
                           "resilience/preemptions": 1},
                 exit_reason="preempt"),
            # session 2: fresh process, counters restart, completes
            dict(base, kind="final", step=100, time_unix=200.0,
                 session_start_unix=190.0,
                 counters={"train/steps_total": 50,
                           "checkpoint/restores": 1},
                 exit_reason="complete"),
        ]
        with open(tdir / "metrics.jsonl", "w") as f:
            f.write("\n".join(json.dumps(l) for l in lines) + "\n")
        assert telemetry_report.main([str(tmp_path), "--json", "-"]) == 0
        out = capsys.readouterr().out
        rec = json.loads(out[out.index("{"):])  # summary carries no braces
        assert rec["sessions"] == 2
        assert rec["counters"]["train/steps_total"] == 100
        assert rec["counters"]["resilience/preemptions"] == 1
        assert rec["counters"]["resilience/bad_steps"] == 2
        assert rec["goodput"] == pytest.approx(0.98)  # 98/100 across both
        assert "in 2 session(s)" in out
        assert "preemptions=1" in out


class TestTelemetryReportShards:
    """ISSUE 4 satellite: a run dir holding per-host telemetry shards
    reports per-host figures and flags the slowest host; single-shard
    dirs keep the exact pre-fleet behavior (pinned above)."""

    def _line(self, host, step, *, p50, p95, kind="window", **over):
        line = {
            "schema_version": 3, "kind": kind, "host": host, "step": step,
            "time_unix": 100.0 + step, "session_start_unix": 99.0,
            "metrics": {"train/loss": 2.0}, "gauges": {},
            "counters": {"train/steps_total": step},
            "derived": {"examples_per_sec": 640.0, "tokens_per_sec": None,
                        "step_time_p50": p50, "step_time_p95": p95,
                        "mfu": 0.01, "goodput": 1.0},
        }
        line.update(over)
        return line

    def _fleet_dir(self, tmp_path):
        """The REAL multi-host layout: process 0's stream is
        metrics.jsonl (no host-0 shard — sinks.make_sinks writes none),
        hosts k>0 each have telemetry.host{k}.jsonl."""
        tdir = tmp_path / "telemetry"
        tdir.mkdir()
        fleet = {
            "hosts": [
                {"host": 0, "step_time_p50": 0.01, "step_time_p95": 0.011,
                 "data_fetch_p95": 0.001, "steps_lost": 0,
                 "peak_live_bytes": 1024, "io_retries": 0,
                 "batches_skipped": 0},
                {"host": 1, "step_time_p50": 0.04, "step_time_p95": 0.05,
                 "data_fetch_p95": 0.045, "steps_lost": 0,
                 "peak_live_bytes": 1024, "io_retries": 7,
                 "batches_skipped": 0},
            ],
            "slowest_host": 1, "skew": 4.5, "side": "input",
            "straggler": True,
        }
        main_lines = [
            self._line(0, 10, p50=0.01, p95=0.011),
            self._line(0, 10, p50=0.01, p95=0.011, kind="fleet",
                       fleet=fleet),
            self._line(0, 20, p50=0.01, p95=0.011, kind="final",
                       metrics={}, exit_reason="complete"),
        ]
        shard1 = [
            self._line(1, 10, p50=0.04, p95=0.05),
            self._line(1, 20, p50=0.04, p95=0.05, kind="final",
                       metrics={}, exit_reason="complete",
                       counters={"train/steps_total": 20,
                                 "resilience/steps_lost": 2}),
        ]
        with open(tdir / "metrics.jsonl", "w") as f:
            f.write("\n".join(json.dumps(l) for l in main_lines) + "\n")
        with open(tdir / "telemetry.host1.jsonl", "w") as f:
            f.write("\n".join(json.dumps(l) for l in shard1) + "\n")
        return tmp_path

    def test_shards_merged_and_slowest_flagged(self, tmp_path, capsys):
        import telemetry_report

        wd = self._fleet_dir(tmp_path)
        rc = telemetry_report.main([str(wd), "--json", "-"])
        out = capsys.readouterr().out
        assert rc == 0, out
        assert "fleet: 2 host shard(s); SLOWEST host 1" in out
        assert "host 0:" in out and "host 1:" in out
        assert "<- SLOWEST" in out
        assert "fleet skew (last fleet line): 4.50x" in out
        assert "slowest host 1, input-side" in out
        assert "STRAGGLER flagged in 1 window(s)" in out
        rec = json.loads(out[out.index("{"):])
        assert [h["host"] for h in rec["hosts"]] == [0, 1]
        assert rec["slowest_host"] == 1
        assert rec["hosts"][1]["step_time_p95"] == 0.05
        assert rec["hosts"][1]["steps_lost"] == 2
        assert rec["fleet"]["side"] == "input"
        assert rec["fleet_straggler_windows"] == 1

    def test_single_shard_dir_unchanged(self, tmp_path, capsys):
        """No host shards -> no fleet table, hosts is null (the summary
        and record shape of a pre-ISSUE-4 run dir)."""
        import telemetry_report

        tdir = tmp_path / "telemetry"
        tdir.mkdir()
        with open(tdir / "metrics.jsonl", "w") as f:
            f.write(json.dumps(self._line(0, 10, p50=0.01, p95=0.02)) + "\n")
        rc = telemetry_report.main([str(tmp_path), "--json", "-"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "host shard" not in out
        rec = json.loads(out[out.index("{"):])
        assert rec["hosts"] is None
        assert rec["slowest_host"] is None

    def test_shards_only_dir_still_reports(self, tmp_path, capsys):
        """A dir with ONLY host shards (host 0's record lost) reports
        from the lowest shard instead of erroring."""
        import telemetry_report

        tdir = tmp_path / "telemetry"
        tdir.mkdir()
        with open(tdir / "telemetry.host1.jsonl", "w") as f:
            f.write(
                json.dumps(self._line(1, 10, p50=0.01, p95=0.02)) + "\n"
            )
        rc = telemetry_report.main([str(tmp_path)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "fleet: 1 host shard(s)" in out


class TestRunDiff:
    """tools/run_diff.py (ISSUE 4 tentpole (3)): regression attribution
    between two run dirs, ranked, machine-consumable by bench_gate."""

    def _dir(self, root, name, *, p50=0.010, p95=0.020, mfu=0.010,
             eps=640.0, device_ms=9000.0, fetch_ms=1000.0):
        tdir = root / name / "telemetry"
        tdir.mkdir(parents=True)
        base = {
            "schema_version": 1, "session_start_unix": 99.0, "gauges": {},
        }
        lines = [
            dict(base, kind="window", step=10, time_unix=100.0,
                 metrics={"train/loss": 2.0},
                 counters={"train/steps_total": 10},
                 derived={"examples_per_sec": eps, "tokens_per_sec": None,
                          "step_time_p50": p50, "step_time_p95": p95,
                          "mfu": mfu, "goodput": 1.0}),
            dict(base, kind="final", step=10, time_unix=101.0, metrics={},
                 counters={"train/steps_total": 10},
                 derived={"examples_per_sec": None, "tokens_per_sec": None,
                          "step_time_p50": p50, "step_time_p95": p95,
                          "mfu": None, "goodput": 1.0},
                 exit_reason="complete"),
        ]
        with open(tdir / "metrics.jsonl", "w") as f:
            f.write("\n".join(json.dumps(l) for l in lines) + "\n")
        with open(tdir / "trace.json", "w") as f:
            json.dump({"traceEvents": [
                {"name": "device_step", "ph": "X", "ts": 0.0,
                 "dur": device_ms * 1e3, "pid": 0, "tid": 0},
                {"name": "data_fetch", "ph": "X", "ts": 0.0,
                 "dur": fetch_ms * 1e3, "pid": 0, "tid": 0},
            ]}, f)
        return str(root / name)

    def test_injected_regression_ranked_first(self, tmp_path, capsys):
        """ISSUE 4 acceptance: the injected step-time regression is the
        top-ranked finding."""
        import run_diff

        a = self._dir(tmp_path, "a")
        b = self._dir(tmp_path, "b", p50=0.013, p95=0.027)  # +30/+35%
        out_json = tmp_path / "diff.json"
        rc = run_diff.main([a, b, "--json", str(out_json)])
        out = capsys.readouterr().out
        assert rc == 0, out
        doc = json.load(open(out_json))
        assert doc["regressions"] == 2
        assert doc["ranked"][0]["metric"] == "step_time_p95"  # largest
        assert doc["ranked"][1]["metric"] == "step_time_p50"
        assert doc["ranked"][0]["verdict"] == "regressed"
        first = out.index("REGRESSED step_time_p95")
        assert first < out.index("REGRESSED step_time_p50")
        # unchanged metrics rank after, improvements would sit between
        assert out.index("unchanged goodput") > first

    def test_improvement_and_span_attribution(self, tmp_path, capsys):
        import run_diff

        a = self._dir(tmp_path, "a")
        b = self._dir(tmp_path, "b", mfu=0.02, device_ms=13500.0)
        rc = run_diff.main([a, b, "--json", "-"])
        out = capsys.readouterr().out
        assert rc == 0
        doc = json.loads(out[out.index('{\n'):])
        by_metric = {d["metric"]: d for d in doc["ranked"]}
        assert by_metric["mfu"]["verdict"] == "improved"
        span = by_metric["span/device_step_total_ms"]
        assert span["verdict"] == "regressed"
        assert span["rel_change"] == pytest.approx(0.5)
        assert doc["ranked"][0]["metric"] == "span/device_step_total_ms"

    def test_self_compare_is_clean(self, tmp_path, capsys):
        import run_diff

        a = self._dir(tmp_path, "a")
        rc = run_diff.main([a, a, "--fail-on-regression"])
        out = capsys.readouterr().out
        assert rc == 0, out
        assert "0 regressed" in out
        assert "REGRESSED" not in out

    def test_fail_on_regression_exit_code(self, tmp_path, capsys):
        import run_diff

        a = self._dir(tmp_path, "a")
        b = self._dir(tmp_path, "b", p50=0.02)
        assert run_diff.main([a, b]) == 0  # report-only by default
        assert run_diff.main([a, b, "--fail-on-regression"]) == 1

    def test_missing_run_exits_2(self, tmp_path, capsys):
        import run_diff

        a = self._dir(tmp_path, "a")
        assert run_diff.main([a, str(tmp_path / "nope")]) == 2
        assert "run_b" in capsys.readouterr().err

    def test_zero_baseline_stays_valid_json(self, tmp_path, capsys):
        """recompiles 0 -> 2 has no finite ratio; the doc must still be
        strict-parseable JSON (no bare Infinity) and rank the jump
        first."""
        import run_diff

        base = {"windows": 1, "counters": {}, "first_step": 0,
                "last_step": 10, "exit_reason": "complete",
                "recompiles": 0, "step_time_p50": 0.01}
        a = tmp_path / "a.json"
        b = tmp_path / "b.json"
        a.write_text(json.dumps(base))
        b.write_text(json.dumps(dict(base, recompiles=2)))
        out_json = tmp_path / "diff.json"
        assert run_diff.main(
            [str(a), str(b), "--json", str(out_json)]
        ) == 0
        out = capsys.readouterr().out
        assert "REGRESSED recompiles" in out and "0->new" in out
        raw = out_json.read_text()
        assert "Infinity" not in raw
        doc = json.loads(raw)  # strict parse succeeds
        assert doc["ranked"][0]["metric"] == "recompiles"
        assert doc["ranked"][0]["rel_change"] is None
        assert doc["regressions"] == 1

    def test_absent_fields_not_compared(self, tmp_path, capsys):
        """v1 records (no memory watermark) list the field as not
        comparable instead of inventing a delta."""
        import run_diff

        a = self._dir(tmp_path, "a")
        rec = {"windows": 1, "counters": {}, "step_time_p50": 0.01,
               "step_time_p95": 0.02, "examples_per_sec_mean": 640.0,
               "mfu": 0.01, "goodput": 1.0, "peak_live_bytes": 4096,
               "first_step": 0, "last_step": 10, "exit_reason": "complete"}
        b = tmp_path / "b_report.json"
        b.write_text(json.dumps(rec))
        rc = run_diff.main([a, str(b)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "not comparable: peak_live_bytes: absent in A" in out

    def test_json_feeds_bench_gate_record_mode(self, tmp_path, capsys):
        """The --json doc is directly gateable: stamp floors from run
        A's report, then bench_gate --record the A-vs-B diff doc — the
        regressed candidate fails the gate."""
        import bench_gate
        import run_diff
        import telemetry_report

        a = self._dir(tmp_path, "a")
        b = self._dir(tmp_path, "b", p50=0.013, p95=0.027)
        report_a = tmp_path / "report_a.json"
        assert telemetry_report.main([a, "--json", str(report_a)]) == 0
        floors = tmp_path / "floors.json"
        assert bench_gate.main(
            ["--stamp", str(report_a), "--floors", str(floors)]
        ) == 0
        diff_json = tmp_path / "diff.json"
        assert run_diff.main([a, b, "--json", str(diff_json)]) == 0
        assert bench_gate.main(
            ["--record", str(diff_json), "--floors", str(floors)]
        ) == 1
        out = capsys.readouterr().out
        assert "[FAIL] step_time_p50" in out
        # and the self-compare diff doc passes the same gate
        self_json = tmp_path / "self.json"
        assert run_diff.main([a, a, "--json", str(self_json)]) == 0
        assert bench_gate.main(
            ["--record", str(self_json), "--floors", str(floors)]
        ) == 0

    def test_serving_records_rank_serving_regressions_first(
        self, tmp_path, capsys
    ):
        """ISSUE 8 satellite: run_diff consumes serving bench records
        (the router's canary per-set docs) and ranks TTFT/TPOT/
        prefix-hit regressions first — the canary-compare path."""
        import run_diff

        base = {
            "bench": "serve_router_set", "ttft_p95_ms": 50.0,
            "tpot_p95_ms": 10.0, "req_per_s": 40.0,
            "tok_per_s": 300.0, "prefix_hit_rate": 0.25,
        }
        canary = dict(base, ttft_p95_ms=100.0, prefix_hit_rate=0.05,
                      tok_per_s=310.0)
        a, b = tmp_path / "base.json", tmp_path / "canary.json"
        a.write_text(json.dumps(base))
        b.write_text(json.dumps(canary))
        rc = run_diff.main(
            [str(a), str(b), "--fail-on-regression"]
        )
        out = capsys.readouterr().out
        assert rc == 1  # the canary regressed; compare says so
        lines = [l for l in out.splitlines() if "REGRESSED" in l]
        # Both serving regressions found, largest relative change
        # first (2x TTFT = +100% outranks the -80% hit-rate loss),
        # improvements after.
        assert len(lines) == 2
        assert "ttft_p95_ms" in lines[0]
        assert "prefix_hit_rate" in lines[1]
        assert "improved " in out and "tok_per_s" in out


def test_ci_perf_gates_run_in_tier1(tmp_path):
    """ISSUE 4 CI satellite, at the subprocess level the CI would use:
    bench_gate trajectory mode over the banked BENCH_r0*.json rounds
    AND a run_diff --json self-compare both exit 0 — a perf-record or
    report schema break fails the tier-1 pass instead of silently
    rotting. (Fast: both are pure-JSON CPU paths.)"""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    files = sorted(
        os.path.join(REPO, f)
        for f in os.listdir(REPO)
        if re.fullmatch(r"BENCH_r\d+\.json", f)
    )
    assert files, "no banked BENCH_*.json trajectory in the repo"
    gate = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "bench_gate.py"),
         *files],
        capture_output=True, text=True, timeout=120, env=env,
    )
    assert gate.returncode == 0, gate.stdout + gate.stderr
    assert "0 regressed" in gate.stdout

    # run_diff self-compare: a run dir diffed against itself is clean.
    run = TestRunDiff()._dir(tmp_path, "self")
    diff = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "run_diff.py"),
         run, run, "--json", "-", "--fail-on-regression"],
        capture_output=True, text=True, timeout=120, env=env,
    )
    assert diff.returncode == 0, diff.stdout + diff.stderr
    assert "0 regressed" in diff.stdout
    doc = json.loads(diff.stdout[diff.stdout.index('{\n'):])
    assert doc["regressions"] == 0


class TestBenchGate:
    """tools/bench_gate.py (ISSUE 3 tentpole (4)): the CI perf gate must
    pass on the committed BENCH_r0*.json trajectory and fail on a
    synthetic regression — in both its trajectory and telemetry-record
    modes."""

    def _gate(self, argv):
        import bench_gate

        return bench_gate.main(argv)

    def test_banked_trajectory_passes(self, capsys):
        files = sorted(
            os.path.join(REPO, f)
            for f in os.listdir(REPO)
            if re.fullmatch(r"BENCH_r\d+\.json", f)
        )
        assert files, "no banked BENCH_*.json trajectory in the repo"
        rc = self._gate(files)
        out = capsys.readouterr().out
        assert rc == 0, out
        assert "0 regressed" in out
        assert "[PASS]" in out  # the gate actually compared something
        # Off-rig rounds are skipped under the floors policy, loudly.
        assert "comparability window" in out

    def test_synthetic_step_time_regression_fails(self, tmp_path, capsys):
        """ISSUE 3 acceptance: a 20% step-time regression (on a
        comparable rig fingerprint) exits non-zero."""
        import bench

        floor, fp = bench.FLOORS["tpu"]["mnist_mlp_step_time"]
        rec = {
            "backend": "tpu",
            "metric": "mnist_mlp_step_time",
            "value": floor * 1.2,
            "fingerprint_tflops_pre": fp,
        }
        p = tmp_path / "regressed.json"
        p.write_text(json.dumps(rec))
        rc = self._gate([str(p)])
        out = capsys.readouterr().out
        assert rc == 1
        assert "[FAIL] mnist_mlp_step_time" in out

    def test_off_rig_regression_skipped_not_failed(self, tmp_path, capsys):
        import bench

        floor, fp = bench.FLOORS["tpu"]["gpt2_124m_tokens_per_sec"]
        rec = {
            "backend": "tpu",
            "metric": "gpt2_124m_tokens_per_sec",
            "value": floor * 0.5,  # would regress...
            "fingerprint_tflops_pre": fp * 10,  # ...but on another rig
        }
        p = tmp_path / "offrig.json"
        p.write_text(json.dumps(rec))
        assert self._gate([str(p)]) == 0
        assert "comparability window" in capsys.readouterr().out

    def test_empty_gate_is_an_error(self, tmp_path, capsys):
        p = tmp_path / "nothing.json"
        p.write_text(json.dumps({"rc": 1, "tail": "no records here"}))
        assert self._gate([str(p)]) == 2

    def _record(self, tmp_path, **over):
        rec = {
            "step_time_p50": 0.010,
            "step_time_p95": 0.020,
            "mfu": 0.010,
            "goodput": 1.0,
            "peak_live_bytes": 1_000_000,
            "examples_per_sec_mean": 640.0,
        }
        rec.update(over)
        p = tmp_path / "report.json"
        p.write_text(json.dumps(rec))
        return p

    def test_stamp_then_gate_record(self, tmp_path, capsys):
        good = self._record(tmp_path)
        floors = tmp_path / "floors.json"
        assert self._gate(
            ["--stamp", str(good), "--floors", str(floors)]
        ) == 0
        assert self._gate(
            ["--record", str(good), "--floors", str(floors)]
        ) == 0
        # 20% step-time regression beyond the 10% threshold: fail.
        bad = self._record(tmp_path, step_time_p50=0.012)
        assert self._gate(
            ["--record", str(bad), "--floors", str(floors)]
        ) == 1
        out = capsys.readouterr().out
        assert "[FAIL] step_time_p50" in out
        # memory blow-up beyond threshold: fail too.
        bad = self._record(tmp_path, peak_live_bytes=2_000_000)
        assert self._gate(
            ["--record", str(bad), "--floors", str(floors)]
        ) == 1

    def test_v1_record_missing_fields_skip_gracefully(
        self, tmp_path, capsys
    ):
        """A schema-v1 run's record (no peak_live_bytes) skips the
        memory floor instead of failing it."""
        good = self._record(tmp_path)
        floors = tmp_path / "floors.json"
        self._gate(["--stamp", str(good), "--floors", str(floors)])
        v1 = self._record(tmp_path, peak_live_bytes=None)
        assert self._gate(
            ["--record", str(v1), "--floors", str(floors)]
        ) == 0
        out = capsys.readouterr().out
        assert "[SKIP] peak_live_bytes: absent from record" in out

    def _serve_record(self, tmp_path, name="serve.json", **over):
        rec = {
            "bench": "serve_router",
            "ttft_p50_ms": 30.0,
            "ttft_p95_ms": 60.0,
            "tpot_p50_ms": 8.0,
            "tpot_p95_ms": 14.0,
            "e2e_p95_ms": 150.0,
            "req_per_s": 40.0,
            "tok_per_s": 320.0,
            "prefix_hit_rate": 0.2,
            "post_warmup_recompiles": 0,
        }
        rec.update(over)
        p = tmp_path / name
        p.write_text(json.dumps(rec))
        return p

    def test_serve_router_record_stamps_and_gates(self, tmp_path, capsys):
        """ISSUE 8 satellite: bench_gate accepts the serve_router
        record keys — latency maxima, throughput/prefix-hit minima,
        recompiles pinned — in both --stamp and --record modes."""
        good = self._serve_record(tmp_path)
        floors = tmp_path / "serve_floors.json"
        assert self._gate(
            ["--stamp", str(good), "--floors", str(floors)]
        ) == 0
        with open(floors) as f:
            stamped = json.load(f)
        assert stamped["ttft_p95_ms"] == {"max": 60.0}
        assert stamped["tok_per_s"] == {"min": 320.0}
        assert stamped["prefix_hit_rate"] == {"min": 0.2}
        assert self._gate(
            ["--record", str(good), "--floors", str(floors)]
        ) == 0
        # A 2x TTFT regression fails; so does a prefix-cache collapse.
        bad = self._serve_record(
            tmp_path, "bad.json", ttft_p95_ms=120.0
        )
        assert self._gate(
            ["--record", str(bad), "--floors", str(floors)]
        ) == 1
        assert "[FAIL] ttft_p95_ms" in capsys.readouterr().out
        bad = self._serve_record(
            tmp_path, "bad2.json", prefix_hit_rate=0.0
        )
        assert self._gate(
            ["--record", str(bad), "--floors", str(floors)]
        ) == 1

    def test_chaos_error_rate_gated_at_zero(self, tmp_path, capsys):
        """ISSUE 10 satellite: the serve_chaos availability record
        gates ``error_rate`` with a max of 0 — the threshold slack
        multiplies the zero bound into zero, so ONE failed request
        under the replica kill regresses the gate. ``p95_vs_baseline``
        gates as a declared-multiple maximum."""
        rec = {
            "bench": "serve_chaos",
            "error_rate": 0.0,
            "p95_vs_baseline": 3.0,
            "failover_count": 2,
        }
        good = tmp_path / "chaos.json"
        good.write_text(json.dumps(rec))
        floors = tmp_path / "chaos_floors.json"
        assert self._gate(
            ["--stamp", str(good), "--floors", str(floors)]
        ) == 0
        with open(floors) as f:
            stamped = json.load(f)
        assert stamped["error_rate"] == {"max": 0.0}
        assert stamped["p95_vs_baseline"] == {"max": 3.0}
        assert self._gate(
            ["--record", str(good), "--floors", str(floors)]
        ) == 0
        bad = tmp_path / "chaos_bad.json"
        bad.write_text(json.dumps(dict(rec, error_rate=0.05)))
        assert self._gate(
            ["--record", str(bad), "--floors", str(floors)]
        ) == 1
        assert "[FAIL] error_rate" in capsys.readouterr().out
        worse = tmp_path / "chaos_worse.json"
        worse.write_text(json.dumps(dict(rec, p95_vs_baseline=9.0)))
        assert self._gate(
            ["--record", str(worse), "--floors", str(floors)]
        ) == 1

    def test_spec_speedup_stamps_and_gates(self, tmp_path, capsys):
        """ISSUE 11 satellite: the serve_spec record's tpot_speedup
        gates as a stamped MINIMUM — a drafter/verify regression that
        quietly eats the speedup fails like any other perf loss."""
        rec = {
            "bench": "serve_spec",
            "tpot_speedup": 2.1,
            "draft_hit_rate": 0.9,
            "accepted_per_step": 4.2,
        }
        good = tmp_path / "spec.json"
        good.write_text(json.dumps(rec))
        floors = tmp_path / "spec_floors.json"
        assert self._gate(
            ["--stamp", str(good), "--floors", str(floors)]
        ) == 0
        with open(floors) as f:
            stamped = json.load(f)
        assert stamped["tpot_speedup"] == {"min": 2.1}
        assert stamped["draft_hit_rate"] == {"min": 0.9}
        assert self._gate(
            ["--record", str(good), "--floors", str(floors)]
        ) == 0
        bad = tmp_path / "spec_bad.json"
        bad.write_text(json.dumps(dict(rec, tpot_speedup=1.0)))
        assert self._gate(
            ["--record", str(bad), "--floors", str(floors)]
        ) == 1
        assert "[FAIL] tpot_speedup" in capsys.readouterr().out

    def test_affinity_hit_rate_stamps_and_gates(self, tmp_path, capsys):
        """ISSUE 12 satellite: the serve_affinity record's
        with-affinity hit rate gates as a stamped MINIMUM — a scheduler
        regression that quietly reverts the fleet to cache-blind
        dispatch fails CI like any other perf loss."""
        rec = {
            "bench": "serve_affinity",
            "prefix_hit_rate_affinity": 0.5,
            "prefix_hit_rate_no_affinity": 0.33,
            "affinity_hit_gain": 0.17,
        }
        good = tmp_path / "affinity.json"
        good.write_text(json.dumps(rec))
        floors = tmp_path / "affinity_floors.json"
        assert self._gate(
            ["--stamp", str(good), "--floors", str(floors)]
        ) == 0
        with open(floors) as f:
            stamped = json.load(f)
        assert stamped["prefix_hit_rate_affinity"] == {"min": 0.5}
        assert self._gate(
            ["--record", str(good), "--floors", str(floors)]
        ) == 0
        bad = tmp_path / "affinity_bad.json"
        bad.write_text(
            json.dumps(dict(rec, prefix_hit_rate_affinity=0.1))
        )
        assert self._gate(
            ["--record", str(bad), "--floors", str(floors)]
        ) == 1
        assert "[FAIL] prefix_hit_rate_affinity" in capsys.readouterr().out

    def test_affinity_keys_ranked_by_run_diff(self, tmp_path):
        """ISSUE 12 satellite: the affinity keys land in run_diff's
        DIFF_KEYS/GATE_KEYS — an affinity regression ranks and the
        candidate's rate flattens for bench_gate --record."""
        import run_diff

        a = {"bench": "serve_affinity", "prefix_hit_rate_affinity": 0.5,
             "affinity_hit_gain": 0.2}
        b = {"bench": "serve_affinity", "prefix_hit_rate_affinity": 0.2,
             "affinity_hit_gain": 0.0}
        a_path, b_path = tmp_path / "a.json", tmp_path / "b.json"
        a_path.write_text(json.dumps(a))
        b_path.write_text(json.dumps(b))
        out = tmp_path / "diff.json"
        rc = run_diff.main(
            [str(a_path), str(b_path), "--json", str(out)]
        )
        assert rc == 0
        with open(out) as f:
            doc = json.load(f)
        ranked = {d["metric"]: d["verdict"] for d in doc["ranked"]}
        assert ranked["prefix_hit_rate_affinity"] == "regressed"
        assert doc["prefix_hit_rate_affinity"] == 0.2

    def test_quant_keys_stamp_and_gate(self, tmp_path, capsys):
        """ISSUE 15 satellite: the serve_quant record's
        tpot_speedup_quant gates as a stamped MINIMUM and
        hbm_bytes_per_replica as a MAXIMUM — a dequant-path regression
        that eats the speedup, or a registry change that quietly grows
        the per-replica footprint, fails CI like any other perf loss."""
        rec = {
            "bench": "serve_quant",
            "tpot_speedup_quant": 1.03,
            "hbm_bytes_per_replica": 41132,
        }
        good = tmp_path / "quant.json"
        good.write_text(json.dumps(rec))
        floors = tmp_path / "quant_floors.json"
        assert self._gate(
            ["--stamp", str(good), "--floors", str(floors)]
        ) == 0
        with open(floors) as f:
            stamped = json.load(f)
        assert stamped["tpot_speedup_quant"] == {"min": 1.03}
        assert stamped["hbm_bytes_per_replica"] == {"max": 41132}
        assert self._gate(
            ["--record", str(good), "--floors", str(floors)]
        ) == 0
        slow = tmp_path / "quant_slow.json"
        slow.write_text(json.dumps(dict(rec, tpot_speedup_quant=0.4)))
        assert self._gate(
            ["--record", str(slow), "--floors", str(floors)]
        ) == 1
        assert "[FAIL] tpot_speedup_quant" in capsys.readouterr().out
        fat = tmp_path / "quant_fat.json"
        fat.write_text(
            json.dumps(dict(rec, hbm_bytes_per_replica=9 * 41132))
        )
        assert self._gate(
            ["--record", str(fat), "--floors", str(floors)]
        ) == 1
        assert "[FAIL] hbm_bytes_per_replica" in capsys.readouterr().out

    def test_quant_keys_ranked_by_run_diff(self, tmp_path):
        """ISSUE 15 satellite: the quant keys land in run_diff's
        DIFF_KEYS/GATE_KEYS — a quant regression ranks and the
        candidate's values flatten for bench_gate --record."""
        import run_diff

        a = {"bench": "serve_quant", "tpot_speedup_quant": 1.1,
             "hbm_bytes_per_replica": 41132, "stream_agreement": 1.0}
        b = {"bench": "serve_quant", "tpot_speedup_quant": 0.6,
             "hbm_bytes_per_replica": 41132, "stream_agreement": 0.8}
        a_path, b_path = tmp_path / "qa.json", tmp_path / "qb.json"
        a_path.write_text(json.dumps(a))
        b_path.write_text(json.dumps(b))
        out = tmp_path / "qdiff.json"
        rc = run_diff.main(
            [str(a_path), str(b_path), "--json", str(out)]
        )
        assert rc == 0
        with open(out) as f:
            doc = json.load(f)
        ranked = {d["metric"]: d["verdict"] for d in doc["ranked"]}
        assert ranked["tpot_speedup_quant"] == "regressed"
        assert ranked["stream_agreement"] == "regressed"
        assert doc["tpot_speedup_quant"] == 0.6
        assert doc["hbm_bytes_per_replica"] == 41132

    def test_floorless_report_lists_unbanked_gate_keys(
        self, tmp_path, capsys
    ):
        """ISSUE 11 satellite: the floorless-keys report WARNS (exit 0)
        for every gate key with no banked floor — the ROADMAP standing
        note's harvest list (sharded_step_time, serving TTFT/TPOT/
        prefix-hit, chaos p95) made explicit — and drops keys a
        stamped floors file covers."""
        rc = self._gate(["--floorless-report"])
        out = capsys.readouterr().out
        assert rc == 0
        for key in ("sharded_step_time", "ttft_p95_ms", "tpot_p95_ms",
                    "prefix_hit_rate", "p95_vs_baseline",
                    "tpot_speedup",
                    # ISSUE 13: the overload/traffic keys stay on the
                    # harvest list until a TPU floor is stamped.
                    "ttft_p95_interactive_ms", "ttft_p95_batch_ms",
                    "shed_rate_interactive", "scale_up_latency_s",
                    # ISSUE 15: the quantization pair joins it (the
                    # CPU CI ratio is dispatch-bound ~1.0; the
                    # memory-bound floor needs the HBM rig).
                    "tpot_speedup_quant", "hbm_bytes_per_replica"):
            assert f"[WARN] gate key '{key}'" in out, key
        # A stamped floor removes its key from the report.
        floors = tmp_path / "floors.json"
        floors.write_text(json.dumps({"tpot_speedup": {"min": 2.0}}))
        rc = self._gate(["--floorless-report", "--floors", str(floors)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "'tpot_speedup'" not in out
        assert "'sharded_step_time'" in out

    def test_trajectory_gate_appends_floorless_warnings(self, capsys):
        files = sorted(
            os.path.join(REPO, f)
            for f in os.listdir(REPO)
            if re.fullmatch(r"BENCH_r\d+\.json", f)
        )
        assert self._gate(files) == 0
        out = capsys.readouterr().out
        assert "bench_gate floorless:" in out
        assert "[WARN] gate key 'sharded_step_time'" in out


class TestFaultInjectServe:
    """ISSUE 10 satellite: tools/fault_inject.py --serve arms the
    serving fault grammar in the child's environment."""

    def test_serve_spec_exported_to_child(self, capsys):
        import fault_inject

        rc = fault_inject.main([
            "--serve", "--spec", "crash@1:4,badhealth@0:2", "--",
            sys.executable, "-c",
            "import os, sys; "
            "sys.exit(0 if os.environ.get('TPU_SERVE_FAULT_INJECT')"
            " == 'crash@1:4,badhealth@0:2' else 3)",
        ])
        assert rc == 0

    def test_serve_spec_validated_before_spawn(self, capsys):
        import fault_inject

        with pytest.raises(ValueError, match="unknown serve fault"):
            fault_inject.main([
                "--serve", "--spec", "sigterm@5", "--",
                sys.executable, "-c", "raise SystemExit(9)",
            ])
        # ...and the train grammar rejects serve kinds symmetrically.
        with pytest.raises(ValueError, match="unknown fault kind"):
            fault_inject.main([
                "--spec", "crash@1:4", "--",
                sys.executable, "-c", "raise SystemExit(9)",
            ])


class TestHostInputBench:
    """ISSUE 6 CI satellite: the input-pipeline smoke — a BENCH-style
    record from the real reader+worker pipeline, bit-identity verified,
    on BOTH decode stages (native C++ and the tf/numpy fallback)."""

    def _run(self, capsys, monkeypatch, tmp_path, native: bool):
        import host_input_bench

        monkeypatch.setenv(
            "TFE_TPU_NATIVE_DECODE", "1" if native else "0"
        )
        # Pin the record-count cache into this test's tmp dir so the
        # tool's setdefault can't leak a deleted path into the process.
        monkeypatch.setenv("TFE_TPU_CACHE_DIR", str(tmp_path / "cache"))
        rc = host_input_bench.main(["--smoke", "--json", "--n=16"])
        rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        return rc, rec

    @pytest.mark.timeout(300)
    def test_smoke_record_native_vs_fallback(
        self, capsys, monkeypatch, tmp_path
    ):
        from tensorflow_examples_tpu import native

        rc, rec = self._run(capsys, monkeypatch, tmp_path, native=False)
        assert rc == 0, rec
        assert rec["metric"] == "host_input_pipeline_images_per_sec"
        assert rec["backend"] == "cpu" and rec["complete"] is True
        assert rec["decoder"] == "fallback"
        assert rec["identical"] is True  # parallel == sequential, bytewise
        assert rec["value"] > 0 and rec["sequential_images_per_sec"] > 0
        assert rec["fingerprint_tflops"] > 0
        assert rec["workers"] == 4 and rec["readers"] == 2
        assert rec["extras"][0]["metric"] == "host_input_seq_images_per_sec"
        if native.available("fastjpeg"):
            rc, rec = self._run(capsys, monkeypatch, tmp_path, native=True)
            assert rc == 0 and rec["decoder"] == "native"
            assert rec["identical"] is True and rec["complete"] is True

    def test_record_gates_against_cpu_floor(self, tmp_path):
        """The emitted record shape is gate-able by bench_gate against
        bench.FLOORS['cpu'] (synthetic values: deterministic verdicts
        on a box whose real throughput swings with ambient load)."""
        import bench
        import bench_gate

        floor, floor_fp = bench.FLOORS["cpu"][
            "host_input_pipeline_images_per_sec"
        ]

        def rec(value):
            return {
                "metric": "host_input_pipeline_images_per_sec",
                "value": value, "unit": "images/sec", "backend": "cpu",
                "fingerprint_tflops": floor_fp,
            }

        ok = tmp_path / "ok.json"
        ok.write_text(json.dumps(rec(floor * 1.5)))
        assert bench_gate.main([str(ok)]) == 0
        bad = tmp_path / "bad.json"
        bad.write_text(json.dumps(rec(floor * 0.5)))
        assert bench_gate.main([str(bad)]) == 1

    def test_pipeline_only_extra_promoted_to_metric(self, tmp_path):
        """ISSUE 6: the buried pipeline_only_images_per_sec annotation
        becomes a first-class gated metric — from the parsed record AND
        from the torn-tail regex fallback."""
        import bench_gate

        doc = {
            "parsed": {
                "metric": "resnet50_examples_per_sec_per_chip",
                "value": 100.0, "backend": "tpu",
                "fingerprint_tflops": 2279.33,
                "extras": [
                    {
                        "metric": "resnet50_input_examples_per_sec_per_chip",
                        "value": 75.0,
                        "pipeline_only_images_per_sec": 474.6,
                    }
                ],
            }
        }
        p = tmp_path / "r.json"
        p.write_text(json.dumps(doc))
        recs = {r["metric"]: r for r in bench_gate.extract_records(str(p))}
        assert (
            recs["resnet50_input_pipeline_only_images_per_sec"]["value"]
            == 474.6
        )
        assert (
            recs["resnet50_input_pipeline_only_images_per_sec"][
                "fingerprint"
            ]
            == 2279.33
        )
        tail = (
            '{"metric": "resnet50_input_examples_per_sec_per_chip", '
            '"value": 75.0, "pipeline_only_images_per_sec": 474.6, '
            '"fingerprint_tflops_pre": 2279.33} "backend": "tpu"'
        )
        t = tmp_path / "t.json"
        t.write_text(json.dumps({"tail": tail}))
        recs = {r["metric"]: r for r in bench_gate.extract_records(str(t))}
        assert (
            recs["resnet50_input_pipeline_only_images_per_sec"]["value"]
            == 474.6
        )
        # banked trajectory (with the floored metric) still gates green
        assert bench_gate.main(
            [os.path.join(REPO, "BENCH_r0*.json")]
        ) == 0


@pytest.mark.serving
class TestServeBench:
    """The tier-1 serving smoke (ISSUE 5 CI satellite): stand the whole
    stack up on CPU, drive 20 concurrent requests over real HTTP via
    ``tools/serve_bench.py --smoke``, and bank a well-formed BENCH
    record with ZERO post-warmup recompiles."""

    @pytest.mark.timeout(300)
    def test_smoke_banks_wellformed_record(self, tmp_path, capsys):
        import serve_bench

        out = tmp_path / "serve_record.json"
        rc = serve_bench.main(
            ["--smoke", "--requests", "20", "--out", str(out)]
        )
        assert rc == 0
        with open(out) as f:
            rec = json.load(f)
        # The stdout line is the same record (the BENCH driver contract:
        # last JSON line of stdout is the result).
        stdout_rec = json.loads(
            capsys.readouterr().out.strip().splitlines()[-1]
        )
        assert stdout_rec == rec
        assert rec["bench"] == "serving" and rec["backend"] == "cpu"
        assert rec["requests"] == 20 and rec["completed"] == 20
        assert rec["errors"] == 0 and rec["ok"] is True
        assert rec["transport"] == "http"
        # Zero-recompile steady state: exactly the warmed ladder.
        assert rec["post_warmup_recompiles"] == 0
        assert rec["compiles"] == rec["expected_compiles"]
        # Verified subset is token-identical to the unbatched reference.
        assert rec["verified"] == 3 and rec["verify_ok"] is True
        for key in ("req_per_s", "tok_per_s", "ttft_p95_ms",
                    "tpot_p95_ms", "e2e_p95_ms", "queue_wait_p95_ms"):
            assert isinstance(rec[key], (int, float)) and rec[key] > 0, key

    @pytest.mark.timeout(300)
    def test_smoke_slo_healthy_fires_zero_alerts(self, tmp_path):
        """ISSUE 19 CI satellite: a healthy smoke under ``--slo`` banks
        alert_count == 0 (the false-positive gate: generous default
        objectives must never fire on a healthy CPU run), full canary
        probe success, and an untouched error budget. The record is
        assembled BEFORE the probe phase, so probe traffic cannot
        pollute the banked percentiles."""
        import serve_bench

        out = tmp_path / "slo_record.json"
        rc = serve_bench.main(
            ["--smoke", "--requests", "12", "--out", str(out), "--slo"]
        )
        assert rc == 0
        with open(out) as f:
            rec = json.load(f)
        assert rec["ok"] is True
        assert rec["requests"] == 12 and rec["completed"] == 12
        assert rec["alert_count"] == 0
        assert rec["alerts_firing"] == 0
        assert rec["probe_success_rate"] == 1.0
        assert rec["error_budget_remaining"] == 1.0
        # The probe phase re-checks the zero-recompile bar: synthetic
        # probes ride the SAME warmed ladder.
        assert rec["post_warmup_recompiles"] == 0
        # --slo needs the HTTP frontend (black-box probes): --inproc
        # and the special modes refuse it loudly.
        with pytest.raises(SystemExit):
            serve_bench.main(["--smoke", "--inproc", "--slo"])
        with pytest.raises(SystemExit):
            serve_bench.main(["--smoke", "--chaos", "--slo"])

    @pytest.mark.timeout(300)
    def test_smoke_trace_out_validates_and_renders(self, tmp_path, capsys):
        """ISSUE 18 CI satellite: ``--smoke --trace-out`` banks >= 1
        ``kind="trace"`` line that validates against schema v13, the
        record carries full coverage (bench drivers keep EVERY trace),
        and ``tools/trace_report.py --trace-id`` renders the span tree
        with its critical path."""
        import serve_bench
        import trace_report

        from tensorflow_examples_tpu.telemetry import schema

        traces = tmp_path / "traces.jsonl"
        out = tmp_path / "rec.json"
        rc = serve_bench.main([
            "--smoke", "--requests", "8", "--out", str(out),
            "--trace-out", str(traces),
        ])
        assert rc == 0
        with open(out) as f:
            rec = json.load(f)
        # A measuring run samples nothing out: coverage is 1.0 and
        # every request left a trace.
        assert rec["traces_kept"] == 8
        assert rec["trace_coverage"] == 1.0
        with open(traces) as f:
            lines = [json.loads(ln) for ln in f if ln.strip()]
        assert len(lines) >= 1
        for line in lines:
            assert line["kind"] == "trace"
            assert (
                line["schema_version"] == schema.SERVING_SCHEMA_VERSION
            )
            problems = schema.validate_line(line)
            assert problems == [], problems
        tid = lines[0]["trace"]["trace_id"]
        capsys.readouterr()  # drop the bench's own stdout
        rc = trace_report.main(["--trace-id", tid, str(traces)])
        rendered = capsys.readouterr().out
        assert rc == 0
        assert tid in rendered
        assert "request" in rendered and "critical path:" in rendered
        # The replica's engine-phase spans made it across the wire
        # into the rendered tree.
        assert "decode_segment" in rendered

    @pytest.mark.timeout(300)
    def test_spec_decode_smoke_banks_ab_record(self, tmp_path):
        """ISSUE 11 satellite: ``--smoke --spec-decode K`` drives the
        SAME prompt-like prompts speculation-off then -on, banks a
        ``serve_spec`` record with the measured tpot_speedup /
        draft_hit_rate / accepted_per_step, and asserts every on-phase
        stream token-identical to its off-phase twin with zero
        post-warmup recompiles across both engines."""
        import serve_bench

        out = tmp_path / "spec_record.json"
        rc = serve_bench.main([
            "--smoke", "--spec-decode", "3", "--requests", "8",
            "--max-new-tokens", "16", "--concurrency", "4",
            "--out", str(out),
        ])
        assert rc == 0
        with open(out) as f:
            rec = json.load(f)
        assert rec["bench"] == "serve_spec" and rec["spec_k"] == 3
        assert rec["errors"] == 0 and rec["ok"] is True
        assert rec["tokens_identical"] is True
        assert rec["verify_ok"] is True
        assert rec["post_warmup_recompiles"] == 0
        # The verify_k rungs are part of the warmed ladder.
        assert rec["expected_compiles"] > 0
        assert rec["tpot_speedup"] is not None and rec["tpot_speedup"] > 0
        assert 0.0 <= rec["draft_hit_rate"] <= 1.0
        assert rec["accepted_per_step"] >= 1.0
        assert rec["accepted_per_step_p50"] >= 1.0
        # Prompt-like traffic through the n-gram drafter must actually
        # accept drafts — otherwise the A/B measured nothing.
        assert rec["draft_hit_rate"] > 0.25

    @pytest.mark.timeout(300)
    def test_weight_dtype_smoke_banks_quant_record(self, tmp_path):
        """ISSUE 15 CI satellite: ``--smoke --weight-dtype int8``
        drives the SAME prompts through an f32 engine and a
        weight-quantized one, banks a ``serve_quant`` record with the
        measured HBM ratio (<= 0.35x — the ~4x claim), the
        first-token-exact + bounded-divergence verdict, and zero
        post-warmup recompiles across both engines."""
        import serve_bench

        out = tmp_path / "quant_record.json"
        rc = serve_bench.main([
            "--smoke", "--weight-dtype", "int8", "--requests", "10",
            "--out", str(out),
        ])
        assert rc == 0
        with open(out) as f:
            rec = json.load(f)
        assert rec["bench"] == "serve_quant"
        assert rec["weight_dtype"] == "int8" and rec["weight_bits"] == 8
        assert rec["errors"] == 0 and rec["ok"] is True
        assert rec["first_token_exact"] is True
        assert rec["stream_agreement"] >= serve_bench.QUANT_AGREEMENT_FLOOR
        assert rec["verify_ok"] is True
        assert rec["post_warmup_recompiles"] == 0
        assert rec["hbm_bytes_per_replica"] <= (
            0.35 * rec["hbm_bytes_per_replica_f32"]
        )
        assert rec["hbm_ratio_vs_f32"] <= 0.35
        assert rec["tpot_speedup_quant"] is not None
        assert rec["tpot_speedup_quant"] > 0

    def test_bench_modes_are_mutually_exclusive(self, capsys):
        """Each mode banks its own record; combining two must be a
        loud usage error, never a silently-one-mode run."""
        import serve_bench

        with pytest.raises(SystemExit) as e:
            serve_bench.main(
                ["--smoke", "--weight-dtype", "int8",
                 "--spec-decode", "3"]
            )
        assert e.value.code == 2
        assert "don't compose" in capsys.readouterr().err

    @pytest.mark.timeout(300)
    def test_router_smoke_two_paged_replicas(self, tmp_path):
        """ISSUE 8 CI satellite: ``--smoke --router`` spins 2 in-proc
        PAGED replicas behind serving/router.py, drives real HTTP
        through the router, and banks a well-formed ``serve_router``
        record — verified tokens, >= 1 prefix-cache hit, and zero
        post-warmup recompiles summed over every replica."""
        import serve_bench

        out = tmp_path / "router_record.json"
        rc = serve_bench.main(
            ["--smoke", "--router", "--requests", "12",
             "--out", str(out)]
        )
        assert rc == 0
        with open(out) as f:
            rec = json.load(f)
        assert rec["bench"] == "serve_router" and rec["replicas"] == 2
        assert rec["requests"] == 12 and rec["completed"] == 12
        assert rec["errors"] == 0 and rec["ok"] is True
        assert rec["transport"] == "router-http"
        # The paged tier: block size banked, >= 1 prefix-cache hit
        # from the shared-prefix prompt set.
        assert rec["kv_block_size"] == 16
        assert rec["prefix_hits"] >= 1
        assert 0 < rec["prefix_hit_rate"] <= 1
        # Zero-recompile steady state ACROSS the fleet.
        assert rec["post_warmup_recompiles"] == 0
        assert rec["compiles"] == rec["expected_compiles"]
        assert rec["verified"] == 3 and rec["verify_ok"] is True
        assert rec["router_dispatched"] >= 12
        assert rec["router_no_replica"] == 0
        for key in ("req_per_s", "tok_per_s", "ttft_p95_ms",
                    "tpot_p95_ms", "e2e_p95_ms"):
            assert isinstance(rec[key], (int, float)) and rec[key] > 0

    @pytest.mark.timeout(420)
    def test_affinity_ab_smoke_banks_record(self, tmp_path):
        """ISSUE 12 CI satellite: ``--smoke --router --affinity ab``
        drives the SAME shared-prefix traffic through an affinity-off
        fleet then an affinity-on one (deterministic sequential
        dispatch with manual probes) and banks the ``serve_affinity``
        record — the acceptance claim is prefix_hit_rate strictly
        GREATER with affinity on, verified streams token-identical,
        zero post-warmup recompiles across both fleets."""
        import serve_bench

        out = tmp_path / "affinity_record.json"
        rc = serve_bench.main(
            ["--smoke", "--router", "--affinity", "ab",
             "--requests", "12", "--out", str(out)]
        )
        assert rc == 0
        with open(out) as f:
            rec = json.load(f)
        assert rec["bench"] == "serve_affinity"
        assert rec["errors"] == 0 and rec["ok"] is True
        # THE acceptance inequality, measured not sampled.
        assert (
            rec["prefix_hit_rate_affinity"]
            > rec["prefix_hit_rate_no_affinity"]
        )
        assert rec["affinity_hit_gain"] > 0
        assert rec["prefix_hits_on"] > rec["prefix_hits_off"]
        # Affinity dispatch actually fired (the counter, not luck).
        assert rec["affinity_dispatches"] >= 1
        assert rec["post_warmup_recompiles"] == 0
        assert rec["verified"] == 3 and rec["verify_ok"] is True
        # The shared-vs-cold TTFT split is banked for the record.
        for key in ("ttft_shared_p50_ms", "ttft_shared_p95_ms",
                    "ttft_cold_p50_ms", "ttft_cold_p95_ms"):
            assert isinstance(rec[key], (int, float)) and rec[key] > 0

    @pytest.mark.timeout(420)
    def test_chaos_smoke_banks_availability_record(self, tmp_path):
        """ISSUE 10 CI satellite: ``--smoke --chaos`` runs a
        SUPERVISED 2-replica paged fleet through a baseline phase and
        a crash-one-replica chaos phase, and banks the serve_chaos
        availability record: zero failed requests (error_rate 0 — the
        bench_gate smoke bound), >= 1 in-flight failover, a completed
        restart cycle, and the chaos p95 within the declared multiple
        of the fault-free baseline."""
        import serve_bench

        from tensorflow_examples_tpu.utils import faults as faults_mod

        out = tmp_path / "chaos_record.json"
        try:
            rc = serve_bench.main(
                ["--smoke", "--chaos", "--replicas", "2",
                 "--requests", "8", "--concurrency", "4",
                 "--out", str(out)]
            )
        finally:
            faults_mod.serve_clear()  # belt-and-braces for the suite
        assert rc == 0
        with open(out) as f:
            rec = json.load(f)
        assert rec["bench"] == "serve_chaos" and rec["replicas"] == 2
        assert rec["ok"] is True
        # Availability: every request of BOTH phases completed even
        # though a replica was killed mid-decode.
        assert rec["errors"] == 0 and rec["error_rate"] == 0.0
        assert rec["faults_fired"] >= 1
        assert rec["failover_count"] >= 1
        # The supervisor completed one restart cycle and the fleet
        # ended green.
        assert rec["router_restarts"] == 1
        assert rec["fleet_restored"] is True
        # Tail latency bounded by the declared multiple.
        assert rec["p95_vs_baseline"] is not None
        assert rec["p95_vs_baseline"] <= rec["p95_budget"]
        # Zero post-warmup recompiles across survivors + the re-warmed
        # replica; verified subset token-identical through failover.
        assert rec["post_warmup_recompiles"] == 0
        assert rec["verified"] == 3 and rec["verify_ok"] is True

    @pytest.mark.timeout(420)
    def test_traffic_flash_smoke_banks_record(self, tmp_path):
        """ISSUE 13 CI satellite: ``--smoke --traffic flash`` drives
        the seeded 3x flash crowd open-loop through a 2-replica
        brownout-enabled fleet and banks the serve_traffic record:
        zero lost requests, zero interactive sheds, per-class TTFT
        p95s and the flash/steady ratio stamped, verified streams
        token-identical, zero post-warmup recompiles."""
        import serve_bench

        out = tmp_path / "traffic_flash.json"
        rc = serve_bench.main(
            ["--smoke", "--traffic", "flash", "--replicas", "2",
             "--out", str(out)]
        )
        assert rc == 0
        with open(out) as f:
            rec = json.load(f)
        assert rec["bench"] == "serve_traffic"
        assert rec["traffic"] == "flash" and rec["ok"] is True
        # Shedding is split from real failures (ISSUE 13 satellite):
        # errors counts LOST requests only, and none were lost.
        assert rec["errors"] == 0 and rec["transport_errors"] == 0
        # All shedding (if any) landed on the batch class.
        assert rec["shed_interactive"] == 0
        assert rec["shed_rate_interactive"] == 0.0
        # The gate keys the record feeds bench_gate are stamped.
        for key in ("ttft_p95_interactive_ms", "ttft_p95_batch_ms",
                    "steady_ttft_p95_interactive_ms",
                    "flash_ttft_p95_interactive_ms"):
            assert isinstance(rec[key], (int, float)) and rec[key] > 0
        # The flash/steady ratio is stamped beside its budget; at the
        # smoke's size it is a reading of this machine's load (TTFTs of
        # a few ms), so the smoke does not gate on it.
        assert rec["flash_ttft_budget"] == serve_bench.FLASH_TTFT_BUDGET
        assert rec["flash_vs_steady_ttft"] == pytest.approx(
            rec["flash_ttft_p95_interactive_ms"]
            / rec["steady_ttft_p95_interactive_ms"], abs=1e-3,
        )
        assert rec["brownout_cleared"] is True
        assert rec["post_warmup_recompiles"] == 0
        assert rec["verified"] == 3 and rec["verify_ok"] is True
        # Replayability: the same seed makes a byte-identical schedule.
        a = serve_bench.make_traffic_schedule(
            "flash", 40, rate=25.0, vocab=211, max_len=64, max_new=8,
            seed=3,
        )
        b = serve_bench.make_traffic_schedule(
            "flash", 40, rate=25.0, vocab=211, max_len=64, max_new=8,
            seed=3,
        )
        assert a == b
        phases = {ev["phase"] for ev in a}
        assert phases == {"steady", "flash", "recover"}
        assert {ev["slo"] for ev in a} == {"interactive", "batch"}

    @pytest.mark.timeout(480)
    def test_traffic_ramp_smoke_scales_fleet(self, tmp_path):
        """ISSUE 13 autoscaler golden (smoke scale): ``--smoke
        --traffic ramp`` starts a 1-replica fleet under the
        telemetry-driven autoscaler; the ramp's peak forces at least
        one green-gated scale-up, scale-down drains back to 1 with
        zero lost requests, the record stamps scale_up_latency_s and
        p95_during_resize_ms, and the brownout ladder fully clears."""
        import serve_bench

        out = tmp_path / "traffic_ramp.json"
        rc = serve_bench.main(
            ["--smoke", "--traffic", "ramp", "--max-replicas", "3",
             "--out", str(out)]
        )
        assert rc == 0
        with open(out) as f:
            rec = json.load(f)
        assert rec["bench"] == "serve_traffic"
        assert rec["traffic"] == "ramp" and rec["ok"] is True
        # Zero failed requests across the whole resize cycle —
        # scale-down is drain-first, so nothing is ever lost.
        assert rec["errors"] == 0 and rec["transport_errors"] == 0
        # The fleet actually resized: up under the peak, back to min.
        assert rec["scale_ups"] >= 1 and rec["scale_downs"] >= 1
        assert rec["replicas_peak"] >= 2
        assert rec["replicas_final"] == 1
        # The autoscaler's own latency is a banked, gateable number.
        assert rec["scale_up_latency_s"] is not None
        assert rec["scale_up_latency_s"] > 0
        assert rec["brownout_cleared"] is True
        assert rec["post_warmup_recompiles"] == 0
        assert rec["verify_ok"] is True

    def test_make_prompts_spans_buckets(self):
        import serve_bench

        prompts = serve_bench.make_prompts(
            16, vocab=97, max_len=64, max_new=8
        )
        lengths = {len(p) for p in prompts}
        assert min(lengths) == 1 and max(lengths) == 56
        assert all(0 <= t < 97 for p in prompts for t in p)

    def test_make_prompts_shared_prefix(self):
        import serve_bench

        prompts = serve_bench.make_prompts(
            16, vocab=97, max_len=64, max_new=8, shared_prefix_every=4
        )
        shared = [prompts[i] for i in range(1, 16, 4)]
        pre = shared[0][:28]
        assert all(p[:28] == pre for p in shared)

    def test_requires_a_target(self):
        import serve_bench

        with pytest.raises(SystemExit):
            serve_bench.main([])


class TestTpuWatchMetrics:
    """ISSUE 18 satellite: ``tools/tpu_watch.sh --metrics`` against a
    ROUTER endpoint — the router serves the same /health //window
    //fleet surface as a replica, so the one watcher script covers
    both. Pinned: healthy polls print the health body and the
    kind=serving window summary; a gone endpoint after a healthy last
    probe means "run ended", exit 0."""

    @pytest.mark.timeout(120)
    def test_watch_polls_router_then_exits_zero_on_endpoint_gone(self):
        import time

        from tensorflow_examples_tpu.serving.router import (
            Router,
            RouterFrontend,
        )

        # No probe loop (start() not called): the hand-probed replica
        # stays eligible, so /health answers "ok": true. The watcher
        # only GETs — no engine needed behind the fake URL.
        router = Router(["http://127.0.0.1:9/"])
        router.replicas[0].probed = True
        rfront = RouterFrontend(router, port=0).start()
        proc = subprocess.Popen(
            ["bash", os.path.join(REPO, "tools", "tpu_watch.sh"),
             "--metrics", f"127.0.0.1:{rfront.port}",
             "--interval", "1"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True,
        )
        try:
            time.sleep(3.5)  # a few healthy polls land
        finally:
            rfront.close()
            router.close()
        try:
            out, _ = proc.communicate(timeout=60)
        finally:
            proc.kill()
        assert proc.returncode == 0, out
        assert '"ok": true' in out  # health body echoed
        assert "kind=serving" in out  # /window summarized
        assert "endpoint gone: run ended" in out
        # Healthy-then-gone is a NORMAL end: the exit-reason pointer,
        # not a stall verdict.
        assert "exit reason is in the run dir" in out
        assert "STALLED" not in out


class TestSloWatch:
    """ISSUE 19 satellite: ``tools/slo_watch.py`` against a live router
    frontend. Pinned: the exit-code contract a deploy pipeline gates on
    (0 healthy, 1 while firing, 2 unreachable) and the rendered view —
    per-rule burn rates, and every firing alert with its severity and
    copy-paste exemplar command."""

    def _router(self):
        from tensorflow_examples_tpu.serving.router import (
            Router,
            RouterFrontend,
        )

        # No probe loop (start() not called): the hand-probed fake
        # replica stays eligible; the watcher only GETs /alerts +
        # /series, so no engine is needed behind the URL.
        router = Router(["http://127.0.0.1:9/"])
        router.replicas[0].probed = True
        rfront = RouterFrontend(router, port=0).start()
        return router, rfront

    @pytest.mark.timeout(120)
    def test_once_healthy_exits_zero(self, capsys):
        import slo_watch

        router, rfront = self._router()
        try:
            # One point in a default-series ring: the rollup tail
            # renders instruments the SLO rules burn on.
            router.series.record("router/e2e.p95", 0.01)
            rc = slo_watch.main(
                [f"127.0.0.1:{rfront.port}", "--once"]
            )
        finally:
            rfront.close()
            router.close()
        out = capsys.readouterr().out
        assert rc == 0
        assert "slo: 0 firing" in out
        assert "ok" in out and "FIRING" not in out
        assert "series" in out  # the /series rollup tail rendered

    @pytest.mark.timeout(120)
    def test_once_firing_exits_one_with_exemplar(self, capsys):
        import slo_watch

        from tensorflow_examples_tpu.telemetry.slo import (
            AlertEngine,
            SLOConfig,
            SLOObjective,
        )

        router, rfront = self._router()
        router.alerts = AlertEngine(
            SLOConfig(
                objectives=(SLOObjective(slo="interactive",
                                         e2e_p95_s=0.01,
                                         error_budget=0.01),),
                pending_for_s=0.0,
            ),
            registry=router.registry,
        )
        try:
            for _ in range(5):
                router.alerts.observe("interactive", e2e_s=1.0,
                                      trace_id="t-worst")
            router.alerts.evaluate()  # ok -> pending
            router.alerts.evaluate()  # pending -> firing (no dwell)
            rc = slo_watch.main(
                [f"http://127.0.0.1:{rfront.port}", "--once"]
            )
        finally:
            rfront.close()
            router.close()
        out = capsys.readouterr().out
        assert rc == 1
        assert "FIRING e2e_interactive" in out
        assert "--trace-id t-worst" in out  # the exemplar copy-paste

    @pytest.mark.timeout(120)
    def test_unreachable_exits_two(self, capsys):
        import slo_watch

        rc = slo_watch.main(
            ["127.0.0.1:9", "--once", "--timeout", "2"]
        )
        assert rc == 2


def test_readme_test_count_is_current():
    """README's `tests/` line states the suite size; keep it honest
    mechanically by comparing against pytest's own
    collection of this directory."""
    with open(os.path.join(REPO, "README.md")) as f:
        m = re.search(r"`tests/` — (\d+) tests", f.read())
    assert m, "README.md no longer carries the `tests/` — N tests line"
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, "-m", "pytest", os.path.join(REPO, "tests"),
         "--collect-only", "-q", "-p", "no:cacheprovider"],
        capture_output=True, text=True, timeout=300,
        env=env,
    )
    cm = re.search(r"(\d+) tests collected", out.stdout)
    assert cm, f"collection failed:\n{out.stdout[-2000:]}{out.stderr[-2000:]}"
    assert int(m.group(1)) == int(cm.group(1)), (
        f"README says {m.group(1)} tests, collection says {cm.group(1)} — "
        "update the README.md tests/ line"
    )


class TestTier1Budget:
    """The tier-1 wall guard (ISSUE 16 satellite): PR 12 noted the
    suite can exceed the 870 s CI wall. Heavy end-to-end goldens are a
    *budgeted allowlist* — a new test declaring a multi-minute timeout
    ceiling must either join the pinned list here (a reviewed wall
    spend) or go behind the ``slow`` marker (out of tier-1). This makes
    the budget regression loud at collection speed, with no subprocess
    suite run."""

    # Every tier-1 test allowed a timeout ceiling >= HEAVY_S, by
    # nodeid suffix. These are the load-bearing acceptance goldens the
    # marker policy (pyproject) says MUST run on every PR; growing
    # this list is a deliberate wall-budget decision, not a side
    # effect.
    HEAVY_S = 420
    ALLOWED_HEAVY = {
        "test_chaos.py::TestChaosGolden::test_kill_one_of_three_zero_failed_requests",
        "test_chaos.py::TestChaosGolden::test_kill_one_of_three_with_speculation_on",
        "test_chaos.py::TestChaosGolden::test_kill_prefill_replica_mid_handoff",
        "test_chaos.py::TestChaosGolden::test_decode_crash_yields_one_stitched_trace",
        "test_chaos.py::TestTakeoverGolden::test_killrouter_mid_stream_zero_lost_token_identical",
        "test_distributed.py::test_two_process_tp_matches_single_process",
        "test_resilience.py::test_fault_inject_tool_standalone",
        "test_tools.py::TestServeBench::test_affinity_ab_smoke_banks_record",
        "test_tools.py::TestServeBench::test_chaos_smoke_banks_availability_record",
        "test_tools.py::TestServeBench::test_traffic_flash_smoke_banks_record",
        "test_tools.py::TestServeBench::test_traffic_ramp_smoke_scales_fleet",
    }

    def _scan(self):
        """(nodeid_suffix, timeout_s, slow?) for every test function,
        via ast — decorator timeouts plus module pytestmark slow."""
        import ast

        found = []
        tests_dir = os.path.join(REPO, "tests")
        for fname in sorted(os.listdir(tests_dir)):
            if not (fname.startswith("test_") and fname.endswith(".py")):
                continue
            tree = ast.parse(
                open(os.path.join(tests_dir, fname)).read()
            )

            def mark_names(dec_list):
                names, timeouts = [], []
                for d in dec_list:
                    expr = d.func if isinstance(d, ast.Call) else d
                    name = ast.unparse(expr)
                    if not name.startswith("pytest.mark."):
                        continue
                    kind = name.split(".")[-1]
                    names.append(kind)
                    if (
                        kind == "timeout"
                        and isinstance(d, ast.Call)
                        and d.args
                        and isinstance(d.args[0], ast.Constant)
                    ):
                        timeouts.append(int(d.args[0].value))
                return names, timeouts

            module_slow = any(
                isinstance(node, ast.Assign)
                and any(
                    getattr(t, "id", None) == "pytestmark"
                    for t in node.targets
                )
                and "slow" in ast.unparse(node.value)
                for node in tree.body
            )
            for node in ast.walk(tree):
                if not isinstance(
                    node, (ast.FunctionDef, ast.AsyncFunctionDef)
                ):
                    continue
                if not node.name.startswith("test_"):
                    continue
                names, timeouts = mark_names(node.decorator_list)
                parents = [
                    c.name for c in ast.walk(tree)
                    if isinstance(c, ast.ClassDef)
                    and node in ast.walk(c)
                ]
                cls_slow = cls_timeouts = None
                for c in ast.walk(tree):
                    if isinstance(c, ast.ClassDef) and any(
                        n is node for n in ast.walk(c)
                    ):
                        cnames, ctimeouts = mark_names(
                            c.decorator_list
                        )
                        cls_slow = "slow" in cnames
                        cls_timeouts = ctimeouts
                suffix = fname + "::" + "::".join(
                    (parents[:1] or []) + [node.name]
                )
                slow = (
                    "slow" in names or bool(cls_slow) or module_slow
                )
                ceiling = max(timeouts + (cls_timeouts or []) + [0])
                found.append((suffix, ceiling, slow))
        return found

    def test_heavy_goldens_are_allowlisted_or_slow(self):
        scanned = self._scan()
        assert len(scanned) > 500  # the scan actually saw the suite
        offenders = [
            (suffix, ceiling)
            for suffix, ceiling, slow in scanned
            if ceiling >= self.HEAVY_S and not slow
            and suffix not in self.ALLOWED_HEAVY
            and not any(
                suffix.startswith(a.split("::")[0])
                and suffix.endswith(a.split("::")[-1])
                for a in self.ALLOWED_HEAVY
            )
        ]
        assert offenders == [], (
            f"tier-1 wall budget: {offenders} declare a >= "
            f"{self.HEAVY_S}s timeout ceiling without the 'slow' "
            "marker and outside the pinned allowlist — mark them slow "
            "or spend the budget explicitly in ALLOWED_HEAVY"
        )

    def test_allowlist_entries_exist(self):
        scanned = {s for s, _, _ in self._scan()}
        missing = {
            a for a in self.ALLOWED_HEAVY
            if not any(
                s.startswith(a.split("::")[0])
                and s.endswith(a.split("::")[-1])
                for s in scanned
            )
        }
        assert missing == set(), (
            f"stale tier-1 budget allowlist entries: {missing}"
        )
