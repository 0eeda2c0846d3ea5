"""Each kind of cell end to end at a toy width on the CPU, through the
same ``execute`` and ``result_line`` the command uses. The command
itself has no CPU switch and fails off the chip (last test)."""

import json
import os
import subprocess
import sys
import time

import pytest

from benchmark import run as run_mod
from benchmark import spec
from benchmark.tests import tiny

E2E = {"train": "train_tokens_per_s", "open": "ttft_p95_ms", "closed": "serve_tokens_per_s"}


@pytest.mark.parametrize("kind", ["train", "open", "closed"])
def test_cell_runs_end_to_end_and_is_correct(kind):
    cell = tiny.tiny_cell(kind)
    run = run_mod.execute(cell, seed=2**31 + 7, seconds=1.0, trace=False,
                          t_start=time.perf_counter())
    line = run_mod.result_line(run, trace=False)
    assert line["correct"] is True, run.correct_detail
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["device"]["platform"] == "cpu"  # never printed under a device metric's name
    assert E2E[kind] in line["metrics"] and "setup_s" in line["metrics"]
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert not [c for c in run.compiles_in_window if "_impl" in c or "train_step" in c]
    json.dumps(line)
    if kind != "train":
        assert run.correct_detail["cold_equals_hit"] is True
        assert run.correct_detail["classify_logprob_worst"] < 1e-3
        assert all(r["n_tokens"] == r["asked"] for r in run.requests)
    if kind == "open":
        assert run.notes["prefix_reused_tokens"] > 0      # the system prompts hit the cache
        assert "tpot_p95_ms" in line["metrics"]
    if kind == "closed":
        assert run.notes["prefix_reused_tokens"] == 0     # nothing shared: the cache is bypassed
        assert run.attempted == run.notes["waves"] * 2    # whole waves of max_slots


def test_traced_run_reports_per_layer_metrics_and_leaves_out_what_it_cannot_read():
    cell = tiny.tiny_cell("closed")
    run = run_mod.execute(cell, seed=3, seconds=1.0, trace=True, t_start=time.perf_counter())
    line = run_mod.result_line(run, trace=True)
    got = set(line["metrics"])
    assert {"warmup_s", "batch_occupancy.generate", "decode_step_p50_ms.generate"} <= got
    # a CPU trace has no device plane: the readers return nothing, nothing is made up
    assert "device_idle.generate" not in got and "decode_hbm_roofline.generate" not in got
    assert "breakdown" in line and "window_s" in line["device"]


def test_a_wrong_tolerance_fails_the_check():
    """The comparison is live: held to a tolerance under float32's own
    rounding, the served path is reported as not correct."""
    cell = tiny.tiny_cell("closed")
    cell.config["correct"]["logit_abs"] = 1e-9
    cell.traffic["output"] = {"dist": "fixed", "value": 2}
    run = run_mod.execute(cell, seed=3, seconds=0.2, trace=False, t_start=time.perf_counter())
    assert run.correct is False


def test_command_fails_off_the_chip_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(spec.HERE, "run.py"), "--workload",
         spec.load_benchmark()["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=spec.ROOT, timeout=300,
    )
    assert p.returncode != 0
    assert "cpu" in p.stderr and "correct" not in p.stdout
