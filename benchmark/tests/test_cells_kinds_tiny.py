"""The ``serve_kinds`` runner end to end at a toy width on the CPU: the
new configuration's file cut to a width no one deploys, its mix and its
readers, through the same ``execute`` and ``result_line`` the command
uses. (After ``test_cells_tiny.py``, which does this for the kinds of
cell the benchmark had.)"""

import copy
import json
import os
import time

from benchmark import run as run_mod
from benchmark import spec

CELL = "command-a-plus-05-2026.serve-longdoc"
TINY_SIZES = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 32, "num_experts_per_tok": 2, "num_shared_experts": 2,
    "sliding_window": 8, "num_experts_published": 8, "num_experts": 4,
    "held_experts": [0, 1, 2, 3], "vocab_size": 128, "max_position_embeddings": 64,
    "param_dtype": "float32",
}
TINY_DEPLOY = {"serve_config": {
    "max_slots": 5, "kv_block_size": 4, "kv_blocks": 81, "prefix_cache": True,
    "prefill_chunk_tokens": 8, "prefill_bucket_floor": 8, "kv_bucket_floor": 16}}


def tiny_cell() -> spec.Cell:
    real = spec.load_cell(CELL)
    config = dict(real.config, **TINY_SIZES)
    config["correct"] = dict(config["correct"], prompt_lens=[6, 21, 40], stream_tokens=4,
                             logit_abs=1e-3, route_gap=0.0, reference_q_block=8,
                             classify_prefixes=3,
                             min_clear_rows={"prefill": 2, "decode": 3},
                             fillers={"requests": 2, "prompt_len": 8, "new_tokens": 24})
    mix = copy.deepcopy(real.traffic)
    mix.update(prompt={"dist": "lognormal", "median": 16, "sigma": 0.6, "min": 4, "max": 40},
               output={"dist": "fixed", "value": 6}, calibration_tokens=3, trace_seconds=0.3)
    return spec.Cell(name=CELL, chips=1, config=config, traffic=mix,
                     deploy=copy.deepcopy(TINY_DEPLOY), end_to_end=real.end_to_end,
                     per_layer=real.per_layer)


def test_the_cell_has_its_files_and_reports_what_the_issue_names():
    cell = spec.load_cell(CELL)
    assert cell.traffic["runner"] == "serve_kinds" and cell.chips == 1
    assert {m["name"] for m in cell.end_to_end} == {"serve_tokens_per_s", "setup_s"}
    new = {m["name"] for m in cell.per_layer if m["name"].endswith(".longdoc")}
    assert len(new) == 6
    # what the cell shares with serve-generate is read by serve-generate's readers
    assert {"decode_step_p50_ms.generate", "device_idle.generate",
            "batch_occupancy.generate"} <= {m["name"] for m in cell.per_layer}
    for name in new:
        assert callable(spec.reader("layer_metrics", name))
    config = cell.config
    for key in config["reduced"]:
        assert config[key] != config["published"][key]
    assert len(config["held_experts"]) == config["num_experts"]
    assert len(config["assumed"]) >= 5 and config["param_dtype"] == "bfloat16"


def test_runs_end_to_end_and_is_correct():
    cell = tiny_cell()
    run = run_mod.execute(cell, seed=2**31 + 11, seconds=1.0, trace=False,
                          t_start=time.perf_counter())
    line = run_mod.result_line(run, trace=False)
    detail = run.correct_detail
    assert line["correct"] is True, detail
    assert line["failed"] == 0 and line["attempted"] == run.notes["waves"] * 5
    assert {"serve_tokens_per_s", "setup_s"} <= set(line["metrics"])
    assert all(r["n_tokens"] == r["asked"] for r in run.requests)
    # the check did what it is for: chunks ran, the window released blocks, nothing was shared
    assert detail["prefill_chunks_in_check"] > 1 and detail["window_blocks_released_in_check"] > 0
    assert detail["prefix_reused_tokens_in_check"] == 0 and run.notes["prefix_reused_tokens"] == 0
    assert detail["free_lists_whole"] and detail["free_lists_whole_after_window"]
    # both served paths gave numbers, and the streams decoded beside the fillers
    assert detail["rows"] == 3 * (3 + 4) and detail["rows_near_tie"] == 0
    assert detail["rows_clear"] == {"prefill": 3 * 4, "decode": 3 * 3}
    assert max(detail["worst"].values()) < 1e-3 and detail["extra_prompts"] == 0
    assert detail["filler_requests"] == 2 and detail["live_slots_mean_in_check"] > 1
    # the reference's seconds are the benchmark's, not the deployment's set-up
    assert 0 < detail["reference_s"] < run.setup_s + detail["reference_s"]
    assert not [c for c in run.compiles_in_window if "_impl" in c]
    json.dumps(line)


def test_traced_run_reads_the_counters_and_leaves_out_the_device():
    cell = tiny_cell()
    run = run_mod.execute(cell, seed=5, seconds=1.0, trace=True, t_start=time.perf_counter())
    got = run_mod.result_line(run, trace=True)["metrics"]
    assert {"decode_step_p50_ms.generate", "batch_occupancy.generate",
            "kv_bytes_per_resident_token.longdoc", "expert_load_max_over_mean.longdoc",
            "warmup_s"} <= set(got)
    # the traced slice holds the program's own counters at its two ends
    piece = run.model["slice"]
    assert piece["samples"] >= 2 and piece["seconds"] > 0
    assert piece["decode_steps"] > 0 and piece["kv_sampled_reach_bytes"] > 0
    assert piece["decode_tokens"] <= 5 * piece["decode_steps"]
    # every layer keeping every token would read kv_bytes_token (plus the partly filled blocks)
    assert 0 < got["kv_bytes_per_resident_token.longdoc"]["value"]
    assert got["expert_load_max_over_mean.longdoc"]["value"] >= 1.0
    # a CPU trace has no device plane, and peaks are never made up: nothing under their names
    assert not {"device_idle.generate", "decode_hbm_roofline.longdoc",
                "moe_expert_roofline.longdoc", "serve_mfu.longdoc"} & set(got)


def test_a_wrong_tolerance_fails_the_check():
    cell = tiny_cell()
    cell.config["correct"].update(logit_abs=1e-12)
    cell.traffic["output"] = {"dist": "fixed", "value": 2}
    run = run_mod.execute(cell, seed=3, seconds=0.2, trace=False, t_start=time.perf_counter())
    assert run.correct is False


def test_rows_that_decide_nothing_do_not_make_a_run_correct():
    """Every row a near-tie: further prompts are drawn, and with none
    that decides the verdict is false, whatever the rows read."""
    cell = tiny_cell()
    cell.config["correct"].update(route_gap=1e9, extra_prompts_max=2)
    cell.traffic["output"] = {"dist": "fixed", "value": 2}
    run = run_mod.execute(cell, seed=4, seconds=0.2, trace=False, t_start=time.perf_counter())
    detail = run.correct_detail
    assert run.correct is False and detail["extra_prompts"] == 2
    assert detail["rows_clear"] == {"prefill": 0, "decode": 0}
    assert detail["rows_near_tie"] == detail["rows"] == 5 * (3 + 4)


def test_the_control_fails_by_the_cells_own_comparison():
    """The reference on int8-rounded weights, through check_outputs: not
    correct, where the same engine against the float32 reference is."""
    from benchmark import control_serve_kinds

    cell = tiny_cell()
    cell.config["correct"].update(logit_abs=2e-4)
    out = control_serve_kinds.control(cell, seed=2**31 + 5)
    assert out["correct"] is True, out["correct_detail"]
    assert out["control_correct"] is False
    worst = out["control_correct_detail"]["worst"]
    assert min(worst.values()) > 2e-4  # both paths' rows read it
