"""The ``serve_latent`` runner end to end at a toy width on the CPU: the
new configuration's file cut to a width no one deploys, its mix and its
readers, through the same ``execute`` and ``result_line`` the command
uses. (After ``test_cells_kinds_tiny.py``.)"""

import copy
import json
import time

from benchmark import run as run_mod
from benchmark import spec

CELL = "glm-4.7-flash.serve-shareddoc"
TINY_SIZES = {
    "hidden_size": 64, "num_attention_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 16,
    "qk_nope_head_dim": 12, "qk_rope_head_dim": 8, "v_head_dim": 16, "intermediate_size": 96,
    "moe_intermediate_size": 32, "n_routed_experts": 8, "num_experts_per_tok": 2,
    "num_hidden_layers": 3, "vocab_size": 128, "max_position_embeddings": 128,
    "param_dtype": "float32",
}
TINY_DEPLOY = {"serve_config": {
    "max_slots": 4, "kv_block_size": 4, "kv_blocks": 257, "prefix_cache": True,
    "prefill_chunk_tokens": 8, "prefill_bucket_floor": 8, "kv_bucket_floor": 32}}
DOCS = [32, 48, 64]


def tiny_cell() -> spec.Cell:
    real = spec.load_cell(CELL)
    config = dict(real.config, **TINY_SIZES)
    config["correct"] = dict(
        config["correct"], document_len=40, prompt_lens=[49, 51, 46], cold_end_rows=4,
        extra_prompt_len=50, stream_tokens=4, logit_abs=1e-3, route_gap=0.0, reference_q_block=8,
        classify_prefixes=3, min_clear_rows={"cold": 1, "hit": 4, "decode": 3},
        fillers={"requests": 2, "prompt_len": 8, "new_tokens": 16})
    mix = copy.deepcopy(real.traffic)
    mix.update(prefixes={"lengths": DOCS, "weights": [1, 1, 1]},
               prompt={"dist": "lognormal", "median": 8, "sigma": 0.6, "min": 3, "max": 16},
               output={"dist": "fixed", "value": 6}, calibration_tokens=3, trace_seconds=0.3,
               publish={"question_tokens": 3, "new_tokens": 2})
    return spec.Cell(name=CELL, chips=1, config=config, traffic=mix,
                     deploy=copy.deepcopy(TINY_DEPLOY), end_to_end=real.end_to_end,
                     per_layer=real.per_layer)


def test_the_cell_has_its_files_and_reports_what_the_issue_names():
    cell = spec.load_cell(CELL)
    assert cell.traffic["runner"] == "serve_latent" and cell.chips == 1
    assert {m["name"] for m in cell.end_to_end} == {"serve_tokens_per_s", "setup_s"}
    new = {m["name"] for m in cell.per_layer if m["name"].endswith(".shareddoc")}
    assert new == {"serve_mfu.shareddoc", "decode_hbm_roofline.shareddoc",
                   "moe_expert_roofline.shareddoc", "prefix_hit_share.shareddoc",
                   "extend_share.shareddoc"}
    # the readers that no model's sizes enter are the ones the benchmark had
    assert {"decode_step_p50_ms.generate", "device_idle.generate", "batch_occupancy.generate",
            "kv_bytes_per_resident_token.longdoc",
            "expert_load_max_over_mean.longdoc"} <= {m["name"] for m in cell.per_layer}
    assert not {m["name"] for m in cell.per_layer} & {
        "idle_in_batcher.generate", "idle_in_engine_launch.generate",
        "idle_in_engine_fetch.generate", "idle_unattributed.generate"}
    for name in new:
        assert callable(spec.reader("layer_metrics", name))
    config = cell.config
    assert config["reduced"] == ["num_hidden_layers", "max_position_embeddings",
                                 "num_nextn_predict_layers"]
    for key in config["reduced"]:
        assert config[key] != config["published"][key]
    # every width, every expert and the whole vocabulary as published
    assert (config["hidden_size"], config["num_attention_heads"], config["q_lora_rank"],
            config["kv_lora_rank"], config["qk_nope_head_dim"], config["qk_rope_head_dim"],
            config["v_head_dim"]) == (2048, 20, 768, 512, 192, 64, 256)
    assert (config["intermediate_size"], config["moe_intermediate_size"],
            config["n_routed_experts"], config["num_experts_per_tok"], config["n_shared_experts"],
            config["routed_scaling_factor"], config["vocab_size"],
            config["tie_word_embeddings"]) == (10240, 1536, 64, 4, 1, 1.8, 154880, False)
    assert len(config["assumed"]) >= 6 and config["param_dtype"] == "bfloat16"
    mix = cell.traffic
    assert mix["prefixes"]["lengths"] == list(range(8192, 29697, 3072))
    assert len(set(mix["prefixes"]["weights"])) == 1
    assert mix["prompt"] == {"dist": "lognormal", "median": 128, "sigma": 0.6, "min": 32, "max": 512}
    assert mix["output"] == {"dist": "fixed", "value": 128}
    assert (mix["kind"], mix["clients_per_slot"], mix["slo"], mix["temperature"]) == (
        "closed", 2, "batch", 0.0)
    serve = cell.deploy["serve_config"]
    assert (serve["max_slots"], serve["kv_block_size"], serve["kv_blocks"],
            serve["prefill_chunk_tokens"], serve["prefix_cache"]) == (32, 16, 11264, 512, True)
    # a wave turns over inside the traced slice, which the extend programs' share reads
    check = config["correct"]
    assert set(check["min_clear_rows"]) == {"cold", "hit", "decode"}
    assert min(check["prompt_lens"]) > check["document_len"] > 16000
    assert check["fillers"]["requests"] + 1 == serve["max_slots"]


def test_runs_end_to_end_and_is_correct():
    cell = tiny_cell()
    run = run_mod.execute(cell, seed=2**31 + 11, seconds=1.0, trace=False,
                          t_start=time.perf_counter())
    line = run_mod.result_line(run, trace=False)
    detail = run.correct_detail
    assert line["correct"] is True, detail
    assert line["failed"] == 0 and line["attempted"] == run.notes["waves"] * 4
    assert {"serve_tokens_per_s", "setup_s"} <= set(line["metrics"])
    assert all(r["n_tokens"] == r["asked"] for r in run.requests)
    # the check did what it is for: the cold prompt ran in chunks and ends inside its
    # question, every other prompt hit the document
    assert detail["failed_by"] == [] and 44 <= detail["cold_prompt_len"] <= 49
    assert detail["cold_prefill_chunks"] >= 6 and detail["hit_reused_tokens"] == [0, 40, 40]
    assert detail["free_list_whole"] and detail["free_list_whole_after_window"]
    assert detail["rows"] == 3 * (3 + 4) and detail["rows_near_tie"] == 0
    assert detail["rows_clear"] == {"cold": 1, "hit": 2 + 2 * 3 + 3, "decode": 3 * 3}
    assert max(detail["worst"].values()) < 1e-3 and detail["extra_prompts"] == 0
    # three streams and one filler fill the four slots
    assert len(detail["live_slots_mean_in_check"]) == 1 < detail["live_slots_mean_in_check"][0]
    assert detail["absorbed_tokens_in_check"] > 0
    # the window was what the traffic implies: every request a hit of its whole document
    assert detail["every_request_hit_its_whole_document"]
    assert run.notes["prefix_reused_tokens"] == run.notes["prefix_reused_tokens_due"] > 0
    assert run.notes["prefix_reused_tokens"] == sum(r["reused"] for r in run.requests)
    assert run.notes["documents"] == 3 and run.notes["document_tokens"] == sum(DOCS)
    assert 0 < detail["reference_s"] < run.setup_s + detail["reference_s"]
    assert not [c for c in run.compiles_in_window if "_impl" in c]
    json.dumps(line)


def test_traced_run_reads_the_counters_and_leaves_out_the_device():
    cell = tiny_cell()
    run = run_mod.execute(cell, seed=5, seconds=1.0, trace=True, t_start=time.perf_counter())
    got = run_mod.result_line(run, trace=True)["metrics"]
    assert {"decode_step_p50_ms.generate", "batch_occupancy.generate",
            "kv_bytes_per_resident_token.longdoc", "expert_load_max_over_mean.longdoc",
            "prefix_hit_share.shareddoc", "warmup_s"} <= set(got)
    assert 0 < run.notes["tails_host_share_pct"] < 100.0  # the batcher's clock, not the device's
    piece = run.model["slice"]
    assert piece["samples"] >= 2 and piece["decode_steps"] > 0
    assert piece["kv_sampled_reach_bytes"] > 0
    # documents are most of every prompt, and slots that share one hold its blocks once
    assert 60.0 < got["prefix_hit_share.shareddoc"]["value"] < 100.0
    assert 0 < got["kv_bytes_per_resident_token.longdoc"]["value"] < run.model["kv_bytes_token"]
    assert got["expert_load_max_over_mean.longdoc"]["value"] >= 1.0
    # a CPU trace has no device plane, and peaks are never made up: nothing under their names
    assert not {"device_idle.generate", "decode_hbm_roofline.shareddoc", "extend_share.shareddoc",
                "moe_expert_roofline.shareddoc", "serve_mfu.shareddoc"} & set(got)


def test_a_wrong_tolerance_fails_the_check():
    cell = tiny_cell()
    cell.config["correct"].update(logit_abs=1e-12)
    cell.traffic["output"] = {"dist": "fixed", "value": 2}
    run = run_mod.execute(cell, seed=3, seconds=0.2, trace=False, t_start=time.perf_counter())
    assert run.correct is False


def test_rows_that_decide_nothing_do_not_make_a_run_correct():
    cell = tiny_cell()
    cell.config["correct"].update(route_gap=1e9, extra_prompts_max=2)
    cell.traffic["output"] = {"dist": "fixed", "value": 2}
    run = run_mod.execute(cell, seed=4, seconds=0.2, trace=False, t_start=time.perf_counter())
    detail = run.correct_detail
    assert run.correct is False and detail["extra_prompts"] == 2
    assert detail["rows_clear"] == {"cold": 0, "hit": 0, "decode": 0}
    assert detail["failed_by"] == [f"min_clear_rows:{kind}" for kind in ("cold", "decode", "hit")]
    assert detail["rows_near_tie"] == detail["rows"] == 5 * (3 + 4)


def test_the_control_fails_by_the_cells_own_comparison():
    """The reference on int8-rounded weights, through check_outputs: not
    correct, where the same engine against the float32 reference is."""
    from benchmark import control_serve_latent

    cell = tiny_cell()
    cell.config["correct"].update(logit_abs=2e-4)
    out = control_serve_latent.control(cell, seed=2**31 + 5)
    assert out["correct"] is True, out["correct_detail"]
    assert out["control_correct"] is False
    # by the limit and by nothing else: its own document, cold and then hit as the first
    assert {f.split(":")[0] for f in out["control_correct_detail"]["failed_by"]} == {"logit_abs"}
