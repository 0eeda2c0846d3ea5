"""The ``serve_mixed`` runner end to end at a toy width on the CPU: the
new configuration's file cut to a width no one deploys, its mix, its
roofline arithmetic and its readers, through the same ``execute`` and
``result_line`` the command uses; the files load through ``spec.py``
and count the bytes of ISSUE 34's arithmetic. (After
``test_cells_kinds_tiny.py``.)"""

import copy
import json
import time

import pytest

from benchmark import record, roofline_mimo_v2 as ops
from benchmark import run as run_mod
from benchmark import spec

CELL = "mimo-v2.5.serve-mixedlen"
NEW = ("serve_mfu.mixedlen", "decode_hbm_roofline.mixedlen", "moe_expert_roofline.mixedlen",
       "window_kind_bytes_share.mixedlen", "decode_gather_over_reach.mixedlen")
TINY_SIZES = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 1, "head_dim": 24,
    "v_head_dim": 16, "swa_num_attention_heads": 4, "swa_num_key_value_heads": 2,
    "swa_head_dim": 24, "swa_v_head_dim": 16, "sliding_window": 8,
    "intermediate_size": 96, "moe_intermediate_size": 32, "num_experts_per_tok": 2,
    "n_routed_experts_published": 8, "n_routed_experts": 4, "held_experts": [0, 1, 2, 3],
    "hybrid_layer_pattern": [0, 1, 1, 0, 1], "moe_layer_freq": [0, 1, 1, 1, 1],
    "num_hidden_layers": 5, "vocab_size": 128, "max_position_embeddings": 64,
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
    mix.update(prompt={"dist": "lognormal", "median": 12, "sigma": 1.0, "min": 4, "max": 40},
               output={"dist": "fixed", "value": 6}, calibration_tokens=3, trace_seconds=0.3)
    return spec.Cell(name=CELL, chips=1, config=config, traffic=mix,
                     deploy=copy.deepcopy(TINY_DEPLOY), end_to_end=real.end_to_end,
                     per_layer=real.per_layer)


def test_the_cell_has_its_files_and_reports_what_the_issue_names():
    cell = spec.load_cell(CELL)
    assert cell.traffic["runner"] == "serve_mixed" and cell.chips == 1
    assert callable(spec.runner("serve_mixed"))
    assert {m["name"] for m in cell.end_to_end} == {"serve_tokens_per_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW) <= names and {
        "warmup_s", "batch_occupancy.generate", "decode_step_p50_ms.generate",
        "device_idle.generate", "prefill_share.longdoc", "kv_bytes_per_resident_token.longdoc",
        "expert_load_max_over_mean.longdoc"} <= names
    for m in cell.per_layer:
        assert callable(spec.reader("layer_metrics", m["name"]))
        if m["name"] in NEW:
            assert m["moves"] == "serve_tokens_per_s" and m["workloads"] == [CELL]
    config = cell.config
    for key in config["reduced"]:
        assert config[key] != config["published"][key]
    assert config["hybrid_layer_pattern"] == config["published"]["hybrid_layer_pattern"][:7]
    assert config["moe_layer_freq"] == config["published"]["moe_layer_freq"][:7]
    assert len(config["held_experts"]) == config["n_routed_experts"] == 16
    assert len(config["assumed"]) >= 8 and config["param_dtype"] == "bfloat16"
    assert "16 chips" in config["deployment"]
    assert hasattr(spec.reference(config["reference"]), "forward")
    # the traffic exactly as the issue names it
    mix, serve = cell.traffic, cell.deploy["serve_config"]
    assert mix["prompt"] == {"dist": "lognormal", "median": 2048, "sigma": 1.0,
                             "min": 128, "max": 24576}
    assert mix["output"] == {"dist": "fixed", "value": 512} and mix["clients_per_slot"] == 2
    assert mix["kind"] == "closed" and mix["slo"] == "batch" and not mix.get("prefixes")
    assert serve == {"max_slots": 32, "kv_block_size": 16, "kv_blocks": 50176,
                     "prefix_cache": True, "prefill_chunk_tokens": 512,
                     "prefill_bucket_floor": 512, "kv_bucket_floor": 2048}


def test_the_roofline_counts_the_bytes_of_the_issues_arithmetic():
    s = ops.sizes(spec.load_cell(CELL).config)
    assert ops.resident_token_bytes(s, 2) == 5120        # 2 full layers x (768 + 512) x 2 B
    assert ops.window_token_bytes(s, 2) == 25600         # 5 window layers x (1,536 + 1,024) x 2 B
    assert ops.one_shape_token_bytes(s, 2) == 35840      # one row shape for every layer
    assert ops.attention_params(s, "full") == 89_128_960
    assert ops.attention_params(s, "window") == 94_371_840 + 64
    assert ops.expert_params(s) == 25_165_824 and ops.expert_layers(s) == 6
    n = ops.param_count(s)
    assert abs(n - 3.430e9) < 1e6 and abs(2 * n - 6.86e9) < 5e6   # 6.86 GB in bf16
    # a decode step reads every weight but the embedding and the experts not hit
    all_hit = ops.decode_step_bytes(s, itemsize=2, experts_hit=6 * 16, reach_bytes=0)
    assert all_hit == 2 * (n - s["vocab"] * s["d"])
    # the window layers reach 128 rows whatever the context
    assert ops.cache_reach_bytes(s, itemsize=2, contexts=[1000, 50]) == \
        5120 * 1050 + 25600 // 5 * 5 * (128 + 50)
    # causal attention inside each kind's reach, keys of 192 beside values of 128
    per_key = 2 * 64 * (192 + 128)
    assert ops.attention_flops(s, 0, 3) == 7 * per_key * 6
    assert ops.attention_flops(s, 1000, 1001) == per_key * (2 * 1001 + 5 * 128)


def synthetic_run(**counters) -> record.Run:
    """A ``Run`` as a traced chip run would hand the readers, made up:
    one decode program, two gmm calls, the counters of a short window."""
    from benchmark import peaks, trace_reduce

    cell = spec.load_cell(CELL)
    s = ops.sizes(cell.config)
    plane = trace_reduce.PlaneSummary(
        name="/device:TPU:0", span_s=1.0, busy_s=0.9,
        ops={"%gmm.1 = bf16[256,2048]{1,0} custom-call(x)": [36, 0.036],
             "%gmm.2 = bf16[4096,2048]{1,0} custom-call(x)": [18, 0.054]},
        modules={"jit_paged_decode_impl_K32768": [0.05, 0.05]})
    return record.Run(
        cell=cell, window_s=10.0, requests=[
            {"ok": True, "prompt_len": 2048, "n_tokens": 512},
            {"ok": True, "prompt_len": 200, "n_tokens": 512}],
        counters=counters,
        model={"sizes": s, "param_itemsize": 2, "max_slots": 32, "expert_layers": 6,
               "slice": {"decode_steps": 2, "moe_decode_experts_hit": 2 * 6 * 12,
                         "moe_pairs_held": 2 * 6 * 16 + 6 * 256,
                         "kv_sampled_reach_bytes": 2 * 5120 * 32 * 3000}},
        trace=trace_reduce.TraceSummary(planes=[plane], host_window_s=1.0),
        peaks=peaks.peaks_for("TPU v5 lite"))


COUNTERS = {
    "serving/moe_pairs_held": 20000, "serving/kv_sampled_bytes": 4000,
    "serving/kv_sampled_bytes_kind_full": 3000, "serving/kv_sampled_bytes_kind_window128": 1000,
    "serving/kv_sampled_tokens": 500, "serving/decode_gathered_tokens": 4000,
}


def test_the_new_readers_return_a_number_on_a_synthetic_run():
    run = synthetic_run(**COUNTERS)
    got = {name: spec.reader("layer_metrics", name)(run) for name in NEW}
    assert got["window_kind_bytes_share.mixedlen"] == 25.0
    assert got["decode_gather_over_reach.mixedlen"] == 8.0
    assert all(0 < got[name] < 100 for name in NEW if name.endswith("roofline.mixedlen")
               or name.startswith("serve_mfu")), got
    s = run.model["sizes"]
    flops = ops.request_flops(s, 2048, 512) + ops.request_flops(s, 200, 512) \
        + 20000 * ops.pair_flops(s)
    assert got["serve_mfu.mixedlen"] == pytest.approx(100 * flops / 10.0 / 197e12)
    step = ops.decode_step_bytes(s, itemsize=2, experts_hit=72, reach_bytes=5120 * 32 * 3000)
    assert got["decode_hbm_roofline.mixedlen"] == pytest.approx(100 * step / 819e9 / 0.05)


@pytest.mark.parametrize("name", NEW)
def test_a_new_reader_returns_none_without_its_counters(name):
    """On a program that lacks what ISSUE 34 adds (the parent), or on a
    run without a trace, a reader leaves its metric out and raises nothing."""
    bare = record.Run(cell=spec.load_cell(CELL), window_s=10.0,
                      requests=[{"ok": True, "prompt_len": 8, "n_tokens": 2}])
    assert spec.reader("layer_metrics", name)(bare) is None
    old = synthetic_run(**{k: v for k, v in COUNTERS.items()
                           if "kind_" not in k and "gathered" not in k and "pairs" not in k})
    old.model.pop("slice")
    old.model.pop("expert_layers")
    assert spec.reader("layer_metrics", name)(old) is None


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
    assert detail["prefix_reused_tokens_in_check"] == 0
    assert detail["free_lists_whole"] and detail["free_lists_whole_after_window"]
    assert detail["rows"] == 3 * (3 + 4) and detail["rows_near_tie"] == 0
    assert max(detail["worst"].values()) < 1e-3 and detail["extra_prompts"] == 0
    assert detail["filler_requests"] == 2 and detail["live_slots_mean_in_check"] > 1
    assert [k["kind"] for k in run.notes["pool_kinds"]] == ["full", "window8"]
    assert [k["rows"] for k in run.notes["pool_kinds"]] == [[24, 16], [48, 32]]
    # the run's record says what each program family ran by kind (span/kind_plan)
    plans = run.notes["kind_plan"]
    assert {p["family"] for p in plans} == {"prefill", "extend", "decode"}
    assert all([k["sink"] for k in p["kinds"]] == [False, True] for p in plans)
    assert not [c for c in run.compiles_in_window if "_impl" in c]
    json.dumps(line)


def test_traced_run_reads_the_counters_and_leaves_out_the_device():
    cell = tiny_cell()
    run = run_mod.execute(cell, seed=5, seconds=1.0, trace=True, t_start=time.perf_counter())
    got = run_mod.result_line(run, trace=True)["metrics"]
    assert {"decode_step_p50_ms.generate", "batch_occupancy.generate", "warmup_s",
            "kv_bytes_per_resident_token.longdoc", "expert_load_max_over_mean.longdoc",
            "window_kind_bytes_share.mixedlen", "decode_gather_over_reach.mixedlen"} <= set(got)
    piece = run.model["slice"]
    assert piece["samples"] >= 2 and piece["decode_steps"] > 0
    assert piece["kv_sampled_reach_bytes"] > 0
    # rows by kind: far under what one row shape for every layer would hold
    s = run.model["sizes"]
    assert run.model["kv_bytes_token"] == ops.one_shape_token_bytes(s, 4)
    assert 0 < got["window_kind_bytes_share.mixedlen"]["value"] < 100
    assert got["decode_gather_over_reach.mixedlen"]["value"] >= 1.0
    # a CPU trace has no device plane, and peaks are never made up: nothing under their names
    assert not {"device_idle.generate", "decode_hbm_roofline.mixedlen",
                "moe_expert_roofline.mixedlen", "serve_mfu.mixedlen"} & set(got)


def test_the_control_fails_by_the_cells_own_comparison():
    """The reference on int8-rounded weights, through check_outputs: not
    correct, where the same engine against the float32 reference is."""
    from benchmark import control_serve_kinds, control_serve_mixed

    assert control_serve_mixed.main is control_serve_kinds.main
    cell = tiny_cell()
    cell.config["correct"].update(logit_abs=2e-4)
    out = control_serve_kinds.control(cell, seed=2**31 + 5)
    assert out["correct"] is True, out["correct_detail"]
    assert out["control_correct"] is False
    assert min(out["control_correct_detail"]["worst"].values()) > 2e-4
