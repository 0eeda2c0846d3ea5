"""Tiny stand-ins for the CPU tests: the same runners, readers and
generator at a width no one deploys. Nothing these produce is a device
number; no result of theirs is printed under a device metric's name."""

from __future__ import annotations

import copy
import json
import os

from benchmark import spec

TINY_SIZES = {"n_layer": 2, "n_embd": 32, "n_head": 2, "n_positions": 64, "n_ctx": 64,
              "vocab_size": 128}
TINY_MIXES = {
    "train": {"runner": "train", "kind": "train", "first_steps": 1, "rate_steps": 2,
              "trace_lead_steps": 1, "trace_steps": 2, "loss_falls_min_seconds": 1e9,
              "program_fields": {"global_batch_size": 2, "log_every": 2}},
    "open": {"runner": "serve", "kind": "open", "slo": "interactive",
             "prefixes": {"lengths": [16, 32], "weights": [0.6, 0.4]},
             "prompt": {"dist": "lognormal", "median": 6, "sigma": 0.5, "min": 2, "max": 12},
             "output": {"dist": "lognormal", "median": 4, "sigma": 0.5, "min": 2, "max": 8},
             "late_limit_ms": 1e9, "trace_seconds": 0.3},
    "closed": {"runner": "serve", "kind": "closed", "slo": "batch",
               "prompt": {"dist": "uniform", "min": 4, "max": 12},
               "output": {"dist": "fixed", "value": 6},
               "clients_per_slot": 2, "trace_seconds": 0.3},
}
TINY_DEPLOY = {"serve_config": {"max_slots": 2, "kv_block_size": 16, "kv_blocks": 12,
                                "prefix_cache": True}, "rate_per_s": 8.0}


# What each kind of tiny cell reports: the readers under benchmark/, by
# name — also those whose cells BENCHMARK.json does not hold yet.
TINY_METRICS = {
    "train": (["train_tokens_per_s", "setup_s"],
              ["warmup_s", "train_step_device_ms", "train_mfu", "flash_fwd_roofline.train",
               "device_idle.train"]),
    "open": (["ttft_p95_ms", "tpot_p95_ms", "setup_s"],
             ["warmup_s", "queue_wait_p95_ms.chat", "decode_step_p50_ms.chat",
              "prefill_p50_ms.chat", "device_idle.chat"]),
    "closed": (["serve_tokens_per_s", "setup_s"],
               ["warmup_s", "batch_occupancy.generate", "decode_step_p50_ms.generate",
                "decode_hbm_roofline.generate", "device_idle.generate"]),
}


def tiny_cell(kind: str, name: str = "tiny.cell") -> spec.Cell:
    """A cell of ``kind`` (train | open | closed) on the gpt2-124m file
    cut to a toy width."""
    with open(os.path.join(spec.HERE, "configs", "gpt2-124m.json")) as f:
        config = json.load(f)
    config.update(TINY_SIZES)
    config["correct"] = dict(config["correct"], reference_len=64, prompt_lens=[20, 36],
                             stream_tokens=4, logit_abs=1e-3, train_loss_abs=0.05)
    end_to_end, per_layer = TINY_METRICS[kind]
    return spec.Cell(
        name=name, chips=1, config=config, traffic=copy.deepcopy(TINY_MIXES[kind]),
        deploy=copy.deepcopy(TINY_DEPLOY),
        end_to_end=[{"name": n, "unit": "x"} for n in end_to_end],
        per_layer=[{"name": n, "unit": "x"} for n in per_layer],
    )
