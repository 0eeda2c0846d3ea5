"""A cell, a configuration, a mix and a per-layer metric dropped in as
new files plus entries are found by name; no existing file is touched."""

import hashlib
import json
import os
import shutil

from benchmark import record, spec


def _digests(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            if "__pycache__" not in d:
                p = os.path.join(d, f)
                out[os.path.relpath(p, root)] = hashlib.sha256(open(p, "rb").read()).hexdigest()
    return out


def test_new_files_are_found_without_touching_an_existing_one(tmp_path):
    root = str(tmp_path)
    shutil.copytree(spec.HERE, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(os.path.join(root, "benchmark"))
    bench = spec.load_benchmark()

    b = os.path.join(root, "benchmark")
    config = json.load(open(os.path.join(b, "configs", "gpt2-124m.json")))
    config.update(name="gpt2-medium", n_layer=24, n_embd=1024, n_head=16)
    json.dump(config, open(os.path.join(b, "configs", "gpt2-medium.json"), "w"))
    mix = json.load(open(os.path.join(b, "traffic", "serve-chat.json")))
    mix["rate_profile"] = [[0, 1], [0.33, 3], [0.67, 1]]
    json.dump(mix, open(os.path.join(b, "traffic", "serve-chat-burst.json"), "w"))
    json.dump({"serve_config": {"max_slots": 16, "kv_block_size": 16, "kv_blocks": 512},
               "rate_per_s": 9.0},
              open(os.path.join(b, "cells", "gpt2-medium.serve-chat-burst.json"), "w"))
    with open(os.path.join(b, "layer_metrics", "prefix_hit_share.py"), "w") as f:
        f.write("def read(run):\n"
                "    reused = run.counters.get('serving/prefix_reused_tokens')\n"
                "    total = run.counters.get('serving/prefill_tokens')\n"
                "    return None if not total else 100.0 * reused / total\n")

    bench["configs"].append({"name": "gpt2-medium", "source": "x", "reduced": [], "why": "y",
                             "file": "benchmark/configs/gpt2-medium.json"})
    bench["workloads"].append({"name": "gpt2-medium.serve-chat-burst", "config": "gpt2-medium",
                               "traffic": "serve-chat-burst", "chips": 1, "why": "z"})
    bench["per_layer"].append({"name": "prefix_hit_share", "unit": "%", "better": "higher",
                               "source": "program_counter", "layer": "engine",
                               "moves": "serve_tokens_per_s",
                               "workloads": ["gpt2-medium.serve-chat-burst"]})
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))

    cell = spec.load_cell("gpt2-medium.serve-chat-burst", root=root)
    assert cell.config["n_layer"] == 24 and cell.traffic["rate_profile"][1] == [0.33, 3]
    assert cell.deploy["rate_per_s"] == 9.0
    assert [m["name"] for m in cell.per_layer if "workloads" in m] == ["prefix_hit_share"]
    assert spec.program_config(cell.config).num_layers == 24
    assert callable(spec.runner(cell.traffic["runner"], root=root))
    read = spec.reader("layer_metrics", "prefix_hit_share", root=root)
    run = record.Run(cell=cell, counters={"serving/prefix_reused_tokens": 30,
                                           "serving/prefill_tokens": 120})
    assert read(run) == 25.0
    assert read(record.Run(cell=cell)) is None       # nothing to read: left out of the line
    # an old cell still loads, and sees none of the new metric
    old = spec.load_cell(bench["workloads"][0]["name"], root=root)
    assert "prefix_hit_share" not in [m["name"] for m in old.per_layer]
    after = _digests(os.path.join(root, "benchmark"))
    assert {k: v for k, v in after.items() if k in before} == before


def test_every_metric_and_cell_of_benchmark_json_has_its_files():
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert callable(spec.runner(cell.traffic["runner"]))
        assert len(cell.end_to_end) >= 2 and len(cell.per_layer) >= 1
        assert os.path.exists(os.path.join(spec.HERE, "reference", cell.config["reference"] + ".py"))
        names = {m["name"] for m in cell.end_to_end}
        assert all(m["moves"] in names for m in cell.per_layer), w["name"]
    for m in bench["end_to_end"]:
        assert callable(spec.reader("end_to_end", m["name"]))
    for m in bench["per_layer"]:
        assert callable(spec.reader("layer_metrics", m["name"]))
