"""Finds a cell's files by the names in BENCHMARK.json.

A cell is one entry of ``workloads``: a configuration under a traffic
mix. Everything that belongs to one configuration, one mix, one cell or
one metric sits in a file of its own, found here by name, so a later PR
adds files and entries and edits none:

    benchmark/configs/<config>.json       sizes as published + how the program is built from them
    benchmark/traffic/<mix>.json          parameters of the one general generator; names its runner
    benchmark/cells/<cell>.json           what a deployment must state (slots, pool, rate)
    benchmark/end_to_end/<metric>.py      read(run) -> float | None
    benchmark/layer_metrics/<metric>.py   read(run) -> float | None
    benchmark/reference/<arch>.py         plain float32 forward and loss
    benchmark/runners/<kind>.py           run(ctx) -> Run
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
from typing import Any

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
PKG = os.path.basename(HERE)  # this directory under any root (tests mirror the layout elsewhere)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


@dataclasses.dataclass
class Cell:
    """One cell with its three files read in."""

    name: str
    chips: int
    config: dict      # benchmark/configs/<config>.json
    traffic: dict     # benchmark/traffic/<mix>.json
    deploy: dict      # benchmark/cells/<cell>.json
    end_to_end: list[dict]   # BENCHMARK.json entries this cell reports
    per_layer: list[dict]


def reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, *, root: str = ROOT, bench: dict | None = None) -> Cell:
    bench = bench or load_benchmark(root)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        known = ", ".join(w["name"] for w in bench["workloads"])
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json (has: {known})")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
    deploy_path = os.path.join(root, PKG, "cells", name + ".json")
    return Cell(
        name=name,
        chips=int(entry["chips"]),
        config=_load_json(os.path.join(root, cfg_entry["file"])),
        traffic=_load_json(
            os.path.join(root, PKG, "traffic", entry["traffic"] + ".json")
        ),
        deploy=_load_json(deploy_path) if os.path.exists(deploy_path) else {},
        end_to_end=[m for m in bench["end_to_end"] if reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if reports(m, name)],
    )


def load_by_path(path: str, attr: str | None = None) -> Any:
    """Import a file whose name may hold dots (``ttft_p95_ms.py``,
    ``device_idle.chat.py``) and return the module or one attribute."""
    if not os.path.exists(path):
        raise SystemExit(f"missing {os.path.relpath(path, ROOT)}")
    mod_name = "_bench_" + os.path.basename(path)[:-3].replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return getattr(mod, attr) if attr else mod


def reader(kind_dir: str, metric: str, *, root: str = ROOT):
    """``read(run)`` of one metric: ``kind_dir`` is ``end_to_end`` or
    ``layer_metrics``."""
    return load_by_path(
        os.path.join(root, PKG, kind_dir, metric + ".py"), "read"
    )


def runner(kind: str, *, root: str = ROOT):
    return load_by_path(
        os.path.join(root, PKG, "runners", kind + ".py"), "run"
    )


def reference(arch: str, *, root: str = ROOT):
    return load_by_path(
        os.path.join(root, PKG, "reference", arch + ".py")
    )


def program_config(config: dict, **overrides):
    """The program's own config object for a configuration file: the
    class the file names, with the fields the file maps from the
    published keys, every other field at the program's default."""
    prog = config["program"]
    cls = getattr(importlib.import_module(prog["workload"]), prog["config_class"])
    fields = {dst: config[src] for dst, src in prog["fields"].items()}
    fields.update(overrides)
    return cls(**fields)


def fold_seed(seed: int, stream: int = 0) -> int:
    """``--seed`` may be any whole number a little over 2**31; the
    program's seeds are int32. One hash per stream, below 2**31 - 2."""
    import numpy as np

    state = np.random.SeedSequence([int(seed), int(stream)]).generate_state(1)
    return int(state[0]) % (2**31 - 3)
