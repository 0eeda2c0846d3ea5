#!/usr/bin/env python
"""Itemized collective census for the MoE EP train step: the residual
all-reduces in the dp2×model4 compiled step must be *attributed*, not
just counted.

Compiles the same MoE GPT-2 train step as bench.py's census probe on an
8-virtual-device dp2×model4 CPU mesh, then walks the optimized HLO and
classifies every collective instruction by

- **kind** (all-reduce / all-gather / all-to-all / reduce-scatter /
  collective-permute),
- **mesh axis**, decoded from ``replica_groups`` (on a dp2×model4 mesh
  with row-major device order, groups of 4 consecutive ids = ``model``,
  groups of stride-4 pairs = ``data``, the full set = both),
- **origin bucket**, from the ``op_name`` metadata XLA carries through
  from jaxpr equation names (router/aux math, expert dispatch,
  backward (transpose), optimizer update, train metrics, other).

Prints a human table plus one ``EP_CENSUS <json>`` line for tooling.
Run: ``python tools/ep_census.py`` (self-pins CPU + 8 devices).
"""

import collections
import json
import re
import sys


def _ids_to_axis(ids: list, n_devices: int, model: int) -> str:
    if not ids or all(len(g) <= 1 for g in ids):
        return "none"
    sizes = {len(g) for g in ids}
    if sizes == {n_devices}:
        return "data+model"
    first = sorted(ids[0])
    if len(first) == model and first == list(
        range(first[0], first[0] + model)
    ):
        return "model"
    return "data"


def classify_axis(line: str, n_devices: int, model: int) -> str:
    """Decode the mesh axis from an HLO replica_groups attribute.

    Handles both the literal ``{{0,1},{2,3}}`` form and the iota form
    ``[G,S]<=[dims]T(perm)`` (materialized with numpy: iota over
    prod(dims), reshape, transpose, flatten, regroup into G rows)."""
    g = re.search(r"replica_groups=(\{\{[^}]*\}(?:,\{[^}]*\})*\})", line)
    if g:
        ids = [
            [int(x) for x in grp.split(",") if x.strip() != ""]
            for grp in re.findall(r"\{([\d,]*)\}", g.group(1))
        ]
        return _ids_to_axis(ids, n_devices, model)
    g = re.search(
        r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?",
        line,
    )
    if g:
        import numpy as np

        ng, gs = int(g.group(1)), int(g.group(2))
        dims = [int(x) for x in g.group(3).split(",")]
        arr = np.arange(int(np.prod(dims))).reshape(dims)
        if g.group(4):
            arr = arr.transpose([int(x) for x in g.group(4).split(",")])
        ids = arr.reshape(ng, gs).tolist()
        return _ids_to_axis(ids, n_devices, model)
    return "?"


_BUCKET_RULES = (
    # (bucket, regex over op_name) — first match wins; ordered so the
    # backward pass is recognized before forward-ish keywords inside it.
    ("optimizer", re.compile(r"adamw?|lamb|lars|sgd|opt_update|scale_by")),
    ("metrics", re.compile(r"metrics|grad_norm|global_norm|loss_mean")),
    ("backward", re.compile(r"transpose\(|/vjp|backward|grad")),
    ("router/aux", re.compile(r"moe.*(route|gate|aux|pmean|softmax)|aux_loss")),
    ("ep_dispatch", re.compile(r"all_to_all|moe|expert")),
)


def classify_bucket(op_name: str) -> str:
    low = op_name.lower()
    for bucket, rx in _BUCKET_RULES:
        if rx.search(low):
            return bucket
    return "other"


def census(hlo: str, n_devices: int, model: int):
    rows = []
    # Definition sites only (the %name = shape opcode(...) form) — a
    # plain substring count also hits operand REFERENCES like
    # %all-reduce.12 and overcounts ~2-3x (the round-2/3 census did
    # exactly that). Shape is non-greedy so
    # tuple-shaped collectives (lax.all_to_all lowers to one) match,
    # and the async -start halves count once (-done is skipped).
    for m in re.finditer(
        r"^\s*(?:ROOT\s+)?%?[\w.\-]+ = (.+?) (all-reduce|all-gather|"
        r"all-to-all|reduce-scatter|collective-permute)(?:-start)?\(",
        hlo,
        re.M,
    ):
        line_end = hlo.find("\n", m.start())
        line = hlo[m.start(): line_end if line_end != -1 else None]
        shape, kind = m.group(1), m.group(2)
        axis = classify_axis(line, n_devices, model)
        op = re.search(r'op_name="([^"]*)"', line)
        op_name = op.group(1) if op else ""
        rows.append(
            {
                "kind": kind,
                "axis": axis,
                "bucket": classify_bucket(op_name),
                "shape": shape,
                "op_name": op_name[-160:],
            }
        )
    return rows


def main() -> int:
    import os

    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    )
    import jax

    jax.config.update("jax_platforms", "cpu")

    from tensorflow_examples_tpu.core.mesh import MeshConfig, create_mesh
    from tensorflow_examples_tpu.data.memory import train_iterator
    from tensorflow_examples_tpu.train.loop import Trainer
    from tensorflow_examples_tpu.workloads import gpt2

    cfg = gpt2.Gpt2Config(
        vocab_size=512, seq_len=128, num_layers=2, num_heads=4, d_model=64,
        dropout=0.0, moe_experts=8, moe_top_k=2, moe_every=1,
        global_batch_size=8, precision="f32", log_every=10**9,
        checkpoint_every=0, watchdog_secs=0,
    )
    mesh = create_mesh(MeshConfig(data=2, model=4))
    trainer = Trainer(gpt2.make_task(cfg, mesh), cfg, mesh=mesh)
    ds, _ = gpt2.datasets(cfg)
    batch = trainer._put_batch(next(train_iterator(ds, 8, seed=0)))
    hlo = trainer._train_step.lower(trainer.state, batch).compile().as_text()

    rows = census(hlo, n_devices=8, model=4)
    by_kind = collections.Counter(r["kind"] for r in rows)
    table = collections.Counter(
        (r["kind"], r["axis"], r["bucket"]) for r in rows
    )
    print(f"{'kind':<20} {'axis':<12} {'bucket':<12} count")
    for (kind, axis, bucket), cnt in sorted(table.items()):
        print(f"{kind:<20} {axis:<12} {bucket:<12} {cnt}")
    print()
    samples = {}
    for r in rows:
        samples.setdefault((r["kind"], r["axis"], r["bucket"]), []).append(
            (r["shape"], r["op_name"])
        )
    for key, items in sorted(samples.items()):
        print(f"--- {key} ({len(items)})")
        for shape, op in items[:3]:
            print(f"    {shape}  {op}")
    out = {
        "totals": dict(by_kind),
        "table": [
            {"kind": k, "axis": a, "bucket": b, "count": c}
            for (k, a, b), c in sorted(table.items())
        ],
    }
    print("EP_CENSUS " + json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
