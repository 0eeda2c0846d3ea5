#!/usr/bin/env python
"""Structural fingerprint of a workload's compiled train step.

Prints one JSON line with XLA cost-model FLOPs/bytes/transcendentals,
the optimized-HLO instruction count, and an op histogram — all
rig-speed-independent — so two repo versions can be diffed for
compiled-program changes (`git worktree add /tmp/old <rev>`, run this
in both, diff the lines).

Used to resolve the round-4 bert 0.87x / cifar10 0.42x sub-floor
readings: both steps fingerprinted identically between the round-3
floor-stamp commit (d99bceb) and HEAD — FLOPs equal to <0.0001%, op
histograms within 0.3%, HEAD marginally leaner — so the deficits were
on the rig's side, not in the code.

Usage: python tools/hlo_fingerprint.py {cifar10|bert|mnist}
Compiles on the CPU backend: structure, not speed, is the signal.
gpt2 is deliberately unsupported: its bench program runs the Pallas
flash kernel + fused CE, which on CPU compile as interpret-mode scan
loops structurally unrelated to the TPU custom calls — a fingerprint
of that would adjudicate the wrong program.
"""

import collections
import dataclasses
import json
import os
import re
import sys


def main() -> int:
    if len(sys.argv) != 2 or sys.argv[1] not in ("cifar10", "bert", "mnist"):
        print(__doc__)
        return 2
    which = sys.argv[1]

    import jax

    jax.config.update("jax_platforms", "cpu")
    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )

    from tensorflow_examples_tpu.core.mesh import MeshConfig, create_mesh
    from tensorflow_examples_tpu.data.memory import train_iterator
    from tensorflow_examples_tpu.train.loop import Trainer

    # The TPU bench shape of each workload (bench.py), so the
    # fingerprint tracks the program the floors measure.
    common = dict(log_every=10**9, checkpoint_every=0, eval_every=0,
                  train_steps=10**6, watchdog_secs=0, precision="bf16",
                  dropout=0.0)  # bench.py sets dropout=0.0 everywhere
    if which == "cifar10":
        from tensorflow_examples_tpu.data.sources import synthetic_images
        from tensorflow_examples_tpu.workloads import cifar10 as wl

        cfg_cls, batch = wl.Cifar10Config, 128
        make_ds = lambda cfg: synthetic_images(
            n=256, shape=(32, 32, 3), num_classes=10, seed=0
        )
    elif which == "mnist":
        from tensorflow_examples_tpu.data.sources import synthetic_images
        from tensorflow_examples_tpu.workloads import mnist as wl

        cfg_cls, batch = wl.MnistConfig, 256
        make_ds = lambda cfg: synthetic_images(
            n=256, shape=(28, 28, 1), num_classes=10, seed=0
        )
    else:
        from tensorflow_examples_tpu.workloads import bert_glue as wl

        cfg_cls, batch = wl.BertGlueConfig, 32
        make_ds = lambda cfg: wl.datasets(cfg)[0]

    fields = {f.name for f in dataclasses.fields(cfg_cls)}
    cfg = cfg_cls(
        global_batch_size=batch,
        **{k: v for k, v in common.items() if k in fields},
    )
    mesh = create_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    trainer = Trainer(wl.make_task(cfg), cfg, mesh=mesh)
    it = train_iterator(make_ds(cfg), cfg.global_batch_size, seed=0)
    dev_batch = trainer._put_batch(next(it))
    c = trainer._train_step.lower(trainer.state, dev_batch).compile()
    ca = c.cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    hlo = c.as_text()
    # Opcode after `= <type>`: the type may be a tuple — possibly
    # NESTED, e.g. a while carrying `(f32[2]{0}, (s32[], u32[]))` —
    # and opcodes may be hyphenated (`all-reduce`, `get-tuple-element`).
    # A regex `\([^)]*\)` stops at the first `)`, silently dropping
    # nested-tuple ops (exactly the control-flow ops a perf diff cares
    # about), so tuple types are skipped by balanced-paren scan.
    def _opcodes(text):
        for line in text.splitlines():
            m = re.search(r"=\s+", line)
            if not m:
                continue
            i, n = m.end(), len(line)
            if i < n and line[i] == "(":
                depth = 0
                while i < n:
                    depth += (line[i] == "(") - (line[i] == ")")
                    i += 1
                    if depth == 0:
                        break
                m2 = re.match(r"\s*([\w-]+)\(", line[i:])
            else:
                m2 = re.match(r"\S+\s+([\w-]+)\(", line[i:])
            if m2:
                yield m2.group(1)

    ops = collections.Counter(_opcodes(hlo))
    print(json.dumps({
        "workload": which,
        "batch": cfg.global_batch_size,
        "flops": ca.get("flops"),
        "bytes": ca.get("bytes accessed"),
        "transcendentals": ca.get("transcendentals"),
        "hlo_instructions": sum(ops.values()),
        "top_ops": sorted(ops.items(), key=lambda kv: -kv[1])[:18],
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
