#!/usr/bin/env python
"""On-chip flash-attention block-size sweep.

The Pallas flash kernels pick their tiles from constants in
ops/attention.py (``flash_blocks``), set from a per-kernel sweep on a
v5e at GPT-2 124M's training shape (PERF.md §6 has the table). This
tool is the end-to-end check of such a choice: it sweeps explicit
block_q x block_kv (which set all three kernels' tile) over the benched
shapes, forward AND forward+backward, on the real chip.

TPU only (interpret-mode cells would time Python, not the chip): off
the chip it exits non-zero before timing anything. Emits ONE JSON line
(always-emit watchdog, bench.py pattern); a truncated snapshot still
carries every completed (shape, config) cell.

Usage: python tools/flash_tune.py [--budget=SECS]
"""

import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402  (repo-root bench.py: backend resolution, probes)
from tools.diag_common import (  # noqa: E402
    make_emit, parse_budget, start_watchdog,
)

OUT: dict = {"diag": "flash_tune", "shapes": []}
_emit = make_emit(OUT)

# (name, batch, heads, seq, head_dim, causal, timing iters/window).
# gpt2/gpt2_long mirror the bench shapes; bert's seq 128 admits only
# one block config so it is not worth sweeping.
SHAPES = [
    ("gpt2_b8_s1024", 8, 12, 1024, 64, True, 30),
    ("gpt2_long_b2_s4096", 2, 12, 4096, 64, True, 8),
]
BLOCKS = (128, 256, 512)


def _time(fn, args, iters: int, windows: int = 3) -> float:
    """Median ms per call over ``windows`` timing windows."""
    import jax

    jax.block_until_ready(fn(*args))  # compile + warm
    ts = []
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        ts.append((time.perf_counter() - t0) / iters)
    return statistics.median(ts) * 1e3


def _sweep_shape(name, b, h, s, d, causal, iters, deadline) -> dict:
    import jax
    import jax.numpy as jnp

    from tensorflow_examples_tpu.ops.attention import flash_attention

    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, h, s, d), jnp.bfloat16)
    k = jax.random.normal(kk, (b, h, s, d), jnp.bfloat16)
    v = jax.random.normal(kv, (b, h, s, d), jnp.bfloat16)
    rec = {"name": name, "batch": b, "heads": h, "seq": s, "head_dim": d,
           "causal": causal, "cells": []}
    for bq in BLOCKS:
        for bk in BLOCKS:
            if s % bq or s % bk:
                continue
            if time.monotonic() > deadline:
                rec["truncated"] = True
                return rec

            def fwd(q, k, v, _bq=bq, _bk=bk):
                return flash_attention(
                    q, k, v, causal=causal, block_q=_bq, block_kv=_bk
                ).mean()

            fwd_j = jax.jit(fwd)
            bwd_j = jax.jit(jax.grad(fwd, argnums=(0, 1, 2)))
            cell = {"block_q": bq, "block_kv": bk}
            cell["fwd_ms"] = round(_time(fwd_j, (q, k, v), iters), 4)
            cell["fwdbwd_ms"] = round(_time(bwd_j, (q, k, v), iters), 4)
            rec["cells"].append(cell)
    if rec["cells"]:
        rec["best_fwd"] = min(rec["cells"], key=lambda c: c["fwd_ms"])
        rec["best_fwdbwd"] = min(rec["cells"], key=lambda c: c["fwdbwd_ms"])
    return rec


def main() -> int:
    budget = parse_budget(sys.argv[1:])
    deadline = time.monotonic() + budget
    bench._require_tpu()  # exits non-zero off the TPU
    bench.BACKEND = "tpu"
    watchdog = start_watchdog(budget, _emit)
    try:
        OUT["backend"] = bench.BACKEND
        OUT["probe_tflops"] = round(bench._probe_quick(), 2)
        OUT["launch_us"] = round(bench._probe_launch_us(), 2)
        for shape in SHAPES:
            if time.monotonic() > deadline:
                OUT["truncated"] = True
                break
            OUT["shapes"].append(_sweep_shape(*shape, deadline))
        # A partial table is not a table (the whole point is a full one).
        OUT["complete"] = (
            "truncated" not in OUT
            and len(OUT["shapes"]) == len(SHAPES)
            and all(
                not s.get("truncated") and s.get("cells")
                for s in OUT["shapes"]
            )
        )
    except Exception as e:  # noqa: BLE001 — partials must still emit
        OUT["error"] = f"{type(e).__name__}: {e}"
    watchdog.cancel()
    _emit()
    return 1 if "error" in OUT else 0


if __name__ == "__main__":
    sys.exit(main())
