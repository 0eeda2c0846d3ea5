#!/usr/bin/env python
"""MoE grouped-path on-chip component diagnosis.

The round-5 first live window measured the rewritten sort-based
grouped MoE bench at 20.7k tok/s (rel_mfu 0.00026) — 3x SLOWER than
the round-4 scatter formulation it replaced (62.6k, rel_mfu 0.00154)
and ~170x below dense GPT-2, even though at the bench shape
([16384, 768] x [8, 768, 3072], every dim %128 == 0) the megablox gmm
Pallas kernel should engage. Window values were stable (±0.3%), so the
compiled program itself is slow, not dispatch.

This tool times each component of the grouped path in isolation on the
chip so the regression can be attributed to ONE of: the gmm kernel
forward, its custom-vjp backward (tgmm), the argsort-based slotting,
the permutation gathers, or the surrounding step. For each it also
times the obvious alternative (ragged_dot, scatter impl) at the same
shape.

Usage: python tools/moe_diag.py [--budget=SECS]
Emits ONE JSON line (always, partial on budget/deadline like bench.py).
"""

import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402
from tools.diag_common import (  # noqa: E402
    make_emit, parse_budget, start_watchdog,
)

OUT: dict = {"diag": "moe_components"}
_emit = make_emit(OUT)

# The TPU bench shape (bench.bench_moe): GPT-2 124M, batch 8, seq 1024,
# E=8 top-2 -> n·k = 16384 rows through d=768 / ff=3072 experts.
N_TOK, TOP_K, E, D, FF = 8192, 2, 8, 768, 3072
ROWS = N_TOK * TOP_K


def _timeit(fn, *args, iters: int = 10, warmup: int = 3) -> float:
    """Median wall ms per call of jitted fn (block_until_ready)."""
    import jax

    jfn = jax.jit(fn)
    out = jfn(*args)
    for _ in range(warmup - 1):
        out = jfn(*args)
    jax.block_until_ready(out)
    ts = []
    for _ in range(iters):
        t0 = time.monotonic()
        jax.block_until_ready(jfn(*args))
        ts.append((time.monotonic() - t0) * 1e3)
    return round(statistics.median(ts), 4)


def _component_benches(deadline: float) -> None:
    import jax
    import jax.numpy as jnp
    from jax import lax, random

    from tensorflow_examples_tpu.parallel import moe

    # CPU rehearsal uses tiny shapes (the TPU ones would take minutes
    # per ragged_dot on this 1-core host); the on-chip run uses the
    # exact bench shape.
    tpu = bench.BACKEND == "tpu"
    rows, d, ff = (ROWS, D, FF) if tpu else (256, 64, 128)
    n_tok, bsz, seq = (N_TOK, 8, 1024) if tpu else (rows // TOP_K, 2, 64)

    k0 = random.PRNGKey(0)
    lhs = random.normal(k0, (rows, d), jnp.bfloat16)
    rhs_in = random.normal(k0, (E, d, ff), jnp.bfloat16)
    rhs_out = random.normal(k0, (E, ff, d), jnp.bfloat16)
    h = random.normal(k0, (rows, ff), jnp.bfloat16)
    sizes_even = jnp.full((E,), rows // E, jnp.int32)
    expert_ids = random.randint(k0, (rows,), 0, E, jnp.int32)

    def gmm_like(lo, hi):  # pin backend decision out of the way
        from jax.experimental.pallas.ops.tpu.megablox import ops as mb
        return mb.gmm(lo, hi, sizes_even, lo.dtype)

    def gmm_tiling_sweep():
        # The kernel's default (128,128,128) was never swept on v5e;
        # if gmm_fwd_in reads slow, this says whether tiling is why.
        from jax.experimental.pallas.ops.tpu.megablox import ops as mb
        res = {}
        for t in ((128, 128, 128), (256, 256, 256), (512, 256, 256),
                  (512, 512, 512), (1024, 768, 512)):
            try:
                # positional like parallel/moe.py: gmm is a custom_vjp
                # with nondiff_argnums — tiling= by keyword happens to
                # work today but is not contract across jax bumps.
                res["x".join(map(str, t))] = _timeit(
                    lambda lo, hi, _t=t: mb.gmm(
                        lo, hi, sizes_even, lo.dtype, _t),
                    lhs, rhs_in)
            except Exception as e:  # noqa: BLE001 — a tiling may be
                res["x".join(map(str, t))] = f"error: {type(e).__name__}: {e}"
        return res

    comp: dict = {}
    steps = ([
        ("gmm_fwd_in", lambda: _timeit(gmm_like, lhs, rhs_in)),
        ("gmm_fwd_out", lambda: _timeit(gmm_like, h, rhs_out)),
        ("gmm_fwdbwd_in", lambda: _timeit(
            jax.grad(lambda lo, hi: gmm_like(lo, hi).astype(
                jnp.float32).sum(), argnums=(0, 1)), lhs, rhs_in)),
        ("gmm_tiling_sweep", gmm_tiling_sweep),
    ] if tpu else []) + [
        ("ragged_fwd_in", lambda: _timeit(
            lambda lo, hi: lax.ragged_dot(lo, hi, sizes_even), lhs, rhs_in)),
        ("argsort_rows", lambda: _timeit(
            lambda ids: jnp.argsort(jnp.argsort(ids)), expert_ids)),
        ("pair_sort", lambda: _timeit(
            lambda ids: moe._pair_sort(
                [ids[:n_tok], ids[n_tok:]], E), expert_ids)),
        ("ragged_fwdbwd_in", lambda: _timeit(
            jax.grad(lambda lo, hi: lax.ragged_dot(
                lo, hi, sizes_even).astype(jnp.float32).sum(),
                argnums=(0, 1)), lhs, rhs_in)),
        ("dense_ffn_ref", lambda: _timeit(
            lambda t, a, b: (t @ a) @ b, lhs[:n_tok],
            rhs_in[0], rhs_out[0])),
    ]
    for name, run in steps:
        if time.monotonic() > deadline:
            OUT["truncated"] = True
            return
        try:
            comp[name] = run()
        except Exception as e:  # noqa: BLE001 — name the failing piece
            comp[name] = f"error: {type(e).__name__}: {e}"
        OUT["components_ms"] = comp
        _emit()

    # The full MoE block fwd and fwd+bwd, both impls, outside any
    # Trainer machinery: isolates the layer from the train step.
    k1, k2 = random.split(k0)
    gate_w = random.normal(k1, (d, E), jnp.float32)
    b_in = jnp.zeros((E, ff), jnp.bfloat16)
    b_out = jnp.zeros((E, d), jnp.bfloat16)
    x = random.normal(k2, (bsz, seq, d), jnp.bfloat16)

    for impl in ("grouped", "scatter"):
        if time.monotonic() > deadline:
            OUT["truncated"] = True
            return

        def blk(xx, gw, wi, wo):
            out, aux, _ = moe.moe_ffn(
                gw, wi, b_in, wo, b_out, xx, top_k=TOP_K, impl=impl)
            return out.astype(jnp.float32).sum() + aux

        try:
            comp[f"block_fwd_{impl}"] = _timeit(
                blk, x, gate_w, rhs_in, rhs_out)
            comp[f"block_fwdbwd_{impl}"] = _timeit(
                jax.grad(blk, argnums=(0, 1, 2, 3)),
                x, gate_w, rhs_in, rhs_out)
        except Exception as e:  # noqa: BLE001
            comp[f"block_{impl}"] = f"error: {type(e).__name__}: {e}"
        OUT["components_ms"] = comp
        _emit()


def _full_step(impl: str, steps: int = 10) -> dict:
    """The bench_moe train step with the impl pinned — the config is
    bench.moe_bench_config, NOT a copy, so the timing here explains
    the exact moe_top2_tokens_per_sec program."""
    from tensorflow_examples_tpu.data.memory import train_iterator
    from tensorflow_examples_tpu.train.loop import Trainer
    from tensorflow_examples_tpu.workloads import gpt2

    cfg = bench.moe_bench_config(moe_impl=impl)
    batch, seq = cfg.global_batch_size, cfg.seq_len
    trainer = Trainer(gpt2.make_task(cfg), cfg, mesh=bench._chip_mesh())
    ds, _ = gpt2.datasets(cfg)
    it = train_iterator(ds, batch, seed=0)
    batches = [trainer._put_batch(next(it)) for _ in range(4)]
    dts = bench._time_steps(trainer, batches, steps, warmup=3)
    med = statistics.median(dts)
    return {
        "impl": impl,
        "ms_per_step": round(med / steps * 1e3, 3),
        "tokens_per_sec": round(batch * seq * steps / med, 1),
    }


def main() -> int:
    budget = parse_budget(sys.argv[1:], default=600)
    deadline = time.monotonic() + budget - 30
    bench._require_tpu()  # exits non-zero off the TPU
    bench.BACKEND = "tpu"
    watchdog = start_watchdog(budget, _emit)
    try:
        OUT["backend"] = bench.BACKEND
        OUT["probe_tflops"] = round(bench._probe_quick(), 2)
        OUT["launch_us"] = round(bench._probe_launch_us(), 2)
        _component_benches(deadline)
        OUT["full_step"] = []
        for impl in ("grouped", "scatter"):
            if time.monotonic() > deadline:
                OUT["truncated"] = True
                break
            OUT["full_step"].append(_full_step(impl))
            _emit()
        OUT["complete"] = not OUT.get("truncated", False)
    except Exception as e:  # noqa: BLE001 — partials must still emit
        OUT["error"] = f"{type(e).__name__}: {e}"
    watchdog.cancel()
    _emit()
    return 1 if "error" in OUT else 0


if __name__ == "__main__":
    sys.exit(main())
