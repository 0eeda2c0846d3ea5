"""``benchmark/sizing.py``'s analysis for a cell of ``runners/serve_kinds``:
XLA's compile-time memory analysis of the largest rung of each program
family, for a described v5e (no chip), with the cell's own
``serve_config``. ``sizing.py`` builds GPT-2's model and GPT-2-shaped
arguments; this builds the configuration's workload and one table per
block-id space.

    JAX_PLATFORMS=cpu python3 -m benchmark.sizing_kinds \
        --cell command-a-plus-05-2026.serve-longdoc [--max-slots 16] [--chunk 256]

Bytes counted by the compiler, never a time.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib
import json
import os
import sys
import time

from benchmark.sizing import FIT_SHARE, HBM_BYTES


def engine_programs(engine, sharding) -> dict:
    """{family: (jitted program, abstract arguments)} of the largest
    rung of each family, shaped as ``InferenceEngine.warmup`` calls
    them (one table per kind where the pool has several)."""
    import jax
    import jax.numpy as jnp

    from tensorflow_examples_tpu.serving.engine import _pack

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype), sharding=sharding)

    def tabs(nbs, *lead):
        return _pack([arr((*lead, nb), "int32") for nb in nbs])

    p = jax.tree.map(lambda x: arr(x.shape, x.dtype), engine.params)
    kv = jax.tree.map(lambda x: arr(x.shape, x.dtype), engine.pool.kv_state())
    bs, s, kinds = engine.cfg.kv_block_size, engine.cfg.max_slots, engine._kinds
    lb, kb = engine.prefill_ladder[-1], engine.kv_ladder[-1]
    i32, f32, key = arr((), "int32"), arr((), "float32"), arr((2,), "uint32")
    return {
        "decode": (engine._decode_fns[kb], (
            p, kv, arr((s,), "int32"), arr((s,), "int32"),
            tabs(engine._kind_blocks(kb // bs), s),
            arr((s,), "int32"), arr((s,), "float32"), arr((s,), "int32"))),
        "prefill": (engine._prefill_fns[lb], (
            p, kv, tabs([lb // bs] * kinds), arr((1, lb), "int32"), i32, key, f32, i32)),
        "extend": (engine._extend_fns[lb], (
            p, kv, tabs(engine._kind_blocks(engine.pool.max_blocks_per_slot)),
            tabs([lb // bs] * kinds), arr((1, lb), "int32"), i32, i32, key, f32, i32)),
    }


def analyse(cell_name: str, overrides: dict, families=("decode", "prefill", "extend")) -> dict:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark import spec
    from tensorflow_examples_tpu.serving.engine import InferenceEngine, ServeConfig

    jax.config.update("jax_enable_compilation_cache", False)
    cell = spec.load_cell(cell_name)
    pcfg = spec.program_config(cell.config)
    workload = importlib.import_module(cell.config["program"]["workload"])
    model_cfg = workload.model_config(pcfg)
    serve_cfg = dataclasses.replace(ServeConfig(**cell.deploy["serve_config"]), **overrides)

    # Zeros on the host stand in for the weights (9.5 GB here, as sizing.py
    # does for GPT-2's): only their shapes reach the compiler.
    shapes = jax.eval_shape(workload.make_task(pcfg).init_fn, jax.random.PRNGKey(0))["params"]
    params = jax.tree.map(lambda x: jnp.zeros(x.shape, x.dtype), shapes)
    engine = InferenceEngine(model_cfg, params, cfg=serve_cfg)

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    calls = engine_programs(engine, chip)
    pool = engine.pool
    out = {
        "cell": cell_name, "serve_config": dataclasses.asdict(serve_cfg),
        "kind_blocks": [pool.kind_blocks(k) for k in range(len(pool.kinds))],
        "pool_bytes": sum(int(a.size) * a.dtype.itemsize for arrs in pool.kv_state() for a in arrs),
        "param_bytes": sum(int(a.size) * a.dtype.itemsize for a in jax.tree.leaves(shapes)),
        "programs": {}, "fits": True, "limit_bytes": int(HBM_BYTES * FIT_SHARE),
    }
    for fam in families:
        fn, args = calls[fam]
        t0 = time.time()
        try:
            m = fn.lower(*args).compile().memory_analysis()
        except Exception as e:  # the compiler refuses what does not fit
            out["programs"][fam] = {"refused": f"{type(e).__name__}: {str(e)[:400]}"}
            out["fits"] = False
            continue
        need = (m.argument_size_in_bytes + m.output_size_in_bytes
                - m.alias_size_in_bytes + m.temp_size_in_bytes)
        out["programs"][fam] = {
            "argument_bytes": m.argument_size_in_bytes, "temp_bytes": m.temp_size_in_bytes,
            "need_bytes": need, "compile_host_s": round(time.time() - t0, 1),
        }
        out["fits"] = out["fits"] and need <= out["limit_bytes"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", required=True)
    ap.add_argument("--max-slots", type=int)
    ap.add_argument("--kv-blocks", type=int)
    ap.add_argument("--chunk", type=int, help="prefill_chunk_tokens and prefill_bucket_floor")
    ap.add_argument("--families", default="decode,prefill,extend")
    a = ap.parse_args(argv)
    overrides = {}
    if a.max_slots:
        overrides["max_slots"] = a.max_slots
    if a.kv_blocks:
        overrides["kv_blocks"] = a.kv_blocks
    if a.chunk:
        overrides.update(prefill_chunk_tokens=a.chunk, prefill_bucket_floor=a.chunk)
    print(json.dumps(analyse(a.cell, overrides, tuple(a.families.split(",")))))
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    sys.exit(main())
