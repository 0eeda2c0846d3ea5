"""Sizes a serving cell from XLA's compile-time memory analysis.

No chip is needed and none is used: the TPU's compiler is installed in
the sandbox and compiles for a v5e that is described, not attached
(on-chip-measurement guide, section 2, rehearsal 3). For one
configuration, one ``max_slots`` and one ``kv_blocks`` this compiles
the largest rung of each program family the engine serves with
(prefill L<max>, extend T<max>, decode K<max>) exactly as the engine
builds them, and prints what each needs on the device.

    JAX_PLATFORMS=cpu python3 -m benchmark.sizing --config gpt2-xl \
        --max-slots 8 --kv-blocks 176

The rule the cells' numbers follow is in benchmark/README.md. What is
printed is a count of bytes by the compiler, never a time.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HBM_BYTES = 16 * 2**30          # one v5e chip (benchmark/peaks.py)
# One program at a time is analysed; the process also keeps the host
# transfers in flight and the allocator's fragmentation. 6% is held back.
FIT_SHARE = 0.94


def engine_programs(engine, sharding) -> dict:
    """{family: (jitted program, abstract arguments)} of the largest
    rung of each program family the engine serves with, shaped exactly
    as ``InferenceEngine.warmup`` calls them, every argument placed by
    ``sharding`` (a described chip)."""
    import jax
    import jax.numpy as jnp

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)

    def sds(x):
        return arr(x.shape, x.dtype)

    p = jax.tree.map(sds, engine.params)
    kv = jax.tree.map(sds, engine.pool.kv_state())
    bs, s = engine.cfg.kv_block_size, engine.cfg.max_slots
    lb, kb = engine.prefill_ladder[-1], engine.kv_ladder[-1]
    scalar_i, scalar_f, key = arr((), "int32"), arr((), "float32"), arr((2,), "uint32")
    return {
        "decode": (engine._decode_fns[kb], (
            p, kv, arr((s,), "int32"), arr((s,), "int32"), arr((s, kb // bs), "int32"),
            arr((s,), "int32"), arr((s,), "float32"), arr((s,), "int32"))),
        "prefill": (engine._prefill_fns[lb], (
            p, kv, arr((lb // bs,), "int32"), arr((1, lb), "int32"), scalar_i, key,
            scalar_f, scalar_i)),
        "extend": (engine._extend_fns[lb], (
            p, kv, arr((engine.pool.max_blocks_per_slot,), "int32"),
            arr((lb // bs,), "int32"), arr((1, lb), "int32"), scalar_i, scalar_i,
            key, scalar_f, scalar_i)),
    }


def analyse(config_name: str, max_slots: int, kv_blocks: int,
            families=("decode", "prefill", "extend")) -> dict:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark import spec
    from tensorflow_examples_tpu.serving.engine import InferenceEngine, ServeConfig

    jax.config.update("jax_enable_compilation_cache", False)
    config = json.load(open(os.path.join(spec.HERE, "configs", config_name + ".json")))
    pcfg = spec.program_config(config)
    workload = __import__(config["program"]["workload"], fromlist=["x"])
    model_cfg = workload.model_config(pcfg)
    from tensorflow_examples_tpu.models import transformer

    model = transformer.Transformer(model_cfg)
    shapes = jax.eval_shape(
        lambda k: model.init({"params": k}, jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(0),
    )
    params = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    engine = InferenceEngine(
        model_cfg, params,
        cfg=ServeConfig(max_slots=max_slots, kv_block_size=16, kv_blocks=kv_blocks),
    )
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    calls = engine_programs(engine, chip)
    out = {
        "config": config_name, "max_slots": max_slots, "kv_blocks": kv_blocks,
        "pool_tokens": (kv_blocks - 1) * engine.cfg.kv_block_size, "programs": {}, "fits": True,
        "limit_bytes": int(HBM_BYTES * FIT_SHARE),
    }
    for fam in families:
        fn, args = calls[fam]
        t0 = time.time()
        try:
            m = fn.lower(*args).compile().memory_analysis()
        except Exception as e:  # the compiler refuses what does not fit
            out["programs"][fam] = {"refused": f"{type(e).__name__}: {str(e)[:300]}"}
            out["fits"] = False
            break
        need = (m.argument_size_in_bytes + m.output_size_in_bytes
                - m.alias_size_in_bytes + m.temp_size_in_bytes)
        out["programs"][fam] = {
            "argument_bytes": m.argument_size_in_bytes,
            "output_bytes": m.output_size_in_bytes,
            "alias_bytes": m.alias_size_in_bytes,
            "temp_bytes": m.temp_size_in_bytes,
            "need_bytes": need,
            "compile_host_s": round(time.time() - t0, 1),
        }
        if need > out["limit_bytes"]:
            out["fits"] = False
            break
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--max-slots", type=int, required=True)
    ap.add_argument("--kv-blocks", type=int, required=True)
    ap.add_argument("--families", default="decode,prefill,extend")
    a = ap.parse_args(argv)
    res = analyse(a.config, a.max_slots, a.kv_blocks, tuple(a.families.split(",")))
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    sys.exit(main())
