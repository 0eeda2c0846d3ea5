#!/usr/bin/env python
"""GPT-2 serving CLI: checkpoint -> live /generate endpoint.

    python examples/gpt2/serve.py --workdir=/path/to/run --port=8000 \
        --max_slots=8

    curl -s localhost:8000/generate -d \
        '{"text": "The ", "max_new_tokens": 32, "temperature": 0.8}'

Loads the latest checkpoint (same eval_shape-template restore as
generate.py), warms up the serving engine's whole bucket ladder (the
AOT pass — steady state is zero-recompile, watch
``post_warmup_recompiles`` on ``/health``), starts the continuous
batcher and the HTTP frontend, and serves until SIGTERM — which drains
in-flight requests, 503s new ones, and exits 0 (the same preemption
contract as training; a second signal force-quits).

Text in/out uses a BPE vocab (--vocab_dir, or vocab.json/merges.txt
in --data_dir), falling back to raw bytes for byte-level corpora
(vocab_size <= 256, same rule as generate.py); otherwise send token
ids as "prompt". A schema-v4
``kind="serving"`` stats line is appended to ``workdir/serving.jsonl``
every ``--stats_every`` seconds (the serving counterpart of training's
``metrics.jsonl`` — same JSONL discipline, ``/window`` serves the
latest line). The same tick samples the in-process time-series store
(ISSUE 19), so ``GET /series`` serves ring-buffered instrument history
with p50/p95/p99 rollups.
"""

import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))

from absl import app, flags

from tensorflow_examples_tpu.train.checkpoint import CheckpointManager
from tensorflow_examples_tpu.train.cli import _setup
from tensorflow_examples_tpu.train.config import define_flags_from_config
from tensorflow_examples_tpu.train.loop import state_factory
from tensorflow_examples_tpu.workloads import gpt2

define_flags_from_config(gpt2.Gpt2Config())
flags.DEFINE_integer("port", 8000, "HTTP port (0 = auto-assign)")
flags.DEFINE_integer("max_slots", 8, "concurrent decode slots")
flags.DEFINE_integer("max_queue", 64, "bounded submit queue (then 503)")
flags.DEFINE_float("max_delay_s", 0.002, "idle burst-coalescing window")
flags.DEFINE_float("serve_watchdog_secs", 60.0,
                   "serve-loop hang detection (0 disables)")
flags.DEFINE_float("stats_every", 10.0,
                   "seconds between serving.jsonl stats lines (0 disables)")
flags.DEFINE_integer(
    "kv_block_size", 16,
    "token rows per KV block (docs/serving.md). Power of two dividing "
    "the bucket floors and max_len; a request holds the blocks its "
    "tokens fill, and shared prompt prefixes prefill once.")
flags.DEFINE_integer(
    "kv_blocks", 0,
    "physical KV blocks (0 = the worst case, max_slots x max_len / "
    "kv_block_size); shrink to bank the memory paging saves — "
    "exhaustion sheds load loudly (503)")
flags.DEFINE_string(
    "kv_dtype", "",
    "KV cache storage dtype: '' (cache dtype), 'int8', or 'fp8' "
    "(per-row scales; bounded-divergence modes; fp8 needs backend "
    "float8 support)")
flags.DEFINE_string(
    "weight_dtype", "",
    "weight-only quantization (docs/serving.md quantization section): "
    "'' serves the checkpoint's dtype; 'int8'/'fp8' quantize every "
    "matmul weight at load time via the precision registry — HBM "
    "param bytes drop ~4x, dequant happens inside the compiled "
    "matmuls, streams are bounded-divergence vs f32 (serve_bench "
    "--weight-dtype banks the gate record). Composes with "
    "workdir/sharding.json: quantized payloads shard by the weight's "
    "rule, scales inherit their weight's spec.")
flags.DEFINE_boolean(
    "prefix_cache", True,
    "reuse immutable full prompt blocks across requests")
flags.DEFINE_integer(
    "spec_decode_k", 0,
    "speculative decoding draft window (docs/serving.md): verify up to "
    "K drafted tokens per decode step. Output streams stay "
    "token-identical — K buys TPOT on prompt-like text, never changes "
    "tokens. 0 disables.")
flags.DEFINE_integer(
    "draft_ngram", 3,
    "longest n-gram the self-speculative drafter matches against the "
    "request's own context (spec_decode_k > 0 only)")
flags.DEFINE_string(
    "decode_attention", "",
    "decode attention impl: '' (engine default), 'xla' (gather "
    "reference), 'flash' (Pallas prefill attend), or 'paged_flash' "
    "(fused paged-decode kernel)")
flags.DEFINE_string(
    "role", "mixed",
    "fleet scheduling role (docs/serving.md scheduling section): "
    "'mixed' (default — serves everything), 'prefill' (runs prompts to "
    "completion-of-prefill and exports KV pages), or 'decode' (imports "
    "pages and continues streams). Advisory: every role still answers "
    "a full /generate. Published on /health for the router.")
flags.DEFINE_integer(
    "prefill_chunk_tokens", 0,
    "chunked prefill admission (docs/serving.md): split any cold "
    "prompt tail longer than this into block-aligned chunks run one "
    "per decode-loop iteration, so a long prefill interleaves with "
    "decode steps. Requires prefix_cache and must be a multiple of "
    "--kv_block_size. 0 disables.")
flags.DEFINE_boolean(
    "brownout", False,
    "overload brownout ladder (docs/serving.md overload section): "
    "under pressure shed batch -> cap max_new_tokens -> skip "
    "speculation -> shed interactive, stepped with hysteresis; the "
    "level is published on /health for the router and autoscaler.")
flags.DEFINE_integer(
    "brownout_queue_hi", 0,
    "brownout queue-depth high watermark (0 = 2 * max_slots)")
flags.DEFINE_float(
    "brownout_hold_s", 0.5,
    "brownout hysteresis: min dwell per rung up, sustained-clear "
    "time per rung down")
flags.DEFINE_integer(
    "brownout_max_new_tokens", 8,
    "brownout level-2 generation cap (streams retire early as a "
    "prefix, truncated='brownout')")
flags.DEFINE_string("vocab_dir", "", "dir with vocab.json+merges.txt")
flags.DEFINE_string(
    "serve_sharding_config", "",
    "ShardingConfig JSON for sharded serving (docs/sharding.md); "
    "default: auto-load <workdir>/sharding.json — the config the "
    "training run persisted — falling back to replicated params. "
    "'off' forces replicated placement.")
FLAGS = flags.FLAGS


class _ByteTokenizer:
    """generate.py's byte-level text fallback (vocab_size <= 256) with
    the encode/decode surface the frontend expects of a tokenizer."""

    def encode(self, text):
        return list(text.encode())

    def decode(self, tokens):
        return bytes(
            min(max(int(t), 0), 255) for t in tokens
        ).decode(errors="replace")


def _load_tokenizer(cfg):
    from tensorflow_examples_tpu.data.tokenizers import ByteLevelBPE

    for d in (FLAGS.vocab_dir, cfg.data_dir):
        if d and os.path.exists(os.path.join(d, "vocab.json")):
            return ByteLevelBPE.from_dir(d)
    return _ByteTokenizer() if cfg.vocab_size <= 256 else None


def main(argv):
    del argv
    import jax
    import jax.numpy as jnp

    from tensorflow_examples_tpu.serving import (
        ContinuousBatcher,
        InferenceEngine,
        ServeConfig,
        ServingFrontend,
        run_until_preempted,
    )

    cfg = _setup(gpt2, gpt2.Gpt2Config())
    if not cfg.workdir:
        raise app.UsageError("--workdir is required for serve")

    # One ShardingConfig drives train AND serve (docs/sharding.md): the
    # trainer persisted its placement spec next to the checkpoints;
    # serving places the restored params + KV pool by the same rules
    # instead of replicating. --serve_sharding_config overrides (or
    # 'off' disables). Resolved BEFORE the restore so the checkpoint
    # deserializes STRAIGHT into the sharded layout — a model that only
    # fits split must never materialize on one device.
    from tensorflow_examples_tpu.models.transformer import GPT2_RULES
    from tensorflow_examples_tpu.sharding import ShardingConfig

    sharding = None
    src = FLAGS.serve_sharding_config
    if src != "off":
        path = src or os.path.join(cfg.workdir, "sharding.json")
        if src or os.path.exists(path):
            import dataclasses as _dc

            sharding = ShardingConfig.load(path)
            # Serving has no data parallelism within one process — a
            # training config's data axis would only replicate params
            # over devices serving never uses (and make a pod-trained
            # config unserveable on a single chip). Collapse it.
            sharding = _dc.replace(
                sharding, mesh={**sharding.mesh, "data": 1}
            )
            try:
                sharding.build_mesh()
            except ValueError as e:
                if src:
                    # Explicitly requested config: fail loudly.
                    raise
                # Auto-loaded from the workdir: a host too small for
                # the training layout serves replicated, as before.
                print(
                    f"sharding config {path} does not fit this host "
                    f"({e}); serving with replicated params",
                    file=sys.stderr,
                )
                sharding = None
            else:
                print(f"sharding config: {path}", file=sys.stderr)

    make_state, _ = state_factory(gpt2.make_task(cfg), cfg)
    abstract = jax.eval_shape(make_state, jax.random.PRNGKey(0))
    if sharding is not None:
        # Shardings on the WHOLE template — params by the rules, the
        # optimizer moments inheriting them — so nothing (the Adam
        # state is 2x the param bytes) ever lands whole on one device.
        from tensorflow_examples_tpu.sharding import state_shardings

        mesh = sharding.build_mesh()
        sh = state_shardings(
            abstract,
            mesh,
            sharding.sharding_rules(default=GPT2_RULES),
            zero1=sharding.zero1,
            batch_axes=sharding.batch_axes,
        )
        abstract = jax.tree.map(
            lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                              sharding=s),
            abstract,
            sh,
        )
    restored = CheckpointManager(cfg.workdir).restore_latest(abstract)
    if restored is None:
        raise SystemExit(f"no checkpoint under {cfg.workdir}")
    # Already placed when sharded (the engine's device_put is then a
    # no-op); asarray only on the replicated path.
    params = (
        restored[0].params
        if sharding is not None
        else jax.tree.map(jnp.asarray, restored[0].params)
    )

    engine = InferenceEngine(
        gpt2.model_config(cfg),
        params,
        cfg=ServeConfig(
            max_slots=FLAGS.max_slots,
            max_queue=FLAGS.max_queue,
            max_delay_s=FLAGS.max_delay_s,
            watchdog_secs=FLAGS.serve_watchdog_secs,
            kv_block_size=FLAGS.kv_block_size,
            kv_blocks=FLAGS.kv_blocks,
            kv_dtype=FLAGS.kv_dtype,
            weight_dtype=FLAGS.weight_dtype,
            prefix_cache=FLAGS.prefix_cache,
            spec_decode_k=FLAGS.spec_decode_k,
            draft_ngram=FLAGS.draft_ngram,
            role=FLAGS.role,
            prefill_chunk_tokens=FLAGS.prefill_chunk_tokens,
            brownout=FLAGS.brownout,
            brownout_queue_hi=FLAGS.brownout_queue_hi,
            brownout_hold_s=FLAGS.brownout_hold_s,
            brownout_max_new_tokens=FLAGS.brownout_max_new_tokens,
            **(
                {"attention": FLAGS.decode_attention}
                if FLAGS.decode_attention else {}
            ),
        ),
        sharding=sharding,
    )
    # Say where the weights and the cache actually landed: a workdir
    # layout that did not fit this host serves replicated on one device
    # (above), and that must be visible, not inferred.
    from tensorflow_examples_tpu.telemetry.memory import tree_bytes

    held = lambda tree: len(
        set().union(*(x.devices() for x in jax.tree.leaves(tree)))
    )
    per_dev = lambda tree: tree_bytes(tree, per_device=True) / 2**20
    kv = (engine.pool.k, engine.pool.v)  # one array per layer each
    print(
        f"placement: params on {held(engine.params)} "
        f"({per_dev(engine.params):.0f} MiB each) and KV pool on "
        f"{held(kv)} ({per_dev(kv):.0f} MiB each) of "
        f"{jax.device_count()} device(s)",
        file=sys.stderr,
    )
    t0 = time.perf_counter()
    engine.warmup()
    print(
        f"warm: {engine.expected_compiles()} programs in "
        f"{time.perf_counter() - t0:.1f}s; serving from step "
        f"{restored[1]}",
        file=sys.stderr,
    )

    batcher = ContinuousBatcher(engine).start()
    frontend = ServingFrontend(
        batcher, port=FLAGS.port, tokenizer=_load_tokenizer(cfg)
    ).start()
    print(f"listening on :{frontend.port} (POST /generate)", file=sys.stderr)

    if FLAGS.stats_every > 0:
        stats_path = os.path.join(cfg.workdir, "serving.jsonl")

        def stats_loop():
            while not batcher._stop.is_set():
                time.sleep(FLAGS.stats_every)
                # One stats tick = one time-series ring sample
                # (ISSUE 19): GET /series history accrues on exactly
                # the cadence the stats line does.
                frontend.series.sample()
                with open(stats_path, "a") as f:
                    f.write(json.dumps(batcher.stats_line()) + "\n")

        threading.Thread(
            target=stats_loop, name="serving-stats", daemon=True
        ).start()

    raise SystemExit(run_until_preempted(frontend))


if __name__ == "__main__":
    app.run(main)
