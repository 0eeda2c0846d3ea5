#!/usr/bin/env python
"""Closed-loop load generator for the serving stack; banks a BENCH record.

Stands the whole serving path up — engine (AOT warmup over the bucket
ladder), continuous batcher, HTTP frontend — then drives it closed-loop
(``--concurrency`` worker threads, each submitting its next request the
moment the previous one resolves: offered load = concurrency / mean
latency, the standard closed-loop operating point) and emits ONE
BENCH-style JSON record::

    {"bench": "serving", "backend": "cpu", "requests": 20,
     "concurrency": 8, "req_per_s": ..., "tok_per_s": ...,
     "ttft_p50_ms": ..., "ttft_p95_ms": ..., "tpot_p50_ms": ...,
     "tpot_p95_ms": ..., "e2e_p95_ms": ..., "queue_wait_p95_ms": ...,
     "expected_compiles": ..., "compiles": ...,
     "post_warmup_recompiles": 0, "shed": 0, "errors": 0,
     "verified": 3, "verify_ok": true, "ok": true}

``ok`` is the CI verdict: every request completed, the verified subset
is token-identical to the engine's unbatched reference replay, and NOT
ONE compile happened after warmup (``post_warmup_recompiles == 0`` —
the zero-recompile steady-state claim, measured, not asserted).

Modes:

* ``--smoke`` — tier-1 CI: a tiny random-param GPT-2 on whatever
  backend is present (CPU in CI), 20 mixed-length requests over HTTP,
  3 of them verified against the reference. Seconds, not minutes.
* ``--workdir DIR`` — load a real trained checkpoint (the
  ``examples/gpt2`` layout, trained at the DEFAULT model shape — the
  workdir banks no config, so a checkpoint from non-default
  ``--num_layers``/``--d_model``/... flags will fail the template
  restore; serve those via ``examples/gpt2/serve.py``, which takes the
  full flag surface) and measure serving throughput/latency at
  ``--concurrency`` on the local accelerator.
* ``--router`` (ISSUE 8) — stand up ``--replicas`` N full serving
  stacks IN THIS PROCESS (each its own engine + batcher + HTTP
  frontend on a loopback port), put ``serving/router.py`` in front,
  and drive the whole tier through the router. Replicas size their
  KV pool by ``--kv-block-size`` and ``--kv-dtype``, and a quarter of
  the prompts share a common prefix so the prefix cache takes real
  hits; the record (``"bench": "serve_router"``) adds replica count,
  router retry counters, and ``prefix_hit_rate`` to the latency/
  throughput keys, and ``ok`` additionally requires zero post-warmup
  recompiles summed over EVERY replica. ``--smoke --router`` is the
  tier-1 fleet smoke.

* ``--chaos`` (ISSUE 10) — availability under injected faults: a
  SUPERVISED 3-replica (default) in-proc paged fleet behind the
  hardened router, a fault-free baseline phase, then a deterministic
  serve fault schedule (``--fault-spec``, ``utils/faults.py`` grammar;
  default crashes the replica next in the router's rotation,
  mid-decode) under the same load, then
  wait for the supervisor to restore the fleet. Banks a
  ``serve_chaos`` record: ``error_rate`` (0 on a healthy tier —
  in-flight failover means replica death drops nothing),
  ``failover_count``, ejection/readmit/restart counters, and
  ``p95_vs_baseline`` (client-observed e2e p95 ratio vs the declared
  ``CHAOS_P95_BUDGET``). ``bench_gate`` gates ``error_rate`` at 0 and
  ``p95_vs_baseline`` as a max. ``--smoke --chaos`` is the tier-1
  chaos smoke. The run then reuses the warm fleet for the ISSUE 16
  router-kill phase: a fresh primary/standby ``RouterPair`` over the
  same replicas, ``killrouter@T`` hard-aborting the primary
  mid-stream, clients failing over on idempotency keys. Banks a
  second ``serve_takeover`` record (to ``<out>_takeover.json``):
  ``takeover_latency_s`` vs ``TAKEOVER_LATENCY_BUDGET_S``,
  ``lost_requests`` (gated at 0 — an accepted request survives router
  death via the durable journal), ``resumed_streams``, ``dedup_hits``
  (a duplicated request_id retry returns the ORIGINAL tokens), and
  zero post-warmup recompiles fleet-wide.

* ``--affinity {on,off,ab}`` (ISSUE 12, with ``--router``) — prefix-
  affinity dispatch control. ``ab`` is the A/B mode: the SAME shared-
  prefix-heavy prompt sequence through an affinity-off fleet then an
  affinity-on one, driven sequentially with manual probe sweeps so
  routing is deterministic, banking a ``serve_affinity`` record —
  ``prefix_hit_rate_affinity`` strictly above
  ``prefix_hit_rate_no_affinity`` is the acceptance inequality ``ok``
  asserts, with the shared-vs-cold TTFT split and zero post-warmup
  recompiles alongside. ``bench_gate`` pins the -affinity rate as a
  stamped minimum.

* ``--spec-decode K`` (ISSUE 11) — speculative-decoding A/B: the SAME
  prompt-like prompts (tiled motifs — the traffic speculation exists
  for) through two engines, speculation off then on at draft window K,
  banking a ``serve_spec`` record: ``tpot_speedup`` (off/on TPOT p50
  ratio — the headline the tentpole claims), ``draft_hit_rate`` and
  ``accepted_per_step`` p50 (why it moved), ``tokens_identical`` (the
  determinism contract, checked over EVERY request) and zero
  post-warmup recompiles across both engines. ``bench_gate`` gates
  ``tpot_speedup`` as a stamped minimum.

* ``--weight-dtype {int8,fp8}`` (ISSUE 15) — weight-quantization A/B:
  the SAME mixed-length prompts through an f32 engine and one whose
  weights the precision registry quantized at load time, banking a
  ``serve_quant`` record: ``hbm_bytes_per_replica`` +
  ``hbm_ratio_vs_f32`` (the ~4x HBM claim, via
  ``engine.byte_breakdown``), ``tpot_speedup_quant`` /
  ``ttft_speedup_quant``, and the bounded-divergence verdict int8 KV
  established — ``first_token_exact`` over every request plus
  ``stream_agreement`` >= ``QUANT_AGREEMENT_FLOOR``, zero post-warmup
  recompiles on both engines. ``--smoke --weight-dtype int8`` is the
  tier-1 quantization smoke; ``bench_gate`` gates
  ``tpot_speedup_quant`` (min) and ``hbm_bytes_per_replica`` (max).

* ``--traffic {ramp,flash,diurnal}`` (ISSUE 13) — the replayable
  open-loop traffic model: seeded exponential arrivals at a per-mode
  rate profile, heavy-tail prompt lengths, a seeded interactive/batch
  SLO mix, driven open-loop (arrivals never back off) against a
  brownout-enabled fleet. ``flash`` pins the flash-crowd golden (all
  shedding on batch, interactive flash TTFT p95 within
  ``FLASH_TTFT_BUDGET`` x steady, token-identical streams, ladder
  cleared); ``ramp`` pins the autoscaler golden (1 -> ``--max-
  replicas`` -> 1, drain-first, ``scale_up_latency_s`` +
  ``p95_during_resize_ms`` stamped); ``diurnal`` is the long-horizon
  shape. Banks the ``serve_traffic`` record whose per-class p95s /
  shed rates / scale-up latency ``bench_gate`` accepts. Same
  ``--traffic-seed`` = byte-identical scenario — composable with a
  ``--fault-spec``-style chaos schedule by arming the fault env
  around the run.

``--inproc`` skips the HTTP hop (batcher futures driven directly) to
separate transport cost from engine cost; ``--out`` banks the record
as a JSON file next to the BENCH_r*.json trajectory.

``--slo`` (ISSUE 19, plain + ``--router`` modes) runs the SLO
AlertEngine over the organic traffic plus a known-answer canary probe
sweep after the drive; the record banks ``alert_count`` (gated max by
``bench_gate``), ``probe_success_rate`` (gated min) and
``error_budget_remaining``, and ``ok`` additionally requires
``alert_count == 0`` — the healthy smoke's zero-alerts claim. Probe
traffic is excluded from the banked percentiles and counters (the
record / counter snapshot is taken first).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
)


SMOKE_MODEL = dict(
    vocab_size=211,
    max_len=64,
    num_layers=2,
    num_heads=2,
    d_model=32,
    dropout=0.0,
    attention="xla",
)


def build_smoke_engine(serve_cfg=None, *, registry=None):
    """Tiny random-param GPT-2 + engine, shared with tests/test_serving:
    big enough to cross prefill buckets, small enough for tier-1."""
    import jax

    from tensorflow_examples_tpu.models import transformer
    from tensorflow_examples_tpu.serving.engine import (
        InferenceEngine,
        ServeConfig,
    )

    cfg = transformer.TransformerConfig(**SMOKE_MODEL)
    model = transformer.Transformer(cfg)
    import jax.numpy as jnp

    tokens = jnp.zeros((1, 8), jnp.int32)
    params = model.init({"params": jax.random.PRNGKey(0)}, tokens)["params"]
    return InferenceEngine(
        cfg,
        params,
        cfg=serve_cfg or ServeConfig(max_slots=8, prefill_bucket_floor=16,
                                     kv_bucket_floor=32),
        registry=registry,
    )


def build_checkpoint_engine(workdir: str, serve_cfg, *, registry=None):
    """Engine over the latest checkpoint in an ``examples/gpt2`` workdir
    (restores through an eval_shape template like generate.py). The
    template is the DEFAULT Gpt2Config — the workdir banks no config,
    so non-default-shape checkpoints cannot be restored here."""
    import jax
    import jax.numpy as jnp

    from tensorflow_examples_tpu.serving.engine import InferenceEngine
    from tensorflow_examples_tpu.train.checkpoint import CheckpointManager
    from tensorflow_examples_tpu.train.loop import state_factory
    from tensorflow_examples_tpu.workloads import gpt2

    cfg = gpt2.Gpt2Config(workdir=workdir)
    make_state, _ = state_factory(gpt2.make_task(cfg), cfg)
    abstract = jax.eval_shape(make_state, jax.random.PRNGKey(0))
    try:
        restored = CheckpointManager(workdir).restore_latest(abstract)
    except Exception as e:
        raise SystemExit(
            f"restore failed against the default-shape template — a "
            f"checkpoint trained with non-default model flags must be "
            f"served via examples/gpt2/serve.py instead: {e}"
        ) from None
    if restored is None:
        raise SystemExit(f"no checkpoint under {workdir}")
    params = jax.tree.map(jnp.asarray, restored[0].params)
    return InferenceEngine(
        gpt2.model_config(cfg), params, cfg=serve_cfg, registry=registry
    )


def make_patterned_prompts(n: int, *, vocab: int, max_len: int,
                           max_new: int,
                           seed: int = 0) -> list[list[int]]:
    """Prompt-LIKE prompts for the speculation A/B (ISSUE 11): each is
    a short random motif tiled to a mixed length, the repetitive shape
    of real prompt traffic (code, templates, boilerplate) that the
    self-speculative n-gram drafter exists for. Random-token prompts
    (``make_prompts``) are the adversarial case — near-zero draft hits
    — and exactly what a speculation bench must NOT quietly use."""
    import numpy as np

    rng = np.random.default_rng(seed)
    cap = max(4, max_len - max_new)
    prompts = []
    for i in range(n):
        motif = [
            int(t) for t in rng.integers(0, vocab, int(rng.integers(3, 7)))
        ]
        ln = int(rng.integers(max(4, cap // 3), cap + 1))
        prompts.append((motif * (ln // len(motif) + 1))[:ln])
    prompts[0] = prompts[0][:max(4, cap // 3)]
    prompts[-1] = (prompts[-1] * 4)[:cap]
    return prompts


def make_prompts(n: int, *, vocab: int, max_len: int, max_new: int,
                 seed: int = 0,
                 shared_prefix_every: int = 0) -> list[list[int]]:
    """Mixed-length prompts spanning the prefill buckets (that's the
    continuous-batching claim under test: different lengths coalesce).

    ``shared_prefix_every=k`` gives every k-th prompt one common
    system-prompt-style prefix (half the prompt budget) plus a random
    tail — the traffic shape the paged pool's prefix cache exists for
    (the first such prompt prefills it, later ones hit)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    cap = max(2, max_len - max_new)
    lengths = [int(rng.integers(1, cap + 1)) for _ in range(n)]
    # Force the extremes so every run exercises bucket 1 and the top.
    lengths[0], lengths[-1] = 1, cap
    prompts = [
        [int(t) for t in rng.integers(0, vocab, (ln,))] for ln in lengths
    ]
    if shared_prefix_every:
        pre_len = max(1, cap // 2)
        prefix = [int(t) for t in rng.integers(0, vocab, (pre_len,))]
        for i in range(1, n, shared_prefix_every):
            tail = 1 + int(rng.integers(0, max(1, cap - pre_len)))
            prompts[i] = prefix + [
                int(t) for t in rng.integers(0, vocab, (tail,))
            ]
    return prompts


def make_affinity_prompts(n: int, *, vocab: int, max_len: int,
                          max_new: int, block: int = 16,
                          seed: int = 0):
    """The affinity A/B's traffic shape (ISSUE 12): two distinct
    shared prefixes (block-aligned, so their chain keys are exactly
    matchable) interleaved with cold prompts —
    ``(prompts, groups)`` where groups[i] is "shared" or "cold". A
    cache-BLIND router spreads each shared group over the fleet (every
    replica pays its own cold prefill of the prefix); an affinity
    router parks each group on the replica already holding its chain,
    which is the measured hit-rate gap the record banks."""
    import numpy as np

    rng = np.random.default_rng(seed)
    cap = max(block + 2, max_len - max_new)
    pre_len = max(block, (cap * 2 // 3) // block * block)
    pre_len = min(pre_len, (cap - 2) // block * block)
    prefixes = {
        "A": [int(t) for t in rng.integers(0, vocab, pre_len)],
        "B": [int(t) for t in rng.integers(0, vocab, pre_len)],
    }
    prompts, groups = [], []
    for i in range(n):
        g = ("A", "B", "cold")[i % 3]
        if g == "cold":
            ln = int(rng.integers(2, cap + 1))
            prompts.append(
                [int(t) for t in rng.integers(0, vocab, ln)]
            )
            groups.append("cold")
        else:
            tail = 1 + int(rng.integers(0, max(1, cap - pre_len)))
            prompts.append(
                prefixes[g]
                + [int(t) for t in rng.integers(0, vocab, tail)]
            )
            groups.append("shared")
    return prompts, groups


def _post_json(url: str, body: dict, timeout: float) -> tuple[int, dict]:
    # The serving stack's one JSON-over-HTTP client: transport-level
    # failures (URLError, reset, timeout, torn JSON body) come back as
    # status 0 and count as THIS request's error instead of killing
    # the worker thread and stranding every prompt it would have
    # pulled next.
    from tensorflow_examples_tpu.serving.router import post_json

    return post_json(url, body, timeout)


def drive(frontend, prompts, *, concurrency: int, max_new: int,
          temperature: float, top_k: int, http_url: str | None,
          timeout: float, trace_recorder=None) -> dict:
    """Closed loop: workers pull the next prompt off a shared list the
    moment their current request resolves. Returns per-request replies
    (index-aligned with ``prompts``), per-request CLIENT wall times
    (``client_s`` — includes every router retry/failover, which the
    replica-measured ``total_s`` cannot see), + wall time.

    ``trace_recorder`` (ISSUE 18): a ``tracing.TraceRecorder`` makes
    the bench the CLIENT-side trace originator for replica-direct
    runs — each request ships a wire context, the reply's
    ``trace_spans`` ingest under a client root span, and the trace
    finishes with the client wall. Router runs leave this None: the
    router mints and owns the trace there."""
    from tensorflow_examples_tpu.telemetry import tracing

    replies: list[tuple[int, dict] | None] = [None] * len(prompts)
    client_s: list[float | None] = [None] * len(prompts)
    next_i = [0]
    lock = threading.Lock()

    def worker():
        while True:
            with lock:
                i = next_i[0]
                if i >= len(prompts):
                    return
                next_i[0] += 1
            body = {
                "prompt": prompts[i],
                "max_new_tokens": max_new,
                "temperature": temperature,
                "top_k": top_k,
                "seed": i,  # per-request stream: replayable
            }
            root_id = None
            ctx = None
            if trace_recorder is not None:
                ctx = trace_recorder.new_context()
                root_id = tracing.new_span_id()
                body["trace"] = {
                    "trace_id": ctx.trace_id,
                    "parent_span_id": root_id,
                    "sampled": True,
                }
            t_mono = time.monotonic()
            t_req = time.perf_counter()
            if http_url is not None:
                replies[i] = _post_json(http_url, body, timeout)
            else:
                replies[i] = frontend.handle_request(body, kind="generate")
            client_s[i] = time.perf_counter() - t_req
            if trace_recorder is not None:
                status, reply = replies[i] or (0, {})
                spans = (
                    reply.pop("trace_spans", None)
                    if isinstance(reply, dict) else None
                )
                if spans:
                    trace_recorder.ingest(
                        ctx.trace_id, spans, parent_id=root_id
                    )
                trace_recorder.add_span(
                    ctx.trace_id, tracing.close_span(
                        "request", t_mono, span_id=root_id,
                        tags={"status": int(status)},
                    )
                )
                trace_recorder.finish(
                    ctx.trace_id, slo="interactive",
                    status=int(status), e2e_s=client_s[i],
                )

    t0 = time.perf_counter()
    threads = [
        threading.Thread(target=worker, name=f"serve-bench-{k}", daemon=True)
        for k in range(concurrency)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=timeout * max(1, len(prompts)))
    wall = time.perf_counter() - t0
    return {"replies": replies, "client_s": client_s, "wall_s": wall}


def tally_replies(replies) -> dict:
    """Split non-200 outcomes by MEANING (ISSUE 13 satellite): a
    503 load-shed is correct overload behavior, a 4xx is the request's
    own fault, and only transport failures / unexpected statuses are
    ``errors`` — so an overload run with correct shedding doesn't read
    as a broken fleet, and a chaos record's error_rate-at-0 criterion
    stays honest about what it counts."""
    completed = shed = rejected = transport = other = 0
    for r in replies:
        if r is None:
            transport += 1  # the worker never got an answer
            continue
        status = r[0]
        if status == 200:
            completed += 1
        elif status == 0:
            transport += 1
        elif status == 503:
            shed += 1
        elif 400 <= status < 500:
            rejected += 1
        else:
            other += 1
    return {
        "completed": completed,
        "shed_total": shed,
        "rejected_total": rejected,
        "transport_errors": transport,
        "errors": transport + other,
    }


def bench_record(engine, registry, outcome, prompts, *, concurrency,
                 verified, verify_ok, backend) -> dict:
    hists = registry.histogram_summaries()

    def pct(name, q):
        h = hists.get(f"serving/{name}")
        v = h and h.get(f"p{q}")
        return round(v * 1e3, 3) if v is not None else None

    replies = outcome["replies"]
    done = [r for r in replies if r is not None and r[0] == 200]
    toks = sum(len(r[1].get("tokens", ())) for r in done)
    wall = outcome["wall_s"]
    counters = registry.counter_values()
    tally = tally_replies(replies)
    rec = {
        "bench": "serving",
        "backend": backend,
        "requests": len(prompts),
        "completed": len(done),
        "errors": tally["errors"],
        "shed_total": tally["shed_total"],
        "rejected_total": tally["rejected_total"],
        "transport_errors": tally["transport_errors"],
        "concurrency": concurrency,
        "max_slots": engine.cfg.max_slots,
        "wall_s": round(wall, 3),
        "req_per_s": round(len(done) / wall, 3) if wall else None,
        "tok_per_s": round(toks / wall, 3) if wall else None,
        "generated_tokens": toks,
        "queue_wait_p95_ms": pct("queue_wait", 95),
        "prefill_p95_ms": pct("prefill", 95),
        "ttft_p50_ms": pct("ttft", 50),
        "ttft_p95_ms": pct("ttft", 95),
        "tpot_p50_ms": pct("tpot", 50),
        "tpot_p95_ms": pct("tpot", 95),
        "e2e_p50_ms": pct("e2e", 50),
        "e2e_p95_ms": pct("e2e", 95),
        "expected_compiles": engine.expected_compiles(),
        "compiles": int(counters.get("compile/count", 0)),
        "post_warmup_recompiles": engine.post_warmup_recompiles(),
        "shed": int(counters.get("serving/shed_total", 0)),
        "verified": verified,
        "verify_ok": verify_ok,
    }
    stats = engine.pool.paged_stats()
    rec["kv_block_size"] = stats["block_size"]
    rec["kv_bits"] = stats["kv_bits"]
    rec["prefix_hits"] = stats["prefix_hits"]
    rec["prefix_misses"] = stats["prefix_misses"]
    rec["prefix_hit_rate"] = stats["prefix_hit_rate"]
    # Closed-loop benches must COMPLETE everything — a shed here is a
    # misconfigured bench, not acceptable overload behavior — but the
    # record still says which kind of non-200 happened.
    rec["ok"] = bool(
        len(done) == len(replies)
        and verify_ok
        and rec["post_warmup_recompiles"] == 0
    )
    return rec


def _pct_from_values(values, q):
    """Client-side percentile over per-reply values, in ms (router mode
    has no shared registry to read — every replica owns its own)."""
    import numpy as np

    vals = [v for v in values if isinstance(v, (int, float))]
    if not vals:
        return None
    return round(float(np.percentile(vals, q)) * 1e3, 3)


def build_replica_stacks(args, serve_kw, n: int) -> list:
    """``n`` warmed in-proc serving stacks — (engine, batcher,
    frontend, registry) each on its own loopback port. Warmups run
    concurrently: XLA compilation releases the GIL, so N replicas warm
    in roughly one replica's wall time."""
    from tensorflow_examples_tpu.serving.batcher import ContinuousBatcher
    from tensorflow_examples_tpu.serving.engine import ServeConfig
    from tensorflow_examples_tpu.serving.frontend import ServingFrontend
    from tensorflow_examples_tpu.telemetry.registry import MetricsRegistry

    replicas: list = [None] * n

    def build_one(k: int) -> None:
        reg = MetricsRegistry()
        serve_cfg = ServeConfig(**serve_kw)
        if args.workdir:
            engine = build_checkpoint_engine(
                args.workdir, serve_cfg, registry=reg
            )
        else:
            engine = build_smoke_engine(serve_cfg, registry=reg)
        # Fleet identity (ISSUE 10): serve-side fault specs
        # (kind@replica:arg, $TPU_SERVE_FAULT_INJECT) key on it.
        engine.replica_id = k
        engine.warmup()
        batcher = ContinuousBatcher(engine, registry=reg).start()
        frontend = ServingFrontend(batcher, port=0).start()
        replicas[k] = (engine, batcher, frontend, reg)

    warm_threads = [
        threading.Thread(target=build_one, args=(k,), daemon=True)
        for k in range(n)
    ]
    for t in warm_threads:
        t.start()
    for t in warm_threads:
        t.join()
    return replicas


def run_router_bench(args) -> dict:
    """Stand up --replicas in-proc serving stacks behind the router and
    drive the tier end-to-end; returns the ``serve_router`` record."""
    import jax

    from tensorflow_examples_tpu.serving.router import (
        Router,
        RouterConfig,
        RouterFrontend,
    )

    serve_kw = dict(
        max_slots=args.max_slots,
        max_delay_s=0.002,
        request_timeout_s=args.timeout,
        kv_block_size=args.kv_block_size,
        kv_dtype=args.kv_dtype,
    )
    if args.smoke:
        serve_kw.update(prefill_bucket_floor=16, kv_bucket_floor=32)

    t0 = time.perf_counter()
    replicas = build_replica_stacks(args, serve_kw, args.replicas)
    warmup_s = time.perf_counter() - t0
    print(
        f"# {args.replicas} replicas warm "
        f"({replicas[0][0].expected_compiles()} programs each, paged "
        f"block={args.kv_block_size}, "
        f"kv_dtype={args.kv_dtype or 'fp'}) in {warmup_s:.1f}s",
        file=sys.stderr,
    )

    urls = [f"http://127.0.0.1:{fe.port}" for _, _, fe, _ in replicas]
    router = Router(
        urls,
        cfg=RouterConfig(
            probe_interval_s=0.2, request_timeout_s=args.timeout,
            prefix_affinity=(args.affinity != "off"),
            # Bench runs keep every trace (ISSUE 18): coverage banks
            # at 1.0 on a healthy tier, and the kept set is the full
            # population the attribution tool reads.
            trace_sample_fraction=1.0,
        ),
        trace_path=(args.trace_out or None),
    ).start()
    rfront = RouterFrontend(router, port=0).start()

    n = args.requests or (20 if args.smoke else 64)
    verify = args.verify if args.verify >= 0 else (3 if args.smoke else 0)
    model_cfg = replicas[0][0].model_cfg
    # Every 4th prompt shares a system-prompt-style prefix: the first
    # one prefills the prefix cache, later ones hit it (the record's
    # prefix_hit_rate is the measured claim, and the tier-1 smoke
    # asserts >= 1 hit).
    prompts = make_prompts(
        n,
        vocab=model_cfg.vocab_size,
        max_len=model_cfg.max_len,
        max_new=args.max_new_tokens,
        shared_prefix_every=4,
    )
    try:
        outcome = drive(
            None, prompts,
            concurrency=args.concurrency, max_new=args.max_new_tokens,
            temperature=args.temperature, top_k=args.top_k,
            http_url=rfront.url("/generate"), timeout=args.timeout,
        )
        verify_ok = True
        for i in range(min(verify, n)):
            reply = outcome["replies"][i]
            if reply is None or reply[0] != 200:
                verify_ok = False
                continue
            ref = replicas[0][0].reference_generate(
                prompts[i], max_new=args.max_new_tokens, seed=i,
                temperature=args.temperature, top_k=args.top_k,
            )
            if reply[1]["tokens"] != ref:
                verify_ok = False
                print(
                    f"# VERIFY FAIL req {i}: served "
                    f"{reply[1]['tokens']} != reference {ref}",
                    file=sys.stderr,
                )
        # Snapshot the router counters BEFORE the --slo probe phase:
        # probes ride the router too and must not inflate the banked
        # router_dispatched (ISSUE 19 exclusion contract).
        router_counters = router.registry.counter_values()
        if args.slo:
            # The organic traffic already fed router.alerts through
            # the trace path; the prober adds the black-box
            # availability sweep (router + every replica directly).
            from tensorflow_examples_tpu.serving.prober import (
                CanaryProber,
                fleet_targets,
            )

            prober = CanaryProber(
                fleet_targets(f"http://127.0.0.1:{rfront.port}", urls),
                alerts=router.alerts,
            )
            for _ in range(3):
                prober.probe_once()
    finally:
        rfront.close()
        router.close()
        for _, batcher, frontend, _ in replicas:
            batcher.close(drain=True)
            frontend.close()

    replies = outcome["replies"]
    done = [r for r in replies if r is not None and r[0] == 200]
    toks = sum(len(r[1].get("tokens", ())) for r in done)
    wall = outcome["wall_s"]
    tally = tally_replies(replies)
    errors = tally["errors"]

    def field(name):
        return [r[1].get(name) for r in done]

    tpots = [
        (r[1]["total_s"] - r[1]["ttft_s"]) / (len(r[1]["tokens"]) - 1)
        for r in done
        if isinstance(r[1].get("ttft_s"), (int, float))
        and isinstance(r[1].get("total_s"), (int, float))
        and len(r[1].get("tokens", ())) > 1
    ]
    hits = sum(e.pool.prefix_hits for e, _, _, _ in replicas)
    misses = sum(e.pool.prefix_misses for e, _, _, _ in replicas)
    recompiles = sum(
        e.post_warmup_recompiles() for e, _, _, _ in replicas
    )
    rec = {
        "bench": "serve_router",
        "backend": jax.default_backend(),
        "replicas": args.replicas,
        "requests": len(prompts),
        "completed": len(done),
        "errors": errors,
        "shed_total": tally["shed_total"],
        "rejected_total": tally["rejected_total"],
        "transport_errors": tally["transport_errors"],
        "concurrency": args.concurrency,
        "max_slots": args.max_slots,
        "wall_s": round(wall, 3),
        "req_per_s": round(len(done) / wall, 3) if wall else None,
        "tok_per_s": round(toks / wall, 3) if wall else None,
        "generated_tokens": toks,
        "queue_wait_p95_ms": _pct_from_values(field("queue_wait_s"), 95),
        "ttft_p50_ms": _pct_from_values(field("ttft_s"), 50),
        "ttft_p95_ms": _pct_from_values(field("ttft_s"), 95),
        "tpot_p50_ms": _pct_from_values(tpots, 50),
        "tpot_p95_ms": _pct_from_values(tpots, 95),
        "e2e_p50_ms": _pct_from_values(field("total_s"), 50),
        "e2e_p95_ms": _pct_from_values(field("total_s"), 95),
        "expected_compiles": sum(
            e.expected_compiles() for e, _, _, _ in replicas
        ),
        "compiles": sum(
            int(reg.counter_values().get("compile/count", 0))
            for _, _, _, reg in replicas
        ),
        "post_warmup_recompiles": recompiles,
        "shed": sum(
            int(reg.counter_values().get("serving/shed_total", 0))
            for _, _, _, reg in replicas
        ),
        "kv_block_size": args.kv_block_size,
        "kv_bits": replicas[0][0].pool.kv_bits,
        "prefix_hits": hits,
        "prefix_misses": misses,
        "prefix_hit_rate": hits / (hits + misses) if hits + misses else 0.0,
        "router_dispatched": int(
            router_counters.get("router/dispatched_total", 0)
        ),
        "router_retries": int(
            router_counters.get("router/retries_total", 0)
        ),
        "router_no_replica": int(
            router_counters.get("router/no_replica_total", 0)
        ),
        "verified": min(verify, n),
        "verify_ok": verify_ok,
        "warmup_s": round(warmup_s, 3),
        "affinity": args.affinity != "off",
        "transport": "router-http",
    }
    # The router owns the traces in this mode; its recorder's summary
    # is the record's tracing claim (ISSUE 18). stats() only reads
    # registry counters, so the closed router is safe to ask.
    rec.update(router.recorder.stats())
    rec["ok"] = bool(
        len(done) == len(replies) and verify_ok and recompiles == 0
    )
    if args.slo:
        # Healthy fleet smoke banks alert_count=0 and
        # probe_success_rate=1.0 (the ISSUE 19 acceptance golden).
        rec.update(router.alerts.stats())
        rec["ok"] = bool(rec["ok"] and rec["alert_count"] == 0)
    return rec


def run_affinity_bench(args) -> dict:
    """``--router --affinity ab`` (ISSUE 12): the SAME shared-prefix-
    heavy prompt sequence through one 2-replica fleet twice — prefix
    affinity OFF, then ON, with every replica's prefix cache reset
    between phases so both start cold — banking one ``serve_affinity``
    record. Requests run sequentially with a manual probe sweep before
    each dispatch, so routing (and therefore the hit counts) is
    deterministic: the record's claim is measured, not sampled.

    The claims it carries: ``prefix_hit_rate_affinity`` strictly above
    ``prefix_hit_rate_no_affinity`` on shared-prefix traffic (the
    acceptance headline — bench_gate pins the -affinity rate as a
    minimum), TTFT p50/p95 split shared-vs-cold for the on phase, every
    verified stream token-identical to the unbatched reference, and
    zero post-warmup recompiles across both phases."""
    import jax

    from tensorflow_examples_tpu.serving.router import (
        Router,
        RouterConfig,
    )

    serve_kw = dict(
        max_slots=args.max_slots,
        max_delay_s=0.002,
        request_timeout_s=args.timeout,
        kv_block_size=args.kv_block_size,
        kv_dtype=args.kv_dtype,
    )
    if args.smoke:
        # A single coarse bucket per program family: the A/B measures
        # ROUTING (hit counts, shared-vs-cold TTFT), which is bucket-
        # granularity-agnostic — a 3-program ladder keeps the tier-1
        # smoke's two warmups cheap.
        serve_kw.update(prefill_bucket_floor=64, kv_bucket_floor=64)

    n = args.requests or (12 if args.smoke else 48)
    verify = args.verify if args.verify >= 0 else (3 if args.smoke else 0)
    n_rep = args.replicas  # main() already defaulted it (2 for --router)

    t0 = time.perf_counter()
    replicas = build_replica_stacks(args, serve_kw, n_rep)
    warmup_s = time.perf_counter() - t0
    urls = [f"http://127.0.0.1:{fe.port}" for _, _, fe, _ in replicas]

    def phase(affinity: bool, prompts):
        # Fresh caches per phase (the A/B must compare cold-start to
        # cold-start) without paying a second fleet warmup: reset every
        # pool — prefix cache, hit counters, and all slots — while the
        # batchers idle between the sequential requests.
        for engine, _, _, _ in replicas:
            engine.pool.reset()
        # No probe thread: one manual sweep before every dispatch keeps
        # the router's load/digest view exact, so the phase's routing
        # is a pure function of the prompt sequence.
        router = Router(
            urls,
            cfg=RouterConfig(
                probe_interval_s=3600.0,
                request_timeout_s=args.timeout,
                prefix_affinity=affinity,
            ),
        )
        replies = []
        t0 = time.perf_counter()
        try:
            for i, prompt in enumerate(prompts):
                router.probe_once()
                replies.append(router.handle(
                    {
                        "prompt": prompt,
                        "max_new_tokens": args.max_new_tokens,
                        "temperature": args.temperature,
                        "top_k": args.top_k,
                        "seed": i,
                    },
                    kind="generate",
                ))
            wall = time.perf_counter() - t0
            verify_ok = True
            for i in range(min(verify, len(prompts))):
                status, reply = replies[i]
                ref = replicas[0][0].reference_generate(
                    prompts[i], max_new=args.max_new_tokens, seed=i,
                    temperature=args.temperature, top_k=args.top_k,
                )
                if status != 200 or reply.get("tokens") != ref:
                    verify_ok = False
                    print(
                        f"# VERIFY FAIL affinity req {i}: "
                        f"{reply.get('tokens')} != reference {ref}",
                        file=sys.stderr,
                    )
            hits = sum(e.pool.prefix_hits for e, _, _, _ in replicas)
            misses = sum(e.pool.prefix_misses for e, _, _, _ in replicas)
            recompiles = sum(
                e.post_warmup_recompiles() for e, _, _, _ in replicas
            )
            affinity_hits = int(
                router.registry.counter_values().get(
                    "router/affinity_hits_total", 0
                )
            )
        finally:
            router.close()
        return {
            "replies": replies,
            "wall_s": wall,
            "hits": hits,
            "misses": misses,
            "recompiles": recompiles,
            "affinity_hits": affinity_hits,
            "verify_ok": verify_ok,
        }

    if args.workdir:
        from tensorflow_examples_tpu.workloads import gpt2

        model_cfg = gpt2.model_config(gpt2.Gpt2Config())
    else:
        from tensorflow_examples_tpu.models import transformer

        model_cfg = transformer.TransformerConfig(**SMOKE_MODEL)
    prompts, groups = make_affinity_prompts(
        n, vocab=model_cfg.vocab_size, max_len=model_cfg.max_len,
        max_new=args.max_new_tokens, block=args.kv_block_size,
    )
    try:
        off = phase(False, prompts)
        on = phase(True, prompts)
    finally:
        for _, batcher, frontend, _ in replicas:
            batcher.close(drain=True)
            frontend.close()

    def rate(p):
        looked = p["hits"] + p["misses"]
        return p["hits"] / looked if looked else 0.0

    def group_ttfts(p, want):
        return [
            reply.get("ttft_s")
            for (status, reply), g in zip(p["replies"], groups)
            if status == 200 and g == want
        ]

    errors = sum(
        1 for p in (off, on) for status, _ in p["replies"]
        if status != 200
    )
    on_rate, off_rate = rate(on), rate(off)
    recompiles = off["recompiles"] + on["recompiles"]
    rec = {
        "bench": "serve_affinity",
        "backend": jax.default_backend(),
        "replicas": n_rep,
        "requests": 2 * n,
        "requests_per_phase": n,
        "shared_requests_per_phase": groups.count("shared"),
        "errors": errors,
        "wall_s": round(off["wall_s"] + on["wall_s"], 3),
        "warmup_s": round(warmup_s, 3),
        "kv_block_size": args.kv_block_size,
        "prefix_hit_rate_affinity": round(on_rate, 4),
        "prefix_hit_rate_no_affinity": round(off_rate, 4),
        "affinity_hit_gain": round(on_rate - off_rate, 4),
        "prefix_hits_on": on["hits"],
        "prefix_hits_off": off["hits"],
        "affinity_dispatches": on["affinity_hits"],
        "ttft_shared_p50_ms": _pct_from_values(
            group_ttfts(on, "shared"), 50
        ),
        "ttft_shared_p95_ms": _pct_from_values(
            group_ttfts(on, "shared"), 95
        ),
        "ttft_cold_p50_ms": _pct_from_values(
            group_ttfts(on, "cold"), 50
        ),
        "ttft_cold_p95_ms": _pct_from_values(
            group_ttfts(on, "cold"), 95
        ),
        "post_warmup_recompiles": recompiles,
        "verified": min(verify, n),
        "verify_ok": bool(off["verify_ok"] and on["verify_ok"]),
        "transport": "router-http",
    }
    rec["ok"] = bool(
        errors == 0
        and rec["verify_ok"]
        and recompiles == 0
        and on_rate > off_rate
    )
    return rec


# Declared p95 budget for the chaos record (ISSUE 10): the chaos
# phase's client-observed e2e p95 must stay within this multiple of the
# fault-free baseline phase's. Generous on purpose — a failover adds
# one full re-prefill + backoff to the victims, and the 2-vCPU CI rig
# is load-noisy; the claim is "bounded", not "free".
CHAOS_P95_BUDGET = 25.0

# ISSUE 16: detect-to-serving promotion wall the serve_takeover record
# gates on (the time from the standby noticing the stale lease to its
# first post-promotion dispatch being possible — probe rebuild plus
# journal replay; the heartbeat miss budget itself is configured, not
# measured).
TAKEOVER_LATENCY_BUDGET_S = 10.0


def _client_p95_ms(outcome) -> float | None:
    vals = [
        s for s, r in zip(outcome["client_s"], outcome["replies"])
        if s is not None and r is not None and r[0] == 200
    ]
    return _pct_from_values(vals, 95)


def _drive_takeover(endpoints, prompts, *, concurrency, max_new,
                    temperature, top_k, timeout) -> dict:
    """Closed loop with CLIENT-SIDE failover (ISSUE 16): every request
    carries an idempotency key, and a worker that sees a transport
    reset or a fenced/retryable 503 simply retries against the other
    router endpoint until its deadline — the protocol a real client of
    a primary/standby pair speaks. Because retries reuse the
    request_id, a request the dying primary already completed comes
    back as a journal dedupe hit, and one it only accepted comes back
    from the standby's replay; the caller can't tell, which is the
    point."""
    replies: list[tuple[int, dict] | None] = [None] * len(prompts)
    client_s: list[float | None] = [None] * len(prompts)
    retries = [0]
    next_i = [0]
    lock = threading.Lock()

    def worker():
        while True:
            with lock:
                i = next_i[0]
                if i >= len(prompts):
                    return
                next_i[0] += 1
            body = {
                "prompt": prompts[i],
                "max_new_tokens": max_new,
                "temperature": temperature,
                "top_k": top_k,
                "seed": i,  # per-request stream: replayable
                "request_id": f"tko-{i}",
            }
            t_req = time.perf_counter()
            deadline = t_req + timeout
            last = None
            while True:
                for url in endpoints:
                    last = _post_json(url, body, timeout)
                    if last[0] == 200:
                        break
                    with lock:
                        retries[0] += 1
                if last[0] == 200 or time.perf_counter() > deadline:
                    break
                time.sleep(0.05)
            replies[i] = last
            client_s[i] = time.perf_counter() - t_req

    t0 = time.perf_counter()
    threads = [
        threading.Thread(
            target=worker, name=f"serve-bench-{k}", daemon=True
        )
        for k in range(concurrency)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=timeout * max(1, len(prompts)))
    wall = time.perf_counter() - t0
    return {
        "replies": replies, "client_s": client_s, "wall_s": wall,
        "client_retries": retries[0],
    }


def _takeover_phase(args, fleet, mk) -> dict:
    """The --chaos router-kill phase (ISSUE 16): a fresh RouterPair
    over the already-warm fleet, ``killrouter@T`` armed mid-stream,
    clients failing over between the two endpoints. Banks the
    ``serve_takeover`` record: takeover_latency_s, ZERO lost accepted
    requests, resumed_streams, dedup_hits — each measured, not
    asserted by construction."""
    import shutil
    import tempfile

    from tensorflow_examples_tpu.serving.chaos import RouterPair
    from tensorflow_examples_tpu.utils import faults as faults_mod

    n = args.requests or (12 if args.smoke else 48)
    kill_at = max(2, n // 3)
    miss_budget_s = 1.0
    tmp = tempfile.mkdtemp(prefix="serve_takeover_")
    pair = RouterPair(
        fleet.urls,
        journal_path=os.path.join(tmp, "journal.jsonl"),
        lease_path=os.path.join(tmp, "lease.json"),
        router_cfg=fleet.router_cfg,
        standby_interval_s=0.1,
        miss_budget_s=miss_budget_s,
    ).start()
    prompts = make_prompts(n, seed=303, **mk)
    faults_mod.serve_clear()
    fault_engine = faults_mod.serve_install(f"killrouter@{kill_at}")
    print(
        f"# takeover phase: killrouter@{kill_at} over {n} requests, "
        f"heartbeat miss budget {miss_budget_s:.1f}s",
        file=sys.stderr,
    )
    try:
        out = _drive_takeover(
            pair.endpoints(), prompts,
            concurrency=args.concurrency,
            max_new=args.max_new_tokens,
            temperature=args.temperature, top_k=args.top_k,
            timeout=args.timeout,
        )
        # The standby starts serving the moment it grabs the lease,
        # BEFORE journal replay finishes — clients can drain while
        # promote() is still running, so wait for the completion event
        # instead of sampling it.
        promoted = pair.monitor.promoted.wait(
            timeout=TAKEOVER_LATENCY_BUDGET_S
        )
        # Every stream — died-in-flight, journal-replayed, deduped —
        # must be token-identical to the unbatched reference.
        verify_ok = True
        ref_engine = fleet.replicas[0].engine
        verify = min(len(prompts), max(
            args.verify if args.verify >= 0 else 3, 3
        ))
        for i in range(verify):
            reply = out["replies"][i]
            if reply is None or reply[0] != 200:
                verify_ok = False
                continue
            ref = ref_engine.reference_generate(
                prompts[i], max_new=args.max_new_tokens, seed=i,
                temperature=args.temperature, top_k=args.top_k,
            )
            if reply[1]["tokens"] != ref:
                verify_ok = False
                print(
                    f"# VERIFY FAIL takeover req {i}: served "
                    f"{reply[1]['tokens']} != reference {ref}",
                    file=sys.stderr,
                )
        # Idempotency: a duplicated request_id retry must return the
        # ORIGINAL tokens as a dedupe hit, not burn a generation.
        active = pair.endpoints()[1] if promoted else pair.endpoints()[0]
        first_ok = next(
            (i for i, r in enumerate(out["replies"])
             if r is not None and r[0] == 200), None
        )
        dedup_ok = False
        resume_ok = False
        if first_ok is not None:
            orig = out["replies"][first_ok][1]["tokens"]
            status, dup = _post_json(active, {
                "prompt": prompts[first_ok],
                "max_new_tokens": args.max_new_tokens,
                "temperature": args.temperature, "top_k": args.top_k,
                "seed": first_ok, "request_id": f"tko-{first_ok}",
            }, args.timeout)
            dedup_ok = (
                status == 200 and dup.get("dedup") is True
                and dup.get("tokens") == orig
            )
            # Client resume: reconnect at a committed offset, get the
            # remainder of the SAME stream.
            cut = max(1, len(orig) // 2)
            status, res = _post_json(active, {
                "prompt": prompts[first_ok],
                "max_new_tokens": args.max_new_tokens,
                "temperature": args.temperature, "top_k": args.top_k,
                "seed": first_ok, "request_id": f"tko-{first_ok}",
                "resume_from": cut,
            }, args.timeout)
            resume_ok = (
                status == 200 and res.get("tokens") == orig[cut:]
            )
        tally = tally_replies(out["replies"])
        counters = pair.registry.counter_values()
        recompiles = sum(
            rep.engine.post_warmup_recompiles()
            for rep in fleet.replicas if rep.engine is not None
        )
        lost = n - tally["completed"]
        latency = pair.monitor.takeover_latency_s
        rec = {
            "bench": "serve_takeover",
            "replicas": len(fleet.replicas),
            "fault_spec": f"killrouter@{kill_at}",
            "faults_fired": len(fault_engine.fired),
            "requests": n,
            "completed": tally["completed"],
            "lost_requests": lost,
            "client_retries": out["client_retries"],
            "concurrency": args.concurrency,
            "promoted": promoted,
            "heartbeat_miss_budget_s": miss_budget_s,
            "takeover_latency_s": (
                round(latency, 4) if latency is not None else None
            ),
            "takeover_budget_s": TAKEOVER_LATENCY_BUDGET_S,
            "replayed_intents": pair.monitor.replayed,
            "journal_appends": int(
                counters.get("router/journal_appends_total", 0)
            ),
            "resumed_streams": int(
                counters.get("router/resumed_streams_total", 0)
            ),
            "dedup_hits": int(
                counters.get("router/dedup_hits_total", 0)
            ),
            "fenced_dispatches": int(
                counters.get("router/fenced_dispatch_total", 0)
            ),
            "post_warmup_recompiles": recompiles,
            "verified": verify,
            "verify_ok": verify_ok,
            "dedup_ok": dedup_ok,
            "resume_ok": resume_ok,
            "transport": "router-http",
        }
        rec["ok"] = bool(
            tally["completed"] == n
            and lost == 0
            and promoted
            and fault_engine.fired
            and verify_ok
            and dedup_ok
            and resume_ok
            and rec["dedup_hits"] >= 1
            and recompiles == 0
            and latency is not None
            and latency <= TAKEOVER_LATENCY_BUDGET_S
        )
        return rec
    finally:
        faults_mod.serve_clear()
        pair.close()
        shutil.rmtree(tmp, ignore_errors=True)


def run_chaos_bench(args) -> dict:
    """ISSUE 10: availability under injected faults. Stands up a
    3-replica (default) in-proc paged fleet WITH supervision
    (serving/chaos.ChaosFleet), measures a fault-free baseline phase,
    arms a deterministic serve fault schedule (default: crash the
    replica the router dispatches to next, mid-decode), drives a chaos
    phase through the hardened router, then waits for the supervisor
    to restore the fleet. The record is the
    availability claim CI gates: ``error_rate`` (must be 0 — in-flight
    failover means a replica death drops nothing), ``failover_count``,
    ejection/restart counters, and ``p95_vs_baseline`` (client-observed
    e2e p95 ratio, bounded by the declared budget)."""
    import jax

    from tensorflow_examples_tpu.serving.chaos import ChaosFleet
    from tensorflow_examples_tpu.serving.engine import ServeConfig
    from tensorflow_examples_tpu.serving.router import (
        RouterConfig,
        RouterFrontend,
    )
    from tensorflow_examples_tpu.telemetry.registry import MetricsRegistry
    from tensorflow_examples_tpu.utils import faults as faults_mod

    serve_kw = dict(
        max_slots=args.max_slots,
        max_delay_s=0.002,
        request_timeout_s=args.timeout,
        kv_block_size=args.kv_block_size,
        kv_dtype=args.kv_dtype,
    )
    if args.smoke:
        serve_kw.update(prefill_bucket_floor=16, kv_bucket_floor=32)

    def factory():
        reg = MetricsRegistry()
        serve_cfg = ServeConfig(**serve_kw)
        if args.workdir:
            return build_checkpoint_engine(
                args.workdir, serve_cfg, registry=reg
            )
        return build_smoke_engine(serve_cfg, registry=reg)

    n_replicas = args.replicas if args.replicas > 0 else 3
    fleet = ChaosFleet(
        [factory] * n_replicas,
        router_cfg=RouterConfig(
            probe_interval_s=0.1,
            request_timeout_s=args.timeout,
            retry_budget_s=min(30.0, args.timeout),
            max_retries=4,
            eject_after=2,
            eject_cooldown_s=1.0,
        ),
    )
    t0 = time.perf_counter()
    fleet.start()
    warmup_s = time.perf_counter() - t0
    print(
        f"# chaos fleet: {n_replicas} supervised paged replicas warm "
        f"in {warmup_s:.1f}s",
        file=sys.stderr,
    )
    rfront = RouterFrontend(fleet.router, port=0).start()

    n = args.requests or (12 if args.smoke else 48)
    verify = args.verify if args.verify >= 0 else (3 if args.smoke else 0)
    model_cfg = fleet.replicas[0].engine.model_cfg
    mk = dict(
        vocab=model_cfg.vocab_size, max_len=model_cfg.max_len,
        max_new=args.max_new_tokens, shared_prefix_every=4,
    )
    drive_kw = dict(
        concurrency=args.concurrency, max_new=args.max_new_tokens,
        temperature=args.temperature, top_k=args.top_k,
        http_url=rfront.url("/generate"), timeout=args.timeout,
    )
    base_prompts = make_prompts(n, seed=101, **mk)
    chaos_prompts = make_prompts(n, seed=202, **mk)
    fault_engine = None
    try:
        base_out = drive(None, base_prompts, **drive_kw)
        spec = args.fault_spec
        if not spec:
            # The default schedule crashes the replica the router
            # dispatches to NEXT, mid-decode: in a probe taken now
            # every replica is idle, so that is the one with the fewest
            # dispatches (Router.pick). A fixed index is reached only
            # if the router happens to send that replica work between
            # two probes, which the baseline's leftovers decide.
            fleet.router.probe_once()
            snaps = fleet.router.replica_snapshots()
            spec = "crash@%d:4" % min(
                range(n_replicas), key=lambda k: snaps[k]["dispatched"]
            )
        print(f"# chaos schedule: {spec}", file=sys.stderr)
        fault_engine = faults_mod.serve_install(spec)
        chaos_out = drive(None, chaos_prompts, **drive_kw)
        restored = fleet.await_fleet_green(
            n_replicas, timeout_s=args.timeout * 3
        )
        # Verify chaos-phase replies (the failed-over ones included)
        # token-for-token against the unbatched reference on a
        # SURVIVOR engine — failover replay must be invisible.
        verify_ok = True
        ref_engine = fleet.replicas[0].engine
        for i in range(min(verify, n)):
            reply = chaos_out["replies"][i]
            if reply is None or reply[0] != 200:
                verify_ok = False
                continue
            ref = ref_engine.reference_generate(
                chaos_prompts[i],
                max_new=args.max_new_tokens, seed=i,
                temperature=args.temperature, top_k=args.top_k,
            )
            if reply[1]["tokens"] != ref:
                verify_ok = False
                print(
                    f"# VERIFY FAIL chaos req {i}: served "
                    f"{reply[1]['tokens']} != reference {ref}",
                    file=sys.stderr,
                )
        # ISSUE 16: the router-kill phase rides the same warm fleet —
        # a fresh primary/standby RouterPair, killrouter mid-stream,
        # clients failing over on their idempotency keys.
        takeover = _takeover_phase(args, fleet, mk)
    finally:
        faults_mod.serve_clear()
        rfront.close()
        supervisor = fleet.supervisor
        router = fleet.router
        fleet.close()

    base_tally = tally_replies(base_out["replies"])
    chaos_tally = tally_replies(chaos_out["replies"])
    base_done = base_tally["completed"]
    chaos_done = chaos_tally["completed"]
    # ISSUE 13 satellite: error_rate counts transport failures and
    # unexpected statuses ONLY — a load-shed 503 is stamped separately
    # (shed_total), so the error_rate-at-0 gate criterion says "no
    # request was LOST", not "the fleet never shed".
    base_errors = base_tally["errors"]
    chaos_errors = chaos_tally["errors"]
    shed_total = base_tally["shed_total"] + chaos_tally["shed_total"]
    rejected_total = (
        base_tally["rejected_total"] + chaos_tally["rejected_total"]
    )
    base_p95 = _client_p95_ms(base_out)
    chaos_p95 = _client_p95_ms(chaos_out)
    p95_ratio = (
        round(chaos_p95 / base_p95, 3)
        if base_p95 and chaos_p95 else None
    )
    counters = router.registry.counter_values()
    restarts = sum(supervisor.restarts.values())
    survivor_recompiles = sum(
        rep.engine.post_warmup_recompiles()
        for rep in fleet.replicas if rep.engine is not None
    )
    errors = base_errors + chaos_errors
    fired = list(fault_engine.fired) if fault_engine is not None else []
    rec = {
        "bench": "serve_chaos",
        "backend": jax.default_backend(),
        "replicas": n_replicas,
        "fault_spec": spec,
        "faults_fired": len(fired),
        "requests": 2 * n,
        "completed": base_done + chaos_done,
        "errors": errors,
        "error_rate": round(errors / (2 * n), 4),
        "shed_total": shed_total,
        "rejected_total": rejected_total,
        "transport_errors": (
            base_tally["transport_errors"]
            + chaos_tally["transport_errors"]
        ),
        "concurrency": args.concurrency,
        "baseline_e2e_p95_ms": base_p95,
        "chaos_e2e_p95_ms": chaos_p95,
        "p95_vs_baseline": p95_ratio,
        "p95_budget": CHAOS_P95_BUDGET,
        "failover_count": int(
            counters.get("router/failovers_total", 0)
        ),
        "router_retries": int(counters.get("router/retries_total", 0)),
        "router_ejections": int(
            counters.get("router/ejections_total", 0)
        ),
        "router_readmits": int(
            counters.get("router/readmits_total", 0)
        ),
        "router_restarts": restarts,
        "fleet_restored": bool(restored),
        "post_warmup_recompiles": survivor_recompiles,
        "verified": min(verify, n),
        "verify_ok": verify_ok,
        "warmup_s": round(warmup_s, 3),
        "kv_block_size": args.kv_block_size,
        "transport": "router-http",
    }
    # ok still requires every request SERVED (shed included in the
    # completeness check — this closed-loop tier must not shed), but
    # error_rate itself stays an honest lost-request rate.
    rec["ok"] = bool(
        base_done + chaos_done == 2 * n
        and verify_ok
        and restored
        and fired
        and survivor_recompiles == 0
        and (p95_ratio is None or p95_ratio <= CHAOS_P95_BUDGET)
    )
    rec["takeover"] = takeover
    return rec


def run_spec_bench(args) -> dict:
    """--spec-decode K (ISSUE 11): drive the SAME prompt-like prompts
    through two freshly built engines — speculation off, then
    speculation on at draft window K — and bank one ``serve_spec``
    record. The claims it carries, all measured: ``tpot_speedup``
    (off-phase TPOT p50 / on-phase TPOT p50 — the headline),
    ``draft_hit_rate`` and ``accepted_per_step`` p50 (why the headline
    moved), ``tokens_identical`` (every on-phase stream token-for-token
    equal to its off-phase twin — speculation is a latency
    optimization, never a numerics change), and zero post-warmup
    recompiles across BOTH engines (the verify_k rungs are part of the
    warmed ladder, counted in expected_compiles)."""
    import jax

    from tensorflow_examples_tpu.serving.batcher import ContinuousBatcher
    from tensorflow_examples_tpu.serving.engine import ServeConfig
    from tensorflow_examples_tpu.serving.frontend import ServingFrontend
    from tensorflow_examples_tpu.telemetry.registry import MetricsRegistry

    serve_kw = dict(
        max_slots=args.max_slots,
        max_delay_s=0.002,
        request_timeout_s=args.timeout,
        kv_block_size=args.kv_block_size,
        kv_dtype=args.kv_dtype,
    )
    if args.smoke:
        serve_kw.update(prefill_bucket_floor=16, kv_bucket_floor=32)

    def build(spec_k: int):
        reg = MetricsRegistry()
        cfg = ServeConfig(spec_decode_k=spec_k, **serve_kw)
        if args.workdir:
            eng = build_checkpoint_engine(args.workdir, cfg, registry=reg)
        else:
            eng = build_smoke_engine(cfg, registry=reg)
        eng.warmup()
        return eng, reg

    def phase(eng, reg, prompts):
        batcher = ContinuousBatcher(eng, registry=reg).start()
        frontend = ServingFrontend(batcher, port=0)  # in-proc transport
        try:
            outcome = drive(
                frontend, prompts,
                concurrency=args.concurrency,
                max_new=args.max_new_tokens,
                temperature=args.temperature, top_k=args.top_k,
                http_url=None, timeout=args.timeout,
            )
        finally:
            batcher.close(drain=True)
            frontend.close()
        return outcome

    n = args.requests or (12 if args.smoke else 48)
    # Both engines (and their full AOT warmups) are built BEFORE the
    # clock starts: wall_s measures request driving only, comparable
    # with every other serve_bench record's.
    off_eng, off_reg = build(0)
    on_eng, on_reg = build(args.spec_decode)
    model_cfg = off_eng.model_cfg
    prompts = make_patterned_prompts(
        n, vocab=model_cfg.vocab_size, max_len=model_cfg.max_len,
        max_new=args.max_new_tokens,
    )
    t0 = time.perf_counter()
    off_out = phase(off_eng, off_reg, prompts)
    on_out = phase(on_eng, on_reg, prompts)
    wall = time.perf_counter() - t0

    def done(outcome):
        return [
            r for r in outcome["replies"] if r is not None and r[0] == 200
        ]

    errors = 2 * n - len(done(off_out)) - len(done(on_out))
    identical = len(done(off_out)) == n and len(done(on_out)) == n and all(
        a[1].get("tokens") == b[1].get("tokens")
        for a, b in zip(off_out["replies"], on_out["replies"])
    )

    def tpot_ms(reg, q):
        h = reg.histogram_summaries().get("serving/tpot")
        v = h and h.get(f"p{q}")
        return round(v * 1e3, 4) if v is not None else None

    def toks_per_s(outcome):
        toks = sum(len(r[1].get("tokens", ())) for r in done(outcome))
        return round(toks / outcome["wall_s"], 3) if outcome["wall_s"] \
            else None

    on_counters = on_reg.counter_values()
    req_steps = on_counters.get("serving/spec_request_steps", 0)
    drafted = on_counters.get("serving/spec_drafted_total", 0)
    accepted = on_counters.get("serving/spec_accepted_total", 0)
    acc_hist = on_reg.histogram_summaries().get(
        "serving/accepted_per_step"
    )
    off_tpot, on_tpot = tpot_ms(off_reg, 50), tpot_ms(on_reg, 50)
    recompiles = (
        off_eng.post_warmup_recompiles() + on_eng.post_warmup_recompiles()
    )
    rec = {
        "bench": "serve_spec",
        "backend": jax.default_backend(),
        "requests": n,
        "spec_k": args.spec_decode,
        "draft": "ngram",
        "max_new_tokens": args.max_new_tokens,
        "concurrency": args.concurrency,
        "temperature": args.temperature,
        "errors": errors,
        "wall_s": round(wall, 3),
        "tpot_off_p50_ms": off_tpot,
        "tpot_on_p50_ms": on_tpot,
        "tpot_speedup": (
            round(off_tpot / on_tpot, 3)
            if off_tpot and on_tpot else None
        ),
        "tok_per_s_off": toks_per_s(off_out),
        "tok_per_s_on": toks_per_s(on_out),
        "draft_hit_rate": (
            round(accepted / drafted, 4) if drafted else 0.0
        ),
        "accepted_per_step": (
            round((req_steps + accepted) / req_steps, 4)
            if req_steps else 0.0
        ),
        "accepted_per_step_p50": (
            acc_hist and acc_hist.get("p50")
        ),
        "tokens_identical": identical,
        "expected_compiles": on_eng.expected_compiles(),
        "post_warmup_recompiles": recompiles,
        "kv_block_size": serve_kw["kv_block_size"],
        "verified": n,
        "verify_ok": identical,
        "transport": "inproc",
    }
    rec["ok"] = bool(errors == 0 and identical and recompiles == 0)
    return rec


# Divergence floor for the serve_quant verdict: mean fraction of
# stream positions agreeing with the f32 twin — the same gate shape
# the int8 KV golden uses (first token exact, bounded divergence).
QUANT_AGREEMENT_FLOOR = 0.75


def run_quant_bench(args) -> dict:
    """--weight-dtype D (ISSUE 15): drive the SAME mixed-length
    prompts through two freshly built engines — weights served as
    loaded (f32), then weight-quantized to D via the precision
    registry — and bank one ``serve_quant`` record. The claims it
    carries, all measured: ``hbm_bytes_per_replica`` (quantized param
    bytes from ``engine.byte_breakdown``) with ``hbm_ratio_vs_f32``
    (the ~4x HBM-per-replica claim, the fleet-economics headline),
    ``tpot_speedup_quant`` / ``ttft_speedup_quant`` (f32 p50 / quant
    p50 — decode is memory-bound, so 1-byte weights buy TPOT on HBM
    rigs; ~1.0 where weights fit in cache), and the divergence verdict
    int8 KV established: ``first_token_exact`` over EVERY request plus
    ``stream_agreement`` >= QUANT_AGREEMENT_FLOOR, with zero
    post-warmup recompiles on both engines (the quantized tree warms
    the same AOT ladder)."""
    import jax

    from tensorflow_examples_tpu.serving.batcher import ContinuousBatcher
    from tensorflow_examples_tpu.serving.engine import ServeConfig
    from tensorflow_examples_tpu.serving.frontend import ServingFrontend
    from tensorflow_examples_tpu.telemetry.registry import MetricsRegistry

    serve_kw = dict(
        max_slots=args.max_slots,
        max_delay_s=0.002,
        request_timeout_s=args.timeout,
        kv_block_size=args.kv_block_size,
        kv_dtype=args.kv_dtype,
    )
    if args.smoke:
        serve_kw.update(prefill_bucket_floor=16, kv_bucket_floor=32)

    def build(weight_dtype: str):
        reg = MetricsRegistry()
        cfg = ServeConfig(weight_dtype=weight_dtype, **serve_kw)
        if args.workdir:
            eng = build_checkpoint_engine(args.workdir, cfg, registry=reg)
        else:
            eng = build_smoke_engine(cfg, registry=reg)
        eng.warmup()
        return eng, reg

    def phase(eng, reg, prompts):
        batcher = ContinuousBatcher(eng, registry=reg).start()
        frontend = ServingFrontend(batcher, port=0)  # in-proc transport
        try:
            outcome = drive(
                frontend, prompts,
                concurrency=args.concurrency,
                max_new=args.max_new_tokens,
                temperature=args.temperature, top_k=args.top_k,
                http_url=None, timeout=args.timeout,
            )
        finally:
            batcher.close(drain=True)
            frontend.close()
        return outcome

    n = args.requests or (12 if args.smoke else 48)
    # Both engines (and their AOT warmups) are built before the clock
    # starts: wall_s measures request driving only.
    f32_eng, f32_reg = build("")
    q_eng, q_reg = build(args.weight_dtype)
    model_cfg = f32_eng.model_cfg
    prompts = make_prompts(
        n, vocab=model_cfg.vocab_size, max_len=model_cfg.max_len,
        max_new=args.max_new_tokens,
    )
    t0 = time.perf_counter()
    f32_out = phase(f32_eng, f32_reg, prompts)
    q_out = phase(q_eng, q_reg, prompts)
    wall = time.perf_counter() - t0

    def done(outcome):
        return [
            r for r in outcome["replies"] if r is not None and r[0] == 200
        ]

    errors = 2 * n - len(done(f32_out)) - len(done(q_out))
    # first_token_exact is a NUMERICS verdict over the pairs that both
    # completed — a transport error/timeout is already counted in
    # ``errors`` (which fails ``ok`` on its own) and must not
    # masquerade as quantization divergence.
    first_exact = True
    agreements = []
    for a, b in zip(f32_out["replies"], q_out["replies"]):
        if a is None or b is None or a[0] != 200 or b[0] != 200:
            continue
        ta, tb = a[1].get("tokens") or [], b[1].get("tokens") or []
        if not ta or not tb or ta[0] != tb[0]:
            first_exact = False
        width = max(len(ta), len(tb))
        if width:
            agreements.append(
                sum(x == y for x, y in zip(ta, tb)) / width
            )
    agreement = (
        round(sum(agreements) / len(agreements), 4)
        if agreements else 0.0
    )

    def p50_ms(reg, hist):
        h = reg.histogram_summaries().get(f"serving/{hist}")
        v = h and h.get("p50")
        return round(v * 1e3, 4) if v is not None else None

    def speedup(f32_v, q_v):
        return round(f32_v / q_v, 3) if f32_v and q_v else None

    def toks_per_s(outcome):
        toks = sum(len(r[1].get("tokens", ())) for r in done(outcome))
        return round(toks / outcome["wall_s"], 3) if outcome["wall_s"] \
            else None

    bb_q = q_eng.byte_breakdown()
    bb_f = f32_eng.byte_breakdown()
    tpot_f, tpot_q = p50_ms(f32_reg, "tpot"), p50_ms(q_reg, "tpot")
    ttft_f, ttft_q = p50_ms(f32_reg, "ttft"), p50_ms(q_reg, "ttft")
    recompiles = (
        f32_eng.post_warmup_recompiles() + q_eng.post_warmup_recompiles()
    )
    rec = {
        "bench": "serve_quant",
        "backend": jax.default_backend(),
        "requests": n,
        "weight_dtype": args.weight_dtype,
        "weight_bits": bb_q["weight_bits"],
        "max_new_tokens": args.max_new_tokens,
        "concurrency": args.concurrency,
        "temperature": args.temperature,
        "errors": errors,
        "wall_s": round(wall, 3),
        "tpot_f32_p50_ms": tpot_f,
        "tpot_quant_p50_ms": tpot_q,
        "tpot_speedup_quant": speedup(tpot_f, tpot_q),
        "ttft_f32_p50_ms": ttft_f,
        "ttft_quant_p50_ms": ttft_q,
        "ttft_speedup_quant": speedup(ttft_f, ttft_q),
        "tok_per_s_f32": toks_per_s(f32_out),
        "tok_per_s_quant": toks_per_s(q_out),
        "hbm_bytes_per_replica": bb_q["params_bytes"],
        "hbm_bytes_per_replica_f32": bb_f["params_bytes"],
        "hbm_ratio_vs_f32": (
            round(bb_q["params_bytes"] / bb_f["params_bytes"], 4)
            if bb_f["params_bytes"] else None
        ),
        "first_token_exact": first_exact,
        "stream_agreement": agreement,
        "expected_compiles": q_eng.expected_compiles(),
        "post_warmup_recompiles": recompiles,
        "kv_block_size": serve_kw["kv_block_size"],
        "kv_dtype": args.kv_dtype,
        "verified": n,
        "verify_ok": bool(
            first_exact and agreement >= QUANT_AGREEMENT_FLOOR
        ),
        "transport": "inproc",
    }
    rec["ok"] = bool(
        errors == 0
        and rec["verify_ok"]
        and recompiles == 0
    )
    return rec


# ---------------------------------------------------------------------------
# Replayable traffic model (ISSUE 13 tentpole (4)): "millions of
# users" as a seeded, deterministic scenario.

# Flash-crowd acceptance budget: interactive TTFT p95 during the flash
# window must stay within this multiple of the steady-state window's.
# The golden's 2x — the whole point of SLO classes + brownout is that
# a 3x arrival spike lands on batch, not on interactive latency.
FLASH_TTFT_BUDGET = 2.0


def traffic_rate_multiplier(mode: str, frac: float,
                            flash_factor: float) -> float:
    """Arrival-rate multiplier at request-index fraction ``frac`` of
    the run — index-based, so the shape is exact for any n and fully
    deterministic."""
    if mode == "flash":
        # Steady -> 3x flash crowd -> steady.
        return flash_factor if 0.35 <= frac < 0.70 else 1.0
    if mode == "ramp":
        # Quiet start -> sustained peak (the scale-up forcing
        # function) -> cool-down (lets the autoscaler drain back).
        if frac < 0.10:
            return 0.3
        if frac < 0.70:
            return 1.0
        return 0.2
    if mode == "diurnal":
        # Two "days" of sinusoidal load.
        import math

        return 0.25 + 0.75 * (
            0.5 - 0.5 * math.cos(2 * math.pi * 2 * frac)
        )
    raise ValueError(f"unknown traffic mode {mode!r}")


def traffic_phase(mode: str, frac: float) -> str:
    if mode == "flash":
        if frac < 0.35:
            return "steady"
        return "flash" if frac < 0.70 else "recover"
    if mode == "ramp":
        if frac < 0.10:
            return "low"
        return "peak" if frac < 0.70 else "cool"
    return "diurnal"


def make_traffic_schedule(mode: str, n: int, *, rate: float,
                          vocab: int, max_len: int, max_new: int,
                          batch_fraction: float = 0.3,
                          flash_factor: float = 3.0,
                          seed: int = 0) -> list[dict]:
    """A seeded OPEN-LOOP arrival schedule: n requests with exponential
    inter-arrival times at the mode's rate profile, heavy-tail
    (lognormal) prompt lengths, and a seeded interactive/batch class
    mix. Same seed -> byte-identical schedule, so every scenario —
    including a flash crowd composed with a chaos fault spec — replays
    exactly."""
    import numpy as np

    rng = np.random.default_rng(seed)
    cap = max(4, max_len - max_new)
    median = max(3, cap // 6)
    schedule = []
    t = 0.0
    for i in range(n):
        frac = i / max(n - 1, 1)
        r = rate * traffic_rate_multiplier(mode, frac, flash_factor)
        t += float(rng.exponential(1.0 / r))
        ln = int(np.clip(
            rng.lognormal(mean=np.log(median), sigma=0.9), 1, cap
        ))
        schedule.append({
            "t": t,
            "prompt": [int(x) for x in rng.integers(0, vocab, (ln,))],
            "slo": (
                "batch" if rng.random() < batch_fraction
                else "interactive"
            ),
            "seed": i,
            "max_new": max_new,
            "phase": traffic_phase(mode, frac),
        })
    return schedule


def drive_open_loop(frontend, schedule, *, http_url: str | None,
                    timeout: float, temperature: float = 0.0,
                    top_k: int = 0, workers: int | None = None) -> dict:
    """OPEN-loop driver: requests fire at their scheduled arrival time
    whether or not earlier ones resolved — the load does not politely
    back off when the fleet slows down, which is exactly what a flash
    crowd doesn't do. ``workers`` defaults to one per request (true
    open loop); an explicit cap can serialize arrivals once every
    worker is tied up in a slow request, so late fires (> 50 ms behind
    schedule) are counted in the outcome's ``late_fires`` rather than
    silently skewing the phase-labeled percentiles. Returns
    index-aligned replies, client wall times, and each request's fire
    time (wall clock, for the resize-window percentile)."""
    import concurrent.futures as cf

    n = len(schedule)
    if workers is None:
        workers = min(n, 1024)
    replies: list = [None] * n
    client_s: list = [None] * n
    fired_unix: list = [None] * n
    late = [0]
    late_lock = threading.Lock()

    def fire(i: int, ev: dict) -> None:
        if (time.perf_counter() - t0) - ev["t"] > 0.05:
            with late_lock:
                late[0] += 1
        body = {
            "prompt": ev["prompt"],
            "max_new_tokens": ev["max_new"],
            "temperature": temperature,
            "top_k": top_k,
            "seed": ev["seed"],
            "slo": ev["slo"],
        }
        fired_unix[i] = time.time()
        t_req = time.perf_counter()
        if http_url is not None:
            replies[i] = _post_json(http_url, body, timeout)
        else:
            replies[i] = frontend.handle_request(body, kind="generate")
        client_s[i] = time.perf_counter() - t_req

    t0 = time.perf_counter()
    with cf.ThreadPoolExecutor(max_workers=workers) as pool:
        for i, ev in enumerate(schedule):
            delay = ev["t"] - (time.perf_counter() - t0)
            if delay > 0:
                time.sleep(delay)
            pool.submit(fire, i, ev)
    if late[0]:
        print(
            f"# open-loop driver: {late[0]}/{n} requests fired "
            ">50ms behind schedule (worker saturation)",
            file=sys.stderr,
        )
    return {
        "replies": replies,
        "client_s": client_s,
        "fired_unix": fired_unix,
        "late_fires": late[0],
        "wall_s": time.perf_counter() - t0,
    }


def _stream_matches(reply: dict, ref: list) -> bool:
    """Token-identity under brownout: a level-2-capped stream is a
    PREFIX of the reference; anything else must match exactly."""
    toks = reply.get("tokens") or []
    if reply.get("truncated") == "brownout":
        return bool(toks) and toks == ref[: len(toks)]
    return toks == ref


def _class_values(outcome, schedule, field: str, *, slo: str,
                  phases=None) -> list:
    return [
        r[1].get(field)
        for r, ev in zip(outcome["replies"], schedule)
        if r is not None and r[0] == 200 and ev["slo"] == slo
        and (phases is None or ev["phase"] in phases)
    ]


def run_traffic_bench(args) -> dict:
    """``--traffic {ramp,flash,diurnal}`` (ISSUE 13): the replayable
    million-user traffic model, driven open-loop against a
    brownout-enabled fleet, banking one ``serve_traffic`` record.

    * ``flash`` — a fixed fleet (default 2 replicas) under a seeded
      3x flash crowd. The record's headline claims: all shedding lands
      on the batch class (``shed_interactive == 0``), interactive TTFT
      p95 during the flash stays within ``FLASH_TTFT_BUDGET`` x the
      steady window's, every delivered stream token-identical (prefix
      under a brownout cap) to ``reference_generate``, zero post-warmup
      recompiles fleet-wide, and the brownout ladder fully cleared by
      the end of the run.
    * ``ramp`` — a 1-replica fleet + the telemetry-driven autoscaler
      (supervisor.Autoscaler over in-proc replicas). The record stamps
      ``scale_up_latency_s`` (decision -> green -> routed),
      ``p95_during_resize_ms``, peak replica count, and drain-first
      scale-down back to min with zero lost requests.
    * ``diurnal`` — two sinusoidal load "days" over the fixed fleet;
      the long-horizon stability shape the chaos tier can compose
      with.
    """
    import jax

    from tensorflow_examples_tpu.serving.router import (
        Router,
        RouterConfig,
        RouterFrontend,
    )

    mode = args.traffic
    serve_kw = dict(
        max_slots=args.max_slots,
        max_delay_s=0.002,
        request_timeout_s=args.timeout,
        kv_block_size=args.kv_block_size,
        kv_dtype=args.kv_dtype,
        # The whole point of the traffic tier: overload is a
        # first-class input. Ladder thresholds scale with the slot
        # count; the hold is short so a CI-scale run can walk the
        # ladder up AND back down.
        brownout=True,
        brownout_queue_hi=max(4, 2 * args.max_slots),
        brownout_hold_s=0.25,
        brownout_max_new_tokens=max(2, args.max_new_tokens // 2),
    )
    if args.smoke:
        serve_kw.update(prefill_bucket_floor=16, kv_bucket_floor=32)

    if mode == "ramp":
        # The ramp's peak must actually OUTRUN one replica or there is
        # nothing to autoscale: the smoke default arrives well above a
        # single smoke engine's throughput, so the queue builds, the
        # ladder engages, and the scale-up golden has a forcing
        # function (flash/diurnal run at a fixed-fleet rate instead).
        n = args.requests or (240 if args.smoke else 400)
        rate = args.rate or (300.0 if args.smoke else 50.0)
    else:
        n = args.requests or (60 if args.smoke else 400)
        rate = args.rate or (25.0 if args.smoke else 50.0)
    verify = args.verify if args.verify >= 0 else (3 if args.smoke else 0)

    t0 = time.perf_counter()
    autoscaler = supervisor = None
    spawned: list = []
    # Telemetry of replicas the autoscaler scaled DOWN mid-run: stop()
    # tears the engine/batcher away, so their recompiles, brownout
    # events, and counters are snapshotted here first — otherwise a
    # drained replica's numbers silently vanish from the record (and
    # "zero post-warmup recompiles fleet-wide" could pass falsely).
    harvest: dict = {"recompiles": 0, "events": [], "counters": {}}
    if mode == "ramp":
        from tensorflow_examples_tpu.serving.chaos import InProcReplica
        from tensorflow_examples_tpu.serving.engine import ServeConfig
        from tensorflow_examples_tpu.serving.supervisor import (
            Autoscaler,
            AutoscalerConfig,
            Supervisor,
        )
        from tensorflow_examples_tpu.telemetry.registry import (
            MetricsRegistry,
        )

        def build_engine():
            reg = MetricsRegistry()
            cfg = ServeConfig(**serve_kw)
            if args.workdir:
                return build_checkpoint_engine(
                    args.workdir, cfg, registry=reg
                )
            return build_smoke_engine(cfg, registry=reg)

        class _HarvestingReplica(InProcReplica):
            def stop(self):
                eng, batcher = self.engine, self.batcher
                if eng is not None:
                    harvest["recompiles"] += \
                        eng.post_warmup_recompiles()
                    for k, v in eng.registry.counter_values().items():
                        harvest["counters"][k] = \
                            harvest["counters"].get(k, 0) + v
                if batcher is not None:
                    harvest["events"].extend(batcher._overload.events)
                super().stop()

        first = _HarvestingReplica(build_engine, replica_id=0).start()
        spawned.append(first)
        router = Router(
            [first.url],
            cfg=RouterConfig(
                probe_interval_s=0.1, request_timeout_s=args.timeout,
            ),
        ).start()
        supervisor = Supervisor(
            router, [first], poll_s=0.25, health_stall_s=15.0,
        ).start()

        def spawn(idx):
            rep = _HarvestingReplica(
                build_engine, replica_id=idx
            ).start()
            spawned.append(rep)
            return rep

        autoscaler = Autoscaler(
            router,
            supervisor,
            spawn,
            cfg=AutoscalerConfig(
                min_replicas=1,
                max_replicas=args.max_replicas,
                target_queue_depth=args.target_queue,
                hold_s=0.4,
                scale_down_idle_s=1.0,
                drain_timeout_s=args.timeout,
                warm_timeout_s=300.0,
                evaluate_every_s=0.15,
            ),
        ).start()
        engines = lambda: [  # noqa: E731 - tiny accessor
            rep.engine for rep in spawned if rep.engine is not None
        ]
        regs = lambda: [  # noqa: E731
            rep.engine.registry for rep in spawned
            if rep.engine is not None
        ]
        batchers = lambda: [  # noqa: E731
            rep.batcher for rep in spawned if rep.batcher is not None
        ]
        n_initial = 1
    else:
        replicas = build_replica_stacks(args, serve_kw, args.replicas)
        router = Router(
            [f"http://127.0.0.1:{fe.port}" for _, _, fe, _ in replicas],
            cfg=RouterConfig(
                probe_interval_s=0.1, request_timeout_s=args.timeout,
            ),
        ).start()
        engines = lambda: [e for e, _, _, _ in replicas]  # noqa: E731
        regs = lambda: [r for _, _, _, r in replicas]  # noqa: E731
        batchers = lambda: [b for _, b, _, _ in replicas]  # noqa: E731
        n_initial = args.replicas
    rfront = RouterFrontend(router, port=0).start()
    warmup_s = time.perf_counter() - t0
    model_cfg = engines()[0].model_cfg
    schedule = make_traffic_schedule(
        mode, n, rate=rate, vocab=model_cfg.vocab_size,
        max_len=model_cfg.max_len, max_new=args.max_new_tokens,
        batch_fraction=args.batch_fraction,
        flash_factor=args.flash_factor, seed=args.traffic_seed,
    )
    print(
        f"# traffic={mode} n={n} rate={rate}/s "
        f"batch_fraction={args.batch_fraction} over "
        f"{n_initial} replica(s), warm in {warmup_s:.1f}s",
        file=sys.stderr,
    )

    # Sample the fleet size during the drive (ramp's replicas_peak).
    peak = [len(router.replicas)]
    sampling = threading.Event()

    def sampler():
        while not sampling.is_set():
            peak[0] = max(peak[0], len(router.replicas))
            time.sleep(0.05)

    sampler_thread = threading.Thread(target=sampler, daemon=True)
    sampler_thread.start()

    try:
        outcome = drive_open_loop(
            None, schedule, http_url=rfront.url("/generate"),
            timeout=args.timeout, temperature=args.temperature,
            top_k=args.top_k,
        )
        # Let the ladder walk back down (and, in ramp mode, the
        # autoscaler drain back to min) before the verdict: "engages
        # AND fully clears within the run" is the acceptance claim.
        settle_deadline = time.monotonic() + (
            30.0 if args.smoke else 120.0
        )
        while time.monotonic() < settle_deadline:
            levels = [b.brownout_level for b in batchers()]
            scaled_in = (
                autoscaler is None
                or (len(router.replicas) <= 1
                    and not autoscaler.acting())
            )
            if all(lv == 0 for lv in levels) and scaled_in:
                break
            time.sleep(0.2)
        # Verify the first --verify completed interactive streams
        # against the unbatched reference (prefix-identical under a
        # brownout cap).
        verify_ok = True
        checked = 0
        ref_engine = engines()[0]
        for i, ev in enumerate(schedule):
            if checked >= verify:
                break
            reply = outcome["replies"][i]
            if reply is None or reply[0] != 200:
                continue
            checked += 1
            ref = ref_engine.reference_generate(
                ev["prompt"], max_new=ev["max_new"], seed=ev["seed"],
                temperature=args.temperature, top_k=args.top_k,
            )
            if not _stream_matches(reply[1], ref):
                verify_ok = False
                print(
                    f"# VERIFY FAIL traffic req {i}: "
                    f"{reply[1].get('tokens')} !~ reference {ref}",
                    file=sys.stderr,
                )
        brownout_events = list(harvest["events"])
        for b in batchers():
            brownout_events.extend(b._overload.events)
        # A scaled-down replica's frozen level is moot (it was drained
        # and removed); "cleared" is about the LIVE fleet.
        brownout_levels = [b.brownout_level for b in batchers()]
        recompiles = harvest["recompiles"] + sum(
            e.post_warmup_recompiles() for e in engines()
        )
        counter_sum: dict = dict(harvest["counters"])
        for reg in regs():
            for k, v in reg.counter_values().items():
                counter_sum[k] = counter_sum.get(k, 0) + v
    finally:
        sampling.set()
        sampler_thread.join(timeout=2)
        rfront.close()
        if autoscaler is not None:
            autoscaler.close()
        if supervisor is not None:
            supervisor.close()
        router.close()
        if mode == "ramp":
            for rep in spawned:
                rep.close()
        else:
            for _, batcher, fe, _ in replicas:
                batcher.close(drain=True)
                fe.close()

    tally = tally_replies(outcome["replies"])
    by_class = {
        slo: [
            r for r, ev in zip(outcome["replies"], schedule)
            if ev["slo"] == slo and r is not None
        ]
        for slo in ("interactive", "batch")
    }
    shed_by_class = {
        slo: sum(1 for r in rs if r[0] == 503)
        for slo, rs in by_class.items()
    }
    n_by_class = {
        slo: sum(1 for ev in schedule if ev["slo"] == slo)
        for slo in ("interactive", "batch")
    }
    steady_p95 = _pct_from_values(
        _class_values(outcome, schedule, "ttft_s",
                      slo="interactive", phases=("steady",)), 95,
    )
    flash_p95 = _pct_from_values(
        _class_values(outcome, schedule, "ttft_s",
                      slo="interactive", phases=("flash",)), 95,
    )
    # Resize-window latency (ramp): TTFT p95 of requests fired while a
    # scale action was in flight (scale-up: decision -> green; plus a
    # 2s tail after any event while dispatch redistributes).
    resize_windows = []
    if autoscaler is not None:
        up_times = [
            t for t, verb, _ in autoscaler.events if verb == "scale_up"
        ]
        for t, lat in zip(up_times, autoscaler.scale_up_latencies):
            resize_windows.append((t - lat, t + 2.0))
        for t, verb, _ in autoscaler.events:
            if verb == "scale_down":
                resize_windows.append((t, t + 2.0))
    resize_ttfts = [
        r[1].get("ttft_s")
        for r, fu in zip(outcome["replies"], outcome["fired_unix"])
        if r is not None and r[0] == 200 and fu is not None
        and any(a <= fu <= b for a, b in resize_windows)
    ]
    scale_up_lat = (
        max(autoscaler.scale_up_latencies)
        if autoscaler is not None and autoscaler.scale_up_latencies
        else None
    )
    brownout_max_level = max(
        (to for _, _, to, _ in brownout_events), default=0
    )
    rec = {
        "bench": "serve_traffic",
        "traffic": mode,
        "backend": jax.default_backend(),
        "seed": args.traffic_seed,
        "replicas": n_initial,
        "replicas_peak": peak[0],
        "replicas_final": len(router.replicas),
        "requests": n,
        "completed": tally["completed"],
        "errors": tally["errors"],
        "shed_total": tally["shed_total"],
        "rejected_total": tally["rejected_total"],
        "transport_errors": tally["transport_errors"],
        "shed_interactive": shed_by_class["interactive"],
        "shed_batch": shed_by_class["batch"],
        "shed_rate_interactive": round(
            shed_by_class["interactive"]
            / max(n_by_class["interactive"], 1), 4
        ),
        "shed_rate_batch": round(
            shed_by_class["batch"] / max(n_by_class["batch"], 1), 4
        ),
        "preempted_batch": int(
            counter_sum.get("serving/preempted_total", 0)
        ),
        "rate_req_per_s": rate,
        "flash_factor": args.flash_factor,
        "batch_fraction": args.batch_fraction,
        "wall_s": round(outcome["wall_s"], 3),
        "late_fires": outcome["late_fires"],
        "warmup_s": round(warmup_s, 3),
        "ttft_p50_interactive_ms": _pct_from_values(
            _class_values(outcome, schedule, "ttft_s",
                          slo="interactive"), 50),
        "ttft_p95_interactive_ms": _pct_from_values(
            _class_values(outcome, schedule, "ttft_s",
                          slo="interactive"), 95),
        "ttft_p95_batch_ms": _pct_from_values(
            _class_values(outcome, schedule, "ttft_s", slo="batch"),
            95),
        "e2e_p95_interactive_ms": _pct_from_values(
            _class_values(outcome, schedule, "total_s",
                          slo="interactive"), 95),
        "e2e_p95_batch_ms": _pct_from_values(
            _class_values(outcome, schedule, "total_s", slo="batch"),
            95),
        "steady_ttft_p95_interactive_ms": steady_p95,
        "flash_ttft_p95_interactive_ms": flash_p95,
        "flash_vs_steady_ttft": (
            round(flash_p95 / steady_p95, 3)
            if steady_p95 and flash_p95 else None
        ),
        "flash_ttft_budget": FLASH_TTFT_BUDGET,
        "brownout_max_level": brownout_max_level,
        "brownout_transitions": len(brownout_events),
        "brownout_engaged": bool(brownout_events),
        "brownout_cleared": bool(
            all(lv == 0 for lv in brownout_levels)
        ),
        "scale_ups": (
            int(len(autoscaler.scale_up_latencies))
            if autoscaler is not None else 0
        ),
        "scale_downs": (
            int(sum(1 for _, verb, _ in autoscaler.events
                    if verb == "scale_down"))
            if autoscaler is not None else 0
        ),
        "scale_up_latency_s": (
            round(scale_up_lat, 3) if scale_up_lat else None
        ),
        "p95_during_resize_ms": _pct_from_values(resize_ttfts, 95),
        "post_warmup_recompiles": recompiles,
        "verified": checked,
        "verify_ok": verify_ok,
        "kv_block_size": args.kv_block_size,
        "transport": "router-http",
    }
    if mode == "flash":
        # The TTFT ratio is a claim about a loaded accelerator. The
        # smoke's toy model on a shared CPU has steady TTFTs of a few
        # milliseconds, under the host's scheduling noise (a ratio of
        # 5.9 with nothing shed and the ladder never engaged): it is
        # stamped there, and gated on a real run only.
        rec["ok"] = bool(
            rec["errors"] == 0
            and rec["shed_interactive"] == 0
            and verify_ok
            and recompiles == 0
            and rec["brownout_cleared"]
            and (
                args.smoke
                or rec["flash_vs_steady_ttft"] is None
                or rec["flash_vs_steady_ttft"] <= FLASH_TTFT_BUDGET
            )
        )
    elif mode == "ramp":
        rec["ok"] = bool(
            rec["errors"] == 0
            and verify_ok
            and recompiles == 0
            and rec["scale_ups"] >= 1
            and rec["replicas_peak"] >= min(args.max_replicas, 2)
            and rec["replicas_final"] <= 1
            and rec["scale_up_latency_s"] is not None
            and rec["brownout_engaged"]
            and rec["brownout_cleared"]
        )
    else:
        rec["ok"] = bool(
            rec["errors"] == 0
            and verify_ok
            and recompiles == 0
            and rec["brownout_cleared"]
        )
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny model, 20 requests, verify 3 (tier-1 CI)")
    ap.add_argument("--workdir", default="",
                    help="serve the latest checkpoint in this run dir")
    ap.add_argument("--router", action="store_true",
                    help="drive --replicas in-proc serving stacks "
                         "through serving/router.py (ISSUE 8)")
    ap.add_argument("--chaos", action="store_true",
                    help="ISSUE 10: supervised in-proc fleet + injected "
                         "fault schedule; banks the serve_chaos "
                         "availability record (error_rate, failovers, "
                         "p95-vs-baseline)")
    ap.add_argument("--spec-decode", type=int, default=0, metavar="K",
                    help="ISSUE 11: A/B the same prompt-like prompts "
                         "with speculation off vs on (K drafts per "
                         "step); banks the serve_spec record "
                         "(tpot_speedup, draft_hit_rate, "
                         "accepted_per_step, tokens_identical)")
    ap.add_argument("--affinity", choices=("on", "off", "ab"),
                    default="on",
                    help="ISSUE 12 (--router): prefix-affinity dispatch"
                         " on/off, or 'ab' — drive the same shared-"
                         "prefix traffic through an affinity-off fleet "
                         "then an affinity-on one and bank the "
                         "serve_affinity A/B record "
                         "(prefix_hit_rate_affinity vs "
                         "prefix_hit_rate_no_affinity, shared-vs-cold "
                         "TTFT)")
    ap.add_argument("--traffic", choices=("ramp", "flash", "diurnal"),
                    default="",
                    help="ISSUE 13: replayable open-loop traffic model "
                         "against a brownout-enabled fleet. 'flash' = "
                         "3x flash crowd over a fixed fleet (per-class "
                         "shed/latency claims); 'ramp' = the "
                         "autoscaler golden (1->max->1, drain-first); "
                         "'diurnal' = two sinusoidal load days. Banks "
                         "the serve_traffic record")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="traffic: steady-state arrival rate req/s "
                         "(default 25 smoke / 50)")
    ap.add_argument("--batch-fraction", type=float, default=0.3,
                    help="traffic: fraction of arrivals in the batch "
                         "SLO class")
    ap.add_argument("--flash-factor", type=float, default=3.0,
                    help="traffic flash: arrival-rate multiple during "
                         "the flash window")
    ap.add_argument("--traffic-seed", type=int, default=0,
                    help="traffic: schedule seed (same seed = "
                         "byte-identical scenario)")
    ap.add_argument("--max-replicas", type=int, default=3,
                    help="traffic ramp: autoscaler ceiling")
    ap.add_argument("--target-queue", type=float, default=3.0,
                    help="traffic ramp: autoscaler queue-depth target "
                         "per replica")
    ap.add_argument("--fault-spec", default="",
                    help="serve fault schedule for --chaos "
                         "(utils/faults.py grammar, e.g. 'crash@1:4,"
                         "badhealth@0:3'); default: crash the replica "
                         "the router dispatches to next, mid-decode")
    ap.add_argument("--replicas", type=int, default=0,
                    help="replica count (default: 2 for --router, "
                         "3 for --chaos)")
    ap.add_argument("--kv-block-size", type=int, default=16,
                    help="token rows per KV block (a power of two "
                         "dividing the bucket floors and max_len)")
    ap.add_argument("--kv-dtype", default="",
                    help="'' (cache dtype), 'int8', or 'fp8' (fp8 "
                         "needs backend float8 support)")
    ap.add_argument("--weight-dtype", default="",
                    choices=("", "int8", "fp8"),
                    help="ISSUE 15: A/B the same prompts through an "
                         "f32 engine and a weight-quantized one; "
                         "banks the serve_quant record "
                         "(tpot_speedup_quant, hbm_bytes_per_replica, "
                         "first_token_exact + stream_agreement)")
    ap.add_argument("--requests", type=int, default=0,
                    help="request count (default: 20 smoke / 64 otherwise)")
    ap.add_argument("--concurrency", type=int, default=8)
    ap.add_argument("--max-new-tokens", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--max-slots", type=int, default=8)
    ap.add_argument("--verify", type=int, default=-1,
                    help="replay N requests unbatched and compare "
                         "token-for-token (-1: 3 in smoke, 0 otherwise)")
    ap.add_argument("--inproc", action="store_true",
                    help="skip the HTTP hop (engine+batcher cost only)")
    ap.add_argument("--timeout", type=float, default=120.0,
                    help="per-request client timeout (seconds)")
    ap.add_argument("--out", default="", help="bank the record here")
    ap.add_argument("--trace-out", default="",
                    help="ISSUE 18: land the run's kept traces as "
                         "schema-v13 kind=\"trace\" JSONL here "
                         "(plain + --router modes); the record banks "
                         "trace_coverage / slow_trace_count either way")
    ap.add_argument("--slo", action="store_true",
                    help="ISSUE 19: run the SLO AlertEngine + canary "
                         "prober over the run (plain + --router "
                         "modes); the record banks alert_count / "
                         "probe_success_rate / error_budget_remaining "
                         "and ok additionally requires alert_count==0")
    args = ap.parse_args(argv)
    if not args.smoke and not args.workdir:
        ap.error("pick a target: --smoke or --workdir DIR")
    if args.affinity == "ab" and not args.router:
        ap.error("--affinity ab is a --router A/B mode")
    if args.slo and args.inproc:
        ap.error("--slo needs the HTTP frontend for black-box probes "
                 "(drop --inproc)")
    if args.slo and (args.chaos or args.traffic or args.weight_dtype
                     or args.spec_decode > 0 or args.affinity == "ab"):
        ap.error("--slo composes with the plain and --router modes "
                 "only")
    modes = [name for name, on in (
        ("--weight-dtype", bool(args.weight_dtype)),
        ("--spec-decode", args.spec_decode > 0),
        ("--traffic", bool(args.traffic)),
        ("--chaos", args.chaos),
        ("--router", args.router),
    ) if on]
    if len(modes) > 1:
        # Each mode banks its own record; silently running only one
        # would label the output as measuring something it didn't.
        ap.error(f"pick ONE bench mode: {' + '.join(modes)} don't "
                 "compose")
    if args.replicas <= 0:
        args.replicas = 3 if args.chaos else 2

    if args.traffic:
        rec = run_traffic_bench(args)
        print(json.dumps(rec))
        if args.out:
            with open(args.out, "w") as f:
                json.dump(rec, f, indent=1)
                f.write("\n")
        return 0 if rec["ok"] else 1

    if args.router and args.affinity == "ab":
        rec = run_affinity_bench(args)
        print(json.dumps(rec))
        if args.out:
            with open(args.out, "w") as f:
                json.dump(rec, f, indent=1)
                f.write("\n")
        return 0 if rec["ok"] else 1

    if args.weight_dtype:
        rec = run_quant_bench(args)
        print(json.dumps(rec))
        if args.out:
            with open(args.out, "w") as f:
                json.dump(rec, f, indent=1)
                f.write("\n")
        return 0 if rec["ok"] else 1

    if args.spec_decode > 0:
        rec = run_spec_bench(args)
        print(json.dumps(rec))
        if args.out:
            with open(args.out, "w") as f:
                json.dump(rec, f, indent=1)
                f.write("\n")
        return 0 if rec["ok"] else 1

    if args.chaos:
        rec = run_chaos_bench(args)
        takeover = rec.pop("takeover", None)
        print(json.dumps(rec))
        if takeover is not None:
            print(json.dumps(takeover))
        if args.out:
            with open(args.out, "w") as f:
                json.dump(rec, f, indent=1)
                f.write("\n")
            if takeover is not None:
                root, ext = os.path.splitext(args.out)
                tko_out = f"{root}_takeover{ext or '.json'}"
                with open(tko_out, "w") as f:
                    json.dump(takeover, f, indent=1)
                    f.write("\n")
        return 0 if (
            rec["ok"] and (takeover is None or takeover["ok"])
        ) else 1

    if args.router:
        rec = run_router_bench(args)
        print(json.dumps(rec))
        if args.out:
            with open(args.out, "w") as f:
                json.dump(rec, f, indent=1)
                f.write("\n")
        return 0 if rec["ok"] else 1

    import jax

    from tensorflow_examples_tpu.serving.batcher import ContinuousBatcher
    from tensorflow_examples_tpu.serving.engine import ServeConfig
    from tensorflow_examples_tpu.serving.frontend import ServingFrontend
    from tensorflow_examples_tpu.telemetry.registry import MetricsRegistry

    registry = MetricsRegistry()  # private: the record owns its counters
    serve_cfg = ServeConfig(
        max_slots=args.max_slots,
        max_delay_s=0.002,
        request_timeout_s=args.timeout,
        kv_block_size=args.kv_block_size,
        kv_dtype=args.kv_dtype,
        **(dict(prefill_bucket_floor=16, kv_bucket_floor=32)
           if args.smoke else {}),
    )
    if args.workdir:
        engine = build_checkpoint_engine(
            args.workdir, serve_cfg, registry=registry
        )
    else:
        engine = build_smoke_engine(serve_cfg, registry=registry)

    n = args.requests or (20 if args.smoke else 64)
    verify = args.verify if args.verify >= 0 else (3 if args.smoke else 0)
    prompts = make_prompts(
        n,
        vocab=engine.model_cfg.vocab_size,
        max_len=engine.model_cfg.max_len,
        max_new=args.max_new_tokens,
    )

    t0 = time.perf_counter()
    engine.warmup()
    warmup_s = time.perf_counter() - t0
    print(
        f"# warm: {engine.expected_compiles()} programs in {warmup_s:.1f}s "
        f"(prefill ladder {engine.prefill_ladder}, "
        f"kv ladder {engine.kv_ladder})",
        file=sys.stderr,
    )

    batcher = ContinuousBatcher(engine, registry=registry).start()
    frontend = ServingFrontend(batcher, port=0)
    http_url = None
    if not args.inproc:
        frontend.start()
        http_url = frontend.url("/generate")
    # Client-originated tracing (ISSUE 18): a closed-loop bench keeps
    # EVERY trace (sample_fraction=1.0 — it is measuring, not
    # serving production traffic), so trace_coverage banks at 1.0 on
    # a healthy run and the slow-trace count is exhaustive.
    from tensorflow_examples_tpu.telemetry import tracing

    recorder = tracing.TraceRecorder(
        registry=registry, path=args.trace_out or None,
        sample_fraction=1.0,
    )
    try:
        outcome = drive(
            frontend, prompts,
            concurrency=args.concurrency, max_new=args.max_new_tokens,
            temperature=args.temperature, top_k=args.top_k,
            http_url=http_url, timeout=args.timeout,
            trace_recorder=recorder,
        )
        verify_ok = True
        for i in range(min(verify, n)):
            reply = outcome["replies"][i]
            if reply is None or reply[0] != 200:
                verify_ok = False
                continue
            ref = engine.reference_generate(
                prompts[i], max_new=args.max_new_tokens, seed=i,
                temperature=args.temperature, top_k=args.top_k,
            )
            if reply[1]["tokens"] != ref:
                verify_ok = False
                print(
                    f"# VERIFY FAIL req {i}: served {reply[1]['tokens']} "
                    f"!= reference {ref}",
                    file=sys.stderr,
                )
        # The record is assembled BEFORE the --slo probe phase: probe
        # traffic must never pollute the banked percentiles (ISSUE 19).
        rec = bench_record(
            engine, registry, outcome, prompts,
            concurrency=args.concurrency, verified=min(verify, n),
            verify_ok=verify_ok, backend=jax.default_backend(),
        )
        if args.slo:
            from tensorflow_examples_tpu.serving.prober import (
                CanaryProber,
            )
            from tensorflow_examples_tpu.telemetry.slo import AlertEngine

            # The SLO stack owns its own registry so probe/ and
            # alert/ instruments never mix into the bench record's.
            alerts = AlertEngine(registry=MetricsRegistry())
            for r in outcome["replies"]:  # organic feed first
                body = r[1] if r is not None and r[0] == 200 else {}
                alerts.observe(
                    "interactive",
                    ttft_s=body.get("ttft_s"),
                    e2e_s=body.get("total_s"),
                    error=(r is None or r[0] >= 500),
                )
            prober = CanaryProber(
                {"replica": frontend.url("")},
                alerts=alerts, registry=alerts.registry,
            )
            for _ in range(3):
                prober.probe_once()
            rec.update(alerts.stats())
            # Probes ride the warmed buckets: a probe-induced
            # recompile fails the record, same as an organic one.
            rec["post_warmup_recompiles"] = engine.post_warmup_recompiles()
            rec["ok"] = bool(
                rec["ok"]
                and rec["post_warmup_recompiles"] == 0
                and rec["alert_count"] == 0
            )
    finally:
        batcher.close(drain=True)
        frontend.close()
        recorder.close()

    rec["warmup_s"] = round(warmup_s, 3)
    rec["transport"] = "inproc" if args.inproc else "http"
    rec.update(recorder.stats())  # trace_coverage / slow_trace_count
    print(json.dumps(rec))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rec, f, indent=1)
            f.write("\n")
    return 0 if rec["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
