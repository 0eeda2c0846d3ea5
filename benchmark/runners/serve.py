"""Runner of a serving cell: the engine, the batcher and the frontend
as ``examples/gpt2/serve.py`` builds them, driven from threads of this
one process through ``ServingFrontend.handle_request`` — the call the
HTTP handler makes, minus the socket.

Weights are made on the device from ``--seed`` by the workload's own
``init_fn`` under ``jit`` (what a fresh checkpoint of this model
holds, in its dtype) and handed to ``InferenceEngine`` as serve.py
hands a restored checkpoint. The cell's file pins what a deployment
must state (``serve_config``: slots, block size, pool) and nothing
else. Warm-up is ``engine.warmup()`` and then real requests through
the normal path — the correctness checks — so that whatever compiles
lazily on a first request does so before the window.
"""

from __future__ import annotations

import importlib
import statistics
import threading
import time

import numpy as np

from benchmark import peaks as peaks_mod
from benchmark import record, roofline, spec, trace_reduce, traffic_gen

# The engine's compiled programs (serving/engine.py's *_impl step
# functions): one of these compiling inside the window is an error.
# They are jitted functools.partial objects, which JAX names "<unknown>".
PROGRAMS = ("_impl", "<unknown>")
COUNTERS = (
    "serving/decode_steps", "serving/decode_tokens", "serving/prefill_tokens",
    "serving/prefix_reused_tokens", "serving/preempted_total", "serving/shed_total",
    "serving/kv_exhausted_total", "serving/errors_total", "serving/requests_total",
)
HISTS = ("serving/decode_step", "serving/prefill", "serving/queue_wait")


class Server:
    """Engine + batcher + frontend, and the registry they publish to."""

    def __init__(self, ctx: record.Context):
        import jax

        from tensorflow_examples_tpu.serving.batcher import ContinuousBatcher
        from tensorflow_examples_tpu.serving.engine import InferenceEngine, ServeConfig
        from tensorflow_examples_tpu.serving.frontend import ServingFrontend
        from tensorflow_examples_tpu.telemetry.registry import MetricsRegistry

        cell = ctx.cell
        config = cell.config
        workload = importlib.import_module(config["program"]["workload"])
        self.pcfg = spec.program_config(config)
        self.model_cfg = workload.model_config(self.pcfg)
        task = workload.make_task(self.pcfg, mesh=None)
        key = jax.random.PRNGKey(spec.fold_seed(ctx.seed))
        self.params = jax.jit(task.init_fn)(key)["params"]
        self.serve_cfg = ServeConfig(**cell.deploy["serve_config"])
        self.registry = MetricsRegistry()
        for name in HISTS:  # keep every sample of a window, not the last 8192
            self.registry.histogram(name, max_samples=1 << 20)
        self.engine = InferenceEngine(
            self.model_cfg, self.params, cfg=self.serve_cfg, registry=self.registry
        )
        t0 = time.perf_counter()
        self.engine.warmup()
        self.warmup_s = time.perf_counter() - t0
        self.batcher = ContinuousBatcher(self.engine).start()
        self.frontend = ServingFrontend(self.batcher, port=0)  # never bound

    def handle(self, body: dict, kind: str = "generate"):
        return self.frontend.handle_request(body, kind=kind)

    def close(self) -> None:
        self.batcher.close(drain=True, timeout=60.0)

    # ---- window bookkeeping: counters as deltas, histograms as the
    # samples recorded since the mark
    def mark(self) -> dict:
        return {
            "counters": dict(self.registry.counter_values()),
            "hist_counts": {n: self.registry.histogram(n).count for n in HISTS},
        }

    def since(self, mark: dict) -> tuple[dict, dict]:
        now = self.registry.counter_values()
        counters = {n: int(now.get(n, 0)) - int(mark["counters"].get(n, 0)) for n in COUNTERS}
        hists = {}
        for name in HISTS:
            h = self.registry.histogram(name)
            with h._lock:  # no public "samples since": listed in PERF.md for the tracing issue
                samples = list(h._samples)
            fresh = h.count - mark["hist_counts"][name]
            hists[name] = samples[-fresh:] if fresh > 0 else []
        return counters, hists


def run(ctx: record.Context) -> record.Run:
    cell, mix = ctx.cell, ctx.cell.traffic
    server = Server(ctx)
    try:
        correct, detail = check_outputs(ctx, server)
        if ctx.rates:
            sweep(ctx, server)
            return record.Run(cell=cell)
        return _window(ctx, server, correct, detail)
    finally:
        server.close()


def _window(ctx, server, correct, detail, rate=None) -> record.Run:
    import jax

    cell, mix = ctx.cell, ctx.cell.traffic
    vocab = int(server.model_cfg.vocab_size)
    slots = int(server.serve_cfg.max_slots)
    notes = {}
    if mix["kind"] == "closed":
        clients = traffic_gen.closed_loop_clients(mix, slots, server.serve_cfg.max_queue)
        # One wave of real requests, cut to a few tokens each, sizes the work:
        # whole waves, as many as last --seconds.
        calib = traffic_gen.make_requests(mix, slots, spec.fold_seed(ctx.seed, 7), vocab=vocab)
        asked = calib[0]["body"]["max_new_tokens"]
        short = min(asked, int(mix.get("calibration_tokens", 32)))
        for r in calib:
            r["body"]["max_new_tokens"] = short
        records, calib_s = traffic_gen.drive_closed_loop(server.handle, calib, clients)
        # A wave is its prefills and then ``asked`` decode steps: the first from the
        # replies' time to first token, the second from their gap between tokens.
        # (Sizing only; the metric is on the benchmark's own clock.)
        ok = [r for r in records if r["ok"] and r["n_tokens"] > 1]
        if ok:
            gap = statistics.median((r["total_s"] - r["ttft_s"]) / (r["n_tokens"] - 1) for r in ok)
            wave_s = statistics.median(r["ttft_s"] for r in ok) + (asked - 1) * gap
        else:
            wave_s = calib_s * asked / short
        waves = max(1, int(round(ctx.seconds / wave_s)))
        requests = traffic_gen.make_requests(mix, waves * slots, ctx.seed, vocab=vocab)
        notes.update(clients=clients, waves=waves, calibration_wave_s=wave_s)
        drive = lambda: traffic_gen.drive_closed_loop(server.handle, requests, clients)
    elif mix["kind"] == "open":
        rate = float(rate if rate is not None else cell.deploy["rate_per_s"])
        times = traffic_gen.arrival_times(mix, rate, ctx.seconds, ctx.seed)
        requests = traffic_gen.make_requests(mix, len(times), ctx.seed, vocab=vocab)
        # A running server has its system prompts cached: one short request each.
        for prefix in traffic_gen.shared_prefixes(mix, ctx.seed, vocab=vocab):
            tail = [int(t) for t in np.random.default_rng(len(prefix)).integers(0, vocab, (16,))]
            server.handle({"prompt": prefix + tail, "max_new_tokens": 4, "slo": mix.get("slo", "interactive")})
        notes.update(rate_per_s=rate)
        drive = lambda: traffic_gen.drive_open_loop(server.handle, requests, times)
    else:
        raise ValueError(f"traffic kind {mix['kind']!r}: this runner drives 'open' and 'closed'")

    tracer = _Tracer(ctx, mix) if ctx.trace and not ctx.rates else None
    mark = server.mark()
    t_open = time.perf_counter()
    if tracer:
        tracer.start()
    records, window_s = drive()
    t_close = time.perf_counter()
    trace = tracer.finish() if tracer else None
    counters, hists = server.since(mark)
    compiled = ctx.compiles.between(t_open, t_close)
    bad = [c for c in compiled if any(p in c for p in PROGRAMS)]
    if bad:
        raise RuntimeError(f"the engine's own programs compiled inside the window: {bad}")

    failed = sum(1 for r in records if not r["ok"])
    late = [r["late_s"] for r in records]
    notes.update(
        requests=len(records), failed=failed,
        generator_late_p95_ms=1e3 * (record.percentile(late, 95) or 0.0),
        generator_late_max_ms=1e3 * max(late, default=0.0),
        kv_exhausted_total=counters["serving/kv_exhausted_total"],
        preempted_total=counters["serving/preempted_total"],
        shed_total=counters["serving/shed_total"],
        prefix_reused_tokens=counters["serving/prefix_reused_tokens"],
        output_tokens=sum(r["n_tokens"] for r in records if r["ok"]),
        errors=sorted({str(r["error"])[:120] for r in records if r["error"]})[:3],
    )
    late_limit = float(mix.get("late_limit_ms", 100.0))
    if mix["kind"] == "open" and not ctx.rates and notes["generator_late_p95_ms"] > late_limit:
        raise RuntimeError(
            f"the generator ran late (p95 {notes['generator_late_p95_ms']:.1f} ms): a starved "
            "generator is not a fast server; nothing is reported"
        )
    exact = all(r["n_tokens"] == r["asked"] for r in records if r["status"] == 200)
    detail["every_reply_has_the_tokens_asked"] = bool(exact)
    item = int(np.dtype(server.params["wte"]["embedding"].dtype).itemsize)
    config = cell.config
    run = record.Run(
        cell=cell, setup_s=t_open - ctx.t_start, warmup_s=server.warmup_s,
        window_s=window_s, attempted=len(records), failed=failed,
        correct=bool(correct and exact), correct_detail=detail,
        requests=records, counters=counters, hists=hists,
        model={
            "n_params": int(sum(x.size for x in jax.tree.leaves(server.params))),
            "param_itemsize": item, "max_slots": slots,
            "kv_bytes_token": roofline.kv_bytes_per_token(
                n_layer=int(server.model_cfg.num_layers),
                n_embd=int(server.model_cfg.d_model), cache_itemsize=item),
            "chips": cell.chips,
        },
        compiles_in_window=compiled, notes=notes, trace=trace,
    )
    run.peaks = peaks_mod.peaks_of_this_device()
    return run


class _Tracer:
    """Profiles a few seconds in the middle of the window, from a
    thread of its own (the load goes on)."""

    def __init__(self, ctx, mix):
        self.dir = ctx.trace_dir
        self.lead = float(mix.get("trace_lead_share", 0.35)) * ctx.seconds
        self.length = min(float(mix.get("trace_seconds", 3.0)), max(ctx.seconds * 0.4, 0.2))
        self.host_window_s = None
        self.error = None
        self._thread = threading.Thread(target=self._run, name="tracer")

    def start(self):
        self._thread.start()

    def _run(self):
        import jax

        try:
            time.sleep(self.lead)
            jax.profiler.start_trace(self.dir)
            t0 = time.perf_counter()
            time.sleep(self.length)
            self.host_window_s = time.perf_counter() - t0
            jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001 — surfaced by finish()
            self.error = e

    def finish(self):
        self._thread.join()
        if self.error is not None:
            raise self.error
        return trace_reduce.reduce_trace(self.dir, self.host_window_s)


def sweep(ctx, server) -> None:
    """``--rates``: the window at each rate in turn, one process, one
    set-up; one line each. The knee is the highest rate at which the
    requests completed by the window's end are >= 95% of those due in
    time to finish in it, and none failed or was shed
    (benchmark/README.md)."""
    import json

    for rate in ctx.rates:
        run = _window(ctx, server, True, {}, rate=rate)
        ok = [r for r in run.requests if r["ok"]]
        done_in_window = sum(1 for r in ok if r["due_s"] + r["client_s"] <= ctx.seconds)
        # A request due in the window's last moments cannot finish in it however
        # idle the server: those due later than one median service time before
        # the end are left out of "due".
        service = statistics.median(r["total_s"] - r["queue_wait_s"] for r in ok) if ok else 0.0
        due_early = sum(1 for r in run.requests if r["due_s"] <= ctx.seconds - service)
        ttft = [1e3 * (r["late_s"] + r["ttft_s"]) for r in ok]
        wait = [1e3 * r["queue_wait_s"] for r in ok]
        print("# sweep " + json.dumps({
            "rate_per_s": rate, "due": run.attempted, "failed": run.failed,
            "completed_share": done_in_window / max(due_early, 1),
            "median_service_s": service, "drain_s": run.window_s - ctx.seconds,
            "ttft_p50_ms": record.percentile(ttft, 50), "ttft_p95_ms": record.percentile(ttft, 95),
            "queue_wait_p50_ms": record.percentile(wait, 50),
            "queue_wait_p95_ms": record.percentile(wait, 95),
            **{k: run.notes[k] for k in ("kv_exhausted_total", "shed_total", "preempted_total",
                                         "generator_late_p95_ms", "output_tokens")},
            "decode_step_p50_ms": 1e3 * (record.percentile(run.hists["serving/decode_step"], 50) or 0),
        }), flush=True)


def check_outputs(ctx, server) -> tuple[bool, dict]:
    """The served path against the plain float32 reference, at the
    published widths, outside the window. Logits, not tokens: with
    random weights the largest logit changes on rounding.

    1. classify: the served top-5 log-probabilities against the
       reference's log-softmax of the same tokens;
    2. greedy streams through prefill -> paged decode: the reference's
       logit of every served token within the tolerance of the
       reference's maximum at that position;
    3. the same prompt again equals the first answer (a cold prefill
       equals a prefix-cache hit), and the hit was a hit.
    """
    import jax
    import jax.numpy as jnp

    config = ctx.cell.config
    check = config["correct"]
    tol = float(check["logit_abs"])
    ref = spec.reference(config["reference"])
    vocab = int(server.model_cfg.vocab_size)
    pad = int(check.get("reference_len", 256))
    pad = min(pad, int(server.model_cfg.max_len))
    rng = np.random.default_rng(np.random.SeedSequence([int(ctx.seed), 12]))
    n_new = int(check.get("stream_tokens", 12))
    lengths = [n for n in check.get("prompt_lens", [24, 70, 150]) if n + n_new <= pad]
    prompts = [[int(t) for t in rng.integers(0, vocab, (n,))] for n in lengths]

    rows, detail, ok = [], {"logit_tolerance": tol}, True
    served_top, streams = [], []
    for p in prompts:
        status, reply = server.handle({"prompt": p, "top_n": 5}, kind="classify")
        ok &= status == 200
        served_top.append(reply.get("top") or [])
        rows.append(p)
    reused0 = server.registry.counter("serving/prefix_reused_tokens").value
    for p in prompts[-2:]:
        status, first = server.handle({"prompt": p, "max_new_tokens": n_new})
        status2, again = server.handle({"prompt": p, "max_new_tokens": n_new})
        ok &= status == 200 and status2 == 200
        toks = first.get("tokens") or []
        ok &= len(toks) == n_new
        detail.setdefault("cold_equals_hit", True)
        detail["cold_equals_hit"] &= toks == (again.get("tokens") or [])
        streams.append((p, toks))
        rows.append(p + toks)
    reused = server.registry.counter("serving/prefix_reused_tokens").value - reused0
    detail["prefix_reused_tokens_in_check"] = int(reused)
    if server.serve_cfg.prefix_cache and server.serve_cfg.kv_block_size:
        ok &= reused > 0
    ok &= detail.get("cold_equals_hit", True)

    tokens = np.zeros((len(rows), pad), np.int32)
    for i, r in enumerate(rows):
        tokens[i, :len(r)] = r  # causal: the padding behind a row is inert
    logits = np.asarray(
        jax.jit(ref.forward, static_argnames=("n_layer",))(
            server.params, jnp.asarray(tokens), n_layer=int(server.model_cfg.num_layers)
        )
    ).astype(np.float64)

    worst_lp = 0.0
    for i, (p, top) in enumerate(zip(prompts, served_top)):
        row = logits[i, len(p) - 1]
        logp = row - (np.log(np.sum(np.exp(row - row.max()))) + row.max())
        ok &= len(top) == 5
        for entry in top:
            worst_lp = max(worst_lp, abs(entry["logprob"] - logp[entry["token"]]))
    worst_gap = 0.0
    for j, (p, toks) in enumerate(streams):
        i = len(prompts) + j
        for k, tok in enumerate(toks):
            row = logits[i, len(p) + k - 1]
            worst_gap = max(worst_gap, float(row.max() - row[tok]))
    detail.update(classify_logprob_worst=worst_lp, stream_logit_gap_worst=worst_gap)
    ok &= worst_lp <= tol and worst_gap <= tol
    return bool(ok), detail
