"""Runner of a serving cell whose model is not GPT-2: layers of several
kinds over the paged pool's block-id spaces, grouped-query rows, an
expert layer that holds some of the experts.

What it takes from ``runners/serve.py``, unchanged: ``Server`` (the
engine, the batcher and the frontend as ``examples/gpt2/serve.py``
builds them, weights from the workload's ``init_fn`` under ``jit``,
``engine.warmup()``), ``_window`` (the calibration wave, the closed or
open loop, the tracer thread, the window's counters and histograms, the
check that nothing compiled inside it) and ``_Tracer``.

What it replaces, because there they are GPT-2's:

* ``check_outputs``: the plain reference is called with the
  configuration (window, head split, held experts, vocabulary slice),
  on prompts long enough that the window binds, blocks are released and
  several chunks run. Numbers from BOTH served paths are held to the
  limit: ``classify`` log-probabilities (prefill and extend programs)
  and the log-probability of every streamed token as the decode program
  itself computed it (``"logprobs": true`` on ``/generate``), the
  streams decoded while other requests fill the slots. There is no
  prefix-cache hit to demand (with a window kind the pool shares no
  prompt blocks), instead both block-id spaces' free lists must be
  whole after the drain. Rows where the router nearly tied over an
  expert held here decide nothing (the reference reports the gap; the
  configuration file's ``correct.why``), so enough other rows are
  demanded from each path, and further prompts drawn until there are.
* ``run.model``: parameters, cache bytes and the operations of the
  model's own block (``benchmark/roofline_cohere2_moe.py``), the
  window's counters the new readers read (routed pairs, experts hit,
  sampled pool bytes) and, in a traced run, the same counters over the
  traced slice alone (``SliceCounters``).
* ``setup_s`` leaves out the seconds the reference took.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from benchmark import record, roofline_cohere2_moe as ops, spec, trace_reduce
from benchmark.runners import serve as base

# Beside base.COUNTERS: what the engine counts for a model with experts
# and a pool of several kinds (docs/observability.md).
COUNTERS = (
    "serving/moe_pairs_held", "serving/moe_pairs_routed",
    "serving/moe_decode_pairs_held", "serving/moe_decode_experts_hit",
    "serving/kv_sampled_bytes", "serving/kv_sampled_tokens",
    "serving/kv_sampled_reach_bytes",
    "serving/kv_window_blocks_released_total", "serving/prefill_chunks",
)
EXPERT_COUNTER = "serving/moe_pairs_expert_"
# What a roofline share of the traced slice is a share of: the decode
# steps inside it, their live requests, the experts they hit and the
# cache bytes they reached (window-wide averages describe other steps).
SLICE_COUNTERS = (
    "serving/decode_steps", "serving/decode_tokens",
    "serving/moe_decode_pairs_held", "serving/moe_decode_experts_hit",
    "serving/moe_pairs_held", "serving/moe_pairs_routed",
    "serving/kv_sampled_reach_bytes",
)
SLICE_EVENT = "bench/counters"


class Server(base.Server):
    def since(self, mark: dict) -> tuple[dict, dict]:
        counters, hists = super().since(mark)
        now = self.registry.counter_values()
        for name in now:
            if name in COUNTERS or name.startswith(EXPERT_COUNTER):
                counters[name] = int(now[name]) - int(mark["counters"].get(name, 0))
        return counters, hists


class SliceCounters:
    """Writes the registry's counters into the profiler's own trace,
    every ``period`` seconds, as an event whose stats are the counters'
    values. Whatever slice of the run the tracer keeps then holds the
    counters at its own start and end, on its own clock: their
    difference is what happened inside it. Outside a trace the event
    goes nowhere."""

    def __init__(self, registry, period: float = 0.02):
        self.registry, self.period = registry, period
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, name="slice-counters", daemon=True)

    def start(self):
        self._thread.start()
        return self

    def _run(self):
        from jax.profiler import TraceAnnotation

        while not self._stop.wait(self.period):
            values = {n.split("/")[1]: int(self.registry.counter(n).value)
                      for n in SLICE_COUNTERS}
            with TraceAnnotation(SLICE_EVENT, **values):
                pass

    def stop(self):
        self._stop.set()
        self._thread.join()

    @staticmethod
    def read(trace_dir: str) -> dict | None:
        """Last minus first event of the trace under ``trace_dir``."""
        from jax.profiler import ProfileData

        try:
            data = ProfileData.from_file(trace_reduce.find_xplane(trace_dir))
        except FileNotFoundError:
            return None
        events = sorted(
            (ev.start_ns, dict(ev.stats)) for plane in data.planes for line in plane.lines
            for ev in line.events if ev.name == SLICE_EVENT)
        if len(events) < 2:
            return None
        (t0, first), (t1, last) = events[0], events[-1]
        return {"seconds": (t1 - t0) / 1e9, "samples": len(events),
                **{k: int(last[k]) - int(first[k]) for k in first}}


def run(ctx: record.Context) -> record.Run:
    server = Server(ctx)
    sampler = SliceCounters(server.registry).start() if ctx.trace and not ctx.rates else None
    try:
        correct, detail = check_outputs(ctx, server)
        if ctx.rates:
            base.sweep(ctx, server)
            return record.Run(cell=ctx.cell)
        run_ = base._window(ctx, server, correct, detail)
        whole = free_lists_whole(server)
        run_.correct_detail["free_lists_whole_after_window"] = whole
        run_.correct = bool(run_.correct and whole)
        run_.model.update(model_numbers(ctx, server))
        # The reference is the benchmark's own work, not the deployment's set-up.
        run_.setup_s -= detail["reference_s"]
        if sampler is not None:
            sampler.stop()
            run_.model["slice"] = run_.notes["slice"] = SliceCounters.read(ctx.trace_dir)
        return run_
    finally:
        if sampler is not None:
            sampler.stop()
        server.close()


def model_numbers(ctx, server) -> dict:
    import jax

    config = ctx.cell.config
    s = ops.sizes(config)
    item = int(np.dtype(server.params["wte"]["embedding"].dtype).itemsize)
    return {
        "sizes": s, "param_itemsize": item,
        "n_params": int(sum(x.size for x in jax.tree.leaves(server.params))),
        # every layer keeping every token: what a pool of one kind would hold
        "kv_bytes_token": (s["window_layers"] + s["full_layers"]) * ops.kv_row_bytes(s, item),
        "layers": s["window_layers"] + s["full_layers"],
    }


def free_lists_whole(server) -> bool:
    """Both block-id spaces hold every block again (nothing leaked by a
    finish, a release or a chunk), once the batcher has nothing left."""
    pool = server.engine.pool
    for _ in range(50):  # the last reply is out before its slot is freed
        if not pool.active_slots:
            break
        time.sleep(0.1)
    with pool._lock:
        full = len(pool._free_blocks) + len(pool._evictable) == pool.num_blocks - 1
        windows = all(len(w.free) == w.num_blocks - 1 for w in pool._windows)
    return bool(full and windows and not pool.active_slots)


def _log_softmax(row: np.ndarray) -> np.ndarray:
    return row - (np.log(np.sum(np.exp(row - row.max()))) + row.max())


def _together(calls: list) -> list:
    """Each call on a thread of its own, started in order; their results."""
    out = [None] * len(calls)

    def one(i):
        out[i] = calls[i]()

    threads = [threading.Thread(target=one, args=(i,)) for i in range(len(calls))]
    for t in threads:
        t.start()
        time.sleep(0.01)  # in order: the fillers hold their slots before the streams arrive
    for t in threads:
        t.join()
    return out


def check_outputs(ctx, server, *, reference_weights: str | None = None) -> tuple[bool, dict]:
    """The served path against the plain float32 reference, at the
    published widths, outside the window. Logits, not tokens.

    For each prompt (``correct.prompt_lens``: shorter than the window,
    just past it, and well past it, so chunks run, the window binds and
    blocks are released):

    * ``classify`` top-5 log-probabilities of the prompt and of its
      ``classify_prefixes - 1`` next shorter prefixes, one request at a
      time: the prefill and extend programs' numbers;
    * a greedy stream of ``stream_tokens`` with ``"logprobs": true``,
      all prompts' streams at once and behind ``filler_requests`` short
      requests that keep decoding meanwhile, so the decode program runs
      them at many live slots: the first token's log-probability is the
      prefill's, every later one is what the decode program computed
      from its own logits (the window gather, the decode softmax, the
      paged rows written by earlier steps);
    * ONE reference pass over prompt + stream gives the logits at every
      such position, and per row how close its router came to choosing
      otherwise over an expert held here (``route_gap``).

    A row clear of such a near-tie must lie within ``logit_abs``; a row
    nearer than ``route_gap`` decides nothing (bf16 rounding may route
    it otherwise: a whole expert's output, not a rounding). At least
    ``min_clear_rows`` clear rows of each path are demanded: where the
    three prompts give fewer, further short prompts are drawn, up to
    ``extra_prompts_max``, and with too few even then the run is not
    correct. ``reference_weights="int8"`` is the control: the reference
    in the nearest precision below, which must come out not correct
    (``benchmark/control_serve_kinds.py``)."""
    config = ctx.cell.config
    check = config["correct"]
    tol, gap_min = float(check["logit_abs"]), float(check["route_gap"])
    need = {k: int(v) for k, v in check["min_clear_rows"].items()}
    ref = spec.reference(config["reference"])
    vocab, max_len = int(server.model_cfg.vocab_size), int(server.model_cfg.max_len)
    n_new = int(check["stream_tokens"])
    n_pre = int(check["classify_prefixes"])
    q_block = int(check["reference_q_block"])
    rng = np.random.default_rng(np.random.SeedSequence([int(ctx.seed), 12]))
    lengths = [n for n in check["prompt_lens"] if n + n_new <= max_len]

    def draw(n):
        return [int(t) for t in rng.integers(0, vocab, (n,))]

    prompts = [draw(n) for n in lengths]
    fill = check["fillers"]
    n_fill = max(0, min(int(fill["requests"]), int(server.serve_cfg.max_slots) - len(prompts)))
    fill_len = min(int(fill["prompt_len"]), max_len // 4)
    fillers = [{"prompt": draw(fill_len), "max_new_tokens": min(int(fill["new_tokens"]),
                                                                 max_len - fill_len)}
               for _ in range(n_fill)]

    counter = lambda name: server.registry.counter(name).value  # noqa: E731
    names = ("serving/prefix_reused_tokens", "serving/kv_window_blocks_released_total",
             "serving/prefill_chunks")
    at_start = {n: counter(n) for n in names}
    ok, rows_out, reference_s = True, [], 0.0

    def classify(p):
        """[(prefix length, top-5)] of p and its next shorter prefixes."""
        nonlocal ok
        out = []
        for cut in range(n_pre):
            status, reply = server.handle({"prompt": p[:len(p) - cut], "top_n": 5},
                                          kind="classify")
            top = reply.get("top") or []
            ok &= status == 200 and len(top) == 5
            out.append((len(p) - cut, top))
        return out

    def compare(p, tops, gen, pad_to):
        """One reference pass over p + its stream; the rows it gives."""
        nonlocal ok, reference_s
        toks, lps = gen.get("tokens") or [], gen.get("logprobs") or []
        ok &= len(toks) == n_new and len(lps) == n_new
        if not ok:
            return
        first = len(p) - n_pre  # row r holds the logits after r + 1 tokens
        t0 = time.perf_counter()
        logits, gaps = ref.forward(
            server.params, p + toks, config, rows=range(first, len(p) + n_new - 1),
            pad_to=pad_to, q_block=q_block, weights=reference_weights)
        reference_s += time.perf_counter() - t0
        logp = [_log_softmax(row) for row in logits]
        for n, top in tops:
            i = n - 1 - first
            err = max((abs(e["logprob"] - logp[i][e["token"]]) for e in top), default=np.inf)
            rows_out.append({"len": len(p), "row": f"classify@{n}", "path": "prefill",
                             "err": float(err), "gap": float(gaps[i])})
        for k, (tok, lp) in enumerate(zip(toks, lps)):
            i = len(p) - 1 + k - first
            rows_out.append({
                "len": len(p), "row": k, "path": "decode" if k else "prefill",
                "err": float(abs(lp - logp[i][tok])), "gap": float(gaps[i]),
                # the served greedy token against the reference's own maximum there
                "behind": float(logits[i].max() - logits[i][tok])})

    def clear(path):
        return [r for r in rows_out if r["path"] == path and r["gap"] >= gap_min]

    # 1. classify, one request at a time
    tops = [classify(p) for p in prompts]
    # 2. the streams, together, while the fillers decode
    steps0, tokens0 = counter("serving/decode_steps"), counter("serving/decode_tokens")
    generate = lambda body: lambda: server.handle(body)  # noqa: E731
    replies = _together(
        [generate(f) for f in fillers]
        + [generate({"prompt": p, "max_new_tokens": n_new, "logprobs": True}) for p in prompts])
    steps = counter("serving/decode_steps") - steps0
    live = (counter("serving/decode_tokens") - tokens0) / max(steps, 1)
    for f, (status, reply) in zip(fillers, replies):
        ok &= status == 200 and len(reply.get("tokens") or []) == f["max_new_tokens"]
    streams = replies[n_fill:]
    ok &= all(status == 200 for status, _ in streams)
    # 3. the reference, one pass a prompt
    pad = max(lengths) + n_new
    for p, top, (_, gen) in zip(prompts, tops, streams):
        if ok:
            compare(p, top, gen, pad)
    # 4. further short prompts while a path has too few rows that decide
    extra = 0
    while ok and extra < int(check["extra_prompts_max"]) and any(
            len(clear(path)) < need[path] for path in need):
        p = draw(min(lengths))
        top = classify(p)
        status, gen = server.handle({"prompt": p, "max_new_tokens": n_new, "logprobs": True})
        ok &= status == 200
        if ok:
            compare(p, top, gen, None)
        extra += 1

    near = [r for r in rows_out if r["gap"] < gap_min]
    detail = {
        "logit_tolerance": tol, "route_gap": gap_min, "reference_weights": reference_weights,
        "rows": len(rows_out), "rows_near_tie": len(near), "extra_prompts": extra,
        "rows_clear": {path: len(clear(path)) for path in need}, "rows_clear_needed": need,
        "worst": {path: max((r["err"] for r in clear(path)), default=None) for path in need},
        "worst_near_tie": max((r["err"] for r in near), default=None),
        "stream_behind_worst": max((r["behind"] for r in rows_out if "behind" in r), default=None),
        "live_slots_mean_in_check": live, "filler_requests": n_fill,
        "prefix_reused_tokens_in_check": int(counter(names[0]) - at_start[names[0]]),
        "window_blocks_released_in_check": int(counter(names[1]) - at_start[names[1]]),
        "prefill_chunks_in_check": int(counter(names[2]) - at_start[names[2]]),
        "free_lists_whole": free_lists_whole(server),
        "reference_s": reference_s,
        "by_row": [[r["len"], r["row"], r["path"], round(r["err"], 5), round(min(r["gap"], 99.0), 4)]
                   for r in rows_out],
    }
    for path in need:  # enough rows that decide, and each within the limit
        ok &= detail["rows_clear"][path] >= need[path] and (detail["worst"][path] or 0.0) <= tol
    ok &= all(np.isfinite(r["err"]) for r in rows_out)
    # the traffic must have done what the check is for, and left nothing behind
    ok &= detail["free_lists_whole"] and detail["prefix_reused_tokens_in_check"] == 0
    ok &= live >= n_fill / 2  # the streams' decode steps had the fillers beside them
    if max(lengths) > int(config["sliding_window"]):
        ok &= detail["window_blocks_released_in_check"] > 0 and detail["prefill_chunks_in_check"] > 1
    return bool(ok), detail
