"""Runner of a serving cell whose model caches a LATENT row and whose
traffic shares long documents: the prefix cache does the work.

What it takes, unchanged: from ``runners/serve.py`` ``_Tracer`` and the
``Server`` bookkeeping (the engine, the batcher and the frontend as
``examples/gpt2/serve.py`` builds them, weights from the workload's
``init_fn`` under ``jit``, ``engine.warmup()``); from
``runners/serve_kinds.py`` its ``Server`` (the expert and pool counters),
``SliceCounters`` (the program's counters at the two ends of the traced
slice) and the small helpers of its check.

What it replaces:

* the window. ``serve._window`` draws its calibration wave from another
  seed than the window's requests, so the wave would bring documents of
  its own; here set-up publishes the window's documents (one request a
  document, one at a time: a deployment that loads its corpus), the
  calibration wave asks questions about THOSE documents, and after the
  window the run is held to what the traffic implies: every request a
  prefix hit of its whole document (``serving/prefix_reused_tokens``
  over the window = the documents' tokens of its requests), nothing
  preempted, shed or exhausted.
* ``check_outputs``: one document beyond 16k tokens under several
  questions — the first prompt cold (chunked prefill), the others prefix
  hits (demanded by the counter, their tails through the extend program
  over the cached latent rows), every prompt's stream decoded at a full
  batch beside filler requests, each streamed token's log-probability
  as the decode program computed it — all against the plain float32
  reference (expanded attention), rows where the router nearly tied
  deciding nothing and rows that decide demanded of EACH kind, cold,
  hit and decode (``runners/serve_kinds.py`` has the scheme).
* ``run.model``: the sizes and operations of the model's own block
  (``benchmark/roofline_glm4_moe_lite.py``).
* ``setup_s`` leaves out the seconds the reference took.
"""

from __future__ import annotations

import statistics
import time

import numpy as np

from benchmark import peaks as peaks_mod
from benchmark import record, roofline_glm4_moe_lite as ops, spec, traffic_gen
from benchmark.runners import serve as base
from benchmark.runners import serve_kinds as kinds
from benchmark.runners.serve_kinds import _log_softmax, _together

# Beside the counters of base and kinds: what the engine counts for a latent row.
COUNTERS = ("serving/latent_attn_absorbed_tokens", "serving/prefix_hits", "serving/prefix_misses")


class Server(kinds.Server):
    def since(self, mark: dict) -> tuple[dict, dict]:
        counters, hists = super().since(mark)
        now = self.registry.counter_values()
        for name in COUNTERS:
            counters[name] = int(now.get(name, 0)) - int(mark["counters"].get(name, 0))
        return counters, hists


def run(ctx: record.Context) -> record.Run:
    if ctx.rates:
        raise SystemExit("--rates sweeps an open loop; this runner drives a closed one")
    server = Server(ctx)
    sampler = kinds.SliceCounters(server.registry).start() if ctx.trace else None
    try:
        correct, detail = check_outputs(ctx, server)
        run_ = window(ctx, server, correct, detail)
        whole = free_list_whole(server)
        run_.correct_detail["free_list_whole_after_window"] = whole
        run_.correct = bool(run_.correct and whole)
        # The reference is the benchmark's own work, not the deployment's set-up.
        run_.setup_s -= detail["reference_s"]
        if sampler is not None:
            sampler.stop()
            run_.model["slice"] = run_.notes["slice"] = kinds.SliceCounters.read(ctx.trace_dir)
        return run_
    finally:
        if sampler is not None:
            sampler.stop()
        server.close()


def model_numbers(ctx, server) -> dict:
    import jax

    s = ops.sizes(ctx.cell.config)
    item = int(np.dtype(server.params["wte"]["embedding"].dtype).itemsize)
    return {
        "sizes": s, "param_itemsize": item, "max_slots": int(server.serve_cfg.max_slots),
        "n_params": int(sum(x.size for x in jax.tree.leaves(server.params))),
        "kv_bytes_token": ops.kv_bytes_token(s, item),
        "layers": s["dense_layers"] + s["sparse_layers"], "chips": ctx.cell.chips,
    }


def free_list_whole(server) -> bool:
    """Every block is free or parked in the prefix cache again (nothing
    leaked by a finish, a hit or a chunk), once the batcher has nothing left."""
    pool = server.engine.pool
    for _ in range(50):  # the last reply is out before its slot is freed
        if not pool.active_slots:
            break
        time.sleep(0.1)
    with pool._lock:
        whole = len(pool._free_blocks) + len(pool._evictable) == pool.num_blocks - 1
    return bool(whole and not pool.active_slots)


# ------------------------------------------------------------------ window


def window(ctx, server, correct, detail) -> record.Run:
    """Publish the documents, size the window by one calibration wave
    over them, then the closed loop; the run as ``serve._window`` hands
    it back, with each record's reused document length beside it."""
    cell, mix = ctx.cell, ctx.cell.traffic
    vocab = int(server.model_cfg.vocab_size)
    slots = int(server.serve_cfg.max_slots)
    clients = traffic_gen.closed_loop_clients(mix, slots, server.serve_cfg.max_queue)

    # 1. the corpus, loaded once: one request a document, one at a time
    docs = traffic_gen.shared_prefixes(mix, ctx.seed, vocab=vocab)
    pub = mix.get("publish", {})
    t0 = time.perf_counter()
    for i, doc in enumerate(docs):
        tail = [int(t) for t in np.random.default_rng([int(ctx.seed) % 2**32, 9, i]).integers(
            0, vocab, (int(pub.get("question_tokens", 16)),))]
        status, _ = server.handle({"prompt": doc + tail, "slo": mix.get("slo", "interactive"),
                                   "max_new_tokens": int(pub.get("new_tokens", 4))})
        if status != 200:
            raise RuntimeError(f"publishing document {i} ({len(doc)} tokens) failed: {status}")
    publish_s = time.perf_counter() - t0

    def about_the_corpus(requests):
        """The generator's requests with the window's documents in front
        (it draws its documents from the seed it is given)."""
        lengths = mix["prefixes"]["lengths"]
        for r in requests:
            own = r["body"]["prompt"][lengths[r["prefix"]]:]
            r["body"]["prompt"] = docs[r["prefix"]] + own
        return requests

    # 2. one wave of real requests about the documents, cut to a few tokens each,
    # sizes the work: whole waves, as many as last --seconds
    calib = about_the_corpus(traffic_gen.make_requests(
        mix, slots, spec.fold_seed(ctx.seed, 7), vocab=vocab))
    asked = calib[0]["body"]["max_new_tokens"]
    short = min(asked, int(mix.get("calibration_tokens", 32)))
    for r in calib:
        r["body"]["max_new_tokens"] = short
    records, calib_s = traffic_gen.drive_closed_loop(server.handle, calib, clients)
    ok = [r for r in records if r["ok"] and r["n_tokens"] > 1]
    if ok:
        gap = statistics.median((r["total_s"] - r["ttft_s"]) / (r["n_tokens"] - 1) for r in ok)
        wave_s = statistics.median(r["ttft_s"] for r in ok) + (asked - 1) * gap
    else:
        wave_s = calib_s * asked / short
    waves = max(1, int(round(ctx.seconds / wave_s)))
    requests = traffic_gen.make_requests(mix, waves * slots, ctx.seed, vocab=vocab)
    doc_tokens = [len(docs[r["prefix"]]) for r in requests]

    # 3. the window
    tracer = base._Tracer(ctx, mix) if ctx.trace else None
    mark = server.mark()
    t_open = time.perf_counter()
    if tracer:
        tracer.start()
    records, window_s = traffic_gen.drive_closed_loop(server.handle, requests, clients)
    t_close = time.perf_counter()
    trace = tracer.finish() if tracer else None
    counters, hists = server.since(mark)
    compiled = ctx.compiles.between(t_open, t_close)
    bad = [c for c in compiled if any(p in c for p in base.PROGRAMS)]
    if bad:
        raise RuntimeError(f"the engine's own programs compiled inside the window: {bad}")
    for r, n in zip(records, doc_tokens):
        r["reused"] = n  # what of the prompt the traffic says was served from the cache

    failed = sum(1 for r in records if not r["ok"])
    reused_due = sum(n for r, n in zip(records, doc_tokens) if r["status"] == 200)
    notes = dict(
        clients=clients, waves=waves, calibration_wave_s=wave_s, publish_s=publish_s,
        documents=len(docs), document_tokens=sum(map(len, docs)),
        requests=len(records), failed=failed,
        kv_exhausted_total=counters["serving/kv_exhausted_total"],
        preempted_total=counters["serving/preempted_total"],
        shed_total=counters["serving/shed_total"],
        prefix_reused_tokens=counters["serving/prefix_reused_tokens"],
        prefix_reused_tokens_due=reused_due,
        prompt_tokens=counters["serving/prefill_tokens"],
        prefix_misses=counters["serving/prefix_misses"],
        latent_attn_absorbed_tokens=counters["serving/latent_attn_absorbed_tokens"],
        output_tokens=sum(r["n_tokens"] for r in records if r["ok"]),
        # how the window's time divides: the steps it took (waves x output tokens if the
        # slots turn over together, more where requests run out of phase) and how long
        # its last replies trailed one another
        decode_steps=counters["serving/decode_steps"],
        decode_tokens=counters["serving/decode_tokens"],
        last_wave_drain_s=_drain_s(records, slots),
        # the batcher's clock around the single-request programs (here the question tails
        # through the extend programs, admission's host work included) over the window;
        # extend_share.shareddoc is their device time over the traced slice's
        tails_host_share_pct=100.0 * sum(hists.get("serving/prefill") or ()) / max(window_s, 1e-9),
        errors=sorted({str(r["error"])[:120] for r in records if r["error"]})[:3],
    )
    exact = all(r["n_tokens"] == r["asked"] for r in records if r["status"] == 200)
    # what the traffic implies: every request a hit of its whole document, no more
    # (a question is unique) and no less (no document left the pool), nothing turned away
    as_implied = (
        counters["serving/prefix_reused_tokens"] == reused_due
        and counters["serving/prefix_misses"] == 0
        and not (counters["serving/kv_exhausted_total"] or counters["serving/preempted_total"]
                 or counters["serving/shed_total"])
    )
    detail["every_reply_has_the_tokens_asked"] = bool(exact)
    detail["every_request_hit_its_whole_document"] = bool(as_implied)
    run = record.Run(
        cell=cell, setup_s=t_open - ctx.t_start, warmup_s=server.warmup_s,
        window_s=window_s, attempted=len(records), failed=failed,
        correct=bool(correct and exact and as_implied), correct_detail=detail,
        requests=records, counters=counters, hists=hists,
        model=model_numbers(ctx, server),
        compiles_in_window=compiled, notes=notes, trace=trace,
    )
    run.peaks = peaks_mod.peaks_of_this_device()
    return run


def _drain_s(records: list, slots: int) -> float | None:
    """Seconds between the first and the last reply of the window's last
    ``slots`` replies (the tail in which the batch runs partly empty)."""
    done = sorted(r["due_s"] + r["client_s"] for r in records if r["ok"])
    return done[-1] - done[-slots] if len(done) >= slots else None


# ------------------------------------------------------------------- check


def check_outputs(ctx, server, *, reference_weights: str | None = None) -> tuple[bool, dict]:
    """The served path against the plain float32 reference, at the
    published widths, outside the window. Logits, not tokens.

    One document of ``correct.document_len`` tokens (beyond 16k) under
    ``len(prompt_lens)`` questions, at the context lengths the window's
    programs run at, each row named by what ``served`` it:

    * ``cold``: ``classify`` top-5 log-probabilities of the first
      prompt, the document not yet in the pool: chunked prefill, 33
      chunks through the extend program, each over the latent rows the
      earlier ones wrote. One prompt gives one such row, and about one
      row in nine is clear of a router tie, so the prompt ENDS where the
      reference says the router is farthest from one (the best of the
      question's last ``cold_end_rows`` positions, one reference pass);
    * ``hit``: the same of the cold prompt's ``classify_prefixes - 1``
      next shorter prefixes, of every other prompt and its prefixes, and
      each stream's first token: prefix hits of the whole document (the
      counter must show each), the tail through the extend program over
      the 16k cached rows that the chunked prefill wrote;
    * ``decode``: a greedy stream of ``stream_tokens`` from every prompt
      with ``"logprobs": true``, all at once and behind short filler
      requests that fill the other slots and keep decoding: every token
      after the first is what the decode program computed from its own
      logits (absorbed attention through the block tables, the rows
      written by earlier steps), at the window's batch;
    * ONE reference pass a prompt over prompt + stream gives the logits
      at every such position and, per row, how close its router came to
      choosing otherwise (``route_gap``).

    A row clear of a near-tie must lie within ``logit_abs``; a row
    nearer than ``route_gap`` decides nothing. ``min_clear_rows`` clear
    rows of EACH kind are demanded: while one has fewer, a further
    question of ``extra_prompt_len`` tokens on the same document is
    served (a hit, its stream beside fillers), up to
    ``extra_prompts_max``, and with too few even then the run is not
    correct. ``failed_by`` names every condition that failed.
    ``reference_weights="int8"`` is the control: another document, the
    reference in the nearest precision below, which must come out not
    correct by ``logit_abs`` alone (``benchmark/control_serve_latent.py``)."""
    config = ctx.cell.config
    check = config["correct"]
    tol, gap_min = float(check["logit_abs"]), float(check["route_gap"])
    need = {k: int(v) for k, v in check["min_clear_rows"].items()}
    ref = spec.reference(config["reference"])
    vocab, max_len = int(server.model_cfg.vocab_size), int(server.model_cfg.max_len)
    n_new, n_pre = int(check["stream_tokens"]), int(check["classify_prefixes"])
    slots, chunk = int(server.serve_cfg.max_slots), int(server.serve_cfg.prefill_chunk_tokens)
    rng = np.random.default_rng(np.random.SeedSequence(
        [int(ctx.seed), 12, int(reference_weights is not None)]))

    def draw(n):
        return [int(t) for t in rng.integers(0, vocab, (n,))]

    doc_len = min(int(check["document_len"]), max_len // 2)
    lens = [min(int(n), max_len - n_new) for n in check["prompt_lens"]]
    x_len = min(int(check["extra_prompt_len"]), max_len - n_new)
    doc = draw(doc_len)
    fill = check["fillers"]
    fill_len = min(int(fill["prompt_len"]), max_len // 4)
    pad = max(*lens, x_len) + n_new

    counter = lambda name: server.registry.counter(name).value  # noqa: E731
    reused, chunks = "serving/prefix_reused_tokens", "serving/prefill_chunks"
    absorbed = "serving/latent_attn_absorbed_tokens"
    at_start = {n: counter(n) for n in (reused, absorbed)}
    failed_by, rows_out, live, hit_tokens, cold_chunks, reference_s = set(), [], [], [], None, 0.0

    def reference(tokens, rows):
        nonlocal reference_s
        t0 = time.perf_counter()
        out = ref.forward(server.params, tokens, config, rows=rows, pad_to=pad,
                          q_block=int(check["reference_q_block"]), weights=reference_weights)
        reference_s += time.perf_counter() - t0
        return out

    def classify(p):
        """[(prefix length, top-5)] of p and its next shorter prefixes,
        one request at a time; the tokens the FIRST of them reused (and,
        of the first prompt, the chunks it took)."""
        nonlocal cold_chunks
        out, before, chunks0 = [], counter(reused), counter(chunks)
        for cut in range(n_pre):
            status, reply = server.handle({"prompt": p[:len(p) - cut], "top_n": 5},
                                          kind="classify")
            top = reply.get("top") or []
            if status != 200 or len(top) != 5:
                failed_by.add("served")
            out.append((len(p) - cut, top))
            if cut == 0:
                hit_tokens.append(counter(reused) - before)
                if cold_chunks is None:
                    cold_chunks = counter(chunks) - chunks0
        return out

    def compare(p, tops, gen, how):
        """One reference pass over p + its stream; the rows it gives.
        ``how``: what served the prompt's first classify (cold | hit)."""
        toks, lps = gen.get("tokens") or [], gen.get("logprobs") or []
        if len(toks) != n_new or len(lps) != n_new:
            failed_by.add("served")
            return
        first = len(p) - n_pre  # row r holds the logits after r + 1 tokens
        logits, gaps = reference(p + toks, range(first, len(p) + n_new - 1))
        logp = [_log_softmax(row) for row in logits]
        for cut, (n, top) in enumerate(tops):
            i = n - 1 - first
            err = max((abs(e["logprob"] - logp[i][e["token"]]) for e in top), default=np.inf)
            rows_out.append({"len": len(p), "row": f"classify@{n}",
                             "served": how if cut == 0 else "hit",
                             "err": float(err), "gap": float(gaps[i])})
        for k, (tok, lp) in enumerate(zip(toks, lps)):
            i = len(p) - 1 + k - first
            rows_out.append({
                "len": len(p), "row": k, "served": "decode" if k else "hit",
                "err": float(abs(lp - logp[i][tok])), "gap": float(gaps[i]),
                # the served greedy token against the reference's own maximum there
                "behind": float(logits[i].max() - logits[i][tok])})

    def serve(prompts, hows):
        """Classify each prompt in turn, then stream them all together
        while fillers decode in the other slots; compare each."""
        tops = [classify(p) for p in prompts]
        n_fill = max(0, min(int(fill["requests"]), slots - len(prompts)))
        fillers = [{"prompt": draw(fill_len),
                    "max_new_tokens": min(int(fill["new_tokens"]), max_len - fill_len)}
                   for _ in range(n_fill)]
        steps0, tokens0 = counter("serving/decode_steps"), counter("serving/decode_tokens")
        generate = lambda body: lambda: server.handle(body)  # noqa: E731
        replies = _together(
            [generate(f) for f in fillers]
            + [generate({"prompt": p, "max_new_tokens": n_new, "logprobs": True}) for p in prompts])
        steps = counter("serving/decode_steps") - steps0
        live.append((counter("serving/decode_tokens") - tokens0) / max(steps, 1))
        for f, (status, reply) in zip(fillers, replies):
            if status != 200 or len(reply.get("tokens") or []) != f["max_new_tokens"]:
                failed_by.add("served")
        if live[-1] < n_fill / 2:  # the streams' decode steps had the fillers beside them
            failed_by.add("fillers")
        for p, top, (status, gen), how in zip(prompts, tops, replies[n_fill:], hows):
            if status != 200:
                failed_by.add("served")
            elif "served" not in failed_by:
                compare(p, top, gen, how)

    def clear(kind):
        return [r for r in rows_out if r["served"] == kind and r["gap"] >= gap_min]

    # 0. where the cold prompt ends: the row of its question's last cold_end_rows at
    # which the reference's router is farthest from a tie (a prompt of n tokens reads row n - 1)
    cold = doc + draw(lens[0] - doc_len)
    first = max(doc_len + n_pre, len(cold) - int(check["cold_end_rows"]))
    _, gaps = reference(cold, range(first, len(cold)))
    cold = cold[:first + 1 + int(np.argmax(gaps))]
    # 1. the cold prompt, then the other questions on its document: hits
    serve([cold] + [doc + draw(n - doc_len) for n in lens[1:]], ["cold"] + ["hit"] * (len(lens) - 1))
    # 2. further questions while a kind has too few rows that decide
    extra = 0
    while "served" not in failed_by and extra < int(check["extra_prompts_max"]) and any(
            len(clear(kind)) < need[kind] for kind in need):
        serve([doc + draw(x_len - doc_len)], ["hit"])
        extra += 1

    near = [r for r in rows_out if r["gap"] < gap_min]
    worst = {kind: max((r["err"] for r in clear(kind)), default=None) for kind in need}
    for kind in need:  # enough rows that decide, and each within the limit
        if len(clear(kind)) < need[kind]:
            failed_by.add(f"min_clear_rows:{kind}")
        if (worst[kind] or 0.0) > tol:
            failed_by.add(f"logit_abs:{kind}")
    if not all(np.isfinite(r["err"]) for r in rows_out):
        failed_by.add("served")
    # the traffic must have done what the check is for, and left nothing behind: the cold
    # prompt ran in chunks, every other prompt hit the whole document
    whole = free_list_whole(server)
    if not whole:
        failed_by.add("free_list_whole")
    if cold_chunks < (-(-len(cold) // chunk) if chunk else 0):
        failed_by.add("cold_prefill_chunks")
    if hit_tokens[0] or min(hit_tokens[1:], default=doc_len) < doc_len:
        failed_by.add("hit_reused_tokens")
    detail = {
        "logit_tolerance": tol, "route_gap": gap_min, "reference_weights": reference_weights,
        "failed_by": sorted(failed_by),
        "rows": len(rows_out), "rows_near_tie": len(near), "extra_prompts": extra,
        "rows_clear": {kind: len(clear(kind)) for kind in need}, "rows_clear_needed": need,
        "worst": worst, "worst_near_tie": max((r["err"] for r in near), default=None),
        "stream_behind_worst": max((r["behind"] for r in rows_out if "behind" in r), default=None),
        "live_slots_mean_in_check": live, "document_len": doc_len, "cold_prompt_len": len(cold),
        "cold_prefill_chunks": int(cold_chunks), "hit_reused_tokens": [int(n) for n in hit_tokens],
        "prefix_reused_tokens_in_check": int(counter(reused) - at_start[reused]),
        "absorbed_tokens_in_check": int(counter(absorbed) - at_start[absorbed]),
        "free_list_whole": whole, "reference_s": reference_s,
        "by_row": [[r["len"], r["row"], r["served"], round(r["err"], 5),
                    round(min(r["gap"], 99.0), 5)] for r in rows_out],
    }
    return not failed_by, detail
