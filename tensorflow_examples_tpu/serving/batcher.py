"""Continuous-batching request queue over the inference engine.

The throughput story of serving (the "serves heavy traffic" half of the
ROADMAP north star) is batching; the latency story is NOT waiting for a
full batch. Continuous batching does both: the decode step always runs
at the engine's fixed ``[max_slots]`` shape, and requests join (prefill
into a free slot) and leave (retire at EOS/limit) BETWEEN steps — a new
request never waits for the current batch to finish, a finished request
never makes the batch wait.

Flow control, outermost first:

* **Backpressure**: the submit queue is bounded (``max_queue``). A full
  queue sheds the request immediately (:class:`QueueFull`, counted in
  ``serving/shed_total``) — the caller gets a 503 now instead of a
  timeout later, and the queue can never grow without bound.
* **Admission control**: a request whose prompt+generation budget
  cannot fit the model's ``max_len`` is rejected up front
  (``serving/rejected_total``); one whose deadline already passed while
  queued is expired without touching the device
  (``serving/expired_total``).
* **Coalescing**: from idle, the first arrival opens a ``max_delay_s``
  window so a burst prefills together before the first decode step;
  under load, admission happens opportunistically between decode steps
  with no added delay. ``max_batch`` caps concurrency below the slot
  count when wanted.
* **Deadlines**: a request past its deadline mid-generation retires
  early with what it has (``truncated="deadline"``).

Latency accounting (the histograms the frontend's ``/metrics`` renders,
all ``registry.TimeHistogram``): ``serving/queue_wait`` (submit ->
admitted), ``serving/prefill`` (prefill wall), ``serving/ttft``
(submit -> first token), ``serving/tpot`` (per generated token decode
wall), ``serving/e2e`` (submit -> done).

The loop runs on one daemon thread; a ``utils.diagnostics.Watchdog``
(``watchdog_secs > 0``) gets phase markers (``serve_idle`` /
``serve_admit`` / ``serve_prefill`` / ``serve_decode``) so a wedged
device step is attributed exactly like a training-loop hang.

Speculative decoding (ISSUE 11, ``ServeConfig.spec_decode_k > 0``):
each decode step first asks the per-request draft source
(serving/speculative.py) for up to k candidate tokens, runs the
engine's compiled ``verify_k`` rung over launch token + drafts, and
commits the longest agreeing prefix — multiple tokens per step, every
one of them a verify-SAMPLED token at its own position key, so streams
stay token-identical to the non-speculative path. TPOT records
wall/committed per token; ``serving/accepted_per_step`` and the
``serving/spec_*`` counters carry the acceptance story onto the
schema-v8 stats line.

Threading contract (checked by graftlint, ISSUE 14 — see
docs/static_analysis.md): the batcher deliberately owns NO lock, so it
carries no ``# guard:`` annotations. Every structure crossed by the
frontend submit threads and the loop thread synchronizes itself — the
per-class ``queue.Queue``s and the ``_arrival`` Event internally, the
per-request shed/spec tallies through the LOCKED metrics registry
(``telemetry/registry.py``, annotated there; this is why the
lock pass surfaces no tally aggregation race here), and the brownout
controller via its annotated ``_ttft`` sample lock plus documented
atomic ``level`` reads (``serving/overload.py``). Everything else
(``_active``/``_prefilling``/``_staged``/``_draining``) is
single-writer on the loop thread with GIL-atomic len()/int/bool
snapshot reads from close()/stats_line(), as noted field-by-field
below. The runtime lock-order detector and the thread-leak guard
(tests/conftest.py) cover the dynamic side in the overload tier.

SLO classes (ISSUE 13): every request carries an ``slo`` class —
``interactive`` (default) or ``batch`` — and the batcher keeps one
bounded queue per class. Interactive is served first at every decision
point: admission drains the interactive queue before the batch queue,
chunked-prefill turns prefer interactive, and when the slots are full
an interactive arrival PREEMPTS the most recently admitted batch
request (its slot is freed and the request re-queued; replay from the
prompt is token-identical by the per-request seeding, so preemption is
a latency event, never a content one). Latency histograms and shed
counters exist per class (``serving/ttft_interactive`` /
``serving/shed_batch_total`` / ...) next to the class-blind ones, and
the schema-v10 stats line carries the split. Under pressure the
brownout ladder (``serving/overload.py``, ``ServeConfig.brownout``)
sheds batch FIRST, then caps generation budgets, then drops
speculation's extra verify work, and sheds interactive only as the
last rung before falling over.
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
import logging
import queue
import threading
import time

from tensorflow_examples_tpu.serving.engine import EngineStepError
from tensorflow_examples_tpu.serving.overload import OverloadController
from tensorflow_examples_tpu.serving.paged_kv import BlockExhausted
from tensorflow_examples_tpu.telemetry import registry as registry_mod
from tensorflow_examples_tpu.telemetry import schema
from tensorflow_examples_tpu.telemetry.spans import span
from tensorflow_examples_tpu.telemetry.tracing import (
    ExemplarStore,
    close_span,
)

log = logging.getLogger(__name__)

# SLO classes, in service-priority order: admission, chunk turns and
# preemption all prefer earlier classes (ISSUE 13).
SLO_CLASSES = ("interactive", "batch")


class QueueFull(RuntimeError):
    """Bounded submit queue is full: request load-shed (HTTP 503)."""


class Draining(RuntimeError):
    """Batcher is draining for shutdown: new requests rejected (503)."""


class DeadlineExceeded(RuntimeError):
    """The request's deadline passed before any token was produced."""


@dataclasses.dataclass
class Request:
    """One generate/classify request (token ids in, token ids out).

    The disaggregated roles (ISSUE 12) add two more kinds: ``prefill``
    runs the prompt to completion-of-prefill and resolves with the
    first generated token plus the slot's serialized KV pages
    (``Result.pages``); ``resume`` imports ``pages``/``first_token``
    from a prefill replica and continues the decode stream."""

    prompt: list[int]
    max_new_tokens: int = 16
    temperature: float = 0.0
    top_k: int = 0
    seed: int = 0
    eos_id: int | None = None
    deadline_s: float | None = None  # relative to submit time
    kind: str = "generate"       # generate | classify | prefill | resume
    classify_top_n: int = 5
    logprobs: bool = False       # generate: also return the model's
    #                              log-probability of each generated
    #                              token (Result.logprobs) — the first
    #                              from the prefill's logits, the rest
    #                              from the decode steps' own fetch
    pages: dict | None = None        # resume: the handed-off KV pages
    first_token: int | None = None   # resume: the prefill's sampled token
    skip_tokens: int = 0             # prefill: leading prompt tokens the
    #                                  importer already caches (router
    #                                  digest exchange, ISSUE 15) — the
    #                                  export ships only the rest
    slo: str = "interactive"     # interactive | batch (ISSUE 13):
    #                              interactive is served first
    #                              everywhere; batch absorbs shedding
    #                              and preemption first
    trace: dict | None = None    # ISSUE 18: the router's trace context
    #                              ({"trace_id", "parent_span_id",
    #                              "sampled"}). When set, the batcher
    #                              collects this request's spans
    #                              (queue_wait, prefill chunks, decode
    #                              segments, preemptions) and returns
    #                              them on Result.spans; None costs the
    #                              hot path nothing.


@dataclasses.dataclass
class Result:
    """Resolved request payload (the frontend serializes this)."""

    tokens: list[int]               # generated tokens (generate)
    prompt_len: int
    top: list[dict] | None = None   # classify payload
    logprobs: list[float] | None = None  # per token, where asked for
    truncated: str | None = None  # None | "deadline" | "max_len"
    #                               | "shutdown" | "brownout" (the
    #                               level-2 generation cap bit: tokens
    #                               are a PREFIX of the uncapped stream)
    queue_wait_s: float = 0.0
    ttft_s: float | None = None
    total_s: float = 0.0
    # Per-request speculation accounting (ISSUE 11; zeros with
    # speculation off): drafts offered to verify steps and drafts
    # accepted. len(tokens) - 1 - spec_accepted = plain decode commits,
    # which is how the accounting test ties streams to counters.
    spec_drafted: int = 0
    spec_accepted: int = 0
    # Disaggregated prefill (ISSUE 12): the serialized KV pages a
    # kind="prefill" request resolves with (None otherwise).
    pages: dict | None = None
    # ISSUE 18: this request's replica-side span dicts (None when the
    # request carried no trace context). The frontend returns them as
    # the reply's "trace_spans"; top-level spans carry parent_id=None
    # and the router reparents them under its dispatch span.
    spans: list | None = None


class _InFlight:
    __slots__ = (
        "req", "future", "slot", "t_submit", "t_admit", "t_first",
        "deadline", "tokens", "logprobs", "last_token", "spec_drafted",
        "spec_accepted", "max_new_eff", "spans", "t_decode0",
        "decode_seg", "decode_tok0",
    )

    def __init__(self, req: Request, future, t_submit: float):
        self.req = req
        self.future = future
        self.slot: int | None = None
        self.t_submit = t_submit
        self.t_admit: float | None = None
        self.t_first: float | None = None
        self.deadline = (
            t_submit + req.deadline_s
            if req.deadline_s is not None else None
        )
        self.tokens: list[int] = []
        self.logprobs: list[float] = []  # filled where req.logprobs
        self.last_token: int | None = None
        # ISSUE 18 trace collection (None = untraced, zero overhead).
        # The span list SURVIVES preemption resets below — a preempted
        # request's trace shows every decode segment it lived through.
        self.spans: list | None = [] if req.trace is not None else None
        self.t_decode0: float | None = None  # current decode segment t0
        self.decode_seg = 0
        self.decode_tok0 = 0  # committed tokens at segment start
        # Per-request speculation accounting (ISSUE 11): drafts offered
        # to verify steps and drafts accepted. Committed tokens ==
        # len(tokens) always — acceptance is a speed story, never a
        # content one (test-pinned).
        self.spec_drafted = 0
        self.spec_accepted = 0
        # Effective generation budget (ISSUE 13): the brownout level-2
        # cap at ADMISSION time, <= req.max_new_tokens. A capped stream
        # retires with truncated="brownout" — still a prefix of the
        # uncapped stream.
        self.max_new_eff = req.max_new_tokens


class ContinuousBatcher:
    def __init__(self, engine, *, registry=None, watchdog=None,
                 draft=None):
        self.engine = engine
        cfg = engine.cfg
        self.max_batch = min(
            cfg.max_batch or cfg.max_slots, cfg.max_slots
        )
        self.max_delay_s = cfg.max_delay_s
        # Speculative decoding (ISSUE 11): with spec_decode_k > 0 the
        # decode step becomes draft-propose / verify-commit — the
        # drafter proposes up to k tokens per request, one compiled
        # verify_k forward scores them, and the longest agreeing prefix
        # commits. ``draft=`` injects a custom DraftSource (a small
        # draft model, a test stub); default is the self-speculative
        # n-gram source.
        self.spec_k = int(getattr(cfg, "spec_decode_k", 0) or 0)
        self._draft = None
        if self.spec_k > 0 and hasattr(engine, "verify"):
            if draft is None:
                from tensorflow_examples_tpu.serving.speculative import (
                    make_draft,
                )

                draft = make_draft(cfg)
            self._draft = draft
        self.registry = (
            registry if registry is not None else engine.registry
        )
        # One bounded queue per SLO class (ISSUE 13): admission drains
        # interactive first; a class sheds only against its OWN bound,
        # and the brownout ladder sheds batch fleet-wide before
        # interactive ever queues deep.
        self._queues: dict[str, queue.Queue] = {
            cls: queue.Queue(maxsize=cfg.max_queue)
            for cls in SLO_CLASSES
        }
        # Signaled on every submit so the idle loop can block on
        # "anything arrived in ANY class queue".
        self._arrival = threading.Event()
        # Brownout overload controller (serving/overload.py): ticked
        # once per loop iteration with queue depth + KV occupancy (+
        # its own recent-TTFT window); submit() reads its level.
        self._overload = OverloadController(
            registry=self.registry,
            enabled=bool(getattr(cfg, "brownout", False)),
            queue_hi=(
                int(getattr(cfg, "brownout_queue_hi", 0) or 0)
                or 2 * cfg.max_slots
            ),
            kv_hi=float(getattr(cfg, "brownout_kv_hi", 0.92)),
            ttft_hi_s=float(getattr(cfg, "brownout_ttft_hi_s", 0.0)),
            clear_frac=float(getattr(cfg, "brownout_clear_frac", 0.5)),
            hold_s=float(getattr(cfg, "brownout_hold_s", 0.5)),
            max_new_tokens_cap=int(
                getattr(cfg, "brownout_max_new_tokens", 8)
            ),
        )
        # ISSUE 18: worst-recent TTFT/e2e observations with their
        # trace_id, exposed as /metrics exemplars. Per-INSTANCE (not
        # module-global): in-proc fleets share one process, and a
        # shared store would cross-pollute replicas' exemplars.
        self.exemplars = ExemplarStore()
        self._active: dict[int, _InFlight] = {}
        # Chunked prefills in flight (ISSUE 12): slot -> (item, engine
        # ChunkedPrefill state). One chunk runs per decode-loop
        # iteration (oldest first), so a long cold prompt's prefill
        # interleaves with decode steps instead of monopolizing them.
        # Single-writer: the loop thread.
        self._prefilling: dict[int, tuple] = {}
        # Requests the loop has dequeued but not yet admitted into
        # _active (mid-prefill). close(drain=True)'s poll must count
        # them or a drain landing in that window truncates an accepted
        # request. Single-writer (the loop thread); int reads are
        # atomic under the GIL.
        self._staged = 0
        self._draining = False
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._start_unix = time.time()
        self._watchdog = watchdog
        if watchdog is None and cfg.watchdog_secs > 0:
            from tensorflow_examples_tpu.utils.diagnostics import Watchdog

            self._watchdog = Watchdog(
                cfg.watchdog_secs,
                fatal_timeout_s=4 * cfg.watchdog_secs,
            )

    # ------------------------------------------------------------ intake

    def submit(self, req: Request) -> concurrent.futures.Future:
        """Enqueue; resolves to :class:`Result`. Raises
        :class:`Draining`/:class:`QueueFull` instead of queueing when
        the request can never be served promptly, and fails the future
        fast on admission-impossible requests."""
        reg = self.registry
        reg.counter("serving/requests_total").inc()
        if self._draining or self._stop.is_set():
            reg.counter("serving/rejected_total").inc()
            raise Draining("serving is draining; retry against a live host")
        if req.slo not in SLO_CLASSES:
            fut = concurrent.futures.Future()
            fut.set_exception(ValueError(
                f"unknown slo class {req.slo!r}; one of {SLO_CLASSES}"
            ))
            reg.counter("serving/rejected_total").inc()
            return fut
        if self._overload.sheds(req.slo):
            # Brownout shed (ISSUE 13): the ladder sheds batch at level
            # 1 and interactive only at level 4 — a 503 NOW, before the
            # queue, so degradation lands on the class that can absorb
            # it.
            reg.counter("serving/shed_total").inc()
            reg.counter(f"serving/shed_{req.slo}_total").inc()
            reg.counter("serving/brownout_shed_total").inc()
            raise QueueFull(
                f"brownout level {self._overload.level}: shedding "
                f"{req.slo} traffic; retry later"
            )
        fut: concurrent.futures.Future = concurrent.futures.Future()
        item = _InFlight(req, fut, time.monotonic())
        budget = len(req.prompt) + (
            req.max_new_tokens
            if req.kind in ("generate", "resume") else 0
        )
        if req.kind not in ("generate", "classify", "prefill", "resume"):
            fut.set_exception(ValueError(f"unknown kind {req.kind!r}"))
            reg.counter("serving/rejected_total").inc()
            return fut
        if req.logprobs and (
            req.kind != "generate" or self._draft is not None
        ):
            # A verify step commits several tokens from one fetch of
            # tokens alone, and a resumed stream's first token was
            # sampled elsewhere: neither has a log-probability to give.
            fut.set_exception(ValueError(
                "'logprobs' is served on /generate without speculative "
                "decoding only"
            ))
            reg.counter("serving/rejected_total").inc()
            return fut
        if not req.prompt or budget > self.engine.model_cfg.max_len:
            fut.set_exception(
                ValueError(
                    f"prompt ({len(req.prompt)}) + max_new_tokens must fit "
                    f"1..max_len={self.engine.model_cfg.max_len}"
                )
            )
            reg.counter("serving/rejected_total").inc()
            return fut
        vocab = self.engine.model_cfg.vocab_size
        if any(t < 0 or t >= vocab for t in req.prompt):
            # jit-side gathers clamp out-of-range ids, which would
            # silently generate from a DIFFERENT prompt — reject here.
            fut.set_exception(
                ValueError(f"prompt token ids must be in [0, {vocab})")
            )
            reg.counter("serving/rejected_total").inc()
            return fut
        if req.kind == "resume":
            if not isinstance(req.pages, dict):
                fut.set_exception(ValueError(
                    "resume requires the prefill replica's 'pages' "
                    "payload"
                ))
                reg.counter("serving/rejected_total").inc()
                return fut
            ft = req.first_token
            if not isinstance(ft, int) or isinstance(ft, bool) \
                    or not 0 <= ft < vocab:
                fut.set_exception(ValueError(
                    f"resume 'first_token' must be a token id in "
                    f"[0, {vocab})"
                ))
                reg.counter("serving/rejected_total").inc()
                return fut
        q = self._queues[req.slo]
        try:
            q.put_nowait(item)
        except queue.Full:
            reg.counter("serving/shed_total").inc()
            reg.counter(f"serving/shed_{req.slo}_total").inc()
            raise QueueFull(
                f"{req.slo} request queue at capacity ({q.maxsize}); "
                "load shed"
            ) from None
        self._arrival.set()
        if self._draining or self._stop.is_set():
            # Raced close(): its queue sweep may already have passed,
            # leaving this item unresolved in a dead batcher (the caller
            # would block its full request timeout instead of getting an
            # instant 503). Pull it back out if the loop hasn't taken
            # it; whoever dequeued it first resolves the future.
            with q.mutex:
                try:
                    q.queue.remove(item)
                    removed = True
                except ValueError:
                    removed = False
            if removed:
                reg.counter("serving/rejected_total").inc()
                raise Draining(
                    "serving is draining; retry against a live host"
                )
        reg.gauge("serving/queue_depth").set(self.queue_depth())
        return fut

    def queue_depth(self) -> int:
        """Total queued requests across SLO classes (the load signal
        the frontend's /health and the brownout controller read)."""
        return sum(q.qsize() for q in self._queues.values())

    @property
    def brownout_level(self) -> int:
        return self._overload.level

    # --------------------------------------------------------- lifecycle

    def start(self) -> "ContinuousBatcher":
        if self._watchdog is not None:
            self._watchdog.start()
        self._thread = threading.Thread(
            target=self._loop, name="serving-batcher", daemon=True
        )
        self._thread.start()
        return self

    def close(self, *, drain: bool = True, timeout: float = 60.0) -> None:
        """Stop accepting, optionally finish everything already
        accepted (queued + in flight), then stop the loop thread."""
        self._draining = True
        if drain:
            deadline = time.monotonic() + timeout

            def busy():
                return bool(
                    self._active or self._staged or self._prefilling
                    or self.queue_depth()
                )

            while (
                time.monotonic() < deadline
                and self._thread is not None
                and self._thread.is_alive()
            ):
                if not busy():
                    # A request dequeued this instant may not have
                    # bumped _staged yet; confirm emptiness after a
                    # tick before declaring the drain complete.
                    time.sleep(0.01)
                    if not busy():
                        break
                time.sleep(0.005)
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
        if self._watchdog is not None:
            self._watchdog.stop()
        # Anything still unresolved (drain=False, or the drain timed
        # out) is failed/retired now — callers must never block forever.
        self._fail_pending(Draining("serving shut down before drain"))

    @property
    def draining(self) -> bool:
        return self._draining

    def _fail_pending(self, exc: Exception) -> None:
        for q in self._queues.values():
            while True:
                try:
                    item = q.get_nowait()
                except queue.Empty:
                    break
                item.future.set_exception(exc)
        for item, _ in list(self._prefilling.values()):
            self._prefilling.pop(item.slot, None)
            self._retire(item, truncated="shutdown")
        for item in list(self._active.values()):
            self._retire(item, truncated="shutdown")

    # -------------------------------------------------------------- loop

    def _wd(self, phase: str) -> None:
        if self._watchdog is not None:
            self._watchdog.enter(phase)

    def _loop(self) -> None:
        reg = self.registry
        decode_steps = 0
        while not self._stop.is_set():
            # One busy iteration on the profiler's clock (ISSUE 25):
            # serve_admission -> [serve_admit ...] -> [serve_prefill_chunk]
            # -> serve_decode_step {engine_decode_build/upload/dispatch/
            # fetch} -> serve_commit, with nothing between the spans but
            # this loop's own tests.
            with span("serve_admission"):
                # Brownout tick (ISSUE 13): one controller evaluation
                # per loop iteration — queue depth + KV occupancy here,
                # the controller's own recent-TTFT window inside. Cheap
                # host math; the ladder's hysteresis does the rate
                # limiting.
                self._overload.update(
                    queue_depth=self.queue_depth(),
                    kv_occupancy=float(self.engine.pool.occupancy),
                )
                # Interactive preempts batch for decode slots (ISSUE
                # 13): free slots for waiting interactive requests
                # BEFORE this iteration's admission, so the preempted
                # batch slots are immediately reusable.
                self._preempt_for_interactive()
                staged = self._gather()
            if staged:
                self._wd("serve_prefill")
                for item in staged:
                    try:
                        # The request's whole admission (ISSUE 36): its
                        # claim, its prefill or extend, its bookkeeping.
                        with span(schema.SERVE_ADMIT_SPAN,
                                  tokens=len(item.req.prompt)):
                            self._admit(item)
                    except Exception as e:  # noqa: BLE001 — one bad
                        # request must not take the serve loop down
                        log.exception("prefill failed; failing request")
                        if item.slot is not None:
                            self.engine.pool.free(item.slot)
                            self._drop_draft(item.slot)
                            item.slot = None
                        if not item.future.done():
                            item.future.set_exception(e)
                        reg.counter("serving/errors_total").inc()
                        if isinstance(e, EngineStepError):
                            # The failed step consumed the donated KV
                            # caches — every in-flight request's state
                            # is gone with them.
                            self._fail_active(e)
                    finally:
                        self._staged -= 1
            if self._prefilling:
                # ONE chunk per loop iteration (oldest admission
                # first): the decode step below runs between chunks,
                # which is the whole TTFT-vs-TPOT admission bargain.
                self._wd("serve_prefill")
                # The whole turn, the final chunk's _finish_prefill too.
                with span("serve_prefill_chunk"):
                    self._chunk_step()
            if not self._active:
                continue
            self._wd("serve_decode")
            t0 = time.perf_counter()
            drafts_by_slot: dict[int, int] = {}
            try:
                with span("serve_decode_step", active=len(self._active)):
                    out = self._decode_step(drafts_by_slot)
            except BlockExhausted as e:
                # Host-side exhaustion BEFORE the device step: no
                # donated state was lost, so only the named slots (the
                # requests that needed a new block) fail — loudly —
                # and the engine keeps serving the rest. Freeing them
                # returns their blocks, so the survivors' next growth
                # usually succeeds.
                log.warning(
                    "KV block exhaustion: failing %d of %d active "
                    "request(s): %s", len(e.slots), len(self._active), e,
                )
                reg.counter("serving/errors_total").inc()
                for slot in e.slots:
                    item = self._active.pop(slot, None)
                    if item is None:
                        continue
                    self.engine.pool.free(slot)
                    self._drop_draft(slot)
                    if not item.future.done():
                        item.future.set_exception(e)
                continue
            except Exception as e:  # noqa: BLE001 — fail the batch,
                # keep serving: the next admissions start clean
                log.exception("decode step failed; failing active batch")
                reg.counter("serving/errors_total").inc()
                self._fail_active(e)
                continue
            dt = time.perf_counter() - t0
            with span("serve_commit"):
                decode_steps += 1
                if self._watchdog is not None:
                    self._watchdog.ping(decode_steps)
                self._commit(out, drafts_by_slot, dt)

    def _commit(self, out: dict, drafts_by_slot: dict[int, int],
                dt: float) -> None:
        """Book one decode step's tokens (``out``: {slot: committed
        token list}) against their requests: append, record the
        per-token latencies, finish what is done. ``dt`` is the step's
        wall time on the host, ``drafts_by_slot`` non-empty on a verify
        step. Loop-thread only, under ``span/serve_commit``."""
        reg = self.registry
        tpot = reg.histogram("serving/tpot")
        reg.histogram("serving/decode_step").record(dt)
        for slot, toks in out.items():
            item = self._active[slot]
            cls_tpot = reg.histogram(
                f"serving/tpot_{item.req.slo}"
            )
            item.spec_drafted += drafts_by_slot.get(slot, 0)
            item.spec_accepted += len(toks) - 1
            per_tok = dt / len(toks)
            committed: list[int] = []
            if item.req.logprobs:  # a plain step: one token a slot
                item.logprobs.append(float(self.engine.last_logprobs[slot]))
            for token in toks:
                item.tokens.append(token)
                item.last_token = token
                committed.append(token)
                tpot.record(per_tok)
                cls_tpot.record(per_tok)
                if item.req.eos_id is not None \
                        and token == item.req.eos_id:
                    # Tokens past eos in the same verify window are
                    # discarded — identical to the non-speculative
                    # stream, which stops here.
                    break
            if self._draft is not None:
                if drafts_by_slot:  # a verify step, not a fallback
                    # The ENGINE-committed count (pre-eos-discard),
                    # so the histogram and the spec_* counters
                    # measure the same thing.
                    reg.histogram(
                        "serving/accepted_per_step"
                    ).record(float(len(toks)))
                self._draft.extend(slot, committed)
            self._maybe_finish(item)
        reg.gauge("serving/active_requests").set(len(self._active))

    def _decode_step(self, drafts_by_slot: dict[int, int]):
        """One device step over the active set; returns {slot:
        committed token list}. Speculation on: propose per-request
        drafts (capped at the request's remaining budget minus the one
        token the verify itself samples) and run the verify_k rung; a
        step where NO request has a draft falls back to the plain
        one-token decode rung — same tokens, (k+1)x less compute.
        Brownout level 3+ (ISSUE 13) forces that fallback every step:
        speculation's extra verify compute is the cheapest thing to
        drop under pressure, and dropping it never changes tokens."""
        if self._draft is None or self._overload.spec_disabled():
            out = self.engine.decode([
                (
                    it.slot, it.last_token, it.req.seed,
                    it.req.temperature, it.req.top_k,
                )
                for it in self._active.values()
            ])
            return {slot: [tok] for slot, tok in out.items()}
        entries = []
        proposed: dict[int, int] = {}
        for it in self._active.values():
            remaining = it.req.max_new_tokens - len(it.tokens)
            k_eff = min(self.spec_k, remaining - 1)
            k_eff = min(k_eff, it.max_new_eff - len(it.tokens) - 1)
            drafts = (
                self._draft.propose(it.slot, k_eff) if k_eff > 0 else []
            )
            proposed[it.slot] = len(drafts)
            entries.append((
                it.slot, it.last_token, drafts, it.req.seed,
                it.req.temperature, it.req.top_k,
            ))
        if not any(e[2] for e in entries):
            # drafts_by_slot stays empty: this is a plain decode step,
            # and the accepted_per_step histogram (like the spec_*
            # counters) measures VERIFY steps only.
            out = self.engine.decode([
                (slot, tok, seed, temp, tk)
                for slot, tok, _, seed, temp, tk in entries
            ])
            return {slot: [tok] for slot, tok in out.items()}
        drafts_by_slot.update(proposed)
        return self.engine.verify(entries)

    def _gather(self) -> list[_InFlight]:
        """Pull admissible requests without over-committing slots. Idle:
        block briefly for the first arrival, then hold a
        ``max_delay_s`` window so a burst prefills together. Busy:
        drain whatever is queued into the free slots, no waiting."""
        free = min(
            self.max_batch - len(self._active) - len(self._prefilling),
            self.engine.pool.num_slots - self.engine.pool.active_slots,
        )
        staged: list[_InFlight] = []
        if not self._active and not self._prefilling:
            self._wd("serve_idle")
            try:
                self._take(staged, timeout=0.05)
            except queue.Empty:
                return staged
            window_end = time.monotonic() + self.max_delay_s
            while len(staged) < free:
                remaining = window_end - time.monotonic()
                if remaining <= 0:
                    break
                try:
                    self._take(staged, timeout=remaining)
                except queue.Empty:
                    break
        else:
            self._wd("serve_admit")
            while len(staged) < free:
                try:
                    self._take(staged)
                except queue.Empty:
                    break
        self.registry.gauge("serving/queue_depth").set(
            self.queue_depth()
        )
        return staged

    def _fail_active(self, exc: Exception) -> None:
        """Fail and free every in-flight request — decoding AND
        mid-chunked-prefill, whose written blocks died with the same
        donated device state (a step error lost or poisoned it; next
        admissions start clean)."""
        for it, _ in list(self._prefilling.values()):
            del self._prefilling[it.slot]
            self.engine.pool.free(it.slot)
            it.slot = None
            if not it.future.done():
                it.future.set_exception(exc)
        for it in list(self._active.values()):
            del self._active[it.slot]
            self.engine.pool.free(it.slot)
            self._drop_draft(it.slot)
            if not it.future.done():
                it.future.set_exception(exc)

    def _drop_draft(self, slot: int | None) -> None:
        if self._draft is not None and slot is not None:
            self._draft.end(slot)

    def _take(self, staged: list, timeout: float | None = None) -> None:
        """Dequeue one request into ``staged`` — INTERACTIVE FIRST
        (ISSUE 13: the class order is the admission order), counted in
        ``_staged`` the moment it leaves a queue so the drain poll
        never sees it in neither place. With a timeout, blocks on the
        arrival event until any class queue has an item."""
        deadline = (
            None if timeout is None else time.monotonic() + timeout
        )
        while True:
            for cls in SLO_CLASSES:
                try:
                    item = self._queues[cls].get_nowait()
                except queue.Empty:
                    continue
                self._staged += 1
                staged.append(item)
                return
            if deadline is None:
                raise queue.Empty
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise queue.Empty
            # Clear-then-recheck closes the missed-wakeup race with
            # submit()'s put-then-set.
            self._arrival.clear()
            if any(not q.empty() for q in self._queues.values()):
                continue
            if not self._arrival.wait(timeout=remaining):
                raise queue.Empty

    # ------------------------------------------------------- preemption

    def _preempt_for_interactive(self) -> None:
        """Interactive preempts batch for decode slots (ISSUE 13):
        when interactive requests are queued and the slots are
        exhausted, evict batch requests — most recently admitted first
        (least sunk work), mid-chunked-prefill before decoding — and
        re-queue them at the back of the batch queue. Replay from the
        prompt is token-identical by the per-request seeding, so a
        preemption costs the batch request latency, never content.
        Loop-thread only."""
        waiting = self._queues["interactive"].qsize()
        if not waiting:
            return
        free = min(
            self.max_batch - len(self._active) - len(self._prefilling),
            self.engine.pool.num_slots - self.engine.pool.active_slots,
        )
        need = waiting - max(free, 0)
        if need <= 0:
            return
        victims: list[_InFlight] = [
            it for it, _ in self._prefilling.values()
            if it.req.slo == "batch"
        ]
        victims += sorted(
            (it for it in self._active.values()
             if it.req.slo == "batch"),
            key=lambda it: it.t_admit or 0.0, reverse=True,
        )
        for item in victims[:need]:
            self._preempt(item)

    def _preempt(self, item: _InFlight) -> None:
        reg = self.registry
        slot = item.slot
        self._prefilling.pop(slot, None)
        self._active.pop(slot, None)
        self.engine.pool.free(slot)
        self._drop_draft(slot)
        if item.spans is not None:
            if item.t_decode0 is not None:
                self._close_decode_segment(item, preempted=True)
            else:
                # Evicted mid-prefill: a point marker keeps the
                # preemption visible (and forced-kept) in the trace.
                item.spans.append(close_span(
                    "preempted", time.monotonic(),
                    tags={"preempted": True, "phase": "prefill"},
                ))
        # Full reset: re-admission replays prefill + decode from the
        # prompt (same tokens by seeding); the original t_submit keeps
        # queue-wait/deadline accounting honest about the total wait.
        item.slot = None
        item.t_admit = None
        item.t_first = None
        item.tokens = []
        item.logprobs = []
        item.last_token = None
        item.spec_drafted = 0
        item.spec_accepted = 0
        item.max_new_eff = item.req.max_new_tokens
        reg.counter("serving/preempted_total").inc()
        try:
            self._queues["batch"].put_nowait(item)
        except queue.Full:
            # The batch queue itself is saturated: the preemption
            # becomes a shed — batch absorbs it, by design.
            reg.counter("serving/shed_total").inc()
            reg.counter("serving/shed_batch_total").inc()
            if not item.future.done():
                item.future.set_exception(QueueFull(
                    "preempted for interactive traffic and the batch "
                    "queue is full; load shed"
                ))

    def _admit(self, item: _InFlight) -> None:
        reg = self.registry
        now = time.monotonic()
        if item.deadline is not None and now > item.deadline:
            reg.counter("serving/expired_total").inc()
            item.future.set_exception(
                DeadlineExceeded(
                    f"deadline ({item.req.deadline_s:.3f}s) passed after "
                    f"{now - item.t_submit:.3f}s in queue"
                )
            )
            return
        slot = self.engine.pool.alloc()
        if slot is None:  # _gather bounds by free slots; belt-and-braces
            reg.counter("serving/shed_total").inc()
            item.future.set_exception(QueueFull("no free KV slot"))
            return
        item.slot = slot
        item.t_admit = now
        reg.histogram("serving/queue_wait").record(now - item.t_submit)
        req = item.req
        reg.histogram(
            f"serving/queue_wait_{req.slo}"
        ).record(now - item.t_submit)
        if item.spans is not None:
            # ISSUE 18: the queue-wait span carries the brownout rung
            # in force AT ADMISSION — a brownout_level tag > 0 is a
            # forced-keep signal for the tail sampler.
            item.spans.append(close_span(
                "queue_wait", item.t_submit,
                tags={"slo": req.slo,
                      "brownout_level": self._overload.level},
            ))
        cap = self._overload.max_new_cap()
        if cap is not None and req.kind in ("generate", "resume"):
            # Brownout level 2 (ISSUE 13): cap the generation budget at
            # admission — the stream retires early with
            # truncated="brownout", still a prefix of the full stream.
            item.max_new_eff = min(req.max_new_tokens, cap)
        if req.kind == "resume":
            # Disaggregated decode (ISSUE 12): no prefill — map the
            # handed-off KV pages in and continue the stream from the
            # prefill replica's first token.
            t_import = time.monotonic()
            self.engine.import_kv_pages(slot, req.pages, req.prompt)
            item.t_first = time.monotonic()
            if item.spans is not None:
                item.spans.append(close_span(
                    "resume_import", t_import,
                    tags={"tokens": len(req.prompt)},
                ))
            ttft = item.t_first - item.t_submit
            reg.histogram("serving/ttft").record(ttft)
            reg.histogram(f"serving/ttft_{req.slo}").record(ttft)
            self._overload.note_ttft(ttft)
            if item.spans is not None:
                self.exemplars.record(
                    "serving/ttft", ttft, req.trace["trace_id"]
                )
                self._start_decode_segment(item)
            item.tokens.append(req.first_token)
            item.last_token = req.first_token
            if self._draft is not None:
                self._draft.begin(
                    slot, list(req.prompt) + [req.first_token]
                )
            self._active[slot] = item
            self._maybe_finish(item)
            return
        open_chunked = getattr(self.engine, "prefill_open", None)
        if callable(open_chunked):
            state = open_chunked(
                slot, req.prompt, seed=req.seed,
                temperature=req.temperature, top_k=req.top_k,
            )
            if state is not None and len(state.spans) == 1:
                # The COLD TAIL fits one chunk (a mostly-cached long
                # prompt): run it inline — the documented chunking
                # semantics key on the cold tail, and queueing this
                # effectively-warm request behind an older 16k chunked
                # prefill would stall its TTFT for nothing.
                t0 = time.perf_counter()
                with span("serve_prefill", tokens=len(req.prompt)):
                    _, first, last_logits = self.engine.prefill_step(
                        state
                    )
                reg.histogram("serving/prefill").record(
                    time.perf_counter() - t0
                )
                self._finish_prefill(item, first, last_logits)
                return
            if state is not None:
                # Chunked admission: the slot's blocks are claimed; the
                # loop runs one chunk per iteration from here on and
                # _finish_prefill fires on the final one.
                self._prefilling[slot] = (item, state)
                return
        t0 = time.perf_counter()
        with span("serve_prefill", tokens=len(req.prompt)):
            first, last_logits = self.engine.prefill(
                slot, req.prompt, seed=req.seed,
                temperature=req.temperature, top_k=req.top_k,
            )
        reg.histogram("serving/prefill").record(time.perf_counter() - t0)
        self._finish_prefill(item, first, last_logits)

    def _start_decode_segment(self, item: _InFlight) -> None:
        """Open a decode segment span (traced requests only): one
        continuous slot residency. Preemption closes it; re-admission
        opens the next — a preempted request's trace shows each
        segment it decoded through."""
        item.t_decode0 = time.monotonic()
        item.decode_seg += 1
        item.decode_tok0 = len(item.tokens)

    def _close_decode_segment(self, item: _InFlight, *,
                              preempted: bool = False) -> None:
        if item.spans is None or item.t_decode0 is None:
            return
        tags = {
            "segment": item.decode_seg,
            "tokens": len(item.tokens) - item.decode_tok0,
        }
        if preempted:
            tags["preempted"] = True
        item.spans.append(
            close_span("decode_segment", item.t_decode0, tags=tags)
        )
        item.t_decode0 = None

    def _finish_prefill(self, item: _InFlight, first: int,
                        last_logits) -> None:
        """Shared tail of single-shot and chunked prefill: record TTFT
        and route the request by kind (classify resolves the logits
        head, prefill exports the KV pages, generate enters the decode
        set)."""
        reg = self.registry
        req, slot = item.req, item.slot
        item.t_first = time.monotonic()
        ttft = item.t_first - item.t_submit
        reg.histogram("serving/ttft").record(ttft)
        reg.histogram(f"serving/ttft_{req.slo}").record(ttft)
        self._overload.note_ttft(ttft)
        if item.spans is not None:
            # Admission-to-first-token: single-shot this is the one
            # prefill dispatch; chunked, it brackets the per-chunk
            # spans (decode steps interleave inside — that is the
            # chunking's point and the span shows it).
            item.spans.append(close_span(
                "prefill", item.t_admit,
                tags={"prompt_tokens": len(req.prompt)},
            ))
            self.exemplars.record(
                "serving/ttft", ttft, req.trace["trace_id"]
            )
        if req.kind == "classify":
            from tensorflow_examples_tpu.serving.engine import top_logprobs

            self.engine.pool.free(slot)
            item.slot = None
            self._resolve(
                item,
                Result(
                    tokens=[], prompt_len=len(req.prompt),
                    top=top_logprobs(last_logits, req.classify_top_n),
                ),
            )
            return
        if req.kind == "prefill":
            # Disaggregated prefill (ISSUE 12): the work product is the
            # slot's KV pages, not a decode stream — export, free, and
            # hand the payload (plus the first sampled token) back for
            # the router to ship to a decode replica.
            pages = self.engine.export_kv_pages(
                slot, req.prompt, skip_tokens=req.skip_tokens
            )
            self.engine.pool.free(slot)
            item.slot = None
            self._resolve(
                item,
                Result(
                    tokens=[first], prompt_len=len(req.prompt),
                    pages=pages,
                ),
            )
            return
        item.tokens.append(first)
        item.last_token = first
        if req.logprobs:
            from tensorflow_examples_tpu.serving.engine import token_logprob

            item.logprobs.append(token_logprob(last_logits, first))
        if item.spans is not None:
            self._start_decode_segment(item)
        if self._draft is not None:
            # The drafter's context: prompt + everything committed.
            self._draft.begin(slot, list(req.prompt) + [first])
        self._active[slot] = item
        self._maybe_finish(item)

    def _chunk_step(self) -> None:
        """Run ONE chunk of the oldest in-flight chunked prefill; on
        the final chunk the request joins the decode set exactly as a
        single-shot admission would (token-identical: the final chunk's
        sampling key is the unchunked prefill's). Interactive chunked
        prefills take the turn before batch ones (ISSUE 13) — the
        chunk turn is a decode-slot-adjacent resource, and the class
        order is the service order."""
        reg = self.registry
        slot = next(
            (s for s, (it, _) in self._prefilling.items()
             if it.req.slo == "interactive"),
            next(iter(self._prefilling)),
        )
        item, state = self._prefilling[slot]
        if item.deadline is not None and time.monotonic() > item.deadline:
            # A dead-on-arrival stream must not keep stalling everyone
            # else's decode steps for its remaining chunks — abandon it
            # now, exactly like the queued-deadline expiry (504).
            del self._prefilling[slot]
            self.engine.pool.free(slot)
            item.slot = None
            reg.counter("serving/expired_total").inc()
            if not item.future.done():
                item.future.set_exception(DeadlineExceeded(
                    f"deadline ({item.req.deadline_s:.3f}s) passed "
                    "mid-chunked-prefill"
                ))
            return
        t_chunk = time.monotonic()
        try:
            done, first, last_logits = self.engine.prefill_step(state)
        except Exception as e:  # noqa: BLE001 — one bad chunk must not
            # take the serve loop down
            log.exception("prefill chunk failed; failing request")
            self._prefilling.pop(slot, None)
            self.engine.pool.free(slot)
            item.slot = None
            if not item.future.done():
                item.future.set_exception(e)
            reg.counter("serving/errors_total").inc()
            if isinstance(e, EngineStepError):
                self._fail_active(e)
            return
        if item.spans is not None:
            item.spans.append(close_span(
                "prefill_chunk", t_chunk, tags={"chunk": state.idx}
            ))
        if not done:
            return
        del self._prefilling[slot]
        # Chunked prefill wall = admission to final chunk (decode steps
        # interleave inside it — that is the point, and what an
        # operator reading serving/prefill for a chunked request should
        # see).
        reg.histogram("serving/prefill").record(
            time.monotonic() - item.t_admit
        )
        self._finish_prefill(item, first, last_logits)

    # ----------------------------------------------------------- retire

    def _maybe_finish(self, item: _InFlight) -> None:
        req, truncated = item.req, None
        done = (
            len(item.tokens) >= req.max_new_tokens
            or (req.eos_id is not None and item.last_token == req.eos_id)
        )
        if not done and len(item.tokens) >= item.max_new_eff:
            # Brownout level-2 cap (ISSUE 13): retire early with what
            # we have — a prefix of the full stream, labeled so the
            # client knows the fleet cheapened it, not the model.
            done, truncated = True, "brownout"
            self.registry.counter(
                "serving/brownout_truncated_total"
            ).inc()
        if not done and item.deadline is not None \
                and time.monotonic() > item.deadline:
            done, truncated = True, "deadline"
        if not done and item.slot is not None and (
            len(req.prompt) + len(item.tokens)
            >= self.engine.model_cfg.max_len
        ):
            done, truncated = True, "max_len"  # admission makes this rare
        if done:
            self._retire(item, truncated=truncated)

    def _retire(self, item: _InFlight, *, truncated: str | None) -> None:
        if item.slot is not None and item.slot in self._active:
            del self._active[item.slot]
        if item.slot is not None:
            self.engine.pool.free(item.slot)
            self._drop_draft(item.slot)
        self._close_decode_segment(item)
        self._resolve(
            item,
            Result(
                tokens=item.tokens,
                logprobs=item.logprobs if item.req.logprobs else None,
                prompt_len=len(item.req.prompt),
                truncated=truncated,
                spec_drafted=item.spec_drafted,
                spec_accepted=item.spec_accepted,
            ),
        )

    def _resolve(self, item: _InFlight, result: Result) -> None:
        now = time.monotonic()
        result.queue_wait_s = (
            (item.t_admit or now) - item.t_submit
        )
        result.ttft_s = (
            item.t_first - item.t_submit if item.t_first else None
        )
        result.total_s = now - item.t_submit
        reg = self.registry
        reg.histogram("serving/e2e").record(result.total_s)
        reg.histogram(
            f"serving/e2e_{item.req.slo}"
        ).record(result.total_s)
        reg.counter("serving/completed_total").inc()
        # Handoff accounting: the DELIVERING replica owns the whole
        # stream (resume counts the first token too), the prefill leg
        # counts zero — so fleet-summed generated_tokens stays exact
        # whether a handoff completes or falls back to the full path
        # after a successful prefill leg.
        generated = 0 if item.req.kind == "prefill" else len(
            result.tokens
        )
        reg.counter("serving/generated_tokens_total").inc(generated)
        if item.spans is not None:
            result.spans = item.spans
            self.exemplars.record(
                "serving/e2e", result.total_s,
                item.req.trace["trace_id"],
            )
        if not item.future.set_running_or_notify_cancel():
            return  # caller gave up; nothing to deliver
        item.future.set_result(result)

    # ------------------------------------------------------------- stats

    def stats_line(self) -> dict:
        """A schema-v6 ``kind="serving"`` JSONL line: the serving
        counterpart of the training window line (validated in tier-1;
        the frontend serves the latest one at ``/window`` and
        examples/gpt2/serve.py appends them to ``serving.jsonl``).
        The pool (serving/paged_kv.py) adds its block/prefix-cache
        fields to the ``serving`` object — the v6 additions."""
        reg = self.registry
        counters = {
            k: v for k, v in reg.counter_values().items()
            if k.startswith(("serving/", "compile/"))
        }
        gauges = {
            k: v for k, v in reg.gauge_values().items()
            if k.startswith("serving/")
        }
        hists = reg.histogram_summaries()
        derived = {}
        for name in ("queue_wait", "prefill", "ttft", "tpot", "e2e"):
            h = hists.get(f"serving/{name}")
            if h and h["count"]:
                derived[f"{name}_p50"] = h["p50"]
                derived[f"{name}_p95"] = h["p95"]
        serving = {
            # Chunk-prefilling requests count as active: they hold a
            # slot and stall one chunk per loop iteration.
            "active_requests": len(self._active) + len(self._prefilling),
            "queue_depth": self.queue_depth(),
            "slots": self.engine.pool.num_slots,
            "kv_occupancy": self.engine.pool.occupancy,
            "post_warmup_recompiles": (
                self.engine.post_warmup_recompiles()
            ),
            "draining": 1 if self._draining else 0,
        }
        if self.spec_k > 0:
            # Schema-v8 speculation keys (ISSUE 11): how many tokens a
            # verify step commits and how often drafts land — the
            # measured numbers behind any TPOT-speedup claim.
            steps = counters.get("serving/spec_request_steps", 0)
            drafted = counters.get("serving/spec_drafted_total", 0)
            accepted = counters.get("serving/spec_accepted_total", 0)
            serving["spec_k"] = self.spec_k
            serving["draft_hit_rate"] = (
                accepted / drafted if drafted else 0.0
            )
            serving["accepted_per_step"] = (
                (steps + accepted) / steps if steps else 0.0
            )
        # Schema-v10 overload keys (ISSUE 13): the SLO-class split and
        # the brownout ladder's state — the per-class latency story an
        # operator reads to see WHO is paying for an overload.
        for cls in SLO_CLASSES:
            for name in ("queue_wait", "ttft", "tpot"):
                h = hists.get(f"serving/{name}_{cls}")
                if h and h["count"]:
                    serving[f"{name}_p95_{cls}"] = h["p95"]
        serving["shed_interactive"] = int(
            counters.get("serving/shed_interactive_total", 0)
        )
        serving["shed_batch"] = int(
            counters.get("serving/shed_batch_total", 0)
        )
        serving["preempted_batch"] = int(
            counters.get("serving/preempted_total", 0)
        )
        serving["brownout_level"] = int(self._overload.level)
        serving["brownout_transitions"] = int(
            self._overload.transitions()
        )
        serving.update(self.engine.pool.paged_stats())
        # Schema-v11 precision keys (ISSUE 15): what precision this
        # replica is actually serving at and what it costs vs f32 —
        # stamped only when the engine holds quantized weights (an
        # unquantized line carries none, like every earlier bump).
        pstats = getattr(self.engine, "precision_stats", None)
        pstats = pstats() if callable(pstats) else None
        if pstats:
            serving["weight_bits"] = pstats["weight_bits"]
            serving["param_bytes"] = pstats["param_bytes"]
            serving["param_bytes_f32"] = pstats["param_bytes_f32"]
            serving["quantized_params"] = pstats["quantized_params"]
        return {
            "schema_version": schema.SERVING_SCHEMA_VERSION,
            "kind": "serving",
            "step": int(
                counters.get("serving/decode_steps", 0)
            ),
            "time_unix": time.time(),
            "session_start_unix": self._start_unix,
            "host": 0,
            "metrics": {},
            "counters": counters,
            "gauges": gauges,
            "derived": derived,
            "serving": serving,
        }


def default_registry():  # convenience re-export for the frontend/tools
    return registry_mod.default_registry()
