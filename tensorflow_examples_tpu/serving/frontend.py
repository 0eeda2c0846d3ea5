"""HTTP frontend + SIGTERM drain for the serving stack.

Extends the ISSUE-4 stdlib ``http.server`` pattern
(``telemetry/serve.py``) with the request side: POST endpoints that
feed the continuous batcher and block on its futures, next to the same
observability surface a training process exposes.

Endpoints:

* ``POST /generate`` — body ``{"prompt": [ids], "max_new_tokens": n,
  "temperature": t, "top_k": k, "seed": s, "eos_id": id,
  "deadline_s": d, "slo": "interactive"|"batch", "logprobs": bool}``
  (all but ``prompt`` optional; ``"text"`` may replace ``prompt`` when
  the frontend was built with a tokenizer). ``logprobs`` adds
  ``"logprobs": [...]`` to the reply: the model's own log-probability
  (temperature 1, as ``/classify`` reports) of each generated token,
  from the same device fetch as the token; refused with speculative
  decoding on. ``slo`` is the ISSUE 13 service class:
  batch queues behind interactive and absorbs shedding/preemption
  first.
  Replies ``{"tokens": [...], "prompt_len": n, "truncated": null,
  "queue_wait_s": ..., "ttft_s": ..., "total_s": ...}`` (+ ``"text"``
  with a tokenizer).
* ``POST /classify`` — same request shape (no generation knobs);
  replies the top-n next-token distribution
  ``{"top": [{"token": id, "logprob": lp}, ...]}``.
* ``POST /prefill`` / ``POST /resume`` — the disaggregated-role
  handoff pair (ISSUE 12). ``/prefill`` runs the
  prompt to completion-of-prefill and replies ``{"first_token": id,
  "pages": {...}}`` (``serving/scheduler.py`` wire format, int8 scales
  included); ``/resume`` takes the same generate body plus
  ``pages``/``first_token`` and continues the decode stream —
  token-identical to a mixed replica serving the whole request. The
  router orchestrates the pair; roles are advisory, so every replica
  still answers a full ``/generate`` (that is what makes role failover
  a plain in-flight failover).
* ``GET /metrics`` — the registry as Prometheus text
  (``telemetry.serve.render_prometheus``): the ``serving/*`` counters
  and gauges plus the latency summaries — ``serving_queue_wait``,
  ``serving_prefill``, ``serving_ttft``, ``serving_tpot``,
  ``serving_e2e`` — each with p50/p95/p99 quantiles.
* ``GET /health`` — JSON: draining flag, active/queued requests, KV
  occupancy, post-warmup recompile count, watchdog phase when the
  batcher runs one. 503 once draining (a load balancer stops routing
  here the moment the drain starts).
* ``GET /window`` — the latest schema-v4 ``kind="serving"`` stats line
  (``ContinuousBatcher.stats_line``).
* ``GET /series`` — the in-process time-series store (ISSUE 19):
  ring-buffered history of every instrument, sampled on the stats
  loop's cadence, with p50/p95/p99 rollups per series.

Status mapping (the flow-control contract, outermost first):
``QueueFull``/``Draining`` -> 503 (retry elsewhere/later, body says
which), ``DeadlineExceeded`` -> 504, admission ``ValueError``/bad JSON
-> 400, anything else -> 500 with the exception class named.

**SIGTERM drain** (resilience-layer parity with
``train.resilience.PreemptionGuard``): :func:`run_until_preempted`
installs the guard, serves until SIGTERM/SIGINT, then (1) flips the
batcher to draining — new submits raise ``Draining``, the frontend
returns 503 — (2) waits for every accepted request to finish, (3)
closes the ports, (4) returns exit code 0. A second signal force-quits
through the guard's escalation path, exactly like training.
"""

from __future__ import annotations

import concurrent.futures
import http.server
import json
import logging
import socket
import threading
import time

from tensorflow_examples_tpu.serving.batcher import (
    ContinuousBatcher,
    DeadlineExceeded,
    Draining,
    QueueFull,
    Request,
)
from tensorflow_examples_tpu.serving.paged_kv import BlockExhausted
from tensorflow_examples_tpu.telemetry import timeseries as timeseries_mod
from tensorflow_examples_tpu.telemetry.serve import (
    json_safe,
    render_prometheus,
)
from tensorflow_examples_tpu.utils import faults as faults_mod
# Module-level on purpose: a lazy import inside run_until_preempted would
# leave a multi-second window after "ready" during which SIGTERM still
# hits the default handler (import of the train package is slow) — the
# guard must be installable the instant the caller asks.
from tensorflow_examples_tpu.train.resilience import PreemptionGuard

log = logging.getLogger(__name__)

_MAX_BODY = 1 << 20  # 1 MiB of JSON is already a pathological prompt
# The /resume body carries a whole prompt's serialized KV pages —
# sized for the repo's own worst case, not a guess: gpt2 at fp32 is
# 2 (k+v) * 12 layers * 12 heads * 64 head_dim * 4 B ~= 72 KiB per
# token, so a max_len=1024 prompt serializes to ~75 MiB raw and
# ~100 MiB after base64. A cap below that would 413 exactly the
# long-prompt handoffs disaggregation exists for, silently degrading
# every such request to double-prefill fallback.
_MAX_RESUME_BODY = 256 << 20


class _TrackingHTTPServer(http.server.ThreadingHTTPServer):
    """ThreadingHTTPServer that keeps the set of in-flight client
    connections, so :meth:`ServingFrontend.abort` can RESET them —
    simulating a replica process dying mid-request (clients observe a
    transport failure, never a polite HTTP status). The chaos harness
    (serving/chaos.py) and the ``crash@R:N`` serve fault are the
    consumers; normal shutdown never touches this."""

    # An overloaded replica must SHED (a 503 the class queues decide),
    # never silently drop connections: the stdlib default accept
    # backlog of 5 overflows under a flash crowd's connection burst
    # and turns correct shedding into spurious transport failures
    # (ISSUE 13).
    request_queue_size = 128

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.conn_lock = threading.Lock()
        self.live_connections: set = set()

    def process_request(self, request, client_address):
        with self.conn_lock:
            self.live_connections.add(request)
        super().process_request(request, client_address)

    def shutdown_request(self, request):
        with self.conn_lock:
            self.live_connections.discard(request)
        super().shutdown_request(request)


def _request_from_body(body: dict, *, kind: str, tokenizer=None) -> Request:
    """Validated JSON body -> :class:`Request` (raises ValueError with a
    client-facing message on any malformed field)."""
    if not isinstance(body, dict):
        raise ValueError("body must be a JSON object")
    prompt = body.get("prompt")
    if prompt is None and "text" in body:
        if tokenizer is None:
            raise ValueError(
                "this server has no tokenizer; send token ids as 'prompt'"
            )
        if not isinstance(body["text"], str):
            raise ValueError("'text' must be a string")
        prompt = tokenizer.encode(body["text"])
    if (
        not isinstance(prompt, list)
        or not prompt
        or not all(isinstance(t, int) and not isinstance(t, bool)
                   for t in prompt)
    ):
        raise ValueError("'prompt' must be a non-empty list of token ids")
    known = {
        "prompt", "text", "max_new_tokens", "temperature", "top_k",
        "seed", "eos_id", "deadline_s", "top_n", "slo", "logprobs",
        # ISSUE 16: idempotency / resume markers. The ROUTER consumes
        # these (journal dedupe, replay-and-skip) and strips them
        # before dispatch, but a replica must also tolerate them so a
        # client talking straight to one frontend isn't rejected —
        # accepted and ignored here (a single replica regenerates
        # deterministically anyway).
        "request_id", "resume_from",
        # ISSUE 18: the router's traceparent-style context. A traced
        # request's replica-side spans come back in the reply under
        # "trace_spans"; an untraced body costs nothing.
        "trace",
        # ISSUE 19: the synthetic canary prober's tag. The router
        # strips it before dispatch, but the prober also probes
        # replicas DIRECTLY (per-replica black-box TTFT), so a replica
        # must tolerate it — accepted and ignored here (a replica has
        # no journal or organic-vs-probe accounting to protect).
        "probe",
    }
    if kind == "resume":
        known |= {"pages", "first_token"}
    elif kind == "prefill":
        # Delta handoff (ISSUE 15): the router's digest exchange —
        # leading prompt tokens the resume-side replica already
        # caches, so the export leaves those pages off the wire.
        known |= {"skip_tokens"}
    unknown = set(body) - known
    if unknown:
        raise ValueError(f"unknown fields: {sorted(unknown)}")
    slo = body.get("slo", "interactive")
    if slo not in ("interactive", "batch"):
        raise ValueError(
            "'slo' must be 'interactive' or 'batch'"
        )
    logprobs = body.get("logprobs", False)
    if not isinstance(logprobs, bool):
        raise ValueError("'logprobs' must be true or false")
    pages = first_token = None
    if kind == "resume":
        pages = body.get("pages")
        if not isinstance(pages, dict):
            raise ValueError("'pages' must be the prefill replica's "
                             "page payload object")
        first_token = body.get("first_token")
        if not isinstance(first_token, int) or isinstance(
            first_token, bool
        ):
            raise ValueError("'first_token' must be a token id")

    def number(name, default, cls=float, minimum=None, maximum=None):
        v = body.get(name, default)
        if v is None:
            if default is None:  # nullable fields (eos_id, deadline_s)
                return None
            raise ValueError(f"'{name}' must be a number")
        if isinstance(v, bool) or not isinstance(v, (int, float)):
            raise ValueError(f"'{name}' must be a number")
        if cls is int and isinstance(v, float) and not v.is_integer():
            raise ValueError(f"'{name}' must be an integer")
        v = cls(v)
        if minimum is not None and v < minimum:
            raise ValueError(f"'{name}' must be >= {minimum}")
        if maximum is not None and v > maximum:
            raise ValueError(f"'{name}' must be <= {maximum}")
        return v

    return Request(
        prompt=[int(t) for t in prompt],
        max_new_tokens=number("max_new_tokens", 16, int, 1),
        temperature=number("temperature", 0.0, float, 0.0),
        top_k=number("top_k", 0, int, 0),
        seed=number("seed", 0, int, 0, maximum=2**31 - 1),
        eos_id=number("eos_id", None, int, 0),
        deadline_s=number("deadline_s", None, float, 0.0),
        kind=kind,
        classify_top_n=number("top_n", 5, int, 1),
        logprobs=logprobs,
        pages=pages,
        first_token=first_token,
        skip_tokens=(
            number("skip_tokens", 0, int, 0) if kind == "prefill" else 0
        ),
        slo=slo,
        # Tolerant parse: a malformed context disables tracing for
        # this request, never fails it (same contract as the router's
        # TraceContext.from_wire).
        trace=(
            body["trace"]
            if isinstance(body.get("trace"), dict)
            and isinstance(body["trace"].get("trace_id"), str)
            and body["trace"]["trace_id"]
            else None
        ),
    )


class ServingFrontend:
    """The serving process's HTTP surface. One daemon-threaded
    ``ThreadingHTTPServer``; request handlers block on batcher futures
    (scrape endpoints never do), so a slow generation cannot starve
    ``/metrics``."""

    def __init__(
        self,
        batcher: ContinuousBatcher,
        *,
        port: int = 0,
        bind_host: str = "",
        tokenizer=None,
    ):
        self.batcher = batcher
        self.tokenizer = tokenizer
        self.requested_port = int(port)
        self.bind_host = bind_host
        self.port: int | None = None
        self._httpd: http.server.ThreadingHTTPServer | None = None
        self._thread: threading.Thread | None = None
        self._lock = threading.Lock()
        # In-process time-series store (ISSUE 19), served as
        # GET /series. The frontend owns no cadence of its own — the
        # serving process's stats loop calls ``series.sample()`` on
        # its tick (examples/gpt2/serve.py), exactly like the stats
        # line itself.
        self.series = timeseries_mod.TimeSeriesStore(batcher.registry)

    @property
    def replica_id(self) -> int:
        """This stack's replica index in a fleet (0 standalone) — the
        key the serve fault engine targets (``utils/faults.py``)."""
        return int(getattr(self.batcher.engine, "replica_id", 0))

    # ------------------------------------------------------------ payloads

    def handle_request(self, body: dict, *, kind: str) -> tuple[int, dict]:
        """(status, reply) for one generate/classify body — the HTTP
        handler minus the socket, so tests and the bench can drive the
        full admission/serialization path in-process."""
        try:
            req = _request_from_body(
                body, kind=kind, tokenizer=self.tokenizer
            )
        except ValueError as e:
            return 400, {"error": str(e)}
        try:
            fut = self.batcher.submit(req)
            result = fut.result(
                timeout=self.batcher.engine.cfg.request_timeout_s
            )
        except Draining as e:
            return 503, {"error": str(e), "draining": True}
        except QueueFull as e:
            # "shed": true marks a LOAD shed (queue full / brownout) —
            # what lets serve_bench (ISSUE 13 satellite) count correct
            # shedding apart from transport failures in its records.
            return 503, {"error": str(e), "retry": True, "shed": True}
        except BlockExhausted as e:
            # Paged-KV capacity shed: same retry contract as QueueFull,
            # but "exhausted" marks it apart — a wedged-full pool can
            # shed FOREVER (leaked refcounts, stuck long requests), so
            # the router still counts these against the circuit breaker
            # where a policy shed (queue/brownout, transient by
            # construction) does not.
            return 503, {
                "error": str(e), "retry": True, "shed": True,
                "exhausted": True,
            }
        except DeadlineExceeded as e:
            return 504, {"error": str(e)}
        except ValueError as e:
            return 400, {"error": str(e)}
        except concurrent.futures.TimeoutError:
            return 504, {
                "error": (
                    "request timed out after "
                    f"{self.batcher.engine.cfg.request_timeout_s}s"
                )
            }
        except Exception as e:  # noqa: BLE001 — surface, don't crash
            log.exception("request failed")
            return 500, {"error": f"{type(e).__name__}: {e}"}
        reply: dict = {
            "prompt_len": result.prompt_len,
            "truncated": result.truncated,
            "queue_wait_s": result.queue_wait_s,
            "ttft_s": result.ttft_s,
            "total_s": result.total_s,
        }
        if kind == "classify":
            reply["top"] = result.top
        elif kind == "prefill":
            # Disaggregated handoff (ISSUE 12): the product is the KV
            # pages + the first sampled token, which the router ships
            # to a decode replica's /resume.
            reply["first_token"] = result.tokens[0]
            reply["pages"] = result.pages
        else:
            reply["tokens"] = result.tokens
            if result.logprobs is not None:
                reply["logprobs"] = result.logprobs
            if self.tokenizer is not None:
                reply["text"] = self.tokenizer.decode(result.tokens)
        if result.spans:
            # ISSUE 18: the replica's per-request spans ride the reply
            # — the router (or a direct client) adopts them into the
            # request's trace tree. No shared memory assumed, so
            # in-proc and cross-process fleets stitch identically.
            reply["trace_spans"] = result.spans
        return 200, reply

    def health_payload(self) -> tuple[int, dict]:
        batcher = self.batcher
        engine = batcher.engine
        body = {
            "ok": not batcher.draining,
            "draining": batcher.draining,
            # Mid-chunked-prefill requests ARE active load (each one
            # stalls a chunk per decode-loop iteration) — the router's
            # load score and the affinity guard must see them.
            "active_requests": (
                len(batcher._active) + len(batcher._prefilling)
            ),
            "queue_depth": batcher.queue_depth(),
            "slots": engine.pool.num_slots,
            "kv_occupancy": engine.pool.occupancy,
            "post_warmup_recompiles": engine.post_warmup_recompiles(),
            "warmed": engine.warmed,
        }
        body["role"] = getattr(engine.cfg, "role", "mixed")
        # Brownout state (ISSUE 13): the router's probe and the
        # autoscaler both read the level here — a browning-out replica
        # is visible to the fleet BEFORE it sheds interactive traffic.
        body["brownout_level"] = int(batcher.brownout_level)
        body["brownout_transitions"] = int(
            batcher._overload.transitions()
        )
        stats = engine.pool.paged_stats()
        body["kv_block_occupancy"] = stats["kv_block_occupancy"]
        body["kv_slot_occupancy"] = stats["kv_slot_occupancy"]
        body["prefix_hit_rate"] = stats["prefix_hit_rate"]
        # The affinity summary (ISSUE 12): content chain keys of the
        # cached prefix blocks — what the router's prefix-affinity
        # dispatch matches prompts against.
        d = engine.pool.prefix_digest()
        body["prefix_block_size"] = engine.pool.block_size
        body["prefix_blocks"] = d["blocks"]
        body["prefix_chains"] = d["chains"]
        body["prefix_digest"] = d["keys"]
        # ISSUE 13 satellite: say when the digest is capped, so
        # affinity misses on very large caches are diagnosable.
        body["digest_truncated"] = bool(d.get("truncated"))
        if d.get("bloom"):
            # ISSUE 15 satellite: past the cap the FULL chain-key set
            # still routes — as a bloom filter the router matches
            # against instead of the truncated list.
            body["prefix_bloom"] = d["bloom"]
        wd = batcher._watchdog
        if wd is not None:
            status = wd.status()
            body.update(
                phase=status["phase"],
                phase_age_secs=status["phase_age_secs"],
                stalled_secs=status["stalled_secs"],
            )
        return (200 if body["ok"] else 503), body

    # ----------------------------------------------------------- lifecycle

    def start(self) -> "ServingFrontend":
        server = self

        class Handler(http.server.BaseHTTPRequestHandler):
            def _send(self, status, content_type, payload: bytes):
                self.send_response(status)
                self.send_header("Content-Type", content_type)
                self.send_header("Content-Length", str(len(payload)))
                self.end_headers()
                self.wfile.write(payload)

            def _send_json(self, status, obj):
                self._send(
                    status,
                    "application/json",
                    (json.dumps(json_safe(obj)) + "\n").encode(),
                )

            def do_POST(self):  # noqa: N802 - http.server contract
                path = self.path.split("?", 1)[0].rstrip("/")
                feng = faults_mod.serve_active()
                if feng is not None and feng.transport_fault(
                    server.replica_id
                ):
                    # Injected transport fault (ISSUE 10): drop the
                    # request with no response bytes — the client sees
                    # a reset, exactly like a died-mid-request process.
                    self.close_connection = True
                    return
                if path not in ("/generate", "/classify", "/prefill",
                                "/resume"):
                    self._send_json(
                        404,
                        {"error": "POST endpoints: /generate /classify "
                                  "/prefill /resume"},
                    )
                    return
                max_body = (
                    _MAX_RESUME_BODY if path == "/resume" else _MAX_BODY
                )
                try:
                    try:
                        n = int(self.headers.get("Content-Length", 0))
                    except ValueError:
                        n = -1
                    if n < 0:
                        self._send_json(
                            400, {"error": "bad Content-Length header"}
                        )
                        return
                    if n > max_body:
                        self._send_json(
                            413, {"error": f"body exceeds {max_body} bytes"}
                        )
                        return
                    try:
                        body = json.loads(self.rfile.read(n) or b"{}")
                    except json.JSONDecodeError as e:
                        self._send_json(400, {"error": f"bad JSON: {e}"})
                        return
                    status, reply = server.handle_request(
                        body, kind=path[1:]
                    )
                    self._send_json(status, reply)
                except ConnectionError:  # client went away mid-write
                    pass

            def do_GET(self):  # noqa: N802 - http.server contract
                path = self.path.split("?", 1)[0].rstrip("/") or "/"
                try:
                    if path == "/metrics":
                        self._send(
                            200,
                            "text/plain; version=0.0.4; charset=utf-8",
                            render_prometheus(
                                server.batcher.registry,
                                exemplars=server.batcher.exemplars,
                            ).encode(),
                        )
                    elif path == "/health":
                        feng = faults_mod.serve_active()
                        if feng is not None and feng.health_fault(
                            server.replica_id
                        ):
                            # Injected poisoned /health (ISSUE 10):
                            # non-JSON garbage with a 200 — the probe
                            # loop must mark this replica unhealthy,
                            # never crash.
                            self._send(
                                200, "application/json",
                                b"<<<not json at all>>>",
                            )
                            return
                        self._send_json(*server.health_payload())
                    elif path == "/window":
                        self._send_json(200, server.batcher.stats_line())
                    elif path == "/series":
                        # Ring-buffered instrument history (ISSUE 19),
                        # sampled by the stats loop's tick.
                        self._send_json(
                            200, server.series.to_payload()
                        )
                    else:
                        self._send(
                            404,
                            "text/plain; charset=utf-8",
                            b"GET: /metrics /health /window /series   "
                            b"POST: /generate /classify /prefill "
                            b"/resume\n",
                        )
                except ConnectionError:
                    pass

            def log_message(self, fmt, *args):  # quiet under load
                log.debug("serving frontend: " + fmt, *args)

        self._httpd = _TrackingHTTPServer(
            (self.bind_host, self.requested_port), Handler
        )
        self._httpd.daemon_threads = True
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(
            target=self._httpd.serve_forever,
            name="serving-frontend",
            daemon=True,
        )
        self._thread.start()
        log.info(
            "serving frontend live on port %d "
            "(POST /generate /classify; GET /metrics /health /window)",
            self.port,
        )
        return self

    def url(self, path: str = "/generate") -> str:
        host = self.bind_host or "127.0.0.1"
        return f"http://{host}:{self.port}{path}"

    def close(self) -> None:
        """Idempotent; stops accepting connections (in-flight handler
        threads finish their writes — they hold batcher futures, which
        the drain resolves first)."""
        with self._lock:
            httpd, self._httpd = self._httpd, None
            thread, self._thread = self._thread, None
        if httpd is None:
            return
        httpd.shutdown()
        httpd.server_close()
        if thread is not None and thread is not threading.current_thread():
            thread.join(timeout=5)

    def abort(self) -> None:
        """Die like a killed process (the chaos harness's crash verb):
        stop listening AND reset every in-flight client connection, so
        callers observe a transport failure — never a drained 503 or a
        polite error body. Handler threads are left to hit the dead
        sockets on their own (their writes raise ConnectionError, which
        the handlers already swallow); nothing is joined. Safe from any
        thread, including the batcher loop mid-decode."""
        with self._lock:
            httpd, self._httpd = self._httpd, None
            self._thread = None
        if httpd is None:
            return
        try:
            httpd.shutdown()
            httpd.server_close()
        except OSError:  # pragma: no cover - best-effort teardown
            pass
        with httpd.conn_lock:
            conns = list(httpd.live_connections)
        for conn in conns:
            try:
                conn.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass  # already gone


def run_until_preempted(
    frontend: ServingFrontend,
    *,
    poll_s: float = 0.2,
    drain_timeout_s: float = 60.0,
    guard=None,
) -> int:
    """Serve until SIGTERM/SIGINT, then drain and return 0.

    The serving mirror of the trainer's preemption contract
    (``train.resilience.PreemptionGuard``): first signal starts a clean
    drain — the batcher rejects new work (frontend answers 503), every
    already-accepted request runs to completion, ports close, exit 0 —
    and a second signal force-quits. ``guard`` is injectable for tests
    (anything with ``.install()`` and ``.requested``).
    """
    if guard is None:
        guard = PreemptionGuard()
    guard.install()
    batcher = frontend.batcher
    try:
        while not guard.requested:
            time.sleep(poll_s)
        log.warning(
            "preemption requested: draining %d active + %d queued requests",
            len(batcher._active), batcher.queue_depth(),
        )
        batcher.registry.counter("serving/preemptions").inc()
        batcher.close(drain=True, timeout=drain_timeout_s)
        log.info("drain complete; shutting down frontend")
        return 0
    finally:
        frontend.close()
        if not batcher._stop.is_set():
            batcher.close(drain=False)
        if hasattr(guard, "uninstall"):
            guard.uninstall()
