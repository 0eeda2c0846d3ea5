"""Self-describing JSONL metrics schema (ISSUE 2 CI satellite; v2 in
ISSUE 3; v3 in ISSUE 4; v4 in ISSUE 5; v5 in ISSUE 7; v6 in ISSUE 8 —
paged-KV block/prefix-cache fields and router-tier fields on the
``serving`` object, see ``SERVING_KEYS_V6``; v7 in ISSUE 10 —
fault-tolerance counters on the router's ``serving`` object, see
``SERVING_KEYS_V7``; v8 in ISSUE 11 — speculative-decoding measurement
keys on the batcher's ``serving`` object, see ``SERVING_KEYS_V8``; v9
in ISSUE 12 — the prefix-cache summary behind cache-aware fleet
scheduling, see ``SERVING_KEYS_V9``; v10 in ISSUE 13 — SLO-class
admission, brownout, and digest-truncation observability, see
``SERVING_KEYS_V10``; v11 in ISSUE 15 — the weight-quantization
story behind int8/fp8 end-to-end serving, see ``SERVING_KEYS_V11``).

Every line the JSONL sink emits carries ``schema_version`` so offline
consumers (tools/telemetry_report.py, tools/bench_gate.py, future
BENCH_* harvesters) can evolve without guessing. ``validate_line`` is
the single source of truth for what a line must look like — the tier-1
test validates every emitted line through it, and the report CLI
refuses lines it cannot validate rather than mis-aggregating them.

Hand-rolled (no jsonschema dependency — the image is pip-install-free);
the structure is small enough that explicit checks read better anyway.

Line shape (version 3; version-1/-2 lines remain valid input)::

    {
      "schema_version": 3,
      "kind": "window" | "eval" | "final" | "memory" | "compile_warning"
              | "fleet",
      "step": <int >= 0>,            # loop step the line was emitted at
      "time_unix": <float>,          # wall clock at emission
      "session_start_unix": <float>, # constant per fit-session: the
                                     #   boundary marker for resumed runs
      "metrics": {"train/loss": 1.2, ...},      # window means
      "counters": {"data/batches_fetched": 10, ...},  # cumulative
                                     #   WITHIN the session (fit deltas)
      "gauges": {...},                          # instantaneous values
      "derived": {"examples_per_sec": ..., "step_time_p50": ...,
                  "mfu": ..., "goodput": ...},  # may hold nulls
      "exit_reason": "preempt" | ...  # kind == "final" only

      # --- version 2 additions (telemetry/memory.py, compilation.py,
      #     profiling.py) ---
      "memory": {"live_bytes": ..., "peak_live_bytes": ...,
                 "params_bytes": ..., ...},  # numeric|null; REQUIRED on
                                     #   kind == "memory" (the init
                                     #   breakdown snapshot), optional
                                     #   on window/final lines
      "compile": {"fn": "train_step", "delta": "...axis 0: 64->32...",
                  "count": 2, "wall_secs": 0.4},  # REQUIRED on (and
                                     #   exclusive to) compile_warning
      "profile": {"dir": "...", "start_step": 10, "num_steps": 10,
                  "wall_secs": 1.2}  # final lines only: cross-link to
                                     #   the in-loop profiler window

      # --- version 3 additions (telemetry/fleet.py) ---
      "host": 0,                     # REQUIRED on every v3 line: the
                                     #   jax.process_index() that wrote it
      "fleet": {                     # REQUIRED on (and exclusive to)
                                     #   kind == "fleet" lines
        "hosts": [{"host": 0, "step_time_p50": 0.01,
                   "step_time_p95": 0.02, "data_fetch_p95": 0.001,
                   "steps_lost": 0, "peak_live_bytes": 1024,
                   "data_work_p95": 0.001}, ...],  # data_work_p95:
                                     #   additive (ISSUE 6), optional
                                     #   on read
        "slowest_host": 1,           # int|null: p95 argmax
        "skew": 3.2,                 # slowest p95 / fleet median p95
        "side": "input",             # "compute"|"input"|null: where the
                                     #   straggler's excess time sits
        "straggler": true,           # skew crossed straggler_skew_factor
        "emergency": true            # optional: cached snapshot from the
                                     #   watchdog-fatal path (no collective)
      }

      # --- version 4 additions (serving/batcher.py stats lines) ---
      "serving": {                   # REQUIRED on (and exclusive to)
                                     #   kind == "serving" lines; all
                                     #   numeric
        "active_requests": 3, "queue_depth": 0, "slots": 8,
        "kv_occupancy": 0.375, "post_warmup_recompiles": 0,
        "draining": 0
      }

      # --- version 5 additions (sharding/; train/loop.py) ---
      "sharding": {                  # OPTIONAL, kind == "final" only:
                                     #   placement provenance
        "mesh_shape": {"data": 2, "model": 4, ...},  # axis -> size
        "param_sharding_digest": "1f2e3d...",  # sharding/resolve.py
                                     #   digest: mesh-shape independent,
                                     #   rule-table sensitive
        "zero1": false               # optional bool
      }
    }

Version-1/-2 lines (the pre-ISSUE-3/-4 streams) carry none of the later
fields and only their own kinds; they still validate, so old run dirs
keep reporting.
"""

from __future__ import annotations

import numbers
from typing import Any

# Version 5 (ISSUE 7): additive — training lines may carry a
# "sharding" object on kind="final" (mesh shape + param-sharding
# digest). SCHEMA_VERSION is what the trainer hub stamps.
SCHEMA_VERSION = 5

# Version 6 (ISSUE 8): additive — the serving object may carry
# paged-KV fields (block_size / blocks_total / blocks_used /
# kv_block_occupancy / kv_slot_occupancy / prefix_hits /
# prefix_misses / prefix_hit_rate / kv_bits) and router-tier fields
# (replicas / router_dispatched / router_retries / router_no_replica),
# all numeric. serving/batcher.py and serving/router.py stamp
# SERVING_SCHEMA_VERSION on their ``kind="serving"`` stats lines (a
# v3-shaped line plus the required "serving" object introduced in v4:
# active_requests / queue_depth / slots / kv_occupancy /
# post_warmup_recompiles / draining).
#
# Version 7 (ISSUE 10): additive — the router's serving object may
# carry the fault-tolerance counters (router_ejections /
# router_readmits / router_hedges / router_failovers /
# router_restarts), all numeric; forbidden on v4-v6 serving lines.
#
# Version 8 (ISSUE 11): additive — a speculative-decoding serving line
# may carry spec_k (the configured draft window), draft_hit_rate
# (accepted drafts / offered drafts) and accepted_per_step (mean
# committed tokens per request verify step), all numeric; forbidden on
# v4-v7 serving lines, same mislabeling rule as every earlier bump.
#
# Version 9 (ISSUE 12): additive — a cache-aware serving line may
# carry prefix_blocks (published prefix-cache blocks; the affinity
# digest's size) and prefix_chains (distinct chain heads), both
# numeric. The batcher stamps a paged replica's own counts; the router
# stamps the probe-summed fleet totals. Forbidden on v4-v8 serving
# lines, same mislabeling rule as every earlier bump.
#
# Version 10 (ISSUE 13): additive — an overload-aware serving line may
# carry the SLO-class split (per-class queue-wait/TTFT/TPOT p95s and
# shed counters, batch preemptions), the brownout controller's state
# (brownout_level / brownout_transitions), and the paged pool's
# digest_truncated flag (0/1 — the affinity digest hit its cap, so
# affinity misses on very large caches are diagnosable). The batcher
# stamps its own numbers; the router stamps the fleet view (max
# brownout level, summed transitions). Forbidden on v4-v9 serving
# lines, same mislabeling rule as every earlier bump.
#
# Version 11 (ISSUE 15): additive — a weight-quantized serving line
# may carry the precision registry's facts (weight_bits /
# param_bytes / param_bytes_f32 / quantized_params — what precision
# the replica is ACTUALLY serving at, and what it costs in HBM
# versus f32). All numeric; optional on write (an unquantized line
# carries none), FORBIDDEN on v4-v10 serving lines, same mislabeling
# rule as every earlier bump.
#
# Version 12 (ISSUE 16): additive — a control-plane-resilient serving
# line may carry the router journal/takeover facts (journal_appends /
# takeover_total / resumed_streams / dedup_hits — counters — and
# takeover_latency_s, the last promotion's detect-to-serving wall
# time). Stamped by the router only; FORBIDDEN on v4-v11 serving
# lines, same mislabeling rule as every earlier bump.
#
# Version 13 (ISSUE 18): a new line KIND — ``kind="trace"`` carries one
# completed per-request trace tree (top-level "trace" object:
# trace_id, SLO class, final status, client-visible e2e seconds, the
# tail-sampler's keep_reason, and the span list — each span a
# span_id/name/start_unix/dur_s record with optional parent_id and
# tags). Written by telemetry/tracing.py with the PR-2 sink discipline
# (one line per trace, flushed per append, torn-tail-tolerant read).
# Both the kind and the object are FORBIDDEN on v4-v12 lines. The
# serving object gains the trace-accounting keys (traces_kept /
# traces_dropped / trace_coverage / slow_trace_count — stamped by the
# router only), FORBIDDEN on v4-v12 serving lines, same mislabeling
# rule as every earlier bump.
#
# Version 14 (ISSUE 19): a new line KIND — ``kind="alert"`` carries one
# SLO alert transition (top-level "alert" object: rule name, SLO
# class, state — firing or resolved — severity, the burn rate and
# error budget remaining at transition time, and optionally the
# offending replica, the observed value vs objective, and the
# worst-offender exemplar ``trace_id`` that joins the alert to its
# ISSUE-18 trace). Written by telemetry/slo.py with the PR-2 sink
# discipline. Both the kind and the object are FORBIDDEN on v4-v13
# lines. The serving object gains the alerting summary keys
# (alerts_firing / error_budget_remaining / probe_success_rate /
# alert_count — stamped by the router only), FORBIDDEN on v4-v13
# serving lines, same mislabeling rule as every earlier bump.
SERVING_SCHEMA_VERSION = 14

SUPPORTED_VERSIONS = (1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14)

KINDS_V1 = ("window", "eval", "final")
KINDS_V2 = KINDS_V1 + ("memory", "compile_warning")
KINDS_V3 = KINDS_V2 + ("fleet",)
KINDS_V12 = KINDS_V3 + ("serving",)
KINDS_V13 = KINDS_V12 + ("trace",)
KINDS = KINDS_V13 + ("alert",)

_REQUIRED = ("schema_version", "kind", "step", "time_unix",
             "session_start_unix", "metrics", "counters", "gauges",
             "derived")

# v2-only top-level objects: forbidden on v1 lines (a "v1" line carrying
# them is a mislabeled v2 line — flag it instead of half-validating).
_V2_FIELDS = ("memory", "compile", "profile")

# v3-only top-level fields, same rule for v1/v2 lines.
_V3_FIELDS = ("host", "fleet")

# v4-only top-level objects, same rule for v1/v2/v3 lines.
_V4_FIELDS = ("serving",)

# v5-only top-level objects, forbidden on earlier versions.
_V5_FIELDS = ("sharding",)

# v13-only top-level objects, forbidden on earlier versions (a line
# carrying a trace tree without the v13 stamp is mislabeled).
_V13_FIELDS = ("trace",)

# v14-only top-level objects, same mislabeling rule.
_V14_FIELDS = ("alert",)

# Required keys of a v5 sharding object (writer: train/loop.py via
# telemetry/hub.py sharding_info).
SHARDING_KEYS = ("mesh_shape", "param_sharding_digest")

# Required keys of a v4 serving object (the writer is
# serving/batcher.py stats_line; every one is numeric).
SERVING_KEYS = ("active_requests", "queue_depth", "slots",
                "kv_occupancy", "post_warmup_recompiles", "draining")

# v6-only serving-object keys (optional on write — a dense-pool line
# carries none of the paged fields, a single-engine line none of the
# router fields — but FORBIDDEN on v4/v5 serving lines: a "v4" line
# carrying them is a mislabeled v6 line, same rule as every earlier
# version bump's top-level objects).
SERVING_KEYS_V6 = ("block_size", "blocks_total", "blocks_used",
                   "kv_block_occupancy", "kv_slot_occupancy",
                   "prefix_hits", "prefix_misses", "prefix_hit_rate",
                   "kv_bits", "replicas", "router_dispatched",
                   "router_retries", "router_no_replica")

# v7-only serving-object keys (ISSUE 10): the router's fault-tolerance
# counters — circuit-breaker ejections/readmits, hedged dispatches,
# in-flight failovers, and supervisor restart cycles. Optional on
# write (a single-engine line carries none), FORBIDDEN on v4-v6
# serving lines, same mislabeling rule as every earlier bump.
SERVING_KEYS_V7 = ("router_ejections", "router_readmits",
                   "router_hedges", "router_failovers",
                   "router_restarts")

# v8-only serving-object keys (ISSUE 11): the speculative-decoding
# measurement trio the batcher stamps when spec_decode_k > 0. Optional
# on write (a non-speculative line carries none), FORBIDDEN on v4-v7
# serving lines.
SERVING_KEYS_V8 = ("accepted_per_step", "draft_hit_rate", "spec_k")

# v9-only serving-object keys (ISSUE 12): the prefix-cache summary
# behind cache-aware fleet scheduling — published blocks (the affinity
# digest's size) and distinct chain heads. Optional on write (a
# dense-pool line carries neither), FORBIDDEN on v4-v8 serving lines.
SERVING_KEYS_V9 = ("prefix_blocks", "prefix_chains")

# v10-only serving-object keys (ISSUE 13): the overload story — the
# SLO-class split (interactive vs batch latency p95s, per-class shed
# counters, batch preemptions), the brownout ladder's state, and the
# paged pool's digest-truncation flag. All numeric; optional on write
# (a pre-overload line carries none), FORBIDDEN on v4-v9 serving
# lines, same mislabeling rule as every earlier bump.
SERVING_KEYS_V10 = (
    "queue_wait_p95_interactive", "queue_wait_p95_batch",
    "ttft_p95_interactive", "ttft_p95_batch",
    "tpot_p95_interactive", "tpot_p95_batch",
    "shed_interactive", "shed_batch", "preempted_batch",
    "brownout_level", "brownout_transitions", "digest_truncated",
)

# v11-only serving-object keys (ISSUE 15): the precision registry's
# serving facts — weight payload bits, param bytes as stored vs what
# the same tree costs at f32, and the quantized-leaf count. Stamped by
# the batcher only when the engine serves quantized weights; FORBIDDEN
# on v4-v10 serving lines.
SERVING_KEYS_V11 = ("weight_bits", "param_bytes", "param_bytes_f32",
                    "quantized_params")

# v12-only serving-object keys (ISSUE 16): the control-plane
# resilience story — durable-journal appends, standby promotions and
# the last takeover's detect-to-serving latency, client streams
# resumed mid-generation, and idempotent-retry dedupe hits. All
# numeric; optional on write (a journal-less router carries none),
# FORBIDDEN on v4-v11 serving lines, same mislabeling rule as every
# earlier bump.
SERVING_KEYS_V12 = ("journal_appends", "takeover_total",
                    "resumed_streams", "dedup_hits",
                    "takeover_latency_s")

# v13-only serving-object keys (ISSUE 18): the router's per-request
# tracing accounting — traces the tail sampler kept vs dropped, the
# kept fraction, and how many kept traces were slow for their SLO
# class. All numeric; stamped by the router only (a replica line
# carries none), FORBIDDEN on v4-v12 serving lines, same mislabeling
# rule as every earlier bump.
SERVING_KEYS_V13 = ("traces_kept", "traces_dropped", "trace_coverage",
                    "slow_trace_count")

# v14-only serving-object keys (ISSUE 19): the SLO engine's summary —
# alerts currently firing, the worst rule's error budget remaining
# (fraction, 1.0 = untouched), the synthetic canary prober's rolling
# success rate, and the cumulative firing-transition count. All
# numeric; stamped by the router only (a replica line carries none),
# FORBIDDEN on v4-v13 serving lines, same mislabeling rule as every
# earlier bump.
SERVING_KEYS_V14 = ("alerts_firing", "error_budget_remaining",
                    "probe_success_rate", "alert_count")

# Required keys of a v13 trace object (writer: telemetry/tracing.py
# TraceRecorder.finish) and of each entry in its "spans" list.
TRACE_KEYS = ("trace_id", "slo", "status", "e2e_s", "keep_reason",
              "spans")
TRACE_SPAN_KEYS = ("span_id", "name", "start_unix", "dur_s")

# Required keys of a v14 alert object (writer: telemetry/slo.py
# AlertEngine). Optional extras — "replica" (string), "value" /
# "threshold" / "window_s" (numbers), "trace_id" (the worst-offender
# exemplar, string) — are typed-checked when present.
ALERT_KEYS = ("name", "slo", "state", "severity", "burn_rate",
              "budget_remaining", "since_unix")
ALERT_STATES = ("firing", "resolved")

# Instrument namespaces of the serving tier whose counter/gauge/
# histogram registrations the graftlint drift pass cross-checks
# against the docs catalog (ISSUE 15 satellite: the pass LEARNS this
# list from here — adding a namespace is a schema-module edit, not a
# lint-pass edit).
INSTRUMENT_PREFIXES = ("serving/", "router/", "autoscaler/",
                       "precision/", "trace/", "alert/", "probe/")

# Instruments of a model with experts and a pool of several layer kinds
# (ISSUE 28; writers: serving/blocks.py `count_stats`, serving/engine.py
# `decode`, serving/paged_kv.py; catalog: docs/observability.md). Counters unless
# noted; `serving/moe_pairs_expert_<id>` is one counter per held expert.
MOE_KV_KIND_INSTRUMENTS = (
    "serving/moe_pairs_routed", "serving/moe_pairs_held",
    "serving/moe_decode_pairs_held", "serving/moe_decode_experts_hit",
    "serving/kv_window_blocks_released_total",
    "serving/kv_sampled_bytes", "serving/kv_sampled_tokens",
    "serving/kv_sampled_reach_bytes",
    "serving/kv_blocks_in_use_full",    # gauge
    "serving/kv_blocks_in_use_window",  # gauge
)
MOE_EXPERT_COUNTER_PREFIX = "serving/moe_pairs_expert_"

# The instrument of a block that caches a latent row (ISSUE 32; writer:
# serving/blocks.py `Glm4MoeLiteBlock.count_stats`; catalog:
# docs/observability.md): the query tokens that went through its
# (absorbed) latent attention, counted in the program and fetched with
# the tokens. Such a block books the MOE_KV_KIND_INSTRUMENTS expert and
# `kv_sampled_*` counters too, the latter right for its row and for
# blocks shared between slots. `span/mla_plan` (args: family, queries,
# context, dtype, form, head_group, rows — the widths of the row's
# arrays as stored) is recorded once per traced shape.
LATENT_ATTENTION_TOKENS = "serving/latent_attn_absorbed_tokens"
MLA_PLAN_SPAN = "mla_plan"
MLA_PLAN_ARGS = (
    "family", "queries", "context", "dtype", "form", "head_group", "rows",
)

# Instruments of a pool whose kinds keep rows of their own shape (ISSUE
# 34; writer: serving/engine.py `decode`; catalog: docs/observability.md),
# sampled at every decode step beside `kv_sampled_bytes` /
# `kv_sampled_tokens`: `serving/kv_sampled_bytes_kind_<kind>` — the
# pool's bytes in use by kind (`full`, `window<W>`:
# `PagedKVPool.kind_name`; a pool of several kinds books them, and they
# add up to `kv_sampled_bytes`) — and `serving/decode_gathered_tokens`
# (the step's rung K x its live slots: the token rows the full kind's
# gather touches, against the `kv_sampled_tokens` resident). `span/kind_plan`
# (args: family, rung, kinds — per kind its window, KV heads, K and V
# row widths as stored, sink or none, physical blocks and the columns
# of its table in the program) is recorded once per traced program of
# such a pool.
KV_KIND_BYTES_COUNTER_PREFIX = "serving/kv_sampled_bytes_kind_"
DECODE_GATHERED_TOKENS = "serving/decode_gathered_tokens"
KIND_PLAN_SPAN = "kind_plan"
KIND_PLAN_ARGS = ("family", "rung", "kinds")
KIND_PLAN_KIND_KEYS = (
    "window", "kv_heads", "k_row", "v_row", "sink", "blocks", "table_blocks",
)

# Instruments of the extend family's context ladder (ISSUE 35; writer:
# serving/engine.py `_extend_launch`; catalog: docs/observability.md),
# booked at every extend launch, a chunk of a chunked prefill and a
# prefix hit's tail alike: `serving/extend_gathered_tokens` — the
# launch's context rung, the cached token rows the full kind's gather
# touches — beside `serving/extend_context_tokens`, the cached tokens
# the launch reads (its `ctx`).
EXTEND_GATHERED_TOKENS = "serving/extend_gathered_tokens"
EXTEND_CONTEXT_TOKENS = "serving/extend_context_tokens"

# The per-host entry of a fleet line's "hosts" list: "host" is a
# required int, and each of these is required numeric-or-null (the
# writer side, fleet.VECTOR_KEYS, aliases FLEET_VECTOR_KEYS below — the
# allgathered vector and the validated line cannot drift apart).
# io_retries and batches_skipped are each host's OWN pre-reduction
# numbers — the line-level counters carry the fleet sums, so these
# entries are the only place a flaky host's IO churn stays localizable.
FLEET_HOST_KEYS = ("step_time_p50", "step_time_p95", "data_fetch_p95",
                   "steps_lost", "peak_live_bytes", "io_retries",
                   "batches_skipped")

# Additive (optional-on-read) host keys: written by every current fleet
# line but NOT required by the validator, so v3 lines from runs that
# predate them keep validating. data_work_p95 (ISSUE 6) is host time
# actually spent PRODUCING batches (the ``data_work`` span) — the
# straggler input-side verdict reads it instead of data_fetch_p95,
# which also counts queue back-pressure wait and would misreport a
# fast host blocked on the device as input-bound. Values present in a
# hosts entry are still numeric-or-null checked.
FLEET_HOST_KEYS_OPTIONAL = ("data_work_p95",)

# The full allgathered per-host vector, in wire order.
FLEET_VECTOR_KEYS = FLEET_HOST_KEYS + FLEET_HOST_KEYS_OPTIONAL


def _is_number(v: Any) -> bool:
    return isinstance(v, numbers.Real) and not isinstance(v, bool)


def _check_numeric_map(obj: dict, section: str, problems: list[str]) -> None:
    sec = obj.get(section)
    if not isinstance(sec, dict):
        problems.append(f"{section} is not an object")
        return
    for k, v in sec.items():
        if not isinstance(k, str):
            problems.append(f"{section} key {k!r} is not a string")
        # NaN/Inf pass through json.dumps as bare tokens; numeric or
        # null is the contract (a NaN loss window is still a number).
        if v is not None and not _is_number(v):
            problems.append(f"{section}[{k!r}] = {v!r} is not numeric")


def validate_line(obj: Any) -> list[str]:
    """Return the list of schema violations (empty = valid)."""
    if not isinstance(obj, dict):
        return [f"line is {type(obj).__name__}, not an object"]
    problems: list[str] = []
    for key in _REQUIRED:
        if key not in obj:
            problems.append(f"missing required field {key!r}")
    if problems:
        return problems
    version = obj["schema_version"]
    if version not in SUPPORTED_VERSIONS:
        problems.append(
            f"schema_version {version!r} not in {SUPPORTED_VERSIONS}"
        )
        return problems
    kinds = {1: KINDS_V1, 2: KINDS_V2, 3: KINDS_V3}.get(
        version,
        KINDS_V12 if version < 13
        else (KINDS_V13 if version < 14 else KINDS),
    )
    if obj["kind"] not in kinds:
        problems.append(f"kind {obj['kind']!r} not in {kinds}")
    if not isinstance(obj["step"], int) or isinstance(obj["step"], bool) \
            or obj["step"] < 0:
        problems.append(f"step {obj['step']!r} is not a non-negative int")
    for key in ("time_unix", "session_start_unix"):
        if not _is_number(obj[key]):
            problems.append(f"{key} {obj[key]!r} is not a number")
    for section in ("metrics", "gauges"):
        _check_numeric_map(obj, section, problems)
    counters = obj["counters"]
    if not isinstance(counters, dict):
        problems.append("counters is not an object")
    else:
        for k, v in counters.items():
            if not isinstance(v, int) or isinstance(v, bool) or v < 0:
                problems.append(
                    f"counters[{k!r}] = {v!r} is not a non-negative int"
                )
    derived = obj["derived"]
    if not isinstance(derived, dict):
        problems.append("derived is not an object")
    else:
        for k, v in derived.items():
            if v is not None and not _is_number(v):
                problems.append(f"derived[{k!r}] = {v!r} is not numeric")
    if obj["kind"] == "final" and not isinstance(
        obj.get("exit_reason"), str
    ):
        problems.append("final line is missing a string exit_reason")
    if obj["kind"] != "final" and "exit_reason" in obj:
        problems.append("exit_reason on a non-final line")

    if version == 1:
        for fields, v in ((_V2_FIELDS, 2), (_V3_FIELDS, 3),
                          (_V4_FIELDS, 4), (_V5_FIELDS, 5),
                          (_V13_FIELDS, 13), (_V14_FIELDS, 14)):
            for key in fields:
                if key in obj:
                    problems.append(
                        f"v{v} field {key!r} on a schema-v1 line"
                    )
        return problems

    # ------------------------------------------------- v2 additions
    if "memory" in obj:
        _check_numeric_map(obj, "memory", problems)
    if obj["kind"] == "memory" and "memory" not in obj:
        problems.append("memory line is missing the memory object")

    if obj["kind"] == "compile_warning":
        comp = obj.get("compile")
        if not isinstance(comp, dict):
            problems.append(
                "compile_warning line is missing the compile object"
            )
        else:
            for key in ("fn", "delta"):
                if not isinstance(comp.get(key), str):
                    problems.append(
                        f"compile[{key!r}] = {comp.get(key)!r} is not a "
                        "string"
                    )
            if "count" in comp and (
                not isinstance(comp["count"], int)
                or isinstance(comp["count"], bool)
                or comp["count"] < 0
            ):
                problems.append(
                    f"compile['count'] = {comp['count']!r} is not a "
                    "non-negative int"
                )
            if "wall_secs" in comp and not _is_number(comp["wall_secs"]):
                problems.append(
                    f"compile['wall_secs'] = {comp['wall_secs']!r} is not "
                    "a number"
                )
    elif "compile" in obj:
        problems.append("compile object on a non-compile_warning line")

    if "profile" in obj:
        if obj["kind"] != "final":
            problems.append("profile object on a non-final line")
        elif not isinstance(obj["profile"], dict):
            problems.append("profile is not an object")
        else:
            prof = obj["profile"]
            if not isinstance(prof.get("dir"), str):
                problems.append("profile['dir'] is not a string")
            for key in ("start_step", "num_steps"):
                v = prof.get(key)
                if not isinstance(v, int) or isinstance(v, bool) or v < 0:
                    problems.append(
                        f"profile[{key!r}] = {v!r} is not a non-negative "
                        "int"
                    )

    if version == 2:
        for fields, v in ((_V3_FIELDS, 3), (_V4_FIELDS, 4),
                          (_V5_FIELDS, 5), (_V13_FIELDS, 13),
                          (_V14_FIELDS, 14)):
            for key in fields:
                if key in obj:
                    problems.append(
                        f"v{v} field {key!r} on a schema-v2 line"
                    )
        return problems

    # ------------------------------------------------- v3 additions
    host = obj.get("host")
    if not isinstance(host, int) or isinstance(host, bool) or host < 0:
        problems.append(f"host {host!r} is not a non-negative int")

    if obj["kind"] == "fleet":
        fleet = obj.get("fleet")
        if not isinstance(fleet, dict):
            problems.append("fleet line is missing the fleet object")
        else:
            hosts = fleet.get("hosts")
            if not isinstance(hosts, list) or not hosts:
                problems.append(
                    f"fleet['hosts'] = {hosts!r} is not a non-empty list"
                )
            else:
                for i, entry in enumerate(hosts):
                    if not isinstance(entry, dict):
                        problems.append(
                            f"fleet['hosts'][{i}] is not an object"
                        )
                        continue
                    h = entry.get("host")
                    if not isinstance(h, int) or isinstance(h, bool) \
                            or h < 0:
                        problems.append(
                            f"fleet['hosts'][{i}]['host'] = {h!r} is not "
                            "a non-negative int"
                        )
                    for key in FLEET_HOST_KEYS:
                        if key not in entry:
                            problems.append(
                                f"fleet['hosts'][{i}] is missing {key!r}"
                            )
                    for k, v in entry.items():
                        if k != "host" and v is not None \
                                and not _is_number(v):
                            problems.append(
                                f"fleet['hosts'][{i}][{k!r}] = {v!r} is "
                                "not numeric"
                            )
            slowest = fleet.get("slowest_host")
            if slowest is not None and (
                not isinstance(slowest, int) or isinstance(slowest, bool)
                or slowest < 0
            ):
                problems.append(
                    f"fleet['slowest_host'] = {slowest!r} is not a "
                    "non-negative int or null"
                )
            skew = fleet.get("skew")
            if skew is not None and not _is_number(skew):
                problems.append(
                    f"fleet['skew'] = {skew!r} is not numeric or null"
                )
            side = fleet.get("side")
            if side not in (None, "compute", "input"):
                problems.append(
                    f"fleet['side'] = {side!r} is not 'compute'/'input'/"
                    "null"
                )
            if not isinstance(fleet.get("straggler", False), bool):
                problems.append(
                    f"fleet['straggler'] = {fleet['straggler']!r} is not "
                    "a bool"
                )
    elif "fleet" in obj:
        problems.append("fleet object on a non-fleet line")

    if version == 3:
        if "serving" in obj:
            problems.append("v4 field 'serving' on a schema-v3 line")
        if "sharding" in obj:
            problems.append("v5 field 'sharding' on a schema-v3 line")
        if "trace" in obj:
            problems.append("v13 field 'trace' on a schema-v3 line")
        if "alert" in obj:
            problems.append("v14 field 'alert' on a schema-v3 line")
        return problems

    # ------------------------------------------------- v4 additions
    if obj["kind"] == "serving":
        if not isinstance(obj.get("serving"), dict):
            problems.append("serving line is missing the serving object")
        else:
            _check_numeric_map(obj, "serving", problems)
            for key in SERVING_KEYS:
                if key not in obj["serving"]:
                    problems.append(
                        f"serving object is missing required key {key!r}"
                    )
            if version < 6:
                for key in SERVING_KEYS_V6:
                    if key in obj["serving"]:
                        problems.append(
                            f"v6 serving key {key!r} on a schema-v"
                            f"{version} line"
                        )
            if version < 7:
                for key in SERVING_KEYS_V7:
                    if key in obj["serving"]:
                        problems.append(
                            f"v7 serving key {key!r} on a schema-v"
                            f"{version} line"
                        )
            if version < 8:
                for key in SERVING_KEYS_V8:
                    if key in obj["serving"]:
                        problems.append(
                            f"v8 serving key {key!r} on a schema-v"
                            f"{version} line"
                        )
            if version < 9:
                for key in SERVING_KEYS_V9:
                    if key in obj["serving"]:
                        problems.append(
                            f"v9 serving key {key!r} on a schema-v"
                            f"{version} line"
                        )
            if version < 10:
                for key in SERVING_KEYS_V10:
                    if key in obj["serving"]:
                        problems.append(
                            f"v10 serving key {key!r} on a schema-v"
                            f"{version} line"
                        )
            if version < 11:
                for key in SERVING_KEYS_V11:
                    if key in obj["serving"]:
                        problems.append(
                            f"v11 serving key {key!r} on a schema-v"
                            f"{version} line"
                        )
            if version < 12:
                for key in SERVING_KEYS_V12:
                    if key in obj["serving"]:
                        problems.append(
                            f"v12 serving key {key!r} on a schema-v"
                            f"{version} line"
                        )
            if version < 13:
                for key in SERVING_KEYS_V13:
                    if key in obj["serving"]:
                        problems.append(
                            f"v13 serving key {key!r} on a schema-v"
                            f"{version} line"
                        )
            if version < 14:
                for key in SERVING_KEYS_V14:
                    if key in obj["serving"]:
                        problems.append(
                            f"v14 serving key {key!r} on a schema-v"
                            f"{version} line"
                        )
    elif "serving" in obj:
        problems.append("serving object on a non-serving line")

    # ------------------------------------------------ v13 trace lines
    if obj["kind"] == "trace":
        trace = obj.get("trace")
        if not isinstance(trace, dict):
            problems.append("trace line is missing the trace object")
        else:
            for key in TRACE_KEYS:
                if key not in trace:
                    problems.append(
                        f"trace object is missing required key {key!r}"
                    )
            for key in ("trace_id", "slo", "keep_reason"):
                v = trace.get(key)
                if key in trace and not isinstance(v, str):
                    problems.append(
                        f"trace[{key!r}] = {v!r} is not a string"
                    )
            status = trace.get("status")
            if "status" in trace and (
                not isinstance(status, int) or isinstance(status, bool)
            ):
                problems.append(
                    f"trace['status'] = {status!r} is not an int"
                )
            if "e2e_s" in trace and not _is_number(trace["e2e_s"]):
                problems.append(
                    f"trace['e2e_s'] = {trace['e2e_s']!r} is not a number"
                )
            spans = trace.get("spans")
            if "spans" in trace and not isinstance(spans, list):
                problems.append(
                    f"trace['spans'] = {spans!r} is not a list"
                )
            for i, sp in enumerate(spans if isinstance(spans, list)
                                   else ()):
                if not isinstance(sp, dict):
                    problems.append(f"trace['spans'][{i}] is not an object")
                    continue
                for key in TRACE_SPAN_KEYS:
                    if key not in sp:
                        problems.append(
                            f"trace['spans'][{i}] is missing {key!r}"
                        )
                for key in ("span_id", "name"):
                    if key in sp and not isinstance(sp[key], str):
                        problems.append(
                            f"trace['spans'][{i}][{key!r}] = "
                            f"{sp[key]!r} is not a string"
                        )
                for key in ("start_unix", "dur_s"):
                    if key in sp and not _is_number(sp[key]):
                        problems.append(
                            f"trace['spans'][{i}][{key!r}] = "
                            f"{sp[key]!r} is not a number"
                        )
                parent = sp.get("parent_id")
                if parent is not None and not isinstance(parent, str):
                    problems.append(
                        f"trace['spans'][{i}]['parent_id'] = {parent!r} "
                        "is not a string or null"
                    )
                tags = sp.get("tags")
                if tags is not None and not isinstance(tags, dict):
                    problems.append(
                        f"trace['spans'][{i}]['tags'] = {tags!r} is not "
                        "an object"
                    )
    elif "trace" in obj:
        problems.append("trace object on a non-trace line")

    # ------------------------------------------------ v14 alert lines
    if obj["kind"] == "alert":
        alert = obj.get("alert")
        if not isinstance(alert, dict):
            problems.append("alert line is missing the alert object")
        else:
            for key in ALERT_KEYS:
                if key not in alert:
                    problems.append(
                        f"alert object is missing required key {key!r}"
                    )
            for key in ("name", "slo", "severity"):
                v = alert.get(key)
                if key in alert and not isinstance(v, str):
                    problems.append(
                        f"alert[{key!r}] = {v!r} is not a string"
                    )
            state = alert.get("state")
            if "state" in alert and state not in ALERT_STATES:
                problems.append(
                    f"alert['state'] = {state!r} not in {ALERT_STATES}"
                )
            for key in ("burn_rate", "budget_remaining", "since_unix",
                        "value", "threshold", "window_s"):
                if key in alert and not _is_number(alert[key]):
                    problems.append(
                        f"alert[{key!r}] = {alert[key]!r} is not a number"
                    )
            for key in ("replica", "trace_id"):
                v = alert.get(key)
                if v is not None and not isinstance(v, str):
                    problems.append(
                        f"alert[{key!r}] = {v!r} is not a string or null"
                    )
    elif "alert" in obj:
        problems.append("alert object on a non-alert line")

    if version == 4:
        if "sharding" in obj:
            problems.append("v5 field 'sharding' on a schema-v4 line")
        return problems

    # ------------------------------------------------- v5 additions
    if "sharding" in obj:
        if obj["kind"] != "final":
            problems.append("sharding object on a non-final line")
        elif not isinstance(obj["sharding"], dict):
            problems.append("sharding is not an object")
        else:
            sh = obj["sharding"]
            for key in SHARDING_KEYS:
                if key not in sh:
                    problems.append(
                        f"sharding object is missing required key {key!r}"
                    )
            mesh = sh.get("mesh_shape")
            if mesh is not None:
                if not isinstance(mesh, dict) or not mesh:
                    problems.append(
                        "sharding['mesh_shape'] is not a non-empty object"
                    )
                else:
                    for axis, size in mesh.items():
                        if (
                            not isinstance(axis, str)
                            or not isinstance(size, int)
                            or isinstance(size, bool)
                            or size < 1
                        ):
                            problems.append(
                                f"sharding['mesh_shape'][{axis!r}] = "
                                f"{size!r} is not a positive int"
                            )
            digest = sh.get("param_sharding_digest")
            if digest is not None and not isinstance(digest, str):
                problems.append(
                    f"sharding['param_sharding_digest'] = {digest!r} is "
                    "not a string"
                )
            if "zero1" in sh and not isinstance(sh["zero1"], bool):
                problems.append(
                    f"sharding['zero1'] = {sh['zero1']!r} is not a bool"
                )
    return problems


def validate(obj: Any) -> None:
    """Raise ValueError listing every violation (empty = returns None)."""
    problems = validate_line(obj)
    if problems:
        raise ValueError(
            "telemetry line violates schema v%d:\n  %s"
            % (SCHEMA_VERSION, "\n  ".join(problems))
        )
