"""Inference serving engine (ISSUE 5 tentpole).

The training half of the repo can fit, survive faults, and observe
itself; this package opens the inference half: load a trained
checkpoint and serve concurrent generate/classify requests at
TPU-friendly static shapes.

Layout (one module per concern, mirroring the training stack):

* ``kv_cache.py``  — the bucket ladders, the gather of a slot's view
  out of the pool by its block table, and the variable-length decode
  and verify attentions that read it (the per-slot generalization of
  ``ops/decode.flash_decode_attention``'s populated-prefix contract).
* ``paged_kv.py``  — ISSUE 8: the KV pool, the only one — slots with
  per-slot length tracking over block-granular storage, a free-list
  block allocator with loud exhaustion, a prefix cache reusing
  immutable full prompt blocks (shared system prompts prefill once),
  optional int8/fp8 KV with per-row scales.
* ``router.py``    — ISSUE 8/10: the fleet tier — an HTTP router over
  N engine replicas with load-aware dispatch from ``/health`` probes,
  drain-aware rollout, per-replica circuit breakers, bounded
  retry-with-backoff, optional hedged dispatch, in-flight failover on
  replica death, and canary per-set records for ``tools/run_diff.py``.
* ``supervisor.py`` — ISSUE 10: replica supervision — detect a dead or
  stuck replica, restart it (process- or in-proc), re-admit to the
  router only after ``/health`` goes green.
* ``chaos.py``     — ISSUE 10: the serving chaos harness — restartable
  in-proc replicas the fault engine (``utils/faults.py`` serve specs)
  can crash/slow/starve deterministically, assembled as a
  :class:`~.chaos.ChaosFleet` (replicas + hardened router +
  supervisor) for the chaos acceptance tier and ``serve_bench
  --chaos``.
* ``engine.py``    — the compiled serving step: bucketed prefill and
  extend + fixed-shape continuous decode, warmed up ahead of traffic
  over the padding-bucket ladder and wrapped in the PR-3 recompilation sentinel
  so steady-state serving is provably zero-recompile. ISSUE 11 adds
  the speculative ``verify_k`` rungs (score k draft tokens in one
  forward, commit the longest agreeing prefix, token-identical by
  per-position sampling keys) and the ``attention="paged_flash"``
  fused Pallas paged-decode kernel (``ops/paged_decode.py``).
* ``scheduler.py``  — ISSUE 12: cache-aware fleet scheduling
  primitives — content-addressed prefix chain keys (the affinity hash
  the router matches prompts against replica digests with), the
  block-aligned chunk planner behind chunked prefill admission, and
  the serialized KV-page wire format of the disaggregated
  prefill->decode handoff.
* ``speculative.py`` — ISSUE 11: the draft side of speculative
  decoding — the self-speculative n-gram ``DraftSource`` (a small
  draft model plugs into the same interface) and the deterministic
  acceptance rule.
* ``batcher.py``   — the continuous-batching request queue: admission
  control, max-batch/max-delay coalescing, per-request deadlines,
  bounded-queue backpressure with a load-shed counter, futures back to
  callers; with speculation on, the decode step becomes draft-propose/
  verify-commit with per-request acceptance accounting.
* ``frontend.py``  — stdlib HTTP endpoints (``/generate`` ``/classify``
  ``/metrics`` ``/health`` ``/window``) + SIGTERM drain with
  resilience-layer parity (reuses ``train.resilience.PreemptionGuard``).

``tools/serve_bench.py`` drives the whole stack closed-loop and banks a
BENCH-style JSON record; ``docs/serving.md`` is the operator guide.
"""

from tensorflow_examples_tpu.serving.batcher import (  # noqa: F401
    ContinuousBatcher,
    DeadlineExceeded,
    Draining,
    QueueFull,
    Request,
)
from tensorflow_examples_tpu.serving.engine import (  # noqa: F401
    InferenceEngine,
    ServeConfig,
)
from tensorflow_examples_tpu.serving.frontend import (  # noqa: F401
    ServingFrontend,
    run_until_preempted,
)
from tensorflow_examples_tpu.serving.paged_kv import (  # noqa: F401
    BlockExhausted,
    PagedKVPool,
)
from tensorflow_examples_tpu.serving.router import (  # noqa: F401
    Router,
    RouterConfig,
    RouterFrontend,
)

# supervisor.py / chaos.py are imported lazily by their consumers
# (tools/serve_fleet.py, serve_bench --chaos, tests/test_chaos.py) —
# importing them here would drag the chaos machinery into every
# serving import.
