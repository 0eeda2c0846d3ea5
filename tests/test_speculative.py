"""Speculative decoding (ISSUE 11): drafter units, acceptance rule,
token-identical goldens with speculation ON, accounting, exhaustion
degradation, and the schema-v8 serving keys.

The load-bearing tests are the goldens: mixed greedy AND
temperature-sampled requests through the continuous batcher with
``spec_decode_k > 0`` must come out token-identical to the engine's
unbatched reference replay, at two block sizes. That is
the determinism contract: speculation buys TPOT, it never changes one
token (acceptance consumes the per-request ``fold_in`` key stream per
POSITION, so which rows ship cannot change what any position draws).
"""

import json
import os
import sys

import numpy as np
import pytest

from tensorflow_examples_tpu.models import transformer
from tensorflow_examples_tpu.serving.batcher import (
    ContinuousBatcher,
    Request,
)
from tensorflow_examples_tpu.serving.engine import (
    InferenceEngine,
    ServeConfig,
)
from tensorflow_examples_tpu.serving.speculative import (
    NgramDraft,
    accept_drafts,
    make_draft,
)
from tensorflow_examples_tpu.telemetry import schema
from tensorflow_examples_tpu.telemetry.registry import MetricsRegistry

pytestmark = pytest.mark.serving

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))


def tiny_cfg(**kw):
    import serve_bench  # needs the tools path above

    base = dict(serve_bench.SMOKE_MODEL)
    base.update(kw)
    return transformer.TransformerConfig(**base)


def _tiny_params(cfg):
    import jax
    import jax.numpy as jnp

    model = transformer.Transformer(cfg)
    return model.init(
        {"params": jax.random.PRNGKey(1)}, jnp.zeros((1, 8), jnp.int32)
    )["params"]


def _spec_engine(*, params=None, cfg=None, **serve_kw):
    cfg = cfg or tiny_cfg()
    kw = dict(
        max_slots=4, prefill_bucket_floor=16, kv_bucket_floor=32,
        max_delay_s=0.002, spec_decode_k=3,
    )
    kw.update(serve_kw)
    engine = InferenceEngine(
        cfg,
        params if params is not None else _tiny_params(cfg),
        cfg=ServeConfig(**kw),
        registry=MetricsRegistry(),
    )
    counts = engine.warmup()
    assert sum(counts.values()) == engine.expected_compiles()
    return engine


@pytest.fixture(scope="module")
def spec_engine():
    """One warmed engine with spec_decode_k=3 for the module (the
    default 16-row blocks)."""
    engine = _spec_engine()
    yield engine
    assert engine.pool.active_slots == 0, "a test leaked KV slots"


@pytest.fixture(scope="module")
def paged_spec_engine():
    """The same at block 8: the spec window crosses block boundaries
    (same ladder floors)."""
    engine = _spec_engine(kv_block_size=8)
    yield engine
    assert engine.pool.active_slots == 0, "a test leaked KV slots"


def _spec_requests(n, cfg, *, max_new=6, seed=123):
    """Mixed prompt-like (tiled motif) and adversarial (random)
    prompts, a third sampled rather than greedy — speculation must be
    invisible on BOTH traffic shapes."""
    rng = np.random.default_rng(seed)
    cap = cfg.max_len - max_new
    reqs = []
    for i in range(n):
        ln = int(rng.integers(4, cap + 1))
        if i % 2 == 0:
            motif = [int(t) for t in rng.integers(0, cfg.vocab_size, 4)]
            prompt = (motif * (ln // 4 + 1))[:ln]
        else:
            prompt = [int(t) for t in rng.integers(0, cfg.vocab_size, ln)]
        temp, top_k = ((0.0, 0), (0.9, 0), (1.0, 7))[i % 3]
        reqs.append(Request(
            prompt=prompt, max_new_tokens=max_new, temperature=temp,
            top_k=top_k, seed=i,
        ))
    return reqs


# ------------------------------------------------------------ drafter


class TestNgramDraft:
    def test_repeated_motif_proposes_continuation(self):
        d = NgramDraft(max_ngram=3)
        d.begin(0, [1, 2, 3, 1, 2, 3, 1, 2])
        assert d.propose(0, 3) == [3, 1, 2]

    def test_cycle_extrapolates_past_context_end(self):
        # A period-1 loop must fill the whole window, not one token.
        d = NgramDraft(max_ngram=3)
        d.begin(0, [9, 5, 5, 5])
        assert d.propose(0, 4) == [5, 5, 5, 5]
        d2 = NgramDraft(max_ngram=2)
        d2.begin(1, [7, 8, 7, 8])
        assert d2.propose(1, 4) == [7, 8, 7, 8]

    def test_no_repeat_proposes_nothing(self):
        d = NgramDraft(max_ngram=3)
        d.begin(0, [1, 2, 3, 4, 5, 6])
        assert d.propose(0, 4) == []

    def test_longest_ngram_wins(self):
        # [1,2] occurs twice with different continuations; the 2-gram
        # match (continuation 7) must beat the 1-gram's.
        d = NgramDraft(max_ngram=3)
        d.begin(0, [1, 2, 7, 4, 2, 9, 1, 2])
        assert d.propose(0, 1) == [7]

    def test_extend_and_end_lifecycle(self):
        d = NgramDraft(max_ngram=2)
        d.begin(3, [1, 2])
        d.extend(3, [1, 2])
        assert d.propose(3, 2) == [1, 2]
        d.end(3)
        d.end(3)  # idempotent
        assert 3 not in d._ctx

    def test_deterministic(self):
        ctx = list(np.random.default_rng(0).integers(0, 50, 40))
        a, b = NgramDraft(), NgramDraft()
        a.begin(0, ctx)
        b.begin(0, ctx)
        assert a.propose(0, 5) == b.propose(0, 5)

    def test_make_draft_factory(self):
        assert isinstance(make_draft(ServeConfig()), NgramDraft)
        with pytest.raises(ValueError, match="draft"):
            make_draft(ServeConfig(draft="llama-draft"))


class TestAcceptance:
    def test_all_agree_commits_k_plus_one(self):
        assert accept_drafts([5, 6, 7], [5, 6, 7, 8], limit=10) \
            == [5, 6, 7, 8]

    def test_first_disagreement_stops(self):
        assert accept_drafts([5, 9, 7], [5, 6, 7, 8], limit=10) == [5, 6]

    def test_no_drafts_commits_one(self):
        assert accept_drafts([], [4, 0, 0, 0], limit=10) == [4]

    def test_limit_caps_committed_rows(self):
        assert accept_drafts([5, 6, 7], [5, 6, 7, 8], limit=2) == [5, 6]
        assert accept_drafts([5, 6, 7], [5, 6, 7, 8], limit=1) == [5]


# ------------------------------------------------------------- goldens


class TestSpeculativeGolden:
    def test_logprobs_are_refused_with_speculation_on(self, spec_engine):
        """A verify step commits several tokens from one fetch of
        tokens alone: a request that asks for log-probabilities is
        refused at submit, by name, and nothing is queued."""
        batcher = ContinuousBatcher(spec_engine).start()
        try:
            fut = batcher.submit(
                Request(prompt=[3, 4, 5], max_new_tokens=4, logprobs=True)
            )
            with pytest.raises(ValueError, match="logprobs.*speculative"):
                fut.result(timeout=30)
            assert batcher.queue_depth() == 0
        finally:
            batcher.close(drain=True)

    @pytest.mark.timeout(300)
    @pytest.mark.parametrize("seed", [123, 321])
    def test_paged_token_identical_to_reference(self, paged_spec_engine,
                                                seed):
        """THE ISSUE 11 golden: 10 mixed requests — greedy AND
        temperature sampling — through the batcher with speculation on,
        token-identical to the unbatched reference, zero post-warmup
        recompiles, and real draft acceptance happened (the spec window
        crosses block boundaries at block 8)."""
        eng = paged_spec_engine
        reqs = _spec_requests(10, eng.model_cfg, seed=seed)
        compiles_before = dict(eng.sentinel.compile_counts())
        batcher = ContinuousBatcher(eng).start()
        try:
            futs = [batcher.submit(r) for r in reqs]
            results = [f.result(timeout=120) for f in futs]
        finally:
            batcher.close(drain=True)
        for req, res in zip(reqs, results):
            ref = eng.reference_generate(
                req.prompt, max_new=req.max_new_tokens, seed=req.seed,
                temperature=req.temperature, top_k=req.top_k,
            )
            assert res.tokens == ref, (
                f"speculative != reference for prompt_len="
                f"{len(req.prompt)} temp={req.temperature}"
            )
        counters = eng.registry.counter_values()
        assert counters.get("serving/spec_accepted_total", 0) >= 1, (
            "motif prompts must take real draft acceptances or the "
            "golden only covered the degenerate path"
        )
        assert eng.sentinel.compile_counts() == compiles_before
        assert eng.post_warmup_recompiles() == 0
        assert eng.pool.used_bytes() == 0

    @pytest.mark.timeout(120)
    def test_eos_mid_window_truncates_exactly(self, spec_engine):
        """Tokens past eos inside an accepted verify window are
        discarded — the stream equals the non-speculative one, which
        stops at eos."""
        eng = spec_engine
        prompt = [9, 3, 5, 9, 3, 5, 9, 3]
        ref = eng.reference_generate(
            prompt, max_new=8, seed=4, temperature=1.0
        )
        j = next(
            i for i, t in enumerate(ref) if i and t not in ref[:i]
        )
        batcher = ContinuousBatcher(eng).start()
        try:
            res = batcher.submit(Request(
                prompt=prompt, max_new_tokens=8, eos_id=ref[j],
                temperature=1.0, seed=4,
            )).result(timeout=60)
        finally:
            batcher.close(drain=True)
        assert res.tokens == ref[:j + 1]
        assert res.truncated is None

    @pytest.mark.timeout(120)
    def test_accounting_committed_equals_stream(self):
        """Acceptance-counter accounting: every committed token is a
        stream token — decode_tokens == sum(len(stream) - 1) (the
        first token comes from prefill), and accepted <= drafted."""
        eng = _spec_engine()
        reqs = _spec_requests(6, eng.model_cfg, max_new=8, seed=77)
        batcher = ContinuousBatcher(eng).start()
        try:
            futs = [batcher.submit(r) for r in reqs]
            results = [f.result(timeout=120) for f in futs]
        finally:
            batcher.close(drain=True)
        counters = eng.registry.counter_values()
        stream_tokens = sum(len(res.tokens) for res in results)
        assert counters["serving/decode_tokens"] \
            == stream_tokens - len(reqs)
        drafted = counters.get("serving/spec_drafted_total", 0)
        accepted = counters.get("serving/spec_accepted_total", 0)
        assert 0 <= accepted <= drafted
        # Verify steps commit exactly request_steps + accepted tokens;
        # draft-less steps fall back to plain decode, so <=.
        assert counters["serving/spec_request_steps"] + accepted \
            <= counters["serving/decode_tokens"]
        # Per-request accounting (Result.spec_*): the fleet counters
        # are exactly the per-request sums, and each stream's length is
        # its decode commits (prefill token + accepted + plain steps).
        assert sum(r.spec_drafted for r in results) == drafted
        assert sum(r.spec_accepted for r in results) == accepted
        for res in results:
            assert 0 <= res.spec_accepted <= res.spec_drafted
            assert res.spec_accepted <= len(res.tokens) - 1

    @pytest.mark.timeout(120)
    def test_paged_exhaustion_shrinks_window_before_shedding(self):
        """A pool that cannot back the full spec window but CAN back
        one more row must shrink the window (serve slower), not fail
        the request — speculation never reduces availability."""
        cfg = tiny_cfg()
        eng = InferenceEngine(
            cfg, _tiny_params(cfg),
            cfg=ServeConfig(
                max_slots=2, prefill_bucket_floor=16, kv_bucket_floor=32,
                max_delay_s=0.0, kv_block_size=8, spec_decode_k=3,
                kv_blocks=4,  # 3 usable blocks = 24 rows
            ),
            registry=MetricsRegistry(),
        )
        eng.warmup()
        batcher = ContinuousBatcher(eng).start()
        try:
            # 16-token prompt (2 blocks) + 7 generated tops out INSIDE
            # the third block: the +3 spec lookahead would want a 4th
            # block the pool cannot give near the end.
            res = batcher.submit(Request(
                prompt=list(range(100, 116)), max_new_tokens=7, seed=1,
            )).result(timeout=60)
        finally:
            batcher.close(drain=True)
        assert res.tokens == eng.reference_generate(
            list(range(100, 116)), max_new=7, seed=1
        )
        assert eng.post_warmup_recompiles() == 0


# ------------------------------------------------------------ schema v8


class TestSchemaV8:
    @pytest.mark.timeout(120)
    def test_stats_line_carries_spec_keys_and_validates(self, spec_engine):
        eng = spec_engine
        batcher = ContinuousBatcher(eng).start()
        try:
            batcher.submit(Request(
                prompt=[5, 6, 5, 6, 5, 6], max_new_tokens=6, seed=2,
            )).result(timeout=60)
            line = json.loads(json.dumps(batcher.stats_line()))
        finally:
            batcher.close(drain=True)
        assert line["schema_version"] == schema.SERVING_SCHEMA_VERSION == 14
        assert schema.validate_line(line) == []
        serving = line["serving"]
        assert serving["spec_k"] == 3
        assert 0.0 <= serving["draft_hit_rate"] <= 1.0
        assert serving["accepted_per_step"] >= 1.0

    def test_v8_keys_flagged_on_older_versions(self):
        """Satellite: the speculation keys are v8-only — a 'v7' (or
        older) serving line carrying them is a mislabeled v8 line."""
        base = {
            "schema_version": 8, "kind": "serving", "step": 1,
            "time_unix": 1.0, "session_start_unix": 1.0, "host": 0,
            "metrics": {}, "counters": {}, "gauges": {}, "derived": {},
            "serving": {
                "active_requests": 0, "queue_depth": 0, "slots": 4,
                "kv_occupancy": 0.0, "post_warmup_recompiles": 0,
                "draining": 0, "spec_k": 3, "draft_hit_rate": 0.5,
                "accepted_per_step": 2.0,
            },
        }
        assert schema.validate_line(base) == []
        for version in (4, 5, 6, 7):
            stale = dict(base, schema_version=version)
            problems = schema.validate_line(stale)
            for key in schema.SERVING_KEYS_V8:
                assert any(
                    f"v8 serving key '{key}'" in p for p in problems
                ), (version, key, problems)

    def test_spec_free_line_carries_no_v8_keys(self):
        """A NON-speculative batcher's line must not leak the keys."""
        cfg = tiny_cfg()
        eng = InferenceEngine(
            cfg, _tiny_params(cfg),
            cfg=ServeConfig(max_slots=2, prefill_bucket_floor=16,
                            kv_bucket_floor=32),
            registry=MetricsRegistry(),
        )
        batcher = ContinuousBatcher(eng)
        line = batcher.stats_line()
        for key in schema.SERVING_KEYS_V8:
            assert key not in line["serving"]


# ------------------------------------------------------- config guards


class TestSpecConfig:
    def test_negative_k_rejected(self):
        cfg = tiny_cfg()
        with pytest.raises(ValueError, match="spec_decode_k"):
            InferenceEngine(
                cfg, _tiny_params(cfg),
                cfg=ServeConfig(spec_decode_k=-1),
                registry=MetricsRegistry(),
            )

    def test_window_must_fit_prefill_floor(self):
        cfg = tiny_cfg()
        with pytest.raises(ValueError, match="prefill_bucket_floor"):
            InferenceEngine(
                cfg, _tiny_params(cfg),
                cfg=ServeConfig(spec_decode_k=16,
                                prefill_bucket_floor=16),
                registry=MetricsRegistry(),
            )

    def test_paged_flash_refuses_fp8_kv(self):
        """The one thing the fused kernel is still refused for: it
        dequantizes int8 in-kernel, an fp8 pool goes through the XLA
        gather."""
        cfg = tiny_cfg()
        with pytest.raises(ValueError, match="paged_flash.*int8"):
            InferenceEngine(
                cfg, _tiny_params(cfg),
                cfg=ServeConfig(attention="paged_flash", kv_dtype="fp8"),
                registry=MetricsRegistry(),
            )
