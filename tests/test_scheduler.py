"""Cache-aware fleet scheduling (ISSUE 12): prefix-affinity chain
keys/digests, chunked prefill admission, disaggregated prefill/decode
KV-page handoff, schema v9.

The load-bearing tests:

* :class:`TestChunkedPrefillGolden` — a long cold prompt admitted
  mid-load is split into block-aligned chunks that INTERLEAVE with the
  co-scheduled requests' decode steps (structurally asserted), never
  stalls decode longer than ~one chunk (pinned budget), and the chunked
  stream is token-identical to the unchunked reference (the golden
  replay makes that free).
* :class:`TestHandoffGolden` — a prompt prefilled on one engine,
  exported as serialized KV pages, imported on ANOTHER engine, and
  decoded there is token-identical to the reference; over HTTP the
  /prefill -> /resume pair carries the same contract, and a geometry
  mismatch is a loud 400.

Everything else is deterministic unit coverage: content chain keys
(stable across pool resets — the property cross-replica affinity
relies on), the chunk planner, the page codec, the router's
affinity-vs-load pick, and the v9 schema pin mirroring every prior
version bump's.
"""

import json
import os
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from tensorflow_examples_tpu.models import transformer
from tensorflow_examples_tpu.serving import scheduler
from tensorflow_examples_tpu.serving.batcher import (
    ContinuousBatcher,
    Request,
)
from tensorflow_examples_tpu.serving.engine import (
    InferenceEngine,
    ServeConfig,
)
from tensorflow_examples_tpu.serving.frontend import ServingFrontend
from tensorflow_examples_tpu.serving.paged_kv import PagedKVPool
from tensorflow_examples_tpu.serving.router import Router, RouterConfig
from tensorflow_examples_tpu.telemetry import schema
from tensorflow_examples_tpu.telemetry.registry import MetricsRegistry

pytestmark = pytest.mark.serving

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))


TINY_MODEL = dict(
    vocab_size=211,
    max_len=64,
    num_layers=1,
    num_heads=2,
    d_model=16,
    dropout=0.0,
    attention="xla",
)


def _build_engine(*, max_len=64, **serve_kw):
    import jax
    import jax.numpy as jnp

    base = dict(TINY_MODEL)
    base["max_len"] = max_len
    cfg = transformer.TransformerConfig(**base)
    model = transformer.Transformer(cfg)
    params = model.init(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32)
    )["params"]
    kw = dict(
        max_slots=4, prefill_bucket_floor=16, kv_bucket_floor=32,
        kv_block_size=8, max_delay_s=0.0, request_timeout_s=60.0,
    )
    kw.update(serve_kw)
    return InferenceEngine(
        cfg, params, cfg=ServeConfig(**kw), registry=MetricsRegistry()
    )


# ------------------------------------------------------------ chain keys


class TestChainKeys:
    def test_deterministic_and_parent_sensitive(self):
        a = scheduler.chain_key("", [1, 2, 3, 4])
        assert a == scheduler.chain_key("", [1, 2, 3, 4])
        assert a != scheduler.chain_key(a, [1, 2, 3, 4])
        assert a != scheduler.chain_key("", [1, 2, 3, 5])

    def test_prompt_chain_caps_below_length(self):
        """Exactly prefix_lookup's cap: the tail keeps >= 1 token, so
        a block-aligned prompt publishes one less key than blocks."""
        assert len(scheduler.prompt_chain_keys(list(range(32)), 8)) == 3
        assert len(scheduler.prompt_chain_keys(list(range(33)), 8)) == 4
        assert scheduler.prompt_chain_keys([1, 2], 8) == []

    def test_affinity_walk_stops_at_first_miss(self):
        keys = scheduler.prompt_chain_keys(list(range(40)), 8)
        assert scheduler.affinity_blocks(keys, set(keys)) == 4
        assert scheduler.affinity_blocks(keys, set(keys[:2])) == 2
        # A matching deep key without its ancestors is unreachable.
        assert scheduler.affinity_blocks(keys, {keys[3]}) == 0


class TestChunkPlan:
    def test_block_aligned_spans_cover_tail(self):
        spans = scheduler.plan_chunks(100, 16, 32, 8)
        assert spans == [(16, 48), (48, 80), (80, 100)]
        assert scheduler.plan_chunks(48, 0, 16, 8) == [
            (0, 16), (16, 32), (32, 48)
        ]
        assert scheduler.plan_chunks(16, 16, 16, 8) == []

    def test_rejects_misaligned_inputs(self):
        with pytest.raises(ValueError, match="multiple of block_size"):
            scheduler.plan_chunks(64, 0, 12, 8)
        with pytest.raises(ValueError, match="block-aligned"):
            scheduler.plan_chunks(64, 3, 16, 8)
        with pytest.raises(ValueError, match="exceeds prompt length"):
            scheduler.plan_chunks(16, 24, 16, 8)


class TestPageCodec:
    def _payload(self, dtype=np.float32, scales=False):
        rng = np.random.default_rng(0)
        shape = (2, 3, 2, 8, 4)
        arrays = {
            "k": rng.standard_normal(shape).astype(dtype),
            "v": rng.standard_normal(shape).astype(dtype),
        }
        if scales:
            arrays["k_scale"] = rng.standard_normal(shape[:-1]).astype(
                np.float32
            )
            arrays["v_scale"] = rng.standard_normal(shape[:-1]).astype(
                np.float32
            )
        meta = dict(block_size=8, num_layers=2, num_heads=2,
                    head_dim=4, length=20, kv_bits=32)
        return meta, arrays

    def test_roundtrip_through_json(self):
        meta, arrays = self._payload()
        wire = json.loads(json.dumps(scheduler.encode_pages(meta, arrays)))
        meta2, arrays2 = scheduler.decode_pages(wire)
        assert meta2 == meta
        for name in arrays:
            assert np.array_equal(arrays2[name], arrays[name])

    def test_int8_scales_ride_along(self):
        meta, arrays = self._payload(dtype=np.int8, scales=True)
        meta["kv_bits"] = 8
        wire = json.loads(json.dumps(scheduler.encode_pages(meta, arrays)))
        _, arrays2 = scheduler.decode_pages(wire)
        assert arrays2["k"].dtype == np.int8
        assert np.array_equal(arrays2["k_scale"], arrays["k_scale"])

    def test_malformations_are_loud(self):
        meta, arrays = self._payload()
        wire = scheduler.encode_pages(meta, arrays)
        with pytest.raises(ValueError, match="wire version"):
            scheduler.decode_pages(dict(wire, version=99))
        bad = json.loads(json.dumps(wire))
        bad["arrays"]["k"]["shape"] = [1, 1, 1, 1, 1]
        with pytest.raises(ValueError, match="does not match shape"):
            scheduler.decode_pages(bad)
        bad = json.loads(json.dumps(wire))
        bad["arrays"]["v"]["data"] = "@@not-base64@@"
        with pytest.raises(ValueError, match="malformed pages array"):
            scheduler.decode_pages(bad)
        with pytest.raises(ValueError, match="missing the k/v"):
            scheduler.decode_pages(dict(wire, arrays={}))
        with pytest.raises(ValueError, match="JSON object"):
            scheduler.decode_pages([1, 2])


# ---------------------------------------------------------- pool digest


class TestPrefixDigest:
    def _pool(self):
        return PagedKVPool(
            num_layers=1, num_slots=2, num_heads=1, max_len=64,
            head_dim=4, block_size=8, registry=MetricsRegistry(),
        )

    def _publish(self, pool, prompt):
        slot = pool.alloc()
        blocks = pool.alloc_blocks(-(-len(prompt) // pool.block_size))
        pool.assign(slot, blocks)
        pool.insert_prefix(slot, prompt)
        return slot

    def test_digest_matches_prompt_chain(self):
        pool = self._pool()
        prompt = list(range(20))  # 2 full blocks + partial tail
        self._publish(pool, prompt)
        d = pool.prefix_digest()
        assert d["blocks"] == 2 and d["chains"] == 1
        keys = scheduler.prompt_chain_keys(prompt, 8)
        assert scheduler.affinity_blocks(keys, set(d["keys"])) == 2
        # A different prompt matches nothing.
        other = scheduler.prompt_chain_keys(list(range(50, 70)), 8)
        assert scheduler.affinity_blocks(other, set(d["keys"])) == 0

    def test_digest_stable_across_reset(self):
        """The satellite pin: content-addressed keys survive reset()
        (fresh physical ids, same tokens -> same digest) — the property
        that makes cross-replica and restart-spanning affinity sound."""
        pool = self._pool()
        prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7, 9, 3, 2, 3]
        self._publish(pool, prompt)
        before = pool.prefix_digest()
        pool.reset()
        assert pool.prefix_digest()["keys"] == []
        self._publish(pool, prompt)
        after = pool.prefix_digest()
        assert after["keys"] == before["keys"]
        assert after["blocks"] == before["blocks"]

    def test_two_chains_counted(self):
        pool = self._pool()
        self._publish(pool, list(range(20)))
        self._publish(pool, list(range(100, 120)))
        d = pool.prefix_digest()
        assert d["blocks"] == 4 and d["chains"] == 2

    def test_digest_capped_shallowest_first(self):
        pool = self._pool()
        prompt = list(range(33))  # 4 full blocks published
        self._publish(pool, prompt)
        d = pool.prefix_digest(max_keys=2)
        keys = scheduler.prompt_chain_keys(prompt, 8)
        # The cap keeps the shallow (most reusable) links.
        assert d["keys"] == keys[:2]

    def test_reallocate_drops_digest(self):
        pool = self._pool()
        self._publish(pool, list(range(20)))
        pool.reallocate()
        assert pool.prefix_digest() == {
            "keys": [], "blocks": 0, "chains": 0, "truncated": False,
        }


# -------------------------------------------------------- affinity pick


class TestAffinityPick:
    """Router dispatch-policy units — no sockets, states set by hand
    (the pattern of test_router.TestPick)."""

    def _router(self, **cfg_kw):
        r = Router(
            ["http://a:1", "http://b:2"],
            cfg=RouterConfig(**cfg_kw) if cfg_kw else None,
        )
        for rep in r.replicas:
            rep.probed = True
            rep.block_size = 8
        return r

    def test_prefers_longest_cached_chain(self):
        r = self._router()
        a, b = r.replicas
        prompt = list(range(40))
        keys = scheduler.prompt_chain_keys(prompt, 8)
        a.prefix_digest = frozenset(keys[:1])
        b.prefix_digest = frozenset(keys[:3])
        assert r.pick(prompt=prompt) is b
        assert (
            r.registry.counter_values()["router/affinity_hits_total"]
            == 1
        )

    def test_affinity_never_starves_a_hot_replica(self):
        """The load guard: the chain-holder only wins while its load
        score is within affinity_load_gap of the least-loaded."""
        r = self._router()
        a, b = r.replicas
        prompt = list(range(40))
        b.prefix_digest = frozenset(
            scheduler.prompt_chain_keys(prompt, 8)
        )
        b.queue_depth = r.cfg.affinity_load_gap + 1.0
        assert r.pick(prompt=prompt) is a
        b.queue_depth = r.cfg.affinity_load_gap - 0.5
        assert r.pick(prompt=prompt) is b

    def test_affinity_disabled_falls_back_to_load(self):
        r = self._router(prefix_affinity=False)
        a, b = r.replicas
        prompt = list(range(40))
        b.prefix_digest = frozenset(
            scheduler.prompt_chain_keys(prompt, 8)
        )
        b.dispatched = 1
        assert r.pick(prompt=prompt) is a

    def test_no_digest_no_preference(self):
        r = self._router()
        picked = {r.pick(prompt=list(range(40))).url for _ in range(2)}
        assert len(picked) == 2  # plain dispatched-tiebreak rotation

    def test_role_filter_mixed_serves_everything(self):
        r = self._router()
        a, b = r.replicas
        a.role, b.role = "prefill", "decode"
        assert r.pick(role="prefill") is a
        assert r.pick(role="decode") is b
        assert r.pick() is not None  # full path matches any role
        a.role = "mixed"
        assert r.pick(role="decode") in (a, b)

    def test_snapshot_carries_scheduling_fields(self):
        r = self._router()
        snap = r.replicas[0].snapshot_locked()
        assert snap["role"] == "mixed"
        assert snap["prefix_blocks"] == 0
        assert snap["prefix_chains"] == 0


# ----------------------------------------------- chunked prefill golden

# A chunked admission may stall co-scheduled decode steps by AT MOST
# ~one chunk: the pinned budget is a generous multiple of the longest
# single chunk actually measured (CI rigs are load-noisy; the claim is
# "bounded by a chunk", not "free").
CHUNK_STALL_FACTOR = 8.0
CHUNK_STALL_SLACK_S = 0.25


@pytest.fixture(scope="module")
def chunk_engine():
    """One warmed chunk-admission engine shared by the chunked-prefill
    goldens (the AOT warmup dominates; tests reset the pool and assert
    counter DELTAS so sharing is sound)."""
    engine = _build_engine(
        max_len=128, prefill_chunk_tokens=16, kv_bucket_floor=32,
    )
    engine.warmup()
    yield engine
    assert engine.pool.active_slots == 0, "a test leaked KV slots"


class TestChunkedPrefillGolden:
    @pytest.mark.timeout(300)
    def test_long_cold_prompt_interleaves_and_stays_token_identical(
        self, chunk_engine
    ):
        """ISSUE 12 (b): a long cold prompt admitted while short
        requests decode is split into block-aligned chunks, every
        chunk-to-chunk gap contains a decode step (the structural
        interleave claim), no decode gap exceeds the pinned
        one-chunk budget, and the chunked stream is token-identical
        to the unchunked reference replay."""
        engine = chunk_engine
        engine.pool.reset()
        counters0 = dict(engine.registry.counter_values())
        calls = []
        lock = threading.Lock()
        orig_step = engine.prefill_step
        orig_decode = engine.decode

        def step(state):
            t0 = time.perf_counter()
            out = orig_step(state)
            with lock:
                calls.append(("chunk", time.perf_counter() - t0,
                              time.perf_counter()))
            return out

        def decode(entries):
            out = orig_decode(entries)
            with lock:
                calls.append(("decode", 0.0, time.perf_counter()))
            return out

        engine.prefill_step = step
        engine.decode = decode
        batcher = ContinuousBatcher(engine).start()
        rng = np.random.default_rng(11)
        long_prompt = [int(t) for t in rng.integers(0, 211, 100)]
        try:
            shorts = [
                batcher.submit(Request(
                    prompt=[5 + i, 6, 7], max_new_tokens=24, seed=i,
                ))
                for i in range(2)
            ]
            deadline = time.monotonic() + 30
            while not batcher._active and time.monotonic() < deadline:
                time.sleep(0.002)
            assert batcher._active, "short requests never started"
            long_fut = batcher.submit(Request(
                prompt=long_prompt, max_new_tokens=4, seed=7,
                temperature=0.7,
            ))
            results = [f.result(timeout=120) for f in shorts]
            long_res = long_fut.result(timeout=120)
        finally:
            batcher.close(drain=True)
            engine.prefill_step = orig_step
            engine.decode = orig_decode
        # Token-identical to the unbatched reference — chunking is an
        # admission policy, never a numerics change.
        assert long_res.tokens == engine.reference_generate(
            long_prompt, max_new=4, seed=7, temperature=0.7,
        )
        for i, res in enumerate(results):
            assert res.tokens == engine.reference_generate(
                [5 + i, 6, 7], max_new=24, seed=i,
            )
        counters = engine.registry.counter_values()
        assert counters["serving/chunked_prefills"] - counters0.get(
            "serving/chunked_prefills", 0
        ) == 1
        # 100 cold tokens at chunk 16 -> 7 chunks (6 full + ragged).
        assert counters["serving/prefill_chunks"] - counters0.get(
            "serving/prefill_chunks", 0
        ) == 7
        chunk_idx = [i for i, c in enumerate(calls) if c[0] == "chunk"]
        assert len(chunk_idx) == 7
        # Structural interleave: a decode step sits between every
        # consecutive pair of chunks (one chunk per loop iteration,
        # decode after — the shorts outlive the whole chunked prefill
        # by construction).
        for i, j in zip(chunk_idx, chunk_idx[1:]):
            between = [calls[k][0] for k in range(i + 1, j)]
            assert "decode" in between, (
                f"chunks {i}->{j} ran back-to-back: {calls}"
            )
        # The stall bound: during the chunk phase, no decode-to-decode
        # gap exceeds the pinned budget of ~one chunk.
        max_chunk = max(c[1] for c in calls if c[0] == "chunk")
        decode_times = [
            c[2] for c in calls[chunk_idx[0]:chunk_idx[-1] + 2]
            if c[0] == "decode"
        ]
        gaps = [b - a for a, b in zip(decode_times, decode_times[1:])]
        budget = CHUNK_STALL_FACTOR * max_chunk + CHUNK_STALL_SLACK_S
        assert max(gaps) <= budget, (max(gaps), budget)
        assert engine.post_warmup_recompiles() == 0

    @pytest.mark.timeout(300)
    def test_decode_steps_between_chunks_leave_the_prompt_as_it_was(
        self, chunk_engine
    ):
        """A slot in the middle of a chunked prefill holds blocks but
        is not stepping: a decode step of the OTHER slots must hand it
        a null table row, like an empty slot's. (It used to hand it its
        own, and wrote a stand-in token's K/V over the prompt's
        position 0: the same tokens more often than not, other
        logits.) Logits, not tokens."""
        engine = chunk_engine
        engine.pool.reset()
        rng = np.random.default_rng(3)
        long = [int(t) for t in rng.integers(0, 50, (70,))]
        short = [int(t) for t in rng.integers(0, 50, (5,))]

        def chunked(between):
            slot = engine.pool.alloc()
            state, done = engine.prefill_open(slot, long), False
            assert len(state.spans) > 2  # cold: nothing of it is cached
            while not done:
                between()
                done, _, last = engine.prefill_step(state)
            engine.pool.free(slot)
            return last

        alone = chunked(lambda: None)
        engine.pool.reset()  # drops the prompt's published blocks too
        other = engine.pool.alloc()
        tok = [engine.prefill(other, short)[0]]
        with_steps = chunked(lambda: tok.append(
            engine.decode([(other, tok[-1], 0, 0.0, 0)])[other]
        ))
        engine.pool.free(other)
        np.testing.assert_array_equal(with_steps, alone)

    def test_chunked_prefill_reuses_cached_prefix(self, chunk_engine):
        """A chunked admission still takes the prefix-cache hit: the
        cached context never re-chunks, only the cold tail does."""
        engine = chunk_engine
        engine.pool.reset()
        chunks0 = engine.registry.counter_values().get(
            "serving/prefill_chunks", 0
        )
        batcher = ContinuousBatcher(engine).start()
        rng = np.random.default_rng(12)
        prefix = [int(t) for t in rng.integers(0, 211, 64)]
        try:
            first = batcher.submit(Request(
                prompt=prefix + [1, 2], max_new_tokens=2, seed=0,
            )).result(timeout=120)
            chunks_cold = engine.registry.counter_values()[
                "serving/prefill_chunks"
            ] - chunks0
            second = batcher.submit(Request(
                prompt=prefix + [3, 4, 5], max_new_tokens=2, seed=1,
            )).result(timeout=120)
        finally:
            batcher.close(drain=True)
        chunks_total = engine.registry.counter_values()[
            "serving/prefill_chunks"
        ] - chunks0
        # First admission chunked the cold 66 tokens (5 chunks); the
        # second hit 64 cached tokens, so its whole cold tail is the
        # 3-token remainder — ONE span, one extend call, exactly what
        # the plain prefix-hit path would have run.
        assert chunks_cold == 5
        assert chunks_total == chunks_cold + 1
        assert engine.pool.prefix_hits >= 1
        assert first.tokens == engine.reference_generate(
            prefix + [1, 2], max_new=2, seed=0
        )
        assert second.tokens == engine.reference_generate(
            prefix + [3, 4, 5], max_new=2, seed=1
        )

    @pytest.mark.timeout(300)
    def test_deadline_expiry_abandons_remaining_chunks(
        self, chunk_engine
    ):
        """A chunked prefill whose deadline passes mid-plan is
        abandoned (504, serving/expired_total) instead of stalling
        everyone else's decode steps for chunks that can deliver
        nothing."""
        engine = chunk_engine
        engine.pool.reset()
        chunks0 = engine.registry.counter_values().get(
            "serving/prefill_chunks", 0
        )
        orig_step = engine.prefill_step

        def slow_step(state):
            time.sleep(0.05)
            return orig_step(state)

        engine.prefill_step = slow_step
        batcher = ContinuousBatcher(engine).start()
        rng = np.random.default_rng(13)
        long_prompt = [int(t) for t in rng.integers(0, 211, 100)]
        try:
            fut = batcher.submit(Request(
                prompt=long_prompt, max_new_tokens=4, seed=0,
                deadline_s=0.08,
            ))
            from tensorflow_examples_tpu.serving.batcher import (
                DeadlineExceeded,
            )

            with pytest.raises(DeadlineExceeded, match="chunked"):
                fut.result(timeout=60)
        finally:
            batcher.close(drain=True)
            engine.prefill_step = orig_step
        chunks = engine.registry.counter_values().get(
            "serving/prefill_chunks", 0
        ) - chunks0
        # Far fewer than the 7 chunks a full admission runs.
        assert chunks < 7
        assert engine.registry.counter_values().get(
            "serving/expired_total", 0
        ) >= 1
        assert engine.pool.active_slots == 0

    def test_chunk_requires_prefix_cache(self):
        """The chunk program IS the extend rung, which exists only with
        the prefix cache on."""
        with pytest.raises(ValueError, match="prefix_cache=True"):
            _build_engine(prefix_cache=False, prefill_chunk_tokens=16)

    def test_chunk_must_be_block_multiple(self):
        with pytest.raises(ValueError, match="multiple of kv_block"):
            _build_engine(kv_block_size=8, prefill_chunk_tokens=12)

    def test_role_validated(self):
        with pytest.raises(ValueError, match="role="):
            _build_engine(role="gpu")


# ------------------------------------------------------- handoff golden


@pytest.fixture(scope="module")
def handoff_engines():
    """One donor + one importer (same params — the disagg contract
    assumes one model behind every role). NOT warmed: the handoff
    goldens pin token identity and recompile-freedom, not latency, so
    lazy first-use compilation (1 per rung = within the sentinel
    allowance) keeps the module cheap."""
    donor = _build_engine()
    importer = _build_engine()
    yield donor, importer
    assert donor.pool.active_slots == 0
    assert importer.pool.active_slots == 0


class TestHandoffGolden:
    @pytest.mark.timeout(300)
    def test_imported_pages_decode_token_identical(
        self, handoff_engines
    ):
        """Engine-level ISSUE 12 (c): prefill on A, export, import on
        B, decode on B — the stream is token-identical to the
        reference (fp32 pages roundtrip bitwise)."""
        donor, importer = handoff_engines
        rng = np.random.default_rng(21)
        prompt = [int(t) for t in rng.integers(0, 211, 37)]
        slot = donor.pool.alloc()
        first, _ = donor.prefill(slot, prompt, seed=5, temperature=0.7)
        pages = json.loads(json.dumps(
            donor.export_kv_pages(slot, prompt)
        ))
        donor.pool.free(slot)
        batcher = ContinuousBatcher(importer).start()
        try:
            res = batcher.submit(Request(
                prompt=prompt, max_new_tokens=5, seed=5,
                temperature=0.7, kind="resume", pages=pages,
                first_token=int(first),
            )).result(timeout=120)
        finally:
            batcher.close(drain=True)
        assert res.tokens == importer.reference_generate(
            prompt, max_new=5, seed=5, temperature=0.7
        )
        assert importer.post_warmup_recompiles() == 0
        # The import seeded the importer's prefix cache: the next
        # shared-prefix admission hits locally.
        hits_before = importer.pool.prefix_hits
        slot = importer.pool.alloc()
        importer.prefill(slot, prompt[:16] + [9], seed=0)
        importer.pool.free(slot)
        assert importer.pool.prefix_hits == hits_before + 1

    @pytest.mark.timeout(300)
    def test_geometry_mismatch_rejected(self, handoff_engines):
        donor, importer = handoff_engines
        prompt = list(range(20))
        slot = donor.pool.alloc()
        donor.prefill(slot, prompt, seed=0)
        pages = donor.export_kv_pages(slot, prompt)
        donor.pool.free(slot)
        wrong = json.loads(json.dumps(pages))
        wrong["block_size"] = 16
        slot = importer.pool.alloc()
        try:
            with pytest.raises(ValueError, match="geometry mismatch"):
                importer.import_kv_pages(slot, wrong, prompt)
            with pytest.raises(ValueError, match="pages cover"):
                importer.import_kv_pages(slot, pages, prompt + [1])
        finally:
            importer.pool.free(slot)

    @pytest.mark.timeout(300)
    def test_prefill_resume_over_http(self, handoff_engines):
        """The wire pair: POST /prefill on a prefill-role stack, ship
        the reply's pages to POST /resume on a decode-role stack, and
        the resumed stream is token-identical to the reference."""
        donor, importer = handoff_engines
        stacks = []
        for engine in (donor, importer):
            batcher = ContinuousBatcher(engine).start()
            frontend = ServingFrontend(batcher, port=0).start()
            stacks.append((batcher, frontend))
        rng = np.random.default_rng(22)
        prompt = [int(t) for t in rng.integers(0, 211, 29)]

        def post(frontend, path, body):
            req = urllib.request.Request(
                frontend.url(path), data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json"},
            )
            try:
                with urllib.request.urlopen(req, timeout=60) as resp:
                    return resp.status, json.loads(resp.read())
            except urllib.error.HTTPError as e:
                return e.code, json.loads(e.read() or b"{}")

        try:
            status, pre = post(
                stacks[0][1], "/prefill",
                {"prompt": prompt, "seed": 3, "temperature": 0.7},
            )
            assert status == 200, pre
            assert isinstance(pre["first_token"], int)
            assert isinstance(pre["pages"], dict)
            status, out = post(
                stacks[1][1], "/resume",
                {"prompt": prompt, "max_new_tokens": 4, "seed": 3,
                 "temperature": 0.7, "pages": pre["pages"],
                 "first_token": pre["first_token"]},
            )
            assert status == 200, out
            assert out["tokens"] == importer.reference_generate(
                prompt, max_new=4, seed=3, temperature=0.7
            )
            # Malformed resume bodies are 400s, never 500s.
            status, err = post(
                stacks[1][1], "/resume",
                {"prompt": prompt, "first_token": 1},
            )
            assert status == 400 and "pages" in err["error"]
        finally:
            for batcher, frontend in stacks:
                batcher.close(drain=True)
                frontend.close()

    @pytest.mark.timeout(300)
    def test_int8_pages_roundtrip(self):
        """int8 pools hand off int8 payloads + blockwise scales; the
        importer's continuation matches the donor's own continuation
        exactly (same quantized cache bytes on both sides — one engine
        plays both roles, importing into a different slot, which
        exercises the same wire + scatter path as a cross-process
        handoff)."""
        engine = _build_engine(kv_dtype="int8")  # lazy compiles: only
        #                                          the 2 rungs it uses
        rng = np.random.default_rng(23)
        prompt = [int(t) for t in rng.integers(0, 211, 21)]
        d_slot = engine.pool.alloc()
        first, _ = engine.prefill(d_slot, prompt, seed=9)
        pages = json.loads(json.dumps(
            engine.export_kv_pages(d_slot, prompt)
        ))
        assert pages["kv_bits"] == 8
        assert "k_scale" in pages["arrays"]
        i_slot = engine.pool.alloc()
        engine.import_kv_pages(i_slot, pages, prompt)
        donor_stream, importer_stream = [], []
        d_tok = i_tok = int(first)
        for _ in range(4):
            d_tok = engine.decode(
                [(d_slot, d_tok, 9, 0.0, 0)]
            )[d_slot]
            i_tok = engine.decode(
                [(i_slot, i_tok, 9, 0.0, 0)]
            )[i_slot]
            donor_stream.append(d_tok)
            importer_stream.append(i_tok)
        engine.pool.free(d_slot)
        engine.pool.free(i_slot)
        assert importer_stream == donor_stream


# ------------------------------------------ streaming delta (ISSUE 15)


class TestDeltaHandoff:
    """PR 11 follow-up: /prefill -> /resume ships only the pages the
    importer's prefix cache doesn't already hold — the digest exchange
    rides the handoff request as ``skip_tokens`` and the pages'
    ``start_block`` meta."""

    def test_start_block_codec_roundtrip_and_malformations(self):
        meta = dict(block_size=8, num_layers=1, num_heads=2, head_dim=8,
                    length=20, kv_bits=32, start_block=1)
        arrays = {"k": np.ones((1, 2, 2, 8, 8), np.float32),
                  "v": np.zeros((1, 2, 2, 8, 8), np.float32)}
        payload = json.loads(json.dumps(
            scheduler.encode_pages(meta, arrays)
        ))
        got_meta, got_arrays = scheduler.decode_pages(payload)
        assert got_meta["start_block"] == 1
        assert got_arrays["k"].shape == (1, 2, 2, 8, 8)
        # Absent start_block reads as 0 (pre-delta payloads): neither
        # the wire nor the parsed meta carry the key.
        no_skip = scheduler.encode_pages(
            {**meta, "start_block": 0}, arrays
        )
        assert "start_block" not in no_skip
        assert "start_block" not in scheduler.decode_pages(no_skip)[0]
        with pytest.raises(ValueError, match="start_block"):
            scheduler.encode_pages({**meta, "start_block": -1}, arrays)
        bad = dict(payload)
        bad["start_block"] = -2
        with pytest.raises(ValueError, match="start_block"):
            scheduler.decode_pages(bad)
        whole = dict(payload)
        whole["start_block"] = 5  # 5 * 8 >= length 20
        with pytest.raises(ValueError, match="skips the whole"):
            scheduler.decode_pages(whole)

    @pytest.mark.timeout(300)
    def test_delta_import_token_identical_when_prefix_held(self):
        """Engine level: the importer already caches the shared prefix
        (an earlier full handoff); a delta export skipping it imports
        clean and the continued stream is token-identical — while the
        wire payload carries strictly fewer blocks."""
        donor = _build_engine()
        importer = _build_engine()
        rng = np.random.default_rng(31)
        prompt = [int(t) for t in rng.integers(0, 211, 37)]
        # Round 1: full handoff seeds the importer's prefix cache.
        slot = donor.pool.alloc()
        first, _ = donor.prefill(slot, prompt, seed=5)
        full = donor.export_kv_pages(slot, prompt)
        donor.pool.free(slot)
        i_slot = importer.pool.alloc()
        importer.import_kv_pages(i_slot, full, prompt)
        importer.pool.free(i_slot)
        # Round 2: same prompt, digest says the importer holds
        # (len-1)//bs * bs = 32 leading tokens.
        slot = donor.pool.alloc()
        first2, _ = donor.prefill(slot, prompt, seed=5)
        delta = json.loads(json.dumps(
            donor.export_kv_pages(slot, prompt, skip_tokens=32)
        ))
        donor.pool.free(slot)
        assert first2 == first
        assert delta["start_block"] == 4
        nb_full = len(full["arrays"]["k"]["data"])
        nb_delta = len(delta["arrays"]["k"]["data"])
        assert nb_delta < nb_full // 3  # 1 of 5 blocks on the wire
        exported = donor.registry.counter_values()
        assert exported["serving/kv_pages_delta_skipped"] == 4
        i_slot = importer.pool.alloc()
        importer.import_kv_pages(i_slot, delta, prompt)
        stream = []
        tok = int(first)
        for _ in range(4):
            tok = importer.decode([(i_slot, tok, 5, 0.0, 0)])[i_slot]
            stream.append(tok)
        importer.pool.free(i_slot)
        ref = importer.reference_generate(prompt, max_new=5, seed=5)
        assert [int(first)] + stream == ref

    @pytest.mark.timeout(300)
    def test_cold_importer_rejects_delta_loudly(self):
        """A delta payload landing on a replica whose prefix cache
        does NOT cover the skip (probe-stale digest) is a loud
        ValueError (-> 400 -> router full-path fallback), never a torn
        cache."""
        donor = _build_engine()
        cold = _build_engine()
        prompt = list(range(40))
        slot = donor.pool.alloc()
        donor.prefill(slot, prompt, seed=0)
        delta = donor.export_kv_pages(slot, prompt, skip_tokens=16)
        donor.pool.free(slot)
        i_slot = cold.pool.alloc()
        try:
            with pytest.raises(ValueError, match="prefix cache covers"):
                cold.import_kv_pages(i_slot, delta, prompt)
        finally:
            cold.pool.free(i_slot)

    @pytest.mark.timeout(300)
    def test_skip_tokens_over_http_prefill(self):
        """The wire surface: /prefill accepts skip_tokens and the
        reply's pages carry start_block; junk skip_tokens is a 400."""
        engine = _build_engine()
        batcher = ContinuousBatcher(engine).start()
        frontend = ServingFrontend(batcher, port=0).start()

        def post(path, body):
            req = urllib.request.Request(
                frontend.url(path), data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json"},
            )
            try:
                with urllib.request.urlopen(req, timeout=60) as resp:
                    return resp.status, json.loads(resp.read())
            except urllib.error.HTTPError as e:
                return e.code, json.loads(e.read() or b"{}")

        prompt = list(range(30))
        try:
            status, pre = post(
                "/prefill", {"prompt": prompt, "skip_tokens": 16},
            )
            assert status == 200, pre
            assert pre["pages"]["start_block"] == 2
            status, err = post(
                "/prefill", {"prompt": prompt, "skip_tokens": -1},
            )
            assert status == 400
            # skip_tokens is a prefill-leg field only.
            status, err = post(
                "/generate", {"prompt": prompt, "skip_tokens": 8},
            )
            assert status == 400 and "unknown" in err["error"]
        finally:
            batcher.close(drain=True)
            frontend.close()

    def test_failed_delta_handoff_counts_no_savings(self):
        """router/handoff_delta_tokens_total only ticks on COMPLETED
        handoffs: a handoff whose legs fail (dead replicas here) falls
        back to the full path having saved nothing."""
        from tensorflow_examples_tpu.serving.router import RouterConfig

        router = Router(
            ["http://127.0.0.1:1", "http://127.0.0.1:2"],
            cfg=RouterConfig(max_retries=0, retry_budget_s=0.1,
                             retry_backoff_s=0.0),
        )
        pre, dec = router.replicas
        for r, role in ((pre, "prefill"), (dec, "decode")):
            r.probed = True
            r.role = role
            r.block_size = 8
        prompt = list(range(33))
        dec.prefix_digest = frozenset(
            scheduler.prompt_chain_keys(prompt, 8)
        )
        out = router._handle_disagg({"prompt": prompt}, prompt, {})
        assert out is None  # both legs dead -> full-path fallback
        counters = router.registry.counter_values()
        assert counters.get("router/handoff_delta_tokens_total", 0) == 0

    def test_router_digest_exchange_is_conservative_minimum(self):
        """_decode_cached_tokens: the skip is the MINIMUM over eligible
        decode-serving replicas (safe whichever one the resume lands
        on), and 0 the moment any candidate has no digest."""
        router = Router(["http://a", "http://b"])
        a, b = router.replicas
        for r, role in ((a, "decode"), (b, "mixed")):
            r.probed = True
            r.role = role
            r.block_size = 8
        prompt = list(range(33))
        keys = scheduler.prompt_chain_keys(prompt, 8)
        a.prefix_digest = frozenset(keys)       # holds 4 blocks
        b.prefix_digest = frozenset(keys[:2])   # holds 2 blocks
        assert router._decode_cached_tokens(prompt, {}) == 16
        b.prefix_digest = frozenset()
        assert router._decode_cached_tokens(prompt, {}) == 0
        b.role = "prefill"  # not a resume candidate anymore
        assert router._decode_cached_tokens(prompt, {}) == 32


# ------------------------------------------- bloom digest (ISSUE 15)


class TestBloomDigest:
    def test_roundtrip_no_false_negatives(self):
        keys = [scheduler.chain_key("", [i]) for i in range(300)]
        bloom = scheduler.decode_bloom(json.loads(json.dumps(
            scheduler.encode_bloom(keys)
        )))
        assert len(bloom) == 300
        assert all(k in bloom for k in keys), "bloom NEVER false-negs"

    def test_false_positive_rate_sane(self):
        keys = [scheduler.chain_key("", [i]) for i in range(500)]
        bloom = scheduler.decode_bloom(scheduler.encode_bloom(keys))
        probes = [
            scheduler.chain_key("x", [i]) for i in range(2000)
        ]
        fp = sum(p in bloom for p in probes) / len(probes)
        assert fp < 0.05, f"false-positive rate {fp} out of spec"

    def test_empty_filter_is_falsy_and_matches_nothing(self):
        bloom = scheduler.decode_bloom(scheduler.encode_bloom([]))
        assert not bloom
        assert scheduler.chain_key("", [1]) not in bloom

    def test_malformed_payloads_are_loud(self):
        good = scheduler.encode_bloom(["ab"])
        for mutate in (
            lambda p: p.pop("bits"),
            lambda p: p.__setitem__("bits", "###"),
            lambda p: p.__setitem__("m", 7),
            lambda p: p.__setitem__("m", scheduler.BLOOM_MAX_BITS * 2),
            lambda p: p.__setitem__("k", 0),
            lambda p: p.__setitem__("n", -1),
        ):
            bad = dict(good)
            mutate(bad)
            with pytest.raises(ValueError):
                scheduler.decode_bloom(bad)
        with pytest.raises(ValueError):
            scheduler.decode_bloom("not a dict")

    def test_affinity_blocks_walks_a_bloom(self):
        prompt = list(range(40))
        keys = scheduler.prompt_chain_keys(prompt, 8)
        bloom = scheduler.decode_bloom(scheduler.encode_bloom(keys[:3]))
        got = scheduler.affinity_blocks(keys, bloom)
        assert got >= 3  # exact is 3; a false positive may extend it

    def test_pool_publishes_bloom_when_truncated(self):
        pool = PagedKVPool(
            num_layers=1, num_slots=2, num_heads=1, max_len=32,
            head_dim=4, block_size=8, registry=MetricsRegistry(),
        )
        for i in range(6):
            slot = pool.alloc()
            prompt = [i * 100 + j for j in range(16)]
            total = -(-len(prompt) // 8)
            blocks = pool.alloc_blocks(total)
            pool.assign(slot, blocks)
            pool.lengths[slot] = len(prompt)
            pool.insert_prefix(slot, prompt)
            pool.free(slot)
        full = pool.prefix_digest()
        assert "bloom" not in full  # under the cap: exact keys suffice
        capped = pool.prefix_digest(max_keys=4)
        assert capped["truncated"]
        bloom = scheduler.decode_bloom(capped["bloom"])
        # The bloom covers EVERY chain key, including the shed tail.
        assert len(bloom) == full["blocks"]
        assert all(k in bloom for k in full["keys"])

    def test_bloom_cached_until_published_set_changes(self):
        """The encoded filter is built once per cache generation (and
        outside the lock): an unchanged cache serves the same object
        to every probe; publishing a new chain invalidates it."""
        pool = PagedKVPool(
            num_layers=1, num_slots=2, num_heads=1, max_len=32,
            head_dim=4, block_size=8, registry=MetricsRegistry(),
        )

        def publish(base):
            slot = pool.alloc()
            prompt = [base + j for j in range(16)]
            blocks = pool.alloc_blocks(2)
            pool.assign(slot, blocks)
            pool.lengths[slot] = 16
            pool.insert_prefix(slot, prompt)
            pool.free(slot)

        publish(0)
        publish(100)
        b1 = pool.prefix_digest(max_keys=1)["bloom"]
        b2 = pool.prefix_digest(max_keys=1)["bloom"]
        assert b1 is b2, "unchanged cache must reuse the encoded bloom"
        publish(200)
        b3 = pool.prefix_digest(max_keys=1)["bloom"]
        assert b3 is not b1
        key = scheduler.chain_key("", [200 + j for j in range(8)])
        assert key in scheduler.decode_bloom(b3)

    def test_router_probe_prefers_bloom_over_truncated_list(self):
        router = Router(["http://a"])
        (a,) = router.replicas
        prompt = list(range(40))
        keys = scheduler.prompt_chain_keys(prompt, 8)
        payload = scheduler.encode_bloom(keys)

        def fake_get(url, timeout):
            return 200, {
                "ok": True,
                "prefix_block_size": 8,
                "prefix_digest": keys[:1],  # capped list
                "digest_truncated": True,
                "prefix_bloom": payload,
            }

        from tensorflow_examples_tpu.serving import router as router_mod

        orig = router_mod._get_json
        router_mod._get_json = fake_get
        try:
            router.probe_once()
        finally:
            router_mod._get_json = orig
        assert isinstance(a.prefix_digest, scheduler.BloomDigest)
        assert scheduler.affinity_blocks(keys, a.prefix_digest) >= len(
            keys
        ) - 0
        # A malformed bloom keeps the key list instead of failing the
        # probe sweep.
        def bad_get(url, timeout):
            return 200, {
                "ok": True,
                "prefix_block_size": 8,
                "prefix_digest": keys[:1],
                "prefix_bloom": {"m": 7, "k": 1, "n": 1, "bits": "x"},
            }

        router_mod._get_json = bad_get
        try:
            router.probe_once()
        finally:
            router_mod._get_json = orig
        assert a.prefix_digest == frozenset(keys[:1])


# -------------------------------------------------------------- schema


class TestSchemaV9:
    def test_paged_stats_line_carries_prefix_summary(self):
        engine = _build_engine()
        batcher = ContinuousBatcher(engine)
        line = json.loads(json.dumps(batcher.stats_line()))
        assert line["schema_version"] == schema.SERVING_SCHEMA_VERSION
        assert line["schema_version"] == 14
        assert schema.validate_line(line) == []
        assert line["serving"]["prefix_blocks"] == 0
        assert line["serving"]["prefix_chains"] == 0

    def test_v9_keys_flagged_on_older_versions(self):
        """Satellite pin: prefix_blocks/prefix_chains are v9-only — a
        'v8' (or older) serving line carrying them is a mislabeled v9
        line, same rule as every earlier bump."""
        base = {
            "schema_version": 9, "kind": "serving", "step": 1,
            "time_unix": 1.0, "session_start_unix": 1.0, "host": 0,
            "metrics": {}, "counters": {}, "gauges": {}, "derived": {},
            "serving": {
                "active_requests": 0, "queue_depth": 0, "slots": 4,
                "kv_occupancy": 0.0, "post_warmup_recompiles": 0,
                "draining": 0, "prefix_blocks": 3, "prefix_chains": 1,
            },
        }
        assert schema.validate_line(base) == []
        for version in (4, 5, 6, 7, 8):
            stale = dict(base, schema_version=version)
            problems = schema.validate_line(stale)
            for key in schema.SERVING_KEYS_V9:
                assert any(
                    f"v9 serving key '{key}'" in p for p in problems
                ), (version, key, problems)
