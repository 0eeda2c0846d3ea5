"""Serving a block that is not GPT-2's (ISSUE 28): Cohere2-MoE at a toy
width through the engine's paged path — grouped-query rows, window and
full layers over two block-id spaces, an expert layer that holds some
of the experts — against the plain reference
(``benchmark/reference/cohere2_moe.py``), and everything the engine
refuses for such a block, by name.

Sizes: hidden 64, 4 query / 2 KV heads of 16, window 8, block 4, layers
S,S,S,F, 8 experts top 2 with 2 shared, 4 held, a 128-row vocabulary.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import extend_rungs  # noqa: E402
from benchmark import spec  # noqa: E402
from tensorflow_examples_tpu.serving import paged_kv  # noqa: E402
from tensorflow_examples_tpu.serving.batcher import (  # noqa: E402
    ContinuousBatcher,
    Request,
)
from tensorflow_examples_tpu.serving.engine import (  # noqa: E402
    InferenceEngine,
    ServeConfig,
)
from tensorflow_examples_tpu.telemetry import schema  # noqa: E402
from tensorflow_examples_tpu.telemetry.registry import MetricsRegistry  # noqa: E402
from tensorflow_examples_tpu.workloads import cohere2_moe as workload  # noqa: E402

REF = spec.reference("cohere2_moe")
# The configuration-file keys the reference reads, at the toy width.
TINY = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    intermediate_size=32, num_experts_per_tok=2, num_shared_experts=2,
    sliding_window=8, rope_theta=50000.0, layer_norm_eps=1e-5, logit_scale=1.0,
    layer_types=["sliding_attention"] * 3 + ["full_attention"],
    num_hidden_layers=4, held_experts=[0, 1, 2, 3], vocab_size=128,
)
ROUTER = 8
SERVE = dict(max_slots=2, kv_block_size=4, kv_blocks=33, prefill_bucket_floor=8,
             kv_bucket_floor=16, prefill_chunk_tokens=8)


def program_config(**over):
    sizes = {k: (tuple(v) if isinstance(v, list) else v) for k, v in {**TINY, **over}.items()}
    return workload.Cohere2MoeServeConfig(
        **sizes, router_experts=ROUTER, seq_len=64, param_dtype="float32")


@pytest.fixture(scope="module")
def model():
    pcfg = program_config()
    params = jax.jit(workload.make_task(pcfg).init_fn)(jax.random.PRNGKey(0))["params"]
    return workload.model_config(pcfg), params


def make_engine(model, **over):
    mcfg, params = model
    reg = MetricsRegistry()
    eng = InferenceEngine(mcfg, params, cfg=ServeConfig(**{**SERVE, **over}), registry=reg)
    return eng, reg


@pytest.fixture(scope="module")
def engine(model):
    eng, reg = make_engine(model)
    eng.warmup()
    return eng, reg


def prompt_of(n, seed=0):
    return [int(t) for t in np.random.default_rng(seed).integers(0, 128, (n,))]


def serve(eng, slot, prompt, n_new):
    """Chunked prefill -> paged decode, by hand: (tokens, first logits)."""
    state = eng.prefill_open(slot, prompt)
    if state is None:
        tok, last = eng.prefill(slot, prompt)
    else:
        done = False
        while not done:
            done, tok, last = eng.prefill_step(state)
    toks = [tok]
    for _ in range(n_new - 1):
        toks.append(eng.decode([(slot, toks[-1], 0, 0.0, 0)])[slot])
    return toks, last


def free_lists_whole(pool):
    return (len(pool._free_blocks) == pool.num_blocks - 1
            and all(len(w.free) == w.num_blocks - 1 for w in pool._windows))


class TestAgainstTheReference:
    @pytest.mark.parametrize("n", [5, 8, 21, 30, 50])
    def test_logits_through_chunked_prefill_and_paged_decode(self, engine, model, n):
        """Prompts shorter than a chunk (the prefill rung), of one chunk,
        and longer than the window (several chunks, blocks released):
        the first logits and every greedy token are the reference's."""
        eng, _ = engine
        _, params = model
        prompt = prompt_of(n, seed=n)
        slot = eng.pool.alloc()
        toks, last = serve(eng, slot, prompt, 8)
        eng.pool.free(slot)
        seq = prompt + toks
        logits, _ = REF.forward(params, seq, TINY, rows=range(n - 1, len(seq) - 1), q_block=8)
        np.testing.assert_allclose(last, logits[0], atol=2e-5)
        for k, tok in enumerate(toks):
            assert logits[k].max() - logits[k][tok] < 2e-5, (n, k)
        assert eng.post_warmup_recompiles() == 0

    def test_two_requests_decode_together_as_they_do_alone(self, engine):
        eng, _ = engine
        prompts = [prompt_of(27, seed=1), prompt_of(11, seed=2)]
        alone = []
        for p in prompts:
            slot = eng.pool.alloc()
            alone.append(serve(eng, slot, p, 6)[0])
            eng.pool.free(slot)
        slots = [eng.pool.alloc(), eng.pool.alloc()]
        firsts = []
        for slot, p in zip(slots, prompts):
            firsts.append(serve(eng, slot, p, 1)[0][0])
        streams = [[f] for f in firsts]
        for _ in range(5):
            out = eng.decode([(s, st[-1], 0, 0.0, 0) for s, st in zip(slots, streams)])
            for s, st in zip(slots, streams):
                st.append(out[s])
        for slot in slots:
            eng.pool.free(slot)
        assert streams == alone

    def test_decode_steps_between_chunks_leave_the_prompt_as_it_was(self, engine):
        """A slot mid-prefill is handed null tables by the other slots'
        decode steps, in every kind: its logits are those of the same
        chunks run alone, and its garbage row routes no pair."""
        eng, reg = engine
        long, short = prompt_of(40, seed=7), prompt_of(5, seed=8)

        def chunked(between):
            slot = eng.pool.alloc()
            state, done = eng.prefill_open(slot, long), False
            while not done:
                between()
                done, _, last = eng.prefill_step(state)
            eng.pool.free(slot)
            return last

        alone = chunked(lambda: None)
        other = eng.pool.alloc()
        tok = [eng.prefill(other, short)[0]]
        routed0 = reg.counter("serving/moe_pairs_routed").value
        chunks0 = reg.counter("serving/prefill_chunks").value

        def step():
            before = reg.counter("serving/moe_pairs_routed").value
            tok.append(eng.decode([(other, tok[-1], 0, 0.0, 0)])[other])
            # one live row a step: top-2 pairs in each of the 4 layers, no more
            assert reg.counter("serving/moe_pairs_routed").value - before == 2 * 4

        with_steps = chunked(step)
        eng.pool.free(other)
        np.testing.assert_array_equal(with_steps, alone)
        assert reg.counter("serving/prefill_chunks").value - chunks0 == 5
        assert reg.counter("serving/moe_pairs_routed").value > routed0

    def test_generate_returns_the_decode_programs_own_logprobs(self, model):
        """``logprobs`` on a generate request: the first token's from
        the prefill's logits, every later one computed by the decode
        program and fetched with the token — each the reference's
        log-softmax at that position, and the tokens as without it."""
        _, params = model
        eng, _ = make_engine(model)
        batcher = ContinuousBatcher(eng).start()
        try:
            prompt = prompt_of(21, seed=4)
            plain = batcher.submit(Request(prompt=prompt, max_new_tokens=6)).result(120)
            got = batcher.submit(
                Request(prompt=prompt, max_new_tokens=6, logprobs=True)).result(120)
        finally:
            batcher.close(drain=True, timeout=30.0)
        assert plain.logprobs is None and got.tokens == plain.tokens
        seq = prompt + got.tokens
        logits, _ = REF.forward(params, seq, TINY, rows=range(20, len(seq) - 1), q_block=8)
        for k, (tok, lp) in enumerate(zip(got.tokens, got.logprobs)):
            row = logits[k] - logits[k].max()
            assert abs(lp - (row[tok] - np.log(np.exp(row).sum()))) < 2e-5, k

    def test_the_shares_add_up(self):
        """The routed parts of all expert shares plus the shared experts
        counted once equal the uncut layer (the reference holding all 8)."""
        shares = [[0, 1, 2, 3], [4, 5, 6, 7]]
        pcfg_all = program_config(held_experts=list(range(ROUTER)))
        whole = jax.jit(workload.make_task(pcfg_all).init_fn)(jax.random.PRNGKey(3))["params"]
        tokens = prompt_of(24, seed=9)
        for layer in (0, 3):  # a window layer and the full one
            x, uncut = REF.layer_parts(whole, tokens, dict(TINY, held_experts=list(range(ROUTER))), layer)
            parts = []
            for held in shares:
                cut = jax.tree.map(lambda a: a, whole)
                cut[f"h_{layer}"] = dict(cut[f"h_{layer}"])
                cut[f"h_{layer}"]["moe"] = {
                    k: (v if k == "router" else v[jnp.asarray(held)])
                    for k, v in whole[f"h_{layer}"]["moe"].items()
                }
                parts.append(REF.layer_parts(cut, tokens, dict(TINY, held_experts=held), layer)[1])
            # each share's x' = x + a + shared + its routed part; none holds nothing
            none = jax.tree.map(lambda a: a, whole)
            none[f"h_{layer}"] = dict(none[f"h_{layer}"])
            none[f"h_{layer}"]["moe"] = {
                k: (v if k == "router" else v[:0]) for k, v in whole[f"h_{layer}"]["moe"].items()
            }
            alike = REF.layer_parts(none, tokens, dict(TINY, held_experts=[]), layer)[1]
            summed = alike + sum(p - alike for p in parts)
            np.testing.assert_allclose(summed, uncut, atol=1e-5)
            assert np.abs(parts[0] - alike).max() > 1e-3  # a share does add something

    def test_the_engines_share_is_the_references_share(self, model):
        """The same, through the program: an engine that holds experts
        4..7 serves what the reference computes for that share."""
        pcfg = program_config(held_experts=[4, 5, 6, 7])
        params = jax.jit(workload.make_task(pcfg).init_fn)(jax.random.PRNGKey(0))["params"]
        eng = InferenceEngine(workload.model_config(pcfg), params, cfg=ServeConfig(**SERVE),
                              registry=MetricsRegistry())
        prompt = prompt_of(19, seed=4)
        slot = eng.pool.alloc()
        _, last = serve(eng, slot, prompt, 1)
        logits, _ = REF.forward(params, prompt, dict(TINY, held_experts=[4, 5, 6, 7]),
                                rows=[len(prompt) - 1], q_block=8)
        np.testing.assert_allclose(last, logits[0], atol=2e-5)


class TestTheExtendFamilysContextRungs:
    """ISSUE 35: an extend program per (tail bucket, context rung); a
    launch takes the smallest context rung that holds its context. The
    prefix cache is off in a pool with a window kind, so this block's
    only extend launches are the chunks of a chunked prefill."""

    @pytest.fixture(scope="class")
    def wide(self):
        """A window of 8 blocks: wider than the two lower rungs' tables."""
        pcfg = program_config(sliding_window=32)
        params = jax.jit(workload.make_task(pcfg).init_fn)(jax.random.PRNGKey(0))["params"]
        return workload.model_config(pcfg), params

    @pytest.mark.parametrize("window", [8, 32])
    @pytest.mark.parametrize("ctx,rung", extend_rungs.CASES)
    def test_a_chunk_through_a_lower_rung_is_the_whole_tables(self, model, wide, ctx, rung,
                                                              window):
        prompt = prompt_of(ctx + 7, seed=ctx)
        extend_rungs.assert_lower_rung_is_whole_tables(
            lambda: make_engine(model if window == 8 else wide)[0],
            lambda eng: extend_rungs.last_chunk(eng, prompt, ctx), rung, atol=2e-5)

    @pytest.mark.parametrize("ctx,rung", [(0, 16), (1, 16), (16, 16), (17, 32), (32, 32),
                                          (33, 64), (56, 64)])
    def test_the_smallest_rung_that_holds_the_context_and_its_tables(self, wide, ctx, rung):
        """The full kind's table has the rung's blocks, a window kind's
        no more than the ``W / BS + 1`` one query's window touches."""
        eng, _ = make_engine(wide)
        assert eng.extend_ladder == [16, 32, 64] and eng.prefill_ladder == [8]
        key, tb, tables = eng._extend_launch(0, ctx, 5)
        assert (key, tb) == ((8, rung) if rung < 64 else 8, 8)
        assert [t.shape for t in tables] == [(rung // 4,), (min(rung // 4, 32 // 4 + 1),)]
        spec = {f.name: f.shape for f in eng._specs["extend", key]}
        assert spec["ctx_table"] == [t.shape for t in tables]

    def test_every_rung_is_compiled_before_traffic_and_none_after(self, model):
        eng, _ = make_engine(model)
        counts = eng.warmup()
        assert {n for n in counts if "extend" in n} == {
            "serve_extend_T8", "serve_extend_T8_C16", "serve_extend_T8_C32"}
        assert sum(counts.values()) == eng.expected_compiles() == 1 + 3 + 3
        slot = eng.pool.alloc()
        serve(eng, slot, prompt_of(60, seed=3), 2)      # chunks over contexts 0, 8 ... 56
        eng.pool.free(slot)
        assert eng.sentinel.compile_counts() == counts
        assert eng.post_warmup_recompiles() == 0

    def test_the_counters_add_up_to_the_rungs_and_the_contexts(self, model):
        eng, reg = make_engine(model)
        slot = eng.pool.alloc()
        serve(eng, slot, prompt_of(30, seed=4), 1)      # chunks from 0, 8, 16 and 24
        serve(eng, eng.pool.alloc(), prompt_of(45, seed=5), 1)   # ... and 32, 40
        assert reg.counter(schema.EXTEND_GATHERED_TOKENS).value == (
            16 + 16 + 16 + 32) + (16 + 16 + 16 + 32 + 32 + 64)
        assert reg.counter(schema.EXTEND_CONTEXT_TOKENS).value == 48 + 120
        assert reg.counter("serving/prefill_chunks").value == 4 + 6


class TestThePoolsKinds:
    def test_two_spaces_sized_from_the_config_with_no_new_field(self, engine):
        eng, _ = engine
        pool = eng.pool
        assert pool.kinds == (None, 8) and pool.layer_kind == (1, 1, 1, 0)
        assert pool.kind_blocks(0) == 33                  # kv_blocks counts the full kind
        assert pool.kind_blocks(1) == 2 * ((8 + 8) // 4 + 1) + 1   # slots x ((W + chunk)/BS + 1) + null
        assert [a.shape for a in pool.k] == [(11, 4, 32)] * 3 + [(33, 4, 32)]  # rows are Hkv x D

    def test_window_blocks_are_released_and_only_those(self, engine):
        eng, reg = engine
        pool, window = eng.pool, eng.pool._windows[0]
        released0 = reg.counter("serving/kv_window_blocks_released_total").value
        slot = pool.alloc()
        prompt = prompt_of(41, seed=5)
        state = eng.prefill_open(slot, prompt)
        peak = 0
        done = False
        while not done:
            done, tok, _ = eng.prefill_step(state)
            peak = max(peak, window.used)
        for _ in range(12):
            tok = eng.decode([(slot, tok, 0, 0.0, 0)])[slot]
            peak = max(peak, window.used)
        n = int(pool.lengths[slot])
        assert n == 41 + 12
        # bounded whatever the context: at most (W + chunk) / BS + 1 live blocks
        assert peak <= window.per_slot == 5
        # the full kind keeps every block of the context
        assert int(pool._slot_blocks[slot]) == -(-n // 4)
        assert (pool._refcount > 0).sum() == -(-n // 4)
        # the window kind holds exactly the blocks a query at n - 1 can read
        live = np.flatnonzero(window.tables[slot])
        assert live.min() == (n - 1 - 8 + 1) // 4 and live.max() == (n - 1) // 4
        assert reg.counter("serving/kv_window_blocks_released_total").value - released0 \
            == live.min()
        assert reg.gauge("serving/kv_blocks_in_use_window").value == len(live)
        assert pool.used_bytes() == (pool._refcount > 0).sum() * pool.bytes_per_block(0) \
            + len(live) * pool.bytes_per_block(1)
        pool.free(slot)
        assert free_lists_whole(pool)

    def test_free_lists_whole_after_finish_preemption_and_reset(self, model):
        eng, reg = make_engine(model)
        eng.warmup()
        batcher = ContinuousBatcher(eng).start()
        try:
            futures = [batcher.submit(Request(prompt=prompt_of(n, seed=n), max_new_tokens=5,
                                              slo="batch")) for n in (30, 9, 22, 44)]
            # an interactive arrival evicts a batch request from its slot
            urgent = batcher.submit(Request(prompt=prompt_of(13, seed=7), max_new_tokens=3,
                                            slo="interactive"))
            for f in (*futures, urgent):
                assert len(f.result(timeout=120).tokens) in (3, 5)
        finally:
            batcher.close(drain=True, timeout=60.0)
        assert eng.pool.active_slots == 0 and free_lists_whole(eng.pool)
        assert reg.counter("serving/kv_exhausted_total").value == 0
        slot = eng.pool.alloc()
        serve(eng, slot, prompt_of(25, seed=8), 3)
        assert not free_lists_whole(eng.pool)
        eng.pool.reset()
        assert free_lists_whole(eng.pool) and eng.pool.active_slots == 0

    def test_block_exhausted_counts_the_full_space(self, model):
        eng, _ = make_engine(model, kv_blocks=9)   # 8 usable blocks = 32 tokens
        slot = eng.pool.alloc()
        with pytest.raises(paged_kv.BlockExhausted):
            eng.prefill_open(slot, prompt_of(40, seed=1))
        assert free_lists_whole(eng.pool)         # all-or-nothing: nothing leaked

    def test_no_prompt_block_is_shared_across_requests(self, engine):
        """Cross-request prefix sharing with a window kind present: the
        pool neither publishes nor serves a prefix (a hit would find
        released blocks); chunked prefill within a request still runs."""
        eng, reg = engine
        prompt = prompt_of(33, seed=6)
        chunks0 = reg.counter("serving/prefill_chunks").value
        for _ in range(2):
            slot = eng.pool.alloc()
            serve(eng, slot, prompt, 2)
            eng.pool.free(slot)
        assert reg.counter("serving/prefill_chunks").value - chunks0 == 2 * 5
        assert reg.counter("serving/prefix_reused_tokens").value == 0
        assert not eng.pool.prefix_cache_enabled and not eng.pool._cache
        assert eng.pool.prefix_lookup(prompt) == ([], 0)
        assert eng.pool.prefix_digest()["blocks"] == 0

    def test_one_kind_pool_is_as_it_was(self):
        pool = paged_kv.PagedKVPool(num_layers=2, num_slots=2, num_heads=2, max_len=32,
                                    head_dim=8, block_size=4, registry=MetricsRegistry())
        assert pool.kinds == (None,) and pool.layer_kind == (0, 0) and not pool._windows
        assert pool.prefix_cache_enabled
        pool.ensure_span(0, 0, 16)                 # nothing to do for a pool of one kind
        assert pool.bytes_per_block() == pool.bytes_per_block(0) == 2 * 2 * 4 * 16 * 4


REFUSED = [
    (dict(spec_decode_k=2), "speculative verify"),
    (dict(role="prefill"), "KV page export/import"),
    (dict(role="decode"), "KV page export/import"),
    (dict(kv_dtype="int8"), "quantized KV"),
    (dict(weight_dtype="int8"), "weight quantization"),
    (dict(attention="paged_flash"), "paged_flash"),
    (dict(attention="flash"), "flash prefill"),
]


class TestWhatIsRefused:
    @pytest.mark.parametrize("over,mechanism", REFUSED, ids=[m for _, m in REFUSED])
    def test_refused_by_name_at_construction(self, model, over, mechanism):
        with pytest.raises(NotImplementedError, match="cohere2_moe") as e:
            make_engine(model, **over)
        assert mechanism in str(e.value) and "GPT-2 only" in str(e.value)

    def test_block_size_zero_is_refused_as_for_any_block(self, model):
        """Not a mechanism that serves GPT-2 only: the dense pool serves nobody."""
        with pytest.raises(ValueError, match="dense .* pool .* is gone"):
            make_engine(model, kv_block_size=0, kv_blocks=0, prefill_chunk_tokens=0)

    def test_sharded_serving_is_refused(self, model):
        mcfg, params = model
        with pytest.raises(NotImplementedError, match="sharded serving"):
            InferenceEngine(mcfg, params, cfg=ServeConfig(**SERVE), sharding=object())

    @pytest.mark.parametrize("what", ["export", "import"])
    def test_page_handoff_is_refused_when_called(self, engine, what):
        eng, _ = engine
        with pytest.raises(NotImplementedError, match=f"KV page {what}"):
            if what == "export":
                eng.export_kv_pages(0, [1, 2, 3])
            else:
                eng.import_kv_pages(0, {}, [1, 2, 3])

    def test_a_config_of_neither_model_is_refused(self):
        with pytest.raises(TypeError, match="no serving block"):
            InferenceEngine(object(), {}, cfg=ServeConfig())
