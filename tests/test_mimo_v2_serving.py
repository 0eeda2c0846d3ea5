"""Serving MiMo-V2.5's block (ISSUE 34) at a toy width through the
engine's paged path — cache rows by kind (1 KV head in full layers, 2
in window layers, keys of 24 beside values of 16), rotary on the first
8 dimensions with a base a kind, a sink logit in the window layers'
softmax, a dense first layer, then expert layers that hold some of the
experts under a biased choice — against the plain reference
(``benchmark/reference/mimo_v2.py``).

Sizes: hidden 64, 4 query heads, window 8, block 4, layers F,W,W,F,W
(the first dense), 8 experts top 2 with 4 held, a 128-row vocabulary.
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
from tensorflow_examples_tpu.serving import blocks, kv_cache, paged_kv  # noqa: E402
from tensorflow_examples_tpu.serving import engine as engine_mod  # noqa: E402
from tensorflow_examples_tpu.serving.engine import (  # noqa: E402
    InferenceEngine,
    ServeConfig,
)
from tensorflow_examples_tpu.telemetry import schema, spans  # noqa: E402
from tensorflow_examples_tpu.telemetry.registry import MetricsRegistry  # noqa: E402
from tensorflow_examples_tpu.workloads import mimo_v2 as workload  # noqa: E402

REF = spec.reference("mimo_v2")
# The configuration-file keys the reference reads, at the toy width.
TINY = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=1, head_dim=24, v_head_dim=16,
    rope_theta=1e7, add_full_attention_sink_bias=False,
    swa_num_attention_heads=4, swa_num_key_value_heads=2, swa_head_dim=24, swa_v_head_dim=16,
    swa_rope_theta=1e4, add_swa_attention_sink_bias=True, sliding_window=8,
    partial_rotary_factor=0.334, attention_value_scale=0.707, layernorm_epsilon=1e-5,
    intermediate_size=96, moe_intermediate_size=32, num_experts_per_tok=2,
    hybrid_layer_pattern=[0, 1, 1, 0, 1], moe_layer_freq=[0, 1, 1, 1, 1],
    num_hidden_layers=5, held_experts=[0, 1, 2, 3], vocab_size=128,
)
ROUTER = 8
SERVE = dict(max_slots=2, kv_block_size=4, kv_blocks=33, prefill_bucket_floor=8,
             kv_bucket_floor=16, prefill_chunk_tokens=8)
TOL = 2e-5


def program_config(**over):
    sizes = {k: (tuple(v) if isinstance(v, list) else v) for k, v in {**TINY, **over}.items()}
    return workload.MimoV2ServeConfig(
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


# ------------------------------------------------------------------ (a)


class TestAgainstTheReference:
    @pytest.mark.parametrize("n", [5, 8, 21, 30, 50])
    def test_logits_through_prefill_chunked_extend_and_paged_decode(self, engine, model, n):
        """Prompts shorter than a chunk (the prefill rung), of one chunk,
        and longer than the window plus two chunks (several extends,
        window blocks released mid-prompt): the first LOGITS and every
        greedy token's are the reference's full forward."""
        eng, reg = engine
        _, params = model
        released0 = reg.counter("serving/kv_window_blocks_released_total").value
        prompt = prompt_of(n, seed=n)
        slot = eng.pool.alloc()
        toks, last = serve(eng, slot, prompt, 8)
        eng.pool.free(slot)
        seq = prompt + toks
        logits, _ = REF.forward(params, seq, TINY, rows=range(n - 1, len(seq) - 1), q_block=8)
        np.testing.assert_allclose(last, logits[0], atol=TOL)
        for k, tok in enumerate(toks):
            assert logits[k].max() - logits[k][tok] < TOL, (n, k)
        if n > 8 + 2 * 8:
            assert reg.counter("serving/kv_window_blocks_released_total").value > released0
        assert eng.post_warmup_recompiles() == 0 and free_lists_whole(eng.pool)

    def test_decode_logprobs_are_the_references(self, model):
        """The decode program's own log-probabilities (the paged rows by
        kind, the window gather, the sink in the decode softmax)."""
        from tensorflow_examples_tpu.serving.batcher import ContinuousBatcher, Request

        _, params = model
        eng, _ = make_engine(model)
        batcher = ContinuousBatcher(eng).start()
        try:
            prompt = prompt_of(29, seed=4)
            got = batcher.submit(
                Request(prompt=prompt, max_new_tokens=7, logprobs=True)).result(120)
        finally:
            batcher.close(drain=True, timeout=30.0)
        seq = prompt + got.tokens
        logits, _ = REF.forward(params, seq, TINY, rows=range(28, len(seq) - 1), q_block=8)
        for k, (tok, lp) in enumerate(zip(got.tokens, got.logprobs)):
            row = logits[k] - logits[k].max()
            assert abs(lp - (row[tok] - np.log(np.exp(row).sum()))) < TOL, k

    @pytest.mark.parametrize("ctx,rung", extend_rungs.CASES)
    def test_a_chunk_through_a_lower_context_rung_is_the_whole_tables(self, model, ctx,
                                                                      rung):
        """ISSUE 35: the chunk that starts at ``ctx`` takes the smallest
        context rung that holds it and gives the tokens and logits of the
        same chunk through the whole-table program — rows by kind, the
        sink in the window layers. (A window kind is present, so the
        prefix cache is off: chunks are this block's only extend
        launches.)"""
        prompt = prompt_of(ctx + 7, seed=ctx)
        extend_rungs.assert_lower_rung_is_whole_tables(
            lambda: make_engine(model)[0],
            lambda eng: extend_rungs.last_chunk(eng, prompt, ctx), rung, atol=TOL)

    def test_two_requests_decode_together_as_they_do_alone(self, engine):
        eng, _ = engine
        prompts = [prompt_of(27, seed=1), prompt_of(11, seed=2)]
        alone = []
        for p in prompts:
            slot = eng.pool.alloc()
            alone.append(serve(eng, slot, p, 6)[0])
            eng.pool.free(slot)
        slots = [eng.pool.alloc(), eng.pool.alloc()]
        streams = [[serve(eng, slot, p, 1)[0][0]] for slot, p in zip(slots, prompts)]
        for _ in range(5):
            out = eng.decode([(s, st[-1], 0, 0.0, 0) for s, st in zip(slots, streams)])
            for s, st in zip(slots, streams):
                st.append(out[s])
        for slot in slots:
            eng.pool.free(slot)
        assert streams == alone


# ------------------------------------------------------------------ (b)


LEFT_OUT = {
    "the sink dropped": dict(add_swa_attention_sink_bias=False),
    "the value scale dropped": dict(attention_value_scale=1.0),
    "the kinds' rotary bases swapped": dict(rope_theta=1e4, swa_rope_theta=1e7),
    "rotary over the whole head": dict(partial_rotary_factor=1.0),
    "the window one key wider": dict(sliding_window=9),
}


class TestTheToleranceCatchesWhatIsLeftOut:
    @pytest.fixture(scope="class")
    def served(self, engine):
        eng, _ = engine
        prompt = prompt_of(30, seed=11)
        slot = eng.pool.alloc()
        toks, last = serve(eng, slot, prompt, 4)
        eng.pool.free(slot)
        return prompt, toks, last

    @pytest.mark.parametrize("what", sorted(LEFT_OUT))
    def test_a_reference_without_it_is_out_of_tolerance(self, served, model, what):
        """Each piece of the mathematics moves the logits by far more
        than the tolerance the served path is held to: leaving it out of
        the program could not pass test (a)."""
        _, params = model
        prompt, toks, last = served
        wrong = dict(TINY, **LEFT_OUT[what])
        right, _ = REF.forward(params, prompt + toks, TINY, rows=[len(prompt) - 1], q_block=8)
        other, _ = REF.forward(params, prompt + toks, wrong, rows=[len(prompt) - 1], q_block=8)
        assert np.abs(last - right[0]).max() < TOL
        assert np.abs(last - other[0]).max() > 10 * TOL, what


# ------------------------------------------------------------------ (c)


class TestTheShare:
    def test_the_shares_add_up(self):
        """The routed parts of all disjoint expert shares (there is no
        shared expert) over what every chip computes alike equal the
        uncut layer (the reference holding all 8)."""
        shares = [[0, 1, 2], [3, 4, 5, 6], [7]]
        everything = list(range(ROUTER))
        pcfg_all = program_config(held_experts=everything)
        whole = jax.jit(workload.make_task(pcfg_all).init_fn)(jax.random.PRNGKey(3))["params"]
        tokens = prompt_of(24, seed=9)

        def holding(layer, held):
            cut = dict(whole)
            cut[f"h_{layer}"] = dict(cut[f"h_{layer}"])
            cut[f"h_{layer}"]["moe"] = {
                k: (v if k in ("router", "bias") else v[jnp.asarray(held, jnp.int32)])
                for k, v in whole[f"h_{layer}"]["moe"].items()
            }
            return REF.layer_parts(cut, tokens, dict(TINY, held_experts=held), layer)[1]

        for layer in (2, 3):  # a window expert layer and the full one
            _, uncut = REF.layer_parts(whole, tokens, dict(TINY, held_experts=everything), layer)
            alike = holding(layer, [])  # x + attention: what every chip computes alike
            parts = [holding(layer, held) for held in shares]
            np.testing.assert_allclose(alike + sum(p - alike for p in parts), uncut, atol=1e-5)
            assert all(np.abs(p - alike).max() > 1e-3 for p in parts)  # every share adds

    def test_the_engines_share_is_the_references_share(self):
        """Through the program: an engine that holds experts 4..7 serves
        what the reference computes for that share."""
        pcfg = program_config(held_experts=[4, 5, 6, 7])
        params = jax.jit(workload.make_task(pcfg).init_fn)(jax.random.PRNGKey(0))["params"]
        eng = InferenceEngine(workload.model_config(pcfg), params, cfg=ServeConfig(**SERVE),
                              registry=MetricsRegistry())
        prompt = prompt_of(19, seed=4)
        slot = eng.pool.alloc()
        _, last = serve(eng, slot, prompt, 1)
        logits, _ = REF.forward(params, prompt, dict(TINY, held_experts=[4, 5, 6, 7]),
                                rows=[len(prompt) - 1], q_block=8)
        np.testing.assert_allclose(last, logits[0], atol=TOL)

    def test_the_expert_counters_are_the_blocks_own(self, model):
        eng, reg = make_engine(model)
        slot = eng.pool.alloc()
        serve(eng, slot, prompt_of(13, seed=3), 3)
        # 4 expert layers x top 2: 13 prompt tokens (chunks of 8 and 5), then 2 decode steps
        assert reg.counter("serving/moe_pairs_routed").value == 4 * 2 * (13 + 2)
        held = sum(reg.counter(f"serving/moe_pairs_expert_{e}").value for e in range(4))
        assert 0 < held == reg.counter("serving/moe_pairs_held").value < 4 * 2 * 15


# ------------------------------------------------------------------ (d)


def kinds_pool(**over):
    """A pool of MiMo's shape: full layers of (4, 3)-wide rows, window
    layers of (8, 6), W = 8 x BS."""
    kw = dict(num_layers=4, num_slots=2, num_heads=1, max_len=256, head_dim=4, block_size=4,
              num_blocks=65, registry=MetricsRegistry(), layer_windows=(None, 32, 32, None),
              window_span=16, rows=((4, 3), (8, 6), (8, 6), (4, 3)))
    return paged_kv.PagedKVPool(**{**kw, **over})


class TestThePoolsRowsByKind:
    def test_each_kinds_arrays_have_its_own_rows_and_bytes(self, engine):
        eng, _ = engine
        pool = eng.pool
        assert pool.kinds == (None, 8) and pool.layer_kind == (0, 1, 1, 0, 1)
        assert pool.rows is None and pool.kind_rows == ((24, 16), (48, 32))
        nb_window = 2 * ((8 + 8) // 4 + 1) + 1
        assert [a.shape for a in pool.k] == [
            (33, 4, 24), (nb_window, 4, 48), (nb_window, 4, 48), (33, 4, 24), (nb_window, 4, 48)]
        assert [a.shape for a in pool.v] == [
            (33, 4, 16), (nb_window, 4, 32), (nb_window, 4, 32), (33, 4, 16), (nb_window, 4, 32)]
        assert pool.bytes_per_block(0) == 2 * 4 * (24 + 16) * 4
        assert pool.bytes_per_block(1) == 3 * 4 * (48 + 32) * 4
        assert pool.bytes_per_block() == pool.bytes_per_block(0) + pool.bytes_per_block(1)
        for kind in (0, 1):
            assert pool.bytes_per_block(kind) * pool.kind_blocks(kind) == sum(
                a.nbytes for arrs in pool.kv_state()
                for a, k in zip(arrs, pool.layer_kind) if k == kind)
        assert [pool.kind_name(k) for k in (0, 1)] == ["full", "window8"]

    def test_used_bytes_count_each_kind_by_its_own_row(self, engine):
        eng, reg = engine
        pool = eng.pool
        before = {n: reg.counter(n).value for n in (
            "serving/kv_sampled_bytes", "serving/kv_sampled_bytes_kind_full",
            "serving/kv_sampled_bytes_kind_window8", "serving/kv_sampled_tokens",
            "serving/decode_gathered_tokens", "serving/kv_sampled_reach_bytes")}
        slot = pool.alloc()
        serve(eng, slot, prompt_of(21, seed=5), 2)   # one decode step at position 21, on K32
        full, window = -(-22 // 4), len(np.flatnonzero(pool._windows[0].tables[slot]))
        assert pool.used_bytes_by_kind() == [
            full * pool.bytes_per_block(0), window * pool.bytes_per_block(1)]
        assert pool.used_bytes() == sum(pool.used_bytes_by_kind())
        got = {n: reg.counter(n).value - v for n, v in before.items()}
        assert got["serving/kv_sampled_bytes_kind_full"] == full * pool.bytes_per_block(0)
        assert got["serving/kv_sampled_bytes_kind_window8"] == window * pool.bytes_per_block(1)
        assert got["serving/kv_sampled_bytes"] == pool.used_bytes()
        assert got["serving/kv_sampled_tokens"] == 22 and got["serving/decode_gathered_tokens"] == 32
        # each layer's own row of VALUES, inside its kind's reach: 22 rows full, 8 window
        assert got["serving/kv_sampled_reach_bytes"] == 4 * (2 * 40 * 22 + 3 * 80 * 8)
        pool.free(slot)

    @pytest.mark.parametrize("context", [40, 100, 250])
    def test_a_window_of_eight_blocks_holds_no_more_than_its_bound(self, context):
        """W = 8 x BS, the chunk 4 x BS: whatever the context, a slot
        never holds more than (W + span) / BS + 1 window blocks, through
        chunks and decode steps alike; the full kind keeps them all."""
        pool = kinds_pool()
        window = pool._windows[0]
        assert window.per_slot == (32 + 16) // 4 + 1 and pool.kind_blocks(1) == 2 * 13 + 1
        slot = pool.alloc()
        pool.claim_prompt_blocks(slot, list(range(context - 6)))
        peak = 0
        for start in range(0, context - 6, 16):
            pool.ensure_span(slot, start, min(start + 16, context - 6))
            peak = max(peak, window.used)
        for position in range(context - 6, context):
            pool.ensure_position(slot, position)
            peak = max(peak, window.used)
        assert peak <= window.per_slot
        live = np.flatnonzero(window.tables[slot])
        assert live.min() == max(context - 1 - 32 + 1, 0) // 4 and live.max() == (context - 1) // 4
        assert int(pool._slot_blocks[slot]) == -(-context // 4)
        pool.free(slot)
        assert free_lists_whole(pool)

    def test_rows_that_differ_inside_a_kind_are_refused(self):
        with pytest.raises(ValueError, match="ONE row shape"):
            kinds_pool(rows=((4, 3), (8, 6), (8, 7), (4, 3)))
        with pytest.raises(ValueError, match="as many arrays"):
            kinds_pool(rows=((4, 3), (8, 6), (8,), (4, 3)))
        with pytest.raises(ValueError, match="for 4 layers"):
            kinds_pool(rows=((4, 3), (8, 6)))

    @pytest.mark.parametrize("rows", [None, (16, 16), ((16, 16),) * 3], ids=repr)
    def test_a_one_row_shape_pool_is_array_for_array_what_it_was(self, rows):
        """Rows omitted, given once, or given per layer and all alike:
        the arrays, kinds and bytes a pool has always had."""
        pool = paged_kv.PagedKVPool(
            num_layers=3, num_slots=2, num_heads=2, max_len=32, head_dim=8, block_size=4,
            registry=MetricsRegistry(), layer_windows=(8, None, 8), window_span=8, rows=rows)
        assert pool.rows == (16, 16) and pool.layer_rows == ((16, 16),) * 3
        assert pool.kinds == (None, 8) and pool.layer_kind == (1, 0, 1)
        assert pool.kind_rows == ((16, 16), (16, 16)) and len(pool.kv_state()) == 2
        nb = 2 * ((8 + 8) // 4 + 1) + 1
        for arrs in pool.kv_state():
            assert [a.shape for a in arrs] == [(nb, 4, 16), (17, 4, 16), (nb, 4, 16)]
            assert all(a.dtype == jnp.float32 for a in arrs)
        assert pool.bytes_per_block(0) == 1 * 4 * 32 * 4 and pool.bytes_per_block(1) == 2 * 4 * 32 * 4
        assert pool.bytes_per_block() == 3 * 4 * 32 * 4

    def test_a_quantized_pool_keeps_its_scales_and_refuses_rows_by_kind(self):
        pool = paged_kv.PagedKVPool(num_layers=2, num_slots=2, num_heads=2, max_len=32,
                                    head_dim=8, block_size=4, kv_dtype="int8",
                                    registry=MetricsRegistry())
        assert len(pool.kv_state()) == 4 and pool.k_scale[0].shape == (17, 4, 2)
        assert pool.bytes_per_block() == 2 * 4 * (32 + 2 * 2 * 4)
        with pytest.raises(ValueError, match="no such heads"):
            kinds_pool(kv_dtype="int8")


# ------------------------------------------------------------------ (e)


def _old_grouped_decode(q, k_blocks, v_blocks, positions, block_tables, *, num_kv_heads,
                        window=None, sm_scale=None):
    """``kv_cache.grouped_decode_attention`` as it was before ISSUE 34."""
    s_n, h, d = q.shape
    g = num_kv_heads
    k, v = kv_cache.gather_layer_kv(k_blocks, v_blocks, block_tables, g, q.dtype)
    if sm_scale is None:
        sm_scale = d ** -0.5
    scores = jnp.einsum(
        "sgrd,skgd->sgrk", q.reshape(s_n, g, h // g, d), k,
        preferred_element_type=jnp.float32,
    ) * sm_scale
    base = kv_cache.window_base(positions, window, k_blocks.shape[1])
    key_pos = jnp.reshape(base, (-1, 1)) + jnp.arange(k.shape[1])[None, :]
    ok = kv_cache._window_ok(positions[:, None], key_pos, window)
    scores = jnp.where(ok[:, None, None, :], scores, kv_cache.NEG_INF)
    p = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum(
        "sgrk,skgd->sgrd", p, v, preferred_element_type=jnp.float32
    ).astype(q.dtype).reshape(s_n, h, d)


def _old_grouped_chunk(q, k, v, k_ctx=None, v_ctx=None, *, ctx_len=0, ctx_base=0, window=None,
                       sm_scale=None):
    """``kv_cache.grouped_chunk_attention`` as it was before ISSUE 34."""
    t_n, h, d = q.shape
    g = k.shape[1]
    if sm_scale is None:
        sm_scale = d ** -0.5
    q_pos = ctx_len + jnp.arange(t_n)
    ok_tail = kv_cache._window_ok(q_pos[:, None], q_pos[None, :], window)
    if k_ctx is not None:
        c_pos = ctx_base + jnp.arange(k_ctx.shape[0])
        ok_ctx = kv_cache._window_ok(q_pos[:, None], c_pos[None, :], window) & (
            c_pos < ctx_len)[None, :]

    def one_group(args):
        qg, kg, vg, kcg, vcg = args
        pieces = [(kg, vg, ok_tail)]
        if kcg is not None:
            pieces.insert(0, (kcg, vcg, ok_ctx))
        prob = jax.nn.softmax(jnp.concatenate([
            jnp.where(ok[None], jnp.einsum(
                "rtd,kd->rtk", qg, kx, preferred_element_type=jnp.float32
            ) * sm_scale, kv_cache.NEG_INF)
            for kx, _, ok in pieces
        ], axis=-1), axis=-1)
        out, col = None, 0
        for _, vx, _ in pieces:
            part = jnp.einsum(
                "rtk,kd->rtd", prob[..., col:col + vx.shape[0]].astype(vx.dtype), vx,
                preferred_element_type=jnp.float32)
            out = part if out is None else out + part
            col += vx.shape[0]
        return out

    by_group = lambda x: None if x is None else jnp.moveaxis(x, 1, 0)  # noqa: E731
    out = jax.lax.map(one_group, (
        jnp.moveaxis(q.reshape(t_n, g, h // g, d), (1, 2), (0, 1)),
        by_group(k), by_group(v), by_group(k_ctx), by_group(v_ctx)))
    return jnp.moveaxis(out, 2, 0).reshape(t_n, h, d).astype(q.dtype)


def _normal(seed, *shape, dtype=jnp.float32):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape), dtype)


def _dense_attention(q, k, v, ok, sm_scale, sinks=None):
    """softmax(q k) v in numpy float64: q [T, H, D], k [C, G, D], v
    [C, G, Dv], ok [T, C]; head n reads KV head n // (H / G)."""
    q, k, v = (np.asarray(x, np.float64) for x in (q, k, v))
    h, g = q.shape[1], k.shape[1]
    out = np.zeros((q.shape[0], h, v.shape[-1]))
    for n in range(h):
        s = np.where(ok, q[:, n] @ k[:, n // (h // g)].T * sm_scale, -np.inf)
        top = s.max(-1, keepdims=True)
        if sinks is not None:
            top = np.maximum(top, float(sinks[n]))
        e = np.exp(s - top)
        den = e.sum(-1, keepdims=True) + (0.0 if sinks is None else np.exp(float(sinks[n]) - top))
        out[:, n] = (e / den) @ v[:, n // (h // g)]
    return out


class TestTheGroupedAttentions:
    @pytest.mark.parametrize("window", [None, 8])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
    def test_decode_without_sinks_at_equal_widths_is_bit_for_bit_what_it_was(self, window, dtype):
        q = _normal(0, 3, 4, 16, dtype=dtype)
        k_blocks, v_blocks = _normal(1, 9, 4, 32, dtype=dtype), _normal(2, 9, 4, 32, dtype=dtype)
        positions = jnp.asarray([5, 14, 9], jnp.int32)
        tables = jnp.asarray([[1, 2, 0, 0], [3, 4, 5, 6], [7, 8, 1, 0]], jnp.int32)
        if window is not None:
            tables = tables[:, :3]
        args = (q, k_blocks, v_blocks, positions, tables)
        kw = dict(num_kv_heads=2, window=window)
        new = jax.jit(lambda *a: kv_cache.grouped_decode_attention(*a, **kw, sinks=None))
        old = jax.jit(lambda *a: _old_grouped_decode(*a, **kw))
        np.testing.assert_array_equal(np.asarray(new(*args), np.float32),
                                      np.asarray(old(*args), np.float32))
        assert str(jax.make_jaxpr(new)(*args)) == str(jax.make_jaxpr(old)(*args))

    @pytest.mark.parametrize("window", [None, 8])
    @pytest.mark.parametrize("context", [False, True], ids=["prefill", "extend"])
    def test_chunk_without_sinks_at_equal_widths_is_bit_for_bit_what_it_was(self, window, context):
        q, k, v = _normal(0, 8, 4, 16), _normal(1, 8, 2, 16), _normal(2, 8, 2, 16)
        ctx = (_normal(3, 12, 2, 16), _normal(4, 12, 2, 16)) if context else ()
        kw = dict(window=window, **(dict(ctx_len=9, ctx_base=0) if context else {}))
        new = jax.jit(lambda *a: kv_cache.grouped_chunk_attention(*a, **kw, sinks=None))
        old = jax.jit(lambda *a: _old_grouped_chunk(*a, **kw))
        np.testing.assert_array_equal(new(q, k, v, *ctx), old(q, k, v, *ctx))
        assert str(jax.make_jaxpr(new)(q, k, v, *ctx)) == str(jax.make_jaxpr(old)(q, k, v, *ctx))

    @pytest.mark.parametrize("sink", [False, True], ids=["no sink", "sink"])
    @pytest.mark.parametrize("window", [None, 8])
    def test_values_narrower_than_keys_and_a_sink_against_plain_numpy(self, window, sink):
        """Keys of 24 beside values of 16, a sink logit a head: the chunk
        form over a context and the decode form through a table both
        give softmax-with-sink(q k) v."""
        t_n, c_n, h, g, d, dv = 8, 12, 4, 2, 24, 16
        q, k, v = _normal(0, t_n, h, d), _normal(1, t_n, g, d), _normal(2, t_n, g, dv)
        kc, vc = _normal(3, c_n, g, d), _normal(4, c_n, g, dv)
        sinks = _normal(5, h) if sink else None
        ctx_len = 10
        got = kv_cache.grouped_chunk_attention(
            q, k, v, kc, vc, ctx_len=ctx_len, window=window, sm_scale=d ** -0.5, sinks=sinks)
        assert got.shape == (t_n, h, dv)
        q_pos = ctx_len + np.arange(t_n)
        k_pos = np.concatenate([np.arange(c_n), q_pos])
        ok = (k_pos[None] <= q_pos[:, None]) & np.concatenate(
            [np.arange(c_n) < ctx_len, np.ones(t_n, bool)])[None]
        if window is not None:
            ok &= q_pos[:, None] - k_pos[None] < window
        want = _dense_attention(q, np.concatenate([kc, k]), np.concatenate([vc, v]), ok,
                                d ** -0.5, sinks)
        np.testing.assert_allclose(got, want, atol=1e-5)
        # decode: the same rows as blocks of 4, one slot's query at the last position
        rows_k = np.concatenate([kc[:ctx_len], k]).reshape(-1, g * d)
        rows_v = np.concatenate([vc[:ctx_len], v]).reshape(-1, g * dv)
        pad = (-len(rows_k)) % 4
        k_blocks = jnp.asarray(np.pad(rows_k, ((4, pad), (0, 0))).reshape(-1, 4, g * d))
        v_blocks = jnp.asarray(np.pad(rows_v, ((4, pad), (0, 0))).reshape(-1, 4, g * dv))
        position = ctx_len + t_n - 1
        first = 0 if window is None else max(position - window + 1, 0) // 4
        table = jnp.arange(first + 1, k_blocks.shape[0], dtype=jnp.int32)[None]
        one = kv_cache.grouped_decode_attention(
            q[-1:], k_blocks, v_blocks, jnp.asarray([position], jnp.int32), table,
            num_kv_heads=g, window=window, sm_scale=d ** -0.5, sinks=sinks)
        np.testing.assert_allclose(one[0], want[-1], atol=1e-5)

    def test_a_slot_that_sees_nothing_gives_zeros_not_nans_under_a_sink(self):
        """A parked slot's row (null table, position 0 masked by a
        window that starts later) must stay finite: the sink alone
        holds the mass."""
        q = _normal(0, 1, 4, 24)
        blocks_k, blocks_v = jnp.zeros((3, 4, 48)), jnp.ones((3, 4, 32))
        out = kv_cache._softmax_with_sink(jnp.full((1, 2, 2, 8), kv_cache.NEG_INF),
                                          _normal(1, 4).reshape(1, 2, 2, 1))
        assert np.all(np.asarray(out) == 0)
        got = kv_cache.grouped_decode_attention(
            q, blocks_k, blocks_v, jnp.asarray([0], jnp.int32), jnp.zeros((1, 3), jnp.int32),
            num_kv_heads=2, window=8, sinks=_normal(1, 4))
        assert np.all(np.isfinite(np.asarray(got)))


# --------------------------------------------------- the interface, the record


REFUSED = [
    (dict(spec_decode_k=2), "speculative verify"),
    (dict(role="prefill"), "KV page export/import"),
    (dict(kv_dtype="int8"), "quantized KV"),
    (dict(weight_dtype="int8"), "weight quantization"),
    (dict(attention="paged_flash"), "paged_flash"),
    (dict(attention="flash"), "flash prefill"),
]


class TestWhatTheBlockTellsTheEngine:
    def test_per_layer_heads_widths_scale_and_sink(self, model):
        mcfg, _ = model
        block = blocks.block_for(mcfg)
        assert isinstance(block, blocks.MimoV2Block) and not block.own_attention
        full = blocks.LayerAttention(4, 1, 24, 16, 24 ** -0.5, False)
        window = blocks.LayerAttention(4, 2, 24, 16, 24 ** -0.5, True)
        assert block.layer_attention == (full, window, window, full, window)
        assert block.cache_rows == tuple(a.rows for a in block.layer_attention)
        assert block.cache_rows[0] == ((1, 24), (1, 16)) and block.cache_rows[1] == ((2, 24), (2, 16))
        assert block.row_values == (40, 80, 80, 40, 80)
        assert block.layer_windows == (None, 8, 8, None, 8) and mcfg.rotary_dim == 8
        assert block.stats_len == 4 + 2

    def test_the_other_blocks_say_the_same_of_every_layer(self):
        from tensorflow_examples_tpu.models.transformer import TransformerConfig

        gpt2 = blocks.block_for(TransformerConfig(
            vocab_size=64, max_len=32, d_model=32, num_layers=3, num_heads=4))
        assert gpt2.layer_attention == (blocks.LayerAttention(4, 4, 8, 8, 8 ** -0.5),) * 3
        assert gpt2.cache_rows == (((4, 8), (4, 8)),) * 3

    def test_the_published_sizes_give_the_issues_parameter_count(self):
        """3.430 B parameters, 6.86 GB in bfloat16, from the
        configuration file through the workload (shapes only)."""
        config = spec._load_json(os.path.join(spec.HERE, "configs", "mimo-v2.5.json"))
        pcfg = spec.program_config(config)
        shapes = jax.eval_shape(workload.make_task(pcfg).init_fn, jax.random.PRNGKey(0))["params"]
        n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
        assert abs(n - 3.430e9) < 1e6
        nbytes = sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(shapes))
        assert abs(nbytes - 6.86e9) < 5e6
        mcfg = workload.model_config(pcfg)
        block = blocks.block_for(mcfg)
        assert mcfg.layer_windows == (None, 128, 128, 128, 128, None, 128) and mcfg.rotary_dim == 64
        assert [tuple(h * w for h, w in row) for row in block.cache_rows[:2]] == [
            (768, 512), (1536, 1024)]
        # a resident token: 2 full layers for good, 5 window layers for 128 positions
        full = sum(v for v, w in zip(block.row_values, block.layer_windows) if w is None)
        assert 2 * full == 5120 and 2 * (sum(block.row_values) - full) == 25600

    def test_kind_plan_is_recorded_once_per_traced_program(self, model):
        engine_mod._record_kind_plan.cache_clear()
        before = len([e for e in spans._default.events() if e["name"] == schema.KIND_PLAN_SPAN])
        eng, _ = make_engine(model)
        eng.warmup()
        plans = [e["args"] for e in spans._default.events()
                 if e["name"] == schema.KIND_PLAN_SPAN][before:]
        assert sorted((p["family"], p["rung"]) for p in plans) == [
            ("decode", 16), ("decode", 32), ("decode", 64),
            ("extend", 8), ("extend", 8), ("extend", 8), ("prefill", 8)]
        # one extend program a context rung, told apart by the columns
        # of the full kind's table; the window kind's stay W / BS + 1
        assert sorted(
            [k["table_blocks"] for k in p["kinds"]] for p in plans if p["family"] == "extend"
        ) == [[4, 3], [8, 3], [16, 3]]
        assert all(set(p) == set(schema.KIND_PLAN_ARGS) for p in plans)
        nb_window = 2 * ((8 + 8) // 4 + 1) + 1
        decode = next(p for p in plans if (p["family"], p["rung"]) == ("decode", 64))
        assert decode["kinds"] == [
            dict(window=None, kv_heads=1, k_row=24, v_row=16, sink=False, blocks=33,
                 table_blocks=16),
            dict(window=8, kv_heads=2, k_row=48, v_row=32, sink=True, blocks=nb_window,
                 table_blocks=3)]
        assert all(set(k) == set(schema.KIND_PLAN_KIND_KEYS) for p in plans for k in p["kinds"])

    @pytest.mark.parametrize("over,mechanism", REFUSED, ids=[m for _, m in REFUSED])
    def test_what_the_engine_refuses_it_refuses_by_name(self, model, over, mechanism):
        with pytest.raises(NotImplementedError, match="mimo_v2") as e:
            make_engine(model, **over)
        assert mechanism in str(e.value) and "GPT-2 only" in str(e.value)
