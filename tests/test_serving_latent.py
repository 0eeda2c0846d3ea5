"""Serving a block whose cache row is its own (ISSUE 32): GLM-4.7-Flash
at a toy width through the engine's paged path — a latent row a token
(no heads, no V), attention absorbed for decode and in either form for
prompt chunks, a dense first layer, a router that chooses by a biased
score, the prefix cache on — against the plain reference
(``benchmark/reference/glm4_moe_lite.py``, expanded attention only), and
everything the engine refuses for such a block, by name.

Sizes: hidden 64, 4 heads, q rank 24, latent 16 + 8 rotary, nope 12,
v 16, block 4, 1 dense + 2 expert layers, 8 experts top 2 with a
non-zero bias, scale 1.8, 1 shared, a 128-row vocabulary; float32.

The row's STORED form (ISSUE 33): a row of a tile or more lies in whole
128-lane tiles — the published 576 values in 640 columns; here a latent
of 136 + 8 = 144 in 256 — the pad written as zeros and read by nothing
(``TestTheStoredForm``); the 24-value toy row, under one tile, is
stored as it is.
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
from tensorflow_examples_tpu.parallel import moe  # noqa: E402
from tensorflow_examples_tpu.serving import blocks, kv_cache, paged_kv  # noqa: E402
from tensorflow_examples_tpu.serving.batcher import (  # noqa: E402
    ContinuousBatcher,
    Request,
)
from tensorflow_examples_tpu.serving.engine import (  # noqa: E402
    InferenceEngine,
    ServeConfig,
)
from tensorflow_examples_tpu.telemetry import schema, spans  # noqa: E402
from tensorflow_examples_tpu.telemetry.registry import MetricsRegistry  # noqa: E402
from tensorflow_examples_tpu.workloads import glm4_moe_lite as workload  # noqa: E402

REF = spec.reference("glm4_moe_lite")
# The configuration-file keys the program and the reference read, at the toy width.
TINY = dict(
    hidden_size=64, num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
    qk_nope_head_dim=12, qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96,
    moe_intermediate_size=32, n_routed_experts=8, num_experts_per_tok=2,
    n_shared_experts=1, routed_scaling_factor=1.8, first_k_dense_replace=1,
    rope_theta=1e6, rms_norm_eps=1e-5, vocab_size=128, num_hidden_layers=3,
)
LATENT = 16 + 8
STORED = 128  # a whole tile: the width the 24-value rows are padded to where a test pads them
WIDE = dict(kv_lora_rank=136)  # a row of 136 + 8 = 144 values: over one tile, stored in two
WIDE_LATENT, WIDE_STORED = 136 + 8, 256
SERVE = dict(max_slots=3, kv_block_size=4, kv_blocks=49, prefill_bucket_floor=8,
             kv_bucket_floor=16, prefill_chunk_tokens=8)


def program_config(**over):
    sizes = {k: (tuple(v) if isinstance(v, list) else v) for k, v in {**TINY, **over}.items()}
    return workload.Glm4MoeLiteServeConfig(**sizes, seq_len=64, param_dtype="float32")


def init(pcfg, seed=0):
    """Seeded parameters with weights large enough that layers matter
    and a bias large enough that it moves the choice."""
    params = jax.jit(workload.make_task(pcfg).init_fn)(jax.random.PRNGKey(seed))["params"]
    return jax.tree_util.tree_map_with_path(
        lambda path, x: x * (10.0 if path[-1].key != "scale" else 1.0), params)


@pytest.fixture(scope="module")
def model():
    pcfg = program_config()
    return workload.model_config(pcfg), init(pcfg)


@pytest.fixture(scope="module")
def wide_model():
    pcfg = program_config(**WIDE)
    return workload.model_config(pcfg), init(pcfg)


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
    """Prefill (chunked where the engine chunks) -> paged decode, by
    hand: (tokens, first logits)."""
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


def free_list_whole(pool):
    return len(pool._free_blocks) + len(pool._evictable) == pool.num_blocks - 1


class TestAgainstTheReference:
    @pytest.mark.parametrize("n", [5, 8, 21, 30, 50])
    def test_logits_through_prefill_chunks_and_paged_decode(self, engine, model, n):
        """Prompts shorter than a chunk (the prefill rung), of one chunk
        and of several (the extend rung over cached latent rows): the
        first logits and every greedy token are the reference's, which
        attends EXPANDED where the program attends absorbed."""
        eng, _ = engine
        _, params = model
        prompt = prompt_of(n, seed=n)
        slot = eng.pool.alloc()
        toks, last = serve(eng, slot, prompt, 8)
        eng.pool.free(slot)
        seq = prompt + toks
        logits, _ = REF.forward(params, seq, TINY, rows=range(n - 1, len(seq) - 1), q_block=8)
        np.testing.assert_allclose(last, logits[0], atol=1e-4)
        for k, tok in enumerate(toks):
            assert logits[k].max() - logits[k][tok] < 1e-4, (n, k)
        assert eng.post_warmup_recompiles() == 0

    def test_a_prefix_hit_and_its_tail_equal_the_cold_path(self, model):
        """The same document with another question: its blocks are
        mapped, only the tail runs (the extend rung over a hit), and the
        logits are those of the whole prompt served cold and of the
        reference."""
        _, params = model
        eng, reg = make_engine(model)
        doc, q1, q2 = prompt_of(32, seed=1), prompt_of(5, seed=2), prompt_of(7, seed=3)
        slot = eng.pool.alloc()
        serve(eng, slot, doc + q1, 2)
        eng.pool.free(slot)
        reused0 = reg.counter("serving/prefix_reused_tokens").value
        slot = eng.pool.alloc()
        toks, hit = serve(eng, slot, doc + q2, 4)
        eng.pool.free(slot)
        assert reg.counter("serving/prefix_reused_tokens").value - reused0 == 32
        cold_eng, _ = make_engine(model, prefix_cache=False, prefill_chunk_tokens=0,
                                  prefill_bucket_floor=64)
        slot = cold_eng.pool.alloc()
        cold_toks, cold = serve(cold_eng, slot, doc + q2, 4)
        np.testing.assert_allclose(hit, cold, atol=1e-4)
        assert toks == cold_toks
        seq = doc + q2
        logits, _ = REF.forward(params, seq, TINY, rows=[len(seq) - 1], q_block=8)
        np.testing.assert_allclose(hit, logits[0], atol=1e-4)

    @pytest.mark.parametrize("kind,ctx,rung", [
        *(("chunk", *case) for case in extend_rungs.CASES),
        *(("hit", *case) for case in extend_rungs.CASES[1:]),
    ])
    def test_a_launch_through_a_lower_context_rung_is_the_whole_tables(
            self, model, kind, ctx, rung):
        """ISSUE 35: a chunk of a chunked prefill, and the tail of a
        prefix hit, over ``ctx`` cached latent rows take the smallest
        context rung that holds them and give the tokens and logits of
        the same launch through the whole-table program."""
        prompt = prompt_of(ctx + 7, seed=ctx)

        def launch(eng):
            if kind == "chunk":
                return extend_rungs.last_chunk(eng, prompt, ctx)
            slot = eng.pool.alloc()
            serve(eng, slot, prompt[:ctx] + prompt_of(3, seed=99), 1)  # the document, cached
            eng.pool.free(slot)
            out = extend_rungs.hit_tail(eng, prompt)
            assert eng.registry.counter("serving/prefix_reused_tokens").value == ctx
            return out

        extend_rungs.assert_lower_rung_is_whole_tables(
            lambda: make_engine(model)[0], launch, rung, atol=1e-4)

    def test_only_the_chunk_bucket_has_context_rungs(self, model):
        """A tail shorter than a chunk is one launch a request and keeps
        the whole-table program alone; the bucket that writes prompts
        chunk by chunk has a program a context rung (ISSUE 35)."""
        eng, reg = make_engine(model, prefill_bucket_floor=4)
        assert eng.prefill_ladder == [4, 8] and eng.extend_ladder == [16, 32, 64]
        assert list(eng._extend_fns) == [4, 8, (8, 16), (8, 32)]
        assert eng.expected_compiles() == 2 + 3 + 4
        key, tb, tables = eng._extend_launch(0, 16, 3)
        assert (key, tb) == (4, 4) and tables[0].shape == (16,)
        key, tb, tables = eng._extend_launch(0, 16, 7)
        assert (key, tb) == ((8, 16), 8) and tables[0].shape == (4,)
        assert reg.counter(schema.EXTEND_GATHERED_TOKENS).value == 64 + 16
        assert reg.counter(schema.EXTEND_CONTEXT_TOKENS).value == 16 + 16

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

    def test_generate_through_the_batcher_with_the_decode_programs_logprobs(self, model):
        """Through ``ContinuousBatcher``: a cold chunked prompt, then the
        same document again as a hit; each streamed token's
        log-probability is the reference's log-softmax there."""
        _, params = model
        eng, reg = make_engine(model)
        batcher = ContinuousBatcher(eng).start()
        try:
            doc = prompt_of(24, seed=4)
            first = batcher.submit(Request(
                prompt=doc + prompt_of(3, seed=5), max_new_tokens=5, logprobs=True)).result(120)
            prompt = doc + prompt_of(6, seed=6)
            got = batcher.submit(Request(prompt=prompt, max_new_tokens=6, logprobs=True)).result(120)
        finally:
            batcher.close(drain=True, timeout=30.0)
        assert len(first.tokens) == 5
        assert reg.counter("serving/prefix_reused_tokens").value == 24
        seq = prompt + got.tokens
        logits, _ = REF.forward(params, seq, TINY, rows=range(len(prompt) - 1, len(seq) - 1),
                                q_block=8)
        for k, (tok, lp) in enumerate(zip(got.tokens, got.logprobs)):
            row = logits[k] - logits[k].max()
            assert abs(lp - (row[tok] - np.log(np.exp(row).sum()))) < 1e-4, k
        assert free_list_whole(eng.pool)

    def test_the_shares_add_up(self):
        """The routed parts that disjoint ``held_experts`` subsets give,
        with what every chip computes alike (attention, the shared
        expert) counted once, equal the uncut layer."""
        shares = [[0, 1, 2], [3, 4, 5, 6, 7]]
        whole = init(program_config(), seed=3)
        tokens = prompt_of(24, seed=9)
        layer = 2
        x, uncut = REF.layer_parts(whole, tokens, TINY, layer)

        def cut_to(held):
            cut = dict(whole)
            cut[f"h_{layer}"] = dict(whole[f"h_{layer}"])
            cut[f"h_{layer}"]["moe"] = {
                k: (v[jnp.asarray(held, jnp.int32)] if k.startswith("w_") else v)
                for k, v in whole[f"h_{layer}"]["moe"].items()
            }
            return REF.layer_parts(cut, tokens, dict(TINY, held_experts=held), layer)[1]

        alike = cut_to([])
        parts = [cut_to(held) for held in shares]
        np.testing.assert_allclose(alike + sum(p - alike for p in parts), uncut, atol=1e-4)
        assert all(np.abs(p - alike).max() > 1e-3 for p in parts)  # a share does add something

    def test_the_engines_share_is_the_references_share(self):
        """The same, through the program: an engine that holds experts
        3..7 serves what the reference computes for that share."""
        held = [3, 4, 5, 6, 7]
        pcfg = program_config(held_experts=held)
        params = init(pcfg)
        assert params["h_1"]["moe"]["w_gate"].shape[0] == 5
        eng = InferenceEngine(workload.model_config(pcfg), params, cfg=ServeConfig(**SERVE),
                              registry=MetricsRegistry())
        prompt = prompt_of(19, seed=4)
        slot = eng.pool.alloc()
        _, last = serve(eng, slot, prompt, 1)
        logits, _ = REF.forward(params, prompt, dict(TINY, held_experts=held),
                                rows=[len(prompt) - 1], q_block=8)
        np.testing.assert_allclose(last, logits[0], atol=1e-4)


def expanded_attention(q_nope, q_pe, rows, w_uk, w_uv, ctx_rows=None, *, ctx_len=0, sm_scale):
    """The model's attention as published, straight from the rows: head
    h's key is ``[c_kv W_uk[h] | k_pe]``, its value ``c_kv W_uv[h]``;
    query t (at position ``ctx_len + t``) sees the first ``ctx_len``
    cached rows and the chunk's own rows up to itself."""
    dc, t_n = w_uk.shape[0], q_nope.shape[0]
    seen = np.tril(np.ones((t_n, t_n), bool))
    if ctx_rows is not None:
        rows = jnp.concatenate([ctx_rows, rows])
        ctx = np.broadcast_to(np.arange(ctx_rows.shape[0]) < ctx_len, (t_n, ctx_rows.shape[0]))
        seen = np.concatenate([ctx, seen], axis=1)
    c_kv, k_pe = rows[:, :dc], rows[:, dc:]
    k_nope = jnp.einsum("kc,chn->hkn", c_kv, w_uk)
    v = jnp.einsum("kc,chv->hkv", c_kv, w_uv)
    s = (jnp.einsum("thn,hkn->htk", q_nope, k_nope) + jnp.einsum("thr,kr->htk", q_pe, k_pe))
    p = jax.nn.softmax(jnp.where(seen[None], s * sm_scale, -1e30), axis=-1)
    return jnp.einsum("htk,hkv->thv", p, v)


class TestTheTwoForms:
    @pytest.fixture(scope="class")
    def rows(self):
        rng = np.random.default_rng(0)
        f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
        t, c, h, dn, dr, dc, dv = 8, 24, 4, 12, 8, 16, 16
        return dict(q_nope=f(t, h, dn), q_pe=f(t, h, dr), rows=f(t, dc + dr),
                    w_uk=f(dc, h, dn) * 0.3, w_uv=f(dc, h, dv) * 0.3, ctx_rows=f(c, dc + dr))

    @staticmethod
    def stored(rows, width):
        """``rows`` as the pool keeps them; NaN in the pad columns where
        zeros would do shows that nothing reads them."""
        return None if rows is None else kv_cache.pad_columns(rows, width)

    @pytest.mark.parametrize("ctx_len", [None, 24, 13])
    @pytest.mark.parametrize("score_elements", [256, 512, 1 << 26])
    def test_absorbed_equals_expanded_on_the_same_rows(self, rows, ctx_len, score_elements,
                                                       monkeypatch):
        """The engine's chunk attention (absorbed, the heads in groups
        of 1, 2 or all 4 by the scores allowed at once) against the
        published form on the same rows."""
        monkeypatch.setattr(kv_cache, "LATENT_SCORE_ELEMENTS", score_elements)
        kw = dict(rows, sm_scale=20 ** -0.5)
        if ctx_len is None:
            kw.pop("ctx_rows")
        else:
            kw["ctx_len"] = ctx_len
        got = kv_cache.latent_chunk_attention(**kw)
        assert got.shape == (8, 4, 16)
        np.testing.assert_allclose(got, expanded_attention(**kw), atol=1e-4)

    @pytest.mark.parametrize("ctx_len", [None, 13])
    def test_the_stored_width_changes_no_number_of_a_chunk(self, rows, ctx_len):
        """The same rows at the stored width (24 values in 128 columns,
        the pad zero) give the very numbers of the rows at 24."""
        kw = dict(rows, sm_scale=20 ** -0.5)
        if ctx_len is None:
            kw.pop("ctx_rows")
        else:
            kw["ctx_len"] = ctx_len
        want = kv_cache.latent_chunk_attention(**kw)
        kw.update(rows=self.stored(rows["rows"], STORED),
                  ctx_rows=self.stored(kw.get("ctx_rows"), STORED))
        np.testing.assert_array_equal(kv_cache.latent_chunk_attention(**kw), want)

    def test_a_decode_step_is_the_last_row_of_a_chunk(self, rows):
        """Absorbed through a block table = the published form's last
        query over the same rows laid out in blocks."""
        all_rows = jnp.concatenate([rows["ctx_rows"], rows["rows"]])        # 32 rows
        blocks_ = jnp.concatenate([jnp.zeros((1, 4, 24)), all_rows.reshape(8, 4, 24)])
        table = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], jnp.int32)
        got = kv_cache.latent_decode_attention(
            rows["q_nope"][-1:], rows["q_pe"][-1:], blocks_, jnp.asarray([31]), table,
            rows["w_uk"], rows["w_uv"], sm_scale=20 ** -0.5)
        want = expanded_attention(**rows, ctx_len=24, sm_scale=20 ** -0.5)[-1:]
        np.testing.assert_allclose(got, want, atol=1e-4)
        # ... and the same blocks at the stored width, pad columns zero: the same numbers
        wide = kv_cache.latent_decode_attention(
            rows["q_nope"][-1:], rows["q_pe"][-1:], self.stored(blocks_, STORED),
            jnp.asarray([31]), table, rows["w_uk"], rows["w_uv"], sm_scale=20 ** -0.5)
        np.testing.assert_array_equal(wide, got)

    def test_the_head_group_bounds_the_scores(self):
        assert kv_cache.latent_head_group(20, 512, 32768 + 512) == 2
        assert kv_cache.latent_head_group(20, 128, 32768 + 128) == 10
        assert kv_cache.latent_head_group(20, 8, 64) == 20
        assert kv_cache.latent_head_group(20, 1 << 20, 1 << 20) == 1

    def test_the_program_counts_the_tokens_it_attended(self, model):
        """``serving/latent_attn_absorbed_tokens``: 30 prompt tokens
        through the chunk programs and 2 decode steps, each once (every
        layer counts them; one layer's share is booked)."""
        eng, reg = make_engine(model)
        serve(eng, eng.pool.alloc(), prompt_of(30, seed=11), 3)
        assert reg.counter(schema.LATENT_ATTENTION_TOKENS).value == 32

    def test_the_plan_is_recorded_once_per_traced_shape(self, model):
        plans = lambda: [  # noqa: E731
            e["args"] for e in spans._default.events() if e["name"] == "mla_plan"]
        blocks._record_mla_plan.cache_clear()
        before = len(plans())
        eng, _ = make_engine(model)
        eng.warmup()
        by_family = {}
        for p in plans()[before:]:
            by_family.setdefault(p["family"], []).append(p)
        assert len(by_family["prefill"]) == 1
        assert [p["context"] for p in by_family["decode"]] == [16, 32, 64]
        assert all(p["form"] == "absorbed" for p in by_family["decode"])
        # one extend program a context rung: the chunk's own 8 columns
        # behind the rung's, the whole table (64) first as it is keyed
        assert [p["context"] for p in by_family["extend"]] == [72, 24, 40]
        assert all(p["head_group"] == 4 for p in by_family["extend"])
        assert all(p["rows"] == [LATENT] for p in plans()[before:])  # the widths as stored
        assert all(set(p) == set(schema.MLA_PLAN_ARGS) for p in plans()[before:])
        n = len(plans())
        eng2, _ = make_engine(model)
        eng2.warmup()  # the same shapes traced again: no new plan
        assert len(plans()) == n


def log_softmax(row):
    row = np.asarray(row, np.float64) - np.max(row)
    return row - np.log(np.exp(row).sum())


class TestTheStoredForm:
    """A row of a tile or more is stored in whole 128-lane tiles (ISSUE
    33; here 144 values in 256 columns): that changes no number, the
    pad is written as zeros, and nothing reads it — a pool whose pad
    columns hold NaN serves what a clean one serves."""

    SIZES = dict(TINY, **WIDE)

    DOC, Q1, Q2, SHORT = 32, 5, 7, 5

    @staticmethod
    def poison(pool):
        """NaN into every pad column of every block, before anything is written."""
        pool.set_kv_state(tuple(
            tuple(a.at[..., WIDE_LATENT:].set(jnp.nan) for a in arrs) for arrs in pool.kv_state()))

    def served(self, model, poisoned):
        """One engine through the three program families: a short
        prompt (the prefill rung), a document cold, then the same
        document under another question (a prefix hit: the extend rung
        over cached rows) and four decode steps of it. Returns the
        last-row logits of the prefill and of the hit, the decode
        program's own log-probabilities, the tokens, and the engine."""
        eng, reg = make_engine(model)
        if poisoned:
            self.poison(eng.pool)
        short = prompt_of(self.SHORT, seed=21)
        doc, q1, q2 = (prompt_of(n, seed=s) for n, s in ((self.DOC, 22), (self.Q1, 23), (self.Q2, 24)))
        slot = eng.pool.alloc()
        _, prefill = serve(eng, slot, short, 1)
        eng.pool.free(slot)
        slot = eng.pool.alloc()
        serve(eng, slot, doc + q1, 1)
        eng.pool.free(slot)
        reused = reg.counter("serving/prefix_reused_tokens").value
        slot = eng.pool.alloc()
        toks, hit = serve(eng, slot, doc + q2, 1)
        assert reg.counter("serving/prefix_reused_tokens").value - reused == self.DOC
        logprobs = []
        reach = reg.counter("serving/kv_sampled_reach_bytes").value
        for _ in range(4):
            toks.append(eng.decode([(slot, toks[-1], 0, 0.0, 0)])[slot])
            logprobs.append(float(eng.last_logprobs[slot]))
        # what a step MUST read: the row's 144 values a layer, not the 256 stored
        n = self.DOC + self.Q2
        assert reg.counter("serving/kv_sampled_reach_bytes").value - reach == sum(
            (n + k) * 3 * WIDE_LATENT * 4 for k in range(1, 5))
        return dict(prefill=np.asarray(prefill), extend=np.asarray(hit),
                    decode=np.asarray(logprobs), tokens=toks, prompts=(short, doc + q2),
                    engine=eng, slot=slot)

    @pytest.fixture(scope="class")
    def clean(self, wide_model):
        return self.served(wide_model, poisoned=False)

    @pytest.fixture(scope="class")
    def poisoned(self, wide_model):
        return self.served(wide_model, poisoned=True)

    @pytest.mark.parametrize("kind", ["prefill", "extend", "decode"])
    def test_served_log_probabilities_are_the_references(self, wide_model, clean, kind):
        _, params = wide_model
        short, long_ = clean["prompts"]
        if kind == "prefill":
            logits, _ = REF.forward(params, short, self.SIZES, rows=[len(short) - 1], q_block=8)
            got, want = log_softmax(clean["prefill"]), log_softmax(logits[0])
        elif kind == "extend":
            logits, _ = REF.forward(params, long_, self.SIZES, rows=[len(long_) - 1], q_block=8)
            got, want = log_softmax(clean["extend"]), log_softmax(logits[0])
        else:
            seq = long_ + clean["tokens"]
            logits, _ = REF.forward(params, seq, self.SIZES, rows=range(len(long_), len(seq) - 1),
                                    q_block=8)
            got = clean["decode"]
            want = [log_softmax(row)[tok] for row, tok in zip(logits, clean["tokens"][1:])]
        np.testing.assert_allclose(got, want, atol=1e-4)

    @pytest.mark.parametrize("kind", ["prefill", "extend", "decode"])
    def test_nan_in_the_pad_columns_reaches_no_score_and_no_value(self, clean, poisoned, kind):
        assert np.isfinite(poisoned[kind]).all()
        np.testing.assert_array_equal(poisoned[kind], clean[kind])
        assert poisoned["tokens"] == clean["tokens"]

    def test_the_pad_is_written_as_zeros_and_nothing_else_is_touched(self, poisoned):
        eng, slot = poisoned["engine"], poisoned["slot"]
        pool = eng.pool
        n = int(pool.lengths[slot])
        assert n == self.DOC + self.Q2 + 4
        blocks_ = pool.block_tables[slot, : -(-n // 4)]
        for layer in pool.k:
            rows = np.asarray(layer)[blocks_].reshape(-1, WIDE_STORED)[:n]
            assert np.isfinite(rows).all() and (rows[:, WIDE_LATENT:] == 0).all()
            assert np.abs(rows[:, :WIDE_LATENT]).min(axis=1).max() > 0  # real values before the pad
            # a block no request ever held keeps what it had: no program re-wrote the pool
            never = np.asarray(layer)[pool._free_blocks[0]]
            assert np.isnan(never[:, WIDE_LATENT:]).all() and (never[:, :WIDE_LATENT] == 0).all()


ROUTER_CASES = [
    ("sigmoid", False, 1.0), ("softmax", False, 1.0), ("sigmoid", True, 1.0),
    ("sigmoid", True, 1.8), ("sigmoid", False, 1.8),
]


class TestTheRouter:
    @pytest.mark.parametrize("select,biased,scale", ROUTER_CASES)
    def test_bias_moves_the_choice_and_not_the_weights(self, select, biased, scale):
        """Chosen by score + bias, weighed by the score without it, the
        scale behind the normalisation; with neither, ``sigmoid`` and
        ``softmax`` are bit for bit what they were."""
        rng = np.random.default_rng(1)
        tokens = jnp.asarray(rng.normal(size=(32, 16)), jnp.float32)
        gate_w = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
        bias = jnp.asarray(rng.normal(size=(8,)) * 0.5, jnp.float32)
        plain_g, plain_e, *_ = moe._router(tokens, gate_w, top_k=2, rng=None, jitter=0.0,
                                           select=select)
        kw = dict(select_bias=bias) if biased else {}
        gates, experts, *_ = moe._router(tokens, gate_w, top_k=2, rng=None, jitter=0.0,
                                         select=select, scale=scale, **kw)
        if not biased and scale == 1.0:
            for a, b in zip(gates + experts, plain_g + plain_e):
                np.testing.assert_array_equal(a, b)
            return
        logits = np.asarray(tokens) @ np.asarray(gate_w)
        score = 1 / (1 + np.exp(-logits))
        choose = score + (np.asarray(bias) if biased else 0.0)
        want_e = np.argsort(-choose, axis=-1)[:, :2]
        np.testing.assert_array_equal(np.stack(experts, 1), want_e)
        picked = np.take_along_axis(score, want_e, axis=-1)
        want_g = picked / picked.sum(-1, keepdims=True) * scale
        np.testing.assert_allclose(np.stack(gates, 1), want_g, rtol=1e-5)
        if biased:  # the bias did move a choice in this draw
            assert (np.stack(experts, 1) != np.stack(plain_e, 1)).any()

    def test_a_held_layer_passes_them_on(self):
        rng = np.random.default_rng(2)
        f = lambda *s: jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)  # noqa: E731
        router, bias = f(16, 8), f(8) * 3
        w = (f(8, 16, 12), f(8, 16, 12), f(8, 12, 16))
        tokens = f(10, 16)
        plain, _ = moe.moe_ffn_held(router, *w, tokens, held=tuple(range(8)), top_k=2)
        scaled, _ = moe.moe_ffn_held(router, *w, tokens, held=tuple(range(8)), top_k=2, scale=1.8)
        np.testing.assert_allclose(scaled, 1.8 * plain, rtol=1e-5)
        biased, _ = moe.moe_ffn_held(router, *w, tokens, held=tuple(range(8)), top_k=2,
                                     select_bias=bias)
        assert np.abs(np.asarray(biased) - np.asarray(plain)).max() > 1e-3


class TestTheLatentPool:
    def make(self, **over):
        kw = dict(num_layers=3, num_slots=3, num_heads=1, max_len=64, head_dim=LATENT,
                  block_size=4, num_blocks=13, registry=MetricsRegistry(), rows=(LATENT,))
        return paged_kv.PagedKVPool(**{**kw, **over})

    def test_one_latent_row_a_token_and_no_v_array(self, engine):
        eng, _ = engine
        pool = eng.pool
        # one entry a layer (ISSUE 34): every layer keeps the same latent row
        assert eng.model.row_values == (LATENT,) * 3 and eng.model.cache_rows == (((1, LATENT),),) * 3
        assert len(pool.kv_state()) == 1 and pool.rows == (LATENT,)
        assert [a.shape for a in pool.k] == [(49, 4, LATENT)] * 3
        with pytest.raises(IndexError):
            pool.v
        assert pool.kinds == (None,) and pool.prefix_cache_enabled
        # a block: 3 layers x 4 rows x 24 values x 4 bytes, once (no V)
        assert pool.bytes_per_block() == pool.bytes_per_block(0) == 3 * 4 * LATENT * 4

    @pytest.mark.parametrize("values,stored", [(576, 640), (144, 256), (128, 128), (129, 256),
                                               (24, 24), (127, 127)])
    def test_a_row_of_a_tile_or_more_is_stored_in_whole_tiles(self, values, stored):
        assert kv_cache.lane_dense(values) == stored

    def test_the_pool_counts_what_it_stores(self, wide_model):
        """The published geometry in small: 144 values in 256 columns,
        and ``bytes_per_block`` is the arrays' real bytes, pad included."""
        eng, _ = make_engine(wide_model)
        pool = eng.pool
        assert eng.model.row_values == (WIDE_LATENT,) * 3
        assert eng.model.cache_rows == (((1, WIDE_STORED),),) * 3 and pool.rows == (WIDE_STORED,)
        assert [a.shape for a in pool.k] == [(49, 4, WIDE_STORED)] * 3
        assert pool.bytes_per_block() == 3 * 4 * WIDE_STORED * 4
        assert pool.bytes_per_block() * pool.num_blocks == sum(a.nbytes for a in pool.k)

    def test_shared_blocks_are_refcounted_and_counted_once(self):
        pool = self.make()
        doc = prompt_of(8, seed=1)
        a = pool.alloc()
        pool.claim_prompt_blocks(a, doc + [1, 2, 3])
        pool.insert_prefix(a, doc + [1, 2, 3])
        b = pool.alloc()
        ctx, fresh = pool.claim_prompt_blocks(b, doc + [4, 5])
        assert ctx == 8 and len(fresh) == 1
        shared = [int(x) for x in pool.block_tables[a, :2]]
        assert shared == [int(x) for x in pool.block_tables[b, :2]]
        assert all(pool._refcount[bid] == 2 for bid in shared)
        # 2 shared + 1 private each: 4 blocks in use, not 6
        assert pool.used_bytes() == 4 * pool.bytes_per_block()
        pool.free(a)
        assert all(pool._refcount[bid] == 1 for bid in shared)
        pool.free(b)
        assert free_list_whole(pool) and pool.used_bytes() == 0

    @staticmethod
    def published(pool, slot, prompt):
        """Claim, publish and release ``prompt``: what a finished
        request leaves in the prefix cache. Returns the tokens it hit."""
        ctx, _ = pool.claim_prompt_blocks(slot, prompt)
        pool.insert_prefix(slot, prompt)
        pool.free(slot)
        return ctx

    @staticmethod
    def cache_is_a_forest(pool):
        """Every published block's parent is published too, and the
        child counts are the cache's."""
        counts = {}
        for (parent, _), bid in pool._cache.items():
            assert parent == -1 or parent in pool._cache_key
            assert pool._cache_key[bid][0] == parent
            counts[parent] = counts.get(parent, 0) + 1
        counts.pop(-1, None)
        assert {k: v for k, v in pool._children.items() if v} == counts
        return True

    @pytest.mark.parametrize("pressure", [1, 2, 3, 4])
    def test_eviction_takes_a_chain_from_its_leaves(self, pressure):
        """An idle document under pressure loses its LAST blocks, and
        what is left of it still hits (released head first, it lost its
        first block and with it the whole chain)."""
        pool = self.make(num_blocks=7, num_slots=2)  # 6 usable
        doc = prompt_of(16, seed=1)  # 4 blocks, then a private tail
        assert self.published(pool, pool.alloc(), doc + [1]) == 0
        assert len(pool._evictable) == 4 and len(pool._free_blocks) == 2
        other = pool.alloc()
        pool.claim_prompt_blocks(other, prompt_of(4 * (2 + pressure), seed=2))
        assert self.cache_is_a_forest(pool)
        assert pool.prefix_lookup(doc + [2])[1] == 16 - 4 * pressure
        assert pool.prefix_hits == (1 if pressure < 4 else 0)

    def test_a_question_goes_before_the_document_it_was_asked_of(self):
        """A finished request's question blocks are leaves of its
        document's chain: they go first, the document stays whole, and
        an older document goes before a younger one."""
        pool = self.make(num_blocks=14, num_slots=2)  # 13 usable
        old, young = prompt_of(8, seed=1), prompt_of(8, seed=2)
        for doc, q in ((old, 3), (young, 4), (young, 5)):
            self.published(pool, pool.alloc(), doc + prompt_of(4, seed=q) + [0])
        # parked: 2 + 2 document blocks, 3 question blocks; 6 free
        assert len(pool._evictable) == 7 and len(pool._free_blocks) == 6
        live = pool.alloc()
        pool.claim_prompt_blocks(live, prompt_of(36, seed=6))  # 9 blocks: evicts 3
        assert self.cache_is_a_forest(pool) and len(pool._cache) == 4
        assert pool.prefix_lookup(young + [9])[1] == 8
        pool.free(live)
        live = pool.alloc()
        pool.claim_prompt_blocks(live, prompt_of(44, seed=7))  # 11 blocks: 2 more go
        assert pool.prefix_lookup(young + [9])[1] == 8  # read since: the old one went
        assert pool.prefix_lookup(old + [9])[1] == 0

    def test_no_child_outlives_its_parent(self):
        """Two requests race on one new document: the second publishes
        its question under the first's chain, which it never held. When
        only that chain is left to evict it goes WITH what hangs under
        it: no block stays keyed by a parent id that can be published
        again under other tokens, and nothing leaks."""
        pool = self.make(num_blocks=9, num_slots=3)  # 8 usable
        doc = prompt_of(8, seed=1)
        pa, pb = doc + prompt_of(4, seed=2) + [0], doc + prompt_of(4, seed=3) + [0]
        a, b = pool.alloc(), pool.alloc()
        pool.claim_prompt_blocks(a, pa)  # both cold: 4 blocks each
        pool.claim_prompt_blocks(b, pb)
        pool.insert_prefix(a, pa)
        pool.insert_prefix(b, pb)  # its question under a's document
        theirs = int(pool.block_tables[b, 2])
        assert pool._cache_key[theirs][0] == int(pool.block_tables[a, 1])
        pool.free(a)  # parked: a's question, then its document, last block first
        assert len(pool._evictable) == 3 and not pool._free_blocks[1:]
        c = pool.alloc()
        pool.claim_prompt_blocks(c, prompt_of(12, seed=4))  # 3 blocks: 1 free, 2 evicted
        assert self.cache_is_a_forest(pool)
        assert theirs not in pool._cache_key and pool._refcount[theirs] == 1
        hit, ctx = pool.prefix_lookup(pb)
        assert ctx == 4  # the document's head is still there
        pool.release_prefix(hit)
        for slot in (b, c):
            pool.free(slot)
        assert free_list_whole(pool) and not pool.active_slots

    def test_exhaustion_is_loud_and_claims_nothing(self):
        pool = self.make(num_blocks=5, prefix_cache=False)
        a = pool.alloc()
        pool.claim_prompt_blocks(a, prompt_of(12))
        b = pool.alloc()
        with pytest.raises(paged_kv.BlockExhausted):
            pool.claim_prompt_blocks(b, prompt_of(9))
        assert len(pool._free_blocks) == 1
        pool.free(a)
        pool.free(b)
        assert free_list_whole(pool)

    def test_reset_and_reallocate_keep_one_array_a_layer(self):
        pool = self.make()
        slot = pool.alloc()
        pool.claim_prompt_blocks(slot, prompt_of(10))
        pool.insert_prefix(slot, prompt_of(10))
        pool.reallocate()
        assert len(pool.kv_state()) == 1 and len(pool._cache) == 0
        pool.reset()
        assert free_list_whole(pool) and pool.active_slots == 0

    def test_a_quantised_pool_wants_rows_with_heads(self):
        with pytest.raises(ValueError, match="no such heads"):
            self.make(kv_dtype="int8")

    def test_the_pools_with_heads_are_as_they_were(self):
        pool = paged_kv.PagedKVPool(num_layers=2, num_slots=2, num_heads=2, max_len=16,
                                    head_dim=8, block_size=4, registry=MetricsRegistry())
        assert pool.rows == (16, 16) and len(pool.kv_state()) == 2
        assert pool.k[0].shape == pool.v[1].shape == (9, 4, 16)
        assert pool.bytes_per_block() == 2 * 2 * 4 * 16 * 4

    def test_preemption_through_the_batcher_leaves_the_free_list_whole(self, model):
        """A pool too small for both requests' growth: the one that
        cannot grow is preempted or failed, the other finishes, and
        nothing leaks."""
        eng, reg = make_engine(model, kv_blocks=12, max_slots=2)
        batcher = ContinuousBatcher(eng).start()
        try:
            futures = [batcher.submit(Request(prompt=prompt_of(17, seed=s), max_new_tokens=20))
                       for s in (1, 2)]
            done = []
            for f in futures:
                try:
                    done.append(len(f.result(120).tokens))
                except Exception as e:  # the one that could not grow fails, loudly
                    assert "exhausted" in str(e)
        finally:
            batcher.close(drain=True, timeout=30.0)
        assert 20 in done and reg.counter("serving/kv_exhausted_total").value >= 1
        assert free_list_whole(eng.pool) and eng.pool.active_slots == 0

    def test_sampled_bytes_count_a_shared_block_once_and_reach_per_reader(self, model):
        """``kv_sampled_bytes`` is the blocks in use, a shared block
        once; ``kv_sampled_reach_bytes`` counts it for every slot that
        reads it; ``kv_sampled_tokens`` the tokens resident."""
        eng, reg = make_engine(model)
        doc = prompt_of(16, seed=1)
        slots, last = [], {}
        for seed in (2, 3):
            slot = eng.pool.alloc()
            toks, _ = serve(eng, slot, doc + prompt_of(3, seed=seed), 1)
            slots.append(slot)
            last[slot] = toks[-1]
        assert reg.counter("serving/prefix_reused_tokens").value == 16
        before = {n: reg.counter(f"serving/kv_sampled_{n}").value
                  for n in ("bytes", "tokens", "reach_bytes")}
        eng.decode([(s, last[s], 0, 0.0, 0) for s in slots])
        got = {n: reg.counter(f"serving/kv_sampled_{n}").value - before[n] for n in before}
        block = eng.pool.bytes_per_block()
        # 4 shared document blocks + 1 private block a slot (19 + 1 tokens each)
        assert got["bytes"] == 6 * block == eng.pool.used_bytes()
        assert got["tokens"] == 2 * 20
        assert got["reach_bytes"] == 2 * 20 * 3 * LATENT * 4 == 2 * 20 * (block // 4)
        for s in slots:
            eng.pool.free(s)


REFUSED = [
    (dict(spec_decode_k=2), "speculative verify"),
    (dict(role="prefill"), "KV page export/import"),
    (dict(role="decode"), "KV page export/import"),
    (dict(kv_dtype="int8"), "quantized KV"),
    (dict(kv_dtype="fp8"), "quantized KV"),
    (dict(weight_dtype="int8"), "weight quantization"),
    (dict(attention="paged_flash"), "paged_flash"),
    (dict(attention="flash"), "flash prefill"),
]


class TestWhatIsRefused:
    def test_an_own_row_that_is_not_lane_dense_is_refused_by_name(self, wide_model, monkeypatch):
        """A block that makes its own row pads it to whole 128-lane
        tiles or is not served: the TPU would re-lay the whole pool
        around every write (ISSUE 33)."""
        monkeypatch.setattr(kv_cache, "lane_dense", lambda width: width)
        with pytest.raises(ValueError, match=r"glm4_moe_lite.*144 values.*128-lane tiles"):
            make_engine(wide_model)

    @pytest.mark.parametrize("over,mechanism", REFUSED,
                             ids=[f"{m}-{list(o.values())[0]}" for o, m in REFUSED])
    def test_refused_by_name_at_construction(self, model, over, mechanism):
        with pytest.raises(NotImplementedError, match="glm4_moe_lite") as e:
            make_engine(model, **over)
        assert mechanism in str(e.value) and "GPT-2 only" in str(e.value)
        if mechanism != "weight quantization":
            assert "latent row" in str(e.value)

    def test_sharded_serving_is_refused(self, model):
        mcfg, params = model
        with pytest.raises(NotImplementedError, match="sharded serving.*latent row"):
            InferenceEngine(mcfg, params, cfg=ServeConfig(**SERVE), sharding=object())

    @pytest.mark.parametrize("what", ["export", "import"])
    def test_page_handoff_is_refused_when_called(self, engine, what):
        eng, _ = engine
        with pytest.raises(NotImplementedError, match=f"KV page {what}"):
            if what == "export":
                eng.export_kv_pages(0, [1, 2, 3])
            else:
                eng.import_kv_pages(0, {}, [1, 2, 3])

    def test_the_workload_has_no_sharded_placement(self):
        with pytest.raises(NotImplementedError, match="one chip"):
            workload.make_task(program_config(), mesh=object())

    def test_a_config_of_no_served_model_names_all_three(self):
        with pytest.raises(TypeError, match="GPT-2.*Cohere2MoeConfig.*Glm4MoeLiteConfig"):
            blocks.block_for(object())


class TestTheModel:
    def test_the_published_sizes_count_what_the_issue_counts(self):
        """Parameter shapes at the published widths: an expert layer is
        635.3 M, the dense layer 84.7 M, attention 21.76 M, embedding
        and head 634.4 M (no array is made)."""
        from tensorflow_examples_tpu.models import glm4_moe_lite as model_mod

        cfg = workload.model_config(workload.Glm4MoeLiteServeConfig())
        shapes = model_mod.param_shapes(cfg)
        count = lambda tree: sum(  # noqa: E731
            int(np.prod(s)) for s in jax.tree.leaves(tree, is_leaf=lambda x: isinstance(x, tuple)))
        assert cfg.latent_dim == 576 and cfg.num_layers == 7 and len(cfg.held_experts) == 64
        assert round(count(shapes["h_0"]["attn"]) / 1e6, 2) == 21.76
        assert round(count(shapes["h_0"]) / 1e6, 1) == 84.7
        assert round(count(shapes["h_1"]) / 1e6, 1) == 635.3
        assert round((count(shapes["wte"]) + count(shapes["lm_head"])) / 1e6, 1) == 634.4
        assert "mlp" in shapes["h_0"] and "moe" in shapes["h_6"] and "h_7" not in shapes

    def test_seeded_init_is_leaf_by_leaf_and_the_bias_is_drawn(self):
        pcfg = program_config()
        a = jax.jit(workload.make_task(pcfg).init_fn)(jax.random.PRNGKey(5))["params"]
        b = jax.jit(workload.make_task(pcfg).init_fn)(jax.random.PRNGKey(5))["params"]
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_array_equal(x, y)
        bias = np.asarray(a["h_1"]["moe"]["bias"])
        assert bias.dtype == np.float32 and np.abs(bias).max() > 0
        assert np.all(np.asarray(a["h_1"]["ln_1"]["scale"]) == 1)
        assert a["lm_head"]["kernel"].shape == (64, 128)  # untied
