"""What the three blocks' test files share to drive the extend family's
context ladder (ISSUE 35): a launch through the smallest context rung
that holds its context must give what the same launch gives through the
whole-table program. The toy engines all serve chunks of 8 tokens in
blocks of 4 over 64 positions: context rungs 16, 32 and 64 (the whole
table)."""

import numpy as np

from tensorflow_examples_tpu.serving import engine as engine_mod
from tensorflow_examples_tpu.serving import scheduler
from tensorflow_examples_tpu.telemetry import schema

# (context of the launch, the rung it must take): none — a chunked
# prompt's first chunk —, on the lowest rung's edge, one block over it.
CASES = [(0, 16), (16, 16), (20, 32)]


def assert_lower_rung_is_whole_tables(make, launch, rung, atol):
    """``launch(engine)`` — :func:`last_chunk` or :func:`hit_tail` behind
    whatever it needs cached — takes context rung ``rung``, under the
    whole table, and gives the tokens and last-row logits (within
    ``atol``) of the same launch forced through the whole-table
    program. Each side on an engine of its own from ``make()``: a hit
    leaves its own blocks in the prefix cache, and the next would reach
    further."""
    toks, last, gathered = launch(make())
    assert gathered == rung < 64
    eng = make()
    eng.extend_ladder = eng.extend_ladder[-1:]  # every launch on the top rung, as before the ladder
    whole_toks, whole, gathered = launch(eng)
    assert gathered == 64
    np.testing.assert_allclose(last, whole, atol=atol)
    assert toks == whole_toks


def _gathered(eng) -> int:
    return eng.registry.counter(schema.EXTEND_GATHERED_TOKENS).value


def _decoded(eng, slot, tok, last, n_new):
    toks = [tok]
    for _ in range(n_new - 1):
        toks.append(eng.decode([(slot, toks[-1], 0, 0.0, 0)])[slot])
    eng.pool.free(slot)
    return toks, last


def last_chunk(eng, prompt, ctx, n_new=4):
    """``prompt`` through chunks planned by hand, the last of them the
    tokens from ``ctx`` on, then ``n_new - 1`` decode steps: (tokens,
    the last chunk's last-row logits, the context columns that chunk's
    launch gathered)."""
    bs, chunk = eng.cfg.kv_block_size, eng.cfg.prefill_chunk_tokens
    slot = eng.pool.alloc()
    eng.pool.claim_prompt_blocks(slot, prompt)
    state = engine_mod.ChunkedPrefill(
        slot, list(prompt),
        scheduler.plan_chunks(ctx, 0, chunk, bs) + [(ctx, len(prompt))],
        0, 0.0, 0,
    )
    done = False
    while not done:
        before = _gathered(eng)
        done, tok, last = eng.prefill_step(state)
    gathered = _gathered(eng) - before
    return (*_decoded(eng, slot, tok, last, n_new), gathered)


def hit_tail(eng, prompt, n_new=4):
    """``prompt`` through ``engine.prefill`` — a prefix hit where its
    head is cached: only the tail runs —, then ``n_new - 1`` decode
    steps: (tokens, last-row logits, the context columns gathered)."""
    slot = eng.pool.alloc()
    before = _gathered(eng)
    tok, last = eng.prefill(slot, prompt)
    gathered = _gathered(eng) - before
    return (*_decoded(eng, slot, tok, last, n_new), gathered)
