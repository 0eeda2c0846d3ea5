"""Compiled-kernel numerics on the TPU (SURVEY.md §4).

The CPU suite proves the Pallas kernels in interpret mode; this module
proves the SAME kernels compiled by Mosaic on the real chip, at real
workload shapes, against the XLA reference implementations. With no
TPU the session fails in ``conftest.py`` before anything here runs.

Tolerances: inputs are bf16 (the production precision policy), softmax /
logsumexp accumulate in f32 in both the kernel and the reference, so
disagreement is bf16 rounding of inputs/outputs plus reordered f32
accumulation — a few ULP of bf16, hence the 2e-2 absolute bands below.
"""

import jax
import jax.numpy as jnp
import pytest

from tensorflow_examples_tpu.ops.attention import (
    attention_reference,
    flash_attention,
    flash_attention_with_lse,
)
from tensorflow_examples_tpu.ops.cross_entropy import (
    cross_entropy_per_example,
    cross_entropy_reference,
)


def _qkv(b, h, s, d, dtype=jnp.bfloat16, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (b, h, s, d)
    return tuple(jax.random.normal(k, shape, dtype) for k in keys)


def _max_abs(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))


@pytest.mark.parametrize("causal", [True, False])
def test_flash_fwd_compiled_parity(causal):
    # GPT-2 124M attention shape: 12 heads, seq 1024, head_dim 64.
    q, k, v = _qkv(2, 12, 1024, 64)
    out = flash_attention(q, k, v, causal=causal, interpret=False)
    ref = attention_reference(q, k, v, causal=causal)
    assert out.dtype == q.dtype
    assert _max_abs(out, ref) < 2e-2


def test_flash_bwd_compiled_parity():
    q, k, v = _qkv(2, 12, 1024, 64)
    g = jax.random.normal(jax.random.PRNGKey(7), q.shape, q.dtype)

    def loss(f):
        def inner(q, k, v):
            return jnp.sum(f(q, k, v).astype(jnp.float32) * g.astype(jnp.float32))

        return jax.grad(inner, argnums=(0, 1, 2))

    flash = lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=False)
    ref = lambda q, k, v: attention_reference(q, k, v, causal=True)
    for got, want in zip(jax.jit(loss(flash))(q, k, v), jax.jit(loss(ref))(q, k, v)):
        # Gradients sum seq-many bf16 contributions; scale tolerance with
        # the reference's magnitude rather than assuming unit scale.
        band = 2e-2 * (1.0 + float(jnp.max(jnp.abs(want.astype(jnp.float32)))))
        assert _max_abs(got, want) < band


def test_flash_lse_compiled_parity():
    q, k, v = _qkv(1, 8, 2048, 64, seed=3)
    out, lse = flash_attention_with_lse(q, k, v, causal=True, interpret=False)
    ref = attention_reference(q, k, v, causal=True)
    # Reference lse computed directly (f32, causal-masked).
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * (64**-0.5)
    row = jax.lax.broadcasted_iota(jnp.int32, s.shape[-2:], 0)
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape[-2:], 1)
    s = jnp.where(row >= col, s, -1e30)
    ref_lse = jax.nn.logsumexp(s, axis=-1)
    assert _max_abs(out, ref) < 2e-2
    assert _max_abs(lse, ref_lse) < 2e-2


def test_flash_key_bias_compiled_parity():
    # BERT padding-mask shape: non-causal, [batch, seq] key bias.
    q, k, v = _qkv(2, 12, 512, 64, seed=5)
    kb = jnp.where(
        jnp.arange(512)[None] < jnp.asarray([512, 300])[:, None], 0.0, -1e30
    ).astype(jnp.float32)
    out = flash_attention(
        q, k, v, causal=False, key_bias=kb, interpret=False
    )
    ref = attention_reference(q, k, v, causal=False, key_bias=kb)
    assert _max_abs(out, ref) < 2e-2


def test_flash_key_bias_bwd_compiled_parity():
    # The Mosaic rank-2 block constraint that broke the fwd bias spec
    # applied equally to both bwd kernels' kb specs; prove them compiled
    # too (interpret mode never enforces the constraint).
    q, k, v = _qkv(2, 12, 512, 64, seed=6)
    kb = jnp.where(
        jnp.arange(512)[None] < jnp.asarray([512, 300])[:, None], 0.0, -1e30
    ).astype(jnp.float32)
    g = jax.random.normal(jax.random.PRNGKey(8), q.shape, q.dtype)

    def grads(f):
        def inner(q, k, v):
            return jnp.sum(f(q, k, v).astype(jnp.float32) * g.astype(jnp.float32))

        return jax.jit(jax.grad(inner, argnums=(0, 1, 2)))

    flash = lambda q, k, v: flash_attention(
        q, k, v, causal=False, key_bias=kb, interpret=False
    )
    ref = lambda q, k, v: attention_reference(q, k, v, causal=False, key_bias=kb)
    for got, want in zip(grads(flash)(q, k, v), grads(ref)(q, k, v)):
        band = 2e-2 * (1.0 + float(jnp.max(jnp.abs(want.astype(jnp.float32)))))
        assert _max_abs(got, want) < band


def test_flash_decode_compiled_parity():
    from tensorflow_examples_tpu.ops.decode import (
        decode_attention_reference,
        flash_decode_attention,
    )

    # GPT-2 decode shape: 12 heads, 4k cache, single-token step + prefill.
    k = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 4096, 64), jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 4096, 64), jnp.bfloat16)
    for q_len, length in ((1, 1000), (1, 4096), (512, 512), (256, 2048)):
        q = jax.random.normal(
            jax.random.PRNGKey(2), (2, 12, q_len, 64), jnp.bfloat16
        )
        out = flash_decode_attention(
            q, k, v, jnp.asarray(length), interpret=False
        )
        ref = decode_attention_reference(q, k, v, length)
        assert out.dtype == q.dtype
        assert _max_abs(out, ref) < 2e-2, (q_len, length)


def test_paged_decode_compiled_parity():
    """ISSUE 11: the fused paged-decode kernel (block-table gather +
    varlen masked attention in one launch) compiled on chip, fp and
    int8-dequant-in-kernel, against the XLA gather oracle."""
    import numpy as np

    from tensorflow_examples_tpu.core.precision import quantize_int8_rows
    from tensorflow_examples_tpu.ops.paged_decode import (
        paged_decode_attention,
        paged_decode_reference,
    )

    s, h, d, bs, nb_pool = 8, 12, 64, 16, 65
    rng = np.random.default_rng(0)
    q = jax.random.normal(jax.random.PRNGKey(0), (s, h, d), jnp.float32)
    # The paged pool's per-layer layout: [NB, BS, H*D].
    kb = jax.random.normal(
        jax.random.PRNGKey(1), (nb_pool, bs, h * d), jnp.float32
    )
    vb = jax.random.normal(
        jax.random.PRNGKey(2), (nb_pool, bs, h * d), jnp.float32
    )
    nb = 8  # bucket = 128 rows
    perm = rng.permutation(np.arange(1, nb_pool))
    tables = jnp.asarray(
        perm[: s * nb].reshape(s, nb), jnp.int32
    )
    lengths = jnp.asarray(
        [1, 15, 16, 17, 64, 100, 127, 128], jnp.int32
    )
    out = paged_decode_attention(
        q, kb, vb, lengths, tables, interpret=False
    )
    ref = paged_decode_reference(q, kb, vb, lengths, tables)
    assert _max_abs(out, ref) < 2e-2
    heads = lambda x: x.reshape(nb_pool, bs, h, d)
    qk, ks = quantize_int8_rows(heads(kb))  # scales [NB, BS, H]
    qv, vs = quantize_int8_rows(heads(vb))
    qk, qv = qk.reshape(kb.shape), qv.reshape(vb.shape)
    out8 = paged_decode_attention(
        q, qk, qv, lengths, tables, k_scale=ks, v_scale=vs,
        interpret=False,
    )
    ref8 = paged_decode_reference(
        q, qk, qv, lengths, tables, k_scale=ks, v_scale=vs
    )
    assert _max_abs(out8, ref8) < 2e-2


def test_flash_decode_ladder_compiled_parity():
    """The power-of-two KV-grid bucket ladder (round 4) compiled on
    chip: one jit serves every context length through a 32k-slot cache,
    exact at and around bucket boundaries. Short contexts must also be
    FAST — the grid flatness itself is measured by bench.py
    --bench=decode_grid; this asserts the numerics."""
    from tensorflow_examples_tpu.ops.decode import (
        decode_attention_reference,
        flash_decode_attention,
    )

    k = jax.random.normal(jax.random.PRNGKey(4), (1, 8, 32768, 64), jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(5), (1, 8, 32768, 64), jnp.bfloat16)
    q = jax.random.normal(jax.random.PRNGKey(6), (1, 8, 1, 64), jnp.bfloat16)
    f = jax.jit(lambda q_, k_, v_, n: flash_decode_attention(
        q_, k_, v_, n, interpret=False
    ))
    for length in (200, 256, 257, 4096, 4097, 32768):
        out = f(q, k, v, jnp.asarray(length))
        ref = decode_attention_reference(q, k, v, length)
        assert _max_abs(out, ref) < 2e-2, length


def test_fused_ce_compiled_parity():
    # GPT-2 LM-head shape: one step's tokens against the full 50257 vocab.
    n, v = 2048, 50257
    logits = jax.random.normal(jax.random.PRNGKey(0), (n, v), jnp.bfloat16)
    labels = jax.random.randint(jax.random.PRNGKey(1), (n,), 0, v)
    nll = cross_entropy_per_example(logits, labels, interpret=False)
    ref = cross_entropy_reference(logits, labels)
    assert nll.dtype == jnp.float32
    assert _max_abs(nll, ref) < 2e-2


def test_fused_ce_bwd_compiled_parity():
    n, v = 1024, 50257
    logits = jax.random.normal(jax.random.PRNGKey(2), (n, v), jnp.bfloat16)
    labels = jax.random.randint(jax.random.PRNGKey(3), (n,), 0, v)

    def mean_nll(fn):
        return jax.jit(jax.grad(lambda lg: jnp.mean(fn(lg, labels))))

    got = mean_nll(
        lambda lg, lb: cross_entropy_per_example(lg, lb, interpret=False)
    )(logits)
    want = mean_nll(cross_entropy_reference)(logits)
    # dlogits entries are O(softmax/n) — tiny; absolute band scaled by n.
    assert _max_abs(got, want) < 2e-2 / n * 50


def test_flash_in_scan_compiled_parity():
    """The flash kernel INSIDE a lax.scan body, compiled by Mosaic on
    the chip — the steps_per_launch bundled-step composition. Proves a
    Pallas call under scan lowers/compiles on this backend and that
    per-slice outputs match per-launch calls, clearing the way for
    bundling flash-attention workload benches (the bundled bert/
    cifar10/mnist benches are XLA-attention; this is the flash case)."""
    qs, ks, vs = (
        jax.random.normal(
            jax.random.PRNGKey(i), (2, 1, 4, 256, 64), jnp.bfloat16
        )
        for i in range(3)
    )

    @jax.jit
    def scanned(qs, ks, vs):
        def body(carry, qkv):
            q, k, v = qkv
            o = flash_attention(q, k, v, causal=True, interpret=False)
            return carry + jnp.sum(o.astype(jnp.float32)), o

        return jax.lax.scan(body, jnp.float32(0.0), (qs, ks, vs))

    total, outs = scanned(qs, ks, vs)
    for i in range(2):
        ref = attention_reference(qs[i], ks[i], vs[i], causal=True)
        assert _max_abs(outs[i], ref) < 2e-2, i
    assert float(total) == pytest.approx(
        float(jnp.sum(outs.astype(jnp.float32))), rel=1e-3
    )


def test_moe_grouped_gmm_compiled_parity():
    """The sort-based grouped MoE path on the chip uses the MegaBlocks
    Pallas grouped matmul (``megablox.gmm``) instead of the generic
    masked ragged_dot the CPU tests exercise — so its compiled numerics
    (fwd AND grads) must be proven on silicon against the
    static-capacity scatter reference at a no-drop capacity."""
    from tensorflow_examples_tpu.parallel.moe import moe_ffn

    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    e, d, ff, b, s = 8, 256, 1024, 2, 512  # tile-divisible: gmm engages
    args = (
        jax.random.normal(ks[0], (d, e), jnp.float32) * 0.5,
        jax.random.normal(ks[1], (e, d, ff), jnp.float32) * 0.1,
        jax.random.normal(ks[2], (e, ff), jnp.float32) * 0.01,
        jax.random.normal(ks[3], (e, ff, d), jnp.float32) * 0.1,
        jax.random.normal(ks[4], (e, d), jnp.float32) * 0.01,
        jax.random.normal(ks[5], (b, s, d), jnp.float32),
    )
    kw = dict(capacity_factor=8.0, top_k=2, rng=None)
    want, aux_w, _ = jax.jit(
        lambda *a: moe_ffn(*a, impl="scatter", **kw)
    )(*args)
    got, aux_g, drop = jax.jit(
        lambda *a: moe_ffn(*a, impl="grouped", **kw)
    )(*args)
    assert float(drop) == 0.0
    assert _max_abs(got, want) < 5e-3
    assert float(aux_g) == pytest.approx(float(aux_w), rel=1e-4)

    def loss(impl):
        def f(*a):
            out, aux, _ = moe_ffn(*a, impl=impl, **kw)
            return jnp.sum(out.astype(jnp.float32) ** 2) + 0.01 * aux

        return jax.jit(jax.grad(f, argnums=(0, 1, 3, 5)))

    for g_ref, g_new, name in zip(
        loss("scatter")(*args), loss("grouped")(*args),
        ("gate", "w_in", "w_out", "x"),
    ):
        band = 5e-3 * (1.0 + _max_abs(g_ref, jnp.zeros_like(g_ref)))
        assert _max_abs(g_new, g_ref) < band, name
