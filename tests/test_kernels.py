"""Pallas kernel numerics vs pure-XLA references (SURVEY.md §4).

Runs the real kernel code in Pallas interpret mode on CPU; on TPU the
same code path compiles via Mosaic (exercised by bench.py / examples).
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflow_examples_tpu.ops.attention import (
    attention_reference,
    flash_attention,
)
from tensorflow_examples_tpu.ops.cross_entropy import (
    cross_entropy_loss,
    cross_entropy_per_example,
    cross_entropy_reference,
)
from tensorflow_examples_tpu.ops.decode import (
    decode_attention_reference,
    flash_decode_attention,
)


def _qkv(rng, shape, dtype):
    ks = jax.random.split(rng, 3)
    return [jax.random.normal(k, shape, dtype) for k in ks]


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("seq", [128, 256])
    def test_forward_matches_reference(self, causal, seq):
        q, k, v = _qkv(jax.random.PRNGKey(0), (2, 3, seq, 64), jnp.float32)
        out = flash_attention(q, k, v, causal=causal, block_q=128, block_kv=128)
        ref = attention_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_forward_bf16(self):
        q, k, v = _qkv(jax.random.PRNGKey(1), (1, 2, 256, 64), jnp.bfloat16)
        out = flash_attention(q, k, v, causal=True)
        ref = attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(
            out.astype(np.float32), ref.astype(np.float32), atol=2e-2, rtol=2e-2
        )

    @pytest.mark.parametrize("causal", [True, False])
    def test_gradients_match_reference(self, causal):
        q, k, v = _qkv(jax.random.PRNGKey(2), (1, 2, 256, 64), jnp.float32)

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=causal) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(attention_reference(q, k, v, causal=causal) ** 2)

        g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(g_flash, g_ref, "qkv"):
            np.testing.assert_allclose(
                a, b, atol=5e-4, rtol=5e-4, err_msg=f"d{name}"
            )

    def test_uneven_blocks(self):
        # seq divisible by blocks but blocks differ; causal offsets exercise
        # the loop-bound math.
        q, k, v = _qkv(jax.random.PRNGKey(3), (1, 1, 256, 64), jnp.float32)
        out = flash_attention(q, k, v, causal=True, block_q=64, block_kv=128)
        ref = attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_cross_attention_lengths(self):
        # seq_q != seq_kv: causal diagonal is bottom-right aligned, like
        # the reference; exercises the offset loop-bound math.
        rng = jax.random.PRNGKey(5)
        q = jax.random.normal(rng, (1, 2, 128, 64))
        k = jax.random.normal(jax.random.PRNGKey(6), (1, 2, 384, 64))
        v = jax.random.normal(jax.random.PRNGKey(7), (1, 2, 384, 64))
        for causal in (True, False):
            out = flash_attention(q, k, v, causal=causal, block_q=64, block_kv=128)
            ref = attention_reference(q, k, v, causal=causal)
            np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
        # Gradients through the offset path too.
        g = jax.grad(
            lambda q, k, v: jnp.sum(flash_attention(q, k, v, causal=True) ** 2),
            argnums=(0, 1, 2),
        )(q, k, v)
        gr = jax.grad(
            lambda q, k, v: jnp.sum(attention_reference(q, k, v, causal=True) ** 2),
            argnums=(0, 1, 2),
        )(q, k, v)
        for a, b, name in zip(g, gr, "qkv"):
            np.testing.assert_allclose(
                a, b, atol=5e-4, rtol=5e-4, err_msg=f"d{name}"
            )

    def test_jit_compatible(self):
        q, k, v = _qkv(jax.random.PRNGKey(4), (2, 2, 128, 64), jnp.float32)
        jitted = jax.jit(lambda q, k, v: flash_attention(q, k, v))
        np.testing.assert_allclose(
            jitted(q, k, v), flash_attention(q, k, v), atol=1e-6, rtol=1e-6
        )

    @pytest.mark.parametrize("causal", [False, True])
    def test_key_bias_matches_reference(self, causal):
        """Padding-mask bias (the BERT shape): [batch, seq_kv] additive,
        broadcast over heads/rows, spanning multiple KV blocks so the
        per-block bias tiles are exercised."""
        q, k, v = _qkv(jax.random.PRNGKey(8), (2, 3, 256, 64), jnp.float32)
        # Batch row 0 masks the last 77 keys; row 1 masks none.
        from tensorflow_examples_tpu.ops.attention import NEG_INF

        kb = np.zeros((2, 256), np.float32)
        kb[0, -77:] = NEG_INF
        kb = jnp.asarray(kb)
        out = flash_attention(
            q, k, v, causal=causal, key_bias=kb, block_q=64, block_kv=64
        )
        ref = attention_reference(q, k, v, causal=causal, key_bias=kb)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_key_bias_gradients(self):
        """Grads wrt q/k/v through the biased kernel must match the
        reference; the bias cotangent is defined as zero (mask data)."""
        q, k, v = _qkv(jax.random.PRNGKey(9), (1, 2, 128, 64), jnp.float32)
        from tensorflow_examples_tpu.ops.attention import NEG_INF

        kb = jnp.asarray(
            np.where(np.arange(128) < 100, 0.0, NEG_INF)[None], jnp.float32
        )

        def loss(f):
            return lambda q, k, v: jnp.sum(
                f(q, k, v) ** 2
            )

        flash = lambda q, k, v: flash_attention(
            q, k, v, causal=False, key_bias=kb
        )
        ref = lambda q, k, v: attention_reference(
            q, k, v, causal=False, key_bias=kb
        )
        g_flash = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(g_flash, g_ref, "qkv"):
            np.testing.assert_allclose(
                a, b, atol=5e-4, rtol=5e-4, err_msg=f"d{name}"
            )
        # Masked keys must contribute exactly zero dk/dv.
        np.testing.assert_allclose(np.asarray(g_flash[1])[:, :, 100:], 0.0)
        np.testing.assert_allclose(np.asarray(g_flash[2])[:, :, 100:], 0.0)


class TestFlashDecode:
    """KV-cache flash-decode kernel vs the masked-XLA reference."""

    @pytest.mark.parametrize(
        "q_len,length",
        [(1, 1), (1, 13), (1, 512), (7, 200), (128, 128), (96, 300)],
    )
    def test_matches_reference(self, q_len, length):
        rng = jax.random.PRNGKey(0)
        q = jax.random.normal(rng, (2, 3, q_len, 64))
        k = jax.random.normal(jax.random.PRNGKey(1), (2, 3, 512, 64))
        v = jax.random.normal(jax.random.PRNGKey(2), (2, 3, 512, 64))
        out = flash_decode_attention(q, k, v, jnp.asarray(length))
        ref = decode_attention_reference(q, k, v, length)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_garbage_cache_tail_ignored(self):
        """Slots ≥ length must not affect the output (they hold stale or
        uninitialized data in real decode)."""
        q = jax.random.normal(jax.random.PRNGKey(3), (1, 2, 1, 64))
        k = jax.random.normal(jax.random.PRNGKey(4), (1, 2, 256, 64))
        v = jax.random.normal(jax.random.PRNGKey(5), (1, 2, 256, 64))
        out = flash_decode_attention(q, k, v, jnp.asarray(100))
        k2 = k.at[:, :, 100:].set(1e4)
        v2 = v.at[:, :, 100:].set(-1e4)
        out2 = flash_decode_attention(q, k2, v2, jnp.asarray(100))
        np.testing.assert_allclose(out, out2, atol=0, rtol=0)

    def test_overlong_length_clamps_like_traced(self):
        """length > max_len: the static path must clamp to the full
        cache exactly like the traced path's searchsorted clamp (it
        used to raise a bare StopIteration); both must equal the
        full-cache answer."""
        q = jax.random.normal(jax.random.PRNGKey(9), (1, 2, 1, 64))
        k = jax.random.normal(jax.random.PRNGKey(10), (1, 2, 256, 64))
        v = jax.random.normal(jax.random.PRNGKey(11), (1, 2, 256, 64))
        full = decode_attention_reference(q, k, v, 256)
        np.testing.assert_allclose(
            flash_decode_attention(q, k, v, 300), full, atol=2e-5, rtol=2e-5
        )
        np.testing.assert_allclose(
            flash_decode_attention(q, k, v, jnp.asarray(300)),
            full, atol=2e-5, rtol=2e-5,
        )

    def test_jit_traced_length(self):
        """length as a traced scalar: one compile serves every context
        size — the property the generate() scan relies on."""
        q = jax.random.normal(jax.random.PRNGKey(6), (1, 2, 1, 64))
        k = jax.random.normal(jax.random.PRNGKey(7), (1, 2, 256, 64))
        v = jax.random.normal(jax.random.PRNGKey(8), (1, 2, 256, 64))
        f = jax.jit(flash_decode_attention)
        for n in (1, 77, 256):
            np.testing.assert_allclose(
                f(q, k, v, jnp.asarray(n)),
                decode_attention_reference(q, k, v, n),
                atol=2e-5, rtol=2e-5,
            )

    def test_odd_lengths_partial_blocks(self):
        """max_len/q_len without a block divisor (e.g. 4·odd): the cdiv
        grid's padded tail must be fully masked."""
        q = jax.random.normal(jax.random.PRNGKey(9), (1, 2, 36, 64))
        k = jax.random.normal(jax.random.PRNGKey(10), (1, 2, 516, 64))
        v = jax.random.normal(jax.random.PRNGKey(11), (1, 2, 516, 64))
        out = flash_decode_attention(
            q, k, v, jnp.asarray(400), block_q=32, block_kv=256
        )
        ref = decode_attention_reference(q, k, v, 400)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_bucket_ladder_boundaries(self):
        """The power-of-two KV-grid ladder (O(context) sequencing): the
        traced length must pick a sufficient bucket and stay exact at
        and around every bucket boundary, jit'd once for all lengths."""
        q = jax.random.normal(jax.random.PRNGKey(15), (1, 2, 1, 64))
        k = jax.random.normal(jax.random.PRNGKey(16), (1, 2, 1024, 64))
        v = jax.random.normal(jax.random.PRNGKey(17), (1, 2, 1024, 64))
        f = jax.jit(
            functools.partial(flash_decode_attention, block_kv=64)
        )
        for n in (1, 64, 65, 128, 129, 512, 513, 1000, 1024):
            np.testing.assert_allclose(
                f(q, k, v, jnp.asarray(n)),
                decode_attention_reference(q, k, v, n),
                atol=2e-5, rtol=2e-5, err_msg=f"length={n}",
            )

    def test_static_length_single_bucket(self):
        """A Python-int length compiles exactly one bucket, no switch."""
        q = jax.random.normal(jax.random.PRNGKey(18), (1, 2, 1, 64))
        k = jax.random.normal(jax.random.PRNGKey(19), (1, 2, 1024, 64))
        v = jax.random.normal(jax.random.PRNGKey(20), (1, 2, 1024, 64))
        out = flash_decode_attention(q, k, v, 100, block_kv=64)
        ref = decode_attention_reference(q, k, v, 100)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_bf16_cache(self):
        q = jax.random.normal(jax.random.PRNGKey(12), (1, 2, 1, 64), jnp.bfloat16)
        k = jax.random.normal(jax.random.PRNGKey(13), (1, 2, 128, 64), jnp.bfloat16)
        v = jax.random.normal(jax.random.PRNGKey(14), (1, 2, 128, 64), jnp.bfloat16)
        out = flash_decode_attention(q, k, v, jnp.asarray(64))
        ref = decode_attention_reference(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), 64,
        )
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            out.astype(np.float32), ref, atol=2e-2, rtol=2e-2
        )


class TestPagedDecodeKernel:
    """ISSUE 11 satellite: the fused Pallas paged-decode kernel
    (ops/paged_decode.py) pinned element-wise against the XLA gather
    path (the serving oracle) in interpret mode, across the slot-length
    / block-table edge cases the paged pool actually produces."""

    BS, NB, H, D = 8, 9, 2, 16

    def _pool(self, seed=0, dtype=jnp.float32):
        rng = np.random.default_rng(seed)
        # The paged pool's per-layer layout: [NB, BS, H*D].
        shape = (self.NB, self.BS, self.H * self.D)
        k = jnp.asarray(rng.standard_normal(shape), dtype)
        v = jnp.asarray(rng.standard_normal(shape), dtype)
        return k, v

    def _case(self, lengths, tables, seed=0):
        from tensorflow_examples_tpu.ops.paged_decode import (
            paged_decode_attention,
            paged_decode_reference,
        )

        rng = np.random.default_rng(seed + 100)
        s = len(lengths)
        q = jnp.asarray(
            rng.standard_normal((s, self.H, self.D)), jnp.float32
        )
        k, v = self._pool(seed)
        lengths = jnp.asarray(lengths, jnp.int32)
        tables = jnp.asarray(tables, jnp.int32)
        out = paged_decode_attention(q, k, v, lengths, tables)
        ref = paged_decode_reference(q, k, v, lengths, tables)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-6, rtol=2e-6
        )
        return out

    def test_single_block_and_length_one(self):
        self._case([1, 8], [[3, 0], [5, 0]])

    def test_ragged_last_block(self):
        # Lengths ending mid-block: the final block is partially
        # populated and masked, exactly the common decode state.
        self._case([13, 21, 30], [[1, 2, 0, 0], [3, 4, 5, 0],
                                  [6, 7, 8, 2]])

    def test_empty_slot_is_finite_garbage(self):
        # A parked slot (length 0) must come out finite (its output is
        # discarded downstream — both paths emit garbage there, and
        # DIFFERENT garbage: the oracle's all-masked softmax is
        # uniform, the kernel's epsilon-guarded sum is ~0 — so only
        # the populated slot is compared element-wise) and never NaN.
        from tensorflow_examples_tpu.ops.paged_decode import (
            paged_decode_attention,
            paged_decode_reference,
        )

        rng = np.random.default_rng(5)
        q = jnp.asarray(
            rng.standard_normal((2, self.H, self.D)), jnp.float32
        )
        k, v = self._pool(5)
        lengths = jnp.asarray([0, 5], jnp.int32)
        tables = jnp.asarray([[0, 0], [4, 0]], jnp.int32)
        out = paged_decode_attention(q, k, v, lengths, tables)
        ref = paged_decode_reference(q, k, v, lengths, tables)
        assert bool(jnp.all(jnp.isfinite(out)))
        np.testing.assert_allclose(
            np.asarray(out[1]), np.asarray(ref[1]), atol=2e-6, rtol=2e-6
        )

    def test_null_padded_tables_never_leak(self):
        # Two slots share a pool; slot 0's null-padded tail entries
        # must not read slot 1's blocks: perturbing an UNREFERENCED
        # block changes nothing.
        from tensorflow_examples_tpu.ops.paged_decode import (
            paged_decode_attention,
        )

        rng = np.random.default_rng(7)
        q = jnp.asarray(
            rng.standard_normal((1, self.H, self.D)), jnp.float32
        )
        k, v = self._pool(7)
        lengths = jnp.asarray([10], jnp.int32)
        tables = jnp.asarray([[2, 6, 0, 0]], jnp.int32)
        base = paged_decode_attention(q, k, v, lengths, tables)
        k2 = k.at[5].add(100.0)  # block 5 is unreferenced
        v2 = v.at[5].add(100.0)
        again = paged_decode_attention(q, k2, v2, lengths, tables)
        np.testing.assert_array_equal(np.asarray(base), np.asarray(again))

    def test_int8_scales_dequant_in_kernel(self):
        from tensorflow_examples_tpu.core.precision import (
            quantize_int8_rows,
        )
        from tensorflow_examples_tpu.ops.paged_decode import (
            paged_decode_attention,
            paged_decode_reference,
        )

        rng = np.random.default_rng(3)
        s = 3
        q = jnp.asarray(
            rng.standard_normal((s, self.H, self.D)), jnp.float32
        )
        k, v = self._pool(3)
        # Per (block, row, head) scales [NB, BS, H], as the pool keeps.
        heads = lambda x: x.reshape(self.NB, self.BS, self.H, self.D)
        qk, ks = quantize_int8_rows(heads(k))
        qv, vs = quantize_int8_rows(heads(v))
        qk, qv = qk.reshape(k.shape), qv.reshape(v.shape)
        lengths = jnp.asarray([5, 16, 27], jnp.int32)
        tables = jnp.asarray(
            [[1, 0, 0, 0], [2, 3, 0, 0], [4, 5, 6, 7]], jnp.int32
        )
        out = paged_decode_attention(
            q, qk, qv, lengths, tables, k_scale=ks, v_scale=vs
        )
        ref = paged_decode_reference(
            q, qk, qv, lengths, tables, k_scale=ks, v_scale=vs
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-6, rtol=2e-6
        )

    def test_jit_traced_lengths_and_tables(self):
        from tensorflow_examples_tpu.ops.paged_decode import (
            paged_decode_attention,
            paged_decode_reference,
        )

        rng = np.random.default_rng(11)
        q = jnp.asarray(
            rng.standard_normal((2, self.H, self.D)), jnp.float32
        )
        k, v = self._pool(11)
        fn = jax.jit(
            lambda *a: paged_decode_attention(*a, interpret=True)
        )
        lengths = jnp.asarray([7, 19], jnp.int32)
        tables = jnp.asarray([[3, 0, 0], [1, 2, 4]], jnp.int32)
        out = fn(q, k, v, lengths, tables)
        ref = paged_decode_reference(q, k, v, lengths, tables)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-6, rtol=2e-6
        )

    def test_scale_pairing_enforced(self):
        from tensorflow_examples_tpu.ops.paged_decode import (
            paged_decode_attention,
        )

        k, v = self._pool()
        with pytest.raises(ValueError, match="both k_scale and v_scale"):
            paged_decode_attention(
                jnp.zeros((1, self.H, self.D)), k, v,
                jnp.ones((1,), jnp.int32),
                jnp.zeros((1, 2), jnp.int32),
                k_scale=jnp.ones((self.NB, self.BS, self.H)),
            )


class TestFusedCrossEntropy:
    @pytest.mark.parametrize("vocab", [1000, 50257])
    def test_forward_matches_reference(self, vocab):
        rng = jax.random.PRNGKey(0)
        logits = jax.random.normal(rng, (64, vocab), jnp.float32) * 3
        labels = jax.random.randint(jax.random.PRNGKey(1), (64,), 0, vocab)
        nll = cross_entropy_per_example(logits, labels, fused=True)
        ref = cross_entropy_reference(logits, labels)
        np.testing.assert_allclose(nll, ref, atol=1e-5, rtol=1e-5)

    def test_gradient_matches_reference(self):
        vocab = 4099  # not divisible by block_v: exercises padding mask
        logits = jax.random.normal(jax.random.PRNGKey(2), (32, vocab))
        labels = jax.random.randint(jax.random.PRNGKey(3), (32,), 0, vocab)

        g_fused = jax.grad(
            lambda l: jnp.mean(cross_entropy_per_example(l, labels, fused=True))
        )(logits)
        g_ref = jax.grad(
            lambda l: jnp.mean(cross_entropy_reference(l, labels))
        )(logits)
        np.testing.assert_allclose(g_fused, g_ref, atol=1e-6, rtol=1e-5)

    def test_loss_weighted_mean_masks_padding(self):
        logits = jax.random.normal(jax.random.PRNGKey(4), (2, 8, 512))
        labels = jax.random.randint(jax.random.PRNGKey(5), (2, 8), 0, 512)
        weights = jnp.ones((2, 8)).at[:, -3:].set(0.0)
        loss = cross_entropy_loss(logits, labels, weights, fused=True)
        ref_rows = cross_entropy_reference(
            logits.reshape(-1, 512), labels.reshape(-1)
        ).reshape(2, 8)
        expected = np.sum(np.asarray(ref_rows) * np.asarray(weights)) / np.sum(
            np.asarray(weights)
        )
        np.testing.assert_allclose(float(loss), expected, rtol=1e-6)

    def test_bf16_logits(self):
        logits = jax.random.normal(
            jax.random.PRNGKey(6), (16, 1024), jnp.bfloat16
        )
        labels = jax.random.randint(jax.random.PRNGKey(7), (16,), 0, 1024)
        nll = cross_entropy_per_example(logits, labels, fused=True)
        ref = cross_entropy_reference(logits.astype(jnp.float32), labels)
        np.testing.assert_allclose(nll, ref, atol=2e-2, rtol=2e-2)

    @pytest.mark.parametrize(
        "b,s",
        [
            (8, 16),  # everything divides: full batch+seq+model sharding
            (1, 16),  # batch 1 on a dp mesh: batch axes dropped
            (8, 7),   # seq indivisible by model/context: seq axes dropped
            (3, 5),   # nothing divides: degenerates to the plain call
        ],
    )
    def test_mesh_ce_matches_plain_across_divisibility(self, b, s):
        """mesh_cross_entropy_per_example must reproduce the unsharded
        NLL for every branch of the shared axis-dropping policy
        (core/mesh.py token_partition_axes) — including the replicated
        fallbacks for decode-time batch=1 and odd seq lengths."""
        from tensorflow_examples_tpu.core.mesh import MeshConfig, create_mesh
        from tensorflow_examples_tpu.ops.cross_entropy import (
            mesh_cross_entropy_per_example,
        )

        vocab = 97
        mesh = create_mesh(MeshConfig(data=2, model=2, context=2))
        logits = jax.random.normal(jax.random.PRNGKey(8), (b, s, vocab))
        labels = jax.random.randint(
            jax.random.PRNGKey(9), (b, s), 0, vocab
        )
        want = cross_entropy_reference(
            logits.reshape(-1, vocab), labels.reshape(-1)
        ).reshape(b, s)
        got = jax.jit(
            functools.partial(mesh_cross_entropy_per_example, mesh=mesh)
        )(logits, labels)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
        )


def test_block_autofit_odd_lengths():
    """Auto (None) block sizes must fit sequences the 256 target doesn't
    divide, stepping down through hardware-legal (multiple-of-128, then
    multiple-of-8) divisors; pathological lengths raise instead of
    degenerating, and explicit block sizes are enforced, not overridden."""
    import jax
    import pytest

    from tensorflow_examples_tpu.ops.attention import (
        _fit_block,
        _resolve_block,
        attention_reference,
        flash_attention,
    )

    assert _fit_block(256, 384) == 128  # prefers the 128-multiple divisor
    assert _fit_block(256, 320) == 160  # no 128-multiple divides 320; 8-mult
    assert _fit_block(256, 256) == 256
    assert _fit_block(256, 100) == 100  # whole sequence as one block
    with pytest.raises(ValueError):  # 1021 prime: no legal tiling
        _fit_block(256, 1021)
    with pytest.raises(ValueError):  # explicit size that doesn't divide
        _resolve_block(192, 1024)
    for s in (320, 384):
        q = jax.random.normal(jax.random.PRNGKey(0), (1, 2, s, 64))
        k = jax.random.normal(jax.random.PRNGKey(1), (1, 2, s, 64))
        v = jax.random.normal(jax.random.PRNGKey(2), (1, 2, s, 64))
        out = flash_attention(q, k, v, causal=True)
        ref = attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


# ------------------------------------------- the plan and the three kernels


def _reference_with_lse(q, k, v, *, causal, key_bias=None):
    """attention_reference plus the row logsumexp it never returns."""
    from tensorflow_examples_tpu.ops.attention import NEG_INF

    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * q.shape[-1] ** -0.5
    if key_bias is not None:
        s = s + key_bias[:, None, None, :]
    if causal:
        sq, sk = s.shape[-2:]
        row = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        s = jnp.where(row + (sk - sq) >= col, s, NEG_INF)
    o = attention_reference(q, k, v, causal=causal, key_bias=key_bias)
    return o, jax.nn.logsumexp(s, axis=-1)


# name -> (seq_q, seq_kv, causal, block_q, block_kv, key_bias?, lse?).
# None blocks = the tiles flash_blocks plans, per kernel.
_PLAN_CASES = {
    "planned_1024_causal": (1024, 1024, True, None, None, False, False),
    "planned_1024_full": (1024, 1024, False, None, None, False, False),
    "wide_kv_tiles": (512, 512, True, 128, 256, False, False),
    "tall_q_tiles": (512, 512, True, 256, 128, False, False),
    "key_bias": (256, 256, False, 128, 64, True, False),
    "key_bias_causal": (256, 256, True, 64, 128, True, False),
    "lse_cotangent": (256, 256, True, 64, 128, False, True),
    "short_queries": (128, 384, True, 64, 128, False, True),
}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("case", sorted(_PLAN_CASES))
def test_flash_kernels_match_reference(case, dtype):
    """Forward and all three gradients against attention_reference at
    the planned tiles for (1,024, 64) and at tiles with block_q !=
    block_kv, with a key bias, with an lse cotangent and with fewer
    queries than keys — at the operands' own width (bf16 bands are
    tests_tpu's: 2e-2, and 2e-2 x (1 + max|want|) for gradients) and in
    f32 at the f32 tests' tolerances."""
    from tensorflow_examples_tpu.ops.attention import (
        NEG_INF,
        flash_attention_with_lse,
    )

    seq_q, seq_kv, causal, block_q, block_kv, biased, with_lse = _PLAN_CASES[case]
    ks = jax.random.split(jax.random.PRNGKey(11), 5)
    q = jax.random.normal(ks[0], (2, 2, seq_q, 64), dtype)
    k = jax.random.normal(ks[1], (2, 2, seq_kv, 64), dtype)
    v = jax.random.normal(ks[2], (2, 2, seq_kv, 64), dtype)
    g = jax.random.normal(ks[3], q.shape, jnp.float32)
    h = jax.random.normal(ks[4], q.shape[:3], jnp.float32)
    kb = None
    if biased:  # batch row 0 pads its last 77 keys away
        kb = jnp.zeros((2, seq_kv), jnp.float32).at[0, -77:].set(NEG_INF)
    blocks = dict(block_q=block_q, block_kv=block_kv)

    def flash(q, k, v):
        if with_lse:
            return flash_attention_with_lse(q, k, v, causal=causal, **blocks)
        o = flash_attention(q, k, v, causal=causal, key_bias=kb, **blocks)
        return o, jnp.zeros(q.shape[:3], jnp.float32)

    def reference(q, k, v):
        o, lse = _reference_with_lse(q, k, v, causal=causal, key_bias=kb)
        return o, lse if with_lse else jnp.zeros_like(lse)

    def loss(f):
        def inner(q, k, v):
            o, lse = f(q, k, v)
            return jnp.sum(o.astype(jnp.float32) * g) + jnp.sum(lse * h)

        return inner

    bf16 = dtype == jnp.bfloat16
    for got, want in zip(flash(q, k, v), reference(q, k, v)):
        tol = 2e-2 if bf16 else 2e-5
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            atol=tol, rtol=tol,
        )
    got = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(reference), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(got, want, "qkv"):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        if bf16:
            assert np.max(np.abs(a - b)) < 2e-2 * (1 + np.max(np.abs(b))), name
        else:
            np.testing.assert_allclose(
                a, b, atol=5e-4, rtol=5e-4, err_msg=f"d{name}"
            )


@pytest.mark.parametrize("span", [128, 256])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_walks_several_chunks(monkeypatch, causal, span):
    """Sequences longer than one group x chunk rectangle walk several on
    the grid, the running state in scratch; shrink the span so 256
    queries against 512 keys are 2 x 4 rectangles of one 128-row tile,
    or 1 x 2 of two — crossed by the diagonal at offsets other than 0."""
    from tensorflow_examples_tpu.ops import attention

    monkeypatch.setattr(attention, "_SPAN", span)
    assert attention._span(512, 128) == span
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 256, 64))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 512, 64))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 2, 512, 64))
    flash = lambda q, k, v: flash_attention(
        q, k, v, causal=causal, block_q=128, block_kv=128
    )
    ref = lambda q, k, v: attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(flash(q, k, v), ref(q, k, v), atol=2e-5, rtol=2e-5)
    loss = lambda f: lambda q, k, v: jnp.sum(f(q, k, v) ** 2)
    got = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4, err_msg=f"d{name}")


@pytest.mark.parametrize("seq", [128, 320, 384, 512, 1024, 4096])
def test_flash_blocks_are_legal_tiles(seq):
    """Every kernel's planned tile divides the length and is a multiple
    of 128 where one divides it, else of 8 (the whole sequence when it
    is shorter than the target)."""
    from tensorflow_examples_tpu.ops.attention import KERNELS, flash_blocks

    for kernel in KERNELS:
        for causal in (True, False):
            blocks = flash_blocks(seq, seq, 64, jnp.bfloat16, causal, kernel)
            for b in blocks:
                assert seq % b == 0 and b % 8 == 0, (kernel, blocks)
                if seq % 128 == 0:
                    assert b % 128 == 0, (kernel, blocks)


def test_flash_blocks_refuse_untileable_length():
    from tensorflow_examples_tpu.ops.attention import flash_blocks

    with pytest.raises(ValueError, match="multiple-of-8"):  # 1021 is prime
        flash_blocks(1021, 1021, 64, jnp.bfloat16, True, "fwd")
    with pytest.raises(KeyError):
        flash_blocks(1024, 1024, 64, jnp.bfloat16, True, "dv")


def _kernel_products(fn, *args):
    """(operand dtypes, result dtype) of every dot_general inside every
    pallas_call of ``fn``'s jaxpr, by kernel."""
    found = []

    def jaxprs_in(value):
        if hasattr(value, "eqns"):
            yield value
        elif hasattr(value, "jaxpr"):
            yield from jaxprs_in(value.jaxpr)
        elif isinstance(value, (tuple, list)):
            for item in value:
                yield from jaxprs_in(item)

    def walk(jaxpr, kernel):
        for eqn in jaxpr.eqns:
            inside = kernel
            if eqn.primitive.name == "pallas_call":
                found.append([])
                inside = found[-1]
            if eqn.primitive.name == "dot_general" and inside is not None:
                inside.append((
                    tuple(v.aval.dtype for v in eqn.invars),
                    eqn.outvars[0].aval.dtype,
                ))
            for value in eqn.params.values():
                for sub in jaxprs_in(value):
                    walk(sub, inside)

    walk(jax.make_jaxpr(fn)(*args).jaxpr, None)
    return found


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def test_flash_products_take_the_operands_own_width(dtype):
    """All nine products of the three kernels (two forward, three dq,
    four dk/dv; traced once per static body) take their operands in the
    caller's dtype and accumulate in f32: bf16 inputs feed the MXU bf16,
    f32 inputs f32 as before."""
    q, k, v = _qkv(jax.random.PRNGKey(0), (1, 2, 256, 64), dtype)
    kernels = _kernel_products(
        jax.grad(
            lambda q, k, v: jnp.sum(
                flash_attention(q, k, v, causal=True).astype(jnp.float32)
            ),
            argnums=(0, 1, 2),
        ),
        q, k, v,
    )
    # forward, dq, dk/dv in the order they are called: two, three and
    # four products a traced body (masked and plain bodies both count).
    assert len(kernels) == 3
    for products, each in zip(kernels, (2, 3, 4)):
        assert products and len(products) % each == 0, (each, products)
        for operands, result in products:
            assert operands == (dtype, dtype), products
            assert result == jnp.float32, products


def test_flash_plan_recorded_once_per_traced_shape():
    """span/flash_plan carries the shapes, the dtype and the three tile
    pairs, once per shape and never per call."""
    from tensorflow_examples_tpu.ops.attention import KERNELS, flash_blocks
    from tensorflow_examples_tpu.telemetry import spans

    plans = lambda: [
        e for e in spans._default.events() if e["name"] == "flash_plan"
    ]
    before = len(plans())
    q, k, v = _qkv(jax.random.PRNGKey(0), (1, 1, 136, 8), jnp.float32)
    for _ in range(2):
        flash_attention(q, k, v, causal=True)
    (event,) = plans()[before:]
    assert event["args"]["q"] == "(1, 1, 136, 8)"
    assert event["args"]["dtype"] == "float32"
    for kernel in KERNELS:
        want = flash_blocks(136, 136, 8, jnp.float32, True, kernel)
        assert event["args"][kernel] == str(want)


# ------------------------------------------------ chip-free TPU lowering
#
# Interpret mode never enforces Mosaic's block-shape rules, so a kernel
# can pass every parity test above and still be unlowerable for the TPU
# (ops/paged_decode.py shipped that way). Cross-lowering on the CPU runs
# the Pallas TPU lowering's Python-side checks at the shapes GPT-2 124M
# actually uses; the compiled numerics are tests_tpu/'s job on the chip.

_S = jax.ShapeDtypeStruct


def _paged_args(kv_dtype, q_dtype=jnp.float32):
    return (
        _S((8, 12, 64), q_dtype),
        _S((512, 16, 768), kv_dtype),
        _S((512, 16, 768), kv_dtype),
        _S((8,), jnp.int32),
        _S((8, 64), jnp.int32),
    )


def _lowering_cases():
    from tensorflow_examples_tpu.ops.attention import (
        flash_attention_with_lse,
    )
    from tensorflow_examples_tpu.ops.paged_decode import (
        paged_decode_attention,
    )

    bf16, f32 = jnp.bfloat16, jnp.float32
    qkv = (_S((2, 12, 1024, 64), bf16),) * 3
    cell = (_S((16, 12, 1024, 64), bf16),) * 3
    flash = functools.partial(flash_attention, causal=True, interpret=False)
    total = lambda f: lambda *a: jnp.sum(f(*a).astype(f32))
    logits = _S((16 * 1023, 50257), bf16)
    labels = _S((16 * 1023,), jnp.int32)
    ce = functools.partial(cross_entropy_per_example, interpret=False)
    decode = functools.partial(flash_decode_attention, interpret=False)
    paged = functools.partial(paged_decode_attention, interpret=False)
    scales = (_S((512, 16, 12), f32),) * 2
    return {
        "flash_fwd": (flash, qkv),
        "flash_grad": (jax.grad(total(flash), argnums=(0, 1, 2)), qkv),
        "flash_lse": (
            functools.partial(
                flash_attention_with_lse, causal=True, interpret=False
            ),
            qkv,
        ),
        "flash_key_bias": (
            lambda q, k, v, kb: flash_attention(
                q, k, v, causal=False, key_bias=kb, interpret=False
            ),
            (_S((2, 12, 512, 64), bf16),) * 3 + (_S((2, 512), f32),),
        ),
        # The training cell's own call (batch 16 x 12 heads, bf16) ...
        "flash_cell_fwd": (flash, cell),
        "flash_cell_grad": (jax.grad(total(flash), argnums=(0, 1, 2)), cell),
        # ... and BERT's: non-causal, a padding bias, seq 128.
        "flash_key_bias_grad": (
            jax.grad(
                lambda q, k, v, kb: jnp.sum(flash_attention(
                    q, k, v, causal=False, key_bias=kb, interpret=False
                ).astype(f32)),
                argnums=(0, 1, 2),
            ),
            (_S((8, 12, 128, 64), bf16),) * 3 + (_S((8, 128), f32),),
        ),
        "decode_step": (
            decode,
            (_S((8, 12, 1, 64), bf16),)
            + (_S((8, 12, 1024, 64), bf16),) * 2
            + (_S((), jnp.int32),),
        ),
        # The engine's flash prefill: the fresh K/V ARE the cache and
        # the bucket length is static (f32, smallest rung).
        "decode_prefill_rung": (
            lambda q, k, v: decode(q, k, v, 16),
            (_S((1, 12, 16, 64), f32),) * 3,
        ),
        "fused_ce_fwd": (ce, (logits, labels)),
        "fused_ce_grad": (
            jax.grad(lambda lg, lb: jnp.mean(ce(lg, lb))), (logits, labels)
        ),
        "paged_decode_f32": (paged, _paged_args(f32)),
        "paged_decode_bf16": (paged, _paged_args(bf16, bf16)),
        "paged_decode_int8": (
            lambda q, k, v, ln, tb, ks, vs: paged(
                q, k, v, ln, tb, k_scale=ks, v_scale=vs
            ),
            _paged_args(jnp.int8) + scales,
        ),
    }


@pytest.mark.parametrize("name", sorted(_lowering_cases()))
def test_pallas_entry_points_lower_for_tpu(name):
    fn, args = _lowering_cases()[name]
    lowered = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
    assert "tpu_custom_call" in lowered.as_text()
