"""Context/tensor parallelism on the 8-fake-CPU-device mesh (SURVEY.md §4).

Ring and Ulysses attention under shard_map must match the full-sequence
XLA reference — forward and gradients — and the mesh_attention dispatcher
must route each mesh shape to a working implementation.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from tensorflow_examples_tpu.core.mesh import MeshConfig, create_mesh
from tensorflow_examples_tpu.ops.attention import attention_reference
from tensorflow_examples_tpu.parallel.attention import attention_spec, mesh_attention
from tensorflow_examples_tpu.parallel.ring import ring_attention, ulysses_attention


def qkv(b=2, h=4, s=32, d=8, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (b, h, s, d)
    return tuple(jax.random.normal(k, shape, jnp.float32) for k in ks)


@pytest.fixture(scope="module")
def ctx_mesh():
    return create_mesh(MeshConfig(data=2, context=4))


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("fn", [ring_attention, ulysses_attention])
def test_context_parallel_matches_reference(ctx_mesh, causal, fn):
    q, k, v = qkv()
    ref = attention_reference(q, k, v, causal=causal)
    local = functools.partial(fn, axis_name="context", causal=causal)
    spec = P("data", None, "context", None)
    out = jax.jit(
        jax.shard_map(
            local, mesh=ctx_mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5)


@pytest.mark.parametrize("fn", [ring_attention, ulysses_attention])
def test_context_parallel_grads(ctx_mesh, fn):
    q, k, v = qkv(s=16)
    spec = P("data", None, "context", None)
    local = functools.partial(fn, axis_name="context", causal=True)
    sharded = jax.shard_map(
        local, mesh=ctx_mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )

    def loss(f, q, k, v):
        return jnp.sum(f(q, k, v) ** 2)

    g_ref = jax.grad(functools.partial(loss, attention_reference), argnums=(0, 1, 2))(
        q, k, v
    )
    g_out = jax.jit(
        jax.grad(functools.partial(loss, sharded), argnums=(0, 1, 2))
    )(q, k, v)
    for r, o in zip(g_ref, g_out):
        np.testing.assert_allclose(np.asarray(r), np.asarray(o), atol=5e-4)


@pytest.mark.parametrize(
    "mesh_cfg,impl",
    [
        (MeshConfig(data=8), "flash"),
        (MeshConfig(data=2, model=4), "flash"),
        (MeshConfig(data=2, context=4), "ring"),
        (MeshConfig(data=2, context=4), "ulysses"),
        (MeshConfig(data=2, fsdp=2, context=2), "ring"),
    ],
)
def test_mesh_attention_dispatch(mesh_cfg, impl):
    mesh = create_mesh(mesh_cfg)
    q, k, v = qkv(b=8)
    ref = attention_reference(q, k, v, causal=True)
    sharding = NamedSharding(mesh, attention_spec(mesh))
    args = jax.device_put((q, k, v), sharding)
    out = jax.jit(
        functools.partial(mesh_attention, mesh=mesh, causal=True, impl=impl)
    )(*args)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5)


@pytest.mark.parametrize(
    "mesh_cfg",
    [MeshConfig(data=8), MeshConfig(data=2, model=4), MeshConfig(model=8)],
)
def test_mesh_decode_attention_matches_reference(mesh_cfg):
    """Flash-decode under shard_map (batch over data, heads over model)
    must match the masked-cache XLA reference — the TP decode path."""
    from tensorflow_examples_tpu.ops.decode import decode_attention_reference
    from tensorflow_examples_tpu.parallel.attention import (
        decode_spec,
        mesh_decode_attention,
    )

    mesh = create_mesh(mesh_cfg)
    b, h, max_len, d = 8, 8, 64, 8
    q = jax.random.normal(jax.random.PRNGKey(0), (b, h, 1, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, h, max_len, d))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, h, max_len, d))
    length = jnp.asarray(37)
    ref = decode_attention_reference(q, k, v, length)
    sharding = NamedSharding(mesh, decode_spec(mesh, b, h))
    qs, ks, vs = jax.device_put((q, k, v), sharding)
    out = jax.jit(functools.partial(mesh_decode_attention, mesh=mesh))(
        qs, ks, vs, length
    )
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5)


def test_generate_under_tp_mesh_matches_single_device():
    """End-to-end sampling with a dp×tp mesh: greedy generate through the
    sharded flash-decode path must reproduce the meshless output."""
    from tensorflow_examples_tpu.models import transformer

    cfg = transformer.TransformerConfig(
        vocab_size=97, max_len=32, num_layers=2, num_heads=4,
        d_model=16, dropout=0.0, attention="flash",
    )
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 97, (2, 4)), jnp.int32
    )
    plain = transformer.Transformer(cfg)
    params = plain.init({"params": jax.random.PRNGKey(0)}, tokens)["params"]
    want = transformer.generate(
        plain, params, tokens, num_tokens=6,
        rng=jax.random.PRNGKey(1), temperature=0.0,
    )
    mesh = create_mesh(MeshConfig(data=2, model=4))
    meshed = transformer.Transformer(cfg, mesh=mesh)
    got = transformer.generate(
        meshed, params, tokens, num_tokens=6,
        rng=jax.random.PRNGKey(1), temperature=0.0,
    )
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


class TestExplicitEP:
    """moe_ffn_ep: all-to-all expert dispatch vs the single-program path."""

    def _args(self, e=8, d=16, ff=32, b=8, s=16, seed=0):
        ks = jax.random.split(jax.random.PRNGKey(seed), 6)
        return (
            jax.random.normal(ks[0], (d, e)) * 0.5,
            jax.random.normal(ks[1], (e, d, ff)) * 0.1,
            jax.random.normal(ks[2], (e, ff)) * 0.01,
            jax.random.normal(ks[3], (e, ff, d)) * 0.1,
            jax.random.normal(ks[4], (e, d)) * 0.01,
            jax.random.normal(ks[5], (b, s, d)),
        )

    @pytest.mark.parametrize("top_k", [1, 2])
    def test_matches_single_program(self, top_k):
        """With capacity ample enough that nothing drops, the explicit
        all-to-all dispatch must reproduce moe_ffn exactly (same math,
        different transport)."""
        from tensorflow_examples_tpu.parallel.moe import moe_ffn, moe_ffn_ep

        mesh = create_mesh(MeshConfig(data=2, model=4))
        args = self._args()
        kw = dict(capacity_factor=8.0, top_k=top_k, rng=None)
        want, aux_w, drop_w = moe_ffn(*args, **kw)
        got, aux_g, drop_g = jax.jit(
            functools.partial(moe_ffn_ep, mesh=mesh, **kw)
        )(*args)
        np.testing.assert_allclose(
            np.asarray(want), np.asarray(got), atol=2e-5, rtol=2e-5
        )
        np.testing.assert_allclose(float(aux_w), float(aux_g), rtol=1e-5)
        assert float(drop_w) == 0.0 and float(drop_g) == 0.0

    def test_grads_match_single_program(self):
        from tensorflow_examples_tpu.parallel.moe import moe_ffn, moe_ffn_ep

        mesh = create_mesh(MeshConfig(data=2, model=4))
        args = self._args(b=4, s=8)
        kw = dict(capacity_factor=8.0, top_k=2, rng=None)

        def loss(fn, *a):
            out, aux, _ = fn(*a, **kw)
            return jnp.sum(out**2) + 0.01 * aux

        g_ref = jax.grad(functools.partial(loss, moe_ffn), argnums=(0, 1, 3, 5))(
            *args
        )
        g_ep = jax.jit(
            jax.grad(
                functools.partial(
                    loss, functools.partial(moe_ffn_ep, mesh=mesh)
                ),
                argnums=(0, 1, 3, 5),
            )
        )(*args)
        for r, o, name in zip(g_ref, g_ep, ("gate", "w_in", "w_out", "x")):
            np.testing.assert_allclose(
                np.asarray(r), np.asarray(o), atol=5e-4, rtol=5e-4,
                err_msg=f"d{name}",
            )

    def test_dispatch_is_all_to_all(self):
        """The point of the explicit path: the compiled HLO must exchange
        tokens with all-to-all, not all-gather the dispatch buffers."""
        from tensorflow_examples_tpu.parallel.moe import moe_ffn_ep

        mesh = create_mesh(MeshConfig(data=2, model=4))
        args = self._args()
        hlo = (
            jax.jit(
                functools.partial(
                    moe_ffn_ep, mesh=mesh, capacity_factor=2.0, top_k=2
                )
            )
            .lower(*args)
            .compile()
            .as_text()
        )
        assert "all-to-all" in hlo

    def test_ep_indivisible_token_dims_replicate(self):
        """Decode-time shapes — batch 1, single-token step — must not
        trace-fail on a mesh with batch/context axes: the token spec
        drops non-dividing axes and replicates (only the `model`
        all-to-all is essential)."""
        from tensorflow_examples_tpu.parallel.moe import moe_ffn, moe_ffn_ep

        mesh = create_mesh(MeshConfig(data=2, model=4))
        args = self._args(b=1, s=1)
        kw = dict(capacity_factor=8.0, top_k=2, rng=None)
        want, _, _ = moe_ffn(*args, **kw)
        got, _, _ = jax.jit(functools.partial(moe_ffn_ep, mesh=mesh, **kw))(
            *args
        )
        np.testing.assert_allclose(
            np.asarray(want), np.asarray(got), atol=2e-5, rtol=2e-5
        )

    def test_moe_generate_under_mesh(self):
        """End-to-end: greedy sampling from an MoE model on a dp×tp mesh
        (the MoeMlp auto-EP path at decode shapes) matches meshless."""
        from tensorflow_examples_tpu.models import transformer

        cfg = transformer.TransformerConfig(
            vocab_size=97, max_len=16, num_layers=2, num_heads=4,
            d_model=16, dropout=0.0, attention="flash",
            moe_experts=8, moe_every=2, moe_top_k=2,
            moe_capacity_factor=4.0,
        )
        prompt = jnp.asarray([[5, 17, 3]], jnp.int32)  # batch 1
        plain = transformer.Transformer(cfg)
        params = plain.init({"params": jax.random.PRNGKey(0)}, prompt)["params"]
        want = transformer.generate(
            plain, params, prompt, num_tokens=4,
            rng=jax.random.PRNGKey(1), temperature=0.0,
        )
        mesh = create_mesh(MeshConfig(data=2, model=4))
        got = transformer.generate(
            transformer.Transformer(cfg, mesh=mesh), params, prompt,
            num_tokens=4, rng=jax.random.PRNGKey(1), temperature=0.0,
        )
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))

    def test_gmm_tiling_respects_row_divisibility(self):
        """gmm's make_group_metadata requires tm | m; the adaptive
        tiling must halve tm until it divides, prefer a tk that DIVIDES
        k (768 takes 384-wide tiles exactly; a capped 512 leaves a
        masked 256 remainder tile every pass), and pick the large tiles
        at the bench shape (the whole point — 128^3 at
        [16384, 768, 3072] is ~19k grid steps of overhead)."""
        from tensorflow_examples_tpu.parallel.moe import (
            GMM_TILE_CAP, _gmm_tiling,
        )

        cap = GMM_TILE_CAP
        assert _gmm_tiling(16384, 768, 3072) == (cap, 384, cap)
        assert _gmm_tiling(256, 128, 128) == (256, 128, 128)
        assert _gmm_tiling(256, 3072, 3072) == (256, cap, cap)  # cap | k
        # No lane-aligned divisor <= cap: fall back to min(cap, k).
        assert _gmm_tiling(256, 64, 64) == (256, 64, 64)
        # No divisor in [cap/2, cap] either (640's largest is 128):
        # one near-cap masked pass beats five tiny exact ones.
        assert _gmm_tiling(256, 640, 640) == (256, cap, cap)
        m, k, n = 384, 768, 3072  # m = 3·128: cap halves to 128
        tm, tk, tn = _gmm_tiling(m, k, n)
        assert m % tm == 0 and tm == 128
        assert k % tk == 0 and tk <= k and tn <= n

    def test_gmm_tiling_widens_for_wide_weights(self):
        """Weights that a 2048-wide n tile divides take it, with the
        widest k tile that keeps one weight tile within the byte
        budget (the chip sweep of PR 28: fewer passes over the rows
        won every time, tn = tk = 2048 did not fit VMEM), and row tiles
        of 256, 128 for few rows. It is a rule on (m, k, n, itemsize),
        not a table of shapes; narrower weights keep the cap."""
        from tensorflow_examples_tpu.parallel.moe import (
            GMM_TILE_CAP, GMM_WEIGHT_TILE_BYTES, _gmm_tiling,
        )

        assert _gmm_tiling(4096, 4096, 4096) == (256, 1024, 2048)
        assert _gmm_tiling(256, 4096, 4096) == (128, 1024, 2048)
        assert _gmm_tiling(512, 8192, 2048) == (256, 1024, 2048)
        assert _gmm_tiling(384, 2048, 6144) == (128, 1024, 2048)
        for m, k, n, item in [(4096, 4096, 4096, 2), (512, 8192, 2048, 2)]:
            tm, tk, tn = _gmm_tiling(m, k, n, item)
            assert tk * tn * item <= GMM_WEIGHT_TILE_BYTES and m % tm == 0
        # float32 weights: the budget leaves no k tile wider than the cap
        cap = GMM_TILE_CAP
        assert _gmm_tiling(4096, 4096, 4096, 4) == (cap, cap, cap)
        assert _gmm_tiling(4096, 4096, 3072) == (cap, cap, cap)  # 2048 does not divide n

    @pytest.mark.parametrize("top_k", [1, 2])
    def test_grouped_matches_scatter_impl(self, top_k):
        """The sort-based dropless ragged_dot path (the TPU hot path)
        must compute the same function as the static-capacity
        scatter/gather reference when nothing drops — outputs, aux
        loss, and grads."""
        from tensorflow_examples_tpu.parallel.moe import moe_ffn

        args = self._args()
        kw = dict(capacity_factor=8.0, top_k=top_k, rng=None)
        want, aux_w, _ = moe_ffn(*args, impl="scatter", **kw)
        got, aux_g, drop_g = jax.jit(
            functools.partial(moe_ffn, impl="grouped", **kw)
        )(*args)
        np.testing.assert_allclose(
            np.asarray(want), np.asarray(got), atol=2e-5, rtol=2e-5
        )
        np.testing.assert_allclose(float(aux_w), float(aux_g), rtol=1e-5)
        assert float(drop_g) == 0.0  # dropless by construction

        def loss(impl, *a):
            out, aux, _ = moe_ffn(*a, impl=impl, **kw)
            return jnp.sum(out**2) + 0.01 * aux

        g_ref = jax.grad(
            functools.partial(loss, "scatter"), argnums=(0, 1, 3, 5)
        )(*args)
        g_new = jax.jit(
            jax.grad(
                functools.partial(loss, "grouped"), argnums=(0, 1, 3, 5)
            )
        )(*args)
        for r, o, name in zip(g_ref, g_new, ("gate", "w_in", "w_out", "x")):
            np.testing.assert_allclose(
                np.asarray(r), np.asarray(o), atol=5e-4, rtol=5e-4,
                err_msg=f"d{name}",
            )

    def test_sorted_capacity_slotting_invariants(self):
        """_capacity_slots_sorted under OVERFLOW: the pair<->slot maps
        stay mutually inverse bijections on the kept set, the buffer
        holds exactly the kept tokens, and the kept count is
        sum_e min(count_e, capacity)."""
        import numpy as np

        from tensorflow_examples_tpu.parallel.moe import (
            _capacity_slots_sorted,
        )

        rng = np.random.default_rng(0)
        # cap 14 vs per-expert pair counts [12, 9, 27, 18] (this seed):
        # two experts UNDERFILL (invalid-slot branch) and two OVERFLOW
        # (dropped-pair branch) — both sides of the quota exercised.
        n, d, e, top_k, cap = 33, 5, 4, 2, 14
        tokens = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
        experts = [
            jnp.asarray(rng.integers(0, e, n), jnp.int32)
            for _ in range(top_k)
        ]
        xin, pair_slot, pair_keep, slot_pair, slot_valid, kept = (
            _capacity_slots_sorted(tokens, experts, top_k, e, cap)
        )
        eid = np.stack([np.asarray(x) for x in experts], 1).reshape(-1)
        counts = np.bincount(eid, minlength=e)
        assert int(kept) == int(np.minimum(counts, cap).sum())
        ps, pk = np.asarray(pair_slot), np.asarray(pair_keep)
        sp, sv = np.asarray(slot_pair), np.asarray(slot_valid)
        x = np.asarray(xin)
        filled = 0
        for slot in range(e * cap):
            if not sv[slot]:
                # invalid slots are zero and (if in range) not claimed
                assert np.all(x[slot] == 0)
                continue
            p = sp[slot]
            assert pk[p] and ps[p] == slot  # inverse bijection
            assert eid[p] == slot // cap  # right expert's queue
            np.testing.assert_array_equal(
                x[slot], np.asarray(tokens)[p // top_k]
            )
            filled += 1
        assert filled == int(kept)
        # every kept pair's slot points back at it
        for p in np.nonzero(pk)[0]:
            assert sv[ps[p]] and sp[ps[p]] == p

    def test_ep_fallback_without_model_axis(self):
        """E % model != 0 (or model == 1) must fall through to the
        single-program path and still be correct."""
        from tensorflow_examples_tpu.parallel.moe import moe_ffn, moe_ffn_ep

        mesh = create_mesh(MeshConfig(data=8))
        args = self._args(e=6)
        kw = dict(capacity_factor=8.0, top_k=1, rng=None)
        want, _, _ = moe_ffn(*args, **kw)
        got, _, _ = moe_ffn_ep(*args, mesh=mesh, **kw)
        np.testing.assert_allclose(
            np.asarray(want), np.asarray(got), atol=2e-5, rtol=2e-5
        )


@pytest.mark.parametrize("zigzag", [True, False])
def test_ring_zigzag_and_contiguous_match_reference(ctx_mesh, zigzag):
    """Both causal ring schedules — zigzag (default) and contiguous with
    lax.cond hop skipping — against the full-sequence reference."""
    q, k, v = qkv(s=64, seed=3)
    ref = attention_reference(q, k, v, causal=True)
    local = functools.partial(
        ring_attention, axis_name="context", causal=True, zigzag=zigzag
    )
    spec = P("data", None, "context", None)
    sharded = jax.shard_map(
        local, mesh=ctx_mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    out = jax.jit(sharded)(q, k, v)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5)

    def loss(f, q, k, v):
        return jnp.sum(f(q, k, v) ** 2)

    g_ref = jax.grad(functools.partial(loss, attention_reference), argnums=(0, 1, 2))(
        q, k, v
    )
    g_out = jax.jit(
        jax.grad(functools.partial(loss, sharded), argnums=(0, 1, 2))
    )(q, k, v)
    for r, o in zip(g_ref, g_out):
        np.testing.assert_allclose(np.asarray(r), np.asarray(o), atol=5e-4)


def test_ring_odd_shard_falls_back_to_contiguous(ctx_mesh):
    """Auto zigzag must not fire on odd shard lengths (s=20 over c=4 →
    shard 5); the contiguous path covers it."""
    q, k, v = qkv(s=20, seed=5)
    ref = attention_reference(q, k, v, causal=True)
    local = functools.partial(ring_attention, axis_name="context", causal=True)
    spec = P("data", None, "context", None)
    out = jax.jit(
        jax.shard_map(
            local, mesh=ctx_mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5)


def _attend_pairs(jaxpr) -> int:
    """Query rows x key rows the attention kernels of ``jaxpr`` visit on
    one device: a scan's body times its length, the costlier branch of
    a cond. A kernel call counts its whole q x k rectangle, a causal one
    too (which visits about half of it)."""
    total = 0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "pallas_call":
            q, k = (v.aval.shape for v in eqn.invars[:2])  # [B*H, S, D]
            total += q[1] * k[1]
            continue
        inner = [
            _attend_pairs(getattr(x, "jaxpr", x))
            for v in eqn.params.values()
            for x in (v if isinstance(v, (tuple, list)) else [v])
            if hasattr(getattr(x, "jaxpr", x), "eqns")
        ]
        if name == "cond":
            total += max(inner)
        else:
            total += eqn.params.get("length", 1) * sum(inner)
    return total


def test_ring_causal_zigzag_costs_about_half_of_noncausal(ctx_mesh):
    """The load-balance claim, counted: a causal zigzag ring visits
    about half the query-key pairs of the non-causal ring at the same
    shape on EVERY device (causal attends half the pairs; the naive
    contiguous ring burned the full non-causal cost on causal inputs,
    ratio 1.0). Counted from the traced program — kernel calls by their
    operand shapes, the scan by its length — so the answer does not
    depend on how busy the machine is."""
    q, k, v = qkv(b=1, h=2, s=2048, d=32, seed=7)
    spec = P(None, None, "context", None)

    def trace(**kw):
        local = functools.partial(ring_attention, axis_name="context", **kw)
        return jax.make_jaxpr(
            jax.shard_map(
                local, mesh=ctx_mesh, in_specs=(spec, spec, spec),
                out_specs=spec, check_vma=False,
            )
        )(q, k, v).jaxpr

    c = ctx_mesh.shape["context"]
    shard = 2048 // c
    full = _attend_pairs(trace(causal=False))
    assert full == c * shard * shard  # every hop, the whole shard pair
    zigzag = _attend_pairs(trace(causal=True))
    # Hop 0 is three half-chunk calls (two of them causal, counted
    # whole), every later hop two half-chunk attends whichever branch a
    # device takes: (3 + 2 (c - 1)) / 4c of the non-causal ring's.
    half = shard // 2
    assert zigzag == (3 + 2 * (c - 1)) * half * half
    assert zigzag / full <= 0.6, (zigzag, full)
    # The contiguous causal ring only SKIPS masked hops (lockstep: no
    # wall-time win): traced, it is the non-causal ring's cost.
    assert _attend_pairs(trace(causal=True, zigzag=False)) == full


def test_mesh_attention_no_mesh():
    q, k, v = qkv()
    ref = attention_reference(q, k, v, causal=True)
    out = mesh_attention(q, k, v, mesh=None, causal=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5)


@pytest.mark.parametrize("s", [20, 18])
def test_mesh_attention_pads_causal_to_zigzag(ctx_mesh, s):
    """Odd-shard corner closed at the wrapper:
    causal context-parallel shapes that previously took the unbalanced
    contiguous ring (s=20 over c=4 → odd shard 5) or could not shard at
    all (s=18, 18 % 4 != 0) are padded globally to the next multiple of
    2c. Tail pads are causally invisible to every real query, so
    outputs AND gradients must match the unpadded reference exactly."""
    q, k, v = qkv(s=s, seed=11)
    ref = attention_reference(q, k, v, causal=True)
    f = jax.jit(
        functools.partial(mesh_attention, mesh=ctx_mesh, causal=True)
    )
    out = f(q, k, v)
    assert out.shape == q.shape
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5)

    def loss(fn, q, k, v):
        return jnp.sum(fn(q, k, v) ** 2)

    g_ref = jax.grad(
        functools.partial(loss, attention_reference), argnums=(0, 1, 2)
    )(q, k, v)
    g_out = jax.jit(
        jax.grad(functools.partial(loss, f), argnums=(0, 1, 2))
    )(q, k, v)
    for r, o in zip(g_ref, g_out):
        np.testing.assert_allclose(np.asarray(r), np.asarray(o), atol=5e-4)
