"""The paged KV pool's device layout, judged by the TPU's compiler
(ISSUE 26).

The pool is one ``[NB, BS, H*D]`` array per layer so that no paged
program converts, copies or slices a pool-sized array: with a minor
dimension of ``D = 64`` (half a 128-lane tile) XLA:TPU moved ``NB``
minor-most and every program re-ordered the WHOLE pool on its way in
and out — 78% of a decode step on the chip (PERF.md, PR 23/25).

This compiles the engine's own largest decode, prefill and extend
programs for a DESCRIBED v5e — the TPU compiler is installed in the
sandbox; no chip is attached or used, as ``benchmark/sizing.py`` does
for the cells — on a small model whose pool dwarfs everything else,
and reads the optimised HLO: bytes and ops by the compiler, never a
time. It is the "did the layout engage" guard, and catches the next
accidental relayout.
"""

import os
import re
import sys

import jax
import numpy as np
import pytest

from tensorflow_examples_tpu.models import transformer
from tensorflow_examples_tpu.serving import launch_block
from tensorflow_examples_tpu.serving.engine import InferenceEngine, ServeConfig

pytestmark = pytest.mark.serving

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# head_dim 64, H*D = 128 lanes, and a pool (2 x 2 layers x 4.2 M
# elements) far larger than the weights (0.5 M) or any gathered view
# (4 slots x 128 tokens x 128).
MODEL = dict(vocab_size=512, max_len=128, num_layers=2, num_heads=2,
             d_model=128, dropout=0.0, attention="xla")
SERVE = dict(max_slots=4, prefill_bucket_floor=16, kv_bucket_floor=32,
             kv_block_size=16, kv_blocks=2048)

# Results that move no bytes, whatever their size.
FREE_OPS = {"parameter", "get-tuple-element", "tuple", "bitcast"}
_INSTR = re.compile(
    r"^\s*(?:ROOT )?%?(?P<name>[\w.\-]+) = (?P<dtype>\w+)\[(?P<dims>[\d,]*)\]"
    r"(?:\{[^}]*\})? (?P<op>[\w\-]+)\((?P<rest>.*)$"
)
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?(?P<name>[\w.\-]+) \(.*\{\s*$")


@pytest.fixture(scope="module")
def described_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        from jax.experimental import topologies

        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu here, or it is held elsewhere
        pytest.skip(f"a v5e cannot be described here: {e}")
    # A program compiled for a described chip is written to the
    # persistent cache and cannot be read back without one.
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was)


def _engine(kv_dtype):
    mcfg = transformer.TransformerConfig(**MODEL)
    params = transformer.Transformer(mcfg).init(
        {"params": jax.random.PRNGKey(0)}, np.zeros((1, 8), np.int32)
    )["params"]
    return InferenceEngine(
        mcfg, params, cfg=ServeConfig(kv_dtype=kv_dtype, **SERVE)
    )


def _results(hlo: str):
    """``(op, dtype, dims, name)`` of every instruction of the optimised
    HLO that is neither free nor an in-place scatter (a ``scatter``, or
    a fusion whose computation holds one)."""
    bodies: dict[str, list[str]] = {}
    current = None
    for line in hlo.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            current = bodies.setdefault(head["name"], [])
        elif current is not None:
            current.append(line)
    scatters = {
        name for name, body in bodies.items()
        if any(" scatter(" in line for line in body)
    }
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if not m or m["op"] in FREE_OPS or m["op"] == "scatter":
            continue
        called = re.search(r"calls=%?([\w.\-]+)", m["rest"])
        if m["op"] == "fusion" and called and called[1] in scatters:
            continue
        dims = [int(d) for d in m["dims"].split(",") if d]
        yield m["op"], m["dtype"], dims, m["name"]


def _pool_sized_results(hlo: str, sizes: set[int]) -> list[str]:
    """Those whose result has a pool's or a layer's element count."""
    return [
        f"{op} {dtype}{dims} {name}" for op, dtype, dims, name in _results(hlo)
        if int(np.prod(dims)) in sizes
    ]


def _results_of_bytes(hlo: str, at_least: float) -> list[str]:
    """Those whose result holds ``at_least`` bytes or more."""
    width = lambda dtype: int(re.search(r"\d+", dtype)[0]) // 8 or 1  # noqa: E731
    return [
        f"{op} {dtype}{dims} {name}" for op, dtype, dims, name in _results(hlo)
        if dtype != "token" and dtype != "pred"
        and int(np.prod(dims)) * width(dtype) >= at_least
    ]


def _packed(engine, family, args, chip, rung=None):
    """``args`` of ``sizing*.engine_programs`` in the form the engine
    launches: params, the pool and the ONE operand block (of the
    family's largest rung, or of ``rung``)."""
    ladder = engine.kv_ladder if family == "decode" else engine.prefill_ladder
    spec = engine._specs[family, rung or ladder[-1]]
    return (*args[:2], jax.ShapeDtypeStruct(
        (launch_block.size(spec),), np.int32, sharding=chip
    ))


@pytest.mark.parametrize("kv_dtype", ["", "int8"], ids=["f32", "int8"])
@pytest.mark.parametrize("family", ["decode", "prefill", "extend"])
@pytest.mark.parametrize("form", ["long", "packed"])
def test_no_program_touches_the_whole_pool(described_chip, form, family,
                                           kv_dtype):
    """``long``: every operand an argument of its own, as
    ``benchmark/sizing.py`` spells the programs out. ``packed``: params,
    the pool and the ONE operand block — the form the engine launches,
    so what is served is what is guarded."""
    sys.path.insert(0, REPO)
    try:
        from benchmark import sizing
    finally:
        sys.path.remove(REPO)

    engine = _engine(kv_dtype)
    state = engine.pool.kv_state()
    layer = state[0][0]
    assert layer.shape == (SERVE["kv_blocks"], SERVE["kv_block_size"],
                           MODEL["d_model"])
    pool_bytes = sum(a.nbytes for a in jax.tree.leaves(state))
    fn, args = sizing.engine_programs(engine, described_chip)[family]
    if form == "packed":
        args = _packed(engine, family, args, described_chip)
    compiled = fn.lower(*args).compile()

    sizes = {layer.size, layer.size * MODEL["num_layers"]}
    assert _pool_sized_results(compiled.as_text(), sizes) == []
    mem = compiled.memory_analysis()
    # Donated and updated in place: the whole pool is aliased ...
    assert mem.alias_size_in_bytes >= pool_bytes
    # ... and nothing pool-sized is left among the temporaries (the
    # [L, NB, H, BS, D] pool's programs held 2.5 times the pool).
    assert mem.temp_size_in_bytes < pool_bytes / 4, (
        mem.temp_size_in_bytes, pool_bytes)


# ------------------------------------------------- every block's cache rows
#
# The same guard for the rows the other blocks declare (ISSUE 33): GPT-2's
# 768 = 6 x 128 lanes was the only width the guard above ever saw, and a
# latent row of 512 + 64 = 576 values, stored as one array, came back
# with PR 26's pathology — the TPU kept ``bf16[NB, 16, 576]`` with ``NB``
# minor-most and wrapped each layer's row scatter in two pool-sized
# copies, in every program. Stated in BYTES, so that a row kept as
# several arrays is judged by what its programs move: no result, other
# than the in-place row scatters, that holds a quarter or more of ONE
# layer's pool bytes, and temporaries under a quarter of the pool.


def _glm_engine():
    """GLM-4.7-Flash's block at the published latent widths (512 + 64),
    everything else tiny, bf16 as served: one dense and one expert
    layer, a pool (2 layers x 2,048 blocks) far larger than the weights
    or any gathered view (4 slots x 256 rows)."""
    from tensorflow_examples_tpu.workloads import glm4_moe_lite as workload

    pcfg = workload.Glm4MoeLiteServeConfig(
        hidden_size=128, num_attention_heads=2, q_lora_rank=32,
        qk_nope_head_dim=16, v_head_dim=16, intermediate_size=128,
        moe_intermediate_size=128, n_routed_experts=4, num_experts_per_tok=2,
        vocab_size=256, num_hidden_layers=2, seq_len=256,
    )
    assert (pcfg.kv_lora_rank, pcfg.qk_rope_head_dim) == (512, 64)
    return _kinds_engine(workload, pcfg)


def _cohere_engine():
    """Cohere2-MoE's block: grouped-query rows of 2 x 64 = 128 lanes,
    window and full layers (two block-id spaces), held experts."""
    from tensorflow_examples_tpu.workloads import cohere2_moe as workload

    pcfg = workload.Cohere2MoeServeConfig(
        hidden_size=128, num_attention_heads=4, num_key_value_heads=2,
        head_dim=64, intermediate_size=128, num_experts_per_tok=2,
        num_shared_experts=1, sliding_window=64,
        layer_types=("sliding_attention", "full_attention"),
        num_hidden_layers=2, held_experts=(0, 1), router_experts=4,
        vocab_size=256, seq_len=256,
    )
    return _kinds_engine(workload, pcfg)


def _kinds_engine(workload, pcfg):
    params = jax.jit(workload.make_task(pcfg).init_fn)(
        jax.random.PRNGKey(0)
    )["params"]
    return InferenceEngine(
        workload.model_config(pcfg), params,
        cfg=ServeConfig(max_slots=4, kv_block_size=16, kv_blocks=2048,
                        prefill_chunk_tokens=32, prefill_bucket_floor=32,
                        kv_bucket_floor=128),
    )


# "extend_lowest": the extend family's LOWEST context rung (ISSUE 35),
# the one the cells' chunks run most; the others are the largest rungs.
@pytest.mark.parametrize("family", ["decode", "prefill", "extend", "extend_lowest"])
@pytest.mark.parametrize(
    "make_engine", [_glm_engine, _cohere_engine], ids=["glm4_moe_lite", "cohere2_moe"]
)
def test_no_program_of_any_block_touches_the_whole_pool(described_chip,
                                                         make_engine, family):
    sys.path.insert(0, REPO)
    try:
        from benchmark import sizing_kinds
    finally:
        sys.path.remove(REPO)

    engine = make_engine()
    pool = engine.pool
    state = pool.kv_state()
    pool_bytes = sum(a.nbytes for a in jax.tree.leaves(state))
    # One layer's arrays, of the kind with the most blocks.
    layer_bytes = max(
        sum(arrs[layer].nbytes for arrs in state)
        for layer in range(pool.num_layers)
    )
    # No weight, nor all of them, could be taken for a quarter of it.
    assert layer_bytes / 4 > 2 * sum(
        a.nbytes for a in jax.tree.leaves(engine.params)
    )
    rung = None
    if family == "extend_lowest":
        family, rung = "extend", (engine.prefill_ladder[-1], engine.extend_ladder[0])
        assert rung == (32, 128) and rung in engine._extend_fns
    fn, args = sizing_kinds.engine_programs(engine, described_chip)[family]
    if rung:
        fn = engine._extend_fns[rung]
    compiled = fn.lower(*_packed(engine, family, args, described_chip, rung)).compile()

    assert _results_of_bytes(compiled.as_text(), layer_bytes / 4) == []
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes / 4, (
        mem.temp_size_in_bytes, pool_bytes)


@pytest.mark.parametrize("heads,named", [(3, True), (2, False), (1, False)])
def test_rows_with_heads_that_are_not_lane_dense_are_named(caplog, heads, named):
    """K and V rows are as wide as the model is: 3 x 64 = 192 values
    (GPT-2 XL's 25 x 64 = 1,600 likewise) are served, and said to be
    re-laid; whole tiles (128) and toy widths under one tile (64) say
    nothing."""
    mcfg = transformer.TransformerConfig(**dict(
        MODEL, num_heads=heads, d_model=64 * heads, vocab_size=64, max_len=32))
    params = transformer.Transformer(mcfg).init(
        {"params": jax.random.PRNGKey(0)}, np.zeros((1, 8), np.int32)
    )["params"]
    with caplog.at_level("WARNING", logger="tensorflow_examples_tpu.serving.engine"):
        InferenceEngine(mcfg, params, cfg=ServeConfig(
            max_slots=2, prefill_bucket_floor=16, kv_bucket_floor=32, kv_block_size=16))
    said = [r.getMessage() for r in caplog.records if "128-lane tiles" in r.getMessage()]
    assert len(said) == (2 if named else 0)  # K's array and V's
    assert all("gpt2" in m and f"{64 * heads} values" in m for m in said)


# ------------------------------------------------- tokens on the new layout
#
# The same mathematics on the re-laid pool: every paged path at a
# lane-dense geometry (head_dim 64, H*D = 128, blocks of 16) against
# the engine's plain cacheless reference.

GOLDEN_MODEL = dict(MODEL, vocab_size=211, max_len=64)
GOLDEN_SERVE = dict(max_slots=4, prefill_bucket_floor=16,
                    kv_bucket_floor=32, kv_block_size=16)


def _golden_engine(**serve_kw):
    """Unwarmed: only the rungs a test drives are compiled."""
    from tensorflow_examples_tpu.telemetry.registry import MetricsRegistry

    mcfg = transformer.TransformerConfig(**GOLDEN_MODEL)
    params = transformer.Transformer(mcfg).init(
        {"params": jax.random.PRNGKey(3)}, np.zeros((1, 8), np.int32)
    )["params"]
    return InferenceEngine(
        mcfg, params, cfg=ServeConfig(**GOLDEN_SERVE, **serve_kw),
        registry=MetricsRegistry(),
    )


def _prompt(seed, n):
    rng = np.random.default_rng(seed)
    return [int(t) for t in rng.integers(0, GOLDEN_MODEL["vocab_size"], n)]


def _decode_stream(eng, slot, first, n, seed):
    out = [int(first)]
    for _ in range(n - 1):
        out.append(eng.decode([(slot, out[-1], seed, 0.0, 0)])[slot])
    return out


def _assert_tracks(eng, got, ref):
    """fp pools: token-identical. Quantized pools: the bounded
    divergence their goldens pin (first token exact — prefill attends
    fresh unquantized K/V — and >= 75% of the stream agreeing)."""
    if not eng.pool.quantized:
        assert got == ref
        return
    assert got[0] == ref[0], "first token must be exact"
    agree = sum(a == b for a, b in zip(got, ref))
    assert agree >= 0.75 * len(ref), (got, ref)


@pytest.mark.timeout(300)
@pytest.mark.parametrize("kv_dtype,attention", [
    ("", "xla"), ("", "paged_flash"), ("int8", "xla"),
    ("int8", "paged_flash"), ("fp8", "xla"),
])
def test_decode_and_extend_track_the_reference(kv_dtype, attention):
    """Prefill -> decode across a block boundary, then a second prompt
    that HITS the first one's published blocks (the extend rung over
    gathered context), both slots decoding in one batch."""
    from tensorflow_examples_tpu.core import precision

    if kv_dtype == "fp8" and not precision.fp8_supported():
        pytest.skip("no float8_e4m3fn on this build")
    eng = _golden_engine(kv_dtype=kv_dtype, attention=attention)
    head = _prompt(1, 32)                      # two full blocks
    a, b = head + _prompt(2, 5), head + _prompt(3, 9)
    n = 12
    slot_a = eng.pool.alloc()
    tok_a, _ = eng.prefill(slot_a, a, seed=4)
    hits = eng.pool.prefix_hits
    slot_b = eng.pool.alloc()
    tok_b, _ = eng.prefill(slot_b, b, seed=5)
    assert eng.pool.prefix_hits == hits + 1    # b ran the extend rung
    got_a, got_b = [int(tok_a)], [int(tok_b)]
    for _ in range(n - 1):
        out = eng.decode([(slot_a, got_a[-1], 4, 0.0, 0),
                          (slot_b, got_b[-1], 5, 0.0, 0)])
        got_a.append(out[slot_a])
        got_b.append(out[slot_b])
    eng.pool.free(slot_a)
    eng.pool.free(slot_b)
    _assert_tracks(eng, got_a, eng.reference_generate(a, max_new=n, seed=4))
    _assert_tracks(eng, got_b, eng.reference_generate(b, max_new=n, seed=5))


@pytest.mark.timeout(300)
def test_verify_commits_the_reference_tokens():
    """The speculative verify rung: right drafts are all committed,
    wrong ones rejected at the first disagreement, and the committed
    stream is the reference's either way (the window crosses a block
    boundary at row 16)."""
    eng = _golden_engine(spec_decode_k=3)
    prompt = _prompt(7, 13)
    ref = eng.reference_generate(prompt, max_new=16, seed=6, temperature=0.9)
    assert len(set(ref)) > 4                   # a stream worth drafting
    slot = eng.pool.alloc()
    tok, _ = eng.prefill(slot, prompt, seed=6, temperature=0.9)
    got = [int(tok)]
    accepted = []
    for spoil in (False, True, False, True):
        drafts = list(ref[len(got):len(got) + 3])
        if spoil:
            drafts[1] = (drafts[1] + 1) % GOLDEN_MODEL["vocab_size"]
        new = eng.verify([(slot, got[-1], drafts, 6, 0.9, 0)])[slot]
        accepted.append(len(new) - 1)
        got += new
    eng.pool.free(slot)
    assert accepted == [3, 1, 3, 1]
    assert got == ref[:len(got)]


@pytest.mark.timeout(300)
@pytest.mark.parametrize("kv_dtype", ["", "int8"], ids=["f32", "int8"])
def test_exported_pages_continue_identically(kv_dtype):
    """export -> wire -> import into ANOTHER engine: the wire keeps its
    [L, pages, H, BS, D] order, so the importer's continuation is the
    donor's own (and, unquantized, the reference's)."""
    import json

    donor, importer = (_golden_engine(kv_dtype=kv_dtype) for _ in range(2))
    prompt = _prompt(11, 37)
    n = 8
    d_slot = donor.pool.alloc()
    first, _ = donor.prefill(d_slot, prompt, seed=9)
    pages = json.loads(json.dumps(donor.export_kv_pages(d_slot, prompt)))
    mcfg = donor.model_cfg
    from tensorflow_examples_tpu.serving import scheduler

    _, arrays = scheduler.decode_pages(pages)
    assert arrays["k"].shape == (
        mcfg.num_layers, 3, mcfg.num_heads, 16, mcfg.head_dim)
    if kv_dtype:
        assert arrays["k_scale"].shape == arrays["k"].shape[:-1]
    i_slot = importer.pool.alloc()
    importer.import_kv_pages(i_slot, pages, prompt)
    donor_stream = _decode_stream(donor, d_slot, first, n, 9)
    importer_stream = _decode_stream(importer, i_slot, first, n, 9)
    donor.pool.free(d_slot)
    importer.pool.free(i_slot)
    assert importer_stream == donor_stream
    _assert_tracks(
        donor, donor_stream, donor.reference_generate(prompt, max_new=n, seed=9)
    )
