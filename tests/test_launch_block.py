"""The engine's launch protocol (ISSUE 29): one packed operand block
in, keys made in the program.

A launch moves its small operands to the device in ONE explicit
``jax.device_put`` of one ``int32`` vector (``serving/launch_block``),
and nothing else on the serve thread touches the device between two
programs: no per-operand upload, no eager ``PRNGKey`` / ``fold_in``.
All on the CPU: what is counted here is transfers and programs, never
a time.
"""

import jax
import numpy as np
import pytest

from tensorflow_examples_tpu.models import transformer
from tensorflow_examples_tpu.serving import engine as engine_mod
from tensorflow_examples_tpu.serving import launch_block
from tensorflow_examples_tpu.serving.batcher import ContinuousBatcher, Request
from tensorflow_examples_tpu.serving.engine import InferenceEngine, ServeConfig
from tensorflow_examples_tpu.telemetry.registry import MetricsRegistry
from tensorflow_examples_tpu.workloads import cohere2_moe

pytestmark = pytest.mark.serving

MODEL = dict(vocab_size=211, max_len=64, num_layers=2, num_heads=2,
             d_model=32, dropout=0.0, attention="xla")
SERVE = dict(max_slots=3, prefill_bucket_floor=16, kv_bucket_floor=32,
             spec_decode_k=2)


def _gpt2(**serve_kw):
    cfg = transformer.TransformerConfig(**MODEL)
    params = transformer.Transformer(cfg).init(
        {"params": jax.random.PRNGKey(0)}, np.zeros((1, 8), np.int32)
    )["params"]
    return InferenceEngine(
        cfg, params, registry=MetricsRegistry(),
        cfg=ServeConfig(**SERVE, **serve_kw),
    )


def _two_kinds():
    """Cohere2-MoE at a toy width: three window-8 layers to one full
    layer, so every table is one array per kind."""
    pcfg = cohere2_moe.Cohere2MoeServeConfig(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, intermediate_size=32, num_experts_per_tok=2,
        num_shared_experts=2, sliding_window=8, rope_theta=50000.0,
        layer_norm_eps=1e-5, logit_scale=1.0,
        layer_types=("sliding_attention",) * 3 + ("full_attention",),
        num_hidden_layers=4, held_experts=(0, 1, 2, 3), vocab_size=128,
        router_experts=8, seq_len=64, param_dtype="float32",
    )
    params = jax.jit(cohere2_moe.make_task(pcfg).init_fn)(
        jax.random.PRNGKey(0))["params"]
    return InferenceEngine(
        cohere2_moe.model_config(pcfg), params, registry=MetricsRegistry(),
        cfg=ServeConfig(max_slots=2, kv_block_size=4, kv_blocks=33,
                        prefill_bucket_floor=8, kv_bucket_floor=16,
                        prefill_chunk_tokens=8),
    )


# "no_prefix": the prefix cache off, so no extend family (and no chunked
# prefill): a second configuration of the one pool.
ENGINES = {
    "paged": lambda: _gpt2(kv_block_size=16, prefill_chunk_tokens=16),
    "no_prefix": lambda: _gpt2(prefix_cache=False),
    "two_kinds": _two_kinds,
}


@pytest.fixture(scope="module")
def engines():
    made = {}

    def get(name):
        if name not in made:
            made[name] = ENGINES[name]()
        return made[name]
    return get


@pytest.fixture(scope="module")
def warm(engines):
    def get(name):
        engine = engines(name)
        if not engine.warmed:
            engine.warmup()
        return engine
    return get


# ------------------------------------------------------ pack and unpack


def _random_values(spec, rng):
    """Values of every field's shape whose bits are arbitrary (a
    float32 field holds the temperature 0.7 first, then anything a
    float32 can hold, NaN payloads included)."""
    def draw(shape, dtype):
        bits = rng.integers(-2**31, 2**31, shape, dtype=np.int64)
        arr = bits.astype(np.int32)
        if dtype == "float32":
            arr = arr.view(np.float32)
            arr.reshape(-1)[:1] = 0.7
        return arr

    return [
        [draw(s, f.dtype) for s in f.shape] if isinstance(f.shape, list)
        else draw(f.shape, f.dtype)
        for f in spec
    ]


def _bits(x):
    return np.asarray(x).reshape(-1).view(np.int32)


@pytest.mark.parametrize("name,kind", [
    ("paged", "prefill"), ("paged", "decode"), ("paged", "extend"),
    ("paged", "verify"), ("no_prefix", "prefill"), ("no_prefix", "decode"),
    ("no_prefix", "verify"), ("two_kinds", "prefill"), ("two_kinds", "decode"),
    ("two_kinds", "extend"),
])
def test_every_spec_round_trips_bit_exactly(engines, name, kind):
    engine = engines(name)
    rng = np.random.default_rng(29)
    specs = {r: s for (k, r), s in engine._specs.items() if k == kind}
    assert specs, (name, kind)
    for rung, spec in specs.items():
        values = _random_values(spec, rng)
        block = launch_block.pack(spec, values)
        assert block.dtype == np.int32
        assert block.shape == (launch_block.size(spec),)
        got = jax.jit(lambda b, spec=spec: launch_block.unpack(spec, b))(block)
        assert len(got) == len(spec)
        for field, want, have in zip(spec, values, got):
            if isinstance(field.shape, list):
                # one array per kind of the pool, in the pool's order
                assert len(have) == len(want) == engine._kinds
            else:
                want, have = [want], [have]
            for w, h in zip(want, have):
                assert h.shape == w.shape and h.dtype == w.dtype, field
                np.testing.assert_array_equal(_bits(h), _bits(w), str(field))
    if name == "two_kinds" and kind != "prefill":
        (tables,) = [f for f in spec if f.name in ("tables", "ctx_table")]
        assert len({s[-1] for s in tables.shape}) == 2   # two widths


def test_the_spec_is_what_the_step_function_reads(engines):
    """The packed form's operands are the long form's, in its order —
    a per-kind table where the long form takes the ``_pack`` of one
    array per kind, (seed, position) where it takes the key."""
    engine = engines("two_kinds")
    names = lambda kind: [
        f.name for f in engine._specs[kind, engine.prefill_ladder[-1]]]
    assert names("prefill") == ["block_ids", "tokens", "length", "seed",
                                "position", "temperature", "top_k"]
    assert names("extend") == ["ctx_table", "tail_ids", "tokens", "ctx_len",
                               "tail_len", "seed", "position", "temperature",
                               "top_k"]
    decode = engine._specs["decode", engine.kv_ladder[0]]
    assert [f.name for f in decode] == [
        "tokens", "positions", "tables", "seeds", "temps", "top_ks"]
    assert [f.dtype for f in decode].count("float32") == 1
    # Without the prefix cache there is no extend family to lay out; a
    # prefill still names its blocks.
    no_prefix = engines("no_prefix")._specs
    assert "extend" not in {kind for kind, _ in no_prefix}
    assert no_prefix["prefill", 16][0] == launch_block.Field(
        "block_ids", [(1,)])


@pytest.mark.parametrize("name", sorted(ENGINES))
def test_the_engine_has_four_program_families(engines, name):
    """Prefill, decode, extend and verify, over one pool: every program
    of every engine here lowers as ``(params, kv, block)`` with the
    pool's whole state, and nothing else, donated."""
    engine = engines(name)
    families = {"prefill": engine._prefill_fns, "decode": engine._decode_fns,
                "extend": engine._extend_fns, "verify": engine._verify_fns}
    assert {kind for kind, _ in engine._specs} == {
        kind for kind, fns in families.items() if fns}
    assert {(kind, rung) for kind, fns in families.items() for rung in fns} \
        == set(engine._specs)
    for (kind, rung), spec in engine._specs.items():
        block = launch_block.pack(spec, launch_block.zeros(spec))
        lowered = families[kind][rung].lower(
            engine.params, engine.pool.kv_state(), block)
        (params, kv, operands), _ = lowered.args_info
        assert all(a.donated for a in jax.tree.leaves(kv)), (kind, rung)
        assert not any(
            a.donated for a in jax.tree.leaves((params, operands))
        ), (kind, rung)


def test_pack_hands_back_a_buffer_of_its_own():
    spec = (launch_block.Field("tokens", (4,)),)
    tokens = np.arange(4, dtype=np.int32)
    block = launch_block.pack(spec, [tokens])
    assert not np.shares_memory(block, tokens)
    tokens[:] = -1               # the pool's own tables are written again
    np.testing.assert_array_equal(block, np.arange(4))


def test_a_value_of_another_shape_is_refused_by_name():
    spec = (launch_block.Field("tokens", (4,)),
            launch_block.Field("temperature", (), "float32"))
    with pytest.raises(ValueError, match="'tokens' has shape"):
        launch_block.pack(spec, [np.zeros((3,), np.int32), 0.5])
    with pytest.raises(ValueError, match=r"packs int32\[5\]"):
        launch_block.unpack(spec, np.zeros((6,), np.int32))
    with pytest.raises(ValueError):                 # a value short
        launch_block.pack(spec, [np.zeros((4,), np.int32)])


# ---------------------------------- one transfer and one launch, each call


def _prompt(n, seed=0):
    return [int(t) for t in np.random.default_rng(seed).integers(1, 200, (n,))]


SAMPLED = dict(seed=11, temperature=0.9, top_k=7)


def _guarded(engine, call):
    """Run ``call`` with implicit host->device transfers disallowed;
    (launches, transfers) it made."""
    reg = engine.registry
    names = ("serving/launches_total", "serving/launch_transfers_total")
    before = [reg.counter(n).value for n in names]
    with jax.transfer_guard_host_to_device("disallow"):
        out = call()
    return out, tuple(reg.counter(n).value - b for n, b in zip(names, before))


@pytest.mark.parametrize("name", ["paged", "no_prefix"])
def test_prefill_decode_and_verify_are_one_transfer_and_one_launch(warm, name):
    engine = warm(name)
    engine.pool.reset()
    (tok, last), moved = _guarded(
        engine, lambda: engine.prefill(0, _prompt(9), **SAMPLED))
    assert moved == (1, 1) and last.shape == (MODEL["vocab_size"],)
    entry = (0, tok, SAMPLED["seed"], SAMPLED["temperature"], SAMPLED["top_k"])
    out, moved = _guarded(engine, lambda: engine.decode([entry]))
    assert moved == (1, 1) and set(out) == {0}
    entry = (0, out[0], [3, 4], *entry[2:])
    out, moved = _guarded(engine, lambda: engine.verify([entry]))
    assert moved == (1, 1) and 1 <= len(out[0]) <= 3


def test_a_prefix_hit_is_one_transfer_and_one_launch(warm):
    engine = warm("paged")
    engine.pool.reset()
    prompt = _prompt(40, seed=1)
    engine.prefill(0, prompt)
    reused = engine.registry.counter("serving/prefix_reused_tokens")
    before = reused.value
    _, moved = _guarded(
        engine, lambda: engine.prefill(1, prompt + [5, 6], **SAMPLED))
    assert moved == (1, 1)
    assert reused.value - before == 32       # the extend rung served it


def test_every_chunk_is_one_transfer_and_one_launch(warm):
    engine = warm("paged")
    engine.pool.reset()
    state = engine.prefill_open(0, _prompt(40, seed=2), **SAMPLED)
    assert len(state.spans) == 3
    done = False
    while not done:
        (done, tok, _), moved = _guarded(
            engine, lambda: engine.prefill_step(state))
        assert moved == (1, 1)
    assert tok is not None


def test_warmup_counts_its_launches_the_same_way(warm):
    engine = warm("two_kinds")
    reg = engine.registry
    assert (reg.counter("serving/launches_total").value
            == reg.counter("serving/launch_transfers_total").value
            == engine.expected_compiles())
    assert engine.post_warmup_recompiles() == 0


# ------------------------------------------- keys are made in the program


def test_no_key_program_runs_on_the_serve_thread(warm, monkeypatch):
    """A sampled request through the batcher, single-shot and chunked:
    between ``warmup()`` and the reply no ``_threefry_seed`` /
    ``_threefry_fold_in`` compiles (JAX's own lowering event), nothing
    calls ``request_key`` (every program was traced in warm-up; an
    eager call would be the serve thread's), no engine program
    compiles, and the streams are the plain reference's — which does
    call it eagerly, outside the watch."""
    import jax.monitoring
    from jax._src import monitoring as monitoring_src

    engine = warm("paged")
    engine.pool.reset()
    prompts = [_prompt(9, seed=3), _prompt(40, seed=4)]
    compiled, keyed = [], []
    real = engine_mod.request_key

    def listen(event, duration, **kw):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            compiled.append(str(kw.get("fun_name", "?")))

    def watched(seed, position):
        keyed.append((seed, position))
        return real(seed, position)

    chunks = engine.registry.counter("serving/prefill_chunks")
    chunks_before = chunks.value
    batcher = ContinuousBatcher(engine).start()
    try:
        monkeypatch.setattr(engine_mod, "request_key", watched)
        jax.monitoring.register_event_duration_secs_listener(listen)
        try:
            results = [
                batcher.submit(Request(prompt=p, max_new_tokens=6, **SAMPLED))
                .result(timeout=120) for p in prompts
            ]
        finally:
            monitoring_src.unregister_event_duration_listener(listen)
            monkeypatch.undo()
    finally:
        batcher.close(drain=True, timeout=60.0)
    assert keyed == []
    assert not [n for n in compiled if "threefry" in n or "_impl" in n], compiled
    assert chunks.value - chunks_before == 3     # the long prompt was chunked
    assert engine.post_warmup_recompiles() == 0
    for prompt, result in zip(prompts, results):
        assert result.tokens == engine.reference_generate(
            prompt, max_new=6, **SAMPLED)
    # the watch would have seen an eager key: the reference just made some
    assert engine_mod.request_key is real
