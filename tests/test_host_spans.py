"""The program's spans on the profiler's clock, and its named programs
(ISSUE 25).

``Tracer.span`` writes each span into an open ``jax.profiler`` session
as an event ``span/<name>`` on the calling thread's line — the clock the
device's events are on — beside its histogram sample and Chrome event;
the serve thread's busy iteration is covered by spans without holes;
and every engine rung compiles to a program that carries the step
function's name and the rung. All on the CPU: a CPU trace has the host
plane, which is the half this file is about. The reduction that lays
these spans over the device's idle gaps is the benchmark's
(``benchmark/host_spans.py``, tested in ``benchmark/tests``).
"""

import glob
import os
import subprocess
import sys
import threading
import time

import pytest

from tensorflow_examples_tpu.models import transformer
from tensorflow_examples_tpu.serving.batcher import ContinuousBatcher, Request
from tensorflow_examples_tpu.serving.engine import InferenceEngine, ServeConfig
from tensorflow_examples_tpu.telemetry import spans as spans_mod
from tensorflow_examples_tpu.telemetry.registry import (
    MetricsRegistry,
    TimeHistogram,
)

pytestmark = pytest.mark.telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODEL = dict(vocab_size=211, max_len=64, num_layers=2, num_heads=2,
             d_model=32, dropout=0.0, attention="xla")


def _span_lines(trace_dir):
    """{line index: [(start_ns, end_ns, name, stats)]} of the ``span/``
    events on the host plane of the trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            evs = [
                (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
                 dict(ev.stats))
                for ev in line.events if ev.name.startswith("span/")
            ]
            if evs:
                out[i] = sorted(evs, key=lambda e: (e[0], -e[1]))
    return out


def _engine(**serve_kw):
    import jax
    import jax.numpy as jnp

    cfg = transformer.TransformerConfig(**MODEL)
    params = transformer.Transformer(cfg).init(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return InferenceEngine(
        cfg, params, registry=MetricsRegistry(),
        cfg=ServeConfig(max_slots=2, prefill_bucket_floor=16,
                        kv_bucket_floor=32, **serve_kw),
    )


# ------------------------------------------------------------ Tracer.span


def test_spans_land_in_the_profiler_trace_nested_with_their_args(tmp_path):
    import jax

    tracer = spans_mod.Tracer(registry=MetricsRegistry())
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracer.span("outer", active=3):
            with tracer.span("inner", K=512, dir="/x/y"):
                time.sleep(0.002)
            with tracer.span("second"):
                pass
    finally:
        jax.profiler.stop_trace()
    (events,) = _span_lines(str(tmp_path)).values()   # one thread, one line
    assert [e[2] for e in events] == [
        "span/outer", "span/inner", "span/second"]
    outer, inner, second = events
    assert outer[0] <= inner[0] and inner[1] <= second[0] \
        and second[1] <= outer[1]                       # nested as opened
    assert inner[1] - inner[0] >= 2_000_000
    assert outer[3] == {"active": 3}
    assert inner[3] == {"K": 512, "dir": "/x/y"}
    assert second[3] == {}


def test_each_thread_writes_its_own_line(tmp_path):
    import jax

    tracer = spans_mod.Tracer(registry=MetricsRegistry())

    def work():
        with tracer.span("in_thread"):
            time.sleep(0.001)

    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracer.span("in_main"):
            t = threading.Thread(target=work, name="serving-batcher")
            t.start()
            t.join(timeout=30)
    finally:
        jax.profiler.stop_trace()
    assert not t.is_alive()
    lines = _span_lines(str(tmp_path))
    assert sorted([e[2] for e in evs] for evs in lines.values()) == [
        ["span/in_main"], ["span/in_thread"]]


def test_without_a_profiler_session_the_span_records_as_before():
    reg = MetricsRegistry()
    tracer = spans_mod.Tracer(registry=reg)
    with tracer.span("alone", step=7):
        assert tracer.active_span_names() == ["alone"]
    assert tracer.active_span_names() == []
    (ev,) = tracer.events()
    assert ev["name"] == "alone" and ev["ph"] == "X" \
        and ev["args"] == {"step": 7} and ev["dur"] >= 0
    assert reg.histogram("span/alone").count == 1


def test_a_span_survives_an_exception_in_its_body(tmp_path):
    import jax

    reg = MetricsRegistry()
    tracer = spans_mod.Tracer(registry=reg)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with pytest.raises(ValueError):
            with tracer.span("failing"):
                raise ValueError("boom")
    finally:
        jax.profiler.stop_trace()
    assert tracer.active_span_names() == []
    assert reg.histogram("span/failing").count == 1
    (events,) = _span_lines(str(tmp_path)).values()
    assert [e[2] for e in events] == ["span/failing"]


def test_the_telemetry_package_still_imports_without_jax():
    """The report tools read run records with it; the annotation class
    is imported at the first span, not with the package."""
    code = (
        "import sys; import tensorflow_examples_tpu.telemetry as t; "
        "assert 'jax' not in sys.modules, 'telemetry imported jax'; "
        "t.spans.Tracer().span('x').__enter__(); "
        "assert 'jax' in sys.modules"
    )
    subprocess.run([sys.executable, "-c", code], check=True, cwd=REPO,
                   timeout=120)


def test_cost_of_an_empty_span_is_reported(record_property):
    """Reported, not asserted on time (CHANGES.md carries parent vs
    change): one empty ``Tracer.span`` with no profiler session."""
    tracer = spans_mod.Tracer(registry=MetricsRegistry(), max_events=0)
    n = 20_000
    for _ in range(1_000):
        with tracer.span("warm"):
            pass
    t0 = time.perf_counter_ns()
    for _ in range(n):
        with tracer.span("empty", active=1):
            pass
    per_span_us = (time.perf_counter_ns() - t0) / n / 1e3
    record_property("empty_span_us", round(per_span_us, 3))
    print(f"empty Tracer.span: {per_span_us:.2f} us")
    assert tracer.dropped == n + 1_000 and per_span_us > 0


# ------------------------------------------------------- TimeHistogram


def test_histogram_samples_since_a_mark():
    h = TimeHistogram("t", max_samples=4)
    assert h.samples_since(0) == []
    h.record(1.0)
    h.record(2.0)
    mark = h.count
    assert h.samples_since(mark) == []
    h.record(3.0)
    h.record(4.0)
    assert h.samples_since(mark) == [3.0, 4.0]
    assert h.samples_since(0) == [1.0, 2.0, 3.0, 4.0]
    h.record(5.0)                       # the oldest sample falls out
    assert h.samples_since(mark) == [3.0, 4.0, 5.0]
    assert h.samples_since(0) == [2.0, 3.0, 4.0, 5.0]   # what is kept
    with pytest.raises(ValueError):
        h.samples_since(h.count + 1)


# ------------------------------------------------------- program names

RUNGS = [
    ("_prefill_fns", "paged_prefill_impl_L", "serve_prefill_L"),
    ("_decode_fns", "paged_decode_impl_K", "serve_decode_K"),
    ("_extend_fns", "extend_impl_T", "serve_extend_T"),
    ("_verify_fns", "paged_verify_impl_K", "serve_verify_K"),
]


def _rung(bucket) -> str:
    """A rung as its program's name ends: the bucket, or for an extend
    program under the whole table ``(tb, cb)`` as ``<tb>_C<cb>``."""
    return "{}_C{}".format(*bucket) if isinstance(bucket, tuple) else str(bucket)


@pytest.fixture(scope="module")
def engines():
    """GPT-2's block with every family, and the two-kind block (the
    serve-longdoc cell's), which has no verify family."""
    from test_launch_block import _two_kinds

    make = {"gpt2": lambda: _engine(spec_decode_k=2), "two_kinds": _two_kinds}
    made = {}

    def get(name):
        if name not in made:
            made[name] = make[name]()
        return made[name]
    return get


@pytest.mark.parametrize(
    "block,attr,program,sentinel",
    [("gpt2", *r) for r in RUNGS] + [("two_kinds", *r) for r in RUNGS[:3]],
)
def test_every_rung_is_named_after_its_step_function_and_rung(
        engines, block, attr, program, sentinel):
    engine = engines(block)
    fns = getattr(engine, attr)
    assert fns, f"{attr} is empty"
    for bucket, fn in fns.items():
        bucket = _rung(bucket)
        assert fn.__name__ == f"{program}{bucket}"
        # benchmark/runners/serve.py fails a run in whose window a
        # program with "_impl" in its name compiles; the roofline
        # reader finds decode programs by "decode_impl"
        assert "_impl" in fn.__name__
        assert ("decode_impl" in fn.__name__) == (attr == "_decode_fns")
        # the sentinel's names are the operator's, and stay
        assert f"{sentinel}{bucket}" in engine.sentinel.compile_counts()


def test_the_two_kind_block_has_an_extend_program_a_context_rung(engines):
    """ISSUE 35: chunks of 8 under kv rungs 16, 32 and 64 — the whole
    table under the name it had, the rungs under it ``_C<cb>``."""
    assert [fn.__name__ for fn in engines("two_kinds")._extend_fns.values()] == [
        "extend_impl_T8", "extend_impl_T8_C16", "extend_impl_T8_C32"]


@pytest.mark.parametrize("serve_kw", [{}, dict(kv_block_size=16, prefill_chunk_tokens=64)],
                         ids=["whole_prompts", "chunked"])
def test_gpt2_keeps_its_nineteen_programs_and_their_names(serve_kw):
    """GPT-2's tails run to ``max_len`` (no chunk cap on its prefill
    ladder), so the only context rung at or above the longest tail is
    the whole table: at the serving cell's ladders (1,024 positions, the
    default floors) the engine has the 19 programs it had before the
    extend family got a context ladder (ISSUE 35), under their names."""
    import jax
    import jax.numpy as jnp

    cfg = transformer.TransformerConfig(**{**MODEL, "max_len": 1024})
    params = transformer.Transformer(cfg).init(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32)
    )["params"]
    engine = InferenceEngine(cfg, params, registry=MetricsRegistry(),
                             cfg=ServeConfig(max_slots=2, **serve_kw))
    tails, contexts = [16, 32, 64, 128, 256, 512, 1024], [64, 128, 256, 512, 1024]
    assert engine.extend_ladder == [1024]
    names = [
        fn.__name__
        for fns in (engine._prefill_fns, engine._decode_fns, engine._extend_fns)
        for fn in fns.values()
    ]
    assert names == (
        [f"paged_prefill_impl_L{lb}" for lb in tails]
        + [f"paged_decode_impl_K{kb}" for kb in contexts]
        + [f"extend_impl_T{tb}" for tb in tails]
    )
    assert len(names) == engine.expected_compiles() == 19
    assert sorted(engine.sentinel.compile_counts()) == sorted(
        [f"serve_prefill_L{lb}" for lb in tails]
        + [f"serve_decode_K{kb}" for kb in contexts]
        + [f"serve_extend_T{tb}" for tb in tails]
    )


def test_jax_reports_the_names_in_its_compile_events():
    """JAX's own lowering event (the benchmark's CompileLog listens to
    it) carries each rung's name; nothing of the engine's is
    ``<unknown>`` any more."""
    import jax.monitoring
    from jax._src import monitoring as monitoring_src

    seen = []

    def listen(event, duration, **kw):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            seen.append(str(kw.get("fun_name", "?")))

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        engine = _engine(spec_decode_k=2)
        engine.warmup()
    finally:
        monitoring_src.unregister_event_duration_listener(listen)
    for attr, program, _ in RUNGS:
        for bucket in map(_rung, getattr(engine, attr)):
            assert f"jit({program}{bucket})" in seen, (program, bucket, seen)
    assert not [n for n in seen if "unknown" in n], seen
    assert engine.post_warmup_recompiles() == 0


def test_a_rung_lowers_to_a_module_with_its_name():
    import jax
    import jax.numpy as jnp

    engine = _engine()
    bucket = engine.kv_ladder[0]
    s = engine.cfg.max_slots
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    lowered = engine._decode_fns[bucket].lower(
        engine.params, engine.pool.kv_state(), i32(s), i32(s),
        i32(s, bucket // engine.cfg.kv_block_size), i32(s),
        jax.ShapeDtypeStruct((s,), jnp.float32), i32(s),
    )
    assert f"module @jit_paged_decode_impl_K{bucket} " in lowered.as_text()[:200]


# ----------------------------------------------------- the serve thread


@pytest.fixture(scope="module")
def served_trace(tmp_path_factory):
    """Two requests through the batcher under a profiler session; the
    serve thread's ``span/`` events and the batcher's thread ident."""
    import jax

    trace_dir = str(tmp_path_factory.mktemp("serve_trace"))
    engine = _engine()
    engine.warmup()
    spans_mod.reset_default_tracer()
    batcher = ContinuousBatcher(engine).start()
    try:
        batcher.submit(Request(prompt=[5, 6, 7], max_new_tokens=3)).result(
            timeout=120)                 # whatever is lazy happens here
        jax.profiler.start_trace(trace_dir)
        try:
            futures = [
                batcher.submit(Request(prompt=list(range(1, n)),
                                       max_new_tokens=6))
                for n in (9, 21)
            ]
            results = [f.result(timeout=120) for f in futures]
        finally:
            jax.profiler.stop_trace()
        ident = batcher._thread.ident
    finally:
        batcher.close(drain=True, timeout=60.0)
    assert all(len(r.tokens) == 6 for r in results)
    lines = _span_lines(trace_dir)
    (events,) = [evs for evs in lines.values()
                 if any(e[2] == "span/serve_decode_step" for e in evs)]
    chrome = [e for e in spans_mod.default_tracer().events()
              if e["tid"] == ident]
    return events, chrome


def _children(events, parent):
    return [e for e in events
            if e is not parent and parent[0] <= e[0] and e[1] <= parent[1]]


def test_the_serve_thread_runs_the_span_sequence_without_holes(served_trace):
    events, _ = served_trace
    top = [e for e in events
           if not any(o is not e and o[0] <= e[0] and e[1] <= o[1]
                      for o in events)]
    names = [e[2] for e in top]
    allowed = {"span/serve_admission", "span/serve_prefill",
               "span/serve_decode_step", "span/serve_commit"}
    assert set(names) <= allowed, set(names) - allowed
    steps = [i for i, n in enumerate(names) if n == "span/serve_decode_step"]
    assert len(steps) >= 5
    for i in steps:
        # commit right behind every decode step; admission in front of
        # it, with only this iteration's prefills between
        assert names[i + 1] == "span/serve_commit"
        j = i - 1
        while j >= 0 and names[j] == "span/serve_prefill":
            j -= 1
        # (the first iteration's admission was waiting for a request
        # when the trace began: a span opened before the session is
        # not in it)
        assert names[j] == "span/serve_admission" if j >= 0 else i == steps[0]
        assert j <= 0 or names[j - 1] in (
            "span/serve_commit", "span/serve_admission")
    assert "span/serve_prefill" in names


def test_build_upload_dispatch_fetch_sit_inside_the_decode_step(served_trace):
    events, _ = served_trace
    for step in (e for e in events if e[2] == "span/serve_decode_step"):
        inner = [e[2] for e in _children(events, step)]
        assert inner == [
            "span/engine_decode_build", "span/engine_decode_upload",
            "span/engine_decode_dispatch", "span/engine_decode_fetch"]
        assert step[3]["active"] >= 1
        build = _children(events, step)[0]
        assert build[3]["K"] in (32, 64)
    for prefill in (e for e in events if e[2] == "span/serve_prefill"):
        assert [e[2] for e in _children(events, prefill)] == [
            "span/engine_prefill_build", "span/engine_prefill_upload",
            "span/engine_prefill_dispatch", "span/engine_prefill_fetch"]


def test_the_busy_thread_is_inside_a_span_nearly_all_the_time(served_trace):
    """Between the first decode step and the last commit the holes are
    the ``while`` test and the context managers' own entry and exit."""
    events, _ = served_trace
    first = min(e[0] for e in events if e[2] == "span/serve_decode_step")
    last = max(e[1] for e in events if e[2] == "span/serve_commit")
    top = [e for e in events if first <= e[0] and e[1] <= last
           and e[2] in ("span/serve_admission", "span/serve_prefill",
                        "span/serve_decode_step", "span/serve_commit")]
    covered = sum(e[1] - e[0] for e in top)
    assert covered / (last - first) > 0.9


def test_the_same_spans_reach_the_chrome_buffer_on_the_batcher_thread(
        served_trace):
    events, chrome = served_trace
    assert chrome, "no Chrome event carries the serving-batcher thread's id"
    in_trace = {e[2] for e in events}
    assert {"span/" + e["name"] for e in chrome} >= in_trace
    counts = lambda name: sum(1 for e in events if e[2] == "span/" + name)
    assert counts("serve_commit") == counts("serve_decode_step") \
        == counts("engine_decode_fetch")
