"""The three shares of device idle under the spans of ISSUE 36
(``benchmark/span_idle.py``; ``idle_in_admission.serve``,
``idle_in_kv_pool.serve``, ``idle_in_extend.serve``): nothing to read on
the recorded chip trace, which is older than the spans; a share of the
device's idle on a serve thread recorded live on the CPU with them."""

import dataclasses
import os
import shutil

import pytest

from benchmark import host_spans, span_idle, spec, trace_reduce
from benchmark.tests.test_host_spans import Ev, Line, Plane, _Run

DATA = os.path.join(os.path.dirname(__file__), "data")
SERVE_TRACE = os.path.join(DATA, "serve_small.xplane.pb")
SHARES = ("idle_in_admission.serve", "idle_in_kv_pool.serve", "idle_in_extend.serve")
MS = 1_000_000


@pytest.fixture
def traced_run(monkeypatch):
    """A run whose cell's trace directory holds ``planes``: ``ProfileData``
    hands them out (the file on disk is only found), or, given ``path``,
    the real file is copied there and read."""
    made = []

    def make(cell, planes=None, path=None):
        trace_dir = os.path.join(spec.ROOT, ".bench_out", "trace", cell)
        shutil.rmtree(trace_dir, ignore_errors=True)
        made.append(trace_dir)
        leaf = os.path.join(trace_dir, "plugins", "profile", "t0")
        os.makedirs(leaf, exist_ok=True)
        if path is None:
            with open(os.path.join(leaf, "host.xplane.pb"), "wb") as f:
                f.write(b"stand-in")
            import jax.profiler

            monkeypatch.setattr(
                jax.profiler.ProfileData, "from_file",
                staticmethod(lambda _: dataclasses.make_dataclass("D", ["planes"])(planes)))
        else:
            shutil.copy(path, os.path.join(leaf, "host.xplane.pb"))
            from jax.profiler import ProfileData

            planes = list(ProfileData.from_file(path).planes)
        host_spans._load.cache_clear()
        span_idle._names_on_the_line.cache_clear()
        return _Run(cell, trace_reduce.reduce_planes(planes))

    yield make
    for d in made:
        shutil.rmtree(d, ignore_errors=True)
    host_spans._load.cache_clear()
    span_idle._names_on_the_line.cache_clear()


def _read(run):
    return {m: spec.reader("layer_metrics", m)(run) for m in SHARES}


def test_the_entries_name_their_spans_cells_and_metric():
    entries = {m["name"]: m for m in spec.load_benchmark()["per_layer"]}
    for name in SHARES:
        m = entries[name]
        assert (m["unit"], m["better"], m["source"], m["moves"]) == (
            "%", "lower", "program_span", "serve_tokens_per_s")
        assert callable(spec.reader("layer_metrics", name))
    assert entries["idle_in_admission.serve"]["layer"] == "frontend and batcher"
    assert entries["idle_in_kv_pool.serve"]["layer"] == "engine"
    assert entries["idle_in_extend.serve"]["layer"] == "engine"
    assert entries["idle_in_admission.serve"]["workloads"] == ["glm-4.7-flash.serve-shareddoc"]
    assert entries["idle_in_kv_pool.serve"]["workloads"] == ["glm-4.7-flash.serve-shareddoc"]
    assert entries["idle_in_extend.serve"]["workloads"] == [
        "glm-4.7-flash.serve-shareddoc", "command-a-plus-05-2026.serve-longdoc"]


@pytest.mark.skipif(not os.path.exists(SERVE_TRACE), reason="no recorded serving trace in this tree")
def test_the_recorded_chip_trace_has_none_of_the_spans(traced_run):
    """The serve thread of ``serve_small.xplane.pb`` (recorded before the
    spans) is read, and holds none of them: each share is None, not 0."""
    run = traced_run("test-span-idle.chip", path=SERVE_TRACE)
    assert host_spans.for_run(run) is not None
    assert _read(run) == dict.fromkeys(SHARES)


def test_a_plain_run_reads_nothing():
    assert _read(_Run("test-span-idle.plain", None)) == dict.fromkeys(SHARES)


def _serve_loop_planes(admit_during_busy=False):
    """Three 10 ms iterations: admission 0-1, an admit 1-3 {kv_claim
    1.2-1.6}, decode step 3-8 {dispatch 4-5, fetch 5-7.5}, commit 8-10;
    the decode program runs 4.0-7.0 (the clock's offset is 0).
    ``admit_during_busy``: a program runs over the whole admit too."""
    spans, modules, ops = [], [], []
    for k in range(3):
        t = 10 * k
        spans += [Ev(n, (t + a) * MS, (b - a) * MS) for n, a, b in (
            ("span/serve_admission", 0, 1), ("span/serve_admit", 1, 3),
            ("span/kv_claim", 1.2, 1.6), ("span/serve_decode_step", 3, 8),
            ("span/engine_decode_dispatch", 4, 5), ("span/engine_decode_fetch", 5, 7.5),
            ("span/serve_commit", 8, 10))]
        runs = [(4.0, 7.0)] + ([(0.9, 3.1)] if admit_during_busy else [])
        for a, b in runs:
            modules.append(Ev("jit_paged_decode_impl_K64(1)" if a == 4.0 else "jit_other(2)",
                              (t + a) * MS, (b - a) * MS))
            ops.append(Ev("%fusion.1 = f32[] fusion()", (t + a) * MS, (b - a) * MS))
    return [Plane("/host:CPU", [Line("serving-batcher", spans)]),
            Plane("/device:TPU:0", [Line("XLA Modules", modules), Line("XLA Ops", ops)])]


def test_a_span_that_saw_no_idle_reads_zero_and_a_missing_one_none(traced_run):
    run = traced_run("test-span-idle.zero", planes=_serve_loop_planes(admit_during_busy=True))
    shares = _read(run)
    assert shares["idle_in_admission.serve"] == 0.0
    assert shares["idle_in_kv_pool.serve"] == 0.0
    assert shares["idle_in_extend.serve"] is None      # no extend span on the line
    run = traced_run("test-span-idle.idle", planes=_serve_loop_planes())
    shares = _read(run)
    # two gaps (7-14, 17-24) in a 23 ms window; in each the admit's own
    # 1.6 ms and the claim's 0.4
    assert shares["idle_in_admission.serve"] == pytest.approx(100 * 2 * 1.6 / 23)
    assert shares["idle_in_kv_pool.serve"] == pytest.approx(100 * 2 * 0.4 / 23)


# ------------------------------------------ live: the program's own spans


DOC = list(range(1, 41))


@pytest.fixture(scope="module")
def live_host_line(tmp_path_factory):
    """The program's serve thread, recorded on the CPU under a profiler
    session: GPT-2 at a toy width, the prefix cache on, chunks of 16;
    two questions on a published document (prefix hits) and one cold
    prompt of 40 (three chunks). The serve thread's ``span/`` events."""
    import glob

    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from tensorflow_examples_tpu.models import transformer
    from tensorflow_examples_tpu.serving.batcher import ContinuousBatcher, Request
    from tensorflow_examples_tpu.serving.engine import InferenceEngine, ServeConfig
    from tensorflow_examples_tpu.telemetry.registry import MetricsRegistry

    cfg = transformer.TransformerConfig(vocab_size=211, max_len=64, num_layers=2, num_heads=2,
                                        d_model=32, dropout=0.0, attention="xla")
    params = transformer.Transformer(cfg).init(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 8), jnp.int32))["params"]
    engine = InferenceEngine(cfg, params, registry=MetricsRegistry(), cfg=ServeConfig(
        max_slots=2, prefill_bucket_floor=16, kv_bucket_floor=32, kv_block_size=8,
        prefill_chunk_tokens=16))
    engine.warmup()
    trace_dir = str(tmp_path_factory.mktemp("live_trace"))
    batcher = ContinuousBatcher(engine).start()
    try:
        batcher.submit(Request(prompt=DOC, max_new_tokens=2)).result(timeout=120)
        jax.profiler.start_trace(trace_dir)
        try:
            futures = [batcher.submit(Request(prompt=p, max_new_tokens=6)) for p in (
                DOC + [50, 51, 52], DOC + [60, 61, 62], list(range(100, 140)))]
            [f.result(timeout=120) for f in futures]
        finally:
            jax.profiler.stop_trace()
    finally:
        batcher.close(drain=True, timeout=60.0)
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    spans, _ = host_spans.host_events(list(ProfileData.from_file(path).planes))
    return spans


def _with_a_device(spans):
    """The CPU trace has no device plane: put one under it. A decode
    step runs from the start of its dispatch span to the middle of its
    fetch (the clock's offset is 0), a prefill or an extend for 0.1 ms
    behind its dispatch; the device idles the rest."""
    modules = []
    fetches = sorted(s for s in spans if s[2] == host_spans.DECODE_FETCH)
    for start, end, name in spans:
        if not name.endswith("_dispatch"):
            continue
        if name == host_spans.DECODE_DISPATCH:
            f0, f1 = next((f0, f1) for f0, f1, _ in fetches if f0 >= end)
            modules.append(Ev("jit_paged_decode_impl_K32(1)", start, (f0 + f1) / 2 - start))
        else:
            modules.append(Ev("jit_extend_impl_T16(2)", end, 0.1 * MS))
    line = [Ev(name, s, e - s) for s, e, name in spans]
    ops = [Ev("%fusion.1 = f32[] fusion()", m.start_ns, m.duration_ns) for m in modules]
    return [Plane("/host:CPU", [Line("serving-batcher", line)]),
            Plane("/device:TPU:0", [Line("XLA Modules", modules), Line("XLA Ops", ops)])]


def test_the_live_spans_give_each_share_a_part_of_the_idle(live_host_line, traced_run, capsys):
    names = {n for _, _, n in live_host_line}
    assert {"span/serve_admit", "span/kv_claim", "span/kv_publish",
            "span/engine_extend_build", "span/engine_extend_fetch"} <= names
    run = traced_run("test-span-idle.live", planes=_with_a_device(live_host_line))
    shares = _read(run)
    idle = spec.reader("layer_metrics", "device_idle.generate")(run)
    for name, share in shares.items():
        assert share is not None and 0 < share <= idle, (name, share, idle)
    assert sum(shares.values()) <= idle
    found = host_spans.for_run(run)
    window_ns = run.trace.window_s * 1e9
    # the shares are the host_idle line's own numbers for their names
    assert shares["idle_in_admission.serve"] == pytest.approx(
        100 * found.idle_ns["span/serve_admit"] / window_ns)
    # the serve thread leaves no stretch of device idle without a span but
    # the loop's own tests (~10-20 us each: 1.4% of a toy CPU window of
    # 1 ms iterations, nothing of a chip's 50 ms ones)
    assert found.idle_ns.get(host_spans.UNATTRIBUTED, 0.0) < 0.05 * window_ns
    line = next(ln for ln in capsys.readouterr().out.splitlines()
                if ln.startswith("# host_idle "))
    assert "span/kv_claim" in line and "span/engine_extend_dispatch" in line
