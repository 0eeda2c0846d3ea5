"""Host spans x device gaps (``benchmark/host_spans.py``): the clock
offset, the attribution and the four idle shares on hand-made planes
whose answer is known, and on one small serving trace recorded on the
chip (``record_serve_trace.py``)."""

import dataclasses
import os
import shutil

import pytest

from benchmark import host_spans, spec, trace_reduce

DATA = os.path.join(os.path.dirname(__file__), "data")
SERVE_TRACE = os.path.join(DATA, "serve_small.xplane.pb")
MS = 1_000_000
NEW_METRICS = ("idle_in_batcher.generate", "idle_in_engine_launch.generate",
               "idle_in_engine_fetch.generate", "idle_unattributed.generate")


@dataclasses.dataclass
class Ev:
    name: str
    start_ns: float
    duration_ns: float


@dataclasses.dataclass
class Line:
    name: str
    events: list


@dataclasses.dataclass
class Plane:
    name: str
    lines: list


def _ev(name, start_ms, end_ms):
    return Ev(name, start_ms * MS, (end_ms - start_ms) * MS)


def _planes(steps=4, skew_ms=1.5, pjrt=True, first_host_step=0, extra=()):
    """A serial serve loop of 10 ms steps: admission 0-1, decode step 1-8
    {build 1-2, upload 2-3, dispatch 3-4, fetch 4-7.5}, commit 8-10. The
    device runs each step's program from 3.6 to 6.6 on the host's clock
    and stamps it ``skew_ms`` early; PJRT enqueues at 3.5 and calls back
    at 6.7."""
    spans, calls, modules, ops = [], [], [], []
    for k in range(steps):
        t = 10 * k
        if k >= first_host_step:
            spans += [
                _ev("span/serve_admission", t, t + 1),
                _ev("span/serve_decode_step", t + 1, t + 8),
                _ev("span/engine_decode_build", t + 1, t + 2),
                _ev("span/engine_decode_upload", t + 2, t + 3),
                _ev("span/engine_decode_dispatch", t + 3, t + 4),
                _ev("span/engine_decode_fetch", t + 4, t + 7.5),
                _ev("span/serve_commit", t + 8, t + 10),
                _ev("$batcher.py:600 _commit", t + 8.1, t + 9),   # the Python tracer's: not ours
            ]
            if pjrt:
                calls += [_ev(host_spans.ENQUEUE, t + 3.5, t + 3.55),
                          _ev(host_spans.DONE, t + 6.7, t + 6.75)]
        modules.append(_ev("jit_paged_decode_impl_K64(77)", t + 3.6 - skew_ms, t + 6.6 - skew_ms))
        ops += [_ev("%fusion.1 = f32[] fusion()", t + 3.6 - skew_ms, t + 5.0 - skew_ms),
                _ev("%copy.2 = f32[] copy()", t + 5.0 - skew_ms, t + 6.6 - skew_ms)]
    spans += list(extra)
    return [
        Plane("/host:CPU", [Line("tracer", [_ev("span/profile", 0, 1)]),
                            Line("serving-batcher", spans), Line("main/9", calls)]),
        Plane("/device:TPU:0", [Line("XLA Modules", modules), Line("XLA Ops", ops)]),
    ]


def _clock(planes):
    spans, pjrt = host_spans.host_events(planes)
    (executions, _, _), = host_spans.device_timelines(planes)
    return host_spans.clock_offset(executions, spans, pjrt)


def test_the_thread_line_is_found_by_its_marker_and_only_span_events_are_taken():
    spans, pjrt = host_spans.host_events(_planes(steps=2))
    assert len(spans) == 14 and all(n.startswith("span/") for _, _, n in spans)
    assert "span/profile" not in {n for _, _, n in spans}       # another thread's line
    assert len(pjrt[host_spans.ENQUEUE]) == 2 and len(pjrt[host_spans.DONE]) == 2
    assert host_spans.host_events(_planes()[1:]) == ([], {host_spans.ENQUEUE: [], host_spans.DONE: []})


def test_offset_interval_from_the_spans_alone():
    """dispatch start 3.0 - device start 2.1 = 0.9; fetch end 7.5 -
    device end 5.1 = 2.4: the estimate is the lower end."""
    c = _clock(_planes(pjrt=False))
    assert c.bounds == "spans" and c.pairs == 4 and c.shift == 0
    assert c.offset_ns == pytest.approx(0.9 * MS) and c.slack_ns == pytest.approx(1.5 * MS)


def test_pjrt_events_tighten_both_ends():
    """enqueue start 3.5 - 2.1 = 1.4; completion callback start 6.7 - 5.1 = 1.6."""
    c = _clock(_planes())
    assert c.bounds == "pjrt"
    assert c.offset_ns == pytest.approx(1.4 * MS) and c.slack_ns == pytest.approx(0.2 * MS)


def test_a_step_cut_off_by_the_start_of_the_trace_shifts_the_pairing():
    """The device shows a leading execution whose dispatch span began
    before the host tracer did: device k pairs with host step k - 1."""
    c = _clock(_planes(steps=6, first_host_step=1))
    assert c.shift == -1 and c.pairs == 5
    assert c.offset_ns == pytest.approx(1.4 * MS)


def test_an_empty_interval_gives_none_not_a_guess():
    planes = _planes(pjrt=False)
    fetch = next(e for e in planes[0].lines[1].events
                 if e.name == "span/engine_decode_fetch" and e.start_ns == 24 * MS)
    fetch.duration_ns = 0.5 * MS      # the tokens in hand before the device finished
    assert _clock(planes) is None
    assert host_spans.reduce_planes(planes) is None


def test_attribution_goes_to_the_innermost_span():
    """With the offset 1.4 the program runs 3.5-6.5 on the host's
    clock; a gap is fetch 1.0, decode step's own 0.5, commit 2.0,
    admission 1.0, build 1.0, upload 1.0, dispatch 0.5 ms."""
    found = host_spans.reduce_planes(_planes())
    gaps = 3
    want = {"span/engine_decode_fetch": 1.0, "span/serve_decode_step": 0.5,
            "span/serve_commit": 2.0, "span/serve_admission": 1.0,
            "span/engine_decode_build": 1.0, "span/engine_decode_upload": 1.0,
            "span/engine_decode_dispatch": 0.5}
    assert {k: v / MS for k, v in found.idle_ns.items()} == {
        k: pytest.approx(gaps * v) for k, v in want.items()}
    assert {k: v / MS for k, v in found.layer_ns.items()} == {
        "engine_fetch": pytest.approx(3.0), "engine_launch": pytest.approx(7.5),
        "batcher": pytest.approx(10.5)}
    assert found.span_ns == pytest.approx(33 * MS)


def test_a_span_of_no_layer_counts_for_the_layer_around_it_and_bare_time_for_none():
    compile_ = _ev("span/compile", 13.1, 13.4)              # inside step 1's dispatch
    planes = _planes(extra=[compile_])
    commit = next(e for e in planes[0].lines[1].events
                  if e.name == "span/serve_commit" and e.start_ns == 18 * MS)
    commit.duration_ns = 1.0 * MS                           # a hole: 19-20 ms, no span open
    found = host_spans.reduce_planes(planes)
    assert found.idle_ns["span/compile"] == pytest.approx(0.3 * MS)
    assert found.idle_ns[host_spans.UNATTRIBUTED] == pytest.approx(1.0 * MS)
    assert found.layer_ns["engine_launch"] == pytest.approx(7.5 * MS)   # compile is still launch
    assert found.layer_ns["batcher"] == pytest.approx(9.5 * MS)
    assert found.layer_ns[host_spans.UNATTRIBUTED] == pytest.approx(1.0 * MS)


def test_segments_are_disjoint_and_carry_the_open_stack():
    spans = [(0, 10, "a"), (1, 4, "b"), (2, 3, "c"), (4, 6, "d"), (12, 13, "e")]
    assert host_spans.segments(spans) == [
        (0, 1, ("a",)), (1, 2, ("a", "b")), (2, 3, ("a", "b", "c")), (3, 4, ("a", "b")),
        (4, 6, ("a", "d")), (6, 10, ("a",)), (12, 13, ("e",))]


class _Run:
    def __init__(self, cell_name, trace):
        self.cell = dataclasses.make_dataclass("C", ["name"])(cell_name)
        self.trace = trace


@pytest.fixture
def traced_run(monkeypatch):
    """A run whose cell's trace directory holds the stand-in planes:
    ``ProfileData`` is replaced, the file on disk is only found."""
    made = []

    def make(planes, cell="test-host-spans.cell", host_window_s=0.040):
        trace_dir = os.path.join(spec.ROOT, ".bench_out", "trace", cell)
        made.append(trace_dir)
        leaf = os.path.join(trace_dir, "plugins", "profile", "t0")
        os.makedirs(leaf, exist_ok=True)
        with open(os.path.join(leaf, "host.xplane.pb"), "wb") as f:
            f.write(b"stand-in")
        import jax.profiler

        monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                            staticmethod(lambda path: dataclasses.make_dataclass("D", ["planes"])(planes)))
        host_spans._load.cache_clear()
        summary = trace_reduce.reduce_planes(planes)
        summary.host_window_s = host_window_s
        return _Run(cell, summary)

    yield make
    for d in made:
        shutil.rmtree(d, ignore_errors=True)
    host_spans._load.cache_clear()


def test_the_four_shares_sum_to_the_idle_share_and_the_log_lines_are_printed(traced_run, capsys):
    run = traced_run(_planes())
    shares = {m: spec.reader("layer_metrics", m)(run) for m in NEW_METRICS}
    # 40 ms window: busy 4 x 3 ms; idle 21 ms between programs + 7 ms of lead and tail
    assert shares["idle_in_batcher.generate"] == pytest.approx(100 * 10.5 / 40)
    assert shares["idle_in_engine_launch.generate"] == pytest.approx(100 * 7.5 / 40)
    assert shares["idle_in_engine_fetch.generate"] == pytest.approx(100 * 3.0 / 40)
    assert shares["idle_unattributed.generate"] == pytest.approx(100 * 7.0 / 40)
    assert sum(shares.values()) == pytest.approx(100 * run.trace.idle_share)
    assert sum(shares.values()) == pytest.approx(spec.reader("layer_metrics", "device_idle.generate")(run))
    out = capsys.readouterr().out.splitlines()
    assert len([ln for ln in out if ln.startswith("# clock ")]) == 1       # parsed once a run
    clock = next(ln for ln in out if ln.startswith("# clock "))
    assert '"offset_us": 1400.0' in clock and '"pairs": 4' in clock
    assert any(ln.startswith("# host_idle ") and "span/serve_commit" in ln for ln in out)


def test_a_trace_without_program_spans_reads_as_nothing(traced_run):
    """The parent commit, or a CPU run: no ``span/`` line, or no device
    plane. Every reader returns None and raises nothing."""
    planes = _planes()
    planes[0].lines[1].events = [e for e in planes[0].lines[1].events
                                 if not e.name.startswith("span/")]
    run = traced_run(planes)
    assert [spec.reader("layer_metrics", m)(run) for m in NEW_METRICS] == [None] * 4
    run = traced_run(_planes()[:1], cell="test-host-spans.cpu")
    assert [spec.reader("layer_metrics", m)(run) for m in NEW_METRICS] == [None] * 4
    plain = _Run("test-host-spans.plain", None)
    assert [spec.reader("layer_metrics", m)(plain) for m in NEW_METRICS] == [None] * 4


def test_the_new_entries_have_their_readers_and_move_the_serving_metric():
    bench = spec.load_benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in (*NEW_METRICS, "decode_hbm_roofline.generate"):
        m = entries[name]
        assert m["moves"] == "serve_tokens_per_s" and m["unit"] == "%"
        assert all(w.endswith(".serve-generate") for w in m["workloads"])
        assert callable(spec.reader("layer_metrics", name))
    assert [entries[n]["source"] for n in NEW_METRICS] == ["program_span"] * 4
    assert entries["decode_hbm_roofline.generate"]["source"] == "device_trace"


def test_the_recorded_train_trace_has_no_serve_thread():
    from jax.profiler import ProfileData

    planes = ProfileData.from_file(os.path.join(DATA, "small.xplane.pb")).planes
    assert host_spans.reduce_planes(planes) is None


@pytest.mark.skipif(not os.path.exists(SERVE_TRACE), reason="no recorded serving trace in this tree")
def test_recorded_serving_trace_from_the_chip():
    """The program's own engine and batcher at a toy width on the chip
    (``record_serve_trace.py``): the spans are on the profiler's clock,
    the programs carry their names, the offset interval is not empty,
    and every idle nanosecond between the device's ops lands somewhere."""
    from jax.profiler import ProfileData

    # ProfileData hands its planes out once: reduce_planes has to keep them
    found = host_spans.reduce_planes(ProfileData.from_file(SERVE_TRACE).planes)
    planes = list(ProfileData.from_file(SERVE_TRACE).planes)
    spans, pjrt = host_spans.host_events(planes)
    names = {n for _, _, n in spans}
    assert {"span/serve_admission", "span/serve_prefill", "span/serve_decode_step",
            "span/engine_decode_build", "span/engine_decode_upload",
            "span/engine_decode_dispatch", "span/engine_decode_fetch",
            "span/serve_commit"} <= names
    assert pjrt[host_spans.ENQUEUE] and pjrt[host_spans.DONE]
    (executions, gaps, span_ns), = host_spans.device_timelines(planes)
    programs = {n for _, _, n in executions}
    assert any(p.startswith("jit_paged_decode_impl_K") for p in programs)
    assert any(p.startswith("jit_paged_prefill_impl_L") for p in programs)
    assert not [p for p in programs if "unknown" in p]

    assert found is not None and found.clock.pairs >= 8
    assert 0 < found.clock.offset_ns < 5 * MS          # the device plane reads early, by ms
    assert found.clock.slack_ns >= 0
    summary = trace_reduce.reduce_planes(planes)
    idle_between_ops = (summary.planes[0].span_s - summary.planes[0].busy_s) * 1e9
    assert sum(found.idle_ns.values()) == pytest.approx(idle_between_ops, rel=1e-6)
    assert sum(found.layer_ns.values()) == pytest.approx(idle_between_ops, rel=1e-6)
    # a serial loop: the chip idles while the host launches and while it commits
    assert found.layer_ns["engine_launch"] > 0 and found.layer_ns["batcher"] > 0
    assert found.layer_ns.get(host_spans.UNATTRIBUTED, 0.0) < 0.25 * idle_between_ops
