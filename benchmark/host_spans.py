"""Host spans x device gaps: what the host was doing while the chip idled.

The program's ``Tracer.span`` (telemetry/spans.py) writes every span into
the profiler's own trace as an event ``span/<name>`` on the calling
thread's line of the ``/host:CPU`` plane. This module reads the same
``.xplane.pb`` as ``trace_reduce``, takes the serve thread's spans and
the device's idle gaps (exactly ``trace_reduce``'s), estimates the offset
between the two planes' clocks, and gives every instant of device idle
time to the innermost span open on that thread at that instant.

The two planes' clocks are not one: in ``tests/data/small.xplane.pb`` the
device line reads 1.2-1.8 ms earlier than the host's PJRT events for the
same execution. So the offset is estimated from causality and reported
with its slack, and an inconsistent trace gives no number, not a guess.

A trace of a program without such spans (a parent commit) holds nothing
to read: every reader here returns ``None`` and the harness leaves the
metric out of the line.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
import re

from benchmark import spec, trace_reduce

HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "span/"
# The line to read is the one that holds one of these: the serve loop's
# decode step, or the train loop's device step.
MARKERS = ("span/serve_decode_step", "span/device_step")
# PJRT's own events for one execution, on whichever host thread runs them:
# the program cannot start on the device before its enqueue starts, and the
# completion callback cannot start before the device has finished.
ENQUEUE, DONE = "DoEnqueueProgram", "tpu::System::Execute=>Done"
DECODE_PROGRAM = re.compile(r"decode_impl")
DECODE_DISPATCH, DECODE_FETCH = "span/engine_decode_dispatch", "span/engine_decode_fetch"
UNATTRIBUTED = "unattributed"
# A span's layer, by its name; an instant belongs to the innermost open span
# that has one (a ``span/compile`` inside a dispatch is still the launch).
LAYERS = (
    ("engine_fetch", re.compile(r"^span/engine_\w+_fetch$")),
    ("engine_launch", re.compile(r"^span/engine_\w+_(build|upload|dispatch)$")),
    ("batcher", re.compile(r"^span/serve_")),
)
MAX_SHIFT = 3   # executions cut off by either end of the trace, at most
MIN_PAIRS = 3


@dataclasses.dataclass
class Clock:
    """``offset_ns`` is added to device times to put them on the host's
    clock; any value in [offset, offset + slack] satisfies every pair."""

    offset_ns: float
    slack_ns: float
    pairs: int
    shift: int          # device execution k pairs with host dispatch k + shift
    bounds: str         # "pjrt" where PJRT's events tightened the spans' own


@dataclasses.dataclass
class HostIdle:
    clock: Clock
    idle_ns: dict        # innermost span name (or "unattributed") -> ns of device idle
    layer_ns: dict       # layer (or "unattributed") -> ns
    span_ns: float       # first device op start -> last device op end


def layer_of(stack: tuple) -> str:
    for name in reversed(stack):
        for layer, rx in LAYERS:
            if rx.search(name):
                return layer
    return UNATTRIBUTED


# ------------------------------------------------------------ from the planes


def host_events(planes):
    """(spans, pjrt): ``spans`` the ``span/`` events of the first thread
    line that holds one of MARKERS, as (start_ns, end_ns, name) sorted
    by start; ``pjrt`` the ENQUEUE and DONE events of every host line."""
    spans, pjrt = [], {ENQUEUE: [], DONE: []}
    for plane in planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            mine, marked = [], False
            for ev in line.events:
                name = ev.name
                if name.startswith(SPAN_PREFIX):
                    mine.append((ev.start_ns, ev.start_ns + ev.duration_ns, name))
                    marked = marked or name in MARKERS
                elif name in pjrt:
                    pjrt[name].append((ev.start_ns, ev.start_ns + ev.duration_ns))
            if marked and not spans:
                spans = sorted(mine, key=lambda s: (s[0], -s[1]))
    for evs in pjrt.values():
        evs.sort()
    return spans, pjrt


def device_timelines(planes):
    """Per device plane that ran anything: (executions, gaps, span_ns) —
    the ``XLA Modules`` executions as (start, end, name) and the idle
    gaps between the merged ``XLA Ops`` intervals as (start, end),
    ``trace_reduce``'s own union."""
    out = []
    for plane in planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        execs, intervals = [], []
        for line in plane.lines:
            if line.name == trace_reduce.MODULES_LINE:
                execs += [(ev.start_ns, ev.start_ns + ev.duration_ns,
                           trace_reduce.short_name(ev.name)) for ev in line.events]
            elif line.name == trace_reduce.OPS_LINE:
                intervals += [(ev.start_ns, ev.start_ns + ev.duration_ns) for ev in line.events]
        if not intervals:
            continue
        _, gaps = trace_reduce._union_and_gaps(intervals)
        span_ns = max(e for _, e in intervals) - min(s for s, _ in intervals)
        out.append((sorted(execs), [(nxt - gap, nxt) for gap, nxt in gaps], span_ns))
    return out


# ------------------------------------------------------------------ the clock


def _only(events, lo, hi):
    """The one event that starts in [lo, hi], else None."""
    found = [ev for ev in events if lo <= ev[0] <= hi]
    return found[0] if len(found) == 1 else None


def decode_steps(spans):
    """The serve thread's decode steps as (dispatch, fetch) pairs of
    (start, end): a dispatch whose fetch the trace cut off is left out."""
    marks = sorted((s, e, name) for s, e, name in spans
                   if name in (DECODE_DISPATCH, DECODE_FETCH))
    return [(a[:2], b[:2]) for a, b in zip(marks, marks[1:])
            if a[2] == DECODE_DISPATCH and b[2] == DECODE_FETCH]


def clock_offset(executions, spans, pjrt=None) -> Clock | None:
    """Pair the k-th decode execution with the k-th decode dispatch and
    fetch span. Causality bounds the offset ``off`` added to device
    times: ``dev_start + off >= dispatch_start`` and ``dev_end + off <=
    fetch_end`` for every pair; PJRT's enqueue and completion events of
    the same step, where there is exactly one of each, tighten both
    (their meaning is PJRT's, not a contract: if they contradict each
    other the spans' own bounds stand). The estimate is the lower end:
    an idle device starts within microseconds of its enqueue, and in a
    serial loop it is always idle at dispatch. Either end of the trace
    may have cut a step in two, so the pairing is tried a few steps to
    either side and the consistent one nearest zero wins (the planes'
    clocks differ by a millisecond or two, a step by many). ``None``
    where no pairing is consistent."""
    dev = [(s, e) for s, e, name in executions if DECODE_PROGRAM.search(name)]
    steps = decode_steps(spans)
    enqueues, dones = (pjrt or {}).get(ENQUEUE, ()), (pjrt or {}).get(DONE, ())
    best = None
    for shift in range(-MAX_SHIFT, MAX_SHIFT + 1):
        pairs = [(dev[k], steps[k + shift]) for k in range(len(dev))
                 if 0 <= k + shift < len(steps)]
        if len(pairs) < MIN_PAIRS:
            continue
        lows, highs, tight_lows, tight_highs = [], [], [], []
        for (dev_s, dev_e), (dispatch, fetch) in pairs:
            lows.append(dispatch[0] - dev_s)
            highs.append(fetch[1] - dev_e)
            # (the enqueue runs on a thread of PJRT's own and may start
            # after the dispatch span has returned)
            enq = _only(enqueues, dispatch[0], fetch[1])
            done = _only(dones, dispatch[0], fetch[1])
            if enq:
                tight_lows.append(enq[0] - dev_s)
            if done:
                tight_highs.append(done[0] - dev_e)
        lo, hi = max(lows), min(highs)
        tight_lo, tight_hi = max([lo, *tight_lows]), min([hi, *tight_highs])
        if tight_lo <= tight_hi and (tight_lo, tight_hi) != (lo, hi):
            found = Clock(tight_lo, tight_hi - tight_lo, len(pairs), shift, "pjrt")
        elif lo <= hi:
            found = Clock(lo, hi - lo, len(pairs), shift, "spans")
        else:
            continue
        if best is None or abs(found.offset_ns) < abs(best.offset_ns):
            best = found
    return best


# ------------------------------------------------------------------ attribution


def segments(spans):
    """Cut one thread's properly nested spans into disjoint pieces
    (start, end, stack), ``stack`` the names open there, outermost first."""
    out, stack = [], []   # stack: (end, name)
    cursor = None

    def emit(until):
        nonlocal cursor
        if stack and cursor is not None and until > cursor:
            out.append((cursor, until, tuple(n for _, n in stack)))
        cursor = until if cursor is None else max(cursor, until)

    for start, end, name in spans:
        while stack and stack[-1][0] <= start:
            emit(stack[-1][0])
            stack.pop()
        emit(start)
        stack.append((end, name))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    return out


def attribute(gaps, segs, offset_ns: float):
    """ns of the gaps (device clock; ``offset_ns`` puts them on the
    host's) by the stack open during them; what no span covers goes to
    the empty stack."""
    total: dict[tuple, float] = {}
    i = 0
    for g0, g1 in sorted(gaps):
        g0, g1 = g0 + offset_ns, g1 + offset_ns
        covered = 0.0
        while i < len(segs) and segs[i][1] <= g0:
            i += 1
        j = i
        while j < len(segs) and segs[j][0] < g1:
            s0, s1, stack = segs[j]
            part = min(s1, g1) - max(s0, g0)
            if part > 0:
                total[stack] = total.get(stack, 0.0) + part
                covered += part
            j += 1
        if g1 - g0 > covered:
            total[()] = total.get((), 0.0) + (g1 - g0 - covered)
    return total


def reduce_planes(planes) -> HostIdle | None:
    """The idle time inside the device's own span, by span and by
    layer, averaged over the device planes that ran anything (as
    ``TraceSummary.busy_s`` is). The window's idle lead and tail are
    the reader's to add: they depend on the host's window."""
    planes = list(planes)   # ProfileData hands its planes out once
    spans, pjrt = host_events(planes)
    timelines = device_timelines(planes)
    if not spans or not timelines:
        return None
    segs = segments(spans)
    idle_ns, layer_ns, clocks, span_ns = {}, {}, [], 0.0
    for executions, gaps, plane_span_ns in timelines:
        clock = clock_offset(executions, spans, pjrt)
        if clock is None:
            return None
        clocks.append(clock)
        span_ns += plane_span_ns / len(timelines)
        for stack, ns in attribute(gaps, segs, clock.offset_ns).items():
            ns /= len(timelines)
            name = stack[-1] if stack else UNATTRIBUTED
            idle_ns[name] = idle_ns.get(name, 0.0) + ns
            layer = layer_of(stack)
            layer_ns[layer] = layer_ns.get(layer, 0.0) + ns
    return HostIdle(clock=min(clocks, key=lambda c: c.slack_ns), idle_ns=idle_ns,
                    layer_ns=layer_ns, span_ns=span_ns)


# ------------------------------------------------------------------ for a run


@functools.lru_cache(maxsize=2)
def _load(path: str, mtime_ns: int) -> HostIdle | None:
    from jax.profiler import ProfileData

    found = reduce_planes(ProfileData.from_file(path).planes)
    if found is None:
        print("# clock " + json.dumps(None), flush=True)
        return None
    c = found.clock
    print("# clock " + json.dumps({
        "offset_us": c.offset_ns / 1e3, "slack_us": c.slack_ns / 1e3, "pairs": c.pairs,
        "shift": c.shift, "bounds": c.bounds}), flush=True)
    print("# host_idle " + json.dumps(
        {k: v / 1e9 for k, v in sorted(found.idle_ns.items(), key=lambda kv: -kv[1])}),
        flush=True)
    return found


def for_run(run) -> HostIdle | None:
    """The reduction of the trace the runner wrote for this run's cell,
    parsed once however many readers ask."""
    if run.trace is None:
        return None
    trace_dir = os.path.join(spec.ROOT, ".bench_out", "trace", run.cell.name)
    try:
        path = trace_reduce.find_xplane(trace_dir)
    except FileNotFoundError:
        return None
    return _load(path, os.stat(path).st_mtime_ns)


def idle_share_pct(run, layer: str) -> float | None:
    """Device idle time while the serve thread's innermost span was of
    ``layer``, as % of the traced window (``TraceSummary.window_s``, the
    base of ``device_idle``, so the layers' shares sum to it). The
    window's idle lead and tail, before the first device op and after
    the last, are ``unattributed``."""
    found = for_run(run)
    if found is None or run.trace.window_s <= 0:
        return None
    ns = found.layer_ns.get(layer, 0.0)
    if layer == UNATTRIBUTED:
        ns += max(run.trace.window_s * 1e9 - found.span_ns, 0.0)
    return 100.0 * ns / (run.trace.window_s * 1e9)
