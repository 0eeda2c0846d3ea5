"""From the profiler's ``.xplane.pb`` to numbers, with nothing but JAX.

``jax.profiler.ProfileData`` reads the file: planes, their lines, and
events with a start and a duration in nanoseconds. On a TPU each chip
is a plane ``/device:TPU:<n>`` whose lines include ``XLA Modules`` (one
event per execution of a compiled program, named after the jitted
function) and ``XLA Ops`` (one event per operation inside it). Busy
time is the union of the op intervals; idle is the rest of the window.

The reduction is kept here, and checked against a small recorded trace
in ``benchmark/tests``, so that every PR computes the same number the
same way and no PR that claims a gain can change it.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class PlaneSummary:
    name: str
    span_s: float = 0.0            # first op start -> last op end
    busy_s: float = 0.0            # union of op intervals
    ops: dict = dataclasses.field(default_factory=dict)      # name -> [count, seconds]
    modules: dict = dataclasses.field(default_factory=dict)  # name -> [durations, s]
    gaps: list = dataclasses.field(default_factory=list)     # (seconds, module that follows)


@dataclasses.dataclass
class TraceSummary:
    planes: list
    host_window_s: float | None = None   # start_trace returned -> stop_trace called
    lines_seen: dict = dataclasses.field(default_factory=dict)  # plane -> [line names]

    @property
    def window_s(self) -> float:
        span = max((p.span_s for p in self.planes), default=0.0)
        return max(span, self.host_window_s or 0.0)

    @property
    def busy_s(self) -> float:
        """Averaged over the device planes that ran anything."""
        used = [p.busy_s for p in self.planes if p.busy_s > 0]
        return sum(used) / len(used) if used else 0.0

    @property
    def idle_share(self) -> float | None:
        return 1.0 - self.busy_s / self.window_s if self.window_s > 0 else None

    def module_durations(self, pattern: str) -> list[float]:
        """Seconds of every execution of the programs whose name
        matches, over all device planes."""
        rx = re.compile(pattern)
        return [
            d for p in self.planes for name, ds in p.modules.items()
            if rx.search(name) for d in ds
        ]

    def op_seconds(self, pattern: str) -> tuple[int, float]:
        """(count, seconds) of the operations whose name matches."""
        rx = re.compile(pattern)
        n, s = 0, 0.0
        for p in self.planes:
            for name, (count, secs) in p.ops.items():
                if rx.search(name):
                    n, s = n + count, s + secs
        return n, s

    def top_ops(self, k: int = 10) -> list[list]:
        total: dict[str, float] = {}
        for p in self.planes:
            for name, (_, secs) in p.ops.items():
                fam = op_family(name)
                total[fam] = total.get(fam, 0.0) + secs
        return [[n, s] for n, s in sorted(total.items(), key=lambda kv: -kv[1])[:k]]

    def top_gaps(self, k: int = 10) -> list[list]:
        """Idle time on the device, summed by the program that ended
        the gap. (Naming a gap after what the HOST was doing needs host
        spans on the profiler's clock inside the program: not there
        yet.)"""
        total: dict[str, float] = {}
        for p in self.planes:
            for secs, follows in p.gaps:
                key = f"before {follows}"
                total[key] = total.get(key, 0.0) + secs
        return [[n, s] for n, s in sorted(total.items(), key=lambda kv: -kv[1])[:k]]


def _union_and_gaps(intervals: list[tuple[int, int]]):
    """Union length of [start, end) intervals in ns, and the gaps
    between merged runs as (gap_ns, start of the run that follows)."""
    busy, gaps = 0, []
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        if cur_e is None:
            cur_s, cur_e = s, e
        elif s <= cur_e:
            cur_e = max(cur_e, e)
        else:
            busy += cur_e - cur_s
            gaps.append((s - cur_e, s))
            cur_s, cur_e = s, e
    if cur_e is not None:
        busy += cur_e - cur_s
    return busy, gaps


_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")


def op_family(name: str) -> str:
    """The device's op events carry the whole HLO line
    (``%attn.60 = (bf16[192,1024,64]{...}, ...) custom-call(...)``).
    For the breakdown: the name without its numbers, and the opcode —
    ``attn custom-call``, ``fusion fusion``, ``fusion.remat_uncompressed copy``."""
    head, _, rest = name.partition(" = ")
    stem = re.sub(r"\.\d+", "", head.strip().lstrip("%"))
    m = _OPCODE.search(" " + rest)
    return f"{stem} {m.group(1)}" if m else stem


def short_name(name: str) -> str:
    """``jit__paged_decode_impl(1234567)`` -> ``jit__paged_decode_impl``."""
    return re.sub(r"\(\d+\)$", "", name.strip())


def reduce_planes(planes) -> TraceSummary:
    """``planes``: an iterable of objects with ``.name`` and ``.lines``,
    each line with ``.name`` and ``.events``, each event with ``.name``,
    ``.start_ns`` and ``.duration_ns`` (ProfileData's own shape; the
    tests hand in plain stand-ins)."""
    out, seen = [], {}
    for plane in planes:
        lines = list(plane.lines)
        seen[plane.name] = [ln.name for ln in lines]
        if not DEVICE_PLANE.match(plane.name):
            continue
        summary = PlaneSummary(plane.name)
        modules = []  # (start, end, name)
        for line in lines:
            if line.name == MODULES_LINE:
                for ev in line.events:
                    name = short_name(ev.name)
                    summary.modules.setdefault(name, []).append(ev.duration_ns / 1e9)
                    modules.append((ev.start_ns, ev.start_ns + ev.duration_ns, name))
        intervals = []
        for line in lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                rec = summary.ops.setdefault(ev.name, [0, 0.0])
                rec[0] += 1
                rec[1] += ev.duration_ns / 1e9
                intervals.append((ev.start_ns, ev.start_ns + ev.duration_ns))
        if intervals:
            busy_ns, gaps = _union_and_gaps(intervals)
            summary.busy_s = busy_ns / 1e9
            summary.span_s = (max(e for _, e in intervals) - min(s for s, _ in intervals)) / 1e9
            modules.sort()
            for gap_ns, next_start in gaps:
                follows = next(
                    (n for s, e, n in modules if s <= next_start < e), "(no program)"
                )
                summary.gaps.append((gap_ns / 1e9, follows))
        out.append(summary)
    return TraceSummary(planes=out, lines_seen=seen)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_trace(trace_dir: str, host_window_s: float | None = None) -> TraceSummary:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(trace_dir))
    summary = reduce_planes(data.planes)
    summary.host_window_s = host_window_s
    return summary
