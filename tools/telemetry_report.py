#!/usr/bin/env python
"""Turn a run dir's telemetry into a human summary + machine JSON.

    python tools/telemetry_report.py /path/to/workdir
    python tools/telemetry_report.py /path/to/workdir --json report.json

Reads ``<workdir>/telemetry/metrics.jsonl`` (the schema-versioned JSONL
the trainer's Telemetry writes every log window — see
docs/observability.md) and, when present, ``trace.json`` (the Chrome
span timeline), and prints:

* run shape: steps covered, windows, wall span, how the run ended
  (the ``kind="final"`` line's exit_reason — "complete" vs. "preempt"
  vs. "error:...")
* throughput: examples/sec (mean of windows + last window), tokens/sec
  for token workloads
* step time: p50 / p95 (+ mean) from the step_time histogram
* MFU estimate: 6ND model FLOPs over the device peak (flagged when the
  peak was a fallback guess, e.g. CPU smoke runs)
* goodput + the resilience/IO counters behind it (bad steps, rollbacks,
  steps lost, preemptions, batch skips, IO retries)
* per-phase host time from the trace (where the loop's wall time went)
* device-side facts when the run recorded them (schema v2, ISSUE 3):
  peak live-memory watermark + the params/opt/other init breakdown,
  compile count + post-warmup recompile warnings, and the in-loop
  profiler window cross-link. v1 runs simply omit these lines — absent
  fields degrade gracefully.
* fleet facts (schema v3, ISSUE 4): when the run dir holds per-host
  telemetry shards (``telemetry.host{k}.jsonl``), they are merged into
  a per-host table and the slowest host is flagged; the last
  ``kind="fleet"`` line's skew/straggler verdict is rendered either
  way. Single-shard dirs report exactly as before.
* SLO alert facts (schema v14, ISSUE 19): when the run dir holds an
  ``alerts.jsonl`` sink (serve_fleet ``--alerts-out``), the firing /
  resolved episode count, per-episode durations, the worst remaining
  error budget, and the exemplar trace ids (ready for ``trace_report
  --trace-id``) are summarized. Dirs without a sink omit the section.

``--json`` additionally writes one machine-readable record with the
same numbers — shaped for dropping into future BENCH_*.json entries.

Lines that fail schema validation are skipped LOUDLY (counted +
reported): a half-written crash tail must not silently skew the
aggregates. Exit code 1 if no valid telemetry is found.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tensorflow_examples_tpu.telemetry import accounting, schema  # noqa: E402


def load_lines(path: str) -> tuple[list[dict], int]:
    """(valid schema lines, invalid-line count) from a metrics JSONL."""
    valid, bad = [], 0
    with open(path) as f:
        for raw in f:
            raw = raw.strip()
            if not raw:
                continue
            try:
                obj = json.loads(raw)
            except json.JSONDecodeError:
                bad += 1
                continue
            if schema.validate_line(obj):
                bad += 1
                continue
            valid.append(obj)
    return valid, bad


def _mean(vals: list[float]) -> float | None:
    vals = [v for v in vals if v is not None]
    return sum(vals) / len(vals) if vals else None


def _is_session_boundary(prev: dict, line: dict) -> bool:
    """Did a new fit-session start between these adjacent lines?

    Primary signal: ``session_start_unix`` changing — every line carries
    its session's id, exact even across SIGKILLs. Fallbacks for lines
    predating the field: a ``kind="final"`` line ends its session, and
    any per-key counter decrease means a fresh process restarted at 0.
    """
    a = prev.get("session_start_unix")
    b = line.get("session_start_unix")
    if a is not None and b is not None:
        return a != b
    if prev["kind"] == "final":
        return True
    return any(
        line["counters"].get(k, 0) < v for k, v in prev["counters"].items()
    )


def _split_sessions(lines: list[dict]) -> list[list[dict]]:
    """Split the JSONL into fit sessions (counters restart per session;
    a preempted-then-resumed run appends several to one file)."""
    sessions: list[list[dict]] = []
    cur: list[dict] = []
    for line in lines:
        if cur and _is_session_boundary(cur[-1], line):
            sessions.append(cur)
            cur = []
        cur.append(line)
    if cur:
        sessions.append(cur)
    return sessions


def _aggregate_counters(sessions: list[list[dict]]) -> dict[str, int]:
    """Whole-run counters: sum each session's last (= highest) values —
    the per-session counters are cumulative, so the last line carries
    the session total. Fleet lines are skipped: they ride immediately
    after every reduced window carrying HOST-LOCAL counters (their
    per-host evidence lives in the fleet object), so a torn tail ending
    on one would silently swap the fleet-reduced totals for one host's."""
    totals: dict[str, int] = {}
    for sess in sessions:
        last = next(
            (l for l in reversed(sess) if l["kind"] != "fleet"), sess[-1]
        )
        for k, v in last["counters"].items():
            totals[k] = totals.get(k, 0) + v
    return totals


def summarize(lines: list[dict], trace: dict | None) -> dict:
    """Aggregate validated lines (+ optional trace) into one record."""
    windows = [l for l in lines if l["kind"] == "window"]
    evals = [l for l in lines if l["kind"] == "eval"]
    finals = [l for l in lines if l["kind"] == "final"]
    memories = [l for l in lines if l["kind"] == "memory"]
    compile_warnings = [l for l in lines if l["kind"] == "compile_warning"]
    last = lines[-1]
    sessions = _split_sessions(lines)
    counters = _aggregate_counters(sessions)
    gauges = last["gauges"]
    # The freshest derived block that actually has throughput: final
    # lines often carry an empty partial window (derived nulls).
    derived = {}
    for l in reversed(lines):
        if l["derived"].get("examples_per_sec") is not None:
            derived = l["derived"]
            break
    else:
        derived = last["derived"]

    record = {
        "schema_version": schema.SCHEMA_VERSION,
        "windows": len(windows),
        "eval_windows": len(evals),
        "sessions": len(sessions),
        "first_step": lines[0]["step"],
        "last_step": last["step"],
        "wall_span_secs": last["time_unix"] - lines[0]["time_unix"],
        "exit_reason": finals[-1]["exit_reason"] if finals else None,
        "examples_per_sec_mean": _mean(
            [w["derived"].get("examples_per_sec") for w in windows]
        ),
        "examples_per_sec_last": derived.get("examples_per_sec"),
        "tokens_per_sec_last": derived.get("tokens_per_sec"),
        "step_time_p50": last["derived"].get("step_time_p50"),
        "step_time_p95": last["derived"].get("step_time_p95"),
        "mfu": derived.get("mfu"),
        # Whole-run goodput from the cross-session counter totals (a
        # single line's goodput only covers its own process session).
        "goodput": accounting.goodput(counters),
        "counters": counters,
        "flops_per_step": gauges.get("telemetry/flops_per_step"),
        "peak_flops_total": gauges.get("telemetry/peak_flops_total"),
    }
    # ----- schema-v2 device-side fields (None/absent on v1 runs) -----
    last_memory = next(
        (l["memory"] for l in reversed(lines)
         if isinstance(l.get("memory"), dict)),
        None,
    )
    record["memory"] = last_memory
    record["peak_live_bytes"] = (last_memory or {}).get("peak_live_bytes")
    record["memory_breakdown"] = (
        memories[-1]["memory"] if memories else None
    )
    record["compiles"] = counters.get("compile/count")
    record["recompiles"] = counters.get("compile/recompiles")
    record["compile_warnings"] = [
        {"step": l["step"], **l.get("compile", {})}
        for l in compile_warnings
    ]
    record["profile"] = next(
        (l["profile"] for l in reversed(finals) if "profile" in l), None
    )
    # ----- schema-v5 sharding provenance (None/absent on older runs) --
    sharding = next(
        (l["sharding"] for l in reversed(finals) if "sharding" in l), None
    )
    record["sharding"] = sharding
    record["mesh_shape"] = (sharding or {}).get("mesh_shape")
    record["param_sharding_digest"] = (sharding or {}).get(
        "param_sharding_digest"
    )
    # A model-parallel run's step time under its own gate key: the
    # bench_gate `sharded_step_time` record kind (a sharded layout's
    # step time is not comparable to the 1-device floor, so it gets its
    # own stamped bound).
    mesh_shape = record["mesh_shape"] or {}
    nontrivial = any(
        a != "data" and int(s) > 1 for a, s in mesh_shape.items()
    )
    record["sharded_step_time"] = (
        record["step_time_p50"] if nontrivial else None
    )
    # ----- schema-v3 fleet fields (None/absent on v1/v2 runs) -----
    fleet_lines = [l for l in lines if l["kind"] == "fleet"]
    record["fleet"] = fleet_lines[-1]["fleet"] if fleet_lines else None
    record["fleet_straggler_windows"] = sum(
        1 for l in fleet_lines if l["fleet"].get("straggler")
    )
    if trace is not None:
        phases: dict[str, dict] = {}
        for ev in trace.get("traceEvents", []):
            p = phases.setdefault(ev["name"], {"count": 0, "total_ms": 0.0})
            p["count"] += 1
            p["total_ms"] += ev.get("dur", 0.0) / 1e3
        record["trace_phases"] = {
            name: {"count": p["count"], "total_ms": round(p["total_ms"], 3)}
            for name, p in sorted(
                phases.items(), key=lambda kv: -kv[1]["total_ms"]
            )
        }
        if trace.get("droppedEventCount"):
            record["trace_dropped_events"] = trace["droppedEventCount"]
    return record


def resolve_metrics_path(arg: str) -> str | None:
    """A run dir / telemetry dir / metrics.jsonl argument -> the primary
    metrics file (host 0's run record), or — when only host shards
    exist — the lowest-indexed shard."""
    cand = [
        arg,
        os.path.join(arg, "metrics.jsonl"),
        os.path.join(arg, "telemetry", "metrics.jsonl"),
    ]
    path = next((p for p in cand if os.path.isfile(p)), None)
    if path is not None:
        return path
    shards = _shard_paths(arg) or _shard_paths(os.path.join(arg, "telemetry"))
    return shards[0][1] if shards else None


def _shard_paths(d: str) -> list[tuple[int, str]]:
    """``(host, path)`` for each telemetry.host{k}.jsonl under ``d``,
    ordered by host index."""
    if not os.path.isdir(d):
        return []
    hits = []
    for name in os.listdir(d):
        m = re.fullmatch(r"telemetry\.host(\d+)\.jsonl", name)
        if m:
            hits.append((int(m.group(1)), os.path.join(d, name)))
    return sorted(hits)


def host_shard_records(telemetry_dir: str) -> list[dict]:
    """Per-host mini-records from the dir's host shards (ISSUE 4
    satellite): one summary row per ``telemetry.host{k}.jsonl``. Empty
    for single-shard (single-host) run dirs — their report is exactly
    the pre-fleet one.

    Process 0 writes no shard (metrics.jsonl IS its stream — see
    sinks.host_metrics_path), so when shards exist without a host-0
    one, the main record file is merged in as host 0."""
    shards = _shard_paths(telemetry_dir)
    main = os.path.join(telemetry_dir, "metrics.jsonl")
    if shards and not any(h == 0 for h, _ in shards) and os.path.isfile(main):
        shards.insert(0, (0, main))
    out = []
    for host, path in shards:
        lines, bad = load_lines(path)
        if not lines:
            continue
        rec = summarize(lines, None)
        out.append(
            {
                "host": host,
                "windows": rec["windows"],
                "last_step": rec["last_step"],
                "exit_reason": rec["exit_reason"],
                "step_time_p50": rec["step_time_p50"],
                "step_time_p95": rec["step_time_p95"],
                "examples_per_sec_last": rec["examples_per_sec_last"],
                "steps_lost": rec["counters"].get(
                    "resilience/steps_lost", 0
                ),
                "peak_live_bytes": rec["peak_live_bytes"],
                "invalid_lines": bad,
            }
        )
    return out


def alert_summary(run_dir: str) -> dict | None:
    """ISSUE 19 satellite: summarize the run dir's schema-v14
    ``kind="alert"`` firing/resolve JSONL (``alerts.jsonl``, the
    AlertEngine sink serve_fleet's ``--alerts-out`` lands) — how many
    alerts fired, how long each episode lasted (firing -> resolved,
    paired by alert name), how much error budget the worst rule had
    left, and the exemplar trace ids a responder would feed to
    ``trace_report --trace-id``. None when the run has no alert sink."""
    cand = [
        os.path.join(run_dir, "alerts.jsonl"),
        os.path.join(run_dir, "telemetry", "alerts.jsonl"),
    ]
    path = next((p for p in cand if os.path.isfile(p)), None)
    if path is None:
        return None
    from tensorflow_examples_tpu.telemetry import slo

    alerts = slo.read_alerts(path)
    if not alerts:
        return None
    firings = [a for a in alerts if a.get("state") == "firing"]
    open_since: dict[str, float] = {}
    episodes = []
    for a in alerts:
        name = a.get("name")
        t = a.get("_time_unix")
        if a.get("state") == "firing":
            if name not in open_since and t is not None:
                open_since[name] = t
        elif a.get("state") == "resolved" and name in open_since:
            start = open_since.pop(name)
            episodes.append(
                {
                    "name": name,
                    "slo": a.get("slo"),
                    "duration_s": (
                        round(t - start, 3) if t is not None else None
                    ),
                }
            )
    budgets = [
        a["budget_remaining"]
        for a in alerts
        if isinstance(a.get("budget_remaining"), (int, float))
        and not isinstance(a.get("budget_remaining"), bool)
    ]
    return {
        "path": path,
        "firings": len(firings),
        "resolved": sum(1 for a in alerts if a.get("state") == "resolved"),
        "still_firing": sorted(open_since),
        "episodes": episodes,
        "min_budget_remaining": min(budgets) if budgets else None,
        "exemplar_trace_ids": [
            a["trace_id"]
            for a in firings
            if isinstance(a.get("trace_id"), str)
        ][:5],
    }


def build_record(arg: str) -> tuple[dict | None, int, str]:
    """(record, skipped-line count, error) for a run-dir argument — the
    shared entry point for main() and tools/run_diff.py. ``record`` is
    None exactly when ``error`` is non-empty."""
    path = resolve_metrics_path(arg)
    if path is None:
        return None, 0, (
            f"no telemetry found under {arg!r} (looked for "
            "telemetry/metrics.jsonl and telemetry.host*.jsonl — was the "
            "run started with --workdir and the jsonl sink enabled?)"
        )
    lines, skipped = load_lines(path)
    if not lines:
        return None, skipped, (
            f"{path}: no valid schema-v{schema.SCHEMA_VERSION} lines "
            f"({skipped} invalid)"
        )
    trace_file = os.path.join(os.path.dirname(path), "trace.json")
    trace = None
    if os.path.isfile(trace_file):
        try:
            with open(trace_file) as f:
                trace = json.load(f)
        except json.JSONDecodeError:
            print(f"WARNING: unreadable trace {trace_file}", file=sys.stderr)
    record = summarize(lines, trace)
    # ISSUE 19: a run dir that landed an alert sink gets the SLO
    # section; dirs without one simply omit it.
    record["alerts"] = (
        alert_summary(arg if os.path.isdir(arg) else os.path.dirname(arg))
        or alert_summary(os.path.dirname(path))
    )
    hosts = host_shard_records(os.path.dirname(path))
    record["hosts"] = hosts or None
    p95s = [
        (h["step_time_p95"], h["host"])
        for h in hosts
        if h["step_time_p95"] is not None
    ]
    record["slowest_host"] = max(p95s)[1] if p95s else None
    return record, skipped, ""


def _fmt(v, unit="", nd=2) -> str:
    if v is None:
        return "n/a"
    if isinstance(v, float):
        return f"{v:,.{nd}f}{unit}"
    return f"{v}{unit}"


def render(record: dict, skipped: int) -> str:
    out = []
    out.append("== telemetry report ==")
    out.append(
        f"run: steps {record['first_step']}..{record['last_step']} over "
        f"{record['windows']} window(s) + {record['eval_windows']} eval "
        f"in {record['sessions']} session(s), "
        f"{_fmt(record['wall_span_secs'], 's')} wall; "
        f"ended: {record['exit_reason'] or 'UNKNOWN (no final line)'}"
    )
    out.append(
        f"throughput: {_fmt(record['examples_per_sec_mean'])} examples/sec "
        f"mean ({_fmt(record['examples_per_sec_last'])} last window)"
        + (
            f", {_fmt(record['tokens_per_sec_last'])} tokens/sec"
            if record["tokens_per_sec_last"] is not None
            else ""
        )
    )
    p50, p95 = record["step_time_p50"], record["step_time_p95"]
    out.append(
        "step time: p50 "
        + _fmt(p50 * 1e3 if p50 is not None else None, "ms")
        + " / p95 "
        + _fmt(p95 * 1e3 if p95 is not None else None, "ms")
    )
    mfu = record["mfu"]
    out.append(
        "mfu estimate: "
        + (_fmt(mfu * 100, "%", nd=4) if mfu is not None else "n/a")
        + " (6ND analytic)"
        + (
            " (no peak FLOPs for this device kind; set "
            "--telemetry_peak_tflops)"
            if mfu is None
            else ""
        )
    )
    gp = record["goodput"]
    c = record["counters"]
    out.append(
        "goodput: "
        + (_fmt(gp * 100, "%", nd=2) if gp is not None else "n/a")
        + f" of {c.get('train/steps_total', 0)} stepped "
        + f"(bad={c.get('resilience/bad_steps', 0)} "
        + f"lost={c.get('resilience/steps_lost', 0)} "
        + f"rollbacks={c.get('resilience/rollbacks', 0)} "
        + f"preemptions={c.get('resilience/preemptions', 0)})"
    )
    out.append(
        f"input: {c.get('data/batches_fetched', 0)} batches fetched, "
        f"{c.get('data/batches_skipped', 0)} skipped poisoned, "
        f"{c.get('io/retries', 0)} io retries; checkpoints: "
        f"{c.get('checkpoint/saves', 0)} saved / "
        f"{c.get('checkpoint/restores', 0)} restored"
    )
    # ----- schema-v2 device-side sections (omitted for v1 runs) -----
    mem = record.get("memory")
    if mem and mem.get("peak_live_bytes") is not None:
        line = f"memory: peak live {mem['peak_live_bytes'] / 2**20:,.1f}MiB"
        bd = record.get("memory_breakdown")
        if bd:
            line += (
                f" (at init: params {bd.get('params_bytes', 0) / 2**20:,.1f}"
                f" / opt {bd.get('opt_bytes', 0) / 2**20:,.1f}"
                f" / other {bd.get('other_bytes', 0) / 2**20:,.1f} MiB)"
            )
        if mem.get("device_peak_bytes_in_use") is not None:
            line += (
                f"; device allocator peak "
                f"{mem['device_peak_bytes_in_use'] / 2**20:,.1f}MiB"
            )
        out.append(line)
    if record.get("compiles") is not None:
        warns = record.get("compile_warnings") or []
        line = (
            f"compiles: {record['compiles']} "
            f"({record.get('recompiles') or 0} post-warmup recompile(s), "
            f"{len(warns)} warning line(s))"
        )
        out.append(line)
        for w in warns[:5]:
            out.append(
                f"  RECOMPILE step {w.get('step')} {w.get('fn')}: "
                f"{w.get('delta')}"
            )
    prof = record.get("profile")
    if prof:
        out.append(
            f"profiler window: {prof.get('num_steps')} step(s) from "
            f"run-relative step {prof.get('start_step')} in "
            f"{_fmt(prof.get('wall_secs'), 's')} -> {prof.get('dir')}"
        )
    # ----- schema-v5 sharding provenance (omitted for older runs) -----
    sharding = record.get("sharding")
    if sharding:
        mesh_shape = sharding.get("mesh_shape") or {}
        shape = "x".join(
            f"{a}={s}" for a, s in mesh_shape.items() if int(s) > 1
        ) or "1 device"
        line = (
            f"sharding: mesh {shape}, digest "
            f"{sharding.get('param_sharding_digest')}"
        )
        if sharding.get("zero1"):
            line += ", ZeRO-1 optimizer sharding"
        if record.get("sharded_step_time") is not None:
            line += (
                "; sharded_step_time "
                f"{_fmt(record['sharded_step_time'] * 1e3, 'ms')}"
            )
        out.append(line)
    # ----- schema-v3 fleet sections (omitted for v1/v2 runs) -----
    hosts = record.get("hosts")
    if hosts:
        slowest = record.get("slowest_host")
        out.append(
            f"fleet: {len(hosts)} host shard(s)"
            + (f"; SLOWEST host {slowest}" if slowest is not None else "")
        )
        for h in hosts:
            p50, p95 = h["step_time_p50"], h["step_time_p95"]
            out.append(
                f"  host {h['host']}: step p50 "
                + _fmt(p50 * 1e3 if p50 is not None else None, "ms")
                + " / p95 "
                + _fmt(p95 * 1e3 if p95 is not None else None, "ms")
                + f", {_fmt(h['examples_per_sec_last'])} examples/sec, "
                + f"lost={h['steps_lost']}, "
                + f"ended: {h['exit_reason'] or 'UNKNOWN'}"
                + (" <- SLOWEST" if h["host"] == slowest else "")
            )
    fl = record.get("fleet")
    if fl:
        line = (
            f"fleet skew (last fleet line): {_fmt(fl.get('skew'), 'x')}"
        )
        if fl.get("slowest_host") is not None:
            line += f", slowest host {fl['slowest_host']}"
        if fl.get("side"):
            line += f", {fl['side']}-side"
        if record.get("fleet_straggler_windows"):
            line += (
                f"; STRAGGLER flagged in "
                f"{record['fleet_straggler_windows']} window(s)"
            )
        if fl.get("emergency"):
            line += " (emergency snapshot)"
        out.append(line)
    # ----- schema-v14 SLO alert section (omitted without a sink) -----
    al = record.get("alerts")
    if al:
        line = (
            f"slo alerts: {al['firings']} firing / {al['resolved']} "
            f"resolved event(s)"
        )
        if al.get("min_budget_remaining") is not None:
            line += (
                "; worst error budget remaining "
                + _fmt(al["min_budget_remaining"] * 100, "%", nd=1)
            )
        if al.get("still_firing"):
            line += f"; STILL FIRING: {', '.join(al['still_firing'])}"
        out.append(line)
        for ep in al.get("episodes", [])[:5]:
            out.append(
                f"  {ep['name']} ({ep.get('slo')}): fired for "
                + _fmt(ep.get("duration_s"), "s")
            )
        for tid in al.get("exemplar_trace_ids", []):
            out.append(
                f"  exemplar: trace_report --trace-id {tid}"
            )
    if "trace_phases" in record:
        out.append("host time by span (from trace.json):")
        for name, p in record["trace_phases"].items():
            out.append(
                f"  {name:<20} {p['total_ms']:>12,.1f}ms  x{p['count']}"
            )
    if skipped:
        out.append(
            f"WARNING: skipped {skipped} line(s) that failed schema "
            "validation (torn tail or version drift)"
        )
    return "\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument(
        "workdir",
        help="run dir (containing telemetry/metrics.jsonl), the telemetry "
        "dir itself, or a metrics.jsonl path",
    )
    ap.add_argument(
        "--json",
        metavar="PATH",
        help="also write the machine-readable record here ('-' = stdout)",
    )
    args = ap.parse_args(argv)

    record, skipped, err = build_record(args.workdir)
    if record is None:
        print(err, file=sys.stderr)
        return 1
    print(render(record, skipped))
    if args.json:
        payload = json.dumps(record, indent=2) + "\n"
        if args.json == "-":
            sys.stdout.write(payload)
        else:
            with open(args.json, "w") as f:
                f.write(payload)
    return 0


if __name__ == "__main__":
    sys.exit(main())
