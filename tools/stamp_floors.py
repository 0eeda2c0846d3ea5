#!/usr/bin/env python
"""Turn a bench.py sweep JSON into ready-to-paste floor stamps.

Usage: python tools/stamp_floors.py /path/to/sweep.json

Prints, for the record's backend:
- the ``FLOORS[backend]`` entries as Python source — (median,
  fingerprint) pairs per metric, each stamped with its OWN record's
  pre-fingerprint when present (harvest_merge.py output) and the
  sweep-level pre-fingerprint otherwise (plain ``--bench=all`` sweeps);
- the ``REL_MFU_FLOORS[backend]`` entries;
- a markdown table row per metric (median, window spread, rel_mfu) so
  the stamp and its evidence land together.

Floors move only with their fingerprints, from a measurement under
bench.py's protocol — this tool makes the mechanical part of that a
copy-paste. (The floors themselves await replacement: bench.py
docstring, ROADMAP queue 1.)
"""

import json
import sys

# Diagnostics whose healthy value is a fixed point and whose failure
# direction _result()'s unit heuristic would misread are never floored
# (bench.py documents each beside FLOORS). Shared with apply_floors.py.
UNFLOORED = {"decode_grid_step_time_ratio"}


def parse_sweep(d):
    """(backend, results, errored, sweep_fp) from a sweep/merge record.
    The single parse both halves of the floors workflow (print + apply)
    share, so they can never disagree on what counts as stampable."""
    backend = d.get("backend", "?")
    fp = d.get("fingerprint_tflops_pre", d.get("fingerprint_tflops", 0.0))
    everything = [d] + d.get("extras", [])
    results = [r for r in everything if "error" not in r and "metric" in r
               and r.get("metric") != "selftest"]
    errored = [
        r.get("bench", r.get("metric"))
        for r in everything
        if "error" in r and r.get("metric") != "selftest"
    ]
    return backend, results, errored, fp


def main() -> int:
    if len(sys.argv) != 2:
        print(__doc__)
        return 2
    with open(sys.argv[1]) as f:
        d = json.load(f)
    backend, results, errored, fp = parse_sweep(d)
    fp_post = d.get("fingerprint_tflops_post")

    spread = d.get("fingerprint_spread")
    print(f"# backend={backend}  fingerprint pre={fp} post={fp_post}"
          + (f"  spread={spread}" if spread else ""))
    if d.get("truncated"):
        print(f"# TRUNCATED (not stamped): {d['truncated']}")
    if errored:
        # An unstamped metric keeps its OLD (value, fingerprint) floor
        # while the compiled program may have changed — the exact
        # violation the floors policy forbids. Make it loud.
        print(
            f"# ERRORED (NOT STAMPED — their old floors are now stale, "
            f"fix or remove them): {errored}"
        )
    # Each per-bench record is self-contained and carries its OWN probe
    # fingerprint; stamping with the merged min-over-all-probes would
    # let a single bad probe (observed at 78 vs a ~40-100k range)
    # poison every floor's fingerprint at once.
    unfloored = UNFLOORED
    print(f'\n# --- FLOORS["{backend}"] entries ---')
    for r in results:
        if r["metric"] in unfloored:
            print(f'        # {r["metric"]}: {r["value"]} — diagnostic, '
                  f'deliberately unfloored')
            continue
        rfp = r.get("fingerprint_tflops_pre", r.get("fingerprint_tflops", fp))
        print(f'        "{r["metric"]}": ({r["value"]}, {rfp}),')
    print(f'\n# --- REL_MFU_FLOORS["{backend}"] entries ---')
    for r in results:
        if "rel_mfu" in r:
            print(f'        "{r["metric"]}": {r["rel_mfu"]},')
    print("\n# --- markdown table ---")
    print("| Metric | Median | Windows | rel_mfu | launch µs |")
    print("|---|---|---|---|---|")
    for r in results:
        win = " / ".join(str(w) for w in r.get("window_values", []))
        print(
            f"| {r['metric']} | {r['value']} {r.get('unit', '')} | {win} "
            f"| {r.get('rel_mfu', '—')} "
            f"| {r.get('probe_launch_us_at_bench', '—')} |"
        )
    st = d.get("selftest")
    if st is not None:
        print(f"\n# selftest: ok={st.get('ok')} — {st.get('summary')}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
