#!/usr/bin/env python
"""Merge per-bench harvest JSONs into one sweep-shaped record.

A sweep can be run one bench per process
(``python bench.py --bench=<name>``), so that a failure loses only the
bench in flight. Each process emits a self-contained record (pre/post
fingerprints, probe_tflops_at_bench, rel_mfu). This tool folds a directory of those into ONE record shaped
like a ``--bench=all`` sweep so ``tools/stamp_floors.py`` can print the
floor stamps unchanged.

Merge semantics:
- headline = resnet50 record if present, else the first by ALL_ORDER;
- ``extras`` = every other completed record;
- every record keeps its own pre/post fingerprints (stamp_floors
  stamps per record); min/max over ALL pre/post probes — the rig
  drift across the sweep — is recorded as ``fingerprint_spread``;
- records whose backend != the majority backend are dropped loudly
  (a cpu record must not stamp TPU floors);
- a ``harvested`` list names the per-bench files folded in.

Usage: python tools/harvest_merge.py RESULTS_DIR > merged.json
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
try:
    # Single source of truth for the bench list — hand-duplicating it
    # here would silently miss benches added to bench.py later.
    from bench import ALL_ORDER as ORDER  # noqa: E402
except Exception:  # bench.py imports jax; fall back if that breaks
    ORDER = [
        "resnet50", "resnet50_input", "gpt2", "gpt2_long", "gpt2_long16k",
        "gpt2_decode", "gpt2_decode_long", "bert", "cifar10", "mnist",
        "collectives", "moe", "decode_grid",
    ]


def main() -> int:
    if len(sys.argv) != 2:
        print(__doc__)
        return 2
    d = sys.argv[1]
    recs = {}
    selftest = None
    for fn in sorted(os.listdir(d)):
        if not fn.endswith(".json"):
            continue
        path = os.path.join(d, fn)
        try:
            with open(path) as f:
                r = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            print(f"merge: skipping {fn}: {e}", file=sys.stderr)
            continue
        if r.get("metric") == "selftest" or "selftest" in r:
            st = r.get("selftest")
            if st is not None:
                selftest = st
            if r.get("metric") == "selftest":
                continue
        name = r.get("bench") or fn[:-5]
        if "error" in r:
            print(f"merge: {name} errored: {r['error']}", file=sys.stderr)
        recs[name] = r

    if not recs:
        print("merge: no bench records found", file=sys.stderr)
        return 1

    # Prefer tpu whenever ANY tpu record exists: a cpu majority must
    # never cause the chip-measured records to be the ones dropped.
    backends = {r.get("backend", "?") for r in recs.values()}
    backend = "tpu" if "tpu" in backends else sorted(backends)[0]
    dropped = [n for n, r in recs.items() if r.get("backend", "?") != backend]
    for n in dropped:
        print(f"merge: DROPPING {n} (backend {recs[n].get('backend')!r} != "
              f"majority {backend!r})", file=sys.stderr)
        del recs[n]

    pres = [r["fingerprint_tflops_pre"] for r in recs.values()
            if isinstance(r.get("fingerprint_tflops_pre"), (int, float))]
    posts = [r["fingerprint_tflops_post"] for r in recs.values()
             if isinstance(r.get("fingerprint_tflops_post"), (int, float))]
    fps = pres + posts

    # Per-bench records may themselves carry sweep-level keys: a bench
    # subprocess's _assemble attaches any previously-banked harvest as
    # "tpu_harvest" (and lists its own skipped siblings as
    # "truncated"). Left in place these would nest the merged artifact
    # inside itself, one level per finalize cycle.
    for r in recs.values():
        for k in ("tpu_harvest", "extras", "truncated", "harvested"):
            r.pop(k, None)

    ordered = sorted(recs, key=lambda n: ORDER.index(n) if n in ORDER else 99)
    head_name = "resnet50" if "resnet50" in recs else ordered[0]
    out = dict(recs[head_name])
    out["extras"] = [recs[n] for n in ordered if n != head_name]
    out["backend"] = backend
    if fps:
        # The head record keeps ITS OWN pre/post fingerprints (it is a
        # self-contained bench record; stamp_floors stamps each metric
        # with its record's own probe). The window-wide drift — which
        # can include a wedged probe observed at ~78 vs the healthy
        # ~40-100k range — lives only in fingerprint_spread.
        out["fingerprint_spread"] = [min(fps), max(fps)]
    out["harvested"] = ordered
    missing = [n for n in ORDER if n not in recs]
    if missing:
        out["truncated"] = missing
    if selftest is not None:
        out["selftest"] = selftest
    json.dump(out, sys.stdout)
    print()
    return 0


if __name__ == "__main__":
    sys.exit(main())
