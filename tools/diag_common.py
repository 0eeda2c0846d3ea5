"""Shared plumbing for the one-shot on-chip measurement tools
(tools/flash_tune.py, tools/moe_diag.py, tools/profile_trace.py).

Each tool prints its record as JSON lines with an always-emit
guarantee: a watchdog emits a truncated snapshot at budget-15s (so an
outer time limit can never discard completed measurements), and main
emits the full record on normal exit. Consumers
(tools/last_json_line.py) take the LAST parseable line, so a main that
finishes inside the headroom wins over the snapshot. Device and
compile-cache set-up is ``bench._require_tpu`` — one rule for bench.py
and these tools (``core/device.py``).
"""

import json
import sys
import threading


def parse_budget(argv, default: float = 600.0) -> float:
    for a in argv:
        if a.startswith("--budget="):
            return float(a.split("=", 1)[1])
    return default


def make_emit(out: dict):
    """Emit callable over a shared record dict, safe to call from the
    watchdog timer thread while main still assigns keys (snapshots a
    shallow copy — the C encoder raises on a dict that changes size
    mid-iteration — and never lets a racing snapshot kill the run)."""

    def _emit(truncated: bool = False) -> None:
        try:
            rec = dict(out)
            if truncated:
                rec["truncated"] = True
            sys.stdout.write(json.dumps(rec) + "\n")
            sys.stdout.flush()
        except Exception:
            pass

    return _emit


def start_watchdog(budget: float, emit) -> threading.Timer:
    """Daemon timer that emits a truncated snapshot shortly before the
    caller's outer deadline; cancel() it on the normal-exit path."""
    t = threading.Timer(max(budget - 15.0, 5.0), emit, (True,))
    t.daemon = True
    t.start()
    return t
