#!/usr/bin/env python
"""Extract the last parseable JSON line from a log file.

bench.py and the diag tools print their record as one JSON line on
stdout, but a caller that captures stdout+stderr merged must fish the
record out of surrounding log noise — and
always-emit children may print a truncated snapshot BEFORE the full
record, so the LAST parseable line is the authoritative one.

Usage: python tools/last_json_line.py LOG OUT [require_key=value ...]
Writes the record to OUT and exits 0 iff one was found and every
``key=value`` requirement matches (string compare); else exits 1.
"""

import json
import sys


def last_json_line(path: str):
    rec = None
    try:
        with open(path, errors="replace") as f:
            for line in f:
                line = line.strip()
                if line.startswith("{"):
                    try:
                        rec = json.loads(line)
                    except json.JSONDecodeError:
                        pass
    except OSError:
        return None
    return rec


def main() -> int:
    if len(sys.argv) < 3:
        print(__doc__)
        return 2
    rec = last_json_line(sys.argv[1])
    if rec is None:
        return 1
    for req in sys.argv[3:]:
        k, _, v = req.partition("=")
        if str(rec.get(k)) != v:
            return 1
    json.dump(rec, open(sys.argv[2], "w"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
