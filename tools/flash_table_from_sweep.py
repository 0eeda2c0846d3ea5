#!/usr/bin/env python
"""Turn a banked flash_tune sweep into the committed block table.

Usage:
  python tools/flash_table_from_sweep.py docs/tpu_sweeps/round5_flash_tune.json

Writes docs/tpu_sweeps/flash_block_table.json:
  {"source": <sweep file>, "by_seq": {"1024": {"block_q": B, "block_kv": B},
   ...}}
using each shape's ``best_fwdbwd`` cell (training is the default
consumer; the fwd-only optimum is recorded alongside for reference).
ops/attention.py loads the table at kernel-build time. The kernel
source hash (tools/kernel_source_hash.py) covers the table file, so
swapping it changes the hash a recorded tests_tpu/ result was about
(the sweep itself also ran every cell compiled on-chip).
"""

import json
import os
import sys


def main() -> int:
    if len(sys.argv) != 2:
        print(__doc__)
        return 2
    with open(sys.argv[1]) as f:
        sweep = json.load(f)
    if not sweep.get("complete"):
        print("flash_table_from_sweep: sweep record is not complete — "
              "refusing to freeze a partial table")
        return 1
    by_seq = {}
    for shape in sweep.get("shapes", []):
        best = shape.get("best_fwdbwd")
        if not best:
            continue
        by_seq[str(shape["seq"])] = {
            "block_q": best["block_q"],
            "block_kv": best["block_kv"],
            "fwdbwd_ms": best.get("fwdbwd_ms"),
            "fwd_best": shape.get("best_fwd"),
            "shape": {k: shape[k] for k in ("batch", "heads", "head_dim")},
        }
    if not by_seq:
        print("flash_table_from_sweep: no best cells in sweep")
        return 1
    out_path = os.path.join(
        os.path.dirname(os.path.abspath(sys.argv[1])),
        "flash_block_table.json",
    )
    with open(out_path, "w") as f:
        json.dump(
            {"source": os.path.basename(sys.argv[1]), "by_seq": by_seq},
            f, indent=1,
        )
    print(f"wrote {out_path}: {json.dumps(by_seq)[:300]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
