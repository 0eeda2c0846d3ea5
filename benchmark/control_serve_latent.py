"""The control of a ``serve_latent`` cell's ``correct``: the same served
engine, the same prompts, the runner's own comparison
(``runners/serve_latent.check_outputs``), with the reference computed in
the nearest precision below the configuration's (every matrix rounded
to int8 levels, one scale per output channel). It must come out NOT
correct, by ``logit_abs``: a limit that lets it pass measures nothing.

    python3 benchmark/control_serve_latent.py --workload <cell> --seed <n>

Prints the check's detail twice, as the cell runs it and as the
control, and last one JSON line ``{"correct": ..., "control_correct":
...}``; exits 0 where the first is true and the second false. Nothing
here is a measurement of the cell: no window runs.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import record, spec  # noqa: E402
from benchmark.runners import serve_latent  # noqa: E402


def control(cell, seed: int) -> dict:
    """Both verdicts of one served engine, with their details."""
    ctx = record.Context(cell=cell, seed=seed, seconds=0.0, trace=False, t_start=T_START,
                         trace_dir="", compiles=record.CompileLog.get())
    server = serve_latent.Server(ctx)
    try:
        out = {}
        for name, weights in (("correct", None), ("control_correct", "int8")):
            out[name], out[name + "_detail"] = serve_latent.check_outputs(
                ctx, server, reference_weights=weights)
        return out
    finally:
        server.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)

    from tensorflow_examples_tpu.core import device

    device.enable_compile_cache()
    device.require_device("tpu")
    out = control(cell, args.seed)
    for name in ("correct", "control_correct"):
        print("# " + json.dumps({name: out[name], **out[name + "_detail"]}, default=str), flush=True)
    print(json.dumps({k: out[k] for k in ("correct", "control_correct")}), flush=True)
    return 0 if out["correct"] and not out["control_correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
