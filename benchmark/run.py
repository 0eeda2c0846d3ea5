"""The benchmark's entry point: one cell, one process, one last line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's files by name (benchmark/spec.py), runs the cell's
runner (set-up, warm-up of every shape, then the measured window), has
each metric's reader read the run, and prints one JSON object as the
last line of standard output. It runs on the machine it is started on,
holds the chip in this one process, and fails at once without a TPU.
``--rates a,b,c`` sweeps an open-loop cell for its knee instead
(benchmark/README.md).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # before the heavy imports: set-up counts them

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import record, spec  # noqa: E402

OUT_DIR = os.path.join(ROOT, ".bench_out")  # traces; listed in .gitignore


def info(**kw) -> None:
    """An earlier line: for the reader of a log, ignored by the driver."""
    print("# " + json.dumps(kw, default=str), flush=True)


def execute(cell, *, seed: int, seconds: float, trace: bool, t_start: float,
            rates=None, root: str = ROOT) -> record.Run:
    """Run one cell with its runner. The tests call this with a tiny
    configuration on the CPU; the command line adds only the device
    check in front and the result line behind."""
    trace_dir = os.path.join(OUT_DIR, "trace", cell.name)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
    ctx = record.Context(
        cell=cell, seed=seed, seconds=seconds, trace=trace, t_start=t_start,
        trace_dir=trace_dir, compiles=record.CompileLog.get(), rates=rates,
    )
    return spec.runner(cell.traffic["runner"], root=root)(ctx)


def read_metrics(run: record.Run, entries: list[dict], kind_dir: str,
                 root: str = ROOT) -> dict:
    out = {}
    for m in entries:
        value = spec.reader(kind_dir, m["name"], root=root)(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def device_block(run: record.Run, trace: bool) -> dict:
    import jax

    devs = jax.local_devices()
    # PJRT counts the buffers it hands out (weights, pools, batches) under
    # ``peak_bytes_in_use`` and what running programs reserve beside them (their
    # temp) under ``peak_bytes_reserved``: the 124M train step read 1.8 GB and
    # 8.9 GB (my chip run, PR 23; XLA's analysis said 9.7 GiB in all). Their sum
    # bounds the peak from above; the two need not coincide.
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    out = {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": jax.device_count(), "memory_peak_bytes": peak,
    }
    if trace and run.trace is not None:
        out["busy_s"] = run.trace.busy_s
        out["window_s"] = run.trace.window_s
    return out


def result_line(run: record.Run, trace: bool, root: str = ROOT) -> dict:
    cell = run.cell
    if trace:
        metrics = read_metrics(run, cell.per_layer, "layer_metrics", root)
    else:
        metrics = read_metrics(run, cell.end_to_end, "end_to_end", root)
    line = {
        "correct": bool(run.correct), "attempted": int(run.attempted),
        "failed": int(run.failed), "metrics": metrics,
        "device": device_block(run, trace),
    }
    if trace and run.trace is not None:
        line["breakdown"] = {
            "device_ops": run.trace.top_ops(10), "idle_gaps": run.trace.top_gaps(10),
        }
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--rates", default="", help="sweep: comma-separated requests/s")
    args = ap.parse_args(argv)

    bench = spec.load_benchmark()
    cell = spec.load_cell(args.workload, bench=bench)
    seconds = float(args.seconds if args.seconds is not None else bench["run_seconds"])

    import jax

    from tensorflow_examples_tpu.core import device

    # The program's one place for the persistent cache: <checkout>/.jax_cache,
    # or JAX_COMPILATION_CACHE_DIR. The benchmark assigns nothing itself.
    device.enable_compile_cache()
    device.require_device("tpu")  # SystemExit, naming the platform found
    if jax.device_count() < cell.chips:
        raise SystemExit(
            f"cell {cell.name} asks for {cell.chips} chip(s); JAX found "
            f"{jax.device_count()} ({jax.devices()[0].device_kind}); nothing ran"
        )
    rates = [float(r) for r in args.rates.split(",") if r] or None
    run = execute(cell, seed=args.seed, seconds=seconds, trace=bool(args.trace),
                  t_start=T_START, rates=rates)
    if rates:
        return 0  # the sweep printed its own lines; it is not a result
    info(memory_stats={str(d): d.memory_stats() for d in jax.local_devices()})
    info(cell=cell.name, setup_s=run.setup_s, window_s=run.window_s,
         compiles_in_window=run.compiles_in_window, correct=run.correct_detail,
         **run.notes)
    if run.trace is not None:
        info(trace_lines=run.trace.lines_seen,
             modules={n: [len(d), sum(d)] for p in run.trace.planes
                      for n, d in p.modules.items()})
    print(json.dumps(result_line(run, bool(args.trace))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
