#!/usr/bin/env python
"""Bank ONE real ``jax.profiler`` trace of the GPT-2 bench step.

Every rel_mfu bench.py reports is an ANALYTIC number (XLA cost-model
FLOPs / raw-matmul probe), not an observed device utilization. This
tool takes the observation: it runs the exact gpt2 bench
configuration (batch 8, seq 1024, bf16, flash + fused CE, one-chip
mesh), traces ~10 steps with ``jax.profiler``, converts the xplane
with TensorFlow's profiler plugin (available in-image), and emits:

- ``overview``: the OverviewPage analysis fields (device duty cycle,
  MXU utilization where the backend reports it, step-time breakdown);
- ``op_profile`` / ``framework_op_stats``: JSON tool outputs, op-level
  self-times (top entries only — the full JSONs land next to the
  banked record, not inside it);
- ``step_ms_during_trace``: wall step time measured around the traced
  steps, so the trace can be cross-checked against the bench numbers.

The xplane.pb itself is copied to ``chiprun_out/profile_trace/`` (what
a chip run brings back) when it is under 16 MB.

Emits ONE JSON line (always-emit watchdog pattern, diag_common);
``complete`` is true only when a trace was collected AND converted.
TPU only: off the chip it exits non-zero before tracing anything.

Spec: SURVEY.md §5a (profiling hook) — the framework side
(``--profile``) is train/loop.py's jax.profiler integration; this is
the measurement-protocol side.
"""

import glob
import json
import os
import shutil
import sys

# Must be set before ANY google.protobuf import (TF's plugin protos are
# stale vs the image's C++ protobuf): pure-python parsing is slower but
# always compatible.
os.environ.setdefault("PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION", "python")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402
from tools.diag_common import (  # noqa: E402
    make_emit, parse_budget, start_watchdog,
)

OUT: dict = {"diag": "profile_trace", "complete": False}
_emit = make_emit(OUT)

TRACE_DIR = "/tmp/tpu_profile_trace"
BANK_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "chiprun_out", "profile_trace",
)


def _trace_gpt2(steps: int = 10, warmup: int = 5) -> dict:
    """Run the gpt2 bench shape; trace ``steps`` launches.

    Capture is delegated to the trainer's in-loop profiler window
    (``profile_start_step``/``profile_num_steps``/``profile_dir``,
    telemetry/profiling.py) — the same code path ``--profile`` uses in
    production runs — so this tool keeps only the xplane-conversion and
    banking protocol. The warmup steps run before the window opens, so
    jit compilation never pollutes the trace.
    """
    from tensorflow_examples_tpu.data.memory import train_iterator
    from tensorflow_examples_tpu.telemetry import registry as registry_mod
    from tensorflow_examples_tpu.train.loop import Trainer
    from tensorflow_examples_tpu.workloads import gpt2

    tpu = bench.BACKEND == "tpu"
    cfg = gpt2.Gpt2Config(
        global_batch_size=8 if tpu else 1,
        seq_len=1024 if tpu else 128,
        dropout=0.0,
        precision="bf16",
        attention="flash" if tpu else "xla",
        fused_ce=tpu,
        log_every=10**9,
        checkpoint_every=0,
        train_steps=warmup + steps,
        watchdog_secs=0,
        preempt_checkpoint=False,
        telemetry_sinks="",
        telemetry_trace=False,
        profile_start_step=warmup,
        profile_num_steps=steps,
        profile_dir=TRACE_DIR,
        **({} if tpu else dict(num_layers=2, num_heads=2, d_model=64,
                               vocab_size=512)),
    )
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    trainer = Trainer(gpt2.make_task(cfg), cfg, mesh=bench._chip_mesh())
    it = train_iterator(gpt2.datasets(cfg)[0], cfg.global_batch_size, seed=0)
    trainer.fit(it, num_steps=cfg.train_steps)
    gauges = registry_mod.default_registry().gauge_values()
    traced = int(gauges.get("profile/steps", 0) or 0)
    dt = float(gauges.get("profile/wall_secs", 0.0) or 0.0)
    tokens = cfg.global_batch_size * cfg.seq_len * traced
    out = {
        "batch": cfg.global_batch_size,
        "seq": cfg.seq_len,
        "traced_steps": traced,
        "step_ms_during_trace": (
            round(dt / traced * 1e3, 3) if traced and dt else None
        ),
        "tokens_per_sec_during_trace": round(tokens / dt, 1) if dt else None,
    }
    return out


def _convert(xplanes: list) -> dict:
    """xplane -> tool outputs via TF's profiler plugin."""
    from tensorflow.python.profiler.internal import (
        _pywrap_profiler_plugin as pp,
    )

    out: dict = {}
    # overview_page is a serialized OverviewPage proto; its analysis
    # message carries the device utilization numbers we're after.
    try:
        from tensorboard_plugin_profile.protobuf import overview_page_pb2

        data, ok = pp.xspace_to_tools_data(list(xplanes), "overview_page", {})
        if ok:
            page = overview_page_pb2.OverviewPage()
            page.ParseFromString(data)
            out["overview"] = {
                f.name: (round(v, 4) if isinstance(v, float) else v)
                for f, v in page.analysis.ListFields()
                if isinstance(v, (int, float, str, bool))
            }
            out["input_analysis"] = {
                f.name: (round(v, 4) if isinstance(v, float) else v)
                for f, v in page.input_analysis.ListFields()
                if isinstance(v, (int, float, str, bool))
            }
    except Exception as e:  # noqa: BLE001 — partial conversion still banks
        out["overview_error"] = f"{type(e).__name__}: {e}"
    for tool, top in (("op_profile", None), ("framework_op_stats", 12)):
        try:
            data, ok = pp.xspace_to_tools_data(list(xplanes), tool, {})
            if not ok:
                out[f"{tool}_error"] = str(data)[:200]
                continue
            s = data.decode() if isinstance(data, bytes) else str(data)
            os.makedirs(BANK_DIR, exist_ok=True)
            with open(os.path.join(BANK_DIR, f"{tool}.json"), "w") as f:
                f.write(s)
            parsed = json.loads(s)
            if tool == "framework_op_stats" and isinstance(parsed, list):
                # gviz table: keep the top rows (rank, op, self-time %).
                table = parsed[0] if parsed else {}
                rows = (table.get("rows") or [])[: top or 12]
                out[tool] = [
                    [c.get("v") for c in r.get("c", [])][:6] for r in rows
                ]
            else:
                out[f"{tool}_banked"] = True
        except Exception as e:  # noqa: BLE001
            out[f"{tool}_error"] = f"{type(e).__name__}: {e}"
    return out


def main() -> int:
    budget = parse_budget(sys.argv[1:], default=420.0)
    bench._require_tpu()  # exits non-zero off the TPU
    bench.BACKEND = "tpu"
    watchdog = start_watchdog(budget, _emit)
    try:
        OUT["backend"] = bench.BACKEND
        OUT["probe_tflops"] = round(bench._probe_quick(), 2)
        OUT["launch_us"] = round(bench._probe_launch_us(), 2)
        OUT.update(_trace_gpt2())
        xplanes = glob.glob(
            os.path.join(TRACE_DIR, "**", "*.xplane.pb"), recursive=True
        )
        OUT["xplane_files"] = [os.path.basename(p) for p in xplanes]
        if xplanes:
            OUT.update(_convert(xplanes))
            total = sum(os.path.getsize(p) for p in xplanes)
            OUT["xplane_bytes"] = total
            if total < 16 * 2**20:
                os.makedirs(BANK_DIR, exist_ok=True)
                for p in xplanes:
                    shutil.copy(p, BANK_DIR)
                OUT["trace_banked_to"] = BANK_DIR
        OUT["complete"] = bool(xplanes and "overview" in OUT)
    except Exception as e:  # noqa: BLE001 — partials must still emit
        OUT["error"] = f"{type(e).__name__}: {e}"
    watchdog.cancel()
    _emit()
    return 1 if "error" in OUT else 0


if __name__ == "__main__":
    sys.exit(main())
