"""Config system: one dataclass per workload + an absl-flags CLI bridge.

Contract preserved from the reference (BASELINE.json:north_star): each
example keeps a ``python <example>/train.py --device=tpu`` CLI. Flags are
generated from the dataclass fields, so every config knob is a CLI flag.
"""

from __future__ import annotations

import dataclasses
from typing import Any

from tensorflow_examples_tpu.core.mesh import MeshConfig


@dataclasses.dataclass
class TrainConfig:
    # Device / distribution
    device: str = "tpu"  # tpu | cpu — reference contract flag
    mesh_data: int = -1  # -1: all remaining devices on the data axis
    mesh_fsdp: int = 1
    mesh_model: int = 1
    mesh_context: int = 1
    mesh_pipe: int = 1

    # Optimization
    global_batch_size: int = 128
    eval_batch_size: int = 0  # 0 → global_batch_size
    train_steps: int = 1000
    warmup_steps: int = 0
    learning_rate: float = 1e-3
    weight_decay: float = 0.0
    grad_clip_norm: float = 0.0  # 0 disables
    grad_accum_steps: int = 1
    steps_per_launch: int = 1  # run K train steps per device launch via
    #   lax.scan (the Keras steps_per_execution equivalent): amortizes
    #   per-launch dispatch cost for small steps. Cadences (log/eval/
    #   checkpoint) and the step span must be multiples of K.
    precision: str = "bf16"  # f32 | bf16 | bf16_full
    remat: bool = False  # jax.checkpoint the model apply
    zero1: bool = False  # shard optimizer state over the batch axes even
    #   for replicated params (ZeRO-1 / weight-update sharding)
    sharding_config: str = ""  # path to a ShardingConfig JSON
    #   (tensorflow_examples_tpu/sharding/; docs/sharding.md): when set,
    #   it is the single source of truth for mesh shape, param rules,
    #   batch axes, and ZeRO-1 — the mesh_*/zero1 knobs above are
    #   ignored. Training persists the active config (from whichever
    #   source) to workdir/sharding.json; serving auto-loads it.

    # Loop cadence
    log_every: int = 100
    eval_every: int = 0  # 0 disables periodic eval
    checkpoint_every: int = 1000
    seed: int = 42

    # IO
    workdir: str = ""  # checkpoints + tensorboard; "" disables
    data_dir: str = ""  # dataset location; "" → synthetic data
    resume: bool = True  # restore latest checkpoint from workdir

    # Profiling / sanitizers
    profile: bool = False  # legacy sugar: capture a profiler trace
    #   around run-relative steps 10-20 (= profile_start_step=10,
    #   profile_num_steps=10)
    profile_start_step: int = 0  # with profile_num_steps > 0: first
    #   run-relative step of the windowed jax.profiler device trace
    #   (telemetry/profiling.py); the window is one-shot per fit
    profile_num_steps: int = 0  # steps the profiler window covers;
    #   0 disables (unless legacy --profile is set)
    profile_dir: str = ""  # trace output dir; "" → <workdir>/profile
    #   (or /tmp/tpu_profile without a workdir). The final JSONL line
    #   cross-links the captured window under "profile".
    debug_nans: bool = False  # jax_debug_nans: fail fast at the op that
    #   produced a NaN (SURVEY.md §5b — the functional model removes data
    #   races by construction; NaN tracing is the remaining sanitizer)
    watchdog_secs: float = 600.0  # hang detector: dump all thread stacks
    #   if no step completes for this long (0 disables; SURVEY.md §5c)

    # Resilience (train/resilience.py; docs/resilience.md)
    preempt_checkpoint: bool = True  # SIGTERM/SIGINT: checkpoint at the
    #   next step boundary, then exit cleanly (code 0) — the resumed run
    #   is bitwise-identical to an uninterrupted one
    bad_step_policy: str = "skip"  # off | skip | rollback | abort —
    #   what to do about NaN/Inf losses/grads and loss spikes. "skip"
    #   drops the bad update ON DEVICE (no host sync on the happy path)
    #   and aborts after bad_step_patience consecutive bad steps;
    #   "rollback" instead restores the latest checkpoint there
    bad_step_patience: int = 5  # consecutive bad steps before the
    #   skip->abort / rollback escalation
    loss_spike_factor: float = 0.0  # >0: a loss above factor*EMA(loss)
    #   also counts as a bad step (host-side, detection lags a few steps)
    watchdog_fatal_secs: float = 0.0  # >0: if a step/input stall lasts
    #   this long, dump diagnostics and fail fast (exit 87) instead of
    #   hanging the slice; 0 keeps the watchdog detection-only
    io_retries: int = 3  # bounded retries for flaky file reads
    #   (data/sources.py) with exponential backoff
    io_backoff_secs: float = 0.25  # initial backoff; doubles per retry
    max_skipped_batches: int = 0  # poisoned-batch skip budget in the
    #   prefetch pipeline: corrupt host batches are skipped (and counted)
    #   up to this many times before the run errors out; 0 = fail fast

    # Input pipeline (data/; docs/data.md)
    prefetch_depth: int = 2  # device-prefetch look-ahead: batches held
    #   host→device ahead of the consuming step (the floor when the
    #   adaptive controller is armed)
    prefetch_depth_max: int = 0  # > prefetch_depth arms depth-adaptive
    #   double buffering (data/prefetch.DepthController): the queue
    #   deepens toward this bound while the observed data_fetch p95
    #   dominates the device_step p95 and decays back when the input
    #   side is comfortably ahead; 0 keeps the fixed depth
    input_workers: int = 0  # background decode/augment worker threads
    #   (data/workers.py): > 0 moves the ImageNet TFRecord hot path onto
    #   the sharded-parallel python pipeline (N readers + this many
    #   decode workers, deterministic and exactly resumable); 0 keeps
    #   the inline tf.data/native path
    input_readers: int = 2  # parallel shard-reader threads of the
    #   python TFRecord pipeline (only meaningful with input_workers>0);
    #   1 = the literal sequential reference stream

    # Telemetry (tensorflow_examples_tpu/telemetry/; docs/observability.md)
    telemetry_sinks: str = "jsonl,tensorboard,console"  # comma list of
    #   metric sinks per log window: "jsonl" (schema-versioned
    #   workdir/telemetry/metrics.jsonl, crash-safe append, process 0),
    #   "tensorboard" (clu writer with explicit null-writer fallback),
    #   "console" (the classic step log line). File sinks need --workdir.
    telemetry_trace: bool = True  # export the host span timeline as
    #   Chrome-trace JSON (workdir/telemetry/trace.json) on exit — load
    #   in chrome://tracing or ui.perfetto.dev
    telemetry_flush_every: int = 1  # flush sinks every N log windows
    #   (1 = per window; the JSONL sink additionally flushes per line)
    telemetry_peak_tflops: float = 0.0  # per-device peak TFLOP/s for the
    #   MFU estimate; 0 = look the PJRT device kind up in
    #   telemetry/accounting.py's table (a kind that is not there, the
    #   CPU included, has no peak and reports no MFU)
    metrics_port: int = 0  # >0: serve live observability endpoints on
    #   this port from every process (telemetry/serve.py): /metrics
    #   (Prometheus text from the registry), /health (watchdog phase +
    #   last-window age; 503 on a stall), /window (latest JSONL line).
    #   Closed on every exit path including watchdog-fatal. 0 disables.
    straggler_skew_factor: float = 2.0  # fleet straggler threshold
    #   (telemetry/fleet.py): when the slowest host's step-time p95
    #   exceeds this multiple of the fleet median, the kind="fleet"
    #   line flags it and a WARNING names the host and whether the skew
    #   is compute- or input-side. 0 disables the warning (fleet lines
    #   still emit).
    compile_warmup: int = 1  # expected compilations per jitted step fn
    #   (telemetry/compilation.py): the first N distinct input
    #   signatures are normal jit warmup; any compile beyond that is a
    #   RECOMPILATION — logged at WARNING naming the shape/dtype delta
    #   and emitted as a kind="compile_warning" JSONL line

    def mesh_config(self) -> MeshConfig:
        return MeshConfig(
            data=self.mesh_data,
            fsdp=self.mesh_fsdp,
            model=self.mesh_model,
            context=self.mesh_context,
            pipe=self.mesh_pipe,
        )

    def replace(self, **kw) -> "TrainConfig":
        return dataclasses.replace(self, **kw)


def define_flags_from_config(config: Any, flags_module=None) -> None:
    """Register one absl flag per dataclass field (name, default, type)."""
    from absl import flags as absl_flags

    fl = flags_module or absl_flags
    for f in dataclasses.fields(config):
        default = getattr(config, f.name)
        if f.name in fl.FLAGS:
            continue
        if isinstance(default, bool):
            fl.DEFINE_boolean(f.name, default, f.name)
        elif isinstance(default, int):
            fl.DEFINE_integer(f.name, default, f.name)
        elif isinstance(default, float):
            fl.DEFINE_float(f.name, default, f.name)
        else:
            fl.DEFINE_string(f.name, str(default), f.name)


def config_from_flags(config: Any, flags_values=None) -> Any:
    """Overlay parsed absl flag values onto a config instance."""
    from absl import flags as absl_flags

    fv = flags_values or absl_flags.FLAGS
    updates = {}
    for f in dataclasses.fields(config):
        if f.name in fv:
            updates[f.name] = getattr(fv, f.name)
    return dataclasses.replace(config, **updates)


def apply_device_flag(device: str, *, debug_nans: bool = False) -> None:
    """Honor the reference's ``--device`` contract.

    ``--device=cpu`` pins the CPU backend (tests and tiny-size runs).
    ``--device=tpu`` overrides nothing: JAX selects the platform, and
    ``core.device.require_device`` then stops the run at start-up
    unless that platform is the TPU.
    """
    import jax

    if device not in ("tpu", "cpu"):
        raise ValueError(f"--device={device!r} not in ('tpu', 'cpu')")
    if device == "cpu":
        jax.config.update("jax_platforms", "cpu")
    if debug_nans:
        jax.config.update("jax_debug_nans", True)
