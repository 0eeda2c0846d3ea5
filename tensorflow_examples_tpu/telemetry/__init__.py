"""Telemetry subsystem (ISSUE 2 host side, ISSUE 3 device side):
metrics registry, span tracer, pluggable sinks, derived
throughput/MFU/goodput accounting, recompilation sentinel, memory
accounting, and in-loop profiler windows.

See docs/observability.md for the architecture and file formats.

Layer map:

* ``registry``    — process-local counters/gauges/time-histograms every
                    runtime layer publishes into (``default_registry()``).
* ``spans``       — ``with span("data_fetch")`` host timeline; Chrome
                    trace export; open-span introspection for watchdog
                    hang dumps.
* ``sinks``       — JSONL (crash-safe append), clu/TensorBoard (explicit
                    null-writer fallback), console.
* ``accounting``  — examples/sec, 6ND model-FLOPs MFU, goodput math.
* ``schema``      — the self-describing JSONL line schema + validator
                    (v2: memory / compile_warning / profile fields).
* ``compilation`` — recompilation sentinel around the jitted step fns:
                    compile counts/spans + post-warmup recompile
                    warnings naming the shape/dtype delta.
* ``memory``      — HBM/host memory accounting: init breakdown, peak
                    watermark gauge, OOM allocation forensics.
* ``profiling``   — programmable one-shot ``jax.profiler`` windows
                    (TrainConfig ``profile_start_step``/``num_steps``/
                    ``dir``) cross-linked from the run's final line.
* ``fleet``       — per-host health-vector allgather, slowest-host /
                    skew-ratio attribution, ``kind="fleet"`` lines and
                    the straggler warning (ISSUE 4).
* ``serve``       — the opt-in per-process /metrics (Prometheus text),
                    /health, /window HTTP endpoints (ISSUE 4).
* ``hub``         — the ``Telemetry`` object the trainer owns, tying the
                    above together per run.
"""

from tensorflow_examples_tpu.telemetry.accounting import (  # noqa: F401
    goodput,
    mfu,
    peak_flops_per_device,
    train_step_flops,
)
from tensorflow_examples_tpu.telemetry.compilation import (  # noqa: F401
    CompilationSentinel,
)
from tensorflow_examples_tpu.telemetry.fleet import (  # noqa: F401
    FleetMonitor,
)
from tensorflow_examples_tpu.telemetry.hub import Telemetry  # noqa: F401
from tensorflow_examples_tpu.telemetry.memory import (  # noqa: F401
    MemoryMonitor,
    live_array_bytes,
    tree_bytes,
)
from tensorflow_examples_tpu.telemetry.profiling import (  # noqa: F401
    ProfilerWindow,
)
from tensorflow_examples_tpu.telemetry.registry import (  # noqa: F401
    MetricsRegistry,
    default_registry,
    reset_default_registry,
)
from tensorflow_examples_tpu.telemetry.schema import (  # noqa: F401
    SCHEMA_VERSION,
    validate_line,
)
from tensorflow_examples_tpu.telemetry.serve import (  # noqa: F401
    MetricsServer,
    render_prometheus,
)
from tensorflow_examples_tpu.telemetry.spans import (  # noqa: F401
    Tracer,
    active_span_names,
    default_tracer,
    reset_default_tracer,
    span,
)
