#!/usr/bin/env python
"""Framework benchmark — prints exactly ONE JSON line for the driver.

North-star metric (BASELINE.json:metric): **ResNet-50 ImageNet
examples/sec/chip**, measured two ways so input-pipeline cost is visible
separately (SURVEY.md §3(4), §7 hard-part (a)):

- ``resnet50``        — synthetic batches already resident on device
                        (pure compute ceiling).
- ``resnet50_input``  — fed by the real host pipeline: tf.data TFRecord
                        shards → JPEG decode → augment → threaded C++
                        normalize → async device prefetch.

Secondary benches: GPT-2 124M tokens/sec (``gpt2``, ``gpt2_long``,
``gpt2_long16k``, ``gpt2_decode``), BERT, CIFAR-10, MNIST step-time,
ICI/mesh collective bandwidth (``collectives``), MoE (``moe``).
``--bench=all`` (the default) runs the suite and emits the north-star
as the headline with the rest under ``"extras"``.

Measurement protocol:

- every bench times **3 windows** and reports the **median**; the
  per-window values are emitted (``window_values``) so noise is visible
  in the record, not asserted away;
- a raw-matmul probe runs **before and after** the sweep
  (``fingerprint_tflops_pre/post``, each a median of 5 windows) AND
  once, quickly, immediately before each bench
  (``probe_tflops_at_bench``);
- every compute bench emits ``model_tflops_per_sec`` — analytic
  FLOPs/step from XLA's cost model on the exact compiled executable
  (hand-counted for the decode bench: XLA's count includes a lax.scan
  body once, not × trip count), divided by the median step time — and
  ``rel_mfu`` = model_tflops / probe_tflops_at_bench.

FLOORS: ``vs_baseline`` and ``rel_mfu_vs_floor`` compare against
``FLOORS``/``REL_MFU_FLOORS``, which were stamped on hardware this repo
no longer runs on — on a real chip they mean nothing until the
``benchmark`` PRs (ROADMAP queue 1, items 1 and 3) replace them with
cells measured under the ledger.

The device: this is a measurement of the TPU. ``main()`` exits non-zero,
before timing anything, when JAX's default backend is anything else;
there is no CPU fallback and no record from one. A failed bench, a
``sweep_error`` or a fired watchdog still prints the one JSON line
(what completed is evidence) and then exits non-zero. The process
touches JAX itself, so it holds the chip: it starts no child that needs
one. The compiled-kernel tests are their own command —
``python -m pytest tests_tpu/``.

Budget: the whole run operates under a wall-clock budget
(``--budget=S`` / ``$BENCH_BUDGET_S``, default 540 s). Benches that
don't fit the remaining budget are skipped and listed under
``"truncated"``; a watchdog thread is the backstop — if the main thread
is stuck inside a compile when the budget expires, the watchdog emits
everything completed so far as the one JSON line and exits 3. The
compile cache is ``core/device.enable_compile_cache``'s.
"""

import json
import os
import statistics
import subprocess
import sys
import threading
import time

# Regression floors: (value, rig_fingerprint_tflops) pairs per
# (backend, metric) — see FLOORS in the module docstring: the "tpu"
# numbers come from a shared rig whose matmul probe read far above one
# physical v5e (the fingerprints below say so themselves) and are kept
# only until the benchmark PRs replace this table.
FLOORS = {
    "tpu": {
        # 2026-07-31 round-4 sweep (median-of-3 windows, per-bench
        # pre-probes). Each floor carries ITS OWN record's
        # pre-fingerprint — the rig drifted [78, 99912] probe-TFLOP/s
        # across that window.
        "resnet50_examples_per_sec_per_chip": (185187.0807, 65958.3),
        "resnet50_input_examples_per_sec_per_chip": (124.0052, 53598.89),  # 1-CPU host!
        # ISSUE 6: the r02 pipeline-only figure (host decode+augment,
        # no device in the loop) promoted from a buried extras
        # annotation to a tracked, floored metric. Fingerprint is the
        # r02 record's own.
        "resnet50_input_pipeline_only_images_per_sec": (474.6, 2279.33),
        "gpt2_124m_tokens_per_sec": (3592223.8352, 59962.35),
        "gpt2_long4k_tokens_per_sec": (4231329.5553, 47927.17),
        "gpt2_long16k_tokens_per_sec": (9130385.6576, 70377.3),
        "gpt2_decode_tokens_per_sec": (3094517.5665, 62363.12),
        "gpt2_decode_long_tokens_per_sec": (1510532.0, 51264.06),
        # bert/cifar10/mnist: restamped 2026-08-01 under the K=8
        # bundled protocol (FLOOR_BUNDLES carries the 8; a future
        # unbundled record flags floor_protocol_mismatch).
        "bert_base_examples_per_sec_per_chip": (174256.466, 69610.49),
        "cifar10_resnet20_examples_per_sec_per_chip": (1602954.8218, 54962.94),
        "mnist_mlp_step_time": (0.0104, 55840.55),  # ms/step
        "allreduce_busbw": (3401.0685, 86610.5),  # GB/s, n=1 loopback
        "moe_top2_tokens_per_sec": (62555.0, 45538.05),
        # decode_grid_step_time_ratio is deliberately NOT floored: it is
        # a diagnostic whose healthy value is ~1.0 (O(context)
        # sequencing) and whose failure direction is UP toward ~8
        # (O(max_len)); a floor at the measured 0.78 would make a
        # healthy 1.0 read as a regression through the lower-is-better
        # branch.
    },
    "cpu": {
        # 2026-07-30 round-4 protocol sweep (median-of-3 windows, probe
        # pre 0.10 / post 0.09 TFLOP/s, uncontended single-core host).
        # main() no longer runs on the CPU; tools/bench_gate.py and its
        # tests still read these.
        "resnet50_examples_per_sec_per_chip": (0.436, 0.09),
        "resnet50_input_examples_per_sec_per_chip": (0.472, 0.10),
        # ISSUE 6: stamped 2026-08-04 from tools/host_input_bench.py
        # --smoke on this 2-vCPU rig (parallel pipeline, 4 workers /
        # 2 readers, native decode, record-shuffle window on;
        # sequential reference ~610-700). LOWEST of three back-to-back
        # healthy records (runs here spread ~715-915 with ambient
        # load; the tool's own median-of-5 GEMM probe is the
        # fingerprint — NOT bench.py's probe — and a loaded run's
        # probe collapses with it, so the 2x comparability window
        # already skips the worst noise).
        "host_input_pipeline_images_per_sec": (715.9, 0.0881),
        "gpt2_124m_tokens_per_sec": (37.3, 0.10),
        "gpt2_long4k_tokens_per_sec": (19.6, 0.10),
        "gpt2_long16k_tokens_per_sec": (23.6, 0.10),
        "gpt2_decode_tokens_per_sec": (3200.8, 0.10),
        "gpt2_decode_long_tokens_per_sec": (1965.0, 0.10),
        "bert_base_examples_per_sec_per_chip": (1607.1, 0.10),
        "cifar10_resnet20_examples_per_sec_per_chip": (92.1, 0.10),
        "mnist_mlp_step_time": (3.86, 0.10),  # ms/step
        "allreduce_busbw": (0.88, 0.10),  # GB/s, 8 virtual devices
        "moe_top2_tokens_per_sec": (8606.3, 0.10),
    },
}

# Launch protocol each floor was stamped under: steps_per_launch of the
# record that produced the FLOORS value (metrics absent here were
# stamped unbundled, bundle=1). _result flags "floor_protocol_mismatch"
# whenever a record's bundle differs from its floor's — vs_baseline
# across that boundary mixes launch amortization with per-step change.
# Restamps must move these entries together with FLOORS (stamped
# mechanically by tools/apply_floors.py from each record's "bundle"
# key; the round-4 pre-registered bert/cifar10/mnist K=8 protocol
# landed with the 2026-08-01 round-5 restamp below).
FLOOR_BUNDLES: dict[str, dict[str, int]] = {
    "tpu": {
        "resnet50_examples_per_sec_per_chip": 1,
        "resnet50_input_examples_per_sec_per_chip": 1,
        "gpt2_124m_tokens_per_sec": 1,
        "gpt2_long4k_tokens_per_sec": 1,
        "gpt2_long16k_tokens_per_sec": 1,
        "gpt2_decode_tokens_per_sec": 1,
        "bert_base_examples_per_sec_per_chip": 8,
        "cifar10_resnet20_examples_per_sec_per_chip": 8,
        "mnist_mlp_step_time": 8,
        "allreduce_busbw": 1,
    },
    "cpu": {},
}

# Drift-cancelled floors: rel_mfu = model_tflops/probe_tflops measured
# under the 3-window protocol. Stamped per-metric by
# tools/apply_floors.py from each metric's most recent record. CPU side
# from the 2026-07-30 round-4 sweep. Same standing as FLOORS.
REL_MFU_FLOORS: dict[str, dict[str, float]] = {
    "tpu": {
        "resnet50_examples_per_sec_per_chip": 0.07961,
        "resnet50_input_examples_per_sec_per_chip": 6e-05,
        "gpt2_124m_tokens_per_sec": 0.06236,
        "gpt2_long4k_tokens_per_sec": 0.0515,
        "gpt2_long16k_tokens_per_sec": 0.10832,
        "gpt2_decode_tokens_per_sec": 0.01937,
        "gpt2_decode_long_tokens_per_sec": 0.13992,
        # bert/cifar10/mnist rel_mfu floors were DROPPED with the K=8
        # restamp (2026-08-01): their round-4 stamps were per-step
        # values, and a bundled record's rel_mfu (chip no longer idle
        # between launches) would read ~10x over them — a silent
        # protocol conflation, not an efficiency gain. They return when
        # the queued re-measure banks bundled records WITH rel_mfu
        # (the compiled-bundled/k FLOPs fallback) and apply_floors
        # restamps all three consistently.
        "moe_top2_tokens_per_sec": 0.00154,
    },
    "cpu": {
        # Round-4 sweep (2026-07-30). gpt2 dropped 0.729 → 0.306 NOT
        # from a slowdown (raw tokens/s moved 40.9 → 37.3, within this
        # host's ambient swing) but because the gather-free CE changed
        # the step's XLA cost-analysis FLOPs — the rel_mfu NUMERATOR.
        "resnet50_examples_per_sec_per_chip": 0.102,
        "resnet50_input_examples_per_sec_per_chip": 0.127,
        "gpt2_124m_tokens_per_sec": 0.306,
        "gpt2_long4k_tokens_per_sec": 0.232,
        "gpt2_long16k_tokens_per_sec": 0.604,
        "gpt2_decode_tokens_per_sec": 0.019,
        "gpt2_decode_long_tokens_per_sec": 0.028,
        "bert_base_examples_per_sec_per_chip": 0.078,
        "cifar10_resnet20_examples_per_sec_per_chip": 0.224,
        "mnist_mlp_step_time": 0.324,
        "moe_top2_tokens_per_sec": 0.299,
    },
}

BACKEND = "cpu"  # main() sets "tpu" or exits; tests import on the CPU
WINDOWS = 3  # timing windows per bench; median reported

# ------------------------------------------------------- budget machinery
#
# One deadline for the whole process (None = unbounded). Everything that
# can block — benches, subprocess helpers, the backend probe — consults
# _remaining(); the watchdog thread is the last line of defense for
# hangs inside native code where Python-level checks never run.

_DEADLINE: "float | None" = None
_RESULTS: list = []  # completed per-bench dicts, in completion order
_META: dict = {}  # backend / fingerprints, merged at emit
# Full sweep plan (set in main for --bench=all BEFORE anything can
# block, so even a watchdog firing during backend resolution emits an
# honest truncated list). _assemble derives "truncated" as
# planned − completed.
_SWEEP_PLANNED: list = []
_IN_FLIGHT: "str | None" = None
_EMIT_LOCK = threading.Lock()
_EMITTED = False


def _remaining() -> float:
    return float("inf") if _DEADLINE is None else _DEADLINE - time.monotonic()


def _assemble() -> dict:
    """Fold completed benches into the single driver JSON object:
    headline = highest-priority error-free bench per ALL_ORDER (benches
    EXECUTE cheapest-first to maximize coverage under the budget, but
    the record is always presented headline-first), everything else
    under "extras", budget victims under "truncated"."""
    rank = {n: i for i, n in enumerate(ALL_ORDER)}
    results = sorted(_RESULTS, key=lambda r: rank.get(r.get("bench"), 99))
    head = next((r for r in results if "error" not in r), None)
    if head is None and results:
        # Everything errored: surface the first real error (with its
        # bench identity) at top level rather than a generic message.
        head = results[0]
    out = dict(head) if head is not None else {"error": "no bench completed"}
    extras = [r for r in results if r is not head]
    if extras:
        out["extras"] = extras
    done = {r.get("bench") for r in results}
    trunc = []
    if _IN_FLIGHT is not None and _IN_FLIGHT not in done:
        trunc.append(_IN_FLIGHT)
    # Every planned-but-not-completed bench — skipped by the budget
    # check, in flight at watchdog fire, or never reached — is
    # truncated; absence would read as "not part of the sweep".
    for name in _SWEEP_PLANNED:
        if name not in done and name not in trunc:
            trunc.append(name)
    if trunc:
        out["truncated"] = trunc
    out.update(_META)
    return out


def _emit(out: "dict | None" = None) -> None:
    """Print the ONE JSON line, exactly once per process. Never raises:
    a failure here would break the always-one-parseable-line contract
    for both the main thread and the watchdog."""
    global _EMITTED
    with _EMIT_LOCK:
        if _EMITTED:
            return
        try:
            line = json.dumps(out if out is not None else _assemble())
        except Exception as e:  # non-serializable value in a bench dict
            line = json.dumps({"error": f"emit failed: {type(e).__name__}: {e}"})
        try:
            print(line)
            sys.stdout.flush()
        except Exception:
            pass  # stdout gone (driver killed the pipe); nothing to do
        _EMITTED = True


def _watchdog_fire() -> None:
    _META.setdefault("budget_expired", True)
    _emit()
    os._exit(3)  # main thread may be stuck in native code; don't wait


def _require_tpu() -> None:
    """Exit non-zero unless JAX's default backend is the TPU. In this
    process, not a probe child: a child that touched the chip would
    hold it, and this process needs it next."""
    from tensorflow_examples_tpu.core import device

    device.enable_compile_cache()
    device.require_device("tpu")
    print(f"bench: {device.describe_devices()}", file=sys.stderr)


# ------------------------------------------------------------- rig probe


_PROBE_STATE: dict = {}


def _probe_window(iters: int) -> float:
    """One raw big-matmul timing window → TFLOP/s. The jitted matmul and
    its inputs are built once per process (a fresh lambda per window
    would miss the jit cache and recompile every probe)."""
    import jax
    import jax.numpy as jnp

    if BACKEND not in _PROBE_STATE:
        n = 8192 if BACKEND == "tpu" else 1024
        dtype = jnp.bfloat16 if BACKEND == "tpu" else jnp.float32
        k = jax.random.PRNGKey(0)
        a = jax.random.normal(k, (n, n), dtype)
        b = jax.random.normal(k, (n, n), dtype)
        f = jax.jit(lambda a, b: a @ b)
        f(a, b).block_until_ready()  # compile once
        _PROBE_STATE[BACKEND] = (f, a, b, n)
    f, a, b, n = _PROBE_STATE[BACKEND]
    f(a, b).block_until_ready()  # warm window
    t0 = time.perf_counter()
    for _ in range(iters):
        out = f(a, b)
    out.block_until_ready()
    dt = time.perf_counter() - t0
    return 2 * n**3 * iters / dt / 1e12


def fingerprint_tflops(windows: int = 5) -> float:
    """Rig behavior stamp: median of ``windows`` probe windows."""
    iters = 10 if BACKEND == "tpu" else 3
    return statistics.median(_probe_window(iters) for _ in range(windows))


def _probe_quick() -> float:
    """Cheap single-window probe run immediately before each bench."""
    return _probe_window(5 if BACKEND == "tpu" else 2)


def _probe_launch_us(n: int = 200, windows: int = 3) -> float:
    """Dispatch-chain fingerprint: wall µs per chained jitted no-op step.

    The matmul probe saturates on device FLOPs and cannot see per-launch
    host dispatch cost — but the small-step benches (cifar10, mnist,
    resnet50_input, decode) run exactly in the regime where that cost
    dominates. Chained x = f(x) launches replicate _time_steps' async-dispatch
    pattern: one block at the end, so the figure is launch pipeline
    throughput, not round-trip latency."""
    import jax
    import jax.numpy as jnp

    key = ("launch", BACKEND)
    if key not in _PROBE_STATE:
        f = jax.jit(lambda x: x + 1.0, donate_argnums=0)
        x0 = f(jnp.zeros((8, 128), jnp.float32))
        x0.block_until_ready()  # compile once
        _PROBE_STATE[key] = (f, x0)
    f, x = _PROBE_STATE[key]
    best = None
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(n):
            x = f(x)
        x.block_until_ready()
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    _PROBE_STATE[key] = (f, x)
    return best / n * 1e6


# -------------------------------------------------------------- plumbing


def _result(
    metric: str,
    values: "float | list[float]",
    unit: str,
    *,
    model_tflops_per_sec: "float | None" = None,
    **extra,
) -> dict:
    """Assemble one bench record. ``values``: per-window measurements
    (a scalar is accepted for benches without windows); the median is
    the headline value and the sorted window list is emitted so
    run-to-run spread is part of the record."""
    if isinstance(values, (int, float)):
        values = [float(values)]
    value = statistics.median(values)
    floor, floor_fp = FLOORS.get(BACKEND, {}).get(metric, (0.0, 0.0))
    if "step_time" in metric or "ms" in unit:
        vs = floor / value if floor else 1.0  # lower is better
    else:
        vs = value / floor if floor else 1.0
    out = {
        "metric": metric,
        "value": round(value, 4),
        "unit": unit,
        "vs_baseline": round(vs, 4),
        # Compare with probe_tflops_at_bench before reading vs_baseline
        # as a regression/improvement (FLOORS POLICY, module docstring).
        "floor_fingerprint_tflops": floor_fp,
        "window_values": [round(v, 4) for v in sorted(values)],
        **extra,
    }
    # A floor is only comparable to a record measured under the same
    # launch protocol: flag when the record's steps_per_launch differs
    # from the bundle the floor was stamped at, so a vs_baseline that
    # conflates launch amortization with per-step perf is visibly
    # transitional rather than silently green.
    rec_bundle = int(extra.get("bundle", 1) or 1)
    floor_bundle = FLOOR_BUNDLES.get(BACKEND, {}).get(metric, 1)
    if floor and rec_bundle != floor_bundle:
        out["floor_protocol_mismatch"] = (
            f"record bundle={rec_bundle}, floor stamped at "
            f"bundle={floor_bundle}"
        )
    if model_tflops_per_sec is not None:
        out["model_tflops_per_sec"] = round(model_tflops_per_sec, 3)
        # Which analysis produced the FLOPs numerator (ADVICE r4):
        # "compiled" = XLA cost model on the compiled executable,
        # "lowered" = pre-optimization lowering (verified equal on this
        # rig but not guaranteed on other versions/backends),
        # "hand-counted" = analytic formula in the bench itself.
        out["flops_analysis"] = _step_flops.last_mode or "hand-counted"
        _step_flops.last_mode = None
    return out


def _chip_mesh():
    """1-device mesh: workload benches measure per-chip throughput."""
    import jax

    from tensorflow_examples_tpu.core.mesh import MeshConfig, create_mesh

    return create_mesh(MeshConfig(data=1), devices=jax.devices()[:1])


def _step_flops(
    trainer, batch, *, bundle: int = 1
) -> "float | None":
    """Analytic FLOPs/step from XLA's cost model on the train step.

    ``bundle == 1`` (unbundled benches): analyse the exact compiled
    executable — AOT lower+compile populates the jit cache, so the
    bench pays the one compile it would pay anyway.
    Call BEFORE the first execution — the step donates its state
    buffers.

    ``bundle`` > 1 (bundled benches, which execute a DIFFERENT scanned
    program; ``batch`` is the [k, ...] stack): first try the
    single-step LOWERING only — no backend compile of a program that
    never executes. A backend whose pre-compile cost model returns
    None gives nothing there; then analyse the compiled BUNDLED
    program itself — the same executable the bench warms up anyway —
    and report flops / k. The record's "flops_analysis" key says which
    path produced the number."""
    import jax

    _step_flops.last_mode = None

    def _flops_of(analysable) -> "float | None":
        ca = analysable.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        if ca is None:  # lowering-only analysis unsupported here
            return None
        f = float(ca.get("flops", 0.0))
        # Only a usable value earns provenance (a zero-FLOPs result
        # returns None and must not label a later bench).
        return f if f > 0 else None

    try:
        one = jax.tree.map(lambda x: x[0], batch) if bundle > 1 else batch
        use_compiled = bundle == 1
        lowered = trainer._train_step.lower(trainer.state, one)
        f = _flops_of(lowered.compile() if use_compiled else lowered)
        if f is not None:
            _step_flops.last_mode = "compiled" if use_compiled else "lowered"
            return f
        if bundle > 1:
            bundled = trainer._build_bundled_step(bundle)
            f = _flops_of(bundled.lower(trainer.state, batch).compile())
            if f is not None:
                _step_flops.last_mode = "compiled-bundled/k"
                return f / bundle
        return None
    except Exception as e:  # cost model availability varies by backend
        print(f"bench: cost_analysis unavailable ({e})", file=sys.stderr)
        return None


# Read-once provenance for the most recent _step_flops call; _result
# consumes it into the record's "flops_analysis" key.
_step_flops.last_mode = None


def _time_steps(
    trainer, batches, steps, warmup, windows: int = WINDOWS, bundle: int = 1
):
    """Time jitted train steps over pre-placed device batches.

    Returns per-window wall times (seconds for ``steps`` steps each).
    State threads through all windows (the step donates its input).

    ``bundle`` > 1: ``batches`` are [k, batch, ...] stacks (from
    ``_bundle_prep``) and each launch is the steps_per_launch scanned
    step — ``steps`` still counts TRAIN steps, so windows time
    ``steps / bundle`` launches and throughput math is unchanged."""
    import jax

    step_fn = (
        trainer._train_step if bundle == 1 else trainer._build_bundled_step(bundle)
    )
    assert steps % bundle == 0, (steps, bundle)
    state = trainer.state
    for i in range(max(1, warmup // bundle)):
        state, m = step_fn(state, batches[i % len(batches)])
    jax.block_until_ready(m["loss"])
    dts = []
    for _ in range(windows):
        t0 = time.perf_counter()
        for i in range(steps // bundle):
            state, m = step_fn(state, batches[i % len(batches)])
        jax.block_until_ready(m["loss"])
        dts.append(time.perf_counter() - t0)
    return dts


def _bundle_prep(trainer, it, n: int, bundle: int):
    """Pre-place ``n`` [bundle, batch, ...] stacks for bundled timing."""
    from tensorflow_examples_tpu.core.sharding import bundle_sharding
    from tensorflow_examples_tpu.data.prefetch import bundle_batches, put_batch

    sh = bundle_sharding(trainer.mesh)
    bb = bundle_batches(it, bundle)
    return [put_batch(next(bb), sh) for _ in range(n)]


def _throughput(dts, per_step_units, steps):
    """Per-window throughput values from per-window wall times."""
    return [steps * per_step_units / dt for dt in dts]


def _model_tflops(flops, steps, dt_window):
    """Analytic model TFLOP/s: per-step FLOPs × steps over one window's
    wall time (None when the cost model gave nothing)."""
    return flops * steps / dt_window / 1e12 if flops else None


# ------------------------------------------------------------- resnet-50


def _resnet50_trainer(batch: int):
    from tensorflow_examples_tpu.train.loop import Trainer
    from tensorflow_examples_tpu.workloads import imagenet

    cfg = imagenet.ImagenetConfig(
        global_batch_size=batch,
        precision="bf16",
        log_every=10**9,
        checkpoint_every=0,
        eval_every=0,
        train_steps=10**6,
        watchdog_secs=0,
    )
    return Trainer(imagenet.make_task(cfg), cfg, mesh=_chip_mesh()), cfg


def bench_resnet50() -> dict:
    """North-star: examples/sec/chip, synthetic data resident on device.

    The batch-4 CPU shape is what FLOORS["cpu"] was stamped at."""
    from tensorflow_examples_tpu.data import imagenet as imagenet_data

    batch = 256 if BACKEND == "tpu" else 4
    steps = 20 if BACKEND == "tpu" else 2
    warmup = 5 if BACKEND == "tpu" else 1
    trainer, cfg = _resnet50_trainer(batch)
    it = imagenet_data.synthetic_train_iter(
        batch, image_size=cfg.image_size, num_classes=cfg.num_classes, seed=0
    )
    batches = [trainer._put_batch(next(it)) for _ in range(2)]
    flops = _step_flops(trainer, batches[0])
    dts = _time_steps(trainer, batches, steps, warmup)
    dt_med = statistics.median(dts)
    return _result(
        "resnet50_examples_per_sec_per_chip",
        _throughput(dts, batch, steps),
        "examples/sec/chip",
        batch=batch,
        model_tflops_per_sec=_model_tflops(flops, steps, dt_med),
    )


def _write_bench_tfrecords(root: str, *, shards=4, per_shard=128, size=256):
    """Synthetic JPEG ImageNet-schema TFRecord shards for the input bench."""
    import numpy as np

    done = os.path.join(root, ".complete")
    if os.path.exists(done):
        return
    os.makedirs(root, exist_ok=True)
    import tensorflow as tf

    tf.config.set_visible_devices([], "GPU")
    rng = np.random.default_rng(0)
    for s in range(shards):
        path = os.path.join(root, f"train-{s:05d}-of-{shards:05d}")
        with tf.io.TFRecordWriter(path) as w:
            for _ in range(per_shard):
                img = rng.integers(0, 256, (size, size, 3), np.uint8)
                enc = tf.io.encode_jpeg(img).numpy()
                ex = tf.train.Example(
                    features=tf.train.Features(
                        feature={
                            "image/encoded": tf.train.Feature(
                                bytes_list=tf.train.BytesList(value=[enc])
                            ),
                            "image/class/label": tf.train.Feature(
                                int64_list=tf.train.Int64List(
                                    value=[int(rng.integers(1, 1001))]
                                )
                            ),
                        }
                    )
                ).SerializeToString()
                w.write(ex)
    with open(done, "w") as f:
        f.write("ok")


def bench_resnet50_input() -> dict:
    """North-star, host-pipeline-fed: TFRecord → decode → augment →
    C++ normalize → async device prefetch → train step."""
    import jax

    from tensorflow_examples_tpu.data import imagenet as imagenet_data
    from tensorflow_examples_tpu.data.prefetch import device_prefetch

    batch = 256 if BACKEND == "tpu" else 4
    steps = 10 if BACKEND == "tpu" else 2
    warmup = 3 if BACKEND == "tpu" else 1
    root = "/tmp/bench_imagenet_tfrecords"
    _write_bench_tfrecords(root)

    # Host-pipeline-only throughput (no device): isolates input cost.
    # ISSUE 6: measured through the sharded-parallel reader + worker-
    # pool pipeline (data/workers.py) — the production hot path — with
    # the worker count sized to the host.
    input_workers = max(2, min(8, os.cpu_count() or 1))
    input_readers = 2
    host_it = imagenet_data.parallel_tfrecord_iter(
        root, "train", batch, train=True,
        num_readers=input_readers, num_workers=input_workers,
    )
    next(host_it)  # warm the pool + native decode
    pipe_vals = []
    pipe_batches = 4 if BACKEND == "tpu" else 2
    for _ in range(WINDOWS):
        t0 = time.perf_counter()
        for _ in range(pipe_batches):
            next(host_it)
        pipe_vals.append(pipe_batches * batch / (time.perf_counter() - t0))
    host_it.close()  # drain worker/reader threads before the train feed

    trainer, cfg = _resnet50_trainer(batch)
    it = device_prefetch(
        imagenet_data.parallel_tfrecord_iter(
            root, "train", batch, train=True,
            num_readers=input_readers, num_workers=input_workers,
        ),
        trainer._batch_sharding,
    )
    flops = _step_flops(trainer, next(it))
    state = trainer.state
    for _ in range(warmup):
        state, m = trainer._train_step(state, next(it))
    jax.block_until_ready(m["loss"])
    dts = []
    for _ in range(WINDOWS):
        t0 = time.perf_counter()
        for _ in range(steps):
            state, m = trainer._train_step(state, next(it))
        jax.block_until_ready(m["loss"])
        dts.append(time.perf_counter() - t0)
    dt_med = statistics.median(dts)
    return _result(
        "resnet50_input_examples_per_sec_per_chip",
        _throughput(dts, batch, steps),
        "examples/sec/chip",
        batch=batch,
        pipeline_only_images_per_sec=round(statistics.median(pipe_vals), 1),
        pipeline_only_windows=[round(v, 1) for v in sorted(pipe_vals)],
        input_workers=input_workers,
        input_readers=input_readers,
        model_tflops_per_sec=_model_tflops(flops, steps, dt_med),
    )


# ----------------------------------------------------------------- gpt-2


def bench_gpt2(
    steps=None,
    warmup=None,
    *,
    batch=None,
    seq=None,
    metric="gpt2_124m_tokens_per_sec",
    remat=False,
) -> dict:
    from tensorflow_examples_tpu.data.memory import train_iterator
    from tensorflow_examples_tpu.train.loop import Trainer
    from tensorflow_examples_tpu.workloads import gpt2

    tpu = BACKEND == "tpu"
    steps = steps if steps is not None else (30 if tpu else 3)
    warmup = warmup if warmup is not None else (5 if tpu else 1)
    batch = batch if batch is not None else (8 if tpu else 1)
    seq = seq if seq is not None else (1024 if tpu else 256)

    cfg = gpt2.Gpt2Config(
        global_batch_size=batch,
        seq_len=seq,
        dropout=0.0,
        precision="bf16",
        attention="flash" if tpu else "xla",
        fused_ce=tpu,
        remat=remat,
        log_every=10**9,
        checkpoint_every=0,
        train_steps=10**6,  # schedule horizon only
        watchdog_secs=0,
    )
    trainer = Trainer(gpt2.make_task(cfg), cfg, mesh=_chip_mesh())
    ds, _ = gpt2.datasets(cfg)
    it = train_iterator(ds, cfg.global_batch_size, seed=0)
    batches = [trainer._put_batch(next(it)) for _ in range(4)]
    flops = _step_flops(trainer, batches[0])
    dts = _time_steps(trainer, batches, steps, warmup)
    dt_med = statistics.median(dts)
    return _result(
        metric,
        _throughput(dts, batch * seq, steps),
        "tokens/sec/chip",
        batch=batch,
        seq=seq,
        model_tflops_per_sec=_model_tflops(flops, steps, dt_med),
    )


def bench_gpt2_long() -> dict:
    """Long-context variant: rematerialized blocks + blockwise attention."""
    tpu = BACKEND == "tpu"
    return bench_gpt2(
        steps=10 if tpu else 2,
        warmup=3 if tpu else 1,
        batch=2 if tpu else 1,
        seq=4096 if tpu else 512,
        metric="gpt2_long4k_tokens_per_sec",
        remat=True,
    )


def bench_gpt2_long16k() -> dict:
    """16k-token single-chip training step: possible
    because the flash kernel streams KV blocks through VMEM (grid over
    KV) instead of holding the whole sequence resident, and remat bounds
    activation memory. CPU fallback uses 1k (interpret-mode kernels)."""
    tpu = BACKEND == "tpu"
    return bench_gpt2(
        steps=4 if tpu else 2,
        warmup=2 if tpu else 1,
        batch=1,
        seq=16384 if tpu else 1024,
        metric="gpt2_long16k_tokens_per_sec",
        remat=True,
    )


def bench_gpt2_decode(
    *,
    prompt_len=None,
    dec=None,
    batch=None,
    seq_len=None,
    metric="gpt2_decode_tokens_per_sec",
) -> dict:
    """KV-cache sampling throughput (the reference's eval.py sampling
    path): prefill ``prompt_len``-token prompts, decode ``dec`` tokens
    per sequence through the static-shape cache, one jitted program.
    Attention runs the flash-decode kernel (ops/decode.py): O(context)
    cache reads per step, not O(max_len)."""
    import jax
    import jax.numpy as jnp

    from tensorflow_examples_tpu.models import transformer
    from tensorflow_examples_tpu.workloads import gpt2

    tpu = BACKEND == "tpu"
    batch = batch if batch is not None else (8 if tpu else 1)
    dec = dec if dec is not None else (128 if tpu else 16)
    prompt_len = prompt_len if prompt_len is not None else (128 if tpu else 16)
    cfg = (
        gpt2.Gpt2Config(
            dropout=0.0, **({"seq_len": seq_len} if seq_len else {})
        )
        if tpu
        else gpt2.Gpt2Config(
            vocab_size=256, seq_len=seq_len or 64, num_layers=2, num_heads=2,
            d_model=64, dropout=0.0,
        )
    )
    model = transformer.Transformer(gpt2.model_config(cfg))
    prompt = jnp.ones((batch, prompt_len), jnp.int32)
    params = model.init({"params": jax.random.PRNGKey(0)}, prompt)["params"]
    if tpu:
        params = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)

    gen = jax.jit(
        lambda p, pr, rng: transformer.generate(
            model, p, pr, num_tokens=dec, rng=rng, temperature=1.0, top_k=40
        )
    )
    rng = jax.random.PRNGKey(1)
    # Analytic fwd FLOPs, hand-counted: XLA cost_analysis counts the
    # decode lax.scan body ONCE (not × trip count), so it can't be used
    # here. Matmuls: 2·(12·L·d²) per token + LM head 2·d·V per scored
    # position; attention: 4·d·n per layer per token attending n keys.
    L, d, V = cfg.num_layers, cfg.d_model, cfg.vocab_size
    t_p = prompt.shape[1]
    mat = 24 * L * d * d
    prefill = t_p * (mat + 2 * d * V) + 4 * d * L * t_p * (t_p + 1) // 2
    decode = dec * (mat + 2 * d * V) + 4 * d * L * (
        dec * t_p + dec * (dec - 1) // 2
    )
    flops = float(batch * (prefill + decode))
    gen(params, prompt, rng).block_until_ready()
    iters = 5 if tpu else 2
    dts = []
    for w in range(WINDOWS):
        t0 = time.perf_counter()
        for i in range(iters):
            out = gen(params, prompt, jax.random.PRNGKey(w * iters + i))
        out.block_until_ready()
        dts.append(time.perf_counter() - t0)
    vals = [iters * batch * dec / dt for dt in dts]
    dt_med = statistics.median(dts)
    return _result(
        metric,
        vals,
        "tokens/sec/chip",
        batch=batch,
        prefill_len=prompt_len,
        decode_len=dec,
        model_tflops_per_sec=_model_tflops(flops, iters, dt_med),
    )


def bench_gpt2_decode_long() -> dict:
    """Long-prefill sampling: prefill 4096 tokens, decode 256, through
    a 4352-slot cache. The naive decode path would read the full static cache every step;
    the flash-decode kernel's scalar-prefetch clamp bounds each step's
    reads to the populated prefix."""
    tpu = BACKEND == "tpu"
    return bench_gpt2_decode(
        prompt_len=4096 if tpu else 48,
        dec=256 if tpu else 8,
        batch=4 if tpu else 1,
        seq_len=4352 if tpu else 64,
        metric="gpt2_decode_long_tokens_per_sec",
    )


def bench_bert() -> dict:
    """BERT-base GLUE fine-tune throughput (examples/sec/chip, seq 128)."""
    from tensorflow_examples_tpu.data.memory import train_iterator
    from tensorflow_examples_tpu.train.loop import Trainer
    from tensorflow_examples_tpu.workloads import bert_glue

    tpu = BACKEND == "tpu"
    cfg = bert_glue.BertGlueConfig(
        global_batch_size=32 if tpu else 4,
        precision="bf16" if tpu else "f32",
        dropout=0.0,
        log_every=10**9,
        checkpoint_every=0,
        eval_every=0,
        train_steps=10**6,
        watchdog_secs=0,
        **({} if tpu else dict(
            seq_len=32, vocab_size=512, num_layers=2, num_heads=2,
            d_model=32, d_ff=64,
        )),
    )
    # steps_per_launch bundling on TPU: the 1.2-1.7 ms/step regime is
    # per-launch dispatch-bound, so the bench measures the framework's
    # bundled loop —
    # the configuration a user would run this workload with. FLOPs come
    # from the single-step program (the scanned body is the same step).
    steps, warmup, bundle = (24, 8, 8) if tpu else (3, 1, 1)
    trainer = Trainer(bert_glue.make_task(cfg), cfg, mesh=_chip_mesh())
    ds, _ = bert_glue.datasets(cfg)
    it = train_iterator(ds, cfg.global_batch_size, seed=0)
    if bundle > 1:
        batches = _bundle_prep(trainer, it, 2, bundle)
        flops = _step_flops(trainer, batches[0], bundle=bundle)
    else:
        batches = [trainer._put_batch(next(it)) for _ in range(2)]
        flops = _step_flops(trainer, batches[0])
    dts = _time_steps(trainer, batches, steps, warmup, bundle=bundle)
    dt_med = statistics.median(dts)
    return _result(
        "bert_base_examples_per_sec_per_chip",
        _throughput(dts, cfg.global_batch_size, steps),
        "examples/sec/chip",
        batch=cfg.global_batch_size,
        seq=cfg.seq_len,
        bundle=bundle,
        model_tflops_per_sec=_model_tflops(flops, steps, dt_med),
    )


def bench_cifar10() -> dict:
    """CIFAR-10 ResNet-20 training throughput (single-device workload)."""
    from tensorflow_examples_tpu.data.memory import train_iterator
    from tensorflow_examples_tpu.data.sources import synthetic_images
    from tensorflow_examples_tpu.train.loop import Trainer
    from tensorflow_examples_tpu.workloads import cifar10

    tpu = BACKEND == "tpu"
    cfg = cifar10.Cifar10Config(
        global_batch_size=128 if tpu else 16,
        precision="bf16" if tpu else "f32",
        log_every=10**9,
        checkpoint_every=0,
        eval_every=0,
        train_steps=10**6,
        watchdog_secs=0,
    )
    # Bundled on TPU: ~1.2 ms/step is dispatch-bound (rel_mfu 0.00044
    # in the round-4 record — the chip idles between launches); see
    # bench_bert for the rationale.
    steps, warmup, bundle = (32, 8, 8) if tpu else (3, 1, 1)
    trainer = Trainer(cifar10.make_task(cfg), cfg, mesh=_chip_mesh())
    ds = synthetic_images(n=2048, shape=(32, 32, 3), num_classes=10, seed=0)
    it = train_iterator(ds, cfg.global_batch_size, seed=0)
    if bundle > 1:
        batches = _bundle_prep(trainer, it, 2, bundle)
        flops = _step_flops(trainer, batches[0], bundle=bundle)
    else:
        batches = [trainer._put_batch(next(it)) for _ in range(4)]
        flops = _step_flops(trainer, batches[0])
    dts = _time_steps(trainer, batches, steps, warmup, bundle=bundle)
    dt_med = statistics.median(dts)
    return _result(
        "cifar10_resnet20_examples_per_sec_per_chip",
        _throughput(dts, cfg.global_batch_size, steps),
        "examples/sec/chip",
        batch=cfg.global_batch_size,
        bundle=bundle,
        model_tflops_per_sec=_model_tflops(flops, steps, dt_med),
    )


# ----------------------------------------------------------------- mnist


def bench_mnist() -> dict:
    from tensorflow_examples_tpu.data.memory import train_iterator
    from tensorflow_examples_tpu.data.sources import synthetic_images
    from tensorflow_examples_tpu.train.loop import Trainer
    from tensorflow_examples_tpu.workloads import mnist

    # Bundled on TPU: at ~0.11 ms/step the launch IS the step cost;
    # ms/step under bundling is launch_time / k (see bench_bert).
    tpu = BACKEND == "tpu"
    steps, warmup, bundle = (200, 24, 8) if tpu else (50, 5, 1)
    cfg = mnist.MnistConfig(
        global_batch_size=256,
        precision="bf16",
        dropout=0.0,
        log_every=10**9,
        checkpoint_every=0,
        watchdog_secs=0,
    )
    ds = synthetic_images(n=4096, shape=(28, 28, 1), num_classes=10, seed=0)
    trainer = Trainer(mnist.make_task(cfg), cfg, mesh=_chip_mesh())
    it = train_iterator(ds, cfg.global_batch_size, seed=0)
    if bundle > 1:
        batches = _bundle_prep(trainer, it, 4, bundle)
        flops = _step_flops(trainer, batches[0], bundle=bundle)
    else:
        batches = [trainer._put_batch(next(it)) for _ in range(8)]
        flops = _step_flops(trainer, batches[0])
    dts = _time_steps(trainer, batches, steps, warmup, bundle=bundle)
    dt_med = statistics.median(dts)
    return _result(
        "mnist_mlp_step_time",
        [dt / steps * 1e3 for dt in dts],
        "ms/step",
        bundle=bundle,
        model_tflops_per_sec=_model_tflops(flops, steps, dt_med),
    )


# ----------------------------------------------------------- collectives


def bench_collectives() -> dict:
    """All-reduce / all-gather bus bandwidth over the device mesh
    (SURVEY.md §5h: replaces the reference stack's NCCL perf tests)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devices = jax.devices()
    n = len(devices)
    mesh = Mesh(np.asarray(devices), ("x",))
    elems = (16 * 2**20) if BACKEND == "tpu" else (2 * 2**20)  # per device
    x = jnp.ones((n * elems,), jnp.float32)
    x = jax.device_put(x, NamedSharding(mesh, P("x")))

    @jax.jit
    def do_psum(x):
        return shard_map(
            lambda v: jax.lax.psum(v, "x"),
            mesh=mesh,
            in_specs=P("x"),
            out_specs=P("x"),
        )(x)

    @jax.jit
    def do_gather(x):
        # Gather then re-slice to the local shard: keeps out_specs P("x")
        # (replication inference fails on degenerate 1-device meshes).
        return shard_map(
            lambda v: jax.lax.all_gather(v, "x", tiled=True)[: v.shape[0]],
            mesh=mesh,
            in_specs=P("x"),
            out_specs=P("x"),
        )(x)

    def timed_windows(f, iters=10):
        f(x).block_until_ready()
        dts = []
        for _ in range(WINDOWS):
            t0 = time.perf_counter()
            for _ in range(iters):
                out = f(x)
            out.block_until_ready()
            dts.append((time.perf_counter() - t0) / iters)
        return dts

    bytes_per_dev = elems * 4
    # Ring-algorithm bus bandwidth (the NCCL convention): payload scaled
    # by 2(n-1)/n for all-reduce, (n-1)/n for all-gather.
    scale_ar = 2 * (n - 1) / n if n > 1 else 1.0
    scale_ag = (n - 1) / n if n > 1 else 1.0
    ar_vals = [
        bytes_per_dev * scale_ar / t / 1e9 for t in timed_windows(do_psum)
    ]
    ag_vals = [
        bytes_per_dev * scale_ag / t / 1e9 for t in timed_windows(do_gather)
    ]
    return _result(
        "allreduce_busbw",
        ar_vals,
        "GB/s",
        n_devices=n,
        allgather_busbw_gbps=round(statistics.median(ag_vals), 2),
        allgather_windows=[round(v, 2) for v in sorted(ag_vals)],
        payload_mb_per_device=bytes_per_dev / 2**20,
    )


# ------------------------------------------------------------------- moe

_MOE_MESH_PROBE = r"""
import collections, json, re
from tensorflow_examples_tpu.core.mesh import MeshConfig, create_mesh
from tensorflow_examples_tpu.data.memory import train_iterator
from tensorflow_examples_tpu.train.loop import Trainer
from tensorflow_examples_tpu.workloads import gpt2
cfg = gpt2.Gpt2Config(
    vocab_size=512, seq_len=128, num_layers=2, num_heads=4, d_model=64,
    dropout=0.0, moe_experts=8, moe_top_k=2, moe_every=1,
    global_batch_size=8, precision="f32", log_every=10**9,
    checkpoint_every=0, watchdog_secs=0,
)
mesh = create_mesh(MeshConfig(data=2, model=4))
trainer = Trainer(gpt2.make_task(cfg, mesh), cfg, mesh=mesh)
ds, _ = gpt2.datasets(cfg)
batch = trainer._put_batch(next(train_iterator(ds, 8, seed=0)))
hlo = trainer._train_step.lower(trainer.state, batch).compile().as_text()
# Definition sites only: a plain substring count also matches operand
# REFERENCES (%all-reduce.12 as an argument) and overcounted ~2-3x in
# rounds 2-3. Non-greedy shape so
# tuple-shaped collectives (lax.all_to_all lowers to one) match.
ops = collections.Counter(
    m.group(1)
    for m in re.finditer(
        r"^\s*(?:ROOT\s+)?%?[\w.\-]+ = (?:.+?) (all-to-all|all-reduce|"
        r"all-gather|reduce-scatter|collective-permute)(?:-start)?\(",
        hlo, re.M,
    )
)
print("MOE_COLLECTIVES " + json.dumps(dict(ops)))
"""


def _moe_mesh_collectives(timeout_s: float = 600.0) -> dict:
    """Compile the MoE train step on an 8-device dp×model CPU mesh in a
    subprocess and count the collectives XLA inserted for expert
    dispatch (EP's comm pattern must be measured,
    not assumed). Subprocess because the mesh needs its own 8-device
    CPU runtime — pinned through the child's ENVIRONMENT, so it never
    reaches for the chip this process holds. Capped by the remaining
    wall budget — the census is a code property, not a perf number, so
    losing it to the budget costs nothing the test suite doesn't
    already cover."""
    timeout_s = min(timeout_s, _remaining() - 45.0)
    if timeout_s < 30.0:
        return {"skipped": "insufficient budget for mesh census"}
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS=os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8",
    )
    try:
        r = subprocess.run(
            [sys.executable, "-c", _MOE_MESH_PROBE],
            capture_output=True,
            text=True,
            timeout=timeout_s,
            cwd=os.path.dirname(os.path.abspath(__file__)),
            env=env,
        )
        for line in r.stdout.splitlines():
            if line.startswith("MOE_COLLECTIVES "):
                return json.loads(line.split(" ", 1)[1])
        return {"error": (r.stderr or r.stdout).strip()[-300:]}
    except subprocess.TimeoutExpired:
        return {"error": f"mesh probe timed out >{timeout_s:.0f}s"}


def moe_bench_config(moe_impl: str = ""):
    """The ONE moe-bench model/workload config, shared with
    tools/moe_diag.py so the diagnosis always times the exact program
    the ``moe_top2_tokens_per_sec`` record measures (a drifted copy
    would attribute the wrong workload)."""
    from tensorflow_examples_tpu.workloads import gpt2

    tpu = BACKEND == "tpu"
    batch = 8 if tpu else 1
    seq = 1024 if tpu else 128
    return gpt2.Gpt2Config(
        global_batch_size=batch,
        seq_len=seq,
        dropout=0.0,
        precision="bf16",
        attention="flash" if tpu else "xla",
        fused_ce=tpu,
        moe_experts=8,
        moe_top_k=2,
        moe_every=2,
        moe_impl=moe_impl,
        log_every=10**9,
        checkpoint_every=0,
        train_steps=10**6,
        watchdog_secs=0,
        **({} if tpu else dict(
            vocab_size=512, num_layers=2, num_heads=4, d_model=64
        )),
    )


def bench_moe() -> dict:
    """MoE GPT-2 training throughput (E=8, top-2, every 2nd block) on
    the chip, with the 8-device-mesh dispatch-collective census
    attached."""
    from tensorflow_examples_tpu.data.memory import train_iterator
    from tensorflow_examples_tpu.train.loop import Trainer
    from tensorflow_examples_tpu.workloads import gpt2

    tpu = BACKEND == "tpu"
    cfg = moe_bench_config()
    batch, seq = cfg.global_batch_size, cfg.seq_len
    steps, warmup = (20, 5) if tpu else (3, 1)
    trainer = Trainer(gpt2.make_task(cfg), cfg, mesh=_chip_mesh())
    ds, _ = gpt2.datasets(cfg)
    it = train_iterator(ds, cfg.global_batch_size, seed=0)
    batches = [trainer._put_batch(next(it)) for _ in range(4)]
    flops = _step_flops(trainer, batches[0])
    dts = _time_steps(trainer, batches, steps, warmup)
    dt_med = statistics.median(dts)
    return _result(
        "moe_top2_tokens_per_sec",
        _throughput(dts, batch * seq, steps),
        "tokens/sec/chip",
        batch=batch,
        seq=seq,
        experts=cfg.moe_experts,
        top_k=cfg.moe_top_k,
        mesh_dispatch_collectives=_moe_mesh_collectives(),
        model_tflops_per_sec=_model_tflops(flops, steps, dt_med),
    )


# ----------------------------------------------------------- decode grid


def bench_decode_grid() -> dict:
    """Single-token flash-decode step time vs cache max_len at a fixed
    short context: with the power-of-two KV-grid
    bucket ladder (ops/decode.py) the step must be ~flat in max_len —
    the headline value is t(32k)/t(4k), ~1.0 when sequencing is
    O(context) and ~8 if it were O(max_len). TPU-only: interpret mode
    would time the Python grid loop, not the chip."""
    import jax
    import jax.numpy as jnp

    from tensorflow_examples_tpu.ops.decode import flash_decode_attention

    if BACKEND != "tpu":
        raise RuntimeError(
            "tpu-only microbench (interpret mode times Python, not the chip)"
        )
    b, h, d, ctx = 8, 12, 64, 256
    iters = 50
    q = jax.random.normal(jax.random.PRNGKey(0), (b, h, 1, d), jnp.bfloat16)
    f = jax.jit(flash_decode_attention)
    per_len = {}
    for max_len in (4096, 16384, 32768):
        k = jax.random.normal(
            jax.random.PRNGKey(1), (b, h, max_len, d), jnp.bfloat16
        )
        v = jax.random.normal(
            jax.random.PRNGKey(2), (b, h, max_len, d), jnp.bfloat16
        )
        ln = jnp.asarray(ctx)
        f(q, k, v, ln).block_until_ready()
        ts = []
        for _ in range(WINDOWS):
            t0 = time.perf_counter()
            for _ in range(iters):
                out = f(q, k, v, ln)
            out.block_until_ready()
            ts.append((time.perf_counter() - t0) / iters * 1e6)
        per_len[max_len] = statistics.median(ts)
    ratios = [per_len[32768] / per_len[4096]]
    return _result(
        "decode_grid_step_time_ratio",
        ratios,
        "x (32k cache / 4k cache, ctx 256)",
        context_len=ctx,
        us_per_step={str(k_): round(v_, 1) for k_, v_ in per_len.items()},
    )


# ------------------------------------------------------------------ main

BENCHES = {
    "resnet50": bench_resnet50,
    "resnet50_input": bench_resnet50_input,
    "gpt2": bench_gpt2,
    "gpt2_long": bench_gpt2_long,
    "gpt2_long16k": bench_gpt2_long16k,
    "gpt2_decode": bench_gpt2_decode,
    "gpt2_decode_long": bench_gpt2_decode_long,
    "bert": bench_bert,
    "cifar10": bench_cifar10,
    "mnist": bench_mnist,
    "collectives": bench_collectives,
    "moe": bench_moe,
    "decode_grid": bench_decode_grid,
}

# Headline-first order for --bench=all.
ALL_ORDER = [
    "resnet50",
    "resnet50_input",
    "gpt2",
    "gpt2_long",
    "gpt2_long16k",
    "gpt2_decode",
    "gpt2_decode_long",
    "bert",
    "cifar10",
    "mnist",
    "collectives",
    "moe",
    "decode_grid",
]


# Conservative per-bench wall estimates (compile + windows, COLD compile
# cache) used only to ORDER execution cheapest-first (the skip decision
# is a fixed remaining-time threshold in run_all); a completed bench
# records its true cost in "bench_seconds".
_EST_SECONDS = {
    "cpu": {
        "resnet50": 80, "resnet50_input": 150, "gpt2": 75, "gpt2_long": 90,
        "gpt2_long16k": 120, "gpt2_decode": 60, "gpt2_decode_long": 60,
        "bert": 50, "cifar10": 70, "mnist": 45, "collectives": 60,
        "moe": 180, "decode_grid": 1,
    },
    "tpu": {
        "resnet50": 90, "resnet50_input": 150, "gpt2": 75, "gpt2_long": 75,
        "gpt2_long16k": 90, "gpt2_decode": 75, "gpt2_decode_long": 75,
        "bert": 60, "cifar10": 60, "mnist": 60, "collectives": 45,
        "moe": 180, "decode_grid": 90,
    },
}


def run_bench(name: str) -> dict:
    """Probe the rig immediately before the bench, run it, attach the
    drift-cancelled rel_mfu (see module docstring)."""
    global _IN_FLIGHT
    _IN_FLIGHT = name
    t0 = time.perf_counter()
    try:
        probe = _probe_quick()
        r = BENCHES[name]()
    except Exception as e:
        # Recorded, not swallowed: the sweep goes on (what completes is
        # evidence) and main() exits non-zero for any "error" record.
        return {"metric": name, "bench": name, "error": f"{type(e).__name__}: {e}"}
    r["bench"] = name
    r["probe_tflops_at_bench"] = round(probe, 2)
    r["bench_seconds"] = round(time.perf_counter() - t0, 1)
    r["probe_launch_us_at_bench"] = round(_probe_launch_us(), 2)
    mt = r.get("model_tflops_per_sec")
    if mt:
        r["rel_mfu"] = round(mt / probe, 5)
        mfu_floor = REL_MFU_FLOORS.get(BACKEND, {}).get(r["metric"])
        if mfu_floor:
            r["rel_mfu_vs_floor"] = round(r["rel_mfu"] / mfu_floor, 4)
    return r


def run_all() -> None:
    """Run the sweep cheapest-first (estimated cold-compile cost), so
    the budget buys the maximum number of completed benches; _assemble
    re-sorts the record headline-first. A bench is attempted whenever
    >60 s remain — over-running is safe (the watchdog emits everything
    completed so far) and execution is cost-ascending, so attempting
    strictly dominates skipping. Appends to module result state so the
    watchdog can emit a partial record at any instant."""
    global _IN_FLIGHT
    est = _EST_SECONDS.get(BACKEND, {})
    for name in sorted(ALL_ORDER, key=lambda n: est.get(n, 60)):
        if _remaining() < 60:
            # Recorded as truncated by _assemble's planned-minus-done
            # sweep accounting; just log the decision here.
            print(
                f"bench: skipping {name} ({_remaining():.0f}s left)",
                file=sys.stderr,
            )
            continue
        _RESULTS.append(run_bench(name))
        # Cleared only after the result is recorded: a watchdog firing
        # mid-bench must see it as in-flight OR completed, never neither.
        _IN_FLIGHT = None


def main() -> int:
    global BACKEND, _DEADLINE, _IN_FLIGHT
    which = "all"

    def _parse_budget(s: str, fallback: float = 540.0) -> float:
        try:
            return float(s)
        except ValueError:
            print(f"bench: bad budget {s!r}; using {fallback}", file=sys.stderr)
            return fallback

    budget = _parse_budget(os.environ.get("BENCH_BUDGET_S", "540"))
    for a in sys.argv[1:]:
        if a.startswith("--bench="):
            which = a.split("=", 1)[1]
        elif a.startswith("--budget="):
            budget = _parse_budget(a.split("=", 1)[1], budget)
    known = set(BENCHES) | {"all"}
    if which not in known:
        _emit({"error": f"unknown --bench={which}", "known": sorted(known)})
        return 2
    _require_tpu()  # exits non-zero off the TPU, before anything is timed
    BACKEND = "tpu"
    _META["backend"] = BACKEND
    if which == "all":
        # Before anything that can block: a watchdog firing pre-sweep
        # must still list the whole plan.
        _SWEEP_PLANNED.extend(ALL_ORDER)
    watchdog = None
    if budget > 0:
        _DEADLINE = time.monotonic() + budget
        _META["budget_s"] = budget
        # Backstop fires shortly before the budget so the emit beats an
        # outer `timeout <budget+60>`; daemon thread survives a main
        # thread stuck inside a native compile.
        watchdog = threading.Timer(max(budget - 15.0, 5.0), _watchdog_fire)
        watchdog.daemon = True
        watchdog.start()
    try:
        fp_pre = round(fingerprint_tflops(), 2)
        # Back-compat scalar stamp: the pre-sweep median.
        _META["fingerprint_tflops_pre"] = _META["fingerprint_tflops"] = fp_pre
        _META["fingerprint_launch_us_pre"] = round(_probe_launch_us(), 2)
        if which == "all":
            run_all()
        else:
            _RESULTS.append(run_bench(which))
            _IN_FLIGHT = None
        _META["fingerprint_tflops_post"] = round(fingerprint_tflops(), 2)
        _META["fingerprint_launch_us_post"] = round(_probe_launch_us(), 2)
    except Exception as e:
        # Keyed so it can never clobber a completed headline's "metric"
        # (out.update(_META) in _assemble); the line below still prints
        # what completed, and the exit code says the sweep broke.
        _META["sweep_error"] = f"{type(e).__name__}: {e}"
    finally:
        if watchdog is not None:
            watchdog.cancel()
        _emit()
    failed = "sweep_error" in _META or any("error" in r for r in _RESULTS)
    return 1 if failed or not _RESULTS else 0


if __name__ == "__main__":
    sys.exit(main())
