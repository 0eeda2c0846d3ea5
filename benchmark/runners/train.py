"""Runner of a training cell: ``Trainer.fit`` as the CLI builds it.

The trainer and its iterators come from ``train/cli.py`` (the
functions ``train_main`` calls), with the program's config at its
defaults except the sizes the configuration file maps and the seed.
Two facts of ``fit`` decide the timing: its batches are fetched by a
prefetch thread two ahead of the step, and it waits for the device only
at a log flush and at its last step. So the window opens and closes on
a device sync: a warm-up ``fit`` and then one timed ``fit(num_steps=N)``
on the same Trainer, both ending in the loop's own final flush, N
chosen from the warm-up's rate to last ``--seconds``. The rate is all
N steps' tokens over all of that time, fit's own start and end
included (telemetry set-up, a prefetch thread, the final line; no
checkpoint and no evaluation, because the defaults ``workdir=""`` and
no evaluation iterator make none).
"""

from __future__ import annotations

import dataclasses
import importlib
import time

import numpy as np

from benchmark import peaks as peaks_mod
from benchmark import record, spec, trace_reduce

# The trainer's compiled programs: one of these compiling inside the
# window is an error; anything else is counted and named.
PROGRAMS = ("train_step", "eval_step", "bundled")


def run(ctx: record.Context) -> record.Run:
    import jax

    from tensorflow_examples_tpu.train import cli

    cell, mix = ctx.cell, ctx.cell.traffic
    config = cell.config
    workload = importlib.import_module(config["program"]["workload"])
    # The program bakes ``cfg.seed`` into the compiled step (the dropout
    # key is a closed-over constant), so a new seed is a new program and
    # 77 s of compilation (my chip run, PR 23). The step is therefore
    # built at the program's default seed, and ``--seed`` gives what the
    # contract asks of it: the weights and the order of the batches.
    pcfg = spec.program_config(config, **mix.get("program_fields", {}))
    seeded = dataclasses.replace(pcfg, seed=spec.fold_seed(ctx.seed))
    train_fn, _, local = cli._iterators(workload, seeded)
    trainer = cli._build_trainer(workload, pcfg)
    trainer.config = seeded
    # jit(make_state)(PRNGKey(seed)): the key is an argument. The leaves go
    # into the first state's tree, whose static optimizer the step was built on.
    structure = jax.tree.structure(trainer.state)
    trainer.state = None  # freed first: two states at once would be the run's memory peak
    trainer.state = jax.tree.unflatten(structure, jax.tree.leaves(trainer._init_state()))
    trainer.config = pcfg
    n_params = int(sum(x.size for x in jax.tree.leaves(trainer.state.params)))
    tokens_per_step = int(pcfg.global_batch_size) * int(pcfg.seq_len)

    def fit(n: int) -> tuple[dict, float]:
        t0 = time.perf_counter()
        last = trainer.fit(
            train_fn, num_steps=int(trainer.state.step) + n, local_batches=local
        )
        return last, time.perf_counter() - t0

    # Warm-up: the first step compiles (or reads the cache); the second
    # fit runs at speed and gives the rate N is chosen from.
    _, warmup_s = fit(int(mix.get("first_steps", 2)))
    rate_steps = int(mix.get("rate_steps", 8))
    warm_last, dt = fit(rate_steps)
    steps_per_s = rate_steps / dt
    correct, detail = _check_loss(ctx, trainer, pcfg)

    n = max(1, int(round(steps_per_s * ctx.seconds)))
    t_open = time.perf_counter()
    last, window_s = fit(n)
    t_close = t_open + window_s
    compiled = ctx.compiles.between(t_open, t_close)

    loss0, loss1 = float(warm_last["loss"]), float(last["loss"])
    detail.update(loss_before=loss0, loss_after=loss1)
    falls = np.isfinite(loss1) and loss1 < loss0
    if ctx.seconds >= float(mix.get("loss_falls_min_seconds", 0)):
        correct = correct and bool(falls)
    bad = [c for c in compiled if any(p in c for p in PROGRAMS)]
    if bad:
        raise RuntimeError(f"the trainer's own programs compiled inside the window: {bad}")

    out = record.Run(
        cell=cell,
        setup_s=t_open - ctx.t_start,
        warmup_s=warmup_s,
        window_s=window_s,
        attempted=n, failed=0,
        correct=bool(correct), correct_detail=detail,
        train={"steps": n, "tokens_per_step": tokens_per_step,
               "seq_len": int(pcfg.seq_len), "batch": int(pcfg.global_batch_size)},
        model={"n_params": n_params, "chips": cell.chips,
               "heads": int(pcfg.num_heads), "head_dim": int(pcfg.d_model) // int(pcfg.num_heads),
               "n_layer": int(pcfg.num_layers)},
        compiles_in_window=compiled,
        notes={"steps": n, "warmup_steps_per_s": steps_per_s},
    )
    out.peaks = peaks_mod.peaks_of_this_device()
    if ctx.trace:
        out.trace = _traced_fit(ctx, trainer, pcfg, fit, mix)
    return out


def _check_loss(ctx, trainer, pcfg):
    """The program's loss (its evaluation step: bf16 compute, the flash
    kernel, the fused cross-entropy, no dropout) on a seeded batch of
    two sequences, against the plain float32 reference on the same
    parameters."""
    import jax
    import jax.numpy as jnp

    config = ctx.cell.config
    ref = spec.reference(config["reference"])
    rng = np.random.default_rng(np.random.SeedSequence([int(ctx.seed), 11]))
    rows = max(2, int(np.prod([trainer.mesh.shape[a] for a in trainer.mesh.axis_names])))
    tokens = rng.integers(0, pcfg.vocab_size, (rows, pcfg.seq_len + 1)).astype(np.int32)
    got = float(trainer.evaluate(iter([{"tokens": tokens}]))["nll"])
    want = float(
        jax.jit(ref.loss, static_argnames=("n_layer",))(
            trainer.state.params, jnp.asarray(tokens), n_layer=int(pcfg.num_layers)
        )
    )
    tol = float(config["correct"]["train_loss_abs"])
    ok = abs(got - want) <= tol
    return ok, {"loss_program": got, "loss_reference": want, "loss_tolerance": tol}


def _traced_fit(ctx, trainer, pcfg, fit, mix):
    """A short fit of its own under the program's profiler window
    (``profile_start_step``/``profile_num_steps``): the trace holds the
    loop's ``StepTraceAnnotation``s and the device's own timeline."""
    lead, steps = int(mix.get("trace_lead_steps", 4)), int(mix.get("trace_steps", 10))
    trainer.config = dataclasses.replace(
        pcfg, profile_start_step=lead, profile_num_steps=steps, profile_dir=ctx.trace_dir
    )
    try:
        fit(lead + steps + 2)
    finally:
        trainer.config = pcfg
    return trace_reduce.reduce_trace(ctx.trace_dir)
