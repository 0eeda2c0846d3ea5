"""Shared CLI runner for example entrypoints.

Each ``examples/<name>/train.py`` in the reference was a full copy-pasted
script; here it is a thin shim over this module, preserving the CLI
contract ``python <example>/train.py --device=tpu --flag=...``
(BASELINE.json:north_star) while the actual loop lives in the framework.

A workload module plugs in via a small protocol:
  - ``make_task(cfg) -> Task``          (required)
  - ``datasets(cfg) -> (train, eval)``  (required; InMemoryDataset pair or
                                         iterator factories)
  - ``eval_dataset(cfg) -> eval``       (optional; lets eval.py skip
                                         loading the train split)
  - ``train_augment(cfg) -> fn | None`` (optional)
  - ``make_train_iter(cfg, start) / make_eval_iter(cfg)`` (optional full
     override for streaming pipelines like ImageNet)
"""

from __future__ import annotations

from absl import app, logging

from tensorflow_examples_tpu.core import device, distributed
from tensorflow_examples_tpu.core.mesh import create_mesh
from tensorflow_examples_tpu.data.memory import eval_batches, train_iterator
from tensorflow_examples_tpu.train.checkpoint import CheckpointManager
from tensorflow_examples_tpu.train.config import (
    apply_device_flag,
    config_from_flags,
    define_flags_from_config,
)
from tensorflow_examples_tpu.train.loop import Trainer


def _setup(workload, default_cfg):
    logging.set_verbosity(logging.INFO)
    cfg = config_from_flags(default_cfg)
    apply_device_flag(cfg.device, debug_nans=cfg.debug_nans)
    from tensorflow_examples_tpu.utils.diagnostics import install_crash_handlers
    from tensorflow_examples_tpu.utils.faults import configure_io_retry

    install_crash_handlers(cfg.workdir)
    # Flaky-input-store policy for every file reader (data/sources.py).
    configure_io_retry(cfg.io_retries, cfg.io_backoff_secs)
    device.enable_compile_cache()
    distributed.initialize()
    # After initialize(): this is the first call that touches the
    # backend. Logs the one line that names the device.
    device.require_device(cfg.device)
    return cfg


def _build_trainer(workload, cfg):
    """Create the mesh once and hand it to both the task and the Trainer
    (models that pin activation shardings or run shard_map'd attention
    need the concrete mesh at trace time). With ``--sharding_config``
    the mesh comes from the config file (docs/sharding.md) — the one
    spec that also drives serving — and the Trainer inherits its rules
    and ZeRO-1 policy too."""
    sharding = None
    if getattr(cfg, "sharding_config", ""):
        from tensorflow_examples_tpu.sharding import ShardingConfig

        sharding = ShardingConfig.load(cfg.sharding_config)
        mesh = sharding.build_mesh()
    else:
        mesh = create_mesh(cfg.mesh_config())
    return Trainer(
        workload.make_task(cfg, mesh=mesh), cfg, mesh=mesh,
        sharding=sharding,
    )


def _host_eval_batches(test_ds, eval_bs):
    """Per-host eval slice: host h evaluates rows h::P at batch B/P.

    Matches Trainer.evaluate's multi-process default (per_host=True):
    hosts read disjoint shards, the jitted step's global weighted sums
    merge them, padding equalizes differing per-host batch counts.
    Single-process: the identity (full set, full batch size).
    """
    import jax

    from tensorflow_examples_tpu.data.memory import InMemoryDataset

    nproc = jax.process_count()
    if nproc == 1:
        return eval_batches(test_ds, eval_bs)
    local = InMemoryDataset(
        {k: v[jax.process_index()::nproc] for k, v in test_ds.arrays.items()}
    )
    return eval_batches(local, max(eval_bs // nproc, 1))


def _iterators(workload, cfg):
    """Resolve (train_iter_fn(start), eval_iter_fn()) from the protocol."""
    eval_bs = cfg.eval_batch_size or cfg.global_batch_size
    if hasattr(workload, "make_train_iter"):
        train_fn = lambda start: workload.make_train_iter(cfg, start)
        eval_fn = (
            (lambda: workload.make_eval_iter(cfg))
            if hasattr(workload, "make_eval_iter")
            else None
        )
        local = getattr(workload, "train_iter_is_per_host", lambda c: False)(cfg)
        return train_fn, eval_fn, local
    train_ds, test_ds = workload.datasets(cfg)
    augment = (
        workload.train_augment(cfg) if hasattr(workload, "train_augment") else None
    )
    train_fn = lambda start: train_iterator(
        train_ds,
        cfg.global_batch_size,
        seed=cfg.seed,
        start_step=start,
        augment=augment,
    )
    eval_fn = lambda: _host_eval_batches(test_ds, eval_bs)
    return train_fn, eval_fn, False  # in-memory iterators are global-view


def _eval_iterator(workload, cfg):
    """Eval-only resolver: never loads the training split."""
    eval_bs = cfg.eval_batch_size or cfg.global_batch_size
    if hasattr(workload, "make_eval_iter"):
        return lambda: workload.make_eval_iter(cfg)
    if hasattr(workload, "eval_dataset"):
        test_ds = workload.eval_dataset(cfg)
    elif hasattr(workload, "make_train_iter"):
        return None
    else:
        _, test_ds = workload.datasets(cfg)
    return lambda: _host_eval_batches(test_ds, eval_bs)


def train_main(workload, default_cfg):
    """Build the absl main() for a workload's train.py."""
    define_flags_from_config(default_cfg)

    def main(argv):
        del argv
        cfg = _setup(workload, default_cfg)
        train_fn, eval_fn, local = _iterators(workload, cfg)
        trainer = _build_trainer(workload, cfg)
        metrics = trainer.fit(
            train_fn, eval_iter_fn=eval_fn, local_batches=local
        )
        print({k: round(v, 4) for k, v in metrics.items()})

    return main


def eval_main(workload, default_cfg):
    """Build the absl main() for a workload's eval.py."""
    define_flags_from_config(default_cfg)

    def main(argv):
        del argv
        cfg = _setup(workload, default_cfg)
        if not cfg.workdir:
            raise app.UsageError("--workdir is required for eval")
        eval_fn = _eval_iterator(workload, cfg)
        if eval_fn is None:
            raise app.UsageError(
                f"workload {workload.__name__} defines no eval pipeline"
            )
        trainer = _build_trainer(workload, cfg)
        restored = CheckpointManager(cfg.workdir).restore_latest(trainer.state)
        if restored is None:
            raise SystemExit(f"no checkpoint under {cfg.workdir}")
        trainer.state = restored[0]
        metrics = trainer.evaluate(eval_fn())
        print({k: round(v, 4) for k, v in metrics.items()})

    return main
