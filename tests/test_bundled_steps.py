"""steps_per_launch (bundled train steps): K steps per device launch
via lax.scan — the TPU-native equivalent of the reference lineage's
Keras ``steps_per_execution`` (SURVEY.md §3(1) hot loop; the dispatch-
bound regime of the small workloads is the motivation).

Parity contract under test: K scanned steps == K separate launches —
same RNG stream (keyed off state.step), same optimizer sequence
(incl. optax.MultiSteps grad accumulation) — so the bundled path may
only change WALL TIME, never the training trajectory.
"""

import numpy as np
import pytest

from tensorflow_examples_tpu.data.memory import train_iterator
from tensorflow_examples_tpu.data.prefetch import bundle_batches
from tensorflow_examples_tpu.data.sources import synthetic_images
from tensorflow_examples_tpu.train.loop import Trainer
from tensorflow_examples_tpu.workloads import mnist


def tiny_cfg(**kw):
    defaults = dict(
        device="cpu",
        global_batch_size=32,
        train_steps=8,
        log_every=8,
        learning_rate=1e-2,
        hidden=16,
        num_layers=1,
        dropout=0.0,
        precision="f32",
        checkpoint_every=0,
        workdir="",
    )
    defaults.update(kw)
    return mnist.MnistConfig(**defaults)


def _data(n=256):
    return synthetic_images(n=n, shape=(28, 28, 1), num_classes=10, seed=0)


def _params_vec(state):
    import jax

    return np.concatenate(
        [np.ravel(np.asarray(x)) for x in jax.tree.leaves(state.params)]
    )


def _run(cfg):
    trainer = Trainer(mnist.make_task(cfg), cfg)
    ds = _data()
    metrics = trainer.fit(
        train_iterator(ds, cfg.global_batch_size, seed=0),
        num_steps=cfg.train_steps,
    )
    return trainer, metrics


class TestBundledSteps:
    def test_bundle_matches_unbundled(self, devices):
        """8 steps as 2 launches of 4 == 8 launches of 1: identical final
        params (same data, same rng-by-step, same update sequence) and
        the same window-mean loss."""
        t1, m1 = _run(tiny_cfg())
        t4, m4 = _run(tiny_cfg(steps_per_launch=4))
        assert int(t1.state.step) == int(t4.state.step) == 8
        np.testing.assert_allclose(
            _params_vec(t1.state), _params_vec(t4.state), rtol=2e-5, atol=2e-6
        )
        assert abs(m1["loss"] - m4["loss"]) < 1e-4, (m1["loss"], m4["loss"])

    def test_bundle_with_grad_accum(self, devices):
        """optax.MultiSteps micro-steps tick per scan iteration: bundled
        and unbundled runs with grad_accum_steps=2 stay in lockstep."""
        t1, _ = _run(tiny_cfg(grad_accum_steps=2))
        t4, _ = _run(tiny_cfg(grad_accum_steps=2, steps_per_launch=4))
        np.testing.assert_allclose(
            _params_vec(t1.state), _params_vec(t4.state), rtol=2e-5, atol=2e-6
        )

    def test_cadence_validation(self, devices):
        cfg = tiny_cfg(steps_per_launch=3)  # 8 % 3 != 0
        trainer = Trainer(mnist.make_task(cfg), cfg)
        with pytest.raises(ValueError, match="steps_per_launch"):
            trainer.fit(
                train_iterator(_data(), cfg.global_batch_size, seed=0),
                num_steps=cfg.train_steps,
            )

    def test_resume_phase_validation(self, devices):
        """A k-unaligned resume point (checkpoint from an unbundled run)
        must be rejected even when the remaining SPAN divides by k —
        cadences fire on (step+1) % cadence and step+1 only visits
        start_step + i*k."""
        cfg = tiny_cfg(steps_per_launch=4, train_steps=14, log_every=0)
        trainer = Trainer(mnist.make_task(cfg), cfg)
        trainer.state = trainer.state.replace(step=6)  # span 8 % 4 == 0
        with pytest.raises(ValueError, match="start step"):
            trainer.fit(
                train_iterator(_data(), cfg.global_batch_size, seed=0),
                num_steps=cfg.train_steps,
            )

    def test_profile_trace_is_one_shot(self, devices, monkeypatch):
        """The profile window (steps ~10-20) captures exactly once; the
        chunked loop must not re-arm the trace after it stops (a re-arm
        would sync + restart the profiler every step for the rest of
        the run)."""
        import jax

        calls = {"start": 0, "stop": 0}
        monkeypatch.setattr(
            jax.profiler,
            "start_trace",
            lambda *a, **k: calls.__setitem__("start", calls["start"] + 1),
        )
        monkeypatch.setattr(
            jax.profiler,
            "stop_trace",
            lambda: calls.__setitem__("stop", calls["stop"] + 1),
        )
        cfg = tiny_cfg(train_steps=40, log_every=40, profile=True)
        _run(cfg)
        assert calls == {"start": 1, "stop": 1}, calls

    def test_checkpoint_at_bundle_boundary(self, devices, tmp_path):
        cfg = tiny_cfg(
            steps_per_launch=4,
            checkpoint_every=4,
            workdir=str(tmp_path),
            train_steps=8,
        )
        _run(cfg)
        from tensorflow_examples_tpu.train.checkpoint import CheckpointManager

        cfg2 = tiny_cfg(workdir=str(tmp_path))
        t2 = Trainer(mnist.make_task(cfg2), cfg2)
        restored = CheckpointManager(str(tmp_path)).restore_latest(t2.state)
        assert restored is not None and int(restored[1]) == 8


class TestBundledPipeline:
    def test_bundle_over_pp_step_matches_unbundled(self, devices):
        """lax.scan OVER the 1F1B pipeline step — a scan whose body is
        itself a shard_map'd scheduled program, the riskiest
        steps_per_launch composition — must reproduce the unbundled
        trajectory."""
        import jax

        from tensorflow_examples_tpu.core.mesh import MeshConfig, create_mesh
        from tensorflow_examples_tpu.workloads import gpt2

        def run(k):
            cfg = gpt2.Gpt2Config(
                vocab_size=64, seq_len=16, num_layers=2, num_heads=4,
                d_model=32, dropout=0.0, attention="xla",
                global_batch_size=16, train_steps=4, warmup_steps=1,
                learning_rate=3e-3, log_every=4, checkpoint_every=0,
                eval_every=0, precision="f32", num_microbatches=2,
                steps_per_launch=k,
            )
            mesh = create_mesh(MeshConfig(data=4, pipe=2))
            task = gpt2.make_task(cfg, mesh=mesh)
            trainer = Trainer(task, cfg, mesh=mesh)
            ds, _ = gpt2.datasets(cfg)
            m = trainer.fit(
                train_iterator(ds, cfg.global_batch_size, seed=0),
                num_steps=cfg.train_steps,
            )
            vec = np.concatenate(
                [
                    np.ravel(np.asarray(x))
                    for x in jax.tree.leaves(trainer.state.params)
                ]
            )
            return m["loss"], vec

        loss1, p1 = run(1)
        loss2, p2 = run(2)
        assert abs(loss1 - loss2) < 1e-4, (loss1, loss2)
        np.testing.assert_allclose(p1, p2, rtol=2e-5, atol=2e-6)


class TestBundleBatches:
    def test_stacks_k_batches(self):
        it = iter([{"x": np.full((2, 3), i)} for i in range(6)])
        out = list(bundle_batches(it, 3))
        assert len(out) == 2
        assert out[0]["x"].shape == (3, 2, 3)
        assert out[1]["x"][0, 0, 0] == 3

    def test_partial_bundle_raises(self):
        it = iter([{"x": np.zeros(2)} for _ in range(5)])
        gen = bundle_batches(it, 3)
        next(gen)
        with pytest.raises(ValueError, match="mid-bundle"):
            next(gen)

    def test_clean_exhaustion(self):
        assert list(bundle_batches(iter([]), 4)) == []
