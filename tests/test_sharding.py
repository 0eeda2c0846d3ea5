"""Unified sharding subsystem (ISSUE 7): one ShardingConfig drives 2-D
GSPMD training, ZeRO-1 optimizer sharding, reshardable checkpoints, and
sharded serving.

The load-bearing claims, each pinned here:

* a GPT-2 step on a (2,2) or (4,2) CPU mesh matches the 1-device loss
  trajectory within f32 reduction-order tolerance;
* a checkpoint written on an 8-device mesh restores BITWISE-identically
  onto 1 device and onto a differently shaped 2-D mesh, while a
  rules-table drift fails with a named ShardingMismatchError;
* ZeRO-1 cuts measured per-device optimizer bytes ≥ 4x on an 8-way
  batch mesh without changing the math;
* the serving engine placed by the same config keeps batched output
  token-identical to the unbatched reference with zero post-warmup
  recompiles.
"""

import dataclasses
import json
import os
import sys

import numpy as np
import pytest

from tensorflow_examples_tpu.core.mesh import AxisNames
from tensorflow_examples_tpu.models import transformer
from tensorflow_examples_tpu.sharding import (
    ResolvedSharding,
    ShardingConfig,
    ShardingMismatchError,
    resolve_params,
)
from tensorflow_examples_tpu.sharding.config import (
    rules_from_json,
    rules_to_json,
    spec_from_json,
    spec_to_json,
)
from tensorflow_examples_tpu.telemetry import schema
from tensorflow_examples_tpu.train.loop import Trainer
from tensorflow_examples_tpu.workloads import gpt2

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))


def tiny_cfg(**kw):
    base = dict(
        vocab_size=64,
        seq_len=16,
        num_layers=2,
        num_heads=4,
        d_model=32,
        dropout=0.0,
        attention="xla",
        global_batch_size=16,
        train_steps=3,
        warmup_steps=5,
        learning_rate=3e-3,
        log_every=10,
        checkpoint_every=0,
        eval_every=0,
        precision="f32",
        watchdog_secs=0,
    )
    base.update(kw)
    return gpt2.Gpt2Config(**base)


def gpt2_sharding(mesh: dict, **kw) -> ShardingConfig:
    """A config with the GPT-2 rules EMBEDDED (serialized round-trip),
    so training exercises the config's table, not the task fallback."""
    return ShardingConfig(
        mesh=mesh, rules=rules_to_json(transformer.GPT2_RULES), **kw
    )


def make_trainer(cfg, sc: ShardingConfig) -> Trainer:
    mesh = sc.build_mesh()
    task = gpt2.make_task(cfg, mesh=mesh)
    return Trainer(task, cfg, mesh=mesh, sharding=sc)


def run_steps(trainer: Trainer, cfg, n: int) -> list[float]:
    """n deterministic train steps off one synthetic token stream."""
    import jax

    rng = np.random.RandomState(0)
    losses = []
    state = trainer.state
    for _ in range(n):
        batch = {
            "tokens": rng.randint(
                0, cfg.vocab_size, size=(cfg.global_batch_size,
                                         cfg.seq_len + 1)
            ).astype(np.int32)
        }
        state, metrics = trainer._train_step(
            state, trainer._put_batch(batch)
        )
        losses.append(float(metrics["loss"]))
    trainer.state = state
    del jax
    return losses


# ----------------------------------------------------------- config unit


class TestShardingConfig:
    def test_spec_json_roundtrip(self):
        from jax.sharding import PartitionSpec as P

        for spec in (P(), P("data"), P(None, "model"),
                     P(("data", "fsdp"), None, "model")):
            assert spec_from_json(spec_to_json(spec)) == spec

    def test_rules_roundtrip_resolves_identically(self):
        rt = rules_from_json(rules_to_json(transformer.GPT2_RULES))
        for path in (
            "h_0/attn/qkv/kernel", "h_3/mlp_fc/kernel",
            "h_1/mlp_proj/bias", "wte/embedding", "ln_f/scale",
        ):
            assert rt.spec_for(path) == transformer.GPT2_RULES.spec_for(
                path
            ), path

    def test_json_dict_roundtrip(self):
        sc = gpt2_sharding({"data": 2, "model": 4}, zero1=True)
        rt = ShardingConfig.from_json_dict(sc.to_json_dict())
        assert rt == sc

    def test_save_load_with_extra(self, tmp_path):
        sc = gpt2_sharding({"data": 2, "model": 2})
        path = str(tmp_path / "sharding.json")
        sc.save(path, extra={"param_sharding_digest": "abc123"})
        loaded, extra = ShardingConfig.load_with_extra(path)
        assert loaded == sc
        assert extra["param_sharding_digest"] == "abc123"
        # A bare config object (no wrapper) also loads.
        with open(path, "w") as f:
            json.dump(sc.to_json_dict(), f)
        assert ShardingConfig.load(path) == sc

    def test_unknown_axis_rejected(self):
        with pytest.raises(ValueError, match="unknown mesh axes"):
            ShardingConfig(mesh={"banana": 2})
        with pytest.raises(ValueError, match="positive int"):
            ShardingConfig(mesh={"model": 0})
        with pytest.raises(ValueError, match="unknown sharding config"):
            ShardingConfig.from_json_dict({"mesh": {}, "nope": 1})

    def test_build_mesh_uses_prefix_of_devices(self, devices):
        mesh = ShardingConfig(mesh={"data": 2, "model": 2}).build_mesh()
        assert mesh.devices.size == 4
        one = ShardingConfig(mesh={"data": 1}).build_mesh()
        assert one.devices.size == 1
        full = ShardingConfig().build_mesh()  # data=-1: all devices
        assert full.devices.size == 8
        with pytest.raises(ValueError, match="needs 16 devices"):
            ShardingConfig(mesh={"data": 4, "model": 4}).build_mesh()

    def test_batch_sharding_follows_config_axes(self):
        from jax.sharding import PartitionSpec as P

        sc = ShardingConfig(mesh={"data": 2, "model": 2})
        mesh = sc.build_mesh()
        assert sc.batch_sharding(mesh).spec == P(("data",))
        assert sc.bundle_sharding(mesh).spec == P(None, ("data",))


# ---------------------------------------------------------- resolve unit


class TestResolve:
    def _abstract_params(self, cfg):
        import jax

        model = transformer.Transformer(gpt2.model_config(cfg))
        return jax.eval_shape(
            lambda r: model.init({"params": r},
                                 np.zeros((1, cfg.seq_len), np.int32)),
            jax.random.PRNGKey(0),
        )["params"]

    def test_digest_is_mesh_shape_independent(self):
        cfg = tiny_cfg()
        params = self._abstract_params(cfg)
        rules = transformer.GPT2_RULES
        d = {
            name: resolve_params(
                params, gpt2_sharding(mesh).build_mesh(), rules
            ).digest()
            for name, mesh in (
                ("2x2", {"data": 2, "model": 2}),
                ("4x2", {"data": 4, "model": 2}),
                ("1x1", {"data": 1}),
            )
        }
        assert d["2x2"] == d["4x2"] == d["1x1"]
        # A rules change moves the digest.
        from tensorflow_examples_tpu.core.sharding import ShardingRules

        other = resolve_params(
            params,
            gpt2_sharding({"data": 2, "model": 2}).build_mesh(),
            ShardingRules(),
        ).digest()
        assert other != d["2x2"]

    def test_byte_totals_split_replicated_vs_sharded(self):
        cfg = tiny_cfg()
        params = self._abstract_params(cfg)
        mesh = gpt2_sharding({"data": 1, "model": 2}).build_mesh()
        resolved = resolve_params(params, mesh, transformer.GPT2_RULES)
        totals = resolved.byte_totals()
        assert totals["sharded_per_device_bytes"] > 0
        assert totals["replicated_per_device_bytes"] > 0  # embeddings
        assert (
            totals["per_device_bytes"]
            == totals["sharded_per_device_bytes"]
            + totals["replicated_per_device_bytes"]
        )
        assert totals["per_device_bytes"] < totals["global_bytes"]
        # The table renders every row + the totals line.
        table = resolved.table_str()
        assert "wte/embedding" in table and "replicated" in table
        # On a 1-device mesh everything is (locally) replicated.
        mesh1 = ShardingConfig(mesh={"data": 1}).build_mesh()
        r1 = resolve_params(params, mesh1, transformer.GPT2_RULES)
        t1 = r1.byte_totals()
        assert t1["per_device_bytes"] == t1["global_bytes"]
        assert isinstance(r1, ResolvedSharding)


# -------------------------------------------------- training acceptance


class TestDigestAgreement:
    """ISSUE 8 satellite (ROADMAP 1d): sharding.json is written by
    process 0 only and restore validation is per-process — the fit-
    start allgather is the cross-host agreement check, failing with
    the mismatching host NAMED before any restore runs."""

    DIGEST_A = "0123456789abcdef"
    DIGEST_B = "fedcba9876543210"

    def _gather(self, rows):
        def allgather(vec):
            return np.stack(
                [np.frombuffer(bytes.fromhex(d), np.uint8).astype(
                    np.int32
                ) for d in rows]
            )

        return allgather

    def test_agreement_passes(self):
        from tensorflow_examples_tpu.sharding import (
            verify_digest_agreement,
        )

        verify_digest_agreement(
            self.DIGEST_A,
            allgather=self._gather([self.DIGEST_A] * 4),
            process_index=0,
            process_count=4,
        )

    def test_single_process_never_gathers(self):
        from tensorflow_examples_tpu.sharding import (
            verify_digest_agreement,
        )

        def boom(vec):
            raise AssertionError("collective entered on 1 process")

        verify_digest_agreement(
            self.DIGEST_A, allgather=boom, process_count=1
        )

    def test_mismatch_names_the_host(self):
        from tensorflow_examples_tpu.sharding import (
            ShardingMismatchError,
            verify_digest_agreement,
        )

        rows = [self.DIGEST_A, self.DIGEST_A, self.DIGEST_B,
                self.DIGEST_A]
        with pytest.raises(ShardingMismatchError) as ei:
            verify_digest_agreement(
                self.DIGEST_A,
                allgather=self._gather(rows),
                process_index=0,
                process_count=4,
            )
        msg = str(ei.value)
        assert "host 2" in msg and self.DIGEST_B in msg
        assert self.DIGEST_A in msg  # both digests shown
        assert "host 1" not in msg  # agreeing hosts are not accused


class TestShardedTraining:
    def test_2d_mesh_matches_1device_loss_trajectory(self):
        """THE tentpole training claim: 2x2 and 4x2 (data, model) GSPMD
        layouts reproduce the 1-device loss trajectory (f32
        reduction-order tolerance), driven end-to-end by the
        serializable config."""
        cfg = tiny_cfg()
        ref = run_steps(
            make_trainer(cfg, ShardingConfig(mesh={"data": 1})), cfg, 3
        )
        for mesh in ({"data": 2, "model": 2}, {"data": 4, "model": 2}):
            got = run_steps(
                make_trainer(cfg, gpt2_sharding(mesh)), cfg, 3
            )
            # f32 reduction-order deltas compound through the optimizer
            # (~1e-3 relative by step 3 on CPU XLA); 3e-3 relative keeps
            # the parity claim while tolerating summation order.
            np.testing.assert_allclose(
                got, ref, rtol=3e-3, atol=0,
                err_msg=f"mesh {mesh} diverged from 1-device trajectory",
            )

    def test_params_actually_sharded_over_model(self):
        cfg = tiny_cfg()
        trainer = make_trainer(cfg, gpt2_sharding({"data": 2, "model": 2}))
        qkv = trainer.state.params["h_0"]["attn"]["qkv"]["kernel"]
        assert "model" in str(qkv.sharding.spec)
        shard = qkv.addressable_shards[0].data
        assert shard.shape[2] == qkv.shape[2] // 2  # heads dim split

    def test_zero1_quarters_per_device_opt_bytes(self):
        """Acceptance: ZeRO-1 on an 8-way batch mesh drops measured
        per-device optimizer bytes to ≤ 1/4 of the replicated
        baseline (actually ~1/8 — the moments shard 8 ways)."""
        cfg = tiny_cfg()
        base = make_trainer(cfg, gpt2_sharding({"data": 8}))
        z1 = make_trainer(cfg, gpt2_sharding({"data": 8}, zero1=True))
        repl = base.state.byte_breakdown(per_device=True)["opt_state"]
        shrd = z1.state.byte_breakdown(per_device=True)["opt_state"]
        assert repl == base.state.byte_breakdown()["opt_state"]
        assert shrd <= repl / 4, (shrd, repl)
        # Global bytes unchanged — only placement moved.
        assert (
            z1.state.byte_breakdown()["opt_state"]
            == base.state.byte_breakdown()["opt_state"]
        )

    def test_zero1_step_matches_replicated(self):
        cfg = tiny_cfg()
        ref = run_steps(make_trainer(cfg, gpt2_sharding({"data": 8})),
                        cfg, 2)
        got = run_steps(
            make_trainer(cfg, gpt2_sharding({"data": 8}, zero1=True)),
            cfg, 2,
        )
        np.testing.assert_allclose(got, ref, rtol=1e-6)


# ---------------------------------------- fit integration + provenance


@pytest.fixture(scope="module")
def sharded_fit(tmp_path_factory):
    """One 2x2 GPT-2 fit with a workdir, shared by the provenance/
    telemetry/report assertions below (compiles are the cost)."""
    import jax

    wd = str(tmp_path_factory.mktemp("sharded_fit"))
    cfg = tiny_cfg(
        train_steps=2, log_every=1, checkpoint_every=2, workdir=wd
    )
    sc = gpt2_sharding({"data": 2, "model": 2})
    trainer = make_trainer(cfg, sc)
    rng = np.random.RandomState(1)

    def data(start=0):
        while True:
            yield {
                "tokens": rng.randint(
                    0, cfg.vocab_size,
                    size=(cfg.global_batch_size, cfg.seq_len + 1),
                ).astype(np.int32)
            }

    trainer.fit(data())
    del jax
    return wd, cfg, sc, trainer


class TestFitProvenance:
    def test_zero_post_warmup_recompiles(self, sharded_fit):
        """The CI smoke (ISSUE 7 satellite): a 2x2 CPU-mesh GPT-2 fit
        emits zero post-warmup recompiles under the sentinel."""
        _, _, _, trainer = sharded_fit
        assert trainer.sentinel.post_warmup_recompiles() == 0

    def test_sharding_json_persisted(self, sharded_fit):
        wd, _, sc, trainer = sharded_fit
        loaded, extra = ShardingConfig.load_with_extra(
            os.path.join(wd, "sharding.json")
        )
        assert loaded == trainer.sharding
        assert extra["param_sharding_digest"] == trainer.sharding_digest()
        assert extra["mesh_shape"]["data"] == 2
        assert extra["mesh_shape"]["model"] == 2

    def test_final_line_carries_sharding(self, sharded_fit):
        wd, _, _, trainer = sharded_fit
        path = os.path.join(wd, "telemetry", "metrics.jsonl")
        lines = [json.loads(l) for l in open(path)]
        for line in lines:
            assert schema.validate_line(line) == [], line
        finals = [l for l in lines if l["kind"] == "final"]
        assert finals and "sharding" in finals[-1]
        sh = finals[-1]["sharding"]
        assert sh["mesh_shape"] == {
            "data": 2, "fsdp": 1, "model": 2, "context": 1, "pipe": 1
        }
        assert sh["param_sharding_digest"] == trainer.sharding_digest()
        # Non-final lines never carry it (schema v5 contract).
        assert all("sharding" not in l for l in lines if l["kind"] != "final")

    def test_report_renders_mesh_and_digest(self, sharded_fit):
        wd, _, _, trainer = sharded_fit
        import telemetry_report

        record, skipped, _ = telemetry_report.build_record(wd)
        assert skipped == 0
        assert record["mesh_shape"]["model"] == 2
        assert record["param_sharding_digest"] == trainer.sharding_digest()
        # Nontrivial model axis -> the sharded_step_time gate key.
        assert record["sharded_step_time"] == record["step_time_p50"]
        text = telemetry_report.render(record, 0)
        assert "sharding: mesh" in text
        assert trainer.sharding_digest() in text

    def test_resume_same_rules_is_clean(self, sharded_fit):
        """A second fit in the same workdir (same config) passes the
        digest check and restores."""
        wd, cfg, sc, _ = sharded_fit
        trainer = make_trainer(
            cfg.replace(train_steps=2), sc
        )
        rng = np.random.RandomState(2)

        def data(start=0):
            while True:
                yield {
                    "tokens": rng.randint(
                        0, cfg.vocab_size,
                        size=(cfg.global_batch_size, cfg.seq_len + 1),
                    ).astype(np.int32)
                }

        trainer.fit(data())  # restores step 2, loop body is a no-op
        assert int(trainer.state.step) == 2

    def test_drifted_rules_fail_with_named_error(self, sharded_fit):
        wd, cfg, _, _ = sharded_fit
        from jax.sharding import PartitionSpec as P

        drifted = ShardingConfig(
            mesh={"data": 2, "model": 2},
            rules=rules_to_json(transformer.GPT2_RULES)
            + [["wte/embedding", spec_to_json(P("model", None))]],
        )
        trainer = make_trainer(cfg, drifted)
        with pytest.raises(ShardingMismatchError, match="wte/embedding"):
            trainer.fit(iter([]))


# -------------------------------------------- checkpoint resharding


class TestCheckpointResharding:
    def test_bitwise_restore_across_mesh_shapes(self, tmp_path):
        """Acceptance: save on an 8-device (2,4) mesh, restore on 1
        device AND on a (4,2) layout — params bitwise-identical."""
        import jax

        from tensorflow_examples_tpu.train.checkpoint import (
            CheckpointManager,
        )

        cfg = tiny_cfg()
        src = make_trainer(cfg, gpt2_sharding({"data": 2, "model": 4}))
        run_steps(src, cfg, 2)  # real moments, not init zeros
        wd = str(tmp_path)
        with CheckpointManager(wd, async_save=False) as ckpt:
            ckpt.save(2, src.state)
        want = {
            "/".join(str(getattr(p, "key", p)) for p in path): np.asarray(
                leaf
            )
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                src.state.params
            )[0]
        }

        for mesh in ({"data": 1}, {"data": 4, "model": 2}):
            dst = make_trainer(cfg, gpt2_sharding(mesh))
            with CheckpointManager(wd, async_save=False) as ckpt:
                restored, step = ckpt.restore_latest(dst.state)
            assert step == 2
            got = jax.tree_util.tree_flatten_with_path(restored.params)[0]
            for path, leaf in got:
                key = "/".join(
                    str(getattr(p, "key", p)) for p in path
                )
                np.testing.assert_array_equal(
                    np.asarray(leaf), want[key], err_msg=f"{mesh} {key}"
                )
            # Restored INTO the destination layout, not the source's.
            qkv = restored.params["h_0"]["attn"]["qkv"]["kernel"]
            n_model = mesh.get("model", 1)
            assert (
                qkv.sharding.shard_shape(qkv.shape)[2]
                == qkv.shape[2] // max(n_model, 1)
            )

        # The restore-only consumers' path (generate/serve CLIs):
        # a shardings-free eval_shape template must restore a
        # SHARDED-saved checkpoint onto the default device.
        import jax as _jax

        from tensorflow_examples_tpu.train.loop import state_factory

        make_state, _ = state_factory(
            gpt2.make_task(cfg), cfg
        )
        abstract = _jax.eval_shape(make_state, _jax.random.PRNGKey(0))
        with CheckpointManager(wd, async_save=False) as ckpt:
            restored, step = ckpt.restore_latest(abstract)
        assert step == 2
        got = np.asarray(
            restored.params["h_0"]["attn"]["qkv"]["kernel"]
        )
        np.testing.assert_array_equal(got, want["h_0/attn/qkv/kernel"])


# ------------------------------------------------------ sharded serving


@pytest.mark.serving
class TestShardedServing:
    def _engine(self, sc=None, **serve_kw):
        import jax

        from tensorflow_examples_tpu.serving.engine import (
            InferenceEngine,
            ServeConfig,
        )

        mcfg = transformer.TransformerConfig(
            vocab_size=211, max_len=64, num_layers=2, num_heads=2,
            d_model=32, dropout=0.0, attention="xla",
        )
        model = transformer.Transformer(mcfg)
        params = model.init(
            {"params": jax.random.PRNGKey(0)},
            np.zeros((1, 8), np.int32),
        )["params"]
        serve = ServeConfig(
            max_slots=4, prefill_bucket_floor=16, kv_bucket_floor=32,
            **serve_kw,
        )
        return InferenceEngine(mcfg, params, cfg=serve, sharding=sc)

    # Every placement test runs at the default block size and at 8:
    # the pool's per-layer [NB, BS, H*D] arrays shard whole heads on
    # the last dim whatever NB and BS are.
    POOLS = pytest.mark.parametrize(
        "serve_kw", [{}, {"kv_block_size": 8}], ids=["block16", "block8"]
    )

    @POOLS
    def test_sharded_params_and_pool(self, serve_kw):
        import jax

        eng = self._engine(
            gpt2_sharding({"data": 1, "model": 2}), **serve_kw
        )
        qkv = eng.params["h_0"]["attn"]["qkv"]["kernel"]
        assert "model" in str(qkv.sharding.spec)  # NOT replicated
        assert len({s.device for s in qkv.addressable_shards}) == 2

        def specs():
            arrays = jax.tree.leaves((eng.pool.k, eng.pool.v))
            assert len(arrays) == 2 * eng.model_cfg.num_layers
            return [a.sharding.spec for a in arrays]

        old_specs = specs()
        mcfg = eng.model_cfg
        assert all(tuple(sp) == (None, None, "model") for sp in old_specs)
        assert eng.pool.k[0].addressable_shards[0].data.shape[-1] == (
            mcfg.num_heads // 2 * mcfg.head_dim
        )
        assert eng.param_sharding_digest is not None
        # reallocate() preserves the pool placement.
        eng.pool.reallocate()
        assert specs() == old_specs

    @POOLS
    def test_batched_token_identity_and_zero_recompiles(self, serve_kw):
        """Acceptance: serving from sharded (non-replicated) params
        keeps batched output token-identical to the unbatched reference
        and zero post-warmup recompiles — through the continuous
        batcher, mixed lengths and sampling settings."""
        from tensorflow_examples_tpu.serving.batcher import (
            ContinuousBatcher,
            Request,
        )

        eng = self._engine(
            gpt2_sharding({"data": 1, "model": 2}), **serve_kw
        )
        eng.warmup()
        assert eng.warmed
        reqs = [
            Request(prompt=[7], max_new_tokens=5, seed=3),
            Request(prompt=[1, 2, 3, 4, 5], max_new_tokens=6, seed=11,
                    temperature=0.9, top_k=13),
            Request(prompt=list(range(1, 20)), max_new_tokens=4, seed=5,
                    temperature=0.7),
            Request(prompt=[9, 8, 7], max_new_tokens=6, seed=21),
            Request(prompt=list(range(40, 2, -1)), max_new_tokens=5,
                    seed=8, temperature=1.1, top_k=7),
            Request(prompt=[3, 1], max_new_tokens=6, seed=13),
        ]
        batcher = ContinuousBatcher(eng).start()
        try:
            futures = [batcher.submit(r) for r in reqs]
            got = [f.result(timeout=120).tokens for f in futures]
        finally:
            batcher.close()
        for r, tokens in zip(reqs, got):
            ref = eng.reference_generate(
                r.prompt, max_new=r.max_new_tokens, seed=r.seed,
                temperature=r.temperature, top_k=r.top_k,
            )
            assert tokens == ref, (r.prompt, tokens, ref)
        assert eng.post_warmup_recompiles() == 0

    @POOLS
    def test_sharded_matches_replicated_engine(self, serve_kw):
        """Placement must not change tokens: the sharded engine's
        greedy output equals the replicated engine's."""
        a = self._engine(
            gpt2_sharding({"data": 1, "model": 2}), **serve_kw
        )
        b = self._engine(None, **serve_kw)
        for eng in (a, b):
            eng.warmup()

        def drive(eng):
            slot = eng.pool.alloc()
            tok, _ = eng.prefill(slot, [5, 4, 3], seed=2)
            out = [tok]
            for _ in range(4):
                out.append(eng.decode([(slot, out[-1], 2, 0.0, 0)])[slot])
            eng.pool.free(slot)
            return out

        assert drive(a) == drive(b)


# ------------------------------------------- quantized x sharded (ISSUE 15)


@pytest.mark.serving
class TestQuantizedShardedServing:
    """The precision registry composes with the ShardingConfig: the
    quantized payload shards by the weight's rule, its per-row scale
    inherits the weight's spec (rank-clipped), and a 2x2-mesh int8
    GPT-2 serves with the same divergence contract as an unsharded
    one — at <= 0.35x the f32 sharded baseline's per-device bytes."""

    def _engine(self, *, weight_dtype, mesh={"data": 2, "model": 2}):
        import jax

        from tensorflow_examples_tpu.serving.engine import (
            InferenceEngine,
            ServeConfig,
        )

        mcfg = transformer.TransformerConfig(
            vocab_size=211, max_len=64, num_layers=2, num_heads=2,
            d_model=32, dropout=0.0, attention="xla",
        )
        model = transformer.Transformer(mcfg)
        params = model.init(
            {"params": jax.random.PRNGKey(0)},
            np.zeros((1, 8), np.int32),
        )["params"]
        return InferenceEngine(
            mcfg, params,
            cfg=ServeConfig(
                max_slots=4, prefill_bucket_floor=16, kv_bucket_floor=32,
                weight_dtype=weight_dtype,
            ),
            sharding=gpt2_sharding(mesh),
        )

    def test_clip_is_scale_only_bad_rules_still_fail_loudly(self):
        """Rank clipping exists FOR quantization scales; an over-ranked
        spec on any other leaf must keep failing at placement — a
        typo'd rules table must not silently re-place a bias."""
        import jax
        from jax.sharding import PartitionSpec as P

        from tensorflow_examples_tpu.core.mesh import MeshConfig, create_mesh
        from tensorflow_examples_tpu.core.sharding import (
            ShardingRules,
            shardings_for_params,
        )

        mesh = create_mesh(MeshConfig(data=4, model=2))
        tree = {"mlp_fc": {"bias": np.zeros((8,), np.float32)}}
        rules = ShardingRules([(r"bias", P("data", "model"))])
        sh = shardings_for_params(tree, mesh, rules)
        with pytest.raises(ValueError):
            jax.device_put(tree, sh)
        # LayerNorm params are also literally named 'scale' — the clip
        # keys on the QuantizedWeight child's key TYPE, so a bad rule
        # on ln scale keeps the loud failure too.
        ln = {"ln_1": {"scale": np.ones((8,), np.float32)}}
        ln_rules = ShardingRules([(r"ln_1/scale", P("data", "model"))])
        with pytest.raises(ValueError):
            jax.device_put(
                ln, shardings_for_params(ln, mesh, ln_rules)
            )

    def test_anchored_rules_still_match_quantized_leaves(self):
        """Quantization extends leaf paths (.../kernel -> .../kernel/q
        + /scale); rules resolve against the WEIGHT's path, so an
        ANCHORED pattern like 'kernel$' keeps sharding a quantized
        weight instead of silently replicating it."""
        import jax
        from jax.sharding import PartitionSpec as P

        from tensorflow_examples_tpu.core.mesh import MeshConfig, create_mesh
        from tensorflow_examples_tpu.core.precision import (
            PrecisionConfig,
            quantize_tree,
        )
        from tensorflow_examples_tpu.core.sharding import (
            ShardingRules,
            shardings_for_params,
        )

        mesh = create_mesh(MeshConfig(data=4, model=2))
        tree = quantize_tree(
            {"mlp_fc": {"kernel": np.ones((8, 16), np.float32)}},
            PrecisionConfig.weight_only("int8"),
        )
        rules = ShardingRules([(r"mlp_fc/kernel$", P(None, "model"))])
        placed = jax.device_put(
            tree, shardings_for_params(tree, mesh, rules)
        )
        qw = placed["mlp_fc"]["kernel"]
        assert "model" in str(qw.q.sharding.spec), (
            "anchored rule must still shard the quantized payload"
        )
        # The scale [8] clips the weight's spec to P(None): replicated
        # here, but resolved THROUGH the weight's rule, not a no-match.
        assert all(a is None for a in qw.scale.sharding.spec)

    def test_scales_sharded_like_their_weights(self):
        from tensorflow_examples_tpu.core.precision import QuantizedWeight

        eng = self._engine(weight_dtype="int8")
        qkv = eng.params["h_0"]["attn"]["qkv"]["kernel"]
        assert isinstance(qkv, QuantizedWeight)
        # The payload keeps the weight's full spec (heads over model)…
        assert "model" in str(qkv.q.sharding.spec)
        assert len({s.device for s in qkv.q.addressable_shards}) >= 2
        # …and the scale [d, 3, H] carries the spec's leading dims —
        # the head axis survives the rank clip, so the scale splits
        # over `model` exactly where its weight does.
        assert "model" in str(qkv.scale.sharding.spec)
        assert len(qkv.scale.sharding.spec) == qkv.scale.ndim
        # Replicated-by-rule leaves (embeddings) stay replicated.
        wte = eng.params["wte"]["embedding"]
        assert isinstance(wte, QuantizedWeight)
        assert all(a is None for a in wte.q.sharding.spec)

    @pytest.mark.timeout(300)
    def test_golden_bytes_and_zero_recompiles(self):
        """The satellite acceptance in one run: batcher golden
        first-token-exact vs the f32 sharded twin with bounded stream
        divergence, zero post-warmup recompiles, and per-device param
        bytes <= 0.35x the f32 sharded baseline via
        byte_breakdown(per_device=True)."""
        from tensorflow_examples_tpu.serving.batcher import (
            ContinuousBatcher,
            Request,
        )

        f32 = self._engine(weight_dtype="")
        quant = self._engine(weight_dtype="int8")
        bb_q = quant.byte_breakdown(per_device=True)
        bb_f = f32.byte_breakdown(per_device=True)
        assert bb_q["params_bytes"] <= 0.35 * bb_f["params_bytes"]
        # The per-device view reports only per-device-meaningful
        # fields — no silently-global numbers to mis-ratio against.
        assert "params_bytes_f32" not in bb_q
        assert "kv_cache_bytes" not in bb_q
        for eng in (f32, quant):
            eng.warmup()
        reqs = [
            Request(prompt=[7], max_new_tokens=5, seed=3),
            Request(prompt=[1, 2, 3, 4, 5], max_new_tokens=6, seed=11,
                    temperature=0.9, top_k=13),
            Request(prompt=list(range(1, 20)), max_new_tokens=4, seed=5),
            Request(prompt=list(range(40, 2, -1)), max_new_tokens=5,
                    seed=8),
        ]
        batcher = ContinuousBatcher(quant).start()
        try:
            futures = [batcher.submit(r) for r in reqs]
            got = [f.result(timeout=120).tokens for f in futures]
        finally:
            batcher.close()
        for r, tokens in zip(reqs, got):
            own = quant.reference_generate(
                r.prompt, max_new=r.max_new_tokens, seed=r.seed,
                temperature=r.temperature, top_k=r.top_k,
            )
            assert tokens == own, "batched != quantized reference"
            ref = f32.reference_generate(
                r.prompt, max_new=r.max_new_tokens, seed=r.seed,
                temperature=r.temperature, top_k=r.top_k,
            )
            assert tokens[0] == ref[0], "first token must be exact"
            agree = sum(a == b for a, b in zip(tokens, ref))
            assert agree >= 0.75 * len(ref), (tokens, ref)
        assert quant.post_warmup_recompiles() == 0

    def test_sharded_quantized_matches_replicated_quantized(self):
        """Quantization happens on the host BEFORE placement, so the
        sharded tree holds the same values — placement still never
        changes tokens, quantized or not."""
        from tensorflow_examples_tpu.serving.engine import (
            InferenceEngine,
            ServeConfig,
        )

        sharded = self._engine(weight_dtype="int8")
        mcfg = sharded.model_cfg
        import jax

        model = transformer.Transformer(mcfg)
        params = model.init(
            {"params": jax.random.PRNGKey(0)},
            np.zeros((1, 8), np.int32),
        )["params"]
        replicated = InferenceEngine(
            mcfg, params,
            cfg=ServeConfig(
                max_slots=4, prefill_bucket_floor=16, kv_bucket_floor=32,
                weight_dtype="int8",
            ),
        )
        for eng in (sharded, replicated):
            eng.warmup()

        def drive(eng):
            slot = eng.pool.alloc()
            tok, _ = eng.prefill(slot, [5, 4, 3], seed=2)
            out = [tok]
            for _ in range(4):
                out.append(eng.decode([(slot, out[-1], 2, 0.0, 0)])[slot])
            eng.pool.free(slot)
            return out

        assert drive(sharded) == drive(replicated)


# ------------------------------------------------------------- schema v5


class TestSchemaV5:
    def _line(self, **kw):
        base = {
            "schema_version": schema.SCHEMA_VERSION,
            "kind": "final",
            "host": 0,
            "step": 10,
            "time_unix": 2.0,
            "session_start_unix": 1.0,
            "metrics": {},
            "counters": {},
            "gauges": {},
            "derived": {},
            "exit_reason": "complete",
            "sharding": {
                "mesh_shape": {"data": 2, "model": 2},
                "param_sharding_digest": "ab12cd34",
                "zero1": False,
            },
        }
        base.update(kw)
        return base

    def test_final_line_with_sharding_validates(self):
        assert schema.validate_line(self._line()) == []

    def test_sharding_on_non_final_rejected(self):
        bad = self._line(kind="window")
        del bad["exit_reason"]
        assert any(
            "non-final" in p for p in schema.validate_line(bad)
        )

    def test_sharding_on_v3_line_rejected(self):
        assert any(
            "v5 field" in p
            for p in schema.validate_line(self._line(schema_version=3))
        )

    def test_sharding_shape_checked(self):
        bad = self._line()
        bad["sharding"] = {"mesh_shape": {"data": 0}}
        problems = schema.validate_line(bad)
        assert any("positive int" in p for p in problems)
        assert any("param_sharding_digest" in p for p in problems)


# ----------------------------------------------------------- tools


class TestShardViz:
    ARGS = [
        "--workload", "gpt2",
        "--set", "num_layers=2", "--set", "d_model=32",
        "--set", "num_heads=4", "--set", "vocab_size=64",
        "--set", "seq_len=16",
    ]

    def test_table_and_totals(self, capsys):
        import shard_viz

        rc = shard_viz.main(
            ["--mesh", "data=2,model=2", "--zero1"] + self.ARGS
        )
        assert rc == 0
        out = capsys.readouterr().out
        assert "h_0/attn/qkv/kernel" in out
        assert "replicated" in out and "model" in out
        assert "param sharding digest:" in out
        assert "x reduction" in out  # zero1 opt-state summary

    def test_json_output_matches_resolve(self, capsys):
        import shard_viz

        rc = shard_viz.main(
            ["--mesh", "data=2,model=2", "--json"] + self.ARGS
        )
        assert rc == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["mesh_shape"]["model"] == 2
        rows = {r["path"]: r for r in doc["rows"]}
        qkv = rows["h_0/attn/qkv/kernel"]
        assert not qkv["replicated"]
        assert qkv["per_device_bytes"] == qkv["global_bytes"] // 2
        assert rows["wte/embedding"]["replicated"]
        totals = doc["totals"]
        assert totals["per_device_bytes"] < totals["global_bytes"]

    def test_loads_a_persisted_config(self, tmp_path, capsys):
        import shard_viz

        path = str(tmp_path / "sharding.json")
        gpt2_sharding({"data": 2, "model": 2}).save(path)
        rc = shard_viz.main(["--config", path] + self.ARGS)
        assert rc == 0
        assert "mesh:" in capsys.readouterr().out

    def test_bad_field_named(self):
        import shard_viz

        with pytest.raises(ValueError, match="no such field"):
            shard_viz.main(
                ["--mesh", "data=2", "--workload", "gpt2",
                 "--set", "nope=1"]
            )


class TestBenchGateShardedStepTime:
    def test_stamp_and_gate(self, tmp_path, capsys):
        import bench_gate

        record = {
            "step_time_p50": 0.01,
            "sharded_step_time": 0.012,
            "goodput": 1.0,
        }
        rec_path = str(tmp_path / "record.json")
        floors_path = str(tmp_path / "floors.json")
        with open(rec_path, "w") as f:
            json.dump(record, f)
        assert bench_gate.main(
            ["--stamp", rec_path, "--floors", floors_path]
        ) == 0
        floors = json.load(open(floors_path))
        assert floors["sharded_step_time"] == {"max": 0.012}
        # Same record gates green...
        assert bench_gate.main(
            ["--record", rec_path, "--floors", floors_path]
        ) == 0
        # ...a 50% sharded-step-time regression gates red.
        record["sharded_step_time"] = 0.018
        with open(rec_path, "w") as f:
            json.dump(record, f)
        assert bench_gate.main(
            ["--record", rec_path, "--floors", floors_path]
        ) == 1
        out = capsys.readouterr().out
        assert "sharded_step_time" in out
