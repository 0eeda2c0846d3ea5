"""Precision registry (ISSUE 15): per-row int8/fp8 quantization, the
serializable PrecisionConfig rules table, load-time tree quantization,
and the sharding composition (scales placed like their weights).

The serving-side acceptance — quantized batcher golden, byte claims,
schema v11 — lives in tests/test_serving.py / test_sharding.py /
test_tools.py; this file pins the registry's own contracts.
"""

import json

import numpy as np
import pytest

from tensorflow_examples_tpu.core import precision as P

pytestmark = pytest.mark.serving


# ------------------------------------------------------ row quantization


class TestRowQuantization:
    def test_int8_roundtrip_error_bounded(self):
        import jax.numpy as jnp

        rng = np.random.default_rng(0)
        x = rng.standard_normal((6, 64)).astype(np.float32) * 3.0
        q, s = P.quantize_rows(jnp.asarray(x), jnp.int8)
        assert q.dtype == jnp.int8 and s.shape == (6,)
        back = np.asarray(P.dequantize_rows(q, s))
        # Symmetric absmax: per-row error <= half a quantization step.
        step = np.abs(x).max(axis=-1, keepdims=True) / P.INT8_MAX
        assert np.all(np.abs(back - x) <= 0.5 * step + 1e-7)

    def test_zero_row_exact(self):
        import jax.numpy as jnp

        x = jnp.zeros((2, 8), jnp.float32)
        q, s = P.quantize_rows(x, jnp.int8)
        assert np.all(np.asarray(s) == 1.0)
        assert np.all(np.asarray(P.dequantize_rows(q, s)) == 0.0)

    def test_int8_matches_legacy_helper(self):
        """quantize_rows(int8) IS quantize_int8_rows — the paged pool's
        contract has one implementation."""
        import jax.numpy as jnp

        x = jnp.asarray(
            np.random.default_rng(1).standard_normal((3, 16)), jnp.float32
        )
        q1, s1 = P.quantize_rows(x, jnp.int8)
        q2, s2 = P.quantize_int8_rows(x)
        assert np.array_equal(np.asarray(q1), np.asarray(q2))
        assert np.array_equal(np.asarray(s1), np.asarray(s2))

    @pytest.mark.skipif(not P.fp8_supported(), reason="no fp8 backend")
    def test_fp8_roundtrip_error_bounded(self):
        rng = np.random.default_rng(2)
        x = rng.standard_normal((4, 32)).astype(np.float32)
        q, s = P.quantize_rows(x, P.fp8_dtype())
        back = np.asarray(P.dequantize_rows(q, s))
        # e4m3 carries a ~2^-3 relative mantissa step per element.
        amax = np.abs(x).max(axis=-1, keepdims=True)
        assert np.all(np.abs(back - x) <= amax * 0.05 + 1e-7)

    def test_host_quantizer_matches_device(self):
        """Load-time (numpy) quantization == the jnp path bit for bit —
        the tree a sharded engine places is the tree an unsharded one
        computes."""
        import jax.numpy as jnp

        x = np.random.default_rng(3).standard_normal((5, 24)).astype(
            np.float32
        )
        qh, sh = P._quantize_rows_host(x, "int8")
        qd, sd = P.quantize_rows(jnp.asarray(x), jnp.int8)
        assert np.array_equal(qh, np.asarray(qd))
        assert np.array_equal(sh, np.asarray(sd))


# ----------------------------------------------------------- the registry


def _tree():
    rng = np.random.default_rng(7)
    return {
        "wte": {"embedding": rng.standard_normal((40, 8)).astype(
            np.float32
        )},
        "h_0": {
            "ln_1": {
                "scale": np.ones(8, np.float32),
                "bias": np.zeros(8, np.float32),
            },
            "attn": {
                "qkv": {
                    "kernel": rng.standard_normal((8, 3, 2, 4)).astype(
                        np.float32
                    ),
                    "bias": np.zeros((3, 2, 4), np.float32),
                },
            },
            "mlp_fc": {
                "kernel": rng.standard_normal((8, 32)).astype(np.float32),
                "bias": np.zeros(32, np.float32),
            },
        },
        "step": np.int32(3),  # non-floating leaves pass through
    }


class TestPrecisionConfig:
    def test_weight_only_rules_and_json_roundtrip(self, tmp_path):
        cfg = P.PrecisionConfig.weight_only("int8", kv_dtype="fp8")
        assert cfg.quantizes and cfg.kv_dtype == "fp8"
        assert cfg.dtype_for("h_0/mlp_fc/kernel") == "int8"
        assert cfg.dtype_for("wte/embedding") == "int8"
        assert cfg.dtype_for("h_0/ln_1/scale") == ""
        path = str(tmp_path / "precision.json")
        cfg.save(path)
        assert P.PrecisionConfig.load(path) == cfg
        with open(path) as f:
            assert json.load(f)["version"] == P.PRECISION_JSON_VERSION

    def test_first_match_wins(self):
        cfg = P.PrecisionConfig(
            rules=((r"mlp_fc/kernel", ""), (r"kernel", "int8")),
        )
        assert cfg.dtype_for("h_0/mlp_fc/kernel") == ""
        assert cfg.dtype_for("h_0/attn/qkv/kernel") == "int8"

    def test_validation_is_loud(self):
        with pytest.raises(ValueError, match="dtype"):
            P.PrecisionConfig(rules=(("x", "int4"),))
        with pytest.raises(ValueError, match="kv_dtype"):
            P.PrecisionConfig(kv_dtype="bf16")
        with pytest.raises(ValueError, match="unknown"):
            P.PrecisionConfig.from_json_dict({"nope": 1})
        with pytest.raises(ValueError, match="not in"):
            P.PrecisionConfig.weight_only("f16")
        # Malformed rules are ValueError (the documented contract),
        # never a TypeError out of the unpack.
        with pytest.raises(ValueError, match="rule"):
            P.PrecisionConfig(rules=(5,))
        with pytest.raises(ValueError, match="rules"):
            P.PrecisionConfig.from_json_dict({"rules": [5]})
        with pytest.raises(ValueError, match="rules"):
            P.PrecisionConfig.from_json_dict({"rules": "kernel:int8"})

    def test_empty_dtype_is_identity(self):
        cfg = P.PrecisionConfig.weight_only("")
        assert not cfg.quantizes
        tree = _tree()
        out = P.quantize_tree(tree, cfg)
        assert out["h_0"]["mlp_fc"]["kernel"] is tree["h_0"]["mlp_fc"][
            "kernel"
        ]


class TestQuantizeTree:
    def test_kernels_quantize_norms_and_ints_pass_through(self):
        tree = _tree()
        out = P.quantize_tree(tree, P.PrecisionConfig.weight_only("int8"))
        assert isinstance(out["wte"]["embedding"], P.QuantizedWeight)
        assert isinstance(
            out["h_0"]["attn"]["qkv"]["kernel"], P.QuantizedWeight
        )
        # Per-row scales drop exactly the last axis.
        qkv = out["h_0"]["attn"]["qkv"]["kernel"]
        assert qkv.scale.shape == (8, 3, 2)
        assert not isinstance(out["h_0"]["ln_1"]["scale"],
                              P.QuantizedWeight)
        assert not isinstance(out["h_0"]["mlp_fc"]["bias"],
                              P.QuantizedWeight)
        assert out["step"] == np.int32(3)

    def test_one_d_leaves_never_quantize_even_under_blanket_rule(self):
        out = P.quantize_tree(
            _tree(), P.PrecisionConfig(default="int8")
        )
        assert not isinstance(out["h_0"]["ln_1"]["bias"],
                              P.QuantizedWeight)
        assert isinstance(out["h_0"]["mlp_fc"]["kernel"],
                          P.QuantizedWeight)

    def test_tree_paths_expose_q_and_scale_leaves(self):
        """The sharding composition hinges on this: a QuantizedWeight
        flattens into q/scale leaves UNDER the weight's own path, so
        the weight's rule places both (scale by rank clipping)."""
        import jax

        out = P.quantize_tree(_tree(), P.PrecisionConfig.weight_only(
            "int8"
        ))
        paths = {
            P._tree_path_str(p)
            for p, _ in jax.tree_util.tree_flatten_with_path(out)[0]
        }
        assert "h_0/mlp_fc/kernel/q" in paths
        assert "h_0/mlp_fc/kernel/scale" in paths
        assert "wte/embedding/q" in paths

    def test_bytes_ratio_and_stats(self):
        tree = _tree()
        out = P.quantize_tree(tree, P.PrecisionConfig.weight_only("int8"))
        stats = P.tree_precision_stats(out)
        f32_stats = P.tree_precision_stats(tree)
        assert stats["weight_bits"] == 8
        assert stats["quantized_params"] == 3
        assert stats["param_bytes_f32"] == f32_stats["param_bytes"]
        assert stats["param_bytes"] < 0.5 * stats["param_bytes_f32"]
        assert f32_stats["quantized_params"] == 0
        assert f32_stats["weight_bits"] == 32

    def test_stats_agree_with_tree_bytes(self):
        """tree_precision_stats' stored-byte walk and
        telemetry/memory.tree_bytes are two sources of the same HBM
        number (the precision/param_bytes gauge vs the gated
        hbm_bytes_per_replica) — pinned equal so they cannot silently
        desynchronize."""
        from tensorflow_examples_tpu.telemetry.memory import tree_bytes

        for cfg in (P.PrecisionConfig.weight_only("int8"),
                    P.PrecisionConfig.weight_only("")):
            out = P.quantize_tree(_tree(), cfg)
            assert P.tree_precision_stats(out)["param_bytes"] == \
                tree_bytes(out)

    def test_cast_rules_cast(self):
        import jax.numpy as jnp

        out = P.quantize_tree(
            _tree(), P.PrecisionConfig(rules=((r"kernel", "bf16"),))
        )
        assert out["h_0"]["mlp_fc"]["kernel"].dtype == jnp.bfloat16

    def test_fp8_rule_without_support_is_loud(self, monkeypatch):
        monkeypatch.setattr(P, "fp8_supported", lambda: False)
        with pytest.raises(ValueError, match="fp8"):
            P.quantize_tree(
                _tree(), P.PrecisionConfig.weight_only("fp8")
            )


class TestMaterialize:
    def test_passthrough_on_plain_leaves(self):
        import jax.numpy as jnp

        x = jnp.ones((2, 3))
        assert P.materialize(x) is x
        assert np.array_equal(
            np.asarray(P.take_rows(x, jnp.asarray([1]))), np.ones((1, 3))
        )

    def test_dequant_in_jit_matches_eager(self):
        import jax
        import jax.numpy as jnp

        w = np.random.default_rng(9).standard_normal((8, 16)).astype(
            np.float32
        )
        qw = P.QuantizedWeight(*P._quantize_rows_host(w, "int8"))
        # The dequantized weight is the same in a trace as outside one,
        # bit for bit ...
        assert np.array_equal(
            np.asarray(jax.jit(P.materialize)(qw)),
            np.asarray(qw.dequantize()),
        )
        # ... and the dot that consumes it agrees with numpy's to the
        # rounding of an 8-term float32 sum of O(1) summands: one
        # output here is a cancelling sum (-0.0178) that the two
        # summation orders round 2.4e-7 apart, which no relative
        # tolerance on the RESULT covers.
        f = jax.jit(lambda t, x: jnp.dot(x, P.materialize(t)))
        x = jnp.ones((2, 8))
        np.testing.assert_allclose(
            np.asarray(f(qw, x)),
            np.asarray(x) @ np.asarray(qw.dequantize()),
            rtol=1e-5, atol=1e-5,
        )

    def test_take_rows_gathers_then_dequantizes(self):
        import jax.numpy as jnp

        w = np.random.default_rng(11).standard_normal((12, 6)).astype(
            np.float32
        )
        qw = P.QuantizedWeight(*P._quantize_rows_host(w, "int8"))
        idx = jnp.asarray([3, 0, 7])
        got = np.asarray(P.take_rows(qw, idx))
        want = np.asarray(qw.dequantize())[np.asarray(idx)]
        assert np.array_equal(got, want)
