"""BERT model + GLUE workload: HF parity, metric math, e2e fine-tune."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflow_examples_tpu.models import bert
from tensorflow_examples_tpu.ops import glue_metrics
from tensorflow_examples_tpu.train.loop import Trainer
from tensorflow_examples_tpu.data.memory import eval_batches, train_iterator
from tensorflow_examples_tpu.workloads import bert_glue


def tiny_cfg(**kw):
    base = dict(
        task="sst2",
        seq_len=16,
        vocab_size=120,
        num_layers=2,
        num_heads=2,
        d_model=16,
        d_ff=32,
        dropout=0.0,
        global_batch_size=16,
        train_steps=40,
        warmup_steps=4,
        learning_rate=3e-4,
        log_every=20,
        eval_every=0,
        checkpoint_every=0,
        precision="f32",
    )
    base.update(kw)
    return bert_glue.BertGlueConfig(**base)


def run_tiny(cfg, mesh):
    task = bert_glue.make_task(cfg, mesh=mesh)
    trainer = Trainer(task, cfg, mesh=mesh)
    train_ds, _ = bert_glue.datasets(cfg)
    it = train_iterator(train_ds, cfg.global_batch_size, seed=0)
    losses = []
    state = trainer.state
    for _ in range(cfg.train_steps):
        state, m = trainer._train_step(state, trainer._put_batch(next(it)))
        losses.append(float(m["loss"]))
    trainer.state = state
    return losses, trainer


def test_padding_mask_invariance():
    """Tokens beyond attention_mask must not affect the logits."""
    cfg = bert.BertConfig(
        vocab_size=50, max_len=16, num_layers=2, num_heads=2,
        d_model=16, d_ff=32, dropout=0.0,
    )
    model = bert.BertClassifier(cfg, num_labels=2)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(1, 50, (2, 16)), jnp.int32)
    mask = jnp.asarray((np.arange(16) < 10)[None].repeat(2, 0), jnp.int32)
    params = model.init({"params": jax.random.PRNGKey(0)}, tokens)["params"]
    out1 = model.apply({"params": params}, tokens, mask)
    toks2 = tokens.at[:, 12].set(7)
    out2 = model.apply({"params": params}, toks2, mask)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-6)


def test_flash_attention_matches_xla():
    """attention="flash" (Pallas kernel + key-bias padding mask) must
    reproduce the XLA softmax path on ragged per-row masks — logits AND
    parameter gradients."""
    base = dict(
        vocab_size=50, max_len=32, num_layers=2, num_heads=2,
        d_model=16, d_ff=32, dropout=0.0,
    )
    model_x = bert.BertClassifier(bert.BertConfig(**base), num_labels=2)
    model_f = bert.BertClassifier(
        bert.BertConfig(**base, attention="flash"), num_labels=2
    )
    rng = np.random.default_rng(1)
    tokens = jnp.asarray(rng.integers(1, 50, (3, 32)), jnp.int32)
    lengths = np.asarray([32, 20, 7])
    mask = jnp.asarray(
        (np.arange(32)[None] < lengths[:, None]).astype(np.int32)
    )
    params = model_x.init({"params": jax.random.PRNGKey(0)}, tokens)["params"]
    out_x = model_x.apply({"params": params}, tokens, mask)
    out_f = model_f.apply({"params": params}, tokens, mask)
    np.testing.assert_allclose(
        np.asarray(out_x), np.asarray(out_f), atol=2e-4, rtol=2e-4
    )

    def loss(m):
        return lambda p: jnp.sum(m.apply({"params": p}, tokens, mask) ** 2)

    g_x = jax.grad(loss(model_x))(params)
    g_f = jax.grad(loss(model_f))(params)
    for a, b in zip(jax.tree.leaves(g_x), jax.tree.leaves(g_f)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4, rtol=5e-4
        )


def test_flash_attention_under_tp_mesh_matches_xla():
    """attention="flash" on a dp×model mesh (ADVICE r3): the key-bias
    flash call now rides the mesh-aware shard_map wrapper, so heads
    stay sharded over `model` around the Pallas call — logits must
    still match the XLA softmax path on ragged masks, and the compiled
    step must not all-gather heads around the kernel."""
    from tensorflow_examples_tpu.core.mesh import MeshConfig, create_mesh

    base = dict(
        vocab_size=50, max_len=32, num_layers=2, num_heads=4,
        d_model=16, d_ff=32, dropout=0.0,
    )
    mesh = create_mesh(MeshConfig(data=2, model=4))
    model_x = bert.BertClassifier(bert.BertConfig(**base), num_labels=2)
    model_f = bert.BertClassifier(
        bert.BertConfig(**base, attention="flash"), num_labels=2, mesh=mesh
    )
    rng = np.random.default_rng(2)
    tokens = jnp.asarray(rng.integers(1, 50, (4, 32)), jnp.int32)
    lengths = np.asarray([32, 20, 7, 13])
    mask = jnp.asarray(
        (np.arange(32)[None] < lengths[:, None]).astype(np.int32)
    )
    params = model_x.init({"params": jax.random.PRNGKey(0)}, tokens)["params"]
    out_x = model_x.apply({"params": params}, tokens, mask)
    fwd = jax.jit(lambda p, t, m: model_f.apply({"params": p}, t, m))
    with mesh:
        out_f = fwd(params, tokens, mask)
        hlo = fwd.lower(params, tokens, mask).compile().as_text()
    np.testing.assert_allclose(
        np.asarray(out_x), np.asarray(out_f), atol=2e-4, rtol=2e-4
    )
    # The no-gather property itself: the compiled forward's only
    # collectives are the Megatron row-parallel psums — zero all-gather
    # instruction DEFINITIONS (operand references like %all-gather.1
    # don't match the definition regex).
    import re

    defs = re.findall(
        r"^\s*(?:ROOT )?%?[\w.\-]+ = (?:.+?) (all-gather|all-to-all)"
        r"(?:-start)?\(",
        hlo,
        re.M,
    )
    assert not defs, f"unexpected gathers around the flash call: {defs}"


def test_hf_parity():
    """Imported HF BertForSequenceClassification weights → identical logits."""
    torch = pytest.importorskip("torch")
    from transformers import BertConfig as HFBertConfig
    from transformers import BertForSequenceClassification

    from tensorflow_examples_tpu.models.hf_import import import_bert

    hf_cfg = HFBertConfig(
        vocab_size=120, hidden_size=16, num_hidden_layers=2,
        num_attention_heads=2, intermediate_size=32,
        max_position_embeddings=32, num_labels=2,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        classifier_dropout=0.0,
    )
    torch.manual_seed(0)
    hf_model = BertForSequenceClassification(hf_cfg).eval()
    cfg, params = import_bert(hf_model)

    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 120, (2, 12))
    mask = np.ones((2, 12), np.int64)
    mask[1, 8:] = 0
    type_ids = np.zeros((2, 12), np.int64)
    type_ids[:, 6:] = 1
    with torch.no_grad():
        hf_logits = hf_model(
            torch.tensor(tokens),
            attention_mask=torch.tensor(mask),
            token_type_ids=torch.tensor(type_ids),
        ).logits.numpy()

    model = bert.BertClassifier(cfg, num_labels=2)
    ours = model.apply(
        {"params": jax.tree.map(jnp.asarray, params)},
        jnp.asarray(tokens, jnp.int32),
        jnp.asarray(mask, jnp.int32),
        jnp.asarray(type_ids, jnp.int32),
    )
    np.testing.assert_allclose(np.asarray(ours), hf_logits, atol=2e-4)


def test_glue_metric_math():
    """F1/MCC/Pearson from aggregated rates must match direct formulas."""
    rng = np.random.default_rng(0)
    preds = rng.integers(0, 2, 200)
    labels = rng.integers(0, 2, 200)
    m = {
        k: float(v)
        for k, v in glue_metrics.confusion_rates(
            jnp.asarray(preds), jnp.asarray(labels), None
        ).items()
    }
    tp = np.sum((preds == 1) & (labels == 1))
    fp = np.sum((preds == 1) & (labels == 0))
    fn = np.sum((preds == 0) & (labels == 1))
    tn = np.sum((preds == 0) & (labels == 0))
    f1_direct = 2 * tp / (2 * tp + fp + fn)
    mcc_direct = (tp * tn - fp * fn) / np.sqrt(
        (tp + fp) * (tp + fn) * (tn + fp) * (tn + fn)
    )
    assert abs(glue_metrics.f1_from_rates(m) - f1_direct) < 1e-6
    assert abs(glue_metrics.mcc_from_rates(m) - mcc_direct) < 1e-6

    x = rng.normal(0, 1, 300)
    y = 0.7 * x + rng.normal(0, 0.5, 300)
    mm = {
        k: float(v)
        for k, v in glue_metrics.moment_means(
            jnp.asarray(x), jnp.asarray(y), None
        ).items()
    }
    assert abs(
        glue_metrics.pearson_from_moments(mm) - np.corrcoef(x, y)[0, 1]
    ) < 1e-5


def test_finetune_learns_sst2(mesh8):
    cfg = tiny_cfg()
    losses, trainer = run_tiny(cfg, mesh8)
    assert np.all(np.isfinite(losses))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.1
    eval_ds = bert_glue.eval_dataset(cfg)
    metrics = trainer.evaluate(eval_batches(eval_ds, cfg.global_batch_size))
    assert metrics["accuracy"] > 0.6  # planted-marker task is learnable
    assert "tp" not in metrics  # finalize strips raw rates


def test_stsb_regression(mesh8):
    cfg = tiny_cfg(task="stsb", train_steps=30)
    losses, trainer = run_tiny(cfg, mesh8)
    assert np.all(np.isfinite(losses))
    eval_ds = bert_glue.eval_dataset(cfg)
    metrics = trainer.evaluate(eval_batches(eval_ds, cfg.global_batch_size))
    assert "pearson" in metrics and -1.0 <= metrics["pearson"] <= 1.0


def test_cola_mcc(mesh8):
    cfg = tiny_cfg(task="cola", train_steps=10)
    _, trainer = run_tiny(cfg, mesh8)
    eval_ds = bert_glue.eval_dataset(cfg)
    metrics = trainer.evaluate(eval_batches(eval_ds, cfg.global_batch_size))
    assert "mcc" in metrics and -1.0 <= metrics["mcc"] <= 1.0


def test_glue_text_to_finetune_chain(tmp_path, mesh8):
    """The full text path: raw GLUE TSV →
    tools/prepare_glue.py (in-repo WordPiece, vocab built from the task
    text) → <task>_<split>.npz → bert_glue workload fine-tune learns the
    separable toy labels through the shared Trainer."""
    import subprocess
    import sys
    import os

    tsv = tmp_path / "train.tsv"
    rows = ["sentence\tlabel"]
    for i in range(64):
        text = "a wonderful heartfelt triumph" if i % 2 else "a dreary boring failure"
        rows.append(f"{text} number {i}\t{i % 2}")
    tsv.write_text("\n".join(rows) + "\n")
    out = tmp_path / "glue"
    tool = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools",
        "prepare_glue.py",
    )
    r = subprocess.run(
        [
            sys.executable, tool, "--task=sst2", f"--input={tsv}",
            "--split=train", f"--out_dir={out}", "--build_vocab=160",
            "--seq_len=16",
        ],
        capture_output=True,
        text=True,
    )
    assert r.returncode == 0, r.stderr

    cfg = tiny_cfg(
        data_dir=str(out), vocab_size=160, train_steps=30, learning_rate=1e-3
    )
    losses, trainer = run_tiny(cfg, mesh8)
    assert np.all(np.isfinite(losses))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.1, losses
    # Eval on the train-split features (no val file): accuracy ≈ 1 on
    # the separable toy task proves the features carry the signal.
    from tensorflow_examples_tpu.data.sources import load_glue

    ds = load_glue(str(out), "sst2", "train", seq_len=16, vocab_size=160)
    m = trainer.evaluate(eval_batches(ds, cfg.global_batch_size))
    assert m["accuracy"] > 0.9, m
