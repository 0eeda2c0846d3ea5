"""ImageNet workload: synthetic smoke e2e + TFRecord pipeline unit tests."""

import numpy as np
import pytest

from tensorflow_examples_tpu.data import imagenet as imagenet_data
from tensorflow_examples_tpu.train.loop import Trainer
from tensorflow_examples_tpu.workloads import imagenet


def tiny_config(**kw):
    base = dict(
        image_size=32,
        num_classes=4,
        global_batch_size=16,
        train_steps=25,
        warmup_steps=5,
        learning_rate=0.01,
        log_every=10,
        eval_every=0,
        checkpoint_every=0,
        precision="f32",
        eval_batches=2,
    )
    base.update(kw)
    return imagenet.ImagenetConfig(**base)


def test_synthetic_smoke(mesh8):
    cfg = tiny_config()
    trainer = Trainer(imagenet.make_task(cfg), cfg, mesh=mesh8)
    it = imagenet.make_train_iter(cfg, 0)
    state = trainer.state
    losses = []
    for _ in range(cfg.train_steps):
        state, m = trainer._train_step(state, trainer._put_batch(next(it)))
        losses.append(float(m["loss"]))
    trainer.state = state
    assert np.all(np.isfinite(losses))
    # Synthetic stream is deliberately noisy; compare window means.
    early, late = np.mean(losses[:5]), np.mean(losses[-5:])
    assert late < early, f"no learning: {early} -> {late} ({losses})"
    metrics = trainer.evaluate(imagenet.make_eval_iter(cfg))
    assert "accuracy" in metrics and "top5_accuracy" in metrics
    assert 0.0 <= metrics["top5_accuracy"] <= 1.0


def _write_tfrecords(tf, tmp_path, split, n_shards=2, per_shard=3):
    rng = np.random.default_rng(0)
    labels = []
    for s in range(n_shards):
        path = str(tmp_path / f"{split}-{s:05d}-of-{n_shards:05d}")
        with tf.io.TFRecordWriter(path) as w:
            for _ in range(per_shard):
                img = rng.integers(0, 255, (48, 64, 3), np.uint8)
                label = int(rng.integers(1, 5))  # 1-based, ImageNet style
                labels.append(label)
                ex = tf.train.Example(
                    features=tf.train.Features(
                        feature={
                            "image/encoded": tf.train.Feature(
                                bytes_list=tf.train.BytesList(
                                    value=[tf.io.encode_jpeg(img).numpy()]
                                )
                            ),
                            "image/class/label": tf.train.Feature(
                                int64_list=tf.train.Int64List(value=[label])
                            ),
                        }
                    )
                )
                w.write(ex.SerializeToString())
    return labels


def test_tfrecord_pipeline(tmp_path):
    tf = pytest.importorskip("tensorflow")
    _write_tfrecords(tf, tmp_path, "train")
    _write_tfrecords(tf, tmp_path, "validation")
    assert imagenet_data.has_tfrecords(str(tmp_path), "train")

    it = imagenet_data.tfrecord_iter(
        str(tmp_path), "train", 4, train=True, image_size=32
    )
    b = next(it)
    assert b["image"].shape == (4, 32, 32, 3)
    assert b["image"].dtype == np.float32
    assert b["label"].min() >= 0 and b["label"].max() <= 3  # 1-based → 0-based

    # Eval: 6 examples at batch 4 → final batch padded with mask.
    batches = list(
        imagenet_data.tfrecord_iter(
            str(tmp_path), "validation", 4, train=False, image_size=32
        )
    )
    assert len(batches) == 2
    assert batches[0]["mask"].sum() == 4
    assert batches[1]["mask"].sum() == 2
    assert batches[1]["image"].shape == (4, 32, 32, 3)


def test_tfrecord_exact_resume(tmp_path):
    """Exact resume on the STREAMING path. A resumed
    iterator (start_step=4) must replay the uninterrupted run's batches
    5… bit-exactly — shuffles, epoch boundaries, and random crop/flip
    augmentations all reproduced on TFRecord data."""
    tf = pytest.importorskip("tensorflow")
    _write_tfrecords(tf, tmp_path, "train", n_shards=2, per_shard=8)

    def take(start_step, n):
        it = imagenet_data.tfrecord_iter(
            str(tmp_path), "train", 4, train=True, image_size=32,
            seed=3, exact=True, start_step=start_step,
        )
        return [next(it) for _ in range(n)]

    # 8 steps × batch 4 = 32 records = 2 epochs of the 16-record set:
    # the comparison crosses an epoch boundary (reshuffle + re-augment).
    full = take(0, 8)
    resumed = take(4, 4)  # resume exactly at the epoch boundary
    for want, got in zip(full[4:], resumed):
        np.testing.assert_array_equal(want["label"], got["label"])
        np.testing.assert_array_equal(want["image"], got["image"])
    # Mid-epoch resumes: in-epoch record skip in epoch 0 and in epoch 1.
    for start in (2, 5):
        got = take(start, 2)
        for want, g in zip(full[start:], got):
            np.testing.assert_array_equal(want["label"], g["label"])
            np.testing.assert_array_equal(want["image"], g["image"])
    # Same seed, fresh run: reproducible from the top as well.
    again = take(0, 2)
    np.testing.assert_array_equal(full[0]["image"], again[0]["image"])
    # Augmentations really are live on this path (two records of the
    # same class differ unless crop/flip collapsed to identity).
    assert not np.array_equal(full[0]["image"], full[1]["image"])


def test_tfrecord_exact_resume_through_workload(tmp_path):
    """The workload plumbs (start_step, deterministic_input) into the
    pipeline — the path fit() uses when restoring a checkpoint."""
    tf = pytest.importorskip("tensorflow")
    _write_tfrecords(tf, tmp_path, "train", n_shards=2, per_shard=8)
    cfg = tiny_config(data_dir=str(tmp_path), global_batch_size=4)

    it0 = imagenet.make_train_iter(cfg, 0)
    full = [next(it0) for _ in range(5)]
    it4 = imagenet.make_train_iter(cfg, 4)
    got = next(it4)
    np.testing.assert_array_equal(full[4]["image"], got["image"])
    np.testing.assert_array_equal(full[4]["label"], got["label"])


def test_workload_routes_to_parallel_pipeline(tmp_path):
    """ISSUE 6 wiring: --input_workers>0 moves the TFRecord train path
    onto the sharded-reader + worker-pool pipeline (background-marked,
    closeable, deterministic across rebuilds), without touching the
    default (input_workers=0) tf.data path."""
    import threading

    from tensorflow_examples_tpu.data import sources as sources_mod

    rng = np.random.default_rng(0)

    def jpeg():
        import io

        from PIL import Image

        img = rng.integers(0, 255, (40, 48, 3), np.uint8)
        buf = io.BytesIO()
        Image.fromarray(img).save(buf, format="JPEG", quality=85)
        return buf.getvalue()

    for s in range(2):
        sources_mod.write_tfrecord(
            str(tmp_path / f"train-{s:05d}-of-00002"),
            [
                sources_mod.make_example(
                    {"image/encoded": jpeg(), "image/class/label": 1 + s}
                )
                for _ in range(8)
            ],
        )
    cfg = tiny_config(
        data_dir=str(tmp_path), global_batch_size=4,
        input_workers=2, input_readers=2,
    )
    started = threading.active_count()
    it = imagenet.make_train_iter(cfg, 0)
    assert getattr(it, "background", False)  # prefetch records data_wait
    a = [next(it) for _ in range(3)]
    assert a[0]["image"].shape == (4, cfg.image_size, cfg.image_size, 3)
    it.close()
    it2 = imagenet.make_train_iter(cfg, 0)
    b = [next(it2) for _ in range(3)]
    it2.close()
    for want, got in zip(a, b):
        np.testing.assert_array_equal(want["image"], got["image"])
    deadline = __import__("time").time() + 5
    while (
        threading.active_count() > started
        and __import__("time").time() < deadline
    ):
        __import__("time").sleep(0.01)
    assert threading.active_count() <= started  # clean drain, no orphans


def test_synthetic_stream_determinism():
    a = next(imagenet_data.synthetic_train_iter(4, image_size=16, seed=7))
    b = next(imagenet_data.synthetic_train_iter(4, image_size=16, seed=7))
    np.testing.assert_array_equal(a["image"], b["image"])
    c = next(
        imagenet_data.synthetic_train_iter(4, image_size=16, seed=7, start_step=1)
    )
    assert not np.array_equal(a["image"], c["image"])
