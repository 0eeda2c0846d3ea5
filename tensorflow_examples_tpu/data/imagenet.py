"""ImageNet input pipeline (SURVEY.md §3(4) — the perf-critical one).

Reference shape: ``TFRecordDataset(shards) → shuffle → map(decode_jpeg +
augment, parallel) → batch → prefetch(device)`` on host CPU threads
overlapped with the device step. Here the same stages run through
``tf.data`` **as a host-side reader only** (TF never touches the TPU;
batches cross into JAX as numpy), feeding the shared loop's async
device-prefetch queue (data/prefetch.py) which replaces
``experimental_distribute_dataset`` + device prefetch:

- standard ImageNet TFRecord schema (``image/encoded``,
  ``image/class/label``) with the classic ResNet augmentation:
  sample_distorted_bounding_box crop → resize 224 → random flip for
  train; 87.5% central crop for eval.
- per-host sharding by ``jax.process_index`` (the multi-worker
  ``dataset.shard(num_workers, index)`` equivalent, SURVEY.md §3(5)).
- without ``data_dir``: a seeded synthetic stream with label-correlated
  low-rank image structure — learnable, so integration tests assert
  actual training, with O(classes·size) memory instead of materializing
  full images.
"""

from __future__ import annotations

import os
from typing import Iterator

import numpy as np

MEAN_RGB = np.array([0.485, 0.456, 0.406], np.float32)
STDDEV_RGB = np.array([0.229, 0.224, 0.225], np.float32)


# --------------------------------------------------------------- synthetic


class SyntheticImageNet:
    """Streaming label-correlated synthetic images.

    Image for class c = outer(u_c, v_c) pattern + noise; u, v are seeded
    per class, so storage is O(classes · size), not O(n · size²)."""

    def __init__(self, *, image_size=224, num_classes=1000, seed=0):
        rng = np.random.default_rng(seed)
        self.u = rng.normal(0, 1, (num_classes, image_size)).astype(np.float32)
        self.v = rng.normal(0, 1, (num_classes, image_size)).astype(np.float32)
        self.phase = rng.normal(0, 1, (num_classes, 3)).astype(np.float32)
        self.num_classes = num_classes
        self.image_size = image_size

    def batch(self, batch_size: int, rng: np.random.Generator):
        y = rng.integers(0, self.num_classes, batch_size).astype(np.int32)
        base = np.einsum("bh,bw->bhw", self.u[y], self.v[y])
        img = base[..., None] * self.phase[y][:, None, None, :]
        img += rng.normal(0, 2.0, img.shape).astype(np.float32)
        return {"image": img.astype(np.float32), "label": y}


def synthetic_train_iter(
    batch_size: int,
    *,
    image_size=224,
    num_classes=1000,
    seed=0,
    start_step=0,
) -> Iterator[dict]:
    src = SyntheticImageNet(
        image_size=image_size, num_classes=num_classes, seed=seed
    )
    step = start_step
    while True:
        yield src.batch(batch_size, np.random.default_rng((seed, step)))
        step += 1


def synthetic_eval_iter(
    batch_size: int, *, image_size=224, num_classes=1000, seed=1, batches=8
) -> Iterator[dict]:
    src = SyntheticImageNet(
        image_size=image_size, num_classes=num_classes, seed=seed
    )
    for step in range(batches):
        b = src.batch(batch_size, np.random.default_rng((seed, step)))
        b["mask"] = np.ones(batch_size, np.float32)
        yield b


# ---------------------------------------------------------------- tfrecord


def _tf():
    import tensorflow as tf

    tf.config.set_visible_devices([], "GPU")  # host-side reader only
    try:
        tf.config.set_visible_devices([], "TPU")
    except Exception:
        pass
    return tf


def _parse_and_decode(tf, record, *, train: bool, image_size: int, aug_seed=None):
    """Decode one example. ``aug_seed`` (a [2] int tensor) switches the
    train augmentations to their STATELESS variants keyed on it — the
    exact-resume path, where the same stream position must produce the
    same crop/flip on every run."""
    feats = tf.io.parse_single_example(
        record,
        {
            "image/encoded": tf.io.FixedLenFeature([], tf.string),
            "image/class/label": tf.io.FixedLenFeature([], tf.int64),
        },
    )
    img_bytes = feats["image/encoded"]
    if train:
        # Classic ResNet crop: random area 8–100%, aspect 3/4–4/3.
        crop_kw = dict(
            bounding_boxes=tf.zeros([1, 0, 4], tf.float32),
            area_range=(0.08, 1.0),
            aspect_ratio_range=(3 / 4, 4 / 3),
            max_attempts=10,
            use_image_if_no_bounding_boxes=True,
        )
        shape = tf.io.extract_jpeg_shape(img_bytes)
        if aug_seed is not None:
            begin, size, _ = tf.image.stateless_sample_distorted_bounding_box(
                shape, seed=aug_seed, **crop_kw
            )
        else:
            begin, size, _ = tf.image.sample_distorted_bounding_box(
                shape, **crop_kw
            )
        y, x, _ = tf.unstack(begin)
        h, w, _ = tf.unstack(size)
        img = tf.image.decode_and_crop_jpeg(
            img_bytes, tf.stack([y, x, h, w]), channels=3
        )
        img = tf.image.resize(img, [image_size, image_size])
        if aug_seed is not None:
            img = tf.image.stateless_random_flip_left_right(
                img, seed=aug_seed + 1
            )
        else:
            img = tf.image.random_flip_left_right(img)
    else:
        img = tf.io.decode_jpeg(img_bytes, channels=3)
        shape = tf.shape(img)
        crop = tf.cast(
            tf.cast(tf.minimum(shape[0], shape[1]), tf.float32) * 0.875, tf.int32
        )
        img = tf.image.resize_with_crop_or_pad(img, crop, crop)
        img = tf.image.resize(img, [image_size, image_size])
    # Emit uint8: normalization runs in the threaded C++ host library
    # (native/fastdata.cpp) after the tf graph — and uint8 batches are
    # 4x cheaper to move between tf.data and numpy.
    img = tf.cast(tf.clip_by_value(img, 0.0, 255.0), tf.uint8)
    # ImageNet TFRecord labels are 1-based.
    label = tf.cast(feats["image/class/label"], tf.int32) - 1
    return {"image": img, "label": label}


def _count_records(tf, files: list, data_dir: str, tag: str) -> int:
    """Total record count across ``files`` — one IO-only pass (no JPEG
    decode), cached keyed by the shard list + sizes, so it runs once
    per dataset, not once per resume.

    The cache lives in a HOST-LOCAL dir (``$TFE_TPU_CACHE_DIR``,
    default ``~/.cache/tensorflow_examples_tpu``), never next to the
    shards: data dirs are often shared read-mostly buckets, and a cold
    multi-host start would have every host racing writes into them
    (ADVICE r3). Each host counts only its own shard subset, so the
    cold-start counting pass itself is per-host by construction; the
    cache just keeps it off the resume path."""
    import hashlib
    import json

    # data_dir participates in the key: the cache is global per host,
    # and two datasets with the standard shard naming and equal sizes
    # but different contents must not share a count. Only genuinely
    # local paths are normalized — abspath would both mangle remote
    # URLs ('gs://b/x' -> '<cwd>/gs:/b/x') and make the key depend on
    # the launch CWD, missing the cache on every scheduler restart.
    is_url = "://" in data_dir
    sig = hashlib.sha1(
        "|".join(
            [data_dir if is_url else os.path.abspath(data_dir)]
            + [
                f"{os.path.basename(f)}:{tf.io.gfile.stat(f).length}"
                for f in files
            ]
        ).encode()
    ).hexdigest()[:16]
    cache_dir = os.environ.get(
        "TFE_TPU_CACHE_DIR",
        os.path.join(os.path.expanduser("~"), ".cache", "tensorflow_examples_tpu"),
    )
    cache = os.path.join(cache_dir, f"record_count-{tag}-{sig}.json")
    try:
        with tf.io.gfile.GFile(cache, "r") as fh:
            return int(json.load(fh)["count"])
    except Exception:
        pass
    n = int(
        tf.data.TFRecordDataset(files)
        .batch(4096)
        .reduce(
            np.int64(0), lambda acc, b: acc + tf.shape(b, out_type=tf.int64)[0]
        )
        .numpy()
    )
    try:
        os.makedirs(cache_dir, exist_ok=True)
        with tf.io.gfile.GFile(cache, "w") as fh:
            json.dump({"count": n}, fh)
    except Exception:
        pass
    return n


def _mix(seed: int, epoch: int) -> int:
    """Cheap int mix for per-epoch tf.data seeds (kept in int32 range)."""
    return (seed * 1_000_003 + epoch * 7919 + 1) % (2**31 - 1)


def tfrecord_iter(
    data_dir: str,
    split: str,
    batch_size: int,
    *,
    train: bool,
    image_size: int = 224,
    seed: int = 0,
    num_parallel: int = 16,
    start_step: int = 0,
    exact: bool = False,
) -> Iterator[dict]:
    """Host tf.data pipeline → numpy batches (masked final eval batch).

    ``exact=True`` (train only) makes the stream a pure function of
    ``seed`` and checkpoint-resumable (SURVEY.md §4, §5b): each epoch is
    an independent deterministic dataset — files permuted by
    numpy ``(seed, epoch)``, seeded record shuffle, order-preserving
    interleave, stateless crop/flip keyed on (seed·epoch mix, in-epoch
    record index) — chained by a Python epoch loop. Resume cost is
    BOUNDED BY ONE EPOCH: a one-time cached record count (IO-only pass,
    no decode) converts ``start_step`` into (epoch, in-epoch offset), so
    restoring at step 450k skips at most one epoch's records of IO and
    none of the decode/augment — and yields batches bit-identical to the
    uninterrupted run's steps N, N+1, … Cost of exactness: the
    order-preserving interleave gives up some read parallelism slack —
    measured small next to decode+augment; flip ``exact=False`` for
    maximum-throughput non-resumable input.
    ``exact=False`` ignores ``start_step`` (a fresh nondeterministic
    shuffle makes skipping meaningless).
    """
    import jax

    tf = _tf()
    pattern = os.path.join(data_dir, f"{split}-*")
    files = sorted(tf.io.gfile.glob(pattern))
    if not files:
        raise FileNotFoundError(f"no TFRecord shards matching {pattern}")
    # Per-host input sharding (multi-host DP, SURVEY.md §3(5)).
    nproc, pidx = jax.process_count(), jax.process_index()
    host_files = files[pidx::nproc]

    if exact and train:
        yield from _exact_train_stream(
            tf, host_files, data_dir, split, batch_size,
            image_size=image_size, seed=seed, num_parallel=num_parallel,
            start_step=start_step,
        )
        return

    if _native_decode_enabled():
        yield from _native_stream(
            tf, host_files, batch_size, train=train,
            image_size=image_size, seed=seed, num_parallel=num_parallel,
        )
        return

    ds = tf.data.Dataset.from_tensor_slices(host_files)
    if train:
        ds = ds.shuffle(len(host_files), seed=seed)
    ds = ds.interleave(
        tf.data.TFRecordDataset,
        cycle_length=num_parallel,
        num_parallel_calls=tf.data.AUTOTUNE,
        deterministic=not train,
    )
    if train:
        ds = ds.shuffle(16 * batch_size, seed=seed)
        ds = ds.repeat()
    ds = ds.map(
        lambda r: _parse_and_decode(tf, r, train=train, image_size=image_size),
        num_parallel_calls=tf.data.AUTOTUNE,
    )
    ds = ds.batch(batch_size, drop_remainder=train)
    ds = ds.prefetch(tf.data.AUTOTUNE)

    for batch in ds.as_numpy_iterator():
        out = {"image": _normalize_uint8(batch["image"]), "label": batch["label"]}
        n = len(out["label"])
        if not train and n < batch_size:
            pad = batch_size - n
            out = {
                k: np.concatenate([v, np.repeat(v[-1:], pad, axis=0)])
                for k, v in out.items()
            }
            out["mask"] = np.concatenate(
                [np.ones(n, np.float32), np.zeros(pad, np.float32)]
            )
        elif not train:
            out["mask"] = np.ones(n, np.float32)
        yield out


def _native_decode_enabled() -> bool:
    """The one C++ stage (libfastjpeg: decode + crop + resize + flip +
    normalize) is used whenever it built; set
    ``TFE_TPU_NATIVE_DECODE=0`` to force the tf.data decode path."""
    if os.environ.get("TFE_TPU_NATIVE_DECODE", "1") == "0":
        return False
    from tensorflow_examples_tpu import native

    return native.available("fastjpeg")


def _image_seeds(seed: int, step: int, n: int) -> np.ndarray:
    """Per-image uint64 splitmix64 seeds for the C++ augment stream —
    a pure function of (dataset seed, batch index, row), so a given
    stream position always draws the same crop/flip. Mixing wraps mod
    2**64 by design; done in Python ints because numpy SCALAR uint64
    multiplies emit overflow RuntimeWarnings on wraparound."""
    m = 2**64
    base = (seed * 0x9E3779B97F4A7C15 + step * 0xC2B2AE3D27D4EB4F) % m
    k3 = 0x165667B19E3779F9
    return np.array([(base + i * k3) % m for i in range(n)], np.uint64)


def _native_stream(
    tf, host_files, batch_size, *, train, image_size, seed, num_parallel
):
    """tf.data as record reader ONLY (parse proto → bytes + label); the
    whole per-image path — JPEG decode (DCT-scaled), ResNet crop,
    bilinear resize, flip, normalize — is one threaded C++ call
    (native/fastjpeg.cpp). Not resume-exact; the ``exact`` stream keeps
    the stateless-tf path."""
    from tensorflow_examples_tpu import native

    def parse_only(record):
        feats = tf.io.parse_single_example(
            record,
            {
                "image/encoded": tf.io.FixedLenFeature([], tf.string),
                "image/class/label": tf.io.FixedLenFeature([], tf.int64),
            },
        )
        return {
            "encoded": feats["image/encoded"],
            "label": tf.cast(feats["image/class/label"], tf.int32) - 1,
        }

    ds = tf.data.Dataset.from_tensor_slices(host_files)
    if train:
        ds = ds.shuffle(len(host_files), seed=seed)
    ds = ds.interleave(
        tf.data.TFRecordDataset,
        cycle_length=num_parallel,
        num_parallel_calls=tf.data.AUTOTUNE,
        deterministic=not train,
    )
    if train:
        ds = ds.shuffle(16 * batch_size, seed=seed)
        ds = ds.repeat()
    ds = ds.map(parse_only, num_parallel_calls=tf.data.AUTOTUNE)
    ds = ds.batch(batch_size, drop_remainder=train)
    ds = ds.prefetch(tf.data.AUTOTUNE)

    step = 0
    for batch in ds.as_numpy_iterator():
        jpegs = list(batch["encoded"])
        n = len(jpegs)
        res = native.decode_augment_batch(
            jpegs,
            train=train,
            out_size=image_size,
            seeds=_image_seeds(seed, step, n) if train else None,
            mean=MEAN_RGB,
            std=STDDEV_RGB,
        )
        assert res is not None, "fastjpeg vanished mid-stream"
        img, _ok = res  # failed decodes are zero-filled (corrupt shards)
        out = {"image": img, "label": batch["label"]}
        if not train and n < batch_size:
            pad = batch_size - n
            out = {
                k: np.concatenate([v, np.repeat(v[-1:], pad, axis=0)])
                for k, v in out.items()
            }
            out["mask"] = np.concatenate(
                [np.ones(n, np.float32), np.zeros(pad, np.float32)]
            )
        elif not train:
            out["mask"] = np.ones(n, np.float32)
        yield out
        step += 1


# ------------------------------------------------- native-augment mirror
#
# Pure-numpy reference for native/fastjpeg.cpp's crop/resize/flip/
# normalize — SAME splitmix64 draws, same arithmetic — so the C++ stage
# is testable against numpy on any host (tests/test_native.py). Decode
# itself is mirrored with PIL (also libjpeg underneath; parity is
# tolerance-checked, not bit-exact, because IDCT rounding may differ
# between libjpeg builds).


class _SplitMix64:
    MASK = 2**64 - 1

    def __init__(self, seed: int):
        self.s = int(seed) & self.MASK

    def next(self) -> int:
        self.s = (self.s + 0x9E3779B97F4A7C15) & self.MASK
        z = self.s
        z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & self.MASK
        z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & self.MASK
        return z ^ (z >> 31)

    def u01(self) -> float:
        return (self.next() >> 11) * (1.0 / 9007199254740992.0)


def _mirror_crop(h, w, train, rng):
    """(y0, x0, ch, cw, flip) — draw-for-draw mirror of fastjpeg.cpp."""
    import math

    if not train:
        m = min(h, w)
        crop = max(1, int(0.875 * m))
        return (h - crop) // 2, (w - crop) // 2, crop, crop, False
    log_lo, log_hi = math.log(3 / 4), math.log(4 / 3)
    found = None
    for _ in range(10):
        a_frac = 0.08 + rng.u01() * 0.92
        ratio = math.exp(log_lo + rng.u01() * (log_hi - log_lo))
        area = a_frac * h * w
        cw = int(math.floor(math.sqrt(area * ratio) + 0.5))
        ch = int(math.floor(math.sqrt(area / ratio) + 0.5))
        if 1 <= cw <= w and 1 <= ch <= h:
            y0 = int(math.floor(rng.u01() * (h - ch + 1)))
            x0 = int(math.floor(rng.u01() * (w - cw + 1)))
            found = (y0, x0, ch, cw)
            break
    if found is None:
        m = min(h, w)
        found = ((h - m) // 2, (w - m) // 2, m, m)
    flip = rng.u01() < 0.5
    return (*found, flip)


def _decode_crop_resize(
    jpeg: bytes, *, train: bool, seed: int, out_size: int
) -> np.ndarray:
    """One image through the PIL/numpy mirror of fastjpeg.cpp's decode +
    crop + bilinear resize + flip — WITHOUT normalization, returning the
    [S, S, 3] f32 0..255 image. Normalization is applied batched by the
    caller (data/augment.normalize_images) with the identical f32
    expression, so batching changes nothing byte-wise."""
    import io

    from PIL import Image

    img = np.asarray(Image.open(io.BytesIO(jpeg)).convert("RGB"), np.float64)
    h, w, _ = img.shape
    rng = _SplitMix64(seed)
    y0, x0, ch, cw, flip = _mirror_crop(h, w, train, rng)
    oy = np.arange(out_size)
    sy = y0 + (oy + 0.5) * ch / out_size - 0.5
    y1 = np.clip(np.floor(sy).astype(np.int64), 0, h - 1)
    y2 = np.clip(y1 + 1, 0, h - 1)
    fy = sy - np.floor(sy)
    sx = x0 + (oy + 0.5) * cw / out_size - 0.5
    x1 = np.clip(np.floor(sx).astype(np.int64), 0, w - 1)
    x2 = np.clip(x1 + 1, 0, w - 1)
    fx = sx - np.floor(sx)
    top = img[y1][:, x1] * ((1 - fy)[:, None] * (1 - fx)[None, :])[..., None] \
        + img[y1][:, x2] * ((1 - fy)[:, None] * fx[None, :])[..., None]
    bot = img[y2][:, x1] * (fy[:, None] * (1 - fx)[None, :])[..., None] \
        + img[y2][:, x2] * (fy[:, None] * fx[None, :])[..., None]
    res = top + bot
    if flip:
        res = res[:, ::-1]
    return res.astype(np.float32)


def decode_augment_reference(
    jpeg: bytes, *, train: bool, seed: int, out_size: int
) -> np.ndarray:
    """Numpy mirror of one fastjpeg.cpp image (denom=1 path: exact when
    the crop is < 2x out_size, which any test-sized image satisfies)."""
    raw = _decode_crop_resize(
        jpeg, train=train, seed=seed, out_size=out_size
    )
    return ((raw / 255.0) - MEAN_RGB) / STDDEV_RGB


def _normalize_uint8(images: np.ndarray) -> np.ndarray:
    """uint8 HWC batch → normalized f32 via the threaded C++ host library
    (native/fastdata.cpp); numpy fallback is the batched LUT gather
    (data/augment.normalize_images — byte-identical to the direct
    expression, mean/std broadcast once). Single definition so the
    exact and non-exact streams cannot drift."""
    from tensorflow_examples_tpu import native
    from tensorflow_examples_tpu.data import augment as augment_mod

    img = native.normalize(images, MEAN_RGB, STDDEV_RGB)
    if img is None:
        img = augment_mod.normalize_images(images, MEAN_RGB, STDDEV_RGB)
    return img


def _exact_train_stream(
    tf,
    host_files: list,
    data_dir: str,
    split: str,
    batch_size: int,
    *,
    image_size: int,
    seed: int,
    num_parallel: int,
    start_step: int,
):
    """Epoch-chained deterministic train stream (see tfrecord_iter).

    Each epoch is built fresh from (seed, epoch): numpy file
    permutation → deterministic interleave → seeded record shuffle
    (reshuffle OFF — the epoch seed varies instead) → skip (first
    resumed epoch only) → stateless-augment map → batch. ``start_step``
    maps to (epoch, in-epoch batches) via the cached per-host record
    count, so the skip never exceeds one epoch."""
    n_records = _count_records(
        tf, host_files, data_dir, f"{split}-h{len(host_files)}"
    )
    bpe = n_records // batch_size  # drop_remainder batches per epoch
    if bpe == 0:
        raise ValueError(
            f"{n_records} records in this host's {split} shards is less "
            f"than one batch of {batch_size}"
        )
    epoch, within = divmod(start_step, bpe)
    skip_records = within * batch_size

    while True:
        rng = np.random.default_rng((seed, epoch))
        order = [host_files[i] for i in rng.permutation(len(host_files))]
        eseed = _mix(seed, epoch)
        ds = tf.data.Dataset.from_tensor_slices(order)
        ds = ds.interleave(
            tf.data.TFRecordDataset,
            cycle_length=num_parallel,
            num_parallel_calls=tf.data.AUTOTUNE,
            deterministic=True,
        )
        ds = ds.shuffle(
            16 * batch_size, seed=eseed, reshuffle_each_iteration=False
        )
        # In-epoch index BEFORE the skip: position k of a resumed epoch
        # carries the same index — hence the same stateless crop/flip —
        # as in the uninterrupted run.
        ds = ds.enumerate()
        if skip_records:
            ds = ds.skip(skip_records)
        ds = ds.map(
            lambda i, r: _parse_and_decode(
                tf, r, train=True, image_size=image_size,
                aug_seed=tf.stack([tf.constant(eseed, tf.int64), i]),
            ),
            num_parallel_calls=tf.data.AUTOTUNE,
        )
        ds = ds.batch(batch_size, drop_remainder=True)
        ds = ds.prefetch(tf.data.AUTOTUNE)
        for batch in ds.as_numpy_iterator():
            yield {
                "image": _normalize_uint8(batch["image"]),
                "label": batch["label"],
            }
        epoch += 1
        skip_records = 0


def has_tfrecords(data_dir: str, split: str) -> bool:
    if not data_dir:
        return False
    import glob

    return bool(glob.glob(os.path.join(data_dir, f"{split}-*")))


# ----------------------------------- parallel pipeline (ISSUE 6 tentpole)
#
# The pure-python hot path: sharded parallel readers (data/sources.py
# ShardedReader — no tf import anywhere on this path) feeding a
# background decode/augment worker pool (data/workers.py). Everything is
# a pure function of (seed, start_step): per-epoch shard order is a
# seeded permutation, records flow in deterministic shard order for ANY
# reader count, per-image augment seeds are keyed on the global batch
# index — so the stream is bit-identical to the sequential
# single-reader/zero-worker reference AND exactly resumable (the golden
# contract tools/host_input_bench.py measures and tests pin).


def parse_imagenet_example(record: bytes) -> tuple[bytes, int]:
    """(jpeg bytes, 0-based label) from one standard ImageNet Example."""
    from tensorflow_examples_tpu.data import sources as sources_mod

    feats = sources_mod.parse_example(record)
    try:
        jpeg = feats["image/encoded"][0]
        label = int(feats["image/class/label"][0]) - 1  # 1-based on disk
    except (KeyError, IndexError) as e:
        raise ValueError(
            f"record is not ImageNet-schema (have {sorted(feats)})"
        ) from e
    return jpeg, label


def decode_augment_batch(
    jpegs: list,
    labels: list,
    *,
    train: bool,
    image_size: int,
    seed: int,
    step: int,
    threads: int | None = None,
) -> dict:
    """One pipeline batch: JPEG decode + ResNet crop/resize/flip +
    normalize. Prefers the threaded C++ stage (native/fastjpeg.cpp) when
    built and enabled; otherwise the PIL/numpy mirror with the SAME
    splitmix64 augment draws, normalized in one batched broadcast
    (data/augment.normalize_images). Deterministic given
    (seed, step, row) on either path."""
    from tensorflow_examples_tpu import native
    from tensorflow_examples_tpu.data import augment as augment_mod

    n = len(jpegs)
    seeds = _image_seeds(seed, step, n) if train else None
    if _native_decode_enabled():
        res = native.decode_augment_batch(
            jpegs, train=train, out_size=image_size, seeds=seeds,
            mean=MEAN_RGB, std=STDDEV_RGB, threads=threads,
        )
        if res is not None:
            img, ok = res
            if not ok.all():
                # Loud, like the PIL fallback (which raises on a bad
                # JPEG): a zero-filled image with a real label is
                # silent training-data corruption. The failure lands at
                # a deterministic batch index on every path.
                bad = [int(i) for i in np.flatnonzero(ok == 0)]
                raise ValueError(
                    f"undecodable JPEG record(s) at batch {step} "
                    f"rows {bad} (corrupt shard?)"
                )
            return {"image": img, "label": np.asarray(labels, np.int32)}
    raw = np.stack(
        [
            _decode_crop_resize(
                j,
                train=train,
                seed=int(seeds[i]) if seeds is not None else 0,
                out_size=image_size,
            )
            for i, j in enumerate(jpegs)
        ]
    )
    img = augment_mod.normalize_images(raw, MEAN_RGB, STDDEV_RGB)
    return {"image": img, "label": np.asarray(labels, np.int32)}


def _count_records_py(host_files: list, data_dir: str, tag: str) -> int:
    """Pure-python record count across this host's shards, cached in
    the same host-local dir as the tf path's count (see
    ``_count_records`` for the cache-placement rationale)."""
    import hashlib
    import json

    from tensorflow_examples_tpu.data import sources as sources_mod

    is_url = "://" in data_dir
    sig = hashlib.sha1(
        "|".join(
            [data_dir if is_url else os.path.abspath(data_dir)]
            + [
                f"{os.path.basename(f)}:{os.path.getsize(f)}"
                for f in host_files
            ]
        ).encode()
    ).hexdigest()[:16]
    cache_dir = os.environ.get(
        "TFE_TPU_CACHE_DIR",
        os.path.join(os.path.expanduser("~"), ".cache", "tensorflow_examples_tpu"),
    )
    cache = os.path.join(cache_dir, f"record_count-{tag}-{sig}.json")
    try:
        with open(cache) as fh:
            return int(json.load(fh)["count"])
    except Exception:
        pass
    n = sum(
        sum(1 for _ in sources_mod.iter_tfrecord_records(f))
        for f in host_files
    )
    try:
        os.makedirs(cache_dir, exist_ok=True)
        with open(cache, "w") as fh:
            json.dump({"count": n}, fh)
    except Exception:
        pass
    return n


def parallel_tfrecord_iter(
    data_dir: str,
    split: str,
    batch_size: int,
    *,
    train: bool,
    image_size: int = 224,
    seed: int = 0,
    num_readers: int = 2,
    num_workers: int = 0,
    start_step: int = 0,
    host_index: int | None = None,
    host_count: int | None = None,
    buffer_records: int = 512,
    decode_threads: int | None = None,
    shuffle_window: int | None = None,
):
    """Sharded-parallel, worker-pipelined, exactly-resumable TFRecord
    input (ISSUE 6 tentpole). Per-host sharding semantics match
    ``tfrecord_iter`` (``files[host::hosts]``); pass ``host_index`` /
    ``host_count`` explicitly to simulate a fleet without jax.

    Train: infinite epoch-chained stream, each epoch a seeded shard
    permutation read in deterministic order, batches dropped at the
    epoch remainder, per-image augment seeds keyed on the global batch
    index. ``start_step`` resumes mid-epoch (skip bounded by one
    epoch's records, none of the decode). Eval: one pass, final batch
    padded with a zero ``mask``.

    ``num_workers > 0`` returns a closeable
    :class:`~tensorflow_examples_tpu.data.workers.PipelinedIterator`
    (``background = True`` — the prefetch layer records pops as
    ``data_wait``); ``num_workers == 0`` decodes inline — the
    sequential reference the parallel stream is bit-identical to.
    """
    import glob as glob_mod

    from tensorflow_examples_tpu.data import sources as sources_mod
    from tensorflow_examples_tpu.data import workers as workers_mod

    pattern = os.path.join(data_dir, f"{split}-*")
    files = sorted(glob_mod.glob(pattern))
    if not files:
        raise FileNotFoundError(f"no TFRecord shards matching {pattern}")
    if host_index is None or host_count is None:
        import jax

        host_index = jax.process_index() if host_index is None else host_index
        host_count = jax.process_count() if host_count is None else host_count
    host_files = files[host_index::host_count]
    if not host_files:
        raise ValueError(
            f"host {host_index}/{host_count} holds zero of the "
            f"{len(files)} {split} shards"
        )
    # Pool workers decode single-threaded (the pool IS the parallelism);
    # the inline path lets the C++ stage use its own threads unless the
    # caller pins it (the bench's sequential reference pins 1).
    if decode_threads is None and num_workers > 0:
        decode_threads = 1

    def decode(item):
        """One worker item: raw records -> parsed -> decoded batch.
        Parsing sits in the worker stage so the consumer thread's
        serial (GIL-held) work per batch is just chunking bytes."""
        step, records = item
        jpegs, labels = [], []
        for rec in records:
            jpeg, label = parse_imagenet_example(rec)
            jpegs.append(jpeg)
            labels.append(label)
        return decode_augment_batch(
            jpegs, labels, train=train, image_size=image_size,
            seed=seed, step=step, threads=decode_threads,
        )

    if train:
        n_records = _count_records_py(
            host_files, data_dir, f"{split}-h{len(host_files)}"
        )
        bpe = n_records // batch_size
        if bpe == 0:
            raise ValueError(
                f"{n_records} records in this host's {split} shards is "
                f"less than one batch of {batch_size}"
            )
        epoch0, within = divmod(start_step, bpe)

        def raw_batches():
            import contextlib

            step = start_step
            epoch = epoch0
            skip = within * batch_size
            while True:
                rng = np.random.default_rng((seed, epoch))
                order = [
                    host_files[i]
                    for i in rng.permutation(len(host_files))
                ]
                records = sources_mod.interleave_shards(
                    order,
                    sources_mod.iter_tfrecord_records,
                    num_readers=num_readers,
                    buffer_records=buffer_records,
                )
                # Record-level shuffle (the tf.data path's 16*batch
                # shuffle buffer): seeded per epoch, applied to the
                # deterministic merged stream — so it mixes WITHIN
                # shards without breaking reader-count independence.
                # The resume skip runs POST-shuffle: batch N of a
                # resumed epoch is the same batch N the uninterrupted
                # run produced. ``shuffle_window`` overrides the
                # default 16*batch window (0 disables; the bench keeps
                # the window under its tiny epoch so it measures the
                # streaming regime real epochs run in).
                window = (
                    16 * batch_size
                    if shuffle_window is None
                    else shuffle_window
                )
                stream = sources_mod.seeded_window_shuffle(
                    records,
                    window,
                    np.random.default_rng((seed, epoch, 1)),
                )
                group: list = []
                with contextlib.closing(records):
                    skipped = 0
                    for rec in stream:
                        if skipped < skip:
                            skipped += 1
                            continue
                        group.append(rec)
                        if len(group) == batch_size:
                            yield (step, group)
                            step += 1
                            group = []
                # Epoch remainder dropped (drop_remainder semantics —
                # bpe full batches per epoch, every epoch).
                epoch += 1
                skip = 0

        source = raw_batches()
    else:

        def raw_batches():
            import contextlib

            records = sources_mod.interleave_shards(
                host_files,
                sources_mod.iter_tfrecord_records,
                num_readers=num_readers,
                buffer_records=buffer_records,
            )
            group: list = []
            step = 0
            with contextlib.closing(records):
                for rec in records:
                    group.append(rec)
                    if len(group) == batch_size:
                        yield (step, group)
                        step += 1
                        group = []
            if group:
                yield (step, group)

        source = raw_batches()

    def finish(batch: dict) -> dict:
        if train:
            return batch
        n = len(batch["label"])
        if n < batch_size:
            pad = batch_size - n
            batch = {
                k: np.concatenate([v, np.repeat(v[-1:], pad, axis=0)])
                for k, v in batch.items()
            }
            batch["mask"] = np.concatenate(
                [np.ones(n, np.float32), np.zeros(pad, np.float32)]
            )
        else:
            batch["mask"] = np.ones(n, np.float32)
        return batch

    if num_workers > 0:
        pool = workers_mod.WorkerPool(
            decode, num_workers, name="imagenet_decode"
        )
        decoded = workers_mod.PipelinedIterator(pool, source)
        if train:
            return decoded
        return _FinishingIterator(decoded, finish)
    return (finish(decode(item)) for item in source)


class _FinishingIterator:
    """Apply a cheap host-side finisher to a background pipeline while
    keeping the ``background``/``close`` contract visible to prefetch."""

    background = True

    def __init__(self, inner, finish):
        self._inner = inner
        self._finish = finish

    def __iter__(self):
        return self

    def __next__(self):
        return self._finish(next(self._inner))

    def close(self) -> None:
        self._inner.close()
