"""Checkpoint/resume on orbax.

Replaces the reference's per-example ``tf.train.CheckpointManager``
(SURVEY.md §2b/§5d) with orbax: async saves (the step never blocks on
filesystem IO), sharded arrays saved/restored directly to the live mesh
layout, and automatic latest-checkpoint resume.

Crash safety: ``CheckpointManager`` is a context manager; ``close()``
(which waits for any in-flight async save) runs on the exception path
out of ``Trainer.fit`` too, so a crash never abandons a half-written
async save as the torn "latest" checkpoint. ``restore_latest`` validates
the saved tree structure/shapes/dtypes against the live state up front
and names the mismatching paths, instead of failing deep inside orbax on
shape or dtype drift.

Integrity (ISSUE 10 satellite): every COMMITTED step directory gets a
``manifest.sha256.json`` sidecar (file -> sha256 over the whole step
dir, written right after the async commit lands — at the next ``save``
or at ``wait``/``close``). ``restore_latest`` verifies the manifest
before restoring: a torn or bit-flipped checkpoint (power loss,
flaky blob store) is skipped with a WARNING **naming the corrupt
file**, and the restore falls back to the newest intact step instead
of failing the run with an opaque orbax error. Checkpoints from
before this PR have no manifest and restore exactly as before.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import threading
from typing import Any

import orbax.checkpoint as ocp

from tensorflow_examples_tpu.telemetry.registry import default_registry
from tensorflow_examples_tpu.telemetry.spans import span as _trace_span

log = logging.getLogger(__name__)

MANIFEST_NAME = "manifest.sha256.json"


def _sha256_file(path: str, chunk: int = 1 << 20) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        while True:
            block = f.read(chunk)
            if not block:
                break
            h.update(block)
    return h.hexdigest()


class CheckpointManager:
    def __init__(self, workdir: str, *, max_to_keep: int = 3, async_save: bool = True):
        # item_handlers pre-registers the standard handler so a FRESH
        # manager (the resume path) can read item_metadata — without it
        # orbax returns None metadata until the first save, and
        # restore-time structure validation would silently skip.
        self._dir = os.path.abspath(os.path.join(workdir, "checkpoints"))
        self._mngr = ocp.CheckpointManager(
            self._dir,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=max_to_keep,
                enable_async_checkpointing=async_save,
            ),
            item_handlers=ocp.StandardCheckpointHandler(),
        )
        # Manifest stamping runs off the training thread (sha256 over a
        # multi-GB step dir would otherwise stall the step loop — the
        # exact blocking cost async_save exists to avoid). The lock
        # serializes stampers; wait()/close() join the in-flight one.
        self._manifest_lock = threading.Lock()
        self._manifest_thread: threading.Thread | None = None

    def __enter__(self) -> "CheckpointManager":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        # Always wait+close — an async save abandoned on the exception
        # path would otherwise be a torn latest-checkpoint.
        self.close()
        return False

    def latest_step(self) -> int | None:
        return self._mngr.latest_step()

    def save(self, step: int, state: Any) -> None:
        # The span covers the ENQUEUE only under async_save (orbax copies
        # device->host then commits in the background); the commit wait
        # shows up in whichever span wraps wait()/close().
        with _trace_span("checkpoint_save", step=step):
            self._mngr.save(step, args=ocp.args.StandardSave(_as_dict(state)))
        default_registry().counter("checkpoint/saves").inc()
        # Every EARLIER step is committed by now (orbax serializes
        # async saves: a new save waits for the previous commit), so
        # any of them still missing an integrity manifest gets one —
        # hashed on a background thread, never the step loop. The
        # just-enqueued step may still be in flight — it is stamped by
        # a later save, or by wait()/close(). If the previous stamper
        # is still running, skip: stamping is idempotent and the next
        # trigger catches up.
        prev = self._manifest_thread
        if prev is None or not prev.is_alive():
            self._manifest_thread = threading.Thread(
                target=self._write_manifests,
                kwargs={"exclude_step": step},
                name="ckpt-manifest-stamp",
                daemon=True,
            )
            self._manifest_thread.start()

    def restore_latest(
        self, state: Any, *, validate: bool = True
    ) -> tuple[Any, int] | None:
        """Restore into ``state``'s structure/shardings; None if no ckpt.

        Abstract template leaves (``jax.eval_shape`` ShapeDtypeStructs,
        the restore-only consumers' path — sampling/serving CLIs) carry
        no sharding; orbax refuses them for checkpoints that were SAVED
        sharded (docs/sharding.md). Such leaves get a default
        single-device placement here, so any checkpoint — written on
        any mesh — restores through a shardings-free template onto the
        local default device (resharding on restore is the contract).

        Integrity fallback (ISSUE 10): steps whose sha256 manifest does
        not verify — and steps orbax itself fails to deserialize — are
        skipped with a WARNING naming the corrupt file, falling back to
        the newest intact step. Structure/shape drift found by
        ``validate`` still raises (that is a config mistake, not
        corruption — silently restoring an OLDER checkpoint with the
        same wrong config would mask it)."""
        steps = sorted(self._mngr.all_steps(), reverse=True)
        if not steps:
            return None
        target = _with_default_shardings(_as_dict(state))
        corrupt: list[str] = []
        for step in steps:
            problems = self.verify_step_integrity(step)
            if problems:
                shown = "; ".join(problems[:5])
                log.warning(
                    "checkpoint at step %d fails its integrity "
                    "manifest (%s)%s", step, shown,
                    " — falling back to an older checkpoint"
                    if step != steps[-1] else "",
                )
                default_registry().counter(
                    "checkpoint/corrupt_skipped"
                ).inc()
                corrupt.append(f"step {step}: {shown}")
                continue
            with _trace_span("checkpoint_restore", step=step):
                if validate:
                    self._validate_structure(step, target)
                try:
                    restored = self._mngr.restore(
                        step, args=ocp.args.StandardRestore(target)
                    )
                except Exception as e:  # noqa: BLE001 — a torn step
                    # that slipped past the manifest (or predates it)
                    # must not fail the run while an intact older
                    # step exists.
                    default_registry().counter(
                        "checkpoint/corrupt_skipped"
                    ).inc()
                    corrupt.append(
                        f"step {step}: {type(e).__name__}: {e}"
                    )
                    if step == steps[-1]:
                        break
                    log.warning(
                        "restore of step %d failed inside orbax "
                        "(%s: %s) — falling back to an older "
                        "checkpoint", step, type(e).__name__, e,
                    )
                    continue
                merged = _merge_arrays(state, restored)
            default_registry().counter("checkpoint/restores").inc()
            if corrupt:
                log.warning(
                    "restored checkpoint at step %d after skipping %d "
                    "corrupt newer step(s)", step, len(corrupt),
                )
            else:
                log.info("restored checkpoint at step %d", step)
            return merged, step
        raise RuntimeError(
            "every checkpoint in %s is corrupt:\n  %s"
            % (self._dir, "\n  ".join(corrupt))
        )

    # ------------------------------------------------------- integrity

    def _step_dir(self, step: int) -> str:
        return os.path.join(self._dir, str(step))

    def _write_manifests(self, exclude_step: int | None = None) -> None:
        """Stamp a sha256 manifest into every committed step dir that
        lacks one (idempotent; the manifest itself is excluded from its
        own hash set). Written atomically so a crash mid-stamp can
        never leave a torn manifest posing as a verdict. A step swept
        away by max_to_keep mid-stamp is skipped, not an error."""
        with self._manifest_lock:
            for step in self._mngr.all_steps():
                if step == exclude_step:
                    continue
                step_dir = self._step_dir(step)
                manifest = os.path.join(step_dir, MANIFEST_NAME)
                if not os.path.isdir(step_dir) \
                        or os.path.exists(manifest):
                    continue
                files = {}
                try:
                    for root, _, names in os.walk(step_dir):
                        for name in sorted(names):
                            if name == MANIFEST_NAME:
                                continue
                            full = os.path.join(root, name)
                            files[os.path.relpath(full, step_dir)] = (
                                _sha256_file(full)
                            )
                    tmp = manifest + ".tmp"
                    with open(tmp, "w") as f:
                        json.dump(
                            {"step": step, "files": files}, f, indent=1
                        )
                        f.write("\n")
                    os.replace(tmp, manifest)
                except FileNotFoundError:
                    continue  # rotated out from under us (max_to_keep)
                log.debug(
                    "stamped integrity manifest for step %d (%d files)",
                    step, len(files),
                )

    def verify_step_integrity(self, step: int) -> list[str]:
        """Problems with step's on-disk bytes vs its manifest (empty =
        intact, or the step predates manifests)."""
        step_dir = self._step_dir(step)
        manifest = os.path.join(step_dir, MANIFEST_NAME)
        if not os.path.exists(manifest):
            return []  # pre-ISSUE-10 checkpoint: nothing to verify
        try:
            with open(manifest) as f:
                doc = json.load(f)
            files = doc["files"]
        except (ValueError, KeyError, OSError) as e:
            return [f"unreadable manifest {manifest}: {e}"]
        problems = []
        for rel, digest in sorted(files.items()):
            full = os.path.join(step_dir, rel)
            if not os.path.isfile(full):
                problems.append(f"missing file {rel}")
            elif _sha256_file(full) != digest:
                problems.append(f"sha256 mismatch in {rel}")
        return problems

    def _validate_structure(self, step: int, target: dict) -> None:
        """Compare the saved tree against the live state; raise a clear
        error naming every drifted path (missing / unexpected / shape or
        dtype mismatch) instead of letting orbax fail deep inside its
        restore machinery."""
        import jax.tree_util as jtu

        try:
            meta = self._mngr.item_metadata(step)
        except Exception as e:  # metadata is best-effort across versions
            log.debug("checkpoint metadata unavailable (%s); skipping", e)
            return
        # Newer orbax hands back a TreeMetadata around the saved tree.
        meta = getattr(meta, "tree", meta)
        if not isinstance(meta, dict):
            return

        def norm(path) -> str:
            # Saved metadata renders optax NamedTuple nodes as dicts while
            # the live tree flattens them with attribute keys ([0].count
            # vs ['0']['count']); normalize every entry to its bare
            # key/index so the two spellings compare equal.
            parts = []
            for p in path:
                for attr in ("key", "name", "idx"):
                    if hasattr(p, attr):
                        parts.append(str(getattr(p, attr)))
                        break
                else:  # pragma: no cover - unknown key type
                    parts.append(str(p))
            return "/".join(parts)

        def by_path(tree):
            return {
                norm(path): leaf
                for path, leaf in jtu.tree_flatten_with_path(tree)[0]
            }

        saved, live = by_path(meta), by_path(target)
        problems = []
        for path in sorted(set(live) - set(saved)):
            problems.append(f"missing from checkpoint: {path}")
        for path in sorted(set(saved) - set(live)):
            problems.append(f"not in live state: {path}")
        for path in sorted(set(saved) & set(live)):
            m, x = saved[path], live[path]
            m_shape = getattr(m, "shape", None)
            m_dtype = getattr(m, "dtype", None)
            x_shape = tuple(getattr(x, "shape", ()))
            if m_shape is not None and tuple(m_shape) != x_shape:
                problems.append(
                    f"shape mismatch at {path}: checkpoint "
                    f"{tuple(m_shape)} vs live {x_shape}"
                )
            elif m_dtype is not None and str(m_dtype) != str(
                getattr(x, "dtype", m_dtype)
            ):
                problems.append(
                    f"dtype mismatch at {path}: checkpoint {m_dtype} vs "
                    f"live {x.dtype}"
                )
        if problems:
            shown = "\n  ".join(problems[:20])
            more = (
                f"\n  ... and {len(problems) - 20} more"
                if len(problems) > 20
                else ""
            )
            raise ValueError(
                f"checkpoint at step {step} does not match the live train "
                f"state ({len(problems)} path(s) drifted — wrong model "
                "config or optimizer for this workdir?):\n  "
                f"{shown}{more}"
            )

    def _join_manifest_thread(self) -> None:
        t = self._manifest_thread
        if t is not None and t is not threading.current_thread():
            t.join()

    def wait(self) -> None:
        self._mngr.wait_until_finished()
        self._join_manifest_thread()
        self._write_manifests()

    def close(self) -> None:
        self._mngr.wait_until_finished()
        self._join_manifest_thread()
        self._write_manifests()
        self._mngr.close()


def _with_default_shardings(tree: Any) -> Any:
    """Give sharding-less abstract leaves a concrete single-device
    placement (concrete arrays and sharding-carrying structs pass
    through untouched)."""
    import jax

    default = None

    def one(leaf):
        nonlocal default
        if (
            isinstance(leaf, jax.ShapeDtypeStruct)
            and getattr(leaf, "sharding", None) is None
        ):
            if default is None:
                default = jax.sharding.SingleDeviceSharding(
                    jax.local_devices()[0]
                )
            return jax.ShapeDtypeStruct(
                leaf.shape, leaf.dtype, sharding=default
            )
        return leaf

    return jax.tree.map(one, tree)


def _as_dict(state: Any) -> dict:
    """Array-only view of TrainState (fns/optimizer objects are rebuilt by
    the caller, orbax stores just the arrays)."""
    return {
        "step": state.step,
        "params": state.params,
        "opt_state": state.opt_state,
        "model_state": state.model_state,
    }


def _merge_arrays(state: Any, restored: dict) -> Any:
    return state.replace(
        step=restored["step"],
        params=restored["params"],
        opt_state=restored["opt_state"],
        model_state=restored.get("model_state", state.model_state),
    )
