"""Device prefetch: overlap host input with device compute.

Equivalent of the reference's ``dataset.prefetch`` + device prefetch into
HBM (BASELINE.json:north_star). A look-ahead queue of batches is
``device_put`` ahead of time with the mesh batch sharding; transfers are
async in JAX, so batch N+1 streams into HBM while step N runs.

Resilience (ISSUE 1): each fetch runs through the fault-injection hook
(utils/faults.py — slow-batch and corrupt-batch faults land here), and a
batch whose host→device conversion/transfer fails is SKIPPED and counted
rather than killing the run, up to a bounded ``max_skips`` budget
(``TrainConfig.max_skipped_batches``; 0 keeps the historical fail-fast).

Telemetry (ISSUE 2): fetches and skips publish into the default metrics
registry (``data/batches_fetched``, ``data/batches_skipped``) so the
formerly write-only skip counter shows up in every JSONL window and in
the run report.

Input-pipeline observability + adaptive depth (ISSUE 6): the loop-level
``data_fetch`` span is split here into its two honest components —

* ``data_work``: host compute actually producing batches. For a plain
  (synchronous) iterator that is the whole ``next(it)`` + fault hooks +
  host→device put; for a background pipeline (the iterator carries
  ``background = True`` — data/workers.PipelinedIterator) the worker
  threads record their own ``data_work`` spans and only hooks + put
  count here.
* ``data_wait``: queue starvation — time this consumer spent blocked on
  a background pipeline's output queue. A fast host back-pressured by
  the device shows ``data_wait``, NOT ``data_work``, which is what keeps
  fleet straggler attribution (telemetry/fleet.py) from blaming a
  device-bound host's input pipeline.

``depth_max > depth`` arms the depth controller: every ``ADAPT_EVERY``
fetches it compares the observed ``span/data_fetch`` p95 against the
``span/device_step`` p95 and deepens the queue (up to ``depth_max``)
while the fetch dominates — i.e. while the loop observably waits on
input — and decays back toward the configured floor when the queue
stays ahead. The live depth is published as the ``data/prefetch_depth``
gauge.
"""

from __future__ import annotations

import collections
import contextlib
import logging
from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np

from tensorflow_examples_tpu.telemetry import registry as _telemetry_registry
from tensorflow_examples_tpu.telemetry.spans import span as _trace_span
from tensorflow_examples_tpu.utils import faults as _faults

log = logging.getLogger(__name__)

# Re-evaluate the prefetch depth every N fetches: long enough for the
# span histograms to hold fresh percentiles, short enough to converge
# within a warmup's worth of steps.
ADAPT_EVERY = 16

# Hysteresis thresholds on fetch_p95 / step_p95: grow while the fetch
# is at least GROW x the device-step dispatch time (the loop is
# observably input-waiting), shrink only when it falls under SHRINK x
# (the queue is comfortably ahead; release the host memory).
GROW_RATIO = 1.0
SHRINK_RATIO = 0.1


def put_batch(batch, sharding):
    """The one host→device placement path (used by loop and prefetch).

    Global-view semantics: every process passes the SAME full global
    batch and each materializes its addressable shards from it. For
    per-host data sources use ``put_local_batch``.

    Across processes the shards are cut on the host
    (``make_array_from_callback``): ``device_put`` of a host value onto
    a sharding that spans processes first asserts, in a collective,
    that every process passed the same value — a fast host would then
    spend its input phase waiting for the slowest one's batch, and the
    fleet's straggler attribution would see every host input-stalled.
    """
    if sharding.is_fully_addressable:
        return jax.tree.map(
            lambda x: jax.device_put(jnp.asarray(x), sharding), batch
        )

    def cut(x):
        x = np.asarray(x)
        return jax.make_array_from_callback(
            x.shape, sharding, lambda index: x[index]
        )

    return jax.tree.map(cut, batch)


def put_local_batch(batch, sharding):
    """Form a GLOBAL array from THIS process's local rows.

    Per-host semantics (multi-host input sharding, SURVEY.md §3(5)):
    each process contributes ``global_batch / process_count`` rows — its
    own shard of the data — and the result is one global jax.Array on
    ``sharding``. On a single process this is identical to ``put_batch``.
    """
    return jax.tree.map(
        lambda x: jax.make_array_from_process_local_data(
            sharding, np.asarray(x)
        ),
        batch,
    )


def bundle_batches(it: Iterator, k: int) -> Iterator:
    """Stack ``k`` consecutive host batches along a new leading axis.

    Feeds the ``steps_per_launch`` bundled train step: each yielded
    pytree has leaves shaped ``[k, batch, ...]``, scanned on device one
    step per slice. Exhaustion mid-bundle is an error — silently
    dropping a partial bundle would skip steps the unbundled loop
    would have run (the loop validates the step span divides by k, so
    a well-sized stream never hits this).
    """
    while True:
        group = []
        for _ in range(k):
            try:
                group.append(next(it))
            except StopIteration:
                if group:
                    raise ValueError(
                        f"input stream ended mid-bundle ({len(group)}/{k} "
                        "batches); size the stream to a multiple of "
                        "steps_per_launch"
                    ) from None
                return
        yield jax.tree.map(lambda *xs: np.stack(xs), *group)


class DepthController:
    """Depth-adaptive double buffering (ISSUE 6 tentpole c).

    Sizes the prefetch queue from the observed ``data_fetch`` p95 vs the
    ``device_step`` p95, within ``[depth, depth_max]``. Inert (fixed
    ``depth``) unless ``depth_max > depth``.
    """

    def __init__(
        self,
        depth: int = 2,
        depth_max: int = 0,
        *,
        registry=None,
        adapt_every: int = ADAPT_EVERY,
    ):
        self.floor = max(int(depth), 1)
        self.depth = self.floor
        self.depth_max = int(depth_max)
        self.adaptive = self.depth_max > self.floor
        self._adapt_every = max(int(adapt_every), 1)
        self._registry = registry
        self._fetches = 0
        self._gauge().set(float(self.depth))

    def _gauge(self):
        reg = (
            self._registry
            if self._registry is not None
            else _telemetry_registry.default_registry()
        )
        return reg.gauge("data/prefetch_depth")

    def observe(self) -> int:
        """Count one fetch; periodically re-derive the depth. Returns
        the (possibly updated) current depth."""
        self._fetches += 1
        if not self.adaptive or self._fetches % self._adapt_every:
            return self.depth
        reg = (
            self._registry
            if self._registry is not None
            else _telemetry_registry.default_registry()
        )
        (fetch_p95,) = reg.histogram("span/data_fetch").percentiles(95)
        (step_p95,) = reg.histogram("span/device_step").percentiles(95)
        if fetch_p95 is None or step_p95 is None or step_p95 <= 0:
            return self.depth
        ratio = fetch_p95 / step_p95
        before = self.depth
        if ratio >= GROW_RATIO and self.depth < self.depth_max:
            self.depth += 1
        elif ratio < SHRINK_RATIO and self.depth > self.floor:
            self.depth -= 1
        if self.depth != before:
            self._gauge().set(float(self.depth))
            log.info(
                "prefetch depth %d -> %d (data_fetch p95 %.4fs vs "
                "device_step p95 %.4fs)",
                before,
                self.depth,
                fetch_p95,
                step_p95,
            )
        return self.depth


_END = object()


def device_prefetch(
    it: Iterator,
    sharding,
    *,
    depth: int = 2,
    depth_max: int = 0,
    local_batches: bool = False,
    max_skips: int = 0,
    fault_hooks: bool = True,
    registry=None,
) -> Iterator:
    """``fault_hooks=False`` (the eval path) keeps this pipeline out of
    the injection engine's fetch-index space, so ``slow@N``/``badbatch@N``
    target train fetch N deterministically even when eval interleaves.

    ``depth_max > depth`` enables the adaptive controller (see
    :class:`DepthController`); the queue is refilled to the live depth
    before every yield, so a depth change takes effect within one step.
    """
    queue = collections.deque()
    put_fn = put_local_batch if local_batches else put_batch
    skipped = 0
    reg = (
        registry
        if registry is not None
        else _telemetry_registry.default_registry()
    )
    fetched_ctr = reg.counter("data/batches_fetched")
    skipped_ctr = reg.counter("data/batches_skipped")
    # The controller always reads the DEFAULT registry: the span
    # histograms it compares (span/data_fetch, span/device_step) are
    # recorded through the default tracer regardless of ``registry``,
    # so forwarding a custom registry would silently disarm adaptation.
    ctl = DepthController(depth, depth_max)
    # Background pipelines (worker pools) do the host work on their own
    # threads — popping their queue is WAIT, not WORK. Plain iterators
    # do the work right here in next(it).
    background = bool(getattr(it, "background", False))

    def finish(batch):
        """Fault hooks + host→device placement for one raw batch."""
        if fault_hooks:
            eng = _faults.active()
            if eng is not None:
                batch = eng.batch_hook(batch)
        return put_fn(batch, sharding)

    def fetch():
        """Next device-resident batch, or _END. With ``max_skips > 0`` a
        batch that fails the host→device put is poisoned: skip it (and
        count), bounded by the budget. With the default ``max_skips=0``
        the original exception propagates untouched — a deterministic
        pipeline bug must surface as itself, not as 'corrupt input'."""
        nonlocal skipped
        while True:
            from_source = True  # a source-iterator bug is never "corrupt
            #   input": it propagates untouched regardless of the budget
            try:
                if background:
                    with _trace_span("data_wait"):
                        batch = next(it)
                    from_source = False
                    with _trace_span("data_work"):
                        out = finish(batch)
                else:
                    with _trace_span("data_work"):
                        batch = next(it)
                        from_source = False
                        out = finish(batch)
            except StopIteration:
                return _END
            except Exception as e:
                if from_source or max_skips <= 0:
                    raise
                skipped += 1
                skipped_ctr.inc()
                if skipped > max_skips:
                    raise RuntimeError(
                        f"poisoned input batch ({skipped} bad, budget "
                        f"max_skipped_batches={max_skips} exhausted): {e}"
                    ) from e
                log.warning(
                    "skipping poisoned input batch %d/%d: %s",
                    skipped,
                    max_skips,
                    e,
                )
                continue
            fetched_ctr.inc()
            return out

    done = False
    try:
        while not done and len(queue) < ctl.depth:
            batch = fetch()
            if batch is _END:
                done = True
            else:
                queue.append(batch)
        while queue:
            out = queue.popleft()
            ctl.observe()
            while not done and len(queue) < ctl.depth:
                batch = fetch()
                if batch is _END:
                    done = True
                else:
                    queue.append(batch)
            yield out
    finally:
        # Unwind a background pipeline promptly (worker threads, reader
        # threads) when the consumer stops early — preemption, eval
        # truncation, an exception in the loop.
        close = getattr(it, "close", None)
        if close is not None:
            with contextlib.suppress(Exception):
                close()
