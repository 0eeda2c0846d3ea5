"""Pipeline parallelism over the ``pipe`` mesh axis: GPipe and 1F1B.

Framework-native extension (SURVEY.md §2d — the reference had no PP; the
distributed design here treats it as a first-class mesh axis like
dp/fsdp/tp/sp). TPU-first shape:

- Stage parameters are the *same pytree* with a leading [stages] axis
  sharded over ``pipe`` — placement is a sharding rule, not a code path,
  exactly like tensor parallelism.
- Schedules run inside a PARTIAL-MANUAL ``shard_map``
  (``axis_names={'pipe'}``): only the pipe axis is manual — each rank
  applies its own stage and activations hop stage→stage with
  ``jax.lax.ppermute`` (nearest-neighbor ICI) — while the batch and
  ``model`` axes stay under the automatic partitioner. That is what
  lets PP COMPOSE with DP/FSDP/TP: inside a stage the math is ordinary
  global-view JAX, so TP falls out of the stacked params' sharding
  rules (workloads/gpt2.py pipe×model rules) exactly as in the
  non-pipelined model, and DP gradient reductions are inserted by XLA
  — no hand-written pmeans.

Two schedules:

- **GPipe** (``pipeline_apply``): forward-only building block whose
  backward is JAX's transpose of the schedule (ppermute transposes to
  the reverse hop). Microbatches stream over M + P - 1 ticks; bubble
  ticks SKIP the stage compute via ``lax.cond`` (previously they burned full FLOPs on clipped garbage). Saved state is
  O(M · microbatch) activations under per-tick remat.
- **1F1B** (``make_pipeline_1f1b``): the real training schedule. The
  per-microbatch loss is computed at the LAST stage inside the
  scheduled program, so microbatch m's backward starts as soon as its
  forward leaves the pipe — forwards and backwards interleave in the
  classic one-forward-one-backward steady state, stages idle only in
  the unavoidable 2(P-1)-tick ramp, and in-flight activations are
  bounded by P - s per stage (the 1F1B memory bound) instead of M.
  Gradients never come from transposing the scan: each backward tick
  recomputes its stage forward from the stashed input (remat) and
  accumulates explicit per-stage param grads, which leave the
  shard_map still sharded over ``pipe``. The schedule itself is
  simulated in numpy at trace time (`_schedule_1f1b`) — per-tick op
  tables with machine-checked queue/stash invariants — and the whole
  thing is wrapped in ``jax.custom_vjp`` so the surrounding
  embed/optimizer code auto-differentiates through it unchanged.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tensorflow_examples_tpu.core.collectives import shard_map as _shard_map

from tensorflow_examples_tpu.core import collectives as coll
from tensorflow_examples_tpu.core.mesh import AxisNames


def _psum_pipe(tree, axis_name):
    """psum over the pipe axis with sub-f32 leaves routed through f32.

    Works around a jaxlib CPU compiler abort (`Invalid binary
    instruction opcode copy` in AllReducePromotion/CloneAllReduce) when
    a bf16/f16 all-reduce appears inside a PARTIAL-manual shard_map
    region — the full-manual formulation compiles the same reduce fine.
    CPU promotes sub-f32 all-reduces to f32 anyway, so this costs
    nothing there; on TPU it spends 2× bytes on the once-per-step
    loss/grad pipe reduces, noise next to the per-tick activation hops.
    """

    def up(x):
        if x.dtype in (jnp.bfloat16, jnp.float16):
            return x.astype(jnp.float32)
        return x

    out = coll.psum(jax.tree.map(up, tree), axis_name)
    return jax.tree.map(lambda o, t: o.astype(t.dtype), out, tree)


def _pin_pipe_dim(stage_params, mesh):
    """Constrain dim0 of every stage-param leaf to ``pipe`` while
    leaving every other dim UNCONSTRAINED — a plain ``None`` would mean
    *replicated* and silently all-gather away the Megatron TP layout the
    pipe×model rules placed on the stacked weights (PP×TP would still
    be numerically right, but each device would hold full un-sharded
    stage weights)."""
    U = P.UNCONSTRAINED

    def pin(p):
        spec = P(*((AxisNames.PIPE,) + (U,) * (p.ndim - 1)))
        return jax.lax.with_sharding_constraint(p, NamedSharding(mesh, spec))

    return jax.tree.map(pin, stage_params)


def _gpipe_local(stage_fn, params, x_mb, axis_name, rng=None):
    """Per-device GPipe schedule (runs inside shard_map).

    params: this device's stage params (leading [1, ...] stage dim kept).
    x_mb: [M, mb, ...] microbatched input, replicated over the pipe axis.
    rng: optional dropout key — folded per (stage, tick), which is
    per (stage, microbatch) since a stage sees one microbatch per tick.
    Returns [M, mb, ...] outputs, valid on every device (psum-broadcast).
    """
    n_stages = coll.axis_size(axis_name)
    stage = lax.axis_index(axis_name)
    m = x_mb.shape[0]
    fwd_perm = coll.ring_perm(n_stages)
    params = jax.tree.map(lambda p: p[0], params)  # drop the stage dim
    if rng is not None:
        rng = jax.random.fold_in(rng, stage)

    def tick(carry, t):
        state, out = carry
        # Stage 0 ingests microbatch t (t < M), others take the incoming
        # activation that arrived last tick.
        mb_idx = jnp.clip(t, 0, m - 1)
        inp = jnp.where(stage == 0, x_mb[mb_idx], state)

        def run(inp):
            if rng is None:
                return stage_fn(params, inp)
            return stage_fn(params, inp, jax.random.fold_in(rng, t))

        # Stage s only holds a real microbatch during ticks
        # [s, s + M - 1]; outside that window (the GPipe bubble) skip the
        # stage compute entirely instead of burning FLOPs on garbage.
        in_window = (t >= stage) & (t <= stage + m - 1)
        y = lax.cond(in_window, run, lambda inp: jnp.zeros_like(inp), inp)
        # Microbatch k exits the last stage at tick k + P - 1.
        done_idx = t - (n_stages - 1)
        is_done = (stage == n_stages - 1) & (done_idx >= 0) & (done_idx < m)
        out = jnp.where(
            is_done, out.at[jnp.clip(done_idx, 0, m - 1)].set(y), out
        )
        # Hop the activation to the next stage (ring hop; the wraparound
        # value into stage 0 is ignored — it re-ingests from x_mb).
        state = coll.ppermute(y, axis_name, fwd_perm)
        return (state, out), None

    state0 = jnp.zeros_like(x_mb[0])
    out0 = jnp.zeros_like(x_mb)
    (_, out), _ = lax.scan(
        jax.checkpoint(tick), (state0, out0), jnp.arange(m + n_stages - 1)
    )
    # Only the last stage holds real outputs; broadcast to all pipe ranks
    # so the (replicated) head/loss runs everywhere.
    return _psum_pipe(
        jnp.where(stage == n_stages - 1, out, jnp.zeros_like(out)), axis_name
    )


def pipeline_apply(
    stage_fn: Callable,
    stage_params,
    x: jax.Array,
    *,
    mesh: Mesh,
    num_microbatches: int,
    rng=None,
) -> jax.Array:
    """Apply a [stages]-stacked stage over ``x`` with GPipe scheduling.

    stage_params: pytree with leading [stages] axis on every leaf,
    sharded over ``pipe``. x: [batch, ...] activations. The batch is
    split into ``num_microbatches`` along axis 0. With ``rng``,
    ``stage_fn`` is called as ``stage_fn(params, x, key)`` with a key
    unique per (stage, microbatch) — the dropout path; without, as
    ``stage_fn(params, x)``.
    """
    n_stages = mesh.shape[AxisNames.PIPE]
    if n_stages == 1:
        single = jax.tree.map(lambda p: p[0], stage_params)
        return stage_fn(single, x) if rng is None else stage_fn(single, x, rng)
    b = x.shape[0]
    if b % num_microbatches:
        raise ValueError(
            f"batch {b} not divisible by num_microbatches {num_microbatches}"
        )
    x_mb = x.reshape((num_microbatches, b // num_microbatches) + x.shape[1:])

    param_specs = jax.tree.map(
        lambda p: P(*((AxisNames.PIPE,) + (None,) * (p.ndim - 1))), stage_params
    )
    constrained = _pin_pipe_dim(stage_params, mesh)
    # Partial-manual: only `pipe` is manual (module docstring). Specs
    # may therefore only reference `pipe`; activations are pipe-
    # replicated (P()), their batch sharding rides the auto axes.
    if rng is None:
        out = _shard_map(
            lambda p, xm: _gpipe_local(stage_fn, p, xm, AxisNames.PIPE),
            mesh=mesh,
            in_specs=(param_specs, P()),
            out_specs=P(),
            axis_names={AxisNames.PIPE},
            check_vma=False,
        )(constrained, x_mb)
    else:
        # rng rides in as an explicit replicated argument (a closure
        # capture inside shard_map is not reliably supported).
        out = _shard_map(
            lambda p, xm, r: _gpipe_local(
                stage_fn, p, xm, AxisNames.PIPE, rng=r
            ),
            mesh=mesh,
            in_specs=(param_specs, P(), P()),
            out_specs=P(),
            axis_names={AxisNames.PIPE},
            check_vma=False,
        )(constrained, x_mb, rng)
    return out.reshape((b,) + x.shape[1:])


# ------------------------------------------------------------------ 1F1B


def interleave_perm(p: int, v: int) -> np.ndarray:
    """Slot-major permutation for interleaved stages.

    Virtual stage ``s = chunk·P + device`` (round-robin, Megatron
    layout) is stored in stacked-param slot ``i = device·v + chunk`` so
    a CONTIGUOUS dim-0 ``pipe`` sharding of the ``[P·v, ...]`` stack
    gives each device exactly its v chunks with zero train-time data
    movement. Returns ``perm`` with ``perm[i] = virtual stage in slot
    i``; apply as ``stacked_logical[perm]`` to produce slot order (and
    ``argsort(perm)`` to undo, e.g. for the eval/GPipe path)."""
    return np.asarray(
        [(i % v) * p + i // v for i in range(p * v)], np.int64
    )


def _sim_schedule(m: int, p: int, v: int, bwd_hi: bool, fwd_lo: bool):
    """One greedy simulation of (interleaved) 1F1B; see _schedule_1f1b."""
    s_total = p * v
    next_f = [0] * s_total
    next_b = [0] * s_total
    f_tick: dict = {}
    b_tick: dict = {}
    ops, mbs, chs = [], [], []
    t = 0
    while any(next_b[s] < m for s in range(s_total)):
        if t > 4 * (m * v + s_total) + 8:
            return None  # this policy deadlocked / stalled
        op_row = [0] * p
        mb_row = [0] * p
        ch_row = [0] * p
        for d in range(p):
            stages = [j * p + d for j in range(v)]  # this device's chunks
            b_cands = []
            f_cands = []
            for s in stages:
                b = next_b[s]
                if b < m and (
                    (s == s_total - 1 and f_tick.get((b, s), t) < t)
                    or (s < s_total - 1 and b_tick.get((b, s + 1), t) < t)
                ):
                    b_cands.append(s)
                f = next_f[s]
                # In-flight bound: the classic S - s, additionally
                # capped at 2P for v > 1 — uncapped, greedy warmup
                # pumps up to m microbatches in flight at chunk 0
                # (GPipe-like memory); the cap costs ≤1% ticks in the
                # swept configs and bounds stash depth by min(m, 2P).
                # For v == 1, S - s ≤ P < 2P: identical to round 3.
                if (
                    f < m
                    and (s == 0 or f_tick.get((f, s - 1), t) < t)
                    and next_f[s] - next_b[s] < min(s_total - s, 2 * p)
                ):
                    f_cands.append(s)
            if b_cands:  # backward priority (1F1B)
                s = max(b_cands) if bwd_hi else min(b_cands)
                b = next_b[s]
                op_row[d], mb_row[d], ch_row[d] = 2, b, s // p
                b_tick[(b, s)] = t
                next_b[s] += 1
            elif f_cands:
                s = min(f_cands) if fwd_lo else max(f_cands)
                f = next_f[s]
                op_row[d], mb_row[d], ch_row[d] = 1, f, s // p
                f_tick[(f, s)] = t
                next_f[s] += 1
        ops.append(op_row)
        mbs.append(mb_row)
        chs.append(ch_row)
        t += 1
    return ops, mbs, chs, t, f_tick, b_tick


def _schedule_1f1b(m: int, p: int, v: int = 1):
    """Simulate the 1F1B schedule — interleaved when v > 1 — for M
    microbatches over P devices × V virtual stages (chunks) per device,
    and return static per-tick op tables.

    Virtual stage ``s = chunk·P + device``; each device runs at most
    ONE op per tick among its chunks. Greedy rules per tick, per
    device: run a backward whose cotangent is available (last virtual
    stage: own forward done earlier; else: stage s+1 ran backward
    earlier) — backward priority; else a forward whose activation is
    available (s == 0: always; else s-1 forwarded earlier) subject to
    the in-flight bound ``next_f[s] - next_b[s] < S - s``; else idle.
    Four chunk tie-break policies are simulated and the one with the
    fewest ticks that converges wins (for v == 1 they coincide with the
    round-3 schedule exactly).

    Returns (op[T,P], mb[T,P], ch[T,P], T, depth, q_f, q_b) int32
    arrays, op ∈ {0 idle, 1 fwd, 2 bwd}; ``depth`` is the exact max
    in-flight count over (device, chunk) pairs from the simulation —
    the runtime sizes its activation stash [v, depth, ...] from it —
    and ``q_f``/``q_b`` are the exact max arrived-but-unconsumed counts
    per receive direction, sizing the [v, q, ...] receive queues (v=1
    gives the classic 2 slots; interleaving legitimately needs more
    during warmup because a device is busy with other chunks while
    arrivals pile up). Asserts the slot-reuse invariants the runtime
    relies on at the computed sizes (slot = mb % size).
    """
    s_total = p * v
    best = None
    for bwd_hi in (True, False):
        for fwd_lo in (True, False):
            r = _sim_schedule(m, p, v, bwd_hi, fwd_lo)
            if r is not None and (best is None or r[3] < best[3]):
                best = r
    if best is None:
        raise AssertionError(f"1F1B schedule failed to converge (m={m}, p={p}, v={v})")
    ops, mbs, chs, t, f_tick, b_tick = best

    # Exact stash depth: max simultaneous in-flight per virtual stage.
    depth = 1
    for s in range(s_total):
        live = 0
        events = sorted(
            [(f_tick[(k, s)], 1) for k in range(m)]
            + [(b_tick[(k, s)], -1) for k in range(m)]
        )
        for _, delta in events:
            live += delta
            depth = max(depth, live)
    # Exact receive-queue sizes: max arrived-but-unconsumed per virtual
    # edge. A forward produced at stage s-1 on tick u arrives at stage s
    # on tick u+1 and is consumed at f_tick[(k, s)].
    def _max_live(ticks, lo, hi, shift):
        live_max = 1
        for s in range(lo, hi):
            # Arrival one tick after production at the neighbor; the
            # +0.5 orders consumption after a same-tick arrival (the
            # runtime delivers arrivals at tick start, then consumes).
            events = sorted(
                [(ticks[(k, s + shift)] + 1, 1) for k in range(m)]
                + [(ticks[(k, s)] + 0.5, -1) for k in range(m)]
            )
            live = 0
            for _, delta in events:
                live += delta
                live_max = max(live_max, live)
        return live_max

    q_f = _max_live(f_tick, 1, s_total, -1)
    q_b = _max_live(b_tick, 0, s_total - 1, +1)
    q_f, q_b = max(2, q_f), max(2, q_b)
    # Queue invariant at the computed sizes: arrival of microbatch k+q
    # (same direction, same edge) must not precede consumption of k.
    for s in range(1, s_total):
        for k in range(m - q_f):
            assert f_tick[(k, s)] <= f_tick[(k + q_f, s - 1)], (s, k)
    for s in range(s_total - 1):
        for k in range(m - q_b):
            assert b_tick[(k, s)] <= b_tick[(k + q_b, s + 1)], (s, k)
    # Stash invariant: backward of k precedes forward of k+depth
    # (slot = mb % depth reuse safety).
    for s in range(s_total):
        for k in range(m - depth):
            assert b_tick[(k, s)] < f_tick[(k + depth, s)], (s, k)
    return (
        np.asarray(ops, np.int32),
        np.asarray(mbs, np.int32),
        np.asarray(chs, np.int32),
        t,
        depth,
        q_f,
        q_b,
    )


def _1f1b_local(
    stage_fn,
    head_loss_fn,
    params,
    head_params,
    x_mb,
    labels_mb,
    rng,
    axis_name,
    op_tbl,
    mb_tbl,
    ch_tbl,
    n_virtual,
    depth,
    q_f,
    q_b,
):
    """Per-device (interleaved) 1F1B program (runs inside shard_map).

    params: this device's stage params, leading [v, ...] chunk dim kept
    (slot-major stacking: chunk j on device d is virtual stage
    ``j·P + d`` — ``interleave_perm``). x_mb: [M, mb, ...] microbatched
    stage-0 input (embed output), labels_mb: [M, mb, ...] labels for
    the last virtual stage's loss. All hops are nearest-neighbor ring
    permutes — the wraparound edge P-1 → 0 is exactly the chunk
    boundary (virtual stage j·P+P-1 → (j+1)·P lives on device 0), so
    interleaving adds no new communication pattern, only chunk routing
    on the receive side. Returns (loss_sum_local, dparams [v, ...],
    dhead_local, dx_mb_local) — the caller reduces loss/dhead/dx over
    the pipe axis (each is produced on one device, zeros elsewhere).
    """
    n_dev = coll.axis_size(axis_name)
    dev = lax.axis_index(axis_name)
    v = n_virtual
    s_total_v = op_tbl.shape[1] * v  # == n_dev · v, static
    m = x_mb.shape[0]
    fwd_perm = coll.ring_perm(n_dev)
    bwd_perm = [(d_, s_) for (s_, d_) in fwd_perm]
    # Static chunk slice for v == 1 (see chunk_params below).
    params_static = jax.tree.map(lambda p_: p_[0], params) if v == 1 else None

    def fwd_loss(p_, hp, x, lbl, mb, s_virt, is_last):
        """Uniform chunk program: block stack + (last virtual stage
        only) loss. rng folds per (virtual stage, microbatch)."""
        if rng is None:
            y = stage_fn(p_, x)
        else:
            key = jax.random.fold_in(jax.random.fold_in(rng, s_virt), mb)
            y = stage_fn(p_, x, key)
        loss = lax.cond(
            is_last,
            lambda: head_loss_fn(hp, y, lbl),
            lambda: jnp.float32(0.0),
        )
        return y, loss

    zeros_x = jnp.zeros_like(x_mb[0])
    d_params0 = jax.tree.map(jnp.zeros_like, params)
    d_head0 = jax.tree.map(jnp.zeros_like, head_params)

    def tick(carry, t):
        in_q, d_q, stash, d_par, d_head, dx_out, loss_acc, y_pay, d_pay = carry
        # Deliver last tick's hops (receive side): a forward activation
        # arrives iff my predecessor ran F last tick (and wasn't the
        # final virtual stage); a cotangent arrives iff my successor ran
        # B last tick (and wasn't virtual stage 0). The receive CHUNK is
        # decoded from the sender's table entry: same chunk within the
        # ring, +1 across the P-1 → 0 wraparound.
        prev_op = op_tbl[t - 1]  # t=0 reads row -1, gated off below
        prev_mb = mb_tbl[t - 1]
        prev_ch = ch_tbl[t - 1]
        y_arr = coll.ppermute(y_pay, axis_name, fwd_perm)
        d_arr = coll.ppermute(d_pay, axis_name, bwd_perm)
        pred, succ = (dev - 1) % n_dev, (dev + 1) % n_dev
        s_snd_f = prev_ch[pred] * n_dev + pred
        f_arrived = (t > 0) & (prev_op[pred] == 1) & (s_snd_f < s_total_v - 1)
        s_snd_b = prev_ch[succ] * n_dev + succ
        b_arrived = (t > 0) & (prev_op[succ] == 2) & (s_snd_b > 0)
        in_q = jnp.where(
            f_arrived,
            in_q.at[(s_snd_f + 1) // n_dev, prev_mb[pred] % q_f].set(y_arr),
            in_q,
        )
        d_q = jnp.where(
            b_arrived,
            d_q.at[(s_snd_b - 1) // n_dev, prev_mb[succ] % q_b].set(d_arr),
            d_q,
        )

        op = op_tbl[t, dev]
        mb = mb_tbl[t, dev]
        ch = ch_tbl[t, dev]
        s_virt = ch * n_dev + dev
        is_first = s_virt == 0
        is_last = s_virt == s_total_v - 1
        lbl = labels_mb[mb]

        def chunk_params():
            # v == 1: ch is constantly 0 but traced (from ch_tbl), so a
            # dynamic slice here could not be hoisted out of the scan —
            # use the static slice taken outside instead (round-3
            # behavior). v > 1: gather the chunk inside do_fwd/do_bwd
            # only, so idle ticks pay nothing.
            if v == 1:
                return params_static
            return jax.tree.map(
                lambda p_: lax.dynamic_index_in_dim(
                    p_, ch, 0, keepdims=False
                ),
                params,
            )

        def do_idle(_):
            return (stash, d_par, d_head, dx_out, loss_acc, zeros_x, zeros_x)

        def do_fwd(_):
            p_ch = chunk_params()
            x_in = jnp.where(is_first, x_mb[mb], in_q[ch, mb % q_f])
            y, loss = fwd_loss(p_ch, head_params, x_in, lbl, mb, s_virt, is_last)
            return (
                stash.at[ch, mb % depth].set(x_in),
                d_par,
                d_head,
                dx_out,
                loss_acc + loss,
                y,
                zeros_x,
            )

        def do_bwd(_):
            p_ch = chunk_params()
            x_in = stash[ch, mb % depth]
            _, vjp = jax.vjp(
                lambda p_, hp, x: fwd_loss(p_, hp, x, lbl, mb, s_virt, is_last),
                p_ch,
                head_params,
                x_in,
            )
            dy = jnp.where(is_last, jnp.zeros_like(zeros_x), d_q[ch, mb % q_b])
            g_loss = jnp.where(is_last, jnp.float32(1.0), jnp.float32(0.0))
            dp, dhp, dx = vjp((dy, g_loss))
            new_dx_out = jnp.where(
                is_first, dx_out.at[mb].set(dx), dx_out
            )
            d_par2 = (
                jax.tree.map(lambda acc, g: acc + g[None], d_par, dp)
                if v == 1  # static accumulate, no scatter
                else jax.tree.map(lambda acc, g: acc.at[ch].add(g), d_par, dp)
            )
            return (
                stash,
                d_par2,
                jax.tree.map(jnp.add, d_head, dhp),
                new_dx_out,
                loss_acc,
                zeros_x,
                dx,
            )

        stash, d_par, d_head, dx_out, loss_acc, y_pay, d_pay = lax.switch(
            op, [do_idle, do_fwd, do_bwd], None
        )
        return (
            in_q,
            d_q,
            stash,
            d_par,
            d_head,
            dx_out,
            loss_acc,
            y_pay,
            d_pay,
        ), None

    carry0 = (
        jnp.zeros((v, q_f) + zeros_x.shape, zeros_x.dtype),  # fwd queue
        jnp.zeros((v, q_b) + zeros_x.shape, zeros_x.dtype),  # bwd queue
        jnp.zeros((v, depth) + zeros_x.shape, zeros_x.dtype),  # act stash
        d_params0,
        d_head0,
        jnp.zeros_like(x_mb),  # dx per microbatch (virtual stage 0 only)
        jnp.float32(0.0),
        zeros_x,  # forward hop payload
        zeros_x,  # backward hop payload
    )
    n_ticks = op_tbl.shape[0]
    (in_q, d_q, stash, d_par, d_head, dx_out, loss_acc, y_pay, d_pay), _ = (
        lax.scan(tick, carry0, jnp.arange(n_ticks))
    )
    return loss_acc, d_par, d_head, dx_out


def make_pipeline_1f1b(
    stage_fn: Callable,
    head_loss_fn: Callable,
    *,
    mesh: Mesh,
    num_microbatches: int,
    num_virtual_stages: int = 1,
):
    """Build the 1F1B pipelined loss:
    ``run(stage_params, head_params, x, labels, rng) -> scalar loss``.

    - ``stage_fn(stage_params, x[, rng_key]) -> y`` — one virtual
      stage's block stack (same contract as ``pipeline_apply``).
    - ``head_loss_fn(head_params, y, labels) -> scalar`` — the
      mean-per-microbatch loss, executed at the LAST virtual stage only
      (so the head matmul is never replicated across stages).

    With ``num_virtual_stages = v > 1`` the schedule is INTERLEAVED
    1F1B (Megatron-style): ``stage_params`` must carry a leading
    ``[P·v]`` dim in SLOT-MAJOR order (``interleave_perm``), each tick
    runs one 1/v-sized chunk, and the pipeline ramp shrinks ~v-fold in
    full-stage units (measured by ``_schedule_1f1b``: p=4, m=8 bubble
    6.0 → 5.0 → 2.5 stage-units for v = 1, 2, 4) at the price of v×
    the ticks, hops, and receive-queue slots — worth it when a stage's
    compute dwarfs the hop latency.

    The returned function is a ``jax.custom_vjp``: its *forward* runs
    the scheduled program, producing the loss AND the explicit
    gradients (stage grads stay ``pipe``-sharded; head/dx reduce over
    the pipe axis once); its backward just scales those cached
    gradients by the incoming cotangent. The surrounding program —
    embedding before, optimizer after — differentiates through it with
    plain ``jax.grad``. Memory: the activation stash is
    [v, depth ≤ min(M, 2P)] per device, sized exactly from the trace-
    time schedule simulation, never M-deep.
    """
    n_stages = mesh.shape[AxisNames.PIPE]
    pipe_axis = AxisNames.PIPE
    v = num_virtual_stages

    def _mb_split(a, m):
        return a.reshape((m, a.shape[0] // m) + a.shape[1:])

    def _impl(stage_params, head_params, x, labels, rng):
        m = num_microbatches
        if x.shape[0] % m:
            raise ValueError(
                f"batch {x.shape[0]} not divisible by num_microbatches {m}"
            )
        op_np, mb_np, ch_np, _, depth, q_f, q_b = _schedule_1f1b(
            m, n_stages, v
        )
        op_tbl, mb_tbl, ch_tbl = (
            jnp.asarray(op_np), jnp.asarray(mb_np), jnp.asarray(ch_np)
        )
        x_mb, labels_mb = _mb_split(x, m), _mb_split(labels, m)

        param_specs = jax.tree.map(
            lambda p: P(*((pipe_axis,) + (None,) * (p.ndim - 1))),
            stage_params,
        )
        head_specs = jax.tree.map(lambda _: P(), head_params)
        constrained = _pin_pipe_dim(stage_params, mesh)

        def local(sp, hp, xm, lm, r=None):
            loss, d_sp, d_hp, dx = _1f1b_local(
                stage_fn, head_loss_fn, sp, hp, xm, lm, r,
                pipe_axis, op_tbl, mb_tbl, ch_tbl, v, depth, q_f, q_b,
            )
            dev = lax.axis_index(pipe_axis)
            is_last = dev == n_stages - 1  # hosts the last virtual stage
            # Only `pipe` is manual here (axis_names below): inside this
            # region the arrays are GLOBAL over the batch/model axes and
            # XLA inserts the DP/TP collectives from their shardings —
            # the hand-written pmeans of the all-manual formulation are
            # gone. Loss and head grads exist on the last device, dx on
            # device 0; one psum each replicates them over the pipe
            # (zeros elsewhere).
            loss = _psum_pipe(jnp.where(is_last, loss, 0.0), pipe_axis)
            d_hp = _psum_pipe(
                jax.tree.map(
                    lambda g: jnp.where(is_last, g, jnp.zeros_like(g)),
                    d_hp,
                ),
                pipe_axis,
            )
            dx = _psum_pipe(dx, pipe_axis)  # zeros off device 0
            return loss / m, d_sp, d_hp, dx

        if rng is None:
            # A None rng can't cross the shard_map boundary as an arg.
            return _shard_map(
                lambda sp, hp, xm, lm: local(sp, hp, xm, lm),
                mesh=mesh,
                in_specs=(param_specs, head_specs, P(), P()),
                out_specs=(P(), param_specs, head_specs, P()),
                axis_names={pipe_axis},
                check_vma=False,
            )(constrained, head_params, x_mb, labels_mb)
        return _shard_map(
            local,
            mesh=mesh,
            in_specs=(param_specs, head_specs, P(), P(), P()),
            out_specs=(P(), param_specs, head_specs, P()),
            axis_names={pipe_axis},
            check_vma=False,
        )(constrained, head_params, x_mb, labels_mb, rng)

    @jax.custom_vjp
    def run(stage_params, head_params, x, labels, rng):
        loss, _, _, _ = _impl(stage_params, head_params, x, labels, rng)
        return loss

    def run_fwd(stage_params, head_params, x, labels, rng):
        loss, d_sp, d_hp, dx_mb = _impl(stage_params, head_params, x, labels, rng)
        dx = dx_mb.reshape((x.shape[0],) + x.shape[1:]) / num_microbatches
        d_sp = jax.tree.map(lambda g: g / num_microbatches, d_sp)
        d_hp = jax.tree.map(lambda g: g / num_microbatches, d_hp)
        return loss, (d_sp, d_hp, dx, labels, rng)

    def run_bwd(res, g):
        d_sp, d_hp, dx, labels, rng = res
        scale = lambda t: jax.tree.map(lambda a: a * g, t)
        zero_lbl = np.zeros(labels.shape, jax.dtypes.float0)
        zero_rng = (
            None if rng is None else np.zeros(rng.shape, jax.dtypes.float0)
        )
        return scale(d_sp), scale(d_hp), dx * g, zero_lbl, zero_rng

    run.defvjp(run_fwd, run_bwd)
    return run
