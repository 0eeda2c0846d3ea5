"""Mixture-of-Experts FFN with expert parallelism (EP).

Framework-native extension (SURVEY.md §2d notes the reference has no MoE
workload; EP is provided as a first-class capability of the parallelism
layer). Switch/GShard-style top-k routing, TPU-first:

Three dispatch formulations share one router:

- ``moe_ffn(impl="grouped")`` — sort-based DROPLESS dispatch (round 5,
  the TPU single-program default): argsort (token, rank) pairs by
  expert → grouped matmuls over contiguous segments (MegaBlocks
  ``megablox.gmm`` Pallas kernel at tile-divisible shapes, masked
  ``lax.ragged_dot`` otherwise) → inverse-permutation gather → gated
  sum. Scatter-free in fwd AND bwd (custom-vjp permutation/partial-
  permutation gathers): the round-4 harvest measured the scatter
  formulation leaving the chip >99% idle (rel_mfu 0.00154 vs dense
  0.0624).
- ``moe_ffn(impl="scatter")`` — static-capacity Switch semantics (the
  CPU default and the parity reference): fixed per-expert ``capacity``,
  overflow falls through the residual — no dynamic shapes under jit;
  the dropped fraction is returned so training can LOG it (a
  silently-high drop rate is the classic MoE failure mode).
- ``moe_ffn_ep`` — explicit expert parallelism under ``shard_map``:
  capacity buffers (the fixed-size all-to-all transport format) built
  by the SORTED-GATHER slotting (scatter-free), one ``lax.all_to_all``
  hop each way over the ``model`` axis.

Experts are the *same* FFN pytree with a leading [experts] axis,
sharded over the ``model`` mesh axis (GPT2_RULES). The router computes
in f32 with jitter noise at train time and the Switch auxiliary
load-balancing loss (mean fraction · mean prob per expert, over rank-0
assignments). (The round-1 formulation built a dense one-hot
``[n, E, C]`` dispatch tensor and einsummed against it: O(n·E·C)
memory — fine for toy shapes, dead at real n·E.)

``moe_ffn_held`` is the grouped formulation for a chip that holds
SOME of the experts (the serving engine's expert layer,
``serving/blocks.py``): the router at its full width with a sigmoid
score, SwiGLU experts, and only the pairs routed to the held experts
computed — this chip's part of the routed sum.

``moe_ffn`` is pure (params in, tokens out) so it slots into flax
modules (models/transformer.py MoeMlp) and composes with remat/scan.
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from tensorflow_examples_tpu.core.collectives import shard_map as _shard_map

log = logging.getLogger(__name__)


def _router(
    tokens: jax.Array,  # [n, d] f32-castable
    gate_w: jax.Array,  # [d, E]
    *,
    top_k: int,
    rng: jax.Array | None,
    jitter: float,
    select: str = "softmax",
    precision=None,
    select_bias: jax.Array | None = None,  # [E] f32
    scale: float = 1.0,
):
    """Top-k router, shared by every dispatch formulation. Returns
    (gates, experts, mean_onehot0 [E], mean_probs [E]). ``select`` is
    the score every expert gets before the top-k: ``softmax`` over the
    experts (Switch/GShard) or an independent ``sigmoid`` each.

    ``select_bias`` is added to the scores for the CHOICE alone: the
    top-k are those of ``score + bias``, their gates the scores
    without it (the load-balancing bias of auxiliary-loss-free
    routing). ``scale`` multiplies the gates after their
    normalisation. ``select="sigmoid"`` with both is DeepSeek-V3's
    ``noaux_tc`` with one group. The defaults leave every gate and
    choice bit-identical to a router that knows neither."""
    e = gate_w.shape[-1]
    logits = jnp.dot(
        tokens.astype(jnp.float32), gate_w.astype(jnp.float32),
        precision=precision,
    )
    if rng is not None and jitter > 0:
        logits += jax.random.uniform(
            rng, logits.shape, jnp.float32, -jitter, jitter
        )
    if select not in ("softmax", "sigmoid"):
        raise ValueError(f"router select={select!r} not in ('softmax', 'sigmoid')")
    probs = (
        jax.nn.sigmoid(logits) if select == "sigmoid"
        else jax.nn.softmax(logits, axis=-1)
    )  # [n, E]

    # Sequential top-k: argmax, mask, repeat (k is tiny and static).
    masked = probs
    experts, gates = [], []
    if select_bias is None:
        for _ in range(top_k):
            ej = jnp.argmax(masked, axis=-1)  # [n]
            pj = jnp.take_along_axis(masked, ej[:, None], axis=-1)[:, 0]
            experts.append(ej)
            gates.append(pj)
            masked = masked * (1.0 - jax.nn.one_hot(ej, e, dtype=jnp.float32))
    else:
        # A biased score may be negative: taken experts drop to -inf.
        masked = probs + select_bias.astype(jnp.float32)
        for _ in range(top_k):
            ej = jnp.argmax(masked, axis=-1)
            experts.append(ej)
            gates.append(jnp.take_along_axis(probs, ej[:, None], axis=-1)[:, 0])
            masked = jnp.where(
                jax.nn.one_hot(ej, e, dtype=jnp.bool_), -jnp.inf, masked
            )
    # top-1: keep the raw router probability as the gate (Switch) — it
    # is how the router gets task-loss gradient. Renormalizing would
    # make the gate identically 1.0 and silently detach the router.
    # top-k>1: renormalize over the chosen experts (GShard) — relative
    # weights still carry gradient there.
    if top_k > 1:
        denom = jnp.maximum(sum(gates), 1e-9)
        gates = [g / denom for g in gates]
    if scale != 1.0:
        gates = [g * scale for g in gates]

    mean_onehot0 = jnp.mean(
        jax.nn.one_hot(experts[0], e, dtype=jnp.float32), axis=0
    )
    mean_probs = jnp.mean(probs, axis=0)
    return gates, experts, mean_onehot0, mean_probs


def _route(
    tokens: jax.Array,  # [n, d] f32-castable
    gate_w: jax.Array,  # [d, E]
    *,
    top_k: int,
    capacity: int,
    rng: jax.Array | None,
    jitter: float,
):
    """Router + static-capacity slotting (the EP transport format).
    Returns (gates, flat_slots, keeps, mean_onehot0 [E], mean_probs [E],
    kept_count scalar)."""
    e = gate_w.shape[-1]
    gates, experts, mean_onehot0, mean_probs = _router(
        tokens, gate_w, top_k=top_k, rng=rng, jitter=jitter
    )

    # Static-capacity slotting: rank-0 assignments queue first, then
    # rank-1, … — each (token, rank) gets a 1-based position in its
    # expert's queue; positions past capacity are dropped.
    counts = jnp.zeros((e,), jnp.int32)
    flat_slots, keeps = [], []
    for ej in experts:
        oh = jax.nn.one_hot(ej, e, dtype=jnp.int32)  # [n, E]
        pos = (jnp.cumsum(oh, axis=0) + counts[None, :]) * oh  # [n, E]
        posj = jnp.sum(pos, axis=-1)  # [n], 1-based
        keeps.append(posj <= capacity)
        flat_slots.append(ej * capacity + jnp.clip(posj - 1, 0, capacity - 1))
        counts = counts + jnp.sum(oh, axis=0)
    kept = sum(jnp.sum(k_.astype(jnp.int32)) for k_ in keeps)
    return gates, flat_slots, keeps, mean_onehot0, mean_probs, kept


def _dispatch(tokens, flat_slots, keeps, e, capacity):
    """Scatter-add kept token rows into the [E·C, d] expert buffers.
    Slots are unique per kept (token, rank) pair, so adds never collide."""
    xin = jnp.zeros((e * capacity, tokens.shape[-1]), tokens.dtype)
    for flat, keep in zip(flat_slots, keeps):
        xin = xin.at[flat].add(
            tokens * keep[:, None].astype(tokens.dtype), mode="drop"
        )
    return xin


def _expert_ffn(xin, w_in, b_in, w_out, b_out):
    """Batched expert FFN over [E, C, d] buffers (one MXU matmul pair)."""
    h = jnp.einsum("ecd,edf->ecf", xin, w_in) + b_in[:, None, :]
    h = jax.nn.gelu(h, approximate=True)
    return jnp.einsum("ecf,efd->ecd", h, w_out) + b_out[:, None, :]


def _combine(yout, flat_slots, keeps, gates, n):
    """Gather each (token, rank)'s output row, gate, and sum — f32."""
    d = yout.shape[-1]
    yflat = yout.reshape(-1, d).astype(jnp.float32)
    out = jnp.zeros((n, d), jnp.float32)
    for flat, keep, gate in zip(flat_slots, keeps, gates):
        out = out + yflat[flat] * (gate * keep)[:, None]
    return out


# megablox gmm tile cap (tm, tk, tn). The kernel's grid is
# ~(n/tn)·(m/tm + g)·(k/tk) steps of one tm x tk x tn MXU pass each; at
# the bench shape ([16384, 768] x [8, 768, 3072]) the upstream default
# (128, 128, 128) is ~19k grid steps whose per-step overhead dwarfs the
# 4.2-MFLOP tile matmul. tools/moe_diag.py sweeps tilings on-chip; this
# cap is the grid-arithmetic choice pending that sweep (not measured),
# and tests_tpu/ re-proves the compiled numerics under it either way.
GMM_TILE_CAP: int = 512

# Wide weights take tiles wider than the cap. [rows, 4096] x [16, 4096,
# 4096] bf16 on a v5e (PR 28; 27 tilings each, 10 calls timed): a
# prefill chunk's product, 4096 rows of which 512 are in the 16 groups,
# took 1.98 ms under the cap's (512, 512, 512) and 1.00 ms under (256,
# 1024, 2048); a decode step's, 256 rows of which 32 in 13 groups, 1.15
# ms under (256, 512, 512) and 0.76 ms under (128, 1024, 2048); reading
# the hit experts' 537 MB once is 0.66 ms. Wider n tiles won every time
# (fewer passes over the rows), tk mattered little, and tn = tk = 2048
# did not fit VMEM: the rule is the widest [tk, tn] weight tile of at
# most GMM_WEIGHT_TILE_BYTES (double-buffered, half of the 16 MiB of
# scoped VMEM), n first. Narrower weights (k < 1024 or n < 2048, the
# training shapes) keep the cap, which no chip run has swept yet.
GMM_WIDE_TN: int = 2048
GMM_WEIGHT_TILE_BYTES: int = 4 << 20


def _gmm_tiling(m: int, k: int, n: int,
                itemsize: int = 2) -> "tuple[int, int, int]":
    """Largest tiles <= GMM_TILE_CAP the shape admits: tm must DIVIDE m
    (make_group_metadata raises otherwise). tk prefers the largest
    lane-aligned (multiple-of-128) tile in [cap/2, cap] that DIVIDES
    k — at the bench shape k=768 a capped 512 tile leaves a masked 256
    remainder tile on every contraction pass, where 384 tiles it
    exactly — and falls back to ``min(cap, k)`` (masked remainder)
    when no such divisor exists. The cap/2 floor keeps shapes like
    k=640/896 (no large divisor) on one near-cap masked pass instead
    of many tiny exact ones — grid-step overhead is the whole reason
    these tiles are big. n is masked internally so its tile is only
    capped to the dim.

    Weights that ``GMM_WIDE_TN`` divides (and whose k a tile twice the
    cap divides) take the wide tiles instead: tn = GMM_WIDE_TN, tk the
    largest power of two that keeps a weight tile of ``itemsize`` bytes
    an element within GMM_WEIGHT_TILE_BYTES, and row tiles of 256 — 128
    where there are no more than 256 rows, so that a few rows in many
    groups do not pad every group to 256."""
    tk_wide = GMM_WEIGHT_TILE_BYTES // (GMM_WIDE_TN * itemsize)
    if n % GMM_WIDE_TN == 0 and tk_wide >= 2 * GMM_TILE_CAP \
            and k % tk_wide == 0:
        tm = 256 if m > 256 else 128
        while m % tm:
            tm //= 2
        return tm, tk_wide, GMM_WIDE_TN
    tm = GMM_TILE_CAP
    while m % tm:
        tm //= 2
    tk = next(
        (t for t in range(GMM_TILE_CAP, GMM_TILE_CAP // 2 - 1, -128)
         if k % t == 0),
        min(GMM_TILE_CAP, k),
    )
    return tm, tk, min(GMM_TILE_CAP, n)


@functools.lru_cache(maxsize=None)
def _warn_ragged_dot_on_tpu(m: int, k: int, n: int) -> None:
    """Once per shape: the grouped path left the Pallas kernel."""
    log.warning(
        "MoE grouped matmul [%d, %d] x [g, %d, %d]: a dimension is not "
        "a multiple of 128, so this runs lax.ragged_dot (g x the ideal "
        "FLOPs on the TPU) instead of the megablox gmm kernel",
        m, k, k, n,
    )


def _grouped_matmul(lhs, rhs, sizes):
    """[m, k] x [g, k, n] with per-group row segments -> [m, n].

    TPU: the MegaBlocks-style Pallas grouped-matmul kernel
    (jax.experimental megablox ``gmm``, custom-vjp complete — dlhs via
    gmm, drhs via tgmm), which does ~1x the ideal FLOPs with MXU-tiled
    segments. Everywhere else (and for tile-incompatible shapes, with
    a warning on the TPU): ``lax.ragged_dot``, whose generic lowering
    masks a [g, m, k] broadcast into one batched dot — g x the ideal
    FLOPs, fine for tests/CPU but exactly what the gmm path exists to
    avoid on the chip."""
    m, k, n = lhs.shape[0], lhs.shape[1], rhs.shape[-1]
    if jax.default_backend() == "tpu":
        # m (rows) is the one dimension megablox gmm REQUIRES to be
        # tile-divisible (make_group_metadata raises otherwise, e.g.
        # any decode-time token count); k/n remainders it masks
        # internally, but tiny k/n would under-fill the MXU anyway.
        if m % 128 == 0 and k % 128 == 0 and n % 128 == 0:
            from jax.experimental.pallas.ops.tpu.megablox import (
                ops as megablox,
            )

            # Positional, as jax 0.9.0's gmm(lhs, rhs, group_sizes,
            # preferred_element_type, tiling, ...) declares them.
            return megablox.gmm(
                lhs, rhs, sizes, lhs.dtype,
                _gmm_tiling(m, k, n, rhs.dtype.itemsize),
            )
        _warn_ragged_dot_on_tpu(m, k, n)
    return lax.ragged_dot(lhs, rhs, sizes)


@jax.custom_vjp
def _permute_rows(x, perm, inv_perm):
    """``x[perm]`` with a GATHER backward.

    XLA transposes a gather into a scatter-add; for a PERMUTATION the
    cotangent is just the inverse gather, and row-granularity scatters
    are exactly what the grouped path exists to avoid on TPU (the
    round-4 scatter formulation measured the chip >99% idle). The
    caller supplies the inverse (argsort already produced it)."""
    del inv_perm
    return x[perm]


def _permute_rows_fwd(x, perm, inv_perm):
    return x[perm], (perm, inv_perm)


def _permute_rows_bwd(res, g):
    perm, inv_perm = res
    return g[inv_perm], None, None


_permute_rows.defvjp(_permute_rows_fwd, _permute_rows_bwd)


@jax.custom_vjp
def _masked_row_gather(src, idx, valid, inv_idx, inv_valid):
    """``src[idx] * valid`` where (idx, valid) describes an INJECTIVE
    row map (no two outputs read the same valid source row) and
    (inv_idx, inv_valid) is its precomputed inverse. The cotangent is
    then the inverse masked gather — never a scatter (the capacity
    slotting below precomputes both directions from one argsort)."""
    del inv_idx, inv_valid
    return src[idx] * valid[:, None].astype(src.dtype)


def _masked_row_gather_fwd(src, idx, valid, inv_idx, inv_valid):
    out = src[idx] * valid[:, None].astype(src.dtype)
    return out, (inv_idx, inv_valid)


def _masked_row_gather_bwd(res, g):
    inv_idx, inv_valid = res
    return (
        g[inv_idx] * inv_valid[:, None].astype(g.dtype),
        None, None, None, None,
    )


_masked_row_gather.defvjp(_masked_row_gather_fwd, _masked_row_gather_bwd)


def _pair_sort(experts, e):
    """The shared sort prelude of both sorted formulations: flatten
    (token, rank) pairs TOKEN-MAJOR (pair p = (token p//k, rank p%k)),
    stable-argsort by expert. Returns (eid, order, inv, sizes)."""
    eid = jnp.stack(experts, axis=1).reshape(-1)          # [n·k]
    order = jnp.argsort(eid)                              # stable
    inv = jnp.argsort(order)
    sizes = jnp.bincount(eid, length=e)
    return eid, order, inv, sizes


def _capacity_slots_sorted(tokens, experts, top_k, e, capacity):
    """Build the [E·C, d] dispatch buffer (the EP all-to-all transport
    format) by SORTED GATHERS instead of scatter-adds.

    One argsort of the (token, rank) pairs by expert yields both
    directions of the pair↔slot bijection (each capacity slot is
    filled by at most one kept pair), so dispatch fwd/bwd and combine
    fwd/bwd are all masked gathers via _masked_row_gather — the
    shard_map EP path has no row-granularity scatter left.

    Queue order is sorted-pair order (token-major), not the scatter
    reference's rank-major cumsum — a different overflow victim set,
    same per-(source, expert) quota semantics; identical whenever
    nothing drops (the parity-tested regime).

    Returns (xin [E·C, d], pair_slot [n·k], pair_keep [n·k],
    slot_pair [E·C], slot_valid [E·C], kept scalar).
    """
    n = tokens.shape[0]
    nk = n * top_k
    eid, order, inv, sizes = _pair_sort(experts, e)
    offsets = jnp.cumsum(sizes) - sizes
    pos = inv - jnp.take(offsets, eid)                    # queue position
    pair_keep = pos < capacity
    pair_slot = eid * capacity + jnp.clip(pos, 0, capacity - 1)
    # slot (e, c) <- sorted row offsets[e] + c when c < sizes[e]; that
    # sorted row is pair order[offsets[e] + c], so the slot reads the
    # PAIR directly (one composed gather — no intermediate sorted
    # [n·k, d] copy) and (pair_slot, pair_keep) is its exact inverse.
    slot_j = offsets[:, None] + jnp.arange(capacity)[None, :]   # [E, C]
    slot_valid = (
        jnp.arange(capacity)[None, :] < sizes[:, None]
    ).reshape(-1)
    slot_j = jnp.clip(slot_j, 0, nk - 1).reshape(-1)
    slot_pair = jnp.take(order, slot_j)
    xin = _masked_row_gather(
        jnp.repeat(tokens, top_k, axis=0),
        slot_pair,
        slot_valid,
        pair_slot,
        pair_keep,
    )
    kept = jnp.sum(pair_keep.astype(jnp.int32))
    return xin, pair_slot, pair_keep, slot_pair, slot_valid, kept


def _grouped_dispatch(tokens, gates, experts, groups, ffn):
    """The sorted dispatch and combine of the grouped formulation,
    around any expert FFN: argsort the (token, rank) pairs by group,
    ``ffn(sorted rows [n*k, d], their group ids, group sizes) -> [n*k,
    d]``, gate, inverse-permute, sum over ranks. Returns ``([n, d]
    f32, sizes [groups] int32)``.

    Both permutation hops ride _permute_rows so fwd AND bwd are
    gathers (argsort hands us the inverse for free); the token
    replication is a jnp.repeat, whose transpose is a contiguous
    [n, k] reduce — the whole fwd+bwd dispatch path is scatter-free."""
    n, d = tokens.shape
    top_k = len(experts)
    eid, order, inv, sizes = _pair_sort(experts, groups)
    sizes = sizes.astype(jnp.int32)
    gat = jnp.stack(gates, axis=1).reshape(-1)            # [n·k] f32
    srt_tok = _permute_rows(
        jnp.repeat(tokens, top_k, axis=0), order, inv
    )                                                     # [n·k, d]
    srt_eid = jnp.take(eid, order, axis=0)
    y = ffn(srt_tok, srt_eid, sizes)
    yw = y.astype(jnp.float32) * _permute_rows(gat, order, inv)[:, None]
    restored = _permute_rows(yw, inv, order)              # pair order
    return jnp.sum(restored.reshape(n, top_k, d), axis=1), sizes


def _moe_ffn_grouped(
    gate_w, w_in, b_in, w_out, b_out, x, *, top_k, rng, jitter
):
    """Sort-based DROPLESS dispatch: the single-chip hot path.

    The capacity formulation's scatter-add dispatch and gathered
    combine dominate single-program MoE step time on TPU (round-4
    measured rel_mfu 0.00154 vs dense 0.0624 — the chip idles while
    row-granularity scatters serialize). This path
    has NO scatter at all:

      argsort (token, rank) pairs by expert → contiguous per-expert
      segments → two ``lax.ragged_dot`` grouped matmuls (XLA's native
      MoE primitive: one MXU pass over [n·k, d] with per-group weight
      selection) → inverse-permutation gather → gated sum over ranks.

    Every shape is static ([n·k, …] regardless of routing), so it jits
    cleanly; group sizes are data. Dropless semantics: no token is ever
    dropped (strictly better than capacity both in quality and in
    wasted slots — there is no padded [E, C] buffer), so the returned
    drop_fraction is identically 0. With ample capacity the capacity
    path computes the same function, which is what the EP parity tests
    check.
    """
    b, s, d = x.shape
    e = gate_w.shape[-1]
    n = b * s
    tokens = x.reshape(n, d)
    gates, experts, moh0, mpr = _router(
        tokens, gate_w, top_k=top_k, rng=rng, jitter=jitter
    )
    aux = e * jnp.sum(moh0 * mpr)

    def ffn(srt_tok, srt_eid, sizes):
        h = _grouped_matmul(srt_tok, w_in, sizes) + jnp.take(
            b_in, srt_eid, axis=0
        )
        h = jax.nn.gelu(h, approximate=True)
        return _grouped_matmul(h, w_out, sizes) + jnp.take(
            b_out, srt_eid, axis=0
        )

    out, _ = _grouped_dispatch(tokens, gates, experts, e, ffn)
    return (
        out.reshape(b, s, d).astype(x.dtype),
        aux,
        jnp.float32(0.0),
    )


def moe_ffn_held(
    router_w: jax.Array,  # [d, E]: the router at its published width
    w_gate: jax.Array,    # [n_held, d, ff]
    w_up: jax.Array,      # [n_held, d, ff]
    w_down: jax.Array,    # [n_held, ff, d]
    tokens: jax.Array,    # [n, d]; the router reads them in float32
    *,
    held: tuple,
    top_k: int,
    valid: jax.Array | None = None,  # [n] bool: rows that are real tokens
    select_bias: jax.Array | None = None,  # [E]: moves the choice, not the weights
    scale: float = 1.0,                    # on the weights, after normalisation
) -> tuple[jax.Array, jax.Array]:
    """The expert layer of a chip that is TOLD WHAT IT HOLDS: ``held``
    are the ids, among the router's ``E`` experts, of the SwiGLU experts
    whose weights are here (``w_*[i]`` is expert ``held[i]``).

    The router scores all ``E`` experts (sigmoid, top ``top_k``,
    weights normalised over the chosen; with ``select_bias`` the top
    are those of score + bias, and ``scale`` multiplies the normalised
    weights: ``_router``), and the chip computes the
    grouped formulation's product for the (token, expert) pairs routed
    to its own experts, dropless, combined with the router's weights.
    Pairs routed to absent experts contribute nothing: the result is
    this chip's PART of the routed sum, and that partial sum goes on
    (the sum over every chip's ``held`` is the whole layer; nothing
    here stands in for the other chips or their exchange).

    Returns ``(part [n, d] float32, pairs [n_held] int32)``: the pairs
    each held expert computed. Rows with ``valid`` false (padding, a
    parked slot) are routed nowhere: not computed, not counted."""
    e = router_w.shape[-1]
    n_held = len(held)
    gates, experts, _, _ = _router(
        tokens, router_w, top_k=min(top_k, e), rng=None, jitter=0.0,
        select="sigmoid", precision=lax.Precision.HIGHEST,
        select_bias=select_bias, scale=scale,
    )
    # Global expert id -> this chip's group; every absent expert is
    # the one group past the held, which the product never visits.
    table = [n_held] * e
    for i, ex in enumerate(held):
        table[ex] = i
    table = jnp.asarray(table, jnp.int32)
    local = [jnp.take(table, ej) for ej in experts]
    if valid is not None:
        local = [jnp.where(valid, lj, n_held) for lj in local]

    def ffn(srt_tok, srt_eid, sizes):
        mine = sizes[:n_held]
        with jax.named_scope("moe_experts"):
            g = _grouped_matmul(srt_tok, w_gate, mine).astype(jnp.float32)
            u = _grouped_matmul(srt_tok, w_up, mine).astype(jnp.float32)
            y = _grouped_matmul(
                (jax.nn.silu(g) * u).astype(w_down.dtype), w_down, mine
            )
        # The rows of the absent group lie behind the held groups and
        # no product wrote them: whatever is there is not a number.
        return jnp.where((srt_eid < n_held)[:, None], y, 0)

    part, sizes = _grouped_dispatch(
        tokens.astype(w_gate.dtype), gates, local, n_held + 1, ffn
    )
    return part, sizes[:n_held]


def moe_ffn(
    gate_w: jax.Array,  # [d, E] router weights
    w_in: jax.Array,    # [E, d, ff]
    b_in: jax.Array,    # [E, ff]
    w_out: jax.Array,   # [E, ff, d]
    b_out: jax.Array,   # [E, d]
    x: jax.Array,       # [B, S, d]
    *,
    capacity_factor: float = 1.25,
    top_k: int = 1,
    rng: jax.Array | None = None,
    jitter: float = 1e-2,
    impl: str | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k MoE FFN (single-program formulations).

    Returns ``(out [B,S,d], aux_loss scalar, drop_fraction scalar)``;
    ``drop_fraction`` is the fraction of (token, rank) assignments that
    overflowed expert capacity and fell through the residual.

    ``impl``: ``"grouped"`` — sort-based dropless dispatch through
    grouped matmuls (drop_fraction ≡ 0; megablox gmm on TPU);
    ``"scatter"`` — the static-capacity scatter/gather formulation
    (Switch drop semantics, the EP transport's reference). Default
    (None) resolves by backend: "grouped" on TPU — where the grouped
    matmul is a real Pallas kernel and row scatters serialize — and
    "scatter" elsewhere, where the grouped path's ragged_dot fallback
    lowers to an E-times-FLOPs masked dot (measured ~6x slower than
    scatter on this CPU) and would skew CPU floors for no benefit.
    """
    if impl is None:
        impl = "grouped" if jax.default_backend() == "tpu" else "scatter"
    if impl not in ("grouped", "scatter"):
        raise ValueError(
            f"moe_ffn impl={impl!r} unknown (expected 'grouped' or "
            "'scatter')"
        )
    b, s, d = x.shape
    e = gate_w.shape[-1]
    n = b * s
    top_k = min(top_k, e)
    if impl == "grouped":
        return _moe_ffn_grouped(
            gate_w, w_in, b_in, w_out, b_out, x,
            top_k=top_k, rng=rng, jitter=jitter,
        )
    tokens = x.reshape(n, d)
    capacity = max(1, int(capacity_factor * top_k * n / e))

    gates, flat_slots, keeps, moh0, mpr, kept = _route(
        tokens, gate_w, top_k=top_k, capacity=capacity, rng=rng, jitter=jitter
    )
    # Switch aux loss over rank-0 assignments:
    # E · Σ_e (fraction of tokens → e) · (mean prob of e).
    aux = e * jnp.sum(moh0 * mpr)
    drop_frac = 1.0 - kept.astype(jnp.float32) / (n * top_k)

    xin = _dispatch(tokens, flat_slots, keeps, e, capacity)
    yout = _expert_ffn(xin.reshape(e, capacity, d), w_in, b_in, w_out, b_out)
    out = _combine(yout, flat_slots, keeps, gates, n)
    return out.reshape(b, s, d).astype(x.dtype), aux, drop_frac


def moe_ffn_ep(
    gate_w: jax.Array,  # [d, E] (replicated)
    w_in: jax.Array,    # [E, d, ff] (sharded over `model`)
    b_in: jax.Array,    # [E, ff]
    w_out: jax.Array,   # [E, ff, d]
    b_out: jax.Array,   # [E, d]
    x: jax.Array,       # [B, S, d] (sharded over batch/context axes)
    *,
    mesh,
    capacity_factor: float = 1.25,
    top_k: int = 1,
    rng: jax.Array | None = None,
    jitter: float = 1e-2,
    impl: str | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Explicit expert-parallel MoE FFN: all-to-all token exchange.

    ``impl`` applies to the SINGLE-PROGRAM fallback only (trivial/
    non-dividing ``model`` axis — see moe_ffn); the shard_map EP path
    is capacity-based by construction (fixed-size all-to-all buffers).

    Same routing math as :func:`moe_ffn`, but dispatch is a
    ``shard_map`` program with POINT-TO-POINT token exchange
    (DESIGN.md §7 EP note): under pure SPMD the partitioner turns the
    scatter/gather dispatch into all-gathers of the full ``[E, C, d]``
    buffer across the ``model`` axis (measured: 0 all-to-all on a
    dp2×model4 mesh — bench.py --bench=moe), moving E·C rows per device
    where an all-to-all moves only C. Here each device routes ITS
    tokens, ships per-expert-group slices to the owning device with one
    ``lax.all_to_all``, runs the local experts' FFN, and ships results
    back with the inverse all-to-all — the GShard/Switch dispatch
    pattern on ICI.

    Tokens are additionally SPLIT over the ``model`` axis inside the
    shard_map (ADVICE r3: the incoming activations are replicated over
    ``model`` under TP, and routing identical copies on every model-rank
    would multiply expert FLOPs and all-to-all payload by m): each
    model-rank takes a contiguous 1/m block of the local token set,
    routes it with capacity/m, and one tiled all-gather over ``model``
    reassembles the combined outputs at the end — per-device expert
    compute is E·C/m slots, the true EP share. Requires
    ``n_local % m == 0`` (any power-of-two batch·seq); otherwise the
    rank-replicated behavior is kept (correct, m× redundant — decode-
    time single-token steps, where FLOPs are negligible anyway).

    Capacity semantics differ from the single-program path by design:
    capacity is per (source rank, expert) — each (device, model-rank)
    may keep up to ``capacity_factor·k·n_local/(m·E)`` tokens per
    expert, so the drop pattern is per-source quota rather than a
    global queue (the standard multi-device MoE behavior; identical
    when nothing overflows). The aux loss is exact: per-expert
    fractions/probs are pmean'd over the token axes (including the
    ``model`` split) BEFORE the product, which equals the global-batch
    Switch aux when shards hold equal token counts (they do: static
    shapes).

    Requires E % mesh.model == 0; gradients flow through the
    all-to-alls (they transpose to themselves reversed) and the
    all-gather (transposes to a psum-scatter).
    """
    from tensorflow_examples_tpu.core.mesh import (
        AxisNames,
        token_partition_axes,
    )

    e = gate_w.shape[-1]
    m = mesh.shape[AxisNames.MODEL] if mesh is not None else 1
    if m <= 1 or e % m:
        return moe_ffn(
            gate_w, w_in, b_in, w_out, b_out, x,
            capacity_factor=capacity_factor, top_k=top_k,
            rng=rng, jitter=jitter, impl=impl,
        )
    top_k = min(top_k, e)
    # Token sharding via the shared axis-dropping policy
    # (core/mesh.py token_partition_axes): a non-dividing axis is
    # dropped — tokens replicate over it, routing stays correct, only
    # the all-to-all over `model` is essential.
    batch_axes, seq_axes = token_partition_axes(mesh, x.shape[0], x.shape[1])
    token_axes = batch_axes + seq_axes
    x_spec = P(
        batch_axes if batch_axes else None,
        seq_axes if seq_axes else None,
        None,
    )
    ew_spec = P(AxisNames.MODEL)  # leading [E] dim of every expert leaf

    def local(gw, wi, bi, wo, bo, xl, key):
        b_loc, s_loc, d = xl.shape
        all_tokens = xl.reshape(-1, d)
        n_all = all_tokens.shape[0]
        # Static decision: split the (model-replicated) local tokens
        # over the model axis so each rank routes a UNIQUE 1/m block.
        split = n_all % m == 0
        if split:
            n_loc = n_all // m
            rank = lax.axis_index(AxisNames.MODEL)
            tokens = lax.dynamic_slice_in_dim(all_tokens, rank * n_loc, n_loc)
        else:
            n_loc, tokens = n_all, all_tokens
        route_axes = token_axes + ((AxisNames.MODEL,) if split else ())
        capacity = max(1, int(capacity_factor * top_k * n_loc / e))
        if key is not None:
            # Decorrelate router jitter across token shards.
            for a in route_axes:
                key = jax.random.fold_in(key, lax.axis_index(a))
        gates, experts, moh0, mpr = _router(
            tokens, gw, top_k=top_k, rng=key, jitter=jitter
        )
        if route_axes:
            moh0 = lax.pmean(moh0, route_axes)
            mpr = lax.pmean(mpr, route_axes)
        aux = e * jnp.sum(moh0 * mpr)
        # Sorted-gather capacity slotting (round 5): the dispatch
        # buffer and the combine are masked gathers in BOTH fwd and
        # bwd — no row-granularity scatter inside the EP program.
        xin, pair_slot, pair_keep, slot_pair, slot_valid, kept = (
            _capacity_slots_sorted(tokens, experts, top_k, e, capacity)
        )
        drop = 1.0 - kept.astype(jnp.float32) / (n_loc * top_k)
        if route_axes:
            drop = lax.pmean(drop, route_axes)

        # [E·C, d] → [m, E/m, C, d]: group g's slice belongs to device g.
        xin = xin.reshape(m, e // m, capacity, d)
        # One hop: device g receives [m(src), E/m, C, d] for ITS experts.
        recv = lax.all_to_all(
            xin, AxisNames.MODEL, split_axis=0, concat_axis=0
        )
        # Local experts over all sources' slots: [E/m, m·C, d].
        buf = recv.transpose(1, 0, 2, 3).reshape(e // m, m * capacity, d)
        yloc = _expert_ffn(buf, wi, bi, wo, bo)
        # Inverse hop: slot layout returns to expert-major [E, C, d].
        yloc = yloc.reshape(e // m, m, capacity, d).transpose(1, 0, 2, 3)
        yout = lax.all_to_all(
            yloc, AxisNames.MODEL, split_axis=0, concat_axis=0
        )
        # Combine: each (token, rank) pair reads its slot (masked
        # gather; inverse = slot->pair map), gates, sums over ranks.
        yflat = yout.reshape(e * capacity, d).astype(jnp.float32)
        gat = jnp.stack(gates, axis=1).reshape(-1)  # [n_loc·k] f32
        y_pair = _masked_row_gather(
            yflat, pair_slot, pair_keep, slot_pair, slot_valid
        )
        out = jnp.sum(
            (y_pair * gat[:, None]).reshape(n_loc, top_k, d), axis=1
        ).astype(xl.dtype)
        if split:
            # Reassemble the model-split blocks (gather order == the
            # axis_index order used for the dynamic_slice above).
            out = lax.all_gather(out, AxisNames.MODEL, tiled=True)
        return out.reshape(b_loc, s_loc, d), aux, drop

    # Pin the expert params' layout so shard_map's in_specs agree with
    # the rules-placed params (no silent resharding inside the step).
    experts_pinned = jax.lax.with_sharding_constraint(
        (w_in, b_in, w_out, b_out), NamedSharding(mesh, ew_spec)
    )
    args = (gate_w, *experts_pinned, x)
    in_specs = (P(), ew_spec, ew_spec, ew_spec, ew_spec, x_spec)
    fn = functools.partial(local, key=None) if rng is None else local
    if rng is not None:
        args += (rng,)
        in_specs += (P(),)
    return _shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=(x_spec, P(), P()),
        check_vma=False,
    )(*args)
