"""Step 0 of a latent-attention change: the two attention forms over
latent rows, timed on the chip at the published widths.

    chiprun -- python3 tools/mla_forms_bench.py --only chunk,decode  # the table of PERF.md §6 (PR 32)
    chiprun -- python3 tools/mla_forms_bench.py --only pool,engine   # the table of PERF.md §6 (PR 33)

For a prompt chunk: ``expanded`` (K and V of every head made from the
rows, then ordinary attention: the form as published, kept here alone)
against ``absorbed`` (``kv_cache.latent_chunk_attention``, what the
engine runs) at a chunk of 512 queries and at a tail of 128, over 8k,
16k, 30k and 32k cached rows (the extend program always
gathers a slot's whole table, 32,768 columns, and masks to the true
context). For a decode step (``latent_decode_attention``): 32 slots
through block tables at the decode rungs, with the row as one array of
576 values and as two (512 and 64).

``pool`` (PR 33): the STORED FORM of the row, timed where it costs —
row scatter + block gather + absorbed attention through DONATED pools
of the shareddoc cell's size (11,264 blocks of 16 rows, 7 layers): a
decode step of 32 slots on K32768 and a 256-token tail over a slot's
whole table. The attention alone (the ``decode`` half) never saw that a
576-wide row makes XLA:TPU re-lay each layer's whole pool around the
scatter. The forms: the row as it was (one array of 576), (a) one array
padded to 640, (b) ``c_kv`` 512 and ``k_pe`` padded to 128, (c) 512 and
64. ``engine``: the engine's own largest decode program and its T256
extend program at the cell's size (seeded weights, synthetic tables),
in the one form the tree serves.

One JSON line per case; the times are the median of ``--reps`` calls
that end in ``block_until_ready``. Fails at once off the TPU: a time
from another platform is not one.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

H, DN, DR, DC, DV = 20, 192, 64, 512, 256  # GLM-4.7-Flash's attention widths


def _time(fn, args, reps: int) -> float:
    import jax

    jax.block_until_ready(fn(*args))  # compile + warm
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        out.append(time.perf_counter() - t0)
    return statistics.median(out)


def expanded_chunk_attention(q_nope, q_pe, rows, w_uk, w_uv, ctx_rows, *, ctx_len, sm_scale):
    """The chunk attention of ``kv_cache.latent_chunk_attention`` in the
    expanded form, same shapes, masks, head groups and numerics: K and V
    of a group's heads are made from every row it sees."""
    import jax
    import jax.numpy as jnp

    from tensorflow_examples_tpu.serving import kv_cache

    t_n, h, _ = q_nope.shape
    dc, dtype = w_uk.shape[0], q_nope.dtype
    f32 = dict(preferred_element_type=jnp.float32)
    q_pos = ctx_len + jnp.arange(t_n)
    seen = jnp.concatenate([
        jnp.broadcast_to(jnp.arange(ctx_rows.shape[0]) < ctx_len, (t_n, ctx_rows.shape[0])),
        q_pos[:, None] >= q_pos[None, :]], axis=1)
    kx = jnp.concatenate([ctx_rows, rows])
    g = kv_cache.latent_head_group(h, t_n, kx.shape[0])

    def one_group(args):
        qn, qp, uk, uv = args  # [G,T,dn] [G,T,dr] [G,dc,dn] [G,dc,dv]
        k_nope = jnp.einsum("kc,gcn->gkn", kx[:, :dc], uk, **f32).astype(dtype)
        v = jnp.einsum("kc,gcv->gkv", kx[:, :dc], uv, **f32).astype(dtype)
        s = (jnp.einsum("gtn,gkn->gtk", qn, k_nope, **f32)
             + jnp.einsum("gtr,kr->gtk", qp, kx[:, dc:], **f32))
        p = jax.nn.softmax(jnp.where(seen[None], s * sm_scale, kv_cache.NEG_INF), axis=-1)
        return jnp.einsum("gtk,gkv->gtv", p.astype(dtype), v, **f32)

    by_group = lambda x: jnp.moveaxis(x, 1, 0).reshape(h // g, g, x.shape[0], x.shape[2])  # noqa: E731
    out = jax.lax.map(one_group, (by_group(q_nope), by_group(q_pe), by_group(w_uk), by_group(w_uv)))
    return jnp.moveaxis(out.reshape(h, t_n, -1), 0, 1).astype(dtype)


# ---------------------------------------------------------------- the pool half
#
# The stored forms of the latent row (PR 33), by the widths of the arrays a
# layer keeps of a token. Everything below is generic over them: the row's
# pieces (one array: ``[c_kv | k_pe]``; two: each its own) and the absorbed
# query's are zero-padded to their array's width, the scores are summed over
# the arrays, the values are the first ``DC`` columns of the first array.
FORMS = {
    "one array of 576": (DC + DR,),
    "a: one array of 640": (640,),
    "b: 512 and 128": (DC, 128),
    "c: 512 and 64": (DC, DR),
}
POOL_BLOCKS, POOL_LAYERS = 11264, 7  # the shareddoc cell's pool


def _as_stored(lat, pe, widths):
    """``c_kv`` and ``k_pe`` (or the absorbed query's two parts) as the
    form keeps them: one array or two, zero-padded to ``widths``."""
    import jax.numpy as jnp

    from tensorflow_examples_tpu.serving import kv_cache

    pieces = [jnp.concatenate([lat, pe], axis=-1)] if len(widths) == 1 else [lat, pe]
    return tuple(kv_cache.pad_columns(x, w) for x, w in zip(pieces, widths))


def form_decode_attention(q_nope, q_pe, blocks, positions, tables, w_uk, w_uv, *, sm_scale):
    """``kv_cache.latent_decode_attention`` over a row stored in any of
    ``FORMS``: ``blocks`` one ``[NB, BS, width]`` array per stored array."""
    import jax
    import jax.numpy as jnp

    from tensorflow_examples_tpu.serving import kv_cache

    s_n, nb = tables.shape
    dtype = q_nope.dtype
    f32 = dict(preferred_element_type=jnp.float32)
    q_lat = jnp.einsum("shn,chn->shc", q_nope, w_uk, **f32).astype(dtype)
    qs = _as_stored(q_lat, q_pe, tuple(b.shape[-1] for b in blocks))
    g = kv_cache.latent_slot_group(
        s_n, nb * blocks[0].shape[1], sum(b.shape[2] * b.dtype.itemsize for b in blocks))

    def one_group(i):
        rows = [b[tables[i:i + g]].reshape(g, -1, b.shape[-1]) for b in blocks]
        s = sum(jnp.einsum("ghr,gkr->ghk", q[i:i + g], r, **f32) for q, r in zip(qs, rows)) * sm_scale
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(col <= positions[i:i + g, None, None], s, kv_cache.NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(dtype)
        return jnp.einsum("ghk,gkr->ghr", p, rows[0], **f32)[..., :DC]

    o_lat = jnp.concatenate([one_group(i) for i in range(0, s_n, g)])
    return jnp.einsum("shc,chv->shv", o_lat.astype(dtype), w_uv, **f32).astype(dtype)


def form_chunk_attention(q_nope, q_pe, rows, w_uk, w_uv, ctx_rows, *, ctx_len, sm_scale):
    """``kv_cache.latent_chunk_attention`` over a row stored in any of
    ``FORMS``: ``rows`` and ``ctx_rows`` one ``[n, width]`` array per stored array."""
    import jax
    import jax.numpy as jnp

    from tensorflow_examples_tpu.serving import kv_cache

    t_n, h, _ = q_nope.shape
    dtype = q_nope.dtype
    f32 = dict(preferred_element_type=jnp.float32)
    q_pos = ctx_len + jnp.arange(t_n)
    c_n = ctx_rows[0].shape[0]
    pieces = [(ctx_rows, jnp.broadcast_to(jnp.arange(c_n) < ctx_len, (t_n, c_n))),
              (rows, q_pos[:, None] >= q_pos[None, :])]
    g = kv_cache.latent_head_group(h, t_n, c_n + t_n)
    widths = tuple(r.shape[-1] for r in rows)

    def one_group(args):
        qn, qp, uk, uv = args  # [G,T,dn] [G,T,dr] [G,dc,dn] [G,dc,dv]
        q_lat = jnp.einsum("gtn,gcn->gtc", qn, uk, **f32).astype(dtype)
        qs = _as_stored(q_lat, qp, widths)
        prob = jax.nn.softmax(jnp.concatenate([
            jnp.where(ok[None], sum(jnp.einsum("gtr,kr->gtk", q, kx, **f32)
                                    for q, kx in zip(qs, kxs)) * sm_scale, kv_cache.NEG_INF)
            for kxs, ok in pieces], axis=-1), axis=-1)
        out = (jnp.einsum("gtk,kc->gtc", prob[..., :c_n].astype(dtype), ctx_rows[0][:, :DC], **f32)
               + jnp.einsum("gtk,kc->gtc", prob[..., c_n:].astype(dtype), rows[0][:, :DC], **f32))
        return jnp.einsum("gtc,gcv->gtv", out.astype(dtype), uv, **f32)

    by_group = lambda x: jnp.moveaxis(x, 1, 0).reshape(h // g, g, x.shape[0], x.shape[2])  # noqa: E731
    out = jax.lax.map(one_group, (by_group(q_nope), by_group(q_pe), by_group(w_uk), by_group(w_uv)))
    return jnp.moveaxis(out.reshape(h, t_n, -1), 0, 1).astype(dtype)


def pool_programs(widths, layers, scale):
    """The two programs of the pool half over ``layers`` donated pools a
    stored array: a decode step (row scatter, then attention through the
    tables, a layer after the other as the engine's forward runs them) and
    a prompt tail (attention over the table's gathered rows, then the
    tail's rows scattered, as ``_forward_extend`` orders them)."""
    import jax
    import jax.numpy as jnp

    def written(pools, layer, index, row):
        return tuple((*arrs[:layer], arrs[layer].at[index].set(x), *arrs[layer + 1:])
                     for arrs, x in zip(pools, row))

    def decode(pools, q_nope, q_pe, c_kv, k_pe, tables, positions, w_uk, w_uv):
        bs = pools[0][0].shape[1]
        index = (jnp.take_along_axis(tables, (positions // bs)[:, None], axis=1)[:, 0], positions % bs)
        row, out = _as_stored(c_kv, k_pe, widths), 0
        for layer in range(layers):
            pools = written(pools, layer, index, row)
            o = form_decode_attention(q_nope, q_pe, tuple(a[layer] for a in pools), positions, tables,
                                      w_uk, w_uv, sm_scale=scale)
            q_nope = q_nope + o[..., :DN] * 0.01  # the next layer waits for this one
            out = out + o
        return pools, out

    def tail(pools, q_nope, q_pe, c_kv, k_pe, ctx_table, tail_ids, ctx_len, w_uk, w_uv):
        bs = pools[0][0].shape[1]
        row, out = _as_stored(c_kv, k_pe, widths), 0
        for layer in range(layers):
            ctx = tuple(a[layer][ctx_table].reshape(-1, a[layer].shape[-1]) for a in pools)
            o = form_chunk_attention(q_nope, q_pe, row, w_uk, w_uv, ctx, ctx_len=ctx_len, sm_scale=scale)
            q_nope = q_nope + o[..., :DN] * 0.01
            out = out + o
        for layer in range(layers):
            pools = written(pools, layer, (tail_ids,), tuple(x.reshape(-1, bs, x.shape[-1]) for x in row))
        return pools, out

    return jax.jit(decode, donate_argnums=(0,)), jax.jit(tail, donate_argnums=(0,))


def _time_donated(fn, pools, args, reps: int):
    """Median seconds of ``fn(pools, *args)``, the pools donated and
    handed on from call to call; also the last call's second result."""
    import jax

    pools, out = jax.block_until_ready(fn(pools, *args))  # compile + warm
    secs = []
    for _ in range(reps):
        t0 = time.perf_counter()
        pools, out = jax.block_until_ready(fn(pools, *args))
        secs.append(time.perf_counter() - t0)
    return statistics.median(secs), out


def pool_half(args, rnd, key, scale) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    slots, bs, n_blocks, layers, rung, t_n = (
        (4, 16, 65, 2, 256, 32) if args.small else (32, 16, POOL_BLOCKS, POOL_LAYERS, 32768, 256))
    nb = rung // bs
    w_uk, w_uv = rnd(DC, H, DN) * 0.05, rnd(DC, H, DV) * 0.05
    tables = jax.random.randint(next(key), (slots, nb), 1, n_blocks, jnp.int32)
    positions = jnp.full((slots,), rung - 3, jnp.int32)
    dec = (rnd(slots, H, DN), rnd(slots, H, DR), rnd(slots, DC), rnd(slots, DR), tables, positions, w_uk, w_uv)
    tail_ids = jnp.arange(1, 1 + t_n // bs, dtype=jnp.int32)
    ext = (rnd(t_n, H, DN), rnd(t_n, H, DR), rnd(t_n, DC), rnd(t_n, DR), tables[0], tail_ids,
           jnp.int32(rung - t_n - 5), w_uk, w_uv)
    lat, pe = rnd(n_blocks, bs, DC), rnd(n_blocks, bs, DR)
    first = {}
    for form, widths in FORMS.items():
        decode, tail = pool_programs(widths, layers, scale)
        for case, fn, operands in (("pool_decode", decode, dec), ("pool_tail", tail, ext)):
            # Every layer's pool holds the same rows in every form, pad columns zero.
            pools = tuple(tuple(x + 0 for _ in range(layers)) for x in _as_stored(lat, pe, widths))
            pool_bytes = sum(a.nbytes for arrs in pools for a in arrs)
            sec, out = _time_donated(fn, pools, operands, args.reps)
            out = np.asarray(out, np.float32)
            print(json.dumps({
                "case": case, "form": form, "layers": layers, "blocks": n_blocks, "slots": slots,
                "rung": rung, "queries": slots if case == "pool_decode" else t_n,
                "pool_gb": pool_bytes / 1e9, "ms": 1e3 * sec, "ms_a_layer": 1e3 * sec / layers,
                "max_abs_from_first_form": float(np.abs(out - first.setdefault(case, out)).max()),
            }), flush=True)


def engine_half(args) -> None:
    """The engine's own programs at the shareddoc cell's size: seeded
    weights, the cell's ``serve_config``, tables of random blocks (what a
    program costs does not depend on which blocks its tables name)."""
    import jax
    import numpy as np

    from tensorflow_examples_tpu.serving.engine import InferenceEngine, ServeConfig
    from tensorflow_examples_tpu.workloads import glm4_moe_lite as workload

    if args.small:
        pcfg = workload.Glm4MoeLiteServeConfig(
            hidden_size=64, num_attention_heads=4, q_lora_rank=24, qk_nope_head_dim=16, v_head_dim=16,
            intermediate_size=96, moe_intermediate_size=32, n_routed_experts=8, num_experts_per_tok=2,
            vocab_size=128, num_hidden_layers=2, seq_len=256, param_dtype="float32")
        serve = dict(max_slots=4, kv_block_size=16, kv_blocks=65, prefill_chunk_tokens=32,
                     prefill_bucket_floor=32, kv_bucket_floor=256)
        t_n = 32
    else:
        pcfg = workload.Glm4MoeLiteServeConfig()
        with open(os.path.join(ROOT, "benchmark", "cells", "glm-4.7-flash.serve-shareddoc.json")) as f:
            serve = json.load(f)["serve_config"]
        t_n = 256
    params = jax.jit(workload.make_task(pcfg).init_fn)(jax.random.PRNGKey(0))["params"]
    engine = InferenceEngine(workload.model_config(pcfg), params, cfg=ServeConfig(**serve))
    pool, s, bs = engine.pool, engine.cfg.max_slots, engine.cfg.kv_block_size
    rng = np.random.default_rng(0)
    kb = engine.kv_ladder[-1]
    tables = rng.integers(1, pool.num_blocks, (s, kb // bs)).astype(np.int32)
    tokens = rng.integers(0, pcfg.vocab_size, (s,)).astype(np.int32)
    zeros = np.zeros((s,), np.int32)
    launches = {
        f"jit_paged_decode_impl_K{kb}": ("decode", engine._decode_fns[kb], engine._specs["decode", kb], (
            tokens, np.full((s,), kb - 3, np.int32), [tables], zeros, zeros.astype(np.float32), zeros)),
        f"jit_extend_impl_T{t_n}": ("prefill", engine._extend_fns[t_n], engine._specs["extend", t_n], (
            [tables[0]], [np.arange(1, 1 + t_n // bs, dtype=np.int32)],
            rng.integers(0, pcfg.vocab_size, (1, t_n)).astype(np.int32), kb - t_n - 5, t_n - 3,
            0, kb - 8, 0.0, 0)),
    }
    for name, (kind, fn, spec, operands) in launches.items():
        sec = _time(lambda block, kind=kind, fn=fn: engine._run_compiled(kind, fn, block),
                    (engine._put(spec, operands),), args.reps)
        print(json.dumps({
            "case": "engine", "program": name, "rows": list(pool.rows), "blocks": pool.num_blocks,
            "pool_gb": pool.bytes_per_block() * pool.num_blocks / 1e9, "ms": 1e3 * sec,
        }), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--device", default="tpu")
    ap.add_argument("--small", action="store_true", help="toy sizes (the CPU rehearsal)")
    ap.add_argument("--only", default="chunk,decode,pool,engine", help="the halves to run, of chunk, decode, pool, engine")
    ap.add_argument("--slot-groups", default="", help="decode: also these slot groups, e.g. 4,8,32")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from tensorflow_examples_tpu.core import device
    from tensorflow_examples_tpu.serving import kv_cache

    device.require_device(args.device)
    halves = set(args.only.split(","))
    if halves - {"chunk", "decode", "pool", "engine"}:
        ap.error(f"--only {args.only}: the halves are chunk, decode, pool, engine")
    dtype = jnp.bfloat16
    key = iter(jax.random.split(jax.random.PRNGKey(0), 64))
    rnd = lambda *shape: jax.random.normal(next(key), shape, jnp.float32).astype(dtype)  # noqa: E731
    w_uk, w_uv = rnd(DC, H, DN) * 0.05, rnd(DC, H, DV) * 0.05
    scale = (DN + DR) ** -0.5
    contexts = [256] if args.small else [8192, 16384, 30720, 32768]
    chunks = [64, 32] if args.small else [512, 128]

    for t_n in (chunks if "chunk" in halves else []):
        q_nope, q_pe, rows = rnd(t_n, H, DN), rnd(t_n, H, DR), rnd(t_n, DC + DR)
        for c in contexts:
            ctx = rnd(c, DC + DR)
            group = kv_cache.latent_head_group(H, t_n, c + t_n)
            forms = {"expanded": expanded_chunk_attention,
                     "absorbed": kv_cache.latent_chunk_attention}
            for form, attention in forms.items():
                fn = jax.jit(lambda qn, qp, r, cx, uk, uv, attention=attention: attention(
                    qn, qp, r, uk, uv, cx, ctx_len=cx.shape[0] - 7, sm_scale=scale))
                sec = _time(fn, (q_nope, q_pe, rows, ctx, w_uk, w_uv), args.reps)
                print(json.dumps({"case": "chunk", "queries": t_n, "context": c, "form": form,
                                  "head_group": group, "ms": 1e3 * sec}), flush=True)

    # Decode: 32 slots, every slot's table at the rung's width.
    slots, bs = (4, 16) if args.small else (32, 16)
    rungs = [256] if args.small else [8192, 16384, 32768]
    if "decode" not in halves:
        rungs = []
    groups = [int(g) for g in args.slot_groups.split(",") if g]
    q_nope, q_pe = rnd(slots, H, DN), rnd(slots, H, DR)
    for rung in rungs:
        nb = rung // bs
        n_blocks = slots * nb // 4 + 1  # slots share documents: a pool smaller than slots x rung
        pool = rnd(n_blocks, bs, DC + DR)
        tables = jax.random.randint(next(key), (slots, nb), 1, n_blocks, jnp.int32)
        positions = jnp.full((slots,), rung - 3, jnp.int32)

        one = jax.jit(lambda qn, qp, blocks, pos, tab, uk, uv: kv_cache.latent_decode_attention(
            qn, qp, blocks, pos, tab, uk, uv, sm_scale=scale))

        def two_arrays(qn, qp, c_blocks, pe_blocks, pos, tab, uk, uv):
            """The same step with the row kept as two arrays."""
            f32 = dict(preferred_element_type=jnp.float32)
            q_lat = jnp.einsum("shn,chn->shc", qn, uk, **f32).astype(qn.dtype)
            c = c_blocks[tab].reshape(slots, -1, DC)
            pe = pe_blocks[tab].reshape(slots, -1, DR)
            s = (jnp.einsum("shc,skc->shk", q_lat, c, **f32)
                 + jnp.einsum("shr,skr->shk", qp, pe, **f32)) * scale
            col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
            p = jax.nn.softmax(jnp.where(col <= pos[:, None, None], s, -1e30), axis=-1).astype(c.dtype)
            o = jnp.einsum("shk,skc->shc", p, c, **f32).astype(qn.dtype)
            return jnp.einsum("shc,chv->shv", o, uv, **f32).astype(qn.dtype)

        sec = _time(one, (q_nope, q_pe, pool, positions, tables, w_uk, w_uv), args.reps)
        row_bytes = slots * rung * (DC + DR) * 2
        print(json.dumps({"case": "decode", "slots": slots, "rung": rung, "row": "one array of 576",
                          "ms": 1e3 * sec, "rows_gb_per_s": row_bytes / sec / 1e9}), flush=True)
        gather_bytes = kv_cache.LATENT_GATHER_BYTES
        for g in groups:  # the engine's grouping, with the limit set to g slots' rows
            kv_cache.LATENT_GATHER_BYTES = g * rung * (DC + DR) * 2
            fn = jax.jit(lambda qn, qp, blocks, pos, tab, uk, uv: kv_cache.latent_decode_attention(
                qn, qp, blocks, pos, tab, uk, uv, sm_scale=scale))
            sec = _time(fn, (q_nope, q_pe, pool, positions, tables, w_uk, w_uv), args.reps)
            kv_cache.LATENT_GATHER_BYTES = gather_bytes
            print(json.dumps({"case": "decode", "slots": slots, "rung": rung, "slot_group": g,
                              "row": "one array of 576", "ms": 1e3 * sec,
                              "rows_gb_per_s": row_bytes / sec / 1e9}), flush=True)
        sec = _time(jax.jit(two_arrays), (q_nope, q_pe, pool[..., :DC], pool[..., DC:], positions,
                                          tables, w_uk, w_uv), args.reps)
        print(json.dumps({"case": "decode", "slots": slots, "rung": rung, "row": "two arrays, 512 and 64",
                          "ms": 1e3 * sec, "rows_gb_per_s": row_bytes / sec / 1e9}), flush=True)
    if "pool" in halves:
        pool_half(args, rnd, key, scale)
    if "engine" in halves:
        engine_half(args)
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {"platform": d.platform, "kind": d.device_kind}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
