"""Step 0 of a latent-attention change: the two attention forms over
latent rows, timed on the chip at the published widths.

    chiprun -- python3 tools/mla_forms_bench.py            # the table of PERF.md §6 (PR 32)

For a prompt chunk: ``expanded`` (K and V of every head made from the
rows, then ordinary attention: the form as published, kept here alone)
against ``absorbed`` (``kv_cache.latent_chunk_attention``, what the
engine runs) at a chunk of 512 queries and at a tail of 128, over 8k,
16k, 30k and 32k cached rows (the extend program always
gathers a slot's whole table, 32,768 columns, and masks to the true
context). For a decode step (``latent_decode_attention``): 32 slots
through block tables at the decode rungs, with the row as one array of
576 values and as two (512 and 64). One JSON line per case; the times
are the median of ``--reps`` calls that end in ``block_until_ready``.
Fails at once off the TPU: a time from another platform is not one.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--device", default="tpu")
    ap.add_argument("--small", action="store_true", help="toy sizes (the CPU rehearsal)")
    ap.add_argument("--only", default="", help="chunk | decode: that half alone")
    ap.add_argument("--slot-groups", default="", help="decode: also these slot groups, e.g. 4,8,32")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from tensorflow_examples_tpu.core import device
    from tensorflow_examples_tpu.serving import kv_cache

    device.require_device(args.device)
    dtype = jnp.bfloat16
    key = iter(jax.random.split(jax.random.PRNGKey(0), 64))
    rnd = lambda *shape: jax.random.normal(next(key), shape, jnp.float32).astype(dtype)  # noqa: E731
    w_uk, w_uv = rnd(DC, H, DN) * 0.05, rnd(DC, H, DV) * 0.05
    scale = (DN + DR) ** -0.5
    contexts = [256] if args.small else [8192, 16384, 30720, 32768]
    chunks = [64, 32] if args.small else [512, 128]

    for t_n in ([] if args.only == "decode" else chunks):
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
    if args.only == "chunk":
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
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {"platform": d.platform, "kind": d.device_kind}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
