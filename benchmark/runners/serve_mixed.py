"""Runner of the mixed-length serving cell: a model whose pool keeps
rows BY KIND (full layers and window layers with their own KV heads,
keys wider than values), so that short and long requests share one
queue and one decode step.

Everything is ``runners/serve_kinds.py``'s, imported: the server, the
window (``serve._window``), the traced slice's own counters, the check
that nothing leaked from either block-id space, and ``check_outputs`` —
classify log-probabilities from the prefill and extend programs and
every streamed token's from the decode program itself, against the
plain reference, rows within ``route_gap`` of a held expert's line
deciding nothing; the reference is found by the configuration's
``reference`` key and called the same way. What is this file's own:

* the window's counters the new readers read — the pool's bytes in use
  kind by kind (``serving/kv_sampled_bytes_kind_<kind>``) and the token
  rows a decode step's gather touches
  (``serving/decode_gathered_tokens``);
* ``run.model``: parameters, cache bytes and the operations of this
  model's block, from ``benchmark/roofline_mimo_v2.py``.

(The shared helper that ``serve.py``, ``serve_kinds.py`` and
``serve_latent.py`` want is a ``benchmark`` issue's: PERF.md §7.)
"""

from __future__ import annotations

import numpy as np

from benchmark import record, roofline_mimo_v2 as ops
from benchmark.runners import serve as base
from benchmark.runners import serve_kinds

GATHERED = "serving/decode_gathered_tokens"
KIND_BYTES = "serving/kv_sampled_bytes_kind_"


class Server(serve_kinds.Server):
    def since(self, mark: dict) -> tuple[dict, dict]:
        counters, hists = super().since(mark)
        now = self.registry.counter_values()
        for name in now:
            if name == GATHERED or name.startswith(KIND_BYTES):
                counters[name] = int(now[name]) - int(mark["counters"].get(name, 0))
        return counters, hists


def run(ctx: record.Context) -> record.Run:
    server = Server(ctx)
    sampler = serve_kinds.SliceCounters(server.registry).start() \
        if ctx.trace and not ctx.rates else None
    try:
        correct, detail = serve_kinds.check_outputs(ctx, server)
        if ctx.rates:
            base.sweep(ctx, server)
            return record.Run(cell=ctx.cell)
        run_ = base._window(ctx, server, correct, detail)
        whole = serve_kinds.free_lists_whole(server)
        run_.correct_detail["free_lists_whole_after_window"] = whole
        run_.correct = bool(run_.correct and whole)
        run_.model.update(model_numbers(ctx, server))
        # The reference is the benchmark's own work, not the deployment's set-up.
        run_.setup_s -= detail["reference_s"]
        if sampler is not None:
            sampler.stop()
            run_.model["slice"] = run_.notes["slice"] = \
                serve_kinds.SliceCounters.read(ctx.trace_dir)
        run_.notes["pool_kinds"] = pool_kinds(server)
        run_.notes["kind_plan"] = kind_plans()
        return run_
    finally:
        if sampler is not None:
            sampler.stop()
        server.close()


def model_numbers(ctx, server) -> dict:
    import jax

    s = ops.sizes(ctx.cell.config)
    item = int(np.dtype(server.params["wte"]["embedding"].dtype).itemsize)
    return {
        "sizes": s, "param_itemsize": item,
        "n_params": int(sum(x.size for x in jax.tree.leaves(server.params))),
        # every layer keeping every token at ONE (the widest) row shape: a pool that knew no kinds
        "kv_bytes_token": ops.one_shape_token_bytes(s, item),
        "layers": len(s["kinds"]), "expert_layers": ops.expert_layers(s),
    }


def pool_kinds(server) -> list:
    """Per kind of the pool: its name, row widths as stored, physical
    blocks and bytes (a note on the run's earlier line)."""
    pool = server.engine.pool
    return [
        {"kind": pool.kind_name(k), "rows": list(pool.kind_rows[k] or ()),
         "blocks": pool.kind_blocks(k), "bytes": pool.kind_blocks(k) * pool.bytes_per_block(k)}
        for k in range(len(pool.kinds))
    ]


def kind_plans() -> list:
    """The ``span/kind_plan`` records the engine made as it traced its
    programs (one a program family and rung: each kind's window, KV
    heads, stored K and V widths, sink, blocks and table columns), for
    the run's earlier line."""
    from tensorflow_examples_tpu.telemetry import spans

    return [e["args"] for e in spans.default_tracer().events() if e["name"] == "kind_plan"]
