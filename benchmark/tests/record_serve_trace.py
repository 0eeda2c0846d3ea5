"""Records the small serving trace ``test_host_spans.py`` checks the
host-span reduction against: the program's own engine and batcher at a
toy width (``tiny.tiny_cell``), two requests of a dozen tokens, so a
prefill lands between decode steps. Run on the chip, once, by hand:

    python3 benchmark/tests/record_serve_trace.py <out_dir> [tokens a request, 12]

and copy ``<out_dir>/serve_small.xplane.pb`` to ``benchmark/tests/data/``.
The Python tracer is off (the file stays small); the program's
``span/`` events and PJRT's own host events are TraceMe's and stay. The
``/host:metadata`` plane — the programs' HLO protos, 0.8 MB that no
reduction reads — is cut out of the copy.
"""

import os
import shutil
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def _varint(data: bytes, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = data[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def without_plane(xspace: bytes, name: bytes) -> bytes:
    """The serialized ``XSpace`` without the plane called ``name``: its
    planes are the length-delimited field 1, a plane's name its field 2
    (protobuf's wire format, read by hand: no xplane_pb2 is installed)."""
    out, i = bytearray(), 0
    while i < len(xspace):
        start = i
        key, i = _varint(xspace, i)
        if key & 7 != 2:
            raise ValueError(f"XSpace field {key >> 3} is not length-delimited")
        size, i = _varint(xspace, i)
        body, i = xspace[i:i + size], i + size
        if key >> 3 == 1 and bytes([0x12, len(name)]) + name in body[:64]:
            continue
        out += xspace[start:i]
    return bytes(out)


def main(out_dir: str, tokens: int = 12) -> None:
    import jax

    from benchmark import record, spec, trace_reduce
    from benchmark.tests import tiny

    serve = spec.load_by_path(os.path.join(spec.HERE, "runners", "serve.py"))
    ctx = record.Context(
        cell=tiny.tiny_cell("closed"), seed=1, seconds=1.0, trace=True,
        t_start=time.perf_counter(), trace_dir=os.path.join(out_dir, "trace"),
        compiles=record.CompileLog.get(),
    )
    server = serve.Server(ctx)   # warm-up of every rung included
    try:
        ask = lambda n: server.handle({"prompt": list(range(1, n + 1)), "max_new_tokens": tokens})
        ask(9), ask(21)          # whatever compiles on a first request does so here
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        jax.profiler.start_trace(ctx.trace_dir, profiler_options=options)
        second = threading.Thread(target=ask, args=(20,))
        first = threading.Thread(target=ask, args=(10,))
        first.start()
        time.sleep(0.02)         # the second request's prefill lands between decode steps
        second.start()
        first.join(), second.join()
        time.sleep(0.02)
        jax.profiler.stop_trace()
    finally:
        server.close()
    with open(trace_reduce.find_xplane(ctx.trace_dir), "rb") as f:
        small = without_plane(f.read(), b"/host:metadata")
    with open(os.path.join(out_dir, "serve_small.xplane.pb"), "wb") as f:
        f.write(small)
    print(len(small), "bytes", jax.devices()[0].device_kind)


if __name__ == "__main__":
    main(sys.argv[1], *map(int, sys.argv[2:3]))
