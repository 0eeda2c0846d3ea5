#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python3 chip_smoke.py

Drives the main path once, through the CLIs a user starts, at the full
width and depth of GPT-2 124M (``Gpt2Config`` defaults: 12 x 768, 12
heads, vocab 50257, seq 1024, global batch 16, bf16 compute, Pallas
flash attention and fused cross-entropy): a few training steps and a
checkpoint, then a server restored from that checkpoint with the paged
KV pool and the prefix cache — once with the XLA decode attention and
once with the fused Pallas paged-decode kernel — answering /generate
over HTTP and draining on SIGTERM. Weights are random, from the seed.

This parent never imports JAX. A chip belongs to one process at a
time, so the phases run as SEQUENTIAL children — device check, train,
serve (xla), serve (paged_flash) — and each child is the one process
that holds the chip while it lives. Every child uses every chip the
process can see (the default ``--mesh_data=-1``): on one chip that is
one device, on a four-chip host training is 4-way data-parallel and
the server — whose workdir layout collapses to a single device — says
so in its ``placement:`` line.

Every phase prints its name, wall seconds and verdict. The first
failing phase ends the run non-zero with the tail of the child's log;
no failure is caught and carried past. With no TPU (or outside the
checkout) it exits non-zero within seconds and prints no result. On
success the LAST stdout line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
with the device as JAX reports it; the line before it carries the
details (versions, losses, request and token counts, prefix hits,
recompiles, warm-up seconds, compile-cache entries added per phase).

Logs and the summary land in ``chiprun_out/chip_smoke/``; checkpoints
go to a temporary directory that is removed at the end. The rehearsal
at toy size on the CPU is tests/test_tpu_device_rules.py, which imports the
phase functions below — there is no switch for it here.
"""

import concurrent.futures
import hashlib
import json
import os
import random
import re
import shutil
import signal
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
DEADLINE_S = 1150.0  # the contract is 1200 s, compilation included
TRAIN_STEPS = 12
# What each phase must have traced, by the name core/device.py logs.
TRAIN_KERNELS = ("flash_attention", "fused_cross_entropy")

_DEVICE_CHILD = """
import importlib.metadata as md, json
import jax, jaxlib
from tensorflow_examples_tpu.core.device import enable_compile_cache
d = jax.devices()
def v(p):
    try: return md.version(p)
    except md.PackageNotFoundError: return None
print("DEVICE " + json.dumps({
    "platform": d[0].platform, "kind": d[0].device_kind, "count": len(d),
    "jax": jax.__version__, "jaxlib": jaxlib.__version__,
    "libtpu": v("libtpu"), "compile_cache": enable_compile_cache(),
}))
"""


class PhaseFailed(Exception):
    """A phase did not do what it must; ``log`` is the child's log."""

    def __init__(self, msg: str, log: str | None = None):
        super().__init__(msg)
        self.log = log


def _tail(path: str | None, n: int = 40) -> str:
    if not path or not os.path.exists(path):
        return ""
    with open(path, errors="replace") as f:
        return "".join(f.readlines()[-n:])


def _read(path: str, last_bytes: int | None = None) -> str:
    """The file's text; only its end when ``last_bytes`` is given."""
    with open(path, "rb") as f:
        if last_bytes is not None:
            f.seek(max(os.path.getsize(path) - last_bytes, 0))
        return f.read().decode(errors="replace")


def _cache_entries(cache_dir: str | None) -> int:
    if not cache_dir or not os.path.isdir(cache_dir):
        return 0
    return sum(1 for n in os.listdir(cache_dir) if n.endswith("-cache"))


def _child_env() -> dict:
    # JAX_LOG_COMPILES is JAX's own switch: every XLA compilation is a
    # log line, which is how serve_phase sees compilations that the
    # engine's shape-signature sentinel cannot.
    return {**os.environ, "JAX_LOG_COMPILES": "1"}


def _spawn(argv, log_path):
    log = open(log_path, "w")
    try:
        return subprocess.Popen(
            argv, cwd=HERE, stdout=log, stderr=subprocess.STDOUT,
            env=_child_env(), start_new_session=True,
        )
    finally:
        log.close()  # the child holds its own descriptor


def _stop(proc) -> None:
    """Kill the child's whole process group, whatever state it is in."""
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait(timeout=30)


def _require_kernels(text: str, kernels, device: str, log_path: str):
    mode = (
        "compiled by Mosaic on tpu" if device == "tpu"
        else f"interpret mode on {device}"
    )
    for k in kernels:
        if f"pallas kernel {k}: {mode}" not in text:
            raise PhaseFailed(
                f"log never says 'pallas kernel {k}: {mode}' — the "
                "kernel did not run the way this device requires",
                log_path,
            )


def _device_line(text: str, device: str, log_path: str) -> dict:
    m = re.search(
        r'device: platform=(\S+) device_kind=("(?:[^"\\]|\\.)*") '
        r"local_devices=(\d+) global_devices=(\d+) process_index=(\d+)",
        text,
    )
    if not m:
        raise PhaseFailed("child never logged its device line", log_path)
    if m.group(1) != device:
        raise PhaseFailed(
            f"child ran on platform {m.group(1)!r}, not {device!r}",
            log_path,
        )
    return {
        "platform": m.group(1), "kind": json.loads(m.group(2)),
        "count": int(m.group(4)),
    }


# ------------------------------------------------------------------ phases


def device_phase(out_dir: str, timeout: float) -> dict:
    """What JAX finds, seen from a child (the parent stays off JAX)."""
    try:
        r = subprocess.run(
            [sys.executable, "-c", _DEVICE_CHILD], cwd=HERE, timeout=timeout,
            capture_output=True, text=True,
        )
    except subprocess.TimeoutExpired:
        raise PhaseFailed(f"device check still running after {timeout:.0f}s")
    with open(os.path.join(out_dir, "device.log"), "w") as f:
        f.write(r.stdout + r.stderr)
    line = next(
        (l for l in r.stdout.splitlines() if l.startswith("DEVICE ")), None
    )
    if r.returncode != 0 or line is None:
        raise PhaseFailed(
            f"device check exited {r.returncode} without a device",
            os.path.join(out_dir, "device.log"),
        )
    return json.loads(line[len("DEVICE "):])


def train_phase(workdir: str, out_dir: str, *, device: str, flags=(),
                steps: int = TRAIN_STEPS, kernels=TRAIN_KERNELS,
                timeout: float = 600.0) -> dict:
    """``examples/gpt2/train.py`` for ``steps`` steps and a checkpoint."""
    log_path = os.path.join(out_dir, "train.log")
    proc = _spawn(
        [
            sys.executable, os.path.join("examples", "gpt2", "train.py"),
            f"--device={device}", f"--workdir={workdir}",
            f"--train_steps={steps}", "--warmup_steps=2", "--log_every=2",
            f"--checkpoint_every={steps}", "--eval_every=0",
            "--telemetry_sinks=jsonl,console", *flags,
        ],
        log_path,
    )
    try:
        rc = proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        raise PhaseFailed(
            f"train.py still running after {timeout:.0f}s", log_path
        )
    finally:
        _stop(proc)
    if rc != 0:
        raise PhaseFailed(f"train.py exited {rc}", log_path)
    text = _read(log_path)
    dev = _device_line(text, device, log_path)
    _require_kernels(text, kernels, device, log_path)
    losses = []
    with open(os.path.join(workdir, "telemetry", "metrics.jsonl")) as f:
        for line in f:
            rec = json.loads(line)
            if rec.get("kind") == "window":
                losses.append(float(rec["metrics"]["train/loss"]))
    if len(losses) < 2:
        raise PhaseFailed(f"only {len(losses)} loss windows", log_path)
    if not all(l == l and abs(l) != float("inf") for l in losses):
        raise PhaseFailed(f"non-finite loss in {losses}", log_path)
    if not losses[-1] < losses[0]:
        raise PhaseFailed(
            f"loss did not fall on the learnable synthetic data: {losses}",
            log_path,
        )
    ckpt = os.path.join(workdir, "checkpoints", str(steps))
    if not os.path.isdir(ckpt):
        raise PhaseFailed(f"no checkpoint at {ckpt}", log_path)
    m = re.search(r"compiled train_step \(#1, ([\d.]+)s\)", text)
    return {
        "device": dev, "steps": steps,
        "loss_first": round(losses[0], 4), "loss_last": round(losses[-1], 4),
        "train_step_compile_s": float(m.group(1)) if m else None,
    }


def make_traffic(vocab_size: int, *, seed: int = 0) -> dict:
    """Seeded token-id prompts that reach what a CPU run never proved:
    several slots filled at once with mixed lengths, a generation that
    crosses the 64 -> 128 KV bucket, a 48-token block-aligned prefix
    shared by two prompts (so the second runs the extend rung), and one
    greedy prompt asked three times. Lengths fit max_len >= 128."""
    rng = random.Random(seed)
    toks = lambda n: [rng.randrange(vocab_size) for _ in range(n)]
    prefix = toks(48)
    return {
        "prefix_first": {"prompt": prefix + toks(5), "max_new_tokens": 8},
        "concurrent": [
            {"prompt": prefix + toks(9), "max_new_tokens": 8},
            {"prompt": toks(5), "max_new_tokens": 12},
            {"prompt": toks(17), "max_new_tokens": 16},
            # 40 + 48 = 88 positions: decodes through K=64 into K=128.
            {"prompt": toks(40), "max_new_tokens": 48},
            {"prompt": toks(70), "max_new_tokens": 10},
            {"prompt": toks(100), "max_new_tokens": 8, "temperature": 0.8,
             "top_k": 40, "seed": 7},
        ],
        "repeat": {"prompt": toks(33), "max_new_tokens": 16},
    }


def _post(url: str, body: dict, timeout: float) -> dict:
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return json.loads(r.read())
    except urllib.error.HTTPError as e:
        raise PhaseFailed(
            f"POST {url} -> {e.code}: {e.read()[:300]!r}"
        ) from e


def _check_reply(body: dict, reply: dict, vocab_size: int) -> list:
    toks = reply.get("tokens")
    want = body["max_new_tokens"]
    if (
        not isinstance(toks, list) or len(toks) != want
        or reply.get("prompt_len") != len(body["prompt"])
        or reply.get("truncated")
        or not all(isinstance(t, int) and 0 <= t < vocab_size for t in toks)
    ):
        raise PhaseFailed(
            f"bad /generate reply for a {len(body['prompt'])}-token "
            f"prompt wanting {want} tokens: {json.dumps(reply)[:400]}"
        )
    return toks


def serve_phase(workdir: str, out_dir: str, *, device: str, attention: str,
                vocab_size: int, flags=(), start_timeout: float = 900.0,
                request_timeout: float = 180.0) -> dict:
    """``examples/gpt2/serve.py`` on the paged pool: restore, warm,
    answer the traffic of ``make_traffic``, drain on SIGTERM."""
    name = f"serve_{attention}"
    log_path = os.path.join(out_dir, f"{name}.log")
    argv = [
        sys.executable, os.path.join("examples", "gpt2", "serve.py"),
        f"--device={device}", f"--workdir={workdir}", "--port=0",
        "--kv_block_size=16", "--stats_every=0",
        f"--decode_attention={attention}", *flags,
    ]
    proc = _spawn(argv, log_path)
    try:
        t0 = time.monotonic()
        port = None
        while port is None:
            m = re.search(r"listening on :(\d+)", _read(log_path, 65536))
            if m:
                port = int(m.group(1))
            elif proc.poll() is not None:
                raise PhaseFailed(
                    f"serve.py exited {proc.returncode} before listening",
                    log_path,
                )
            elif time.monotonic() - t0 > start_timeout:
                raise PhaseFailed(
                    f"serve.py not listening after {start_timeout:.0f}s",
                    log_path,
                )
            else:
                time.sleep(0.5)
        start_s = time.monotonic() - t0
        warm_mark = len(_read(log_path))
        url = f"http://127.0.0.1:{port}"
        traffic = make_traffic(vocab_size)

        def ask(body):
            return _check_reply(
                body, _post(url + "/generate", body, request_timeout),
                vocab_size,
            )

        try:
            # 1. The prefix owner, alone, so its blocks are published
            #    before the prompt that shares them is admitted.
            first = ask(traffic["prefix_first"])
            # 2. Mixed lengths, concurrently: several of the 8 slots.
            with concurrent.futures.ThreadPoolExecutor(8) as pool:
                mixed = list(pool.map(ask, traffic["concurrent"]))
            # 3. One greedy prompt three times: cold prefill, then two
            #    prefix-cache hits through the extend rung.
            reps = [ask(traffic["repeat"]) for _ in range(3)]
        except PhaseFailed as e:
            e.log = log_path
            raise
        # Every reply passed _check_reply, or the phase has failed.
        replies = [first, *mixed, *reps]
        greedy = [first] + [  # every temperature-0 reply, in fixed order
            t for b, t in zip(traffic["concurrent"], mixed)
            if not b.get("temperature")
        ] + reps
        if reps[1] != reps[2]:
            raise PhaseFailed(
                "the same greedy prompt through the same path gave "
                f"different tokens: {reps[1]} vs {reps[2]}", log_path,
            )
        with urllib.request.urlopen(url + "/health", timeout=30) as r:
            health = json.loads(r.read())
        if not health.get("warmed") or health.get(
            "post_warmup_recompiles"
        ) != 0:
            raise PhaseFailed(f"/health: {json.dumps(health)[:300]}", log_path)
        if not health.get("prefix_hit_rate", 0) > 0:
            raise PhaseFailed(
                "prefix_hit_rate is 0: the extend rung never ran", log_path
            )
        # 4. SIGTERM: drain and exit 0.
        t1 = time.monotonic()
        proc.send_signal(signal.SIGTERM)
        try:
            rc = proc.wait(timeout=120)
        except subprocess.TimeoutExpired:
            raise PhaseFailed("no exit 120s after SIGTERM", log_path)
        if rc != 0:
            raise PhaseFailed(f"serve.py exited {rc} on SIGTERM", log_path)
        drain_s = time.monotonic() - t1
    finally:
        _stop(proc)
    with open(os.path.join(out_dir, f"{name}_greedy.json"), "w") as f:
        json.dump(greedy, f)
    text = _read(log_path)
    dev = _device_line(text, device, log_path)
    if attention == "paged_flash":
        _require_kernels(text, ("paged_decode_attention",), device, log_path)
    warm = re.search(
        r"warm: (\d+) programs in ([\d.]+)s; serving from step (\d+)", text
    )
    place = re.search(r"placement: (params on .*device\(s\))", text)
    if not warm or not place:
        raise PhaseFailed("no warm:/placement: start-up lines", log_path)
    # XLA compilations AFTER the ladder was warm, by jitted-function
    # name. The engine's step programs are functools.partial objects
    # (JAX names them <unknown>): one of those compiling again is the
    # recompile the zero-recompile contract forbids, whatever the
    # signature sentinel says. Small eager host-side ops (the first
    # request's fold_in, ...) are reported, not failed.
    late: dict = {}
    last = None
    for m in re.finditer(
        r"Compiling jit\(([^)]*)\) with global shapes and types (.*)",
        text[warm_mark:],
    ):
        # absl and JAX's own handler can both print the same record.
        if m.group(0) != last:
            late[m.group(1)] = late.get(m.group(1), 0) + 1
        last = m.group(0)
    if late.get("<unknown>"):
        raise PhaseFailed(
            f"{late['<unknown>']} engine step program(s) compiled after "
            "warm-up", log_path,
        )
    return {
        "device": dev, "attention": attention,
        "restored_step": int(warm.group(3)),
        "programs": int(warm.group(1)), "warmup_s": float(warm.group(2)),
        "start_s": round(start_s, 1), "placement": place.group(1),
        "requests_sent": len(replies), "requests_ok": len(replies),
        "tokens": sum(map(len, replies)),
        "prefix_hit_rate": health["prefix_hit_rate"],
        "post_warmup_recompiles": health["post_warmup_recompiles"],
        "late_host_compiles": late,
        "cold_equals_hit": reps[0] == reps[1],
        # Equal digests mean two servers gave the same tokens for every
        # greedy request (xla vs paged_flash, one chip vs sharded).
        "greedy_digest": hashlib.sha1(
            json.dumps(greedy).encode()
        ).hexdigest()[:12],
        "drain_s": round(drain_s, 1),
    }


# -------------------------------------------------------------------- main


def main() -> int:
    t_start = time.monotonic()
    remaining = lambda: DEADLINE_S - (time.monotonic() - t_start)
    out_dir = os.path.join(HERE, "chiprun_out", "chip_smoke")
    os.makedirs(out_dir, exist_ok=True)
    work = tempfile.mkdtemp(prefix="chip_smoke_")
    summary: dict = {}
    cache_dir = None

    def phase(name, fn, *a, **kw):
        before = _cache_entries(cache_dir)
        t0 = time.monotonic()
        try:
            out = fn(*a, **kw)
        except PhaseFailed as e:
            print(f"chip_smoke: [{name}] FAIL "
                  f"{time.monotonic() - t0:.1f}s: {e}")
            tail = _tail(e.log)
            if tail:
                print(f"---- tail of {e.log} ----\n{tail}----")
            raise
        out["seconds"] = round(time.monotonic() - t0, 1)
        out["cache_entries_added"] = _cache_entries(cache_dir) - before
        print(f"chip_smoke: [{name}] PASS {out['seconds']}s "
              f"{json.dumps(out)}", flush=True)
        summary[name] = out
        return out

    try:
        if not os.path.isfile(
            os.path.join(HERE, "examples", "gpt2", "serve.py")
        ):
            print("chip_smoke: FAIL: examples/gpt2/serve.py is not next to "
                  "this script — run it from a checkout of the repository")
            return 1
        dev = phase("device", device_phase, out_dir, min(120.0, remaining()))
        if dev["platform"] != "tpu":
            print(f"chip_smoke: FAIL: JAX found platform "
                  f"{dev['platform']!r} ({dev['kind']}), not a TPU")
            return 1
        cache_dir = dev["compile_cache"]
        device = {k: dev[k] for k in ("platform", "kind", "count")}
        phase("train", train_phase, work, out_dir, device="tpu",
              timeout=min(600.0, remaining()))
        for attention in ("xla", "paged_flash"):
            phase(f"serve_{attention}", serve_phase, work, out_dir,
                  device="tpu", attention=attention, vocab_size=50257,
                  start_timeout=max(remaining() - 120.0, 1.0))
        for name in ("train", "serve_xla", "serve_paged_flash"):
            if summary[name]["device"] != device:
                print(f"chip_smoke: FAIL: {name} ran on "
                      f"{summary[name]['device']}, the device check saw "
                      f"{device}")
                return 1
            if name != "train" and summary[name]["restored_step"] != \
                    TRAIN_STEPS:
                print(f"chip_smoke: FAIL: {name} did not restore step "
                      f"{TRAIN_STEPS}")
                return 1
        summary["model"] = "gpt2_124m (Gpt2Config defaults, full depth)"
        summary["total_seconds"] = round(time.monotonic() - t_start, 1)
        with open(os.path.join(out_dir, "summary.json"), "w") as f:
            json.dump(summary, f, indent=1)
        print("chip_smoke: PASS " + json.dumps(summary))
        print(json.dumps({"ok": True, "device": device}))
        return 0
    except PhaseFailed:
        return 1
    finally:
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
