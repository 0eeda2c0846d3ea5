"""The one general traffic generator: a mix file of parameters in, a
schedule of requests out, and the two drivers that send it.

A corrected copy of ``tools/serve_bench.py``'s ``make_traffic_schedule``
and ``drive_open_loop`` (seeded arrivals and lognormal lengths were
sound there). What differs:

* a request is timed from the instant it was DUE, not from when the
  driver got round to it, and how late the driver ran is reported;
* output lengths are drawn, not one fixed number, and prompts can share
  seeded prefixes (system prompts, documents);
* every seed gets the SAME set of lengths and gaps, in another order:
  sizes are the distribution's quantiles at (i + 1/2)/n, not samples,
  so the seed changes which request comes when and what its tokens
  are, never how much work a run holds.

A new mix is a new file under ``benchmark/traffic/``; no code.
"""

from __future__ import annotations

import collections
import math
import statistics
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

_NORMAL = statistics.NormalDist()


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def quantile_set(dist: dict, n: int) -> list[int]:
    """n whole numbers: the distribution's quantiles at (i + 1/2)/n,
    clipped to [min, max]. ``dist``: {"dist": "lognormal", "median",
    "sigma", "min", "max"} | {"dist": "uniform", "min", "max"} |
    {"dist": "fixed", "value"}."""
    kind = dist["dist"]
    if kind == "fixed":
        return [int(dist["value"])] * n
    lo, hi = int(dist["min"]), int(dist["max"])
    out = []
    for i in range(n):
        u = (i + 0.5) / n
        if kind == "lognormal":
            x = math.exp(math.log(dist["median"]) + dist["sigma"] * _NORMAL.inv_cdf(u))
        elif kind == "uniform":
            x = lo + u * (hi - lo + 1) - 0.5
        else:
            raise ValueError(f"unknown distribution {kind!r}")
        out.append(int(min(max(round(x), lo), hi)))
    return out


def _apportion(weights: list[float], n: int) -> list[int]:
    """n items over the weights by largest remainder."""
    total = float(sum(weights))
    raw = [w / total * n for w in weights]
    counts = [int(math.floor(r)) for r in raw]
    for i in sorted(range(len(raw)), key=lambda i: raw[i] - counts[i], reverse=True):
        if sum(counts) == n:
            break
        counts[i] += 1
    return counts


def shared_prefixes(mix: dict, seed: int, *, vocab: int) -> list[list[int]]:
    """The mix's shared prompt heads (system prompts, documents), seeded."""
    if not mix.get("prefixes"):
        return []
    tok = _rng(seed, 3)
    return [
        [int(t) for t in tok.integers(0, vocab, (ln,))]
        for ln in mix["prefixes"]["lengths"]
    ]


def make_requests(mix: dict, n: int, seed: int, *, vocab: int) -> list[dict]:
    """n request bodies for ``ServingFrontend.handle_request``, without
    arrival times. The same seed gives byte-identical requests."""
    prompts = quantile_set(mix["prompt"], n)
    outputs = quantile_set(mix["output"], n)
    _rng(seed, 1).shuffle(prompts)
    _rng(seed, 2).shuffle(outputs)
    prefix_of = [-1] * n
    prefixes = shared_prefixes(mix, seed, vocab=vocab)
    if prefixes:
        weights = mix["prefixes"].get("weights") or [1.0] * len(prefixes)
        prefix_of = [
            i for i, c in enumerate(_apportion(weights, n)) for _ in range(c)
        ]
        _rng(seed, 4).shuffle(prefix_of)
    tok = _rng(seed, 5)
    out = []
    for i in range(n):
        head = prefixes[prefix_of[i]] if prefix_of[i] >= 0 else []
        body = [int(t) for t in tok.integers(0, vocab, (prompts[i],))]
        out.append({
            "body": {
                "prompt": head + body,
                "max_new_tokens": outputs[i],
                "temperature": float(mix.get("temperature", 0.0)),
                "seed": int(i),
                "slo": mix.get("slo", "interactive"),
            },
            "prefix": prefix_of[i],
        })
    return out


def arrival_times(mix: dict, rate: float, seconds: float, seed: int) -> list[float]:
    """Due instants in [0, seconds) of an open loop at ``rate`` requests
    a second: exponential gaps (their quantile set, shuffled by the
    seed). ``rate_profile`` — [[fraction of the window, multiplier],
    ...], piecewise constant — bends the clock for bursts."""
    profile = mix.get("rate_profile") or [[0.0, 1.0]]
    edges = [float(f) * seconds for f, _ in profile] + [float(seconds)]
    mult = [float(m) for _, m in profile]
    # cumulative expected arrivals at each edge
    cum = [0.0]
    for k, m in enumerate(mult):
        cum.append(cum[-1] + rate * m * (edges[k + 1] - edges[k]))
    n = int(round(cum[-1]))
    gaps = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = cum[-1] / (sum(gaps) + gaps[n // 2])  # last arrival just inside
    _rng(seed, 6).shuffle(gaps)
    times, acc = [], 0.0
    for g in gaps:
        acc += g * scale
        k = max(i for i in range(len(mult)) if cum[i] <= acc)
        times.append(edges[k] + (acc - cum[k]) / (rate * mult[k]))
    return times


def closed_loop_clients(mix: dict, max_slots: int, max_queue: int) -> int:
    """Clients of a closed loop: ``clients_per_slot`` x slots, and never
    more than the batcher's queue bound: at the start, and whenever a
    wave of equal-length requests ends, every client can be waiting at
    once, and one request over the bound is shed."""
    return min(int(mix.get("clients_per_slot", 2)) * max_slots, int(max_queue))


def _record(req: dict, status: int, reply: dict, *, due: float, fired: float,
            done: float) -> dict:
    ok = status == 200 and len(reply.get("tokens") or ()) == req["body"]["max_new_tokens"]
    return {
        "ok": ok,
        "status": status,
        "due_s": due,
        "late_s": fired - due,
        "client_s": done - due,          # due -> reply in hand, benchmark's clock
        "queue_wait_s": reply.get("queue_wait_s"),
        "ttft_s": reply.get("ttft_s"),   # submit -> first token, the batcher's clock
        "total_s": reply.get("total_s"),
        "n_tokens": len(reply.get("tokens") or ()),
        "asked": req["body"]["max_new_tokens"],
        "prompt_len": len(req["body"]["prompt"]),
        "tokens": reply.get("tokens"),
        "error": reply.get("error"),
    }


def drive_open_loop(handle, requests: list[dict], times: list[float], *,
                    workers: int = 128) -> tuple[list[dict], float]:
    """Send request i at ``times[i]`` whether or not earlier ones have
    returned. ``handle(body) -> (status, reply)``. Returns the records,
    index-aligned, and the window's length: first due instant to the
    last reply."""
    records: list = [None] * len(requests)
    t0 = time.perf_counter()

    def fire(i: int) -> None:
        fired = time.perf_counter() - t0
        status, reply = handle(requests[i]["body"])
        records[i] = _record(
            requests[i], status, reply, due=times[i], fired=fired,
            done=time.perf_counter() - t0,
        )

    with ThreadPoolExecutor(max_workers=min(workers, max(len(requests), 1))) as pool:
        futures = []
        for i, due in enumerate(times):
            delay = due - (time.perf_counter() - t0)
            if delay > 0:
                time.sleep(delay)
            futures.append(pool.submit(fire, i))
        for f in futures:
            f.result()
    return records, time.perf_counter() - t0


def drive_closed_loop(handle, requests: list[dict], clients: int) -> tuple[list[dict], float]:
    """``clients`` callers, each sending the next request of the shared
    list when its last returns. The window opens at the first send and
    closes when the last reply is in hand."""
    todo = collections.deque(enumerate(requests))
    records: list = [None] * len(requests)
    lock = threading.Lock()
    errors: list[BaseException] = []
    t0 = time.perf_counter()

    def client() -> None:
        try:
            while True:
                with lock:
                    if not todo:
                        return
                    i, req = todo.popleft()
                fired = time.perf_counter() - t0
                status, reply = handle(req["body"])
                records[i] = _record(
                    req, status, reply, due=fired, fired=fired,
                    done=time.perf_counter() - t0,
                )
        except BaseException as e:  # noqa: BLE001 — re-raised by the caller below
            errors.append(e)

    threads = [threading.Thread(target=client, name=f"client-{c}") for c in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    return records, time.perf_counter() - t0
