import json
import os
import time

from benchmark import spec, traffic_gen

MIX = json.load(open(os.path.join(spec.HERE, "traffic", "serve-chat.json")))
GEN = json.load(open(os.path.join(spec.HERE, "traffic", "serve-generate.json")))


def test_same_seed_is_byte_identical_and_large_seeds_work():
    seed = 2**31 + 12345
    a = traffic_gen.make_requests(MIX, 40, seed, vocab=50257)
    b = traffic_gen.make_requests(MIX, 40, seed, vocab=50257)
    assert json.dumps(a) == json.dumps(b)
    assert traffic_gen.arrival_times(MIX, 3.0, 20, seed) == traffic_gen.arrival_times(MIX, 3.0, 20, seed)
    assert json.dumps(a) != json.dumps(traffic_gen.make_requests(MIX, 40, seed + 1, vocab=50257))


def test_every_seed_has_the_same_set_of_sizes_in_another_order():
    def sizes(seed):
        reqs = traffic_gen.make_requests(MIX, 60, seed, vocab=50257)
        heads = {0: 48, 1: 64, 2: 96, 3: 128}
        return (
            sorted(len(r["body"]["prompt"]) - heads[r["prefix"]] for r in reqs),
            sorted(r["body"]["max_new_tokens"] for r in reqs),
            sorted(r["prefix"] for r in reqs),
        )

    assert sizes(1) == sizes(2)
    def gaps(seed):
        t = traffic_gen.arrival_times(MIX, 3.0, 20, seed)
        return sorted(round(b - a, 9) for a, b in zip([0.0] + t, t))

    assert gaps(1) == gaps(2)


def test_mix_limits_hold():
    reqs = traffic_gen.make_requests(MIX, 200, 3, vocab=50257)
    for r in reqs:
        n = len(r["body"]["prompt"])
        assert 16 + 48 <= n <= 512 + 128
        assert 8 <= r["body"]["max_new_tokens"] <= 256
        assert n + r["body"]["max_new_tokens"] <= 1024
    shares = [sum(1 for r in reqs if r["prefix"] == k) / 200 for k in range(4)]
    assert shares == [0.4, 0.3, 0.2, 0.1]
    gen = traffic_gen.make_requests(GEN, 64, 3, vocab=50257)
    assert {r["body"]["max_new_tokens"] for r in gen} == {256}
    assert min(len(r["body"]["prompt"]) for r in gen) == 32
    assert max(len(r["body"]["prompt"]) for r in gen) == 96
    times = traffic_gen.arrival_times(MIX, 3.0, 20, 5)
    assert len(times) == 60 and 0 < times[0] and times[-1] < 20
    assert times == sorted(times)
    burst = dict(MIX, rate_profile=[[0, 1], [1 / 3, 3], [2 / 3, 1]])
    bt = traffic_gen.arrival_times(burst, 3.0, 30, 5)
    middle = sum(1 for t in bt if 10 <= t < 20)
    assert len(bt) == 150 and 80 <= middle <= 100
    assert traffic_gen.closed_loop_clients(GEN, 8, 64) == 16
    assert traffic_gen.closed_loop_clients(GEN, 128, 64) == 64     # the queue bound caps the backlog
    assert traffic_gen.closed_loop_clients(GEN, 128, 256) == 256


def test_open_loop_times_from_the_due_instant():
    """A server that stalls makes later requests late; the lateness is
    in the record, and the latency counts from when each was due."""
    mix = {"prompt": {"dist": "fixed", "value": 4}, "output": {"dist": "fixed", "value": 2}}
    reqs = traffic_gen.make_requests(mix, 4, 0, vocab=100)
    gate = __import__("threading").Lock()

    def handle(body):  # one at a time, 50 ms each: the second waits for the first
        with gate:
            time.sleep(0.05)
        return 200, {"tokens": [1, 2], "ttft_s": 0.0, "total_s": 0.05, "queue_wait_s": 0.0}

    records, window = traffic_gen.drive_open_loop(handle, reqs, [0.0, 0.0, 0.0, 0.0])
    assert all(r["ok"] for r in records)
    assert sorted(r["client_s"] for r in records)[-1] >= 0.19  # the fourth waited for three
    assert window >= 0.19
    assert all(r["late_s"] >= 0 for r in records)


def test_closed_loop_sends_every_request_once():
    reqs = traffic_gen.make_requests(GEN, 20, 0, vocab=100)
    seen = []

    def handle(body):
        seen.append(tuple(body["prompt"]))
        return 200, {"tokens": [0] * body["max_new_tokens"], "ttft_s": 0.0, "total_s": 0.0}

    records, _ = traffic_gen.drive_closed_loop(handle, reqs, 4)
    assert len(seen) == 20 == len(set(seen)) and all(r["ok"] for r in records)
