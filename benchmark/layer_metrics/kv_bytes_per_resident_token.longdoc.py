"""Engine: bytes the pool's blocks in use hold over the tokens resident, both sampled at every decode step (a pool that released nothing reads kv_bytes_token, 16,384 B here, plus its partly filled blocks)."""


def read(run):
    tokens = run.counters.get("serving/kv_sampled_tokens")
    if not tokens:
        return None
    return run.counters["serving/kv_sampled_bytes"] / tokens
