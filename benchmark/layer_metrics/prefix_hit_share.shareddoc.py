"""Frontend and batcher: prompt tokens served from the prefix cache (serving/prefix_reused_tokens) over the prompt tokens admitted (serving/prefill_tokens), over the window, %."""


def read(run):
    total = run.counters.get("serving/prefill_tokens")
    if not total:
        return None
    return 100.0 * run.counters.get("serving/prefix_reused_tokens", 0) / total
