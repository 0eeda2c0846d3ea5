"""Engine: (token, expert) pairs on the busiest held expert over the mean of the held experts, over the window."""

PREFIX = "serving/moe_pairs_expert_"


def read(run):
    pairs = [v for k, v in run.counters.items() if k.startswith(PREFIX)]
    if not pairs or not sum(pairs):
        return None
    return max(pairs) / (sum(pairs) / len(pairs))
