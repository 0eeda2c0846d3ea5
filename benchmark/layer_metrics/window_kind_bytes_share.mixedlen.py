"""Engine: the window kinds' share of the pool's bytes in use, both sampled at every decode step (serving/kv_sampled_bytes_kind_window<W> over serving/kv_sampled_bytes), %: what the layers that forget cost beside the layers that keep."""

PREFIX = "serving/kv_sampled_bytes_kind_window"


def read(run):
    total = run.counters.get("serving/kv_sampled_bytes")
    window = [v for k, v in run.counters.items() if k.startswith(PREFIX)]
    if not total or not window:
        return None
    return 100.0 * sum(window) / total
