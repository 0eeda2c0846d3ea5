"""Engine (paged_kv.py): device idle while the serve thread's innermost span is span/kv_claim (prefix lookup, block allocation, table install) or span/kv_publish (the prefix index's walk and hash on publishing), as % of the traced window."""

from benchmark import span_idle


def read(run):
    return span_idle.idle_share_pct(run, ("span/kv_claim", "span/kv_publish"))
