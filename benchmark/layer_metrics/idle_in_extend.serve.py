"""Engine: device idle while the serve thread's innermost span is span/engine_extend_build, _upload, _dispatch or _fetch (a prefix hit's tail or a prompt chunk launched and fetched), as % of the traced window."""

from benchmark import span_idle


def read(run):
    return span_idle.idle_share_pct(run, tuple(
        f"span/engine_extend_{part}" for part in ("build", "upload", "dispatch", "fetch")))
