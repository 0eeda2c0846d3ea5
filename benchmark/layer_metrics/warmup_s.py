"""Entry layer: the benchmark's clock around engine.warmup(), or around the first training steps (compile or cache read)."""


def read(run):
    return run.warmup_s or None
