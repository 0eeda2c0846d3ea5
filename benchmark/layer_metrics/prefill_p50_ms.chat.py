"""Engine: median of the serving/prefill histogram (host clock around engine.prefill), window only."""

from benchmark import readers


def read(run):
    return readers.hist_percentile_ms(run, "serving/prefill", 50)
