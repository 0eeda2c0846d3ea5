"""Engine: median of the serving/decode_step histogram (host clock around engine.decode, which ends in np.asarray), window only."""

from benchmark import readers


def read(run):
    return readers.hist_percentile_ms(run, "serving/decode_step", 50)
