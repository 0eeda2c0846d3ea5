"""Device: 1 - union of device-op intervals / traced window, from the .xplane.pb."""

from benchmark import readers


def read(run):
    return readers.device_idle_pct(run)
