"""Engine step: device time of the decode programs per decode step (ms)."""

from bench.lib import layer_metrics


def read(run):
    return layer_metrics.phase_ms(run, "decode")
