"""Engine step: device time of the chunk programs per prefill chunk (ms)."""

from bench.lib import layer_metrics


def read(run):
    return layer_metrics.phase_ms(run, "prefill")
