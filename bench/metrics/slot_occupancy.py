"""Scheduler: mean share of the slots decoding per decode tick (%)."""

from bench.lib import layer_metrics


def read(run):
    return layer_metrics.slot_occupancy(run)
