"""Scheduler: 95th percentile of due -> admitted to a slot (ms)."""

from bench.lib import layer_metrics


def read(run):
    return layer_metrics.queue_wait_p95_ms(run)
