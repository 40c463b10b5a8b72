"""Device: share of the window with no op running, in the prefill-bound cell (%)."""

from bench.lib import layer_metrics


def read(run):
    return layer_metrics.idle_share(run)
