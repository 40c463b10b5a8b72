"""Model step: model FLOPs of the window over window times bf16 peak, decode-bound cell (%)."""

from bench.lib import layer_metrics


def read(run):
    return layer_metrics.mfu(run)
