"""Systolic GEMM kernel: share of its roofline in the decode steps (%)."""

from bench.lib import layer_metrics


def read(run):
    return layer_metrics.gemm_roofline(run, "decode")
