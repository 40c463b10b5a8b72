"""Scheduler: device time of the prefill chunk program over the ticks' wall time (%)."""

from bench.lib import program_metrics as pm


def read(run):
    return pm.prefill_tick_share(pm.program_spans(run), run.dev, run.lo, run.hi)
