"""Scheduler: device idle time inside a scheduler tick (``serve.tick``), per tick (ms)."""

from bench.lib import program_metrics as pm


def read(run):
    return pm.tick_idle_ms(pm.program_spans(run), run.dev, run.lo, run.hi)
