"""KV pool: bytes live under the position masks over bytes reserved, per tick (%)."""

from bench.lib import program_metrics as pm


def read(run):
    return pm.kv_live_share(pm.program_spans(run), run.lo, run.hi)
