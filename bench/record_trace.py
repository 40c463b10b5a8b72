#!/usr/bin/env python3
"""Record a short profiler trace of a cell on the chip, trimmed for a test.

    python bench/record_trace.py --workload <name> --seed <n> --out <file.json.gz>

Sets the cell up as a run does, traces the first ``--seconds`` of its window,
reduces the trace (``bench/lib/trace_reduce.py``) and keeps the first
``--ticks`` consecutive harness ticks that hold both a prefill chunk and a
decode step: their device ops and program executions and the host spans that
overlap them.  ``bench/tests/test_trace_reduce.py`` reads the file.  The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import shutil
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import run  # noqa: E402
from bench.lib import harness, spec, trace_reduce  # noqa: E402


def trim(tr: trace_reduce.Trace, ticks: int) -> trace_reduce.Trace:
    """The first ``ticks`` consecutive ``bench.tick`` spans each of which
    holds a ``bench.prefill_chunk`` and a ``bench.decode`` span."""
    spans = tr.spans
    tick_spans = [s for s in spans if s[0] == "bench.tick"]

    def holds(t, name):
        return any(s[0] == name and t[1] <= s[1] and s[2] <= t[2] for s in spans)

    full = [holds(t, "bench.prefill_chunk") and holds(t, "bench.decode") for t in tick_spans]
    for i in range(len(tick_spans) - ticks + 1):
        if all(full[i : i + ticks]):
            lo, hi = tick_spans[i][1], tick_spans[i + ticks - 1][2]
            break
    else:
        raise RuntimeError(f"no {ticks} consecutive ticks hold both a chunk and a decode step")
    dev = tr.devices[0]
    return trace_reduce.Trace(
        [trace_reduce.Device(
            [o for o in dev.ops if lo <= o[1] < hi],
            [m for m in dev.modules if lo <= m[1] < hi],
        )],
        [s for s in spans if s[1] < hi and s[2] > lo],
    )


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--ticks", type=int, default=2)
    args = ap.parse_args(argv)
    bench = spec.load_benchmark()
    wl = spec.workload(bench, args.workload)
    cfg = spec.config(bench, wl["config"])
    mix = spec.traffic(wl["traffic"])
    jax = run.configure_jax(ROOT)
    run.device_info(jax, wl["chips"], require_tpu=True)
    from repro.core import ops

    trace_dir = os.path.join(ROOT, ".bench_trace", "record")
    shutil.rmtree(trace_dir, ignore_errors=True)
    with ops.use_backend("pallas-systolic"):
        cell = harness.Cell(cfg, mix, args.seed, args.seconds)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        cell.drive(
            args.seconds, ramp=mix.get("ramp_s", 0.0),
            on_open=lambda: jax.profiler.start_trace(trace_dir, profiler_options=opts),
            traced=args.seconds, on_traced=jax.profiler.stop_trace,
        )
    tr = trim(trace_reduce.load_profile(trace_dir), args.ticks)
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with gzip.open(args.out, "wt") as f:
        json.dump(tr.to_json(), f)
    print(f"recorded {len(tr.devices[0].ops)} ops, {len(tr.devices[0].modules)} executions, "
          f"{len(tr.spans)} host spans to {args.out}", flush=True)


if __name__ == "__main__":
    main()
