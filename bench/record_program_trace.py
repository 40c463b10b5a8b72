#!/usr/bin/env python3
"""Record a profiler trace of a cell on the chip with the program's own spans,
show where the device's idle time goes, and keep a trimmed test fixture.

    python bench/record_program_trace.py --workload <name> --seed <n> [--out <file.json.gz>]

Sets the cell up as a run does and traces the first ``--seconds`` of its
window.  Prints, as JSON lines:

* ``idle_by_program``: the device's idle seconds in the traced window, cut
  where the innermost open program span (``serve.*``, ``engine.*``,
  ``kv.*``) changes, the share of the idle time inside ``serve.tick``
  spans that falls in a named child span, and the offset between the
  device's clock and the host's with the spread it was found from;
* ``metrics``: ``tick_idle_ms``, ``prefill_tick_share`` and
  ``kv_live_share`` read from the profile's host plane and from the
  program's ring buffer (what a benchmark run reads), side by side;
* ``programs``: the programs the device ran, by name.

With ``--out``, keeps the first ``--ticks`` consecutive harness ticks that
hold both a prefill chunk and a decode step (``bench/record_trace.py``'s
``trim``) and the program spans that overlap them, under ``program``.
``bench/tests/test_program_metrics.py`` reads the file.  The benchmark's own
runs never run this.
"""

from __future__ import annotations

import argparse
import collections
import gzip
import json
import os
import shutil
import statistics
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import record_trace, run  # noqa: E402
from bench.lib import harness, program_metrics, spec, trace_reduce  # noqa: E402


def tick_phase_share(idle: dict) -> float | None:
    """Of the idle time inside program spans (in the window, every program
    span opens inside a ``serve.tick``), the share that falls in a named
    child span rather than in ``serve.tick``'s own time."""
    total = sum(v for k, v in idle.items() if k != program_metrics.OUTSIDE)
    return 1.0 - idle.get(program_metrics.TICK, 0.0) / total if total else None


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--ticks", type=int, default=2)
    args = ap.parse_args(argv)
    bench = spec.load_benchmark()
    wl = spec.workload(bench, args.workload)
    cfg = spec.config(bench, wl["config"])
    mix = spec.traffic(wl["traffic"])
    jax = run.configure_jax(ROOT)
    run.device_info(jax, wl["chips"], require_tpu=True)
    from repro.core import ops
    from repro.obs import trace as obs_trace

    trace_dir = os.path.join(ROOT, ".bench_trace", "record_program")
    shutil.rmtree(trace_dir, ignore_errors=True)
    tracing = []

    def on_open():
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        tracing.append(True)

    def on_traced():
        if tracing:
            jax.profiler.stop_trace()
            tracing.clear()

    with ops.use_backend("pallas-systolic"):
        cell = harness.Cell(cfg, mix, args.seed, args.seconds)
        try:
            win = cell.drive(
                args.seconds, ramp=mix.get("ramp_s", 0.0), on_open=on_open,
                traced=args.seconds, on_traced=on_traced,
            )
        finally:
            on_traced()
    tr = trace_reduce.load_profile(trace_dir)
    program = program_metrics.load_program(trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)
    lo, hi = trace_reduce.window(tr)
    dev = tr.devices[0]
    # A loop that ran out of requests closed the window without its span.
    end = win.traced_end if win.traced_end is not None else win.t1
    ring = program_metrics.from_tracer(obs_trace.get_tracer(), win.t0, end, lo, hi)

    idle = program_metrics.idle_by_program(program, dev, lo, hi)
    ahead = [(enq - s) * 1e-6 for _, s, _, enq in dev.modules if enq is not None and enq > s]
    print(json.dumps({
        "idle_by_program": trace_reduce.top(idle, 20),
        "idle_s": sum(idle.values()),
        "window_s": (hi - lo) * 1e-9,
        "ticks": len(program_metrics.ticks(program, lo, hi)),
        "tick_idle_in_named_child": tick_phase_share(idle),
        "clock_offset_ms": program_metrics.clock_offset(dev) * 1e-6,
        "ahead_ms_quartiles": statistics.quantiles(ahead, n=4) if len(ahead) > 1 else ahead,
        "ahead_ms_range": [min(ahead), max(ahead)] if ahead else None,
        "executions_ahead": [len(ahead), len(dev.modules)],
    }), flush=True)
    print(json.dumps({"metrics": {
        source: {
            "tick_idle_ms": program_metrics.tick_idle_ms(spans, dev, lo, hi),
            "prefill_tick_share": program_metrics.prefill_tick_share(spans, dev, lo, hi),
            "kv_live_share": program_metrics.kv_live_share(spans, lo, hi),
            "ticks": len(program_metrics.ticks(spans, lo, hi)),
        }
        for source, spans in (("profile", program), ("ring_buffer", ring))
    }}), flush=True)
    names = collections.Counter(m[0].split("(", 1)[0] for m in dev.modules if lo <= m[1] < hi)
    print(json.dumps({"programs": dict(names.most_common())}), flush=True)

    if args.out:
        cut = record_trace.trim(tr, args.ticks)
        t_lo = min(s[1] for s in cut.spans if s[0] == "bench.tick")
        t_hi = max(s[2] for s in cut.spans if s[0] == "bench.tick")
        doc = cut.to_json()
        doc["program"] = [p for p in program if p[1] < t_hi and p[2] > t_lo]
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with gzip.open(args.out, "wt") as f:
            json.dump(doc, f)
        print(f"recorded {len(cut.devices[0].ops)} ops, {len(cut.devices[0].modules)} executions, "
              f"{len(cut.spans)} harness spans, {len(doc['program'])} program spans to {args.out}",
              flush=True)


if __name__ == "__main__":
    main()
