#!/usr/bin/env python3
"""Sweep the offered rate of an open-loop cell, to find the highest rate the
program sustains without a growing backlog (the knee).

    python bench/knee.py --workload <name> --rates 1.0,1.5,2.0 --seconds 30

One process, one cell set-up per rate (the compiled programs are shared).
For each rate it prints the requests offered and finished, the backlog
(submitted, no first token yet) at each quarter of the window, and the TTFT
median and 95th percentile.  A rate below the knee ends with a backlog that
does not grow from quarter to quarter.  The cell's mix file then takes a
rate of about 0.8 of the knee, as a number; the benchmark's own runs never
search for one.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

from bench import run  # noqa: E402
from bench.lib import harness, spec  # noqa: E402


def backlog_at(win, t: float) -> int:
    """Requests submitted by ``t`` that had no first token yet."""
    return sum(r.submitted <= t and (not r.times or r.times[0] > t) for r in win.recs)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated requests per second")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=5_000_000_001)
    args = ap.parse_args(argv)
    bench = spec.load_benchmark()
    wl = spec.workload(bench, args.workload)
    cfg = spec.config(bench, wl["config"])
    mix = spec.traffic(wl["traffic"])
    jax = run.configure_jax(ROOT)
    run.device_info(jax, wl["chips"], require_tpu=True)
    from repro.core import ops

    for rate in [float(r) for r in args.rates.split(",")]:
        with ops.use_backend("pallas-systolic"):
            cell = harness.Cell(cfg, dict(mix, rate=rate), args.seed, args.seconds)
            win = cell.drive(args.seconds, ramp=mix.get("ramp_s", 0.0))
        ttft = win.ttft()
        row = {
            "rate": rate,
            "offered": sum(r.submitted >= win.t0 for r in win.recs),
            "finished": len(win.finished()),
            "backlog_by_quarter": [backlog_at(win, win.t0 + q * win.seconds / 4) for q in (1, 2, 3, 4)],
            "ttft_p50_ms": float(np.quantile(ttft, 0.5)) * 1e3 if ttft else None,
            "ttft_p95_ms": float(np.quantile(ttft, 0.95)) * 1e3 if ttft else None,
            "out_tok_s": len(win.tokens()) / win.seconds,
        }
        print("KNEE " + json.dumps(row), flush=True)
        cell.release()
        del cell, win
        gc.collect()


if __name__ == "__main__":
    main()
