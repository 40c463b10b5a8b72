#!/usr/bin/env python3
"""Readings that set a cell's ``correct`` limits, in one process on the chip.

    python bench/calibrate.py --workload <name> --seeds 12 --control-seeds 3 --seconds 12

For each of ``--seeds`` seeds: the cell as it runs (set-up, a window of
``--seconds``, the reference comparison), and its ``mean_logit_gap``.  Then the
precision control on ``--control-seeds`` further seeds: the same run with the
program's own int8 path switched on (int8 weights and int8 activations in
every projection: ``quant.quantize_params`` and ``quant.use_act_quant``),
the step below the bf16 the configuration states.  The sound runs' largest
gap is the lower reading, the control's smallest the upper; PERF.md gives
both and the limit set between them.  The benchmark's own runs never run
this.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

from bench import run  # noqa: E402
from bench.lib import correct, harness, spec  # noqa: E402


def int8_control(cell) -> None:
    """Serve int8 weights.  The fp32 ones go to the host first and are
    quantized one projection at a time (the whole fp32 tree beside the int8
    one and the KV pool does not fit on the chip); the reference makes them
    again."""
    import jax

    from repro import quant

    host = jax.device_get(cell.weights)
    cell.weights = cell.engine.params = None
    cell.engine.params = jax.device_put(quant.quantize_params(host, "int8"))


def reading(cfg, mix, seed: int, seconds: float, control: bool) -> dict:
    from repro import quant
    from repro.core import ops

    t = time.perf_counter()
    act = quant.use_act_quant("int8") if control else contextlib.nullcontext()
    with ops.use_backend("pallas-systolic"), act:
        cell = harness.Cell(cfg, mix, seed, seconds, tamper=int8_control if control else None)
        win = cell.drive(seconds, ramp=mix.get("ramp_s", 0.0))
    cell.release()
    if cell.weights is None:
        cell.engine.params = None
        cell.weights = cell.make_weights()
    g = correct.gaps(cell, win)
    checks = correct.check(cell, win, g)
    out = {
        "seed": seed,
        "control": control,
        "finished": len(win.finished()),
        "seconds": round(time.perf_counter() - t, 1),
        **{n: v for n, v, _, _ in checks},
        "max_gap": float(g.max()),
        "p99_gap": float(np.quantile(g, 0.99)),
        "swapped_share": float(np.mean(g > 0)),
    }
    del cell, win
    gc.collect()
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--first-seed", type=int, default=3_000_000_001)
    args = ap.parse_args(argv)
    bench = spec.load_benchmark()
    wl = spec.workload(bench, args.workload)
    cfg = spec.config(bench, wl["config"])
    mix = spec.traffic(wl["traffic"])
    jax = run.configure_jax(ROOT)
    run.device_info(jax, wl["chips"], require_tpu=True)
    rows = []
    for i in range(args.seeds + args.control_seeds):
        control = i >= args.seeds
        r = reading(cfg, mix, args.first_seed + 7919 * i, args.seconds, control)
        rows.append(r)
        print("READING " + json.dumps(r), flush=True)
    sound = [r["mean_logit_gap"] for r in rows if not r["control"]]
    ctrl = [r["mean_logit_gap"] for r in rows if r["control"]]
    print(json.dumps({"workload": args.workload, "lower": max(sound) if sound else None,
                      "upper": min(ctrl) if ctrl else None,
                      "sound": sound, "control": ctrl}), flush=True)


if __name__ == "__main__":
    main()
