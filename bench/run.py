#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` on the chip this machine holds.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (counted as ``setup_s``, from process start): weights from the seed
on the device, the program's engine and scheduler, every program the window
runs compiled or loaded from the persistent compilation cache.  Then the
window: the cell's traffic for ``--seconds`` of wall time.  Then, with the
program's state freed, the served tokens are held against the plain
reference (``bench/lib/correct.py``).

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` records a
profiler trace of the window and reports the cell's per-layer metrics, the
device's busy and window seconds and a breakdown.  The last line of standard
output is the result, a JSON object; the numbers compared for ``correct``
also close standard error.  Without a TPU, or with fewer chips than the cell
asks for, the run exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench.lib import spec  # noqa: E402

# A traced run records the profiler over the first TRACE_SECONDS of its
# window: a v5e trace holds some 100k device ops a second, and the host
# reads them at about that rate.
TRACE_SECONDS = 5.0


class NoChip(SystemExit):
    pass


def configure_jax(root: str):
    """The persistent compilation cache at a fixed path in the checkout,
    unless ``JAX_COMPILATION_CACHE_DIR`` places it; every program cached."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def device_info(jax, chips: int, require_tpu: bool) -> dict:
    devs = jax.devices()
    if require_tpu and jax.default_backend() != "tpu":
        raise NoChip(f"no TPU: JAX's backend is {jax.default_backend()!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": chips}


def quantile(values, q: float) -> float:
    import numpy as np

    return float(np.quantile(np.asarray(values, np.float64), q))


def end_to_end(win) -> dict:
    """The client-side numbers of the window (host clock)."""
    ttft, itl = win.ttft(), win.itl()
    if not ttft or not itl:
        raise RuntimeError(f"the window served too little: {len(ttft)} first tokens, {len(itl)} gaps")
    return {
        "out_tok_s": len(win.tokens()) / win.seconds,
        "ttft_p95_ms": quantile(ttft, 0.95) * 1e3,
        "itl_p95_ms": quantile(itl, 0.95) * 1e3,
    }


def gemm_calls() -> dict:
    """The program's trace-time GEMM counter, summed per backend."""
    from repro import obs

    out: dict = {}
    for series, v in obs.get_registry().snapshot()["counters"].items():
        if series.startswith("gemm.calls{"):
            backend = series.split('backend="', 1)[1].split('"', 1)[0]
            out[backend] = out.get(backend, 0) + v
    return out


def main(argv=None, *, require_tpu: bool = True, root: str = ROOT, tamper=None) -> dict:
    """Run one cell; returns the result object (also printed)."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = spec.load_benchmark(root)
    wl = spec.workload(bench, args.workload)
    cfg = spec.config(bench, wl["config"], root)
    mix = spec.traffic(wl["traffic"], os.path.join(root, "bench"))

    jax = configure_jax(root)
    device = device_info(jax, wl["chips"], require_tpu)

    from bench.lib import correct, harness, peaks, trace_reduce
    from repro.core import ops

    trace_dir = os.path.join(root, ".bench_trace", args.workload)
    with ops.use_backend("pallas-systolic"):
        cell = harness.Cell(cfg, mix, args.seed, args.seconds, tamper=tamper)
        calls = gemm_calls()
        print(f"gemm.calls per backend (traced at set-up): {calls}", flush=True)
        opened, tracing = [], []

        def on_open():
            opened.append(time.perf_counter() - T_START)
            if args.trace:
                shutil.rmtree(trace_dir, ignore_errors=True)
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                tracing.append(True)

        def on_traced():
            if tracing:
                jax.profiler.stop_trace()
                tracing.clear()

        try:
            win = cell.drive(
                args.seconds, ramp=mix.get("ramp_s", 0.0), on_open=on_open,
                traced=TRACE_SECONDS, on_traced=on_traced,
            )
        finally:
            on_traced()
        setup_s = opened[0]
    device["memory_peak_bytes"] = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.devices()[: wl["chips"]]
    )
    in_win = [r for r in win.recs if r.submitted >= win.t0]
    late = sorted(r.submitted - r.due for r in in_win) or [0.0]
    ttft = win.ttft()
    print(
        f"window: {win.seconds:.3f}s, {len(win.ticks)} ticks, {len(in_win)} requests submitted, "
        f"{len(win.finished())} finished, {len(ttft)} first tokens (ttft p50 "
        f"{quantile(ttft, 0.5) * 1e3 if ttft else 0:.1f} p90 {quantile(ttft, 0.9) * 1e3 if ttft else 0:.1f} ms), "
        f"compiles in window: {win.compiles}, "
        f"generator lateness p95 {quantile(late, 0.95) * 1e3:.3f} ms max {late[-1] * 1e3:.3f} ms, "
        f"peak_bytes_in_use {device['memory_peak_bytes']}",
        flush=True,
    )
    itl = win.itl()
    if itl:
        print(
            "token gaps (ms): "
            + ", ".join(f"p{q:g} {quantile(itl, q / 100) * 1e3:.2f}" for q in (50, 90, 93, 95, 97, 99))
            + f"; {len(itl)} gaps, {sum(1 for t in win.ticks if t.chunks)} of {len(win.ticks)} ticks carry a chunk",
            flush=True,
        )
    result = {"correct": False, "attempted": len(in_win) + win.refused, "failed": win.refused}
    e2e = end_to_end(win)
    e2e["setup_s"] = setup_s
    if args.trace:
        t_read = time.perf_counter()
        tr = trace_reduce.load_profile(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        lo, hi = trace_reduce.window(tr)
        dev = tr.devices[0] if tr.devices else None
        busy = [trace_reduce.busy_ns(d.ops, lo, hi) * 1e-9 for d in tr.devices]
        device["busy_s"] = sum(busy) / len(busy) if busy else 0.0
        device["window_s"] = (hi - lo) * 1e-9
        run = types.SimpleNamespace(
            cfg=cfg, mix=mix, fam=cell.fam, window=win, trace=tr, dev=dev, lo=lo, hi=hi,
            peaks=peaks.PEAKS.get(device["kind"]), slots=cell.slots,
        )
        metrics = {}
        for m in spec.cell_metrics(bench, args.workload, "per_layer"):
            v = spec.metric_reader(m["name"], os.path.join(root, "bench"))(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        print(
            f"trace: {sum(len(d.ops) for d in tr.devices)} device ops, {len(tr.spans)} host spans, "
            f"read and reduced in {time.perf_counter() - t_read:.1f} s",
            flush=True,
        )
        if dev is not None:
            result["breakdown"] = {
                "device_ops": trace_reduce.top(
                    trace_reduce.self_times([o for o in dev.ops if lo <= o[1] < hi])
                ),
                "idle_gaps": trace_reduce.top(trace_reduce.idle_by_host(tr, dev, lo, hi)),
            }
    else:
        units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
        metrics = {
            m["name"]: {"value": e2e[m["name"]], "unit": units[m["name"]]}
            for m in spec.cell_metrics(bench, args.workload, "end_to_end")
        }
    print("end to end: " + ", ".join(f"{k} {v}" for k, v in e2e.items()), flush=True)

    cell.release()
    t_ref = time.perf_counter()
    g = correct.gaps(cell, win)
    print(
        f"served-token gaps: widest {g.max() if g.size else None}, mean {g.mean() if g.size else None} "
        f"({g.size} tokens, reference {time.perf_counter() - t_ref:.1f} s)",
        flush=True,
    )
    checks = correct.check(cell, win, g)
    result["correct"] = correct.passed(checks)
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = [{"name": n, "value": v, "limit": lim, "pass_if": k} for n, v, lim, k in checks]
    for n, v, lim, k in checks:
        print(f"check {n} {v} limit {lim} ({'at most' if k == 'max' else 'at least'})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    try:
        main()
    except NoChip as e:
        print(e, file=sys.stderr)
        sys.exit(2)
