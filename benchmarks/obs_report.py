"""Observability self-measurement: what does the telemetry itself cost?

An instrumentation layer that slows the hot path down gets turned off, and
then nobody has utilisation numbers when they matter.  This benchmark runs
the ``serve_throughput`` continuous-batching trace twice through one warm
engine -- once under ``obs.disabled()``, once with recording on AND measured
profiling sampling at PROFILE_RATE (DESIGN.md §15) -- and reports the
throughput delta.  The budget is **< 3%**: one boolean check per record call
on the disabled path, one dict/append per event on the enabled path, nothing
on the jitted step itself (dispatch records at trace time), and one
block_until_ready window per sampled pool dispatch on the profiled path.

The enabled arm doubles as the utilisation-accounting smoke: its BENCH JSON
carries the roofline model residual, tune-plan hit rate,
TTFT/ITL percentiles, and resident KV bytes of the run, plus structural
validation of the metrics snapshot and the exported Chrome trace.

    PYTHONPATH=src python -m benchmarks.run obs
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import time

# The enabled arm runs with measured-profiling sampling on: the <3% budget
# covers the profiler's steady-state cost, not just the counter layer.  Each
# sampled window is a block_until_ready pipeline drain (~0.3-0.7ms on CPU),
# which cannot amortize against the smoke model's sub-millisecond ticks the
# way it does against real decode steps -- so the benchmark samples at 5%
# (~1 window per run), the rate the serve launcher documents as the
# always-on default.  Higher rates are for targeted investigation, not
# steady state.
PROFILE_RATE = 0.05


def run(
    arch: str = "internlm2-1.8b",
    n_requests: int = 8,
    n_slots: int = 3,
    rate: float = 0.8,
    mean_prompt: int = 10,
    mean_gen: int = 8,
    repeats: int = 3,
    seed: int = 0,
    max_overhead: float = 0.03,
) -> list[str]:
    import jax

    from repro import obs
    from repro.configs import get_smoke
    from repro.data.synthetic import make_request_trace
    from repro.models.registry import get_model
    from repro.serving import (
        ContinuousScheduler,
        ServeConfig,
        ServeEngine,
        requests_from_trace,
    )

    cfg = dataclasses.replace(get_smoke(arch), dtype="float32")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    trace = make_request_trace(
        cfg,
        n_requests=n_requests,
        mean_prompt=mean_prompt,
        mean_gen=mean_gen,
        rate=rate,
        seed=seed,
        max_prompt=2 * mean_prompt,
        max_gen=2 * mean_gen,
    )
    prefix = cfg.n_patches if cfg.frontend == "vit" else 0
    max_len = (
        max(t["prompt"]["tokens"].shape[1] + t["max_new_tokens"] for t in trace)
        + prefix
    )
    # One engine for every run: compiles are shared, so the two arms compare
    # recording cost, not whose trace happened to compile in-window.
    engine = ServeEngine(model, params, ServeConfig(max_len=max_len, batch=n_slots))

    def one_run(enabled: bool):
        ctx = contextlib.nullcontext() if enabled else obs.disabled()
        prof_ctx = obs.sampling(PROFILE_RATE if enabled else 0.0)
        sched = ContinuousScheduler(engine, policy="continuous")
        reqs = requests_from_trace(trace)
        with ctx, prof_ctx:
            t0 = time.perf_counter()
            sched.run(reqs)
            wall = time.perf_counter() - t0
        obs.get_tracer().clear()  # bound the ring buffer across repeats
        return sched, wall

    one_run(True)  # throwaway: absorb any remaining one-off compiles

    best: dict[str, float] = {}
    last_enabled = None
    # Interleave the arms (d, e, d, e, ...) so slow drift in background load
    # biases neither mode; best-of-N then absorbs one-off stalls.
    for _ in range(repeats):
        for mode, enabled in (("disabled", False), ("enabled", True)):
            sched, wall = one_run(enabled)
            tok_s = sched.stats.tokens_out / wall if wall > 0 else 0.0
            best[mode] = max(best.get(mode, 0.0), tok_s)
            if enabled:
                last_enabled = sched
    overhead = 1.0 - best["enabled"] / best["disabled"] if best["disabled"] else 0.0

    # Utilisation accounting + artefact validation from the last enabled run
    # (its tracer events were cleared, so re-export a fresh tick's worth).
    s = last_enabled.stats.summary()
    snap = obs.snapshot_doc(obs.get_registry(), last_enabled.stats.registry, extra=s)
    trace_doc = obs.get_tracer().export_chrome()

    row = {
        "bench": "obs_overhead",
        "arch": arch,
        "n_requests": n_requests,
        "repeats": repeats,
        "tok_per_s_disabled": round(best["disabled"], 2),
        "tok_per_s_enabled": round(best["enabled"], 2),
        "overhead_frac": round(overhead, 4),
        "overhead_budget": max_overhead,
        "overhead_ok": overhead < max_overhead,
        "profile_sample_rate": PROFILE_RATE,
        "model_residual": s["model_residual"],
        "plan_hit_rate": round(obs.plan_hit_rate("pallas-systolic"), 4),
        "ttft_p50_ms": s["ttft_p50_ms"],
        "itl_p50_ms": s["itl_p50_ms"],
        "kv_bytes_resident": s["kv_bytes_resident"],
        "snapshot_valid": not obs.validate_snapshot(snap),
        "trace_valid": not obs.validate_chrome_trace(trace_doc),
    }
    rows = [
        "obs_report.metric,disabled,enabled,overhead_frac,budget,verdict",
        f"tok_per_s,{row['tok_per_s_disabled']},{row['tok_per_s_enabled']},"
        f"{row['overhead_frac']},{max_overhead},"
        f"{'OK' if row['overhead_ok'] else 'REGRESSION'}",
        "BENCH " + json.dumps(row, sort_keys=True),
    ]
    if not (row["snapshot_valid"] and row["trace_valid"]):
        rows.append("WARNING: obs artefacts failed structural validation")
    return rows
