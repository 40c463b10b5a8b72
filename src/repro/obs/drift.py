"""Perf-model drift watchdog: sampled timings vs model vs tune cache.

A tune-cache plan is a *measurement frozen in time*: ``measured_us`` was
true on the day the autotuner ran.  The paper's DSE makes the same bet —
Table I's analytical column is only trustworthy because the measured
column was re-taken whenever the configuration changed.  Serving stacks
change configurations constantly (batch, dtype, runtime version), and a
plan whose stored timing no longer matches reality silently mis-ranks
candidates and mis-budgets the scheduler.

This module closes the loop in three steps:

1. ``probe_decode_plans(engine)`` re-measures every decode-step GEMM of a
   serve config through ``tune.measure`` (at the cached plan's geometry
   when one exists, the analytical heuristic's otherwise) and records
   ``profile.gemm_us{backend,dtype,problem,method}`` samples.  The serve
   launcher runs it once at end-of-run when ``--profile-sample-rate`` > 0,
   so the cost is bounded and off the serving path.
2. ``check_drift(snapshot)`` compares each sampled GEMM series against
   (a) the tune cache's stored ``mean_us`` — *only* when the sample's
   measurement method matches the plan's, so an interpret-wall sample is
   never held against a device-wall plan — and (b) the analytical roofline
   model, producing ``DriftFinding`` rows.
3. ``record_findings`` turns stale findings into ``tune.plan.stale{key}``
   counters and regression-ledger rows so ``obs doctor`` and CI can see
   them after the process is gone.

Staleness is symmetric: a plan that claims 2x the sampled time is as
stale as one that claims half of it (``ratio = max(a, b) / min(a, b)``,
stale when ``ratio > 1 + threshold``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Iterable

from repro.obs import metrics as _metrics

__all__ = [
    "DriftFinding",
    "DEFAULT_DRIFT_THRESHOLD",
    "probe_decode_plans",
    "check_drift",
    "record_findings",
]

# A plan is stale when measured and stored mean disagree by more than
# 1 + threshold in either direction.  0.5 flags anything ≥1.5x off —
# well under the 2x injection the acceptance test uses, well above
# steady-state CPU timer noise for the repeat counts the probe uses.
DEFAULT_DRIFT_THRESHOLD = 0.5


@dataclasses.dataclass(frozen=True)
class DriftFinding:
    """One sampled GEMM series held against the model and the cache."""

    problem: str  # "MxNxK"
    backend: str
    dtype: str
    method: str  # measurement method of the sample
    sampled_us: float  # mean of the sampled windows
    samples: int
    model_us: float  # analytical roofline prediction
    model_ratio: float  # sampled / model (>1: slower than modeled)
    cached_us: float | None  # tune-cache stored mean_us (None: no entry)
    cache_ratio: float | None  # max/min disagreement vs cache, symmetric
    threshold: float
    stale: bool
    key: str | None  # cache key string, when an entry exists
    recommendation: str

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def _problem_mnk(problem: str) -> tuple[int, int, int] | None:
    try:
        m, n, k = (int(x) for x in problem.split("x"))
        return m, n, k
    except (ValueError, AttributeError):
        return None


def probe_decode_plans(
    engine,
    *,
    method: str = "auto",
    repeats: int = 2,
    warmup: int = 1,
    registry: _metrics.Registry | None = None,
) -> list[dict]:
    """Re-measure each decode GEMM problem; record profile.gemm samples.

    Uses the cached plan's block geometry when the cache has an entry for
    the problem (apples-to-apples with its stored ``mean_us``) and the
    analytical heuristic's blocks otherwise.  Returns a summary row per
    problem.  A measurement that fails raises: a probe that skipped the
    problem would report a clean run that measured nothing.
    """
    from repro.core import hw
    from repro.core.blocking import derive_block_plan
    from repro.obs import profile as _profile
    from repro.tune import measure as tune_measure

    chip = hw.get_chip(None)
    dtype = str(engine.cfg.dtype)
    rows: list[dict] = []
    for name, ((m, n, k), plan) in sorted(engine.decode_plans.items()):
        if plan is not None:
            bm, bn, bk = plan.bm, plan.bn, plan.bk
        else:
            try:
                bp = derive_block_plan(m, n, k, in_dtype=dtype, chip=chip)
                bm, bn, bk = bp.bm, bp.bn, bp.bk
            except (ValueError, ZeroDivisionError):
                continue
        ms = tune_measure.measure_matmul(
            m, n, k, bm, bn, bk,
            dtype=dtype, backend="pallas-systolic",
            method=method, repeats=repeats, warmup=warmup,
        )
        _profile.record_gemm_sample(
            m, n, k,
            backend="pallas-systolic", dtype=dtype,
            wall_s=ms.mean_us / 1e6, method=ms.method, registry=registry,
        )
        rows.append(
            {
                "name": name,
                "problem": f"{m}x{n}x{k}",
                "blocks": [bm, bn, bk],
                "mean_us": ms.mean_us,
                "best_us": ms.best_us,
                "method": ms.method,
                "cached": plan is not None,
            }
        )
    return rows


def check_drift(
    snapshot: dict,
    *,
    cache=None,
    chip=None,
    threshold: float = DEFAULT_DRIFT_THRESHOLD,
) -> list[DriftFinding]:
    """Hold every ``profile.gemm_us`` series in ``snapshot`` against the
    analytical model and the tune cache.  Offline: works from a snapshot
    document alone (the ``obs doctor`` path) or a live registry snapshot.
    """
    from repro.core import hw
    from repro.obs.attribution import roofline_seconds
    from repro.tune import cache as tune_cache

    chip = hw.get_chip(chip)
    if cache is None:
        cache = tune_cache.default_cache()
    findings: list[DriftFinding] = []
    for series, h in sorted(snapshot.get("histograms", {}).items()):
        base, labels = _metrics.parse_series(series)
        if base != "profile.gemm_us" or not h.get("count"):
            continue
        mnk = _problem_mnk(labels.get("problem", ""))
        if mnk is None:
            continue
        m, n, k = mnk
        backend = labels.get("backend", "pallas-systolic")
        dtype = labels.get("dtype", "float32")
        method = labels.get("method", "unknown")
        sampled_us = float(h["mean"])
        model_us = roofline_seconds(m, n, k, dtype, chip.name) * 1e6
        model_ratio = sampled_us / model_us if model_us > 0 else float("inf")

        key = tune_cache.CacheKey(backend, chip.name, m, n, k, dtype, "none", 1)
        plan = cache.lookup(key)
        cached_us = cache_ratio = None
        stale = False
        recommendation = "ok"
        key_str: str | None = None
        if plan is not None:
            key_str = key.encode()
            if plan.method == method and plan.mean_us > 0 and sampled_us > 0:
                cached_us = float(plan.mean_us)
                hi, lo = max(sampled_us, cached_us), min(sampled_us, cached_us)
                cache_ratio = hi / lo
                stale = cache_ratio > 1.0 + threshold
                if stale:
                    recommendation = (
                        f"re-tune {key_str}: cached mean_us {cached_us:.1f} vs "
                        f"sampled {sampled_us:.1f} ({cache_ratio:.2f}x apart, "
                        f"threshold {1.0 + threshold:.2f}x)"
                    )
            else:
                recommendation = (
                    f"plan method {plan.method!r} != sample method {method!r}; "
                    "not comparable"
                )
        findings.append(
            DriftFinding(
                problem=labels.get("problem", ""),
                backend=backend,
                dtype=dtype,
                method=method,
                sampled_us=sampled_us,
                samples=int(h["count"]),
                model_us=model_us,
                model_ratio=model_ratio,
                cached_us=cached_us,
                cache_ratio=cache_ratio,
                threshold=threshold,
                stale=stale,
                key=key_str,
                recommendation=recommendation,
            )
        )
    return findings


def record_findings(
    findings: Iterable[DriftFinding],
    *,
    ledger=None,
    registry: _metrics.Registry | None = None,
    sha: str | None = None,
) -> int:
    """Persist stale findings: ``tune.plan.stale{key}`` counters plus one
    regression-ledger row per stale plan.  Returns the stale count."""
    if not _metrics.enabled():
        return sum(1 for f in findings if f.stale)
    reg = registry if registry is not None else _metrics.get_registry()
    n_stale = 0
    for f in findings:
        if not f.stale:
            continue
        n_stale += 1
        reg.inc("tune.plan.stale", 1, key=f.key or f.problem)
        if ledger is not None:
            ledger.record(
                "drift",
                {
                    "sampled_us": f.sampled_us,
                    "cached_us": f.cached_us,
                    "cache_ratio": f.cache_ratio,
                    "model_ratio": f.model_ratio,
                },
                variant=f.key or f.problem,
                dtype=f.dtype,
                sha=sha,
                meta={"method": f.method, "recommendation": f.recommendation},
            )
    return n_stale
