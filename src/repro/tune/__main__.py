"""CLI for the autotuner.

    PYTHONPATH=src python -m repro.tune --m 512 --n 512 --k 512

First run measures the fitter survivors and persists the winner; the second
run for the same problem reports a cache hit.  ``--list`` dumps the cache.
"""

from __future__ import annotations

import argparse
import sys


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="repro.tune",
        description="Empirical block-plan autotuner (the measured half of Table I).",
    )
    p.add_argument("--m", type=int, default=512)
    p.add_argument("--n", type=int, default=512)
    p.add_argument("--k", type=int, default=512)
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--activation", default="none")
    p.add_argument("--tp", type=int, default=1,
                   help="'model'-axis mesh degree: tune the per-shard problem "
                        "of the tp-way collective matmul (cache key carries tp)")
    p.add_argument("--backend", default="pallas-systolic")
    p.add_argument("--chip", default=None, help="registry name (default: current)")
    p.add_argument("--top-k", type=int, default=8, dest="top_k",
                   help="measure at most this many fitter survivors")
    p.add_argument("--repeats", type=int, default=3)
    p.add_argument("--warmup", type=int, default=1)
    p.add_argument("--method", default="auto",
                   choices=("auto", "device-wall", "interpret-wall", "xla-proxy"))
    p.add_argument("--cache", default=None,
                   help="cache file (default: $REPRO_TUNE_CACHE or ~/.cache)")
    p.add_argument("--force", action="store_true",
                   help="re-measure even on a cache hit")
    p.add_argument("--list", action="store_true", dest="list_entries",
                   help="print cache entries and exit")
    return p


def main(argv: list[str] | None = None) -> int:
    parser = build_parser()
    args = parser.parse_args(argv)

    from repro.core import hw
    from repro.tune import autotune
    from repro.tune.cache import PlanCache, default_cache

    if args.chip is not None:
        try:
            hw.get_chip(args.chip)
        except KeyError:
            parser.error(
                f"unknown chip {args.chip!r}; registered: {hw.chip_names()}"
            )

    cache = PlanCache(args.cache) if args.cache else default_cache()

    if args.list_entries:
        entries = cache.items()
        print(f"# cache {cache.path} ({len(entries)} entries)")
        for key, plan in entries:
            print(f"{key} -> {plan.bm}x{plan.bn}x{plan.bk} "
                  f"best={plan.best_us:.1f}us mean={plan.mean_us:.1f}us "
                  f"[{plan.method} x{plan.repeats}]")
        return 0

    result = autotune(
        args.m, args.n, args.k,
        dtype=args.dtype,
        activation=args.activation,
        backend=args.backend,
        chip=args.chip,
        top_k=args.top_k,
        repeats=args.repeats,
        warmup=args.warmup,
        method=args.method,
        cache=cache,
        force=args.force,
        tp=args.tp,
    )

    key = result.key
    print(f"# problem  {key.backend} {key.chip} "
          f"M={key.m} N={key.n} K={key.k} {key.dtype} act={key.activation} "
          f"tp={key.tp}")
    if result.cache_hit:
        print("# cache hit -- no measurement performed (use --force to re-tune)")
    else:
        print(f"# measured {len(result.records)} fitter survivors "
              f"[{result.winner.method}]")
        for rec in result.records:
            print(f"  {rec.ident:>16}  measured={rec.measured_us:10.1f}us  "
                  f"analytical={rec.analytical_us:8.1f}us  ai={rec.arithmetic_intensity:.0f}")
    w = result.winner
    print(f"winner {w.bm}x{w.bn}x{w.bk}  best={w.best_us:.1f}us  "
          f"mean={w.mean_us:.1f}us  method={w.method}")
    print(f"cache  {cache.path}")
    if key.chip != hw.get_chip(None).name:
        # Dispatch looks plans up under the process-default chip; a plan
        # tuned for another target is invisible until the default matches.
        print(f"note   dispatch serves chip={hw.get_chip(None).name!r} here; "
              f"this plan serves on a {key.chip} TPU (or REPRO_CHIP={key.chip} "
              f"off TPU)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
