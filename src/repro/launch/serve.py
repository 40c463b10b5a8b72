"""Serving launcher: synchronized batched prefill+decode, or trace-driven
continuous batching.

Synchronized (fixed batch, all slots in lockstep)::

    PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b --smoke \
        --batch 4 --prompt-len 64 --gen 32

Continuous batching (Poisson arrivals, ragged prompt/gen lengths; the
scheduler keeps refilling freed slots so the matmul units stay busy)::

    PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b --smoke \
        --continuous --requests 16 --slots 4 --rate 0.5

Chunked prefill (``--chunked-prefill``): each admitted prompt is split into
bucketed fixed-size chunks (``--chunk-size``, default 128) and one chunk is
co-scheduled per tick alongside the regular decode step, so a long prompt no
longer stalls every decoding slot for a whole prompt forward (compare the
``p99_tick_ms`` column against a run without the flag)::

    PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b --smoke \
        --continuous --chunked-prefill --chunk-size 16 --requests 16 --slots 4

Quantized serving (``--quantize``, DESIGN.md §10): ``w8a16`` quantizes the
projection weights to block-scaled int8 (dequantized at each GEMM),
``w8a8`` additionally quantizes activations per token and runs the narrow
systolic kernel, ``kv8`` keeps the continuous-batching KV pool resident in
int8 with per-head-per-slot scales::

    PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b --smoke \
        --continuous --quantize kv8 --requests 16 --slots 4

Paged KV cache (``--paged``, DESIGN.md §13): the continuous pool swaps the
per-slot ``max_len`` stripe for fixed-size pages behind a per-slot page
table, so resident KV bytes track tokens actually held; ``--prefix-cache``
adds the radix prefix cache on top, so requests sharing a prompt prefix map
the same refcounted pages and skip that part of prefill (watch the
``prefix_hits`` / ``kv_bytes_live`` summary fields)::

    PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b --smoke \
        --continuous --paged --page-size 16 --prefix-cache --requests 16

Tensor-parallel decode (either mode): ``--model-parallel N`` runs the engine
over a (1, N) ("data", "model") mesh -- params TP-sharded by the
``distributed.sharding`` rules, caches sharded by GSPMD propagation.  Keep
N <= the arch's head count (shard heads, not head_dim; the engine warns
otherwise).  On CPU, fake the devices first::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b --smoke \
        --model-parallel 4 --batch 4 --prompt-len 64 --gen 32
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import time

import jax

from repro import configs
from repro.data.synthetic import (
    make_adversarial_trace,
    make_batch,
    make_request_trace,
)
from repro.launch.compile_cache import configure_compile_cache
from repro.models.registry import get_model
from repro.serving import (
    ContinuousScheduler,
    ServeConfig,
    ServeEngine,
    requests_from_trace,
)


def _dump_metrics(
    metrics_dir: str,
    extra_registry=None,
    extra: dict | None = None,
    name: str = "snapshot.json",
):
    """Write the merged metrics snapshot to ``metrics_dir/<name>``
    (process-wide dispatch registry + the scheduler's private registry)."""
    from repro import obs

    regs = [obs.get_registry()]
    if extra_registry is not None:
        regs.append(extra_registry)
    doc = obs.snapshot_doc(*regs, extra=extra)
    os.makedirs(metrics_dir, exist_ok=True)
    path = os.path.join(metrics_dir, name)
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
    return path


def _prune_tick_snapshots(metrics_dir: str, keep: int) -> None:
    """Keep only the newest ``keep`` periodic ``snapshot-<tick>.json`` files
    (the final merged ``snapshot.json`` is never pruned)."""
    ticks = sorted(
        f
        for f in os.listdir(metrics_dir)
        if f.startswith("snapshot-") and f.endswith(".json")
    )
    for stale in ticks[:-keep] if keep > 0 else ticks:
        with contextlib.suppress(OSError):
            os.remove(os.path.join(metrics_dir, stale))


def _dump_trace(metrics_dir: str) -> str:
    from repro import obs

    path = os.path.join(metrics_dir, "trace.json")
    obs.get_tracer().export_chrome(path)
    return path


def _build_engine(model, params, args, max_len: int, batch: int) -> ServeEngine:
    mesh = None
    if args.model_parallel > 1:
        from repro.launch.mesh import make_local_mesh

        mesh = make_local_mesh(1, args.model_parallel)
        print(f"tensor-parallel mesh: 1x{args.model_parallel} ('data', 'model')")
    return ServeEngine(
        model,
        params,
        ServeConfig(
            max_len=max_len,
            batch=batch,
            temperature=args.temperature,
            seed=args.seed,
        ),
        mesh=mesh,
    )


def run_synchronized(model, params, args) -> None:
    cfg = model.cfg
    max_len = args.prompt_len + args.gen + (
        cfg.n_patches if cfg.frontend == "vit" else 0
    )
    engine = _build_engine(model, params, args, max_len, args.batch)
    prompts = make_batch(
        cfg, batch=args.batch, seq=args.prompt_len, kind="prefill", seed=args.seed
    )

    t0 = time.perf_counter()
    first = engine.prefill(prompts)
    jax.block_until_ready(first)
    t_pf = time.perf_counter() - t0
    print(f"prefill {args.batch}x{args.prompt_len} in {t_pf*1e3:.1f} ms")

    # The first decode step absorbs the compile; steady-state throughput is
    # measured over the remaining gen-2 steps only (never past max_len).
    pieces = [first]
    if args.gen >= 2:
        t0 = time.perf_counter()
        warm = engine.decode(first, 1)
        jax.block_until_ready(warm)
        t_compile = time.perf_counter() - t0
        pieces.append(warm)
        print(f"decode compile+first step {t_compile*1e3:.1f} ms")
    n_steady = args.gen - 2
    if n_steady > 0:
        t0 = time.perf_counter()
        out = engine.decode(pieces[-1], n_steady)
        jax.block_until_ready(out)
        t_dec = time.perf_counter() - t0
        pieces.append(out)
        toks = args.batch * n_steady
        print(
            f"steady-state {toks/max(t_dec,1e-9):.1f} tok/s "
            f"({t_dec/n_steady*1e3:.2f} ms/step over {n_steady} steps)"
        )
    print(engine.decode_plan_report())
    sample = jax.numpy.concatenate(pieces, axis=1)
    print("sample tokens:", sample[0, :16].tolist())
    if args.metrics_dir:
        print("metrics snapshot:", _dump_metrics(args.metrics_dir))
        print("chrome trace:", _dump_trace(args.metrics_dir))


def run_continuous(model, params, args) -> None:
    cfg = model.cfg
    if args.adversarial:
        # The long-prompt worst case: short requests decode steadily, one
        # long prompt lands mid-run.  The trace SLO budgets are meant to
        # trip on (--slo-ttft-ms / --slo-itl-ms acceptance demo).
        trace = make_adversarial_trace(
            cfg,
            n_short=max(1, args.requests - args.long_requests),
            short_prompt=args.mean_prompt,
            short_gen=args.mean_gen,
            long_prompt=args.prompt_len,
            n_long=args.long_requests,
            shared_prefix=args.shared_prefix,
            seed=args.seed,
        )
    else:
        trace = make_request_trace(
            cfg,
            n_requests=args.requests,
            mean_prompt=args.mean_prompt,
            mean_gen=args.mean_gen,
            rate=args.rate,
            seed=args.seed,
            max_prompt=args.prompt_len,
            max_gen=args.gen,
        )
    prefix = cfg.n_patches if cfg.frontend == "vit" else 0
    max_len = (
        max(t["prompt"]["tokens"].shape[1] + t["max_new_tokens"] for t in trace)
        + prefix
    )
    engine = _build_engine(model, params, args, max_len, args.slots)
    slo = None
    if args.slo_ttft_ms or args.slo_itl_ms or args.slo_queue_wait_ms:
        from repro import obs

        slo = obs.SLOSpec(
            ttft_ms=args.slo_ttft_ms,
            itl_ms=args.slo_itl_ms,
            queue_wait_ms=args.slo_queue_wait_ms,
        )
        print(f"slo budgets: {slo.describe()}")
    sched = ContinuousScheduler(
        engine,
        policy=args.policy,
        chunked_prefill=args.chunked_prefill,
        chunk_size=args.chunk_size,
        chunk_budget=args.chunk_budget,
        quantize_kv=args.quantize == "kv8",
        paged=args.paged,
        page_size=args.page_size,
        n_pages=args.pages,
        prefix_cache=args.prefix_cache,
        slo=slo,
    )
    if args.metrics_dir:
        # Flight recorder (DESIGN.md §12): postmortem bundles on SLO
        # violation or engine exception, snapshotting both registries.
        from repro import obs

        sched.flight_recorder = obs.FlightRecorder(
            args.metrics_dir,
            registries=(obs.get_registry(), sched.stats.registry),
        )
    on_tick = None
    if args.metrics_dir:
        interval = max(1, args.metrics_interval)
        keep = max(1, args.metrics_keep)

        def on_tick(s) -> None:
            if s.tick % interval == 0:
                _dump_metrics(
                    args.metrics_dir,
                    s.stats.registry,
                    extra=s.stats.summary(),
                    name=f"snapshot-{s.tick:06d}.json",
                )
                _prune_tick_snapshots(args.metrics_dir, keep)

    results = sched.run(requests_from_trace(trace), on_tick=on_tick)

    from repro.obs import profile as _obs_profile

    if _obs_profile.get_profiler().active():
        # Drift probe (DESIGN.md §15): re-measure this run's decode GEMM
        # problems off the serving path, then hold the samples against the
        # tune cache + analytical model.  Findings land in the registry
        # (tune.plan.stale{key}) before the final snapshot below, so
        # ``obs doctor`` sees them; REPRO_LEDGER also records them.
        from repro.obs import drift as _drift
        from repro.obs import metrics as _obs_metrics

        probe = _drift.probe_decode_plans(engine)
        snap = _obs_metrics.get_registry().snapshot()
        findings = _drift.check_drift(snap)
        ledger = None
        ledger_path = os.environ.get("REPRO_LEDGER")
        if ledger_path:
            from repro.obs.ledger import Ledger

            ledger = Ledger(ledger_path)
        n_stale = _drift.record_findings(findings, ledger=ledger)
        print(
            f"drift probe: {len(probe)} decode GEMMs re-measured, "
            f"{n_stale} stale plan(s)"
        )
        for f in findings:
            if f.stale:
                print(f"  STALE {f.recommendation}")

    s = sched.stats.summary()
    mode = f"{args.policy}+chunked" if args.chunked_prefill else args.policy
    print(
        f"continuous[{mode}] {args.requests} requests over "
        f"{s['ticks']} ticks ({s['idle_ticks']} idle, "
        f"{s['prefill_chunks']} prefill chunks) | "
        f"{s['tokens_out']} tokens, {s['tok_per_s']:.1f} tok/s | "
        f"step latency p50 {s['p50_step_ms']:.2f} ms / p99 {s['p99_step_ms']:.2f} ms | "
        f"tick latency p50 {s['p50_tick_ms']:.2f} ms / p99 {s['p99_tick_ms']:.2f} ms | "
        f"mean slot occupancy {s['mean_occupancy']:.2%}"
    )
    if sched.paged:
        print(
            f"paged kv: {sched.pool.pages_in_use}/{sched.pool.n_pages} pages "
            f"in use at drain, page size {sched.pool.page_size} | "
            f"prefix hits {s['prefix_hits']} ({s['prefix_hit_tokens']} tokens "
            f"of prefill skipped) | preempted {s['preempted']} | "
            f"kv bytes live {s['kv_bytes_live']}"
        )
    if slo is not None:
        print(
            f"slo: {s['requests_conformant']}/{s['requests_finished']} requests "
            f"conformant, {s['slo_violations']} violations | goodput "
            f"{s['goodput_toks']} toks, {s['goodput_tok_per_s']:.1f} tok/s "
            f"(raw {s['tok_per_s']:.1f})"
        )
        fr = sched.flight_recorder
        if fr is not None and fr.paths:
            print(f"postmortem bundles: {len(fr.paths)} in {args.metrics_dir}"
                  + (f" ({fr.suppressed} suppressed)" if fr.suppressed else ""))
    print(engine.decode_plan_report())
    rid0 = min(results)
    print(f"sample tokens (request {rid0}):", results[rid0][:16].tolist())
    if args.metrics_dir:
        # Final snapshot carries the run summary (MFU, TTFT/ITL, KV bytes)
        # in "extra" alongside the raw registry series.
        print(
            "metrics snapshot:",
            _dump_metrics(args.metrics_dir, sched.stats.registry, extra=s),
        )
        print("chrome trace:", _dump_trace(args.metrics_dir))
        print(f"diagnose: python -m repro.obs doctor {args.metrics_dir}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=configs.ALL_ARCHS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--model-parallel",
        type=int,
        default=1,
        metavar="N",
        help="tensor-parallel degree: serve over a (1, N) ('data', 'model') "
        "mesh (needs N visible devices; on CPU set "
        "XLA_FLAGS=--xla_force_host_platform_device_count=N)",
    )
    # continuous-batching mode
    ap.add_argument(
        "--continuous",
        action="store_true",
        help="trace-driven continuous batching (Poisson arrivals, ragged lengths)",
    )
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--rate", type=float, default=0.5, help="arrivals per decode step")
    ap.add_argument("--mean-prompt", type=int, default=24)
    ap.add_argument("--mean-gen", type=int, default=12)
    ap.add_argument(
        "--policy",
        choices=ContinuousScheduler.POLICIES,
        default="continuous",
        help="'gang' reproduces synchronized batching for comparison",
    )
    ap.add_argument(
        "--chunked-prefill",
        action="store_true",
        help="split prompts into bucketed chunks and co-schedule one chunk "
        "per tick with the decode step (keeps decode latency flat under "
        "long prompts)",
    )
    ap.add_argument(
        "--chunk-size",
        type=int,
        default=128,
        help="prefill chunk length (remainders bucket to powers of two)",
    )
    ap.add_argument(
        "--chunk-budget",
        type=int,
        default=1,
        help="max prefill chunks per scheduler tick",
    )
    ap.add_argument(
        "--paged",
        action="store_true",
        help="paged KV cache (DESIGN.md §13): fixed-size pages behind a "
        "per-slot page table instead of the per-slot max_len stripe "
        "(continuous mode, attention families only)",
    )
    ap.add_argument(
        "--page-size",
        type=int,
        default=16,
        metavar="ROWS",
        help="KV rows per page (--paged)",
    )
    ap.add_argument(
        "--pages",
        type=int,
        default=None,
        metavar="N",
        help="page arena size; default slots * ceil(max_len / page_size) "
        "(undersize it to exercise prefix reclaim + preemption)",
    )
    ap.add_argument(
        "--prefix-cache",
        action="store_true",
        help="radix prefix cache over --paged: requests sharing a prompt "
        "prefix attach the same refcounted pages and prefill only their "
        "suffix",
    )
    ap.add_argument(
        "--long-requests",
        type=int,
        default=1,
        metavar="N",
        help="--adversarial: long prompts arriving in the mid-run burst",
    )
    ap.add_argument(
        "--shared-prefix",
        type=int,
        default=0,
        metavar="TOKENS",
        help="--adversarial: identical leading tokens across the long "
        "prompts (exercises --prefix-cache under page pressure)",
    )
    ap.add_argument(
        "--quantize",
        choices=("none", "w8a16", "w8a8", "kv8"),
        default="none",
        help="quantized serving (DESIGN.md §10): w8a16 = int8 weight-only "
        "(weights dequantize at each GEMM), w8a8 = int8 weights AND dynamic "
        "per-token int8 activations through the quantized systolic kernel, "
        "kv8 = int8 KV-cache pool with per-head-per-slot scales "
        "(continuous mode only)",
    )
    ap.add_argument(
        "--metrics-dir",
        default=None,
        help="dump obs telemetry here (DESIGN.md §11-12): final snapshot.json "
        "+ periodic snapshot-<tick>.json (continuous mode, keep-last-K), "
        "trace.json (Chrome trace_event timeline), and postmortem-*.json "
        "flight-recorder bundles on SLO violations; validate with "
        "python -m repro.obs <files>",
    )
    ap.add_argument(
        "--metrics-interval",
        type=int,
        default=50,
        metavar="TICKS",
        help="ticks between periodic snapshot-<tick>.json dumps "
        "(continuous mode; the final merged snapshot.json is always written)",
    )
    ap.add_argument(
        "--metrics-keep",
        type=int,
        default=16,
        metavar="K",
        help="keep only the newest K periodic snapshot-<tick>.json files",
    )
    ap.add_argument(
        "--adversarial",
        action="store_true",
        help="replace the Poisson trace with the long-prompt adversarial "
        "trace (requests-1 short requests at tick 0 + one --prompt-len "
        "prompt mid-run; continuous mode only)",
    )
    # SLO budgets (DESIGN.md §12): per-request latency budgets; goodput
    # counts only tokens from requests that met every configured budget.
    ap.add_argument(
        "--slo-ttft-ms",
        type=float,
        default=None,
        help="TTFT budget (admission -> first token), milliseconds",
    )
    ap.add_argument(
        "--slo-itl-ms",
        type=float,
        default=None,
        help="inter-token latency budget (gap between a request's "
        "consecutive tokens, co-scheduled prefill stalls included), ms",
    )
    ap.add_argument(
        "--slo-queue-wait-ms",
        type=float,
        default=None,
        help="queue-wait budget (eligible -> slot granted), milliseconds",
    )
    ap.add_argument(
        "--profile-sample-rate",
        type=float,
        default=None,
        metavar="RATE",
        help="measured profiling (DESIGN.md §15): sample this fraction of "
        "kernel/collective/KV-pool dispatches with block_until_ready timing "
        "windows, and run the drift probe at end of run (continuous mode). "
        "0 disables; default $REPRO_PROFILE_RATE or 0",
    )
    args = ap.parse_args()
    configure_compile_cache()

    if args.profile_sample_rate is not None:
        from repro.obs import profile as _obs_profile

        _obs_profile.configure(args.profile_sample_rate)
        if args.profile_sample_rate > 0:
            print(f"profiling: sample rate {args.profile_sample_rate}")

    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get_config(args.arch)
    model = get_model(cfg)
    key = jax.random.PRNGKey(args.seed)
    params = model.init(key)

    act_ctx = contextlib.nullcontext()
    if args.quantize in ("w8a16", "w8a8"):
        from repro import quant

        params = quant.quantize_params(params)
        n_q, q_bytes = quant.count_quantized(params)
        print(
            f"quantize[{args.quantize}]: {n_q} projection weights -> int8 "
            f"({q_bytes / 1e6:.1f} MB resident values)"
        )
        if args.quantize == "w8a8":
            act_ctx = quant.use_act_quant("int8")
    elif args.quantize == "kv8" and not args.continuous:
        import warnings

        warnings.warn("--quantize kv8 applies to the continuous-batching "
                      "KV pool; ignored in synchronized mode")

    with act_ctx:
        if args.continuous:
            run_continuous(model, params, args)
        else:
            run_synchronized(model, params, args)


if __name__ == "__main__":
    main()
