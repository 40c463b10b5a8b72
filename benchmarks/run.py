"""Benchmark entry point: one function per paper table.

    PYTHONPATH=src python -m benchmarks.run [table1|table2|table6|roofline|tune|serve|tp]

With ``--ledger PATH`` (or ``REPRO_LEDGER=PATH`` in the environment) every
``BENCH {json}`` row each table prints is also appended to the JSONL
regression ledger at PATH, keyed by (git sha, bench, variant, chip, dtype);
``python -m repro.obs ledger compare --ledger PATH`` then gates the run
against its previous recording (DESIGN.md §12, CI ``ledger-gate`` job).

  table1    DSE over block shapes: analytical fitter/roofline columns plus
            the measured-time column (the f_max analogue) from repro.tune
  table2    scaling
  table6    baseline comparison
  roofline  roofline report over the model zoo
  tune      autotuner report: measured winner vs analytical best per GEMM
            problem, served from the repro.tune plan cache when warm
  serve     continuous vs synchronized batching on one ragged Poisson trace:
            tokens/s, p50/p99 step latency, mean slot occupancy (the serving
            analogue of the paper's DSP-utilisation column); BENCH JSON lines
  serve_long  long-prompt adversarial trace, monolithic vs chunked prefill:
            p99 decode-tick latency must improve under chunking while
            per-request outputs stay identical; BENCH JSON lines
  serve_paged  paged KV + prefix reuse vs the reserved-stripe pool on one
            shared-prefix (system-prompt) trace: prefix-hit TTFT p50 must
            beat no-reuse, peak live paged bytes must undercut the stripe's
            reservation, outputs bit-identical across arms; BENCH JSON lines
  tp        tensor-parallel GEMM on a forced 8-device mesh: overlapped
            collective matmul vs gather-then-matmul vs single-device
            (subprocess -- the device-count flag must precede jax init);
            BENCH JSON lines
  quant     quantized vs bf16 GEMM (dtype-aware model + measured numbers;
            asserts the model predicts int8 >= 1.5x bf16) and fp vs
            w8a16/kv8 serve tok/s on one small trace; BENCH JSON lines
  obs       telemetry self-measurement: serve trace with recording disabled
            vs enabled (overhead budget < 3% tok/s), plus the enabled run's
            MFU / roofline residual / plan hit rate / TTFT / KV bytes and
            structural validation of snapshot + Chrome trace; BENCH JSON
  check     static analysis: repro.check lint + contract-auditor finding
            counts and audit coverage (plans verified, dispatch paths
            traced) so the ledger tracks the tree staying clean; BENCH JSON
"""

from __future__ import annotations

import os
import sys
import time


def _ledger_path(argv: list[str]) -> tuple[str | None, list[str]]:
    """Extract ``--ledger PATH`` from argv (REPRO_LEDGER as fallback)."""
    path = os.environ.get("REPRO_LEDGER") or None
    rest: list[str] = []
    i = 0
    while i < len(argv):
        if argv[i] == "--ledger":
            if i + 1 >= len(argv):
                raise SystemExit("--ledger needs a PATH argument")
            path = argv[i + 1]
            i += 2
            continue
        if argv[i].startswith("--ledger="):
            path = argv[i].split("=", 1)[1]
            i += 1
            continue
        rest.append(argv[i])
        i += 1
    return path, rest


def main() -> None:
    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()
    from benchmarks import (
        check_report,
        obs_report,
        quant_matmul,
        roofline_report,
        serve_paged,
        serve_throughput,
        table1_dse,
        table2_scaling,
        table6_baseline,
        tp_matmul,
        tune_report,
    )

    tables = {
        "table1": table1_dse.run,
        "table2": table2_scaling.run,
        "table6": table6_baseline.run,
        "roofline": roofline_report.run,
        "tune": tune_report.run,
        "serve": serve_throughput.run,
        "serve_long": serve_throughput.run_longprompt,
        "serve_paged": serve_paged.run,
        "tp": tp_matmul.run,
        "quant": quant_matmul.run,
        "obs": obs_report.run,
        "check": check_report.run,
    }
    ledger_path, want = _ledger_path(sys.argv[1:])
    want = want or list(tables)
    ledger = None
    if ledger_path:
        from repro.obs import ledger as obs_ledger

        ledger = obs_ledger.Ledger(ledger_path)
    for name in want:
        t0 = time.perf_counter()
        rows = tables[name]()
        dt = time.perf_counter() - t0
        print(f"# === {name} ({dt:.1f}s) ===")
        for r in rows:
            print(r)
        if ledger is not None:
            from repro.obs import ledger as obs_ledger

            n = obs_ledger.record_bench_rows(ledger, name, rows)
            if n:
                print(f"# ledger: {n} entries -> {ledger.path}")
        print()


if __name__ == "__main__":
    main()
