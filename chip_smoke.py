#!/usr/bin/env python3
"""On-chip smoke test: the systolic GEMM and internlm2-1.8b serving on a TPU.

    python chip_smoke.py                # one chip: kernel + serve phases
    python chip_smoke.py --four-chips   # four chips: TP serving + collective matmuls

Runs in one process (a child could not get the chip its parent holds) and
refuses to run without a TPU: a CPU or interpret-mode run is not a chip run.

Phases on one chip:

* kernel -- the Pallas systolic GEMM, compiled, in bf16, at a paper problem
  (4096^3, ``configs.paper``) and at every internlm2-1.8b projection at
  prefill (256 tokens) and decode (4 slots), lm_head included.  Each result
  is held against ``jnp.dot(..., preferred_element_type=f32)`` on the chip,
  and each compiled program must contain a ``tpu_custom_call``: a kernel that
  fell back to interpret mode (``REPRO_INTERPRET=1``) fails here.
* serve -- internlm2-1.8b at its published widths, random weights from
  ``--seed``, 8 greedy requests over 4 slots through ``ContinuousScheduler``
  + ``ServeEngine`` under the "pallas-systolic" backend.  Every request must
  finish with its token count, no GEMM may trace on the "xla" backend, and
  one prompt's prefill logits must agree with the "xla" backend's.

``--four-chips`` runs only: internlm2-1.8b served tensor-parallel over a
(1, 4) ("data", "model") mesh, token for token against the same requests on
``jax.devices()[0]`` alone, and the all-gather / reduce-scatter collective
matmuls at 4096^3 against the single-device systolic kernel.

The last line of stdout is the JSON verdict, printed only when every phase
passed; any failure exits non-zero.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

ARCH = "internlm2-1.8b"
PREFILL_TOKENS = 256
SLOTS = 4
N_REQUESTS = 8
GEN_TOKENS = 16
PROMPT_LENS = (256, 128)  # alternating: two prefill shapes to compile

# Kernel vs jnp.dot: both accumulate in fp32; the kernel rounds its output to
# bf16 (relative error <= 2^-9 per element), so its max error is about 2^-9
# of max|ref| plus fp32 summation-order noise.  2^-7 leaves a 4x margin and is
# orders of magnitude below a wrong tile or a dropped k-step.
KERNEL_TOL = 2.0**-7
# Serve prefill logits, "pallas-systolic" vs "xla": same bf16 compute, but
# every GEMM's fp32 accumulation order differs, and the resulting one-ulp
# bf16 differences in activations compound over 24 layers.
LOGITS_TOL = 5e-2
# Collective matmuls vs the single-device kernel: both sides are fp32 sums
# rounded once to bf16, so they differ by at most one bf16 ulp (2^-8 of
# max|ref|) where the fp32 sums land on opposite sides of a rounding step.
COLLECTIVE_TOL = KERNEL_TOL


class PhaseError(AssertionError):
    """A check inside a phase failed."""


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise PhaseError(msg)


def _device_label(jax) -> str:
    d = jax.devices()
    return f"[{d[0].platform}:{d[0].device_kind} x{len(d)}]"


def _rel_err(got, ref) -> float:
    import numpy as np

    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    _check(bool(np.isfinite(got).all()), "non-finite values in the result")
    return float(np.max(np.abs(got - ref)) / max(float(np.max(np.abs(ref))), 1e-30))


def _timed(fn, *args, repeats: int = 3) -> float:
    """Median host seconds of fn(*args) to completion (already compiled)."""
    import jax

    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return sorted(times)[len(times) // 2]


def device_check(jax, n_chips: int) -> None:
    import importlib.metadata

    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(f"no TPU found: JAX's backend is {backend!r}")
    devs = jax.devices()
    if len(devs) < n_chips:
        raise SystemExit(f"need {n_chips} TPU chips, JAX sees {len(devs)}")
    import jaxlib

    print(
        f"device: platform={devs[0].platform} kind={devs[0].device_kind!r} "
        f"count={len(devs)} | jax {jax.__version__} jaxlib {jaxlib.__version__} "
        f"libtpu {importlib.metadata.version('libtpu')}"
    )


def gemm_problems(cfg) -> list[tuple[str, int, int, int]]:
    """(name, M, N, K): one paper problem and internlm2's serving GEMMs."""
    from repro.configs.paper import PAPER_MATRIX_SIZES
    from repro.serving.engine import decode_gemm_problems

    d2 = 4096
    assert d2 in PAPER_MATRIX_SIZES["G"]
    probs = {(d2, d2, d2): f"paper-{d2}^3"}
    for phase, m in (("prefill", PREFILL_TOKENS), ("decode", SLOTS)):
        for name, mm, n, k in decode_gemm_problems(cfg, m):
            probs.setdefault((mm, n, k), f"{phase}-{name}")
    probs[(SLOTS, cfg.vocab_size, cfg.d_model)] = "decode-lm_head"
    return [(name, *mnk) for mnk, name in probs.items()]


def kernel_phase(jax, label: str, seed: int) -> None:
    import jax.numpy as jnp

    from repro import configs
    from repro.kernels.systolic import ops as systolic_ops

    ref_dot = jax.jit(lambda a, b: jnp.dot(a, b, preferred_element_type=jnp.float32))
    key = jax.random.PRNGKey(seed)
    for name, m, n, k in gemm_problems(configs.get_config(ARCH)):
        ka, kb, key = jax.random.split(key, 3)
        a = jax.random.normal(ka, (m, k), jnp.bfloat16)
        b = jax.random.normal(kb, (k, n), jnp.bfloat16)
        t0 = time.perf_counter()
        compiled = jax.jit(lambda x, w: systolic_ops.matmul(x, w)).lower(a, b).compile()
        compile_s = time.perf_counter() - t0
        _check(
            "tpu_custom_call" in compiled.as_text(),
            f"{name}: no tpu_custom_call in the compiled GEMM (interpret mode?)",
        )
        err = _rel_err(compiled(a, b), ref_dot(a, b))
        _check(err <= KERNEL_TOL, f"{name}: max rel err {err:.3g} > {KERNEL_TOL:.3g}")
        secs = _timed(compiled, a, b)
        print(
            f"{label} kernel {name} {m}x{n}x{k} bf16: max_rel_err={err:.3g} "
            f"(tol {KERNEL_TOL:.3g}) tpu_custom_call=yes compile_s={compile_s:.2f} "
            f"median_s={secs:.6f} tflops={2 * m * n * k / secs / 1e12:.2f}"
        )


def make_trace(cfg, seed: int) -> list[dict]:
    """N_REQUESTS batch-1 prompts of alternating PROMPT_LENS, GEN_TOKENS each,
    arriving every other scheduler tick."""
    from repro.data.synthetic import make_prompt

    return [
        {
            "rid": i,
            "arrival": 2.0 * i,
            "prompt": make_prompt(
                cfg, seq=PROMPT_LENS[i % len(PROMPT_LENS)], seed=seed + 1 + i
            ),
            "max_new_tokens": GEN_TOKENS,
        }
        for i in range(N_REQUESTS)
    ]


def serve(model, params, trace, *, mesh=None):
    """Drive the trace through ContinuousScheduler + ServeEngine; returns
    (results, summary, total seconds, engine, scheduler)."""
    from repro.serving import (
        ContinuousScheduler,
        ServeConfig,
        ServeEngine,
        requests_from_trace,
    )

    max_len = max(t["prompt"]["tokens"].shape[1] + t["max_new_tokens"] for t in trace)
    engine = ServeEngine(
        model, params, ServeConfig(max_len=max_len, batch=SLOTS), mesh=mesh
    )
    sched = ContinuousScheduler(engine)
    t0 = time.perf_counter()
    results = sched.run(requests_from_trace(trace))
    total = time.perf_counter() - t0
    for t in trace:
        got = results[t["rid"]]
        _check(
            len(got) == t["max_new_tokens"],
            f"request {t['rid']}: {len(got)} tokens, expected {t['max_new_tokens']}",
        )
        _check(
            bool(((got >= 0) & (got < model.cfg.vocab_size)).all()),
            f"request {t['rid']}: token ids out of range",
        )
    return results, sched.stats.summary(), total, engine, sched


def _gemm_calls(backend: str) -> float:
    from repro import obs

    counters = obs.get_registry().snapshot()["counters"]
    return sum(
        v
        for series, v in counters.items()
        if series.startswith("gemm.calls{") and f'backend="{backend}"' in series
    )


def serve_phase(jax, label: str, seed: int) -> None:
    import numpy as np

    from repro import configs
    from repro.core import ops
    from repro.models.registry import get_model

    cfg = configs.get_config(ARCH)
    model = get_model(cfg)
    t0 = time.perf_counter()
    params = jax.block_until_ready(model.init(jax.random.PRNGKey(seed)))
    print(f"{label} serve {ARCH}: {model.n_params / 1e9:.3f}B params fp32, "
          f"init_s={time.perf_counter() - t0:.2f}")
    trace = make_trace(cfg, seed)

    xla_before = _gemm_calls("xla")
    pallas_before = _gemm_calls("pallas-systolic")
    with ops.use_backend("pallas-systolic"):
        results, s, total, engine, _ = serve(model, params, trace)
    n_pallas = _gemm_calls("pallas-systolic") - pallas_before
    n_xla = _gemm_calls("xla") - xla_before
    _check(n_pallas > 0, "serving traced no pallas-systolic GEMM")
    _check(n_xla == 0, f"serving traced {n_xla:g} GEMMs on the xla backend")
    warmup_s = total - s["run_wall_s"]
    stats = jax.devices()[0].memory_stats() or {}
    print(
        f"{label} serve {ARCH} pallas-systolic: {len(results)}/{len(trace)} "
        f"requests finished, {s['tokens_out']} tokens, tok_per_s={s['tok_per_s']} "
        f"ttft_p50_ms={s['ttft_p50_ms']} itl_p50_ms={s['itl_p50_ms']} "
        f"run_wall_s={s['run_wall_s']} warmup_s={warmup_s:.2f} "
        f"(compiles + one run of each program) "
        f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
        f"gemm_traces pallas={n_pallas:g} xla={n_xla:g}"
    )
    print(f"{label} sample tokens (request 0): {results[0].tolist()}")

    prompt = trace[0]["prompt"]
    logits = {}
    for backend in ("pallas-systolic", "xla"):
        with ops.use_backend(backend):
            fn = jax.jit(lambda p, b: model.prefill(p, b, max_len=engine.scfg.max_len)[0])
            logits[backend] = np.asarray(fn(params, prompt), np.float32)
    err = _rel_err(logits["pallas-systolic"], logits["xla"])
    same_top1 = bool(
        (logits["pallas-systolic"].argmax(-1) == logits["xla"].argmax(-1)).all()
    )
    _check(err <= LOGITS_TOL, f"prefill logits pallas vs xla: {err:.3g} > {LOGITS_TOL}")
    print(
        f"{label} prefill logits ({prompt['tokens'].shape[1]} tokens) pallas-systolic "
        f"vs xla: max_rel_err={err:.3g} (tol {LOGITS_TOL}) same_top1={same_top1}"
    )


def _spread(tree) -> float:
    """Fraction of the tree's bytes held in arrays sharded over >1 device."""
    import jax

    leaves = jax.tree.leaves(tree)
    total = sum(x.nbytes for x in leaves)
    split = sum(
        x.nbytes
        for x in leaves
        if len(x.sharding.device_set) > 1 and not x.sharding.is_fully_replicated
    )
    return split / max(total, 1)


def four_chip_phase(jax, label: str, seed: int) -> None:
    import jax.numpy as jnp

    from repro import configs
    from repro.distributed import collective_matmul as cm
    from repro.kernels.systolic import ops as systolic_ops
    from repro.launch.mesh import make_local_mesh
    from repro.models.registry import get_model

    mesh = make_local_mesh(1, 4)
    # fp32 at full matmul precision: the two arms then differ only in fp32
    # summation order (~1e-6), far below the gap between the top two logits,
    # so greedy tokens must agree exactly.
    cfg = dataclasses.replace(configs.get_config(ARCH), dtype="float32")
    model = get_model(cfg)
    params = jax.block_until_ready(model.init(jax.random.PRNGKey(seed)))
    trace = make_trace(cfg, seed)
    with jax.default_matmul_precision("highest"):
        ref, _, _, _, _ = serve(model, params, trace)
        got, s, _, engine, sched = serve(model, params, trace, mesh=mesh)
    for t in trace:
        _check(
            (ref[t["rid"]] == got[t["rid"]]).all(),
            f"request {t['rid']}: TP tokens {got[t['rid']].tolist()} != "
            f"one-chip tokens {ref[t['rid']].tolist()}",
        )
    p_spread = _spread(engine.params)
    c_spread = _spread(sched.pool.cache)
    in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in jax.devices()[:4]]
    print(
        f"{label} serve {ARCH} fp32 TP=4 vs one chip: {len(trace)} requests, "
        f"{s['tokens_out']} tokens identical | sharded bytes: params "
        f"{p_spread:.1%}, kv cache {c_spread:.1%} | bytes_in_use per device {in_use}"
    )
    _check(p_spread > 0.9, f"only {p_spread:.1%} of param bytes are sharded")
    _check(c_spread > 0.9, f"only {c_spread:.1%} of KV cache bytes are sharded")
    _check(
        all(b is not None and b > 1e9 for b in in_use[1:]),
        f"devices 1-3 hold {in_use[1:]} bytes: the model did not spread",
    )

    d2 = 4096
    ka, kb = jax.random.split(jax.random.PRNGKey(seed))
    a = jax.random.normal(ka, (d2, d2), jnp.bfloat16)
    b = jax.random.normal(kb, (d2, d2), jnp.bfloat16)
    single = systolic_ops.matmul(a, b)
    one_secs = _timed(systolic_ops.matmul, a, b)
    for name, fn in (
        ("all_gather_matmul", cm.all_gather_matmul),
        ("reduce_scatter_matmul", cm.reduce_scatter_matmul),
    ):
        # Jitted as callers use it: eager shard_map traces and compiles anew
        # on every call (7.6 s a call on the chip), which times the compiler.
        sharded = jax.jit(lambda x, w, fn=fn: fn(x, w, mesh=mesh))
        y = sharded(a, b)
        _check(
            len(y.sharding.device_set) == 4,
            f"{name}: output on {len(y.sharding.device_set)} devices, expected 4",
        )
        err = _rel_err(y, single)
        _check(err <= COLLECTIVE_TOL, f"{name}: max rel err {err:.3g} > {COLLECTIVE_TOL:.3g}")
        secs = _timed(sharded, a, b)
        print(
            f"{label} {name} {d2}^3 bf16 over 4 chips vs one-chip systolic: "
            f"max_rel_err={err:.3g} (tol {COLLECTIVE_TOL:.3g}) "
            f"output spec {y.sharding.spec} median_s={secs:.6f} "
            f"(one chip {one_secs:.6f})"
        )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--four-chips",
        action="store_true",
        help="run only the four-chip phase (TP serving + collective matmuls)",
    )
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    import jax

    n_chips = 4 if args.four_chips else 1
    device_check(jax, n_chips)
    print(f"compile cache: {cache_dir}")
    label = _device_label(jax)
    if args.four_chips:
        phases = [("four_chips", four_chip_phase)]
    else:
        phases = [("kernel", kernel_phase), ("serve", serve_phase)]
    failed = []
    for name, phase in phases:
        t0 = time.perf_counter()
        try:
            phase(jax, label, args.seed)
        except Exception:
            traceback.print_exc()
            failed.append(name)
            print(f"phase {name}: FAILED after {time.perf_counter() - t0:.1f}s")
        else:
            print(f"phase {name}: ok in {time.perf_counter() - t0:.1f}s")
    if failed:
        print(f"failed phases: {failed}", file=sys.stderr)
        return 1
    d = jax.devices()
    print(json.dumps({
        "ok": True,
        "device": {"platform": d[0].platform, "kind": d[0].device_kind, "count": len(d)},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
