"""Tensor-parallel GEMM: overlapped collective matmul vs its baselines.

The paper's Table I justifies the third array dimension by utilisation: how
busy does a geometry keep the compute.  The mesh-level analogue compares, on
one problem and one mesh, the three ways of running a TP-sharded GEMM:

  single      one device, the plain Pallas systolic matmul (no mesh);
  gather      unoverlapped baseline: ``lax.all_gather`` the full A, then one
              per-shard block matmul (the collective stalls the array);
  overlapped  the collective matmul of ``distributed.collective_matmul``:
              tp ring steps, each ``ppermute`` hop issued under the previous
              block matmul.

One ``BENCH {json}`` line per mode carries best/mean wall time, achieved
GFLOP/s, and an allclose check against the single-device reference.  On an
``--xla_force_host_platform_device_count=8`` CPU mesh the collectives are
memcpys, so "overlapped >= gather" is a sanity floor; on a real TPU mesh the
gap is the hidden ICI time.

On a TPU, ``run()`` (the ``benchmarks.run`` entry) measures in its own
process over the attached chips: the parent already holds them, so a child
could not get them.  Off TPU it re-executes this module in a subprocess
with the forced-device-count flag, which must precede the first jax call;
invoking the module directly inherits whatever devices the environment
already has::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m benchmarks.tp_matmul
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

DEFAULT_TP = 8


def run(tp: int = DEFAULT_TP) -> list[str]:
    """benchmarks.run entry: in process over the TPU's own devices, else a
    subprocess with the forced-device-count flag."""
    import jax

    if jax.default_backend() == "tpu":
        tp = min(tp, len(jax.devices()))
        lines = _bench(_parser().parse_args(["--tp", str(tp)]))
        if any(ln.startswith("FAIL") for ln in lines):
            raise RuntimeError("tp_matmul failed:\n" + "\n".join(lines))
        return lines
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={tp}"
    env["PYTHONPATH"] = (
        os.path.abspath("src") + os.pathsep + env.get("PYTHONPATH", "")
    )
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.tp_matmul", "--tp", str(tp)],
        capture_output=True,
        text=True,
        env=env,
        timeout=1200,
    )
    if out.returncode != 0:
        raise RuntimeError(f"tp_matmul subprocess failed:\n{out.stderr[-3000:]}")
    return [ln for ln in out.stdout.splitlines() if ln.strip()]


def _time_best(fn, *args, repeats: int = 5) -> tuple[float, float]:
    """(best_s, mean_s) of ``fn(*args)`` after one warmup/compile call."""
    import jax

    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return min(times), sum(times) / len(times)


def _parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tp", type=int, default=DEFAULT_TP)
    ap.add_argument("--m", type=int, default=2048)
    ap.add_argument("--n", type=int, default=1024)
    ap.add_argument("--k", type=int, default=512)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--repeats", type=int, default=5)
    return ap


def _bench(args) -> list[str]:
    """BENCH lines for each mode, then a FAIL or WARN line if one applies."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.distributed import collective_matmul as cm
    from repro.kernels.systolic import ops as systolic_ops
    from repro.launch.mesh import make_mesh

    n_dev = len(jax.devices())
    if n_dev < args.tp:
        raise SystemExit(
            f"need {args.tp} devices, have {n_dev}; set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={args.tp}"
        )
    mesh = make_mesh((args.tp,), ("model",))
    dtype = jnp.dtype(args.dtype)
    a = jax.random.normal(jax.random.PRNGKey(0), (args.m, args.k)).astype(dtype)
    b = jax.random.normal(jax.random.PRNGKey(1), (args.k, args.n)).astype(dtype)
    flops = 2 * args.m * args.n * args.k

    # Same per-shard block plan for both sharded modes (one grid step per
    # ring hop: bm = M/tp, bn = N/tp, bk = K) so the comparison isolates the
    # collective schedule, not the tiling.
    block = (args.m // args.tp, args.n // args.tp, args.k)

    def single(x, w):
        return systolic_ops.matmul(x, w)

    def gather(x, w):
        return cm.all_gather_matmul(x, w, mesh=mesh, overlap=False, block=block)

    def overlapped(x, w):
        return cm.all_gather_matmul(x, w, mesh=mesh, overlap=True, block=block)

    ref = np.asarray(single(a, b), np.float32)
    tol = 1e-4 if dtype == jnp.float32 else 1e-1
    lines = []
    for mode, fn in (("single", single), ("gather", gather), ("overlapped", overlapped)):
        y = np.asarray(fn(a, b), np.float32)
        ok = bool(np.allclose(y, ref, rtol=tol, atol=tol))
        best, mean = _time_best(jax.jit(fn), a, b, repeats=args.repeats)
        lines.append(
            "BENCH "
            + json.dumps(
                {
                    "bench": "tp_matmul",
                    "mode": mode,
                    "tp": 1 if mode == "single" else args.tp,
                    "m": args.m,
                    "n": args.n,
                    "k": args.k,
                    "dtype": str(dtype),
                    "best_ms": round(best * 1e3, 3),
                    "mean_ms": round(mean * 1e3, 3),
                    "gflops": round(flops / best / 1e9, 2),
                    "allclose_vs_single": ok,
                }
            )
        )
    rows = {json.loads(ln[len("BENCH "):])["mode"]: json.loads(ln[len("BENCH "):])
            for ln in lines}
    if not all(r["allclose_vs_single"] for r in rows.values()):
        lines.append("FAIL: sharded result diverged from the single-device reference")
    elif rows["overlapped"]["best_ms"] > rows["gather"]["best_ms"] * 1.1:
        # >10% slower than the unoverlapped baseline means the overlap
        # machinery itself is costing time -- that is a regression signal,
        # not noise.
        lines.append("WARN: overlapped slower than gather-then-matmul baseline")
    return lines


def _main(argv=None) -> int:
    lines = _bench(_parser().parse_args(argv))
    for ln in lines:
        print(ln)
    return 1 if any(ln.startswith("FAIL") for ln in lines) else 0


if __name__ == "__main__":
    sys.exit(_main())
