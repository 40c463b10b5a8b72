"""jit'd public wrapper for the grouped (per-expert) matmul kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import hw
from repro.core.blocking import BlockPlan
from repro.core.blocking import round_up as _round_up
from repro.kernels._compat import auto_interpret as _auto_interpret
from repro.kernels.grouped import kernel as _kernel
from repro.obs import attribution as _obs


def _tuned_block(c: int, n: int, k: int, dtype, chip) -> tuple[int, int, int] | None:
    """Tuned, problem-clamped (bc, bn, bk) for the per-expert problem."""
    try:
        from repro.tune import cache as tune_cache
    except ImportError:  # pragma: no cover
        return None
    return tune_cache.tuned_block("pallas-grouped", chip, c, n, k, dtype)


@functools.partial(
    jax.jit,
    static_argnames=("out_dtype", "bc", "bn", "bk", "interpret", "vmem_limit"),
)
def _grouped_jit(x, w, *, out_dtype, bc, bn, bk, interpret, vmem_limit):
    e, c, k = x.shape
    n = w.shape[2]
    cp, np_, kp = _round_up(c, bc), _round_up(n, bn), _round_up(k, bk)
    if (cp, kp) != (c, k):
        x = jnp.pad(x, ((0, 0), (0, cp - c), (0, kp - k)))
    if (kp, np_) != (k, n):
        w = jnp.pad(w, ((0, 0), (0, kp - k), (0, np_ - n)))
    y = _kernel.grouped_matmul_call(
        x, w, bc=bc, bn=bn, bk=bk, out_dtype=out_dtype, interpret=interpret,
        vmem_limit_bytes=vmem_limit,
    )
    return y[:, :c, :n]


def grouped_matmul(
    x: jax.Array,
    w: jax.Array,
    *,
    out_dtype=None,
    bc: int | None = None,
    bn: int | None = None,
    bk: int | None = None,
    interpret: bool | None = None,
    chip: hw.Chip | str | None = None,
) -> jax.Array:
    """y[e] = x[e] @ w[e] for all experts e.

    x: (E, C, K) capacity-dispatched tokens; w: (E, K, N) expert weights.
    Block priority per dim: explicit argument, then a ``repro.tune`` cache
    entry for the per-expert (C, K) @ (K, N) problem, then the heuristic
    default capped at the (padded) per-expert problem size.
    """
    if x.ndim != 3 or w.ndim != 3 or x.shape[0] != w.shape[0]:
        raise ValueError(f"bad grouped shapes {x.shape} @ {w.shape}")
    if x.shape[2] != w.shape[1]:
        raise ValueError(f"contraction mismatch {x.shape} @ {w.shape}")
    chip = hw.get_chip(chip)
    e, c, k = x.shape
    n = w.shape[2]
    out_dtype = jnp.dtype(out_dtype or x.dtype)
    plan_source = "explicit"
    if not (bc and bn and bk):  # fully explicit blocks skip the cache lookup
        tuned = _tuned_block(c, n, k, x.dtype, chip)
        plan_source = "tuned" if tuned is not None else "heuristic"
        if tuned is not None:
            bc, bn, bk = bc or tuned[0], bn or tuned[1], bk or tuned[2]
    # m = E*C: the grouped problem's FLOP count is 2*(E*C)*N*K.
    _obs.record_gemm(
        e * c, n, k, dtype=x.dtype, backend="pallas-grouped", plan_source=plan_source
    )
    bc = bc or min(512, _round_up(c, chip.sublane_dim))
    bn = bn or min(512, _round_up(n, chip.lane_dim))
    bk = bk or min(1024, _round_up(k, chip.lane_dim))
    interpret = _auto_interpret() if interpret is None else interpret
    # Each expert step holds the systolic kernel's working set: size the
    # compiler's VMEM limit by the same plan accounting.
    vmem_limit = BlockPlan(
        c, n, k, bc, bn, bk, in_dtype=str(x.dtype), out_dtype_bytes=out_dtype.itemsize
    ).vmem_limit_bytes(chip)
    return _grouped_jit(
        x, w, out_dtype=str(out_dtype), bc=bc, bn=bn, bk=bk, interpret=interpret,
        vmem_limit=vmem_limit,
    )
