"""jit'd public wrapper for the systolic matmul kernel.

Handles block-plan derivation (balance equations from ``core.blocking``),
padding of non-divisible shapes, dtype policy, and interpret-mode fallback on
CPU.  This is the function ``repro.core.ops.matmul`` dispatches to when the
"pallas-systolic" backend is selected.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.core import hw
from repro.core.blocking import BlockPlan, derive_block_plan
from repro.core.blocking import round_up as _round_up
from repro.kernels._compat import auto_interpret as _auto_interpret
from repro.kernels.systolic import kernel as _kernel
from repro.obs import attribution as _obs
from repro.quant.qarray import DEFAULT_BLOCK_K, QArray, quantize_act, quantize_weight


def _clamp_plan(
    m: int,
    n: int,
    k: int,
    plan: BlockPlan | None,
    chip: hw.Chip | str | None = None,
    in_dtype: str | None = None,
) -> tuple[int, int, int]:
    """Choose (bm, bn, bk), shrinking to the (padded) problem if small.

    ``in_dtype`` sizes the derived plan's streams from the hw byte table
    (int8 streams fit twice the block of bf16); ignored when an explicit
    ``plan`` already carries its own sizing.
    """
    chip = hw.get_chip(chip)
    if plan is None:
        plan = derive_block_plan(
            max(m, chip.sublane_dim),
            max(n, chip.lane_dim),
            max(k, chip.lane_dim),
            in_dtype=in_dtype,
            chip=chip,
        )
    bm = min(plan.bm, _round_up(m, chip.sublane_dim))
    bn = min(plan.bn, _round_up(n, chip.lane_dim))
    bk = min(plan.bk, _round_up(k, chip.lane_dim))
    return bm, bn, bk


def _vmem_limit(
    m: int, n: int, k: int, blocks, in_dtype, out_dtype, chip, *, quant=False
) -> int:
    """The scoped-VMEM limit for running blocks (bm, bn, bk): the same
    ``BlockPlan`` accounting the fitter admitted the plan with."""
    bm, bn, bk = blocks
    return BlockPlan(
        m, n, k, bm, bn, bk,
        in_dtype=str(in_dtype),
        quant_block_k=bk if quant else 0,
        out_dtype_bytes=jnp.dtype(out_dtype).itemsize,
    ).vmem_limit_bytes(chip)


def _tuned_block(
    m: int, n: int, k: int, dtype, activation: str, chip: hw.Chip
) -> tuple[int, int, int] | None:
    """Consult the repro.tune plan cache; clamp a hit to the padded problem.

    Returns None on a miss (or if repro.tune is unavailable), in which case
    the analytical ``_clamp_plan`` heuristic takes over -- the autotuner is
    an accelerant, never a dependency.
    """
    try:
        from repro.tune import cache as tune_cache
    except ImportError:  # pragma: no cover
        return None
    return tune_cache.tuned_block("pallas-systolic", chip, m, n, k, dtype, activation)


@functools.partial(
    jax.jit,
    static_argnames=(
        "out_dtype", "activation", "bm", "bn", "bk", "interpret", "vmem_limit"
    ),
)
def _matmul_jit(
    a, b, bias, *, out_dtype, activation, bm, bn, bk, interpret, vmem_limit
):
    m, k = a.shape
    n = b.shape[1]
    mp, np_, kp = _round_up(m, bm), _round_up(n, bn), _round_up(k, bk)
    a_p = jnp.pad(a, ((0, mp - m), (0, kp - k))) if (mp != m or kp != k) else a
    b_p = jnp.pad(b, ((0, kp - k), (0, np_ - n))) if (kp != k or np_ != n) else b
    bias_p = None
    if bias is not None:
        bias_p = jnp.pad(bias, (0, np_ - n)) if np_ != n else bias
    y = _kernel.systolic_matmul_call(
        a_p,
        b_p,
        bias_p,
        bm=bm,
        bn=bn,
        bk=bk,
        out_dtype=out_dtype,
        activation=activation,
        interpret=interpret,
        vmem_limit_bytes=vmem_limit,
    )
    return y[:m, :n]


def matmul(
    a: jax.Array,
    b: jax.Array,
    bias: jax.Array | None = None,
    *,
    out_dtype=None,
    activation: str = "none",
    plan: BlockPlan | None = None,
    interpret: bool | None = None,
    chip: hw.Chip | str | None = None,
) -> jax.Array:
    """(M, K) @ (K, N) [+bias] [activation] via the 3D-blocked Pallas kernel.

    Block-plan priority: an explicit ``plan`` argument wins; otherwise a
    tuned plan from the ``repro.tune`` cache for this exact problem; finally
    the analytical balance-equation heuristic.
    """
    if a.ndim != 2 or b.ndim != 2:
        raise ValueError(f"expected 2D operands, got {a.shape} @ {b.shape}")
    if a.shape[1] != b.shape[0]:
        raise ValueError(f"contraction mismatch: {a.shape} @ {b.shape}")
    out_dtype = jnp.dtype(out_dtype or a.dtype)
    interpret = _auto_interpret() if interpret is None else interpret
    m, k = a.shape
    n = b.shape[1]
    chip = hw.get_chip(chip)
    blocks = _tuned_block(m, n, k, a.dtype, activation, chip) if plan is None else None
    bm, bn, bk = (
        blocks
        if blocks is not None
        else _clamp_plan(m, n, k, plan, chip, in_dtype=str(a.dtype))
    )
    _obs.record_gemm(
        m,
        n,
        k,
        dtype=a.dtype,
        backend="pallas-systolic",
        plan_source="explicit"
        if plan is not None
        else ("tuned" if blocks is not None else "heuristic"),
    )
    return _matmul_jit(
        a,
        b,
        bias,
        out_dtype=str(out_dtype),
        activation=activation,
        bm=bm,
        bn=bn,
        bk=bk,
        interpret=interpret,
        vmem_limit=_vmem_limit(m, n, k, (bm, bn, bk), a.dtype, out_dtype, chip),
    )


# ---------------------------------------------------------------------------
# Quantized matmul: QArray operands (or fp operands quantized on the fly)
# through the int8/fp8 systolic kernel (DESIGN.md §10).
# ---------------------------------------------------------------------------


def _row_scales(q: QArray, m: int, k: int) -> tuple[jax.Array, int]:
    """A-side scales expanded to per-row: (M, n_kblocks) fp32, plus the
    element k-granularity (0 sentinel = one scale block spans all of K)."""
    qm, qk = q.block
    s = q.scales  # (ceil(M/qm), ceil(K/qk))
    if qm > 1:
        s = jnp.repeat(s, qm, axis=-2)[:m]
    return s.astype(jnp.float32), (0 if s.shape[-1] == 1 else qk)


def _col_scales(q: QArray, k: int, n: int) -> tuple[jax.Array, int]:
    """B-side scales expanded to per-column: (n_kblocks, N) fp32."""
    qk, qn = q.block
    s = q.scales  # (ceil(K/qk), ceil(N/qn))
    if qn > 1:
        s = jnp.repeat(s, qn, axis=-1)[..., :n]
    return s.astype(jnp.float32), (0 if s.shape[-2] == 1 else qk)


@functools.partial(
    jax.jit,
    static_argnames=(
        "out_dtype",
        "activation",
        "bm",
        "bn",
        "bk",
        "qk_a",
        "qk_b",
        "interpret",
        "vmem_limit",
    ),
)
def _quant_matmul_jit(
    av, a_s, bv, b_s, *, out_dtype, activation, bm, bn, bk, qk_a, qk_b,
    interpret, vmem_limit,
):
    m, k = av.shape
    n = bv.shape[1]
    mp, np_, kp = _round_up(m, bm), _round_up(n, bn), _round_up(k, bk)
    # Values pad with 0 (their contribution is 0 under any scale); scale
    # arrays pad with 1 so the padded region never divides by zero.
    if (mp, kp) != (m, k):
        av = jnp.pad(av, ((0, mp - m), (0, kp - k)))
    if (kp, np_) != (k, n):
        bv = jnp.pad(bv, ((0, kp - k), (0, np_ - n)))
    qa_eff = kp if qk_a == 0 else qk_a
    qb_eff = kp if qk_b == 0 else qk_b
    ca = -(-kp // qa_eff)
    cb = -(-kp // qb_eff)
    a_s = jnp.pad(
        a_s, ((0, mp - m), (0, ca - a_s.shape[1])), constant_values=1.0
    )
    b_s = jnp.pad(
        b_s, ((0, cb - b_s.shape[0]), (0, np_ - n)), constant_values=1.0
    )
    y = _kernel.quant_systolic_matmul_call(
        av,
        a_s,
        bv,
        b_s,
        bm=bm,
        bn=bn,
        bk=bk,
        qk_a=qa_eff,
        qk_b=qb_eff,
        out_dtype=out_dtype,
        activation=activation,
        interpret=interpret,
        vmem_limit_bytes=vmem_limit,
    )
    return y[:m, :n]


def quant_matmul(
    a: jax.Array | QArray,
    b: jax.Array | QArray,
    *,
    qdtype: str = "int8",
    out_dtype=None,
    activation: str = "none",
    block_k: int = DEFAULT_BLOCK_K,
    plan: BlockPlan | None = None,
    interpret: bool | None = None,
    chip: hw.Chip | str | None = None,
) -> jax.Array:
    """(M, K) @ (K, N) through the block-scaled quantized systolic kernel.

    Operands may be pre-quantized ``QArray``s (weights usually are) or fp
    arrays quantized here (activations: per-row x per-``block_k`` scales).
    Block-plan priority matches the fp path -- explicit plan, then a tuned
    plan under the quantized dtype's own cache key, then the analytical
    heuristic sized for 1-byte streams -- with bk additionally clamped so a
    k-step never straddles a scale block.
    """
    if not isinstance(a, QArray):
        if a.ndim != 2:
            raise ValueError(f"expected 2D operand, got {a.shape}")
        a = quantize_act(a, qdtype, block_k=block_k)
    if not isinstance(b, QArray):
        if b.ndim != 2:
            raise ValueError(f"expected 2D operand, got {b.shape}")
        b = quantize_weight(b, qdtype, block_k=block_k)
    if a.ndim != 2 or b.ndim != 2:
        raise ValueError(f"expected 2D operands, got {a.shape} @ {b.shape}")
    if a.shape[1] != b.shape[0]:
        raise ValueError(f"contraction mismatch: {a.shape} @ {b.shape}")
    if a.values.dtype != b.values.dtype:
        raise ValueError(
            f"operand qdtypes differ: {a.values.dtype} vs {b.values.dtype}"
        )
    m, k = a.shape
    n = b.shape[1]
    out_dtype = jnp.dtype(out_dtype or jnp.bfloat16)
    interpret = _auto_interpret() if interpret is None else interpret
    chip = hw.get_chip(chip)
    dtype_name = str(a.values.dtype)

    blocks = (
        _tuned_block(m, n, k, dtype_name, activation, chip) if plan is None else None
    )
    if blocks is not None:
        bm, bn, bk = blocks
    else:
        bm, bn, bk = _clamp_plan(m, n, k, plan, chip, in_dtype=dtype_name)
    _obs.record_gemm(
        m,
        n,
        k,
        dtype=dtype_name,
        backend="pallas-systolic",
        plan_source="explicit"
        if plan is not None
        else ("tuned" if blocks is not None else "heuristic"),
    )
    a_s, qk_a = _row_scales(a, m, k)
    b_s, qk_b = _col_scales(b, k, n)
    # One k-step must sit inside one scale block on both operands.
    for qk in (qk_a, qk_b):
        if qk:
            bk = math.gcd(bk, qk)
    return _quant_matmul_jit(
        a.values,
        a_s,
        b.values,
        b_s,
        out_dtype=str(out_dtype),
        activation=activation,
        bm=bm,
        bn=bn,
        bk=bk,
        qk_a=qk_a,
        qk_b=qk_b,
        interpret=interpret,
        vmem_limit=_vmem_limit(
            m, n, k, (bm, bn, bk), dtype_name, out_dtype, chip, quant=True
        ),
    )
