"""Pallas TPU kernel for the 3D-blocked systolic matmul (paper Def. 2/4).

Mapping (see DESIGN.md §2): the paper's PE grid (d_i0, d_j0, d_k0) becomes the
VMEM block triple (bm, bn, bk); its dot-product-unit width d_p is the MXU's
native 128; its two-level blocking becomes the Pallas grid
(M/bm, N/bn, K/bk).  Where the FPGA was forced to run k *slowest* (no II=1
accumulation across iterations), the MXU accumulates freely, so we run k
*innermost* with a C-stationary fp32 accumulator in VMEM scratch -- the
adaptation documented in DESIGN.md §9.2.

The optional fused epilogue (bias + activation) is a beyond-paper extension:
it removes one full write+read of the (M, N) output against HBM for every
FFN projection, directly attacking the roofline memory term.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._compat import tpu_compiler_params

ACTIVATIONS: dict[str, Callable[[jax.Array], jax.Array]] = {
    "none": lambda x: x,
    "relu": jax.nn.relu,
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
    "tanh": jnp.tanh,
}


def _mmm_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k: int, activation: str):
    """One (bm, bn) grid step at contraction block k = program_id(2).

    The paper's Listing 2 inner body: multiply-accumulate one (bm, bk) x
    (bk, bn) tile pair.  ``acc_ref`` is the C-stationary fp32 accumulator
    (the FPGA version streams these partials through its k 'layers'
    instead -- see DESIGN.md).
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == n_k - 1)
    def _epilogue():
        o_ref[...] = ACTIVATIONS[activation](acc_ref[...]).astype(o_ref.dtype)


def _mmm_bias_kernel(a_ref, b_ref, bias_ref, o_ref, acc_ref, *, n_k, activation):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == n_k - 1)
    def _epilogue():
        y = acc_ref[...] + bias_ref[...].astype(jnp.float32)
        o_ref[...] = ACTIVATIONS[activation](y).astype(o_ref.dtype)


def systolic_matmul_call(
    a: jax.Array,
    b: jax.Array,
    bias: jax.Array | None,
    *,
    bm: int,
    bn: int,
    bk: int,
    out_dtype,
    activation: str = "none",
    interpret: bool = False,
    vmem_limit_bytes: int | None = None,
) -> jax.Array:
    """Raw pallas_call wrapper; shapes must already divide the blocks.

    a: (M, K), b: (K, N), bias: (N,) or None -> (M, N).
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (
        (m, n, k),
        (bm, bn, bk),
    )
    if activation not in ACTIVATIONS:
        raise ValueError(f"unknown activation {activation!r}")
    grid = (m // bm, n // bn, k // bk)

    # Index maps: A blocks walk (i, k), B blocks walk (k, j), C blocks (i, j).
    a_spec = pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk))
    b_spec = pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j))
    o_spec = pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j))

    cost = pl.CostEstimate(
        flops=2 * m * n * k,
        bytes_accessed=(
            a.size * a.dtype.itemsize * grid[1]
            + b.size * b.dtype.itemsize * grid[0]
            + m * n * jnp.dtype(out_dtype).itemsize
        ),
        transcendentals=0,
    )
    params = tpu_compiler_params(
        ("parallel", "parallel", "arbitrary"), vmem_limit_bytes
    )

    if bias is None:
        kernel = functools.partial(_mmm_kernel, n_k=grid[2], activation=activation)
        in_specs = [a_spec, b_spec]
        operands = (a, b)
    else:
        assert bias.shape == (n,), bias.shape
        kernel = functools.partial(
            _mmm_bias_kernel, n_k=grid[2], activation=activation
        )
        bias_spec = pl.BlockSpec((1, bn), lambda i, j, kk: (0, j))
        in_specs = [a_spec, b_spec, bias_spec]
        operands = (a, b, bias.reshape(1, n))

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=params,
        cost_estimate=cost,
        interpret=interpret,
        name=f"systolic_mmm_{bm}x{bn}x{bk}_{activation}",
    )(*operands)


# ---------------------------------------------------------------------------
# Quantized variant: int8 x int8 -> int32 (fp8 -> fp32) block dots with the
# block scales applied as each k-step's partial retires into the fp32
# accumulator -- the DSP-packing analogue (DESIGN.md §10).  The scale
# granularity along K (``qk_a``/``qk_b``) is a property of the QArray; the
# dispatcher clamps the kernel's bk so one k-step never straddles a scale
# boundary, which is what lets a *single* fp32 multiply per (bm, bn) block
# apply the whole step's scales.
# ---------------------------------------------------------------------------


def _qdot(a: jax.Array, b: jax.Array) -> jax.Array:
    """One quantized block dot -> fp32.  int8 accumulates exactly in int32
    (the paper's packed-DSP integer MACs); fp8 widens to fp32 first -- the
    MXU consumes fp8 natively, interpret/XLA need the upcast, and the
    result is bit-identical either way (fp8 values are exact in fp32)."""
    if a.dtype == jnp.int8:
        return jnp.dot(a, b, preferred_element_type=jnp.int32).astype(
            jnp.float32
        )
    return jnp.dot(
        a.astype(jnp.float32),
        b.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )


def _qmm_kernel(
    a_ref, as_ref, b_ref, bs_ref, o_ref, acc_ref, *, n_k: int, activation: str
):
    """Quantized (bm, bn) grid step at contraction block k = program_id(2).

    ``as_ref``: (bm, 1) per-row scales of this step's k scale block;
    ``bs_ref``: (1, bn) per-column scales.  Their outer product is the
    dequantization factor of the whole (bm, bk) x (bk, bn) partial, so the
    narrow dot retires into the fp32 accumulator with one fused multiply.
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    part = _qdot(a_ref[...], b_ref[...])
    acc_ref[...] += part * as_ref[...] * bs_ref[...]

    @pl.when(k == n_k - 1)
    def _epilogue():
        o_ref[...] = ACTIVATIONS[activation](acc_ref[...]).astype(o_ref.dtype)


def quant_systolic_matmul_call(
    a: jax.Array,
    a_scales: jax.Array,
    b: jax.Array,
    b_scales: jax.Array,
    *,
    bm: int,
    bn: int,
    bk: int,
    qk_a: int,
    qk_b: int,
    out_dtype,
    activation: str = "none",
    interpret: bool = False,
    vmem_limit_bytes: int | None = None,
) -> jax.Array:
    """Raw quantized pallas_call; shapes must already divide the blocks.

    a: (M, K) int8/fp8 values, a_scales: (M, K // qk_a) fp32 per-row
    per-k-block scales; b: (K, N) values, b_scales: (K // qk_b, N).  The
    dispatcher pre-expands coarser row/column granularities to per-row /
    per-column, so the kernel sees exactly one scale layout.  ``qk_a`` /
    ``qk_b`` must be multiples of ``bk`` (one scale block spans >= one
    k-step), which the dispatcher guarantees by clamping bk.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (
        (m, n, k),
        (bm, bn, bk),
    )
    assert qk_a % bk == 0 and qk_b % bk == 0, (qk_a, qk_b, bk)
    # Scale arrays carry ceil(K/qk) blocks (the last may be partial when the
    # padded K is not a quant-block multiple; padded values are 0 there).
    assert a_scales.shape == (m, -(-k // qk_a)), (a_scales.shape, (m, k, qk_a))
    assert b_scales.shape == (-(-k // qk_b), n), (b_scales.shape, (k, n, qk_b))
    if activation not in ACTIVATIONS:
        raise ValueError(f"unknown activation {activation!r}")
    grid = (m // bm, n // bn, k // bk)

    a_spec = pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk))
    b_spec = pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j))
    # Scale blocks advance once per *quant* block, not per k-step: the index
    # map lands k-step kk inside scale block (kk * bk) // qk.  The scale
    # block index leads a 3-D layout and is squeezed out of the window, so
    # the kernel's (bm, 1) / (1, bn) windows span their array's whole unit
    # dimension -- the only way a width-1 block meets Mosaic's (8, 128)
    # tiling rule.
    a_s3 = a_scales.T.reshape(a_scales.shape[1], m, 1)
    b_s3 = b_scales.reshape(b_scales.shape[0], 1, n)
    as_spec = pl.BlockSpec(
        (None, bm, 1), lambda i, j, kk: ((kk * bk) // qk_a, i, 0)
    )
    bs_spec = pl.BlockSpec(
        (None, 1, bn), lambda i, j, kk: ((kk * bk) // qk_b, 0, j)
    )
    o_spec = pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j))

    cost = pl.CostEstimate(
        flops=2 * m * n * k,
        bytes_accessed=(
            a.size * a.dtype.itemsize * grid[1]
            + b.size * b.dtype.itemsize * grid[0]
            + a_scales.size * 4 * grid[1]
            + b_scales.size * 4 * grid[0]
            + m * n * jnp.dtype(out_dtype).itemsize
        ),
        transcendentals=0,
    )
    return pl.pallas_call(
        functools.partial(_qmm_kernel, n_k=grid[2], activation=activation),
        grid=grid,
        in_specs=[a_spec, as_spec, b_spec, bs_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=tpu_compiler_params(
            ("parallel", "parallel", "arbitrary"), vmem_limit_bytes
        ),
        cost_estimate=cost,
        interpret=interpret,
        name=f"systolic_qmm_{a.dtype.name}_{bm}x{bn}x{bk}_{activation}",
    )(a, a_s3, b, b_s3)
