"""Grouped (per-expert) systolic matmul -- the MoE expert GEMM.

Applies the same 3D blocking discipline as ``kernels/systolic`` to the
batched problem y[e] = x[e] @ w[e]: grid (E, C/bc, N/bn, K/bk) with the
expert index as an outer *parallel* grid dimension.  This is what the
capacity-based MoE dispatch in ``models/moe.py`` lowers its expert compute
to; on the EP mesh axis each chip runs the kernel over its local experts.

Beyond-paper extension of the paper's grid: the paper's 3D grid gains a
fourth, trivially-parallel expert dimension; all balance equations are
unchanged because each expert slice is an independent (C, K, N) matmul.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._compat import tpu_compiler_params


def _grouped_kernel(x_ref, w_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[0], w_ref[0], preferred_element_type=jnp.float32
    )

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def grouped_matmul_call(
    x: jax.Array,
    w: jax.Array,
    *,
    bc: int,
    bn: int,
    bk: int,
    out_dtype,
    interpret: bool = False,
    vmem_limit_bytes: int | None = None,
) -> jax.Array:
    """x: (E, C, K), w: (E, K, N) -> (E, C, N); blocks must divide."""
    e, c, k = x.shape
    e2, k2, n = w.shape
    assert e == e2 and k == k2, (x.shape, w.shape)
    assert c % bc == 0 and n % bn == 0 and k % bk == 0
    grid = (e, c // bc, n // bn, k // bk)

    x_spec = pl.BlockSpec((1, bc, bk), lambda ee, i, j, kk: (ee, i, kk))
    w_spec = pl.BlockSpec((1, bk, bn), lambda ee, i, j, kk: (ee, kk, j))
    o_spec = pl.BlockSpec((1, bc, bn), lambda ee, i, j, kk: (ee, i, j))

    params = tpu_compiler_params(
        ("parallel", "parallel", "parallel", "arbitrary"), vmem_limit_bytes
    )
    cost = pl.CostEstimate(
        flops=2 * e * c * k * n,
        bytes_accessed=x.size * x.dtype.itemsize * (n // bn)
        + w.size * w.dtype.itemsize * (c // bc)
        + e * c * n * jnp.dtype(out_dtype).itemsize,
        transcendentals=0,
    )
    return pl.pallas_call(
        functools.partial(_grouped_kernel, n_k=grid[3]),
        grid=grid,
        in_specs=[x_spec, w_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((e, c, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bc, bn), jnp.float32)],
        compiler_params=params,
        cost_estimate=cost,
        interpret=interpret,
        name=f"grouped_mmm_e{e}_{bc}x{bn}x{bk}",
    )(x, w)
