"""Common layers: norms, rotary embeddings, embeddings, SwiGLU FFN.

Everything is functional: ``init_*`` returns a dict pytree of arrays,
``*_fwd`` applies it.  All dense projections route through
``repro.core.ops.matmul`` so the paper's GEMM substrate is framework-wide.

Weight-only quantization (DESIGN.md §10): ``repro.quant.quantize_params``
replaces projection weights with block-scaled ``QArray``s.  Every GEMM here
casts its weight through ``wcast``, which passes QArrays straight into
``ops.matmul`` -- where they dequantize at the GEMM (w8a16) or drive the
quantized systolic kernel (w8a8) -- so one params pytree serves fp and
quantized decode through identical layer code.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import ops
from repro.quant.qarray import QArray


def wcast(w, dtype):
    """Cast a (possibly quantized) projection weight for a GEMM.

    fp weights cast to the compute dtype; ``QArray`` weights pass through
    unchanged (their compute dtype is decided at the GEMM by
    ``core.ops.matmul``'s quantized dispatch).
    """
    if isinstance(w, QArray):
        return w
    return w.astype(dtype)


# Parameter leaves the layer code only ever reads as ``wcast`` /
# ``.astype(compute dtype)`` right before a contraction (projections, MLA's
# ``wkv_b``, MoE experts and router, embedding tables, output heads), named
# by their dict key.  Everything else -- norm scales, ``conv_w``/``conv_b``,
# SSM gates and decays, sLSTM's recurrent ``r_h`` -- is computed with in
# fp32 and is not listed.
COMPUTE_CAST_LEAVES = frozenset({
    "wq", "wk", "wv", "wo",  # GQA, mLSTM
    "wq_a", "wq_b", "wkv_a", "wkv_b",  # MLA
    "w_gate", "w_up", "w_down", "router",  # SwiGLU, MoE experts
    "w_if", "w_x", "in_proj", "out_proj",  # mLSTM, sLSTM, Mamba2
    "w", "w1", "w2",  # dense, lm_head, audio heads, vit projector
    "table", "tables",  # embeddings
})


@functools.partial(jax.jit, static_argnums=1)
def _astype(w, dtype):
    return w.astype(dtype)


def cast_for_compute(params, dtype):
    """A copy of ``params`` with every ``COMPUTE_CAST_LEAVES`` leaf in
    ``dtype``, so a step program's ``wcast``/``astype`` is a no-op.

    The arithmetic is unchanged: the layers cast these leaves to the compute
    dtype before every use, this only does it once.  Other leaves and
    ``QArray``s are passed through as they are.  One jitted cast per leaf,
    so the transient is one leaf's copy; the caller's arrays are neither
    donated nor modified.
    """
    dtype = jnp.dtype(dtype)

    def cast(path, leaf):
        if (
            not isinstance(leaf, QArray)
            and getattr(path[-1], "key", None) in COMPUTE_CAST_LEAVES
            and jnp.issubdtype(leaf.dtype, jnp.floating)
            and leaf.dtype != dtype
        ):
            return _astype(leaf, dtype)
        return leaf

    return jax.tree_util.tree_map_with_path(
        cast, params, is_leaf=lambda x: isinstance(x, QArray)
    )


def _dense_init(key, d_in: int, d_out: int, dtype=jnp.float32) -> jax.Array:
    scale = (d_in**-0.5)
    return (jax.random.normal(key, (d_in, d_out)) * scale).astype(dtype)


# -- RMSNorm -----------------------------------------------------------------


def init_rmsnorm(d: int) -> dict:
    return {"scale": jnp.ones((d,), jnp.float32)}


def rmsnorm(params: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps) * params["scale"]
    return y.astype(dtype)


# -- Rotary position embeddings ----------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    """Inverse frequencies for the (even) rotary dims."""
    return 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd), positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    rot = hd - hd % 2
    inv = rope_freqs(rot, theta)  # (rot/2,)
    ang = positions[..., None].astype(jnp.float32) * inv  # (..., S, rot/2)
    cos = jnp.cos(ang)[..., None, :]  # (..., S, 1, rot/2)
    sin = jnp.sin(ang)[..., None, :]
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    x1, x2 = jnp.split(x_rot.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), x_pass], axis=-1)


# -- Embedding ---------------------------------------------------------------


def init_embedding(key, vocab: int, d: int) -> dict:
    return {"table": jax.random.normal(key, (vocab, d)) * 0.02}


def embed(params: dict, tokens: jax.Array, compute_dtype) -> jax.Array:
    return params["table"].astype(compute_dtype)[tokens]


def unembed(params: dict, x: jax.Array) -> jax.Array:
    """Logits in fp32 (loss-stable)."""
    return ops.matmul(
        x, params["table"].astype(x.dtype).T, out_dtype=jnp.float32
    )


# -- SwiGLU FFN ---------------------------------------------------------------


def init_swiglu(key, d: int, d_ff: int) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": _dense_init(k1, d, d_ff),
        "w_up": _dense_init(k2, d, d_ff),
        "w_down": _dense_init(k3, d_ff, d),
    }


def swiglu(params: dict, x: jax.Array) -> jax.Array:
    dt = x.dtype
    gate = ops.matmul(x, wcast(params["w_gate"], dt))
    up = ops.matmul(x, wcast(params["w_up"], dt))
    return ops.matmul(jax.nn.silu(gate.astype(jnp.float32)).astype(dt) * up,
                      wcast(params["w_down"], dt))


# -- Dense (bias-free) projection ---------------------------------------------


def init_dense(key, d_in: int, d_out: int) -> dict:
    return {"w": _dense_init(key, d_in, d_out)}


def dense(params: dict, x: jax.Array) -> jax.Array:
    return ops.matmul(x, wcast(params["w"], x.dtype))
