"""Plain fp32 references and the comparison that decides ``correct``.

Each family (``bench/families/<family>.py``) writes its forward pass with the
pieces here, in straightforward ``jax.numpy`` and float32, with every
contraction at ``Precision.HIGHEST`` (a float32 matmul on a TPU otherwise
runs in bf16 passes).  Nothing here imports the program.

The comparison, for a served model: run the reference once over a request's
prompt and the tokens it was served, and read at each served position how far
the served token's logit lies below the reference's best (the *gap*).  Under
greedy decoding a sound program serves the reference's best token or one
within rounding of it; a wrong token lies far below.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512  # query rows per attention block: bounds the score temporaries


def dot(spec: str, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST, preferred_element_type=jnp.float32)


def rmsnorm(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, positions, theta):
    """Rotary embedding on the last axis, halves convention (first half
    rotates against the second).  x: (S, H, d), positions: (S,)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def causal_attention(q, k, v, scale, groups: int = 1):
    """Softmax attention of every query row over the keys at or before it.

    q: (S, H, dq); k: (S, Hk, dq); v: (S, Hk, dv) with H = Hk * groups (query
    head h reads key head h // groups).  S must be a multiple of Q_BLOCK.
    Computed one block of query rows at a time.
    """
    s, h, dq = q.shape
    hk = k.shape[1]
    qb = q.reshape(s // Q_BLOCK, Q_BLOCK, hk, groups, dq)
    kpos = jnp.arange(s)

    def block(args):
        i, qblk = args
        sc = dot("qgpd,tgd->gpqt", qblk, k) * scale
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.where(kpos[None, :] <= qpos[:, None], sc, -jnp.inf)
        w = jax.nn.softmax(sc, axis=-1)
        return dot("gpqt,tgd->qgpd", w, v)

    out = jax.lax.map(block, (jnp.arange(s // Q_BLOCK), qb))
    return out.reshape(s, h, v.shape[-1])


def swiglu(x, w_gate, w_up, w_down):
    g = dot("sd,df->sf", x, w_gate)
    u = dot("sd,df->sf", x, w_up)
    return dot("sf,fd->sd", jax.nn.silu(g) * u, w_down)


def pad_len(n: int) -> int:
    return -(-n // Q_BLOCK) * Q_BLOCK


@functools.partial(jax.jit, static_argnums=(0,))
def _gaps(hidden_fn, weights, tokens, rows, served):
    """Per served token: the reference's best logit minus the served one's.

    tokens: (T,) prompt + served tokens, zero-padded; rows: (R,) positions
    whose next-token logits served ``served`` (R,); rows < 0 are padding.
    """
    h = hidden_fn(weights, tokens)  # (T, d), final-normed
    hr = h[jnp.maximum(rows, 0)]
    logits = dot("rd,dv->rv", hr, weights["lm_head"]["w"].astype(jnp.float32))
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0]
    return jnp.where(rows >= 0, best - got, 0.0)


def served_gaps(hidden_fn, weights, prompt, served, t_pad: int, r_pad: int):
    """Gaps of one request's served tokens (numpy, length len(served)).

    ``hidden_fn(weights, tokens)`` is the family's forward pass to the final
    norm, made once per configuration.  Sequences are padded
    to ``t_pad`` tokens and ``r_pad`` served tokens, so one compile serves a
    whole cell.
    """
    import numpy as np

    p, n = len(prompt), len(served)
    if p + n - 1 > t_pad or n > r_pad:
        raise ValueError(f"request of {p}+{n} tokens exceeds the padding {t_pad}/{r_pad}")
    tokens = np.zeros(t_pad, np.int32)
    tokens[:p] = prompt
    tokens[p : p + n - 1] = served[:-1]
    rows = np.full(r_pad, -1, np.int32)
    rows[:n] = np.arange(p - 1, p + n - 1)
    srv = np.zeros(r_pad, np.int32)
    srv[:n] = served
    with jax.default_matmul_precision("highest"):
        g = _gaps(hidden_fn, weights, jnp.asarray(tokens), jnp.asarray(rows), jnp.asarray(srv))
    return np.asarray(g)[:n]
