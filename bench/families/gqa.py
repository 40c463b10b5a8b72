"""Dense decoder with grouped-query attention and a SwiGLU FFN (InternLM2).

Per layer: x += Wo·attn(rope(Wq·n(x)), rope(Wk·n(x)), Wv·n(x)), then
x += W_down·(silu(W_gate·n(x)) * W_up·n(x)), with n an RMSNorm; a final
RMSNorm and an untied output head.  Query head h reads key/value head
h // (heads / kv_heads).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.lib import reference as ref


def arch_config(cfg: dict):
    """The program's ``ArchConfig`` for this configuration file."""
    from repro.models.config import ArchConfig

    return ArchConfig(
        name=cfg["name"],
        family="dense",
        n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"],
        head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
        attention="gqa",
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        dtype=cfg["compute_dtype"],
    ).validate()


def weight_spec(cfg: dict) -> dict:
    """The parameter tree, leaves ("normal", shape, std) or ("ones", shape)."""
    L, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    h, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, ff, v = d // h, cfg["intermediate_size"], cfg["vocab_size"]

    def dense(*shape):
        return ("normal", shape, shape[-2] ** -0.5)

    return {
        "final_norm": {"scale": ("ones", (d,))},
        "embed": {"table": ("normal", (v, d), 0.02)},
        "lm_head": {"w": dense(d, v)},
        "layers": {
            "attn_norm": {"scale": ("ones", (L, d))},
            "ffn_norm": {"scale": ("ones", (L, d))},
            "attn": {
                "wq": dense(L, d, h * hd),
                "wk": dense(L, d, hk * hd),
                "wv": dense(L, d, hk * hd),
                "wo": dense(L, h * hd, d),
            },
            "ffn": {
                "w_gate": dense(L, d, ff),
                "w_up": dense(L, d, ff),
                "w_down": dense(L, ff, d),
            },
        },
    }


def hidden_fn(cfg: dict):
    """Reference forward of a (T,) token sequence to the final-normed (T, d)."""
    h, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // h
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])

    def fwd(w, tokens):
        f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
        x = f32(w["embed"]["table"])[tokens]
        s = x.shape[0]
        pos = jnp.arange(s)

        def layer(x, lw):
            a = ref.rmsnorm(x, f32(lw["attn_norm"]["scale"]), eps)
            at = lw["attn"]
            q = ref.dot("sd,dn->sn", a, f32(at["wq"])).reshape(s, h, hd)
            k = ref.dot("sd,dn->sn", a, f32(at["wk"])).reshape(s, hk, hd)
            v = ref.dot("sd,dn->sn", a, f32(at["wv"])).reshape(s, hk, hd)
            q, k = ref.rope(q, pos, theta), ref.rope(k, pos, theta)
            o = ref.causal_attention(q, k, v, hd**-0.5, groups=h // hk)
            x = x + ref.dot("sn,nd->sd", o.reshape(s, h * hd), f32(at["wo"]))
            m = ref.rmsnorm(x, f32(lw["ffn_norm"]["scale"]), eps)
            fw = lw["ffn"]
            x = x + ref.swiglu(m, f32(fw["w_gate"]), f32(fw["w_up"]), f32(fw["w_down"]))
            return x, None

        x, _ = jax.lax.scan(layer, x, w["layers"])
        return ref.rmsnorm(x, f32(w["final_norm"]["scale"]), eps)

    return fwd


def layer_gemms(cfg: dict, phase: str, rows: int, live: int) -> list:
    """(M, N, K) of the weight GEMMs one layer runs through ``core.ops.matmul``
    for ``rows`` new tokens (``live``: tokens in the cache after them)."""
    del phase, live
    d, h, hk = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, ff = d // h, cfg["intermediate_size"]
    return [
        (rows, h * hd, d),
        (rows, hk * hd, d),
        (rows, hk * hd, d),
        (rows, d, h * hd),
        (rows, ff, d),
        (rows, ff, d),
        (rows, d, ff),
    ]


def token_flops(cfg: dict, ctx: int) -> float:
    """Model FLOPs of one token that attends over ``ctx`` tokens: 2 per
    weight of every projection (the output head apart), and QK plus PV."""
    L, d, h = cfg["num_hidden_layers"], cfg["hidden_size"], cfg["num_attention_heads"]
    weights = sum(n * k for _, n, k in layer_gemms(cfg, "prefill", 1, 1))
    return L * (2.0 * weights + 4.0 * ctx * d)
