"""Dense decoder with multi-head latent attention and a SwiGLU FFN (MiniCPM3).

Per layer, attention in its expanded form (as the published model computes
it in training and prefill):

  q = W_qb · n_q(W_qa · n(x)) -> per head [q_nope | q_rope], rope on q_rope
  [c | k_r] = W_kva · n(x);  c = n_kv(c);  k_r = rope(k_r), one for all heads
  [k_nope | v] = W_kvb · c, per head;  k = [k_nope | k_r]
  x += W_o · softmax(q·k / sqrt(nope + rope)) v

then x += W_down·(silu(W_gate·n(x)) * W_up·n(x)); a final RMSNorm and an
untied output head.  The configuration file lists where these equations
depart from MiniCPM3's published ones.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.lib import reference as ref


def arch_config(cfg: dict):
    """The program's ``ArchConfig`` for this configuration file."""
    from repro.models.config import ArchConfig, MLAConfig

    return ArchConfig(
        name=cfg["name"],
        family="dense",
        n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"],
        attention="mla",
        mla=MLAConfig(
            q_lora_rank=cfg["q_lora_rank"],
            kv_lora_rank=cfg["kv_lora_rank"],
            qk_nope_head_dim=cfg["qk_nope_head_dim"],
            qk_rope_head_dim=cfg["qk_rope_head_dim"],
            v_head_dim=cfg["v_head_dim"],
        ),
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        dtype=cfg["compute_dtype"],
    ).validate()


def weight_spec(cfg: dict) -> dict:
    """The parameter tree, leaves ("normal", shape, std) or ("ones", shape)."""
    L, d, h = cfg["num_hidden_layers"], cfg["hidden_size"], cfg["num_attention_heads"]
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope_d, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    ff, v = cfg["intermediate_size"], cfg["vocab_size"]

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
                "wq_a": dense(L, d, qr),
                "q_norm": {"scale": ("ones", (L, qr))},
                "wq_b": dense(L, qr, h * (nope + rope_d)),
                "wkv_a": dense(L, d, kvr + rope_d),
                "kv_norm": {"scale": ("ones", (L, kvr))},
                "wkv_b": dense(L, kvr, h * (nope + vd)),
                "wo": dense(L, h * vd, d),
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
    h = cfg["num_attention_heads"]
    kvr = cfg["kv_lora_rank"]
    nope, rope_d, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])

    def fwd(w, tokens):
        f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
        x = f32(w["embed"]["table"])[tokens]
        s = x.shape[0]
        pos = jnp.arange(s)

        def layer(x, lw):
            a = ref.rmsnorm(x, f32(lw["attn_norm"]["scale"]), eps)
            at = lw["attn"]
            ql = ref.rmsnorm(ref.dot("sd,dr->sr", a, f32(at["wq_a"])), f32(at["q_norm"]["scale"]), eps)
            q = ref.dot("sr,rn->sn", ql, f32(at["wq_b"])).reshape(s, h, nope + rope_d)
            q = jnp.concatenate([q[..., :nope], ref.rope(q[..., nope:], pos, theta)], axis=-1)
            kva = ref.dot("sd,dr->sr", a, f32(at["wkv_a"]))
            c = ref.rmsnorm(kva[:, :kvr], f32(at["kv_norm"]["scale"]), eps)
            k_r = ref.rope(kva[:, None, kvr:], pos, theta)  # (S, 1, rope)
            kv = ref.dot("sr,rn->sn", c, f32(at["wkv_b"])).reshape(s, h, nope + vd)
            k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_r, (s, h, rope_d))], axis=-1)
            o = ref.causal_attention(q, k, kv[..., nope:], (nope + rope_d) ** -0.5)
            x = x + ref.dot("sn,nd->sd", o.reshape(s, h * vd), f32(at["wo"]))
            m = ref.rmsnorm(x, f32(lw["ffn_norm"]["scale"]), eps)
            fw = lw["ffn"]
            x = x + ref.swiglu(m, f32(fw["w_gate"]), f32(fw["w_up"]), f32(fw["w_down"]))
            return x, None

        x, _ = jax.lax.scan(layer, x, w["layers"])
        return ref.rmsnorm(x, f32(w["final_norm"]["scale"]), eps)

    return fwd


def layer_gemms(cfg: dict, phase: str, rows: int, live: int) -> list:
    """(M, N, K) of the weight GEMMs one layer runs through ``core.ops.matmul``
    for ``rows`` new tokens (``live``: tokens in the cache after them).

    Prefill expands the latent cache through W_kvb for every live token (the
    expanded attention); decode absorbs W_kvb into the query and output
    einsums instead, so it has no such GEMM."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope_d, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    ff = cfg["intermediate_size"]
    out = [
        (rows, qr, d),
        (rows, h * (nope + rope_d), qr),
        (rows, kvr + rope_d, d),
    ]
    if phase == "prefill":
        out.append((live, h * (nope + vd), kvr))
    return out + [(rows, d, h * vd), (rows, ff, d), (rows, ff, d), (rows, d, ff)]


def token_flops(cfg: dict, ctx: int) -> float:
    """Model FLOPs of one token that attends over ``ctx`` tokens: 2 per
    weight of every projection (W_kvb once, for its own latent; the output
    head apart), and QK plus PV in the expanded form."""
    L, h = cfg["num_hidden_layers"], cfg["num_attention_heads"]
    nope, rope_d, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    weights = sum(n * k for _, n, k in layer_gemms(cfg, "prefill", 1, 1))
    return L * (2.0 * weights + 2.0 * ctx * h * (nope + rope_d + vd))
