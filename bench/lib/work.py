"""Operations and bytes from shapes: the yardstick for rooflines and MFU.

GEMM shapes and per-token model FLOPs come from the family files
(``layer_gemms``, ``token_flops``); this file turns them into least times
and window totals.  Bytes are A, B and C once each at the compute dtype.
"""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def least_seconds(m: int, n: int, k: int, peaks: dict, dtype_bytes: int) -> float:
    """Least time of an (M, K) x (K, N) GEMM on the chip: the larger of its
    FLOPs over peak FLOP/s and its bytes over peak HBM bandwidth."""
    return max(
        2.0 * m * n * k / peaks["bf16_flops"],
        dtype_bytes * (m * k + k * n + m * n) / peaks["hbm_bytes_s"],
    )


def head_gemm(cfg: dict, rows: int) -> tuple[int, int, int]:
    return (rows, cfg["vocab_size"], cfg["hidden_size"])


def step_gemms(fam, cfg: dict, phase: str, rows: int, live: int, head_rows: int) -> list:
    """Every weight GEMM of one decode step or prefill chunk: the layers'
    projections, then the output head on ``head_rows`` rows."""
    per_layer = fam.layer_gemms(cfg, phase, rows, live)
    return per_layer * cfg["num_hidden_layers"] + [head_gemm(cfg, head_rows)]


def head_flops(cfg: dict) -> float:
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def chunk_flops(fam, cfg: dict, offset: int, length: int, last: bool) -> float:
    """Model FLOPs of prefilling prompt tokens [offset, offset + length):
    each token attends over itself and all before it; the output head runs
    once per prompt, for its last token."""
    base = fam.token_flops(cfg, 0)
    per_ctx = fam.token_flops(cfg, 1) - base  # token FLOPs are affine in ctx
    ctx_sum = length * offset + length * (length + 1) / 2
    return length * base + per_ctx * ctx_sum + (head_flops(cfg) if last else 0.0)


def decode_flops(fam, cfg: dict, ctx: int) -> float:
    """Model FLOPs of one decoded token whose query sees ``ctx`` tokens."""
    return fam.token_flops(cfg, ctx) + head_flops(cfg)
