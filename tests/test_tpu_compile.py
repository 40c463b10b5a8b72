"""Main-path kernels compile for a TPU v5e at real widths (no chip needed).

Each case lowers a kernel wrapper with ``interpret=False`` against one chip
of a described ``v5e:2x2`` topology and compiles it with the TPU compiler,
which refuses what interpret mode accepts: blocks that break the (8, 128)
tiling rule and working sets over the kernel's scoped-VMEM limit.  Shapes
are internlm2-1.8b's prefill/decode GEMMs (lm_head included), a paper
problem under its largest tuner candidate, and qwen3-moe-30b-a3b's expert
widths.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import hw
from repro.core.blocking import BlockPlan, derive_block_plan
from repro.kernels.attention import ops as attention_ops
from repro.kernels.grouped import ops as grouped_ops
from repro.kernels.systolic import ops as systolic_ops
from repro.serving.engine import decode_gemm_problems
from repro.tune import candidates as tune_candidates

PREFILL_TOKENS = 256
DECODE_SLOTS = 4
PAPER_PROBLEM = (4096, 4096, 4096)


def _internlm2_problems() -> list[tuple[str, int, int, int]]:
    """(name, M, N, K) of every dense GEMM internlm2-1.8b serves: the
    projections at prefill and decode M, and the lm_head at decode."""
    cfg = get_config("internlm2-1.8b")
    probs = {}
    for phase, m in (("prefill", PREFILL_TOKENS), ("decode", DECODE_SLOTS)):
        for name, mm, n, k in decode_gemm_problems(cfg, m):
            probs.setdefault((mm, n, k), f"{phase}-{name}")
    probs[(DECODE_SLOTS, cfg.vocab_size, cfg.d_model)] = "decode-lm_head"
    return [(name, *mnk) for mnk, name in probs.items()]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # any failure to describe means: no compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A described-topology compile is written to the persistent cache but
    # cannot be read back without a chip: keep the cache out of it.
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was_enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    assert topo.devices[0].device_kind in hw.DEVICE_KINDS
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes_dtypes) -> str:
    args = [
        jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one_chip)
        for shape, dt in shapes_dtypes
    ]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the compiled program"
    return text


INTERNLM2_PROBLEMS = _internlm2_problems()


@pytest.mark.parametrize(
    "name,m,n,k", INTERNLM2_PROBLEMS, ids=[p[0] for p in INTERNLM2_PROBLEMS]
)
def test_systolic_bf16_internlm2(one_chip, name, m, n, k):
    _compile(
        lambda a, b: systolic_ops.matmul(a, b, interpret=False),
        one_chip, ((m, k), "bfloat16"), ((k, n), "bfloat16"),
    )


def test_systolic_fp32_derived_plan(one_chip):
    m, n, k = 512, 8192, 2048
    plan = derive_block_plan(m, n, k, in_dtype="float32", chip="tpu_v5e")
    # The fitter admits this plan above the compiler's 16 MiB default.
    assert plan.vmem_bytes() > 16 * 1024 * 1024
    _compile(
        lambda a, b: systolic_ops.matmul(a, b, plan=plan, interpret=False),
        one_chip, ((m, k), "float32"), ((k, n), "float32"),
    )


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_largest_tune_candidate_paper_problem(one_chip, dtype):
    m, n, k = PAPER_PROBLEM
    cands = tune_candidates.generate(
        m, n, k, dtype=dtype, chip="tpu_v5e", top_k=None
    )
    rec = max(cands, key=lambda c: c.record.vmem_kib).record
    plan = BlockPlan(m, n, k, rec.bm, rec.bn, rec.bk, in_dtype=dtype)
    _compile(
        lambda a, b: systolic_ops.matmul(a, b, plan=plan, interpret=False),
        one_chip, ((m, k), dtype), ((k, n), dtype),
    )


def test_quant_int8(one_chip):
    m, n, k = PREFILL_TOKENS, 8192, 2048
    _compile(
        lambda a, b: systolic_ops.quant_matmul(a, b, qdtype="int8", interpret=False),
        one_chip, ((m, k), "bfloat16"), ((k, n), "bfloat16"),
    )


@pytest.mark.parametrize("k,n", [(2048, 768), (768, 2048)], ids=["up", "down"])
def test_grouped_qwen3_moe_experts(one_chip, k, n):
    moe = get_config("qwen3-moe-30b-a3b").moe
    assert {k, n} == {2048, moe.d_ff_expert}
    e, c = 8, 64
    _compile(
        lambda x, w: grouped_ops.grouped_matmul(x, w, interpret=False),
        one_chip, ((e, c, k), "bfloat16"), ((e, k, n), "bfloat16"),
    )


def test_flash_attention_head_dim_128(one_chip):
    shape = (1, 16, 1024, 128)
    _compile(
        lambda q, kk, v: attention_ops.flash_attention(
            q, kk, v, causal=True, interpret=False
        ),
        one_chip, (shape, "bfloat16"), (shape, "bfloat16"), (shape, "bfloat16"),
    )
