"""repro.quant: QArray round-trips, the quantized systolic kernel vs its
dequantize-then-fp32 oracle, core.ops precision dispatch, weight-only and
w8a8 model equivalence, the int8 KV pool, and the dtype-aware perf model."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import quant
from repro.configs import get_smoke
from repro.core import dse, hw, ops
from repro.core.blocking import BlockPlan
from repro.kernels.systolic import ops as sops
from repro.kernels.systolic.ref import quant_matmul_ref
from repro.models.registry import get_model
from repro.quant.qarray import QArray, quantize, quantize_act, quantize_weight

RNG = np.random.default_rng(0)


def _randn(*shape):
    return jnp.asarray(RNG.standard_normal(shape), jnp.float32)


# ---------------------------------------------------------------------------
# QArray
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("qd", ["int8", "fp8"])
def test_qarray_roundtrip_error_bound(qd):
    x = _randn(48, 200)
    q = quantize(x, qd, block=(1, 64))
    y = q.dequantize()
    # symmetric round-to-nearest: error <= scale/2 per element (int8);
    # fp8 e4m3 has >= 3 mantissa bits near the block max -> <= scale*32
    bound = 0.5 if qd == "int8" else 32.0
    qr, qc = q.block
    s_full = jnp.repeat(jnp.repeat(q.scales, qr, -2), qc, -1)[:48, :200]
    assert float(jnp.max(jnp.abs(y - x) / s_full)) <= bound + 1e-6


def test_qarray_block_shapes_and_nondivisible():
    x = _randn(70, 130)
    q = quantize(x, "int8", block=(16, 32))
    assert q.scales.shape == (5, 5)  # ceil(70/16), ceil(130/32)
    assert q.values.shape == (70, 130)
    assert q.values.dtype == jnp.int8
    # whole-axis sentinel
    q2 = quantize(x, "int8", block=(0, 1))
    assert q2.scales.shape == (1, 130)
    assert q2.block == (70, 1)


def test_qarray_leading_axes_and_scan_slicing():
    """Stacked (L, K, N) weights: per-layer scales; lax.scan slicing the
    leading axis must keep values and scales coherent (pytree aux data is
    leading-axis independent)."""
    w = _randn(3, 32, 16)
    q = quantize_weight(w, "int8", block_k=8)
    assert q.scales.shape == (3, 4, 16)

    def body(carry, qw):
        assert qw.values.shape == (32, 16)
        assert qw.scales.shape == (4, 16)
        return carry, qw.dequantize()

    _, deq = jax.lax.scan(body, 0, q)
    np.testing.assert_allclose(
        np.asarray(deq), np.asarray(q.dequantize()), rtol=0, atol=0
    )


def test_qarray_zero_block_scale_guard():
    x = jnp.zeros((8, 8), jnp.float32)
    q = quantize(x, "int8", block=(0, 0))
    assert float(jnp.max(jnp.abs(q.dequantize()))) == 0.0
    assert float(q.scales[0, 0]) == 1.0  # no div-by-zero sentinel


# ---------------------------------------------------------------------------
# Quantized systolic kernel vs oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("qd", ["int8", "fp8"])
@pytest.mark.parametrize(
    "mnk", [(8, 128, 128), (72, 130, 100), (300, 257, 515)]
)
def test_quant_kernel_matches_oracle_nondivisible(qd, mnk):
    """Acceptance: kernel == dequantize-then-fp32-matmul oracle to atol
    driven by scale granularity, on non-divisible M/N/K."""
    m, n, k = mnk
    qa = quantize_act(_randn(m, k), qd)
    qb = quantize_weight(_randn(k, n), qd)
    y = sops.quant_matmul(qa, qb, out_dtype=jnp.float32)
    ref = quant_matmul_ref(qa, qb)
    # identical quantized values; only fp32 summation order differs, so the
    # tolerance scales with the accumulated magnitude (~ scale granularity).
    tol = 1e-5 * float(jnp.max(jnp.abs(ref)) + 1.0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=tol)


def test_quant_kernel_per_channel_and_activation():
    a, b = _randn(40, 96), _randn(96, 64)
    qa = quantize(a, "int8", block=(1, 0))  # per-row, whole-K scale
    qb = quantize(b, "int8", block=(0, 1))  # per-column, whole-K scale
    y = sops.quant_matmul(qa, qb, out_dtype=jnp.float32, activation="relu")
    ref = quant_matmul_ref(qa, qb, activation="relu")
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-4)
    assert float(jnp.min(y)) >= 0.0


def test_quant_kernel_quantizes_fp_inputs_on_the_fly():
    a, b = _randn(16, 64), _randn(64, 32)
    y = sops.quant_matmul(a, b, qdtype="int8", out_dtype=jnp.float32)
    ref = quant_matmul_ref(quantize_act(a), quantize_weight(b))
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-4)
    # and the quantization error vs the fp product is small but nonzero
    fp = np.asarray(a @ b)
    rel = np.max(np.abs(np.asarray(y) - fp)) / np.max(np.abs(fp))
    assert 0 < rel < 0.05


def test_quant_kernel_mismatched_qdtypes_raise():
    qa = quantize_act(_randn(8, 64), "int8")
    qb = quantize_weight(_randn(64, 8), "fp8")
    with pytest.raises(ValueError, match="qdtypes differ"):
        sops.quant_matmul(qa, qb)


# ---------------------------------------------------------------------------
# core.ops.matmul dispatch
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("prec", ["int8", "fp8"])
def test_ops_matmul_precision_dispatch(prec):
    x, w = _randn(4, 96), _randn(96, 64)
    yq = ops.matmul(x, w, precision=prec, out_dtype=jnp.float32)
    yf = ops.matmul(x, w, out_dtype=jnp.float32)
    rel = float(jnp.max(jnp.abs(yq - yf)) / jnp.max(jnp.abs(yf)))
    assert 0 < rel < 0.05


def test_ops_matmul_precision_backends_agree():
    """xla and pallas-systolic run the same quantized numerics."""
    x, w = _randn(4, 96), _randn(96, 64)
    with ops.use_backend("xla"):
        y1 = ops.matmul(x, w, precision="int8", out_dtype=jnp.float32)
    with ops.use_backend("pallas-systolic"):
        y2 = ops.matmul(x, w, precision="int8", out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-4)


def test_ops_matmul_qarray_weight_w8a16_and_w8a8():
    x, w = _randn(2, 5, 96), _randn(96, 64)  # leading batch dims
    qw = quantize_weight(w)
    yf = ops.matmul(x, w, out_dtype=jnp.float32)
    y16 = ops.matmul(x, qw, out_dtype=jnp.float32)  # weight-only
    np.testing.assert_allclose(
        np.asarray(y16),
        np.asarray(ops.matmul(x, qw.dequantize(x.dtype), out_dtype=jnp.float32)),
        atol=1e-5,
    )
    with quant.use_act_quant("int8"):
        y8 = ops.matmul(x, qw, out_dtype=jnp.float32)
    assert y8.shape == yf.shape == y16.shape
    rel = float(jnp.max(jnp.abs(y8 - yf)) / jnp.max(jnp.abs(yf)))
    assert 0 < rel < 0.05


# ---------------------------------------------------------------------------
# Weight-only quantized models (w8a16/w8a8 decode equivalence)
# ---------------------------------------------------------------------------


def _fp32_model(arch):
    cfg = dataclasses.replace(get_smoke(arch), dtype="float32")
    model = get_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


@pytest.mark.parametrize("arch", ["internlm2-1.8b", "minicpm3-4b", "qwen3-moe-30b-a3b"])
def test_w8a16_decode_close_to_fp32(arch):
    """Quantized decode tracks fp32 on the registry models (GQA, MLA, MoE):
    tolerance-based logits equivalence over prefill + decode steps.

    The prompt comes from its own seeded generator, not the module's shared
    one, so it does not depend on which tests ran before.  That matters for
    MoE: quantization noise upstream of the router can flip a token's top-k
    experts, which moves the logits far more than the weights' rounding
    (on the smoke config, 4 of 12 prompt seeds do so)."""
    cfg, model, params = _fp32_model(arch)
    qparams = quant.quantize_params(params)
    n_q, _ = quant.count_quantized(qparams)
    assert n_q > 0
    rng = np.random.default_rng(0)
    batch = {
        "tokens": jnp.asarray(
            rng.integers(0, cfg.vocab_size, (2, 8)), jnp.int32
        )
    }
    lf, cf = model.prefill(params, batch, max_len=16)
    lq, cq = model.prefill(qparams, batch, max_len=16)
    ref_scale = float(jnp.max(jnp.abs(lf)))
    assert float(jnp.max(jnp.abs(lq - lf))) < 0.1 * ref_scale
    tok = jnp.argmax(lf, -1).astype(jnp.int32)
    for step in range(2):
        lf, cf = model.decode_step(params, tok, cache=cf, pos=jnp.int32(8 + step))
        lq, cq = model.decode_step(qparams, tok, cache=cq, pos=jnp.int32(8 + step))
        ref_scale = float(jnp.max(jnp.abs(lf)))
        assert float(jnp.max(jnp.abs(lq - lf))) < 0.1 * ref_scale
        tok = jnp.argmax(lf, -1).astype(jnp.int32)


def test_w8a8_decode_close_to_fp32():
    cfg, model, params = _fp32_model("internlm2-1.8b")
    qparams = quant.quantize_params(params)
    batch = {
        "tokens": jnp.asarray(RNG.integers(0, cfg.vocab_size, (1, 8)), jnp.int32)
    }
    lf, _ = model.prefill(params, batch, max_len=16)
    with quant.use_act_quant("int8"):
        lq, _ = model.prefill(qparams, batch, max_len=16)
    assert float(jnp.max(jnp.abs(lq - lf))) < 0.15 * float(jnp.max(jnp.abs(lf)))


def test_quantize_params_skips_specials():
    _, _, params = _fp32_model("minicpm3-4b")  # MLA: has wkv_b
    qparams = quant.quantize_params(params)
    layer = jax.tree.map(
        lambda x: x, qparams["layers"], is_leaf=lambda x: isinstance(x, QArray)
    )
    assert isinstance(layer["attn"]["wq_a"], QArray)
    assert not isinstance(layer["attn"]["wkv_b"], QArray)  # absorbed einsum
    assert not isinstance(qparams["embed"]["table"], QArray)  # gather

    _, _, moe_params = _fp32_model("qwen3-moe-30b-a3b")
    qmoe = quant.quantize_params(moe_params)
    ffn = qmoe["layers"]["ffn"]
    assert not isinstance(ffn["w_up"], QArray)  # grouped kernel: skipped
    assert isinstance(qmoe["layers"]["attn"]["wq"], QArray)


# ---------------------------------------------------------------------------
# int8 KV pool (kv8)
# ---------------------------------------------------------------------------


def _pool_engine(arch="internlm2-1.8b", quantize_kv=False, batch=2, max_len=32):
    from repro.serving import KVPool, ServeConfig, ServeEngine

    cfg = dataclasses.replace(get_smoke(arch), dtype="float32")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(
        model, params, ServeConfig(max_len=max_len, batch=batch)
    )
    pool = KVPool(model, batch, max_len, quantize_kv_cache=quantize_kv)
    return cfg, eng, pool


@pytest.mark.parametrize("arch", ["internlm2-1.8b", "minicpm3-4b"])
def test_kv8_decode_close_to_fp(arch):
    """int8 KV pool decode tracks the fp pool within tolerance (GQA + MLA)."""
    cfg, eng, pool_fp = _pool_engine(arch)
    _, _, pool_q = _pool_engine(arch, quantize_kv=True)
    prompt = {
        "tokens": jnp.asarray(RNG.integers(0, cfg.vocab_size, (1, 6)), jnp.int32)
    }
    first, cache_one = eng.prefill_request(prompt)
    for pool in (pool_fp, pool_q):
        slot = pool.alloc()
        pool.write_prefill(slot, cache_one, 6)
    toks = jnp.tile(first, (2, 1))
    out_fp, cache_fp = eng.decode_slots(toks, pool_fp.cache, pool_fp.pos_vector())
    out_q, cache_q = eng.decode_slots(toks, pool_q.cache, pool_q.pos_vector())
    # greedy tokens may differ in principle; the KV payloads must be close
    k_fp = jax.tree.leaves(cache_fp)[0]
    k_q = jax.tree.leaves(cache_q)[0]
    assert k_fp.shape == k_q.shape
    err = float(jnp.max(jnp.abs(k_fp - k_q)))
    assert err < 0.05 * float(jnp.max(jnp.abs(k_fp)) + 1e-9)


def test_kv8_pool_memory_is_narrow_and_masks_hold():
    _, eng, pool = _pool_engine(quantize_kv=True)
    # resident storage is int8 for K/V, exact int32 for pos
    qleaves = jax.tree.leaves(pool._qcache)
    assert any(a.dtype == jnp.int8 for a in qleaves)
    fp = pool.cache
    pos_leaves = [
        a for a in jax.tree.leaves(fp) if a.dtype == jnp.int32 and a.ndim >= 2
    ]
    assert pos_leaves and all(bool(jnp.all(a == -1)) for a in pos_leaves)
    # freeing a written slot re-masks and zeroes through the quantized form
    cfg = eng.cfg
    prompt = {
        "tokens": jnp.asarray(RNG.integers(0, cfg.vocab_size, (1, 4)), jnp.int32)
    }
    _, cache_one = eng.prefill_request(prompt)
    slot = pool.alloc()
    pool.write_prefill(slot, cache_one, 4)
    assert pool.positions[slot] == 4
    pool.free(slot)
    fp = pool.cache
    for a in jax.tree.leaves(fp):
        if a.dtype == jnp.int32 and a.ndim >= 2:
            assert bool(jnp.all(a[:, slot] == -1))
        elif jnp.issubdtype(a.dtype, jnp.floating) and a.ndim >= 3:
            assert float(jnp.max(jnp.abs(a[:, slot]))) == 0.0


def test_kv8_scheduler_end_to_end():
    """A kv8 continuous run drains and produces the full token budget."""
    from repro.data.synthetic import make_request_trace
    from repro.serving import ContinuousScheduler, requests_from_trace
    from repro.serving import ServeConfig, ServeEngine

    cfg = dataclasses.replace(get_smoke("internlm2-1.8b"), dtype="float32")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    trace = make_request_trace(
        cfg, n_requests=4, mean_prompt=6, mean_gen=4, rate=1.0, seed=0,
        max_prompt=8, max_gen=4,
    )
    max_len = max(t["prompt"]["tokens"].shape[1] + t["max_new_tokens"] for t in trace)
    eng = ServeEngine(model, params, ServeConfig(max_len=max_len, batch=2))
    sched = ContinuousScheduler(eng, quantize_kv=True)
    assert sched.quantize_kv
    results = sched.run(requests_from_trace(trace))
    assert len(results) == 4
    for t in trace:
        assert results[t["rid"]].shape[0] == t["max_new_tokens"]


def test_kv8_disabled_for_state_families():
    from repro.serving import ContinuousScheduler, ServeConfig, ServeEngine

    cfg = dataclasses.replace(get_smoke("xlstm-125m"), dtype="float32")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(model, params, ServeConfig(max_len=16, batch=2))
    with pytest.warns(UserWarning, match="kv8 disabled"):
        sched = ContinuousScheduler(eng, quantize_kv=True)
    assert not sched.quantize_kv


# ---------------------------------------------------------------------------
# Dtype-aware performance model
# ---------------------------------------------------------------------------


def test_chip_peak_flops_table():
    chip = hw.get_chip("tpu_v5e")
    assert chip.peak_flops() == chip.peak_flops_bf16
    assert chip.peak_flops("int8") == 2 * chip.peak_flops_bf16
    assert chip.peak_flops("float8_e4m3fn") == 2 * chip.peak_flops_bf16
    assert chip.peak_flops("float32") == 0.5 * chip.peak_flops_bf16
    assert chip.machine_balance("int8") == 2 * chip.machine_balance_hbm


def test_dtype_bytes_table():
    assert hw.dtype_bytes("int8") == 1
    assert hw.dtype_bytes("float8_e4m3fn") == 1
    assert hw.dtype_bytes("bfloat16") == 2
    assert hw.dtype_bytes(jnp.float32) == 4


def test_blockplan_in_dtype_overrides_bytes():
    p = BlockPlan(512, 512, 512, 128, 128, 128, in_dtype="int8")
    assert p.in_dtype_bytes == 1
    p2 = BlockPlan(512, 512, 512, 128, 128, 128, in_dtype="float32")
    assert p2.in_dtype_bytes == 4
    # int8 compute runs at 2x peak -> half the compute time of bf16
    bf = BlockPlan(512, 512, 512, 128, 128, 128, in_dtype="bfloat16")
    assert p.compute_seconds() == pytest.approx(bf.compute_seconds() / 2)


def test_blockplan_counts_scale_bytes():
    base = dict(m=1024, n=1024, k=2048, bm=256, bn=256, bk=256)
    fp = BlockPlan(**base, in_dtype="int8")
    q = BlockPlan(
        **base,
        in_dtype="int8",
        quant_block_k=128,
        out_dtype_bytes=hw.dtype_bytes("bfloat16"),
    )
    # VMEM: one (bm,1) + one (1,bn) fp32 scale stream, double-buffered,
    # plus the wider (bf16) output window vs the 1-byte fp one.
    assert q.vmem_bytes() - fp.vmem_bytes() == (256 + 256) * 4 * 2 + 256 * 256
    # HBM: scale sidecars re-stream with their operands
    kb = 2048 // 128
    n_col, n_row = 1024 // 256, 1024 // 256
    extra = (1024 * kb * 4 * n_col) + (kb * 1024 * 4 * n_row) + 1024 * 1024
    assert q.hbm_traffic_bytes() - fp.hbm_traffic_bytes() == extra


def test_dse_explore_quant_dtypes():
    recs = dse.explore(1024, 1024, 2048, in_dtype="int8")
    assert recs and all(r.in_dtype == "int8" for r in recs)
    assert all(r.in_dtype_bytes == 1 for r in recs)
    assert all(r.quant_block_k == 128 for r in recs)
    # only geometries the quant kernel actually runs: one scale block spans
    # >= one whole k-step, so bk must divide qk (the dispatcher gcd-clamps
    # anything else -- enumerating it would price a kernel that never runs)
    assert all(r.quant_block_k % r.bk == 0 for r in recs)
    best_q = dse.best(recs)
    best_bf = dse.best(dse.explore(1024, 1024, 2048, in_dtype="bfloat16"))
    # same problem, narrow streams + doubled peak -> strictly faster bound
    assert best_q.analytical_us < best_bf.analytical_us
    speedup = best_bf.analytical_us / best_q.analytical_us
    assert speedup >= 1.5


def test_candidates_generate_quant_dtype():
    from repro.tune import candidates

    cands = candidates.generate(512, 512, 512, dtype="int8", top_k=4)
    assert cands
    assert all(c.record.in_dtype == "int8" for c in cands)


def test_measure_quant_dtypes_smoke():
    from repro.tune import measure

    for dtype in ("int8", "float8_e4m3fn"):
        ms = measure.measure_matmul(
            128, 128, 128, 128, 128, 128, dtype=dtype, repeats=1, warmup=1
        )
        assert ms.best_us > 0
    ms = measure.measure_matmul(
        1024, 1024, 1024, 512, 512, 512, dtype="int8",
        method="xla-proxy", repeats=1, warmup=1,
    )
    assert ms.method == "xla-proxy" and ms.best_us > 0
