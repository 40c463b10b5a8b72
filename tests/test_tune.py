"""repro.tune: cache round-trip, fitter agreement, determinism, dispatch."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dse, hw
from repro.tune import (
    CacheKey,
    Measurement,
    PlanCache,
    TunedPlan,
    autotune,
    generate,
)
from repro.tune import cache as tune_cache


@pytest.fixture()
def cache_path(tmp_path, monkeypatch):
    """Point the default cache at a fresh tmpdir for each test."""
    path = tmp_path / "plans.json"
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(path))
    tune_cache.reset_default_cache()
    yield path
    tune_cache.reset_default_cache()


def _stub(best_block, t_fast=7.0, t_slow=40.0):
    """Deterministic measurement: one distinguished geometry is fastest."""

    def measure(rec: dse.DSERecord) -> Measurement:
        t = t_fast if (rec.bm, rec.bn, rec.bk) == best_block else t_slow
        return Measurement(mean_us=t, best_us=t, repeats=1, method="stub")

    return measure


# -- cache ------------------------------------------------------------------


def test_cache_round_trip(tmp_path):
    path = tmp_path / "plans.json"
    key = CacheKey("pallas-systolic", "tpu_v5e", 512, 512, 512, "bfloat16")
    plan = TunedPlan(bm=256, bn=512, bk=128, mean_us=12.5, best_us=11.0,
                     method="device-wall", repeats=3)
    PlanCache(path).store(key, plan)

    reloaded = PlanCache(path)  # fresh instance -> must read from disk
    assert reloaded.lookup(key) == plan
    assert len(reloaded) == 1
    # a different activation is a different problem
    other = CacheKey("pallas-systolic", "tpu_v5e", 512, 512, 512, "bfloat16",
                     activation="gelu")
    assert reloaded.lookup(other) is None


def test_cache_versioning_and_corruption(tmp_path):
    path = tmp_path / "plans.json"
    key = CacheKey("pallas-systolic", "tpu_v5e", 128, 128, 128, "float32")
    plan = TunedPlan(128, 128, 128, 1.0, 1.0, "stub")

    # wrong schema version -> treated as empty, not mis-read
    path.write_text(json.dumps({"version": 999, "entries": {"x": {}}}))
    assert PlanCache(path).lookup(key) is None

    # corrupt file -> empty, and store() rewrites it cleanly
    path.write_text("{not json")
    c = PlanCache(path)
    assert c.lookup(key) is None
    c.store(key, plan)
    assert PlanCache(path).lookup(key) == plan
    assert json.loads(path.read_text())["version"] == tune_cache.SCHEMA_VERSION


def test_cache_non_dict_json_and_merge_on_write(tmp_path):
    path = tmp_path / "plans.json"
    key_a = CacheKey("pallas-systolic", "tpu_v5e", 128, 128, 128, "float32")
    key_b = CacheKey("pallas-systolic", "tpu_v5e", 256, 256, 256, "float32")
    plan = TunedPlan(128, 128, 128, 1.0, 1.0, "stub")

    # valid JSON that is not a dict degrades to empty, never raises
    path.write_text("[]")
    assert PlanCache(path).lookup(key_a) is None

    # merge-on-write: a writer that loaded early must not erase entries
    # stored by another process in the meantime
    early = PlanCache(path)
    assert early.lookup(key_a) is None  # triggers lazy load of empty file
    PlanCache(path).store(key_b, plan)  # "other process" writes
    early.store(key_a, plan)
    final = PlanCache(path)
    assert final.lookup(key_a) == plan and final.lookup(key_b) == plan


@pytest.mark.parametrize("dtype", ["int8", "float8_e4m3fn"])
def test_cache_quant_dtype_keys_round_trip(tmp_path, dtype):
    """int8/fp8 cache keys persist and reload independently of the bf16
    entry for the same geometry (the dtype segment keys quantized plans)."""
    path = tmp_path / "plans.json"
    plan_q = TunedPlan(256, 256, 128, 3.0, 2.5, "interpret-wall", repeats=2)
    plan_bf = TunedPlan(512, 512, 512, 9.0, 8.0, "interpret-wall", repeats=2)
    key_q = CacheKey("pallas-systolic", "tpu_v5e", 512, 512, 512, dtype)
    key_bf = CacheKey("pallas-systolic", "tpu_v5e", 512, 512, 512, "bfloat16")
    c = PlanCache(path)
    c.store(key_q, plan_q)
    c.store(key_bf, plan_bf)
    reloaded = PlanCache(path)
    assert reloaded.lookup(key_q) == plan_q
    assert reloaded.lookup(key_bf) == plan_bf
    assert dtype in key_q.encode()


def test_lookup_block_ignores_v1_blob(tmp_path, monkeypatch):
    """Regression: a hand-written v1 cache file (no tp key segment) reads as
    empty -- lookup_block returns None instead of raising or mis-keying."""
    path = tmp_path / "plans.json"
    v1 = {
        "version": 1,
        "entries": {
            "pallas-systolic|tpu_v5e|512|512|512|bfloat16|none": {
                "bm": 256, "bn": 256, "bk": 256,
                "mean_us": 5.0, "best_us": 4.0, "method": "stub",
            }
        },
    }
    path.write_text(json.dumps(v1))
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(path))
    tune_cache.reset_default_cache()
    try:
        hit = tune_cache.lookup_block(
            "pallas-systolic", "tpu_v5e", 512, 512, 512, "bfloat16"
        )
        assert hit is None
        assert len(PlanCache(path)) == 0
    finally:
        tune_cache.reset_default_cache()


def test_cache_skips_corrupt_entry_keeps_rest(tmp_path):
    """One malformed entry must not discard the whole cache file."""
    path = tmp_path / "plans.json"
    good_key = CacheKey("pallas-systolic", "tpu_v5e", 128, 128, 128, "int8")
    blob = {
        "version": tune_cache.SCHEMA_VERSION,
        "entries": {
            "hand|edited|garbage": {"bm": "not-an-int"},
            good_key.encode(): {
                "bm": 128, "bn": 128, "bk": 128,
                "mean_us": 1.0, "best_us": 1.0, "method": "stub",
                "repeats": 1,
            },
        },
    }
    path.write_text(json.dumps(blob))
    c = PlanCache(path)
    assert len(c) == 1
    assert c.lookup(good_key) == TunedPlan(128, 128, 128, 1.0, 1.0, "stub", 1)


def test_measure_rejects_activation_on_backends_without_epilogue():
    from repro.tune import measure_matmul

    with pytest.raises(ValueError, match="no fused activation"):
        measure_matmul(128, 128, 128, 128, 128, 128,
                       backend="pallas-grouped", activation="relu")


# -- candidates: the fitter stage ------------------------------------------


def test_candidates_agree_with_dse_fitter():
    m = n = k = 1024
    cands = generate(m, n, k, top_k=None)
    records = dse.explore(m, n, k)
    feasible = {r.ident for r in records if r.fits}
    assert feasible  # sweep is non-trivial
    assert {c.ident for c in cands} == feasible
    # ranking is the analytical ranking
    assert [c.rank for c in cands] == list(range(len(cands)))
    bounds = [c.record.analytical_us for c in cands]
    assert bounds == sorted(bounds)


def test_candidates_top_k_and_fallback():
    assert len(generate(1024, 1024, 1024, top_k=3)) == 3
    # awkward primes: nothing in the sweep divides -> heuristic fallback
    cands = generate(97, 131, 61, top_k=8)
    assert len(cands) == 1
    bm, bn, bk = cands[0].block
    assert bm % hw.get_chip(None).sublane_dim == 0 or bm == 97


def test_candidates_respect_chip_budget():
    """A tighter VMEM budget (tpu_v4 entry) must prune more geometries."""
    sweep = dict(bms=(1024, 2048), bns=(1024, 2048), bks=(1024, 2048), top_k=None)
    v5e = {c.ident for c in generate(4096, 4096, 4096, chip="tpu_v5e", **sweep)}
    v4 = {c.ident for c in generate(4096, 4096, 4096, chip="tpu_v4", **sweep)}
    assert v4 < v5e  # strictly fewer survivors under the 24 MiB budget


# -- autotune: the closed loop ---------------------------------------------


def test_autotune_deterministic_under_stub(cache_path):
    best_block = (256, 512, 256)
    r1 = autotune(512, 512, 512, measure_fn=_stub(best_block))
    assert not r1.cache_hit
    assert r1.block == best_block

    # second call: pure cache hit, same winner, no measurement
    def exploding(rec):
        raise AssertionError("measure_fn must not run on a cache hit")

    r2 = autotune(512, 512, 512, measure_fn=exploding)
    assert r2.cache_hit and r2.block == best_block

    # fresh cache, same stub -> same winner (determinism)
    r3 = autotune(512, 512, 512, measure_fn=_stub(best_block),
                  cache=PlanCache(cache_path.parent / "other.json"))
    assert r3.block == best_block


def test_autotune_tie_break_deterministic(cache_path):
    """Constant-time measurements still yield one fixed winner."""
    const = lambda rec: Measurement(3.0, 3.0, 1, "stub")
    r1 = autotune(512, 512, 512, measure_fn=const, force=True)
    r2 = autotune(512, 512, 512, measure_fn=const, force=True)
    assert r1.block == r2.block


def test_autotune_normalizes_dtype(cache_path):
    """np.float32 and "float32" are the same problem and the same key."""
    r = autotune(256, 256, 256, dtype=np.float32,
                 measure_fn=_stub((128, 128, 128)))
    assert r.key.dtype == "float32"
    r2 = autotune(256, 256, 256, dtype="float32", measure_fn=_stub((1, 1, 1)))
    assert r2.cache_hit and r2.block == r.block
    # and the kernels' str(a.dtype) lookup finds it
    hit = tune_cache.lookup_block("pallas-systolic", r.key.chip,
                                  256, 256, 256, "float32")
    assert hit is not None


def test_autotune_reference_backend_measures_reference(cache_path):
    """backend='reference' times the Definition-4 implementation itself."""
    r = autotune(256, 256, 256, dtype="float32", backend="reference",
                 top_k=2, repeats=1, method="interpret-wall")
    assert not r.cache_hit
    assert r.winner.method == "reference-wall"
    # the dispatch path picks it up when geometry divides
    from repro.core import ops as core_ops

    blocks, source = core_ops._reference_blocks(256, 256, 256, jnp.dtype("float32"))
    assert blocks == r.block and source == "tuned"


def test_autotune_rejects_unmeasurable_backend(cache_path):
    with pytest.raises(ValueError, match="no built-in measurement"):
        autotune(256, 256, 256, backend="made-up-backend")


def test_autotune_persists_and_reloads(cache_path):
    r = autotune(256, 512, 256, measure_fn=_stub((256, 512, 256)))
    assert cache_path.exists()
    tune_cache.reset_default_cache()  # force re-read from disk
    hit = tune_cache.lookup_block(
        "pallas-systolic", r.key.chip, 256, 512, 256, "bfloat16"
    )
    assert hit is not None and (hit.bm, hit.bn, hit.bk) == r.block


# -- dispatch: kernels consult the cache -----------------------------------


def test_systolic_matmul_uses_tuned_plan_and_matches_xla(cache_path, monkeypatch):
    from repro.kernels.systolic import ops as K

    m = n = k = 256
    # Tune with a stub that picks a block the heuristic would NOT pick
    # (heuristic derives 256x256x256 for this problem).
    tuned_block = (128, 128, 128)
    autotune(m, n, k, dtype="float32", measure_fn=_stub(tuned_block))

    captured = {}
    orig = K._matmul_jit

    def spy(a, b, bias, **kw):
        captured.update(kw)
        return orig(a, b, bias, **kw)

    monkeypatch.setattr(K, "_matmul_jit", spy)

    ka, kb = jax.random.split(jax.random.PRNGKey(0))
    a = jax.random.normal(ka, (m, k), jnp.float32)
    b = jax.random.normal(kb, (k, n), jnp.float32)

    y_tuned = K.matmul(a, b, interpret=True)
    assert (captured["bm"], captured["bn"], captured["bk"]) == tuned_block

    # without the cache the heuristic picks a different block ...
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(cache_path.parent / "empty.json"))
    tune_cache.reset_default_cache()
    y_plain = K.matmul(a, b, interpret=True)
    assert (captured["bm"], captured["bn"], captured["bk"]) != tuned_block

    # ... and numerics agree either way (block shape only permutes the fp32
    # accumulation order), both matching the XLA reference
    np.testing.assert_allclose(np.asarray(y_tuned), np.asarray(y_plain),
                               rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(np.asarray(y_tuned), np.asarray(a @ b),
                               rtol=5e-4, atol=5e-4)


def test_reference_backend_prefers_tuned_plan(cache_path):
    from repro.core import ops as core_ops

    key = CacheKey("reference", hw.get_chip(None).name, 256, 256, 256, "float32")
    tune_cache.default_cache().store(key, TunedPlan(64, 64, 64, 1.0, 1.0, "stub"))
    assert core_ops._reference_blocks(256, 256, 256, jnp.dtype("float32")) == (
        (64, 64, 64),
        "tuned",
    )
    # non-dividing problem ignores the entry (no entry for 96 anyway)
    (bm, bn, bk), _ = core_ops._reference_blocks(96, 96, 96, jnp.dtype("float32"))
    assert 96 % bm == 0 and 96 % bn == 0 and 96 % bk == 0
    # numerics through the public API with a tuned reference plan
    a = jax.random.normal(jax.random.PRNGKey(2), (256, 256), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(3), (256, 256), jnp.float32)
    with core_ops.use_backend("reference"):
        y = core_ops.matmul(a, w)
    np.testing.assert_allclose(np.asarray(y), np.asarray(a @ w), rtol=2e-4, atol=2e-4)


def test_largest_divisor_block_caps():
    f = __import__("repro.core.ops", fromlist=["_largest_divisor_block"])
    # cap is honoured even when a larger power of two divides
    assert f._largest_divisor_block(2048, 512) == 512
    # non-power-of-two cap rounds down to a power of two
    assert f._largest_divisor_block(2048, 500) == 256
    # odd dims fall through to the dim itself
    assert f._largest_divisor_block(97, 512) == 97


# -- CLI --------------------------------------------------------------------


def test_cli_miss_then_hit(cache_path, capsys):
    from repro.tune.__main__ import main

    args = ["--m", "256", "--n", "256", "--k", "256",
            "--top-k", "2", "--repeats", "1", "--method", "xla-proxy"]
    assert main(args) == 0
    out1 = capsys.readouterr().out
    assert "winner" in out1 and "cache hit" not in out1
    assert cache_path.exists()

    assert main(args) == 0
    out2 = capsys.readouterr().out
    assert "cache hit" in out2

    assert main(["--list"]) == 0
    out3 = capsys.readouterr().out
    assert "1 entries" in out3 and "pallas-systolic" in out3


# -- chip registry ----------------------------------------------------------


def test_chip_registry():
    assert hw.get_chip(None) is hw.get_chip("tpu_v5e")
    assert hw.get_chip(hw.TPU_V4) is hw.TPU_V4
    assert "tpu_v4" in hw.chip_names()
    with pytest.raises(KeyError):
        hw.get_chip("no-such-chip")

    custom = hw.Chip(name="test_chip", vmem_budget_bytes=1 << 20)
    try:
        hw.set_default_chip(custom)
        assert hw.get_chip(None).name == "test_chip"
    finally:
        hw.set_default_chip("tpu_v5e")
    assert hw.get_chip(None) is hw.TPU_V5E


class _FakeDevice:
    def __init__(self, kind):
        self.device_kind = kind


@pytest.mark.parametrize("kind,name", [("TPU v5 lite", "tpu_v5e"), ("TPU v4", "tpu_v4")])
def test_attached_tpu_resolves_from_device_kind(monkeypatch, kind, name):
    """On a TPU backend the default chip follows ``device_kind``."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda: [_FakeDevice(kind)])
    monkeypatch.setattr(hw, "_override", None)
    monkeypatch.setenv("REPRO_CHIP", "tpu_v4" if name == "tpu_v5e" else "tpu_v5e")
    hw.attached_chip_name.cache_clear()
    try:
        assert hw.get_chip(None).name == name
    finally:
        hw.attached_chip_name.cache_clear()


def test_unknown_tpu_kind_raises(monkeypatch):
    """An attached TPU with no chip entry is an error, never a v5e default."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda: [_FakeDevice("TPU v99")])
    monkeypatch.setattr(hw, "_override", None)
    hw.attached_chip_name.cache_clear()
    try:
        with pytest.raises(KeyError, match="TPU v99"):
            hw.get_chip(None)
    finally:
        hw.attached_chip_name.cache_clear()


def test_unknown_repro_chip_raises(monkeypatch):
    """Off TPU, REPRO_CHIP names the modelled chip; a typo is an error."""
    monkeypatch.setattr(hw, "_override", None)
    monkeypatch.setenv("REPRO_CHIP", "tpu_v5f")
    with pytest.raises(KeyError, match="tpu_v5f"):
        hw.get_chip(None)
