"""Serving engine: batched generate, determinism, slot reset, per-slot
(vector-pos) decode primitives, and the KV slot pool."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.data.synthetic import make_batch
from repro.models.registry import get_model
from repro.serving import KVPool, ServeConfig, ServeEngine
from repro.serving.engine import consult_decode_plans, decode_gemm_problems


def _engine(arch="internlm2-1.8b", batch=2, temperature=0.0, max_len=64):
    cfg = dataclasses.replace(get_smoke(arch), dtype="float32")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(
        model,
        params,
        ServeConfig(max_len=max_len, batch=batch, temperature=temperature),
    )
    return eng, cfg


def test_generate_shapes_and_determinism():
    eng, cfg = _engine()
    prompts = make_batch(cfg, batch=2, seq=8, kind="prefill", seed=1)
    out1 = eng.generate(prompts, n_steps=6)
    assert out1.shape == (2, 6)
    assert out1.dtype == jnp.int32
    eng2, _ = _engine()
    out2 = eng2.generate(prompts, n_steps=6)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


def test_greedy_matches_argmax_of_forward():
    """The first generated token equals argmax of the full forward."""
    eng, cfg = _engine()
    prompts = make_batch(cfg, batch=2, seq=8, kind="prefill", seed=2)
    first = eng.prefill(prompts)
    full, _ = eng.model.forward(eng.params, prompts)
    np.testing.assert_array_equal(
        np.asarray(first[:, 0]), np.asarray(jnp.argmax(full[:, -1], axis=-1))
    )


def test_temperature_sampling_runs():
    eng, cfg = _engine(temperature=1.0)
    prompts = make_batch(cfg, batch=2, seq=8, kind="prefill", seed=3)
    out = eng.generate(prompts, n_steps=5)
    assert out.shape == (2, 5)
    assert int(out.min()) >= 0 and int(out.max()) < cfg.vocab_size


def test_audio_multistream_generate():
    eng, cfg = _engine("musicgen-medium")
    prompts = make_batch(cfg, batch=2, seq=8, kind="prefill", seed=4)
    out = eng.generate(prompts, n_steps=4)
    assert out.shape == (2, 4, cfg.n_codebooks)


# ---------------------------------------------------------------------------
# Slot reset (continuous-batching rotation)
# ---------------------------------------------------------------------------


def test_reset_slots_zeroes_cache_and_invalidates_positions():
    eng, cfg = _engine()
    prompts = make_batch(cfg, batch=2, seq=8, kind="prefill", seed=5)
    eng.prefill(prompts)
    eng.reset_slots(jnp.asarray([1, 0]))
    k = eng.cache["layers"]["k"]  # (L, B, T, H, hd)
    assert float(jnp.max(jnp.abs(k[:, 0]))) == 0.0
    assert float(jnp.max(jnp.abs(k[:, 1]))) > 0.0
    # pos = 0 is a VALID position under valid(k) = pos[k] >= 0; cleared slots
    # must be marked -1, not 0, or slot 0's stale key stays attendable.
    pos = eng.cache["layers"]["pos"]  # (L, B, T)
    assert int(jnp.max(pos[:, 0])) == -1
    assert int(jnp.max(pos[:, 1])) >= 0


def test_reset_slot_cannot_attend_to_previous_request():
    """Regression: after reset_slots, decoding a fresh request in the freed
    slot matches decoding it against an empty cache -- the old request's
    keys are unreachable.

    The fresh request decodes at position 3, where the old request's keys
    at positions 0..2 would pass the causal mask if the reset left them
    valid.  The two sides differ only in batch shape (2 rows vs 1), which
    changes the fp32 reduction order of the CPU dot by about 1e-6; a leaked
    key moves the logits by O(1), which the un-reset control shows."""
    eng, cfg = _engine()
    model = eng.model
    prompts = make_batch(cfg, batch=2, seq=8, kind="prefill", seed=6)
    first = eng.prefill(prompts)
    eng.decode(first, 2)  # old request holds keys at positions 0..9
    stale = jax.tree.map(jnp.copy, eng.cache)
    eng.reset_slots(jnp.asarray([1, 0]))  # free slot 0

    tok = jnp.full((2, 1), 7, jnp.int32)
    # slot 0 restarts at pos 3; slot 1 keeps decoding at its depth
    pos = jnp.asarray([3, eng.pos], jnp.int32)
    lg, _ = model.decode_step(eng.params, tok, cache=eng.cache, pos=pos)
    leaked, _ = model.decode_step(eng.params, tok, cache=stale, pos=pos)

    fresh = model.init_cache(1, eng.scfg.max_len, jnp.float32)
    ref, _ = model.decode_step(
        eng.params, tok[:1], cache=fresh, pos=jnp.int32(3)
    )
    bound = 1e-4
    np.testing.assert_allclose(
        np.asarray(lg[0]), np.asarray(ref[0]), rtol=0, atol=bound
    )
    assert float(jnp.max(jnp.abs(leaked[0] - ref[0]))) > 1e3 * bound


# ---------------------------------------------------------------------------
# Vector-pos decode primitives
# ---------------------------------------------------------------------------


def test_vector_pos_decode_matches_scalar():
    """decode_slots with a constant position vector == synchronized decode."""
    eng, cfg = _engine()
    eng2, _ = _engine()
    prompts = make_batch(cfg, batch=2, seq=8, kind="prefill", seed=7)
    first = eng.prefill(prompts)
    ref = eng.decode(first, 3)

    first2 = eng2.prefill(prompts)
    cache = eng2.cache
    tok, outs = first2, []
    for i in range(3):
        pos = jnp.full((2,), 8 + i, jnp.int32)
        tok, cache = eng2.decode_slots(tok, cache, pos)
        outs.append(tok)
    np.testing.assert_array_equal(
        np.asarray(ref), np.asarray(jnp.concatenate(outs, axis=1))
    )


@pytest.mark.parametrize("arch", ["internlm2-1.8b", "minicpm3-4b"])
def test_negative_pos_slot_is_inert(arch):
    """A slot stepped with pos = -1 leaves its cache row bit-for-bit
    untouched (a paused/empty slot must not clobber live state, not even
    its own entry 0)."""
    eng, cfg = _engine(arch)
    prompts = make_batch(cfg, batch=2, seq=8, kind="prefill", seed=8)
    first = eng.prefill(prompts)
    before = jax.tree.map(lambda a: np.asarray(a[:, 0]), eng.cache["layers"])
    cache = eng.cache
    tok, cache = eng.decode_slots(first, cache, jnp.asarray([-1, 8], jnp.int32))
    after = jax.tree.map(lambda a: np.asarray(a[:, 0]), cache["layers"])
    for b, a in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        np.testing.assert_array_equal(b, a)
    # slot 1 advanced: position 8 recorded
    assert int(jnp.max(cache["layers"]["pos"][:, 1])) == 8


# ---------------------------------------------------------------------------
# KV slot pool
# ---------------------------------------------------------------------------


def test_kvpool_lifecycle_and_prefill_scatter():
    eng, cfg = _engine(batch=3, max_len=32)
    pool = KVPool(eng.model, 3, 32, jnp.float32)
    assert pool.n_free == 3 and pool.n_active == 0 and pool.occupancy() == 0.0

    s0 = pool.alloc()
    prompt = make_batch(cfg, batch=1, seq=6, kind="prefill", seed=9)
    first, cache_one = eng.prefill_request(prompt)
    pool.write_prefill(s0, cache_one, 6)
    assert pool.n_active == 1
    assert pool.positions[s0] == 6
    np.testing.assert_array_equal(
        np.asarray(pool.cache["layers"]["k"][:, s0]),
        np.asarray(cache_one["layers"]["k"][:, 0]),
    )
    # untouched slots stay masked
    other = [s for s in range(3) if s != s0][0]
    assert int(jnp.max(pool.cache["layers"]["pos"][:, other])) == -1

    pool.free(s0)
    assert pool.n_free == 3
    assert pool.positions[s0] == -1
    assert int(jnp.max(pool.cache["layers"]["pos"][:, s0])) == -1
    assert float(jnp.max(jnp.abs(pool.cache["layers"]["k"][:, s0]))) == 0.0
    with pytest.raises(ValueError):
        pool.free(s0)


def test_kvpool_pos_vector_drives_decode():
    eng, cfg = _engine(batch=2, max_len=32)
    pool = KVPool(eng.model, 2, 32, jnp.float32)
    slot = pool.alloc()
    prompt = make_batch(cfg, batch=1, seq=5, kind="prefill", seed=10)
    first, cache_one = eng.prefill_request(prompt)
    pool.write_prefill(slot, cache_one, 5)
    pos = np.asarray(pool.pos_vector())
    assert pos[slot] == 5 and (pos[[s for s in range(2) if s != slot]] == -1).all()

    tok = jnp.zeros((2, 1), jnp.int32)
    tok = tok.at[slot].set(first[0])
    _, pool.cache = eng.decode_slots(tok, pool.cache, pool.pos_vector())
    pool.advance([slot])
    assert pool.positions[slot] == 6


def _all_pos_masked(cache_one) -> bool:
    """Every integer (pos) leaf of a batch-1 cache view is fully -1."""
    ok = True
    for leaf in jax.tree.leaves(cache_one):
        if jnp.issubdtype(leaf.dtype, jnp.integer):
            ok = ok and bool(jnp.all(leaf == -1))
    return ok


def test_kvpool_gather_freed_slot_stays_masked():
    """``gather_slot`` of a freed slot yields a view with every position
    ``pos = -1``: the invariant that makes freeing a *masking* operation
    (stale keys unreachable) rather than only a zeroing one."""
    eng, cfg = _engine(batch=2, max_len=32)
    pool = KVPool(eng.model, 2, 32, jnp.float32)
    slot = pool.alloc()
    prompt = make_batch(cfg, batch=1, seq=6, kind="prefill", seed=11)
    _, cache_one = eng.prefill_request(prompt)
    pool.write_prefill(slot, cache_one, 6)
    assert not _all_pos_masked(pool.gather_slot(slot))  # live: positions set
    pool.free(slot)
    view = pool.gather_slot(slot)
    assert _all_pos_masked(view)
    for leaf in jax.tree.leaves(view):
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            assert float(jnp.max(jnp.abs(leaf))) == 0.0


def test_kvpool_write_slot_next_pos_none_keeps_host_mask():
    """``write_slot(..., next_pos=None)`` mid-prefill lands K/V rows in the
    pool but keeps the HOST position -1, so the co-scheduled vector-pos
    decode still sees the slot as empty (guards the invariant the chunked
    prefill of PR 4 leans on)."""
    eng, cfg = _engine(batch=2, max_len=32)
    pool = KVPool(eng.model, 2, 32, jnp.float32)
    slot = pool.alloc()
    prompt = make_batch(cfg, batch=1, seq=6, kind="prefill", seed=12)
    _, cache_one = eng.prefill_request(prompt)

    pool.write_slot(slot, cache_one, next_pos=None)
    # device rows landed ...
    np.testing.assert_array_equal(
        np.asarray(pool.cache["layers"]["k"][:, slot]),
        np.asarray(cache_one["layers"]["k"][:, 0]),
    )
    # ... but the host mask still reports the slot empty
    assert pool.positions[slot] == -1
    assert int(np.asarray(pool.pos_vector())[slot]) == -1

    # a decode step over the pool leaves the mid-prefill slot's cache rows
    # bit-for-bit untouched (its query position is -1 -> inert row)
    before = jax.tree.map(lambda a: np.asarray(a), pool.cache)
    tok = jnp.zeros((2, 1), jnp.int32)
    _, pool.cache = eng.decode_slots(tok, pool.cache, pool.pos_vector())
    after = pool.cache
    for b, a in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        np.testing.assert_array_equal(b, np.asarray(a))

    # finishing the prefill with a real next_pos flips the slot live
    pool.write_slot(slot, cache_one, next_pos=6)
    assert pool.positions[slot] == 6


# ---------------------------------------------------------------------------
# Decode-shape plan consultation (repro.tune cache)
# ---------------------------------------------------------------------------


def test_decode_gemm_problems_shapes():
    _, cfg = _engine()
    probs = decode_gemm_problems(cfg, batch=4)
    assert probs and all(m == 4 for _, m, _, _ in probs)
    names = [n for n, *_ in probs]
    assert "wq" in names and "ffn_in" in names
    _, mla_cfg = _engine("minicpm3-4b")
    mla_names = [n for n, *_ in decode_gemm_problems(mla_cfg, batch=4)]
    assert "wq_a" in mla_names and "wkv_a" in mla_names


def test_engine_consults_tune_cache(tmp_path, monkeypatch):
    """A plan stored for a decode GEMM problem is visible to the engine."""
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "plans.json"))
    from repro.core import hw
    from repro.tune import cache as tune_cache

    tune_cache.reset_default_cache()
    try:
        eng, cfg = _engine()
        assert all(p is None for _, p in eng.decode_plans.values())

        name, m, n, k = decode_gemm_problems(cfg, batch=2)[0]
        chip = hw.get_chip(None)
        tune_cache.default_cache().store(
            tune_cache.CacheKey(
                "pallas-systolic", chip.name, m, n, k, str(jnp.dtype(cfg.dtype))
            ),
            tune_cache.TunedPlan(
                bm=8, bn=128, bk=128, mean_us=1.0, best_us=1.0, method="stub"
            ),
        )
        plans = consult_decode_plans(cfg, 2)
        assert plans[name][1] is not None
        eng2, _ = _engine()
        hits = sum(1 for _, p in eng2.decode_plans.values() if p is not None)
        assert hits >= 1  # identical (m,n,k) problems (wk/wv) share one plan
        assert f"{hits}/" in eng2.decode_plan_report()
    finally:
        tune_cache.reset_default_cache()
