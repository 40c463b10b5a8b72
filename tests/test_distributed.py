"""Distributed: sharding rules + 8-device pjit equivalence (subprocess).

The multi-device checks run in a subprocess so the 8-device XLA_FLAGS never
leaks into this test process (smoke tests must see 1 device).
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ALL_ARCHS, get_smoke
from repro.distributed import sharding
from repro.launch.mesh import make_mesh
from repro.models.registry import get_model

_HELPER = os.path.join(os.path.dirname(__file__), "_distributed_helper.py")


def test_param_specs_cover_every_leaf():
    """Every arch's every param leaf gets a spec with matching rank and
    divisible shardings (rule completeness)."""
    mesh = make_mesh((1, 1), ("data", "model"))
    for arch in ALL_ARCHS:
        cfg = get_smoke(arch)
        model = get_model(cfg)
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        specs = sharding.param_specs(params, mesh)
        n = 0
        for leaf, spec in zip(jax.tree.leaves(params), jax.tree.leaves(
                specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))):
            assert len(spec) <= leaf.ndim, (arch, leaf.shape, spec)
            n += 1
        assert n > 0


def test_cache_specs_cover_every_leaf():
    mesh = make_mesh((1, 1), ("data", "model"))
    for arch in ALL_ARCHS:
        cfg = get_smoke(arch)
        model = get_model(cfg)
        cache = jax.eval_shape(lambda: model.init_cache(2, 16, jnp.float32))
        specs = sharding.cache_specs(cache, mesh)
        for leaf, spec in zip(jax.tree.leaves(cache), jax.tree.leaves(
                specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))):
            assert len(spec) <= leaf.ndim, (arch, leaf.shape, spec)


def test_zero1_adds_data_axis():
    mesh = make_mesh((1, 1), ("data", "model"))
    params = {"w_gate": jax.ShapeDtypeStruct((64, 128), jnp.float32)}
    z = sharding.zero1_specs(params, mesh)
    # data axis size 1 -> divisible, placed on the first free dim
    assert z["w_gate"][0] == "data" or z["w_gate"][0] is None


@pytest.mark.parametrize(
    "case",
    [
        "train_equiv",
        "decode_equiv",
        "moe_ep",
        "tp_allgather",
        "tp_reducescatter",
        "tp_ops_dispatch",
        "tp_serve_equiv",
    ],
)
def test_multidevice_subprocess(case):
    """pjit on a (4, 2) mesh reproduces the single-device step bit-for-bit
    (well, fp32-for-fp32); the tp_* cases run the shard_map collective
    matmul on an 8-way "model" mesh against the single-device systolic
    reference (uneven K/N, bf16+f32, both ppermute ring directions)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = env.get("PYTHONPATH", "") + os.pathsep + os.path.abspath("src")
    out = subprocess.run(
        [sys.executable, _HELPER, case],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr[-3000:]}"
    assert "PASS" in out.stdout


# ---------------------------------------------------------------------------
# Mesh-level blocking / plumbing (no devices needed)
# ---------------------------------------------------------------------------


def test_make_local_mesh_oversubscribed_names_the_fix():
    """Asking for more devices than exist must fail loudly, naming the
    XLA_FLAGS escape hatch (never fall back to a silent smaller mesh)."""
    from repro.launch.mesh import make_local_mesh

    n = len(jax.devices())
    with pytest.raises(ValueError, match="xla_force_host_platform_device_count"):
        make_local_mesh(n + 1, 2)


def test_blockplan_mesh_level():
    from repro.core.blocking import BlockPlan

    plan = BlockPlan(2048, 1024, 512, 256, 128, 512, tp=8)
    assert plan.shard_shape() == (256, 128, 512)
    assert plan.hop_bytes() == 256 * 512 * 2
    # tp=1 plans are trivially balanced and move no collective bytes.
    single = BlockPlan(2048, 1024, 512, 256, 128, 512)
    assert single.hop_bytes() == 0 and single.mesh_balanced()


def test_dse_explores_mesh_level():
    from repro.core import dse

    recs = dse.explore(1024, 1024, 512, tps=(1, 2, 4, 8))
    assert {r.tp for r in recs} == {1, 2, 4, 8}
    for r in recs:
        if r.tp > 1:
            assert r.ident.endswith(f"@tp{r.tp}")
    # indivisible tp is skipped, like any other infeasible geometry
    assert all(r.tp != 3 for r in dse.explore(1024, 1024, 512, tps=(3,)))


def test_tune_cache_key_carries_tp():
    from repro.tune.cache import CacheKey

    k1 = CacheKey("pallas-systolic", "tpu_v5e", 512, 512, 512, "bfloat16")
    k8 = CacheKey("pallas-systolic", "tpu_v5e", 512, 512, 512, "bfloat16", tp=8)
    assert k1.encode() != k8.encode()
    assert k1.encode().endswith("tp1") and k8.encode().endswith("tp8")


def test_tp_tuned_block_clamps_to_shard_problem(tmp_path, monkeypatch):
    """A tp-keyed cache hit whose geometry exceeds the per-shard ring-step
    problem must clamp to it: reduce-scatter steps contract only K/tp, so a
    cached bk up to K would pad the contraction tp-fold if served as-is."""
    from repro.distributed.collective_matmul import _tp_tuned_block
    from repro.tune import cache as tune_cache

    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "plans.json"))
    tune_cache.reset_default_cache()
    key = tune_cache.CacheKey(
        "pallas-systolic", "tpu_v5e", 2048, 1024, 4096, "bfloat16", tp=8
    )
    tune_cache.default_cache().store(
        key, tune_cache.TunedPlan(256, 128, 4096, 1.0, 1.0, "stub")
    )
    # all-gather step (M/tp, N/tp, K): full-K contraction, bk survives
    assert _tp_tuned_block(
        2048, 1024, 4096, "bfloat16", 8, (256, 128, 4096)
    ) == (256, 128, 4096)
    # reduce-scatter step (M/tp, N, K/tp): bk clamps to K/tp = 512
    assert _tp_tuned_block(
        2048, 1024, 4096, "bfloat16", 8, (256, 1024, 512)
    ) == (256, 128, 512)
    tune_cache.reset_default_cache()


def test_tensor_parallel_context_rejects_missing_axis():
    from repro.distributed import collective_matmul as cm

    mesh = make_mesh((1, 1), ("data", "model"))
    with pytest.raises(ValueError, match="no axis"):
        with cm.tensor_parallel(mesh, axis="pod"):
            pass
    assert cm.current_tensor_parallel() is None
    with cm.tensor_parallel(mesh):
        assert cm.current_tensor_parallel() == (mesh, "model")
    assert cm.current_tensor_parallel() is None
