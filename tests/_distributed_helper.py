"""Subprocess body for tests/test_distributed.py (8 host devices)."""

import dataclasses
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke
from repro.data.synthetic import make_batch
from repro.distributed import annotate, sharding
from repro.launch.mesh import make_mesh
from repro.models.registry import get_model
from repro.optim import adamw_init
from repro.train.loop import TrainConfig, make_train_step


def _mesh():
    return make_mesh((4, 2), ("data", "model"))


def train_equiv():
    cfg = dataclasses.replace(get_smoke("glm4-9b"), dtype="float32")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = adamw_init(params)
    batch = make_batch(cfg, batch=8, seq=16, kind="train", seed=0)
    step = make_train_step(model, TrainConfig())

    ref_p, _, ref_m = jax.jit(step)(params, opt, batch, 0)

    mesh = _mesh()
    with mesh, annotate.annotations(mesh):
        p_sh = sharding.param_shardings(params, mesh)
        o_sh = type(opt)(
            step=jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
            mu=sharding.zero1_shardings(params, mesh),
            nu=sharding.zero1_shardings(params, mesh),
        )
        b_sh = sharding.batch_shardings(batch, mesh)
        params_d = jax.device_put(params, p_sh)
        opt_d = jax.device_put(opt, o_sh)
        batch_d = jax.device_put(batch, b_sh)
        got_p, _, got_m = jax.jit(
            step, in_shardings=(p_sh, o_sh, b_sh, None)
        )(params_d, opt_d, batch_d, 0)

    np.testing.assert_allclose(
        float(ref_m["loss"]), float(got_m["loss"]), rtol=1e-5
    )
    for a, b in zip(jax.tree.leaves(ref_p), jax.tree.leaves(got_p)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4
        )
    print("PASS train_equiv")


def decode_equiv():
    cfg = dataclasses.replace(get_smoke("glm4-9b"), dtype="float32")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    cache = model.init_cache(8, 32, jnp.float32)
    tok = jnp.zeros((8, 1), jnp.int32)

    def step(p, c, t, pos):
        return model.decode_step(p, t, cache=c, pos=pos)

    ref_lg, _ = jax.jit(step)(params, cache, tok, jnp.int32(0))

    mesh = _mesh()
    with mesh, annotate.annotations(mesh):
        p_sh = sharding.param_shardings(params, mesh)
        c_sh = sharding.cache_shardings(cache, mesh)
        got_lg, _ = jax.jit(step, in_shardings=(p_sh, c_sh, None, None))(
            jax.device_put(params, p_sh), jax.device_put(cache, c_sh),
            tok, jnp.int32(0),
        )
    np.testing.assert_allclose(
        np.asarray(ref_lg), np.asarray(got_lg), rtol=2e-4, atol=2e-4
    )
    print("PASS decode_equiv")


def moe_ep():
    """MoE with grouped dispatch under EP sharding == single device."""
    cfg = dataclasses.replace(get_smoke("qwen3-moe-30b-a3b"), dtype="float32")
    cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, dispatch_groups=8, capacity_factor=4.0)
    )
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = make_batch(cfg, batch=8, seq=16, kind="train", seed=0)

    def fwd(p, b):
        return model.forward(p, b)[0]

    ref = jax.jit(fwd)(params, batch)
    mesh = _mesh()
    with mesh, annotate.annotations(mesh):
        p_sh = sharding.param_shardings(params, mesh)
        b_sh = sharding.batch_shardings(batch, mesh)
        got = jax.jit(fwd, in_shardings=(p_sh, b_sh))(
            jax.device_put(params, p_sh), jax.device_put(batch, b_sh)
        )
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got), rtol=2e-4, atol=2e-4)
    print("PASS moe_ep")


def _tp_mesh():
    return make_mesh((8,), ("model",))


def tp_allgather():
    """Overlapped all-gather collective matmul == single-device systolic
    reference, on an 8-way mesh: uneven K (pads inside the kernel), both
    dtypes, both ppermute ring directions, and the unoverlapped baseline.

    fp32 tolerances are round-off only: the sharded path accumulates each
    output element over the full K on one device exactly like the
    single-device kernel, but XLA:CPU's dot reduction grouping differs by
    operand shape, so bit-equality is not guaranteed.
    """
    from repro.distributed import collective_matmul as cm
    from repro.kernels.systolic import ops as sops

    mesh = _tp_mesh()
    for dtype, rtol, atol in (
        (jnp.float32, 2e-4, 2e-4),
        (jnp.bfloat16, 5e-2, 5e-1),
    ):
        a = jax.random.normal(jax.random.PRNGKey(0), (128, 200), dtype)
        b = jax.random.normal(jax.random.PRNGKey(1), (200, 256), dtype)
        ref = np.asarray(sops.matmul(a, b), np.float32)
        for direction in ("plus", "minus"):
            for overlap in (True, False):
                y = cm.all_gather_matmul(
                    a, b, mesh=mesh, direction=direction, overlap=overlap
                )
                np.testing.assert_allclose(
                    np.asarray(y, np.float32), ref, rtol=rtol, atol=atol,
                    err_msg=f"{dtype} {direction} overlap={overlap}",
                )
    print("PASS tp_allgather")


def tp_reducescatter():
    """Overlapped reduce-scatter (row-parallel) collective matmul == the
    single-device systolic reference: K sharded 8 ways, fp32 carries, uneven
    N, both dtypes and ring directions, plus the psum_scatter baseline."""
    from repro.distributed import collective_matmul as cm
    from repro.kernels.systolic import ops as sops

    mesh = _tp_mesh()
    for dtype, rtol, atol in (
        (jnp.float32, 2e-4, 2e-4),
        (jnp.bfloat16, 5e-2, 5e-1),
    ):
        a = jax.random.normal(jax.random.PRNGKey(2), (128, 512), dtype)
        b = jax.random.normal(jax.random.PRNGKey(3), (512, 200), dtype)
        ref = np.asarray(sops.matmul(a, b), np.float32)
        for direction in ("plus", "minus"):
            for overlap in (True, False):
                y = cm.reduce_scatter_matmul(
                    a, b, mesh=mesh, direction=direction, overlap=overlap
                )
                np.testing.assert_allclose(
                    np.asarray(y, np.float32), ref, rtol=rtol, atol=atol,
                    err_msg=f"{dtype} {direction} overlap={overlap}",
                )
    print("PASS tp_reducescatter")


def tp_ops_dispatch():
    """core.ops.matmul routes through the collective matmul under an active
    tensor_parallel context (divisible shapes) and falls through to the
    single-device kernel otherwise -- results identical either way."""
    from repro.core import ops as core_ops
    from repro.distributed import collective_matmul as cm

    mesh = _tp_mesh()
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 64, 256), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(5), (256, 512), jnp.float32)
    w_odd = jax.random.normal(jax.random.PRNGKey(6), (256, 129), jnp.float32)
    with core_ops.use_backend("pallas-systolic"):
        ref = core_ops.matmul(x, w)
        ref_odd = core_ops.matmul(x, w_odd)
        with cm.tensor_parallel(mesh):
            got = core_ops.matmul(x, w)
            got_odd = core_ops.matmul(x, w_odd)  # N=129: falls through
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=2e-4, atol=2e-4
    )
    np.testing.assert_array_equal(np.asarray(got_odd), np.asarray(ref_odd))
    print("PASS tp_ops_dispatch")


def tp_serve_equiv():
    """--model-parallel engine (TP-sharded params, greedy fp32) generates the
    same tokens as the single-device engine.

    TP=4 keeps the sharding on whole-head boundaries (smoke n_heads=4); a
    deeper degree would split the rotary head_dim across devices, which is
    both the wrong layout (Megatron shards heads, not head_dim) and a known
    XLA:CPU partitioner numerics hazard -- ServeEngine warns on it.
    """
    from repro.serving import ServeConfig, ServeEngine

    cfg = dataclasses.replace(get_smoke("internlm2-1.8b"), dtype="float32")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = make_batch(cfg, batch=2, seq=16, kind="prefill", seed=0)
    scfg = ServeConfig(max_len=24, batch=2)

    ref = ServeEngine(model, params, scfg).generate(batch, 8)
    mesh = make_mesh((2, 4), ("data", "model"))
    got = ServeEngine(model, params, scfg, mesh=mesh).generate(batch, 8)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))

    # At bf16 compute the engine's served copy keeps the TP layout: every
    # leaf, cast or not, stays on the sharding ``param_shardings`` gave it.
    eng = ServeEngine(
        get_model(dataclasses.replace(cfg, dtype="bfloat16")), params, scfg, mesh=mesh
    )
    served = jax.tree.leaves(eng.params)
    assert any(leaf.dtype == jnp.bfloat16 for leaf in served)
    for want, leaf in zip(jax.tree.leaves(sharding.param_shardings(params, mesh)), served):
        assert leaf.sharding.is_equivalent_to(want, leaf.ndim), (leaf.shape, leaf.sharding)

    import warnings

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        ServeEngine(
            model, params, scfg, mesh=make_mesh((1, 8), ("data", "model"))
        )
    assert any("n_heads" in str(w.message) for w in caught), [
        str(w.message) for w in caught
    ]
    print("PASS tp_serve_equiv")


if __name__ == "__main__":
    {
        "train_equiv": train_equiv,
        "decode_equiv": decode_equiv,
        "moe_ep": moe_ep,
        "tp_allgather": tp_allgather,
        "tp_reducescatter": tp_reducescatter,
        "tp_ops_dispatch": tp_ops_dispatch,
        "tp_serve_equiv": tp_serve_equiv,
    }[sys.argv[1]]()
