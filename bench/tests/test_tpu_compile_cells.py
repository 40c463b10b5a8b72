"""Each cell's decode step and largest prefill chunk compile for a TPU v5e at
full width, with the systolic kernel in them (no chip needed).

The compiler refuses here what interpret mode accepts: tiles that break the
(8, 128) rule, working sets over a kernel's VMEM limit, a program larger than
the chip's memory.  The topology is described inside a module fixture, never
at import: only one process at a time may load the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bench.lib import spec

CELLS = ["minicpm3-docqa-open", "internlm2-chat-saturated"]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler can be loaded here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(desc.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _shapes(tree, sharding):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("phase", ["decode", "chunk"])
def test_cell_program_compiles(one_chip, cell, phase, monkeypatch):
    from repro.core import ops
    from repro.models.registry import get_model

    monkeypatch.setenv("REPRO_INTERPRET", "0")
    bench = spec.load_benchmark()
    wl = spec.workload(bench, cell)
    cfg = spec.config(bench, wl["config"])
    mix = spec.traffic(wl["traffic"])
    model = get_model(spec.family(cfg["family"]).arch_config(cfg))
    max_len = mix["prompt"]["max"] + mix["output"]["max"]
    params = _shapes(jax.eval_shape(model.init, jax.random.PRNGKey(0)), one_chip)
    i32 = jnp.int32
    with ops.use_backend("pallas-systolic"):
        if phase == "decode":
            b = mix["slots"]
            cache = _shapes(jax.eval_shape(lambda: model.init_cache(b, max_len)), one_chip)
            fn = lambda p, t, c, pos: model.decode_step(p, t, cache=c, pos=pos)  # noqa: E731
            args = (params, jax.ShapeDtypeStruct((b, 1), i32, sharding=one_chip), cache,
                    jax.ShapeDtypeStruct((b,), i32, sharding=one_chip))
        else:
            n = mix["chunk_size"]
            cache = _shapes(jax.eval_shape(lambda: model.init_cache(1, max_len)), one_chip)
            fn = lambda p, t, c, off: model.prefill_chunk(p, {"tokens": t}, cache=c, offset=off)  # noqa: E731
            args = (params, jax.ShapeDtypeStruct((1, n), i32, sharding=one_chip), cache,
                    jax.ShapeDtypeStruct((), i32, sharding=one_chip))
        compiled = jax.jit(fn, donate_argnums=(2,)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    # 15.75 GiB: the ``bytes_limit`` a v5e chip reports in ``memory_stats()``
    assert total < 15.75 * 2**30, f"{cell} {phase}: {total / 2**30:.2f} GiB"
