"""Production mesh builders.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state -- the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first jax
init, smoke tests see the real single device.

Mesh layout (DESIGN.md §4):
  single-pod: (16, 16)      axes ("data", "model")    = 256 chips
  multi-pod:  (2, 16, 16)   axes ("pod", "data", "model") = 512 chips

Batch shards over ("pod", "data") -- pure DP across pods keeps inter-pod
traffic to one gradient all-reduce per step (DCN-friendly); weights shard
over "model" (TP/EP) and, FSDP-style, over "data" (ZeRO-3).
"""

from __future__ import annotations

import jax


def make_mesh(shape, axes, *, devices=None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis in ``Auto`` mode.

    JAX 0.9 defaults new meshes to ``Explicit`` axes, under which the
    GSPMD-style code here (``param_shardings`` + ``with mesh`` +
    ``annotate``) is refused, e.g. a gather from a model-sharded embedding
    table.  Every mesh of the repo is built here so all of it runs in the
    mode it was written for.
    """
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=auto, devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """Small mesh over whatever devices exist (tests / single-host runs)."""
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(
            f"requested a {data}x{model} ('data', 'model') mesh but only "
            f"{n} device(s) are visible. On CPU, fake a mesh by setting "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={data * model} "
            f"in the environment BEFORE the first jax call (e.g. "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={data * model} "
            f"python -m repro.launch.serve --model-parallel {model} ...)."
        )
    return make_mesh((data, model), ("data", "model"))


def batch_axes(mesh: jax.sharding.Mesh) -> tuple[str, ...]:
    """The axes the global batch shards over."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def axis_size(mesh: jax.sharding.Mesh, name: str) -> int:
    if name not in mesh.axis_names:
        return 1
    return mesh.shape[name]
