"""Checkpoint: atomic save, resume, async writer, elastic re-shard."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (
    AsyncCheckpointer,
    latest_step,
    restore_checkpoint,
    save_checkpoint,
)
from repro.launch.mesh import make_mesh


def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {
        "params": {"w": jax.random.normal(k, (8, 8)), "b": jnp.zeros((8,))},
        "step_array": jnp.int32(7),
    }


def test_roundtrip(tmp_path):
    t = _tree()
    save_checkpoint(str(tmp_path), 42, t)
    restored, step = restore_checkpoint(str(tmp_path), jax.tree.map(jnp.zeros_like, t))
    assert step == 42
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b)),
        restored,
        t,
    )


def test_latest_step_ignores_incomplete(tmp_path):
    t = _tree()
    save_checkpoint(str(tmp_path), 10, t)
    save_checkpoint(str(tmp_path), 20, t)
    # fake an incomplete checkpoint (no DONE marker)
    os.makedirs(tmp_path / "step_00000030")
    assert latest_step(str(tmp_path)) == 20


def test_restore_missing_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        restore_checkpoint(str(tmp_path / "none"), _tree())


def test_shape_mismatch_raises(tmp_path):
    save_checkpoint(str(tmp_path), 1, {"w": jnp.zeros((4,))})
    with pytest.raises(ValueError):
        restore_checkpoint(str(tmp_path), {"w": jnp.zeros((5,))})


def test_async_checkpointer_gc(tmp_path):
    ck = AsyncCheckpointer(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        ck.save(s, _tree(s))
    ck.wait()
    assert latest_step(str(tmp_path)) == 4
    kept = sorted(n for n in os.listdir(tmp_path) if n.startswith("step_"))
    assert len(kept) == 2  # gc keeps the newest `keep`


def test_elastic_reshard_restore(tmp_path):
    """Restore with explicit shardings places leaves on the current mesh --
    the elastic path a downsized restart takes."""
    t = _tree()
    save_checkpoint(str(tmp_path), 5, t)
    mesh = make_mesh((1, 1), ("data", "model"))
    from jax.sharding import NamedSharding, PartitionSpec as P

    sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), t)
    restored, step = restore_checkpoint(
        str(tmp_path), jax.tree.map(jnp.zeros_like, t), shardings=sh
    )
    assert step == 5
    assert restored["params"]["w"].sharding == sh["params"]["w"]
