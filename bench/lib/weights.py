"""Random weights from the seed, made on the device in one jitted call.

The benchmark makes the weights, and both the program and the plain
reference read them: the reference takes nothing the program made.  A
family's ``weight_spec(cfg)`` gives the tree, in the layout the program's
parameters have, with each leaf as ``("normal", shape, std)`` or
``("ones", shape)``.  Each leaf is made in the dtype the program's own
``init`` declares for it, so the program is served the type it serves.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def key_for(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed, all of its bits used."""
    words = np.random.SeedSequence(seed % 2**128).generate_state(2)
    return jnp.asarray(words, jnp.uint32)


def _is_spec(node) -> bool:
    return isinstance(node, tuple) and isinstance(node[0], str)


def check_layout(spec, abstract) -> None:
    """Raise unless ``spec`` has the structure and shapes of ``abstract``
    (the program's ``jax.eval_shape(model.init, ...)``)."""
    ours = jax.tree.structure(spec, is_leaf=_is_spec)
    theirs = jax.tree.structure(abstract)
    if ours != theirs:
        raise ValueError(
            f"the program's parameter layout changed:\n  benchmark {ours}\n  program   {theirs}"
        )
    for path, (s, a) in zip(
        [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_leaves_with_path(abstract)],
        zip(jax.tree.leaves(spec, is_leaf=_is_spec), jax.tree.leaves(abstract)),
    ):
        if tuple(s[1]) != tuple(a.shape):
            raise ValueError(f"{path}: benchmark shape {s[1]} != program shape {a.shape}")


def make(spec, abstract, seed: int):
    """The weight tree for ``seed``; leaves in ``abstract``'s dtypes."""
    check_layout(spec, abstract)
    leaves = jax.tree.leaves(spec, is_leaf=_is_spec)
    dtypes = [a.dtype for a in jax.tree.leaves(abstract)]
    treedef = jax.tree.structure(abstract)

    @jax.jit
    def gen(key_words):
        key = jax.random.wrap_key_data(key_words, impl="threefry2x32")
        out = []
        for i, (leaf, dt) in enumerate(zip(leaves, dtypes)):
            if leaf[0] == "ones":
                out.append(jnp.ones(leaf[1], dt))
            else:
                k = jax.random.fold_in(key, i)
                out.append((jax.random.normal(k, leaf[1], jnp.float32) * leaf[2]).astype(dt))
        return out

    return jax.tree.unflatten(treedef, gen(key_for(seed)))
