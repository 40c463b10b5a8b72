"""The plain references agree with the program's forward pass at smoke sizes.

Both sides in float32 at the highest matmul precision on the CPU, the same
seeded weights: what is left is summation order, far below the gaps that
decide ``correct`` on the chip.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import reference, spec, weights
from bench.tests import smoke


@pytest.mark.parametrize("cfg", [smoke.GQA, smoke.MLA], ids=["gqa", "mla"])
def test_reference_matches_program_forward(cfg):
    from repro.core import ops
    from repro.models.registry import get_model

    cfg = dict(cfg, name=f"ref-{cfg['family']}", compute_dtype="float32")
    fam = spec.family(cfg["family"])
    model = get_model(fam.arch_config(cfg))
    abstract = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    w = weights.make(fam.weight_spec(cfg), abstract, seed=2**33 + 7)
    t = reference.Q_BLOCK
    tokens = np.random.default_rng(0).integers(0, cfg["vocab_size"], t).astype(np.int32)
    with jax.default_matmul_precision("highest"), ops.use_backend("xla"):
        got, _ = model.forward(w, {"tokens": jnp.asarray(tokens)[None]})
        hidden = jax.jit(fam.hidden_fn(cfg))(w, jnp.asarray(tokens))
        want = reference.dot("sd,dv->sv", hidden, w["lm_head"]["w"])
    got, want = np.asarray(got[0]), np.asarray(want)
    scale = np.max(np.abs(want))
    assert np.max(np.abs(got - want)) <= 1e-4 * scale


def test_served_gaps_are_zero_for_the_reference_own_greedy_tokens():
    cfg = dict(smoke.GQA, name="ref-gaps", compute_dtype="float32")
    fam = spec.family("gqa")
    from repro.models.registry import get_model

    model = get_model(fam.arch_config(cfg))
    abstract = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    w = weights.make(fam.weight_spec(cfg), abstract, seed=5)
    fwd = fam.hidden_fn(cfg)
    prompt = np.arange(1, 9, dtype=np.int32)
    seq = list(prompt)
    for _ in range(6):  # greedy decode with the reference itself
        toks = np.zeros(reference.Q_BLOCK, np.int32)
        toks[: len(seq)] = seq
        with jax.default_matmul_precision("highest"):
            h = jax.jit(fwd)(w, jnp.asarray(toks))
            logits = reference.dot("d,dv->v", h[len(seq) - 1], w["lm_head"]["w"])
        seq.append(int(jnp.argmax(logits)))
    served = np.asarray(seq[len(prompt):], np.int32)
    gaps = reference.served_gaps(fwd, w, prompt, served, reference.Q_BLOCK, 16)
    assert np.max(gaps) <= 1e-5
    wrong = served.copy()
    wrong[3] = (wrong[3] + 1) % cfg["vocab_size"]
    assert reference.served_gaps(fwd, w, prompt, wrong, reference.Q_BLOCK, 16)[3] > 1e-3
