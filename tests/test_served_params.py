"""The engine's served copy of the weights (DESIGN.md §8).

``ServeEngine`` casts every ``layers.COMPUTE_CAST_LEAVES`` leaf to the
compute dtype once, when it is built, so its step programs convert no
weight.  These tests hold that copy to the arithmetic of the caller's fp32
tree, bit for bit, in every family the engine serves.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import quant
from repro.configs import get_smoke
from repro.models.layers import COMPUTE_CAST_LEAVES
from repro.models.registry import get_model
from repro.obs import metrics
from repro.quant.qarray import QArray
from repro.serving import ServeConfig, ServeEngine

FAMILIES = {
    "gqa": "internlm2-1.8b",
    "mla": "minicpm3-4b",
    "moe": "qwen3-moe-30b-a3b",
    "ssm": "xlstm-125m",
    "hybrid": "zamba2-7b",
}
MAX_LEN = 32
PROMPT = 8


def _build(family, quantized=False):
    cfg = get_smoke(FAMILIES[family])
    assert cfg.dtype == "bfloat16"
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    if quantized:
        params = quant.quantize_params(params, "int8")
    eng = ServeEngine(model, params, ServeConfig(max_len=MAX_LEN, batch=2))
    return eng, model, params


def _is_qarray(x):
    return isinstance(x, QArray)


def _leaves(tree):
    return jax.tree_util.tree_leaves_with_path(tree, is_leaf=_is_qarray)


def _cast_role(path, leaf):
    return (
        not isinstance(leaf, QArray)
        and getattr(path[-1], "key", None) in COMPUTE_CAST_LEAVES
        and jnp.issubdtype(leaf.dtype, jnp.floating)
    )


def _assert_same(a, b):
    jax.tree.map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x), np.asarray(y)), a, b
    )


@pytest.mark.parametrize("quantized", [False, True], ids=["fp32", "int8"])
@pytest.mark.parametrize("family", list(FAMILIES))
def test_served_copy_steps_match_the_callers_tree(family, quantized):
    eng, model, params = _build(family, quantized)
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, model.cfg.vocab_size, (1, PROMPT)), jnp.int32)

    # One prefill chunk, then one decode step from the primed cache: the
    # engine's programs on its copy, the model's own on the caller's tree.
    chunk_ref = jax.jit(
        lambda p, t, c: model.prefill_chunk(p, {"tokens": t}, cache=c, offset=0)
    )
    ref_logits, ref_cache = chunk_ref(params, prompt, model.init_cache(1, MAX_LEN))
    tok, cache = eng.prefill_chunk(prompt, model.init_cache(1, MAX_LEN), 0, last=True)
    np.testing.assert_array_equal(
        np.asarray(tok), np.asarray(jnp.argmax(ref_logits, axis=-1))
    )
    _assert_same(cache, ref_cache)

    pos = jnp.asarray([PROMPT], jnp.int32)
    decode_ref = jax.jit(lambda p, t, c, q: model.decode_step(p, t, cache=c, pos=q))
    ref_logits, ref_cache = decode_ref(params, tok, ref_cache, pos)
    logits, _ = eng._decode(eng.params, tok, jax.tree.map(jnp.copy, cache), pos)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(ref_logits))
    tok2, cache = eng.decode_slots(tok, cache, pos)
    np.testing.assert_array_equal(
        np.asarray(tok2), np.asarray(jnp.argmax(ref_logits, axis=-1))
    )
    _assert_same(cache, ref_cache)

    # Roles: listed float leaves in the compute dtype, every other leaf as
    # the caller gave it, QArrays the caller's own objects.
    served = dict(_leaves(eng.params))
    n_cast = n_kept = 0
    for path, leaf in _leaves(params):
        mine = served[path]
        if isinstance(leaf, QArray):
            assert mine is leaf, jax.tree_util.keystr(path)
        elif _cast_role(path, leaf):
            assert mine.dtype == jnp.bfloat16, jax.tree_util.keystr(path)
            n_cast += 1
        else:
            assert mine.dtype == leaf.dtype, jax.tree_util.keystr(path)
            n_kept += 1
    assert n_cast and n_kept
    if quantized:
        assert any(isinstance(x, QArray) for _, x in served.items())

    # The caller's tree is still fp32 and readable after the steps ran.
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        if jnp.issubdtype(leaf.dtype, jnp.floating) and not quantized:
            assert leaf.dtype == jnp.float32, jax.tree_util.keystr(path)
        assert not leaf.is_deleted()
        np.asarray(leaf)


# A convert whose operand is a function argument: a weight (or its per-layer
# slice, which the layer scan passes to its body as an argument) of rank 2
# or more, converted from f32 inside the program.
_PARAM_CONVERT = re.compile(r"stablehlo\.convert %arg\d+ : \(tensor<(?:\d+x){2,}f32>\)")


@pytest.mark.parametrize("family", ["gqa", "mla"])
def test_step_programs_convert_no_weight(family):
    eng, model, params = _build(family)
    tok = jnp.zeros((2, 1), jnp.int32)
    pos = jnp.zeros((2,), jnp.int32)

    def lowered(p):
        decode = eng._decode.lower(p, tok, model.init_cache(2, MAX_LEN), pos)
        chunk = eng._chunk.lower(
            p, jnp.zeros((1, 4), jnp.int32), model.init_cache(1, MAX_LEN), jnp.int32(0), False
        )
        return {"jit_decode_step": decode.as_text(), "jit_prefill_chunk": chunk.as_text()}

    for name, text in lowered(eng.params).items():
        assert f"module @{name}" in text
        assert not _PARAM_CONVERT.findall(text), name
    # The same programs over the caller's fp32 tree convert every weight:
    # the pattern sees what it is meant to.
    for name, text in lowered(params).items():
        assert len(_PARAM_CONVERT.findall(text)) >= 9, name


def test_param_bytes_gauge_counts_the_served_tree():
    metrics.reset()
    eng, _, params = _build("gqa")
    reg = metrics.get_registry()
    want = {}
    for leaf in jax.tree.leaves(eng.params):
        want[str(leaf.dtype)] = want.get(str(leaf.dtype), 0) + leaf.nbytes
    assert set(want) == {"bfloat16", "float32"}
    for dt, n in want.items():
        assert reg.gauge("engine.param_bytes", dtype=dt).value == n
    # Every cast leaf takes half its fp32 bytes.
    fp32 = sum(x.nbytes for x in jax.tree.leaves(params))
    assert 2 * want["bfloat16"] + want["float32"] == fp32
