"""The tracer's second sink: obs spans on the JAX profiler's host plane.

Every ``obs.span`` / ``obs.instant`` also opens a
``jax.profiler.TraceAnnotation`` while a profiler trace records, so the
scheduler's tick phases share a clock with the device's ops.  These tests
record real (CPU) profiler traces into ``tmp_path`` and read them back with
``jax.profiler.ProfileData``.
"""

import dataclasses
import glob
import os

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro import obs
from repro.obs import metrics
from repro.obs import trace as obs_trace

TICK_CHILDREN = {
    "serve.admission",
    "serve.prefill_chunk",
    "serve.decode_tick",
    "serve.gauges",
}
CHUNK_CHILDREN = {"engine.prefill_chunk", "serve.prefill_wait", "kv.write_slot"}
DECODE_CHILDREN = {
    "serve.decode_inputs",
    "engine.decode_slots",
    "serve.decode_wait",
    "serve.tokens",
}
TICK_ARGS = {
    "tick", "rids", "decoded", "chunks", "queued", "prefilling",
    "kv_live_bytes", "kv_reserved_bytes",
}


@pytest.fixture(autouse=True)
def clean_tracer():
    metrics.reset()
    obs.get_tracer().clear()
    yield
    metrics.reset()
    obs.get_tracer().clear()


def _profile(log_dir, body):
    """Run ``body()`` under a profiler trace; the host events recorded,
    as [(name, start_ns, end_ns, {stat: value})]."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    out.append((e.name, e.start_ns, e.end_ns, dict(e.stats)))
    return out


def _named(events, name):
    return [e for e in events if e[0] == name]


def test_span_args_at_entry_and_exit_reach_the_host_plane(tmp_path):
    def body():
        with obs.span("obs.probe", cat="test", size=4, shape="2x2") as sp:
            jnp.ones((8, 8)).block_until_ready()
            sp.set(count=7, ratio=0.5)

    (ev,) = _named(_profile(tmp_path, body), "obs.probe")
    assert ev[3] == {"size": 4, "shape": "2x2", "count": 7, "ratio": 0.5}
    assert ev[2] > ev[1]
    (ring,) = [e for e in obs.get_tracer().events() if e["name"] == "obs.probe"]
    assert ring["args"] == {"size": 4, "shape": "2x2", "count": 7, "ratio": 0.5}


def test_instants_request_scope_and_errors_reach_the_host_plane(tmp_path):
    def body():
        with obs.request_scope(11):
            obs.instant("obs.mark", n=3)
            with pytest.raises(ValueError):
                with obs.span("obs.fails", rids=[1, 2]):
                    raise ValueError("boom")

    events = _profile(tmp_path, body)
    (mark,) = _named(events, "obs.mark")
    assert mark[3] == {"n": 3, "rid": 11}
    (fails,) = _named(events, "obs.fails")
    # a list travels as text; the error class is set on the way out
    assert fails[3] == {"rids": "[1, 2]", "error": "ValueError"}
    ring = {e["name"]: e for e in obs.get_tracer().events()}
    assert ring["obs.fails"]["args"] == {"rids": [1, 2], "error": "ValueError"}


def test_without_a_trace_no_annotation_is_opened_and_the_ring_records(monkeypatch):
    assert not TraceAnnotation.is_enabled()
    opened = []

    class Spy(TraceAnnotation):
        def __init__(self, name, **kwargs):
            opened.append(name)
            super().__init__(name, **kwargs)

    monkeypatch.setattr(obs_trace, "TraceAnnotation", Spy)
    with obs.span("obs.quiet", x=1) as sp:
        sp.set(y=2)
    obs.instant("obs.quiet_mark")
    assert opened == []
    names = [e["name"] for e in obs.get_tracer().events()]
    assert names == ["obs.quiet", "obs.quiet_mark"]
    assert obs.get_tracer().events()[0]["args"] == {"x": 1, "y": 2}


def test_disabled_records_in_neither_sink(tmp_path):
    """``REPRO_OBS=0`` sets the same switch as ``obs.disabled()``."""

    def body():
        with obs.disabled():
            with obs.span("obs.off", x=1) as sp:
                sp.set(y=2)
            obs.instant("obs.off_mark")

    events = _profile(tmp_path, body)
    assert not [e for e in events if e[0].startswith("obs.")]
    assert obs.get_tracer().events() == []


def _engine(max_len=32, slots=2):
    from repro.configs import get_smoke
    from repro.models.registry import get_model
    from repro.serving import ServeConfig, ServeEngine

    cfg = dataclasses.replace(get_smoke("internlm2-1.8b"), dtype="float32")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return ServeEngine(model, params, ServeConfig(max_len=max_len, batch=slots))


def _serve(engine, n=3):
    """A chunked-prefill run of ``n`` requests (prompts of 2-3 chunks)."""
    import numpy as np

    from repro.serving import ContinuousScheduler, Request

    sched = ContinuousScheduler(engine, chunked_prefill=True, chunk_size=4)
    rng = np.random.default_rng(0)
    reqs = [
        Request(
            rid=i,
            prompt={"tokens": rng.integers(0, 100, (1, 6 + 2 * i), dtype=np.int32)},
            max_new_tokens=3,
        )
        for i in range(n)
    ]
    sched.run(reqs)
    return sched


def _within(outer, events):
    s, e = outer
    return [x for x in events if s <= x[0] and x[1] <= e]


def test_scheduler_ticks_hold_their_phases_in_the_ring_buffer():
    _serve(_engine())
    evs = [e for e in obs.get_tracer().events() if e["ph"] == "X"]
    spans = [(e["ts"], e["ts"] + e["dur"], e["name"], e.get("args", {})) for e in evs]
    ticks = [s for s in spans if s[2] == "serve.tick"]
    assert ticks
    chunk_ticks = decode_ticks = 0
    for s, e, _, args in ticks:
        assert TICK_ARGS <= set(args)
        assert 0 <= args["kv_live_bytes"] <= args["kv_reserved_bytes"]
        inside = _within((s, e), spans)
        names = {x[2] for x in inside}
        assert {"serve.admission", "serve.gauges"} <= names
        chunks = [x for x in inside if x[2] == "serve.prefill_chunk"]
        assert len(chunks) == args["chunks"]
        for c in chunks:
            assert CHUNK_CHILDREN <= {x[2] for x in _within(c[:2], inside)}
            chunk_ticks += 1
        dec = [x for x in inside if x[2] == "serve.decode_tick"]
        assert len(dec) == (1 if args["decoded"] else 0)
        for d in dec:
            assert DECODE_CHILDREN <= {x[2] for x in _within(d[:2], inside)}
            decode_ticks += 1
        assert names <= TICK_CHILDREN | CHUNK_CHILDREN | DECODE_CHILDREN | {
            "serve.tick", "kv.gather_slot",
        }
    assert chunk_ticks and decode_ticks
    # the first chunk of each request gathers its slot, later ones do not
    firsts = [x for x in spans if x[2] == "serve.prefill_chunk" and x[3]["offset"] == 0]
    assert len([x for x in spans if x[2] == "kv.gather_slot" and x[3].get("rid") is not None]) == len(firsts)


def test_scheduler_ticks_reach_the_host_plane_with_their_counters(tmp_path):
    engine = _engine()
    sched = []
    events = _profile(tmp_path, lambda: sched.append(_serve(engine)))
    ticks = _named(events, "serve.tick")
    assert len(ticks) == sched[0].stats.ticks
    for _, s, e, stats in ticks:
        assert TICK_ARGS <= set(stats)
        assert stats["kv_reserved_bytes"] > 0
        names = {x[0] for x in events if s <= x[1] and x[2] <= e}
        assert {"serve.admission", "serve.gauges"} <= names
    waits = _named(events, "serve.decode_wait") + _named(events, "serve.prefill_wait")
    assert waits and all(any(t[1] <= w[1] and w[2] <= t[2] for t in ticks) for w in waits)


def _lowered_name(engine, step):
    cache = engine.model.init_cache(engine.scfg.batch, engine.scfg.max_len, jnp.float32)
    one = engine.model.init_cache(1, engine.scfg.max_len, jnp.float32)
    tok = jnp.zeros((engine.scfg.batch, 1), jnp.int32)
    if step == "decode_step":
        low = engine._decode.lower(engine.params, tok, cache, jnp.zeros((engine.scfg.batch,), jnp.int32))
    elif step == "prefill_chunk":
        low = engine._chunk.lower(engine.params, jnp.zeros((1, 4), jnp.int32), one, jnp.int32(0), False)
    else:
        low = engine._prefill.lower(engine.params, {"tokens": jnp.zeros((1, 4), jnp.int32)})
    return low.as_text().split("module @", 1)[1].split(" ", 1)[0]


@pytest.mark.parametrize("step", ["decode_step", "prefill_chunk", "prefill"])
def test_engine_steps_lower_to_named_programs(step):
    assert _lowered_name(_engine(), step) == f"jit_{step}"
