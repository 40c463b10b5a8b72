"""repro.obs: metrics registry, tracer, MFU attribution, and the telemetry
wiring from kernel dispatch through tune to the serving scheduler."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import hw
from repro.core import ops as core_ops
from repro.obs import attribution, metrics, trace as obs_trace
from repro.obs.__main__ import validate_file
from repro.tune import autotune
from repro.tune import cache as tune_cache


@pytest.fixture(autouse=True)
def clean_obs():
    """Fresh process-wide registry + tracer per test (they are shared)."""
    metrics.reset()
    obs.get_tracer().clear()
    yield
    metrics.reset()
    obs.get_tracer().clear()


@pytest.fixture()
def cache_path(tmp_path, monkeypatch):
    path = tmp_path / "plans.json"
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(path))
    tune_cache.reset_default_cache()
    yield path
    tune_cache.reset_default_cache()


# -- metrics ----------------------------------------------------------------


def test_counter_gauge_histogram_basics():
    r = metrics.Registry()
    r.counter("c", backend="xla").inc(2)
    r.counter("c", backend="xla").inc()
    r.counter("c", backend="ref").inc()
    r.gauge("g").set(4.5)
    h = r.histogram("h")
    for v in (1.0, 2.0, 3.0):
        h.observe(v)
    snap = r.snapshot()
    assert snap["counters"]['c{backend="xla"}'] == 3.0
    assert snap["counters"]['c{backend="ref"}'] == 1.0
    assert snap["gauges"]["g"] == 4.5
    assert snap["histograms"]["h"]["count"] == 3
    assert snap["histograms"]["h"]["mean"] == 2.0
    assert r.counter_value("c", backend="xla") == 3.0


def test_counter_rejects_negative():
    with pytest.raises(ValueError, match="only go up"):
        metrics.Counter().inc(-1)


def test_histogram_quantile_clamps_small_samples():
    """The off-by-one this PR fixes: p99 of < 100 samples must be the max,
    never an interior element or an out-of-range index."""
    h = metrics.Histogram()
    h.observe(1.0)
    h.observe(5.0)
    assert h.quantile(0.99) == 5.0
    assert h.quantile(1.0) == 5.0
    assert h.quantile(0.0) == 1.0
    assert h.quantile(0.5) == 1.0  # nearest rank: ceil(0.5*2)-1 = index 0
    h2 = metrics.Histogram()
    for v in range(1, 11):
        h2.observe(float(v))
    assert h2.quantile(0.99) == 10.0  # 10 samples: p99 clamps to max
    assert h2.quantile(0.5) == 5.0


def test_histogram_quantile_edges():
    assert metrics.Histogram().quantile(0.99) == 0.0  # empty -> 0, no raise
    with pytest.raises(ValueError, match="in \\[0, 1\\]"):
        metrics.Histogram().quantile(1.5)


def test_histogram_sliding_window():
    h = metrics.Histogram(maxlen=3)
    for v in range(6):
        h.observe(float(v))
    assert h.values() == [3.0, 4.0, 5.0]  # window slides
    assert h.count == 6 and h.sum == 15.0  # lifetime totals stay exact


def test_disabled_scope_gates_registry_wrappers():
    r = metrics.Registry()
    with metrics.disabled():
        r.inc("c")
        r.observe("h", 1.0)
        metrics.inc("global_c")
        obs_trace.instant("marker")
    assert r.snapshot()["counters"] == {}
    assert metrics.get_registry().snapshot()["counters"] == {}
    assert obs.get_tracer().events() == []
    r.inc("c")  # re-enabled outside the scope
    assert r.counter_value("c") == 1.0


def test_snapshot_doc_merges_and_validates(tmp_path):
    a, b = metrics.Registry(), metrics.Registry()
    a.inc("x")
    b.observe("y", 2.0)
    doc = metrics.snapshot_doc(a, b, extra={"note": "t"})
    assert metrics.validate_snapshot(doc) == []
    assert doc["counters"]["x"] == 1.0
    assert doc["histograms"]["y"]["count"] == 1
    assert doc["extra"] == {"note": "t"}
    # invalid docs are named, not crashed on
    assert metrics.validate_snapshot([]) != []
    assert metrics.validate_snapshot({"schema": 999}) != []
    bad = dict(doc, counters="nope")
    assert metrics.validate_snapshot(bad) != []
    # CLI validator round-trip
    p = tmp_path / "snapshot.json"
    p.write_text(json.dumps(doc))
    assert validate_file(str(p)) == []


def test_prometheus_text_rendering():
    r = metrics.Registry()
    r.inc("gemm.calls", backend="xla")
    r.gauge("occ").set(0.5)
    text = r.to_prometheus()
    assert 'gemm_calls_total{backend="xla"} 1' in text
    assert "occ 0.5" in text


def test_prometheus_escapes_hostile_label_values():
    """Exposition-format escaping (satellite): backslash, double quote, and
    newline in label values must round-trip through the text format instead
    of corrupting it."""
    hostile = 'pa\\th "quoted"\nline2'
    r = metrics.Registry()
    r.inc("c", src=hostile)
    r.observe("h", 1.0, src=hostile)
    text = r.to_prometheus()
    escaped = 'src="pa\\\\th \\"quoted\\"\\nline2"'
    assert f"c_total{{{escaped}}} 1" in text
    assert f"h_count{{{escaped}}} 1" in text
    # no line carries a raw newline mid-series and every line parses as
    # `name{labels} value` -- the round-trip: unescaping the label value
    # recovers the original string
    for line in text.strip().split("\n"):
        name, _, value = line.rpartition(" ")
        float(value)  # parseable sample
    unescaped = (
        escaped[len('src="'):-1]
        .replace("\\n", "\n").replace('\\"', '"').replace("\\\\", "\\")
    )
    assert unescaped == hostile


# -- tracer -----------------------------------------------------------------


def test_span_records_complete_event():
    t = obs_trace.Tracer()
    with t.span("work", cat="test", shape="128x128"):
        pass
    (ev,) = t.events()
    assert ev["name"] == "work" and ev["ph"] == "X"
    assert ev["dur"] >= 0 and ev["args"]["shape"] == "128x128"
    doc = t.export_chrome()
    assert obs_trace.validate_chrome_trace(doc) == []


def test_span_survives_exception():
    t = obs_trace.Tracer()
    with pytest.raises(RuntimeError):
        with t.span("boom"):
            raise RuntimeError("x")
    (ev,) = t.events()
    assert ev["args"]["error"] == "RuntimeError"


def test_tracer_ring_buffer_drops_oldest():
    t = obs_trace.Tracer(capacity=2)
    for i in range(5):
        t.instant(f"e{i}")
    names = [e["name"] for e in t.events()]
    assert names == ["e3", "e4"]
    assert t.export_chrome()["otherData"]["dropped_events"] == 3


def test_ring_buffer_drop_oldest_across_open_span():
    """Satellite (DESIGN.md §15): overflowing the ring while a span is still
    open must not corrupt the export -- spans push on completion, so the
    open span survives the overflow and the drop count stays exact."""
    t = obs_trace.Tracer(capacity=8)
    with t.span("outer"):
        for i in range(20):
            t.instant(f"e{i}")
    # 21 events pushed (20 instants + the span on close), capacity 8
    events = t.events()
    assert len(events) == 8
    names = [e["name"] for e in events]
    assert names == [f"e{i}" for i in range(13, 20)] + ["outer"]
    assert events[-1]["ph"] == "X" and events[-1]["dur"] >= 0
    doc = t.export_chrome()
    assert obs_trace.validate_chrome_trace(doc) == []
    assert doc["otherData"]["dropped_events"] == 21 - 8


def test_request_scope_tags_spans_and_instants():
    t = obs_trace.Tracer()
    with obs_trace.request_scope(7):
        with t.span("work"):
            pass
        t.instant("mark")
        t.instant("explicit", rid=9)      # explicit rid wins over the scope
        t.instant("batched", rids=[1, 2])  # batched tagging wins too
        with obs_trace.request_scope(8):   # nests: inner request wins
            t.instant("inner")
    t.instant("outside")                   # no scope -> no rid
    by_name = {e["name"]: e for e in t.events()}
    assert by_name["work"]["args"]["rid"] == 7
    assert by_name["mark"]["args"]["rid"] == 7
    assert by_name["explicit"]["args"]["rid"] == 9
    assert by_name["batched"]["args"]["rids"] == [1, 2]
    assert "rid" not in by_name["batched"]["args"]
    assert by_name["inner"]["args"]["rid"] == 8
    assert "args" not in by_name["outside"]
    assert obs_trace.current_request() is None


def test_request_timeline_filters_and_sorts():
    events = [
        {"name": "b", "ph": "i", "ts": 2.0, "args": {"rid": 1}},
        {"name": "a", "ph": "i", "ts": 1.0, "args": {"rid": 1}},
        {"name": "tick", "ph": "X", "ts": 3.0, "dur": 1.0,
         "args": {"rids": [1, 2]}},
        {"name": "other", "ph": "i", "ts": 0.0, "args": {"rid": 2}},
        {"name": "untagged", "ph": "i", "ts": 0.5},
    ]
    tl = obs_trace.request_timeline(events, 1)
    assert [e["name"] for e in tl] == ["a", "b", "tick"]
    assert obs_trace.trace_rids(events) == {1, 2}
    # validate_request_timeline names what is missing
    errs = obs_trace.validate_request_timeline(events, 1)
    assert any("serve.admit" in e for e in errs)


def test_instrument_decorator(tmp_path):
    t = obs_trace.Tracer()

    @t.instrument("fn", cat="test")
    def f(x):
        return x + 1

    assert f(1) == 2
    assert t.events()[0]["name"] == "fn"
    # export to disk loads back as a valid Chrome trace
    p = tmp_path / "trace.json"
    t.export_chrome(p)
    assert validate_file(str(p)) == []


# -- attribution ------------------------------------------------------------


def test_record_gemm_counters_and_collecting():
    totals = attribution.GemmTotals()
    with attribution.collecting(totals):
        attribution.record_gemm(
            128, 256, 512, dtype="bfloat16",
            backend="pallas-systolic", plan_source="tuned",
        )
        attribution.record_gemm(
            128, 256, 512, dtype="bfloat16",
            backend="pallas-systolic", plan_source="heuristic",
        )
    assert totals.calls == 2 and totals.flops == 2 * 2.0 * 128 * 256 * 512
    assert totals.plan_hits == 1 and totals.plan_misses == 1
    assert totals.predicted_s > 0
    reg = metrics.get_registry()
    assert reg.counter_value("gemm.calls",
                            backend="pallas-systolic", dtype="bfloat16") == 2.0
    assert reg.counter_value("tune.plan.hit", backend="pallas-systolic") == 1.0
    assert attribution.plan_hit_rate("pallas-systolic") == 0.5
    with pytest.raises(ValueError, match="plan_source"):
        attribution.record_gemm(1, 1, 1, dtype="f", backend="b",
                                plan_source="bogus")


def test_mfu_and_roofline():
    chip = hw.get_chip(None)
    flops = 2.0 * 1024 * 1024 * 1024
    t_peak = flops / chip.peak_flops("bfloat16")
    assert attribution.mfu(flops, t_peak, dtype="bfloat16") == pytest.approx(1.0)
    assert attribution.mfu(flops, 0.0) == 0.0
    # roofline prediction is at least the compute bound, and the unblockable
    # fallback path still returns something positive
    pred = attribution.roofline_seconds(1024, 1024, 1024, "bfloat16", chip.name)
    assert pred >= t_peak * 0.99
    assert attribution.roofline_seconds(3, 5, 7, "bfloat16", chip.name) > 0


def test_matmul_dispatch_records_per_backend():
    x = jnp.ones((8, 16), jnp.float32)
    w = jnp.ones((16, 32), jnp.float32)
    core_ops.matmul(x, w)
    with core_ops.use_backend("reference"):
        core_ops.matmul(x, w)
    with core_ops.use_backend("pallas-systolic"):
        core_ops.matmul(x, w)
    reg = metrics.get_registry()
    for backend in ("xla", "reference", "pallas-systolic"):
        assert reg.counter_value("gemm.calls",
                                 backend=backend, dtype="float32") == 1.0
        assert reg.counter_value("gemm.flops",
                                 backend=backend) == 2.0 * 8 * 16 * 32
    # no tune cache -> the plan-consulting backends record misses
    assert reg.counter_value("tune.plan.miss", backend="pallas-systolic") == 1.0


def test_systolic_dispatch_records_tuned_plan(cache_path):
    from repro.tune import Measurement

    def stub(rec):
        t = 1.0 if (rec.bm, rec.bn, rec.bk) == (128, 128, 128) else 9.0
        return Measurement(mean_us=t, best_us=t, repeats=1, method="stub")

    autotune(256, 256, 256, dtype="float32", measure_fn=stub)
    a = jnp.ones((256, 256), jnp.float32)
    with core_ops.use_backend("pallas-systolic"):
        core_ops.matmul(a, a)
    reg = metrics.get_registry()
    assert reg.counter_value("tune.plan.hit", backend="pallas-systolic") == 1.0
    assert attribution.plan_hit_rate("pallas-systolic") == 1.0


# -- tune cache hit/miss counters (satellite) -------------------------------


def test_autotune_counters_cold_then_warm(cache_path):
    from repro.tune import Measurement

    def stub(rec):
        return Measurement(mean_us=1.0, best_us=1.0, repeats=1, method="stub")

    reg = metrics.get_registry()
    r1 = autotune(256, 256, 256, dtype="float32", measure_fn=stub)
    assert not r1.cache_hit
    assert reg.counter_value("tune.autotune.cache_miss",
                             backend="pallas-systolic") == 1.0
    assert reg.counter_value("tune.autotune.measurements",
                             backend="pallas-systolic") > 0
    r2 = autotune(256, 256, 256, dtype="float32", measure_fn=stub)
    assert r2.cache_hit
    assert reg.counter_value("tune.autotune.cache_hit",
                             backend="pallas-systolic") == 1.0
    assert reg.counter_value("tune.autotune.cache_miss",
                             backend="pallas-systolic") == 1.0
    # the measurement loop left a span
    assert any(e["name"] == "tune.autotune" for e in obs.get_tracer().events())


def test_interpret_run_does_not_pollute_device_entries(cache_path, monkeypatch):
    """A warm device-measured entry must short-circuit an interpret-mode
    autotune (cache hit; provenance untouched), not be overwritten by
    interpret-wall timings keyed to the same problem."""
    key = tune_cache.CacheKey(
        "pallas-systolic", hw.get_chip(None).name, 256, 256, 256, "float32"
    )
    device_plan = tune_cache.TunedPlan(128, 128, 128, 5.0, 4.0, "device-wall")
    tune_cache.default_cache().store(key, device_plan)
    monkeypatch.setenv("REPRO_INTERPRET", "1")
    r = autotune(256, 256, 256, dtype="float32")
    assert r.cache_hit and r.winner.method == "device-wall"
    assert tune_cache.default_cache().lookup(key) == device_plan
    assert metrics.get_registry().counter_value(
        "tune.autotune.cache_hit", backend="pallas-systolic"
    ) == 1.0


# -- collective dispatch (unit: the mesh paths run in subprocess tests) -----


def test_collective_record_dispatch():
    from repro.distributed import collective_matmul as cm

    cm._record_dispatch(
        "allgather", 4, 256, 256, 256, jnp.float32, True, 65536
    )
    reg = metrics.get_registry()
    assert reg.counter_value("collective.calls", mode="allgather") == 1.0
    assert reg.counter_value("collective.hops", mode="allgather") == 3.0
    assert reg.counter_value("collective.hop_bytes",
                             mode="allgather") == 3 * 65536
    snap = reg.snapshot()
    # The modeled gauge is explicitly tagged so it can never be confused
    # with the sampled kind="measured" series (PR 10, satellite 1) -- and
    # the label set round-trips through parse_series.
    series = 'collective.overlap_ratio{kind="modeled",mode="allgather"}'
    assert snap["gauges"][series] > 0
    name, labels = metrics.parse_series(series)
    assert name == "collective.overlap_ratio"
    assert labels == {"kind": "modeled", "mode": "allgather"}
    hops = [e for e in obs.get_tracer().events() if e["name"] == "tp.ring_hop"]
    assert len(hops) == 3 and hops[0]["args"]["bytes"] == 65536
    assert hops[0]["args"]["modeled_s"] > 0
    # unoverlapped dispatch records the call but no hops
    cm._record_dispatch(
        "reducescatter", 4, 256, 256, 256, jnp.float32, False, 1024
    )
    assert reg.counter_value("collective.hops", mode="reducescatter") == 0.0


# -- serving integration ----------------------------------------------------


def _serve_setup(arch="internlm2-1.8b", n=4, seed=0):
    from repro.configs import get_smoke
    from repro.data.synthetic import make_request_trace
    from repro.models.registry import get_model
    from repro.serving import ServeConfig, ServeEngine

    cfg = dataclasses.replace(get_smoke(arch), dtype="float32")
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    trace = make_request_trace(
        cfg, n_requests=n, mean_prompt=8, mean_gen=5, rate=0.7,
        seed=3, min_prompt=4, max_prompt=12, max_gen=8,
    )
    max_len = max(
        t["prompt"]["tokens"].shape[1] + t["max_new_tokens"] for t in trace
    )
    engine = ServeEngine(model, params, ServeConfig(max_len=max_len, batch=2))
    return model, params, engine, trace


def test_serve_run_populates_telemetry():
    from repro.serving import ContinuousScheduler, requests_from_trace

    model, params, engine, trace = _serve_setup()
    sched = ContinuousScheduler(engine, chunked_prefill=True, chunk_size=8)
    sched.run(requests_from_trace(trace))
    s = sched.stats.summary()
    assert s["tokens_out"] == sum(t["max_new_tokens"] for t in trace)
    assert s["model_residual"] > 0
    assert s["ttft_p50_ms"] > 0 and s["kv_bytes_resident"] > 0
    assert s["itl_p50_ms"] > 0
    # snapshot (dispatch registry + scheduler registry) validates
    doc = obs.snapshot_doc(
        metrics.get_registry(), sched.stats.registry, extra=s
    )
    assert metrics.validate_snapshot(doc) == []
    assert doc["histograms"]["serve.ttft_s"]["count"] > 0
    # the trace timeline carries the acceptance-criteria spans
    tr = obs.get_tracer().export_chrome()
    assert obs_trace.validate_chrome_trace(tr) == []
    names = {e["name"] for e in tr["traceEvents"]}
    assert {"serve.prefill_chunk", "serve.decode_tick", "serve.warmup"} <= names
    # engine-side totals: the traced decode step recorded real GEMM work
    assert engine.decode_totals.flops > 0
    assert engine.decode_totals.predicted_s > 0


def test_serve_trace_reconstructs_every_request_timeline():
    """Tentpole acceptance: every request's rid-tagged span chain (admit ->
    prefill -> first_token -> evict, decode ticks attributed via rids)
    validates, under both monolithic and chunked prefill."""
    from repro.serving import ContinuousScheduler, requests_from_trace

    model, params, engine, trace = _serve_setup()
    for chunked in (False, True):
        obs.get_tracer().clear()
        sched = ContinuousScheduler(
            engine, chunked_prefill=chunked, chunk_size=8
        )
        sched.run(requests_from_trace(trace))
        doc = obs.get_tracer().export_chrome()
        assert obs_trace.trace_rids(doc) == {t["rid"] for t in trace}
        for t in trace:
            assert obs_trace.validate_request_timeline(doc, t["rid"]) == []
        # decode ticks carry per-slot attribution
        ticks = [e for e in doc["traceEvents"]
                 if e["name"] == "serve.decode_tick"]
        assert ticks and all(e["args"]["rids"] for e in ticks)
        # engine-layer spans inherit the scheduler's request scope (warmup
        # precompiles run outside any scope, so they stay untagged)
        tagged = {
            e["args"]["rid"]
            for e in doc["traceEvents"]
            if e["name"].startswith("engine.prefill")
            and "rid" in e.get("args", {})
        }
        assert {t["rid"] for t in trace} <= tagged


def test_engine_steps_count_executions_not_compiles():
    """Satellite (DESIGN.md §15): ``gemm.*`` counters record at *trace*
    time -- one bump per compile, not per step -- while ``engine.steps``
    counts executions.  Re-running the same trace through a warm engine
    moves the step counters and leaves the gemm counters alone; total FLOPs
    for a phase is ``totals.flops * engine.steps{phase}``."""
    from repro.serving import ContinuousScheduler, requests_from_trace

    model, params, engine, trace = _serve_setup()

    def gemm_calls():
        return sum(
            v
            for k, v in metrics.get_registry().snapshot()["counters"].items()
            if metrics.parse_series(k)[0] == "gemm.calls"
        )

    reg = metrics.get_registry()
    ContinuousScheduler(engine).run(requests_from_trace(trace))
    steps0 = reg.counter_value("engine.steps", phase="decode")
    prefills0 = reg.counter_value("engine.steps", phase="prefill_request")
    assert steps0 > 0 and prefills0 >= len(trace)
    calls0 = gemm_calls()
    assert calls0 > 0
    ContinuousScheduler(engine).run(requests_from_trace(trace))
    # executions doubled-ish; trace-time gemm records did not move at all
    assert reg.counter_value("engine.steps", phase="decode") > steps0
    assert (
        reg.counter_value("engine.steps", phase="prefill_request")
        >= prefills0 + len(trace)
    )
    assert gemm_calls() == calls0


def test_chunked_prefill_does_not_pollute_itl_histograms():
    """Satellite: under mixed prefill/decode ticks, TTFT and ITL stay
    per-request quantities -- a mid-prefill request contributes no ITL
    samples (its wait lands in TTFT), and the bare decode-step histogram
    never includes prefill work."""
    from repro.serving import ContinuousScheduler, requests_from_trace

    model, params, engine, trace = _serve_setup()
    sched = ContinuousScheduler(engine, chunked_prefill=True, chunk_size=4)
    sched.run(requests_from_trace(trace))
    st = sched.stats
    snap = st.registry.snapshot()["histograms"]
    n_req = len(trace)
    total = sum(t["max_new_tokens"] for t in trace)
    # one TTFT per completed prefill, one ITL per token after the first
    assert snap["serve.ttft_s"]["count"] == n_req
    assert snap["serve.itl_s"]["count"] == total - n_req
    # the step histogram has exactly one sample per decode step, so the
    # co-scheduled prefill chunks (charged to prefill_s) are not in it
    assert len(st.step_latency_s) == st.decode_steps
    assert st.prefill_chunks > 0 and st.prefill_s > 0


def test_prune_tick_snapshots_keeps_newest(tmp_path):
    from repro.launch.serve import _prune_tick_snapshots

    for tick in (10, 20, 30, 40):
        (tmp_path / f"snapshot-{tick:06d}.json").write_text("{}")
    (tmp_path / "snapshot.json").write_text("{}")
    (tmp_path / "trace.json").write_text("{}")
    _prune_tick_snapshots(str(tmp_path), keep=2)
    left = sorted(p.name for p in tmp_path.iterdir())
    assert left == [
        "snapshot-000030.json", "snapshot-000040.json",
        "snapshot.json", "trace.json",
    ]


def test_two_schedulers_do_not_share_histograms():
    from repro.serving import ContinuousScheduler, requests_from_trace

    model, params, engine, trace = _serve_setup()
    s1 = ContinuousScheduler(engine)
    s1.run(requests_from_trace(trace))
    s2 = ContinuousScheduler(engine)
    s2.run(requests_from_trace(trace))
    assert s1.stats.registry is not s2.stats.registry
    assert s1.stats.tokens_out == s2.stats.tokens_out  # same work, own series
    total = sum(t["max_new_tokens"] for t in trace)
    assert s1.stats.tokens_out == total  # not 2x: no shared counter


def test_manual_steps_exclude_warmup_from_latency_histograms():
    """Regression (satellite): driving step() without run()/warmup() used to
    charge the decode compile into the tick/step histograms; now the first
    step() auto-warms outside the stats window."""
    from repro.serving import ContinuousScheduler, Request, requests_from_trace

    model, params, engine, trace = _serve_setup()
    sched = ContinuousScheduler(engine)
    calls = {"n": 0}
    orig = engine.decode_slots

    def spy(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)

    engine.decode_slots = spy
    for r in requests_from_trace(trace):
        sched.submit(r)
    n_steps = 4
    for _ in range(n_steps):
        sched.step()
    engine.decode_slots = orig
    assert sched._warmed
    # warmup's decode ran outside the histograms: every *timed* sample maps
    # to a decode step, and the warmup call is the one extra invocation
    assert calls["n"] == sched.stats.decode_steps + 1
    assert len(sched.stats.step_latency_s) == sched.stats.decode_steps
    assert sched.stats.ticks == n_steps


def test_summary_keeps_legacy_keys():
    from repro.serving.scheduler import SchedulerStats

    s = SchedulerStats().summary()
    for k in (
        "ticks", "decode_steps", "idle_ticks", "tokens_out", "prefill_s",
        "decode_s", "prefill_chunks", "tok_per_s", "p50_step_ms",
        "p99_step_ms", "p50_tick_ms", "p99_tick_ms", "mean_occupancy",
    ):
        assert k in s
    assert s["tok_per_s"] == 0.0  # empty stats: no division blowups


# -- KVPool.bytes_resident (satellite) --------------------------------------


def _pool(arch="internlm2-1.8b", quantize=False):
    from repro.configs import get_smoke
    from repro.models.registry import get_model
    from repro.serving.kvpool import KVPool

    cfg = dataclasses.replace(get_smoke(arch), dtype="float32")
    model = get_model(cfg)
    return KVPool(model, 2, 32, quantize_kv_cache=quantize)


def test_kvpool_bytes_resident_fp():
    pool = _pool()
    expect = sum(
        leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(pool._cache)
    )
    assert pool.bytes_resident() == expect > 0
    # preallocated: occupancy does not change residency
    slot = pool.alloc()
    assert pool.bytes_resident() == expect
    pool.free(slot)


def test_kvpool_bytes_resident_kv8_counts_scale_sidecars():
    fp = _pool(quantize=False)
    q = _pool(quantize=True)
    leaves = jax.tree.leaves(q._qcache)
    int8_bytes = sum(
        x.size * x.dtype.itemsize for x in leaves if x.dtype == jnp.int8
    )
    scale_bytes = sum(
        x.size * x.dtype.itemsize for x in leaves if x.dtype == jnp.float32
    )
    assert scale_bytes > 0  # the sidecars exist and are counted
    assert q.bytes_resident() >= int8_bytes + scale_bytes
    # honest accounting: kv8 resident < fp32 resident, > values alone
    assert int8_bytes < q.bytes_resident() < fp.bytes_resident()
