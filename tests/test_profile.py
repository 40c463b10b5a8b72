"""repro.obs.profile / drift / doctor: sampled measured timing windows, the
perf-model drift watchdog, and the ``obs doctor`` CLI (DESIGN.md §15)."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.core import hw
from repro.obs import doctor as obs_doctor
from repro.obs import drift as obs_drift
from repro.obs import metrics
from repro.obs import profile as obs_profile
from repro.obs.__main__ import main as obs_main, validate_file
from repro.obs.ledger import Ledger
from repro.tune import cache as tune_cache


@pytest.fixture(autouse=True)
def clean_obs(monkeypatch):
    """Fresh registry/tracer/profiler per test; no ambient env leakage."""
    monkeypatch.delenv("REPRO_PROFILE_RATE", raising=False)
    monkeypatch.delenv("REPRO_LEDGER", raising=False)
    metrics.reset()
    obs.get_tracer().clear()
    obs_profile.get_profiler().reset()
    yield
    metrics.reset()
    obs.get_tracer().clear()
    obs_profile.get_profiler().reset()


@pytest.fixture()
def cache_path(tmp_path, monkeypatch):
    path = tmp_path / "plans.json"
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(path))
    tune_cache.reset_default_cache()
    yield path
    tune_cache.reset_default_cache()


def _by_name(snap_section):
    """Collapse formatted series to {base_name: value} (single-label-set)."""
    return {obs.parse_series(k)[0]: v for k, v in snap_section.items()}


# -- profiler ----------------------------------------------------------------


def test_bresenham_sampling_is_deterministic_and_exact():
    p = obs_profile.Profiler(0.25)
    draws = [p.should_sample("s") for _ in range(16)]
    assert sum(draws) == 4  # exactly floor(rate * calls), not in expectation
    # a fresh profiler replays the identical draw sequence: no RNG, no seed
    p2 = obs_profile.Profiler(0.25)
    assert [p2.should_sample("s") for _ in range(16)] == draws
    # streams have independent accumulators with the same deterministic walk
    p3 = obs_profile.Profiler(0.5)
    a = [p3.should_sample("a") for _ in range(4)]
    b = [p3.should_sample("b") for _ in range(4)]
    assert a == b == [False, True, False, True]
    # rate 1.0 samples every call
    assert all(obs_profile.Profiler(1.0).should_sample("x") for _ in range(5))


def test_profiler_inactive_paths_record_nothing():
    p = obs_profile.Profiler(0.0)
    assert not p.active()
    out, wall = p.timed("s", lambda: 41 + 1)
    assert out == 42 and wall is None
    # telemetry disabled beats rate > 0: sample_call degrades to the thunk
    with metrics.disabled():
        p2 = obs_profile.Profiler(1.0)
        assert not p2.active()
        assert p2.sample_call("s", lambda: "x") == "x"
        obs_profile.record_gemm_sample(
            8, 8, 8, backend="b", dtype="float32", wall_s=1e-3
        )
    assert metrics.get_registry().snapshot()["counters"] == {}


def test_sample_call_writes_standard_series():
    r = metrics.Registry()
    p = obs_profile.Profiler(1.0)
    out = p.sample_call(
        "kv.gather", lambda: jnp.ones((4,)), registry=r,
        pool="stripe", path="slot",
    )
    assert out.shape == (4,)
    snap = r.snapshot()
    counters = _by_name(snap["counters"])
    assert counters["kv.gather.calls"] == 1
    assert counters["kv.gather.sampled"] == 1
    assert counters["kv.gather.sampled_us"] > 0
    hist = _by_name(snap["histograms"])
    assert hist["kv.gather_us"]["count"] == 1
    # labels round-trip through the formatted series name
    name, labels = obs.parse_series(next(iter(snap["counters"])))
    assert labels == {"pool": "stripe", "path": "slot"}


def test_sampling_context_and_configure_clamp():
    prof = obs_profile.get_profiler()
    obs_profile.configure(0.1)
    with obs.sampling(1.0):
        assert prof.sample_rate == 1.0
        obs_profile.sample_call("t.stream", lambda: jnp.zeros(2))
    assert prof.sample_rate == 0.1  # context restores the previous rate
    assert metrics.get_registry().counter_value("t.stream.sampled") == 1.0
    obs_profile.configure(7.0)
    assert prof.sample_rate == 1.0  # clamped to [0, 1]
    obs_profile.configure(-3.0)
    assert prof.sample_rate == 0.0


# -- drift watchdog ----------------------------------------------------------


def _stash_sample(m, n, k, us, method="interpret-wall"):
    obs_profile.record_gemm_sample(
        m, n, k, backend="pallas-systolic", dtype="float32",
        wall_s=us / 1e6, method=method,
    )


def _key(m, n, k):
    return tune_cache.CacheKey(
        "pallas-systolic", hw.get_chip(None).name, m, n, k, "float32", "none", 1
    )


def test_check_drift_without_cache_entry_reports_model_only(tmp_path):
    _stash_sample(64, 64, 64, 100.0)
    snap = metrics.get_registry().snapshot()
    cache = tune_cache.PlanCache(tmp_path / "empty.json")
    (f,) = obs_drift.check_drift(snap, cache=cache)
    assert f.problem == "64x64x64" and f.samples == 1
    assert f.sampled_us == pytest.approx(100.0)
    assert f.model_us > 0 and f.model_ratio == pytest.approx(100.0 / f.model_us)
    assert f.cached_us is None and f.cache_ratio is None and not f.stale
    assert f.key is None and f.recommendation == "ok"


def test_check_drift_flags_stale_plans_symmetrically(tmp_path):
    cache = tune_cache.PlanCache(tmp_path / "plans.json")
    plan = tune_cache.TunedPlan(
        bm=2, bn=64, bk=64, mean_us=300.0, best_us=290.0,
        method="interpret-wall",
    )
    cache.store(_key(64, 64, 64), plan)  # claims 3x the sampled time
    cache.store(  # claims a third of the sampled time: stale too
        _key(128, 64, 64),
        dataclasses.replace(plan, mean_us=40.0, best_us=39.0),
    )
    cache.store(  # within threshold: healthy
        _key(32, 64, 64),
        dataclasses.replace(plan, mean_us=110.0, best_us=100.0),
    )
    _stash_sample(64, 64, 64, 100.0)
    _stash_sample(128, 64, 64, 120.0)
    _stash_sample(32, 64, 64, 100.0)
    snap = metrics.get_registry().snapshot()
    by_problem = {
        f.problem: f for f in obs_drift.check_drift(snap, cache=cache)
    }
    slow = by_problem["64x64x64"]
    assert slow.stale and slow.cache_ratio == pytest.approx(3.0)
    assert slow.key == _key(64, 64, 64).encode()
    assert "re-tune" in slow.recommendation
    fast = by_problem["128x64x64"]
    assert fast.stale and fast.cache_ratio == pytest.approx(3.0)
    assert not by_problem["32x64x64"].stale
    assert by_problem["32x64x64"].cache_ratio == pytest.approx(1.1)


def test_check_drift_never_compares_across_measurement_methods(tmp_path):
    """An interpret-wall sample held against a device-wall plan is noise,
    not drift -- provenance must match before the ratio means anything."""
    cache = tune_cache.PlanCache(tmp_path / "plans.json")
    cache.store(
        _key(64, 64, 64),
        tune_cache.TunedPlan(
            bm=2, bn=64, bk=64, mean_us=10.0, best_us=9.0, method="device-wall"
        ),
    )
    _stash_sample(64, 64, 64, 100.0, method="interpret-wall")
    (f,) = obs_drift.check_drift(
        metrics.get_registry().snapshot(), cache=cache
    )
    assert not f.stale and f.cached_us is None
    assert "not comparable" in f.recommendation


def test_record_findings_counters_and_ledger(tmp_path):
    base = dict(
        problem="64x64x64", backend="pallas-systolic", dtype="float32",
        method="interpret-wall", sampled_us=100.0, samples=3,
        model_us=10.0, model_ratio=10.0, threshold=0.5,
    )
    stale = obs_drift.DriftFinding(
        cached_us=300.0, cache_ratio=3.0, stale=True, key="k1",
        recommendation="re-tune k1", **base,
    )
    ok = obs_drift.DriftFinding(
        cached_us=100.0, cache_ratio=1.0, stale=False, key="k2",
        recommendation="ok", **base,
    )
    ledger = Ledger(str(tmp_path / "ledger.jsonl"))
    assert obs_drift.record_findings([stale, ok], ledger=ledger) == 1
    reg = metrics.get_registry()
    assert reg.counter_value("tune.plan.stale", key="k1") == 1.0
    assert reg.counter_value("tune.plan.stale", key="k2") == 0.0
    entries, bad = ledger.entries()
    assert bad == 0 and len(entries) == 1  # only the stale finding lands
    (e,) = entries
    assert e["bench"] == "drift" and e["variant"] == "k1"
    assert e["metrics"]["cache_ratio"] == 3.0
    assert e["meta"]["recommendation"] == "re-tune k1"


# -- serving integration -----------------------------------------------------


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


def test_kv_pool_sampled_timing_both_pools(cache_path):
    """Satellite: KV gather/scatter cost is a measured series in both the
    stripe pool and the paged pool, labeled by pool."""
    from repro.serving import ContinuousScheduler, requests_from_trace

    model, params, engine, trace = _serve_setup()
    for opts, pool in (
        ({}, "stripe"),
        (dict(paged=True, page_size=16), "paged"),
    ):
        metrics.reset()
        sched = ContinuousScheduler(engine, **opts)
        with obs.sampling(1.0):
            sched.run(requests_from_trace(trace))
        snap = metrics.get_registry().snapshot()["counters"]
        kv = [
            (obs.parse_series(k), v)
            for k, v in snap.items()
            if obs.parse_series(k)[0].startswith("kv.")
        ]
        assert kv, f"no kv.* series recorded for {pool}"
        assert {labels["pool"] for (_, labels), _ in kv} == {pool}
        sampled = {
            name: v for (name, _), v in kv if name.endswith(".sampled")
        }
        sampled_us = {
            name: v for (name, _), v in kv if name.endswith(".sampled_us")
        }
        # at rate 1.0 every pool dispatch is a timed window with wall time
        assert sum(sampled.values()) > 0
        assert sum(sampled_us.values()) > 0
        if pool == "paged":  # decode-path page gather runs every tick
            assert sampled.get("kv.gather.sampled", 0) > 0
            assert sampled.get("kv.scatter.sampled", 0) > 0


def test_probe_decode_plans_records_gemm_samples(cache_path):
    model, params, engine, trace = _serve_setup()
    rows = obs_drift.probe_decode_plans(engine, repeats=1, warmup=1)
    measured = [r for r in rows if "mean_us" in r]
    assert measured and all(r["mean_us"] > 0 for r in measured)
    assert {r["name"] for r in measured} >= {"wq", "wo", "ffn_in", "ffn_out"}
    assert all(not r["cached"] for r in measured)  # empty tune cache
    snap = metrics.get_registry().snapshot()
    gemm_hists = [
        (obs.parse_series(k), h)
        for k, h in snap["histograms"].items()
        if obs.parse_series(k)[0] == "profile.gemm_us"
    ]
    # problems dedup into series: wq/wo share MxNxK, as do wk/wv, so the
    # histogram count per series equals the probes that hit that problem
    assert {lb["problem"] for (_, lb), _ in gemm_hists} == {
        r["problem"] for r in measured
    }
    assert sum(h["count"] for _, h in gemm_hists) == len(measured)
    for (_, labels), h in gemm_hists:
        assert labels["backend"] == "pallas-systolic"
        assert labels["method"] in ("interpret-wall", "xla-proxy", "device-wall")


class _EngineClock:
    """A scheduler clock on which time passes only while the engine runs a
    step, a fixed cost per call: the run's tick/phase/wall accounting then
    reads the same on an idle machine and on one shared with other test
    workers, where a stall between two ticks would otherwise land outside
    every tick."""

    def __init__(self, engine, costs: dict):
        self.now = 0.0
        for name, cost in costs.items():
            setattr(engine, name, self._charged(getattr(engine, name), cost))

    def _charged(self, fn, cost: float):
        def call(*args, **kwargs):
            out = fn(*args, **kwargs)
            self.now += cost
            return out

        return call

    def __call__(self) -> float:
        return self.now


def test_doctor_end_to_end_serve_report_and_stale_gate(tmp_path, cache_path, capsys):
    """Acceptance: doctor over a real serve run's metrics dir reports a
    measured phase breakdown covering >= 90% of wall, exits 0 when healthy,
    and exits 1 end-to-end when a tune-cache entry is ~3x off the sampled
    probe timings.  The scheduler runs on ``_EngineClock``, so its
    accounting (and the coverage) does not depend on the CPU's load."""
    from repro.serving import ContinuousScheduler, requests_from_trace

    model, params, engine, trace = _serve_setup()
    clock = _EngineClock(engine, {"prefill_request": 5e-3, "decode_slots": 2e-3})
    sched = ContinuousScheduler(engine, clock=clock)
    with obs.sampling(1.0):
        sched.run(requests_from_trace(trace))
        rows = obs_drift.probe_decode_plans(engine, repeats=1, warmup=1)
    assert any("mean_us" in r for r in rows)

    mdir = tmp_path / "metrics"
    mdir.mkdir()
    doc = obs.snapshot_doc(
        metrics.get_registry(), sched.stats.registry,
        extra=sched.stats.summary(),
    )
    (mdir / "snapshot.json").write_text(json.dumps(doc))
    obs.get_tracer().export_chrome(str(mdir / "trace.json"))

    out1 = tmp_path / "report.json"
    rc = obs_main(["doctor", str(mdir), "--json", "--out", str(out1)])
    assert rc == 0
    printed = capsys.readouterr().out
    report = json.loads(out1.read_text())
    assert json.loads(printed) == report  # --json prints the same document
    assert obs_doctor.validate_doctor_report(report) == []
    assert validate_file(str(out1)) == []  # CLI validator routes kind=doctor

    # acceptance: measured phases sum to within 10% of the run's wall clock
    assert report["wall_basis"] == "sched.run_wall_s"
    assert 0.9 <= report["coverage"] <= 1.0 + 1e-3
    phases = {p["name"]: p for p in report["phases"]}
    assert set(phases) == {"prefill", "decode", "sched_gap", "telemetry"}
    assert phases["decode"]["seconds"] > 0 and phases["prefill"]["seconds"] > 0
    for p in report["phases"]:
        assert p["share"] == pytest.approx(
            p["seconds"] / report["wall_s"], abs=1e-9
        )
    # the sampled KV series show up as extrapolated sinks
    assert report["kv"] and all(r["mean_us"] > 0 for r in report["kv"])
    assert any(r["component"].startswith("kv:") for r in report["top_sinks"])
    # the probe's samples become measured-vs-modeled GEMM residual rows
    assert report["residuals"]["gemms"]
    assert report["residuals"]["serve_model_residual_mean"] > 0
    assert report["stale_plans"] == [] and rc == 0
    # text rendering carries the headline sections
    text = obs_doctor.render_text(report)
    assert "time sinks" in text and "stale plans: none" in text

    # inject a cache entry 3x off the sampled mean -> doctor must exit 1
    g = report["residuals"]["gemms"][0]
    m, n, k = (int(x) for x in g["problem"].split("x"))
    stale_cache = tmp_path / "stale_plans.json"
    tune_cache.PlanCache(stale_cache).store(
        _key(m, n, k),
        tune_cache.TunedPlan(
            bm=2, bn=64, bk=64,
            mean_us=g["sampled_us"] / 3.0, best_us=g["sampled_us"] / 3.0,
            method=g["method"],
        ),
    )
    out2 = tmp_path / "report2.json"
    rc = obs_main([
        "doctor", str(mdir), "--json", "--out", str(out2),
        "--tune-cache", str(stale_cache),
    ])
    capsys.readouterr()
    assert rc == 1
    rep2 = json.loads(out2.read_text())
    (stale,) = rep2["stale_plans"]
    assert stale["key"] == _key(m, n, k).encode()
    assert stale["cache_ratio"] == pytest.approx(3.0, rel=1e-6)
    assert "re-tune" in stale["recommendation"]
    assert "STALE PLANS (1)" in obs_doctor.render_text(rep2)
    assert obs_doctor.validate_doctor_report(rep2) == []

    # stale findings flow into a regression ledger when one is given
    ledger_path = tmp_path / "ledger.jsonl"
    rc = obs_main([
        "doctor", str(mdir), "--json",
        "--tune-cache", str(stale_cache), "--ledger", str(ledger_path),
    ])
    capsys.readouterr()
    assert rc == 1
    entries, bad = Ledger(str(ledger_path)).entries()
    assert bad == 0 and len(entries) == 1
    assert entries[0]["bench"] == "drift"
    assert entries[0]["variant"] == _key(m, n, k).encode()


def test_doctor_exit_2_on_unreadable_or_invalid_inputs(tmp_path, capsys):
    assert obs_main(["doctor", str(tmp_path / "nope")]) == 2
    bad = tmp_path / "bad"
    bad.mkdir()
    (bad / "snapshot.json").write_text('{"counters": []}')
    assert obs_main(["doctor", str(bad)]) == 2
    err = capsys.readouterr().err
    assert "cannot read" in err


def test_serve_run_under_sampling_stays_bit_identical(cache_path):
    """Profiling windows are observation only: a run at sampling rate 1.0
    generates exactly the tokens an unprofiled run does."""
    import numpy as np

    from repro.serving import ContinuousScheduler, requests_from_trace

    model, params, engine, trace = _serve_setup()
    base = ContinuousScheduler(engine).run(requests_from_trace(trace))
    with obs.sampling(1.0):
        profiled = ContinuousScheduler(engine).run(requests_from_trace(trace))
    assert base.keys() == profiled.keys()
    for rid in base:
        assert np.array_equal(base[rid], profiled[rid])
