"""The program's spans against the device: synthetic traces, the program's
ring buffer, and traces recorded on a v5e."""

import gzip
import json
import os
import time
import types

import pytest

from bench.lib import program_metrics as pm
from bench.lib import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


def _tick(s, e, **args):
    return ("serve.tick", s, e, args)


# Two ticks: device busy 10-40 and 60-70 in the first, 120-190 in the
# second; a third tick runs past the window's end.
PROGRAM = [
    _tick(0, 100, kv_live_bytes=30, kv_reserved_bytes=100),
    ("serve.admission", 0, 5, {}),
    ("serve.prefill_chunk", 5, 45, {}),
    ("serve.prefill_wait", 12, 40, {}),
    ("serve.decode_tick", 50, 92, {}),
    ("serve.tokens", 72, 90, {}),
    _tick(110, 198, kv_live_bytes=60, kv_reserved_bytes=100),
    ("serve.decode_tick", 115, 195, {}),
    _tick(210, 400, kv_live_bytes=90, kv_reserved_bytes=100),
]
DEV = tr.Device(
    [("c", 10, 40), ("d", 60, 70), ("d", 120, 190), ("d", 215, 300)],
    [
        ("jit_prefill_chunk(7)", 10, 40, None),
        ("jit_decode_step(8)", 60, 70, None),
        ("jit_decode_step(8)", 120, 190, None),
        ("jit_prefill_chunk(7)", 215, 260, None),
    ],
)


def test_idle_goes_to_the_innermost_program_span_piece_by_piece():
    idle = pm.idle_by_program(PROGRAM, DEV, 0, 210)
    # gaps 0-10: admission 5, chunk 5; 40-60: chunk 5 (its wait has ended),
    # tick 5, decode 10; 70-120: decode 2, tokens 18, decode 2, tick 8,
    # between ticks 10, tick 5, decode 5; 190-210: decode 5, tick 3, 12
    assert idle == {
        "serve.admission": pytest.approx(5e-9),
        "serve.prefill_chunk": pytest.approx(10e-9),
        "serve.tick": pytest.approx(21e-9),
        "serve.decode_tick": pytest.approx(24e-9),
        "serve.tokens": pytest.approx(18e-9),
        pm.OUTSIDE: pytest.approx(22e-9),
    }


def _ahead(dev, off):
    """``dev`` as a trace shows it when the device's clock runs ``off`` ns
    ahead of the host's: each execution enqueued as it began."""
    ops = [(n, s - off, e - off) for n, s, e in dev.ops]
    return tr.Device(ops, [(n, s - off, e - off, s) for n, s, e, _ in dev.modules])


def test_the_device_clock_offset_is_found_and_taken_out():
    dev = _ahead(DEV, 7)
    # one execution queued behind another: enqueued before it could begin
    dev.modules.append(("jit__argmax(9)", 71, 72, 40))
    assert pm.clock_offset(dev) == pytest.approx(7)
    assert pm.clock_offset(DEV) == 0.0
    assert pm.idle_by_program(PROGRAM, dev, 0, 210) == pm.idle_by_program(PROGRAM, DEV, 0, 210)
    assert pm.tick_idle_ms(PROGRAM, dev, 0, 300) == pytest.approx(pm.tick_idle_ms(PROGRAM, DEV, 0, 300))
    assert pm.prefill_tick_share(PROGRAM, dev, 0, 300) == pytest.approx(
        pm.prefill_tick_share(PROGRAM, DEV, 0, 300)
    )


def test_tick_idle_counts_whole_ticks_only():
    # tick 1: 100 - 40 busy = 60; tick 2: 88 - 70 = 18; tick 3 ends past hi
    assert pm.tick_idle_ms(PROGRAM, DEV, 0, 300) == pytest.approx((60 + 18) / 2 * 1e-6)
    assert pm.tick_idle_ms(PROGRAM, DEV, 0, 400) == pytest.approx((60 + 18 + 105) / 3 * 1e-6)


def test_prefill_share_is_chunk_device_time_over_tick_time():
    assert pm.prefill_tick_share(PROGRAM, DEV, 0, 300) == pytest.approx(100 * 30 / 188)
    assert pm.prefill_tick_share(PROGRAM, DEV, 0, 400) == pytest.approx(100 * (30 + 45) / 378)


def test_kv_live_share_is_the_mean_over_ticks():
    assert pm.kv_live_share(PROGRAM, 0, 300) == pytest.approx(45.0)
    assert pm.kv_live_share(PROGRAM, 0, 400) == pytest.approx(60.0)


@pytest.mark.parametrize(
    "program,dev",
    [
        ([], DEV),  # no program spans (a program without them)
        ([p for p in PROGRAM if p[0] != "serve.tick"], DEV),  # no tick span
        (PROGRAM, None),  # no device plane
    ],
)
def test_readers_find_nothing_and_say_none(program, dev):
    assert pm.tick_idle_ms(program, dev, 0, 300) is None
    assert pm.prefill_tick_share(program, dev, 0, 300) is None
    if dev is None:
        assert pm.kv_live_share(program, 0, 300) == pytest.approx(45.0)
    else:
        assert pm.kv_live_share(program, 0, 300) is None


def test_readers_need_the_named_chunk_program_and_the_tick_args():
    anon = tr.Device(DEV.ops, [("jit__lambda(1)",) + m[1:] for m in DEV.modules])
    assert pm.prefill_tick_share(PROGRAM, anon, 0, 300) is None
    bare = [_tick(s, e) if n == "serve.tick" else (n, s, e, a) for n, s, e, a in PROGRAM]
    assert pm.kv_live_share(bare, 0, 300) is None
    assert pm.tick_idle_ms(bare, DEV, 0, 300) is not None


def test_ring_buffer_spans_map_onto_the_trace_clock():
    from repro.obs.trace import Tracer

    tracer = Tracer()
    t0 = time.perf_counter()
    with tracer.span("serve.tick", rids=[1]) as sp:
        with tracer.span("serve.decode_wait", rids=[1]):
            time.sleep(0.01)
        sp.set(kv_live_bytes=1, kv_reserved_bytes=4)
    with tracer.span("other.span"):
        pass
    t1 = time.perf_counter()
    lo, hi = 5_000_000, 5_000_000 + (t1 - t0) * 2e9  # a clock twice as fast
    spans = pm.from_tracer(tracer, t0, t1, lo, hi)
    assert [p[0] for p in spans] == ["serve.tick", "serve.decode_wait"]
    tick, wait = spans
    assert lo <= tick[1] <= wait[1] < wait[2] <= tick[2] <= hi
    assert wait[2] - wait[1] >= 0.01 * 2e9
    assert tick[3] == {"rids": [1], "kv_live_bytes": 1, "kv_reserved_bytes": 4}
    assert pm.kv_live_share(spans, lo, hi) == pytest.approx(25.0)
    # a tracer that does not say where its clock starts gives nothing
    assert pm.from_tracer(types.SimpleNamespace(events=tracer.events), t0, t1, lo, hi) == []


def test_a_run_without_a_traced_window_reads_nothing():
    run = types.SimpleNamespace(window=types.SimpleNamespace(t0=0.0, traced_end=None), lo=0, hi=10)
    assert pm.program_spans(run) == []


def _fixture(name):
    with gzip.open(os.path.join(HERE, "data", name), "rt") as f:
        doc = json.load(f)
    return tr.Trace.from_json(doc), doc.get("program", [])


def test_recorded_fixture_without_program_spans_reads_as_before():
    """The minicpm3 fixture predates the program's spans: the reduction
    reads it as it did, and the program readers find nothing."""
    t, program = _fixture("trace_minicpm3-docqa-open.json.gz")
    assert program == []
    dev = t.devices[0]
    lo = min(s[1] for s in t.spans)
    hi = max(s[2] for s in t.spans)
    assert (len(dev.ops), len(dev.modules), len(t.spans)) == (33750, 14, 15)
    assert tr.busy_ns(dev.ops, lo, hi) == pytest.approx(397233544.0)
    assert tr.idle_by_host(t, dev, lo, hi) == {
        "host.unannotated": pytest.approx(2.594030939),
        "bench.tick": pytest.approx(0.008117002),
        "bench.prefill_chunk": pytest.approx(0.001072614),
        "bench.decode": pytest.approx(1.2e-08),
        "bench.kv_pool": pytest.approx(1e-09),
    }
    assert pm.tick_idle_ms(program, dev, lo, hi) is None
    assert pm.prefill_tick_share(program, dev, lo, hi) is None
    assert pm.kv_live_share(program, lo, hi) is None


def test_a_traced_cpu_run_reports_what_it_can_read(tmp_path):
    """Through the harness on the CPU: the tick's args come from the
    program's ring buffer; with no device plane in a CPU profile, the two
    device readers stay silent."""
    from bench import run
    from bench.tests import smoke

    smoke.write_tree(str(tmp_path))
    args = ["--workload", "mla.open", "--seed", str(2**40 + 7), "--seconds", "2", "--trace", "1"]
    res = run.main(args, require_tpu=False, root=str(tmp_path))
    assert res["correct"], res["checks"]
    assert 0 < res["metrics"]["kv_live_share"]["value"] <= 100
    assert res["metrics"]["kv_live_share"]["unit"] == "%"
    assert "tick_idle_ms" not in res["metrics"] and "prefill_tick_share" not in res["metrics"]


def test_recorded_chip_trace_with_program_spans():
    """Two ticks of internlm2-chat-saturated, each with a 512- or 32-row
    prefill chunk and a decode step of 15 slots, traced on a TPU v5e and
    trimmed (``bench/record_program_trace.py``): the programs are named,
    each tick's phases and counts are on the host plane, and the three
    readers read them."""
    t, program = _fixture("trace_internlm2-chat-saturated.program.json.gz")
    program = [tuple(p) for p in program]
    dev = t.devices[0]
    names = {m[0].split("(", 1)[0] for m in dev.modules}
    assert {"jit_decode_step", "jit_prefill_chunk"} <= names
    assert not any(n.startswith("jit__lambda") for n in names)
    bench_ticks = [s for s in t.spans if s[0] == "bench.tick"]
    lo, hi = bench_ticks[0][1], bench_ticks[-1][2]
    ticks = pm.ticks(program, lo, hi)
    assert len(ticks) == len(bench_ticks) == 2
    for (_, s, e, args), outer in zip(ticks, bench_ticks):
        assert outer[1] <= s < e <= outer[2]  # the program's tick inside the harness's
        assert args["decoded"] == 15 and args["chunks"] == 1
        assert 0 < args["kv_live_bytes"] < args["kv_reserved_bytes"]
        inside = {p[0] for p in program if s <= p[1] and p[2] <= e}
        assert {
            "serve.admission", "serve.prefill_chunk", "engine.prefill_chunk", "serve.prefill_wait",
            "kv.write_slot", "serve.decode_tick", "serve.decode_inputs", "engine.decode_slots",
            "serve.decode_wait", "serve.tokens", "serve.gauges",
        } <= inside
    # the device's clock runs about 1.3 ms ahead of the host's annotations
    assert 1.0e6 < pm.clock_offset(dev) < 1.6e6
    assert pm.tick_idle_ms(program, dev, lo, hi) == pytest.approx(8.1272, rel=1e-4)
    assert pm.prefill_tick_share(program, dev, lo, hi) == pytest.approx(34.2798, rel=1e-4)
    assert pm.kv_live_share(program, lo, hi) == pytest.approx(62.0829, rel=1e-4)
    idle = pm.idle_by_program(program, dev, lo, hi)
    off = pm.clock_offset(dev)
    assert sum(idle.values()) == pytest.approx((hi - lo - tr.busy_ns(dev.ops, lo - off, hi - off)) * 1e-9)
    in_ticks = sum(v for k, v in idle.items() if k != pm.OUTSIDE)
    assert 1 - idle["serve.tick"] / in_ticks >= 0.9  # idle inside a tick lies in a named phase
